"""The benchmark's own yardstick: traffic, statistics, peaks, FLOP and
byte arithmetic, the xplane reduction and the comparison that decides
``correct``.  Nothing here is imported by the program under test."""
