"""``serve.host_turn_ms`` in the saturated cell: no arrival wait, the
host turn is all that stands between two decode steps."""
from harness import spec

read = spec.reader_of("serve.host_turn_ms")
