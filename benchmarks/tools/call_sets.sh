# the proof runs of one cell: two sets of six with the same seeds, then
# three traced runs;  bash benchmarks/tools/call_sets.sh <cell>
cell=$1
rm -f chiprun_out/sets/$cell.*
bash benchmarks/tools/sets.sh $cell 51 0 2001 2002 2003 2004 2005 3000002006
bash benchmarks/tools/sets.sh $cell 51 1 2101 2102 3000002103
