# two sets of runs of one cell with the same seeds, one call:
#   bash benchmarks/tools/sets.sh <cell> <seconds> <trace> <seed> [<seed> ...]
# (SETS=C in the environment: one further set under that name; OUT: where
# the lines go, for a run from an unpacked archive)
cell=$1; seconds=$2; trace=$3; shift 3
out=${OUT:-chiprun_out/sets}; mkdir -p $out
for set in ${SETS:-A B}; do
  for seed in "$@"; do
    t0=$(date +%s)
    python3 benchmarks/run.py --workload $cell --seed $seed --seconds $seconds --trace $trace > $out/last.out 2> $out/last.err
    rc=$?
    line=$(tail -n 1 $out/last.out)
    echo "{\"cell\": \"$cell\", \"set\": \"$set\", \"seed\": $seed, \"trace\": $trace, \"rc\": $rc, \"wall\": $(( $(date +%s) - t0 )), \"result\": ${line:-null}}" >> $out/$cell.t$trace.jsonl
    grep "also\|set-up" $out/last.out | cut -c1-200
    echo "$cell set $set seed $seed rc $rc: $(echo $line | cut -c1-420)"
    if [ $rc != 0 ]; then tail -n 20 $out/last.err | cut -c1-400; fi
  done
  if [ "$trace" = 1 ]; then break; fi
done
