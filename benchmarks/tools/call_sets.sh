# the two full sets of one cell, from an unpacked archive of the tree
# (the same seeds in both sets, each run a process of its own):
#   git add -A && rm -rf .chip_archive && mkdir .chip_archive \
#     && git archive $(git write-tree) | tar -x -C .chip_archive
#   chiprun --timeout 2400 -- bash benchmarks/tools/call_sets.sh <cell> [<req/s>]
# With a rate, the archive's copy of the cell's mix offers that rate
# instead (ISSUE 27's rule tries 8.0, then 6.0, then 4.0 in the chat cell)
# and the lines go to chiprun_out/sets_<rate>.
cell=$1; rate=${2:-}
export OUT=$PWD/chiprun_out/sets${rate:+_$rate}
mkdir -p $OUT; rm -f $OUT/$cell.*
cd .chip_archive || exit 1
if [ -n "$rate" ]; then
  python3 - $cell $rate <<'P'
import json, sys
cell, rate = sys.argv[1], float(sys.argv[2])
bench = json.load(open("BENCHMARK.json"))
mix = next(w["traffic"] for w in bench["workloads"] if w["name"] == cell)
path = f"benchmarks/traffic/{mix}.json"
m = json.load(open(path)); m["requests_per_s"] = rate
json.dump(m, open(path, "w"), indent=2)
print(f"{path}: requests_per_s {rate}")
P
fi
bash benchmarks/tools/sets.sh $cell 51 0 27001 27002 27003 27004 27005 3000027006
python3 benchmarks/tools/spread.py $OUT/$cell.t0.jsonl
echo JAXCACHE=${JAX_COMPILATION_CACHE_DIR:-unset}
