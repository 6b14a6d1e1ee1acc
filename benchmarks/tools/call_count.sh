# The count of a backlog mix, read on the chip: one untraced full run of
# the cell from the unpacked archive (the mix's present count has only to
# outlast the window), what the lane began in it, and the count = 3x that,
# rounded up to a multiple of 4, with the `requests_per_s` that gives it.
# That rate is written into the ARCHIVE's copy of the mix (the machine's
# copy: write it into the repo's file by hand, with the date, from what
# this prints), so that sets run after it in the same call offer the
# final count:
#   chiprun --timeout 2700 -- bash -c 'bash benchmarks/tools/call_count.sh <cell> <seed> \
#     && bash benchmarks/tools/call_sets_seeds.sh <cell> <first seed> \
#     && cd .chip_archive && OUT=$OLDPWD/chiprun_out/count bash benchmarks/tools/sets.sh <cell> 51 1 <seed>'
# Ends with 1, before anything else is spent, where the run is not `correct`.
set -u
cell=$1; seed=${2:-3000029701}
out=$PWD/chiprun_out/count; mkdir -p $out; rm -f $out/*
cd .chip_archive || exit 1
python3 benchmarks/tools/probe_run.py --workload $cell --seed $seed --seconds 51 --trace 0 > $out/t0.out 2> $out/t0.err
echo "untraced rc=$?: $(tail -n 1 $out/t0.out | cut -c1-2400)"
grep "set-up\|\[probe\]\|memory_stats\|kv_pool_temp" $out/t0.out | cut -c1-900
python3 - $out/t0.out $cell <<'E'
import json, sys
res = json.loads(open(sys.argv[1]).read().splitlines()[-1])
if not res["correct"]:
    sys.exit(1)
begun = res["also"]["window"]["begun"]
count = -(-3 * begun // 4) * 4
rate = round(count / 51, 3)
assert round(rate * 51) == count, (rate, count)
bench = json.load(open("BENCHMARK.json"))
mix = next(w["traffic"] for w in bench["workloads"] if w["name"] == sys.argv[2])
path = f"benchmarks/traffic/{mix}.json"
spec = json.load(open(path))
print(f"[count] begun {begun} -> count {count}: requests_per_s {rate} (was {spec['requests_per_s']}) in {path}")
spec["requests_per_s"] = rate
json.dump(spec, open(path, "w"), indent=1)
E
