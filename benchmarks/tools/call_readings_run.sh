# The control's readings for a serve cell too large for tools/readings.py
# (engine + reference together pass the chip's memory): one process a seed.
#   chiprun --timeout 1500 -- bash benchmarks/tools/call_readings_run.sh <cell> <seconds> <seed> [<seed> ...]
cell=$1; secs=$2; shift 2
out=chiprun_out/readings; mkdir -p $out; rm -f $out/$cell.jsonl
for seed in "$@"; do
  t0=$(date +%s)
  python3 benchmarks/tools/readings_run.py --workload $cell --seed $seed --seconds $secs > $out/last.out 2> $out/last.err
  echo "seed $seed rc $? wall $(( $(date +%s) - t0 )) s"
  grep '^{"reading"' $out/last.out >> $out/$cell.jsonl
  tail -n 2 $out/last.err | cut -c1-300
done
python3 - $out/$cell.jsonl <<'PY'
import json, sys
for l in open(sys.argv[1]):
    r = json.loads(l)
    sides = {k: v for k, v in r.items() if isinstance(v, dict) and "correct" in v}
    print(r["seed"], r["finished"], {k: (v["correct"], {n: round(x["value"], 5) for n, x in v["compared"].items() if x["limit"] is not None}) for k, v in sides.items()},
          {k: round(v, 4) for k, v in r["stats"]["program"].items() if k in ("stated_error_share", "distance_from_stated", "rows", "n")})
PY
