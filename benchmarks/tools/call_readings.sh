# The chip call that reads what the limits of `correct` are set from
# (PERF.md section 2): the program, and the arm's control on the same rows
# and tokens, every side through checks.verdict and the cell's own limits.
#   chiprun --timeout 2400 -- bash benchmarks/tools/call_readings.sh <cell> <seconds> <seed,seed,...> [further arguments of readings.py]
# One process for all the seeds of a cell (tools/readings.py: one warmed
# engine beside the reference; `--also fp8` reads a further control on the
# first three seeds, `--control_seeds 3` is the train cell's).  With
# ONE_A_PROCESS=1 in the environment, tools/readings_run.py instead, one
# process a seed: for a cell whose engine and reference do not fit the
# chip together (the Solar cell).  A serve cell's lane starts empty and a
# short window's tap can land before 8 rows are resident (one-row programs
# read logit_error_excess lower, PERF.md section 2): read a chat cell over
# the full 51 s, where a run's tap lands.  Several cells: several calls
# of this script in one chiprun command.
cell=$1; secs=$2; seeds=$3; shift 3
out=chiprun_out/readings; mkdir -p $out; rm -f $out/$cell.jsonl
t0=$(date +%s)
if [ -n "${ONE_A_PROCESS:-}" ]; then
  for seed in ${seeds//,/ }; do
    python3 benchmarks/tools/readings_run.py --workload $cell --seed $seed --seconds $secs "$@" > $out/$cell.out 2> $out/$cell.err
    echo "seed $seed rc $? after $(( $(date +%s) - t0 )) s"
    grep '^{"reading"' $out/$cell.out >> $out/$cell.jsonl
  done
else
  python3 benchmarks/tools/readings.py --workload $cell --seeds $seeds --seconds $secs "$@" > $out/$cell.out 2> $out/$cell.err
  echo "$cell rc $? wall $(( $(date +%s) - t0 )) s"
  grep '^{"reading"' $out/$cell.out > $out/$cell.jsonl
fi
python3 - $out/$cell.jsonl <<'PY'
import json, sys
for l in open(sys.argv[1]):
    r = json.loads(l)
    sides = {k: v for k, v in r.items() if isinstance(v, dict) and "correct" in v}
    print(r["seed"], r.get("finished"), r.get("sampled"),
          {k: (v["correct"], {n: round(x["value"], 7) if isinstance(x["value"], float) else x["value"]
                              for n, x in v["compared"].items() if x["limit"] is not None})
           for k, v in sides.items()},
          {k: round(v, 4) for k, v in r.get("stats", {}).get("program", {}).items()
           if k in ("stated_error_share", "distance_from_stated", "rows", "n")},
          round(r.get("reference_s", 0), 1))
PY
tail -n 3 $out/$cell.err | cut -c1-300
