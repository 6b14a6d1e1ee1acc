# The chip call that reads what the limits are set from (PERF.md section 2),
# one process per cell, a dozen seeds each for the serve cells (the program,
# and the bf16_all control on the same rows and tokens; fp8 on the first
# three), three for the train cell with its fp8 control and its planted
# fault; every side through checks.verdict and the cell's own limits.
# Windows of 15 s, but `chat51`: the chat cell's lane starts empty and a
# short window's tap can land before 8 rows are resident (one-row programs
# read logit_error_excess lower, PERF.md section 2), so six further seeds
# read it over the full 51 s, where a run's tap lands.
#   chiprun --timeout 2400 -- bash benchmarks/tools/call_readings.sh [cells]
out=chiprun_out/readings; mkdir -p $out
cells=${*:-chat backlog train}
for c in $cells; do
  secs=15
  case $c in
    chat)    w=gpt2m-serve-chat-loaded; seeds=27411,27412,27413,27414,27415,27416,27417,27418,27419,27420,27421,3000027422; extra="--also fp8" ;;
    chat51)  w=gpt2m-serve-chat-loaded; secs=51; seeds=27451,27452,27453,27454,27455,3000027456; extra="" ;;
    backlog) w=gpt2m-serve-backlog;     seeds=27431,27432,27433,27434,27435,27436,27437,27438,27439,27440,27441,3000027442; extra="--also fp8" ;;
    train)   w=gpt2m-train-1k;          seeds=71,72,3000000073; extra="--control_seeds 3" ;;
  esac
  t0=$(date +%s)
  python3 benchmarks/tools/readings.py --workload $w --seeds $seeds --seconds $secs $extra > $out/$c.out 2> $out/$c.err
  echo "$c rc $? wall $(( $(date +%s) - t0 )) s"
  grep '^{"reading"' $out/$c.out > $out/$c.jsonl
  python3 - $out/$c.jsonl <<'PY'
import json, sys
for l in open(sys.argv[1]):
    r = json.loads(l)
    sides = {k: v for k, v in r.items() if isinstance(v, dict) and "correct" in v}
    print(r["seed"], {k: (v["correct"], {n: round(x["value"], 7) if isinstance(x["value"], float) else x["value"]
                                          for n, x in v["compared"].items() if x["limit"] is not None})
                      for k, v in sides.items()},
          {k: round(v, 4) for k, v in r.get("stats", {}).get("program", {}).items()
           if k in ("stated_error_share", "distance_from_stated")},
          r.get("sampled"), round(r.get("reference_s", 0), 1))
PY
  tail -n 3 $out/$c.err | cut -c1-300
done
