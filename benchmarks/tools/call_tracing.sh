# The chip calls of PR 24 (tracing; the cells are arguments since PR 32): the
# serve loop's own account beside the
# device trace, and what the instrumentation costs.  Before a call, the
# parent commit with THIS benchmark laid over it (what the driver measures
# the parent with) is unpacked into a directory that .gitignore lists:
#   rm -rf .chip_archive && mkdir -p .chip_archive/parent \
#     && git archive <parent> | tar -x -C .chip_archive/parent \
#     && cp BENCHMARK.json .chip_archive/parent/ \
#     && cp -r benchmarks/. .chip_archive/parent/benchmarks/
#   chiprun --timeout 3000 -- bash benchmarks/tools/call_tracing.sh first [<chat cell> <backlog cell> <train cell>]
#   chiprun --timeout 3500 -- bash benchmarks/tools/call_tracing.sh cost <cell> <seeds...>
#   (PROBE_TREE=.chip_archive/final: the same from an unpacked archive of the final tree)
# Every run is benchmarks/run.py itself through tools/probe_run.py, which
# has it print BOTH groups of metrics in one line (the loop's fold is read
# in every run, traced or not), the fold itself, and for the `off` arm adds
# --flight_recorder=off to the lane's flags.
set -u
top=$PWD
out=$top/chiprun_out/tracing_$1; mkdir -p $out
# the tree the `cost` runs are made from: `.`, or an unpacked
# `git archive $(git write-tree)` (the proof that the committed files do)
mine=${PROBE_TREE:-.}

run() { # tag tree workload seed trace [env...]; all of it from that tree
  tag=$1; tree=$2; wl=$3; seed=$4; trace=$5; shift 5
  tools=$top/$tree/benchmarks/tools
  t0=$(date +%s)
  (cd $top/$tree && env "$@" python3 $tools/probe_run.py --workload $wl \
      --seed $seed --seconds 51 --trace $trace > $out/$tag.out 2> $out/$tag.err)
  rc=$?
  echo "$tag rc=$rc wall $(( $(date +%s) - t0 ))s $(python3 $tools/probe_run.py --brief $out/$tag.out)"
}

gaps() { # tag tree: the kept trace of the run just made, by phase
  python3 $top/$2/benchmarks/tools/gap_phases.py $top/$2/.bench_work/trace \
      > $out/$1.gaps 2> $out/$1.gaps.err
  head -n 16 $out/$1.gaps
}

# (no chat cell since PR 32 left gpt2m-serve-chat-loaded-r2 out: the tail
# cell that is there stands in until one comes back)
chat=${2:-solar-open2-ep8-serve-reason}; backlog=${3:-gpt2m-serve-backlog-r2}
train=${4:-gpt2m-train-1k}
case $1 in
first)
  # both serve cells traced with the trace kept, the parent under this
  # benchmark (its line must lack the new metrics and nothing else), one
  # untraced pair a cell, and the train cell (its driver's spans and the
  # kernels' names changed): parent, change, change, parent
  run chat_t1_parent .chip_archive/parent $chat 3000024001 1
  run chat_t1 . $chat 3000024001 1 BENCH_KEEP_TRACE=1
  gaps chat_t1 .
  run backlog_t1 . $backlog 24002 1 BENCH_KEEP_TRACE=1
  gaps backlog_t1 .
  run backlog_t1_parent .chip_archive/parent $backlog 24002 1
  run chat_t0_parent .chip_archive/parent $chat 24003 0
  run chat_t0 . $chat 24003 0
  run backlog_t0 . $backlog 3000024004 0
  run backlog_t0_parent .chip_archive/parent $backlog 3000024004 0
  run train_t1_parent .chip_archive/parent $train 24005 1
  run train_t1 . $train 24005 1
  ;;
cost)
  # per seed: untraced with the recorder on, off, and traced, in an order
  # that turns with the seed so that no arm always runs first
  cell=$2; shift 2
  i=0
  for seed in "$@"; do
    case $(( i % 3 )) in
      0) order="on off traced";; 1) order="off traced on";; *) order="traced on off";;
    esac
    for arm in $order; do
      case $arm in
        on) run ${cell}_${seed}_on $mine $cell $seed 0;;
        off) run ${cell}_${seed}_off $mine $cell $seed 0 PROBE_RECORDER_OFF=1;;
        traced) run ${cell}_${seed}_traced $mine $cell $seed 1;;
      esac
    done
    i=$(( i + 1 ))
  done
  ;;
esac
echo JAXCACHE=${JAX_COMPILATION_CACHE_DIR:-unset}
