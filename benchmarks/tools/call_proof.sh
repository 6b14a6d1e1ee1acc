# The proof that the committed files are enough: every cell run at its full
# length from an unpacked `git archive $(git write-tree)` (a directory that
# .gitignore lists): each serve cell once untraced and three times traced
# (the first trace kept and its idle gaps charged to the loop's phases by
# tools/gap_phases.py, beside the result line's own idle_gaps), the train
# cell once each way.
#   git add -A && rm -rf .chip_archive && mkdir .chip_archive \
#     && git archive $(git write-tree) | tar -x -C .chip_archive
#   chiprun --timeout 2700 -- bash benchmarks/tools/call_proof.sh
set -u
out=$PWD/chiprun_out/proof; rm -rf $out; mkdir -p $out
cd .chip_archive
run() { # name workload seed trace [env...]
  name=$1; wl=$2; seed=$3; trace=$4; shift 4
  t0=$(date +%s)
  env "$@" python3 benchmarks/run.py --workload $wl --seed $seed --seconds 51 --trace $trace > $out/$name.out 2> $out/$name.err
  echo "$name rc=$? wall $(( $(date +%s) - t0 )) s: $(tail -n 1 $out/$name.out | cut -c1-2600)"
  grep "set-up\|also" $out/$name.out | cut -c1-400
  tail -n 8 $out/$name.err | grep compared | cut -c1-120
}
gaps() { # name: the kept trace of the run just made, by phase
  python3 benchmarks/tools/gap_phases.py .bench_work/trace > $out/$1.gaps 2> $out/$1.gaps.err
  head -n 14 $out/$1.gaps
}
run chat_t0 gpt2m-serve-chat-loaded 3000027101 0
run chat_t1a gpt2m-serve-chat-loaded 27102 1 BENCH_KEEP_TRACE=1
gaps chat_t1a
run chat_t1b gpt2m-serve-chat-loaded 27103 1
run chat_t1c gpt2m-serve-chat-loaded 3000027104 1
run backlog_t0 gpt2m-serve-backlog 27111 0
run backlog_t1a gpt2m-serve-backlog 3000027112 1 BENCH_KEEP_TRACE=1
gaps backlog_t1a
run backlog_t1b gpt2m-serve-backlog 27113 1
run backlog_t1c gpt2m-serve-backlog 27114 1
run train_t0 gpt2m-train-1k 27121 0
run train_t1 gpt2m-train-1k 3000027122 1
ls -a; du -sh .jax_cache .bench_work 2>/dev/null; echo JAXCACHE=${JAX_COMPILATION_CACHE_DIR:-unset}
