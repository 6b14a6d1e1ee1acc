# The proof that the committed files are enough: every cell run at its full
# length from an unpacked `git archive $(git write-tree)` (a directory that
# .gitignore lists), the serve cells once untraced and once traced.
#   git add -A && rm -rf .chip_archive && mkdir .chip_archive \
#     && git archive $(git write-tree) | tar -x -C .chip_archive
#   chiprun --timeout 1500 -- bash benchmarks/tools/call_proof.sh
set -u
out=$PWD/chiprun_out/proof; rm -rf $out; mkdir -p $out
cd .chip_archive
run() { # name workload seed trace
  t0=$(date +%s)
  python3 benchmarks/run.py --workload $2 --seed $3 --seconds 51 --trace $4 > $out/$1.out 2> $out/$1.err
  echo "$1 rc=$? wall $(( $(date +%s) - t0 )) s: $(tail -n 1 $out/$1.out | cut -c1-1500)"
  grep "set-up\|also" $out/$1.out | cut -c1-200
  tail -n 8 $out/$1.err | grep compared | cut -c1-120
}
run chat_t0 gpt2m-serve-chat 3000005001 0
run chat_t1 gpt2m-serve-chat 5002 1
run burst_t0 gpt2m-serve-burst 5003 0
run burst_t1 gpt2m-serve-burst 3000005004 1
run train_t1 gpt2m-train-1k 3000005005 1
ls -a; du -sh .jax_cache .bench_work 2>/dev/null; echo JAXCACHE=${JAX_COMPILATION_CACHE_DIR:-unset}
