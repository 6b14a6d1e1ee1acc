# The proof that the committed files are enough: cells run at their full
# length from an unpacked `git archive $(git write-tree)` (a directory that
# .gitignore lists), each once untraced and then traced (the first trace
# kept, and its idle gaps charged to the loop's phases by
# tools/gap_phases.py beside the result line's own idle_gaps):
#   git add -A && rm -rf .chip_archive && mkdir .chip_archive \
#     && git archive $(git write-tree) | tar -x -C .chip_archive
#   chiprun --timeout 3000 -- bash benchmarks/tools/call_proof.sh <first seed> <traced runs a cell> <cell> [<cell> ...]
#   chiprun --chips 4 --timeout 1500 -- bash benchmarks/tools/call_proof.sh 32141 1 gpt2m-train-1k-dp4
# (a four-chip cell in a call of its own: four chips cost four times as
# much; 0 traced runs: the untraced run alone).  Seeds count up from the
# first, ten a cell, every other one past 2**31.  This is PR 27's
# call_proof.sh and PR 29's call_proof5.sh, which differed in their cells.
set -u
first=$1; traced=$2; shift 2
out=$PWD/chiprun_out/proof; mkdir -p $out
cd .chip_archive || exit 1
run() { # name workload seed trace [env...]
  local name=$1 wl=$2 s=$3 trace=$4; shift 4
  local t0=$(date +%s)
  env "$@" python3 benchmarks/run.py --workload $wl --seed $s --seconds 51 --trace $trace > $out/$name.out 2> $out/$name.err
  echo "$name seed $s rc=$? wall $(( $(date +%s) - t0 )) s: $(tail -n 1 $out/$name.out | cut -c1-3200)"
  grep "set-up\|\[bench\] also\|\[bench\] trace" $out/$name.out | cut -c1-500
  grep "compared\|\[metric\]" $out/$name.err | cut -c1-200
}
i=0
for cell in "$@"; do
  seed=$(( first + 10 * i )); i=$(( i + 1 ))
  run ${cell}.t0 $cell $(( 3000000000 + seed )) 0
  for k in $(seq 1 $traced); do
    s=$(( seed + k )); [ $(( k % 2 )) = 0 ] && s=$(( 3000000000 + s ))
    if [ $k = 1 ]; then
      run ${cell}.t1_$k $cell $s 1 BENCH_KEEP_TRACE=1
      python3 benchmarks/tools/gap_phases.py .bench_work/trace > $out/$cell.gaps 2> $out/$cell.gaps.err
      head -n 14 $out/$cell.gaps
    else
      run ${cell}.t1_$k $cell $s 1
    fi
  done
done
ls -a; du -sh .jax_cache .bench_work 2>/dev/null; echo JAXCACHE=${JAX_COMPILATION_CACHE_DIR:-unset}
