# The proof that the committed files are enough, for all five cells (the
# sibling of call_proof.sh, which covers the three one-chip cells of PR
# 27): every cell run at its full length from an unpacked
# `git archive $(git write-tree)` (a directory that .gitignore lists),
# once untraced and once traced.  The one-chip cells in one call, the
# four-chip cell in a call of its own (four chips cost four times as much):
#   git add -A && rm -rf .chip_archive && mkdir .chip_archive \
#     && git archive $(git write-tree) | tar -x -C .chip_archive
#   chiprun --timeout 3000 -- bash benchmarks/tools/call_proof5.sh one
#   chiprun --chips 4 --timeout 1500 -- bash benchmarks/tools/call_proof5.sh four
# With .parent_archive/ (the parent commit, unpacked the same way) the
# `one` call first shows that the parent fails on the new serve cell at
# once (`unknown workload`), and does not hang.
set -u
which=${1:-one}
out=$PWD/chiprun_out/proof5; mkdir -p $out
root=$PWD
run() { # name workload seed trace
  name=$1; wl=$2; seed=$3; trace=$4
  t0=$(date +%s)
  python3 benchmarks/run.py --workload $wl --seed $seed --seconds 51 --trace $trace > $out/$name.out 2> $out/$name.err
  echo "$name rc=$? wall $(( $(date +%s) - t0 )) s: $(tail -n 1 $out/$name.out | cut -c1-3200)"
  grep "set-up\|also" $out/$name.out | cut -c1-500
  grep "compared\|\[metric\]" $out/$name.err | cut -c1-200
}
if [ "$which" = one ]; then
  if [ -d .parent_archive ]; then
    cd .parent_archive
    t0=$(date +%s)
    timeout 120 python3 benchmarks/run.py --workload solar-open2-ep8-serve-reason --seed 29001 --seconds 51 --trace 0 > $out/parent.out 2> $out/parent.err
    echo "parent on the new serve cell: rc=$? after $(( $(date +%s) - t0 )) s: $(tail -n 2 $out/parent.err | cut -c1-300)"
    cd $root
  fi
  cd .chip_archive
  run reason_t0 solar-open2-ep8-serve-reason 3000029101 0
  run reason_t1 solar-open2-ep8-serve-reason 29102 1
  run chat_t0 gpt2m-serve-chat-loaded 29111 0
  run chat_t1 gpt2m-serve-chat-loaded 3000029112 1
  run backlog_t0 gpt2m-serve-backlog 3000029121 0
  run backlog_t1 gpt2m-serve-backlog 29122 1
  run train_t0 gpt2m-train-1k 29131 0
  run train_t1 gpt2m-train-1k 3000029132 1
else
  cd .chip_archive
  run dp4_t0 gpt2m-train-1k-dp4 3000029141 0
  run dp4_t1 gpt2m-train-1k-dp4 29142 1
fi
du -sh .jax_cache .bench_work 2>/dev/null; echo JAXCACHE=${JAX_COMPILATION_CACHE_DIR:-unset}
