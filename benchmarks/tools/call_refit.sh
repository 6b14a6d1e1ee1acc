# the refusal round's runs of the burst cell (the check read its
# serve_tokens_per_s as too noisy for its bound), two calls:
#   chiprun --timeout 2000 -- bash benchmarks/tools/call_refit.sh steps
#     step 1, the mix as refused (without order_block): one seed twice,
#     two others once; then step 3, the mix as committed: six seeds
#   git add -A && rm -rf .chip_archive && mkdir .chip_archive \
#     && git archive $(git write-tree) | tar -x -C .chip_archive
#   chiprun --timeout 1500 -- bash benchmarks/tools/call_refit.sh final
#     from the unpacked archive of the final tree: one traced run, then
#     the second set on step 3's seeds
cell=gpt2m-serve-burst
mix=benchmarks/traffic/longprompt-burst.json
if [ "$1" = steps ]; then
  rm -f chiprun_out/sets/$cell.*
  cp $mix $mix.committed
  python3 - <<'P'
import json
p = "benchmarks/traffic/longprompt-burst.json"
m = json.load(open(p)); m.pop("order_block"); json.dump(m, open(p, "w"))
P
  SETS=full_shuffle bash benchmarks/tools/sets.sh $cell 51 0 6006 3000006012 6006 6002
  mv $mix.committed $mix
  SETS=blocks bash benchmarks/tools/sets.sh $cell 51 0 6001 6002 6003 6004 6005 3000006009
else
  export OUT=$PWD/chiprun_out/sets_final; rm -rf $OUT
  cd .chip_archive || exit 1
  bash benchmarks/tools/sets.sh $cell 51 1 3000007001
  SETS=blocks2 bash benchmarks/tools/sets.sh $cell 51 0 6001 6002 6003 6004 6005 3000006009
  ls -a; echo JAXCACHE=${JAX_COMPILATION_CACHE_DIR:-unset}
fi
