# Two sets of six full-length runs of one cell, every run on a seed of its
# own (call_sets.sh gives both sets the same six), from an unpacked
# `git archive $(git write-tree)`:
#   chiprun --timeout 3400 -- bash benchmarks/tools/call_sets_seeds.sh <cell> <first seed> [A|B]
# (a third argument: that set alone, its lines under sets_seeds_<set>/, for
# a budget that does not hold both sets in one call)
cell=$1; first=${2:-29201}; only=${3:-}
export OUT=$PWD/chiprun_out/sets_seeds${only:+_$only}
mkdir -p $OUT; rm -f $OUT/$cell.*
cd .chip_archive || exit 1
a=""; b=""
for i in 0 1 2 3 4; do a="$a $((first + i))"; b="$b $((first + 10 + i))"; done
[ "$only" = B ] || SETS=A bash benchmarks/tools/sets.sh $cell 51 0 $a $((3000000000 + first + 5))
[ "$only" = A ] || SETS=B bash benchmarks/tools/sets.sh $cell 51 0 $b $((3000000000 + first + 15))
python3 benchmarks/tools/spread.py $OUT/$cell.t0.jsonl
