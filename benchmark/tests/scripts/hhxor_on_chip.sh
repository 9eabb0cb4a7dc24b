# The chip calls behind PERF.md's numbers for the cell reads.degraded1.chunks.hhxor10-4 (Hitchhiker-XOR
# over RS(10,4) on the device path).  A run fills and seals ~1 GiB and takes ~100 s.
#   bash benchmark/tests/scripts/hhxor_on_chip.sh first <seed>     (on a machine with one v5e chip)
#       a machine's FIRST run of the cell, traced (cold compile cache), then the PARENT (.parent_tree/:
#       `git archive` of the parent commit with this tree's BENCHMARK.json and benchmark/ laid over it,
#       as a parent-against-change comparison lays it): fails at once; then the sealed volume's 14 files against
#       benchmark/reference_hitchhiker.py (tests/scripts/hhxor_volume_check.py)
#   ... hhxor_on_chip.sh traced <seed>...          traced runs of the cell
#   ... hhxor_on_chip.sh set <A|B> <seed>...       plain runs of the cell -> chiprun_out/<cell>/<A|B>.<seed>.out
#       (then: python3 benchmark/tests/scripts/spread.py reads.degraded1.chunks.hhxor10-4 --all)
#   ... hhxor_on_chip.sh pairs <seed> <cell>...    an old cell, parent / change / change / parent, plain
#   ... hhxor_on_chip.sh proof <seed>              from an unpacked `git archive $(git write-tree)` in .bench_tree/
#   ... hhxor_on_chip.sh side <seed>               six plain runs of the cell, each beside one of
#       reads.degraded1.chunks, then a traced run of each
#   ... hhxor_on_chip.sh all <seed>                first (its traced run from .bench_tree/), then three
#       plain runs of the cell, then one pair of each old read cell (ONE_PAIR): what one call on a
#       scarce chip can hold
CELL=reads.degraded1.chunks.hhxor10-4
S=benchmark/tests/scripts; O=$PWD/chiprun_out/$CELL; mkdir -p $O
run() {  # <label> <dir> <command...>: one run, its result line shown
  label=$1; dir=$2; shift 2
  t0=$(date +%s)
  (cd $dir && "$@" > $O/$label.out 2> $O/$label.err); rc=$?
  echo "== $label rc=$rc $(( $(date +%s) - t0 )) s"; python3 $S/show.py $O/$label.out
  grep -E "^(\[(trace|host|servers\] ready|prepare\] ec|window\] (opened|operation))|FAILED)" $O/$label.err | cut -c1-1500
}
bench="python3 benchmark/run.py --seconds ${WINDOW:-20}"
mode=$1; shift
case $mode in
first)
  run first.traced.$1 . $bench --workload $CELL --seed $1 --trace 1
  tail -1 $O/first.traced.$1.out | cut -c1-3000
  run parent.$1 .parent_tree $bench --workload $CELL --seed $1 --trace 0
  tail -3 $O/parent.$1.err | cut -c1-400
  run check.$1 . python3 tests/scripts/hhxor_volume_check.py --workload $CELL --seed $(( $1 + 7 ))
  tail -1 $O/check.$1.out | cut -c1-1500
  ;;
traced)
  for s in "$@"; do
    run traced.$s . $bench --workload $CELL --seed $s --trace 1
    tail -1 $O/traced.$s.out | cut -c1-3000
  done
  ;;
set)
  set=$1; shift
  for s in "$@"; do run $set.$s . $bench --workload $CELL --seed $s --trace 0; done
  ;;
pairs)
  seed=$1; shift
  for cell in "$@"; do
    run $cell.parent.a .parent_tree $bench --workload $cell --seed $seed --trace 0
    run $cell.change.a . $bench --workload $cell --seed $seed --trace 0
    [ -n "$ONE_PAIR" ] && continue
    run $cell.change.b . $bench --workload $cell --seed $((seed + 1)) --trace 0
    run $cell.parent.b .parent_tree $bench --workload $cell --seed $((seed + 1)) --trace 0
  done
  ;;
all)
  # the machine's first run of the cell from the committed files alone
  # (.bench_tree/: `git archive $(git write-tree)`), then the parent, the
  # volume check, three plain runs and a pair of each old read cell
  run proof.first.traced.$1 .bench_tree $bench --workload $CELL --seed $1 --trace 1
  tail -1 $O/proof.first.traced.$1.out | cut -c1-3000
  run parent.$1 .parent_tree $bench --workload $CELL --seed $1 --trace 0
  tail -3 $O/parent.$1.err | cut -c1-400
  run check.$1 . python3 tests/scripts/hhxor_volume_check.py --workload $CELL --seed $(( $1 + 7 ))
  tail -1 $O/check.$1.out | cut -c1-1500
  bash $0 set A $(( $1 + 11 )) $(( $1 + 12 )) $(( $1 + 13 ))
  ONE_PAIR=1 bash $0 pairs $(( $1 + 21 )) reads.degraded1.chunks reads.degraded1.lrc12-2-2 reads.degraded1
  ;;
side)
  # six plain runs of the cell, each beside a run of reads.degraded1.chunks
  # on the same machine (the same bytes under RS(10,4)), then one traced
  # run of each
  for i in 1 2 3 4 5 6; do
    bash $0 set B $(( $1 + i ))
    run chunks.side.$(( $1 + i )) . $bench --workload reads.degraded1.chunks --seed $(( $1 + i )) --trace 0
  done
  bash $0 traced $(( $1 + 9 ))
  run chunks.traced.$(( $1 + 9 )) . $bench --workload reads.degraded1.chunks --seed $(( $1 + 9 )) --trace 1
  tail -1 $O/chunks.traced.$(( $1 + 9 )).out | cut -c1-1500
  ;;
proof)
  test -d .bench_tree/.git && echo "HAS .git"
  run proof.traced.$1 .bench_tree $bench --workload $CELL --seed $1 --trace 1
  tail -1 $O/proof.traced.$1.out | cut -c1-2000
  run proof.chunks.plain .bench_tree $bench --workload reads.degraded1.chunks --seed $(( $1 + 1 )) --trace 0
  ;;
esac
