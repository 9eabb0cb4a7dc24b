# The chip calls behind PERF.md's numbers for PR 35 (Azure's LRC(12,2,2) on the device path; the cell
# reads.degraded1.lrc12-2-2).  A read run writes ~2.5 GiB (fill + seal) and takes ~105 s.
#   chiprun --timeout 1800 -- bash benchmark/tests/scripts/lrc12_on_chip.sh first <seed>
#       a machine's FIRST run of the cell, traced (cold compile cache: compiles_in_window.read has
#       to read 0 there too), then the PARENT (.parent_tree/: `git archive` of the parent commit with
#       this PR's BENCHMARK.json and benchmark/ laid over it, as the driver does): fails at once
#   ... lrc12_on_chip.sh set <A|B> <seed>...        plain runs of the cell -> chiprun_out/<cell>/<A|B>.<seed>.out
#       (then: python3 benchmark/tests/scripts/spread.py reads.degraded1.lrc12-2-2)
#   ... lrc12_on_chip.sh traced <seed>...           traced runs of the cell
#   ... lrc12_on_chip.sh controls <seed>            xor_rebuild and altered_read at the cell's own size, 5 s
#   ... lrc12_on_chip.sh spans <seed>               every request sampled (spans_volume) + spans_report.py
#   ... lrc12_on_chip.sh proof <seed>               from an unpacked `git archive $(git write-tree)` in .bench_tree/
#   (the old cells, parent against change: families_on_chip.sh pairs <seed> <cell>...)
CELL=reads.degraded1.lrc12-2-2
S=benchmark/tests/scripts; O=$PWD/chiprun_out/$CELL; mkdir -p $O
run() {  # <label> <dir> <command...>: one run, its result line shown
  label=$1; dir=$2; shift 2
  t0=$(date +%s)
  (cd $dir && "$@" > $O/$label.out 2> $O/$label.err); rc=$?
  echo "== $label rc=$rc $(( $(date +%s) - t0 )) s"; python3 $S/show.py $O/$label.out
  grep -E "^(\[(trace|host|servers\] ready|prepare\] ec|window\] (opened|operation))|FAILED)" $O/$label.err | cut -c1-1500
}
bench="python3 benchmark/run.py --workload $CELL --seconds ${WINDOW:-20}"
mode=$1; shift
case $mode in
first)
  run first.traced.$1 . $bench --seed $1 --trace 1
  tail -1 $O/first.traced.$1.out | cut -c1-3000
  run parent.$1 .parent_tree $bench --seed $1 --trace 0
  tail -5 $O/parent.$1.err | cut -c1-400
  ;;
set)
  set=$1; shift
  for s in "$@"; do run $set.$s . $bench --seed $s --trace 0; done
  ;;
traced)
  for s in "$@"; do
    run traced.$s . $bench --seed $s --trace 1
    tail -1 $O/traced.$s.out | cut -c1-3000
  done
  ;;
controls)
  for fault in xor_rebuild altered_read; do
    run control.$fault.$1 . python3 benchmark/tests/rehearse.py --manifest BENCHMARK.json --require-platform tpu \
      --wrapper benchmark.tests.faulty_volume:$fault --workload $CELL --seed $1 --seconds 5 --trace 0
    grep -E "^\[compared\]" $O/control.$fault.$1.err | cut -c1-120 | tr '\n' ';'; echo
  done
  ;;
spans)
  K=$O/spans.$1.kept; rm -rf $K
  run spans.$1 . python3 benchmark/tests/rehearse.py --manifest BENCHMARK.json --require-platform tpu \
    --wrapper benchmark.tests.spans_volume:$K --workload $CELL --seed $1 --seconds ${WINDOW:-20} --trace 1
  JAX_PLATFORMS=cpu python3 $S/spans_report.py $K > $O/spans.$1.report.json 2> $O/spans.$1.report.txt
  echo "   report rc=$?"; cut -c1-3000 $O/spans.$1.report.txt
  rm -rf $K/profile; gzip -f $K/traces.json
  ;;
proof)
  test -d .bench_tree/.git && echo "HAS .git"
  run proof.traced.$1 .bench_tree $bench --seed $1 --trace 1
  tail -1 $O/proof.traced.$1.out | cut -c1-2000
  run proof.reads.plain .bench_tree python3 benchmark/run.py --workload reads.degraded1 --seed $(( $1 + 1 )) --seconds ${WINDOW:-20} --trace 0
  ;;
esac
