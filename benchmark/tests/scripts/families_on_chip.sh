# The chip calls behind PERF.md's numbers for PR 34 (a configuration's code reaches prepare(), the
# reference and the warm-up).  A machine takes ~33 GiB of writes before it stalls; a seal run writes
# ~4.3 GiB, a read run ~2.5 GiB: at most 6 seal runs, or 4 seal and 4 read runs, a call.
#   chiprun --timeout 1800 -- bash benchmark/tests/scripts/families_on_chip.sh pairs <seed> <cell>...
#       each cell on the PARENT (.parent_tree/: `git archive` of the parent commit, whole) and on the
#       change, --trace 0 on <seed> and --trace 1 on <seed>+1, order parent, change, change, parent;
#       then pair_report.py: the same `compared` names and limits, the same shapes warmed, the
#       per-layer metrics present on both sides, the end-to-end metrics side by side
#   chiprun --timeout 1800 -- bash benchmark/tests/scripts/families_on_chip.sh rehearse <cell> <trace 0|1> <seed>...
#       a cell of tests/cells-families.json (or of tests/cells.json: control.seal.single.lrc) at its
#       own size, a window of ${WINDOW:-20} s (round 1 of PR 34: 10), the look for the chip on; WRAPPER=benchmark.tests.faulty_volume:xor_rebuild in
#       the environment plants that fault (the read cells' control)
#   ... families_on_chip.sh spans <cell> <seed>
#       one run of such a cell with every request sampled (spans_volume), and spans_report.py's report
#   (a cell's two sets of six for spread.py: rs6_3_on_chip.sh set <cell> <A|B> 0 <seed>...)
#   ... families_on_chip.sh proof
#       the committed files are enough: from an unpacked `git archive $(git write-tree)` in .bench_tree/
S=benchmark/tests/scripts; O=$PWD/chiprun_out/families; mkdir -p $O
run() {  # <label> <dir> <command...>: one run, its result line shown
  label=$1; dir=$2; shift 2
  t0=$(date +%s)
  (cd $dir && "$@" > $O/$label.out 2> $O/$label.err); rc=$?
  echo "== $label rc=$rc $(( $(date +%s) - t0 )) s"; python3 $S/show.py $O/$label.out
  grep -E "^(\[(trace|window\] MB|host|servers\] ready|prepare\] ec)|FAILED)" $O/$label.err | cut -c1-700
}
bench="python3 benchmark/run.py --seconds ${WINDOW:-20}"
manifest_of() {  # the tests' manifest that holds the cell
  grep -q "\"$1\"" benchmark/tests/cells-families.json && echo benchmark/tests/cells-families.json \
    || echo benchmark/tests/cells.json
}
case $1 in
pairs)
  seed=$2; shift 2
  for cell in "$@"; do
    run $cell.parent.plain .parent_tree $bench --workload $cell --seed $seed --trace 0
    run $cell.change.plain . $bench --workload $cell --seed $seed --trace 0
    run $cell.change.traced . $bench --workload $cell --seed $((seed + 1)) --trace 1
    run $cell.parent.traced .parent_tree $bench --workload $cell --seed $((seed + 1)) --trace 1
    python3 $S/pair_report.py $O/$cell
  done
  ;;
rehearse)
  cell=$2; trace=$3; shift 3
  for s in "$@"; do
    run $cell.$trace.$s . python3 benchmark/tests/rehearse.py --manifest $(manifest_of $cell) --require-platform tpu \
      --wrapper ${WRAPPER:-benchmark.served_volume} --workload $cell --seed $s --seconds ${WINDOW:-20} --trace $trace
    grep -E "^\[compared\]" $O/$cell.$trace.$s.err | cut -c1-120 | tr '\n' ';'; echo
    grep -E "^\[window\] (opened|operation)" $O/$cell.$trace.$s.err | cut -c1-900
    [ $trace = 1 ] && tail -1 $O/$cell.$trace.$s.out | cut -c1-3000
  done
  ;;
spans)
  cell=$2; seed=$3; K=$O/$cell.$seed.kept; rm -rf $K
  run $cell.$seed.on . python3 benchmark/tests/rehearse.py --manifest $(manifest_of $cell) --require-platform tpu \
    --wrapper benchmark.tests.spans_volume:$K --workload $cell --seed $seed --seconds ${WINDOW:-20} --trace 1
  JAX_PLATFORMS=cpu python3 $S/spans_report.py $K > $O/$cell.$seed.report.json 2> $O/$cell.$seed.report.txt
  echo "   report rc=$?"; cut -c1-2400 $O/$cell.$seed.report.txt
  rm -rf $K/profile; gzip -f $K/traces.json
  ;;
proof)
  test -d .bench_tree/.git && echo "HAS .git"
  run proof.seal.rs6-3.traced .bench_tree $bench --workload seal.single.rs6-3 --seed 3400000951 --trace 1
  run proof.reads.plain .bench_tree $bench --workload reads.degraded1 --seed 3400000952 --trace 0
  run proof.chunks.traced .bench_tree $bench --workload reads.degraded1.chunks --seed 3400000953 --trace 1
  run proof.seal.plain .bench_tree $bench --workload seal.single --seed 3400000954 --trace 0
  # a directory with only BENCHMARK.json and the paths: no result, non-zero
  mkdir -p .smoke_tree/onlybench && cp -r .bench_tree/BENCHMARK.json .bench_tree/benchmark .smoke_tree/onlybench/ && (cd .smoke_tree/onlybench && python3 benchmark/run.py --workload seal.single --seed 1 --seconds 1 --trace 0 > out.txt 2> err.txt; echo "bare rc=$? stdout_bytes=$(wc -c < out.txt)"; tail -2 err.txt | cut -c1-300)
  ;;
esac
