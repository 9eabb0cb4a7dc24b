# The chip calls behind PERF.md's numbers for seal.single.rs6-3 (PR 27).  A machine takes
# ~33 GiB of writes before it stalls and a seal run writes ~4.3 GiB: at most 7 seal runs a call.
#   chiprun --timeout 1500 -- bash benchmark/tests/scripts/rs6_3_on_chip.sh first
#       the new cell traced and not, the PARENT on it (.parent_tree/: `git archive` of the
#       parent with this PR's BENCHMARK.json and benchmark/ laid over it), both old cells
#       traced, the control at the cell's own size
#   chiprun --timeout 1500 -- bash benchmark/tests/scripts/rs6_3_on_chip.sh pairs <cell> <seed>...
#       parent and change on the same seeds, order alternating (parent first;
#       `pairs <cell> change <seed>...` starts with the change)
#   chiprun --timeout 1500 -- bash benchmark/tests/scripts/rs6_3_on_chip.sh set <cell> <A|B> <trace 0|1> <seed>...
#       one set of runs of a cell, every run on a seed of its own, left under chiprun_out/<cell>/
#       as measure_sets.sh leaves them, for spread.py
#   ... rs6_3_on_chip.sh spans <cell> <seed>
#       one run with every request sampled, as spans_on_chip.sh's "on", of any cell
#   ... rs6_3_on_chip.sh proof
#       the committed files are enough: from an unpacked `git archive $(git write-tree)` in .bench_tree/
S=benchmark/tests/scripts; O=$PWD/chiprun_out/rs6-3; mkdir -p $O
run() {  # <label> <dir> <command...>: one run, its result line shown
  label=$1; dir=$2; shift 2
  t0=$(date +%s)
  (cd $dir && "$@" > $O/$label.out 2> $O/$label.err); rc=$?
  echo "== $label rc=$rc $(( $(date +%s) - t0 )) s"; python3 $S/show.py $O/$label.out
  grep -E "^\[(trace|window\] MB|host)" $O/$label.err | cut -c1-600
}
bench="python3 benchmark/run.py --seconds 10"
case $1 in
first)
  run new.traced . $bench --workload seal.single.rs6-3 --seed 2700000001 --trace 1
  tail -1 $O/new.traced.out | cut -c1-2500
  run new.plain . $bench --workload seal.single.rs6-3 --seed 2700000002 --trace 0
  run parent.on.new .parent_tree $bench --workload seal.single.rs6-3 --seed 2700000003 --trace 0
  grep -m3 -E "HTTP 500|KeyError" $O/parent.on.new.err | cut -c1-300
  run parent.on.new.traced .parent_tree $bench --workload seal.single.rs6-3 --seed 2700000004 --trace 1
  tail -3 $O/parent.on.new.traced.err | cut -c1-300
  run old.seal.traced . $bench --workload seal.single --seed 2700000005 --trace 1
  run old.read.traced . $bench --workload reads.degraded1 --seed 2700000006 --trace 1
  run control . python3 benchmark/tests/rehearse.py --manifest benchmark/tests/cells-rs6-3.json \
    --require-platform tpu --workload control.seal.single.rs6-3-as-rs --seed 2700000007 --seconds 5
  ;;
set)
  cell=$2; name=$3; trace=$4; shift 4
  O=$PWD/chiprun_out/$cell; mkdir -p $O
  for s in "$@"; do
    label=$name.$s; [ $trace = 1 ] && label=traced.$s
    run $label . $bench --workload $cell --seed $s --trace $trace
  done
  ;;
spans)
  K=$O/$2.$3.kept; rm -rf $K
  run $2.$3.on . python3 benchmark/tests/rehearse.py --manifest BENCHMARK.json --require-platform tpu \
    --wrapper benchmark.tests.spans_volume:$K --workload $2 --seed $3 --seconds 10 --trace 1
  JAX_PLATFORMS=cpu python3 $S/spans_report.py $K > $O/$2.$3.report.json 2> $O/$2.$3.report.txt
  echo "   report rc=$?"; cut -c1-2400 $O/$2.$3.report.txt
  rm -rf $K/profile; gzip -f $K/traces.json
  ;;
proof)
  test -d .bench_tree/.git && echo "HAS .git"
  run proof.new.traced .bench_tree $bench --workload seal.single.rs6-3 --seed 2700000051 --trace 1
  run proof.new.plain .bench_tree $bench --workload seal.single.rs6-3 --seed 2700000052 --trace 0
  run proof.old.seal.traced .bench_tree $bench --workload seal.single --seed 2700000053 --trace 1
  run proof.old.read.plain .bench_tree $bench --workload reads.degraded1 --seed 2700000054 --trace 0
  ;;
pairs)
  cell=$2; shift 2; i=0
  # "change" before the seeds: the first pair runs the change first
  if [ "$1" = change ]; then i=1; shift; fi
  for s in "$@"; do i=$((i+1))
    if [ $((i % 2)) = 1 ]; then order=".parent_tree ."; else order=". .parent_tree"; fi
    for d in $order; do
      side=change; [ $d = .parent_tree ] && side=parent
      run pair.$cell.$s.$side $d $bench --workload $cell --seed $s --trace 0
    done
  done
  ;;
esac
