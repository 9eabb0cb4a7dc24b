# The chip calls behind PERF.md's numbers for PR 34's second round (the sealer that clears the last
# call's shards; the window's length).  A machine takes ~33 GiB of writes before it stalls; a seal run
# writes ~3.7 GiB (5 calls of 0.72-0.77 GiB), a read run ~2.5 GiB: at most 6 seal runs a call.
#   chiprun --timeout 2400 -- bash benchmark/tests/scripts/window_on_chip.sh set <cell> <A|B> <seconds> <trace 0|1> <seed>...
#       one set of runs of a cell of BENCHMARK.json at a window of <seconds>, every run on a seed of its
#       own, left under chiprun_out/<cell>/w<seconds>/ (traced runs as traced.<seed>); then spread.py's
#       table for that directory where both sets are there
#   ... window_on_chip.sh faults <seconds>
#       the seal cell's planted faults and the rs6-3 cell's control at the cells' own sizes, with the
#       sealer that clears: each has to come out `correct: false` on the number PERF.md section 4 names
#   ... window_on_chip.sh storm8 <seconds> <seed>...
#       the storm traffic that is not a cell (ROADMAP B2: "try it again after B0"): its rounds' rates
#   ... window_on_chip.sh proof <seconds>
#       the committed files are enough: every cell from an unpacked `git archive $(git write-tree)` in
#       .bench_tree/, two of them traced; and a directory with only BENCHMARK.json and benchmark/ prints
#       no result
S=benchmark/tests/scripts
run() {  # <out dir> <label> <dir> <command...>: one run, its result line shown
  O=$1; label=$2; dir=$3; shift 3
  t0=$(date +%s)
  (cd $dir && "$@" > $O/$label.out 2> $O/$label.err); rc=$?
  echo "== $label rc=$rc $(( $(date +%s) - t0 )) s"; python3 $S/show.py $O/$label.out
  grep -E "^(\[(trace|window\] (MB|pipeline|operation)|host)|FAILED)" $O/$label.err | cut -c1-700
}
case $1 in
set)
  cell=$2; name=$3; seconds=$4; trace=$5; shift 5
  O=$PWD/chiprun_out/$cell/w$seconds; mkdir -p $O
  for s in "$@"; do
    label=$name.$s; [ $trace = 1 ] && label=traced.$s
    run $O $label . python3 benchmark/run.py --workload $cell --seed $s --seconds $seconds --trace $trace
    [ $trace = 1 ] && tail -1 $O/$label.out | cut -c1-3000
  done
  ls $O/B.*.out > /dev/null 2>&1 && python3 $S/spread.py $cell/w$seconds
  ;;
faults)
  seconds=$2; O=$PWD/chiprun_out/faults20; mkdir -p $O
  R="python3 benchmark/tests/rehearse.py --require-platform tpu --seconds $seconds --trace 0"
  for f in unchanged half_batch half_batch_early altered_seal; do
    run $O $f . $R --manifest BENCHMARK.json --wrapper benchmark.tests.faulty_volume:$f --workload seal.single --seed 3400006001
    grep -E "^\[compared\].*FAILED" $O/$f.err | cut -c1-120 | tr '\n' ';'; echo
  done
  run $O rs6-3-as-rs . $R --manifest benchmark/tests/cells-rs6-3.json --workload control.seal.single.rs6-3-as-rs --seed 3400006002
  grep -E "^\[compared\].*FAILED" $O/rs6-3-as-rs.err | cut -c1-120 | tr '\n' ';'; echo
  ;;
storm8)
  seconds=$2; shift 2; O=$PWD/chiprun_out/storm20; mkdir -p $O
  for s in "$@"; do
    run $O storm8.$s . python3 benchmark/tests/rehearse.py --require-platform tpu --workload exp.seal.storm8 --seed $s --seconds $seconds --trace 0
  done
  ;;
proof)
  seconds=$2; O=$PWD/chiprun_out/proof; mkdir -p $O
  test -d .bench_tree/.git && echo "HAS .git"
  bench="python3 benchmark/run.py --seconds $seconds"
  run $O proof.seal.traced .bench_tree $bench --workload seal.single --seed 3400009951 --trace 1
  tail -1 $O/proof.seal.traced.out | cut -c1-3000
  run $O proof.rs6-3.plain .bench_tree $bench --workload seal.single.rs6-3 --seed 3400009952 --trace 0
  run $O proof.chunks.traced .bench_tree $bench --workload reads.degraded1.chunks --seed 3400009953 --trace 1
  run $O proof.reads.plain .bench_tree $bench --workload reads.degraded1 --seed 3400009954 --trace 0
  mkdir -p .smoke_tree/onlybench && cp -r .bench_tree/BENCHMARK.json .bench_tree/benchmark .smoke_tree/onlybench/ \
    && (cd .smoke_tree/onlybench && python3 benchmark/run.py --workload seal.single --seed 1 --seconds 1 --trace 0 > out.txt 2> err.txt
        echo "bare rc=$? stdout_bytes=$(wc -c < out.txt)"; tail -2 err.txt | cut -c1-300)
  ;;
esac
