# The chip calls behind PERF.md's numbers for reads.degraded1.chunks (PR 31).  The cell is in
# BENCHMARK.json under read_ops and setup_s alone, with kernel_roofline.read (PERF.md section 4): plain
# runs go through run.py as the driver's do; a traced run goes through rehearse.py and
# benchmark/tests/cells-chunks.json, which is run.py with the same cell under EVERY read metric (the
# seven `.read` quantities beside kernel_roofline.read).  One mode a line; a call chains modes:
#   chiprun --timeout 2400 -- bash -c 'S=benchmark/tests/scripts/chunks_on_chip.sh; bash $S set H20a 20 0 <seed>...; bash $S ...'
#   parent <seed> <seed>      the PARENT commit on the cell, traced and not (.parent_tree/: `git archive`
#                             of the parent with this PR's benchmark/ laid over it)
#   change <seed> <seed>      the same two runs of this tree
#   parent_line <cell> <seed> the driver's traced run of a cell on the PARENT under this PR's benchmark
#                             files (run.py --trace 1 in .parent_tree/): a result line, no reader raising
#   controls <seed> <seed>    xor_rebuild and altered_read at the cell's own sizes, 5 s: `correct` false
#   set <name> <seconds> <trace 0|1> <seed>...
#                             one set of runs, each on a seed of its own, left as
#                             chiprun_out/reads.degraded1.chunks/<name>.<seed>.out|.err
#   spread <name>...          (no chip) per set and metric: median, quartile distance over it, values;
#                             also of the percentiles each run logs (`[window] operation ms`)
#   pairs <cell> [change] <seed>...
#                             parent and change on the same seeds, order alternating (an old cell
#                             or this one, through run.py)
#   spans <seed>              one run with every request sampled (spans_volume) and its report
#   proof                     the committed files are enough: from an unpacked
#                             `git archive $(git write-tree)` in .bench_tree/ (the new cell traced
#                             both ways: the driver's line and every read metric)
# The calls of PR 31 (A to G while the cell stood in BENCHMARK.json a first time, its per-layer
# quantities printed as `.ops`; H while it stood only in cells-chunks.json; I and J as it is now; sets A and
# B by measure_sets.sh, the rest of A to G by rs6_3_on_chip.sh's set / pairs / spans, which are folded
# in here):
#   A  parent 3100000001 3100000002; change 3100000003 3100000004; set A (the sets' seeds)
#   B  a probe for pauses of the whole server on 3100000011/12 (a wrapper, not kept: ROADMAP S13);
#      controls 3100000021 3100000022
#   C  the probe dumping every thread's stack inside a pause, 3100000031-34
#   D  spans 3100000041 (PERF.md section 5's table); set B (the sets' seeds); one traced run 3100000042
#   E  THE S5b + S11 TRIAL, a patch to ops/rs_mesh.py and parallel/batcher.py that is not in the tree
#      (ROADMAP S5b, S11 describe it): change 3100000061 3100000062; set C 10 0 3100000063-66;
#      spans 3100000067
#   F  parent 3100000071 3100000072; change the same; the parent's reads.degraded1 traced; pairs
#      seal.single 3100000074 75; seal.single.rs6-3 76 77; reads.degraded1 78 79; this cell 80
#   G  proof; pairs reads.degraded1 change 3100000091 92
#   H  (from cells-chunks.json) set H10 10 1 3100000101; set H20a 20 0 3100000111-16;
#      set H20b 20 0 3100000121-26; set H30 30 0 3100000131-36; proof
#   I  one traced run through run.py, 3100000201, with the cell appended to the seven `.read` lists:
#      all eight metrics, and chiprun's note that the driver refuses such a list (the cell does not
#      report read_p99_ms)
#   J  on the final tree: proof; parent_line reads.degraded1.chunks 3100000211; parent_line
#      reads.degraded1 3100000212; set J 10 0 3100000221-26; set K 10 0 3100000241-46;
#      pairs reads.degraded1.chunks 3100000231 32 33
#   K  one traced run through run.py as the manifest is handed in, 3100000251: kernel_roofline.read
#      alone, and no note from chiprun's check of the form
S=benchmark/tests/scripts; cell=reads.degraded1.chunks
O=$PWD/chiprun_out/chunks; C=$PWD/chiprun_out/$cell; mkdir -p $O $C
run() {  # <out-dir> <label> <dir> <command...>: one run, its result line shown
  out=$1; label=$2; dir=$3; shift 3
  t0=$(date +%s)
  (cd $dir && "$@" > $out/$label.out 2> $out/$label.err); rc=$?
  echo "== $label rc=$rc $(( $(date +%s) - t0 )) s"; python3 $S/show.py $out/$label.out
  grep -E "^\[(trace|host|servers\] ready|window\] operation)" $out/$label.err | cut -c1-600
  grep -E "^\[compared\].*FAILED" $out/$label.err
}
R="python3 benchmark/tests/rehearse.py --manifest benchmark/tests/cells-chunks.json --require-platform tpu"
bench() {  # <cell> [trace]: the command that runs it (this cell traced: under every read metric)
  if [ $1 = $cell ] && [ "$2" = 1 ]; then echo "$R --workload $1"; else echo "python3 benchmark/run.py --workload $1"; fi
}
case $1 in
parent|change)
  dir=.; [ $1 = parent ] && dir=.parent_tree
  run $O $1.traced.$2 $dir $(bench $cell 1) --seed $2 --seconds 10 --trace 1
  tail -1 $O/$1.traced.$2.out | cut -c1-3000
  run $O $1.plain.$3 $dir $(bench $cell) --seed $3 --seconds 10 --trace 0
  ;;
parent_line)
  run $O parent.line.$2.$3 .parent_tree $(bench $2) --seed $3 --seconds 10 --trace 1
  tail -1 $O/parent.line.$2.$3.out | cut -c1-1200
  ;;
controls)
  run $O control.xor_rebuild.$2 . $R --wrapper benchmark.tests.faulty_volume:xor_rebuild --workload $cell --seed $2 --seconds 5 --trace 0
  run $O control.altered_read.$3 . $R --wrapper benchmark.tests.faulty_volume:altered_read --workload $cell --seed $3 --seconds 5 --trace 0
  ;;
set)
  name=$2; seconds=$3; trace=$4; shift 4
  for s in "$@"; do
    run $C $name.$s . $(bench $cell $trace) --seed $s --seconds $seconds --trace $trace
    [ $trace = 1 ] && tail -1 $C/$name.$s.out | cut -c1-3000
  done
  ;;
spread)
  shift
  python3 - $C "$@" <<'EOF'
import glob, json, re, statistics, sys
out_dir, names = sys.argv[1], sys.argv[2:]
for name in names:
    rows = {}
    for path in sorted(glob.glob(f"{out_dir}/{name}.*.out")):
        result = json.loads(open(path).read().strip().splitlines()[-1])
        if not result["correct"]:
            print("INCORRECT", path)
        for k, v in result["end_to_end_of_this_run"].items():
            rows.setdefault(k, []).append(v)
        for line in open(path[:-4] + ".err"):
            if line.startswith("[window] operation ms"):
                for k, v in re.findall(r"(p[\d.]+|max)=([\d.]+)", line):
                    if k not in ("p50", "p99"):
                        rows.setdefault("logged " + k, []).append(float(v))
    for k, values in rows.items():
        q = statistics.quantiles(values, n=4)
        med = statistics.median(values)
        print(name, k, "median", round(med, 3), "spread",
              round((q[2] - q[0]) / med, 4), [round(v, 2) for v in values])
EOF
  ;;
pairs)
  what=$2; shift 2; i=0
  # "change" before the seeds: the first pair runs the change first
  if [ "$1" = change ]; then i=1; shift; fi
  for s in "$@"; do i=$((i+1))
    if [ $((i % 2)) = 1 ]; then order=".parent_tree ."; else order=". .parent_tree"; fi
    for d in $order; do
      side=change; [ $d = .parent_tree ] && side=parent
      run $O pair.$what.$s.$side $d $(bench $what) --seed $s --seconds 10 --trace 0
    done
  done
  ;;
spans)
  K=$O/$cell.$2.kept; rm -rf $K
  run $O $cell.$2.on . $R --wrapper benchmark.tests.spans_volume:$K --workload $cell --seed $2 --seconds 10 --trace 1
  JAX_PLATFORMS=cpu python3 $S/spans_report.py $K > $O/$cell.$2.report.json 2> $O/$cell.$2.report.txt
  echo "   report rc=$?"; cut -c1-2400 $O/$cell.$2.report.txt
  rm -rf $K/profile; gzip -f $K/traces.json
  ;;
proof)
  test -d .bench_tree/.git && echo "HAS .git"
  run $O proof.new.traced .bench_tree $(bench $cell) --seed 3100000151 --seconds 10 --trace 1
  tail -1 $O/proof.new.traced.out | cut -c1-3000
  run $O proof.new.traced.all .bench_tree $(bench $cell 1) --seed 3100000152 --seconds 10 --trace 1
  tail -1 $O/proof.new.traced.all.out | cut -c1-1200
  run $O proof.old.read.traced .bench_tree $(bench reads.degraded1) --seed 3100000153 --seconds 10 --trace 1
  tail -1 $O/proof.old.read.traced.out | cut -c1-3000
  run $O proof.old.seal.plain .bench_tree $(bench seal.single) --seed 3100000154 --seconds 10 --trace 0
  run $O proof.old.rs63.plain .bench_tree $(bench seal.single.rs6-3) --seed 3100000155 --seconds 10 --trace 0
  ;;
esac
