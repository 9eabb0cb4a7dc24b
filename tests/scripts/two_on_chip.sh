# The chip calls behind PERF.md's numbers for two dispatches on the device and for the seal pipeline's
# one native call a batch a stage (`stats` lines carry its `pipeline` and write split).  The parent is
# unpacked into .parent_tree/ first:  mkdir .parent_tree && git archive <parent> | tar -x -C .parent_tree
# (then copy this tree's tests/scripts/stats_volume.py over the parent's, so both sides record the same)
# A machine takes ~33 GiB of writes before it stalls and a seal run writes ~3.7 GiB: at most 6-8 seal runs a call.
#   chiprun --timeout 1500 -- bash tests/scripts/two_on_chip.sh pairs <cell> <first seed> <P|C>...
#       plain runs of <cell> in the order given (P: the parent's tree, C: this one); the i-th P and the i-th C
#       share seed <first seed> + i; then pairs_report.py's table of everything under chiprun_out/two/
#   ... two_on_chip.sh traced <cell> <seed>            change then parent under --trace 1, one seed
#   ... two_on_chip.sh stats <cell> <first seed> <P|C>...  plain runs behind tests/scripts/stats_volume.py: the
#       scheduler's stats() round every seal call -> chiprun_out/two/<cell>.<side>.<seed>.stats.jsonl
#   ... two_on_chip.sh chunks <first seed> <P|C>...    reads.degraded1.chunks from its rehearsal manifest, traced:
#       read_p50_ms, read_p99_ms, the seven .read quantities and the logged percentiles
#   ... two_on_chip.sh proof <seed>                    the committed files are enough: seal.single traced and
#       reads.degraded1 plain from an unpacked `git archive $(git write-tree)` in .bench_tree/
# Several of these in one call: bash tests/scripts/two_on_chip.sh all "<args of one>" "<args of the next>" ...
S=benchmark/tests/scripts; O=$PWD/chiprun_out/two; mkdir -p $O
dir_of() { case $1 in P) echo .parent_tree;; V) echo .variant_tree;; *) echo .;; esac; }  # V: a trial tree, if one was made
run() {  # <label> <dir> <command...>
  label=$1; dir=$2; shift 2
  t0=$(date +%s)
  (cd $dir && "$@" > $O/$label.out 2> $O/$label.err); rc=$?
  echo "== $label rc=$rc $(( $(date +%s) - t0 )) s"; python3 $S/show.py $O/$label.out
  grep -E "^(\[(trace|window\] (MB|pipeline|operation)|host)|FAILED)" $O/$label.err | cut -c1-900
}
seeds() {  # <first seed> <P|C>...: prints "<side> <seed>" a run
  first=$1; shift; p=0; c=0; v=0
  for side in "$@"; do
    case $side in
      P) p=$((p + 1)); echo "P $((first + p))";;
      V) v=$((v + 1)); echo "V $((first + v))";;
      *) c=$((c + 1)); echo "C $((first + c))";;
    esac
  done
}
case $1 in
all)
  shift
  for args in "$@"; do bash tests/scripts/two_on_chip.sh $args; done
  ;;
pairs)
  cell=$2; first=$3; shift 3
  seeds $first "$@" | while read side seed; do
    run $cell.$side.$seed $(dir_of $side) python3 benchmark/run.py --workload $cell --seed $seed --seconds 20 --trace 0
  done
  python3 tests/scripts/pairs_report.py $O
  ;;
traced)
  cell=$2; seed=$3
  for side in C P; do
    run $cell.$side.$seed.traced $(dir_of $side) python3 benchmark/run.py --workload $cell --seed $seed --seconds 20 --trace 1
    tail -1 $O/$cell.$side.$seed.traced.out | cut -c1-3500
  done
  ;;
stats)
  cell=$2; first=$3; shift 3
  seeds $first "$@" | while read side seed; do
    : > $O/$cell.$side.$seed.stats.jsonl
    run $cell.$side.$seed.stats $(dir_of $side) env PYTHONPATH=$PWD python3 benchmark/tests/rehearse.py --manifest BENCHMARK.json \
      --require-platform tpu --wrapper tests.scripts.stats_volume:$O/$cell.$side.$seed.stats.jsonl \
      --workload $cell --seed $seed --seconds 20 --trace 0
    python3 tests/scripts/pairs_report.py --stats $O/$cell.$side.$seed.stats.jsonl
  done
  ;;
chunks)
  first=$2; shift 2
  seeds $first "$@" | while read side seed; do
    run chunks-all.$side.$seed $(dir_of $side) python3 benchmark/tests/rehearse.py --manifest benchmark/tests/cells-chunks.json \
      --require-platform tpu --workload reads.degraded1.chunks --seed $seed --seconds 20 --trace 1
  done
  ;;
proof)
  test -d .bench_tree/.git && echo "HAS .git"
  run proof.seal.traced .bench_tree python3 benchmark/run.py --workload seal.single --seed $2 --seconds 20 --trace 1
  tail -1 $O/proof.seal.traced.out | cut -c1-3500
  run proof.reads.plain .bench_tree python3 benchmark/run.py --workload reads.degraded1 --seed $(($2 + 1)) --seconds 20 --trace 0
  ;;
esac
