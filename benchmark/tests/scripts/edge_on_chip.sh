# The chip calls behind PERF.md's numbers for PR 37 (the HTTP edge's stages).  A read run writes
# ~1.2 GiB and takes ~100 s, a seal run ~50 s.  .parent_tree/ holds the parent commit and
# .bench_tree/ the change as committed (git archive <commit or $(git write-tree)> | tar -x -C ...).
#   chiprun --timeout 3300 -- bash benchmark/tests/scripts/edge_on_chip.sh all <seed>
#     cost                       edge_cost.py: the stages' microseconds a request (first: it holds the chip)
#     edge <cell> <seed> [fix]   edge_report.py run + report on the change: the three metrics, the split of
#                                the read, the idle line ("fix": and the tests' fixture); the kept profile
#                                is reduced on the machine and removed
#     pairs <cell> <seed>...     plain runs, parent and change, the order alternating seed by seed
#     short <seed>               cost, both edge runs, three pairs of reads.degraded1
#     all <seed>                 cost; edge on reads.degraded1 (seed, fixture) and on the LRC cell
#                                (seed+1); six pairs of reads.degraded1 on seed, seed+2 .. seed+10 (the
#                                first seed's change run is the plain twin of the traced edge run: the
#                                on-cost); one pair of each other configuration's cell, most at risk first
S=benchmark/tests/scripts; O=$PWD/chiprun_out/edge; mkdir -p $O
show() { python3 $S/show.py $1; grep -E "^(\[(edge|host|trace\] [0-9])|FAILED)" $2 | cut -c1-600; }
plain() {  # <label> <dir> <cell> <seed>
  t0=$(date +%s)
  (cd $2 && python3 benchmark/run.py --workload $3 --seed $4 --seconds 20 --trace 0) \
    > $O/$3.$1.$4.out 2> $O/$3.$1.$4.err
  echo "== $3 $1 seed $4 rc=$? $(( $(date +%s) - t0 )) s"; show $O/$3.$1.$4.out $O/$3.$1.$4.err; }
edge() {  # <cell> <seed> [fix]
  d=$O/$1.$2; rm -rf $d; mkdir -p $d; t0=$(date +%s)
  (cd .bench_tree && python3 $S/edge_report.py run $d --workload $1 --seed $2 --seconds 20) \
    > $d/run.out 2> $d/run.err
  echo "== edge $1 seed $2 rc=$? $(( $(date +%s) - t0 )) s"; show $d/run.out $d/run.err
  (cd .bench_tree && JAX_PLATFORMS=cpu python3 $S/edge_report.py report $d \
    ${3:+--fixture $O/recorded_edge_v5e.json.gz}) > $d/report.json 2> $d/report.txt
  echo "   report rc=$?"; cut -c1-3000 $d/report.txt
  rm -rf $d/profile; gzip -f $d/traces.json; }
pairs() { c=$1; shift; i=0
  for s in "$@"; do i=$((i+1))
    if [ $((i % 2)) = 1 ]; then plain parent .parent_tree $c $s; plain change .bench_tree $c $s
    else plain change .bench_tree $c $s; plain parent .parent_tree $c $s; fi
  done; }
mode=$1; shift
case $mode in
  cost) (cd .bench_tree && python3 $S/edge_cost.py);;
  edge) edge "$@";;
  pairs) pairs "$@";;
  all) s=$1
    (cd .bench_tree && python3 $S/edge_cost.py)
    edge reads.degraded1 $s fix
    edge reads.degraded1.lrc12-2-2 $((s + 1))
    pairs reads.degraded1 $s $((s + 2)) $((s + 4)) $((s + 6)) $((s + 8)) $((s + 10))
    pairs reads.degraded1.lrc12-2-2 $((s + 21))
    pairs reads.degraded1.chunks $((s + 22))
    pairs seal.single $((s + 23))
    pairs seal.single.rs6-3 $((s + 24));;
  short) s=$1  # what "all" does first, with three pairs: for a chip handed over late
    (cd .bench_tree && python3 $S/edge_cost.py)
    edge reads.degraded1 $s fix
    edge reads.degraded1.lrc12-2-2 $((s + 1))
    pairs reads.degraded1 $s $((s + 2)) $((s + 4));;
esac
