# Where the time goes, on the chip (PERF.md section 5, PR 25): each cell with
# every request sampled and a device trace running ("on": spans_volume), against
# the same seeds at the CLI's defaults ("off": run.py --trace 0); then the
# benchmark's own traced run, and the read cell with the planes that ride every
# request switched off.  ~3 GiB written a seed.
#   chiprun --timeout 3300 -- bash benchmark/tests/scripts/spans_on_chip.sh <what> <seed>...
# what: on (one "on" run a cell), smoke (the same + the traced run; writes the
#       tests' fixture; SPANS_OUT=<dir> moves the output from chiprun_out/spans),
#       seal | reads (parent/off/on on every seed, then the traced run; reads
#       also planes-off on every seed), all (both).  "parent" is the parent
#       commit at the defaults, where .parent_tree/ holds it
#       (git archive <parent> | tar -x -C .parent_tree).
what=$1; shift
S=benchmark/tests/scripts; O=${SPANS_OUT:-chiprun_out/spans}; mkdir -p $O
R="python3 benchmark/tests/rehearse.py --manifest BENCHMARK.json --require-platform tpu"
show() { python3 $S/show.py $O/$1.out; python3 -c "
import json,sys
r=json.loads(open('$O/$1.out').read().strip().splitlines()[-1]); print('   e2e', {k: round(v,3) for k,v in r['end_to_end_of_this_run'].items()})"; grep -E "^\[(host|trace\] [0-9])" $O/$1.err | cut -c1-400; }
off() { python3 benchmark/run.py --workload $1 --seed $2 --seconds 10 --trace 0 > $O/$1.$2.off.out 2> $O/$1.$2.off.err; echo "== $1 seed $2 off rc=$?"; show $1.$2.off; }
on() { rm -rf $O/$1.$2.kept; $R --wrapper benchmark.tests.spans_volume:$O/$1.$2.kept --workload $1 --seed $2 --seconds 10 --trace 1 > $O/$1.$2.on.out 2> $O/$1.$2.on.err; echo "== $1 seed $2 on rc=$?"; show $1.$2.on
  JAX_PLATFORMS=cpu python3 $S/spans_report.py $O/$1.$2.kept $3 > $O/$1.$2.report.json 2> $O/$1.$2.report.txt; echo "   report rc=$?"; cut -c1-1800 $O/$1.$2.report.txt
  # the raw spans and the profile are large: the report is what comes back
  rm -rf $O/$1.$2.kept/profile; gzip -f $O/$1.$2.kept/traces.json; }
parent() { test -d .parent_tree || return 0; (cd .parent_tree && python3 benchmark/run.py --workload $1 --seed $2 --seconds 10 --trace 0) > $O/$1.$2.parent.out 2> $O/$1.$2.parent.err; echo "== $1 seed $2 parent rc=$?"; show $1.$2.parent; }
# the counters and the trace at the default 1% sample: what the stages cost when nobody looks
defaults() { rm -rf $O/$1.$2.defaults.kept; $R --wrapper benchmark.tests.spans_volume:defaults=$O/$1.$2.defaults.kept --workload $1 --seed $2 --seconds 10 --trace 1 > $O/$1.$2.defaults.out 2> $O/$1.$2.defaults.err; echo "== $1 seed $2 traced at the default sample rc=$?"; show $1.$2.defaults
  JAX_PLATFORMS=cpu python3 $S/spans_report.py $O/$1.$2.defaults.kept > $O/$1.$2.defaults.report.json 2> $O/$1.$2.defaults.report.txt; grep -E "^\((a|c|d)\)" $O/$1.$2.defaults.report.txt | cut -c1-1500; rm -rf $O/$1.$2.defaults.kept/profile; }
planes_off() { $R --wrapper benchmark.tests.spans_volume:planes-off --workload $1 --seed $2 --seconds 10 --trace 0 > $O/$1.$2.planes_off.out 2> $O/$1.$2.planes_off.err; echo "== $1 seed $2 planes-off rc=$?"; show $1.$2.planes_off; }
traced() { python3 benchmark/run.py --workload $1 --seed $2 --seconds 10 --trace 1 > $O/$1.$2.traced.out 2> $O/$1.$2.traced.err; echo "== $1 seed $2 traced (the benchmark's own) rc=$?"; show $1.$2.traced; grep -E "^\[trace\]" $O/$1.$2.traced.err | cut -c1-300; python3 -c "
import json
r=json.loads(open('$O/$1.$2.traced.out').read().strip().splitlines()[-1]); print('   device_ops', r['breakdown']['device_ops'][:6])"; }
cell() { c=$1; shift; i=0
  for s in "$@"; do i=$((i+1))
    if [ $((i % 2)) = 1 ]; then parent $c $s; off $c $s; on $c $s; else on $c $s; off $c $s; parent $c $s; fi
  done
  traced $c $((${1} + 7)); defaults $c $((${1} + 9))
  if [ $c = reads.degraded1 ]; then for s in "$@"; do planes_off $c $s; done; fi; }
case $what in
  on) on seal.single $1; on reads.degraded1 $1;;
  smoke) on seal.single $1 "--fixture $O/recorded_spans_v5e.json.gz"; on reads.degraded1 $1; traced seal.single $(($1 + 7)); traced reads.degraded1 $(($1 + 8));;
  seal) cell seal.single "$@";;
  reads) cell reads.degraded1 "$@";;
  all) cell seal.single "$@"; cell reads.degraded1 "$@";;
esac
