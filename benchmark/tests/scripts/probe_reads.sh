# reads.degraded1 as committed: the first run compiles what the cache lacks,
# the third is traced; then its control and its planted fault.  ~20 GiB.
#   chiprun --timeout 1800 -- bash benchmark/tests/scripts/probe_reads.sh
S=benchmark/tests/scripts; O=chiprun_out/probe_reads; mkdir -p $O
R="python3 benchmark/tests/rehearse.py --manifest BENCHMARK.json --require-platform tpu"
for spec in "81 0" "82 0" "83 1" "84 0"; do set -- $spec
  python3 benchmark/run.py --workload reads.degraded1 --seed $1 --seconds 10 --trace $2 > $O/reads.$1.out 2> $O/reads.$1.err; echo "== reads.degraded1 seed $1 trace $2 rc=$?"
  python3 $S/show.py $O/reads.$1.out; grep -E "^\[(servers\] ready|fill|prepare|warm-up|window|host|trace)" $O/reads.$1.err | cut -c1-1500
done
for spec in "xor_rebuild 91" "xor_rebuild 92" "xor_rebuild 93" "altered_read 95"; do set -- $spec
  $R --wrapper benchmark.tests.faulty_volume:$1 --workload reads.degraded1 --seed $2 --seconds 5 --trace 0 > $O/$1.$2.out 2> $O/$1.$2.err; echo "== $1 seed $2 rc=$?"
  python3 $S/show.py $O/$1.$2.out; grep -E "^\[compared\].*FAILED" $O/$1.$2.err
done
