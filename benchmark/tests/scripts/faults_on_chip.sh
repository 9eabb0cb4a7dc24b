# The seal cell's control and its planted faults at the cell's own size, on
# the chip (the read cell's are in probe_reads.sh).  ~25 GiB written.
#   chiprun --timeout 1500 -- bash benchmark/tests/scripts/faults_on_chip.sh
# Each has to come out `correct: false` with the number PERF.md section 4
# names.  Windows of 5 s: two calls, enough to have an early and a last one.
S=benchmark/tests/scripts; O=chiprun_out/faults; mkdir -p $O
R="python3 benchmark/tests/rehearse.py --manifest BENCHMARK.json --require-platform tpu"
C="python3 benchmark/tests/rehearse.py --require-platform tpu"
one() { tag=$1; shift; "$@" > $O/$tag.out 2> $O/$tag.err; echo "== $tag rc=$?"; python3 $S/show.py $O/$tag.out; grep -E "^\[compared\].*FAILED" $O/$tag.err; }
for s in 91 92 93; do
  one lrc.$s $C --workload control.seal.single.lrc --seed $s --seconds 5 --trace 0
done
for f in unchanged half_batch half_batch_early altered_seal; do
  one $f $R --wrapper benchmark.tests.faulty_volume:$f --workload seal.single --seed 94 --seconds 5 --trace 0
done
# and the storm traffic that is not a cell yet, as a trial of its steadiness
for s in 51 52 53 54; do
  one storm8.$s $C --workload exp.seal.storm8 --seed $s --seconds 10 --trace 0; grep -E "^\[(window\] MB|host)" $O/storm8.$s.err | cut -c1-500
done
