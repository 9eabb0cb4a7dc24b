# One set of a cell's measurements, in one chip call (a machine takes ~33 GiB
# of writes before it stalls, a seal run writes ~4 GiB: so a set to a call):
#   chiprun --timeout 1800 -- bash benchmark/tests/scripts/measure_sets.sh <cell> <A|B> <traced-seed>...
# 6 runs on the sets' seeds, then one traced run per further seed.  With both
# sets back under chiprun_out/<cell>/: python3 benchmark/tests/scripts/spread.py <cell>
cell=$1; set=$2; shift 2
S=benchmark/tests/scripts; O=chiprun_out/$cell; mkdir -p $O
for s in 2147483659 1234567891 987654321 3141592653 2718281828 1618033989; do
  python3 benchmark/run.py --workload $cell --seed $s --seconds 10 --trace 0 > $O/$set.$s.out 2> $O/$set.$s.err; echo "== $cell set $set seed $s rc=$?"
  python3 $S/show.py $O/$set.$s.out; grep -E "^\[(host|window\] MB)" $O/$set.$s.err | cut -c1-400
done
for s in "$@"; do
  python3 benchmark/run.py --workload $cell --seed $s --seconds 10 --trace 1 > $O/traced.$s.out 2> $O/traced.$s.err; echo "== $cell traced $s rc=$?"
  python3 $S/show.py $O/traced.$s.out; grep -E "^\[(trace|window|host)\]" $O/traced.$s.err | cut -c1-700
done
