# The committed files are enough: run from an unpacked `git archive $(git write-tree)` in .bench_tree/
#   chiprun --timeout 1800 -- bash benchmark/tests/scripts/proof_from_archive.sh
cd .bench_tree || exit 9
test -d .git && echo "HAS .git"
S=benchmark/tests/scripts; O=../chiprun_out/proof2; mkdir -p $O
i=0
for spec in "seal.single 5100000001 1" "seal.single 5100000002 0" "reads.degraded1 5100000003 0" "reads.degraded1 5100000004 1"; do set -- $spec; i=$((i+1))
  python3 benchmark/run.py --workload $1 --seed $2 --seconds 10 --trace $3 > $O/$i.out 2> $O/$i.err; echo "== $spec rc=$?"; python3 $S/show.py $O/$i.out; tail -4 $O/$i.err | cut -c1-200; grep -E "^\[(trace|window\] MB)" $O/$i.err | cut -c1-300
done
tail -1 $O/1.out | cut -c1-3000
# a directory with only BENCHMARK.json and the paths: no result, non-zero
mkdir -p ../.smoke_tree/onlybench && cp -r BENCHMARK.json benchmark ../.smoke_tree/onlybench/ && (cd ../.smoke_tree/onlybench && python3 benchmark/run.py --workload seal.single --seed 1 --seconds 1 --trace 0 > out.txt 2> err.txt; echo "bare rc=$? stdout_bytes=$(wc -c < out.txt)"; tail -3 err.txt)
ls -a . | head -40
