# seal.single as committed (one call every 2.5 s, ~4 GiB written a run), then
# with glibc's mmap threshold held fixed for every process, alternating, then
# one traced run: does the allocator make the run-to-run levels?  ~28 GiB.
#   chiprun --timeout 1500 -- bash benchmark/tests/scripts/probe_seal.sh
S=benchmark/tests/scripts; O=chiprun_out/probe_seal; mkdir -p $O
run() { tag=$1; s=$2; t=$3; shift 3
  "$@" python3 benchmark/run.py --workload seal.single --seed $s --seconds 10 --trace $t > $O/$tag.$s.out 2> $O/$tag.$s.err; echo "== seal.single $tag seed $s trace $t rc=$?"
  python3 $S/show.py $O/$tag.$s.out; grep -E "^\[(fill|window|host|check|trace)" $O/$tag.$s.err | cut -c1-900; }
for s in 71 72 73; do
  run base $s 0 env
  run malloc $s 0 env MALLOC_MMAP_THRESHOLD_=33554432 MALLOC_TRIM_THRESHOLD_=1073741824 MALLOC_TOP_PAD_=268435456
done
run base 74 1 env
