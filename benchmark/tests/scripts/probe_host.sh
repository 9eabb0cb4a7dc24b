# What the chip machine's host is, and what moves a seal's rate from run to
# run (PR 24, second session):
#   chiprun --timeout 1800 -- bash benchmark/tests/scripts/probe_host.sh
# 1. file systems and memory; 2. the 1 GiB volume of ISSUE 24, sealed back to
# back, beside a sampler of /proc/meminfo's Dirty and Writeback: three runs of
# ~14 GiB written each.  (As first run this script went on to seal.single and
# the read cell; the tool ended the call during the fourth run, at 46.8 GiB
# written.  Those parts are probe_seal.sh and probe_reads.sh now, a call each.)
S=benchmark/tests/scripts; O=chiprun_out/probe; mkdir -p $O
R="python3 benchmark/tests/rehearse.py --require-platform tpu"
echo "TMPDIR=$TMPDIR HOME=$HOME JAXCACHE=$JAX_COMPILATION_CACHE_DIR"; nproc
df -hT / /tmp /dev/shm 2>&1 | cat; mount | head -12
grep -E "MemTotal|MemFree|Cached|Dirty|Writeback|AnonHuge|Hugepagesize" /proc/meminfo
cat /sys/kernel/mm/transparent_hugepage/enabled 2>&1; ls /proc/self/ | tr '\n' ' '; echo
lines() { grep -E "^\[(fill|window|host|check)" $1 | cut -c1-1400; }
for s in 61 62 63; do
  ( while true; do echo "$(date +%s.%N) $(grep -E 'Dirty|Writeback:' /proc/meminfo | tr -s ' ' | tr '\n' ' ')"; sleep 0.25; done ) > $O/meminfo.$s.txt & sampler=$!
  echo "unix time at start $(date +%s.%N)"
  $R --workload exp.seal.single1g --seed $s --seconds 10 --trace 0 > $O/g1.$s.out 2> $O/g1.$s.err; echo "== exp.seal.single1g seed $s rc=$?"
  kill $sampler; python3 $S/show.py $O/g1.$s.out; lines $O/g1.$s.err
  sort -k3 -n -r $O/meminfo.$s.txt | head -3
done
