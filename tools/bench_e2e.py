"""End-to-end ec.encode / ec.rebuild benchmark on a real >=1GB volume.

BASELINE configs 1 and 3: build a volume of needles, measure disk->shards
encode MB/s (per CPU tier and via the TPU streaming pipeline) and rebuild
latency for 1..4 lost shards. Results go to PERF.md.

One process: the --tpu pass runs in this interpreter (no child), so it
is the only one that touches the chip.

Usage: python tools/bench_e2e.py [size_gb] [--tpu]
"""
import os, shutil, sys, time, tempfile
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from seaweedfs_tpu.storage.volume import Volume
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.erasure_coding import encoder, layout
from seaweedfs_tpu.native import rs_native as rn


def build_volume(d: str, target_bytes: int) -> str:
    v = Volume(d, "", 7)
    rng = np.random.default_rng(0)
    payload = rng.integers(0, 256, 1 << 20, dtype=np.uint8).tobytes()  # 1MB
    key = 1
    t0 = time.perf_counter()
    while v.content_size() < target_bytes:
        n = Needle(id=key, cookie=0x1234, data=payload)
        v.write_needle(n)
        key += 1
    v.close()
    dt = time.perf_counter() - t0
    base = os.path.join(d, "7")
    sz = os.path.getsize(base + ".dat")
    print(f"built volume: {sz/1e9:.2f} GB, {key-1} needles, "
          f"{sz/dt/1e6:.0f} MB/s append")
    return base


def _warm(base: str) -> None:
    # page-cache warm the .dat so tier ordering doesn't bias the numbers
    with open(base + ".dat", "rb") as f:
        while f.read(1 << 24):
            pass


def bench_encode_cpu(base: str, tier: int, name: str) -> None:
    for i in range(14):
        p = base + layout.shard_ext(i)
        if os.path.exists(p):
            os.remove(p)
    _warm(base)
    rn.force_impl(tier)
    t0 = time.perf_counter()
    encoder.write_ec_files(base)
    dt = time.perf_counter() - t0
    sz = os.path.getsize(base + ".dat")
    print(f"ec.encode disk->shards [{name:>6s} {rn.impl_name():>12s}]: "
          f"{sz/dt/1e6:.0f} MB/s ({dt:.1f}s)")
    rn.force_impl(0)


def bench_encode_tpu(base: str) -> None:
    from seaweedfs_tpu.parallel import streaming
    for i in range(14):
        p = base + layout.shard_ext(i)
        if os.path.exists(p):
            os.remove(p)
    _warm(base)
    t0 = time.perf_counter()
    streaming.pipelined_encode_file(base)
    dt = time.perf_counter() - t0
    sz = os.path.getsize(base + ".dat")
    import jax
    print(f"ec.encode disk->shards [stream {jax.default_backend():>12s}]: "
          f"{sz/dt/1e6:.0f} MB/s ({dt:.1f}s)")


def bench_rebuild(base: str) -> None:
    shard_size = os.path.getsize(base + layout.shard_ext(0))
    # warm all shards
    for i in range(14):
        with open(base + layout.shard_ext(i), "rb") as f:
            while f.read(1 << 24):
                pass
    for lost in ([0], [0, 5], [0, 5, 11], [0, 5, 11, 13]):
        for i in lost:
            os.remove(base + layout.shard_ext(i))
        t0 = time.perf_counter()
        got = encoder.rebuild_ec_files(base)
        dt = time.perf_counter() - t0
        assert sorted(got) == sorted(lost)
        print(f"ec.rebuild {len(lost)} lost shards: {dt:.1f}s "
              f"({len(lost)*shard_size/dt/1e6:.0f} MB/s rebuilt)")


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    size_gb = float(args[0]) if args else 1.0
    d = tempfile.mkdtemp(prefix="ecbench")
    try:
        base = build_volume(d, int(size_gb * 1e9))
        bench_encode_cpu(base, rn.IMPL_AVX2, "warmup")
        bench_encode_cpu(base, rn.IMPL_GFNI, "gfni")
        bench_encode_cpu(base, rn.IMPL_AVX2, "avx2")
        bench_encode_cpu(base, rn.IMPL_SCALAR, "scalar")
        bench_rebuild(base)
        if "--tpu" in sys.argv:
            bench_encode_tpu(base)
    finally:
        shutil.rmtree(d, ignore_errors=True)


if __name__ == "__main__":
    main()
