"""Benchmark: RS(10,4) ec.encode throughput on the accelerator vs CPU.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "MB/s", "vs_baseline": N}

One process per chip: this (parent) process never imports jax.  The
device measurement runs ONCE, first, in a `--tpu-probe` child that holds
the chip only while it runs; the parent then measures the CPU
denominator and the host-side secondary benches.  If the child fails —
no accelerator, a compile error, a timeout — bench.py prints the reason
on stderr and exits non-zero: no CPU timing is ever printed under the
device metric's name.

value       = TPU (default JAX backend) GF(256) parity-kernel throughput in
              MB/s of input shard data, device-resident steady state with
              the parity MATERIALIZED to HBM every step (the parity rows
              are the fori_loop carry). The input is mutated every step so
              no result can be cached, and completion is forced by
              fetching an XOR checksum.
vs_baseline = value / CPU-coder throughput measured in the same process on
              one core, using the BEST available native SIMD tier (GFNI on
              this machine — stronger than the AVX2 PSHUFB method the
              reference's pinned klauspost/reedsolomon v1.10 uses, so the
              ratio is conservative; per-tier numbers are in PERF.md).
              Reference anchor: weed/storage/erasure_coding/ec_encoder.go:199.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

# Budget for the device child: backend start-up, one compile and the
# timed loops.
DEVICE_CHILD_TIMEOUT = 600


def bench_cpu(batch_bytes: int = 256 * 1024, n_batches: int = 32,
              iters: int = 7) -> float:
    """One-core CPU encode in the reference's own shape: 256KB per-shard
    batches (ec_encoder.go:162-192 encodes 10x256KB buffer batches), but
    cycling through n_batches distinct batches so the data streams through
    the cache hierarchy like a real volume encode instead of re-hitting
    one L2-resident batch.

    The denominator is the MEDIAN of `iters` timed sweeps (round-3
    verdict weak #7: 3 averaged sweeps drifted vs_baseline +-15%
    between identical rounds; the median of 7 pins it)."""
    from seaweedfs_tpu.models.coder import RSScheme, make_coder
    coder = make_coder("cpu", RSScheme(10, 4))
    rng = np.random.default_rng(0)
    batches = [rng.integers(0, 256, (10, batch_bytes), dtype=np.uint8)
               for _ in range(n_batches)]
    coder.encode_array(batches[0])  # warm
    sweeps = []
    for _ in range(iters):
        t0 = time.perf_counter()
        for b in batches:
            coder.encode_array(b)
        sweeps.append(time.perf_counter() - t0)
    dt = sorted(sweeps)[len(sweeps) // 2]
    return n_batches * 10 * batch_bytes / dt / 1e6


def bench_tpu(n_bytes_per_shard: int = 32 * 1024 * 1024, outer: int = 5,
              inner: int = 64) -> float:
    """Sustained device throughput of the production kernel (flat-row
    Horner, see ops/rs_jax.py): `inner` encodes inside one compiled
    program; the parity rows are the loop carry so every step writes all
    four to HBM; the input is XOR-mutated per step so nothing can be
    cached/CSE'd; one checksum fetch synchronizes (and stays in the
    denominator).  Runs only in the --tpu-probe child: jax is imported
    HERE and nowhere else in this file, so the parent never holds the
    chip its child needs."""
    import jax
    import jax.numpy as jnp

    from seaweedfs_tpu.ops import gf256
    from seaweedfs_tpu.ops.rs_jax import _apply_matrix_rows, _mat_to_tuple

    pm = _mat_to_tuple(gf256.parity_matrix(10, 4))
    rng = np.random.default_rng(1)
    nw = n_bytes_per_shard // 4
    rows = tuple(
        jax.device_put(rng.integers(0, 2**32, (nw,),
                                    dtype=np.uint64).astype(np.uint32))
        for _ in range(10))

    @jax.jit
    def loop(rows, i0):
        def body(r, carry):
            del carry
            mutated = tuple(w ^ (i0 + r) for w in rows)
            return tuple(_apply_matrix_rows(mutated, pm))
        init = tuple(jnp.zeros((nw,), jnp.uint32) for _ in range(4))
        parity = jax.lax.fori_loop(0, inner, body, init)
        acc = jnp.uint32(0)
        for p in parity:
            acc = acc ^ jnp.bitwise_xor.reduce(p)
        return acc

    jax.device_get(loop(rows, jnp.uint32(1)))  # compile + warm
    times = []
    for i in range(outer):
        t0 = time.perf_counter()
        jax.device_get(loop(rows, jnp.uint32(i * inner + 2)))
        times.append(time.perf_counter() - t0)
    times.sort()
    dt = times[len(times) // 2]  # median, includes ONE fixed sync
    return inner * 10 * n_bytes_per_shard / dt / 1e6


def bench_volume_encode(size_mb: int = 256) -> dict:
    """End-to-end ec.encode of a synthetic volume: .dat -> 14 shard files
    on disk, serial walk vs the staged pipeline (overlapped read/encode/
    write + multi-core CPU sharding). Secondary metrics — the headline
    stays the device kernel number; this one captures what a volume
    server actually experiences, I/O included.

    SEAWEEDFS_TPU_BENCH_EC_MB overrides the volume size."""
    import tempfile

    from seaweedfs_tpu.models.coder import make_coder
    from seaweedfs_tpu.storage.erasure_coding import encoder as ecenc
    from seaweedfs_tpu.storage.erasure_coding import layout

    size_mb = int(os.environ.get("SEAWEEDFS_TPU_BENCH_EC_MB", size_mb))
    size = size_mb * 1024 * 1024
    rng = np.random.default_rng(7)
    with tempfile.TemporaryDirectory() as d:
        base = os.path.join(d, "bench")
        with open(base + ".dat", "wb") as f:
            left = size
            while left:
                n = min(1 << 24, left)
                f.write(rng.integers(0, 256, n, dtype=np.uint8).tobytes())
                left -= n

        def clean():
            for i in range(layout.TOTAL_SHARDS_COUNT):
                os.remove(base + layout.shard_ext(i))

        t0 = time.perf_counter()
        ecenc.write_ec_files(base, make_coder("cpu"))
        serial_s = time.perf_counter() - t0
        clean()
        stats: dict = {}
        t0 = time.perf_counter()
        ecenc.write_ec_files(base, make_coder("cpu-mt"), pipelined=True,
                             stats=stats)
        pipe_s = time.perf_counter() - t0
        clean()
    return {
        "ec_volume_encode_mbps": round(size / pipe_s / 1e6, 1),
        "ec_volume_encode_serial_mbps": round(size / serial_s / 1e6, 1),
        "ec_volume_encode_speedup": round(serial_s / pipe_s, 2),
        "ec_volume_encode_mb": size_mb,
        "ec_volume_encode_stages_s": {
            k: round(stats.get(k, 0.0), 3)
            for k in ("read_s", "encode_s", "write_s", "wall_s")},
    }


def bench_scrub(size_mb: int = 64) -> dict:
    """Scrub read path throughput with the rate limiter OFF: build a
    synthetic volume of 1MB needles, then time one full Scrubber pass
    (superblock walk + per-needle CRC32-C re-verify). This is the
    integrity subsystem's raw ceiling; production runs throttled.

    SEAWEEDFS_TPU_BENCH_SCRUB_MB overrides the volume size."""
    import tempfile

    from seaweedfs_tpu.scrub import Scrubber
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.store import Store

    size_mb = int(os.environ.get("SEAWEEDFS_TPU_BENCH_SCRUB_MB", size_mb))
    rng = np.random.default_rng(7)
    with tempfile.TemporaryDirectory() as d:
        store = Store([d])
        store.add_volume(1)
        for i in range(size_mb):
            data = rng.integers(0, 256, 1024 * 1024,
                                dtype=np.uint8).tobytes()
            store.write_volume_needle(
                1, Needle(id=i + 1, cookie=1, data=data))
        scrubber = Scrubber(store, rate_bytes_per_sec=0)
        t0 = time.perf_counter()
        out = scrubber.run_once()
        dt = time.perf_counter() - t0
        store.close()
    if out["corruptions"]:
        raise RuntimeError(f"scrub bench found phantom corruption: "
                           f"{out['corruptions'][:3]}")
    return {"scrub_mbps": round(out["bytes"] / dt / 1e6, 1),
            "scrub_mb": size_mb}


def bench_telemetry_overhead(n_reads: int = 600,
                             concurrency: int = 8) -> dict:
    """Round-13 telemetry-plane cost: the same single-volume read
    sweep with the RED histogram + hot-key sketch recording live
    (shipped default) vs surgically disabled (http.red = None and a
    no-op sketch), interleaved ON/OFF/ON/OFF so CPU-frequency drift
    hits both arms equally. The per-request work is one bisect + one
    dict update under a lock (histogram) and one sketch offer — the
    claim in PERF.md round 13 is "within noise", so the paired sweeps
    are the evidence."""
    import concurrent.futures
    import tempfile

    from seaweedfs_tpu.client import operation
    from seaweedfs_tpu.client.wdclient import MasterClient
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer

    with tempfile.TemporaryDirectory() as d:
        master = MasterServer(volume_size_limit_mb=64)
        master.start()
        vs = VolumeServer([d], master.url)
        vs.start()
        time.sleep(0.3)
        mc = MasterClient(master.url)
        try:
            fids = [operation.upload_data(
                mc, b"\xa5" * 4096, name=f"t{i}").fid
                for i in range(32)]

            def read_one(i):
                operation.read_data(mc, fids[i % len(fids)])

            def sweep() -> float:
                t0 = time.perf_counter()
                with concurrent.futures.ThreadPoolExecutor(
                        concurrency) as ex:
                    list(ex.map(read_one, range(n_reads)))
                return n_reads / (time.perf_counter() - t0)

            red_on, hot_on = vs.http.red, vs.hotkeys
            hot_off = type(hot_on)(dims=())  # records nothing

            def set_plane(on: bool) -> None:
                vs.http.red = red_on if on else None
                vs.hotkeys = hot_on if on else hot_off

            sweep()  # warm connections + page cache
            on_rps, off_rps = [], []
            for _ in range(2):
                set_plane(True)
                on_rps.append(sweep())
                set_plane(False)
                off_rps.append(sweep())
            set_plane(True)
        finally:
            mc.stop()
            vs.stop()
            master.stop()
    on, off = max(on_rps), max(off_rps)
    return {
        "telemetry_on_rps": round(on, 1),
        "telemetry_off_rps": round(off, 1),
        "telemetry_overhead_pct": round((off - on) / off * 100, 2)
        if off else 0.0,
    }


def _free_port() -> int:
    """Reserve a port number for a server created behind a proxy: the
    proxy must know the target port before HttpServer binds it."""
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _p99_ms(samples_s: list) -> float:
    xs = sorted(samples_s)
    return round(xs[min(len(xs) - 1, int(len(xs) * 0.99))] * 1000, 1)


def _stage_breakdown(tracers, t_mark: float) -> dict:
    """Per-stage latency from the in-process flight recorders: spans
    started after `t_mark` (one fully-sampled untimed op run after the
    timed loop, so instrumentation cost never taints the headline
    numbers), aggregated by span name across every node's recorder.
    Per-request identifiers (fid, host:port) are collapsed so the 16
    chunk POSTs of one PUT land in a single stage row."""
    import re
    fid = re.compile(r"/\d+,[0-9a-f]+")
    host = re.compile(r"http://[^/ ]+")
    stages: dict = {}
    for tr in tracers:
        for s in tr.snapshot(limit=4096)["spans"]:
            if s["start"] < t_mark:
                continue
            name = host.sub("http://<node>", fid.sub("/<fid>", s["name"]))
            st = stages.setdefault(name,
                                   {"count": 0, "total_ms": 0.0})
            st["count"] += 1
            st["total_ms"] += s["duration_ms"]
    return {name: {"count": st["count"],
                   "total_ms": round(st["total_ms"], 2)}
            for name, st in sorted(stages.items())}


def bench_degraded_read(n_reads: int = 30,
                        straggler_ms: float = 200.0) -> dict:
    """EC degraded-read tail latency under one injected straggler.

    In-process cluster: vs1 holds 13 of 14 shards of an EC needle; the
    one shard the needle's data lives in exists only on vs2 (reached
    through a netchaos proxy adding `straggler_ms` latency) and vs3
    (fast). Every read of the needle on vs1 therefore takes one remote
    shard hop. Measured twice over the same layout:

      baseline  resilient_reads=False — the pre-resilience serial walk
                in master-lookup order, which hits the straggler first
                on every read (~straggler_ms tail);
      hedged    resilient_reads=True — breaker-ranked candidates +
                adaptive hedging cut the tail to the hedge delay once,
                then to the fast peer's latency.

    A third mode then re-enables the hot-needle cache (it is held out
    of the first two — a repeat read of one needle would otherwise be
    a memory hit and hide the network path being compared): one cold
    read warms the cache with the reconstructed record, and warm reads
    measure the cache-hit path end to end, asserting bit-identity
    against the original bytes on every sample.

    SEAWEEDFS_TPU_BENCH_DEGRADED_READS overrides n_reads."""
    import tempfile

    from seaweedfs_tpu.client import operation
    from seaweedfs_tpu.client.wdclient import MasterClient
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.shell.commands import ShellContext
    from seaweedfs_tpu.storage.file_id import parse_needle_id_cookie
    from seaweedfs_tpu.utils.httpd import http_call, http_json
    from tools.netchaos import ChaosProxy

    n_reads = int(os.environ.get("SEAWEEDFS_TPU_BENCH_DEGRADED_READS",
                                 n_reads))
    rng = np.random.default_rng(11)
    with tempfile.TemporaryDirectory() as d:
        master = MasterServer(volume_size_limit_mb=64)
        master.start()
        vs1 = VolumeServer([os.path.join(d, "v1")], master.url)
        vs1.start()

        # one needle big enough to span real shard rows
        data = rng.integers(0, 256, 600 * 1024, dtype=np.uint8).tobytes()
        mc = MasterClient(master.url, cache_ttl=0.0)
        res = operation.upload_data(mc, data)
        fid = res.fid
        vid = int(fid.split(",")[0])
        nid, _cookie = parse_needle_id_cookie(fid.split(",", 1)[1])

        # encode while vs1 is the only node: all 14 shards stay local
        sh = ShellContext(master.url, use_grpc=False)
        sh.ec_encode(vid=vid)
        ev = vs1.store.find_ec_volume(vid)
        intervals, _off, _size = ev.locate_needle(nid)
        sids = sorted({iv.to_shard_id_and_offset()[0]
                       for iv in intervals})
        sid = sids[0]  # the data shard vs1 will lose

        # vs2 joins behind a straggler proxy (advertised = proxy addr);
        # vs3 joins fast; both get the shard, then vs1 drops it
        vs2_port = _free_port()
        proxy = ChaosProxy("127.0.0.1", vs2_port,
                           latency_s=straggler_ms / 1000.0).start()
        vs2 = VolumeServer([os.path.join(d, "v2")], master.url,
                           port=vs2_port, advertise=proxy.url)
        vs2.start()
        vs3 = VolumeServer([os.path.join(d, "v3")], master.url)
        vs3.start()
        for vs in (vs2, vs3):  # setup bypasses the proxy: direct addr
            direct = f"{vs.http.host}:{vs.http.port}"
            http_json("POST", f"http://{direct}/admin/ec/copy",
                      {"volume_id": vid, "shard_ids": [sid],
                       "source_data_node": f"{vs1.http.host}:"
                                           f"{vs1.http.port}"})
            http_json("POST", f"http://{direct}/admin/ec/mount",
                      {"volume_id": vid, "shard_ids": [sid]})
        http_json("POST", f"http://{vs1.url}/admin/ec/unmount",
                  {"volume_id": vid, "shard_ids": [sid]})
        http_json("POST", f"http://{vs1.url}/admin/ec/delete_shards",
                  {"volume_id": vid, "shard_ids": [sid]})
        time.sleep(0.2)  # let heartbeats register the new holders

        # hold the hot-needle cache out of the baseline/hedged modes:
        # they compare network paths, not cache hits
        needle_cache = vs1.store.needle_cache
        vs1.store.needle_cache = None

        def measure() -> list:
            # fresh health + location state per mode: the comparison
            # must not inherit the other mode's learned rankings
            # (metrics=None: a throwaway health table needs no series)
            vs1.peer_health = type(vs1.peer_health)()
            vs1.store.peer_health = vs1.peer_health
            vs1._shard_loc_cache.clear()
            samples = []
            for _ in range(n_reads):
                t0 = time.perf_counter()
                status, body, _hdr = http_call(
                    "GET", f"http://{vs1.url}/{fid}", timeout=30)
                samples.append(time.perf_counter() - t0)
                if status != 200 or body != data:
                    raise RuntimeError(
                        f"degraded read failed: HTTP {status}")
            return samples

        try:
            vs1.resilient_reads = False
            vs1.store.resilient_reads = False
            base = measure()
            vs1.resilient_reads = True
            vs1.store.resilient_reads = True
            hedged = measure()
            # where the degraded-read time goes: one fully-sampled
            # extra read, broken down by span across all three nodes
            for node in (vs1, vs2, vs3):
                node.tracer.sample_rate = 1.0
            t_mark = time.time()
            http_call("GET", f"http://{vs1.url}/{fid}", timeout=30)
            breakdown = _stage_breakdown(
                (vs1.tracer, vs2.tracer, vs3.tracer), t_mark)
            for node in (vs1, vs2, vs3):
                node.tracer.sample_rate = 0.01
            # warm-cache mode: the reconstructed record is admitted on
            # the first (cold) read, then every read is a memory hit —
            # no shard hop, no decode. measure() keeps asserting
            # body == data, so bit-identity of cached reads is checked
            # on every sample.
            vs1.store.needle_cache = needle_cache
            http_call("GET", f"http://{vs1.url}/{fid}", timeout=30)
            warm = measure()
            cst = needle_cache.stats() if needle_cache else {}
            if needle_cache and cst["hits"] < n_reads:
                raise RuntimeError(
                    f"warm phase expected cache hits, got {cst}")
        finally:
            mc.stop()
            for vs in (vs3, vs2, vs1):
                vs.stop()
            proxy.stop()
            master.stop()
    base_p99, hedged_p99 = _p99_ms(base), _p99_ms(hedged)
    warm_p99 = _p99_ms(warm)
    return {
        "degraded_read_p99_ms": hedged_p99,
        "degraded_read_nohedge_p99_ms": base_p99,
        "degraded_read_speedup": round(base_p99 / max(hedged_p99, 0.001),
                                       2),
        "degraded_read_straggler_ms": straggler_ms,
        "degraded_read_n": n_reads,
        "degraded_read_stage_breakdown_ms": breakdown,
        "hot_read_warm_p99_ms": warm_p99,
        "hot_read_speedup_vs_hedged": round(
            hedged_p99 / max(warm_p99, 0.001), 2),
    }


def bench_conn_hold(n_conns: int = 10000, n_probe: int = 200,
                    baseline_conns: int = 100) -> dict:
    """Edge connection-hold sweep: N idle keep-alive connections parked
    on the selector while a probe connection keeps issuing requests.

    Each connection sends one ping (the serving core parks a socket
    after its first served request) and then sits idle. Reported:

      thread growth   must stay ~(workers + selector), NOT one thread
                      per connection — that is the point of the
                      selector core;
      RSS growth      per-connection memory, kernel buffers included;
      probe p99       measured twice IN-RUN, at `baseline_conns` and at
                      `n_conns` open sockets — idle parked connections
                      must not tax the served path.

    SEAWEEDFS_TPU_BENCH_CONNS overrides n_conns."""
    import resource
    import threading

    from seaweedfs_tpu.utils.httpd import (HttpServer, RawHttpConnection,
                                           Response)

    n_conns = int(os.environ.get("SEAWEEDFS_TPU_BENCH_CONNS", n_conns))
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    want = n_conns * 2 + 512  # client + server end of every socket
    if soft < want:
        try:
            resource.setrlimit(resource.RLIMIT_NOFILE,
                               (min(want, hard), hard))
            soft = resource.getrlimit(resource.RLIMIT_NOFILE)[0]
        except (ValueError, OSError):
            pass
        if soft < want:  # fd budget caps the sweep, scale it down
            n_conns = max(baseline_conns + 16, (soft - 512) // 2)

    workers = 8
    srv = HttpServer(workers=workers, queue_depth=256)
    srv.add("GET", "/ping", lambda req: Response({"ok": True}))
    srv.start()

    def rss_kb() -> int:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1])
        return 0

    def open_idle(n: int, bag: list) -> None:
        for _ in range(n):
            c = RawHttpConnection(f"127.0.0.1:{srv.port}", 10.0)
            c.send_request("GET", "/ping", None, None)
            status, _b, _h, _close = c.read_response("GET")
            if status != 200:
                raise RuntimeError(f"conn setup ping: HTTP {status}")
            bag.append(c)

    def probe(n: int) -> list:
        c = RawHttpConnection(f"127.0.0.1:{srv.port}", 10.0)
        samples = []
        for _ in range(n):
            t0 = time.perf_counter()
            c.send_request("GET", "/ping", None, None)
            status, _b, _h, _close = c.read_response("GET")
            samples.append(time.perf_counter() - t0)
            if status != 200:
                raise RuntimeError(f"probe: HTTP {status}")
        c.close()
        return samples

    conns: list = []
    try:
        threads0 = threading.active_count()
        rss0 = rss_kb()
        open_idle(baseline_conns, conns)
        p_base = probe(n_probe)
        open_idle(n_conns - len(conns), conns)
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:  # let the last park land
            if srv.conn_stats()["parked"] >= n_conns:
                break
            time.sleep(0.05)
        p_full = probe(n_probe)
        st = srv.conn_stats()
        thread_growth = threading.active_count() - threads0
        rss_growth_kb = rss_kb() - rss0
    finally:
        for c in conns:
            try:
                c.close()
            except OSError:
                pass
        srv.stop()
    base_p99, full_p99 = _p99_ms(p_base), _p99_ms(p_full)
    return {
        "conn_hold_n": n_conns,
        "conn_hold_parked": st["parked"],
        "conn_hold_thread_growth": thread_growth,
        "conn_hold_workers": workers,
        "conn_hold_rss_growth_kb": rss_growth_kb,
        "conn_hold_kb_per_conn": round(
            rss_growth_kb / max(n_conns, 1), 2),
        "conn_hold_probe_p99_ms_100": base_p99,
        "conn_hold_probe_p99_ms_full": full_p99,
        "conn_hold_probe_slowdown": round(
            full_p99 / max(base_p99, 0.001), 2),
    }


def bench_lrc_repair(size_mb: int = 32, iters: int = 3) -> dict:
    """Single-shard repair cost, LRC(10,2,2) vs RS(10,4), on the same
    payload: bytes read from surviving shards per rebuilt MB, and
    repair wall time.  The LRC plan reads the 5 surviving group
    members where RS reads k=10 columns, so the headline ratios are
    ~0.5x bytes-read-per-rebuilt-MB and ~2x wall.

    Bit-identity is asserted IN-RUN twice: the rebuilt shard against
    the originally encoded one (both families), and the LRC encode
    against a pure-Python GF(256) double-loop reference on a sample —
    a fast-but-wrong coder cannot post a number.

    SEAWEEDFS_TPU_BENCH_LRC_MB overrides the volume size."""
    import tempfile

    from seaweedfs_tpu.models.coder import make_coder
    from seaweedfs_tpu.ops import gf256
    from seaweedfs_tpu.ops.lrc import LrcCoder
    from seaweedfs_tpu.storage.erasure_coding import encoder as ecenc
    from seaweedfs_tpu.storage.erasure_coding import layout

    size_mb = int(os.environ.get("SEAWEEDFS_TPU_BENCH_LRC_MB", size_mb))
    size = size_mb * 1024 * 1024
    lost_sid = 2  # a group-0 data shard: the LRC headline case

    # in-run reference check: LrcCoder's batched GF matmul encode must
    # match the O(m*k*n) scalar double loop on a random sample
    lrc = LrcCoder()
    k = lrc.scheme.data_shards
    rng = np.random.default_rng(13)
    sample = rng.integers(0, 256, size=(k, 256), dtype=np.uint8)
    fast = lrc.encode_array(sample)
    gen = lrc._parity
    for r in range(gen.shape[0]):
        row = bytearray(sample.shape[1])
        for c in range(k):
            coef = int(gen[r, c])
            for j in range(sample.shape[1]):
                row[j] ^= gf256.gf_mul(coef, int(sample[c, j]))
        if bytes(fast[r]) != bytes(row):
            raise RuntimeError(
                f"LRC encode diverges from the scalar GF reference "
                f"at parity row {r}")

    rows = {}
    with tempfile.TemporaryDirectory() as d:
        for fam, name in (("rs", "cpu-mt"), ("lrc", "lrc-mt")):
            coder = make_coder(name)
            base = os.path.join(d, fam)
            rng2 = np.random.default_rng(7)
            with open(base + ".dat", "wb") as f:
                left = size
                while left:
                    n = min(1 << 24, left)
                    f.write(rng2.integers(0, 256, n,
                                          dtype=np.uint8).tobytes())
                    left -= n
            ecenc.write_ec_files(base, coder)
            shard_path = base + layout.shard_ext(lost_sid)
            with open(shard_path, "rb") as f:
                golden = f.read()
            walls = []
            stats: dict = {}
            for _ in range(iters):
                os.remove(shard_path)
                stats = {}
                t0 = time.perf_counter()
                ecenc.rebuild_ec_files(base, coder, stats=stats)
                walls.append(time.perf_counter() - t0)
                with open(shard_path, "rb") as f:
                    if f.read() != golden:
                        raise RuntimeError(
                            f"{fam} rebuild of shard {lost_sid} is not "
                            "bit-identical to the encoded shard")
            read_b = stats.get("read_bytes", 0)
            rebuilt_b = stats.get("rebuilt_bytes", 0)
            rows[fam] = {
                "sources": len(stats.get("sources") or []),
                "read_mb": round(read_b / 1e6, 2),
                "read_per_rebuilt_mb": round(read_b / max(1, rebuilt_b),
                                             3),
                "wall_s": round(sorted(walls)[len(walls) // 2], 4),
            }
    return {
        "lrc_repair_mb": size_mb,
        "lrc_repair_lost_sid": lost_sid,
        "lrc_repair_rs": rows["rs"],
        "lrc_repair_lrc": rows["lrc"],
        "lrc_repair_read_ratio": round(
            rows["lrc"]["read_per_rebuilt_mb"]
            / rows["rs"]["read_per_rebuilt_mb"], 3),
        "lrc_repair_wall_speedup": round(
            rows["rs"]["wall_s"] / max(1e-9, rows["lrc"]["wall_s"]), 2),
        "lrc_repair_bit_identical": True,  # raises above otherwise
    }


def bench_repair_network(n_files: int = 6) -> dict:
    """Rebuilder network ingress per MiB rebuilt: partial-column chain
    vs legacy copy+rebuild, same spread layout.

    In-process cluster: vs1 encodes (keeps shards 0-2 and 11-13 plus
    the .ecx), shards 3-6 move to vs2 and 7-10 to vs3. Losing one shard
    then makes vs1 the rebuilder with 6-7 local columns and the rest
    remote. Partial mode runs FIRST (it stages nothing); legacy mode
    runs second on a fresh loss — its copy staging litters the
    rebuilder with full shard files, which would let a later partial
    pass read 'remote' columns locally and fake a ~0 ingress.

    Reported per-MiB ingress counts bytes RECEIVED at the rebuilder:
    ~1 shard-width for the pre-reduced chain vs ~len(need) widths for
    the staging loop (k = 10 on a fully spread layout). Both modes'
    rebuilt shards are verified bit-identical to the originals."""
    import tempfile

    from seaweedfs_tpu.client import operation
    from seaweedfs_tpu.client.wdclient import MasterClient
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.shell.commands import ShellContext
    from seaweedfs_tpu.storage.erasure_coding import layout
    from seaweedfs_tpu.utils.httpd import http_json

    mb = 1024 * 1024
    rng = np.random.default_rng(23)
    with tempfile.TemporaryDirectory() as d:
        master = MasterServer(volume_size_limit_mb=64)
        master.start()
        vs1 = VolumeServer([os.path.join(d, "v1")], master.url)
        vs1.start()
        mc = MasterClient(master.url, cache_ttl=0.0)
        res = operation.upload_data(mc, b"seed")
        vid = int(res.fid.split(",")[0])
        for _ in range(n_files):
            a = mc.assign()
            data = rng.integers(0, 256, int(rng.integers(100, 200)) *
                                1024, dtype=np.uint8).tobytes()
            operation.upload_to(a["fid"], a["url"], data)

        # encode while vs1 is the only node: all 14 shards stay local
        sh = ShellContext(master.url, use_grpc=False)
        sh.ec_encode(vid=vid)
        vs2 = VolumeServer([os.path.join(d, "v2")], master.url)
        vs2.start()
        vs3 = VolumeServer([os.path.join(d, "v3")], master.url)
        vs3.start()
        moves = {vs2: [3, 4, 5, 6], vs3: [7, 8, 9, 10]}
        for vs, sids in moves.items():
            http_json("POST", f"http://{vs.url}/admin/ec/copy",
                      {"volume_id": vid, "shard_ids": sids,
                       "source_data_node": vs1.url,
                       "copy_ecx_file": True})
            http_json("POST", f"http://{vs.url}/admin/ec/mount",
                      {"volume_id": vid, "shard_ids": sids})
        moved = [s for sids in moves.values() for s in sids]
        http_json("POST", f"http://{vs1.url}/admin/ec/unmount",
                  {"volume_id": vid, "shard_ids": moved})
        http_json("POST", f"http://{vs1.url}/admin/ec/delete_shards",
                  {"volume_id": vid, "shard_ids": moved})
        time.sleep(0.3)  # let heartbeats register the spread

        def kill(vs, dir_name, sid) -> bytes:
            path = os.path.join(d, dir_name,
                                f"{vid}{layout.shard_ext(sid)}")
            with open(path, "rb") as f:
                golden = f.read()
            http_json("POST", f"http://{vs.url}/admin/ec/unmount",
                      {"volume_id": vid, "shard_ids": [sid]})
            http_json("POST",
                      f"http://{vs.url}/admin/ec/delete_shards",
                      {"volume_id": vid, "shard_ids": [sid]})
            return golden

        q = master.repair_queue

        def drive(expect_total) -> dict:
            deadline = time.time() + 60
            while time.time() < deadline:
                st = q.status()
                if st["repaired_total"] >= expect_total \
                        and not st["in_flight"]:
                    return st
                q._dispatch()
                time.sleep(0.05)
            raise RuntimeError(f"ec repair stalled: {q.status()}")

        def rebuilt_identical(sid, golden) -> bool:
            path = os.path.join(d, "v1",
                                f"{vid}{layout.shard_ext(sid)}")
            with open(path, "rb") as f:
                return f.read() == golden

        try:
            q.partial_repair = True
            golden4 = kill(vs2, "v2", 4)
            q.submit(vid, "", reason="bench:partial")
            st = drive(1)
            if not st["partial_repairs"]:
                raise RuntimeError(f"partial repair fell back: {st}")
            partial_per_mb = st["last_repair_network_bytes_per_mb"]
            partial_ok = rebuilt_identical(4, golden4)

            q.partial_repair = False
            golden7 = kill(vs3, "v3", 7)
            q.submit(vid, "", reason="bench:legacy")
            st = drive(2)
            legacy_per_mb = st["last_repair_network_bytes_per_mb"]
            legacy_ok = rebuilt_identical(7, golden7)
            if not (partial_ok and legacy_ok):
                raise RuntimeError(
                    f"rebuilt shard not bit-identical "
                    f"(partial={partial_ok}, legacy={legacy_ok})")
        finally:
            mc.stop()
            for vs in (vs3, vs2, vs1):
                vs.stop()
            master.stop()
    return {
        "repair_network_bytes_per_mb": partial_per_mb,
        "repair_network_bytes_per_mb_legacy": legacy_per_mb,
        "repair_network_widths_partial": round(partial_per_mb / mb, 2),
        "repair_network_widths_legacy": round(legacy_per_mb / mb, 2),
        "repair_network_frugality": round(
            legacy_per_mb / max(partial_per_mb, 1.0), 2),
        "repair_partial_bit_identical": partial_ok,
    }


def bench_filer_put(size_mb: int = 4, chunk_kb: int = 256,
                    rtt_ms: float = 15.0) -> dict:
    """Filer auto-chunk PUT throughput: concurrent chunk upload
    (batched assigns + bounded pool) vs the serial per-chunk loop.

    The volume server sits behind a netchaos proxy adding `rtt_ms` of
    latency per request — the stand-in for a real filer->volume network
    hop (this host is single-core, so the win IS latency overlap, which
    the proxy makes deterministic). A 4MB body at 256KB chunks is 16
    uploads: serial pays 16 x rtt, parallel pays ~ceil(16/8) x rtt.
    Read-back equality against the original bytes is asserted for both
    modes. SEAWEEDFS_TPU_BENCH_PUT_MB overrides the body size."""
    import tempfile

    import seaweedfs_tpu.server.filer_server as fsrv
    from seaweedfs_tpu.server.filer_server import FilerServer
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.utils.httpd import http_call
    from tools.netchaos import ChaosProxy

    size_mb = int(os.environ.get("SEAWEEDFS_TPU_BENCH_PUT_MB", size_mb))
    size = size_mb * 1024 * 1024
    rng = np.random.default_rng(13)
    data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
    saved_chunk = fsrv.CHUNK_SIZE
    fsrv.CHUNK_SIZE = chunk_kb * 1024
    with tempfile.TemporaryDirectory() as d:
        master = MasterServer(volume_size_limit_mb=256)
        master.start()
        vs_port = _free_port()
        proxy = ChaosProxy("127.0.0.1", vs_port,
                           latency_s=rtt_ms / 1000.0).start()
        vs = VolumeServer([d], master.url, port=vs_port,
                          advertise=proxy.url)
        vs.start()
        fs = FilerServer(master.url)
        # pin the buffered ingest path: this bench compares the wide
        # upload pool against the serial loop at a fixed RTT, and the
        # streaming pipeline caps fan-out at STREAM_INFLIGHT by design
        # (its own bench is bench_filer_streaming_rss)
        fs.streaming_ingest = False
        fs.start()
        try:
            def put_and_verify(name: str) -> float:
                t0 = time.perf_counter()
                status, body, _ = http_call(
                    "POST", f"http://{fs.url}/bench/{name}",
                    body=data, timeout=300)
                dt = time.perf_counter() - t0
                if status != 201:
                    raise RuntimeError(f"PUT failed: HTTP {status} {body!r}")
                status, got, _ = http_call(
                    "GET", f"http://{fs.url}/bench/{name}", timeout=300)
                if status != 200 or got != data:
                    raise RuntimeError(f"read-back mismatch on {name}")
                return dt

            fs.parallel_uploads = True
            par_s = put_and_verify("parallel.bin")
            fs.parallel_uploads = False
            ser_s = put_and_verify("serial.bin")
            # where the PUT time goes: one fully-sampled extra upload
            # (parallel mode), broken down by span across the stack
            fs.parallel_uploads = True
            for node in (fs, vs, master):
                node.tracer.sample_rate = 1.0
            t_mark = time.time()
            put_and_verify("breakdown.bin")
            breakdown = _stage_breakdown(
                (fs.tracer, vs.tracer, master.tracer), t_mark)
        finally:
            fs.stop()
            vs.stop()
            proxy.stop()
            master.stop()
            fsrv.CHUNK_SIZE = saved_chunk
    return {
        "filer_put_mbps": round(size / par_s / 1e6, 1),
        "filer_put_serial_mbps": round(size / ser_s / 1e6, 1),
        "filer_put_speedup": round(ser_s / par_s, 2),
        "filer_put_chunks": (size + chunk_kb * 1024 - 1)
        // (chunk_kb * 1024),
        "filer_put_rtt_ms": rtt_ms,
        "filer_put_stage_breakdown_ms": breakdown,
    }


def bench_filer_ops(n_shards: int = 3, n_identity_ops: int = 240,
                    n_timed_ops: int = 600, store_ms: float = 4.0,
                    concurrency: int = 32) -> dict:
    """Filer metadata scale-out: aggregate namespace ops/s on an
    N-shard consistent-hash ring (hot-entry + negative-lookup caches
    on) vs the single-filer comparator with caches OFF, driven by the
    sim's seeded zipf workload over a 10^6 keyspace.

    Each filer's store sits behind a single-writer latency shim
    (`store_ms` held under the store lock per entry op) — the stand-in
    for a real DB backend, and the per-shard bottleneck that sharding
    divides and the entry cache bypasses.  Writes are small enough to
    stay inline (no volume servers, no assigns), so the client's warm
    path can be asserted master-free.

    Correctness rides along: the SAME op log is applied to both
    clusters and compared op-by-op (status + file bytes + normalized
    listings), then the full namespace is walked through the routed
    listing path and compared after the concurrent timed phase
    (deterministic per-key payloads make concurrent replay
    order-independent).  Also measured: master calls during warm GETs
    (must be 0) and store reads for 10 repeated GETs of one absent
    path (the negative cache must make it <= 1)."""
    import hashlib
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from seaweedfs_tpu.client.wdclient import MasterClient
    from seaweedfs_tpu.server.filer_server import FilerServer
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.sim.workload import (TenantSpec, ZipfWorkload,
                                            namespace_path)
    from seaweedfs_tpu.utils import clockctl
    from seaweedfs_tpu.utils.httpd import http_json

    class LatencyStore:
        """Single-writer DB stand-in: every entry op holds the store
        lock for the shim latency, so one shard's metadata throughput
        is capped at ~1/store_ms ops/s unless the cache absorbs it."""

        def __init__(self, inner, delay_s: float):
            self.inner = inner
            self.delay_s = delay_s
            self.name = inner.name
            self.op_lock = threading.Lock()
            self.reads = 0

        def _op(self, fn, *a, **kw):
            with self.op_lock:
                clockctl.sleep(self.delay_s)
                return fn(*a, **kw)

        def find_entry(self, p):
            self.reads += 1
            return self._op(self.inner.find_entry, p)

        def insert_entry(self, e):
            return self._op(self.inner.insert_entry, e)

        def update_entry(self, e):
            return self._op(self.inner.update_entry, e)

        def delete_entry(self, p):
            return self._op(self.inner.delete_entry, p)

        def delete_folder_children(self, p):
            return self._op(self.inner.delete_folder_children, p)

        def list_directory_entries(self, *a, **kw):
            return self._op(self.inner.list_directory_entries, *a, **kw)

        def __getattr__(self, name):  # kv_*, close, ...
            return getattr(self.inner, name)

    def build_cluster(n: int, entry_cache: bool):
        master = MasterServer()
        master.start()
        filers = []
        for _ in range(n):
            f = FilerServer(master.url, sharding=(n > 1),
                            entry_cache=entry_cache, qos=False,
                            tracing_enabled=False)
            f.filer.store.inner = LatencyStore(f.filer.store.inner,
                                               store_ms / 1000.0)
            f.start()
            filers.append(f)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            ring = http_json("GET",
                             f"http://{master.url}/cluster/filers")
            if len(ring.get("filers", [])) == n:
                break
            clockctl.sleep(0.05)
        for f in filers:
            f._adopt_ring()
        mc = MasterClient(master.url)
        return master, filers, mc

    def payload(key: int) -> bytes:
        return (f"k{key}:" * 64).encode()[:512]  # inline (< 2KB)

    def norm_listing(body: bytes):
        rows = json.loads(body).get("Entries", [])
        return sorted((r["FullPath"], r["FileSize"]) for r in rows)

    def apply_one(mc, op):
        path = namespace_path(op.key)
        if op.kind == "write":
            status, body, _ = mc.filer_call("PUT", path,
                                            body=payload(op.key))
            return ("w", path, status)
        if op.kind == "scan":
            d = path.rsplit("/", 1)[0]
            status, body, _ = mc.filer_call("GET", d)
            return ("s", d, status,
                    norm_listing(body) if status == 200 else None)
        status, body, _ = mc.filer_call("GET", path)
        return ("r", path, status,
                hashlib.sha256(body).hexdigest()
                if status == 200 else None)

    def walk(mc) -> list:
        """Full namespace through the ROUTED listing path."""
        out, stack = [], ["/"]
        while stack:
            d = stack.pop()
            status, body, _ = mc.filer_call("GET", d)
            if status != 200:
                continue
            for r in json.loads(body).get("Entries", []):
                if r["IsDirectory"]:
                    stack.append(r["FullPath"])
                else:
                    s, b, _ = mc.filer_call("GET", r["FullPath"])
                    out.append((r["FullPath"], s,
                                hashlib.sha256(b).hexdigest()))
        return sorted(out)

    # Metadata traffic is stat/lookup-dominated (every S3 GET/HEAD is a
    # filer read; writes are the minority) — a 90/8/2 read/write/scan
    # mix, zipf-skewed, is the workload the entry caches exist for.
    wl = ZipfWorkload([TenantSpec("tenant-0", 100.0, mix=(0.90, 0.08, 0.02)),
                       TenantSpec("tenant-1", 100.0, mix=(0.90, 0.08, 0.02))],
                      seed=1009, write_size=512)
    ops = wl.generate((n_identity_ops + n_timed_ops) / 200.0)
    identity_ops = ops[:n_identity_ops]
    timed_ops = ops[n_identity_ops:n_identity_ops + n_timed_ops]

    ma, fa, mca = build_cluster(n_shards, entry_cache=True)
    mb, fb, mcb = build_cluster(1, entry_cache=False)
    try:
        # --- phase 1: sequential identity apply (also warms caches)
        rec_a = [apply_one(mca, op) for op in identity_ops]
        rec_b = [apply_one(mcb, op) for op in identity_ops]
        identical = rec_a == rec_b

        # --- phase 2: master-free warm GETs
        warm = [namespace_path(op.key) for op in identity_ops
                if op.kind == "write"][:50]
        mca.filer_ring()
        calls0 = mca.master_calls
        for p in warm:
            mca.filer_call("GET", p)
        master_calls_warm = mca.master_calls - calls0

        # --- phase 3: negative-lookup cache vs repeated misses
        missing = "/zipf/b000/never-written"
        reads0 = sum(f.filer.store.inner.reads for f in fa)
        for _ in range(10):
            mca.filer_call("GET", missing)
        neg_store_reads = sum(f.filer.store.inner.reads
                              for f in fa) - reads0

        # --- phase 4: timed concurrent replay on both clusters
        def replay(mc) -> float:
            t0 = time.perf_counter()
            with ThreadPoolExecutor(max_workers=concurrency) as pool:
                list(pool.map(lambda op: apply_one(mc, op), timed_ops))
            return time.perf_counter() - t0

        dt_a = replay(mca)
        dt_b = replay(mcb)

        # --- phase 5: full-namespace walk must still match
        walk_identical = walk(mca) == walk(mcb)

        redirects = sum(
            f._m_shard._values.get(("redirect",), 0) for f in fa)
        hit_rate = (fa[0].filer.entry_cache.snapshot()["hit_rate"]
                    if fa[0].filer.entry_cache else 0.0)
    finally:
        for f in fa + fb:
            f.stop()
        ma.stop()
        mb.stop()

    ops_a = n_timed_ops / dt_a
    ops_b = n_timed_ops / dt_b
    return {
        "filer_ops_per_sec": round(ops_a, 1),
        "filer_ops_per_sec_1shard": round(ops_b, 1),
        "filer_ops_scaleout_speedup": round(ops_a / ops_b, 2),
        "filer_ops_shards": n_shards,
        "filer_ops_bit_identical": bool(identical and walk_identical),
        "filer_ops_master_calls_warm_get": master_calls_warm,
        "filer_ops_neg_lookup_store_reads": neg_store_reads,
        "filer_ops_redirects": redirects,
        "filer_ops_cache_hit_rate": hit_rate,
        "filer_ops_store_ms": store_ms,
    }


def bench_shard_rebalance(n_shards: int = 3, n_hot_dirs: int = 9,
                          files_per_dir: int = 10,
                          ops_per_phase: int = 360,
                          store_ms: float = 4.0,
                          concurrency: int = 24,
                          converge_timeout_s: float = 45.0) -> dict:
    """Live shard rebalancing vs a frozen ring, on the pathological
    hash layout: N hot directories that all land on ONE shard.

    Both clusters are identical 3-shard rings behind the single-writer
    latency shim (entry caches OFF so every namespace op pays the
    store lock — the per-shard bottleneck migration redistributes).
    The frozen comparator's planner is disarmed; the live cluster's
    planner runs the real closed loop — announce piggybacks feed the
    master, plans dispatch move orders, movers copy and the ring flips
    at commit — on a fast announce cadence.

    Three measured phases on each cluster: BEFORE (all hot dirs on one
    shard), DURING (live cluster migrating under load), AFTER (live
    cluster converged).  Reported: aggregate ops/s and interactive
    (read) p99 per phase, failed client ops on the live cluster across
    ALL phases (must be 0 — the dual-serve window guarantee), and a
    full routed-namespace walk compared across clusters (bit
    identity: migration moves rows, never mutates them)."""
    import hashlib
    import random
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from seaweedfs_tpu.client.wdclient import MasterClient
    from seaweedfs_tpu.server.filer_server import FilerServer
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.utils import clockctl
    from seaweedfs_tpu.utils.httpd import http_json

    class LatencyStore:
        """Single-writer DB stand-in (see bench_filer_ops)."""

        def __init__(self, inner, delay_s: float):
            self.inner = inner
            self.delay_s = delay_s
            self.name = inner.name
            self.op_lock = threading.Lock()

        def _op(self, fn, *a, **kw):
            with self.op_lock:
                clockctl.sleep(self.delay_s)
                return fn(*a, **kw)

        def find_entry(self, p):
            return self._op(self.inner.find_entry, p)

        def insert_entry(self, e):
            return self._op(self.inner.insert_entry, e)

        def update_entry(self, e):
            return self._op(self.inner.update_entry, e)

        def delete_entry(self, p):
            return self._op(self.inner.delete_entry, p)

        def delete_folder_children(self, p):
            return self._op(self.inner.delete_folder_children, p)

        def list_directory_entries(self, *a, **kw):
            return self._op(self.inner.list_directory_entries, *a, **kw)

        def __getattr__(self, name):  # kv_*, close, ...
            return getattr(self.inner, name)

    def build_cluster(live: bool):
        master = MasterServer()
        # both start disarmed: the live cluster's planner is armed
        # (min_rate lowered) only after the BEFORE phase is measured
        master.rebalance.min_rate = float("inf")
        if live:
            # fast loop for bench timescales.  Cooldown short enough
            # for SECOND-hop moves (dirs pile onto the intermediate
            # coldest shard and must be movable again to reach even);
            # equilibrium itself stops the loop — at even spread the
            # imbalance sits under threshold and no plan fires
            master.rebalance.window_s = 2.0
            master.rebalance.threshold = 1.35
            master.rebalance.cooldown_s = 6.0
        master.start()
        filers = []
        for _ in range(n_shards):
            f = FilerServer(master.url, sharding=True,
                            entry_cache=False, qos=False,
                            tracing_enabled=False)
            f.announce_interval_s = 0.5
            f.filer.store.inner = LatencyStore(f.filer.store.inner,
                                               store_ms / 1000.0)
            f.start()
            filers.append(f)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            ring = http_json("GET",
                             f"http://{master.url}/cluster/filers")
            if len(ring.get("filers", [])) == n_shards:
                break
            clockctl.sleep(0.05)
        for f in filers:
            f._adopt_ring()
        return master, filers, MasterClient(master.url)

    def payload(path: str) -> bytes:
        return (f"{path}:" * 40).encode()[:512]  # inline, per-path

    ma, fa, mca = build_cluster(live=True)
    mb, fb, mcb = build_cluster(live=False)
    failed = [0]
    try:
        # the adversarial layout: hot directories that ALL hash onto
        # one shard — on BOTH rings.  The two clusters' members are
        # distinct host:port strings, so their hash layouts differ;
        # picking by one ring alone would hand the frozen comparator
        # an accidentally-even (non-adversarial) spread
        ring_a = fa[0].shard_ring
        ring_b = fb[0].shard_ring
        buckets: dict = {}
        hot_dirs = []
        for i in range(8000):
            d = f"/hot/d{i:04d}"
            k = (ring_a.owner(d), ring_b.owner(d))
            buckets.setdefault(k, []).append(d)
            if len(buckets[k]) >= n_hot_dirs:
                hot_dirs = buckets[k]
                break
        assert len(hot_dirs) == n_hot_dirs, "no co-owned dir set found"

        seeded = []
        for d in hot_dirs:
            for j in range(files_per_dir):
                seeded.append(f"{d}/k{j:02d}")
        for mc in (mca, mcb):
            for p in seeded:
                st, _, _ = mc.filer_call("PUT", p, body=payload(p))
                assert st in (200, 201), (p, st)

        rng = random.Random(1009)
        wseq = [0]

        def gen_ops(n: int) -> list:
            """85/15 read/write over the hot dirs; writes create new
            deterministic paths so migration deltas see fresh rows."""
            ops = []
            for _ in range(n):
                d = rng.choice(hot_dirs)
                if rng.random() < 0.15:
                    wseq[0] += 1
                    ops.append(("w", f"{d}/n{wseq[0]:05d}"))
                else:
                    ops.append(("r", f"{d}/k{rng.randrange(files_per_dir):02d}"))
            return ops

        def replay(mc, ops, count_failures: bool) -> tuple:
            lats = []

            def one(op):
                kind, p = op
                t0 = time.perf_counter()
                if kind == "w":
                    st, _, _ = mc.filer_call("PUT", p, body=payload(p))
                    ok = st in (200, 201)
                else:
                    st, _, _ = mc.filer_call("GET", p)
                    ok = st == 200
                    lats.append(time.perf_counter() - t0)
                if count_failures and not ok:
                    failed[0] += 1

            t0 = time.perf_counter()
            with ThreadPoolExecutor(max_workers=concurrency) as pool:
                list(pool.map(one, ops))
            dt = time.perf_counter() - t0
            lats.sort()
            p99 = lats[int(0.99 * (len(lats) - 1))] if lats else 0.0
            return len(ops) / dt, p99 * 1000.0

        def run_phase(ops):
            """The SAME op list hits both clusters (namespace identity
            holds); live is measured with failure counting on."""
            ops_live, p99_live = replay(mca, ops, True)
            ops_frz, p99_frz = replay(mcb, ops, False)
            return (ops_live, p99_live), (ops_frz, p99_frz)

        before_live, before_frz = run_phase(gen_ops(ops_per_phase))

        # arm the planner: announce piggybacks (0.5s cadence) now feed
        # real plans.  Load stays CONTINUOUS on the live cluster —
        # alternating clusters would leave idle gaps that turn the
        # planner's windowed rates into noise and invite spurious
        # moves — until the override table stops growing and no move
        # is in flight, i.e. the ring has converged.  The frozen
        # cluster replays the same batches afterwards (its performance
        # is stationary; namespace identity still holds).
        ma.rebalance.min_rate = 10.0
        during = {"live": [], "frz": []}
        during_batches = []
        t_during0 = time.monotonic()
        seen, stable, converged = -1, 0, False
        while time.monotonic() - t_during0 < converge_timeout_s:
            batch = gen_ops(ops_per_phase)
            during_batches.append(batch)
            during["live"].append(replay(mca, batch, True))
            reb = http_json("GET",
                            f"http://{ma.url}/cluster/rebalance")
            n_over = len(reb["overrides"])
            moving = reb["planner"]["moving"]
            stable = stable + 1 if (n_over == seen and not moving
                                    and n_over > 0) else 0
            seen = n_over
            if stable >= 3:
                converged = True
                break
        t_during = time.monotonic() - t_during0
        for batch in during_batches:
            during["frz"].append(replay(mcb, batch, False))

        after_live, after_frz = run_phase(gen_ops(ops_per_phase))

        # bit identity: full namespace through the routed listing path
        def walk(mc) -> list:
            out, stack = [], ["/"]
            while stack:
                dpath = stack.pop()
                status, body, _ = mc.filer_call("GET", dpath)
                if status != 200:
                    continue
                for r in json.loads(body).get("Entries", []):
                    if r["IsDirectory"]:
                        stack.append(r["FullPath"])
                    else:
                        s, b, _ = mc.filer_call("GET", r["FullPath"])
                        out.append((r["FullPath"], s,
                                    hashlib.sha256(b).hexdigest()))
            return sorted(out)

        walk_identical = walk(mca) == walk(mcb)
        reb = http_json("GET", f"http://{ma.url}/cluster/rebalance")
        moves = reb["planner"]["commits"]
        spread_after = fa[0].shard_ring.spread(hot_dirs)
    finally:
        for f in fa + fb:
            f.stop()
        ma.stop()
        mb.stop()

    d_live = during["live"] or [before_live]
    d_frz = during["frz"] or [before_frz]
    mean = lambda xs: sum(xs) / len(xs)  # noqa: E731
    return {
        "shard_rebalance_shards": n_shards,
        "shard_rebalance_hot_dirs": n_hot_dirs,
        "shard_rebalance_moves_committed": moves,
        "shard_rebalance_converged": bool(converged),
        "shard_rebalance_converge_s": round(t_during, 1),
        "shard_rebalance_ops_before": round(before_live[0], 1),
        "shard_rebalance_ops_during": round(
            mean([x[0] for x in d_live]), 1),
        "shard_rebalance_ops_after": round(after_live[0], 1),
        "shard_rebalance_ops_frozen": round(after_frz[0], 1),
        "shard_rebalance_speedup": round(
            after_live[0] / after_frz[0], 2),
        "shard_rebalance_p99_ms_before": round(before_live[1], 1),
        "shard_rebalance_p99_ms_during": round(
            max([x[1] for x in d_live]), 1),
        "shard_rebalance_p99_ms_after": round(after_live[1], 1),
        "shard_rebalance_p99_ms_frozen": round(after_frz[1], 1),
        "shard_rebalance_failed_ops": failed[0],
        "shard_rebalance_bit_identical": bool(walk_identical),
        "shard_rebalance_dir_spread_after": spread_after,
        "shard_rebalance_store_ms": store_ms,
    }


def bench_tiering(n_vols: int = 6, files_per_vol: int = 12,
                  file_kb: int = 32, ops_per_phase: int = 240,
                  concurrency: int = 4,
                  converge_timeout_s: float = 75.0,
                  reheat_timeout_s: float = 30.0) -> dict:
    """Temperature-driven tiering autopilot vs a tiering-off comparator.

    Two identical single-node clusters, each with n_vols sealed data
    volumes seeded with the same payloads.  The live cluster's planner
    is armed (fast bands) after the BEFORE phase and the workload gives
    each volume a distinct temperature: one volume is hammered (hot),
    one gets a ~0.8/s trickle (cooling), the rest go silent (cold).
    The autopilot must move cooling->EC and cold->cloud (our own S3
    gateway) purely from heartbeat-piggybacked read counters, then
    promote one cloud volume back to hot when the bench re-heats it.

    Reported: hot-read p99 per phase (BEFORE / DURING migration /
    AFTER, plus the frozen comparator), failed client ops across ALL
    live-lane reads (must be 0 — demote/promote hold the volume lock,
    so concurrent reads wait instead of failing), bit-identical
    readback of every needle at every rung transition, and the
    $/GB-weighted effective-capacity ratio vs tiering-off under a
    declared price model (hot replicated NVMe 1.0, EC parity HDD 0.5,
    cloud object store 0.1 $/GB)."""
    import hashlib
    import random
    import shutil
    import tempfile
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from seaweedfs_tpu.client.operation import upload_to
    from seaweedfs_tpu.gateway.s3_server import S3Server
    from seaweedfs_tpu.server.filer_server import FilerServer
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.storage.file_id import format_needle_id_cookie
    from seaweedfs_tpu.utils import clockctl
    from seaweedfs_tpu.utils.httpd import http_call, http_json

    PRICE = {"hot": 1.0, "ec": 0.5, "cloud": 0.1}  # $/GB weights

    def build_lane(live: bool) -> dict:
        d = tempfile.mkdtemp(prefix="bench-tier-")
        master = MasterServer(volume_size_limit_mb=64)
        if not live:
            # tiering-off comparator: same planner object, permanently
            # below the age gate so no plan can ever fire
            master.tiering.min_age_s = float("inf")
        master.start()
        vs = VolumeServer([os.path.join(d, "v")], master.url)
        vs.start()
        lane = {"dir": d, "master": master, "vs": vs,
                "filer": None, "s3": None}
        if live:
            fs = FilerServer(master.url)
            fs.start()
            s3 = S3Server(fs)
            s3.start()
            http_call("PUT", f"http://{s3.url}/tier")
            lane["filer"], lane["s3"] = fs, s3
        # explicit growth needs the node registered; retry across the
        # first heartbeat
        deadline = time.monotonic() + 10
        vids: list = []
        while time.monotonic() < deadline and len(vids) < n_vols:
            try:
                out = http_json(
                    "POST",
                    f"http://{master.url}/vol/grow?count={n_vols}")
                vids = sorted(out.get("volume_ids", []))
            except (ConnectionError, ValueError):
                pass
            if len(vids) < n_vols:
                clockctl.sleep(0.1)
        assert len(vids) == n_vols, f"volume growth failed: {vids}"
        lane["vids"] = vids
        return lane

    # identical payloads on both lanes, addressed by (vol index, file
    # index) so the lanes' vid numbering need not match
    rng = random.Random(7)
    payloads = {(i, j): rng.randbytes(file_kb * 1024)
                for i in range(n_vols) for j in range(files_per_vol)}
    digests = {k: hashlib.sha256(v).hexdigest()
               for k, v in payloads.items()}

    def seed(lane: dict) -> None:
        """Self-assigned fids (master assign scatters randomly; the
        bench needs an exact files-per-volume layout), then seal every
        data volume — demotion only considers read-only volumes."""
        key = 1
        lane["fids"] = {}
        for i, vid in enumerate(lane["vids"]):
            for j in range(files_per_vol):
                fid = f"{vid},{format_needle_id_cookie(key, 0x1234)}"
                key += 1
                upload_to(fid, lane["vs"].url, payloads[(i, j)],
                          name=f"f{i}_{j}")
                lane["fids"][(i, j)] = fid
        for vid in lane["vids"]:
            http_json("POST",
                      f"http://{lane['vs'].url}/admin/mark_readonly",
                      {"volume_id": vid, "read_only": True})

    la = build_lane(live=True)
    lb = build_lane(live=False)
    failed = [0]
    stop_evt = threading.Event()
    threads: list = []
    try:
        seed(la)
        seed(lb)
        # roles by volume index: 0 hot, 1 cooling, 2.. cold
        hot_fids = [la["fids"][(0, j)] for j in range(files_per_vol)]
        cool_fids = [la["fids"][(1, j)] for j in range(files_per_vol)]
        hot_fids_b = [lb["fids"][(0, j)] for j in range(files_per_vol)]
        cold_idx = list(range(2, n_vols))

        def get(lane: dict, fid: str, count_failures: bool) -> bytes:
            try:
                st, body, _ = http_call(
                    "GET", f"http://{lane['vs'].url}/{fid}")
                ok = st == 200
            except (ConnectionError, OSError):
                ok, body = False, b""
            if not ok and count_failures:
                failed[0] += 1
            return body if ok else b""

        def replay(lane: dict, fids: list, n: int,
                   count_failures: bool) -> float:
            """n hot reads, cycled over fids; returns p99 in ms."""
            lats: list = []

            def one(k):
                t0 = time.perf_counter()
                get(lane, fids[k % len(fids)], count_failures)
                lats.append(time.perf_counter() - t0)

            with ThreadPoolExecutor(max_workers=concurrency) as pool:
                list(pool.map(one, range(n)))
            lats.sort()
            return lats[int(0.99 * (len(lats) - 1))] * 1000.0

        def walk(lane: dict, count_failures: bool) -> bool:
            """Read back EVERY needle and compare against the seeded
            digest — the bit-identity probe run at each rung state."""
            ok = True
            for k, fid in sorted(lane["fids"].items()):
                body = get(lane, fid, count_failures)
                if hashlib.sha256(body).hexdigest() != digests[k]:
                    ok = False
            return ok

        def best_p99(lane: dict, fids: list, reps: int,
                     count_failures: bool) -> float:
            """Best-of-reps p99: scheduler noise only ever ADDS
            latency, so the minimum is the closest estimate of the
            lane's intrinsic tail (the benches share one small box)."""
            return min(replay(lane, fids, ops_per_phase,
                              count_failures) for _ in range(reps))

        # warm connections + page cache, then the BEFORE phase
        replay(la, hot_fids, 64, False)
        replay(lb, hot_fids_b, 64, False)
        p99_before = best_p99(la, hot_fids, 2, True)
        identical_before = walk(la, True)

        # arm the autopilot: fast bands scaled to the bench workload
        # (hammer >> heat_min, trickle inside (cold_max, cool_max],
        # silence -> 0), cloud rung pointed at our own S3 gateway.
        # Heartbeats are already flowing, so plans fire on the next
        # pulse.
        tp = la["master"].tiering
        tp.window_s = 3.0
        tp.cool_max = 1.5
        tp.cold_max = 0.2
        tp.heat_min = 6.0
        tp.min_age_s = 2.0
        tp.cooldown_s = 3.0
        tp.max_moves_per_plan = 4
        tp.cloud_enabled = True
        la["master"].tier_mover.endpoint = f"http://{la['s3'].url}"
        la["master"].tier_mover.bucket = "tier"

        # identical background workload on BOTH lanes (only the
        # autopilot differs): a hammer keeps the hot volume hot, a
        # ~0.8/s trickle holds the cooling volume in the EC band
        def driver(lane: dict, fids: list, pause: float,
                   count_failures: bool):
            k = 0
            while not stop_evt.is_set():
                get(lane, fids[k % len(fids)], count_failures)
                k += 1
                stop_evt.wait(pause)

        cool_fids_b = [lb["fids"][(1, j)] for j in range(files_per_vol)]
        for lane, fids, pause, count in (
                (la, hot_fids, 0.1, True),
                (la, cool_fids, 1.2, True),
                (lb, hot_fids_b, 0.1, False),
                (lb, cool_fids_b, 1.2, False)):
            t = threading.Thread(
                target=driver, args=(lane, fids, pause, count),
                daemon=True, name="bench-tier-driver")
            t.start()
            threads.append(t)

        def tier_status() -> dict:
            return http_json(
                "GET", f"http://{la['master'].url}/cluster/tiering")

        def rung_of(st: dict, vid: int) -> str:
            vols = st["planner"]["volumes"]
            meta = vols.get(str(vid), vols.get(vid, {}))
            return meta.get("rung", "hot")

        want = {la["vids"][i]: "cloud" for i in cold_idx}
        want[la["vids"][1]] = "ec"
        p99_during: list = []
        t0 = time.monotonic()
        stable, converged = 0, False
        st_conv = tier_status()
        while time.monotonic() - t0 < converge_timeout_s:
            p99_during.append(replay(la, hot_fids, 60, True))
            st = tier_status()
            settled = (not st["mover"]["busy"] and all(
                rung_of(st, vid) == rung for vid, rung in want.items()))
            stable = stable + 1 if settled else 0
            if stable >= 2:
                converged, st_conv = True, st
                break
            clockctl.sleep(0.4)
        t_converge = time.monotonic() - t0
        identical_tiered = walk(la, True)

        # steady-state economics at the converged rung layout: the
        # same bytes, weighted by what their rung costs per GB
        def lane_cost(st: dict, flat: bool) -> float:
            cost = 0.0
            for vid in la["vids"]:
                vols = st["planner"]["volumes"]
                meta = vols.get(str(vid), vols.get(vid, {}))
                rung = "hot" if flat else meta.get("rung", "hot")
                cost += meta.get("size", 0) * PRICE[rung]
            return cost

        tiered_cost = lane_cost(st_conv, flat=False)
        flat_cost = lane_cost(st_conv, flat=True)
        capacity_ratio = flat_cost / tiered_cost if tiered_cost else 0.0

        # re-heat: hammer one cloud volume until the autopilot promotes
        # it home (cloud -> hot; it never had EC shards)
        reheat_vid = la["vids"][cold_idx[0]]
        reheat_fids = [la["fids"][(cold_idx[0], j)]
                       for j in range(files_per_vol)]
        t0 = time.monotonic()
        promoted, k = False, 0
        next_poll = 0.0
        while time.monotonic() - t0 < reheat_timeout_s:
            get(la, reheat_fids[k % len(reheat_fids)], True)
            k += 1
            if time.monotonic() - t0 >= next_poll:
                next_poll += 0.5
                if rung_of(tier_status(), reheat_vid) == "hot":
                    promoted = True
                    break
        t_reheat = time.monotonic() - t0

        # snapshot the final rung layout while the steering load is
        # still live, then retire the drivers: BEFORE was measured
        # without them, so the steady-state AFTER/frozen comparison
        # must be too (the drivers exist only to steer temperature
        # through the migration and re-heat phases).  The planner is
        # age-gated off for the epilogue so the now-silent volumes
        # can't start a fresh demotion mid-measurement.
        st_final = tier_status()
        rungs_final = {vid: rung_of(st_final, vid)
                       for vid in la["vids"]}
        stats = http_json(
            "GET", f"http://{la['vs'].url}/admin/tier")["stats"]
        tp.min_age_s = float("inf")
        stop_evt.set()
        for t in threads:
            t.join(timeout=5)

        # interleaved best-of-3 so slow drift on the shared box hits
        # both lanes alike
        after_samples, frozen_samples = [], []
        for _ in range(3):
            after_samples.append(
                replay(la, hot_fids, ops_per_phase, True))
            frozen_samples.append(
                replay(lb, hot_fids_b, ops_per_phase, False))
        p99_after = min(after_samples)
        p99_frozen = min(frozen_samples)
        identical_after = walk(la, True)
        identical_frozen = walk(lb, False)
    finally:
        stop_evt.set()
        for lane in (la, lb):
            if lane.get("s3"):
                lane["s3"].stop()
            if lane.get("filer"):
                lane["filer"].stop()
            lane["vs"].stop()
            lane["master"].stop()
            shutil.rmtree(lane["dir"], ignore_errors=True)

    return {
        "tiering_vols": n_vols,
        "tiering_files": n_vols * files_per_vol,
        "tiering_converged": bool(converged),
        "tiering_converge_s": round(t_converge, 1),
        "tiering_rungs_converged": {
            str(vid): rung_of(st_conv, vid) for vid in la["vids"]},
        "tiering_rungs_final": {
            str(k): v for k, v in rungs_final.items()},
        "tiering_capacity_ratio": round(capacity_ratio, 2),
        "tiering_price_model": "hot=1.0 ec=0.5 cloud=0.1 $/GB",
        "tiering_p99_ms_before": round(p99_before, 1),
        "tiering_p99_ms_during": round(max(p99_during), 1)
        if p99_during else 0.0,
        "tiering_p99_ms_after": round(p99_after, 1),
        "tiering_p99_ms_frozen": round(p99_frozen, 1),
        "tiering_p99_degradation": round(
            p99_after / p99_frozen, 2) if p99_frozen else 0.0,
        "tiering_failed_ops": failed[0],
        "tiering_bit_identical": bool(
            identical_before and identical_tiered and identical_after
            and identical_frozen),
        "tiering_reheat_promoted": bool(promoted),
        "tiering_reheat_s": round(t_reheat, 1),
        "tiering_demotes": stats.get("demotes", 0),
        "tiering_promotes": stats.get("promotes", 0),
        "tiering_bytes_demoted": stats.get("bytes_demoted", 0),
        "tiering_bytes_promoted": stats.get("bytes_promoted", 0),
    }


def bench_replicated_write(n_writes: int = 20,
                           slow_ms: float = 40.0) -> dict:
    """Replicated-write tail latency: concurrent replica fan-out vs
    the serial peer loop.

    A 3-copy volume (replication 002) spans vs1 (written directly) and
    two peers that each sit behind a netchaos proxy adding `slow_ms`
    per request. The serial loop pays sum(peers) ~= 2 x slow_ms per
    write; the concurrent fan-out pays max(peers) ~= slow_ms.
    SEAWEEDFS_TPU_BENCH_REPL_WRITES overrides n_writes."""
    import tempfile

    from seaweedfs_tpu.client.wdclient import MasterClient
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.utils.httpd import http_call
    from tools.netchaos import ChaosProxy

    n_writes = int(os.environ.get("SEAWEEDFS_TPU_BENCH_REPL_WRITES",
                                  n_writes))
    payload = b"\xa5" * 4096
    with tempfile.TemporaryDirectory() as d:
        master = MasterServer(volume_size_limit_mb=64)
        master.start()
        vs1 = VolumeServer([os.path.join(d, "v1")], master.url)
        vs1.start()
        proxies, peers = [], []
        for name in ("v2", "v3"):
            port = _free_port()
            proxy = ChaosProxy("127.0.0.1", port,
                               latency_s=slow_ms / 1000.0).start()
            peer = VolumeServer([os.path.join(d, name)], master.url,
                                port=port, advertise=proxy.url)
            peer.start()
            proxies.append(proxy)
            peers.append(peer)
        mc = MasterClient(master.url, cache_ttl=0.0)
        vs1_direct = f"{vs1.http.host}:{vs1.http.port}"

        def measure() -> list:
            # fresh learned state per mode (metrics=None: a throwaway
            # health table needs no series)
            vs1.peer_health = type(vs1.peer_health)()
            vs1.store.peer_health = vs1.peer_health
            vs1._replica_cache.clear()
            samples = []
            for _ in range(n_writes):
                a = mc.assign(replication="002")
                if a.get("error"):
                    raise RuntimeError(f"assign failed: {a['error']}")
                t0 = time.perf_counter()
                status, body, _ = http_call(
                    "POST", f"http://{vs1_direct}/{a['fid']}",
                    body=payload, timeout=60)
                samples.append(time.perf_counter() - t0)
                if status != 201:
                    raise RuntimeError(
                        f"replicated write failed: HTTP {status} {body!r}")
            return samples

        try:
            vs1.parallel_replication = True
            par = measure()
            vs1.parallel_replication = False
            ser = measure()
        finally:
            mc.stop()
            for peer in peers:
                peer.stop()
            vs1.stop()
            for proxy in proxies:
                proxy.stop()
            master.stop()
    par_p99, ser_p99 = _p99_ms(par), _p99_ms(ser)
    return {
        "replicated_write_p99_ms": par_p99,
        "replicated_write_serial_p99_ms": ser_p99,
        "replicated_write_speedup": round(ser_p99 / max(par_p99, 0.001),
                                          2),
        "replicated_write_slow_ms": slow_ms,
        "replicated_write_replicas": 2,
        "replicated_write_n": n_writes,
    }


def bench_overload(n_reads: int = 12, n_bg: int = 24,
                   blob_kb: int = 600) -> dict:
    """Interactive tail latency while background readers overload one
    volume server — the QoS subsystem's acceptance number.

    The scarce resource is request-processing capacity: EC reads
    (interval locate + shard reassembly) are CPU-bound Python on this
    single-core host, so every concurrently admitted request inflates
    every other request's service time roughly linearly — measured
    here, a ~1ms solo EC read costs ~11ms with twelve riders. `n_bg`
    background threads loop EC GETs tagged X-Weed-Class: background
    while two interactive threads time EC GETs to success; both
    classes honor Retry-After on shed:

      qos on   limit pinned at 4 -> background holds at most 1 of the
               class-weighted slots, the rest are shed at the socket
               edge before buying any CPU; interactive shares the
               core with ~2 requests;
      qos off  every background reader is admitted and interactive
               queues behind ~n_bg concurrent reassemblies.

    overload_goodput_ratio = nqos_p99 / qos_p99 (the floor test wants
    >= 2x) and background progress under QoS must stay > 0 (throttled,
    never starved). SEAWEEDFS_TPU_BENCH_OVERLOAD_READS overrides
    n_reads."""
    import tempfile
    import threading

    from seaweedfs_tpu.client import operation
    from seaweedfs_tpu.client.wdclient import MasterClient
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.shell.commands import ShellContext
    from seaweedfs_tpu.utils.httpd import http_call, retry_after_hint

    n_reads = int(os.environ.get("SEAWEEDFS_TPU_BENCH_OVERLOAD_READS",
                                 n_reads))
    n_reads = max(2, n_reads // 2 * 2)  # two interactive threads
    rng = np.random.default_rng(17)
    blob = rng.integers(0, 256, blob_kb * 1024, dtype=np.uint8).tobytes()
    with tempfile.TemporaryDirectory() as d:
        master = MasterServer(volume_size_limit_mb=64)
        master.start()
        vs = VolumeServer([d], master.url)
        vs.start()
        mc = MasterClient(master.url, cache_ttl=0.0)
        a = operation.upload_data(mc, blob)
        b = operation.upload_data(mc, blob)
        # EC-encode every touched volume: reads now walk the shard
        # reassembly path, whose cost is what overload amplifies
        sh = ShellContext(master.url, use_grpc=False)
        for vid in sorted({int(a.fid.split(",")[0]),
                           int(b.fid.split(",")[0])}):
            sh.ec_encode(vid=vid)
        bg_url = f"http://{vs.url}/{a.fid}"
        int_url = f"http://{vs.url}/{b.fid}"
        # pin the concurrency limit: this bench demonstrates the class
        # weighting (bg_cap = max(1, 4//4) = 1 slot; interactive keeps
        # room for two in-flight), not the adaptive gradient — a moving
        # limit would make the comparison unrepeatable
        vs.qos.configure(min_limit=4, max_limit=4, limit=4)

        def bg_loop(stop: threading.Event, done: list) -> None:
            while not stop.is_set():
                try:
                    status, _b, hdr = http_call(
                        "GET", bg_url,
                        headers={"X-Weed-Class": "background"},
                        timeout=30)
                except (ConnectionError, OSError):
                    stop.wait(0.1)
                    continue
                if status == 200:
                    done.append(1)
                else:  # shed (503) or in-flight timeout (429)
                    ra = retry_after_hint(status, hdr)
                    stop.wait(min(ra if ra is not None else 0.5, 1.0))

        def timed_get() -> float:
            t0 = time.perf_counter()
            give_up = t0 + 20.0
            while True:
                try:
                    status, _b, hdr = http_call("GET", int_url,
                                                timeout=30)
                except (ConnectionError, OSError):
                    status, hdr = 503, {}
                if status == 200 or time.perf_counter() > give_up:
                    return time.perf_counter() - t0
                ra = retry_after_hint(status, hdr)
                time.sleep(min(ra if ra is not None else 0.5, 0.5))

        def run_phase() -> tuple:
            stop = threading.Event()
            done: list = []
            bgs = [threading.Thread(target=bg_loop, args=(stop, done),
                                    daemon=True) for _ in range(n_bg)]
            for t in bgs:
                t.start()
            time.sleep(1.0)  # let the overload establish before sampling
            samples: list = []
            lock = threading.Lock()

            def interactive() -> None:
                for _ in range(n_reads // 2):
                    dt = timed_get()
                    with lock:
                        samples.append(dt)

            its = [threading.Thread(target=interactive)
                   for _ in range(2)]
            for t in its:
                t.start()
            for t in its:
                t.join()
            stop.set()
            for t in bgs:
                t.join(timeout=5)
            return samples, len(done)

        try:
            qos_samples, bg_qos = run_phase()
            vs.qos.enabled = False
            nqos_samples, bg_nqos = run_phase()
        finally:
            mc.stop()
            vs.stop()
            master.stop()
    qos_p99 = _p99_ms(qos_samples)
    nqos_p99 = _p99_ms(nqos_samples)
    return {
        "overload_qos_interactive_p99_ms": qos_p99,
        "overload_nqos_interactive_p99_ms": nqos_p99,
        "overload_goodput_ratio": round(nqos_p99 / max(qos_p99, 0.001),
                                        2),
        "overload_bg_progress_qos": bg_qos,
        "overload_bg_progress_nqos": bg_nqos,
        "overload_bg_readers": n_bg,
        "overload_n": n_reads,
    }


def _vm_hwm_kb(pid: int) -> int:
    """Peak resident set (VmHWM) of a live process, in KB — the
    kernel's own high-water mark, so no sampling thread can miss a
    transient allocation spike."""
    with open(f"/proc/{pid}/status") as f:
        for line in f:
            if line.startswith("VmHWM:"):
                return int(line.split()[1])
    raise RuntimeError(f"no VmHWM in /proc/{pid}/status")


def _stream_put(filer_url: str, path: str, size: int, seed: int,
                block: int = 1 << 20) -> tuple[int, str]:
    """Stream a deterministic `size`-byte body to the filer block at a
    time over a raw socket — no full copy of the body ever exists in
    this process, so the filer child's RSS is the only place body
    memory can accumulate. Returns (status, sha256 of what was sent);
    regenerating with the same seed replays the identical stream."""
    import hashlib
    import socket as _socket

    rng = np.random.default_rng(seed)
    h = hashlib.sha256()
    host, port = filer_url.split(":")
    s = _socket.create_connection((host, int(port)), timeout=300)
    try:
        s.sendall(f"POST {path} HTTP/1.1\r\nHost: {filer_url}\r\n"
                  f"Content-Length: {size}\r\n"
                  f"Connection: close\r\n\r\n".encode())
        sent = 0
        while sent < size:
            n = min(block, size - sent)
            blk = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
            h.update(blk)
            s.sendall(blk)
            sent += n
        s.settimeout(300)
        resp = b""
        while b"\r\n" not in resp:
            got = s.recv(65536)
            if not got:
                break
            resp += got
        status = int(resp.split(b" ", 2)[1]) if resp else 0
        return status, h.hexdigest()
    finally:
        s.close()


def _stream_get_sha(filer_url: str, path: str) -> tuple[int, int, str]:
    """GET `path` and hash the body as it arrives (raw socket,
    Connection: close) — the comparator readback must not re-buffer a
    256MB object in the parent either. Returns (status, bytes,
    sha256)."""
    import hashlib
    import socket as _socket

    host, port = filer_url.split(":")
    s = _socket.create_connection((host, int(port)), timeout=300)
    try:
        s.sendall(f"GET {path} HTTP/1.1\r\nHost: {filer_url}\r\n"
                  f"Connection: close\r\n\r\n".encode())
        s.settimeout(300)
        buf = b""
        while b"\r\n\r\n" not in buf:
            got = s.recv(65536)
            if not got:
                raise ConnectionError("EOF before response headers")
            buf += got
        head, body = buf.split(b"\r\n\r\n", 1)
        status = int(head.split(b" ", 2)[1])
        length = None
        for line in head.split(b"\r\n")[1:]:
            k, _, v = line.partition(b":")
            if k.strip().lower() == b"content-length":
                length = int(v.strip())
        h = hashlib.sha256()
        n = len(body)
        h.update(body)
        while length is None or n < length:
            got = s.recv(1 << 20)
            if not got:
                break
            if length is not None and n + len(got) > length:
                got = got[:length - n]
            h.update(got)
            n += len(got)
        return status, n, h.hexdigest()
    finally:
        s.close()


def bench_filer_streaming_rss(size_mb: int = 256,
                              chunk_mb: int = 4) -> dict:
    """Bounded-memory streaming ingest: the filer's peak RSS while
    ingesting a 256MB-class PUT must be a few CHUNK_SIZE buffers, not
    the body.

    The filer runs ALONE in a child process (`--filer-child` mode of
    this script) so /proc/<pid>/status VmHWM isolates its memory from
    the master, the volume server, and the client, which all stay in
    this process. The client streams a deterministic body over a raw
    socket block at a time (no full copy exists anywhere), a warm-up
    PUT charges thread pools and pooled sockets outside the window,
    and the VmHWM delta across the big PUT is the write path's true
    peak. The buffered comparator child (streaming_ingest off)
    re-ingests the same byte stream — its delta is the whole body, the
    number the streaming path deletes — and the two stored objects
    must match chunk-for-chunk (layout) and byte-for-byte (streamed
    readback hash vs sent hash). SEAWEEDFS_TPU_BENCH_STREAM_MB
    overrides the body size."""
    import tempfile

    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.utils.httpd import http_call

    size_mb = int(os.environ.get("SEAWEEDFS_TPU_BENCH_STREAM_MB",
                                 size_mb))
    size = size_mb * 1024 * 1024
    chunk = chunk_mb * 1024 * 1024
    with tempfile.TemporaryDirectory() as d:
        master = MasterServer(volume_size_limit_mb=1024)
        master.start()
        vs = VolumeServer([d], master.url)
        vs.start()

        def run_child(streaming: bool, name: str) -> dict:
            proc = subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 "--filer-child", master.url, str(chunk),
                 "1" if streaming else "0"],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE)
            try:
                info = json.loads(proc.stdout.readline())
                url, pid = info["url"], info["pid"]
                st, _ = _stream_put(url, f"/warm/{name}",
                                    2 * chunk + 7, seed=7)
                if st != 201:
                    raise RuntimeError(f"warm-up PUT failed: {st}")
                before = _vm_hwm_kb(pid)
                t0 = time.perf_counter()
                st, sha_sent = _stream_put(url, f"/rss/{name}", size,
                                           seed=29)
                dt = time.perf_counter() - t0
                if st != 201:
                    raise RuntimeError(f"PUT failed: HTTP {st}")
                delta_kb = _vm_hwm_kb(pid) - before
                st, got_n, sha_read = _stream_get_sha(
                    url, f"/rss/{name}")
                if st != 200 or got_n != size:
                    raise RuntimeError(
                        f"readback failed: HTTP {st}, {got_n} bytes")
                st, ebody, _ = http_call(
                    "GET", f"http://{url}/__api/entry?path=/rss/{name}",
                    timeout=60)
                layout = [(c["offset"], c["size"]) for c in
                          json.loads(ebody)["entry"]["chunks"]]
                return {"delta_kb": delta_kb, "mbps": size / dt / 1e6,
                        "sha_sent": sha_sent, "sha_read": sha_read,
                        "layout": layout}
            finally:
                proc.stdin.close()
                proc.wait(timeout=60)

        try:
            streamed = run_child(True, "streamed")
            buffered = run_child(False, "buffered")
        finally:
            vs.stop()
            master.stop()
    identical = (streamed["sha_sent"] == streamed["sha_read"]
                 == buffered["sha_sent"] == buffered["sha_read"]
                 and streamed["layout"] == buffered["layout"])
    return {
        "filer_streaming_rss_mb": round(streamed["delta_kb"] / 1024, 1),
        "filer_streaming_rss_buffered_mb": round(
            buffered["delta_kb"] / 1024, 1),
        "filer_streaming_body_mb": size_mb,
        "filer_streaming_chunk_mb": chunk_mb,
        "filer_streaming_budget_mb": 3 * chunk_mb,
        "filer_streaming_mbps": round(streamed["mbps"], 1),
        "filer_streaming_bit_identical": identical,
    }


def _drain_get(netloc: str, path: str, *, digest: bool = False,
               timeout: float = 300.0):
    """GET `path` from `netloc` and DISCARD the body as it arrives
    (recv_into one reusable 1MB scratch buffer) so client-side
    allocation never gates the server throughput being measured.
    Returns (status, nbytes, seconds, sha256|None) — pass digest=True
    for the one read per mode that witnesses bit-identity."""
    import hashlib
    import socket as _socket

    host, port = netloc.split(":")
    t0 = time.perf_counter()
    s = _socket.create_connection((host, int(port)), timeout=timeout)
    try:
        s.sendall(f"GET {path} HTTP/1.1\r\nHost: {netloc}\r\n"
                  f"Connection: close\r\n\r\n".encode())
        s.settimeout(timeout)
        buf = b""
        while b"\r\n\r\n" not in buf:
            got = s.recv(65536)
            if not got:
                raise ConnectionError("EOF before response headers")
            buf += got
        head, body = buf.split(b"\r\n\r\n", 1)
        status = int(head.split(b" ", 2)[1])
        length = None
        for line in head.split(b"\r\n")[1:]:
            k, _, v = line.partition(b":")
            if k.strip().lower() == b"content-length":
                length = int(v.strip())
        h = hashlib.sha256() if digest else None
        n = len(body)
        if h:
            h.update(body)
        scratch = bytearray(1 << 20)
        view = memoryview(scratch)
        while length is None or n < length:
            got = s.recv_into(scratch)
            if not got:
                break
            if h:
                h.update(view[:got])
            n += got
        return (status, n, time.perf_counter() - t0,
                h.hexdigest() if h else None)
    finally:
        s.close()


def bench_read_plane(size_mb: int = 256, clients: int = 32) -> dict:
    """Zero-copy read plane: sendfile GETs vs the buffered path they
    replace, and volume-direct redirects vs filer proxying.

    One `size_mb` needle is served from a live volume server four
    ways: single-stream and `clients`-way concurrent, each with the
    descriptor/sendfile path on (`zero_copy=True`, the default) and
    off (the buffered comparator). The client drains bodies into a
    reusable scratch buffer so both modes see the same (minimal)
    client cost; one hashed read per mode proves the fast path is
    bit-identical before any timing counts. The buffered path pays
    the read() copy into user space, the CRC recompute over the whole
    payload, and the socket write copy; the sendfile path pays none
    of them — the reported speedup is the whole point of the plane.

    The redirect lane PUTs a single-chunk file through the filer and
    fetches it with auto-follow disabled: the raw 302 must carry ZERO
    proxied payload bytes (the filer drops out of the data path
    entirely), and following it must be bit-identical to the
    `?proxy=1` comparator. SEAWEEDFS_TPU_BENCH_READ_MB /
    SEAWEEDFS_TPU_BENCH_READ_CLIENTS override the sizes."""
    import hashlib
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    from seaweedfs_tpu.server.filer_server import FilerServer
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.utils.httpd import http_call, http_json

    size_mb = int(os.environ.get("SEAWEEDFS_TPU_BENCH_READ_MB",
                                 size_mb))
    clients = int(os.environ.get("SEAWEEDFS_TPU_BENCH_READ_CLIENTS",
                                 clients))
    size = size_mb << 20
    rng = np.random.default_rng(41)
    data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
    sha = hashlib.sha256(data).hexdigest()

    with tempfile.TemporaryDirectory() as d:
        master = MasterServer(volume_size_limit_mb=1024)
        master.start()
        vs = VolumeServer([d], master.url)
        vs.start()
        fsrv = FilerServer(master.url)
        fsrv.start()
        try:
            a = http_json("GET", f"http://{master.url}/dir/assign")
            st, _, _ = http_call("POST",
                                 f"http://{a['url']}/{a['fid']}",
                                 body=data, timeout=600)
            if st >= 300:
                raise RuntimeError(f"seed upload failed: HTTP {st}")
            netloc, path = a["url"], f"/{a['fid']}"

            def measure(zero_copy: bool) -> tuple[float, float]:
                vs.zero_copy = zero_copy
                st, n, _, got = _drain_get(netloc, path, digest=True)
                if st != 200 or n != size or got != sha:
                    raise RuntimeError(
                        f"readback mismatch (zero_copy={zero_copy}): "
                        f"HTTP {st}, {n} bytes")
                single = 0.0
                for _ in range(3):
                    _, n, dt, _ = _drain_get(netloc, path)
                    single = max(single, n / dt / 1e6)
                with ThreadPoolExecutor(max_workers=clients) as pool:
                    t0 = time.perf_counter()
                    futs = [pool.submit(_drain_get, netloc, path)
                            for _ in range(clients)]
                    total = sum(f.result()[1] for f in futs)
                    agg = total / (time.perf_counter() - t0) / 1e6
                return single, agg

            zc_single, zc_agg = measure(True)
            buf_single, buf_agg = measure(False)
            vs.zero_copy = True

            # ---- redirect lane: single-chunk file through the filer
            small = data[:3 << 20]
            st, _, _ = http_call("POST",
                                 f"http://{fsrv.url}/bench/one.bin",
                                 body=small, timeout=120)
            if st != 201:
                raise RuntimeError(f"filer PUT failed: HTTP {st}")
            st, raw_body, h = http_call(
                "GET", f"http://{fsrv.url}/bench/one.bin",
                follow_redirects=False, timeout=120)
            redirected = st == 302
            proxied_on_redirect = len(raw_body)
            loc = next((v for k, v in h.items()
                        if k.lower() == "location"), "")
            direct = b""
            if redirected:
                _, direct, _ = http_call("GET", loc, timeout=120)
            _, proxied, _ = http_call(
                "GET", f"http://{fsrv.url}/bench/one.bin?proxy=1",
                timeout=120)
            redirect_identical = (redirected and direct == small
                                  and proxied == small)
        finally:
            fsrv.stop()
            vs.stop()
            master.stop()

    return {
        "read_plane_mb": size_mb,
        "read_plane_single_mbps": round(zc_single, 1),
        "read_plane_single_buffered_mbps": round(buf_single, 1),
        "read_plane_speedup": round(zc_single / buf_single, 2),
        "read_plane_agg_clients": clients,
        "read_plane_agg_mbps": round(zc_agg, 1),
        "read_plane_agg_buffered_mbps": round(buf_agg, 1),
        "read_plane_bit_identical": True,  # hashed reads gate above
        # payload bytes that crossed the filer on the redirected GET:
        # the 302 body. 0 == the filer left the data path.
        "read_plane_redirect_proxied_bytes": proxied_on_redirect,
        # server hops the payload crosses: volume->client direct vs
        # volume->filer->client proxied
        "read_plane_redirect_payload_hops": 1 if redirected else 2,
        "read_plane_redirect_bit_identical": redirect_identical,
    }


def bench_replica_divergence_repair(n_writes: int = 10,
                                    deadline_s: float = 0.5) -> dict:
    """The divergence drill as numbers: writes issued while one
    replica leg is blackholed (netchaos proxy) must all ack on the
    sloppy quorum (zero failures), each missed leg becomes a journal
    hint, the first read on the lagging replica after the heal repairs
    in-line, and the drain settles every debt leaving the replicas
    bit-identical (raw needle records).

    Dark-window write latency is bounded by REPLICATE_DEADLINE_S (set
    to `deadline_s` here) until the peer breaker opens, then failing
    legs cost nothing — the p99 proves divergence never blocks the
    client. drain_s runs from the heal to an empty journal and
    includes the breaker's half-open wait (open_for=5s), the honest
    time-to-settle. SEAWEEDFS_TPU_BENCH_DIVERGENCE_WRITES overrides
    n_writes."""
    import tempfile

    from seaweedfs_tpu.client.wdclient import MasterClient
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.storage.file_id import parse_needle_id_cookie
    from seaweedfs_tpu.utils.httpd import http_call, http_json
    from tools.netchaos import ChaosProxy

    n_writes = int(os.environ.get(
        "SEAWEEDFS_TPU_BENCH_DIVERGENCE_WRITES", n_writes))

    def blob(url: str, vid: int, key: int) -> dict:
        return http_json("GET", f"http://{url}/admin/needle_blob"
                         f"?volumeId={vid}&key={key}")

    with tempfile.TemporaryDirectory() as d:
        master = MasterServer(volume_size_limit_mb=64)
        master.start()
        vs1 = VolumeServer([os.path.join(d, "v1")], master.url)
        vs1.start()
        peer_port = _free_port()
        proxy = ChaosProxy("127.0.0.1", peer_port).start()
        vs2 = VolumeServer([os.path.join(d, "v2")], master.url,
                           port=peer_port, advertise=proxy.url)
        vs2.start()
        mc = MasterClient(master.url, cache_ttl=0.0)
        vs1_direct = f"{vs1.http.host}:{vs1.http.port}"
        vs1.REPLICATE_DEADLINE_S = deadline_s
        try:
            payload = b"\x5a" * 4096
            a = mc.assign(replication="001")
            if a.get("error"):
                raise RuntimeError(f"assign failed: {a['error']}")
            st, _, _ = http_call("POST",
                                 f"http://{vs1_direct}/{a['fid']}",
                                 body=payload, timeout=30)
            if st != 201:
                raise RuntimeError(f"healthy write failed: {st}")

            proxy.set_fault(mode="blackhole")
            fids, dark = [], []
            failed = 0
            for i in range(n_writes):
                a = mc.assign(replication="001")
                if a.get("error"):
                    raise RuntimeError(f"assign failed: {a['error']}")
                t0 = time.perf_counter()
                st, _, _ = http_call(
                    "POST", f"http://{vs1_direct}/{a['fid']}",
                    body=payload, timeout=30)
                dark.append(time.perf_counter() - t0)
                if st != 201:
                    failed += 1
                else:
                    fids.append(a["fid"])
            hints = len(vs1.hint_journal.pending_for(proxy.url))

            proxy.set_fault(mode="pass")
            t_heal = time.perf_counter()
            # first read on the lagging replica: the 404 pulls the
            # needle from the healthy sibling in-line
            t0 = time.perf_counter()
            st, got, _ = http_call("GET",
                                   f"http://{proxy.url}/{fids[0]}",
                                   timeout=30)
            repair_ms = (time.perf_counter() - t0) * 1000
            if st != 200 or got != payload:
                raise RuntimeError(f"read repair failed: HTTP {st}")

            give_up = time.time() + 60
            while len(vs1.hint_journal) and time.time() < give_up:
                vs1.drain_hints()
                time.sleep(0.05)
            if len(vs1.hint_journal):
                raise RuntimeError("hint journal never drained")
            drain_s = time.perf_counter() - t_heal

            identical = True
            for fid in fids:
                vid = int(fid.split(",")[0])
                key, _ = parse_needle_id_cookie(fid.split(",", 1)[1])
                identical = identical and (
                    blob(vs1_direct, vid, key) == blob(proxy.url, vid,
                                                       key))
        finally:
            mc.stop()
            vs2.stop()
            vs1.stop()
            proxy.stop()
            master.stop()
    return {
        "divergence_writes": n_writes,
        "divergence_failed_writes": failed,
        "divergence_hints_journaled": hints,
        "divergence_dark_write_p99_ms": _p99_ms(dark),
        "divergence_read_repair_ms": round(repair_ms, 1),
        "divergence_drain_s": round(drain_s, 2),
        "divergence_deadline_ms": deadline_s * 1000,
        "divergence_bit_identical": identical,
    }


def run_device_child(timeout: float = DEVICE_CHILD_TIMEOUT) -> dict:
    """Run the device sub-bench once in a fresh interpreter (this
    process stays off JAX) and return its JSON: {"tpu_mbps", "device"}.
    Raises RuntimeError with the child's reason on any failure."""
    cmd = [sys.executable, os.path.abspath(__file__), "--tpu-probe"]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout)
    except subprocess.TimeoutExpired:
        raise RuntimeError(f"device child: timeout after {timeout}s")
    if proc.returncode != 0:
        tail = (proc.stderr or proc.stdout or "").strip()[-800:]
        raise RuntimeError(f"device child: rc={proc.returncode}: {tail}")
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            out = json.loads(line)
        except ValueError:
            continue
        if isinstance(out, dict) and "tpu_mbps" in out:
            return out
    raise RuntimeError("device child: rc=0 but no tpu_mbps JSON in "
                       f"stdout: {proc.stdout[-300:]!r}")


def bench_profiler_overhead(n_reads: int = 600,
                            concurrency: int = 8) -> dict:
    """Round-16 continuous-profiling cost: the telemetry-overhead read
    sweep again, but toggling the always-on wall-stack sampler
    (shipped default: 19 Hz) instead of the RED plane. The sampler's
    per-request cost is one module-global check in profiler.tag plus
    two thread-local dict stores when active; the sampling itself
    lives on a dedicated thread waking 19 times a second. The PERF.md
    round-16 claim is "within noise at the default rate"; the paired
    interleaved sweeps (ON/OFF/ON/OFF so CPU-frequency drift hits both
    arms) are the evidence."""
    import concurrent.futures
    import tempfile

    from seaweedfs_tpu.client import operation
    from seaweedfs_tpu.client.wdclient import MasterClient
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer

    with tempfile.TemporaryDirectory() as d:
        master = MasterServer(volume_size_limit_mb=64)
        master.start()
        vs = VolumeServer([d], master.url)
        vs.start()
        time.sleep(0.3)
        mc = MasterClient(master.url)
        try:
            fids = [operation.upload_data(
                mc, b"\xa5" * 4096, name=f"t{i}").fid
                for i in range(32)]

            def read_one(i):
                operation.read_data(mc, fids[i % len(fids)])

            def sweep() -> float:
                t0 = time.perf_counter()
                with concurrent.futures.ThreadPoolExecutor(
                        concurrency) as ex:
                    list(ex.map(read_one, range(n_reads)))
                return n_reads / (time.perf_counter() - t0)

            sweep()  # warm connections + page cache
            on_rps, off_rps = [], []
            for _ in range(2):
                if not vs.sampler.running:
                    vs.sampler.start()
                on_rps.append(sweep())
                vs.sampler.stop()
                off_rps.append(sweep())
            vs.sampler.start()
        finally:
            mc.stop()
            vs.stop()
            master.stop()
    on, off = max(on_rps), max(off_rps)
    return {
        "profiler_on_rps": round(on, 1),
        "profiler_off_rps": round(off, 1),
        "profiler_overhead_pct": round((off - on) / off * 100, 2)
        if off else 0.0,
    }


def bench_tenant_flood(duration_s: float = 1.0,
                       victim_rate: float = 40.0,
                       cap_rate: float = 50.0) -> dict:
    """Round-16 tenant-isolation drill at the governor seam: an
    aggressor tenant floods the write class as fast as a thread can
    submit while a victim tenant offers a modest paced write load.
    Both tenants share one QosGovernor (one node's admission control);
    the only knob that separates them is the per-(class, tenant) token
    bucket (`tenant_class_rates`). Two arms:

    - uncapped: no tenant buckets — the aggressor eats the adaptive
      concurrency limit and the victim sheds on `limit`;
    - capped: writes carry a per-tenant rate of `cap_rate` req/s — the
      aggressor is clipped to the cap and the victim (offering under
      the cap) keeps its admitted/s.

    The victim's admitted/s in the capped arm is the isolation floor
    the qos tests assert."""
    import threading as _threading

    from seaweedfs_tpu.qos import WRITE
    from seaweedfs_tpu.qos.governor import QosGovernor

    def arm(capped: bool) -> dict:
        gov = QosGovernor(initial_limit=32)
        if capped:
            gov.configure(tenant_class_rates={WRITE: cap_rate})
        stop = _threading.Event()
        counts = {"aggressor": 0, "victim": 0}

        def aggressor():
            while not stop.is_set():
                g = gov.admit(WRITE, tenant="aggressor")
                if g.ok:
                    counts["aggressor"] += 1
                    g.release()

        def victim():
            period = 1.0 / victim_rate
            nxt = time.perf_counter()
            while not stop.is_set():
                g = gov.admit(WRITE, tenant="victim")
                if g.ok:
                    counts["victim"] += 1
                    g.release()
                nxt += period
                delay = nxt - time.perf_counter()
                if delay > 0:
                    stop.wait(delay)

        threads = [
            _threading.Thread(target=aggressor, name="flood-aggressor"),
            _threading.Thread(target=victim, name="flood-victim")]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        time.sleep(duration_s)
        stop.set()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        return {k: round(v / dt, 1) for k, v in counts.items()}

    uncapped = arm(capped=False)
    capped = arm(capped=True)
    return {
        "flood_uncapped_aggressor_rps": uncapped["aggressor"],
        "flood_uncapped_victim_rps": uncapped["victim"],
        "flood_capped_aggressor_rps": capped["aggressor"],
        "flood_capped_victim_rps": capped["victim"],
    }


def bench_assign_flood(n_clients: int = 32, dark_s: float = 5.0,
                       edge_s: float = 1.0) -> dict:
    """Master-outage-tolerant writes: a concurrent PUT flood through
    the assign-lease lane vs the master-routed comparator across a
    master-dark window.

    `n_clients` writer threads flood 1KB PUTs for edge + dark + edge
    seconds while a netchaos proxy fronting the master blackholes it
    for the middle `dark_s`. The volume server keeps its direct
    heartbeat lane (grants/renewals continue), so the window models
    the client-visible master outage; true leader death is the chaos
    drill's beat (tests/test_chaos_drill.py). The leased lane mints
    fids from the holder's epoch-stamped range: zero failed writes and
    zero master dials inside the window. The assign_leases=False
    comparator pays a master round trip per write and craters for the
    duration — which is also where the master's assign CPU goes: on a
    live cluster, `tools/prof_collect.py --diff` before/after enabling
    leases shows the /dir/assign route frames draining out of the
    master's flamegraph (the grant path amortizes one Raft commit per
    LEASE_RANGE=4096 fids). Floors (tests/test_bench_floor.py):
    leased >= 2x comparator writes/s, zero leased dark-window
    failures, zero leased dark-window master calls, bit-identical
    stored bytes through both lanes.
    SEAWEEDFS_TPU_BENCH_FLOOD_{CLIENTS,DARK_S,EDGE_S} override
    sizing."""
    import tempfile
    import threading

    from seaweedfs_tpu.client import operation
    from seaweedfs_tpu.client.wdclient import MasterClient
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.utils.httpd import HttpError, http_call
    from seaweedfs_tpu.utils.resilience import Deadline, deadline_scope
    from tools.netchaos import ChaosProxy

    n_clients = int(os.environ.get("SEAWEEDFS_TPU_BENCH_FLOOD_CLIENTS",
                                   n_clients))
    dark_s = float(os.environ.get("SEAWEEDFS_TPU_BENCH_FLOOD_DARK_S",
                                  dark_s))
    edge_s = float(os.environ.get("SEAWEEDFS_TPU_BENCH_FLOOD_EDGE_S",
                                  edge_s))
    payload = b"\x5a\xa5" * 512  # 1KB
    duration = edge_s + dark_s + edge_s

    with tempfile.TemporaryDirectory() as d:
        master = MasterServer(volume_size_limit_mb=64)
        master.start()
        vs = VolumeServer([os.path.join(d, "v")], master.url)
        vs.start()
        proxy = ChaosProxy(master.http.host, master.http.port).start()
        vs_direct = f"{vs.http.host}:{vs.http.port}"

        def flood(mc) -> dict:
            """One lane's run: flood for `duration`, blackhole the
            proxy for the middle `dark_s`, count completions (stamped
            so the dark window is separable) and failures."""
            done: list[tuple[float, str]] = []
            failed = {"total": 0, "dark": 0}
            lock = threading.Lock()
            stop_at = time.monotonic() + duration
            window = {}

            def in_dark(t: float) -> bool:
                return window.get("t0", 1e18) <= t <= \
                    window.get("t1", 1e18)

            def worker():
                while time.monotonic() < stop_at:
                    try:
                        # per-op deadline: a dark-window master dial
                        # fails fast instead of eating the whole run
                        with deadline_scope(Deadline.after(1.0)):
                            a = mc.assign()
                            if not a.get("fid") or a.get("error"):
                                raise ConnectionError(str(a))
                            operation.upload_to(a["fid"], a["url"],
                                                payload)
                    except (ConnectionError, HttpError, OSError):
                        t = time.monotonic()
                        with lock:
                            failed["total"] += 1
                            failed["dark"] += in_dark(t)
                        continue
                    t = time.monotonic()
                    with lock:
                        done.append((t, a["fid"]))

            threads = [threading.Thread(target=worker,
                                        name=f"flood-writer-{i}")
                       for i in range(n_clients)]
            t0 = time.monotonic()
            for t in threads:
                t.start()
            time.sleep(edge_s)
            window["t0"] = time.monotonic()
            calls0 = mc.master_calls
            proxy.set_fault(mode="blackhole")
            time.sleep(dark_s)
            window["t1"] = time.monotonic()
            calls1 = mc.master_calls
            proxy.set_fault(mode="pass")
            for t in threads:
                t.join(timeout=duration + 30)
            wall = time.monotonic() - t0
            dark_writes = sum(1 for t, _ in done if in_dark(t))
            return {"wps": round(len(done) / wall, 1),
                    "writes": len(done),
                    "dark_writes": dark_writes,
                    "failed": failed["total"],
                    "failed_dark": failed["dark"],
                    "master_calls_dark": calls1 - calls0,
                    "fids": [fid for _, fid in done]}

        leased = MasterClient(proxy.url, cache_ttl=0.0)
        legacy = MasterClient(proxy.url, cache_ttl=0.0,
                              assign_leases=False)
        try:
            # warm: grow the volume, let the heartbeat grant land, and
            # prime the client's lease directory so the first dark-
            # window assign already knows its holders
            a = leased.assign()
            if a.get("error"):
                raise RuntimeError(f"warm assign failed: {a['error']}")
            deadline = time.time() + 15
            while time.time() < deadline:
                with vs._lease_lock:
                    if vs._leases:
                        break
                time.sleep(0.05)
            else:
                raise RuntimeError("holder never received a lease")
            if not leased.assign().get("lease_epoch"):
                raise RuntimeError("lease lane never engaged")

            leased_run = flood(leased)
            legacy_run = flood(legacy)

            # bit identity across the lanes: the same payload through a
            # holder-minted fid and a master-minted fid reads back
            # identical (and a sample of the dark-window writes is
            # durable on disk, not just acked)
            la, ma = leased.assign(), legacy.assign()
            operation.upload_to(la["fid"], la["url"], payload)
            operation.upload_to(ma["fid"], ma["url"], payload)
            identical = True
            for fid in (la["fid"], ma["fid"],
                        *leased_run["fids"][-20:]):
                status, body, _ = http_call(
                    "GET", f"http://{vs_direct}/{fid}", timeout=10)
                identical = identical and status == 200 \
                    and body == payload
            lease_assigns = leased.lease_assigns
            lease_fallbacks = leased.lease_fallbacks
        finally:
            leased.stop()
            legacy.stop()
            vs.stop()
            proxy.stop()
            master.stop()

    return {
        "assign_flood_clients": n_clients,
        "assign_flood_dark_s": dark_s,
        "assign_flood_leased_wps": leased_run["wps"],
        "assign_flood_legacy_wps": legacy_run["wps"],
        "assign_flood_speedup": round(
            leased_run["wps"] / max(legacy_run["wps"], 0.1), 2),
        "assign_flood_leased_failed": leased_run["failed"],
        "assign_flood_leased_failed_dark": leased_run["failed_dark"],
        "assign_flood_leased_dark_writes": leased_run["dark_writes"],
        "assign_flood_leased_master_calls_dark":
            leased_run["master_calls_dark"],
        "assign_flood_legacy_failed": legacy_run["failed"],
        "assign_flood_legacy_dark_writes": legacy_run["dark_writes"],
        "assign_flood_lease_assigns": lease_assigns,
        "assign_flood_lease_fallbacks": lease_fallbacks,
        "assign_flood_bit_identical": identical,
    }


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if "--tpu-probe" in argv:
        # Child mode: the device measurement and the device it ran
        # on, one JSON line.  It refuses the CPU backend even when the
        # CPU was asked for by name: this number is a device metric.
        from seaweedfs_tpu.parallel import mesh as mesh_mod
        mesh_mod.ensure_compile_cache()
        device = mesh_mod.require_accelerator("bench.py device sub-bench")
        if device["platform"] == "cpu":
            print("bench.py device sub-bench: the backend is the CPU; "
                  "a CPU timing is not a device metric", file=sys.stderr)
            return 1
        print(json.dumps({"tpu_mbps": bench_tpu(), "device": device}))
        return 0
    if "--filer-child" in argv:
        # Child mode for bench_filer_streaming_rss: host ONLY the
        # filer here so /proc/<pid>/status VmHWM measures the filer's
        # write-path memory, not the client's or the volume server's.
        # Args: master_url chunk_size streaming(0|1). Exits when the
        # parent closes stdin.
        import seaweedfs_tpu.server.filer_server as fsrv
        from seaweedfs_tpu.server.filer_server import FilerServer
        i = argv.index("--filer-child")
        fsrv.CHUNK_SIZE = int(argv[i + 2])
        fs = FilerServer(argv[i + 1])
        fs.streaming_ingest = argv[i + 3] == "1"
        fs.start()
        print(json.dumps({"url": fs.url, "pid": os.getpid()}),
              flush=True)
        sys.stdin.read()
        fs.stop()
        return 0
    try:
        dev = run_device_child()  # first: no accelerator -> fail fast
    except RuntimeError as e:
        print(f"bench.py: {e}", file=sys.stderr)
        return 1
    tpu = float(dev["tpu_mbps"])
    cpu = bench_cpu()
    e2e = bench_volume_encode()  # host-side secondary metrics
    e2e.update(bench_scrub())  # CPU-only integrity read path
    e2e.update(bench_degraded_read())  # hedged EC read tail + hot cache
    e2e.update(bench_conn_hold())  # 10k-conn selector edge hold
    e2e.update(bench_filer_put())  # parallel chunk-upload write path
    e2e.update(bench_replicated_write())  # concurrent replica fan-out
    e2e.update(bench_overload())  # QoS admission under overload
    e2e.update(bench_telemetry_overhead())  # RED+sketch plane cost
    e2e.update(bench_profiler_overhead())  # wall-stack sampler cost
    e2e.update(bench_tenant_flood())  # per-tenant class-rate isolation
    e2e.update(bench_repair_network())  # partial-column repair ingress
    e2e.update(bench_lrc_repair())  # LRC vs RS single-shard repair cost
    e2e.update(bench_filer_streaming_rss())  # bounded-memory ingest
    e2e.update(bench_read_plane())  # sendfile GETs + volume redirects
    e2e.update(bench_replica_divergence_repair())  # hinted-handoff drill
    e2e.update(bench_filer_ops())  # sharded namespace scale-out
    e2e.update(bench_shard_rebalance())  # live hot-dir migration
    e2e.update(bench_tiering())  # temperature-driven tier autopilot
    e2e.update(bench_assign_flood())  # master-dark leased PUT flood
    print(json.dumps({
        "metric": "rs_10_4_encode_throughput",
        "value": round(tpu, 1),
        "unit": "MB/s",
        "vs_baseline": round(tpu / cpu, 2),
        "backend": dev["device"]["platform"],
        "device": dev["device"],
        "cpu_mbps": round(cpu, 1),
        **e2e,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
