"""The one general load generator: fills volumes, prepares the state a
configuration asks for, and drives a window of closed-loop workers.

Everything that tells one cell from another is data: a configuration
file (``configs/<name>.json``: the code its files are held to, ``code``,
with its ``family``; whether the volumes are sealed; which shards are
lost; ``generate_body``, the extra keys of the window's seal requests, of
which ``code`` is the code the program is asked for, in the window and in
``prepare()``'s ``ec.encode`` alike) and a traffic file
(``traffic/<name>.json``: the operation, how many volumes and objects,
how many workers, which shapes to warm).  A new cell is new files: a
sealed read of another geometry or of another code family among them.

All inputs come from ``--seed``.  Every seed gives the same SET of object
sizes and the same number of objects per volume; the bytes, the cookies
and the order differ.  A seal worker's sampled look at the shard files
(which spans of which call) comes from the seed too.
"""

from __future__ import annotations

import hashlib
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.harness import Cluster, log

MIB = 1 << 20
FILL_THREADS = 8


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


class Corpus:
    """What was uploaded: fid -> (sha256, size), and the volumes."""

    def __init__(self):
        self.objects: dict[str, tuple[str, int]] = {}
        self.vids: list[int] = []
        self.lock = threading.Lock()

    def fids(self) -> list[str]:
        return sorted(self.objects)


def fill(cluster: Cluster, traffic: dict, seed: int) -> Corpus:
    """Grow ``volumes`` volumes and put the same objects into each:
    ``fill`` lists size classes (``bytes``, ``count`` per volume), laid
    down in a seeded order through ``client.operation.upload_to`` with
    fids minted here (key = position, cookie from the seed), so that
    every volume gets exactly its share — the master's assign picks a
    volume at random (PERF.md, PR 22)."""
    from seaweedfs_tpu.client import operation
    n_vol = traffic["volumes"]
    grown = cluster.http(
        "POST", cluster.master + f"/vol/grow?count={n_vol}")
    vids = sorted(grown["volume_ids"])
    if len(vids) != n_vol:
        raise RuntimeError(f"/vol/grow gave {grown}")
    corpus = Corpus()
    corpus.vids = vids
    sizes = [c["bytes"] for c in traffic["fill"] for _ in range(c["count"])]

    def fill_part(vid: int, part: int, parts: int) -> None:
        plan = [int(s) for s in rng_for(seed, 1, vid).permutation(sizes)]
        rng = rng_for(seed, 2, vid, part)
        for pos in range(part, len(plan), parts):
            data = rng.bytes(plan[pos])
            cookie = int(rng.integers(1, 1 << 32))
            fid = f"{vid},{pos + 1:x}{cookie:08x}"
            operation.upload_to(fid, cluster.volume, data)
            digest = hashlib.sha256(data).hexdigest()
            with corpus.lock:
                corpus.objects[fid] = (digest, len(data))

    parts = max(1, FILL_THREADS // n_vol)
    t0 = time.monotonic()
    with ThreadPoolExecutor(max_workers=FILL_THREADS) as pool:
        futs = [pool.submit(fill_part, vid, p, parts)
                for vid in vids for p in range(parts)]
        for f in futs:
            f.result()
    dt = time.monotonic() - t0
    total = sum(size for _, size in corpus.objects.values())
    log(f"[fill] {len(corpus.objects)} objects, {total / MIB:.1f} MiB over "
        f"volumes {vids} in {dt:.1f}s ({total / MIB / dt:.0f} MiB/s)")
    return corpus


def asked_code(config: dict) -> str:
    """The code the program is ASKED for: ``generate_body.code``, the
    spec string of a seal request (``""``: the server's default).  What
    the files are HELD to is the configuration's ``code`` block; the two
    are two keys on purpose, and the controls live on their
    disagreement."""
    return config.get("generate_body", {}).get("code", "")


def shard_paths(cluster: Cluster, vid: int, total: int) -> list[str]:
    return [os.path.join(cluster.voldir, f"{vid}.ec{s:02d}")
            for s in range(total)]


def prepare(cluster: Cluster, config: dict, corpus: Corpus) -> None:
    """Bring the volumes into the state the configuration names:
    ``volumes`` (left as they are, to be sealed over and over) or
    ``sealed`` (``ec.encode`` through the shell under the asked code,
    ``asked_code``; then the configuration's lost shards unmounted and
    their files deleted, caches cleared).  How many shard files there are
    comes from the STATED code, ``code.data_shards + code.parity_shards``:
    9 or 16 as well as 14."""
    if config["state"] != "sealed":
        return
    from seaweedfs_tpu.shell.commands import ShellContext
    total = config["code"]["data_shards"] + config["code"]["parity_shards"]
    sh = ShellContext(cluster.master, use_grpc=False)
    sh.lock()
    t0 = time.monotonic()
    for vid in corpus.vids:
        sh.ec_encode(vid=vid, code=asked_code(config))
    sh.unlock()
    log(f"[prepare] ec.encode -code {asked_code(config)!r} of volumes "
        f"{corpus.vids}: {time.monotonic() - t0:.2f}s")
    lost = list(config.get("lost_shards", []))
    for vid in corpus.vids:
        if lost:
            cluster.http("POST", cluster.volume + "/admin/ec/unmount",
                         {"volume_id": vid, "shard_ids": lost})
            for s in lost:
                os.remove(shard_paths(cluster, vid, total)[s])
        st = cluster.http("GET", cluster.volume + "/status")
        bits = 0
        for e in st.get("ec_shards", []):
            if e["id"] == vid:
                bits |= e["ec_index_bits"]
        mounted = {s for s in range(total) if bits & (1 << s)}
        if mounted != set(range(total)) - set(lost):
            raise RuntimeError(
                f"volume {vid}: mounted shards are {sorted(mounted)}, "
                f"wanted all but {lost}")
    cache = {"clear": True}
    if config.get("needle_cache_bytes") is not None:
        # rehearsals only: a tiny corpus needs a tiny cache to stay the
        # same multiple of it; the cells leave the server's default
        cache["capacity_bytes"] = config["needle_cache_bytes"]
    cluster.http("POST", cluster.volume + "/admin/cache", cache)
    log(f"[prepare] shards {lost} of volumes {corpus.vids} unmounted and "
        "deleted; needle cache cleared")


# ---- the window ----

class Window:
    """Closed-loop workers released together at ``t0``; each starts no new
    operation after ``t0 + seconds`` and finishes the one in flight."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.records: list[list[tuple]] = []
        self.t0 = 0.0
        self.t_end = 0.0
        self.t_drained = 0.0

    def run(self, workers: list) -> None:
        """``workers``: callables ``(window, records)``, each a loop that
        ends itself at ``window.t_end``."""
        self.records = [[] for _ in workers]
        gate = threading.Barrier(len(workers) + 1)

        def body(fn, rec):
            gate.wait()
            fn(self, rec)

        with ThreadPoolExecutor(max_workers=len(workers)) as pool:
            futs = [pool.submit(body, fn, rec)
                    for fn, rec in zip(workers, self.records)]
            # set before the gate opens, so that every worker reads it
            self.t0 = time.monotonic() + 0.05
            self.t_end = self.t0 + self.seconds
            gate.wait()
            for f in futs:
                f.result()
        self.t_drained = time.monotonic()

    def all_records(self) -> list[tuple]:
        return [r for recs in self.records for r in recs]


def _sleep_until(t: float) -> None:
    d = t - time.monotonic()
    if d > 0:
        time.sleep(d)


SAMPLE_SPANS = 16          # per call, over all 14 shard files
SAMPLE_BYTES = 64 << 10


def seal_workers(cluster: Cluster, config: dict, traffic: dict,
                 corpus: Corpus, seed: int, stream: int) -> list:
    """One worker per volume: ``POST /admin/ec/generate`` again and
    again, each call a seal of a volume that has no shards: BEFORE a
    call, outside its timed span (in the sealer's pause, where the
    traffic has a period), whatever shard files the call before or the
    warm-up left are deleted through the server (``POST
    /admin/ec/delete_shards``, which takes ``.ecx`` and ``.vif`` with
    the last shard) and seen to be gone.  What a call would otherwise pay
    is the machine's disk REPLACING the ~0.75 GiB the last call left
    (45-49% of a call on the chip machine's 9p disk, and most of the
    cells' run-to-run spread: PERF.md section 6, PR 34), which is the
    price of re-sealing ONE volume (the configurations'
    ``distinct_volumes`` reduction) and not of a seal.  After each reply,
    also outside the timed span: the shard files, ``.ecx`` and ``.vif``
    are looked at (a stat each): every one in place, of the size the
    ``.dat`` gives, and a file other than the one the previous call left
    (gone before the call; where the delete left it, by its inode: the
    program writes ``.tmp`` names and renames); and ``SAMPLE_SPANS``
    spans of ``SAMPLE_BYTES`` at offsets drawn from the seed are read
    from all the files and kept as one sha256 each, for the reference to
    be held against once the window has closed (every call of the window
    is compared so, not only the last, whose files stay).  With
    ``period_seconds`` in the traffic file a worker starts its i-th call
    ``i * period`` after the window opened, or when the one before is
    done and cleared if that is later: a sealer that is given work at a
    rate, so that a run writes a few GiB and not all the disk takes in
    the time.  A record is (start, end, .dat bytes, error or None, stale,
    [(volume, offset, length, digest)], seconds the call started late,
    seconds the delete before it took, the reply's ``pipeline``: the
    program's own account of the call, logged and read by no metric)."""
    from benchmark import reference
    code = config["code"]
    k, total = code["data_shards"], code["data_shards"] + code["parity_shards"]
    body_extra = dict(config.get("generate_body", {}))
    period = traffic.get("period_seconds", 0.0)

    def make(vid: int):
        base = os.path.join(cluster.voldir, str(vid))
        shards = shard_paths(cluster, vid, total)
        last_inodes: dict[str, int] = {}
        rng = rng_for(seed, stream, vid)

        def sample(dat_size: int) -> list[tuple[int, int, int, str]]:
            want = reference.shard_file_size(
                dat_size, k, code["large_block_bytes"],
                code["small_block_bytes"])
            n = min(SAMPLE_BYTES, code["small_block_bytes"])
            out = []
            fds = [os.open(p, os.O_RDONLY) for p in shards]
            try:
                for slot in rng.integers(0, want // n, size=SAMPLE_SPANS):
                    off = int(slot) * n
                    h = hashlib.sha256()
                    for fd in fds:
                        h.update(os.pread(fd, n, off))
                    out.append((vid, off, n, h.hexdigest()))
            finally:
                for fd in fds:
                    os.close(fd)
            return out

        def look(dat_size: int) -> bool:
            """True when something is stale, short or missing."""
            want = reference.shard_file_size(
                dat_size, k, code["large_block_bytes"],
                code["small_block_bytes"])
            stale = False
            for p in shards:
                try:
                    st = os.stat(p)
                except FileNotFoundError:
                    return True
                if st.st_size != want or last_inodes.get(p) == st.st_ino:
                    stale = True
                last_inodes[p] = st.st_ino
            for ext in (".ecx", ".vif"):
                if not os.path.exists(base + ext):
                    stale = True
            return stale

        def clear() -> float:
            """The volume as a first seal finds it: no shard files.
            Returns the seconds that took."""
            if not any(os.path.exists(p) for p in shards):
                return 0.0
            t = time.monotonic()
            try:
                cluster.http("POST", cluster.volume
                             + "/admin/ec/delete_shards",
                             {"volume_id": vid,
                              "shard_ids": list(range(total))})
            except Exception as e:  # noqa: BLE001 — the next look tells
                log(f"[seal] volume {vid}: delete_shards failed: {e}")
            for p in shards:
                if not os.path.exists(p):
                    last_inodes.pop(p, None)
            return time.monotonic() - t

        def loop(window: Window, rec: list) -> None:
            due = window.t0
            # a call that was cleared for is made: the files the window
            # leaves are a call's, whole
            while due < window.t_end and time.monotonic() < window.t_end:
                cleared_s = clear()
                _sleep_until(due)
                start = time.monotonic()
                late, due = start - due, due + period
                err = None
                stale = False
                sampled = []
                dat_size = os.path.getsize(base + ".dat")
                reply = None
                try:
                    reply = cluster.http("POST", cluster.volume
                                         + "/admin/ec/generate",
                                         {"volume_id": vid, **body_extra})
                except Exception as e:  # noqa: BLE001 — counted as failed
                    err = f"{type(e).__name__}: {e}"
                end = time.monotonic()
                if err is None:
                    stale = look(dat_size)
                    if not stale:
                        sampled = sample(dat_size)
                rec.append((start, end, dat_size, err, stale, sampled,
                            late, cleared_s, (reply or {}).get("pipeline")))
            # a sealer with no turn left stays to the close: the window,
            # and a trace of it, is as long as was asked for
            _sleep_until(window.t_end)

        return loop

    return [make(vid) for vid in corpus.vids]


def read_workers(cluster: Cluster, traffic: dict, corpus: Corpus,
                 seed: int, stream: int) -> list:
    """``workers`` readers, each drawing fids uniformly from its own
    seeded stream, through ``client.operation.read_data`` with one fresh
    ``MasterClient`` between them.  A record is (start, end, size, error
    or None, wrong): ``wrong`` when the sha256 of what came back is not
    that of what was uploaded."""
    from seaweedfs_tpu.client import operation
    from seaweedfs_tpu.client.wdclient import MasterClient
    mc = MasterClient(cluster.master)
    fids = corpus.fids()
    if traffic.get("keys", "uniform") != "uniform":
        raise ValueError(f"unknown key distribution {traffic['keys']!r}")

    def make(idx: int):
        rng = rng_for(seed, stream, idx)

        def loop(window: Window, rec: list) -> None:
            _sleep_until(window.t0)
            while True:
                # a block of draws at a time: cheap, and the same stream
                for i in rng.integers(0, len(fids), size=256):
                    fid = fids[int(i)]
                    start = time.monotonic()
                    if start >= window.t_end:
                        return
                    err, wrong, size = None, False, 0
                    try:
                        data = operation.read_data(mc, fid)
                    except Exception as e:  # noqa: BLE001 — counted
                        err = f"{type(e).__name__}: {e}"
                    end = time.monotonic()
                    if err is None:
                        size = len(data)
                        wrong = hashlib.sha256(data).hexdigest() \
                            != corpus.objects[fid][0]
                    rec.append((start, end, size, err, wrong))

        return loop

    return [make(i) for i in range(traffic["workers"])]


def make_workers(cluster: Cluster, config: dict, traffic: dict,
                 corpus: Corpus, seed: int, stream: int) -> list:
    op = traffic["op"]
    if op == "seal":
        if traffic["workers"] != len(corpus.vids):
            raise ValueError("a seal mix has one worker per volume")
        return seal_workers(cluster, config, traffic, corpus, seed,
                            stream)
    if op == "read":
        return read_workers(cluster, traffic, corpus, seed, stream)
    raise ValueError(f"unknown operation {op!r}")
