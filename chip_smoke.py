#!/usr/bin/env python3
"""Chip smoke: the served erasure-coding path, end to end, on the chip.

    python chip_smoke.py                 # one chip, a >= 1 GiB volume
    python chip_smoke.py --chips 4       # four chips: the batch mesh only
    JAX_PLATFORMS=cpu python chip_smoke.py --volume-mib 16   # rehearsal

This (parent) process is a load generator and checker.  It NEVER imports
jax: the chip belongs to the one volume-server process it starts through
the CLI (`python -m seaweedfs_tpu.cli volume ... -ecBatcher`), and after
that server has exited, to one short child that runs the kernels.

Phases, each under a named guard: preflight (native codec built here,
ports, disk), servers, device (the volume server's own report of what
its coder dispatches to), load, encode (`ec.encode` through the shell,
shard files against a CpuCoder reference and the scalar GF tables),
serve (healthy reads), degrade (three shards gone, reads reconstructed),
restore (repair queue or `ec.rebuild`, whichever gets there), batcher
(nothing ran on the CPU behind the curtain), kernels (the two device
coders, jax and mesh, run in a child of their own, bit-identical with
CpuCoder, on the device the server reported).

On success the last line of stdout is exactly
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
On any failure: the phase's name, the exception, the last 50 lines of
each server log — all on stdout — every child stopped, exit code 1, and
no such last line.  A platform other than "tpu" (or a size below
256 MiB) fails its check there and then; the run goes on through the
remaining phases only so that a CPU rehearsal exercises them.
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.abspath(__file__))
MIB = 1 << 20
MIN_VOLUME_MIB = 256         # below this a run is a rehearsal, not a pass
READY_BUDGET_S = 420.0       # server start-up incl. accelerator backend
RESTORE_BUDGET_S = 420.0
VICTIM_SHARDS = (0, 3, 11)   # two data shards and one parity shard
FOUR_CHIP_VOLUMES = 8

KERNELS_CHILD = r"""
import json, sys
import numpy as np
seed, row_bytes = int(sys.argv[1]), int(sys.argv[2])
from seaweedfs_tpu.models.coder import DEVICE_CODERS, make_coder
from seaweedfs_tpu.ops.rs_cpu import CpuCoder
from seaweedfs_tpu.parallel import mesh as mesh_mod
rng = np.random.default_rng(seed)
data = rng.integers(0, 256, (10, row_bytes), dtype=np.uint8)
want = CpuCoder().encode_array(data)
out = {"device": None, "identical": {}}
for name in DEVICE_CODERS:
    got = np.asarray(make_coder(name).encode_array(data))
    out["identical"][name] = bool(np.array_equal(got, want))
out["device"] = mesh_mod.device_report()
print("KERNELS " + json.dumps(out), flush=True)
"""


def log(msg: str) -> None:
    print(msg, flush=True)


class PhaseFailed(Exception):
    pass


class Smoke:
    def __init__(self, args):
        self.seed = args.seed
        self.chips = args.chips
        self.volume_mib = args.volume_mib
        self.procs: list[tuple[str, subprocess.Popen, str]] = []
        self.workdir = ""
        self.seconds: dict[str, float] = {}
        # checks that failed but let the run go on (device, size)
        self.soft_failures: list[str] = []
        self.device: dict | None = None
        self.fids: dict[str, tuple[str, int]] = {}   # fid -> (sha256, size)
        self.sample: list[str] = []
        self.shard_sha: dict[int, list[str]] = {}    # vid -> 14 sha256
        self.master = ""
        self.volume = ""
        self.voldir = ""
        self.stopped = False

    # ---- plumbing ----
    @contextlib.contextmanager
    def phase(self, name: str):
        log(f"[{name}] start")
        t0 = time.monotonic()
        try:
            yield
        except BaseException as e:
            self.seconds[name] = time.monotonic() - t0
            log(f"[{name}] FAILED after {self.seconds[name]:.1f}s: "
                f"{type(e).__name__}: {e}")
            log(traceback.format_exc())
            raise PhaseFailed(name) from e
        self.seconds[name] = time.monotonic() - t0
        verdict = "FAILED its check, the run goes on" \
            if name in self.soft_failures else "ok"
        log(f"[{name}] {verdict} {self.seconds[name]:.2f}s")

    def http(self, method: str, url: str, body=None, timeout: float = 60):
        from seaweedfs_tpu.utils.httpd import http_json
        return http_json(method, f"http://{url}", body, timeout=timeout)

    def spawn(self, name: str, argv: list[str]) -> subprocess.Popen:
        logpath = os.path.join(self.workdir, f"{name}.log")
        logf = open(logpath, "wb")
        # the environment goes to the child as it is: no JAX_PLATFORMS,
        # no XLA_FLAGS, no compile-cache variable is set or unset here
        proc = subprocess.Popen(
            [sys.executable, "-u", "-m", "seaweedfs_tpu.cli", *argv],
            cwd=REPO, stdout=logf, stderr=subprocess.STDOUT,
            stdin=subprocess.DEVNULL, start_new_session=True)
        logf.close()
        self.procs.append((name, proc, logpath))
        log(f"[servers] {name}: pid {proc.pid}: "
            f"python -u -m seaweedfs_tpu.cli {' '.join(argv)}")
        return proc

    @staticmethod
    def describe_exit(proc: subprocess.Popen) -> str:
        rc = proc.returncode
        if rc is None:
            return "still running"
        if rc < 0:
            try:
                signame = signal.Signals(-rc).name
            except ValueError:
                signame = "?"
            return f"killed by signal {-rc} ({signame})"
        return f"exited with code {rc}"

    def check_alive(self) -> None:
        for name, proc, _ in self.procs:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"{name} server {self.describe_exit(proc)}")

    def wait_ready(self, name: str, url: str, path: str) -> None:
        deadline = time.monotonic() + READY_BUDGET_S
        last = ""
        while time.monotonic() < deadline:
            self.check_alive()
            try:
                self.http("GET", url + path, timeout=5)
                return
            except Exception as e:  # noqa: BLE001 — polled until ready
                last = f"{type(e).__name__}: {e}"
            time.sleep(0.25)
        raise TimeoutError(f"{name} not ready at {url}{path} after "
                           f"{READY_BUDGET_S:.0f}s (last: {last})")

    def stop_servers(self) -> None:
        """SIGTERM volume first (its draining heartbeat wants a master),
        then the master; SIGKILL the process group of whatever is left.
        Returns only when every child has EXITED — the chip is released
        when its process ends, not when it is signalled."""
        if self.stopped:
            return
        self.stopped = True
        for name, proc, _ in reversed(self.procs):
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
                try:
                    proc.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    log(f"[stop] {name} ignored SIGTERM for 60s; killing")
            if proc.poll() is None:
                with contextlib.suppress(ProcessLookupError):
                    os.killpg(proc.pid, signal.SIGKILL)
                proc.wait(timeout=30)
            log(f"[stop] {name} {self.describe_exit(proc)}")

    def print_log_tails(self) -> None:
        for name, proc, path in self.procs:
            log(f"----- last 50 lines of {name}.log "
                f"({self.describe_exit(proc)}) -----")
            try:
                with open(path, "r", errors="replace") as f:
                    for line in f.readlines()[-50:]:
                        log(line.rstrip("\n"))
            except OSError as e:
                log(f"(cannot read {path}: {e})")
        log("----- end of server logs -----")

    # ---- phases ----
    def preflight(self) -> None:
        log(f"[preflight] python {sys.version.split()[0]} at "
            f"{sys.executable}; repo {REPO}; cpus {os.cpu_count()}")
        log("[preflight] environment: " + json.dumps({
            k: os.environ.get(k) for k in
            ("JAX_PLATFORMS", "XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR",
             "TMPDIR", "HOME")}))
        if not os.path.isdir(os.path.join(REPO, "seaweedfs_tpu")):
            raise RuntimeError(
                f"{REPO} holds no seaweedfs_tpu package: chip_smoke.py "
                "runs from the root of a checkout")
        sys.path.insert(0, REPO)
        try:
            cxx = subprocess.run(["g++", "--version"], capture_output=True,
                                 text=True, timeout=30)
            cxx_line = (cxx.stdout or cxx.stderr).splitlines()[0]
        except (OSError, subprocess.SubprocessError, IndexError) as e:
            cxx_line = f"g++ not runnable ({type(e).__name__}: {e})"
        from seaweedfs_tpu.native import rs_native
        info = rs_native.rebuild()
        log(f"[preflight] native codec: compiler {cxx_line!r}; flags "
            f"{' '.join(info['flags'])}; built here: {info['built']}; "
            f"gf_impl_name {info['impl']}")
        if not info["ok"]:
            raise RuntimeError(
                "native codec unavailable — no compiler and no loadable "
                f"library ({info['error']}); refusing to crawl on the "
                "pure-Python CRC32C / numpy GF fallbacks")
        from seaweedfs_tpu.utils import crc
        if crc.crc32c(b"123456789") != 0xE3069283:
            raise RuntimeError("native crc32c gives a wrong check value")

        self.workdir = tempfile.mkdtemp(prefix="chip_smoke_")
        self.voldir = os.path.join(self.workdir, "vol")
        os.makedirs(self.voldir)
        os.makedirs(os.path.join(self.workdir, "meta"))
        need = (4 if self.chips == 1 else 8) * self.volume_mib * MIB \
            + 256 * MIB
        free = shutil.disk_usage(self.workdir).free
        log(f"[preflight] work directory {self.workdir}: "
            f"{free / MIB:.0f} MiB free, need ~{need / MIB:.0f} MiB "
            f"(volume + 14 shards + rebuilt copies)")
        if free < need:
            raise RuntimeError(
                f"not enough disk under {self.workdir}: {free / MIB:.0f} "
                f"MiB free < {need / MIB:.0f} MiB needed")
        ports = []
        socks = []
        for _ in range(2):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            socks.append(s)
            ports.append(s.getsockname()[1])
        for s in socks:
            s.close()
        self.master = f"127.0.0.1:{ports[0]}"
        self.volume = f"127.0.0.1:{ports[1]}"
        log(f"[preflight] ports: master {ports[0]}, volume {ports[1]}")
        if self.volume_mib < MIN_VOLUME_MIB:
            self.soft_fail(
                "size", f"--volume-mib {self.volume_mib} is below "
                f"{MIN_VOLUME_MIB}: a rehearsal, never a pass")

    def soft_fail(self, name: str, why: str) -> None:
        self.soft_failures.append(name)
        log(f"[{name}] FAILED: {why} — going on through the remaining "
            "phases; the run exits non-zero")

    def start_servers(self) -> None:
        mport = self.master.rsplit(":", 1)[1]
        vport = self.volume.rsplit(":", 1)[1]
        # upstream's default volume size limit (30000 MB): the load
        # fills volumes only as far as --volume-mib says
        self.spawn("master", [
            "master", "-port", mport, "-mdir",
            os.path.join(self.workdir, "meta"),
            "-volumeSizeLimitMB", "30000"])
        self.wait_ready("master", self.master, "/cluster/status")
        self.spawn("volume", [
            "volume", "-port", vport, "-dir", self.voldir, "-max", "16",
            "-mserver", self.master, "-ecBatcher"])
        self.wait_ready("volume", self.volume, "/status")
        deadline = time.monotonic() + 60
        while True:
            topo = self.http("GET", self.master + "/dir/status")["Topology"]
            nodes = [n for dc in topo.get("data_centers", [])
                     for r in dc.get("racks", []) for n in r.get("nodes", [])]
            if nodes:
                break
            if time.monotonic() > deadline:
                raise TimeoutError("volume server never joined the master")
            self.check_alive()
            time.sleep(0.25)

    def check_device(self) -> None:
        status = self.http("GET", self.volume + "/status")
        batcher = self.http("GET", self.volume + "/admin/ec/batcher")
        dev = status.get("EcDevice")
        log(f"[device] volume server reports EcDevice={json.dumps(dev)}; "
            f"batcher device={json.dumps(batcher.get('device'))} "
            f"mesh_devices={batcher.get('mesh_devices')} "
            f"compile_cache_dir={batcher.get('compile_cache_dir')}")
        if not dev or dev != batcher.get("device"):
            raise RuntimeError(
                "the volume server's status and its batcher disagree "
                f"about the coder's device: {dev} vs "
                f"{batcher.get('device')}")
        self.device = dev
        if dev["count"] != self.chips or \
                batcher.get("mesh_devices") != self.chips:
            raise RuntimeError(
                f"--chips {self.chips} but the coder dispatches to "
                f"{dev['count']} device(s) (mesh_devices "
                f"{batcher.get('mesh_devices')})")
        if dev["platform"] != "tpu":
            self.soft_fail(
                "device", f"the coder's platform is {dev['platform']!r} "
                f"({dev['device_kind']}), not 'tpu'")

    # ---- load ----
    def _upload_all(self, blobs, assign_leases: bool = True) -> None:
        """Upload (bytes) objects through client.operation.upload_data,
        8 at a time; records sha256 + size per fid."""
        from seaweedfs_tpu.client import operation
        from seaweedfs_tpu.client.wdclient import MasterClient
        mc = MasterClient(self.master, assign_leases=assign_leases)
        lock = threading.Lock()

        def one(data: bytes):
            res = operation.upload_data(mc, data)
            with lock:
                self.fids[res.fid] = (hashlib.sha256(data).hexdigest(),
                                      len(data))
        with ThreadPoolExecutor(max_workers=8) as pool:
            for f in [pool.submit(one, b) for b in blobs]:
                f.result()

    def bytes_per_volume(self) -> dict[int, int]:
        out: dict[int, int] = {}
        for fid, (_, size) in self.fids.items():
            vid = int(fid.split(",")[0])
            out[vid] = out.get(vid, 0) + size
        return out

    def load(self) -> None:
        import numpy as np
        rng = np.random.default_rng(self.seed)
        target = self.volume_mib * MIB
        n_small = 300 if self.volume_mib >= MIN_VOLUME_MIB \
            else max(24, 2 * self.volume_mib)
        # log-uniform over 1 KiB..256 KiB, every size different
        sizes = sorted({int(s) for s in np.exp(rng.uniform(
            np.log(1024), np.log(256 * 1024), size=2 * n_small))})
        rng.shuffle(sizes)
        sizes = [int(s) for s in sizes[:n_small]]
        t0 = time.monotonic()
        self._upload_all(rng.bytes(s) for s in sizes)
        small_fids = list(self.fids)
        n_bulk = max(1, -(-(target - sum(sizes)) // MIB))
        for lo in range(0, n_bulk, 64):  # bounded memory: 64 MiB a round
            self._upload_all(rng.bytes(MIB)
                             for _ in range(min(64, n_bulk - lo)))
        dt = time.monotonic() - t0
        total = sum(size for _, size in self.fids.values())
        per_vol = self.bytes_per_volume()
        log(f"[load] {len(self.fids)} objects, {total / MIB:.1f} MiB of "
            f"needle data ({len(sizes)} small objects of "
            f"{len(set(sizes))} distinct sizes {min(sizes)}..{max(sizes)} "
            f"B + {n_bulk} x 1 MiB) in {dt:.1f}s "
            f"({total / MIB / dt:.0f} MiB/s through upload_data); "
            f"volumes: " + json.dumps(
                {v: round(b / MIB, 1) for v, b in sorted(per_vol.items())}))
        if total < target:
            raise RuntimeError(f"loaded {total} B < target {target} B")
        bulk_fids = [f for f in self.fids if f not in set(small_fids)]
        pick = rng.choice(len(bulk_fids), size=min(48, len(bulk_fids)),
                          replace=False)
        self.sample = small_fids + [bulk_fids[i] for i in sorted(pick)]

    def load_four(self) -> None:
        """>= 8 volumes, each >= volume_mib/8 of needle data."""
        import numpy as np
        rng = np.random.default_rng(self.seed)
        grown = self.http(
            "POST", self.master + f"/vol/grow?count={FOUR_CHIP_VOLUMES}")
        vids = set(grown["volume_ids"])
        if len(vids) != FOUR_CHIP_VOLUMES:
            raise RuntimeError(f"/vol/grow gave {grown}")
        per_target = self.volume_mib * MIB // FOUR_CHIP_VOLUMES
        # every assign goes to the master (assign_leases=False), which
        # picks a writable volume at random — the volume server's lease
        # lane would mint every fid for one volume.  The client mints
        # fids 16 to an assign, so filling the emptiest volume takes a
        # multiple of the target.
        cap = max(6 * self.volume_mib, 1024) * MIB
        t0 = time.monotonic()
        while True:
            per_vol = self.bytes_per_volume()
            short = [v for v in vids if per_vol.get(v, 0) < per_target]
            total = sum(per_vol.values())
            if not short:
                break
            if total > cap:
                raise RuntimeError(
                    f"writes do not spread: {total / MIB:.0f} MiB loaded "
                    f"and volumes {short} are still short: {per_vol}")
            self._upload_all((rng.bytes(MIB) for _ in range(16)),
                             assign_leases=False)
        dt = time.monotonic() - t0
        log(f"[load] {len(self.fids)} x 1 MiB objects, "
            f"{total / MIB:.0f} MiB in {dt:.1f}s over "
            f"{len(per_vol)} volumes (each >= {per_target / MIB:.0f} MiB): "
            + json.dumps({v: round(b / MIB, 1)
                          for v, b in sorted(per_vol.items())}))

    # ---- encode ----
    def reference_shard_hashes(self, dat_path: str) -> list[str]:
        """sha256 of the 14 shard files a plain CpuCoder encode of the
        whole .dat gives — no JAX, the layout's own traversal plan."""
        import numpy as np
        from seaweedfs_tpu.ops.rs_cpu import CpuCoder
        from seaweedfs_tpu.storage.erasure_coding import layout
        coder = CpuCoder()
        k, total = coder.scheme.data_shards, coder.scheme.total_shards
        hashers = [hashlib.sha256() for _ in range(total)]
        size = os.path.getsize(dat_path)
        with open(dat_path, "rb") as f:
            for row_off, block, b, step in layout.iter_encode_batches(
                    size, batch_size=4 * MIB, data_shards=k):
                data = np.zeros((k, step), dtype=np.uint8)
                for i in range(k):
                    f.seek(row_off + i * block + b)
                    buf = f.read(step)
                    data[i, :len(buf)] = np.frombuffer(buf, dtype=np.uint8)
                parity = coder.encode_array(data)
                for i in range(k):
                    hashers[i].update(data[i])
                for i in range(total - k):
                    hashers[k + i].update(np.ascontiguousarray(parity[i]))
        return [h.hexdigest() for h in hashers]

    def shard_path(self, vid: int, sid: int) -> str:
        return os.path.join(self.voldir, f"{vid}.ec{sid:02d}")

    @staticmethod
    def file_sha256(path: str) -> str:
        h = hashlib.sha256()
        with open(path, "rb") as f:
            while True:
                buf = f.read(8 * MIB)
                if not buf:
                    break
                h.update(buf)
        return h.hexdigest()

    def check_shards(self, vid: int, want: list[str]) -> None:
        got = [self.file_sha256(self.shard_path(vid, s)) for s in range(14)]
        bad = [s for s in range(14) if got[s] != want[s]]
        if bad:
            raise RuntimeError(
                f"volume {vid}: shard files {bad} differ from the "
                "CpuCoder reference")
        self.shard_sha[vid] = got

    def check_scalar_sample(self, vid: int, n_cols: int = 2048) -> None:
        """Parity bytes at a seeded sample of columns against the
        scalar GF(256) tables of ops/gf256.py."""
        import numpy as np
        from seaweedfs_tpu.ops import gf256
        pm = np.asarray(gf256.parity_matrix(10, 4))
        size = os.path.getsize(self.shard_path(vid, 0))
        rng = np.random.default_rng(self.seed + vid)
        cols = sorted(int(c) for c in rng.integers(0, size, size=n_cols))
        fhs = [open(self.shard_path(vid, s), "rb") for s in range(14)]
        try:
            for c in cols:
                col = []
                for fh in fhs:
                    fh.seek(c)
                    col.append(fh.read(1)[0])
                for i in range(4):
                    acc = 0
                    for j in range(10):
                        acc ^= gf256.gf_mul(int(pm[i, j]), col[j])
                    if acc != col[10 + i]:
                        raise RuntimeError(
                            f"volume {vid} column {c}: parity shard "
                            f"{10 + i} holds {col[10 + i]:#x}, the scalar "
                            f"tables give {acc:#x}")
        finally:
            for fh in fhs:
                fh.close()

    def encode(self, concurrent: bool) -> None:
        from seaweedfs_tpu.shell.commands import ShellContext
        vids = sorted(self.bytes_per_volume())
        want: dict[int, list[str]] = {}
        t0 = time.monotonic()
        for vid in vids:
            self.http("POST", self.volume + "/admin/mark_readonly",
                      {"volume_id": vid, "read_only": True})
            want[vid] = self.reference_shard_hashes(
                os.path.join(self.voldir, f"{vid}.dat"))
        t_ref = time.monotonic() - t0
        dat_bytes = sum(os.path.getsize(os.path.join(self.voldir,
                                                     f"{v}.dat"))
                        for v in vids)
        sh = ShellContext(self.master, use_grpc=False)
        sh.lock()
        t0 = time.monotonic()
        if concurrent:
            def one(vid):
                return ShellContext(self.master,
                                    use_grpc=False).ec_encode(vid=vid)
            with ThreadPoolExecutor(max_workers=len(vids)) as pool:
                for f in [pool.submit(one, v) for v in vids]:
                    f.result()
        else:
            for vid in vids:
                sh.ec_encode(vid=vid)
        t_enc = time.monotonic() - t0
        sh.unlock()
        t0 = time.monotonic()
        for vid in vids:
            self.check_shards(vid, want[vid])
            self.check_scalar_sample(vid)
        t_cmp = time.monotonic() - t0
        b = self.http("GET", self.volume + "/admin/ec/batcher")
        log(f"[encode] ec.encode of {len(vids)} volume(s) {vids}, "
            f"{dat_bytes / MIB:.1f} MiB of .dat, "
            f"{'concurrently' if concurrent else 'one after another'}: "
            f"{t_enc:.2f}s (CpuCoder reference {t_ref:.2f}s, compare "
            f"{t_cmp:.2f}s); 14 shard files per volume sha256-equal to "
            f"the reference, 2048 sampled columns per volume equal to "
            f"the scalar GF tables; batcher mesh_batches="
            f"{b['mesh_batches']} cpu_batches={b['cpu_batches']} "
            f"max_coalesced={b['max_coalesced']} "
            f"programs_compiled={b['programs_compiled']}")

    # ---- serve ----
    def read_sample(self, label: str, readers: int) -> None:
        from seaweedfs_tpu.client import operation
        from seaweedfs_tpu.client.wdclient import MasterClient
        mc = MasterClient(self.master)  # fresh: no cached locations
        bad = []
        lock = threading.Lock()

        def one(fid):
            data = operation.read_data(mc, fid)
            if hashlib.sha256(data).hexdigest() != self.fids[fid][0]:
                with lock:
                    bad.append(fid)
        t0 = time.monotonic()
        with ThreadPoolExecutor(max_workers=readers) as pool:
            for f in [pool.submit(one, fid) for fid in self.sample]:
                f.result()
        dt = time.monotonic() - t0
        if bad:
            raise RuntimeError(
                f"{label}: {len(bad)} of {len(self.sample)} objects read "
                f"back with a wrong sha256: {bad[:5]}")
        log(f"[{label}] {len(self.sample)} objects through "
            f"operation.read_data ({readers} reader(s)), every sha256 "
            f"equal, {dt:.2f}s")

    def mounted_shards(self, vid: int) -> set[int]:
        st = self.http("GET", self.volume + "/status")
        bits = 0
        for e in st.get("ec_shards", []):
            if e["id"] == vid:
                bits |= e["ec_index_bits"]
        return {s for s in range(14) if bits & (1 << s)}

    def recover_stats(self, vid: int) -> dict:
        return self.http(
            "GET", self.volume + f"/admin/ec/shard_stat?volumeId={vid}"
        )["recover_stats"]

    def degrade(self) -> int:
        per_vol = self.bytes_per_volume()
        vid = max(per_vol, key=per_vol.get)
        before = self.recover_stats(vid)
        b0 = self.http("GET", self.volume + "/admin/ec/batcher")
        self.http("POST", self.volume + "/admin/ec/unmount",
                  {"volume_id": vid, "shard_ids": list(VICTIM_SHARDS)})
        for s in VICTIM_SHARDS:
            os.remove(self.shard_path(vid, s))
        self.http("POST", self.volume + "/admin/cache", {"clear": True})
        left = self.mounted_shards(vid)
        if left & set(VICTIM_SHARDS) or len(left) != 11:
            raise RuntimeError(f"after unmount, mounted shards are {left}")
        # ONE reader: every reconstruction is then a B=1 job, so a cold
        # run compiles exactly two rebuild programs (256 KiB and 1 MiB
        # wide) and no read queues behind more than one ~9 s compile —
        # a read carries a 30 s deadline, and with several readers the
        # batches' B (1, 2, 4...) and so the compiles depend on timing
        self.read_sample("degrade", readers=1)
        after = self.recover_stats(vid)
        b1 = self.http("GET", self.volume + "/admin/ec/batcher")
        rebuilt = sum(after.values()) - sum(before.values())
        log(f"[degrade] volume {vid} without shards {list(VICTIM_SHARDS)}: "
            f"recover_stats {before} -> {after} ({rebuilt} intervals "
            f"reconstructed); batcher jobs {b0['jobs_total']} -> "
            f"{b1['jobs_total']}, programs_compiled "
            f"{b0['programs_compiled']} -> {b1['programs_compiled']}")
        if rebuilt <= 0:
            raise RuntimeError(
                "no read was reconstructed: recover_stats did not move")
        return vid

    def restore(self, vid: int) -> None:
        from seaweedfs_tpu.shell.commands import ShellContext
        sh = ShellContext(self.master, use_grpc=False)
        repaired0 = sh.ec_repair_status().get("repaired_total", 0)
        who = None
        asked = False
        deadline = time.monotonic() + RESTORE_BUDGET_S
        while True:
            on_disk = {s for s in range(14)
                       if os.path.exists(self.shard_path(vid, s))}
            if len(on_disk) == 14 and len(self.mounted_shards(vid)) == 14:
                break
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"volume {vid} not back to 14 shards after "
                    f"{RESTORE_BUDGET_S:.0f}s: on disk {sorted(on_disk)}")
            self.check_alive()
            st = sh.ec_repair_status()
            busy = [t for t in st.get("in_flight", []) + st.get("queue", [])
                    if t.get("volume_id") == vid]
            if busy:
                who = who or "the master's repair queue"
            elif not asked:
                asked = True
                sh.lock()
                plans = sh.ec_rebuild()
                sh.unlock()
                done = [p for p in plans if p.get("rebuilt")]
                log(f"[restore] ec.rebuild planned {len(plans)}, rebuilt "
                    f"{[(p['vid'], p['rebuilt']) for p in done]}")
                if done:
                    who = "ec.rebuild"
                continue
            time.sleep(0.5)
        if who is None and \
                sh.ec_repair_status().get("repaired_total", 0) > repaired0:
            who = "the master's repair queue"
        for s in VICTIM_SHARDS:
            got = self.file_sha256(self.shard_path(vid, s))
            if got != self.shard_sha[vid][s]:
                raise RuntimeError(
                    f"volume {vid} shard {s}: rebuilt file differs from "
                    "the one deleted")
        log(f"[restore] redundancy brought back by {who}: volume {vid} "
            f"has 14 shards on disk and mounted; rebuilt shards "
            f"{list(VICTIM_SHARDS)} sha256-equal to the deleted files")

    def check_batcher(self) -> None:
        b = self.http("GET", self.volume + "/admin/ec/batcher")
        facts = {k: b.get(k) for k in (
            "mesh_devices", "device", "jobs_total", "batches_total",
            "mesh_batches", "cpu_batches", "coder_fallbacks",
            "fallback_reason", "max_coalesced", "programs_compiled",
            "output_spread", "compile_cache_dir")}
        log("[batcher] " + json.dumps(facts))
        wrong = []
        if not b["mesh_batches"] > 0:
            wrong.append("mesh_batches == 0")
        if b["cpu_batches"] != 0:
            wrong.append(f"cpu_batches == {b['cpu_batches']}")
        if b["coder_fallbacks"] != 0:
            wrong.append(f"coder_fallbacks == {b['coder_fallbacks']}")
        if b["fallback_reason"] is not None:
            wrong.append(f"fallback_reason == {b['fallback_reason']!r}")
        if b["mesh_devices"] != self.chips:
            wrong.append(f"mesh_devices == {b['mesh_devices']}")
        spread = b.get("output_spread") or {}
        if set(spread) != {str(self.chips)}:
            wrong.append(
                f"dispatch outputs were spread over {spread} devices, "
                f"not all over {self.chips}")
        if wrong:
            raise RuntimeError("; ".join(wrong))
        log(f"[batcher] every one of {sum(spread.values())} dispatches "
            f"had output shards on {self.chips} distinct device(s); "
            f"{b['programs_compiled']} programs compiled for "
            f"{b['jobs_total']} jobs; compile cache at "
            f"{b['compile_cache_dir']}")

    def kernels(self) -> None:
        on_tpu = bool(self.device) and self.device["platform"] == "tpu"
        row_bytes = MIB if on_tpu else 64 << 10
        proc = subprocess.run(
            [sys.executable, "-u", "-c", KERNELS_CHILD, str(self.seed),
             str(row_bytes)],
            cwd=REPO, capture_output=True, text=True, timeout=900,
            stdin=subprocess.DEVNULL)
        line = next((ln for ln in proc.stdout.splitlines()
                     if ln.startswith("KERNELS ")), None)
        if proc.returncode != 0 or line is None:
            log(proc.stdout[-3000:])
            log(proc.stderr[-6000:])
            raise RuntimeError(
                f"kernel child {self.describe_exit(proc)}")
        out = json.loads(line[len("KERNELS "):])
        log(f"[kernels] 10 x {row_bytes} B rows: " + json.dumps(out))
        if not all(out["identical"].values()):
            raise RuntimeError(f"not bit-identical with CpuCoder: {out}")
        if on_tpu and out["device"]["platform"] != "tpu":
            raise RuntimeError(
                f"the device coders did not run on the TPU: {out}")

    # ---- the run ----
    def run(self) -> int:
        with self.phase("preflight"):
            self.preflight()
        with self.phase("servers"):
            self.start_servers()
        with self.phase("device"):
            self.check_device()
        if self.chips == 4:
            with self.phase("load"):
                self.load_four()
            with self.phase("encode"):
                self.encode(concurrent=True)
            with self.phase("batcher"):
                self.check_batcher()
            with self.phase("stop"):
                self.stop_servers()
        else:
            with self.phase("load"):
                self.load()
            with self.phase("encode"):
                self.encode(concurrent=False)
            with self.phase("serve"):
                self.read_sample("serve", readers=4)
            with self.phase("degrade"):
                vid = self.degrade()
            with self.phase("restore"):
                self.restore(vid)
            with self.phase("batcher"):
                self.check_batcher()
            with self.phase("stop"):
                self.stop_servers()
            with self.phase("kernels"):
                self.kernels()
        log("[summary] seconds per phase: " + json.dumps(
            {k: round(v, 2) for k, v in self.seconds.items()}))
        if self.soft_failures:
            log(f"FAILED phase(s): {', '.join(self.soft_failures)} — "
                "every later phase ran and passed, but this is not a "
                "pass")
            self.print_log_tails()
            return 1
        return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--volume-mib", type=int, default=1024)
    args = ap.parse_args(argv)
    smoke = Smoke(args)
    t0 = time.monotonic()
    rc = 1
    try:
        rc = smoke.run()
    except PhaseFailed as e:
        log(f"FAILED phase: {e}")
        smoke.print_log_tails()
    except BaseException as e:  # noqa: BLE001 — reported, then non-zero
        log(f"FAILED outside a phase: {type(e).__name__}: {e}")
        log(traceback.format_exc())
        smoke.print_log_tails()
    finally:
        try:
            smoke.stop_servers()
        finally:
            if smoke.workdir:
                shutil.rmtree(smoke.workdir, ignore_errors=True)
    log(f"[summary] wall {time.monotonic() - t0:.1f}s, exit code {rc}")
    if rc != 0:
        return rc
    # the one rule of this process: it never touched JAX
    if "jax" in sys.modules:
        log("FAILED: the parent process imported jax")
        return 1
    dev = smoke.device
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
