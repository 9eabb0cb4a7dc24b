"""Servers, their logs, and the control directory of one benchmark run.

Copied from ``chip_smoke.py`` (PR 22), which ran on the v5e: spawn through
the CLI, readiness polling, SIGTERM in the right order, log tails on
failure.  The copy is the yardstick's own: later PRs may change the
program and the smoke, never this.

The process that uses this module never imports ``jax``: the chip belongs
to the one volume-server child.
"""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READY_BUDGET_S = 900.0       # a cold run compiles before the server listens
CONTROL_BUDGET_S = 240.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Cluster:
    """One CLI master and one CLI volume server (behind the wrapper
    module ``volume_module``), in a work directory under ``TMPDIR``."""

    def __init__(self, volume_module: str = "benchmark.served_volume"):
        # "module" or "module:first-argument" (the tests' faulty wrapper)
        self.volume_module, _, self.volume_arg = volume_module.partition(":")
        self.procs: list[tuple[str, subprocess.Popen, str]] = []
        self.workdir = tempfile.mkdtemp(prefix="ecbench_")
        self.voldir = os.path.join(self.workdir, "vol")
        self.control = os.path.join(self.workdir, "control")
        for d in (self.voldir, self.control,
                  os.path.join(self.workdir, "meta")):
            os.makedirs(d)
        self.master = ""
        self.volume = ""
        self.stopped = False

    # ---- plumbing ----
    def http(self, method: str, url: str, body=None, timeout: float = 120):
        from seaweedfs_tpu.utils.httpd import http_json
        return http_json(method, f"http://{url}", body, timeout=timeout)

    def spawn(self, name: str, module: str, argv: list[str],
              env: dict) -> None:
        logpath = os.path.join(self.workdir, f"{name}.log")
        with open(logpath, "wb") as logf:
            # the environment goes to the child as it is, plus what the
            # configuration's ``servers.env`` states: no JAX_PLATFORMS, no
            # XLA_FLAGS, no compile-cache variable is set or unset here
            proc = subprocess.Popen(
                [sys.executable, "-u", "-m", module, *argv],
                cwd=REPO, env={**os.environ, **env},
                stdout=logf, stderr=subprocess.STDOUT,
                stdin=subprocess.DEVNULL, start_new_session=True)
        self.procs.append((name, proc, logpath))
        log(f"[servers] {name}: pid {proc.pid}: python -u -m {module} "
            f"{' '.join(argv)}")

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
            time.sleep(0.1)
        raise TimeoutError(f"{name} not ready at {url}{path} after "
                           f"{READY_BUDGET_S:.0f}s (last: {last})")

    def start(self, warm_shapes: dict, volume_size_limit_mb: int,
              max_volumes: int, env: dict) -> dict:
        """Start both servers; returns the wrapper's warm-up report.
        ``warm_shapes`` becomes ``warm.json``: ``encode`` and ``apply``,
        lists of ``[B, columns]``, and ``code``, the spec string of the
        scheme to warm them on (absent or ``""``: the server's
        default)."""
        ports, socks = [], []
        for _ in range(2):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            socks.append(s)
            ports.append(s.getsockname()[1])
        for s in socks:
            s.close()
        self.master = f"127.0.0.1:{ports[0]}"
        self.volume = f"127.0.0.1:{ports[1]}"
        with open(os.path.join(self.control, "warm.json"), "w") as f:
            json.dump(warm_shapes, f)
        # the volume server first: its backend start-up and warm-up are
        # the long pole, and the master is up long before it looks
        self.spawn("volume", self.volume_module, [
            *([self.volume_arg] if self.volume_arg else []),
            self.control, "volume", "-port", str(ports[1]), "-dir",
            self.voldir, "-max", str(max_volumes), "-mserver", self.master,
            "-ecBatcher"], env)
        self.spawn("master", "seaweedfs_tpu.cli", [
            "master", "-port", str(ports[0]), "-mdir",
            os.path.join(self.workdir, "meta"),
            "-volumeSizeLimitMB", str(volume_size_limit_mb)], env)
        self.wait_ready("master", self.master, "/cluster/status")
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
            time.sleep(0.1)
        with open(os.path.join(self.control, "warm.done")) as f:
            return json.load(f)

    def command(self, name: str, body: str = "") -> dict:
        """Ask the volume server's wrapper for ``name``; wait for its
        answer."""
        path = os.path.join(self.control, name)
        done = path + ".done"
        with contextlib.suppress(FileNotFoundError):
            os.remove(done)
        with open(path + ".tmp", "w") as f:
            f.write(body)
        os.replace(path + ".tmp", path)
        deadline = time.monotonic() + CONTROL_BUDGET_S
        while not os.path.exists(done):
            self.check_alive()
            if time.monotonic() > deadline:
                raise TimeoutError(f"the volume server's wrapper did not "
                                   f"answer {name}")
            time.sleep(0.005)
        with open(done) as f:
            reply = json.load(f)
        if "error" in reply:
            raise RuntimeError(f"{name}: {reply['error']}")
        return reply

    def stop(self) -> None:
        """SIGTERM volume first (its draining heartbeat wants a master),
        then the master; SIGKILL the process group of whatever is left.
        Returns only when every child has EXITED."""
        if self.stopped:
            return
        self.stopped = True
        order = sorted(self.procs, key=lambda p: p[0] != "volume")
        for name, proc, _ in order:
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
                try:
                    proc.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    log(f"[stop] {name} ignored SIGTERM for 60s; killing")
            with contextlib.suppress(ProcessLookupError):
                os.killpg(proc.pid, signal.SIGKILL)
            proc.wait(timeout=30)
            log(f"[stop] {name} {self.describe_exit(proc)}")

    def print_log_tails(self, lines: int = 50) -> None:
        for name, proc, path in self.procs:
            log(f"----- last {lines} lines of {name}.log "
                f"({self.describe_exit(proc)}) -----")
            try:
                with open(path, "r", errors="replace") as f:
                    for line in f.readlines()[-lines:]:
                        log(line.rstrip("\n"))
            except OSError as e:
                log(f"(cannot read {path}: {e})")
        log("----- end of server logs -----")

    def cleanup(self) -> None:
        shutil.rmtree(self.workdir, ignore_errors=True)


def preflight() -> None:
    """The program is here, and its native codec is built."""
    if not os.path.isdir(os.path.join(REPO, "seaweedfs_tpu")):
        raise RuntimeError(
            f"{REPO} holds no seaweedfs_tpu package: the benchmark runs "
            "from the root of a checkout of the program")
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from seaweedfs_tpu.native import rs_native
    # built on first use in a checkout (the .so is never committed), so
    # that the servers started next find it and do not build it twice
    if not rs_native.available():
        raise RuntimeError(
            "native codec unavailable (no compiler, or the library did "
            "not load); refusing to crawl on the pure-Python fallbacks")
