"""Resilience layer: deadlines, jittered retries, per-peer circuit
breakers, hedged degraded reads — units plus chaos e2e over a live
in-process cluster with tools/netchaos.py fault-injecting proxies."""

import socket
import sys
import threading
import time
import types

import numpy as np
import pytest

from seaweedfs_tpu.client import operation
from seaweedfs_tpu.client.wdclient import MasterClient
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.shell.commands import ShellContext
from seaweedfs_tpu.utils import resilience, tracing
from seaweedfs_tpu.utils.httpd import HttpServer, Response, http_call, \
    http_json
from seaweedfs_tpu.utils.limiter import TokenBucket
from seaweedfs_tpu.utils.metrics import Registry
from seaweedfs_tpu.utils.resilience import (CLOSED, DEADLINE_HEADER, OPEN,
                                            CircuitBreaker, Deadline,
                                            DeadlineExceeded, PeerHealth,
                                            RetryPolicy, current_deadline,
                                            deadline_scope, hedged)
from tools.netchaos import ChaosProxy


# ---------------- Deadline ----------------

def test_deadline_basics():
    dl = Deadline.after(5.0)
    assert 4.5 < dl.remaining() <= 5.0
    assert not dl.expired()
    assert dl.timeout(cap=1.0) == 1.0
    assert dl.timeout() <= 5.0
    # sub caps the child, never extends the parent
    child = dl.sub(0.5)
    assert child.remaining() <= 0.5
    wide = dl.sub(100.0)
    assert wide.remaining() <= dl.remaining() + 0.001

    gone = Deadline.after(0.0)
    assert gone.expired()
    with pytest.raises(DeadlineExceeded):
        gone.timeout()
    # DeadlineExceeded must trip existing ConnectionError fail-over paths
    assert issubclass(DeadlineExceeded, ConnectionError)


def test_deadline_header_round_trip():
    dl = Deadline.after(3.0)
    parsed = Deadline.from_headers({DEADLINE_HEADER: dl.header_value()})
    assert abs(parsed.remaining() - dl.remaining()) < 0.1
    # absent header: default budget, or None when no default
    assert Deadline.from_headers({}) is None
    fresh = Deadline.from_headers({}, default=7.0)
    assert 6.5 < fresh.remaining() <= 7.0
    # garbage header falls back instead of crashing the request
    assert Deadline.from_headers({DEADLINE_HEADER: "bogus"},
                                 default=1.0).remaining() <= 1.0


def test_deadline_scope_is_ambient():
    assert current_deadline() is None
    dl = Deadline.after(2.0)
    with deadline_scope(dl):
        assert current_deadline() is dl
        with deadline_scope(None):
            assert current_deadline() is None
        assert current_deadline() is dl
    assert current_deadline() is None


def test_http_call_propagates_deadline():
    """An ambient deadline caps the socket timeout AND rides the
    X-Weed-Deadline header to the next hop."""
    seen = {}
    srv = HttpServer("127.0.0.1", 0)

    def ping(req):
        seen["deadline"] = req.headers.get(DEADLINE_HEADER)
        return Response({"ok": True})
    srv.add("GET", "/ping", ping)
    srv.start()
    try:
        with deadline_scope(Deadline.after(4.0)):
            status, _, _ = http_call(
                "GET", f"http://{srv.host}:{srv.port}/ping")
        assert status == 200
        assert seen["deadline"] is not None
        assert 0.0 < float(seen["deadline"]) <= 4.0
        # an exhausted budget fails fast instead of dialing with 0s
        with deadline_scope(Deadline.after(0.0)):
            with pytest.raises(DeadlineExceeded):
                http_call("GET", f"http://{srv.host}:{srv.port}/ping")
    finally:
        srv.stop()


# ---------------- RetryPolicy ----------------

def test_retry_backoff_full_jitter_bounds():
    rp = RetryPolicy(base=0.1, cap=2.0)
    for attempt in range(8):
        ceiling = min(2.0, 0.1 * 2 ** attempt)
        samples = [rp.backoff(attempt) for _ in range(200)]
        assert all(0.0 <= s <= ceiling for s in samples)
        # full jitter, not fixed: the samples must actually spread
        assert max(samples) - min(samples) > ceiling * 0.2


def test_retry_budget_drains_and_refills():
    rp = RetryPolicy(budget_min=2.0, budget_ratio=0.1)
    assert rp.allow_retry("peer")      # 2.0 -> 1.0
    assert rp.allow_retry("peer")      # 1.0 -> 0.0
    assert not rp.allow_retry("peer")  # drained: retries stop
    for _ in range(12):                # healthy traffic earns it back
        rp.record_call("peer")
    assert rp.allow_retry("peer")
    # budget is per destination
    assert rp.allow_retry("other")


def test_retry_call_retries_then_raises():
    rp = RetryPolicy(attempts=3, base=0.001, cap=0.002)
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionError("down")
        return "ok"
    assert rp.call(flaky, dest="d") == "ok"
    assert len(calls) == 3

    def dead():
        raise ConnectionError("still down")
    with pytest.raises(ConnectionError):
        rp.call(dead, dest="d2")
    # DeadlineExceeded is never retried: the budget is gone anyway
    calls2 = []

    def expired():
        calls2.append(1)
        raise DeadlineExceeded("late")
    with pytest.raises(DeadlineExceeded):
        rp.call(expired, dest="d3")
    assert len(calls2) == 1


# ---------------- CircuitBreaker ----------------

def test_breaker_lifecycle():
    br = CircuitBreaker(failure_threshold=2, open_for=0.15)
    assert br.state == CLOSED and br.allow()
    br.record(False)
    assert br.state == CLOSED  # one failure is not a pattern
    br.record(False)
    assert br.state == OPEN
    assert not br.allow()
    assert not br.probe_ripe()
    time.sleep(0.2)
    assert br.probe_ripe()  # due a probe, passively visible
    assert br.allow()       # open -> half-open, probe slot consumed
    assert not br.allow()   # metered: only one probe in flight
    br.record(True, latency_s=0.01)
    assert br.state == CLOSED
    # half-open failure re-opens with a fresh clock
    br.record(False)
    br.record(False)
    time.sleep(0.2)
    assert br.allow()
    br.record(False)
    assert br.state == OPEN


def test_breaker_failed_probe_rearms_open_window():
    br = CircuitBreaker(failure_threshold=1, open_for=0.15)
    br.record(False)
    assert br.state == OPEN
    time.sleep(0.2)
    assert br.probe_ripe()
    br.record(False)  # probe dialed (passively) and failed
    assert br.state == OPEN
    assert not br.probe_ripe()  # window re-armed: not ripe again yet


def test_breaker_score_orders_states():
    fast, slow, broken = (CircuitBreaker(failure_threshold=1)
                          for _ in range(3))
    fast.record(True, 0.002)
    slow.record(True, 0.300)
    broken.record(False)
    assert fast.score() < slow.score() < broken.score()
    assert fast.p95_s() == 0.002


def test_peer_health_rank_and_hedge_delay():
    ph = PeerHealth(failure_threshold=1, open_for=60.0)
    ph.record("fast", True, 0.002)
    ph.record("slow", True, 0.300)
    ph.record("down", False)
    assert ph.rank(["down", "slow", "fast"]) == ["fast", "slow", "down"]
    # adaptive hedge delay: 1.5 x observed p95, clamped
    assert ph.hedge_delay("unknown") == ph.hedge_default_s
    assert abs(ph.hedge_delay("fast") - ph.hedge_min_s) < 1e-9
    assert ph.hedge_delay("slow") == pytest.approx(0.45)
    snap = ph.snapshot()
    assert snap["down"]["state"] == OPEN
    assert snap["fast"]["ewma_ms"] == 2.0


# ---------------- hedged() ----------------

def test_hedged_first_success_wins():
    out = hedged(lambda c: c.encode(), ["a", "b"], delay=0.5)
    assert out == b"a"


def test_hedged_fails_over_on_error():
    def fn(c):
        if c == "bad":
            raise ConnectionError("nope")
        return c
    ph = PeerHealth(failure_threshold=1)
    assert hedged(fn, ["bad", "good"], health=ph, delay=0.5) == "good"
    assert ph.snapshot()["bad"]["state"] == OPEN
    # next call: open circuit is screened out, good is primary
    assert hedged(fn, ["bad", "good"], health=ph, delay=0.5) == "good"


def test_hedged_forces_sole_holder_despite_open_breaker():
    ph = PeerHealth(failure_threshold=1, open_for=60.0)
    ph.record("only", False)
    assert ph.snapshot()["only"]["state"] == OPEN
    assert hedged(lambda c: b"data", ["only"], health=ph) == b"data"


def test_hedged_beats_straggler_p99():
    """Chaos scenario (c), distilled: a 150ms straggler primary must not
    set the tail — the backup request fires at the hedge delay and
    wins. Also: after the first call the learned latencies re-rank the
    fast peer to primary, so the steady state never pays the hedge."""
    def fn(c):
        time.sleep(0.15 if c == "slow" else 0.005)
        return c.encode()

    ph = PeerHealth(hedge_default_s=0.03)
    lat = []
    for _ in range(5):
        t0 = time.perf_counter()
        out = hedged(fn, ph.rank(["slow", "fast"]), health=ph)
        lat.append(time.perf_counter() - t0)
        assert out == b"fast"
    assert lat[0] < 0.12          # hedge fired: ~0.03 + 0.005, not 0.15
    assert max(lat[1:]) < 0.12    # re-ranked: fast is primary now
    assert ph.rank(["slow", "fast"])[0] == "fast"


def test_hedged_respects_deadline():
    t0 = time.perf_counter()
    out = hedged(lambda c: time.sleep(5.0) or c, ["a"],
                 deadline=Deadline.after(0.2))
    assert out is None
    assert time.perf_counter() - t0 < 1.0


# ---------------- hedged(): direct or pooled ----------------

@pytest.fixture
def pool_submits(monkeypatch):
    """What hedged() hands to the hedge pool, counted."""
    pool = resilience._get_hedge_pool()
    submits = []
    real = pool.submit

    def submit(fn, *a, **kw):
        submits.append(fn)
        return real(fn, *a, **kw)
    monkeypatch.setattr(pool, "submit", submit)
    return submits


def _open_unripe(ph: PeerHealth, url: str) -> None:
    for _ in range(ph.failure_threshold):
        ph.record(url, False)
    assert ph.snapshot()[url]["state"] == OPEN
    assert not ph.breaker(url).probe_ripe()


@pytest.mark.parametrize("case,direct", [
    ("one_candidate", True),
    ("one_usable_of_two", True),       # the other is OPEN and not ripe
    ("sole_holder_open", True),        # forced, and forced directly
    ("one_candidate_deadline_argument", False),
    ("one_candidate_deadline_ambient", False),
    ("two_candidates", False),
])
def test_hedged_direct_only_with_one_candidate_and_no_deadline(
        case, direct, pool_submits):
    """A read with nobody to hedge to and no deadline to return at
    stays on the caller's thread; a second candidate or any deadline
    needs the pool's second thread and keeps it."""
    ph = PeerHealth(failure_threshold=2, open_for=60.0)
    seen = []

    def fn(c):
        seen.append((c, threading.get_ident(),
                     threading.current_thread().name))
        return c.encode()

    candidates, kw, scope = ["a"], {}, deadline_scope(None)
    if case == "one_usable_of_two":
        _open_unripe(ph, "b")
        candidates = ["a", "b"]
    elif case == "sole_holder_open":
        _open_unripe(ph, "a")
    elif case == "one_candidate_deadline_argument":
        kw["deadline"] = Deadline.after(5.0)
    elif case == "one_candidate_deadline_ambient":
        scope = deadline_scope(Deadline.after(5.0))
    elif case == "two_candidates":
        candidates = ["a", "b"]
    with scope:
        assert hedged(fn, candidates, health=ph, **kw) == b"a"
    assert [c for c, _, _ in seen] == ["a"]
    _, ident, name = seen[0]
    if direct:
        assert ident == threading.get_ident()
        assert pool_submits == []
        assert ph.hedge_stats() == {"direct": 1, "pooled": 0,
                                    "probe": 0, "fired": 0}
    else:
        assert ident != threading.get_ident()
        assert name.startswith("hedge")
        assert len(pool_submits) == 1
        assert ph.hedge_stats()["direct"] == 0
        assert ph.hedge_stats()["pooled"] == 1
    assert ph.snapshot()["a"]["success_total"] == 1


@pytest.mark.parametrize("path", ["direct", "pooled"])
def test_hedged_sole_holder_recorded_alike_on_both_paths(path,
                                                         pool_submits):
    """What PeerHealth learns from a one-holder read is the same on the
    caller's thread and on the pool: a success with its latency, a
    failure (a None or an exception) without one; failure_threshold of
    them open the breaker, and the sole holder is still dialed."""
    ph = PeerHealth(failure_threshold=3, open_for=60.0)
    kw = {} if path == "direct" else {"deadline": Deadline.after(30.0)}
    calls = []

    def fn(c):
        calls.append(threading.get_ident())
        if mode == "raise":
            raise ConnectionError("down")
        if mode == "none":
            return None
        time.sleep(0.02)
        return b"ok"

    mode = "ok"
    assert hedged(fn, ["only"], health=ph, **kw) == b"ok"
    snap = ph.snapshot()["only"]
    assert snap["success_total"] == 1 and snap["failure_total"] == 0
    assert snap["ewma_ms"] >= 20.0       # the latency was recorded
    for i, mode in enumerate(["raise", "none", "raise"], start=1):
        assert hedged(fn, ["only"], health=ph, **kw) is None
        snap = ph.snapshot()["only"]
        assert snap["failure_total"] == i
        assert snap["consecutive_failures"] == i
    assert snap["state"] == OPEN and snap["opened_total"] == 1
    # OPEN and not ripe: still dialed, and on the same path
    mode = "ok"
    assert hedged(fn, ["only"], health=ph, **kw) == b"ok"
    assert ph.snapshot()["only"]["state"] == CLOSED
    assert len(calls) == 5
    on_caller = [t == threading.get_ident() for t in calls]
    assert on_caller == [path == "direct"] * 5
    assert len(pool_submits) == (0 if path == "direct" else 5)
    assert ph.hedge_stats()[path] == 5


@pytest.mark.parametrize("path", ["direct", "pooled"])
def test_hedged_ambient_span_reaches_fn_and_is_annotated(path):
    span = tracing.Tracer(node="t").root_span("read", sampled=True)
    seen = []
    candidates = ["a"] if path == "direct" else ["a", "b"]
    with tracing.span_scope(span):
        out = hedged(lambda c: seen.append(tracing.current_span()) or c,
                     candidates, health=PeerHealth())
    assert out == "a"
    assert seen == [span]
    attrs = span.to_dict()["annotations"]
    assert attrs["hedge.winner"] == "a"
    assert attrs["hedge.legs_fired"] == 1


def test_hedged_without_health_goes_direct_too():
    me = threading.get_ident()
    assert hedged(lambda c: threading.get_ident(), ["a"]) == me
    assert hedged(lambda c: None, ["a"]) is None


def test_hedge_stats_count_direct_and_pooled_and_are_exported():
    reg = Registry()
    ph = PeerHealth(metrics=reg)
    for _ in range(7):
        assert hedged(lambda c: c, ["only"], health=ph) == "only"
    assert ph.hedge_stats() == {"direct": 7, "pooled": 0,
                                "probe": 0, "fired": 0}
    assert hedged(lambda c: c, ["a", "b"], health=ph, delay=0.5) == "a"
    assert ph.hedge_stats() == {"direct": 7, "pooled": 1,
                                "probe": 0, "fired": 0}
    # a slow primary fires its backup: still one pooled call
    assert hedged(lambda c: time.sleep(0.2 if c == "a" else 0) or c,
                  ["a", "b"], health=ph, delay=0.01) == "b"
    assert ph.hedge_stats() == {"direct": 7, "pooled": 2,
                                "probe": 0, "fired": 1}
    text = reg.expose_text()
    assert 'resilience_hedges_total{outcome="direct"} 7' in text
    assert 'resilience_hedges_total{outcome="pooled"} 2' in text
    # a client's PeerHealth has no registry: the integers still count
    bare = PeerHealth()
    hedged(lambda c: c, ["only"], health=bare)
    assert bare.hedge_stats()["direct"] == 1


def test_hedge_stats_lose_no_count_under_concurrent_readers():
    """Sixteen readers share one client's PeerHealth: every direct call
    is counted once (a lost update would read low)."""
    ph = PeerHealth()
    readers, each = 16, 400
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def reader():
            for _ in range(each):
                hedged(lambda c: c, ["only"], health=ph)
        threads = [threading.Thread(target=reader) for _ in range(readers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert ph.hedge_stats()["direct"] == readers * each
    assert ph.snapshot()["only"]["success_total"] == readers * each


def test_read_data_of_one_holder_is_direct(tmp_path):
    """operation.read_data against a live cluster with one holder (the
    default replication 000): the GET is made on the reader's thread
    and the client's PeerHealth counts it direct."""
    master = MasterServer(volume_size_limit_mb=64)
    master.start()
    vs = VolumeServer([str(tmp_path / "v")], master.url)
    vs.start()
    mc = MasterClient(master.url)
    try:
        data = b"one holder, no deadline" * 100
        fid = operation.upload_data(mc, data).fid
        assert current_deadline() is None
        assert operation.read_data(mc, fid) == data
        assert operation.read_data(mc, fid, byte_range=(4, 9)) == data[4:10]
        assert mc.peer_health.hedge_stats() == {
            "direct": 2, "pooled": 0, "probe": 0, "fired": 0}
        snap = mc.peer_health.snapshot()
        assert [s["success_total"] for s in snap.values()] == [2]
        # a caller with a budget keeps the pool, which can enforce it
        with deadline_scope(Deadline.after(10.0)):
            assert operation.read_data(mc, fid) == data
        assert mc.peer_health.hedge_stats()["pooled"] == 1
    finally:
        mc.stop()
        vs.stop()
        master.stop()


# ---------------- TokenBucket.peek ----------------

def test_token_bucket_peek():
    tb = TokenBucket(1000.0, initial=1000.0)
    assert tb.peek() == pytest.approx(1000.0, abs=50.0)
    tb.consume(1500.0)  # oversized transfer: bucket goes into debt
    assert tb.peek() < 0
    unlimited = TokenBucket(0.0)
    unlimited.consume(1 << 30)  # no-op, never blocks


# ---------------- scrub-aware repair dispatch ----------------

def _stub_node(url, scrubbing):
    return types.SimpleNamespace(url=url, scrubbing=scrubbing)


def test_pick_rebuilder_skips_scrubbing_nodes():
    from seaweedfs_tpu.scrub.repair_queue import RepairQueue
    rq = RepairQueue.__new__(RepairQueue)  # pickers are self-contained
    nodes = {"a:1": _stub_node("a:1", True),
             "b:1": _stub_node("b:1", False),
             "c:1": _stub_node("c:1", False)}
    counts = {"a:1": 9, "b:1": 3, "c:1": 2}
    # a:1 has the most shards but is mid-scrub-pass: b:1 wins
    assert rq._pick_rebuilder(counts, nodes) == "b:1"
    # every holder scrubbing: repair beats politeness
    for n in nodes.values():
        n.scrubbing = True
    assert rq._pick_rebuilder(counts, nodes) == "a:1"


def test_pick_source_prefers_idle_holder():
    from seaweedfs_tpu.scrub.repair_queue import RepairQueue
    rq = RepairQueue.__new__(RepairQueue)
    busy, idle = _stub_node("a:1", True), _stub_node("b:1", False)
    assert rq._pick_source([busy, idle]) is idle
    assert rq._pick_source([busy]) is busy  # sole holder: no choice


def test_heartbeat_carries_scrubbing_flag():
    from seaweedfs_tpu.cluster.topology import Topology
    topo = Topology()
    hb = {"ip": "127.0.0.1", "port": 8080, "scrubbing": True}
    node = topo.sync_data_node_registration(hb)
    assert node.scrubbing is True
    topo.incremental_sync(node, {"scrubbing": False})
    assert node.scrubbing is False
    topo.incremental_sync(node, {})  # absent key: state unchanged
    assert node.scrubbing is False


# ---------------- netchaos proxy ----------------

def _echo_http_backend():
    srv = HttpServer("127.0.0.1", 0)
    srv.add("GET", "/ping", lambda req: Response({"pong": True}))
    srv.start()
    return srv


def test_netchaos_pass_and_latency():
    srv = _echo_http_backend()
    try:
        with ChaosProxy(srv.host, srv.port) as proxy:
            status, body, _ = http_call("GET",
                                        f"http://{proxy.url}/ping")
            assert status == 200 and b"pong" in body
            proxy.set_fault(latency_s=0.2)
            t0 = time.perf_counter()
            status, _, _ = http_call("GET", f"http://{proxy.url}/ping")
            assert status == 200
            assert time.perf_counter() - t0 >= 0.18
            assert proxy.stats["connections"] >= 2
    finally:
        srv.stop()


def test_netchaos_reset_blackhole_and_5xx():
    srv = _echo_http_backend()
    try:
        with ChaosProxy(srv.host, srv.port, mode="reset") as proxy:
            with pytest.raises(ConnectionError):
                http_call("GET", f"http://{proxy.url}/ping", timeout=2)
            proxy.set_fault(mode="http_error", http_status=503)
            status, _, _ = http_call("GET", f"http://{proxy.url}/ping")
            assert status == 503
            proxy.set_fault(mode="blackhole")
            with pytest.raises(ConnectionError):
                http_call("GET", f"http://{proxy.url}/ping", timeout=0.5)
            assert proxy.stats["blackholed"] >= 1
    finally:
        srv.stop()


# ---------------- chaos e2e over a live cluster ----------------

def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class _EcChaosCluster:
    """vs1 holds 13/14 shards of one EC needle; the shard the needle's
    data lives in exists only on the OTHER servers: vs2 behind a chaos
    proxy, plus (optionally) a healthy vs3. Every read of the needle on
    vs1 takes a remote shard hop through the resilience layer."""

    def __init__(self, tmp_path, mode="pass", with_fast_holder=True):
        rng = np.random.default_rng(5)
        self.data = rng.integers(0, 256, 600 * 1024,
                                 dtype=np.uint8).tobytes()
        self.master = MasterServer(volume_size_limit_mb=64)
        self.master.start()
        self.vs1 = VolumeServer([str(tmp_path / "v1")], self.master.url)
        self.vs1.start()
        self.mc = MasterClient(self.master.url, cache_ttl=0.0)
        self.fid = operation.upload_data(self.mc, self.data).fid
        vid = int(self.fid.split(",")[0])
        from seaweedfs_tpu.storage.file_id import parse_needle_id_cookie
        nid, _ = parse_needle_id_cookie(self.fid.split(",", 1)[1])
        ShellContext(self.master.url, use_grpc=False).ec_encode(vid=vid)
        ev = self.vs1.store.find_ec_volume(vid)
        intervals, _, _ = ev.locate_needle(nid)
        sid = sorted({iv.to_shard_id_and_offset()[0]
                      for iv in intervals})[0]

        vs2_port = _free_port()
        self.proxy = ChaosProxy("127.0.0.1", vs2_port, mode=mode).start()
        self.vs2 = VolumeServer([str(tmp_path / "v2")], self.master.url,
                                port=vs2_port, advertise=self.proxy.url)
        self.vs2.start()
        self.servers = [self.vs1, self.vs2]
        src = f"{self.vs1.http.host}:{self.vs1.http.port}"
        targets = [f"{self.vs2.http.host}:{self.vs2.http.port}"]
        if with_fast_holder:
            self.vs3 = VolumeServer([str(tmp_path / "v3")],
                                    self.master.url)
            self.vs3.start()
            self.servers.append(self.vs3)
            targets.append(f"{self.vs3.http.host}:{self.vs3.http.port}")
        for direct in targets:  # setup bypasses the proxy
            http_json("POST", f"http://{direct}/admin/ec/copy",
                      {"volume_id": vid, "shard_ids": [sid],
                       "source_data_node": src})
            http_json("POST", f"http://{direct}/admin/ec/mount",
                      {"volume_id": vid, "shard_ids": [sid]})
        http_json("POST", f"http://{src}/admin/ec/unmount",
                  {"volume_id": vid, "shard_ids": [sid]})
        http_json("POST", f"http://{src}/admin/ec/delete_shards",
                  {"volume_id": vid, "shard_ids": [sid]})
        time.sleep(0.2)
        # every read must take the remote shard hop these scenarios
        # exercise — a warm needle cache would serve repeats from
        # memory and starve the breaker of probe traffic
        self.vs1.store.needle_cache = None

    def read(self, deadline_s=None, timeout=30.0):
        headers = ({DEADLINE_HEADER: f"{deadline_s:.3f}"}
                   if deadline_s else None)
        return http_call("GET", f"http://{self.vs1.url}/{self.fid}",
                         timeout=timeout, headers=headers)

    def stop(self):
        self.mc.stop()
        for vs in reversed(self.servers):
            vs.stop()
        self.proxy.stop()
        self.master.stop()


def test_chaos_blackholed_peer_degraded_read_within_deadline(tmp_path):
    """Scenario (a): the only remote holder of the needed shard is
    blackholed. The remote fetch gets a CHILD deadline (a fraction of
    the edge budget), fails, and degraded reconstruction from the 13
    local shards still answers inside the caller's deadline."""
    c = _EcChaosCluster(tmp_path, mode="blackhole",
                        with_fast_holder=False)
    try:
        t0 = time.perf_counter()
        status, body, _ = c.read(deadline_s=4.0, timeout=6.0)
        elapsed = time.perf_counter() - t0
        assert status == 200
        assert body == c.data
        assert elapsed < 4.0, f"read blew its deadline: {elapsed:.2f}s"
        # the blackholed peer was seen failing
        snap = c.vs1.peer_health.snapshot()
        assert snap[c.proxy.url]["failure_total"] >= 1
    finally:
        c.stop()


def test_chaos_open_circuit_redirects_then_half_open_recovers(tmp_path):
    """Scenario (b): connection resets trip the straggler's breaker
    open; reads keep succeeding via the healthy holder without paying
    for the dead peer. After the fault is healed, a half-open probe
    piggybacked on real traffic closes the breaker again."""
    c = _EcChaosCluster(tmp_path, mode="reset", with_fast_holder=True)
    try:
        # tightened breaker so the test doesn't need 5 failures / 5s
        c.vs1.peer_health = PeerHealth(failure_threshold=1, open_for=0.4)
        c.vs1.store.peer_health = c.vs1.peer_health

        status, body, _ = c.read()
        assert status == 200 and body == c.data
        deadline = time.time() + 5
        while time.time() < deadline:  # first read may have won via vs3
            if c.vs1.peer_health.snapshot().get(
                    c.proxy.url, {}).get("state") == OPEN:
                break
            status, body, _ = c.read()
            assert status == 200 and body == c.data
        assert c.vs1.peer_health.snapshot()[c.proxy.url]["state"] == OPEN

        # open circuit: reads are served by vs3, quickly
        t0 = time.perf_counter()
        status, body, _ = c.read()
        assert status == 200 and body == c.data
        assert time.perf_counter() - t0 < 1.0

        # heal the peer; once the open window elapses, a ripe probe
        # rides along a real read and closes the breaker
        c.proxy.set_fault(mode="pass")
        time.sleep(0.5)
        deadline = time.time() + 5
        while time.time() < deadline:
            status, body, _ = c.read()
            assert status == 200 and body == c.data
            if c.vs1.peer_health.snapshot()[
                    c.proxy.url]["state"] == CLOSED:
                break
            time.sleep(0.1)
        assert c.vs1.peer_health.snapshot()[c.proxy.url]["state"] \
            == CLOSED
    finally:
        c.stop()


def test_cluster_health_surfaces_breakers_and_budget(tmp_path):
    """The shell's cluster.health view: master endpoint + per-node
    /admin/health, including repair-budget fields (satellite: shared
    repair bandwidth budget is observable)."""
    c = _EcChaosCluster(tmp_path, mode="pass", with_fast_holder=False)
    try:
        status, body, _ = c.read()
        assert status == 200
        sh = ShellContext(c.master.url, use_grpc=False)
        out = sh.cluster_health()
        assert out["is_leader"] is True
        assert "repair" in out
        assert "rate_bytes_per_sec" in out["repair"]
        urls = {n["url"] for n in out["nodes"]}
        assert c.proxy.url in urls  # vs2 registered via its advertise
        vs1_node = next(n for n in out["nodes"]
                        if n["url"] == c.vs1.url)
        assert "scrubbing" in vs1_node
        assert "peers" in vs1_node["health"]
    finally:
        c.stop()
