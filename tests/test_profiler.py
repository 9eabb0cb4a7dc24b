"""The continuous-profiling plane (PR 14): the always-on wall-stack
sampler + thread->scope registry, the per-(class, tenant) resource
ledger, the folded-stack algebra behind cluster flamegraphs, and the
hint-journal staleness alert that rides the same telemetry transport.

Layers:

1. registry units — tag() is free when no sampler runs, scoped tags
   nest and restore, the sampler prefixes tagged stacks with
   class:/route: roots and untagged ones with thread:<name>;
2. folded algebra — text round-trip, merge as exact count addition,
   frame-share diffing surfaces a planted regression;
3. ledger units — CPU attribution follows the thread that burned the
   CPU, rows fold into (other) past the bound, merge sums elementwise;
4. plane e2e — an HttpServer with a ledger bills requests per class
   and tenant; /admin/profile serves a window; a wedged hint journal
   trips `hints_stale` in the cluster rollup.
"""

import threading
import time

from seaweedfs_tpu.stats.ledger import FIELDS, OTHER_TENANT, ResourceLedger
from seaweedfs_tpu.stats.telemetry import (HINTS_AGE_MAX_S,
                                           ClusterTelemetry)
from seaweedfs_tpu.utils import clockctl, profiler
from seaweedfs_tpu.utils.profiler import (WallSampler, diff_folded,
                                          frame_shares, merge_folded,
                                          parse_folded, to_folded_text)

# ------------------------------------------- thread->scope registry


def test_tag_is_free_with_no_sampler():
    """The disabled path: no sampler running -> tag() returns None
    without touching the registry, untag(None) is a no-op."""
    assert not profiler._active
    token = profiler.tag("interactive", "read", "tid1")
    assert token is None
    assert threading.get_ident() not in profiler._scopes
    profiler.untag(token)


def test_scope_nests_and_restores():
    s = WallSampler(hz=1000.0)
    s.start()
    try:
        ident = threading.get_ident()
        with profiler.scope(cls="write", route="put"):
            assert profiler._scopes[ident][0] == "write"
            with profiler.scope(cls="background", route="scrub"):
                assert profiler._scopes[ident][0] == "background"
            assert profiler._scopes[ident][0] == "write"
        assert ident not in profiler._scopes
    finally:
        s.stop()


def _busy(stop: threading.Event) -> None:
    x = 0
    while not stop.is_set():
        x += 1


def test_sampler_attributes_tagged_and_untagged_threads():
    """A tagged busy loop folds under class:/route: roots; an untagged
    one folds under its thread name (what the unnamed-thread lint rule
    protects)."""
    s = WallSampler(hz=200.0)
    stop = threading.Event()

    def tagged():
        with profiler.scope(cls="interactive", route="read",
                            trace_id="feedc0de"):
            _busy(stop)

    threads = [
        threading.Thread(target=tagged, daemon=True, name="tagged-w"),
        threading.Thread(target=_busy, args=(stop,), daemon=True,
                         name="plain-worker"),
    ]
    s.start()
    try:
        for t in threads:
            t.start()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            snap = s.snapshot()
            tagged_keys = [k for k in snap["folded"]
                           if k.startswith("class:interactive;route:read;")]
            named_keys = [k for k in snap["folded"]
                          if k.startswith("thread:plain-worker;")]
            if tagged_keys and named_keys:
                break
            time.sleep(0.02)
    finally:
        stop.set()
        s.stop()
        for t in threads:
            t.join(timeout=2.0)
    assert tagged_keys, snap["folded"].keys()
    assert named_keys, snap["folded"].keys()
    # the sampled trace id survives as the stack's exemplar
    assert any(snap["exemplars"].get(k) == "feedc0de"
               for k in tagged_keys)


def test_sampler_window_is_a_delta():
    """window(N) reports only samples taken during the window, not the
    cumulative table."""
    s = WallSampler(hz=200.0)
    stop = threading.Event()
    t = threading.Thread(target=_busy, args=(stop,), daemon=True,
                         name="win-worker")
    s.start()
    t.start()
    try:
        time.sleep(0.3)
        before = s.snapshot()["samples"]
        assert before > 0
        win = s.window(0.3)
        assert 0 < win["samples"] <= s.snapshot()["samples"] - before + 5
        assert win["folded"], "window saw no stacks"
    finally:
        stop.set()
        s.stop()
        t.join(timeout=2.0)


def test_stack_table_bounded_by_overflow_bucket():
    s = WallSampler(hz=0)  # never starts a thread
    assert not s.running
    s.start()
    assert not s.running
    s.max_stacks = 1
    # drive the fold path directly: second distinct stack overflows
    with s._lock:
        for key in ("a;b", "a;b", "c;d", "e;f"):
            if key in s._counts or len(s._counts) < s.max_stacks:
                s._counts[key] = s._counts.get(key, 0) + 1
            else:
                s._counts[profiler.OVERFLOW_KEY] = \
                    s._counts.get(profiler.OVERFLOW_KEY, 0) + 1
    snap = s.snapshot()
    assert snap["folded"]["a;b"] == 2
    assert snap["folded"][profiler.OVERFLOW_KEY] == 2


# ------------------------------------------------- folded algebra


def test_folded_text_roundtrip_and_merge():
    a = {"class:write;httpd._dispatch;store.write": 7,
         "thread:scrubber;scrubber.run_once": 3}
    b = {"class:write;httpd._dispatch;store.write": 5,
         "class:interactive;httpd._dispatch;store.read": 2}
    assert parse_folded(to_folded_text(a)) == a
    assert parse_folded("") == {}
    assert parse_folded("# comment\n\nx;y 4\nx;y 1\n") == {"x;y": 5}
    merged = merge_folded([a, b])
    assert merged["class:write;httpd._dispatch;store.write"] == 12
    assert merged["class:interactive;httpd._dispatch;store.read"] == 2
    assert sum(merged.values()) == sum(a.values()) + sum(b.values())


def test_frame_shares_are_inclusive():
    table = {"a;b;c": 6, "a;d": 4}
    shares = frame_shares(table)
    assert shares["a"] == 1.0  # on every stack
    assert shares["b"] == 0.6
    assert shares["d"] == 0.4
    assert frame_shares({}) == {}


def test_diff_folded_surfaces_planted_regression():
    """A frame that grew from 10% to 60% of samples tops the diff; a
    stable hot frame does not appear (no growth)."""
    baseline = {"root;serve;fast_path": 90, "root;serve;gzip": 10}
    current = {"root;serve;fast_path": 40, "root;serve;gzip": 60}
    rows = diff_folded(baseline, current)
    assert rows, "regression not reported"
    assert rows[0]["frame"] == "gzip"
    assert rows[0]["delta"] == 0.5
    frames = [r["frame"] for r in rows]
    assert "root" not in frames and "serve" not in frames
    # noise floor: a frame under min_share in both profiles is skipped
    assert diff_folded({"a;tiny": 1, "a;big": 999},
                       {"a;tiny": 2, "a;big": 998},
                       min_share=0.05) == []


# ------------------------------------------------------ ledger units


def test_ledger_accumulates_and_sorts_by_cpu():
    led = ResourceLedger()
    led.observe_request("interactive", "10.0.0.1", cpu_s=0.002,
                        bytes_in=0, bytes_out=4096)
    led.observe_request("interactive", "10.0.0.1", cpu_s=0.003,
                        bytes_in=0, bytes_out=4096)
    led.observe_request("write", "10.0.0.2", cpu_s=0.050,
                        bytes_in=65536, bytes_out=128)
    led.charge_disk(8192, cls="interactive", tenant="10.0.0.1")
    snap = led.snapshot()
    assert snap["fields"] == list(FIELDS)
    # hottest CPU first
    assert snap["rows"][0][:2] == ["write", "10.0.0.2"]
    rows = led.rows()
    hot = rows[("interactive", "10.0.0.1")]
    assert hot["requests"] == 2
    assert hot["cpu_ms"] == 5.0
    assert hot["bytes_out"] == 8192
    assert hot["disk_bytes_read"] == 8192


def test_ledger_cpu_attribution_follows_the_hot_tenant():
    """Bill two tenants from their own threads with real thread-CPU
    deltas (the dispatch-site recipe): the tenant that burned the CPU
    dominates the ledger."""
    led = ResourceLedger()

    def serve(tenant: str, spin_s: float) -> None:
        t0 = clockctl.thread_time()
        if spin_s:
            deadline = clockctl.thread_time() + spin_s
            x = 0
            while clockctl.thread_time() < deadline:
                x += 1
        else:
            time.sleep(0.05)  # idle wait burns ~no CPU
        led.observe_request("interactive", tenant,
                            cpu_s=clockctl.thread_time() - t0,
                            bytes_in=0, bytes_out=0)

    hot = threading.Thread(target=serve, args=("hot", 0.05),
                           daemon=True, name="hot-tenant")
    cold = threading.Thread(target=serve, args=("cold", 0.0),
                            daemon=True, name="cold-tenant")
    hot.start(), cold.start()
    hot.join(timeout=5.0), cold.join(timeout=5.0)
    rows = led.rows()
    hot_ms = rows[("interactive", "hot")]["cpu_ms"]
    cold_ms = rows[("interactive", "cold")]["cpu_ms"]
    assert hot_ms >= 10 * max(cold_ms, 0.1), (hot_ms, cold_ms)
    # and the top() helper agrees
    leader = led.top(1, "cpu_ms")[0]
    assert (leader["class"], leader["tenant"]) == ("interactive", "hot")


def test_ledger_bounds_rows_via_other_bucket():
    led = ResourceLedger(max_rows=4)
    for i in range(10):
        led.observe_request("write", f"t{i}", cpu_s=0.001,
                            bytes_in=100, bytes_out=0)
    rows = led.rows()
    # max_rows caps distinct tenants; the per-class (other) aggregate
    # rides on top of the bound
    named = [k for k in rows if k[1] != OTHER_TENANT]
    assert len(named) == 4
    other = rows[("write", OTHER_TENANT)]
    # the overflowed tenants' traffic is conserved, not dropped
    total_reqs = sum(r["requests"] for r in rows.values())
    assert total_reqs == 10
    assert other["requests"] == 6


def test_ledger_merge_sums_elementwise():
    a, b = ResourceLedger(), ResourceLedger()
    a.observe_request("write", "t1", cpu_s=0.001, bytes_in=10,
                      bytes_out=1)
    b.observe_request("write", "t1", cpu_s=0.002, bytes_in=20,
                      bytes_out=2)
    b.observe_request("background", "t2", cpu_s=0.004, bytes_in=0,
                      bytes_out=0)
    merged = ResourceLedger()
    merged.merge_from(a.snapshot())
    merged.merge_from(b.snapshot())
    rows = merged.rows()
    t1 = rows[("write", "t1")]
    assert t1["requests"] == 2
    assert t1["cpu_ms"] == 3.0
    assert t1["bytes_in"] == 30
    assert rows[("background", "t2")]["cpu_ms"] == 4.0


# ------------------------------------------------------- plane e2e


def test_http_dispatch_bills_ledger_and_tags_sampler():
    """The real dispatch seam: an HttpServer with a ledger attached
    bills each request's class/tenant row, honors tenant_fn, and the
    /admin/profile handler exports a window."""
    from seaweedfs_tpu.utils.httpd import HttpServer, Response, http_call, \
        http_json

    srv = HttpServer()
    sampler = WallSampler(hz=97.0)

    def slow(req):
        deadline = clockctl.thread_time() + 0.01
        x = 0
        while clockctl.thread_time() < deadline:  # measurable CPU
            x += 1
        return Response({"ok": True})

    srv.add("GET", "/data/x", slow)
    srv.add("GET", "/admin/profile",
            profiler.make_profile_handler(
                sampler, lambda: f"{srv.host}:{srv.port}", "test"))
    srv.ledger = ResourceLedger()
    srv.tenant_fn = lambda headers, ip: headers.get("X-Tenant", ip)
    srv.start()
    sampler.start()
    try:
        for tenant in ("alice", "alice", "bob"):
            status, _, _ = http_call(
                "GET", f"http://{srv.host}:{srv.port}/data/x",
                headers={"X-Tenant": tenant})
            assert status == 200
        rows = srv.ledger.rows()
        by_tenant = {t: r for (cls, t), r in rows.items()}
        assert by_tenant["alice"]["requests"] == 2
        assert by_tenant["bob"]["requests"] == 1
        assert by_tenant["alice"]["cpu_ms"] > 0
        assert by_tenant["alice"]["bytes_out"] > 0

        win = http_json(
            "GET",
            f"http://{srv.host}:{srv.port}/admin/profile?seconds=0.3")
        assert win["rate_hz"] == 97.0
        assert win["server"] == "test"
        assert win["node"] == f"{srv.host}:{srv.port}"
    finally:
        sampler.stop()
        srv.stop()


def test_wedged_hint_journal_trips_hints_stale_alert(tmp_path):
    """A journal whose drain is wedged (rows recorded, none acked)
    ages past HINTS_AGE_MAX_S and the rollup fires `hints_stale`;
    a healthy journal stays quiet."""
    from seaweedfs_tpu.storage.hinted_handoff import HintJournal

    j = HintJournal(str(tmp_path / "hints.journal"), fsync=False)
    j.record("put", 1, 2, 3, "127.0.0.1:9999")
    st = j.stats()
    assert st["pending_rows"] == 1
    assert st["oldest_debt_age_s"] >= 0.0
    j.close()

    ct = ClusterTelemetry()
    mk = lambda age, pending: [{  # noqa: E731 — table-driven
        "node": "v1", "red": None, "hotkeys": None,
        "hints": {"pending_rows": pending, "oldest_debt_age_s": age}}]
    healthy = ct.rollup(1.0, mk(2.0, 3))
    assert "hints_stale" not in healthy["alerts_firing"]
    assert healthy["hints"][0]["pending_rows"] == 3
    wedged = ct.rollup(2.0, mk(HINTS_AGE_MAX_S + 5.0, 3))
    assert "hints_stale" in wedged["alerts_firing"]
    flooded = ct.rollup(3.0, mk(1.0, 100000))
    assert "hints_stale" in flooded["alerts_firing"]


def test_batcher_exports_wait_and_size_histograms():
    """The EC batch scheduler's stats() carries the per-class
    submit->dispatch wait histogram and the coalesced-size histogram;
    a burst of submissions lands in both."""
    import numpy as np

    from seaweedfs_tpu.parallel.batcher import EcBatchScheduler

    sched = EcBatchScheduler()
    # bench the mesh: every batch takes the CPU drain, whatever the
    # environment's devices
    sched._down_until = float("inf")
    try:
        rng = np.random.default_rng(5)
        data = rng.integers(0, 256, (10, 64), dtype=np.uint8)
        futs = [sched.submit_encode(data, cls="write")
                for _ in range(8)]
        for f in futs:
            f.result(timeout=30)
        st = sched.stats()
        wait = st["wait_hist"]
        assert wait["label_names"] == ["class"]
        write_series = [s for s in wait["series"]
                        if s[0] == ["write"]]
        assert write_series and sum(write_series[0][1]) == 8
        size = st["size_hist"]
        assert sum(sum(s[1]) for s in size["series"]) \
            == st["batches_total"]
    finally:
        sched.stop()


def test_prof_collect_merges_cluster_flamegraph(tmp_path):
    """The acceptance drill: a 3-node cluster (master + volume +
    filer) under mixed load, then tools/prof_collect.py pulls every
    node's window, merges it into one folded file with class-tagged
    stacks, and --diff round-trips against itself with no regression
    rows."""
    import tempfile

    from seaweedfs_tpu.server.filer_server import FilerServer
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.utils.httpd import http_call
    from tools import prof_collect

    with tempfile.TemporaryDirectory() as d:
        ms = MasterServer(volume_size_limit_mb=64, profile_hz=97.0)
        ms.start()
        vs = VolumeServer([d], ms.url, profile_hz=97.0)
        vs.start()
        time.sleep(0.3)
        fs = FilerServer(ms.url, profile_hz=97.0)
        fs.start()
        stop = threading.Event()

        def load():
            i = 0
            while not stop.is_set():
                http_call("POST", f"http://{fs.url}/bench/f{i % 4}",
                          body=b"\xa5" * 8192)
                http_call("GET", f"http://{fs.url}/bench/f{i % 4}")
                i += 1

        loader = threading.Thread(target=load, daemon=True,
                                  name="load-gen")
        loader.start()
        try:
            time.sleep(0.5)  # let samplers see the load
            out = tmp_path / "cluster.folded"
            rc = prof_collect.main(
                ["--master", ms.url, "--node", fs.metrics_url,
                 "--seconds", "1", "--out", str(out)])
            assert rc == 0
            merged = parse_folded(out.read_text())
            assert merged, "empty merged profile"
            assert any(k.startswith("class:") for k in merged), \
                list(merged)[:5]
            # self-diff: nothing grew, so no regression rows
            rc = prof_collect.main(
                ["--master", ms.url, "--node", fs.metrics_url,
                 "--seconds", "0", "--diff", str(out), "--top", "3"])
            assert rc == 0
        finally:
            stop.set()
            loader.join(timeout=5.0)
            fs.stop()
            vs.stop()
            ms.stop()


def test_tenant_flood_floor():
    """The qos isolation floor the bench (bench_tenant_flood)
    demonstrates: with per-tenant write-class rates configured, an
    aggressor flooding the governor cannot push the victim tenant
    below its offered rate."""
    import bench

    out = bench.bench_tenant_flood(duration_s=0.6, victim_rate=40.0,
                                   cap_rate=50.0)
    # the cap clips the aggressor by orders of magnitude...
    assert out["flood_capped_aggressor_rps"] < \
        0.05 * out["flood_uncapped_aggressor_rps"], out
    # ...and the victim (offering under the cap) keeps its throughput:
    # at least half the offered 40/s even under CI scheduling jitter
    assert out["flood_capped_victim_rps"] > 20.0, out
