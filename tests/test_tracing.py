"""Distributed tracing (round 10): X-Weed-Trace propagation across the
serving edges, per-node flight recorders at /debug/traces, the
zero-cost-when-disabled contract, glog trace stamping, pressure-aware
repair-chain planning, and the cross-node trace collector."""

import json
import re
import threading
import time

import pytest

from seaweedfs_tpu.utils import glog, tracing
from seaweedfs_tpu.utils.httpd import http_call, http_json


@pytest.fixture(autouse=True)
def _reset_glog():
    yield
    glog.reset()


def _recorded(snapshot, pred, timeout=5.0) -> list:
    """The spans ``pred`` keeps of ``snapshot()``, once there are any: a
    server span is recorded after its response's flush (its send_ms
    holds it), so a caller may read the recorder a moment before."""
    deadline = time.time() + timeout
    while True:
        spans = [s for s in snapshot()["spans"] if pred(s)]
        if spans or time.time() > deadline:
            return spans
        time.sleep(0.01)


# ---- span / tracer unit semantics ----

def test_header_roundtrip_and_parse():
    tr = tracing.Tracer(node="n", sample_rate=1.0)
    sp = tr.root_span("op", sampled=True)
    parsed = tracing.parse_header(sp.header_value())
    assert parsed == (sp.trace_id, sp.span_id, True)
    assert tracing.parse_header("garbage") is None
    assert tracing.parse_header("a:b") is None
    assert tracing.parse_header("xyz:12ab:1") is None  # non-hex trace
    assert tracing.parse_header("12ab34cd:9f:notanint") is None


def test_child_span_links_parent():
    tr = tracing.Tracer(node="n", sample_rate=1.0)
    root = tr.root_span("root", sampled=True)
    ch = root.child("hop")
    assert ch.trace_id == root.trace_id
    assert ch.parent_id == root.span_id
    assert ch.span_id != root.span_id
    assert ch.sampled is True


def test_noop_span_is_shared_and_inert():
    tr = tracing.Tracer(node="n", enabled=False)
    sp = tr.server_span("GET /x", {})
    assert sp is tracing.NOOP
    assert not sp
    assert sp.child("c") is tracing.NOOP
    sp.annotate("k", 1)
    sp.finish(status=500, error="boom")
    snap = tr.snapshot()
    assert snap["enabled"] is False
    assert snap["spans"] == [] and snap["started"] == 0
    # root spans honor the same contract
    assert tr.root_span("job", sampled=True) is tracing.NOOP


def test_recorder_tail_keep_policy():
    tr = tracing.Tracer(node="n", sample_rate=0.0, slow_ms=50.0)
    fast = tr.server_span("GET /fast", {})
    assert fast.sampled is False
    fast.finish(status=200)  # unsampled, fast, OK -> dropped
    err = tr.server_span("GET /err", {})
    err.finish(status=503)  # 5xx -> always kept
    slow = tr.server_span("GET /slow", {})
    slow._t0 -= 1.0  # fake a 1s request (durations are monotonic)
    slow.finish(status=200)  # past slow_ms -> always kept
    snap = tr.snapshot()
    assert [s["name"] for s in snap["spans"]] == ["GET /err", "GET /slow"]
    assert snap["started"] == 3 and snap["kept"] == 2
    # snapshot filters: trace id and min duration
    assert tr.snapshot(trace_id=err.trace_id)["spans"][0]["name"] \
        == "GET /err"
    assert [s["name"] for s in tr.snapshot(min_ms=500.0)["spans"]] \
        == ["GET /slow"]


def test_scope_helpers_and_annotations():
    tr = tracing.Tracer(node="n", sample_rate=1.0)
    root = tr.root_span("root", sampled=True)
    assert tracing.current_span() is None
    assert tracing.current_trace_id() == ""
    tracing.annotate("dropped", 1)  # no ambient span: free no-op
    with tracing.span_scope(root):
        assert tracing.current_span() is root
        assert tracing.current_trace_id() == root.trace_id
        with tracing.child_scope("stage") as ch:
            assert ch.parent_id == root.span_id
            tracing.annotate("k", "v")
    assert tracing.current_span() is None
    stage = [s for s in tr.snapshot()["spans"] if s["name"] == "stage"]
    assert stage and stage[0]["annotations"] == {"k": "v"}
    # child_scope outside any trace is a NOOP passthrough
    with tracing.child_scope("orphan") as ch:
        assert ch is tracing.NOOP


def test_server_span_continues_inbound_header():
    tr = tracing.Tracer(node="n", sample_rate=0.0)
    inbound = {tracing.TRACE_HEADER: "12ab34cd12ab34cd:9f9f9f9f:1"}
    sp = tr.server_span("GET /x", inbound)
    assert sp.trace_id == "12ab34cd12ab34cd"
    assert sp.parent_id == "9f9f9f9f"
    assert sp.sampled is True  # inherited, beats the 0% head rate
    # malformed header: mint fresh instead of failing the request
    sp2 = tr.server_span("GET /x", {tracing.TRACE_HEADER: "zz:yy"})
    assert len(sp2.trace_id) == 16 and sp2.parent_id == ""


# ---- glog cross-referencing (satellite: [t=...] stamps) ----

def test_glog_lines_carry_trace_id(tmp_path):
    log = tmp_path / "weed.log"
    glog.set_log_file(str(log), also_stderr=False)
    tr = tracing.Tracer(node="n", sample_rate=1.0)
    sp = tr.root_span("op", sampled=True)
    glog.info("plain line")
    with tracing.span_scope(sp):
        glog.info("traced line")
    unsampled = tr.root_span("quiet", sampled=False)
    with tracing.span_scope(unsampled):
        glog.info("unsampled line")
    lines = log.read_text().splitlines()
    assert "[t=" not in lines[0]
    assert f"[t={sp.trace_id[:8]}] traced line" in lines[1]
    # unsampled spans keep the historical line format byte-identical
    assert "[t=" not in lines[2]


# ---- pressure-aware repair-chain planning (satellite) ----

def test_rank_pressure_tiebreak():
    from seaweedfs_tpu.utils.resilience import PeerHealth
    h = PeerHealth()
    urls = ["peer-a:80", "peer-b:80"]
    # fresh, equally-healthy peers: heartbeat pressure breaks the tie
    assert h.rank(urls, pressure={"peer-a:80": 0.9,
                                  "peer-b:80": 0.1})[0] == "peer-b:80"
    assert h.rank(urls, pressure={"peer-a:80": 0.1,
                                  "peer-b:80": 0.9})[0] == "peer-a:80"
    # a genuinely slower peer still loses, whatever its pressure says
    for _ in range(20):
        h.record("peer-a:80", True, latency_s=0.005)
        h.record("peer-b:80", True, latency_s=0.200)
    assert h.rank(urls, pressure={"peer-a:80": 1.0,
                                  "peer-b:80": 0.0})[0] == "peer-a:80"


def test_plan_chain_prefers_calm_holders():
    from seaweedfs_tpu.storage.erasure_coding.partial import plan_chain
    sources = {3: ["busy:1", "calm:1"], 7: ["busy:1", "calm:1"]}
    coeffs = {3: [1, 2], 7: [3, 4]}
    # without pressure, master-lookup order wins
    hops = plan_chain(sources, coeffs)
    assert [h["url"] for h in hops] == ["busy:1"]
    # with pressure, the whole chain routes around the loaded holder
    hops = plan_chain(sources, coeffs,
                      pressure={"busy:1": 0.8, "calm:1": 0.0})
    assert [h["url"] for h in hops] == ["calm:1"]
    assert len(hops[0]["members"]) == 2


# ---- metrics thread-safety (satellite) ----

def test_metrics_expose_races_writers():
    """Counter.inc / Histogram.observe hammered from threads while
    expose_text scrapes AND merge_from folds in remote snapshots
    concurrently (the telemetry-plane hot path): every exposition
    parses, counter totals only go up, and the final totals are
    exact."""
    from seaweedfs_tpu.utils.metrics import Registry
    reg = Registry(namespace="TST")
    ctr = reg.counter("race", "ops_total", "ops", labels=("kind",))
    hist = reg.histogram("race", "lat_seconds", "lat", labels=("kind",))
    n_writers, per = 4, 2000
    n_merges, donor_n = 50, 3
    errors = []

    def writer(i):
        try:
            for j in range(per):
                ctr.inc(f"k{i % 2}")
                hist.observe(j * 1e-4, f"k{i % 2}")
        except Exception as e:  # pragma: no cover - the failure mode
            errors.append(e)

    # a "remote node" snapshot folded in over and over, as the master
    # does with every heartbeat-piggybacked RED snapshot
    donor = Registry(namespace="TST").histogram(
        "race", "lat_seconds", "lat", labels=("kind",))
    for j in range(donor_n):
        donor.observe(j * 1e-3, "k0", exemplar=f"trace{j}")
    donor_snap = donor.snapshot()

    def merger():
        try:
            for _ in range(n_merges):
                hist.merge_from(donor_snap)
        except Exception as e:  # pragma: no cover - the failure mode
            errors.append(e)

    def total_of(text):
        return sum(float(line.rsplit(" ", 1)[1])
                   for line in text.splitlines()
                   if line.startswith("TST_race_ops_total{"))

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(n_writers)]
    threads.append(threading.Thread(target=merger))
    for t in threads:
        t.start()
    last = 0.0
    while any(t.is_alive() for t in threads):
        text = reg.expose_text()
        for line in text.splitlines():
            if line.startswith("#") or not line.strip():
                continue
            float(line.rsplit(" ", 1)[1])  # every sample parses
        now = total_of(text)
        assert now >= last, "counter went backwards under race"
        last = now
    for t in threads:
        t.join()
    assert not errors
    final = reg.expose_text()
    assert total_of(final) == n_writers * per
    hist_counts = sum(float(line.rsplit(" ", 1)[1])
                      for line in final.splitlines()
                      if line.startswith("TST_race_lat_seconds_count"))
    assert hist_counts == n_writers * per + n_merges * donor_n
    # the merged-in exemplars survived and the suffix still parses
    # (the scrape loop above float()s the last token of every line)
    assert 'trace_id="trace' in final


# ---- end-to-end: one S3 PUT, one stitched trace ----

@pytest.fixture
def traced_stack(tmp_path):
    from seaweedfs_tpu.gateway.s3_server import S3Server
    from seaweedfs_tpu.server.filer_server import FilerServer
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    ms = MasterServer(volume_size_limit_mb=64, trace_sample=1.0)
    ms.start()
    vs1 = VolumeServer([str(tmp_path / "v1")], ms.url, trace_sample=1.0)
    vs1.start()
    vs2 = VolumeServer([str(tmp_path / "v2")], ms.url, trace_sample=1.0)
    vs2.start()
    time.sleep(0.3)  # both heartbeats registered before assigns
    fs = FilerServer(ms.url, default_replication="001", trace_sample=1.0)
    fs.start()
    s3 = S3Server(fs, trace_sample=1.0)
    s3.start()
    yield ms, vs1, vs2, fs, s3
    s3.stop()
    fs.stop()
    vs2.stop()
    vs1.stop()
    ms.stop()


def test_s3_put_produces_single_stitched_trace(traced_stack):
    ms, vs1, vs2, fs, s3 = traced_stack
    status, _, _ = http_call("PUT", f"http://{s3.url}/tracebkt")
    assert status < 300
    body = b"\xab" * 256 * 1024
    status, _, _ = http_call("PUT", f"http://{s3.url}/tracebkt/obj",
                             body=body)
    assert status < 300

    # the gateway edge minted the root; find its trace id
    roots = _recorded(s3.tracer.snapshot,
                      lambda s: s["name"] == "PUT /tracebkt/obj")
    assert roots, "gateway recorded no span for the object PUT"
    tid = roots[0]["trace_id"]
    assert roots[0]["parent_id"] == ""  # edge-minted, not continued

    # collect the same trace over HTTP from every node's recorder —
    # gateway/filer serve /debug/traces on their metrics listener
    def collect():
        spans, nodes = [], 0
        for url in (s3.metrics_url, fs.metrics_url, ms.url,
                    vs1.url, vs2.url):
            snap = http_json("GET",
                             f"http://{url}/debug/traces?trace={tid}")
            nodes += bool(snap["spans"])
            spans.extend(snap["spans"])
        return spans, nodes

    # the hops' server spans close after their responses left
    deadline = time.time() + 5
    spans, nodes_answering = collect()
    while (nodes_answering < 3 or len(spans) < 6 or not any(
            (s.get("annotations") or {}).get("replica.fanout")
            for s in spans)) and time.time() < deadline:
        time.sleep(0.05)
        spans, nodes_answering = collect()

    assert nodes_answering >= 3, \
        f"trace only visible on {nodes_answering} nodes"
    assert all(s["trace_id"] == tid for s in spans)
    assert len(spans) >= 6, \
        f"expected >=6 spans, got {[s['name'] for s in spans]}"

    # replica fan-out shows up as an annotated parent + client child
    fanout = [s for s in spans
              if (s.get("annotations") or {}).get("replica.fanout")]
    assert fanout, "no replica fan-out annotation in the trace"
    kids = [s for s in spans
            if s["parent_id"] == fanout[0]["span_id"]
            and s["kind"] == "client"]
    assert kids, "replica fan-out produced no client child span"

    # QoS admission decisions ride the same spans
    verdicts = {(s.get("annotations") or {}).get("qos.verdict")
                for s in spans}
    assert "admitted" in verdicts


def test_webdav_edge_propagates_trace_and_deadline(tmp_path):
    """A traced request through the WebDAV edge carries X-Weed-Trace to
    the volume tier (the chunk upload is a real wire hop) and honors an
    inbound X-Weed-Deadline — an exhausted budget fails the write fast
    instead of uploading chunks."""
    from seaweedfs_tpu.gateway.webdav_server import WebDavServer
    from seaweedfs_tpu.server.filer_server import FilerServer
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.utils import headers as weed_headers

    ms = MasterServer(volume_size_limit_mb=64, trace_sample=1.0)
    ms.start()
    vs = VolumeServer([str(tmp_path / "v")], ms.url, trace_sample=1.0)
    vs.start()
    time.sleep(0.2)
    fs = FilerServer(ms.url, trace_sample=1.0)
    fs.start()
    dav = WebDavServer(fs, trace_sample=1.0)
    dav.start()
    try:
        tid = "00deadbeef001234"
        # > 2048 bytes so the filer uploads real chunks volume-ward
        status, _, _ = http_call(
            "PUT", f"http://{dav.url}/traced.bin", body=b"x" * 8192,
            headers={weed_headers.TRACE: f"{tid}:1234abcd:1",
                     weed_headers.DEADLINE: "30"})
        assert status == 201
        vol_spans = _recorded(vs.tracer.snapshot,
                              lambda s: s["trace_id"] == tid)
        assert vol_spans, \
            "X-Weed-Trace died at the WebDAV edge instead of riding " \
            "the chunk upload to the volume server"

        # deadline honored downstream: an exhausted budget makes the
        # chunk upload raise DeadlineExceeded before any bytes move
        status, _, _ = http_call(
            "PUT", f"http://{dav.url}/late.bin", body=b"y" * 8192,
            headers={weed_headers.DEADLINE: "0.000001"})
        assert status >= 500
        assert fs.filer.find_entry("/late.bin") is None
    finally:
        dav.stop()
        fs.stop()
        vs.stop()
        ms.stop()


def test_iam_edge_continues_inbound_trace(tmp_path):
    """The IAM edge continues an inbound X-Weed-Trace (server span on
    the caller's trace, parented to the caller's span) rather than
    dropping it, so its filer-ward writes stay on the same trace."""
    from seaweedfs_tpu.gateway.iam_server import IamServer
    from seaweedfs_tpu.server.filer_server import FilerServer
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.utils import headers as weed_headers

    ms = MasterServer(volume_size_limit_mb=64)
    ms.start()
    vs = VolumeServer([str(tmp_path / "v")], ms.url)
    vs.start()
    time.sleep(0.2)
    fs = FilerServer(ms.url)
    fs.start()
    iam = IamServer(fs, trace_sample=1.0)
    iam.start()
    try:
        tid, caller_span = "00cafe0000005678", "0badf00d"
        status, body, _ = http_call(
            "POST", f"http://{iam.url}/",
            body=b"Action=CreateUser&UserName=alice",
            headers={"Content-Type": "application/x-www-form-urlencoded",
                     weed_headers.TRACE: f"{tid}:{caller_span}:1",
                     weed_headers.DEADLINE: "10"})
        assert status == 200, body
        edge = _recorded(iam.tracer.snapshot,
                         lambda s: s["trace_id"] == tid)
        assert edge, "IAM edge minted a fresh trace instead of " \
                     "continuing the inbound one"
        assert any(s["parent_id"] == caller_span for s in edge)
    finally:
        iam.stop()
        fs.stop()
        vs.stop()
        ms.stop()


def test_tracing_disabled_is_invisible(tmp_path):
    from seaweedfs_tpu.server.filer_server import FilerServer
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    ms = MasterServer(tracing_enabled=False)
    ms.start()
    vs = VolumeServer([str(tmp_path / "v")], ms.url,
                      tracing_enabled=False)
    vs.start()
    time.sleep(0.2)
    fs = FilerServer(ms.url, tracing_enabled=False)
    fs.start()
    try:
        status, _, _ = http_call("POST", f"http://{fs.url}/z/a.bin",
                                 body=b"q" * 100_000)
        assert status < 300
        status, got, _ = http_call("GET", f"http://{fs.url}/z/a.bin")
        assert status == 200 and got == b"q" * 100_000
        # the write crossed every node; no span was ever allocated
        for tr in (ms.tracer, vs.tracer, fs.tracer):
            snap = tr.snapshot()
            assert snap["spans"] == [] and snap["started"] == 0
        assert vs.tracer.server_span("GET /x", {}) is tracing.NOOP
        out = http_json("GET", f"http://{vs.url}/debug/traces")
        assert out["enabled"] is False and out["spans"] == []
    finally:
        fs.stop()
        vs.stop()
        ms.stop()


# ---- tools/trace_collect.py (tier-1 smoke, fixture servers) ----

def test_trace_collect_stitches_across_nodes(tmp_path, capsys):
    import tools.trace_collect as tc
    from seaweedfs_tpu.client import operation
    from seaweedfs_tpu.client.wdclient import MasterClient
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    ms = MasterServer(trace_sample=1.0)
    ms.start()
    vs = VolumeServer([str(tmp_path / "v")], ms.url, trace_sample=1.0)
    vs.start()
    time.sleep(0.2)
    mc = MasterClient(ms.url, cache_ttl=0.0)
    client_tr = tracing.Tracer(node="client", sample_rate=1.0)
    root = client_tr.root_span("client.put", sampled=True)
    try:
        with tracing.span_scope(root):
            operation.upload_data(mc, b"t" * 50_000)
        root.finish()

        # list mode: the client's trace shows up cluster-wide
        rc = tc.main(["--node", ms.url, "--node", vs.url, "--json"])
        assert rc == 0
        out = json.loads(capsys.readouterr().out)
        rows = {t["trace_id"]: t for t in out["traces"]}
        assert root.trace_id in rows
        assert rows[root.trace_id]["spans"] >= 2

        # stitch mode: Chrome trace-event JSON with per-node processes
        outfile = tmp_path / "trace.json"
        rc = tc.main(["--node", ms.url, "--node", vs.url,
                      "--trace", root.trace_id, "--out", str(outfile)])
        assert rc == 0
        doc = json.loads(outfile.read_text())
        events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert events
        for e in events:
            assert e["ts"] >= 0 and e["dur"] >= 0 and e["pid"] >= 1
        procs = [e for e in doc["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "process_name"]
        assert len(procs) >= 2  # master + volume lanes

        # asking for an unknown trace fails loudly
        rc = tc.main(["--node", ms.url, "--trace", "f" * 16,
                      "--out", str(tmp_path / "none.json")])
        assert rc == 1
    finally:
        mc.stop()
        vs.stop()
        ms.stop()


# ---- sampling overhead (acceptance: <=5% at the 1% head rate) ----

@pytest.mark.slow
def test_put_overhead_at_one_percent_sampling(tmp_path):
    """Measured PUT cost with tracing at the default 1% head rate vs
    disabled. The 5%-overhead acceptance bar is checked with slack
    (CI timer noise dwarfs the real delta on loopback fixtures)."""
    from seaweedfs_tpu.client import operation
    from seaweedfs_tpu.client.wdclient import MasterClient
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer

    def run(enabled: bool) -> float:
        d = tmp_path / ("on" if enabled else "off")
        ms = MasterServer(tracing_enabled=enabled, trace_sample=0.01)
        ms.start()
        vs = VolumeServer([str(d)], ms.url, tracing_enabled=enabled,
                          trace_sample=0.01)
        vs.start()
        time.sleep(0.2)
        mc = MasterClient(ms.url, cache_ttl=0.0)
        body = b"p" * 65536
        try:
            for _ in range(10):  # warmup
                operation.upload_data(mc, body)
            t0 = time.perf_counter()
            for _ in range(150):
                operation.upload_data(mc, body)
            return time.perf_counter() - t0
        finally:
            mc.stop()
            vs.stop()
            ms.stop()

    off = run(False)
    on = run(True)
    assert on <= off * 1.5, \
        f"tracing overhead too high: {off:.3f}s off vs {on:.3f}s on"


# ---- stages (PR 25): annotator always, spans only when sampled ----

class _CountingLock:
    def __init__(self):
        self.taken = 0
        self._lock = threading.Lock()

    def __enter__(self):
        self.taken += 1
        return self._lock.__enter__()

    def __exit__(self, *a):
        return self._lock.__exit__(*a)


class _Annotation:
    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *a):
        self.log.append(("exit", self.name))


@pytest.fixture
def annotator():
    before = tracing._annotator
    _Annotation.log = []
    tracing.set_annotator(_Annotation)
    yield _Annotation.log
    tracing.set_annotator(before)


@pytest.fixture
def spans_made(monkeypatch):
    """How many Spans were constructed, by whatever path."""
    made = []

    class Counted(tracing.Span):
        __slots__ = ()

        def __init__(self, *a, **kw):
            made.append(a[4])
            super().__init__(*a, **kw)
    monkeypatch.setattr(tracing, "Span", Counted)
    return made


@pytest.mark.parametrize("ambient", ["none", "unsampled"])
def test_stage_without_a_sampled_span_only_calls_the_annotator(
        annotator, spans_made, ambient):
    tr = tracing.Tracer(node="n", sample_rate=0.0)
    tr._lock = _CountingLock()
    root = tr.root_span("req") if ambient == "unsampled" else None
    del spans_made[:]
    with tracing.span_scope(root):
        with tracing.stage("outer") as st:
            st.annotate("bytes", 4096)     # goes nowhere, allocates nothing
            tracing.detail("k", "v")
            with tracing.stage("inner"):
                pass
        held = tracing.stage_begin("loose")
        tracing.stage_end(held)
        assert tracing.current_span() is root
    assert spans_made == []
    assert st.span is None and st.elapsed >= 0.0
    assert root is None or root.annotations is None
    assert tr._lock.taken == 0 and tr._started == 0
    assert annotator == [("enter", "outer"), ("enter", "inner"),
                         ("exit", "inner"), ("exit", "outer"),
                         ("enter", "loose"), ("exit", "loose")]


def test_stage_children_link_up_and_self_times_add_up(annotator):
    tr = tracing.Tracer(node="n", sample_rate=1.0)
    root = tr.root_span("req", sampled=True)
    with tracing.span_scope(root):
        time.sleep(0.002)
        with tracing.stage("a") as a:
            a.annotate("bytes", 7)
            time.sleep(0.003)
            with tracing.stage("a.1"):
                time.sleep(0.004)
            with tracing.stage("a.2"):
                time.sleep(0.002)
        with pytest.raises(ValueError):
            with tracing.stage("b"):
                time.sleep(0.001)
                raise ValueError("boom")
    root.finish()
    spans = {s["name"]: s for s in tr.snapshot()["spans"]}
    assert spans["a"]["parent_id"] == spans["b"]["parent_id"] \
        == root.span_id
    assert spans["a.1"]["parent_id"] == spans["a.2"]["parent_id"] \
        == spans["a"]["span_id"]
    assert {s["trace_id"] for s in spans.values()} == {root.trace_id}
    assert spans["a"]["annotations"] == {"bytes": 7}
    assert spans["b"]["status"] == 500 and "boom" in spans["b"]["error"]
    kids: dict = {}
    for s in spans.values():
        kids.setdefault(s["parent_id"], []).append(s)
    self_ms = {n: s["duration_ms"] - sum(
        c["duration_ms"] for c in kids.get(s["span_id"], []))
        for n, s in spans.items()}
    assert all(v >= 0 for v in self_ms.values()), self_ms
    assert sum(self_ms.values()) == pytest.approx(
        spans["req"]["duration_ms"], abs=0.01)
    assert self_ms["a.1"] >= 3.5 and self_ms["a"] >= 2.5
    assert a.elapsed * 1e3 == pytest.approx(spans["a"]["duration_ms"],
                                            abs=0.5)
    # the annotator saw every stage, sampled or not
    assert [n for ev, n in annotator if ev == "enter"] == [
        "a", "a.1", "a.2", "b"]


def test_span_captured_at_a_handoff_parents_another_threads_spans():
    tr = tracing.Tracer(node="n", sample_rate=1.0)
    root = tr.root_span("req", sampled=True)
    with tracing.span_scope(root):
        with tracing.stage("submit-side") as st:
            captured = tracing.current_span()
            t_submit = time.monotonic()
    assert captured is st.span

    def worker():
        assert tracing.current_span() is None   # ContextVars stay behind
        now = time.monotonic()
        captured.record("waited", t_submit, now, {"jobs": 2})
        tok = tracing.attach(captured)
        try:
            with tracing.stage("worked"):
                time.sleep(0.002)
        finally:
            tracing.detach(tok)
        assert tracing.current_span() is None
    th = threading.Thread(target=worker)
    th.start()
    th.join(5)
    assert not th.is_alive()
    spans = {s["name"]: s for s in tr.snapshot()["spans"]}
    for name in ("waited", "worked"):
        assert spans[name]["parent_id"] == captured.span_id
        assert spans[name]["trace_id"] == root.trace_id
    assert spans["waited"]["annotations"] == {"jobs": 2}
    assert spans["waited"]["duration_ms"] >= 0
    # back-dated onto the parent's wall clock
    assert spans["waited"]["start"] == pytest.approx(
        captured.start + (t_submit - captured._t0), abs=1e-6)
    assert spans["worked"]["duration_ms"] >= 1.5


def test_durations_are_monotonic_and_start_is_wall_time():
    tr = tracing.Tracer(node="n", sample_rate=1.0)
    sp = tr.root_span("op", sampled=True)
    assert abs(sp.start - time.time()) < 1.0
    sp.start -= 3600.0          # a wall clock stepped under the span
    sp.finish()
    assert tr.snapshot()["spans"][0]["duration_ms"] < 1000.0


def test_edge_modules_import_without_jax():
    """tracing, httpd and the master sit below jax in the import DAG:
    the benchmark's parent and chip_smoke.py's parent import them and
    may never hold the chip."""
    import subprocess
    import sys
    code = ("import sys\n"
            "import seaweedfs_tpu.utils.tracing\n"
            "import seaweedfs_tpu.utils.httpd\n"
            "import seaweedfs_tpu.server.master\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n"
            "from seaweedfs_tpu.utils import tracing\n"
            "assert tracing._annotator is None\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


def test_server_span_carries_queue_cpu_and_send_ms():
    from seaweedfs_tpu.utils.httpd import HttpServer, Response
    srv = HttpServer()
    srv.tracer = tracing.Tracer(node="edge", sample_rate=0.0, slow_ms=0.0)

    def burn(req):
        t0 = time.thread_time()
        while time.thread_time() - t0 < 0.003:
            pass
        return Response(b"x" * 1000, content_type="text/plain")
    srv.add("GET", "/burn", burn)
    srv.start()
    try:
        for _ in range(2):   # the second rides the kept-alive connection
            status, _body, _ = http_call(
                "GET", f"http://{srv.host}:{srv.port}/burn")
            assert status == 200
        deadline = time.time() + 5
        while len(srv.tracer.snapshot()["spans"]) < 2 \
                and time.time() < deadline:
            time.sleep(0.01)
        spans = srv.tracer.snapshot()["spans"]
    finally:
        srv.stop()
    assert len(spans) == 2
    for s in spans:
        # unsampled, kept by the slow-span tail keep: the floats are there
        assert s["sampled"] is False and s["kind"] == "server"
        assert s["cpu_ms"] >= 2.5
        assert s["cpu_ms"] <= s["duration_ms"] + 1.0
        # send_ms: the write into wfile AND its flush (http.flush), which
        # lie inside the span
        assert 0.0 < s["send_ms"] <= s["duration_ms"]
        # queue_ms: the selector's turn saw the socket readable -> a
        # worker began the request (http.request's queue_us): the first
        # was accepted, the second woken from the park, both handed off
        assert s["queue_ms"] > 0.0
        assert "annotations" not in s
