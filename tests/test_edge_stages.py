"""The HTTP edge's stages (utils/httpd.py): ``http.request`` (one a
request, from before the request line to the park check, with the
selector's hand-off as ``queue_us``), ``http.head`` (request line and
headers), ``http.flush`` (the response to the socket) and
``http.selector`` (a turn of the selector loop that had events), all on
the annotator's clock; a parked keep-alive socket is in no stage."""

import contextlib
import socket
import threading
import time

import pytest

from seaweedfs_tpu.utils import httpd, tracing
from seaweedfs_tpu.utils.httpd import HttpServer, Response


class _Recorded:
    """An annotator: every stage entered, its thread, metadata and its
    interval by ``time.monotonic``."""
    log: list = []
    lock = threading.Lock()

    def __init__(self, name, **meta):
        self.ev = {"name": name, "meta": meta,
                   "thread": threading.get_ident()}

    def __enter__(self):
        self.ev["t0"] = time.monotonic()

    def __exit__(self, *a):
        self.ev["t1"] = time.monotonic()
        with self.lock:
            self.log.append(self.ev)


@pytest.fixture
def recorded():
    before = tracing._annotator
    _Recorded.log = []
    tracing.set_annotator(_Recorded)
    yield _Recorded.log
    tracing.set_annotator(before)


def _server(tracer=None):
    srv = HttpServer()
    srv.tracer = tracer

    def read(req):
        with tracing.stage("volume.read"):
            return Response(b"x" * 1000, content_type="text/plain")
    srv.add("GET", "/r", read)
    srv.start()
    return srv


@contextlib.contextmanager
def _connection(srv):
    """A keep-alive connection to ``srv``, which stops BEFORE the client
    closes (a close read by a worker is one more ``http.request``, with a
    head that finds no request line and no flush)."""
    s = socket.create_connection((srv.host, srv.port))
    try:
        yield s
    finally:
        srv.stop()
        s.close()


def _get(path="/r") -> bytes:
    return f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode()


def _read_responses(sock, n: int) -> None:
    buf = b""
    while buf.count(b"HTTP/1.1 200") < n or not buf.endswith(b"x" * 1000):
        got = sock.recv(65536)
        assert got, buf
        buf += got


def _settle(log, names, n, timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if sum(1 for e in list(log) if e["name"] in names) >= n:
            return
        time.sleep(0.01)


def _inside(child, parent) -> bool:
    return (child["thread"] == parent["thread"]
            and parent["t0"] <= child["t0"] and child["t1"] <= parent["t1"])


@pytest.mark.parametrize("n", [1, 3])
def test_each_request_is_one_stage_with_head_handler_and_flush_inside(
        recorded, n):
    with _connection(_server()) as s:
        for _ in range(n):              # keep-alive: one connection
            s.sendall(_get())
            _read_responses(s, 1)
        _settle(recorded, {"http.request"}, n)
    log = list(recorded)
    reqs = [e for e in log if e["name"] == "http.request"]
    assert len(reqs) == n
    for name in ("http.head", "volume.read", "http.flush"):
        evs = [e for e in log if e["name"] == name]
        assert len(evs) == n, name
        # each lies inside exactly one request, and each request has one
        for r in reqs:
            assert sum(1 for e in evs if _inside(e, r)) == 1, name
    turns = [e for e in log if e["name"] == "http.selector"]
    assert turns and all(e["meta"] == {} for e in turns)
    assert {e["thread"] for e in turns}.isdisjoint(
        {e["thread"] for e in reqs})


def test_queue_us_is_the_handoff_and_zero_when_pipelined(recorded):
    with _connection(_server()) as s:
        s.sendall(_get() * 3)           # three requests in one segment
        _read_responses(s, 3)
        _settle(recorded, {"http.request"}, 3)
    reqs = sorted((e for e in recorded if e["name"] == "http.request"),
                  key=lambda e: e["t0"])
    assert len(reqs) == 3
    # the slice's first request waited for a worker; the other two were
    # read out of the same buffer by the same worker
    assert reqs[0]["meta"]["queue_us"] >= 0
    assert [r["meta"]["queue_us"] for r in reqs[1:]] == [0, 0]
    assert len({r["thread"] for r in reqs}) == 1


def test_a_parked_connection_is_in_no_stage(recorded):
    pause = 0.2
    with _connection(_server()) as s:
        s.sendall(_get())
        _read_responses(s, 1)
        time.sleep(pause)               # the client's think time
        s.sendall(_get())
        _read_responses(s, 1)
        _settle(recorded, {"http.request"}, 2)
    log = list(recorded)
    first, second = sorted((e for e in log if e["name"] == "http.request"),
                           key=lambda e: e["t0"])
    gap = (first["t1"], second["t0"])
    assert gap[1] - gap[0] >= pause * 0.9
    inside_gap = sum(max(0.0, min(e["t1"], gap[1]) - max(e["t0"], gap[0]))
                     for e in log)
    # only the selector's turns (the park, the wake) fall in the pause
    assert inside_gap < pause / 4, inside_gap
    assert second["meta"]["queue_us"] > 0     # woken: a new hand-off


def test_send_ms_includes_the_flush(monkeypatch):
    flush_s = 0.03

    class SlowFlush:
        def __init__(self, w):
            self.w = w

        def write(self, b):
            return self.w.write(b)

        def flush(self):
            time.sleep(flush_s)
            self.w.flush()

        def close(self):
            self.w.close()

    real_init = httpd._ConnHandler.__init__

    def init(self, *a, **kw):
        real_init(self, *a, **kw)
        self.wfile = SlowFlush(self.wfile)
    monkeypatch.setattr(httpd._ConnHandler, "__init__", init)
    tracer = tracing.Tracer(node="edge", sample_rate=1.0)
    with _connection(_server(tracer)) as s:
        s.sendall(_get())
        _read_responses(s, 1)
        deadline = time.time() + 5
        while not any(sp["kind"] == "server"
                      for sp in tracer.snapshot()["spans"]) \
                and time.time() < deadline:
            time.sleep(0.01)
    spans = tracer.snapshot()["spans"]
    server = next(sp for sp in spans if sp["kind"] == "server")
    flush = next(sp for sp in spans if sp["name"] == "http.flush")
    # a sampled request's flush is a child of its server span
    assert flush["parent_id"] == server["span_id"]
    assert flush["duration_ms"] >= flush_s * 1e3 * 0.9
    assert server["send_ms"] >= flush_s * 1e3 * 0.9
    assert server["send_ms"] >= flush["duration_ms"] - 0.5
    assert server["send_ms"] <= server["duration_ms"]


@pytest.mark.parametrize("tracer", ["none", "unsampled"])
def test_no_annotator_and_no_sampled_span_allocate_nothing(
        monkeypatch, tracer):
    made = []

    class Counted(tracing.Span):
        __slots__ = ()

        def __init__(self, *a, **kw):
            made.append(a[5])          # kind
            super().__init__(*a, **kw)
    monkeypatch.setattr(tracing, "Span", Counted)
    metas = []

    class Stage(tracing.stage):
        __slots__ = ()

        def __init__(self, name, meta=None, t0=None):
            metas.append((name, meta))
            super().__init__(name, meta, t0)
    monkeypatch.setattr(tracing, "stage", Stage)
    # no annotator (a test before may have installed the device one)
    monkeypatch.setattr(tracing, "_annotator", None)
    assert not tracing.recording()
    srv = _server(None if tracer == "none"
                  else tracing.Tracer(node="edge", sample_rate=0.0))
    with _connection(srv) as s:
        for _ in range(3):
            s.sendall(_get())
            _read_responses(s, 1)
        s.sendall(_get() * 2)
        _read_responses(s, 2)
    names = [n for n, _m in metas]
    assert names.count("http.request") == 5
    assert names.count("http.head") == names.count("http.flush") == 5
    assert all(m is None for _n, m in metas)        # no metadata dict
    # no child span of any stage; the server span only where a tracer
    # is wired (a Span a request by design, unsampled: no annotations)
    assert made == ([] if tracer == "none" else ["server"] * 5)


def test_stage_meta_reaches_the_annotator_only_as_given(recorded):
    class Quiet(_Recorded):
        @staticmethod
        def is_enabled():
            return False
    assert tracing.recording()              # no switch: always records
    with tracing.stage("a", {"queue_us": 7}):
        with tracing.stage("a.b", t0=123.0) as st:
            pass
    assert st.t0 == 123.0                    # the caller's clock read
    assert [(e["name"], e["meta"]) for e in recorded] == [
        ("a.b", {}), ("a", {"queue_us": 7})]
    tracing.set_annotator(Quiet)
    assert not tracing.recording()          # a profile is not running
