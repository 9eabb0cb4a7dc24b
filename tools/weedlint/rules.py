"""The weedlint rule set: one AST pass, seventeen invariants.

Every rule encodes a contract the cluster depends on ambiently — the
kind that breaks silently at a single call site and only surfaces as a
sim-fidelity gap or a dropped header three hops downstream.  The rule
id in parentheses is what ``# weedlint: disable=<id>`` takes.

raw-clock
    ``time.time()/monotonic()/sleep()`` outside ``utils/clockctl.py``.
    Behavioral timers must read the clockctl indirection so the
    macro-sim's virtual clock reaches them; a raw site is invisible to
    the sim and elapses in wall time mid-simulation.  Measurement-only
    wall-clock reads (bench timing, log timestamps) are legitimate —
    suppress them inline with a justification.

raw-histogram-timer
    ``time.perf_counter()`` inside ``seaweedfs_tpu/``.  Latency that
    feeds a histogram (or any derived rate) must be measured with
    ``clockctl.monotonic()`` — or ``metrics.Histogram.time()``, which
    wraps it — so virtual-clock sims and frozen-clock tests observe the
    same durations the telemetry plane reports.  A perf_counter site
    produces wall-time samples that diverge from every other timer in
    the process.  Tools outside the package (bench drivers) are exempt.

raw-http
    ``urllib.request.urlopen/Request`` or ``http.client.HTTP(S)
    Connection`` outside ``utils/httpd.py``.  Raw clients drop the
    X-Weed-Deadline/Class/Trace headers that ``http_call`` injects, so
    deadlines, QoS class and traces silently stop at that edge.

lock-across-blocking
    a ``with <lock>:`` body that calls ``http_call/http_json/urlopen``,
    ``sleep`` or a no-arg ``.join()``.  Holding a lock across blocking
    I/O turns one slow peer into a pile-up of every thread that
    touches the lock.

swallowed-exit
    a handler in a generator that can eat ``GeneratorExit``: bare
    ``except:`` / ``except BaseException:`` around a ``yield`` without
    a bare re-``raise`` (a preceding ``except GeneratorExit: raise``
    shields later broad handlers), an ``except GeneratorExit`` that
    doesn't re-raise, or a ``yield`` inside ``finally``.  The sim kernel
    closes actor coroutines via GeneratorExit; a swallowing handler
    turns actor teardown into an infinite loop (the PR 8
    ``_reply_chain`` bug).

header-literal
    an inline ``"X-Weed-*"`` string outside ``utils/headers.py``.
    Header names are protocol constants; a typo in a literal fails
    open (header silently not propagated), so all sites must import
    the shared constant.

persistent-socket-timeout
    ``create_connection(..., timeout=)`` in a function that never
    calls ``settimeout``.  The connect timeout persists as the
    socket's I/O timeout and kills long-lived keepalive connections
    after the first idle period (the netchaos proxy-teardown bug);
    long-lived sockets must ``settimeout(None)`` (or an explicit
    per-op value) after connecting.

unbounded-pool
    ``ThreadPoolExecutor()`` without ``max_workers`` or ``Queue()``
    without ``maxsize``.  Unbounded pools/queues convert overload into
    memory growth instead of backpressure; every pool in the data path
    must state its bound.

ambient-scope-loss
    ``executor.submit`` of a closure that reads ambient context
    (``current_span/current_deadline/current_class``) or issues
    ``http_call`` without re-entering a scope.  ContextVars don't
    cross pool threads: the closure must capture the ambient value in
    the submitting thread and re-enter it via ``span_scope/
    deadline_scope/class_scope/attach`` (the filer ``_upload_chunks``
    idiom), otherwise the worker runs traceless and deadline-less.

raw-device-discovery
    ``jax.devices()/local_devices()/device_count()`` outside
    ``parallel/mesh.py``.  Device discovery must route through
    ``mesh.devices()`` so every layer shares one cached probe (and its
    classified ``fallback_reason``), one device report and one start-up
    gate, and so a process that must stay off the chip has one place
    to stay out of.

unbounded-body-read
    a whole-body materialization outside the streaming reader's home
    in ``utils/httpd.py``: ``req.body`` / ``request.body`` (the lazy
    property buffers the ENTIRE request body), ``.readall()`` on a
    stream, or a bare no-arg ``.read()`` on a socket/rfile/stream-ish
    receiver.  Body memory must be the handler's explicit budget —
    chunk-at-a-time via ``req.stream.read(n)`` (the filer
    ``_ingest_body`` idiom) — or a 5GB PUT costs 5GB of filer RSS.
    Deliberate small-body sites (JSON admin endpoints) are baselined;
    new code streams.

unnamed-thread
    ``threading.Thread(...)`` without a ``name=`` kwarg.  The wall
    sampler (utils/profiler.py) prefixes every untagged thread's
    stacks with ``thread:<name>``, and ``Thread-7`` in a cluster
    flamegraph is unattributable.  Every long-lived thread states its
    role; ephemeral helpers still benefit (their samples group under
    one label instead of a counter-suffixed spray).

filer-cache-bypass
    a ``<anything>.store.find_entry(...)`` call inside
    ``seaweedfs_tpu/server/filer_server.py``.  Handler reads must go
    through ``filer.find_entry`` so the hot-entry + negative-lookup
    cache (filer/entry_cache.py) sees every lookup — a raw store read
    both misses the cache's hit-rate win and, worse, can resurrect a
    fact the cache already invalidated.  The row-level escape hatch
    ``.store.inner.find_entry`` stays legal: it is the explicit "raw
    store row, no resolution" API that meta-import and sync sinks use.

hot-path-bytes-copy
    ``bytes(<payload>)`` or a full ``<payload>[:]`` slice inside
    ``seaweedfs_tpu/storage/`` or ``seaweedfs_tpu/server/``.  The
    zero-copy read plane moves payloads as memoryview windows and
    ``(fd, offset, count)`` descriptors — ``utils/httpd.py`` owns the
    only sanctioned materialization points (FileSlice.read_all, the
    buffered sendfile fallback) — so a ``bytes()`` rematerialization
    of a data/blob/payload-named buffer on the read path silently
    reinstates the copy-per-GET the plane exists to remove.
    Deliberate copies (cache-admission snapshots that must outlive a
    mutable buffer, wire framing that needs an owned ``bytes``) are
    baselined or suppressed with a justification; new code passes
    views through to the transport.

lease-wall-clock
    lease/expiry math reading a raw wall clock inside ``seaweedfs_tpu/``:
    an assignment, comparison, dict entry or keyword argument whose
    identifiers mention lease/expiry and whose value calls
    ``time.time()/monotonic()/perf_counter()`` or
    ``datetime.now()/utcnow()`` directly.  Lease TTLs are a correctness
    boundary — the holder refuses to mint past ``expires_at`` and the
    master grants on the same arithmetic — so both sides must read
    ``clockctl.now()``; a raw site puts the grant and the refusal on
    different clocks (and is invisible to the macro-sim's virtual
    time), which is exactly how a holder keeps minting from a range
    the master already re-granted.

hardcoded-shard-count
    a shard-count literal (4/10/14) used as a ``range()`` bound or a
    comparison operand inside ``storage/erasure_coding/``.  Shard
    counts are code-family parameters now — RS(10,4) and LRC(10,2,2)
    volumes coexist on one store, each carrying its CodeSpec in the
    .vif — so iteration and guards must read
    ``layout.DATA_SHARDS_COUNT/TOTAL_SHARDS_COUNT`` or the volume's
    own ``scheme``/``data_shards``.  A literal ``range(14)`` silently
    pins one family's geometry onto every volume it touches.  Sizes
    that merely happen to be 4 (prefetch depth, 4-byte lanes) don't
    match the flagged forms and stay legal; ``layout.py`` is the home
    where the counts are defined.

ring-epoch-forward
    a bare ``==`` between two shard-ring epoch expressions.  Ring
    epochs are forward-only: adoption sites must compare ``>``/``>=``
    so a replayed or stale announcement can never re-install an old
    ring (filer ``_adopt_ring``, wdclient ``note_shard_epoch``, the
    mover's commit adopt).  An ``==`` gate looks equivalent on the
    happy path and silently rejects every LEGITIMATE newer epoch —
    the ring then never converges after a rebalance.  Epoch equality
    that has nothing to do with rings (sim actor incarnations, volume
    cache generations) doesn't name a ring/shard and stays legal;
    ``filer/shard_ring.py`` is the home where epoch semantics live.

tier-move-background
    a call to a tiering data-mover entry point (``demote_volume`` /
    ``promote_volume``) outside a ``with class_scope(BACKGROUND)``
    block.  Tier moves stream whole .dat files (EC encode, cloud
    upload, re-heat download) — issued on the caller's ambient QoS
    class they ride the INTERACTIVE admission lane and starve client
    reads behind a multi-gigabyte transfer.  Every dispatch site must
    lexically enter ``class_scope(BACKGROUND)`` so admission control
    and the X-Weed-Class header see the move for what it is.
    ``storage/tiering.py`` is the home where the mover owns its own
    scope entry.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass
from typing import Optional

RULES: dict[str, str] = {
    "raw-clock": "time.time/monotonic/sleep outside utils/clockctl.py",
    "raw-histogram-timer":
        "time.perf_counter in seaweedfs_tpu/ — time via clockctl",
    "raw-http": "urllib/http.client request outside utils/httpd.py",
    "lock-across-blocking": "with <lock>: body calls blocking I/O",
    "swallowed-exit": "generator handler can swallow GeneratorExit",
    "header-literal": "inline X-Weed-* literal instead of utils/headers.py",
    "persistent-socket-timeout":
        "create_connection(timeout=) without settimeout",
    "unbounded-pool": "ThreadPoolExecutor/Queue without an explicit bound",
    "ambient-scope-loss":
        "submit of closure using ambient scope without re-entry",
    "raw-device-discovery":
        "jax.devices()/local_devices() outside parallel/mesh.py",
    "unbounded-body-read":
        "whole-body read (req.body/.readall()/bare .read()) outside "
        "utils/httpd.py",
    "unnamed-thread":
        "threading.Thread without name= — unattributable in the "
        "profiler's flamegraphs",
    "filer-cache-bypass":
        ".store.find_entry in server/filer_server.py bypasses the "
        "entry cache — call filer.find_entry (or .inner.find_entry "
        "for raw rows)",
    "hot-path-bytes-copy":
        "bytes(<payload>)/full-slice copy in storage/ or server/ — "
        "pass memoryview windows on the read hot path",
    "hardcoded-shard-count":
        "shard-count literal (4/10/14) in storage/erasure_coding/ — "
        "read layout constants or the volume's CodeSpec",
    "lease-wall-clock":
        "lease/expiry math on a raw wall clock (time.time/datetime.now) "
        "— grant and refusal must share clockctl.now()",
    "ring-epoch-forward":
        "shard-ring epoch compared with == — adoption must be >/>= "
        "(forward-only) or a stale ring can re-install",
    "tier-move-background":
        "demote_volume/promote_volume outside class_scope(BACKGROUND) "
        "— tier moves must ride the background admission lane",
}

# files that ARE the sanctioned implementation of a contract
_RULE_HOME = {
    "raw-clock": "utils/clockctl.py",
    "raw-histogram-timer": "utils/clockctl.py",
    "raw-http": "utils/httpd.py",
    "header-literal": "utils/headers.py",
    "raw-device-discovery": "parallel/mesh.py",
    "unbounded-body-read": "utils/httpd.py",
    "hot-path-bytes-copy": "utils/httpd.py",
    "hardcoded-shard-count": "storage/erasure_coding/layout.py",
    "lease-wall-clock": "utils/clockctl.py",
    "ring-epoch-forward": "filer/shard_ring.py",
    "tier-move-background": "storage/tiering.py",
}

_HEADER_PREFIX = "X-Weed-"
_LOCKISH = re.compile(r"(?:^|_)(?:lock|mutex)$", re.IGNORECASE)
_CLOCK_CALLS = {"time.time", "time.monotonic", "time.sleep"}
_HTTP_CALLS = {
    "urllib.request.urlopen", "urllib.request.Request",
    "http.client.HTTPConnection", "http.client.HTTPSConnection",
}
# modules whose aliases we track for canonical-name resolution
_TRACKED_MODULES = ("time", "urllib.request", "urllib", "http.client",
                    "http", "socket", "queue", "concurrent.futures",
                    "concurrent", "jax", "threading", "datetime")
_DEVICE_CALLS = {"jax.devices", "jax.local_devices",
                 "jax.device_count", "jax.local_device_count"}
_BLOCKING_TERMINALS = {"http_call", "http_json", "urlopen"}
# receivers whose no-arg .read() means "buffer to EOF" (sockets, HTTP
# body streams) rather than a small local file
_STREAMISH = re.compile(r"(?:^_*|_)(?:sock(?:et)?|rfile|wfile|stream|"
                        r"conn(?:ection)?|resp(?:onse)?|body)s?$",
                        re.IGNORECASE)
_AMBIENT_READERS = {"current_span", "current_deadline", "current_class"}
# names that hold needle/chunk payload bytes on the read path; a
# bytes()/full-slice copy of one re-buys the copy-per-GET the
# zero-copy plane removed
_PAYLOADISH = re.compile(r"(?:^_*|_)(?:data|blob|body|payload|"
                         r"buf(?:fer)?|chunk|piece|record)s?$",
                         re.IGNORECASE)
# subtrees where the hot-path-bytes-copy rule applies (read data plane)
_HOT_PATH_PREFIXES = ("seaweedfs_tpu/storage/", "seaweedfs_tpu/server/")
# the code-family geometry values of RS(10,4)/LRC(10,2,2): data, parity,
# total — a literal one of these in a range() bound or comparison inside
# the EC subtree pins one family's geometry onto every volume
_SHARD_COUNT_LITERALS = {4, 10, 14}
_EC_SUBTREE = "seaweedfs_tpu/storage/erasure_coding/"
_SCOPE_ENTRIES = {"span_scope", "deadline_scope", "class_scope",
                  "attach", "child_scope"}
# the raw wall clocks lease math must never read directly: lease TTLs
# are grant/refuse arithmetic shared by master and holder, so both
# sides go through clockctl.now() (one indirection, one clock)
_WALL_CLOCK_CALLS = {"time.time", "time.monotonic", "time.perf_counter",
                     "datetime.datetime.now", "datetime.datetime.utcnow",
                     "datetime.datetime.today"}
# identifiers/keys that mark an expression as lease-expiry arithmetic
_LEASEISH = re.compile(r"lease|expir", re.IGNORECASE)
# ring-epoch-forward: both operands name an epoch, and at least one
# names the ring/shard machinery — sim actor incarnations and other
# unrelated "epoch"s stay legal
_EPOCHISH = re.compile(r"epoch", re.IGNORECASE)
_RINGISH = re.compile(r"ring|shard", re.IGNORECASE)
# the tiering mover entry points that stream whole volumes; dispatch
# sites must enter class_scope(BACKGROUND) before calling them
_TIER_MOVE_TERMINALS = {"demote_volume", "promote_volume"}


def _ident_strings(expr: ast.AST) -> list[str]:
    """Every Name/Attribute identifier inside `expr`."""
    out = []
    for n in ast.walk(expr):
        if isinstance(n, ast.Name):
            out.append(n.id)
        elif isinstance(n, ast.Attribute):
            out.append(n.attr)
    return out


@dataclass(frozen=True)
class Violation:
    file: str          # repo-relative, forward slashes
    line: int
    col: int
    rule: str
    message: str
    snippet: str       # stripped source line: baseline key, drift-proof

    def key(self) -> tuple[str, str, str]:
        return (self.file, self.rule, self.snippet)

    def format(self) -> str:
        return f"{self.file}:{self.line}:{self.rule}: {self.message}"


def _dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _terminal(node: ast.AST) -> Optional[str]:
    """Rightmost name of the call target: 'c' for a.b.c, 'f' for f."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _walk_same_scope(node: ast.AST, *, skip_root_check: bool = True):
    """Yield nodes inside `node` without descending into nested
    function/class scopes (their bodies run elsewhere/later).  The
    nested scope's own def node IS yielded — callers like _Scope need
    to see `def work(): ...` to resolve a later `pool.submit(work)` —
    it's only the body that stays opaque."""
    stack = [node]
    first = True
    while stack:
        cur = stack.pop()
        if not first and isinstance(cur, (ast.FunctionDef,
                                          ast.AsyncFunctionDef,
                                          ast.Lambda, ast.ClassDef)):
            yield cur
            continue
        first = False
        yield cur
        stack.extend(ast.iter_child_nodes(cur))


def _mentions_lease(node: ast.AST) -> bool:
    """Does the expression name a lease/expiry — an identifier,
    attribute or string key matching lease/expir?"""
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and _LEASEISH.search(n.id):
            return True
        if isinstance(n, ast.Attribute) and _LEASEISH.search(n.attr):
            return True
        if isinstance(n, ast.Constant) and isinstance(n.value, str) \
                and _LEASEISH.search(n.value):
            return True
    return False


def _contains_yield(node: ast.AST) -> bool:
    return any(isinstance(n, (ast.Yield, ast.YieldFrom))
               for n in _walk_same_scope(node))


def _has_bare_raise(body: list[ast.stmt]) -> bool:
    for stmt in body:
        for n in _walk_same_scope(ast.Module(body=[stmt],
                                             type_ignores=[])):
            if isinstance(n, ast.Raise) and n.exc is None:
                return True
    return False


def _handler_catches(handler: ast.ExceptHandler, names: set[str]) -> bool:
    t = handler.type
    if t is None:
        return "BARE" in names
    types = t.elts if isinstance(t, ast.Tuple) else [t]
    return any(_terminal(x) in names for x in types)


def _is_background_scope(expr: ast.AST) -> bool:
    """True for ``class_scope(BACKGROUND)`` (or the literal
    ``class_scope("background")``) used as a with-item."""
    if not isinstance(expr, ast.Call) or \
            _terminal(expr.func) != "class_scope":
        return False
    for a in expr.args:
        if _terminal(a) == "BACKGROUND":
            return True
        if isinstance(a, ast.Constant) and a.value == "background":
            return True
    return False


class _Scope:
    """Per-function bookkeeping for rules that need whole-function
    context (persistent-socket-timeout, ambient-scope-loss,
    swallowed-exit generator detection)."""

    def __init__(self, node):
        self.node = node
        self.is_generator = (
            isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
            and _contains_yield(node))
        self.create_conn: list[ast.Call] = []
        self.has_settimeout = False
        # locally-defined closures by name, for submit() resolution
        self.local_defs: dict[str, ast.AST] = {}
        if not isinstance(node, ast.Module):
            for n in _walk_same_scope(node):
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                        and n is not node:
                    self.local_defs[n.name] = n
                elif isinstance(n, ast.Assign) \
                        and isinstance(n.value, ast.Lambda):
                    for tgt in n.targets:
                        if isinstance(tgt, ast.Name):
                            self.local_defs[tgt.id] = n.value


class Checker(ast.NodeVisitor):
    def __init__(self, rel_path: str, source: str):
        self.rel = rel_path.replace("\\", "/")
        self.lines = source.splitlines()
        self.violations: list[Violation] = []
        self.aliases: dict[str, str] = {}      # local name -> module
        self.from_imports: dict[str, str] = {}  # local name -> mod.attr
        self.scopes: list[_Scope] = []
        # lexical depth inside `with class_scope(BACKGROUND)` blocks
        self.bg_scope_depth = 0

    # ---- reporting ----

    def _snippet(self, line: int) -> str:
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1].strip()
        return ""

    def report(self, node: ast.AST, rule: str, message: str) -> None:
        if self.rel.endswith(_RULE_HOME.get(rule, "\0")):
            return
        line = getattr(node, "lineno", 1)
        self.violations.append(Violation(
            file=self.rel, line=line, col=getattr(node, "col_offset", 0),
            rule=rule, message=message, snippet=self._snippet(line)))

    # ---- name resolution ----

    def visit_Import(self, node: ast.Import) -> None:
        # plain `import x.y` binds `x` and attribute access already
        # spells the canonical dotted path; only `as` needs mapping
        for a in node.names:
            if a.asname and a.name in _TRACKED_MODULES:
                self.aliases[a.asname] = a.name
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module in _TRACKED_MODULES:
            for a in node.names:
                self.from_imports[a.asname or a.name] = \
                    f"{node.module}.{a.name}"
        self.generic_visit(node)

    def _canonical(self, func: ast.AST) -> Optional[str]:
        """Resolve a call target to its canonical dotted module path
        through `import x as y` / `from x import y` indirection."""
        dotted = _dotted(func)
        if dotted is None:
            return None
        head, _, rest = dotted.partition(".")
        if head in self.from_imports:
            base = self.from_imports[head]
            return f"{base}.{rest}" if rest else base
        if head in self.aliases:
            base = self.aliases[head]
            return f"{base}.{rest}" if rest else base
        return dotted

    # ---- scope management ----

    def _function_scope(self, node) -> None:
        scope = _Scope(node)
        self.scopes.append(scope)
        # a def nested inside `with class_scope(...)` runs later,
        # outside that scope — its body starts unscoped
        saved_bg = self.bg_scope_depth
        self.bg_scope_depth = 0
        self.generic_visit(node)
        self.bg_scope_depth = saved_bg
        self.scopes.pop()
        if scope.create_conn and not scope.has_settimeout:
            for call in scope.create_conn:
                self.report(
                    call, "persistent-socket-timeout",
                    "create_connection timeout persists as the socket "
                    "I/O timeout; call settimeout(None) (or a per-op "
                    "value) after connect")

    visit_FunctionDef = _function_scope
    visit_AsyncFunctionDef = _function_scope

    def visit_Module(self, node: ast.Module) -> None:
        self._function_scope(node)

    # ---- per-node rules ----

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if node.attr == "body" and isinstance(node.value, ast.Name) \
                and node.value.id in ("req", "request"):
            self.report(node, "unbounded-body-read",
                        "req.body buffers the whole request body — "
                        "consume req.stream.read(n) chunk-at-a-time "
                        "(the _ingest_body idiom) so body memory is "
                        "the handler's explicit budget")
        self.generic_visit(node)

    def visit_Constant(self, node: ast.Constant) -> None:
        if isinstance(node.value, str) and \
                node.value.startswith(_HEADER_PREFIX):
            self.report(node, "header-literal",
                        f'inline header literal "{node.value}" — import '
                        "the constant from seaweedfs_tpu.utils.headers")
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        canonical = self._canonical(node.func)
        terminal = _terminal(node.func)

        if canonical in _CLOCK_CALLS:
            what = canonical.split(".")[1]
            self.report(node, "raw-clock",
                        f"raw time.{what}() — use clockctl.{'monotonic' if what == 'monotonic' else ('sleep' if what == 'sleep' else 'now')}() so "
                        "virtual-clock sims reach this timer")
        if canonical == "time.perf_counter" and \
                self.rel.startswith("seaweedfs_tpu/"):
            self.report(node, "raw-histogram-timer",
                        "raw time.perf_counter() — histogram/latency "
                        "timing must use clockctl.monotonic() (or "
                        "metrics.Histogram.time()) so sims and tests "
                        "see the same clock the telemetry plane reports")
        if canonical in _DEVICE_CALLS:
            self.report(node, "raw-device-discovery",
                        f"raw {canonical}() — route through "
                        "seaweedfs_tpu.parallel.mesh.devices() so the "
                        "cached probe and virtual-device config are "
                        "shared")
        if canonical in _HTTP_CALLS:
            self.report(node, "raw-http",
                        f"raw {canonical}() drops X-Weed-Deadline/Class/"
                        "Trace propagation — route through "
                        "utils.httpd.http_call")
        if terminal == "create_connection":
            if any(kw.arg == "timeout" for kw in node.keywords) \
                    or len(node.args) >= 2:
                if self.scopes:
                    self.scopes[-1].create_conn.append(node)
        if terminal == "settimeout" and self.scopes:
            self.scopes[-1].has_settimeout = True

        if terminal in _TIER_MOVE_TERMINALS and not self.bg_scope_depth:
            self.report(node, "tier-move-background",
                        f"{terminal}() outside class_scope(BACKGROUND) "
                        "— a tier move streams whole .dat files and "
                        "must ride the background admission lane; wrap "
                        "the dispatch in `with class_scope(BACKGROUND):`")

        if canonical == "threading.Thread" and \
                not any(kw.arg == "name" for kw in node.keywords):
            self.report(node, "unnamed-thread",
                        "Thread without name= — the wall sampler labels "
                        "untagged stacks thread:<name>, and Thread-7 in "
                        "a cluster flamegraph is unattributable")

        if terminal == "ThreadPoolExecutor":
            if not node.args and not any(kw.arg == "max_workers"
                                         for kw in node.keywords):
                self.report(node, "unbounded-pool",
                            "ThreadPoolExecutor without max_workers — "
                            "state the bound explicitly")
        elif terminal == "Queue":
            if not node.args and not any(kw.arg == "maxsize"
                                         for kw in node.keywords):
                self.report(node, "unbounded-pool",
                            "Queue() without maxsize — unbounded queues "
                            "turn overload into memory growth")

        if terminal == "readall" and isinstance(node.func, ast.Attribute):
            self.report(node, "unbounded-body-read",
                        ".readall() materializes the whole stream — "
                        "loop .read(n) under an explicit buffer budget")
        elif terminal == "read" and isinstance(node.func, ast.Attribute) \
                and not node.args and not node.keywords:
            recv = _terminal(node.func.value)
            if recv is not None and _STREAMISH.search(recv):
                self.report(
                    node, "unbounded-body-read",
                    f"bare {recv}.read() buffers to EOF — pass a size "
                    "and loop so a large peer body can't balloon RSS")

        if terminal == "find_entry" \
                and isinstance(node.func, ast.Attribute) \
                and _terminal(node.func.value) == "store" \
                and self.rel == "seaweedfs_tpu/server/filer_server.py":
            self.report(node, "filer-cache-bypass",
                        ".store.find_entry bypasses the entry cache — "
                        "read through filer.find_entry (cached) or "
                        ".store.inner.find_entry (explicit raw row)")

        if terminal == "submit" and isinstance(node.func, ast.Attribute) \
                and node.args:
            self._check_submit(node)

        if canonical == "range" and self.rel.startswith(_EC_SUBTREE):
            for arg in node.args:
                if isinstance(arg, ast.Constant) \
                        and type(arg.value) is int \
                        and arg.value in _SHARD_COUNT_LITERALS:
                    self.report(
                        arg, "hardcoded-shard-count",
                        f"range({arg.value}) pins one code family's "
                        "shard geometry — iterate layout.DATA_SHARDS_"
                        "COUNT/TOTAL_SHARDS_COUNT or the volume's own "
                        "scheme counts")

        for kw in node.keywords:
            # expires_at=time.time()+ttl spelled as a keyword argument
            if kw.arg and _LEASEISH.search(kw.arg):
                self._check_lease_clock(kw.value, ast.Name(id=kw.arg),
                                        kw.value)

        if canonical == "bytes" and len(node.args) == 1 \
                and not node.keywords \
                and self.rel.startswith(_HOT_PATH_PREFIXES):
            arg = node.args[0]
            if isinstance(arg, ast.Subscript):
                arg = arg.value
            recv = _terminal(arg)
            if recv is not None and _PAYLOADISH.search(recv):
                self.report(
                    node, "hot-path-bytes-copy",
                    f"bytes({recv}…) rematerializes a payload buffer — "
                    "the read plane moves memoryview windows and fd "
                    "descriptors; pass the view through (copy only at "
                    "a sanctioned materialization point, with a "
                    "justified suppression)")

        self.generic_visit(node)

    def _wall_clock_in(self, node: ast.AST) -> Optional[str]:
        """Canonical name of the first raw wall-clock call inside the
        expression, resolved through import aliases, else None."""
        for n in ast.walk(node):
            if isinstance(n, ast.Call):
                canonical = self._canonical(n.func)
                if canonical in _WALL_CLOCK_CALLS:
                    return canonical
        return None

    def _check_lease_clock(self, node: ast.AST, lease_src: ast.AST,
                           clock_src: ast.AST) -> None:
        """lease-wall-clock: lease/expiry math (named by lease_src)
        whose value expression (clock_src) reads a raw wall clock."""
        if not self.rel.startswith("seaweedfs_tpu/"):
            return
        if not _mentions_lease(lease_src):
            return
        what = self._wall_clock_in(clock_src)
        if what:
            self.report(
                node, "lease-wall-clock",
                f"lease/expiry math reads raw {what}() — grant and "
                "refusal must share one clock: route through "
                "clockctl.now() so holders, the master and the "
                "macro-sim's virtual time agree on when a lease lapses")

    def visit_Assign(self, node: ast.Assign) -> None:
        for target in node.targets:
            self._check_lease_clock(node, target, node.value)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self._check_lease_clock(node, node.target, node.value)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is not None:
            self._check_lease_clock(node, node.target, node.value)
        self.generic_visit(node)

    def visit_Dict(self, node: ast.Dict) -> None:
        for key, value in zip(node.keys, node.values):
            if key is not None:
                self._check_lease_clock(node, key, value)
        self.generic_visit(node)

    def visit_Compare(self, node: ast.Compare) -> None:
        # a lease/expiry operand compared against a raw wall clock read
        self._check_lease_clock(node, node, node)
        if len(node.ops) == 1 and isinstance(node.ops[0], ast.Eq):
            left = _ident_strings(node.left)
            right = _ident_strings(node.comparators[0])
            if (any(_EPOCHISH.search(s) for s in left)
                    and any(_EPOCHISH.search(s) for s in right)
                    and any(_RINGISH.search(s)
                            for s in left + right)):
                self.report(
                    node, "ring-epoch-forward",
                    "ring epoch compared with == — epochs are "
                    "forward-only; adopt with > / >= so a stale ring "
                    "can never re-install")
        if self.rel.startswith(_EC_SUBTREE):
            for operand in [node.left] + node.comparators:
                if isinstance(operand, ast.Constant) \
                        and type(operand.value) is int \
                        and operand.value in _SHARD_COUNT_LITERALS \
                        and operand.value != 4:
                    # 4 as a bare comparison operand is usually a size
                    # (lanes, prefetch) — only 10/14 read as shard
                    # counts outside a range()
                    self.report(
                        operand, "hardcoded-shard-count",
                        f"comparison against literal {operand.value} "
                        "hardcodes one code family's shard count — "
                        "compare against layout constants or the "
                        "volume's scheme")
        self.generic_visit(node)

    def visit_Subscript(self, node: ast.Subscript) -> None:
        # <payload>[:] — a whole-buffer copy spelled as a slice
        sl = node.slice
        if isinstance(sl, ast.Slice) and sl.lower is None \
                and sl.upper is None and sl.step is None \
                and self.rel.startswith(_HOT_PATH_PREFIXES):
            recv = _terminal(node.value)
            if recv is not None and _PAYLOADISH.search(recv):
                self.report(
                    node, "hot-path-bytes-copy",
                    f"{recv}[:] copies the whole payload buffer — "
                    "slice a memoryview (or pass the buffer itself) "
                    "instead of duplicating it on the read path")
        self.generic_visit(node)

    def _check_submit(self, node: ast.Call) -> None:
        target = node.args[0]
        closure: Optional[ast.AST] = None
        if isinstance(target, ast.Lambda):
            closure = target
        elif isinstance(target, ast.Name) and self.scopes:
            closure = self.scopes[-1].local_defs.get(target.id)
        if closure is None:
            return
        body = closure.body if isinstance(closure, ast.Lambda) \
            else ast.Module(body=closure.body, type_ignores=[])
        reads_ambient = False
        does_http = False
        enters_scope = False
        for n in _walk_same_scope(body):
            if isinstance(n, ast.Call):
                t = _terminal(n.func)
                if t in _AMBIENT_READERS:
                    reads_ambient = True
                elif t in ("http_call", "http_json"):
                    does_http = True
                elif t in _SCOPE_ENTRIES:
                    enters_scope = True
        if (reads_ambient or does_http) and not enters_scope:
            why = ("reads ambient context" if reads_ambient
                   else "issues http_call")
            self.report(
                node, "ambient-scope-loss",
                f"submitted closure {why} but never re-enters a scope — "
                "capture span/deadline/class in the submitting thread "
                "and re-enter via span_scope/deadline_scope/class_scope")

    def _visit_with(self, node) -> None:
        is_background = any(_is_background_scope(item.context_expr)
                            for item in node.items)
        lockish = None
        for item in node.items:
            term = _terminal(item.context_expr)
            if term and _LOCKISH.search(term):
                lockish = term
                break
        if lockish is not None:
            for n in _walk_same_scope(node):
                if not isinstance(n, ast.Call):
                    continue
                canonical = self._canonical(n.func)
                terminal = _terminal(n.func)
                blocking = None
                if canonical in ("time.sleep", "clockctl.sleep") or \
                        terminal == "sleep":
                    blocking = "sleep"
                elif terminal in _BLOCKING_TERMINALS:
                    blocking = terminal
                elif terminal == "join" and not n.args and \
                        not n.keywords and \
                        isinstance(n.func, ast.Attribute) and \
                        not isinstance(n.func.value, ast.Constant):
                    blocking = "join"
                if blocking:
                    self.report(
                        n, "lock-across-blocking",
                        f"{blocking}() while holding '{lockish}' — "
                        "blocking under a lock serializes every thread "
                        "that touches it; move the I/O outside the "
                        "critical section")
        if is_background:
            self.bg_scope_depth += 1
        self.generic_visit(node)
        if is_background:
            self.bg_scope_depth -= 1

    visit_With = _visit_with
    visit_AsyncWith = _visit_with

    def visit_Try(self, node: ast.Try) -> None:
        in_generator = bool(self.scopes) and self.scopes[-1].is_generator
        if in_generator:
            body_yields = any(_contains_yield(s) for s in node.body)
            shielded = False  # a prior `except GeneratorExit: raise`
            for handler in node.handlers:
                if _handler_catches(handler, {"GeneratorExit"}) and \
                        not _has_bare_raise(handler.body):
                    self.report(
                        handler, "swallowed-exit",
                        "except GeneratorExit without re-raise — actor "
                        "teardown (gen.close()) becomes RuntimeError")
                elif body_yields and not shielded and \
                        _handler_catches(handler,
                                         {"BARE", "BaseException"}) and \
                        not _has_bare_raise(handler.body):
                    self.report(
                        handler, "swallowed-exit",
                        "broad except around a yield can swallow "
                        "GeneratorExit — catch Exception (or re-raise "
                        "GeneratorExit) so gen.close() terminates")
                if _handler_catches(handler,
                                    {"GeneratorExit", "BARE",
                                     "BaseException"}) and \
                        _has_bare_raise(handler.body):
                    # earlier handlers re-raise GeneratorExit, so later
                    # broad handlers can never see it
                    shielded = True
            if any(_contains_yield(s) for s in node.finalbody):
                self.report(
                    node, "swallowed-exit",
                    "yield inside finally — GeneratorExit delivered at "
                    "this yield escapes the cleanup path")
        self.generic_visit(node)


_SUPPRESS_RE = re.compile(
    r"#\s*weedlint:\s*disable=([a-zA-Z0-9_,\s-]+)")


def suppressed_rules(lines: list[str], line_no: int) -> set[str]:
    """Rules disabled at `line_no` (1-based): an inline trailing
    directive, or one anywhere in the contiguous block of pure-comment
    lines directly above (so a multi-line justification comment still
    carries its directive)."""
    out: set[str] = set()

    def collect(text: str) -> None:
        m = _SUPPRESS_RE.search(text)
        if m:
            out.update(r.strip() for r in m.group(1).split(",")
                       if r.strip())

    if 0 <= line_no - 1 < len(lines):
        collect(lines[line_no - 1])
    idx = line_no - 2
    while 0 <= idx < len(lines) and lines[idx].lstrip().startswith("#"):
        collect(lines[idx])
        idx -= 1
    return out


def check_source(rel_path: str, source: str) -> list[Violation]:
    """All non-suppressed violations in one file's source text."""
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Violation(file=rel_path.replace("\\", "/"),
                          line=e.lineno or 1, col=e.offset or 0,
                          rule="syntax-error",
                          message=f"unparseable: {e.msg}",
                          snippet="")]
    checker = Checker(rel_path, source)
    checker.visit(tree)
    lines = checker.lines
    return [v for v in checker.violations
            if v.rule not in suppressed_rules(lines, v.line)]
