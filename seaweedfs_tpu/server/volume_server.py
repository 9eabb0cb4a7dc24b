"""Volume server: public read/write/delete + admin/EC RPCs + heartbeat.

Functional equivalent of reference weed/server/volume_server*.go over
HTTP/JSON. Public data path:

  POST/PUT /<vid>,<key_cookie>     upload (raw body; ?type=replicate for
                                   the replica fan-out leg)
  GET/HEAD /<vid>,<key_cookie>     read (normal volume, else EC, with
                                   remote/degraded fallback)
  DELETE   /<vid>,<key_cookie>     delete (replicated like writes)

Admin plane under /admin/... (JSON), including the nine EC RPCs of
reference weed/server/volume_grpc_erasure_coding.go:24-35.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Optional

import numpy as np

from seaweedfs_tpu.models.coder import (MAX_VOLUME_SHARDS, CodeSpecError,
                                        ErasureCoder, SubstripedCodeError,
                                        refuse_substriped, scheme_from_dict,
                                        scheme_to_dict)
from seaweedfs_tpu.ops.rs_cpu import gf_partial_product
from seaweedfs_tpu.qos import (BACKGROUND, WRITE, QosGovernor, class_scope,
                               classify, current_class, from_headers)
from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.erasure_coding import decoder as ecdec
from seaweedfs_tpu.storage.erasure_coding import encoder as ecenc
from seaweedfs_tpu.storage.erasure_coding import layout
from seaweedfs_tpu.storage.erasure_coding import partial as ecpart
from seaweedfs_tpu.storage.erasure_coding.ec_volume import \
    ec_base_file_name
from seaweedfs_tpu.storage.file_id import parse_needle_id_cookie
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.storage.volume import (CookieMismatchError, DeletedError,
                                          NotFoundError)
from seaweedfs_tpu.utils import headers as weed_headers
from seaweedfs_tpu.utils import clockctl, glog, profiler, tracing
from seaweedfs_tpu.utils.httpd import (HttpError, HttpServer, Request,
                                       Response, http_call, http_json)
from seaweedfs_tpu.utils.resilience import (Deadline, PeerHealth,
                                            RetryPolicy, current_deadline,
                                            deadline_scope, hedged)

PULSE_SECONDS = 2.0
# Refuse to mint fids from a lease this close to its expiry: covers
# clock skew between master and holder plus the in-flight upload time,
# so an acked fid never rides a range the master already re-granted.
LEASE_MINT_SAFETY_S = 3.0
# Wake the heartbeat (renewal piggyback) once a mint leaves this
# fraction or less of the granted range: a write flood can burn
# LEASE_RANGE keys in under one pulse, and waiting out PULSE_SECONDS
# would strand the holder range-exhausted — falling back to a master
# that may be dark. Mirrors the master's LEASE_RANGE_REFILL_FRACTION
# (the threshold at which it stops skipping healthy renewals).
LEASE_REFILL_FRACTION = 0.25
# Default edge budget for a public read that arrives without a
# propagated X-Weed-Deadline: bounds the whole local -> remote ->
# degraded-reconstruction chain (was: unbounded handler + timeout=30
# per remote leg, which could stack).
READ_DEADLINE_S = 30.0


def _human_bytes(n: int) -> str:
    f = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if f < 1024 or unit == "TiB":
            return f"{f:.1f} {unit}" if unit != "B" else f"{int(f)} B"
        f /= 1024
    return f"{int(n)} B"


class VolumeServer:
    def __init__(self, directories: list[str], master_url: str | list,
                 host: str = "127.0.0.1", port: int = 0,
                 public_url: str = "", rack: str = "", data_center: str = "",
                 coder: Optional[ErasureCoder] = None,
                 max_volume_counts: Optional[list[int]] = None,
                 jwt_signing_key: str = "", jwt_read_key: str = "",
                 needle_map_kind: str = "memory",
                 tcp_port: int = -1, grpc_port: Optional[int] = None,
                 concurrent_upload_limit_mb: int = 256,
                 concurrent_download_limit_mb: int = 256,
                 file_size_limit_mb: int = 256,
                 inflight_timeout: float = 30.0,
                 disk_types: Optional[list[str]] = None,
                 scrub_rate_mbps: float = 8.0,
                 scrub_interval_s: float = 600.0,
                 advertise: str = "",
                 resilient_reads: bool = True,
                 parallel_replication: bool = True,
                 fsync: bool = False,
                 qos: bool = True,
                 tracing_enabled: bool = True,
                 trace_sample: float = 0.01,
                 ec_batcher: bool = False,
                 needle_cache_mb: int = 64,
                 hinted_handoff: bool = True,
                 zero_copy: bool = True,
                 assign_leases: bool = True,
                 profile_hz: float = profiler.DEFAULT_HZ):
        """tcp_port >= 0 enables the raw TCP data path (0 = ephemeral;
        reference volume_server_tcp_handlers_write.go). grpc_port starts
        the volume_server_pb gRPC admin plane (0 = ephemeral).

        concurrent_upload/download_limit_mb cap the total request/
        response payload bytes in flight at once; excess writers wait up
        to inflight_timeout then get 429 (reference
        weed/server/volume_server.go:23-30 + `weed volume
        -concurrentUploadLimitMB`). file_size_limit_mb rejects a single
        oversized upload with 413 (`-fileSizeLimitMB`). 0 = unlimited.

        scrub_rate_mbps throttles the background integrity scrubber's
        reads (<= 0 = unthrottled); scrub_interval_s is the idle gap
        between passes (<= 0 disables the scrubber thread; run_once via
        /admin/scrub still works).

        advertise ("host:port") overrides the address this server
        registers with the master — peers then reach it through that
        address instead of the listening socket (how chaos tests and
        bench interpose a tools/netchaos.py proxy on the peer path).
        resilient_reads toggles health-ranked + hedged remote-shard
        fetching (off = the serial lookup-order walk, kept as the
        bench comparator).
        parallel_replication toggles the concurrent replica fan-out
        (off = the one-at-a-time peer loop, kept as the bench
        comparator). fsync forces a durable fsync per commit batch on
        every volume (reference `weed volume -fsync`; group commit in
        storage/volume.py amortizes it across concurrent writers).
        qos toggles the admission-control governor (adaptive
        concurrency limit + class-weighted shedding, see
        seaweedfs_tpu/qos/); off = today's queue-everything behavior,
        kept as the overload-bench comparator.
        tracing_enabled/trace_sample control the distributed-tracing
        flight recorder (utils/tracing.py): head-sample rate for
        guaranteed retention; slow/error spans are kept regardless.
        Off = the shared NOOP span, zero allocation per request.

        ec_batcher routes this node's EC encode/rebuild work through a
        cross-volume batch scheduler (parallel/batcher.py): concurrent
        volumes' block-groups that queue up behind a running dispatch
        coalesce into the next device-mesh dispatch (a lone job is not
        held), with a CPU drain when devices fail mid-run. Off (the
        default) keeps the per-volume coder path. Ignored when an
        explicit `coder` is passed.

        needle_cache_mb byte-budgets the hot-needle record cache
        (storage/needle_cache.py) fronting the healthy and degraded-EC
        read paths; admission follows this server's HotKeys sketch and
        0 disables the cache entirely.

        zero_copy serves eligible whole-needle and Range GETs as
        (fd, offset, length) descriptors that the HTTP core hands to
        os.sendfile — the payload never enters Python. An explicit
        fallback ladder (cached, EC, tiered, compressed-for-plain-
        clients, resize, TTL, v1, sub-threshold payloads) keeps the
        buffered path, which also stays available wholesale as the
        bit-identity comparator (zero_copy=False).

        hinted_handoff turns replicated writes into a sloppy quorum:
        a write whose primary + majority of replica legs land is acked,
        and each missed leg becomes a persisted hint
        (storage/hinted_handoff.py) that a background drain replays
        through the raw needle-blob transfer once the peer heals. Off =
        the legacy any-leg-fails-the-write contract, kept as the
        comparator for the divergence drill.

        assign_leases requests epoch-stamped fid-range leases from the
        master via heartbeat piggyback and serves /admin/lease_assign:
        clients mint fids here, off the master's per-PUT critical path,
        and writes survive a master leader outage while a lease is
        valid. Expiry discipline runs on clockctl so the sim can
        rehearse lease lapses on the virtual clock. Off = this server
        never requests leases and lease_assign answers 503, kept as
        the bench comparator (assign_leases=False).

        profile_hz sets the always-on wall-stack sampler's rate
        (utils/profiler.py; 19Hz default, prime so it can't phase-lock
        with periodic work). 0 disables: no sampler thread, and the
        per-request scope tagging collapses to one global check."""
        urls = (master_url.split(",") if isinstance(master_url, str)
                else list(master_url))
        self.master_urls = [u.strip() for u in urls if u.strip()]
        self.master_url = self.master_urls[0]
        self.http = HttpServer(host, port)
        self._store_dirs = directories
        self._max_volume_counts = max_volume_counts
        self._disk_types = disk_types
        self._rack = rack
        self._dc = data_center
        self._coder = coder
        self._ec_batcher_enabled = ec_batcher and coder is None
        self.ec_batcher = None  # EcBatchScheduler when enabled
        self._needle_map_kind = needle_map_kind
        self._tcp_port = tcp_port
        self.tcp_server = None
        self._grpc_port_arg = grpc_port
        self._grpc_server = None
        self.grpc_port: Optional[int] = None
        self._public_url = public_url
        self.store: Optional[Store] = None
        self.needle_cache = None  # NeedleCache, attached in start()
        self._stop = threading.Event()
        # graceful-drain announcement: rides every heartbeat so the
        # master stops assigning here and grants repair drain grace
        self.draining = False
        self._hb_thread: Optional[threading.Thread] = None
        self.volume_size_limit = 0
        self.jwt_signing_key = jwt_signing_key
        # read JWT (reference jwt.signing.read): when a read key is set —
        # explicitly or in security.toml — GETs require a token signed
        # with it (the filer signs its own chunk reads; same shared key)
        if not jwt_read_key:
            from seaweedfs_tpu.utils import config as _cfg
            conf = _cfg.load_configuration("security")
            jwt_read_key = _cfg.get(conf, "jwt.signing.read.key", "") or ""
        self.jwt_read_key = jwt_read_key
        from seaweedfs_tpu.utils.limiter import InFlightLimiter
        self.file_size_limit = file_size_limit_mb * 1024 * 1024
        self.upload_limiter = InFlightLimiter(
            concurrent_upload_limit_mb * 1024 * 1024, inflight_timeout)
        self.download_limiter = InFlightLimiter(
            concurrent_download_limit_mb * 1024 * 1024, inflight_timeout)
        self.http.body_gate = self._upload_gate
        # vid -> (expires_monotonic, [peer urls]) for replica fan-out
        self._replica_cache: dict[int, tuple[float, list]] = {}
        self.advertise = advertise
        # zero-copy read plane: descriptor GETs via sendfile. The
        # minimum payload keeps tiny hot needles on the buffered path,
        # where the needle cache (and its cache-aware routing) earns
        # its keep; bulk payloads skip the cache and ride the kernel.
        self.zero_copy = zero_copy
        self.zero_copy_min = 64 * 1024
        self.resilient_reads = resilient_reads
        self.parallel_replication = parallel_replication
        self._fsync = fsync
        # sloppy-quorum replication: journal of missed replica legs,
        # drained by a background thread once the peer heals
        self.hinted_handoff = hinted_handoff
        self.hint_journal = None  # HintJournal, attached in start()
        self._hint_thread: Optional[threading.Thread] = None
        # assign leases: vid -> lease dict from the master's grant,
        # plus a local "next_key" mint cursor. Renewal wants ride every
        # full heartbeat; expiry is checked against clockctl at mint.
        self.assign_leases = assign_leases
        self._leases: dict[int, dict] = {}
        self._lease_lock = threading.Lock()
        self.lease_stats = {"installed": 0, "minted": 0, "refused": 0}
        # demand-triggered renewal: set when a mint drains a lease past
        # its refill threshold, waking the heartbeat loop early so a
        # fresh range lands before the active one exhausts (a flood can
        # burn LEASE_RANGE keys in under one pulse). Also set by stop()
        # to keep shutdown prompt.
        self._lease_hungry = threading.Event()
        # lazily-built shared pool for the concurrent replica fan-out
        self._replicate_pool: Optional[object] = None
        self._replicate_pool_lock = threading.Lock()
        # per-peer circuit breakers + latency health, fed by every
        # outbound call (masters and peer volume servers alike)
        self.retry = RetryPolicy()
        # vid -> (expires_monotonic, {shard_id: [peer urls]})
        # vid -> (expires_monotonic, {shard_id: [urls]}, {url: pressure})
        self._shard_loc_cache: dict[int, tuple] = {}
        self._scrub_rate = scrub_rate_mbps * 1024 * 1024
        self._scrub_interval = scrub_interval_s
        self.scrubber = None
        from seaweedfs_tpu.utils.metrics import Registry
        self.metrics = Registry()
        self._m_req = self.metrics.counter(
            "volumeServer", "request_total", "requests", ("type",))
        self._m_lat = self.metrics.histogram(
            "volumeServer", "request_seconds", "request latency", ("type",))
        # gauges refreshed at scrape (reference stats/metrics.go
        # VolumeServerVolumeCounter / disk gauges + disk_supported.go)
        self._m_volumes = self.metrics.gauge(
            "volumeServer", "volumes", "mounted volumes")
        self._m_ec_shards = self.metrics.gauge(
            "volumeServer", "ec_shards", "mounted ec shards")
        self._m_bytes = self.metrics.gauge(
            "volumeServer", "total_disk_size", "bytes across volumes")
        self._m_disk_free = self.metrics.gauge(
            "volumeServer", "disk_free_bytes", "statvfs free bytes",
            ("dir",))
        # mesh->CPU drains in the EC batch scheduler, labeled by the
        # classified reason (device_put / timeout / probe_error)
        self._m_ec_fallbacks = self.metrics.counter(
            "volumeServer", "ec_coder_fallbacks",
            "EC batcher mesh dispatch failures drained via CPU",
            ("reason",))
        # the EC batch scheduler's account of its time (stats()'s
        # stage_s and loop_s), refreshed at scrape
        self._m_ec_stage = self.metrics.gauge(
            "volumeServer", "ec_batch_stage_seconds",
            "cumulative seconds per stage of an EC job, and of the "
            "dispatcher's idle / hold / dispatch", ("stage",))
        # ... and of its work per code geometry (stats()'s by_spec)
        self._m_ec_spec = self.metrics.gauge(
            "volumeServer", "ec_batch_spec",
            "EC batch scheduler counters per code geometry",
            ("spec", "stat"))
        # ... per job width (by_rung) and the groups its column cap cut
        self._m_ec_rung = self.metrics.gauge(
            "volumeServer", "ec_batch_rung",
            "EC batch scheduler counters per job width in columns",
            ("rung", "stat"))
        self._m_ec_cap_splits = self.metrics.gauge(
            "volumeServer", "ec_batch_cap_splits",
            "groups of EC jobs cut into several dispatches by the "
            "column cap")
        # ... and how often its window of two dispatches engages
        self._m_ec_overlapped = self.metrics.gauge(
            "volumeServer", "ec_batch_overlapped",
            "EC mesh dispatches launched while another was still on "
            "the device")
        # hot-needle record cache + selector-core connection counters,
        # refreshed at scrape from their owners' stats() snapshots
        self._m_cache = self.metrics.gauge(
            "volumeServer", "needle_cache",
            "hot-needle cache counters", ("stat",))
        self._m_conns = self.metrics.gauge(
            "volumeServer", "http_connections",
            "selector-core connection counters", ("stat",))
        self.metrics.on_expose(self._refresh_gauges)
        self.peer_health = PeerHealth(metrics=self.metrics)
        # per-volume record of the last repair strategy this server
        # executed ({vid: {"strategy", "sources", "mode"}}), surfaced
        # via /admin/ec/shard_stat for the shell's ec.scheme.status
        self._ec_last_strategy: dict[int, dict] = {}
        # admission control: class-weighted slots under an adaptive
        # concurrency limit; shed requests get 503 + Retry-After at the
        # socket edge, before their body is buffered
        self.qos = QosGovernor(metrics=self.metrics, enabled=qos)
        self.http.admission_gate = self._admission_gate
        # lets the selector core size its worker pool off the adaptive
        # concurrency ceiling and quote governor pressure when shedding
        self.http.governor = self.qos
        self._needle_cache_mb = needle_cache_mb
        # distributed-tracing flight recorder; served at /debug/traces
        self.tracer = tracing.Tracer(
            node=f"volume@{host}:{port}", enabled=tracing_enabled,
            sample_rate=trace_sample)
        self.http.tracer = self.tracer
        # RED edge histogram (single observation site in HttpServer)
        # + hot-needle sketch; both ride heartbeats to the master
        from seaweedfs_tpu.stats.hotkeys import HotKeys
        from seaweedfs_tpu.utils.metrics import RedRecorder
        self.red = RedRecorder(self.metrics, "volume")
        self.http.red = self.red
        self.hotkeys = HotKeys(dims=("needle",))
        # per-volume cumulative read counters — the tiering autopilot's
        # temperature signal, piggybacked on heartbeats via
        # telemetry_snapshot(). Cumulative on purpose: the master diffs
        # successive reports, so a lost heartbeat costs nothing and a
        # restart clamps to zero instead of going negative.
        self.vol_reads: dict[int, int] = collections.defaultdict(int)
        # rung-transition counters for /admin/tier + tier_profile
        self.tier_stats = {"demotes": 0, "promotes": 0,
                           "bytes_demoted": 0, "bytes_promoted": 0,
                           "failed": 0}
        # continuous profiling + per-(class, tenant) resource ledger;
        # both ride the telemetry piggyback to the master
        from seaweedfs_tpu.stats.ledger import ResourceLedger
        self.sampler = profiler.WallSampler(hz=profile_hz)
        self.ledger = ResourceLedger()
        self.http.ledger = self.ledger

    # ---- lifecycle ----
    def start(self) -> None:
        self.http.start()
        self.sampler.start()
        self.tracer.node = f"volume@{self.http.host}:{self.http.port}"
        # register the ADVERTISED address with the master when one is
        # set, so peers route to us through it (chaos-proxy interpose)
        if self.advertise:
            adv_host, adv_port = self.advertise.rsplit(":", 1)
            reg_host, reg_port = adv_host, int(adv_port)
        else:
            reg_host, reg_port = self.http.host, self.http.port
        if self._ec_batcher_enabled and self._coder is None:
            from seaweedfs_tpu.parallel.batcher import (BatchCoder,
                                                        EcBatchScheduler)
            self.ec_batcher = EcBatchScheduler(
                on_fallback=lambda reason: self._m_ec_fallbacks.inc(reason))
            self._coder = BatchCoder(self.ec_batcher)
        # {"platform", "device_kind", "count"} the EC coder dispatches
        # to; None for the host (CPU) coders
        report = getattr(self._coder, "device_report", None)
        self._ec_device = report() if report else None
        if self._ec_device is not None:
            glog.info("volume server EC coder device: %s", self._ec_device)
        self.store = Store(
            self._store_dirs, self._max_volume_counts,
            ip=reg_host, port=reg_port,
            public_url=self._public_url or f"{reg_host}:{reg_port}",
            rack=self._rack, data_center=self._dc, coder=self._coder,
            needle_map_kind=self._needle_map_kind,
            disk_types=self._disk_types, fsync=self._fsync)
        self.store.load_existing_volumes()
        self.store.remote_shard_reader = self._remote_shard_reader
        self.store.peer_health = self.peer_health
        self.store.shard_locations = self._shard_locations
        self.store.shard_pressure = self._shard_pressure
        self.store.resilient_reads = self.resilient_reads
        self.store.remote_partial_reader = self._remote_partial_reader
        if self.hinted_handoff:
            from seaweedfs_tpu.storage.hinted_handoff import HintJournal
            self.hint_journal = HintJournal(
                os.path.join(self._store_dirs[0], "hints.journal"),
                fsync=self._fsync)
            self._hint_thread = threading.Thread(
                target=self._hint_drain_loop, daemon=True,
                name="hint-drain")
            self._hint_thread.start()
        if self._needle_cache_mb > 0:
            from seaweedfs_tpu.storage.needle_cache import NeedleCache
            sketch = self.hotkeys.sketches["needle"]
            self.store.needle_cache = NeedleCache(
                capacity_bytes=self._needle_cache_mb << 20,
                hot_fn=lambda vid, nid: sketch.estimate(
                    "%d,%x" % (vid, nid)))
        self.needle_cache = self.store.needle_cache
        if self._tcp_port >= 0:
            from seaweedfs_tpu.server.volume_tcp import TcpDataServer
            self.tcp_server = TcpDataServer(self.store, self.http.host,
                                            self._tcp_port)
            self.tcp_server.start()
        if self._grpc_port_arg is not None:
            from seaweedfs_tpu.server.volume_grpc import start_volume_grpc
            self._grpc_server, self.grpc_port = start_volume_grpc(
                self, self.http.host, self._grpc_port_arg)
        self._register_routes()
        self.heartbeat_once()
        self._hb_thread = threading.Thread(target=self._heartbeat_loop,
                                           daemon=True,
                                           name="volume-heartbeat")
        self._hb_thread.start()
        from seaweedfs_tpu.scrub import Scrubber
        self.scrubber = Scrubber(self.store,
                                 rate_bytes_per_sec=self._scrub_rate,
                                 interval_s=self._scrub_interval,
                                 report_fn=self._report_scrub,
                                 metrics=self.metrics,
                                 pressure_fn=self.qos.pressure)
        if self._scrub_interval > 0:
            self.scrubber.start()
        glog.info("volume server up at %s (dirs=%s, master=%s)",
                  self.url, ",".join(self._store_dirs), self.master_url)

    def stop(self, graceful: bool = True,
             drain_timeout: float = 5.0) -> None:
        """Stop serving. graceful=True (the default) drains first:
        announce draining to the master (no new assigns, repair drain
        grace for our volumes), let in-flight requests finish, flush
        the group commit, then send a final draining heartbeat so the
        grace clock restarts from the actual departure."""
        self._stop.set()
        self._lease_hungry.set()  # wake the heartbeat loop's wait
        self.sampler.stop()
        if self.scrubber is not None:
            self.scrubber.stop()
        graceful = graceful and self.store is not None
        if graceful:
            self.draining = True
            try:
                self.heartbeat_once()
            except Exception:
                pass  # master gone: hard teardown still proceeds
            self.http.drain(drain_timeout)
        if self._replicate_pool is not None:
            # graceful: wait out queued replica fan-out legs so every
            # acked write reaches its peers before we disappear
            self._replicate_pool.shutdown(wait=graceful)
        if graceful:
            for loc in self.store.locations:
                for v in list(loc.volumes.values()):
                    try:
                        v.sync()
                    except Exception:
                        pass
            try:
                self.heartbeat_once()
            except Exception:
                pass
        if self._hint_thread is not None:
            self._hint_thread.join(timeout=2.0)
        if self.hint_journal is not None:
            self.hint_journal.close()
        self.metrics.stop_push()
        if self.tcp_server is not None:
            self.tcp_server.stop()
        if self._grpc_server is not None:
            self._grpc_server.stop(0)
        self.http.stop()
        if self.ec_batcher is not None:
            self.ec_batcher.stop()
        if self.store:
            self.store.close()

    @property
    def url(self) -> str:
        """Cluster-facing identity: the advertised address when set
        (so peers dial through the interposed proxy), else the socket."""
        return self.advertise or f"{self.http.host}:{self.http.port}"

    def _is_self(self, url: str) -> bool:
        return url in (self.advertise,
                       f"{self.http.host}:{self.http.port}") and bool(url)

    def _master_json(self, method: str, path: str, body=None,
                     timeout: float = 5.0, deadline=None):
        """One master RPC with a deadline cap and breaker bookkeeping.
        An HttpError still counts as transport-healthy (the master
        answered); only ConnectionError marks the peer down."""
        t0 = clockctl.monotonic()
        try:
            out = http_json(method, f"http://{self.master_url}{path}",
                            body, timeout=timeout, deadline=deadline)
        except HttpError:
            self.peer_health.record(self.master_url, True,
                                    clockctl.monotonic() - t0)
            raise
        except ConnectionError:
            self.peer_health.record(self.master_url, False)
            raise
        self.peer_health.record(self.master_url, True,
                                clockctl.monotonic() - t0)
        return out

    def _is_scrubbing(self) -> bool:
        """Mid-scrub-pass right now? Rides every heartbeat so the
        master's repair dispatch can avoid piling rebuild I/O onto a
        disk that the scrubber is already sweeping."""
        s = self.scrubber
        if s is None:
            return False
        try:
            return bool(s.status().get("current"))
        except Exception:
            return False

    # ---- heartbeat (reference volume_grpc_client_to_master.go) ----
    def heartbeat_once(self) -> None:
        hb = self.store.collect_heartbeat()
        hb["scrubbing"] = self._is_scrubbing()
        hb["draining"] = self.draining
        # local overload pressure rides every heartbeat so the master's
        # repair scheduler can back off nodes that are shedding load
        hb["qos_pressure"] = round(self.qos.pressure(), 4)
        # telemetry snapshot (RED histogram + hot-needle sketch)
        # piggybacks the same way — the master merges these into the
        # cluster-wide /cluster/telemetry view
        hb["telemetry"] = self.telemetry_snapshot()
        if self.grpc_port:
            hb["grpc_port"] = self.grpc_port
        lease_req = self._lease_req(hb)
        if lease_req is not None:
            hb["lease_req"] = lease_req
        for _attempt in range(2):  # second try after a leader redirect
            try:
                reply = self._master_json(
                    "POST", "/heartbeat", hb,
                    deadline=Deadline.after(2 * PULSE_SECONDS))
                if reply:
                    self.volume_size_limit = reply.get(
                        "volume_size_limit", 0)
                    if reply.get("jwt_signing_key") \
                            and not self.jwt_signing_key:
                        self.jwt_signing_key = reply["jwt_signing_key"]
                    self._install_leases(reply)
                return
            except HttpError as e:
                old = self.master_url
                self._follow_leader_hint(e)
                if self.master_url == old:
                    return
            except ConnectionError:
                self._fail_over()

    def _follow_leader_hint(self, e: "HttpError") -> None:
        """A follower replied 409 {"leader": url}: re-aim at the leader
        (the reference restarts doHeartbeat at the new leader,
        volume_grpc_client_to_master.go newLeader handling). A 409
        WITHOUT a hint — a deposed leader cut off from the election —
        falls through to _fail_over, else the node would hammer the
        ex-leader forever and never re-register with the winner."""
        import json as _json
        try:
            body = _json.loads(e.body)
        except Exception:
            body = {}
        leader = body.get("leader")
        if leader and leader != self.master_url:
            self.master_url = leader
        else:
            self._fail_over()

    def _fail_over(self) -> None:
        for url in self.master_urls:
            if url == self.master_url:
                continue
            try:
                out = http_json("GET", f"http://{url}/cluster/status",
                                deadline=Deadline.after(2.0))
                self.peer_health.record(url, True)
                # adopt the peer's leader view when it has one; a live
                # follower is still a fine next hop (its 409 will carry
                # the hint once the election settles)
                leader = (out or {}).get("Leader")
                self.master_url = leader or url
                return
            except (ConnectionError, HttpError):
                self.peer_health.record(url, False)
                continue

    # ---- assign leases (local fid minting off the master's path) ----
    def _lease_req(self, hb: dict) -> Optional[dict]:
        """Renewal wants for the heartbeat piggyback: one entry per
        writable local volume, carrying the mint cursor + epoch of any
        lease already held so the master can skip still-healthy ones.
        Also GCs lapsed leases — expiry is the only revocation."""
        if not self.assign_leases:
            return None
        req: dict[str, dict] = {}
        now = clockctl.now()
        with self._lease_lock:
            for vid in [vid for vid, l in self._leases.items()
                        if l["expires_at"] <= now]:
                del self._leases[vid]
            for v in hb.get("volumes", []):
                if v.get("read_only"):
                    continue
                if self.volume_size_limit \
                        and v.get("size", 0) >= self.volume_size_limit:
                    continue
                held = self._leases.get(v["id"])
                req[str(v["id"])] = (
                    {"next_key": held["next_key"], "epoch": held["epoch"]}
                    if held else {})
        return req

    def _install_leases(self, reply: dict) -> None:
        """Adopt granted/renewed leases from a heartbeat reply. A grant
        from an older epoch (a stale leader's last gasp) never replaces
        a newer one; every accepted grant is a fresh range, so the mint
        cursor resets to its key_lo."""
        for l in reply.get("leases") or []:
            vid = int(l["vid"])
            with self._lease_lock:
                cur = self._leases.get(vid)
                if cur is not None and l["epoch"] < cur["epoch"]:
                    continue
                self._leases[vid] = dict(l, next_key=l["key_lo"])
                self.lease_stats["installed"] += 1
            # the grant names this vid's replica peers: prime the
            # fan-out cache so a leased write replicates even while
            # the master (this cache's only other source) is dark
            peers = [r["url"] for r in l.get("replicas", [])
                     if not self._is_self(r["url"])]
            if peers:
                self._replica_cache[vid] = (
                    clockctl.monotonic() + self.REPLICA_CACHE_TTL, peers)

    def _admin_lease_assign(self, req: Request) -> Response:
        """Mint fids locally from an active lease (the direct-to-volume
        assign lane; shape mirrors the master's /dir/assign reply).
        Refuses — 503, so clients fall back to the master — when no
        matching lease is valid: none held, wrong collection, range
        exhausted, or within LEASE_MINT_SAFETY_S of expiry."""
        count = max(1, int(req.query.get("count", "1") or "1"))
        collection = req.query.get("collection", "")
        if self.draining or not self.assign_leases:
            return Response({"error": "no active lease"}, status=503)
        chosen = None
        now = clockctl.now()
        with self._lease_lock:
            for vid, l in self._leases.items():
                if l["expires_at"] - now <= LEASE_MINT_SAFETY_S:
                    continue
                if l.get("collection", "") != collection:
                    continue
                if l["next_key"] + count > l["key_hi"] + 1:
                    continue
                v = self.store.find_volume(vid)
                if v is None or v.read_only:
                    continue
                if self.volume_size_limit \
                        and v.content_size() >= self.volume_size_limit:
                    continue
                key = l["next_key"]
                l["next_key"] += count
                chosen = (vid, dict(l), key)
                break
            if chosen is None:
                self.lease_stats["refused"] += 1
            else:
                self.lease_stats["minted"] += count
                span = chosen[1]["key_hi"] - chosen[1]["key_lo"] + 1
                left = chosen[1]["key_hi"] - chosen[1]["next_key"] + 1
                if left <= span * LEASE_REFILL_FRACTION:
                    # running dry: pulse now, don't wait out the tick
                    self._lease_hungry.set()
        if chosen is None:
            return Response({"error": "no active lease"}, status=503)
        vid, lease, key = chosen
        import random
        from seaweedfs_tpu.storage.file_id import format_needle_id_cookie
        cookie = random.getrandbits(32)
        out = {"fid": f"{vid},{format_needle_id_cookie(key, cookie)}",
               "url": self.url, "publicUrl": self.store.public_url,
               "count": count, "lease_epoch": lease["epoch"],
               "replicas": lease.get("replicas", [])}
        if self.jwt_signing_key:
            from seaweedfs_tpu.utils.security import gen_jwt
            out["auth"] = gen_jwt(self.jwt_signing_key, out["fid"])
        return Response(out)

    def _push_deltas(self) -> None:
        """Send pending volume/EC-shard deltas to the master immediately
        (the reference's delta channels wake the heartbeat stream;
        volume_grpc_client_to_master.go:164-260)."""
        deltas = self.store.drain_deltas()
        if not any(deltas.values()):
            return
        body = {"ip": self.store.ip, "port": self.store.port,
                "is_delta": True, "scrubbing": self._is_scrubbing(),
                "qos_pressure": round(self.qos.pressure(), 4),
                "draining": self.draining,
                "telemetry": self.telemetry_snapshot(),
                **deltas}
        try:
            self._master_json("POST", "/heartbeat", body,
                              deadline=Deadline.after(2 * PULSE_SECONDS))
        except HttpError as e:
            if e.status == 409:
                self._follow_leader_hint(e)
                self.heartbeat_once()
        except ConnectionError:
            self._fail_over()

    def _heartbeat_loop(self) -> None:
        ticks = 0
        while True:
            # pulse cadence, cut short when a mint drains a lease past
            # its refill threshold (or stop() wakes us for shutdown)
            self._lease_hungry.wait(PULSE_SECONDS)
            self._lease_hungry.clear()
            if self._stop.is_set():
                return
            ticks += 1
            if ticks % 12 == 0:
                # TTL volume reaping (reference master vacuum loop
                # cadence); deletions ride the next delta heartbeat
                try:
                    self.store.delete_expired_ttl_volumes()
                except Exception as e:
                    import logging
                    logging.getLogger("seaweedfs_tpu.volume").warning(
                        "TTL reap failed (will retry): %s", e,
                        exc_info=True)
            deltas = self.store.drain_deltas()
            has_delta = any(deltas.values())
            try:
                if has_delta:
                    body = {"ip": self.store.ip, "port": self.store.port,
                            "is_delta": True,
                            "scrubbing": self._is_scrubbing(),
                            "qos_pressure": round(self.qos.pressure(), 4),
                            "draining": self.draining,
                            "telemetry": self.telemetry_snapshot(),
                            **deltas}
                    reply = self._master_json(
                        "POST", "/heartbeat", body,
                        deadline=Deadline.after(2 * PULSE_SECONDS))
                    self._install_leases(reply or {})
                else:
                    self.heartbeat_once()
            except HttpError as e:
                if e.status == 409:  # new leader or master forgot us
                    self._follow_leader_hint(e)
                    self.heartbeat_once()
            except ConnectionError:
                self._fail_over()
                self.heartbeat_once()

    # ---- routes ----
    def _register_routes(self) -> None:
        r = self.http.add
        for method in ("POST", "PUT"):
            r(method, r"/(\d+),([0-9a-fA-F]+)(?:_\d+)?(?:\.\w+)?",
              self._handle_write)
        r("GET", r"/(\d+),([0-9a-fA-F]+)(?:_\d+)?(?:\.\w+)?",
          self._handle_read)
        r("HEAD", r"/(\d+),([0-9a-fA-F]+)(?:_\d+)?(?:\.\w+)?",
          self._handle_read)
        r("DELETE", r"/(\d+),([0-9a-fA-F]+)(?:_\d+)?(?:\.\w+)?",
          self._handle_delete)
        r("GET", "/status", self._handle_status)
        r("GET", "/metrics", self._handle_metrics)
        r("GET", "/ui", self._handle_ui)
        from seaweedfs_tpu.utils.debug import install_debug_routes
        install_debug_routes(self.http)
        # admin
        r("POST", "/admin/allocate_volume", self._admin_allocate_volume)
        r("POST", "/admin/delete_volume", self._admin_delete_volume)
        r("POST", "/admin/mark_readonly", self._admin_mark_readonly)
        r("POST", "/admin/mount_volume", self._admin_mount_volume)
        r("POST", "/admin/unmount_volume", self._admin_unmount_volume)
        r("POST", "/admin/configure_replication",
          self._admin_configure_replication)
        r("POST", "/admin/leave", self._admin_leave)
        r("POST", "/admin/batch_delete", self._admin_batch_delete)
        r("GET", "/admin/volume_file_status",
          self._admin_volume_file_status)
        r("POST", "/admin/vacuum", self._admin_vacuum)
        r("POST", "/admin/sync", self._admin_sync)
        r("POST", "/admin/copy_volume", self._admin_copy_volume)
        r("POST", "/admin/move_volume_disk",
          self._admin_move_volume_disk)
        r("GET", "/admin/volume_file", self._admin_volume_file)
        r("POST", "/admin/tier_upload", self._admin_tier_upload)
        r("POST", "/admin/tier_download", self._admin_tier_download)
        # tiering autopilot: rung state + BACKGROUND-classed moves
        r("GET", "/admin/tier", self._admin_tier_status)
        r("POST", "/admin/tier/demote", self._admin_tier_demote)
        r("POST", "/admin/tier/promote", self._admin_tier_promote)
        r("GET", "/admin/volume_digest", self._admin_volume_digest)
        r("GET", "/admin/needle", self._admin_needle)
        r("GET", "/admin/needle_blob", self._admin_needle_blob)
        r("POST", "/admin/write_needle_blob", self._admin_write_needle_blob)
        # divergence repair: clients report a lagging replica here, the
        # hint journal is inspectable for drills and the ops shell
        r("POST", "/admin/replica_repair", self._admin_replica_repair)
        r("GET", "/admin/hints", self._admin_hints)
        # EC rpcs
        r("POST", "/admin/ec/generate", self._ec_generate)
        r("POST", "/admin/ec/rebuild", self._ec_rebuild)
        r("POST", "/admin/ec/copy", self._ec_copy)
        r("POST", "/admin/ec/mount", self._ec_mount)
        r("POST", "/admin/ec/unmount", self._ec_unmount)
        r("POST", "/admin/ec/delete_shards", self._ec_delete_shards)
        r("POST", "/admin/ec/to_volume", self._ec_to_volume)
        r("POST", "/admin/ec/blob_delete", self._ec_blob_delete)
        r("GET", "/admin/ec/shard_read", self._ec_shard_read)
        r("GET", "/admin/ec/shard_file", self._ec_shard_file)
        r("GET", "/admin/ec/shard_stat", self._ec_shard_stat)
        # partial-column repair (network-frugal rebuild; see
        # storage/erasure_coding/partial.py for the chain protocol)
        r("POST", "/admin/ec/partial_read", self._ec_partial_read)
        r("POST", "/admin/ec/rebuild_partial", self._ec_rebuild_partial)
        # batch-scheduler snapshot (coalescing + fallback counters)
        r("GET", "/admin/ec/batcher", self._admin_ec_batcher)
        r("POST", "/admin/ec/trace", self._admin_ec_trace)
        # integrity scrub
        r("POST", "/admin/scrub", self._admin_scrub)
        r("GET", "/admin/scrub/status", self._admin_scrub_status)
        # direct-to-volume fid minting from the master's assign lease
        r("POST", "/admin/lease_assign", self._admin_lease_assign)
        # per-peer breaker/health table (cluster.health shell command)
        r("GET", "/admin/health", self._admin_health)
        # admission-control snapshot + runtime tuning (cluster.qos)
        r("GET", "/admin/qos", self._admin_qos)
        r("POST", "/admin/qos", self._admin_qos_configure)
        # hot-needle sketch + full telemetry snapshot (RED histogram)
        r("GET", "/admin/hotkeys", self.hotkeys.handler(self.url))
        r("GET", "/admin/telemetry", self._admin_telemetry)
        # folded-stack window from the wall sampler (prof_collect)
        r("GET", "/admin/profile", profiler.make_profile_handler(
            self.sampler, lambda: self.url, "volume"))
        # hot-needle record cache snapshot + runtime resize
        r("GET", "/admin/cache", self._admin_cache)
        r("POST", "/admin/cache", self._admin_cache_configure)

    def _admin_ec_batcher(self, req: Request) -> Response:
        if self.ec_batcher is None:
            return Response({"enabled": False})
        return Response({"enabled": True, **self.ec_batcher.stats()})

    EC_TRACE_MAX_S = 30.0

    def _admin_ec_trace(self, req: Request) -> Response:
        """Take a device trace of this (live) server for ``seconds``
        into ``dir``: the chip's operations and the served path's stages
        (utils/tracing.stage) in one ``.xplane.pb``, on one clock.  Only
        the process that holds the chip can trace it, so the exporter is
        here.  Blocks for the window; 409 while a profile is running."""
        if self.ec_batcher is None:
            return Response({"error": "no device coder: start the "
                             "volume server with -ecBatcher"}, status=404)
        b = req.json()
        try:
            seconds = float(b["seconds"])
            log_dir = str(b["dir"])
        except (KeyError, TypeError, ValueError):
            return Response({"error": 'body: {"seconds": s, "dir": d}'},
                            status=400)
        if not 0 < seconds <= self.EC_TRACE_MAX_S or not log_dir:
            return Response({"error": f"seconds in (0, "
                             f"{self.EC_TRACE_MAX_S:g}], dir not empty"},
                            status=400)
        from seaweedfs_tpu.parallel import mesh as mesh_mod
        try:
            with mesh_mod.device_trace(log_dir) as out:
                clockctl.sleep(seconds)
        except RuntimeError as e:
            # jax allows one profile per process at a time
            return Response({"error": str(e)}, status=409)
        return Response({"seconds": seconds, **out})

    def _admin_health(self, req: Request) -> Response:
        return Response({"url": self.url,
                         "scrubbing": self._is_scrubbing(),
                         "peers": self.peer_health.snapshot()})

    # paths the admission gate never sheds: observability and the tiny
    # control endpoints an operator needs most exactly when the node is
    # overloaded (shedding /admin/qos would saw off the escape hatch)
    QOS_EXEMPT = ("/status", "/metrics", "/ui", "/debug",
                  "/admin/qos", "/admin/health", "/admin/scrub/status",
                  "/admin/ec/batcher", "/admin/ec/trace", "/admin/hotkeys",
                  "/admin/telemetry", "/admin/cache", "/admin/hints",
                  "/admin/profile")

    def _admission_gate(self, method: str, path: str, headers, client):
        """HttpServer admission hook: classify (propagated header wins
        over the method/path default), ask the governor for a slot,
        shed with 503 + Retry-After when it says no."""
        if not self.qos.enabled:
            return None
        for p in self.QOS_EXEMPT:
            if path.startswith(p):
                return None
        cls = from_headers(headers) or classify(method, path)
        grant = self.qos.admit(cls)
        if not grant.ok:
            self._m_req.inc("qos_shed")
            return Response(
                {"error": "overloaded", "class": cls}, status=503,
                headers={"Retry-After": f"{grant.retry_after:.2f}"})
        return grant.release

    def _admin_qos(self, req: Request) -> Response:
        return Response({"url": self.url, **self.qos.snapshot()})

    def _admin_qos_configure(self, req: Request) -> Response:
        return Response({"url": self.url,
                         **self.qos.configure(**(req.json() or {}))})

    def _admin_cache(self, req: Request) -> Response:
        cache = self.store.needle_cache if self.store else None
        if cache is None:
            return Response({"url": self.url, "enabled": False,
                             "connections": self.http.conn_stats()})
        return Response({"url": self.url, "enabled": True,
                         **cache.stats(),
                         "connections": self.http.conn_stats()})

    def _admin_cache_configure(self, req: Request) -> Response:
        cache = self.store.needle_cache if self.store else None
        if cache is None:
            return Response({"error": "cache disabled"}, status=409)
        b = req.json() or {}
        out = cache.configure(
            capacity_bytes=b.get("capacity_bytes"),
            admit_min=b.get("admit_min"))
        if b.get("clear"):
            for loc in self.store.locations:
                for vid in list(loc.volumes):
                    cache.invalidate_volume(vid)
                for vid in list(loc.ec_volumes):
                    cache.invalidate_volume(vid)
            out = cache.stats()
        return Response({"url": self.url, "enabled": True, **out})

    def telemetry_snapshot(self) -> dict:
        snap = {"node": self.url, "server": "volume",
                "red": self.red.snapshot(),
                "hotkeys": self.hotkeys.snapshot(),
                "ledger": self.ledger.snapshot(),
                "tiering": self.tiering_report()}
        if self.hint_journal is not None:
            # journal size/age ride the heartbeat so the master can
            # fire hints_stale when a drain wedges
            st = self.hint_journal.stats()
            snap["hints"] = {"pending_rows": st["pending_rows"],
                             "oldest_debt_age_s": st["oldest_debt_age_s"]}
        return snap

    def tiering_report(self) -> dict:
        """Per-volume tier state + cumulative read counters for the
        master's TieringPlanner (rides every heartbeat's telemetry
        piggyback). A tiered volume's size comes from the backend's
        cached stat — one HEAD against the gateway on the first report
        after demotion, free afterwards."""
        vols = {}
        for loc in self.store.locations:
            for vid, v in list(loc.volumes.items()):
                has_ec = vid in loc.ec_volumes \
                    or self.store.has_ec_volume(vid)
                if v.is_tiered:
                    rung = "cloud"
                else:
                    rung = "ec" if has_ec else "hot"
                try:
                    size = v.content_size()
                except (IOError, OSError, ValueError):
                    size = 0  # tier endpoint blip: report, don't crash
                vols[vid] = {"reads": self.vol_reads.get(vid, 0),
                             "rung": rung, "size": size,
                             "read_only": v.read_only,
                             "has_ec_shards": has_ec}
        return {"volumes": vols, "stats": dict(self.tier_stats)}

    def _admin_telemetry(self, req: Request) -> Response:
        return Response(self.telemetry_snapshot())

    def _refresh_gauges(self) -> None:
        # runs before every exposition (scrape AND push-gateway loop)
        import os
        store = getattr(self, "store", None)
        if store is None:
            return
        hb = store.collect_heartbeat()
        self._m_volumes.set(value=len(hb.get("volumes", [])))
        self._m_ec_shards.set(value=sum(
            bin(e.get("ec_index_bits", 0)).count("1")
            for e in hb.get("ec_shards", [])))
        self._m_bytes.set(value=sum(
            v.get("size", 0) for v in hb.get("volumes", [])))
        for d in self._store_dirs:
            try:
                st = os.statvfs(d)
                self._m_disk_free.set(d, value=st.f_bavail * st.f_frsize)
            except OSError:
                pass
        cache = store.needle_cache
        if cache is not None:
            cs = cache.stats()
            for stat in ("hits", "misses", "bytes", "evictions",
                         "items", "rejects", "coalesced"):
                self._m_cache.set(stat, value=cs[stat])
        for stat, val in self.http.conn_stats().items():
            self._m_conns.set(stat, value=val)
        if self.ec_batcher is not None:
            bs = self.ec_batcher.stats()
            for stage, val in bs["stage_s"].items():
                self._m_ec_stage.set(stage, value=val)
            for part, val in bs["loop_s"].items():
                self._m_ec_stage.set("loop." + part, value=val)
            for spec, counters in bs["by_spec"].items():
                for stat, val in counters.items():
                    if stat == "rows":
                        # the spec's jobs by their operand's rows:
                        # stat="rows.6" beside stat="jobs"
                        for rows, n in val.items():
                            self._m_ec_spec.set(spec, f"rows.{rows}",
                                                value=n)
                    else:
                        self._m_ec_spec.set(spec, stat, value=val)
            for rung, counters in bs["by_rung"].items():
                for stat, val in counters.items():
                    self._m_ec_rung.set(rung, stat, value=val)
            self._m_ec_cap_splits.set(value=bs["cap_splits"])
            self._m_ec_overlapped.set(value=bs["overlapped_dispatches"])

    def _handle_metrics(self, req: Request) -> Response:
        return Response(self.metrics.expose_text(),
                        content_type="text/plain; version=0.0.4")

    def _handle_ui(self, req: Request) -> Response:
        """Status page (reference weed/server/volume_server_ui/): disk,
        concurrency, scrub progress, volumes, EC shards — server-side
        rendered, zero assets."""
        hb = self.store.collect_heartbeat()
        rows = "".join(
            f"<tr><td>{v['id']}</td><td>{v['collection']}</td>"
            f"<td>{_human_bytes(v['size'])}</td><td>{v['file_count']}</td>"
            f"<td>{v['delete_count']}</td>"
            f"<td>{v.get('disk_type', 'hdd')}</td>"
            f"<td>{'RO' if v['read_only'] else 'RW'}</td></tr>"
            for v in hb["volumes"])
        ec_rows = "".join(
            f"<tr><td>{e['id']}</td>"
            f"<td>{bin(e['ec_index_bits']).count('1')}</td>"
            f"<td><code>{e['ec_index_bits']:014b}</code></td></tr>"
            for e in hb["ec_shards"])
        disk_rows = []
        for d in self._store_dirs:
            try:
                st = os.statvfs(d)
                free = st.f_bavail * st.f_frsize
                total = st.f_blocks * st.f_frsize
                disk_rows.append(
                    f"<tr><td>{d}</td><td>{_human_bytes(total)}</td>"
                    f"<td>{_human_bytes(free)}</td></tr>")
            except OSError:
                disk_rows.append(f"<tr><td>{d}</td><td>?</td><td>?</td></tr>")
        scrub = self.scrubber.status() if self.scrubber else {}
        cur = scrub.get("current")
        if cur and cur.get("size"):
            pct = 100.0 * cur["offset"] / cur["size"]
            progress = (f"vol {cur['volume_id']} ({cur['kind']}) "
                        f"{pct:.1f}% ({_human_bytes(cur['offset'])} / "
                        f"{_human_bytes(cur['size'])})")
        else:
            progress = "idle"
        scrub_rows = (
            f"<tr><th>state</th><td>"
            f"{'running' if scrub.get('running') else 'stopped'}</td></tr>"
            f"<tr><th>progress</th><td>{progress}</td></tr>"
            f"<tr><th>rate limit</th><td>"
            f"{_human_bytes(int(scrub.get('rate_bytes_per_sec', 0)))}/s"
            f"</td></tr>"
            f"<tr><th>bytes scrubbed</th><td>"
            f"{_human_bytes(scrub.get('bytes_scrubbed', 0))}</td></tr>"
            f"<tr><th>corruptions found</th><td>"
            f"{scrub.get('corruptions_found', 0)}</td></tr>"
            f"<tr><th>passes completed</th><td>"
            f"{scrub.get('passes_completed', 0)}</td></tr>")
        html = (
            "<html><head><title>seaweedfs-tpu volume server</title>"
            "<style>body{font-family:sans-serif;margin:2em}"
            "table{border-collapse:collapse;margin-bottom:1.5em}"
            "td,th{border:1px solid #999;padding:3px 10px;"
            "text-align:left}</style></head>"
            f"<body><h1>Volume Server {self.url}</h1>"
            f"<p>master: {self.master_url} | rack: {self.store.rack}"
            f" | dc: {self.store.data_center}"
            f" | grpc: {self.grpc_port or '-'}"
            f" | tcp: {self.tcp_server.port if self.tcp_server else '-'}"
            "</p>"
            "<h2>Disk</h2><table><tr><th>dir</th><th>total</th>"
            f"<th>free</th></tr>{''.join(disk_rows)}</table>"
            "<h2>Concurrency</h2><table>"
            f"<tr><th>upload in-flight</th>"
            f"<td>{_human_bytes(self.upload_limiter.in_flight)}</td></tr>"
            f"<tr><th>download in-flight</th>"
            f"<td>{_human_bytes(self.download_limiter.in_flight)}</td>"
            "</tr></table>"
            f"<h2>Scrub</h2><table>{scrub_rows}</table>"
            f"<h2>Volumes ({len(hb['volumes'])})</h2>"
            "<table><tr><th>id</th>"
            "<th>collection</th><th>size</th><th>files</th><th>deleted</th>"
            f"<th>disk</th><th>mode</th></tr>{rows}</table>"
            f"<h2>EC shards ({len(hb['ec_shards'])} vols)</h2>"
            "<table><tr><th>vid</th><th>shards</th>"
            f"<th>bits</th></tr>{ec_rows}</table></body></html>")
        return Response(html, content_type="text/html")

    # ---- integrity scrub ----
    def _admin_scrub(self, req: Request) -> Response:
        """Trigger a synchronous scrub pass (optionally one volume).
        The background thread keeps its own schedule; this is the
        operator/shell entry point."""
        b = req.json() if req.body else {}
        vid = b.get("volume_id")
        result = self.scrubber.run_once(
            volume_id=int(vid) if vid is not None else None,
            use_cursor=bool(b.get("use_cursor", True)))
        return Response(result)

    def _admin_scrub_status(self, req: Request) -> Response:
        return Response(self.scrubber.status())

    def _report_scrub(self, report: dict) -> None:
        """Forward a corruption report to the master's repair queue,
        following a leader redirect like the heartbeat path does."""
        body = {"url": self.url, **report}
        for _attempt in range(2):
            try:
                self._master_json("POST", "/scrub/report", body,
                                  deadline=Deadline.after(5.0))
                return
            except HttpError as e:
                old = self.master_url
                self._follow_leader_hint(e)
                if self.master_url == old:
                    return
            except ConnectionError:
                self._fail_over()

    def _check_jwt(self, req: Request) -> Optional[Response]:
        if not self.jwt_signing_key or req.query.get("type") == "replicate":
            return None
        from seaweedfs_tpu.utils.security import verify_jwt
        auth = req.headers.get("Authorization", "")
        token = auth[7:] if auth.startswith("Bearer ") else \
            req.query.get("jwt", "")
        fid = f"{req.match.group(1)},{req.match.group(2)}"
        if not verify_jwt(self.jwt_signing_key, token, fid):
            return Response({"error": "unauthorized"}, status=401)
        return None

    def _check_read_jwt(self, req: Request) -> Optional[Response]:
        if not self.jwt_read_key:
            return None
        from seaweedfs_tpu.utils.security import verify_jwt
        auth = req.headers.get("Authorization", "")
        token = auth[7:] if auth.startswith("Bearer ") else             req.query.get("jwt", "")
        fid = f"{req.match.group(1)},{req.match.group(2)}"
        if not verify_jwt(self.jwt_read_key, token, fid):
            return Response({"error": "unauthorized"}, status=401)
        return None

    # ---- public data path ----
    def _upload_gate(self, path: str, length: int):
        """Pre-body-read throttle for needle uploads (reference
        volume_server_handlers.go:48-80): consulted by HttpServer with
        the declared Content-Length BEFORE buffering the payload, so N
        concurrent large PUTs wait at the socket instead of ballooning
        RSS. Admin/EC transfers are internal and exempt, as in the
        reference (their sizes are volume-bounded)."""
        if path.startswith("/admin"):
            return None
        if self.file_size_limit > 0 and length > self.file_size_limit:
            return Response({"error": f"file over the limit of "
                             f"{self.file_size_limit} bytes"}, status=413)
        if not self.upload_limiter.try_acquire(length):
            self._m_req.inc("write_shed")
            return Response(
                {"error": "too many requests"}, status=429,
                headers={"Retry-After": "2"})
        return lambda: self.upload_limiter.release(length)

    def _parse_fid(self, req: Request) -> tuple[int, int, int]:
        vid = int(req.match.group(1))
        key, cookie = parse_needle_id_cookie(req.match.group(2))
        return vid, key, cookie

    def _handle_write(self, req: Request) -> Response:
        denied = self._check_jwt(req)
        if denied:
            return denied
        self._m_req.inc("write")
        vid, key, cookie = self._parse_fid(req)
        self.hotkeys.record("needle", "%d,%x" % (vid, key))
        n = Needle(id=key, cookie=cookie, data=req.body,
                   name=req.query.get("name", "").encode(),
                   mime=req.query.get("mime", "").encode())
        if req.query.get("gzip") == "1":
            from seaweedfs_tpu.storage.needle import FLAG_IS_COMPRESSED
            n.flags |= FLAG_IS_COMPRESSED
        if req.query.get("ttl"):
            from seaweedfs_tpu.storage.needle import FLAG_HAS_TTL
            from seaweedfs_tpu.storage.super_block import TTL
            n.ttl = TTL.parse(req.query["ttl"]).to_bytes()
            n.flags |= FLAG_HAS_TTL
            if not n.last_modified:
                n.last_modified = int(clockctl.now())
            from seaweedfs_tpu.storage.needle import \
                FLAG_HAS_LAST_MODIFIED_DATE
            n.flags |= FLAG_HAS_LAST_MODIFIED_DATE
        if req.query.get("ts"):
            n.last_modified = int(req.query["ts"])
        n.set_flags_from_fields()
        try:
            size = self.store.write_volume_needle(vid, n)
        except NotFoundError:
            return Response({"error": f"volume {vid} not found"}, status=404)
        except PermissionError as e:
            return Response({"error": str(e)}, status=409)
        if req.query.get("type") != "replicate":
            err = self._replicate(req, "write")
            if err:
                return Response({"error": err}, status=500)
        return Response({"name": req.query.get("name", ""),
                         "size": len(req.body), "eTag": f"{n.checksum:x}"},
                        status=201)

    def _peek_read_size(self, req: Request) -> int:
        """Estimate a GET's payload from the needle map before touching
        disk, for download byte accounting (the reference reads the map
        entry first too: volume_read.go ReadNeedleDataInto)."""
        try:
            vid = int(req.match.group(1))
            key, _ = parse_needle_id_cookie(req.match.group(2))
        except (AttributeError, ValueError, IndexError):
            return 0
        vol = self.store.find_volume(vid)
        if vol is None:
            # EC-served volumes get accounted too (their reads
            # materialize whole needles just the same)
            ev = self.store.find_ec_volume(vid) \
                if hasattr(self.store, "find_ec_volume") else None
            if ev is not None:
                try:
                    _, size = ev.find_needle_from_ecx(key)
                    return max(int(size), 0)
                except Exception:
                    return 0
            return 0
        nv = vol.nm.get(key)
        if nv is None or nv[1] <= 0:
            return 0
        return int(nv[1])

    def _handle_read(self, req: Request) -> Response:
        with tracing.stage("volume.read"):
            return self._handle_read_limited(req)

    def _handle_read_limited(self, req: Request) -> Response:
        # byte-accounted backpressure only on the real HTTP socket path
        # (gRPC/LocalRequest dispatch never fires on_sent)
        est = 0
        if getattr(req, "handler", None) is not None:
            with tracing.stage("volume.read.peek"):
                est = self._peek_read_size(req)
        if est and not self.download_limiter.try_acquire(est):
            self._m_req.inc("read_shed")
            return Response({"error": "too many requests"}, status=429,
                            headers={"Retry-After": "2"})
        try:
            # request edge: inherit the caller's propagated budget or
            # mint a fresh one; every nested hop (remote shard fetch,
            # degraded recovery, master lookup) reads this scope
            dl = Deadline.from_headers(req.headers,
                                       default=READ_DEADLINE_S)
            with deadline_scope(dl):
                resp = self._handle_read_inner(req)
        except BaseException:
            self.download_limiter.release(est)
            raise
        if est:
            resp.on_sent = lambda: self.download_limiter.release(est)
        return resp

    def _handle_read_inner(self, req: Request) -> Response:
        denied = self._check_read_jwt(req)
        if denied:
            return denied
        self._m_req.inc("read")
        vid, key, cookie = self._parse_fid(req)
        self.hotkeys.record("needle", "%d,%x" % (vid, key))
        # temperature signal for the tiering planner: demand against
        # the volume, wherever the bytes end up coming from (local,
        # EC-degraded, or the cloud tier). GIL-atomic int bump.
        self.vol_reads[vid] += 1
        if req.headers.get("Range") and \
                self.store.find_volume(vid) is None and \
                self.store.has_ec_volume(vid) and \
                not (req.query.get("width") or req.query.get("height")):
            resp = self._ec_ranged_read(req, vid, key, cookie)
            if resp is not None:
                return resp
            # else: metadata says we can't serve the subrange (v1,
            # compressed, malformed range) — fall through to full read
        if self.zero_copy:
            resp = self._zero_copy_read(req, vid, key, cookie)
            if resp is not None:
                return resp
            # else: some rung of the fallback ladder claimed the read —
            # the buffered path below is the single error/repair
            # authority and the bit-identity comparator
        try:
            if self.store.find_volume(vid) is not None:
                try:
                    n = self.store.read_volume_needle(vid, key, cookie)
                except (NotFoundError, ValueError):
                    # divergence suspect: this replica may have missed a
                    # quorum write (404) or hold a torn record (CRC) —
                    # pull from a peer and serve the repaired copy.
                    # DeletedError never repairs: tombstones are
                    # authoritative here
                    n = self._pull_repair(vid, key, cookie)
                    if n is None:
                        raise
            elif self.store.has_ec_volume(vid):
                n = self.store.read_ec_shard_needle(vid, key, cookie)
            else:
                return Response({"error": f"volume {vid} not found"},
                                status=404)
        except (NotFoundError, DeletedError):
            return Response(b"", status=404, content_type="text/plain")
        except CookieMismatchError:
            return Response(b"", status=404, content_type="text/plain")
        with tracing.stage("volume.read.respond"):
            return self._needle_response(req, vid, key, n)

    def _needle_response(self, req: Request, vid: int, key: int,
                         n) -> Response:
        """The buffered read's reply for a needle in hand: ledger
        charge, headers, transforms, range and ETag handling."""
        h = getattr(req, "handler", None)
        self.ledger.charge_disk(
            len(n.data),
            tenant=h.client_address[0] if h is not None else "-")
        headers = {}
        if n.is_compressed:
            accept = req.headers.get("Accept-Encoding", "")
            if "gzip" in accept:
                headers["Content-Encoding"] = "gzip"
            else:
                import gzip as _gz
                n.data = _gz.decompress(n.data)
        if n.last_modified:
            headers["X-Last-Modified"] = str(n.last_modified)
        mime_str = n.mime.decode(errors="replace") if n.mime else ""
        if (req.query.get("width") or req.query.get("height")) and \
                not n.is_compressed:
            from seaweedfs_tpu.utils.images import is_image, resized
            if is_image(mime_str, n.name.decode(errors="replace")):
                n.data = resized(
                    n.data,
                    int(req.query.get("width") or 0) or None,
                    int(req.query.get("height") or 0) or None,
                    req.query.get("mode", ""))
        if n.name:
            headers["X-File-Name"] = n.name.decode(errors="replace")
        if n.has_ttl and n.ttl and n.last_modified:
            from seaweedfs_tpu.storage.super_block import TTL
            ttl = TTL.from_bytes(n.ttl)
            if ttl.minutes and \
                    clockctl.now() > n.last_modified + ttl.minutes * 60:
                return Response(b"", status=404, content_type="text/plain")
        mime = (n.mime.decode(errors="replace")
                if n.mime else "application/octet-stream")
        # cache-aware routing: advertise when this read was (or is now)
        # backed by the hot-needle cache so clients can prefer this
        # replica for the next read of the same needle
        cache = self.store.needle_cache
        if cache is not None and cache.contains(vid, key):
            headers[weed_headers.CACHE_HOT] = "1"
        from seaweedfs_tpu.utils.httpd import (RangeNotSatisfiable,
                                               parse_byte_range)
        try:
            rng = parse_byte_range(req.headers.get("Range", ""),
                                   len(n.data))
        except RangeNotSatisfiable:
            headers["Content-Range"] = f"bytes */{len(n.data)}"
            return Response(b"", status=416, content_type=mime,
                            headers=headers)
        if rng is not None:
            lo, hi = rng
            piece = n.data[lo:hi + 1]
            headers["Content-Range"] = f"bytes {lo}-{hi}/{len(n.data)}"
            return Response(piece, status=206, content_type=mime,
                            headers=headers)
        headers["ETag"] = f'"{n.checksum:x}"'
        if req.headers.get("If-None-Match") == f'"{n.checksum:x}"':
            return Response(b"", status=304, content_type=mime)
        return Response(n.data, content_type=mime, headers=headers)

    def _zero_copy_read(self, req: Request, vid: int, key: int,
                        cookie) -> Optional[Response]:
        """Descriptor fast path: answer a whole-needle or Range GET
        with ``send_file(fd, offset, count)`` so the payload moves
        page-cache -> socket inside the kernel. Returns None to fall
        back to the buffered path — the explicit ladder:

        - in-process dispatch (no socket to sendfile to)
        - image resize (must materialize and transform)
        - cached needle (memory beats disk; keeps cache-aware routing)
        - EC / tiered / v1 volumes, expired volumes, malformed records
        - any lookup error (buffered path owns read-repair + 404 shape)
        - compressed payload for a client that doesn't accept gzip
        - TTL-expired needle (buffered 404 shape kept)
        - payloads under zero_copy_min (syscall setup beats the copy
          only above a threshold; small hot needles feed the cache)

        The ETag is the record's STORED crc — identical to the
        buffered path's computed value for locally written records."""
        if getattr(req, "handler", None) is None:
            return None
        if req.query.get("width") or req.query.get("height"):
            return None
        desc = self.store.read_volume_needle_descriptor(vid, key, cookie)
        if desc is None:
            return None
        n, fd, payload_off, data_size = desc
        try:
            if data_size < self.zero_copy_min:
                return None
            if n.is_compressed and "gzip" not in \
                    req.headers.get("Accept-Encoding", ""):
                return None
            if n.has_ttl and n.ttl and n.last_modified:
                from seaweedfs_tpu.storage.super_block import TTL
                ttl = TTL.from_bytes(n.ttl)
                if ttl.minutes and clockctl.now() > \
                        n.last_modified + ttl.minutes * 60:
                    return None  # buffered path serves the 404 shape
            h = req.handler
            self.ledger.charge_disk(data_size,
                                    tenant=h.client_address[0])
            headers = {weed_headers.ZERO_COPY: "1"}
            if n.is_compressed:
                headers["Content-Encoding"] = "gzip"
            if n.last_modified:
                headers["X-Last-Modified"] = str(n.last_modified)
            if n.name:
                headers["X-File-Name"] = n.name.decode(errors="replace")
            mime = (n.mime.decode(errors="replace")
                    if n.mime else "application/octet-stream")
            from seaweedfs_tpu.utils.httpd import (RangeNotSatisfiable,
                                                   parse_byte_range,
                                                   send_file)
            try:
                rng = parse_byte_range(req.headers.get("Range", ""),
                                       data_size)
            except RangeNotSatisfiable:
                headers["Content-Range"] = f"bytes */{data_size}"
                return Response(b"", status=416, content_type=mime,
                                headers=headers)
            self._m_req.inc("read_zero_copy")
            if rng is not None:
                lo, hi = rng
                headers["Content-Range"] = f"bytes {lo}-{hi}/{data_size}"
                return send_file(fd, payload_off + lo, hi - lo + 1,
                                 status=206, content_type=mime,
                                 headers=headers)
            headers["ETag"] = f'"{n.checksum:x}"'
            if req.headers.get("If-None-Match") == f'"{n.checksum:x}"':
                return Response(b"", status=304, content_type=mime)
            return send_file(fd, payload_off, data_size,
                             content_type=mime, headers=headers)
        finally:
            # send_file dup'd its own handle; the descriptor's is ours
            os.close(fd)

    def _ec_ranged_read(self, req: Request, vid: int, key: int,
                        cookie) -> Optional[Response]:
        """Subrange degraded read: satisfy an EC Range request by
        reconstructing ONLY the needle's requested byte range, not the
        whole record — when a shard is missing, recovery cost scales
        with the range, not the needle (or large-block) size. Returns
        None to fall back to the whole-needle path (v1 volume,
        compressed data, no parsable range)."""
        from seaweedfs_tpu.utils.httpd import (RangeNotSatisfiable,
                                               parse_byte_range)
        try:
            n, data_size = self.store.ec_needle_meta(vid, key, cookie)
        except (NotFoundError, DeletedError, CookieMismatchError):
            return Response(b"", status=404, content_type="text/plain")
        except ValueError:
            return None  # v1 layout: data offset isn't knowable cheaply
        if n.is_compressed or data_size == 0:
            return None  # must inflate (or 404) via the full path
        headers = {}
        if n.last_modified:
            headers["X-Last-Modified"] = str(n.last_modified)
        if n.name:
            headers["X-File-Name"] = n.name.decode(errors="replace")
        if n.has_ttl and n.ttl and n.last_modified:
            from seaweedfs_tpu.storage.super_block import TTL
            ttl = TTL.from_bytes(n.ttl)
            if ttl.minutes and \
                    clockctl.now() > n.last_modified + ttl.minutes * 60:
                return Response(b"", status=404, content_type="text/plain")
        mime = (n.mime.decode(errors="replace")
                if n.mime else "application/octet-stream")
        try:
            rng = parse_byte_range(req.headers["Range"], data_size)
        except RangeNotSatisfiable:
            headers["Content-Range"] = f"bytes */{data_size}"
            return Response(b"", status=416, content_type=mime,
                            headers=headers)
        if rng is None:
            return None  # malformed spec -> full body per RFC
        lo, hi = rng
        try:
            piece = self.store.read_ec_needle_data_range(
                vid, key, lo, hi - lo + 1)
        except (NotFoundError, DeletedError):
            return Response(b"", status=404, content_type="text/plain")
        except Exception as e:
            glog.warning("ec subrange read v%d,%x failed (%s); "
                         "falling back to full read", vid, key, e)
            return None
        self._m_req.inc("ec_subrange")
        headers["Content-Range"] = f"bytes {lo}-{hi}/{data_size}"
        return Response(piece, status=206, content_type=mime,
                        headers=headers)

    def _handle_delete(self, req: Request) -> Response:
        denied = self._check_jwt(req)
        if denied:
            return denied
        self._m_req.inc("delete")
        vid, key, cookie = self._parse_fid(req)
        try:
            if self.store.find_volume(vid) is not None:
                size = self.store.delete_volume_needle(vid, key, cookie)
            elif self.store.has_ec_volume(vid):
                size = self._ec_delete_fanout(vid, key, cookie)
            else:
                return Response({"error": f"volume {vid} not found"},
                                status=404)
        except (NotFoundError, DeletedError):
            return Response({"size": 0}, status=404)
        if req.query.get("type") != "replicate" \
                and self.store.find_volume(vid) is not None:
            err = self._replicate(req, "delete")
            if err:
                return Response({"error": err}, status=500)
        return Response({"size": size}, status=202)

    REPLICA_CACHE_TTL = 5.0  # matches the freshest vidMap tier

    def _replica_peers(self, vid: int) -> list[str]:
        """Peer replica urls for a volume, with a short-TTL cache — a
        master /dir/lookup per write would cost more than the write
        itself (the reference's writers resolve replicas through the
        wdclient vidMap cache the same way)."""
        now = clockctl.monotonic()
        cached = self._replica_cache.get(vid)
        if cached is not None and cached[0] > now:
            return cached[1]
        try:
            locs = self._master_json(
                "GET", f"/dir/lookup?volumeId={vid}",
                deadline=Deadline.after(5.0))
        except (ConnectionError, HttpError):
            return []  # nobody to replicate to (not registered yet)
        others = [l["url"] for l in locs.get("locations", [])
                  if not self._is_self(l["url"])]
        self._replica_cache[vid] = (now + self.REPLICA_CACHE_TTL, others)
        return others

    # Edge budget for one replica fan-out when the client sent none:
    # bounds the whole concurrent batch, not each leg.
    REPLICATE_DEADLINE_S = 20.0

    def _replicate_pool_get(self):
        if self._replicate_pool is None:
            with self._replicate_pool_lock:
                if self._replicate_pool is None:
                    from concurrent.futures import ThreadPoolExecutor
                    self._replicate_pool = ThreadPoolExecutor(
                        max_workers=16, thread_name_prefix="replicate")
        return self._replicate_pool

    def _replicate(self, req: Request, op: str) -> Optional[str]:
        """Synchronous fan-out to the other replicas
        (reference topology/store_replicate.go:58-110), posted to ALL
        peers concurrently so a replicated write costs ~max(peers)
        instead of sum(peers). Per-peer circuit breakers fail fast on
        known-down replicas; any failure drops the cached peer list so
        the next write re-resolves the (possibly moved) topology
        instead of pinning the error for the cache TTL.

        With hinted handoff on, the fan-out is a SLOPPY QUORUM: the
        local write plus a majority of the peer legs completes the
        request, and each missed leg is journaled as a hint the drain
        thread replays after the peer heals (read-repair covers reads
        that hit the lagging replica meanwhile). Only falling below
        the quorum fails the write."""
        vid = int(req.match.group(1))
        vol = self.store.find_volume(vid)
        if vol is not None and \
                vol.super_block.replica_placement.to_byte() == 0:
            # single-copy volume: no peers can exist, skip the lookup
            return None
        others = self._replica_peers(vid)
        if not others:
            return None
        qs = "&".join(f"{k}={v}" for k, v in req.query.items()
                      if k != "type")
        sep = "&" if qs else ""
        dl = current_deadline() or Deadline.after(self.REPLICATE_DEADLINE_S)
        # pool legs don't inherit contextvars: capture the ambient
        # class (a replica leg of a client PUT stays write class) and
        # the ambient trace span, so each replica leg's http_call nests
        # as a child span of the PUT that fanned out
        cls = current_class() or WRITE
        span = tracing.current_span()
        if span is not None:
            span.annotate("replica.fanout", len(others))

        def send(url: str) -> Optional[str]:
            if not self.peer_health.allow(url):
                return f"replica {url}: circuit open"
            target = (f"http://{url}{req.path}?{qs}{sep}type=replicate")
            t0 = clockctl.monotonic()
            try:
                with class_scope(cls), tracing.span_scope(span):
                    if op == "write":
                        status, _body, _ = http_call("POST", target,
                                                     body=req.body,
                                                     deadline=dl)
                    else:
                        status, _body, _ = http_call("DELETE", target,
                                                     deadline=dl)
            except ConnectionError as e:
                self.peer_health.record(url, False)
                return f"replica {url}: {e}"
            # an HTTP answer means the peer is up (same convention as
            # _master_json); the write itself may still have failed
            self.peer_health.record(url, True, clockctl.monotonic() - t0)
            if status >= 400 and status != 404:
                return f"replica {url}: HTTP {status}"
            return None

        if len(others) == 1 or not self.parallel_replication:
            errs = [send(u) for u in others]
        else:
            errs = list(self._replicate_pool_get().map(send, others))
        failed = [(u, e) for u, e in zip(others, errs) if e]
        if not failed:
            return None
        self._replica_cache.pop(vid, None)
        # quorum of the PEER legs (the local write already landed):
        # floor(len/2) keeps a 2-copy volume writable with its only
        # peer dark — availability-biased, the hint closes the gap
        if self.hinted_handoff and self.hint_journal is not None \
                and len(others) - len(failed) >= len(others) // 2:
            key, cookie = parse_needle_id_cookie(req.match.group(2))
            for url, why in failed:
                self.hint_journal.record(op, vid, key, cookie, url,
                                         fid=req.match.group(2))
                glog.warning("replica %s missed %s of %d,%x (%s); "
                             "hint journaled", url, op, vid, key, why)
            if span is not None:
                span.annotate("replica.hinted", len(failed))
            self._m_req.inc("replica_hinted")
            return None
        return "; ".join(why for _, why in failed)

    # cadence of the hint drain pass (the pass itself is cheap when
    # nothing is pending: one dict snapshot)
    HINT_DRAIN_INTERVAL_S = 2.0

    def _hint_drain_loop(self) -> None:
        while not self._stop.wait(self.HINT_DRAIN_INTERVAL_S):
            try:
                self.drain_hints()
            except Exception as e:
                glog.warning("hint drain pass failed (will retry): %s", e)

    def drain_hints(self, limit: int = 256) -> int:
        """One drain pass: replay up to `limit` pending hints, oldest
        first, skipping peers whose breaker is still open. Returns the
        number repaid. Public so drills can force a synchronous drain
        instead of waiting out the loop cadence.

        The BACKGROUND class scope lives HERE, not in the loop: every
        replayed write must carry the background QoS class to the peer
        (http_call stamps X-Weed-Class from the ambient scope), so a
        drain burst after a partition heals queues behind foreground
        traffic — including when a drill invokes this synchronously."""
        j = self.hint_journal
        if j is None or self.store is None:
            return 0
        drained = 0
        with class_scope(BACKGROUND), \
                profiler.scope(cls=BACKGROUND, route="hints"):
            for h in j.pending()[:limit]:
                if self._stop.is_set():
                    break
                if not self.peer_health.allow(h["peer"]):
                    continue
                try:
                    ok = self._replay_hint(h)
                except Exception as e:
                    glog.warning("hint replay %s failed: %s", h, e)
                    ok = False
                if ok:
                    j.ack(h["seq"])
                    drained += 1
        if drained:
            self._m_req.inc("hint_drained")
        return drained

    def _replay_hint(self, h: dict) -> bool:
        """Repay one hint. True means the debt is settled (replayed,
        or moot: needle/volume gone locally, peer no longer hosts the
        volume); False means keep it pending for the next pass."""
        url = h["peer"]
        vid, key = int(h["vid"]), int(h["key"])
        if self._is_self(url):
            return True  # topology moved the replica onto us
        if h["op"] == "delete":
            t0 = clockctl.monotonic()
            try:
                status, _, _ = http_call(
                    "DELETE", f"http://{url}/{vid},{h['fid']}"
                    "?type=replicate",
                    deadline=Deadline.after(10.0))
            except ConnectionError:
                self.peer_health.record(url, False)
                return False
            self.peer_health.record(url, True, clockctl.monotonic() - t0)
            return status < 400 or status == 404
        v = self.store.find_volume(vid)
        if v is None:
            return True  # volume left this node: nothing to hand off
        try:
            blob, size = v.read_needle_blob(key)
        except Exception:
            # deleted (or never committed) since the hint was taken —
            # the delete got its own hint, this one is moot
            return True
        t0 = clockctl.monotonic()
        try:
            status, _, _ = http_call(
                "POST", f"http://{url}/admin/write_needle_blob",
                json_body={"volume_id": vid, "blob": blob.hex(),
                           "size": size},
                deadline=Deadline.after(20.0))
        except ConnectionError:
            self.peer_health.record(url, False)
            return False
        self.peer_health.record(url, True, clockctl.monotonic() - t0)
        # 404 = the peer no longer hosts the volume (moved/rebuilt):
        # the debt is no longer owed to THIS peer
        return status < 400 or status == 404

    # budget for one peer blob fetch during in-line read repair when
    # the read arrived without an ambient deadline
    PULL_REPAIR_DEADLINE_S = 10.0

    def _pull_repair(self, vid: int, key: int,
                     cookie: Optional[int] = None) -> Optional[Needle]:
        """In-line read repair: this replica is missing (or holds a
        corrupt copy of) a needle that a replicated volume should have.
        Pull the raw record from a healthy peer, land it locally with
        strict cache invalidation, and return the repaired needle —
        the read that detected the divergence is also the one that
        heals it. Returns None when no peer can supply the record
        (including the legitimate case: the needle never existed)."""
        if not self.hinted_handoff:
            return None
        v = self.store.find_volume(vid)
        if v is None or v.read_only or v.is_expired():
            return None
        if v.super_block.replica_placement.to_byte() == 0:
            return None  # single copy: nothing to diverge from
        peers = self._replica_peers(vid)
        if not peers:
            return None
        dl = current_deadline() or \
            Deadline.after(self.PULL_REPAIR_DEADLINE_S)
        blob = None
        size = 0
        for url in self.peer_health.rank(peers):
            if not self.peer_health.allow(url):
                continue
            t0 = clockctl.monotonic()
            try:
                out = http_json(
                    "GET", f"http://{url}/admin/needle_blob"
                    f"?volumeId={vid}&key={key}", deadline=dl)
            except HttpError:
                # the peer answered but doesn't have it either
                self.peer_health.record(url, True,
                                        clockctl.monotonic() - t0)
                continue
            except ConnectionError:
                self.peer_health.record(url, False)
                continue
            self.peer_health.record(url, True, clockctl.monotonic() - t0)
            blob, size = bytes.fromhex(out["blob"]), int(out["size"])
            break
        if blob is None:
            return None
        cache = self.store.needle_cache
        if cache is not None:
            # same double-invalidation discipline as
            # Store.write_volume_needle: no stale epoch can be admitted
            cache.invalidate(vid, key)
        try:
            v.write_needle_blob(blob, size)
        except Exception as e:
            glog.warning("read repair of %d,%x failed to land: %s",
                         vid, key, e)
            return None
        finally:
            if cache is not None:
                cache.invalidate(vid, key)
        self._m_req.inc("read_repair")
        glog.info("read-repaired %d,%x from a peer replica", vid, key)
        try:
            return self.store.read_volume_needle(vid, key, cookie)
        except Exception:
            return None

    def _admin_replica_repair(self, req: Request) -> Response:
        """A reader observed this replica lagging (404 here while a
        sibling served the needle): pull the record from a peer now
        instead of waiting for the owner's hint drain."""
        b = req.json()
        vid, key = int(b["volume_id"]), int(b["key"])
        if self.store.find_volume(vid) is None:
            return Response({"error": f"volume {vid} not found"},
                            status=404)
        try:
            self.store.read_volume_needle(vid, key)
            return Response({"repaired": False, "present": True})
        except DeletedError:
            # our tombstone is authoritative — the reporter raced a
            # delete, which the delete fan-out/hints will settle
            return Response({"repaired": False, "present": True})
        except (NotFoundError, ValueError):
            pass
        n = self._pull_repair(vid, key)
        if n is None:
            return Response(
                {"error": "no peer could supply the needle"}, status=409)
        return Response({"repaired": True, "size": len(n.data)})

    def _admin_hints(self, req: Request) -> Response:
        j = self.hint_journal
        if j is None:
            return Response({"url": self.url, "enabled": False,
                             "pending": 0})
        return Response({"url": self.url, "enabled": True,
                         **j.stats(),
                         "hints": j.pending()[:100]})

    def _handle_status(self, req: Request) -> Response:
        hb = self.store.collect_heartbeat()
        extra = {}
        if self.tcp_server is not None:
            extra["TcpPort"] = self.tcp_server.port
        with self._lease_lock:
            extra["Leases"] = {"held": len(self._leases),
                               **self.lease_stats}
        extra["EcDevice"] = self._ec_device
        return Response({"Version": "seaweedfs-tpu 0.1", **extra, **hb})

    # ---- admin ----
    def _admin_allocate_volume(self, req: Request) -> Response:
        b = req.json()
        try:
            self.store.add_volume(b["volume_id"], b.get("collection", ""),
                                  b.get("replication", "000"),
                                  b.get("ttl", ""),
                                  disk_type=b.get("disk_type", ""))
        except ValueError as e:
            return Response({"error": str(e)}, status=400)
        return Response({})

    def _admin_delete_volume(self, req: Request) -> Response:
        b = req.json()
        ok = self.store.delete_volume(b["volume_id"])
        self._push_deltas()
        return Response({"deleted": ok})

    def _admin_mark_readonly(self, req: Request) -> Response:
        b = req.json()
        ok = self.store.mark_volume_readonly(b["volume_id"],
                                             b.get("read_only", True))
        return Response({"ok": ok})

    def _admin_mount_volume(self, req: Request) -> Response:
        """Attach a volume whose files are already on disk (reference
        volume_grpc_admin.go VolumeMount)."""
        ok = self.store.mount_volume(req.json()["volume_id"])
        self._push_deltas()
        return Response({"mounted": ok} if ok else
                        {"error": "volume files not found"},
                        status=200 if ok else 404)

    def _admin_unmount_volume(self, req: Request) -> Response:
        """Detach without deleting files (reference VolumeUnmount)."""
        ok = self.store.unmount_volume(req.json()["volume_id"])
        self._push_deltas()
        return Response({"unmounted": ok} if ok else
                        {"error": "volume not found"},
                        status=200 if ok else 404)

    def _admin_configure_replication(self, req: Request) -> Response:
        """Rewrite a volume's replica placement in its superblock
        (reference command_volume_configure_replication.go)."""
        b = req.json()
        v = self.store.find_volume(b["volume_id"])
        if v is None:
            return Response({"error": "volume not found"}, status=404)
        v.configure_replication(b["replication"])
        self.heartbeat_once()  # re-announce with the new placement
        return Response({"replication": b["replication"]})

    def _admin_volume_file_status(self, req: Request) -> Response:
        """HTTP twin of the ReadVolumeFileStatus gRPC: file sizes,
        mtimes, counts — what shell planners gate destructive ops on."""
        vid = int(req.query["volumeId"])
        v = self.store.find_volume(vid)
        if v is None:
            return Response({"error": "volume not found"}, status=404)
        v.sync()
        base = v.file_name()
        out = {"volume_id": vid, "collection": v.collection,
               "file_count": v.file_count(),
               "last_append_at_ns": v.last_append_at_ns}
        for ext, ts_key, size_key in (
                (".idx", "idx_file_timestamp_seconds", "idx_file_size"),
                (".dat", "dat_file_timestamp_seconds", "dat_file_size")):
            try:
                st = os.stat(base + ext)
                out[ts_key] = int(st.st_mtime)
                out[size_key] = st.st_size
            except OSError:
                pass
        return Response(out)

    def _admin_batch_delete(self, req: Request) -> Response:
        """HTTP twin of the BatchDelete gRPC (local deletes only; the
        caller addresses each replica — reference
        volume_grpc_batch_delete.go)."""
        from seaweedfs_tpu.storage.file_id import FileId
        b = req.json()
        skip = b.get("skip_cookie_check", False)
        results = []
        for fid in b.get("file_ids", []):
            r = {"file_id": fid, "status": 202, "error": "", "size": 0}
            try:
                f = FileId.parse(fid)
                r["size"] = self.store.delete_volume_needle(
                    f.volume_id, f.key, None if skip else f.cookie)
            except (ValueError, KeyError):
                r["status"], r["error"] = 400, "malformed file id"
            except (NotFoundError, DeletedError) as e:
                r["status"], r["error"] = 404, str(e) or "not found"
            except PermissionError as e:
                r["status"], r["error"] = 403, str(e)
            except Exception as e:
                r["status"], r["error"] = 500, f"{type(e).__name__}: {e}"
            results.append(r)
        return Response({"results": results})

    def _admin_leave(self, req: Request) -> Response:
        """Stop heartbeating and unregister from the master — graceful
        drain (reference shell command_volume_server_leave.go)."""
        self._stop.set()
        try:
            http_json("POST", f"http://{self.master_url}/dir/leave",
                      {"url": self.url})
        except (ConnectionError, HttpError) as e:
            return Response({"left": True, "master": str(e)})
        return Response({"left": True})

    def _admin_vacuum(self, req: Request) -> Response:
        b = req.json()
        v = self.store.find_volume(b["volume_id"])
        if v is None:
            return Response({"error": "volume not found"}, status=404)
        garbage = v.garbage_level()
        if b.get("check_only"):
            return Response({"garbage_ratio": garbage})
        cache = self.store.needle_cache
        if cache is not None:
            # vacuum rewrites offsets under the volume: strict drop,
            # before AND after compaction (same race shape as
            # Store.write_volume_needle's double invalidation)
            cache.invalidate_volume(v.id)
        try:
            v.compact()
        finally:
            if cache is not None:
                cache.invalidate_volume(v.id)
        return Response({"garbage_ratio": garbage, "compacted": True})

    def _admin_sync(self, req: Request) -> Response:
        b = req.json() or {}
        v = self.store.find_volume(b.get("volume_id", 0))
        if v:
            v.sync()
        return Response({})

    def _admin_move_volume_disk(self, req: Request) -> Response:
        """Intra-node tier move: relocate a volume's files to a
        location of another disk type (volume.tier.move on one
        server)."""
        b = req.json()
        try:
            ok = self.store.move_volume_disk(b["volume_id"],
                                             b.get("disk_type", ""))
        except ValueError as e:
            return Response({"error": str(e)}, status=400)
        if not ok:
            return Response({"error": "volume not found"}, status=404)
        self._push_deltas()
        return Response({"moved": b["volume_id"]})

    def _admin_copy_volume(self, req: Request) -> Response:
        """Pull a volume's .dat/.idx from a peer and load it
        (reference volume_grpc_copy.go VolumeCopy)."""
        b = req.json()
        vid = b["volume_id"]
        collection = b.get("collection", "")
        src = b["source_data_node"]
        if self.store.find_volume(vid) is not None:
            return Response({"error": f"volume {vid} already exists"},
                            status=409)
        # "" IS the hdd tier, same strictness as add_volume: an
        # untyped copy (balance/evacuate/fix.replication) must not
        # silently flip an hdd volume onto an ssd dir
        want = b.get("disk_type", "") or "hdd"
        candidates = [l for l in self.store.locations
                      if l.disk_type == want]
        if not candidates:
            return Response(
                {"error": f"no {want!r} disk on this server"}, status=400)
        loc = min(candidates, key=lambda l: l.volumes_len())
        name = f"{collection}_{vid}" if collection else str(vid)
        base = os.path.join(loc.directory, name)
        for ext in (".dat", ".idx"):
            url = (f"http://{src}/admin/volume_file?volumeId={vid}"
                   f"&ext={ext}&collection={collection}")
            status, body, hdrs = http_call("GET", url, timeout=300)
            if status >= 400:
                return Response({"error": f"copy {ext}: HTTP {status}"},
                                status=500)
            with open(base + ext, "wb") as f:
                f.write(body)
            # preserve the source's mtime: a replica copy must NOT
            # restart a TTL volume's expiry clock
            src_mtime = hdrs.get(weed_headers.FILE_MTIME)
            if src_mtime:
                os.utime(base + ext, (float(src_mtime),
                                      float(src_mtime)))
        from seaweedfs_tpu.storage.volume import Volume
        vol = Volume(loc.directory, collection, vid)
        loc.add_volume(vol)
        self.store.new_volumes.append(self.store.volume_info(vol))
        self._push_deltas()
        return Response({})

    def _tier_key(self, v) -> str:
        """Node-unique S3 object key for this replica's .dat: replicas
        of a volume compact independently and need not be
        byte-identical, so each node demotes to its own object — a
        shared key would let one replica's upload corrupt another's
        verified copy."""
        return (f"{self.url.replace(':', '_')}_"
                f"{os.path.basename(v.file_name())}.dat")

    def _admin_tier_upload(self, req: Request) -> Response:
        """Move a sealed volume's .dat to an S3-compatible tier
        (reference volume_grpc_tier_upload.go)."""
        b = req.json()
        v = self.store.find_volume(b["volume_id"])
        if v is None:
            return Response({"error": "volume not found"}, status=404)
        try:
            info = v.tier_to(b["endpoint"], b["bucket"],
                             keep_local=b.get("keep_local", False),
                             key=self._tier_key(v))
        except (ValueError, RuntimeError, IOError) as e:
            return Response({"error": str(e)}, status=409)
        return Response({"tiered": v.id, "remote": info.get("remote")})

    def _admin_tier_download(self, req: Request) -> Response:
        """Pull a tiered volume's .dat back to local disk
        (reference volume_grpc_tier_download.go)."""
        b = req.json()
        v = self.store.find_volume(b["volume_id"])
        if v is None:
            return Response({"error": "volume not found"}, status=404)
        try:
            v.untier()
        except (ValueError, RuntimeError, IOError) as e:
            return Response({"error": str(e)}, status=409)
        return Response({"downloaded": v.id})

    def _admin_tier_status(self, req: Request) -> Response:
        """Per-rung census + move counters for tier_profile and
        volume.tier.status."""
        report = self.tiering_report()
        rungs = collections.Counter(
            v["rung"] for v in report["volumes"].values())
        return Response({"url": self.url, "rungs": dict(rungs),
                         **report})

    def _admin_tier_demote(self, req: Request) -> Response:
        """One rung down, BACKGROUND-classed: the S3 upload + readback
        verify inside tier_to must never ride the interactive QoS lane
        (this scope also stamps X-Weed-Class on the outbound PUTs)."""
        b = req.json()
        vid = b["volume_id"]
        v = self.store.find_volume(vid)
        if v is None:
            return Response({"error": "volume not found"}, status=404)
        size = 0
        try:
            with class_scope(BACKGROUND):
                size = v.content_size() if not v.is_tiered else 0
                info = v.tier_to(b["endpoint"], b["bucket"],
                                 keep_local=b.get("keep_local", False),
                                 key=self._tier_key(v))
        except (ValueError, RuntimeError, IOError) as e:
            self.tier_stats["failed"] += 1
            return Response({"error": str(e)}, status=409)
        self.tier_stats["demotes"] += 1
        self.tier_stats["bytes_demoted"] += size
        self._push_deltas()
        return Response({"demoted": vid, "rung": "cloud",
                         "remote": info.get("remote")})

    def _admin_tier_promote(self, req: Request) -> Response:
        """One rung up, BACKGROUND-classed: fetch from the tier,
        verify size + chained crc32c against the .vif record, reopen
        local (the re-heat path)."""
        b = req.json()
        vid = b["volume_id"]
        v = self.store.find_volume(vid)
        if v is None:
            return Response({"error": "volume not found"}, status=404)
        try:
            with class_scope(BACKGROUND):
                v.untier()
        except (ValueError, RuntimeError, IOError) as e:
            self.tier_stats["failed"] += 1
            return Response({"error": str(e)}, status=409)
        self.tier_stats["promotes"] += 1
        self.tier_stats["bytes_promoted"] += v.content_size()
        self._push_deltas()
        return Response({"promoted": vid, "rung": "hot"})

    def _admin_volume_digest(self, req: Request) -> Response:
        """Live (key,size) inventory + digest of one volume replica, for
        volume.check.disk (reference command_volume_check_disk.go
        compares replicas' idx contents)."""
        import hashlib
        vid = int(req.query["volumeId"])
        v = self.store.find_volume(vid)
        if v is None:
            return Response({"error": "volume not found"}, status=404)
        entries = v.live_entries()
        h = hashlib.md5()
        for k, s in entries:
            h.update(k.to_bytes(8, "big") + s.to_bytes(4, "big", signed=True))
        return Response({"volume_id": vid, "file_count": len(entries),
                         "digest": h.hexdigest(),
                         "keys": [[k, s] for k, s in entries]})

    def _admin_needle(self, req: Request) -> Response:
        """Fetch one needle's full record fields by key — the transfer
        unit of volume.check.disk -fix (reference readSourceNeedleBlob)."""
        vid = int(req.query["volumeId"])
        key = int(req.query["key"])
        v = self.store.find_volume(vid)
        if v is None:
            return Response({"error": "volume not found"}, status=404)
        try:
            n = v.read_needle(key)
        except Exception as e:
            return Response({"error": str(e)}, status=404)
        return Response({"key": key, "cookie": n.cookie,
                         "data": n.data.hex(),
                         "name": n.name.decode(errors="replace"),
                         "mime": n.mime.decode(errors="replace")})

    def _admin_needle_blob(self, req: Request) -> Response:
        """Raw needle record for lossless replica repair."""
        vid = int(req.query["volumeId"])
        key = int(req.query["key"])
        v = self.store.find_volume(vid)
        if v is None:
            return Response({"error": "volume not found"}, status=404)
        try:
            blob, size = v.read_needle_blob(key)
        except Exception as e:
            return Response({"error": str(e)}, status=404)
        return Response({"size": size, "blob": blob.hex()})

    def _admin_write_needle_blob(self, req: Request) -> Response:
        b = req.json()
        v = self.store.find_volume(b["volume_id"])
        if v is None:
            return Response({"error": "volume not found"}, status=404)
        try:
            v.write_needle_blob(bytes.fromhex(b["blob"]), b["size"])
        except Exception as e:
            return Response({"error": str(e)}, status=409)
        if self.store.needle_cache is not None:
            # repair path lands raw records without surfacing the key:
            # whole-volume drop keeps the cache strictly consistent
            self.store.needle_cache.invalidate_volume(v.id)
        return Response({})

    def _admin_volume_file(self, req: Request) -> Response:
        vid = int(req.query["volumeId"])
        v = self.store.find_volume(vid)
        if v is None:
            return Response({"error": "volume not found"}, status=404)
        ext = req.query["ext"]
        if ext not in (".dat", ".idx"):
            return Response({"error": "bad ext"}, status=400)
        v.sync()
        path = v.file_name() + ext
        with open(path, "rb") as f:
            return Response(
                f.read(), content_type="application/octet-stream",
                headers={weed_headers.FILE_MTIME:
                         str(os.stat(path).st_mtime)})

    # ---- EC rpcs (reference volume_grpc_erasure_coding.go) ----
    def _ec_generate(self, req: Request) -> Response:
        b = req.json()
        # the pipeline's own account of the seal (read_s / encode_s /
        # write_s / commit_s busy seconds, wall_s, bytes_in, batches,
        # overlapped); empty for the serial path
        stats: dict = {}
        with tracing.stage("volume.ec.generate"):
            try:
                base = self.store.generate_ec_shards(
                    b["volume_id"], pipelined=b.get("pipelined", True),
                    stats=stats, code=b.get("code", ""))
            except CodeSpecError as e:
                # the caller's mistake, not the server's
                return Response({"error": str(e)}, status=400)
        return Response({"base": os.path.basename(base),
                         "pipeline": stats})

    def _ec_volume_coder(self, base: str) -> ErasureCoder:
        """The coder for the volume at `base`, per its .vif CodeSpec
        (store default when absent — legacy volumes are RS(10,4))."""
        from seaweedfs_tpu.storage.erasure_coding.ec_volume import \
            read_volume_info
        return self.store.coder_for_scheme(
            scheme_from_dict(read_volume_info(base).get("code")))

    def _ec_rebuild(self, req: Request) -> Response:
        b = req.json()
        vid = b["volume_id"]
        base = self._ec_base_name(vid, b.get("collection", ""))
        coder = self._ec_volume_coder(base)
        stats: dict = {}
        rebuilt = ecenc.rebuild_ec_files(base, coder,
                                         pipelined=b.get("pipelined", True),
                                         stats=stats)
        ecenc.rebuild_ecx_file(base)
        # shard_size lets the caller (the master's repair queue) account
        # the bytes this repair moved over the wire
        shard_size = 0
        for sid in rebuilt:
            p = base + layout.shard_ext(sid)
            if os.path.exists(p):
                shard_size = os.path.getsize(p)
                break
        sources = stats.get("sources") or []
        strategy = self._record_strategy(vid, coder, sources, "full")
        return Response({"rebuilt_shard_ids": rebuilt,
                         "shard_size": shard_size,
                         "read_bytes": stats.get(
                             "read_bytes", stats.get("bytes_in", 0)),
                         "sources": list(sources),
                         "strategy": strategy})

    def _record_strategy(self, vid: int, coder: ErasureCoder,
                         sources: list, mode: str) -> str:
        """Classify + remember the repair strategy a rebuild used:
        'local' when the planned source set is narrower than k (an LRC
        group repair), 'global' otherwise."""
        k = coder.scheme.data_shards
        plan_capable = hasattr(coder, "plan_rebuild")
        strategy = "local" if plan_capable and sources \
            and len(sources) < k else "global"
        self._ec_last_strategy[vid] = {
            "strategy": strategy, "sources": list(sources), "mode": mode}
        return strategy

    def _ec_base_name(self, vid: int, collection: str = "") -> str:
        # callers that only know the vid (ec.rebuild, the repair queue)
        # get the mounted volume's own stem, collection included
        ev = self.store.find_ec_volume(vid)
        if ev is not None:
            return ev.base_file_name
        for loc in self.store.locations:
            base = ec_base_file_name(loc.directory, collection, vid)
            if os.path.exists(base + ".ecx") or \
                    any(os.path.exists(base + layout.shard_ext(i))
                        for i in range(MAX_VOLUME_SHARDS)):
                return base
        return ec_base_file_name(self.store.locations[0].directory,
                                 collection, vid)

    def _ec_copy(self, req: Request) -> Response:
        """Pull shard files (+ .ecx/.ecj/.vif) from a source server
        (reference VolumeEcShardsCopy:117-168)."""
        b = req.json()
        vid = b["volume_id"]
        src = b["source_data_node"]
        base = self._ec_base_name(vid, b.get("collection", ""))
        exts = [layout.shard_ext(sid) for sid in b.get("shard_ids", [])]
        if b.get("copy_ecx_file", True):
            exts += [".ecx"]
        exts += [e for e in (".ecj", ".vif") if b.get("copy_aux", True)]
        copied = 0
        for ext in exts:
            url = (f"http://{src}/admin/ec/shard_file?volumeId={vid}"
                   f"&ext={ext}&collection={b.get('collection', '')}")
            # idempotent GET: jittered budget-gated retries ride out a
            # transient peer blip mid-repair instead of failing the
            # whole copy step
            status, body, _ = self.retry.call(
                lambda: http_call("GET", url, timeout=120), dest=src)
            if status == 404 and ext in (".ecj", ".vif"):
                continue
            if status >= 400:
                return Response({"error": f"copy {ext}: HTTP {status}"},
                                status=500)
            with open(base + ext, "wb") as f:
                f.write(body)
            copied += len(body)
        # bytes moved over the wire: the master's repair queue charges
        # this against the cluster-wide repair bandwidth budget
        return Response({"bytes": copied})

    def _ec_shard_file(self, req: Request) -> Response:
        vid = int(req.query["volumeId"])
        ext = req.query["ext"]
        base = self._ec_base_name(vid, req.query.get("collection", ""))
        path = base + ext
        if not os.path.exists(path):
            return Response({"error": "not found"}, status=404)
        with open(path, "rb") as f:
            return Response(f.read(), content_type="application/octet-stream")

    def _ec_mount(self, req: Request) -> Response:
        b = req.json()
        try:
            self.store.mount_ec_shards(b.get("collection", ""),
                                       b["volume_id"], b["shard_ids"])
        except NotFoundError as e:
            return Response({"error": str(e)}, status=404)
        finally:
            self._push_deltas()
        return Response({})

    def _ec_unmount(self, req: Request) -> Response:
        b = req.json()
        self.store.unmount_ec_shards(b["volume_id"], b["shard_ids"])
        self._push_deltas()
        return Response({})

    def _ec_delete_shards(self, req: Request) -> Response:
        b = req.json()
        vid = b["volume_id"]
        base = self._ec_base_name(vid, b.get("collection", ""))
        for sid in b["shard_ids"]:
            p = base + layout.shard_ext(sid)
            if os.path.exists(p):
                os.remove(p)
        # when all shards gone, remove index files too (reference
        # VolumeEcShardsDelete removes .ecx/.ecj when no shards remain)
        if not any(os.path.exists(base + layout.shard_ext(i))
                   for i in range(MAX_VOLUME_SHARDS)):
            for ext in (".ecx", ".ecj", ".vif"):
                if os.path.exists(base + ext):
                    os.remove(base + ext)
        return Response({})

    def _ec_to_volume(self, req: Request) -> Response:
        """VolumeEcShardsToVolume: shards -> normal .dat/.idx
        (reference :381-413)."""
        b = req.json()
        vid = b["volume_id"]
        collection = b.get("collection", "")
        base = self._ec_base_name(vid, collection)
        dat_size = ecdec.find_dat_file_size(base, base)
        ecdec.write_dat_file(base, dat_size,
                             pipelined=b.get("pipelined", True))
        ecdec.write_idx_file_from_ec_index(base)
        # unmount EC view, load as normal volume
        self.store.unmount_ec_shards(
            vid, list(range(MAX_VOLUME_SHARDS)))
        from seaweedfs_tpu.storage.volume import Volume
        loc = next(l for l in self.store.locations
                   if os.path.dirname(base) == l.directory)
        vol = Volume(loc.directory, collection, vid)
        loc.add_volume(vol)
        self.store.new_volumes.append(self.store.volume_info(vol))
        self._push_deltas()
        return Response({"dat_size": dat_size})

    def _ec_blob_delete(self, req: Request) -> Response:
        b = req.json()
        ev = self.store.find_ec_volume(b["volume_id"])
        if ev is None:
            return Response({"error": "ec volume not found"}, status=404)
        if self.store.needle_cache is not None:
            self.store.needle_cache.invalidate(
                b["volume_id"], b["needle_id"])
        try:
            ev.delete_needle(b["needle_id"])
        finally:
            if self.store.needle_cache is not None:
                self.store.needle_cache.invalidate(
                    b["volume_id"], b["needle_id"])
        return Response({})

    def _ec_shard_read(self, req: Request) -> Response:
        vid = int(req.query["volumeId"])
        sid = int(req.query["shardId"])
        offset = int(req.query["offset"])
        size = int(req.query["size"])
        ev = self.store.find_ec_volume(vid)
        if ev is None or sid not in ev.shards:
            return Response({"error": "shard not found"}, status=404)
        return Response(ev.shards[sid].read_at(offset, size),
                        content_type="application/octet-stream")

    def _ec_shard_stat(self, req: Request) -> Response:
        """Shard inventory + size for one EC volume — lets a partial
        rebuilder learn the shard width without streaming a shard."""
        vid = int(req.query["volumeId"])
        base = self._ec_base_name(vid, req.query.get("collection", ""))
        sizes = {}
        for i in range(MAX_VOLUME_SHARDS):
            p = base + layout.shard_ext(i)
            if os.path.exists(p):
                sizes[i] = os.path.getsize(p)
        if not sizes:
            return Response({"error": "no shards"}, status=404)
        from seaweedfs_tpu.storage.erasure_coding.ec_volume import \
            read_volume_info
        out = {"volume_id": vid, "shards": sorted(sizes),
               "shard_size": max(sizes.values()),
               "code": scheme_to_dict(scheme_from_dict(
                   read_volume_info(base).get("code"))),
               "recover_stats": dict(self.store.ec_recover_stats),
               "read_stats": dict(self.store.ec_read_stats)}
        last = self._ec_last_strategy.get(vid)
        if last:
            out["last_repair"] = last
        return Response(out)

    # ---- partial-column repair (storage/erasure_coding/partial.py) ----
    def _ec_partial_read(self, req: Request) -> Response:
        """One hop of a partial-column reduction chain: fold the local
        members' GF partial products, XOR in the accumulated column
        recursively requested from the rest of the chain, return ONE
        pre-reduced column upstream. A 409 means the plan is stale for
        this node (shard moved) — the caller falls back."""
        b = req.json()
        vid = int(b["volume_id"])
        offset = int(b["offset"])
        size = int(b["size"])
        n_rows = int(b.get("n_rows", 1))
        chain = b.get("chain") or []
        if not chain or size <= 0 or n_rows <= 0:
            return Response({"error": "bad partial plan"}, status=400)
        ev = self.store.find_ec_volume(vid)
        hop, rest = chain[0], chain[1:]
        rows, cols = [], []
        for sid, coeffs in hop["members"]:
            if len(coeffs) != n_rows:
                return Response({"error": "coeffs/n_rows mismatch"},
                                status=400)
            shard = ev.shards.get(int(sid)) if ev is not None else None
            if shard is None:
                return Response({"error": f"shard {sid} not local"},
                                status=409)
            data = shard.read_at(offset, size)
            if len(data) != size:
                return Response({"error": f"shard {sid} short read"},
                                status=409)
            rows.append(np.frombuffer(data, dtype=np.uint8))
            cols.append(np.asarray(coeffs, dtype=np.uint8))
        acc = np.zeros((n_rows, size), dtype=np.uint8)
        if rows:
            gf_partial_product(np.stack(cols, axis=1), np.stack(rows),
                               out=acc)
        shards_folded = len(rows)
        reasons: list[str] = []
        if rest:
            try:
                arr, dshards, _nbytes, dreasons = self._chain_partial(
                    vid, b.get("collection", ""), offset, size, n_rows,
                    rest)
            except RuntimeError as e:
                return Response({"error": str(e)}, status=502)
            acc ^= arr
            shards_folded += dshards
            reasons.extend(dreasons)
        headers = {ecpart.SHARDS_HEADER: str(shards_folded)}
        if reasons:
            headers[ecpart.FALLBACK_HEADER] = ",".join(reasons)
        self._m_req.inc("ec_partial_read")
        return Response(acc.tobytes(),
                        content_type="application/octet-stream",
                        headers=headers)

    def _chain_partial(self, vid: int, collection: str, offset: int,
                       size: int, n_rows: int, chain: list
                       ) -> tuple[np.ndarray, int, int, list]:
        """Request the accumulated partial column from a reduction
        chain. Breaker-screened; on any failure of the next hop, fall
        back to raw-streaming every remaining member's shard range and
        reducing HERE (ladder rung 1/2 in partial.py). Returns
        (array (n_rows, size), shards_folded, net_bytes_received,
        fallback_reasons); raises RuntimeError when some member shard
        is unobtainable by any means."""
        url = chain[0]["url"]
        expect = len(ecpart.chain_shard_ids(chain))
        if self.peer_health.allow(url):
            t0 = clockctl.monotonic()
            try:
                status, body, hdrs = http_call(
                    "POST", f"http://{url}{ecpart.PARTIAL_READ_PATH}",
                    json_body={"volume_id": vid, "collection": collection,
                               "offset": offset, "size": size,
                               "n_rows": n_rows, "chain": chain},
                    timeout=120)
                self.peer_health.record(url, True, clockctl.monotonic() - t0)
                if status == 200 and len(body) == n_rows * size:
                    arr = np.frombuffer(body, dtype=np.uint8) \
                        .reshape(n_rows, size).copy()
                    shards = int(hdrs.get(ecpart.SHARDS_HEADER, expect))
                    reasons = [r for r in
                               hdrs.get(ecpart.FALLBACK_HEADER,
                                        "").split(",") if r]
                    tracing.annotate("partial_read.net_bytes", len(body))
                    tracing.annotate("partial_read.shards", shards)
                    return arr, shards, len(body), reasons
            except (ConnectionError, OSError):
                self.peer_health.record(url, False)
        arr, shards, nbytes = self._raw_partial_fold(
            vid, offset, size, n_rows, chain)
        tracing.annotate("partial_read.net_bytes", nbytes)
        tracing.annotate("partial_read.fallback", f"chain:{url}")
        return arr, shards, nbytes, [f"chain:{url}"]

    def _raw_partial_fold(self, vid: int, offset: int, size: int,
                          n_rows: int, chain: list
                          ) -> tuple[np.ndarray, int, int]:
        """Full-shard-streaming fallback: fetch each remaining member's
        raw range (local file, planned holder, then any other holder)
        and fold the partial products locally."""
        acc = np.zeros((n_rows, size), dtype=np.uint8)
        shards = 0
        nbytes = 0
        ev = self.store.find_ec_volume(vid)
        for hop in chain:
            for sid, coeffs in hop["members"]:
                sid = int(sid)
                data = None
                local = ev.shards.get(sid) if ev is not None else None
                if local is not None:
                    data = local.read_at(offset, size)
                    if len(data) != size:
                        data = None
                if data is None:
                    data = self._fetch_shard_range(
                        vid, sid, offset, size, prefer=hop["url"])
                    if data is not None:
                        nbytes += len(data)
                if data is None:
                    raise RuntimeError(
                        f"shard {sid}: no reachable holder for "
                        "partial fold")
                gf_partial_product(
                    np.asarray(coeffs, dtype=np.uint8)[:, None],
                    np.frombuffer(data, dtype=np.uint8)[None, :],
                    out=acc)
                shards += 1
        return acc, shards, nbytes

    def _fetch_shard_range(self, vid: int, sid: int, offset: int,
                           size: int, prefer: str = "") -> Optional[bytes]:
        urls = [prefer] if prefer else []
        try:
            locs = self._shard_locations(vid)
        except (ConnectionError, HttpError):
            locs = {}
        rest = [u for u in locs.get(sid, []) if u not in urls]
        urls += self.peer_health.rank(
            rest, pressure=self._shard_pressure(vid))
        for u in urls:
            if not self.peer_health.allow(u) and len(urls) > 1:
                continue
            t0 = clockctl.monotonic()
            try:
                status, body, _ = http_call(
                    "GET",
                    f"http://{u}/admin/ec/shard_read"
                    f"?volumeId={vid}&shardId={sid}"
                    f"&offset={offset}&size={size}", timeout=60)
            except (ConnectionError, OSError):
                self.peer_health.record(u, False)
                continue
            self.peer_health.record(u, True, clockctl.monotonic() - t0)
            if status == 200 and len(body) == size:
                return body
        return None

    def _remote_partial_reader(self, vid: int, coeff_by_sid: dict,
                               offset: int, size: int,
                               n_rows: int) -> Optional[np.ndarray]:
        """Store hook for the scrubber: pull the XOR of remote shards'
        partial products as one pre-reduced column (remote-assisted
        parity recompute on spread deployments)."""
        try:
            locs = self._shard_locations(vid)
        except (ConnectionError, HttpError):
            return None
        chain = ecpart.plan_chain(locs, coeff_by_sid,
                                  health=self.peer_health,
                                  pressure=self._shard_pressure(vid))
        if not chain:
            return None
        try:
            with class_scope(BACKGROUND), \
                    deadline_scope(Deadline.after(60.0)):
                arr, shards, _n, _r = self._chain_partial(
                    vid, "", offset, size, n_rows, chain)
        except RuntimeError:
            return None
        if shards != len(coeff_by_sid):
            return None
        return arr

    def _ensure_ec_aux_files(self, vid: int, collection: str, base: str,
                             sources: dict) -> int:
        """Fetch .ecx (mandatory) and .ecj/.vif (best-effort) from any
        source holder when absent locally. Returns bytes copied."""
        urls: list[str] = []
        for us in sources.values():
            for u in us:
                if u not in urls:
                    urls.append(u)
        urls = self.peer_health.rank(urls,
                                     pressure=self._shard_pressure(vid))
        copied = 0
        for ext in (".ecx", ".ecj", ".vif"):
            if os.path.exists(base + ext):
                continue
            for u in urls:
                try:
                    status, body, _ = http_call(
                        "GET",
                        f"http://{u}/admin/ec/shard_file?volumeId={vid}"
                        f"&ext={ext}&collection={collection}", timeout=60)
                except (ConnectionError, OSError):
                    self.peer_health.record(u, False)
                    continue
                if status >= 400:
                    continue
                with open(base + ext, "wb") as f:
                    f.write(body)
                copied += len(body)
                break
        if not os.path.exists(base + ".ecx"):
            raise RuntimeError("no source holder could supply .ecx")
        return copied

    def _ec_rebuild_partial(self, req: Request) -> Response:
        """Network-frugal rebuild: reconstruct the missing shards from
        pre-reduced partial columns pulled through a reduction chain —
        ~1 shard-width received per lost shard instead of the k full
        shards the copy+rebuild choreography stages. Bit-identical to
        the serial rebuild (XOR folding is associative). The caller
        (master repair queue) falls back to /admin/ec/copy +
        /admin/ec/rebuild on any error here (ladder rung 3)."""
        b = req.json()
        vid = int(b["volume_id"])
        collection = b.get("collection", "")
        missing = sorted(int(s) for s in b.get("missing", []))
        sources = {int(s): [u for u in urls if not self._is_self(u)]
                   for s, urls in (b.get("sources") or {}).items()}
        sources = {s: u for s, u in sources.items() if u}
        batch = int(b.get("batch_size", 0)) or ecenc.DEFAULT_BATCH_SIZE
        if not missing:
            return Response({"error": "nothing to rebuild"}, status=400)
        base = self._ec_base_name(vid, collection)
        local = [i for i in range(MAX_VOLUME_SHARDS)
                 if os.path.exists(base + layout.shard_ext(i))]
        present = sorted((set(local) | set(sources)) - set(missing))
        received = 0
        # aux files first: the .vif names the volume's code family, and
        # the per-volume coder below plans the source set from it
        try:
            received += self._ensure_ec_aux_files(
                vid, collection, base, sources)
        except RuntimeError as e:
            return Response({"error": str(e)}, status=502)
        coder = self._ec_volume_coder(base)
        try:
            refuse_substriped(coder.scheme, "the partial-column chain")
        except SubstripedCodeError as e:
            # the caller falls back to copy + /admin/ec/rebuild, which
            # rebuilds the family from its half-row plan
            return Response({"error": str(e)}, status=409)
        k = coder.scheme.data_shards
        plan_capable = hasattr(coder, "plan_rebuild")
        # a plan-capable (LRC) coder can repair a group loss from fewer
        # than k survivors; only the generic path needs the k floor
        if not plan_capable and len(present) < k:
            return Response(
                {"error": f"only {len(present)} shards known, need {k}"},
                status=409)
        if not (plan_capable or hasattr(coder, "rebuild_matrix")):
            from seaweedfs_tpu.ops.rs_cpu import CpuCoder
            coder = CpuCoder(coder.scheme)
        try:
            src_sids, mat = ecenc.plan_rebuild_sources(
                coder, present, missing)
        except (ValueError, np.linalg.LinAlgError) as e:
            return Response(
                {"error": f"unrecoverable from {present}: {e}"},
                status=409)
        src_sids = list(src_sids)
        shard_size = 0
        for s in src_sids:
            if s in local:
                shard_size = os.path.getsize(base + layout.shard_ext(s))
                break
        if not shard_size:
            shard_size = self._remote_shard_stat(vid, collection, sources)
        if not shard_size:
            return Response({"error": "cannot determine shard size"},
                            status=409)
        workers = int(getattr(self.store.coder, "workers", 1) or 1)
        miss_n = len(missing)
        fallbacks: list[str] = []
        # warm the holder-pressure map once (best-effort: a dead master
        # must not fail a rebuild whose sources came with the request) —
        # chain planning below tie-breaks equally-healthy holders by it
        try:
            self._shard_locations(vid)
        except (ConnectionError, HttpError):
            pass
        pressure = self._shard_pressure(vid)
        local_fhs = {s: open(base + layout.shard_ext(s), "rb")
                     for s in src_sids if s in local}
        remote_src = [s for s in src_sids if s not in local_fhs]
        outs = {m: open(base + layout.shard_ext(m) + ".tmp", "wb")
                for m in missing}
        try:
            for off in range(0, shard_size, batch):
                sz = min(batch, shard_size - off)
                acc = np.zeros((miss_n, sz), dtype=np.uint8)
                if local_fhs:
                    rows, cols = [], []
                    for j, s in enumerate(src_sids):
                        fh = local_fhs.get(s)
                        if fh is None:
                            continue
                        fh.seek(off)
                        buf = fh.read(sz)
                        if len(buf) != sz:
                            raise RuntimeError(
                                f"short local read shard {s}")
                        rows.append(np.frombuffer(buf, dtype=np.uint8))
                        cols.append(mat[:, j])
                    gf_partial_product(np.stack(cols, axis=1),
                                       np.stack(rows), out=acc,
                                       workers=workers)
                if remote_src:
                    coeff_by_sid = {
                        s: mat[:, src_sids.index(s)].tolist()
                        for s in remote_src}
                    chain = ecpart.plan_chain(
                        sources, coeff_by_sid, health=self.peer_health,
                        pressure=pressure)
                    if chain is None:
                        raise RuntimeError(
                            "no holder for some source shard")
                    arr, shards, nbytes, reasons = self._chain_partial(
                        vid, collection, off, sz, miss_n, chain)
                    if shards != len(remote_src):
                        raise RuntimeError(
                            f"chain folded {shards} shards, "
                            f"expected {len(remote_src)}")
                    received += nbytes
                    fallbacks.extend(reasons)
                    acc ^= arr
                for r, m in enumerate(missing):
                    outs[m].write(acc[r].tobytes())
        except Exception as e:
            for fh in outs.values():
                fh.close()
            for m in missing:
                p = base + layout.shard_ext(m) + ".tmp"
                if os.path.exists(p):
                    os.remove(p)
            return Response({"error": f"partial rebuild: {e}"},
                            status=502)
        finally:
            for fh in local_fhs.values():
                fh.close()
            for fh in outs.values():
                try:
                    fh.close()
                except OSError:
                    pass
        for m in missing:
            os.replace(base + layout.shard_ext(m) + ".tmp",
                       base + layout.shard_ext(m))
        ecenc.rebuild_ecx_file(base)
        self._m_req.inc("ec_rebuild_partial")
        mb = shard_size * miss_n / (1024.0 * 1024.0)
        mode = "partial+fallback" if fallbacks else "partial"
        strategy = self._record_strategy(vid, coder, src_sids, mode)
        return Response({
            "rebuilt_shard_ids": missing, "shard_size": shard_size,
            "network_bytes": received,
            "repair_network_bytes_per_mb":
                round(received / mb, 1) if mb else 0.0,
            "fallbacks": fallbacks,
            "strategy": strategy,
            "sources": src_sids,
            "code": scheme_to_dict(coder.scheme).get("family", "rs"),
            "mode": mode})

    def _remote_shard_stat(self, vid: int, collection: str,
                           sources: dict) -> int:
        urls: list[str] = []
        for us in sources.values():
            for u in us:
                if u not in urls:
                    urls.append(u)
        for u in self.peer_health.rank(
                urls, pressure=self._shard_pressure(vid)):
            try:
                resp = http_json(
                    "GET",
                    f"http://{u}/admin/ec/shard_stat?volumeId={vid}"
                    f"&collection={collection}", timeout=10)
            except (ConnectionError, HttpError, OSError):
                continue
            ss = int(resp.get("shard_size", 0))
            if ss > 0:
                return ss
        return 0

    # ---- EC client-side helpers ----
    SHARD_LOC_TTL = 5.0  # matches the replica-lookup cache tier

    def _shard_locations(self, vid: int) -> dict:
        """{shard_id: [peer urls]} for an EC volume via the master's
        /dir/lookup_ec, self excluded, behind a short-TTL cache — a
        degraded read touches up to k+ shards and must not pay one
        master round-trip per column. The same lookup carries each
        holder's heartbeat-reported qos_pressure; _shard_pressure()
        serves it from the same cache entry so chain planning can
        tie-break away from loaded holders for free."""
        now = clockctl.monotonic()
        cached = self._shard_loc_cache.get(vid)
        if cached is not None and cached[0] > now:
            return cached[1]
        info = self._master_json("GET", f"/dir/lookup_ec?volumeId={vid}",
                                 deadline=Deadline.after(5.0))
        locs: dict[int, list[str]] = {}
        pressure: dict[str, float] = {}
        for entry in info.get("shards", []):
            urls = []
            for l in entry["locations"]:
                if self._is_self(l["url"]):
                    continue
                urls.append(l["url"])
                pressure[l["url"]] = float(l.get("qos_pressure", 0.0))
            if urls:
                locs[entry["shard_id"]] = urls
        self._shard_loc_cache[vid] = (now + self.SHARD_LOC_TTL, locs,
                                      pressure)
        return locs

    def _shard_pressure(self, vid: int) -> dict:
        """{url: qos_pressure} from the cached lookup (empty when the
        cache is cold — callers treat missing as unloaded)."""
        cached = self._shard_loc_cache.get(vid)
        if cached is not None and len(cached) > 2:
            return cached[2]
        return {}

    def _remote_shard_reader(self, vid: int, shard_id: int, offset: int,
                             size: int) -> Optional[bytes]:
        """Find the shard's holders via the master and fetch the range
        (reference store_ec.go readRemoteEcShardInterval:270).
        Resilient mode fans out HEDGED across holders ranked by breaker
        health — a backup request fires after the primary's observed
        p95 and the first success wins; legacy mode walks the holders
        serially in lookup order (the bench comparator)."""
        try:
            locs = self._shard_locations(vid)
        except (ConnectionError, HttpError):
            return None
        urls = locs.get(shard_id) or []
        if not urls:
            return None

        def fetch(url: str) -> Optional[bytes]:
            status, body, _ = http_call(
                "GET",
                f"http://{url}/admin/ec/shard_read"
                f"?volumeId={vid}&shardId={shard_id}"
                f"&offset={offset}&size={size}", timeout=30)
            if status == 200 and len(body) == size:
                return body
            return None

        if not self.resilient_reads:
            for url in urls:
                try:
                    out = fetch(url)
                except ConnectionError:
                    continue
                if out is not None:
                    return out
            return None
        # cap this direct fetch under the edge budget: a blackholed
        # holder must leave room for the degraded-reconstruction
        # fallback that runs after we give up here
        from seaweedfs_tpu.utils.resilience import current_deadline
        dl = current_deadline()
        sub = dl.sub(max(0.5, 0.4 * dl.remaining())) \
            if dl is not None else None
        return hedged(fetch,
                      self.peer_health.rank(
                          urls, pressure=self._shard_pressure(vid)),
                      health=self.peer_health, deadline=sub)

    def _ec_delete_fanout(self, vid: int, key: int, cookie: int) -> int:
        """Cookie-check locally then fan the tombstone to every shard
        owner (reference store_ec_delete.go:16-110)."""
        n = self.store.read_ec_shard_needle(vid, key, cookie)
        size = len(n.data)
        try:
            info = self._master_json(
                "GET", f"/dir/lookup_ec?volumeId={vid}",
                deadline=Deadline.after(5.0))
        except (ConnectionError, HttpError):
            info = {"shards": []}
        done = set()
        ev = self.store.find_ec_volume(vid)
        if ev is not None:
            if self.store.needle_cache is not None:
                self.store.needle_cache.invalidate(vid, key)
            ev.delete_needle(key)
            if self.store.needle_cache is not None:
                self.store.needle_cache.invalidate(vid, key)
            done.add(self.url)
            done.add(f"{self.http.host}:{self.http.port}")
        for entry in info.get("shards", []):
            for loc in entry["locations"]:
                if loc["url"] in done or self._is_self(loc["url"]):
                    continue
                done.add(loc["url"])
                t0 = clockctl.monotonic()
                try:
                    http_json("POST",
                              f"http://{loc['url']}/admin/ec/blob_delete",
                              {"volume_id": vid, "needle_id": key},
                              deadline=Deadline.after(10.0))
                    self.peer_health.record(loc["url"], True,
                                            clockctl.monotonic() - t0)
                except ConnectionError:
                    self.peer_health.record(loc["url"], False)
                except HttpError:
                    pass
        return size
