"""Store: a volume server's set of disk locations + the EC read path.

Functional equivalent of reference weed/storage/store.go:43-61 and
store_ec.go. The EC needle read walks intervals; each interval is served
from a local shard, else via the injected remote reader, else degraded-
reconstructed from >= k other shards through the ErasureCoder — the
TPU-backed coder slots in here (reference store_ec.go:125-163,328-382).
"""

from __future__ import annotations

import os
import threading
from typing import Callable, Optional

import numpy as np

from seaweedfs_tpu.models.coder import (DEFAULT_SCHEME, MAX_VOLUME_SHARDS,
                                        ClayScheme, ErasureCoder,
                                        code_spec_name, make_coder,
                                        parse_code_spec)
from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.disk_location import DiskLocation
from seaweedfs_tpu.storage.erasure_coding import layout
from seaweedfs_tpu.storage.erasure_coding.ec_volume import (
    EcVolume, read_runs_into, read_shards_into)
from seaweedfs_tpu.storage import needle
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.super_block import ReplicaPlacement, TTL
from seaweedfs_tpu.storage.volume import (CookieMismatchError, DeletedError,
                                          NotFoundError, Volume)
from seaweedfs_tpu.utils import tracing

# remote_shard_reader(vid, shard_id, offset, size) -> bytes | None
RemoteShardReader = Callable[[int, int, int, int], Optional[bytes]]


def _code_key(scheme) -> dict:
    """What a heartbeat's EC entry says of the code beyond its counts: a
    piggybacked volume's spec (its counts are RS(k, r)'s)."""
    return {"code": code_spec_name(scheme)} if scheme.substripes > 1 else {}


class Store:
    def __init__(self, directories: list[str],
                 max_volume_counts: Optional[list[int]] = None,
                 ip: str = "localhost", port: int = 8080,
                 public_url: str = "", rack: str = "", data_center: str = "",
                 coder: Optional[ErasureCoder] = None,
                 needle_map_kind: str = "memory",
                 disk_types: Optional[list[str]] = None,
                 fsync: bool = False):
        self.ip = ip
        self.needle_map_kind = needle_map_kind
        # fsync per commit batch on every volume (reference -fsync);
        # group commit in volume.py amortizes it across writers
        self.fsync = fsync
        self.port = port
        self.public_url = public_url or f"{ip}:{port}"
        self.rack = rack
        self.data_center = data_center
        # per-dir disk type (reference -disk flag, one entry per -dir;
        # short lists pad with the last value, default hdd)
        types = list(disk_types or ["hdd"])
        types += [types[-1]] * (len(directories) - len(types))
        self.locations = [
            DiskLocation(d, (max_volume_counts or [8] * len(directories))[i],
                         disk_type=types[i] or "hdd",
                         needle_map_kind=needle_map_kind, fsync=fsync)
            for i, d in enumerate(directories)]
        # multi-core CPU coder by default: bit-identical to "cpu",
        # shards each encode batch across the visible cores
        self.coder = coder or make_coder("cpu-mt")
        # per-CodeSpec coder cache for mixed-code stores: RS and LRC
        # volumes on the same disks each decode with their own family
        self._coder_cache: dict = {self.coder.scheme: self.coder}
        self.remote_shard_reader: Optional[RemoteShardReader] = None
        # Injected by the volume server (optional): per-peer breaker
        # registry, a vid -> {shard_id: [urls]} locator, and the switch
        # that turns on health-ranked + straggler-hedged recovery.
        # Without them the degraded path keeps its original
        # fan-out-everything behavior (tests inject bare readers).
        self.peer_health = None
        self.shard_locations: Optional[Callable[[int], dict]] = None
        # shard_pressure(vid) -> {url: pressure 0..1}: peers' advertised
        # QoS backlog, folded into holder ranking as a tiebreak between
        # similarly-healthy candidates (injected by the volume server)
        self.shard_pressure: Optional[Callable[[int], dict]] = None
        self.resilient_reads = True
        # remote_partial_reader(vid, {sid: [coeffs]}, offset, size,
        # n_rows) -> (n_rows, size) uint8 array | None. Injected by the
        # volume server; lets the scrubber check parity on volumes whose
        # data shards are spread across peers by pulling pre-reduced
        # partial columns instead of k raw shard streams.
        self.remote_partial_reader = None
        # Hot-needle record cache (storage/needle_cache.py), injected
        # by the volume server; None keeps every read on the raw path.
        self.needle_cache = None
        self._lock = threading.RLock()
        # delta channels to master (drained by the heartbeat loop)
        self.new_volumes: list[dict] = []
        self.deleted_volumes: list[dict] = []
        self.new_ec_shards: list[dict] = []
        self.deleted_ec_shards: list[dict] = []
        # degraded-read repair-strategy tallies (exposed via shard_stat):
        # "local" = planned group-local recovery, "global" = planned
        # full-width recovery, "generic" = unplanned collect-k fallback;
        # and, once the store serves a vector-coded volume (coder_for_
        # scheme), its family's strategies: "piggyback" / "substripe"
        # (Hitchhiker-XOR), "repair_planes" / "planes_partial" /
        # "layered" (Clay) (_recover_substriped).
        # Intervals each: the values sum to the intervals rebuilt
        self.ec_recover_stats = {"local": 0, "global": 0, "generic": 0}
        # what the EC read path read and waited for (shard_stat's
        # "read_stats"; ec_recover_stats keeps its three keys): intervals
        # served by a local pread / rebuilt, the survivor columns a
        # rebuild read and their bytes, seconds spent in recovery; the
        # needle lookups answered from the index in memory and those
        # that searched the .ecx file (counted by the mounted EcVolumes);
        # the rebuilt intervals whose survivors were read straight into
        # the rows of the job's operand (_gather_survivors); the whole
        # records the needle cache's loader read (_load_ec_record), their
        # bytes and the intervals they were joined from, and the bytes
        # of all rebuilt intervals (so record_intervals / records_loaded
        # is how many blocks a needle spans, recovered_bytes /
        # intervals_recovered how wide a rebuild job is).
        # Bumped by request threads without a lock, like the tallies
        # above: a lost add under contention is tolerated
        self.ec_read_stats = {"intervals_local": 0,
                              "intervals_recovered": 0,
                              "survivor_reads": 0, "survivor_bytes": 0,
                              "recover_s": 0.0,
                              "ecx_lookups": 0, "ecx_file_searches": 0,
                              "survivor_gathers": 0,
                              "records_loaded": 0, "record_bytes": 0,
                              "record_intervals": 0,
                              "recovered_bytes": 0}
        for loc in self.locations:
            loc.ec_read_stats = self.ec_read_stats
        # the one coefficient row a degraded read of plain RS asks for,
        # per (scheme, the k shards read, the shard wanted)
        self._rebuild_rows: dict[tuple, np.ndarray] = {}
        # a planning family's (LRC) repair of one shard, per (scheme, the
        # shard wanted, the shards it may read): (sources, their one
        # coefficient row, "local" / "global"), or None where the
        # pattern cannot be decoded (_repair_plan)
        self._rebuild_plans: dict[tuple, Optional[tuple]] = {}

    def load_existing_volumes(self) -> None:
        for loc in self.locations:
            loc.load_existing_volumes()

    # ---- normal volumes ----
    def add_volume(self, vid: int, collection: str = "",
                   replica_placement: str = "000", ttl: str = "",
                   disk_type: str = "") -> Volume:
        with self._lock:
            if self.find_volume(vid) is not None:
                raise ValueError(f"volume {vid} already exists")
            # "" IS the hdd tier (reference types.DiskType): an untyped
            # allocation must not consume an ssd slot
            want = disk_type or "hdd"
            candidates = [l for l in self.locations
                          if l.disk_type == want]
            if not candidates:
                raise ValueError(
                    f"no {want!r} disk on this server (have "
                    f"{sorted({l.disk_type for l in self.locations})})")
            loc = min(candidates, key=lambda l: l.volumes_len())
            vol = Volume(loc.directory, collection, vid,
                         ReplicaPlacement.parse(replica_placement),
                         TTL.parse(ttl),
                         needle_map_kind=self.needle_map_kind,
                         fsync=self.fsync)
            loc.add_volume(vol)
            self.new_volumes.append(self.volume_info(vol))
            return vol

    def find_volume(self, vid: int) -> Optional[Volume]:
        for loc in self.locations:
            v = loc.find_volume(vid)
            if v is not None:
                return v
        return None

    def delete_volume(self, vid: int) -> bool:
        with self._lock:
            for loc in self.locations:
                v = loc.find_volume(vid)
                if v is not None:
                    info = self.volume_info(v)
                    loc.delete_volume(vid)
                    self.deleted_volumes.append(info)
                    if self.needle_cache is not None:
                        self.needle_cache.invalidate_volume(vid)
                    return True
            return False

    def unmount_volume(self, vid: int) -> bool:
        """Detach a volume WITHOUT deleting its files (reference
        volume_grpc_admin.go VolumeUnmount) — the .dat/.idx stay on disk
        for a later mount or an off-node move."""
        with self._lock:
            for loc in self.locations:
                v = loc.find_volume(vid)
                if v is not None:
                    info = self.volume_info(v)
                    v.close()
                    with loc._lock:
                        loc.volumes.pop(vid, None)
                    self.deleted_volumes.append(info)  # delta: gone here
                    if self.needle_cache is not None:
                        self.needle_cache.invalidate_volume(vid)
                    return True
            return False

    def mount_volume(self, vid: int) -> bool:
        """(Re)attach a volume whose files already sit in a location's
        directory (reference VolumeMount). Uses the same filename
        grammar and .idx requirement as the startup scan."""
        from seaweedfs_tpu.storage.disk_location import _DAT_RE
        with self._lock:
            if self.find_volume(vid) is not None:
                return True
            for loc in self.locations:
                for name in os.listdir(loc.directory):
                    m = _DAT_RE.match(name)
                    if not m or int(m.group("vid")) != vid:
                        continue
                    col = m.group("col") or ""
                    base = os.path.join(loc.directory,
                                        f"{col}_{vid}" if col else str(vid))
                    if not os.path.exists(base + ".idx"):
                        continue
                    vol = Volume(loc.directory, col, vid,
                                 needle_map_kind=self.needle_map_kind,
                                 fsync=self.fsync)
                    loc.add_volume(vol)
                    self.new_volumes.append(self.volume_info(vol))
                    return True
            return False

    def delete_expired_ttl_volumes(self) -> list[int]:
        """Drop TTL volumes whose newest write is older than ttl+grace
        (reference topology_event_handling / volume_checking: TTL
        volumes are removed whole, not needle-by-needle)."""
        with self._lock:
            doomed = [v.id for loc in self.locations
                      for v in list(loc.volumes.values())
                      if v.is_expired_long_enough()
                      and not v.is_compacting]
        reaped = []
        for vid in doomed:
            with self._lock:
                v = self.find_volume(vid)
                # re-check at the moment of deletion: a write acked
                # between the scan and here resets the clock, and a
                # vacuum may have started — never destroy either
                if v is None or v.is_compacting \
                        or not v.is_expired_long_enough():
                    continue
            if self.delete_volume(vid):
                reaped.append(vid)
        return reaped

    def move_volume_disk(self, vid: int, disk_type: str) -> bool:
        """Move a volume's files to a location of another disk type on
        THIS server (intra-node half of volume.tier.move; the
        cross-node half is copy+delete). No-op when already there."""
        want = disk_type or "hdd"
        with self._lock:
            src_loc = None
            for loc in self.locations:
                if vid in loc.volumes:
                    src_loc = loc
                    break
            if src_loc is None:
                return False
            if src_loc.disk_type == want:
                return True
            candidates = [l for l in self.locations
                          if l.disk_type == want]
            if not candidates:
                raise ValueError(f"no {want!r} disk on this server")
            dst_loc = min(candidates, key=lambda l: l.volumes_len())
            v = src_loc.volumes[vid]
            old_info = self.volume_info(v)
            collection = v.collection
            v.close()
            with src_loc._lock:
                src_loc.volumes.pop(vid, None)
            name = (f"{collection}_{vid}" if collection else str(vid))
            for fname in sorted(os.listdir(src_loc.directory)):
                base, dot, _ext = fname.partition(".")
                if dot and base == name:
                    os.rename(os.path.join(src_loc.directory, fname),
                              os.path.join(dst_loc.directory, fname))
            vol = Volume(dst_loc.directory, collection, vid,
                         needle_map_kind=self.needle_map_kind,
                         fsync=self.fsync)
            dst_loc.add_volume(vol)
            # delta: the volume's disk_type changed
            self.deleted_volumes.append(old_info)
            self.new_volumes.append(self.volume_info(vol))
            return True

    def write_volume_needle(self, vid: int, n: Needle) -> int:
        v = self.find_volume(vid)
        if v is None:
            raise NotFoundError(f"volume {vid} not found")
        if self.needle_cache is not None:
            # overwrite: invalidate BEFORE (no cache hit serves the old
            # generation while the write is landing) and again AFTER
            # (a load that read the old bytes off disk mid-write holds
            # a stale epoch and cannot be admitted)
            self.needle_cache.invalidate(vid, n.id)
        try:
            return v.write_needle(n)
        finally:
            if self.needle_cache is not None:
                self.needle_cache.invalidate(vid, n.id)

    def read_volume_needle(self, vid: int, needle_id: int,
                           cookie: Optional[int] = None) -> Needle:
        v = self.find_volume(vid)
        if v is None:
            raise NotFoundError(f"volume {vid} not found")
        if v.is_expired():
            # past-TTL data is gone to readers even before the removal
            # grace deletes the files (reference store read path)
            raise NotFoundError(f"volume {vid} expired")
        cache = self.needle_cache
        if cache is None:
            return v.read_needle(needle_id, cookie)

        def load():
            blob, size = v.read_needle_blob(needle_id)
            # CRC verified ONCE at admission, over memoryview windows
            # (no payload copy); hits below skip the re-check
            needle.verify_record_crc(blob, size, v.version)
            return blob, size, v.version, False

        blob, size, version = cache.get_or_load(vid, needle_id, load)
        # re-parse per hit (handler-side mutation of n.data — gzip
        # decompress, resize — can't touch the cache) but WITHOUT the
        # per-hit CRC walk: the blob was verified at admission
        n = Needle.from_bytes(blob, size, version, check_crc=False)
        n.checksum = needle.payload_crc_stored(blob, size)
        if cookie is not None and n.cookie != cookie:
            raise CookieMismatchError(
                f"cookie mismatch for needle {needle_id:x}")
        return n

    def read_volume_needle_descriptor(self, vid: int, needle_id: int,
                                      cookie: Optional[int] = None):
        """Zero-copy read plane: ``(needle_meta, fd, payload_offset,
        data_size)`` for the volume server to sendfile, or None when
        the read belongs on the buffered ladder — volume missing or
        expired (caller re-drives the buffered path for its richer
        repair/404 handling), needle cached (served from memory), or
        the volume refuses (tiered/v1). NotFound/Deleted/Cookie errors
        are NOT raised here: they return None so the buffered path
        stays the single authority on read-repair and error shape."""
        v = self.find_volume(vid)
        if v is None or v.is_expired():
            return None
        cache = self.needle_cache
        if cache is not None and cache.contains(vid, needle_id):
            return None  # memory beats disk: cache path serves it
        try:
            return v.read_needle_descriptor(needle_id, cookie)
        except (NotFoundError, DeletedError, CookieMismatchError):
            return None

    def delete_volume_needle(self, vid: int, needle_id: int,
                             cookie: Optional[int] = None) -> int:
        v = self.find_volume(vid)
        if v is None:
            raise NotFoundError(f"volume {vid} not found")
        if self.needle_cache is not None:
            self.needle_cache.invalidate(vid, needle_id)
        try:
            return v.delete_needle(needle_id, cookie)
        finally:
            if self.needle_cache is not None:
                self.needle_cache.invalidate(vid, needle_id)

    def mark_volume_readonly(self, vid: int, read_only: bool = True) -> bool:
        v = self.find_volume(vid)
        if v is None:
            return False
        v.read_only = read_only
        return True

    # ---- EC shards ----
    def mount_ec_shards(self, collection: str, vid: int,
                        shard_ids: list[int]) -> None:
        """Mount shard files from whichever location holds each; a shard
        no location has a file for raises NotFoundError (after mounting
        the ones that were found) — the caller asked for it to serve."""
        missing = []
        for sid in shard_ids:
            for loc in self.locations:
                try:
                    if loc.load_ec_shard(collection, vid, sid):
                        ev = loc.find_ec_volume(vid)
                        self.new_ec_shards.append(
                            {"id": vid, "collection": ev.collection,
                             "ec_index_bits": 1 << sid,
                             "data_shards": ev.data_shards,
                             "total_shards": ev.total_shards,
                             **_code_key(ev.scheme)})
                    break
                except FileNotFoundError:
                    continue
            else:
                missing.append(sid)
        if missing:
            raise NotFoundError(
                f"ec volume {vid} (collection {collection!r}): no shard "
                f"file for shard(s) {missing} in any location")

    def coder_for(self, ev: EcVolume) -> ErasureCoder:
        """The coder matching a volume's persisted CodeSpec — self.coder
        for plain RS volumes, a cached family-specific coder otherwise.
        This is the per-volume dispatch that lets RS and LRC volumes
        coexist on one store."""
        return self.coder_for_scheme(getattr(ev, "scheme", None))

    def coder_for_scheme(self, scheme) -> ErasureCoder:
        """One coder per scheme among the store's volumes: the store's
        own coder says which (ErasureCoder.for_scheme), the cache keeps
        it the same object from then on."""
        if scheme is None:
            return self.coder
        c = self._coder_cache.get(scheme)
        if c is None:
            c = self._coder_cache[scheme] = self.coder.for_scheme(scheme)
            for key in getattr(scheme, "strategies", ()):
                self.ec_recover_stats.setdefault(key, 0)
        return c

    def generate_ec_shards(self, vid: int, pipelined: bool = True,
                           stats: Optional[dict] = None,
                           code: str = "") -> str:
        """VolumeEcShardsGenerate equivalent: write one .ecNN file per
        shard of the code (.ec00-.ec13 for RS(10,4), .ec00-.ec08 for
        RS(6,3), .ec00-.ec15 for LRC(12,2,2)) + .ecx + .vif next to the
        volume's files (reference
        server/volume_grpc_erasure_coding.go:38-81). Returns the base file
        name. The volume must exist locally; it is marked readonly first.
        `code` is a code spec (models/coder.parse_code_spec: '' / 'rs' ->
        the store coder's scheme, 'rs-<k>-<m>', 'lrc' -> LRC(10,2,2),
        'lrc-<k>-<l>-<g>', 'hhxor-<k>-<m>'; an unknown one raises
        CodeSpecError); the
        chosen CodeSpec is persisted
        in the .vif, and everything later reads it from there."""
        from seaweedfs_tpu.storage.erasure_coding import encoder as ecenc
        from seaweedfs_tpu.storage.erasure_coding.ec_volume import \
            write_volume_info
        scheme = parse_code_spec(code, self.coder.scheme)
        v = self.find_volume(vid)
        if v is None:
            raise NotFoundError(f"volume {vid} not found")
        coder = self.coder_for_scheme(scheme)
        with tracing.stage("store.ec.generate"):
            v.read_only = True
            with tracing.stage("store.ec.generate.sync"):
                v.sync()
            base = v.file_name()
            with tracing.stage("store.ec.generate.ecx"):
                ecenc.write_sorted_ecx(base)
            ecenc.write_ec_files(base, coder, pipelined=pipelined,
                                 stats=stats)
            with tracing.stage("store.ec.generate.vif"):
                write_volume_info(base, v.version, coder.scheme)
        return base

    def unmount_ec_shards(self, vid: int, shard_ids: list[int]) -> None:
        for sid in shard_ids:
            for loc in self.locations:
                if loc.unload_ec_shard(vid, sid):
                    self.deleted_ec_shards.append(
                        {"id": vid, "ec_index_bits": 1 << sid})
                    break
        if self.needle_cache is not None:
            # shard topology changed under the volume; cached records
            # themselves are still valid bytes, but ec-to-volume
            # conversion reuses the vid — stay strict
            self.needle_cache.invalidate_volume(vid)

    def find_ec_volume(self, vid: int) -> Optional[EcVolume]:
        for loc in self.locations:
            ev = loc.find_ec_volume(vid)
            if ev is not None:
                return ev
        return None

    def has_ec_volume(self, vid: int) -> bool:
        return self.find_ec_volume(vid) is not None

    def read_ec_shard_needle(self, vid: int, needle_id: int,
                             cookie: Optional[int] = None) -> Needle:
        """Locate via .ecx, then read intervals with local -> remote ->
        degraded-reconstruction fallback (reference store_ec.go:125-163).
        With a needle cache wired, the full record blob is read through
        it single-flight, so a hot degraded needle pays its k-column
        decode once and serves every later (and concurrent) reader from
        memory."""
        ev = self.find_ec_volume(vid)
        if ev is None:
            raise NotFoundError(f"ec volume {vid} not found")
        cache = self.needle_cache
        if cache is None:
            with tracing.stage("store.ec.locate"):
                intervals, offset, size = ev.locate_needle(needle_id)
            if t.size_is_deleted(size):
                raise DeletedError(f"needle {needle_id:x} deleted")
            blob = b"".join(
                self._read_one_interval(ev, iv) for iv in intervals)
            n = Needle.from_bytes(blob, size, ev.version)
        else:
            # contains the loader's stages on a miss; its own time is the
            # lookup and the wait for another reader's flight
            with tracing.stage("store.ec.cache"):
                blob, size, version = cache.get_or_load(
                    vid, needle_id,
                    lambda: self._load_ec_record(ev, needle_id))
            # admission verified the blob's CRC; hits skip the re-walk
            n = Needle.from_bytes(blob, size, version, check_crc=False)
            n.checksum = needle.payload_crc_stored(blob, size)
        if cookie is not None and n.cookie != cookie:
            raise NotFoundError(f"cookie mismatch for needle {needle_id:x}")
        return n

    def _load_ec_record(self, ev: EcVolume,
                        needle_id: int) -> tuple[bytes, int, int, bool]:
        """Cache loader: the needle's full record blob via the interval
        ladder. Flags whether any interval was degraded-reconstructed,
        so the cache force-admits records that cost a decode."""
        with tracing.stage("store.ec.locate"):
            intervals, _offset, size = ev.locate_needle(needle_id)
        if t.size_is_deleted(size):
            raise DeletedError(f"needle {needle_id:x} deleted")
        meter = {"recovered": 0}    # bytes of its intervals rebuilt
        blob = b"".join(
            self._read_one_interval(ev, iv, meter) for iv in intervals)
        rs = self.ec_read_stats
        rs["records_loaded"] += 1
        rs["record_bytes"] += len(blob)
        rs["record_intervals"] += len(intervals)
        # on the loader's stage (store.ec.cache), sampled spans only
        tracing.detail("intervals", len(intervals))
        tracing.detail("bytes", len(blob))
        tracing.detail("recovered", meter["recovered"])
        # the one CRC walk this blob ever pays: admission-time, over
        # memoryview windows — hits re-parse with check_crc=False and
        # range reads serve memoryview slices of the verified bytes
        with tracing.stage("store.ec.crc"):
            needle.verify_record_crc(blob, size, ev.version)
        return blob, size, ev.version, meter["recovered"] > 0

    def _read_record_range(self, ev: EcVolume, rec_offset: int,
                           rel_off: int, length: int) -> bytes:
        """Read `length` bytes starting `rel_off` into the record at
        `rec_offset`, touching only the intervals that cover the range.
        Each interval rides the full local -> remote -> degraded ladder,
        so a missing shard costs one reconstruction of THIS range, not
        of the whole record (let alone the whole large-block)."""
        if length <= 0:
            return b""
        intervals = layout.locate_data(
            layout.LARGE_BLOCK_SIZE, layout.SMALL_BLOCK_SIZE,
            ev.data_shards * ev.shard_size(),
            rec_offset + rel_off, length, data_shards=ev.data_shards)
        return b"".join(
            self._read_one_interval(ev, iv) for iv in intervals)

    def ec_needle_meta(self, vid: int, needle_id: int,
                       cookie: Optional[int] = None
                       ) -> tuple[Needle, int]:
        """(needle-with-empty-data, data_size) by reading only the
        record's head (header + data_size field) and tail (flags +
        optional name/mime/lm/ttl/pairs) — the payload between is never
        touched. Serves subrange degraded reads: the caller learns the
        payload length and metadata for the price of a few dozen bytes,
        then fetches just the requested slice. v2/3 only (a v1 record
        has no data_size prefix); CRC is not checkable without the full
        payload, so `checksum` stays 0."""
        ev = self.find_ec_volume(vid)
        if ev is None:
            raise NotFoundError(f"ec volume {vid} not found")
        if ev.version == 1:
            raise ValueError("v1 records have no subrange layout")
        offset, size = ev.find_needle_from_ecx(needle_id)
        if t.size_is_deleted(size):
            raise DeletedError(f"needle {needle_id:x} deleted")
        head_len = t.NEEDLE_HEADER_SIZE + 4
        head = self._read_record_range(ev, offset, 0, head_len)
        n = Needle.parse_header(head)
        if n.size != size:
            raise NotFoundError(
                f"needle {needle_id:x}: header size {n.size} != ecx {size}")
        if cookie is not None and n.cookie != cookie:
            raise NotFoundError(f"cookie mismatch for needle {needle_id:x}")
        if size == 0:
            return n, 0
        data_size = int.from_bytes(head[t.NEEDLE_HEADER_SIZE:head_len],
                                   "big")
        # tail: [flags ... optional fields] up to the end of the body,
        # plus crc (+ v3 timestamp) for completeness of append_at_ns
        tail_off = head_len + data_size
        tail_len = t.NEEDLE_HEADER_SIZE + size - tail_off \
            + t.NEEDLE_CHECKSUM_SIZE \
            + (t.TIMESTAMP_SIZE if ev.version == 3 else 0)
        tail = self._read_record_range(ev, offset, tail_off, tail_len)
        body_tail_len = t.NEEDLE_HEADER_SIZE + size - tail_off
        if body_tail_len > 0:
            n.parse_body_tail(tail[:body_tail_len])
        if ev.version == 3 and len(tail) >= body_tail_len + 12:
            n.append_at_ns = int.from_bytes(
                tail[body_tail_len + 4:body_tail_len + 12], "big")
        return n, data_size

    def read_ec_needle_data_range(self, vid: int, needle_id: int,
                                  lo: int, length: int) -> bytes:
        """data[lo:lo+length] of an EC needle, reading (and on degraded
        paths reconstructing) only the covering byte ranges. A cached
        full record serves any slice from memory; when the requested
        range would need reconstruction and the record fits the cache's
        item cap, the whole record is reconstructed ONCE (single-flight)
        and every range read after — concurrent waiters included —
        slices the cached blob instead of paying its own decode."""
        ev = self.find_ec_volume(vid)
        if ev is None:
            raise NotFoundError(f"ec volume {vid} not found")
        if ev.version == 1:
            raise ValueError("v1 records have no subrange layout")
        offset, size = ev.find_needle_from_ecx(needle_id)
        if t.size_is_deleted(size):
            raise DeletedError(f"needle {needle_id:x} deleted")
        data_off = t.NEEDLE_HEADER_SIZE + 4
        cache = self.needle_cache
        if cache is not None:
            hit = cache.get(vid, needle_id)
            if hit is not None:
                # memoryview WINDOW of the cached record, not a bytes
                # copy: CRC was verified at admission, and epoch
                # invalidation guarantees the underlying blob is
                # immutable for as long as this view can be reachable
                return memoryview(hit[0])[data_off + lo:
                                          data_off + lo + length]
            if (t.get_actual_size(size, ev.version)
                    <= cache.max_item_bytes()
                    and self._range_needs_recovery(
                        ev, offset, data_off + lo, length)):
                blob, _, _ = cache.get_or_load(
                    vid, needle_id,
                    lambda: self._load_ec_record(ev, needle_id))
                return memoryview(blob)[data_off + lo:
                                        data_off + lo + length]
        return self._read_record_range(
            ev, offset, data_off + lo, length)

    def _range_needs_recovery(self, ev: EcVolume, rec_offset: int,
                              rel_off: int, length: int) -> bool:
        """Would reading this range hit the reconstruction ladder? True
        when a covering interval's shard is neither local nor (as far
        as the shard locator knows) held by any reachable peer. Without
        a locator, missing-local plus no remote reader means recovery."""
        if length <= 0:
            return False
        intervals = layout.locate_data(
            layout.LARGE_BLOCK_SIZE, layout.SMALL_BLOCK_SIZE,
            ev.data_shards * ev.shard_size(),
            rec_offset + rel_off, length, data_shards=ev.data_shards)
        locs = None
        for iv in intervals:
            sid = iv.to_shard_id_and_offset(
                data_shards=ev.data_shards)[0]
            if sid in ev.shards:
                continue
            if self.remote_shard_reader is None:
                return True
            if self.shard_locations is None:
                # remote reader but no topology view: assume the peer
                # will serve it (tests inject bare readers)
                continue
            if locs is None:
                try:
                    locs = self.shard_locations(ev.volume_id) or {}
                except Exception:
                    return True
            if not locs.get(sid):
                return True
        return False

    def _read_one_interval(self, ev: EcVolume, iv: layout.Interval,
                           meter: Optional[dict] = None) -> bytes:
        with tracing.stage("store.ec.read_interval"):
            data, shard_id = ev.read_interval(iv)
        if data is not None:
            self.ec_read_stats["intervals_local"] += 1
            return data
        # remote shard
        if self.remote_shard_reader is not None:
            shard_off = iv.to_shard_id_and_offset(
                data_shards=ev.data_shards)[1]
            data = self.remote_shard_reader(ev.volume_id, shard_id, shard_off,
                                            iv.size)
            if data is not None and len(data) == iv.size:
                return data
        # degraded: fetch the same range of >= k other shards and reconstruct
        if meter is not None:
            meter["recovered"] = meter.get("recovered", 0) + iv.size
        with tracing.stage("store.ec.recover") as st:
            st.annotate("bytes", iv.size)
            got = self._recover_one_interval(ev, iv, shard_id)
        rs = self.ec_read_stats
        rs["intervals_recovered"] += 1
        rs["recovered_bytes"] += iv.size
        rs["recover_s"] += st.elapsed
        return got


    RECOVER_POOL_WORKERS = 32  # > 2x total shards: room for concurrent
    #                            degraded reads even with wedged peers

    _recover_pool_init_lock = threading.Lock()  # class-wide is fine:
    #                                             held only at first use

    def _recover_pool(self):
        pool = getattr(self, "_recover_pool_obj", None)
        if pool is None:
            with self._recover_pool_init_lock:
                pool = getattr(self, "_recover_pool_obj", None)
                if pool is None:
                    from concurrent.futures import ThreadPoolExecutor
                    pool = ThreadPoolExecutor(
                        max_workers=self.RECOVER_POOL_WORKERS,
                        thread_name_prefix="ec-recover")
                    self._recover_pool_obj = pool
        return pool

    def _recover_one_interval(self, ev: EcVolume, iv: layout.Interval,
                              wanted_shard: int) -> bytes:
        """Degraded read: collect sibling-shard ranges and reconstruct.
        Local shards read inline; remote peers are fetched CONCURRENTLY
        with first-k-wins — one slow peer must not serialize recovery
        (reference store_ec.go:328-382 fans out a goroutine per source
        shard the same way). Coders that plan their sources (LRC) get a
        plan-first pass: a lost group member reads only its surviving
        local group (~k/l columns) instead of k."""
        coder = self.coder_for(ev)
        k = coder.scheme.data_shards
        total = coder.scheme.total_shards
        shard_off = iv.to_shard_id_and_offset(
            data_shards=ev.data_shards)[1]
        if coder.scheme.substripes > 1:
            return self._recover_substriped(ev, iv.size, shard_off, coder,
                                            wanted_shard)
        plan_capable = hasattr(coder, "plan_rebuild")
        if plan_capable:
            got = self._recover_via_plan(ev, iv, shard_off, coder,
                                         wanted_shard)
            if got is not None:
                return got
        elif hasattr(coder, "rebuild_matrix") \
                and hasattr(coder, "reconstruct_rows"):
            return self._recover_one_row(ev, iv, shard_off, coder,
                                         wanted_shard)
        bufs: dict[int, bytes] = {}
        remote_sids: list[int] = []
        with tracing.stage("store.ec.survivors"):
            for sid in range(total):
                if sid == wanted_shard:
                    continue
                local = ev.shards.get(sid)
                if local is not None:
                    bufs[sid] = local.read_at(shard_off, iv.size)
                    # a plan-capable coder may find an arbitrary k-subset
                    # rank-deficient, so keep every local column for it
                    if len(bufs) >= k and not plan_capable:
                        break
                elif self.remote_shard_reader is not None:
                    remote_sids.append(sid)
            # same reasoning remotely: the fallback is rare (a planned
            # source was unreachable), so over-collect for plan coders
            need = k if not plan_capable \
                else min(total - 1, len(bufs) + len(remote_sids))
            if len(bufs) < need and remote_sids:
                self._fetch_remote_shards(ev, iv, shard_off, bufs,
                                          remote_sids, need)
        self._count_survivors(len(bufs), iv.size)
        if len(bufs) < k:
            raise NotFoundError(
                f"ec volume {ev.volume_id}: only {len(bufs)} shards "
                f"reachable, need {k}")
        shards: list[Optional[bytes]] = [None] * total
        for sid, b in bufs.items():
            shards[sid] = b
        try:
            full = coder.reconstruct(shards)
        except ValueError as e:
            raise NotFoundError(
                f"ec volume {ev.volume_id}: {len(bufs)} shards reachable "
                f"but pattern unrecoverable: {e}")
        self.ec_recover_stats["generic"] += 1
        return full[wanted_shard]

    def _count_survivors(self, n: int, size: int) -> None:
        rs = self.ec_read_stats
        rs["survivor_reads"] += n
        rs["survivor_bytes"] += n * size

    def _survivor_sources(self, ev: EcVolume, sids,
                          want: Optional[int] = None
                          ) -> tuple[dict, list[int]]:
        """Of `sids` in order: ({sid: mounted shard}, stopping at `want`
        of them, [sids that only a peer can serve])."""
        local: dict = {}
        remote: list[int] = []
        for sid in sids:
            shard = ev.shards.get(sid)
            if shard is None:
                remote.append(sid)
                continue
            local[sid] = shard
            if len(local) == want:
                break
        return local, remote

    def _gather_survivors(self, ev: EcVolume, sids: list[int],
                          local: dict, fetched: dict, shard_off: int,
                          size: int, rows: np.ndarray) -> None:
        """Fill rows[r, :size] with shard sids[r]'s range: the mounted
        shards (`local`) read straight into their rows, all in one go
        (read_shards_into), a fetched one by a copy. Columns past `size`
        are the caller's (zero, in a job buffer)."""
        for r, sid in enumerate(sids):
            got = fetched.get(sid)
            if got is not None:
                rows[r, :size] = np.frombuffer(got, dtype=np.uint8)
        mounted = [None if sid in fetched else local[sid] for sid in sids]
        for sid, shard, n in zip(sids, mounted, read_shards_into(
                mounted, shard_off, rows, size)):
            if shard is not None and n != size:
                raise NotFoundError(
                    f"ec volume {ev.volume_id}: shard {sid} ends inside "
                    f"[{shard_off}, {shard_off + size})")
        self._count_survivors(len(sids), size)
        self.ec_read_stats["survivor_gathers"] += 1

    def _recover_one_row(self, ev: EcVolume, iv: layout.Interval,
                         shard_off: int, coder: ErasureCoder,
                         wanted_shard: int) -> bytes:
        """Plain RS: the first k reachable shards (mounted ones first,
        peers first-k-wins for the rest) gathered once into the job's
        operand, and ONE row asked of the coder: the wanted shard's."""
        k = coder.scheme.data_shards
        size = iv.size
        local, remote_sids = self._survivor_sources(
            ev, (sid for sid in range(coder.scheme.total_shards)
                 if sid != wanted_shard), want=k)
        fetched: dict[int, bytes] = {}
        with tracing.stage("store.ec.survivors"):
            if len(local) < k and remote_sids \
                    and self.remote_shard_reader is not None:
                self._fetch_remote_shards(ev, iv, shard_off, fetched,
                                          remote_sids, k - len(local))
            present = sorted([*local, *fetched])[:k]
            if len(present) < k:
                self._count_survivors(len(fetched), size)
                raise NotFoundError(
                    f"ec volume {ev.volume_id}: only {len(present)} "
                    f"shards reachable, need {k}")
            # a scheduler's facade hands out a buffer already on its
            # ladder's rung (zero past `size`): submit copies nothing
            job_rows = getattr(coder, "job_rows", None)
            rows = job_rows(size) if job_rows is not None \
                else np.empty((k, size), dtype=np.uint8)
            self._gather_survivors(ev, present, local, fetched, shard_off,
                                   size, rows)
        key = (coder.scheme, tuple(present), wanted_shard)
        mat = self._rebuild_rows.get(key)
        try:
            if mat is None:
                mat = self._rebuild_rows[key] = np.ascontiguousarray(
                    coder.rebuild_matrix(present, [wanted_shard]),
                    dtype=np.uint8)
            rec = coder.reconstruct_rows(rows, mat)
        except ValueError as e:
            raise NotFoundError(
                f"ec volume {ev.volume_id}: {k} shards reachable but "
                f"pattern unrecoverable: {e}")
        self.ec_recover_stats["generic"] += 1
        return rec[0, :size].tobytes()

    def _repair_plan(self, coder: ErasureCoder, wanted_shard: int,
                     present: frozenset) -> Optional[tuple]:
        """The family's cheapest repair of ``wanted_shard`` from the
        shards of ``present``: (sources, coefficient row over them,
        strategy), kept per (scheme, shard, present set) as
        ``_rebuild_rows`` keeps plain RS's matrices: the plan is
        derived once, not for every read.  ``local`` where it reads
        fewer than k shards (a lost shard's own group), else ``global``.
        None where ``present`` cannot decode the shard."""
        key = (coder.scheme, wanted_shard, present)
        try:
            return self._rebuild_plans[key]
        except KeyError:
            pass
        try:
            src, mat = coder.plan_rebuild(sorted(present), [wanted_shard])
            plan = (list(src),
                    np.ascontiguousarray(mat, dtype=np.uint8),
                    "local" if len(src) < coder.scheme.data_shards
                    else "global")
        except ValueError:
            plan = None
        self._rebuild_plans[key] = plan
        return plan

    def _reachable(self, ev: EcVolume) -> frozenset:
        """The shards a repair may plan on: the mounted ones and, with a
        peer reader, those the locator knows a holder of (every shard
        where there is no locator, or it cannot say: the fetch tells)."""
        reach = set(ev.shards)
        if self.remote_shard_reader is not None:
            located = None
            if self.shard_locations is not None:
                try:
                    located = self.shard_locations(ev.volume_id) or {}
                except Exception:  # noqa: BLE001 — no view: ask the peers
                    pass
            reach.update(range(MAX_VOLUME_SHARDS) if located is None
                         else located)
        return frozenset(reach)

    def _recover_via_plan(self, ev: EcVolume, iv: layout.Interval,
                          shard_off: int, coder: ErasureCoder,
                          wanted_shard: int) -> Optional[bytes]:
        """The family's cheapest-source repair: the plan over every
        other shard where its sources are all mounted here (a lost
        shard's local group: k / l reads); else the plan over what can
        be reached (the mounted shards and, with a peer reader, the
        shards the locator knows a holder of), which is the global
        decode when a second shard of the group is gone.  The plan's
        survivors are gathered once into the coder's job buffer (as
        _recover_one_row does) and ONE row is asked of the coder.
        Returns the recovered range, or None when no plan decodes it or
        a planned source is unreachable (the caller then falls back to
        the generic collect-k ladder)."""
        size = iv.size
        with tracing.stage("store.ec.plan") as st:
            others = frozenset(range(coder.scheme.total_shards)) \
                - {wanted_shard}
            plan = self._repair_plan(coder, wanted_shard, others)
            if plan is not None and any(s not in ev.shards
                                        for s in plan[0]):
                plan = self._repair_plan(
                    coder, wanted_shard, self._reachable(ev) & others)
            if plan is None:
                return None
            src, mat, strategy = plan
            st.annotate("strategy", strategy)
            st.annotate("sources", src)
        local, remote = self._survivor_sources(ev, src)
        if remote and self.remote_shard_reader is None:
            return None
        fetched: dict[int, bytes] = {}
        with tracing.stage("store.ec.survivors"):
            if remote:
                self._fetch_remote_shards(ev, iv, shard_off, fetched,
                                          remote, len(remote))
                if len(fetched) != len(remote):
                    self._count_survivors(len(fetched), size)
                    return None
            # a scheduler's facade hands out a buffer of the plan's rows,
            # already on its ladder's rung (zero past `size`)
            job_rows = getattr(coder, "job_rows", None)
            rows = job_rows(size, len(src)) if job_rows is not None \
                else np.empty((len(src), size), dtype=np.uint8)
            self._gather_survivors(ev, src, local, fetched, shard_off,
                                   size, rows)
        self.ec_recover_stats[strategy] += 1
        return coder.reconstruct_rows(rows, mat)[0, :size].tobytes()

    def _half_plan(self, coder: ErasureCoder, wanted: tuple,
                   present: frozenset) -> Optional[tuple]:
        """A piggybacked family's plan for the ``wanted`` half-rows from
        the shards of ``present``: (sources as (shard, half), matrix),
        kept per (scheme, wanted, present) as _repair_plan keeps LRC's;
        None where ``present`` cannot decode them."""
        key = (coder.scheme, wanted, present)
        try:
            return self._rebuild_plans[key]
        except KeyError:
            pass
        from seaweedfs_tpu.ops import hitchhiker
        try:
            plan = hitchhiker.plan_halves(coder.scheme, present, wanted)
        except ValueError:
            plan = None
        self._rebuild_plans[key] = plan
        return plan

    def _recover_substriped(self, ev: EcVolume, size: int, shard_off: int,
                            coder: ErasureCoder, wanted_shard: int) -> bytes:
        """A vector family's repair of ``size`` bytes of a shard from
        ``shard_off``: a regenerating code's by ``_recover_planes``; a
        piggybacked one's (ops/hitchhiker.py) here.  The range is cut at the halves
        of its units (a unit: ``scheme.unit`` bytes, a small block).  Where
        it touches both halves of a unit, ONE piggyback job rebuilds both
        (k + |group| half-ranges in, 2 out) if that reads fewer bytes
        than the two halves' own plans; any other piece is a substripe job
        (plain RS on that half: k half-ranges).  The interval counts once,
        as ``piggyback`` where a unit of it took that plan, else
        ``substripe``.  A pattern no plan decodes is a NotFoundError:
        this family never falls to the generic collect-k ladder, whose
        sources are whole shards at one offset."""
        if isinstance(coder.scheme, ClayScheme):
            return self._recover_planes(ev, size, shard_off, coder,
                                        wanted_shard)
        unit = coder.scheme.unit
        half = unit // 2
        end = shard_off + size
        out = np.empty(size, dtype=np.uint8)
        others = frozenset(range(coder.scheme.total_shards)) \
            - {wanted_shard}
        strategy = "substripe"
        for base in range(shard_off - shard_off % unit, end, unit):
            lo, hi = max(shard_off, base) - base, min(end, base + unit) - base
            pieces = {sub: (max(lo - sub * half, 0),
                            min(hi - sub * half, half))
                      for sub in (0, 1)
                      if lo < (sub + 1) * half and hi > sub * half}
            if len(pieces) == 2:
                x_lo = min(p[0] for p in pieces.values())
                x_hi = max(p[1] for p in pieces.values())
                both = ((wanted_shard, 0), (wanted_shard, 1))
                plan = self._plan_reachable(ev, coder, both, others)
                alone = [self._plan_reachable(
                    ev, coder, ((wanted_shard, sub),), others)
                    for sub in (0, 1)]
                if plan is not None and (None in alone or len(plan[0]) * (
                        x_hi - x_lo) < sum(len(a[0]) * (p[1] - p[0])
                                           for a, p in zip(
                                               alone, pieces.values()))):
                    rec = self._half_job(ev, coder, plan, "piggyback",
                                         base, x_lo, x_hi)
                    for sub, (p_lo, p_hi) in pieces.items():
                        at = base + sub * half + p_lo - shard_off
                        out[at:at + p_hi - p_lo] = \
                            rec[sub, p_lo - x_lo:p_hi - x_lo]
                    strategy = "piggyback"
                    continue
            for sub, (p_lo, p_hi) in pieces.items():
                plan = self._plan_reachable(ev, coder,
                                            ((wanted_shard, sub),), others)
                rec = self._half_job(ev, coder, plan, "substripe",
                                     base, p_lo, p_hi)
                at = base + sub * half + p_lo - shard_off
                out[at:at + p_hi - p_lo] = rec[0, :p_hi - p_lo]
        stats = self.ec_recover_stats
        stats[strategy] = stats.get(strategy, 0) + 1
        return out.tobytes()

    def _plan_reachable(self, ev: EcVolume, coder: ErasureCoder,
                        wanted: tuple, others: frozenset) -> Optional[tuple]:
        """The half-row plan over every other shard where its sources
        are all mounted here, else over the shards that can be reached
        (_recover_via_plan's rule)."""
        plan = self._half_plan(coder, wanted, others)
        if plan is not None and any(sid not in ev.shards
                                    for sid, _ in plan[0]):
            plan = self._half_plan(coder, wanted,
                                   self._reachable(ev) & others)
        return plan

    def _half_job(self, ev: EcVolume, coder: ErasureCoder,
                  plan: Optional[tuple], strategy: str, base: int,
                  x_lo: int, x_hi: int) -> np.ndarray:
        """One job of a piggybacked family's repair: the plan's sources,
        half-ranges [x_lo, x_hi) of the unit at shard offset ``base``,
        gathered into the coder's job buffer (one read call per half
        for the mounted ones, peers for the rest) and ONE reconstruct_
        rows; returns its rows."""
        if plan is None:
            raise NotFoundError(
                f"ec volume {ev.volume_id} ({coder.scheme!r}): the "
                "reachable shards cannot decode the lost range")
        src, mat = plan
        half = coder.scheme.unit // 2
        with tracing.stage("store.ec.plan") as st:
            st.annotate("strategy", strategy)
            st.annotate("sources", [list(h) for h in src])
        return self._run_job(ev, coder, [
            (sid, base + sub * half + x_lo, 0, 1) for sid, sub in src],
            x_hi - x_lo, mat, "half_rows")

    def _run_job(self, ev: EcVolume, coder: ErasureCoder, sources: list,
                 run: int, mat: np.ndarray, note: str) -> np.ndarray:
        """One job of a vector family's repair: row r of the coder's job
        buffer is shard sid's ``runs`` runs of ``run`` bytes from
        ``off``, one every ``step`` (``sources[r]`` = (sid, off, step,
        runs)), gathered by as few read calls as the mounted shards'
        offsets allow (one call for the rows of one offset and a single
        run; one stepped call a row otherwise), peers for the rest; then
        ONE reconstruct_rows.  Returns its rows."""
        size = run * sources[0][3]
        with tracing.stage("store.ec.survivors") as st:
            st.annotate(note, len(sources))
            st.annotate("runs", sum(s[3] for s in sources))
            st.annotate("bytes", size * len(sources))
            job_rows = getattr(coder, "job_rows", None)
            rows = job_rows(size, len(sources)) if job_rows is not None \
                else np.empty((len(sources), size), dtype=np.uint8)
            remote = [(r, sid, off + j * step, j * run)
                      for r, (sid, off, step, runs) in enumerate(sources)
                      if sid not in ev.shards for j in range(runs)]
            if remote:
                self._fetch_half_rows(ev, remote, run, rows)
            for key in sorted({s[1:] for s in sources
                               if s[0] in ev.shards}):
                off, step, runs = key
                mounted = [ev.shards.get(sid) if (o, sp, n) == key else None
                           for sid, o, sp, n in sources]
                if runs == 1:
                    got = read_shards_into(mounted, off, rows, run)
                else:
                    got = [0 if shard is None else read_runs_into(
                        shard, off, step, runs, rows[r], run)
                        for r, shard in enumerate(mounted)]
                for (sid, *_r), shard, n in zip(sources, mounted, got):
                    if shard is not None and n != size:
                        raise NotFoundError(
                            f"ec volume {ev.volume_id}: shard {sid} ends "
                            f"inside the runs from {off}")
            self._count_survivors(len(sources), size)
            self.ec_read_stats["survivor_gathers"] += 1
        return coder.reconstruct_rows(rows, mat)

    def _recover_planes(self, ev: EcVolume, size: int, shard_off: int,
                        coder: ErasureCoder, wanted_shard: int) -> bytes:
        """A regenerating family's repair of ``size`` bytes of a shard
        from ``shard_off`` (ops/clay.py), unit by unit (a unit:
        ``scheme.unit`` bytes, a small block).  A unit whose d helpers
        can all be read is rebuilt WHOLE by one repair job: each helper's
        beta sub-chunks of its repair planes (q^y runs a helper, y the
        lost node's column), d beta in, the unit out, a piece of it
        sliced off (the piece plan reads what the whole unit does).  The
        interval counts once: ``repair_planes`` where every unit was
        whole, ``planes_partial`` where a piece was asked for, and
        ``layered`` where a helper could not be read and the units were
        decoded on the host from every reachable shard's whole units."""
        from seaweedfs_tpu.ops import clay
        scheme = coder.scheme
        unit = scheme.unit
        end = shard_off + size
        out = np.empty(size, dtype=np.uint8)
        plan = clay.repair_plan(scheme, wanted_shard)
        reach = set(ev.shards)
        if any(h not in reach for h in plan.helpers):
            reach = self._reachable(ev)
        strategy = "repair_planes" if all(
            h in reach for h in plan.helpers) else "layered"
        for base in range(shard_off - shard_off % unit, end, unit):
            lo, hi = max(shard_off, base) - base, min(end, base + unit) - base
            if strategy == "layered":
                got = self._decode_unit(ev, coder, base, wanted_shard)
            else:
                if hi - lo < unit:
                    strategy = "planes_partial"
                with tracing.stage("store.ec.plan") as st:
                    st.annotate("strategy", strategy)
                    st.annotate("sources", [[h, plan.runs]
                                            for h in plan.helpers])
                rec = self._run_job(
                    ev, coder, [(h, base + plan.first, plan.step, plan.runs)
                                for h in plan.helpers], plan.run,
                    clay.repair_mat(scheme, wanted_shard), "helpers")
                got = clay.units_of(scheme, np.asarray(rec)[
                    :, :scheme.repair_columns])
            out[max(shard_off, base) - shard_off:
                min(end, base + unit) - shard_off] = got[lo:hi]
        stats = self.ec_recover_stats
        stats[strategy] = stats.get(strategy, 0) + 1
        return out.tobytes()

    def _decode_unit(self, ev: EcVolume, coder: ErasureCoder, base: int,
                     wanted_shard: int) -> np.ndarray:
        """The unit at shard offset ``base`` of ``wanted_shard`` by the
        layered decode on the host (ops/clay.decode_units) from the whole
        unit of every other shard that can be read, mounted or from a
        peer; more than m unreadable is a NotFoundError."""
        from seaweedfs_tpu.ops import clay
        scheme = coder.scheme
        unit = scheme.unit
        others = [s for s in range(scheme.total_shards) if s != wanted_shard]
        with tracing.stage("store.ec.plan") as st:
            st.annotate("strategy", "layered")
            st.annotate("sources", others)
        rows = np.zeros((len(others), unit), dtype=np.uint8)
        got = {}
        with tracing.stage("store.ec.survivors"):
            mounted = [ev.shards.get(s) for s in others]
            for s, shard, n in zip(others, mounted, read_shards_into(
                    mounted, base, rows, unit)):
                if shard is not None and n == unit:
                    got[s] = rows[others.index(s)]
            remote = [s for s in others if s not in got
                      and s not in ev.shards]
            if remote and self.remote_shard_reader is not None:
                fetched: dict = {}
                iv = layout.Interval(block_index=0, inner_block_offset=0,
                                     size=unit, is_large_block=False,
                                     large_block_rows_count=0)
                self._fetch_remote_shards(ev, iv, base, fetched, remote,
                                          len(remote))
                got.update({s: np.frombuffer(b, dtype=np.uint8)
                            for s, b in fetched.items()})
            self._count_survivors(len(got), unit)
        lost = [s for s in range(scheme.total_shards) if s not in got]
        try:
            return clay.decode_units(scheme, got, lost)[wanted_shard]
        except ValueError as e:
            raise NotFoundError(
                f"ec volume {ev.volume_id} ({scheme!r}): {e}") from None

    def _fetch_half_rows(self, ev: EcVolume, remote: list, size: int,
                         rows: np.ndarray) -> None:
        """rows[r, col:col + size] = shard sid's bytes at ``off`` from a
        peer, for each (r, sid, off, col) of ``remote``, all at once on
        the recovery pool; a range no peer serves is a NotFoundError."""
        if self.remote_shard_reader is None:
            raise NotFoundError(
                f"ec volume {ev.volume_id}: shards "
                f"{sorted({sid for _r, sid, _o, _c in remote})} not "
                "mounted here and no peer reader")
        from seaweedfs_tpu.utils import resilience
        dl = resilience.current_deadline()

        def fetch(sid: int, off: int):
            with resilience.deadline_scope(dl):
                return self.remote_shard_reader(ev.volume_id, sid, off, size)

        pool = self._recover_pool()
        futs = [(r, sid, col, pool.submit(fetch, sid, off))
                for r, sid, off, col in remote]
        for r, sid, col, fut in futs:
            got = fut.result(timeout=None if dl is None
                             else max(0.0, dl.remaining()))
            if got is None or len(got) != size:
                raise NotFoundError(f"ec volume {ev.volume_id}: a "
                                    f"range of shard {sid} is "
                                    "unreachable")
            rows[r, col:col + size] = np.frombuffer(got, dtype=np.uint8)

    def _rank_remote_sids(self, vid: int,
                          sids: list[int]) -> tuple[list[int], int]:
        """Order remote shard candidates by the health of their BEST
        holder (closed circuits first, open last) and decide how many
        extra columns to over-request. Returns (ordered_sids, extra):
        legacy mode (no health/locator, or resilient_reads off) keeps
        the original fan-out-everything behavior via extra=len(sids);
        resilient mode over-requests one column only when a straggler
        is predicted among the holders it is about to use."""
        health, locator = self.peer_health, self.shard_locations
        if health is None or locator is None or not self.resilient_reads:
            return list(sids), len(sids)
        try:
            locs = locator(vid) or {}
        except Exception:
            return list(sids), len(sids)
        from seaweedfs_tpu.utils.resilience import CLOSED
        try:
            pres = self.shard_pressure(vid) if self.shard_pressure \
                else None
        except Exception:
            pres = None

        def sid_key(sid: int) -> tuple[int, float]:
            urls = locs.get(sid) or []
            if not urls:
                return (3, float("inf"))  # no known holder: try last
            br = health.breaker(health.rank(urls, pressure=pres)[0])
            if br.state == CLOSED:
                return (0, br.score())
            if br.probe_ripe():
                return (1, br.score())
            return (2, br.score())

        keys = {sid: sid_key(sid) for sid in sids}
        ordered = sorted(sids, key=lambda s: keys[s])
        # straggler predicted: any holder we are about to lean on is
        # not healthy-closed, or is far slower than the best candidate
        head = ordered[:max(1, len(ordered))]
        best_score = keys[ordered[0]][1] if ordered else 0.0
        predicted = any(
            keys[s][0] > 0
            or (best_score > 0 and keys[s][1] > 3.0 * best_score)
            for s in head)
        return ordered, 1 if predicted else 0

    def _fetch_remote_shards(self, ev: EcVolume, iv: layout.Interval,
                             shard_off: int, bufs: dict,
                             remote_sids: list[int], k: int) -> None:
        """Concurrent first-k-wins fetch into `bufs`, via the shared
        bounded pool: per-read fan-out (like the reference's
        goroutine-per-source-shard) without letting a wedged peer
        accumulate unbounded abandoned threads across many degraded
        reads — stragglers occupy pool slots until their own network
        timeout, which is the backpressure. In resilient mode the
        initial wave is only (needed + predicted-straggler hedge) of
        the HEALTH-RANKED candidates; failures backfill from the
        ranked queue, and the ambient deadline bounds the whole wait."""
        from concurrent.futures import FIRST_COMPLETED, wait

        from seaweedfs_tpu.utils import resilience

        pool = self._recover_pool()
        dl = resilience.current_deadline()
        queue, extra = self._rank_remote_sids(ev.volume_id, remote_sids)
        need = k - len(bufs)
        inflight: dict = {}

        def submit(sid: int) -> None:
            def run():
                # contextvars don't cross into pool threads on their
                # own: re-enter the caller's deadline scope
                with resilience.deadline_scope(dl):
                    return self.remote_shard_reader(
                        ev.volume_id, sid, shard_off, iv.size)
            inflight[pool.submit(run)] = sid

        for _ in range(min(len(queue), need + extra)):
            submit(queue.pop(0))
        while inflight and len(bufs) < k:
            timeout = None
            if dl is not None:
                timeout = dl.remaining()
                if timeout <= 0:
                    break
            done, _ = wait(inflight, timeout=timeout,
                           return_when=FIRST_COMPLETED)
            if not done:
                break  # deadline expired mid-wait
            for fut in done:
                sid = inflight.pop(fut)
                try:
                    got = fut.result()
                except Exception:
                    got = None
                if got is not None and len(got) == iv.size:
                    bufs[sid] = got
                elif queue:
                    submit(queue.pop(0))  # backfill the failure
        for fut in inflight:
            fut.cancel()  # losers/stragglers are abandoned

    def delete_ec_shard_needle(self, vid: int, needle_id: int,
                               cookie: Optional[int] = None) -> int:
        """Cookie-check then tombstone locally (the server layer fans the
        delete to peer shard owners, reference store_ec_delete.go)."""
        n = self.read_ec_shard_needle(vid, needle_id, cookie)
        ev = self.find_ec_volume(vid)
        if self.needle_cache is not None:
            self.needle_cache.invalidate(vid, needle_id)
        try:
            ev.delete_needle(needle_id)
        finally:
            if self.needle_cache is not None:
                self.needle_cache.invalidate(vid, needle_id)
        return len(n.data)

    # ---- heartbeat ----
    def _disk_type_of(self, v: Volume) -> str:
        for loc in self.locations:
            if v.id in loc.volumes:
                return loc.disk_type
        return "hdd"

    def volume_info(self, v: Volume) -> dict:
        return {
            "id": v.id,
            "collection": v.collection,
            "size": v.content_size(),
            "file_count": v.file_count(),
            "delete_count": v.deleted_count(),
            "deleted_byte_count": v.deleted_bytes(),
            "read_only": v.read_only,
            "replica_placement": v.super_block.replica_placement.to_byte(),
            "ttl": v.super_block.ttl.to_uint32(),
            "version": v.version,
            "disk_type": self._disk_type_of(v),
            "tiered": v.is_tiered,
        }

    def collect_heartbeat(self) -> dict:
        volumes = []
        ec_shards = []
        max_volume_count = 0
        for loc in self.locations:
            max_volume_count += loc.max_volume_count
            for v in loc.volumes.values():
                volumes.append(self.volume_info(v))
            for ev in loc.ec_volumes.values():
                ec_shards.append({
                    "id": ev.volume_id,
                    "collection": ev.collection,
                    "ec_index_bits": ev.shard_bits().bits,
                    # the volume's CodeSpec, for the master's planners
                    "data_shards": ev.data_shards,
                    "total_shards": ev.total_shards,
                    **_code_key(ev.scheme),
                })
        disk_slots: dict[str, int] = {}
        for loc in self.locations:
            disk_slots[loc.disk_type] = (disk_slots.get(loc.disk_type, 0)
                                         + loc.max_volume_count)
        return {
            "ip": self.ip, "port": self.port, "public_url": self.public_url,
            "rack": self.rack, "data_center": self.data_center,
            "max_volume_count": max_volume_count,
            "disk_slots": disk_slots,
            "volumes": volumes,
            "ec_shards": ec_shards,
            "has_no_volumes": not volumes and not ec_shards,
        }

    def drain_deltas(self) -> dict:
        with self._lock:
            out = {
                "new_volumes": self.new_volumes,
                "deleted_volumes": self.deleted_volumes,
                "new_ec_shards": self.new_ec_shards,
                "deleted_ec_shards": self.deleted_ec_shards,
            }
            self.new_volumes = []
            self.deleted_volumes = []
            self.new_ec_shards = []
            self.deleted_ec_shards = []
            return out

    def close(self) -> None:
        pool = getattr(self, "_recover_pool_obj", None)
        if pool is not None:
            pool.shutdown(wait=False, cancel_futures=True)
            self._recover_pool_obj = None
        for loc in self.locations:
            loc.close()
