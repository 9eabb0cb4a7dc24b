"""EC volume serving: sorted-index search, deletion journal, shard files.

Functional equivalent of reference weed/storage/erasure_coding/ec_volume.go,
ec_shard.go, ec_volume_delete.go, ec_volume_info.go.
"""

from __future__ import annotations

import json
import os
import threading
from array import array
from bisect import bisect_left
from typing import Callable, Iterator, Optional

import numpy as np

from seaweedfs_tpu.models.coder import scheme_from_dict, scheme_to_dict
from seaweedfs_tpu.native import rs_native
from seaweedfs_tpu.storage import types as t
from seaweedfs_tpu.storage.erasure_coding import layout


def read_volume_info(base_file_name: str) -> dict:
    """Parse the .vif sidecar ({"version": ..., "code": CodeSpec dict}).
    Empty dict when absent/corrupt — pre-CodeSpec volumes default to
    version 3 / RS(10,4) exactly as before."""
    try:
        with open(base_file_name + ".vif", "r", encoding="utf-8") as f:
            info = json.load(f)
        return info if isinstance(info, dict) else {}
    except (OSError, ValueError):
        return {}


def write_volume_info(base_file_name: str, version: int, scheme) -> None:
    """Persist the .vif sidecar: version + the volume's CodeSpec, so a
    mixed-code cluster can pick the right coder per volume at load."""
    with open(base_file_name + ".vif", "w", encoding="utf-8") as f:
        json.dump({"version": version,
                   "code": scheme_to_dict(scheme)}, f)


class NotFoundError(Exception):
    pass


def mark_needle_deleted(f, entry_offset: int) -> None:
    """Overwrite the size field of an .ecx entry with the tombstone
    (reference ec_volume_delete.go:13-25)."""
    f.seek(entry_offset + t.NEEDLE_ID_SIZE + t.OFFSET_SIZE)
    f.write(t.pack_entry(0, 0, t.TOMBSTONE_FILE_SIZE)[-t.SIZE_SIZE:])


def search_needle_from_sorted_index(
        ecx_file, ecx_size: int, needle_id: int,
        process: Optional[Callable] = None) -> tuple[int, int]:
    """Binary search a sorted 16-byte-entry index for needle_id. Returns
    (offset_units, size); raises NotFoundError
    (reference ec_volume.go:221-250 SearchNeedleFromSortedIndex)."""
    lo, hi = 0, ecx_size // t.NEEDLE_MAP_ENTRY_SIZE
    while lo < hi:
        mid = (lo + hi) // 2
        ecx_file.seek(mid * t.NEEDLE_MAP_ENTRY_SIZE)
        buf = ecx_file.read(t.NEEDLE_MAP_ENTRY_SIZE)
        key, off, size = t.unpack_entry(buf)
        if key == needle_id:
            if process is not None:
                process(ecx_file, mid * t.NEEDLE_MAP_ENTRY_SIZE)
            return off, size
        if key < needle_id:
            lo = mid + 1
        else:
            hi = mid
    raise NotFoundError(f"needle {needle_id:x} not in ecx")


_ECX_ENTRY = np.dtype([("key", ">u8"), ("offset", ">u4"), ("size", ">i4")])


def load_sorted_index(raw: bytes) -> tuple[array, array, array]:
    """The entries of a sorted 16-byte-entry index as native-endian
    (keys, offset units, sizes) arrays, built once: `bisect_left` over
    `keys` is the whole search, and a tombstone is one store into
    `sizes`. 16 B a needle, as in the file."""
    entries = np.frombuffer(raw, dtype=_ECX_ENTRY,
                            count=len(raw) // _ECX_ENTRY.itemsize)
    columns = []
    for code, field in (("Q", "key"), ("I", "offset"), ("i", "size")):
        col = array(code)
        native = _ECX_ENTRY[field].newbyteorder("=")
        if col.itemsize != native.itemsize:
            raise RuntimeError(f"array({code!r}) holds {col.itemsize} bytes "
                               f"an item here, the index {native.itemsize}")
        col.frombytes(entries[field].astype(native).tobytes())
        columns.append(col)
    return columns[0], columns[1], columns[2]


def _position(keys: array, needle_id: int) -> int:
    """Where needle_id sits in the sorted keys, or -1."""
    i = bisect_left(keys, needle_id)
    return i if i < len(keys) and keys[i] == needle_id else -1


def _pread_full(fd: int, length: int, offset: int) -> bytes:
    """`length` bytes at `offset`, short only at the end of the file
    (one pread as a rule; a read that comes back short is continued)."""
    data = os.pread(fd, length, offset)
    while 0 < len(data) < length:
        more = os.pread(fd, length - len(data), offset + len(data))
        if not more:
            break
        data += more
    return data


def iterate_ecj_file(base_file_name: str) -> Iterator[int]:
    """Yield needle ids from the deletion journal (8-byte big-endian each,
    reference ec_decoder.go iterateEcjFile)."""
    path = base_file_name + ".ecj"
    if not os.path.exists(path):
        return
    with open(path, "rb") as f:
        while True:
            buf = f.read(t.NEEDLE_ID_SIZE)
            if len(buf) != t.NEEDLE_ID_SIZE:
                return
            yield int.from_bytes(buf, "big")


class ShardBits:
    """Bitmask of owned shard ids (reference ec_volume_info.go:65-117)."""

    __slots__ = ("bits",)

    def __init__(self, bits: int = 0):
        self.bits = bits

    def add_shard_id(self, shard_id: int) -> "ShardBits":
        return ShardBits(self.bits | (1 << shard_id))

    def remove_shard_id(self, shard_id: int) -> "ShardBits":
        return ShardBits(self.bits & ~(1 << shard_id))

    def has_shard_id(self, shard_id: int) -> bool:
        return bool(self.bits & (1 << shard_id))

    def shard_ids(self) -> list[int]:
        # every set bit, whatever the volume's shard count
        return [i for i in range(self.bits.bit_length())
                if self.has_shard_id(i)]

    def shard_id_count(self) -> int:
        return bin(self.bits).count("1")

    def minus_parity_shards(
            self, data_shards: int = layout.DATA_SHARDS_COUNT
    ) -> "ShardBits":
        return ShardBits(self.bits & ((1 << data_shards) - 1))

    def plus(self, other: "ShardBits") -> "ShardBits":
        return ShardBits(self.bits | other.bits)

    def minus(self, other: "ShardBits") -> "ShardBits":
        return ShardBits(self.bits & ~other.bits)

    def __eq__(self, other):
        return isinstance(other, ShardBits) and other.bits == self.bits

    def __repr__(self):
        return f"ShardBits({self.shard_ids()})"


def ec_base_file_name(directory: str, collection: str,
                      volume_id: int) -> str:
    """<dir>/<collection>_<vid> (or <dir>/<vid> in the default
    collection) — the stem every file of an EC volume shares with the
    .dat it was encoded from (reference ec_shard.go EcShardFileName)."""
    name = f"{collection}_{volume_id}" if collection else str(volume_id)
    return os.path.join(directory, name)


class EcVolumeShard:
    """One local .ecNN file (reference ec_shard.go:17-49)."""

    def __init__(self, directory: str, collection: str, volume_id: int,
                 shard_id: int):
        self.directory = directory
        self.collection = collection
        self.volume_id = volume_id
        self.shard_id = shard_id
        self.path = ec_base_file_name(directory, collection, volume_id) \
            + layout.shard_ext(shard_id)
        # positional reads only: no file position, so no lock to share it
        self._f = open(self.path, "rb", buffering=0)
        self.shard_size = os.path.getsize(self.path)

    def read_at(self, offset: int, length: int) -> bytes:
        """One pread; short only at the end of the file."""
        return _pread_full(self._f.fileno(), length, offset)

    def read_into(self, offset: int, buf) -> int:
        """Fill a writable buffer (a row of a job's operand) from
        `offset` by preadv, no bytes object between; returns the bytes
        read, short only at the end of the file."""
        fd = self._f.fileno()
        view = memoryview(buf)
        n = os.preadv(fd, [view], offset)
        while 0 < n < len(view):
            got = os.preadv(fd, [view[n:]], offset + n)
            if not got:
                break
            n += got
        return n

    def close(self):
        self._f.close()

    def destroy(self):
        self.close()
        os.remove(self.path)


def read_shards_into(shards: list[Optional[EcVolumeShard]], offset: int,
                     rows: np.ndarray, size: int) -> list[int]:
    """rows[r, :size] = shards[r]'s bytes from `offset` (None: the row
    is the caller's); returns the bytes read per row, short only at the
    end of a shard. With the native library all the preads are ONE
    foreign call: a request thread gives the interpreter lock away once
    a gather, not once a shard (ten times for RS(10,4)), and each time
    it queues behind every runnable thread of the server to get it
    back. Without the library, one preadv a shard."""
    if rs_native.available():
        return rs_native.pread_rows(
            [-1 if s is None else s._f.fileno() for s in shards],
            offset, rows, size)
    return [0 if s is None else s.read_into(offset, rows[r, :size])
            for r, s in enumerate(shards)]


class EcVolume:
    """A mounted EC volume: local shards + .ecx index + .ecj journal
    (reference ec_volume.go:25-76)."""

    def __init__(self, directory: str, collection: str, volume_id: int,
                 version: int = 3, stats: Optional[dict] = None):
        self.directory = directory
        self.collection = collection
        self.volume_id = volume_id
        # where lookups are counted: the mounting store's ec_read_stats
        # (storage/store.py), a dict of its own otherwise
        self.stats = stats if stats is not None \
            else {"ecx_lookups": 0, "ecx_file_searches": 0}
        self.base_file_name = ec_base_file_name(directory, collection,
                                                volume_id)
        info = read_volume_info(self.base_file_name)
        self.version = int(info.get("version", version))
        # the volume's CodeSpec (RS(10,4) when the .vif predates CodeSpec
        # persistence) — every shard-count consumer below derives from it
        self.scheme = scheme_from_dict(info.get("code"))
        self.shards: dict[int, EcVolumeShard] = {}
        self._ecx_lock = threading.Lock()
        self._ecj_lock = threading.Lock()
        ecx = self.base_file_name + ".ecx"
        # unbuffered: the handle is the tombstones' way to the disk
        # (delete_needle), and the fallback's when the index did not load
        self.ecx_file = open(ecx, "r+b", buffering=0) \
            if os.path.exists(ecx) else None
        self.ecx_file_size = os.path.getsize(ecx) if self.ecx_file else 0
        self.ecx_created_at = os.path.getmtime(ecx) if self.ecx_file else 0
        # the index in memory from mount to unmount (upstream searches
        # the file): lookups bisect these without a lock or a system
        # call; delete_needle, under _ecx_lock, is their one writer
        self._ecx_index: Optional[tuple[array, array, array]] = None
        if self.ecx_file is not None:
            try:
                self._ecx_index = load_sorted_index(_pread_full(
                    self.ecx_file.fileno(), self.ecx_file_size, 0))
            except (OSError, MemoryError):
                pass  # lookups search the file, under the lock
        # shard-location cache for remote reads (volume server fills this)
        self.shard_locations: dict[int, list[str]] = {}
        self.shard_locations_refreshed_at = 0.0
        self.shard_locations_lock = threading.Lock()

    @property
    def data_shards(self) -> int:
        return self.scheme.data_shards

    @property
    def total_shards(self) -> int:
        return self.scheme.total_shards

    def add_shard(self, shard: EcVolumeShard) -> bool:
        if shard.shard_id in self.shards:
            return False
        self.shards[shard.shard_id] = shard
        return True

    def delete_shard(self, shard_id: int) -> Optional[EcVolumeShard]:
        return self.shards.pop(shard_id, None)

    def shard_bits(self) -> ShardBits:
        b = ShardBits()
        for sid in self.shards:
            b = b.add_shard_id(sid)
        return b

    def shard_size(self) -> int:
        for s in self.shards.values():
            return s.shard_size
        return 0

    def find_needle_from_ecx(self, needle_id: int) -> tuple[int, int]:
        """(offset_bytes, size); raises NotFoundError; tombstones surface as
        deleted size (reference ec_volume.go:205-250)."""
        index = self._ecx_index
        if index is None:
            return self._find_needle_in_ecx_file(needle_id)
        self.stats["ecx_lookups"] += 1
        keys, offsets, sizes = index
        i = _position(keys, needle_id)
        if i < 0:
            raise NotFoundError(f"needle {needle_id:x} not in ecx")
        return t.offset_to_actual(offsets[i]), sizes[i]

    def _find_needle_in_ecx_file(self, needle_id: int) -> tuple[int, int]:
        if self.ecx_file is None:
            raise NotFoundError("no ecx file")
        self.stats["ecx_file_searches"] += 1
        with self._ecx_lock:
            off_units, size = search_needle_from_sorted_index(
                self.ecx_file, self.ecx_file_size, needle_id)
        return t.offset_to_actual(off_units), size

    def locate_needle(self, needle_id: int,
                      large_block: int = layout.LARGE_BLOCK_SIZE,
                      small_block: int = layout.SMALL_BLOCK_SIZE
                      ) -> tuple[list[layout.Interval], int, int]:
        """(intervals, offset, size) for the needle's whole on-disk record
        (reference ec_volume.go LocateEcShardNeedle)."""
        offset, size = self.find_needle_from_ecx(needle_id)
        if t.size_is_deleted(size):
            return [], offset, size
        shard_size = self.shard_size()
        record = t.get_actual_size(size, self.version)
        intervals = layout.locate_data(
            large_block, small_block,
            self.data_shards * shard_size, offset, record,
            data_shards=self.data_shards)
        return intervals, offset, size

    def delete_needle(self, needle_id: int) -> None:
        """Tombstone in .ecx (the file, then the index in memory: every
        lookup that starts after this returns sees it) + journal append
        to .ecj (reference ec_volume_delete.go:27-49)."""
        if self.ecx_file is None:
            raise NotFoundError("no ecx file")
        try:
            with self._ecx_lock:
                index = self._ecx_index
                if index is None:
                    search_needle_from_sorted_index(
                        self.ecx_file, self.ecx_file_size, needle_id,
                        mark_needle_deleted)
                else:
                    keys, _, sizes = index
                    i = _position(keys, needle_id)
                    if i < 0:
                        return
                    mark_needle_deleted(self.ecx_file,
                                        i * t.NEEDLE_MAP_ENTRY_SIZE)
                    sizes[i] = t.TOMBSTONE_FILE_SIZE
        except NotFoundError:
            return
        with self._ecj_lock:
            with open(self.base_file_name + ".ecj", "ab") as f:
                f.write(needle_id.to_bytes(t.NEEDLE_ID_SIZE, "big"))

    def read_interval(self, interval: layout.Interval,
                      large_block: int = layout.LARGE_BLOCK_SIZE,
                      small_block: int = layout.SMALL_BLOCK_SIZE
                      ) -> tuple[Optional[bytes], int]:
        """Read one interval from a LOCAL shard. Returns (data, shard_id);
        data is None when the shard is not local (caller goes remote /
        degraded, reference store_ec.go:188-218)."""
        shard_id, off = interval.to_shard_id_and_offset(
            large_block, small_block, self.data_shards)
        shard = self.shards.get(shard_id)
        if shard is None:
            return None, shard_id
        return shard.read_at(off, interval.size), shard_id

    def close(self):
        self._ecx_index = None
        if self.ecx_file:
            self.ecx_file.close()
            self.ecx_file = None
        for s in self.shards.values():
            s.close()
        self.shards.clear()

    def destroy(self):
        for s in list(self.shards.values()):
            s.destroy()
        self.close()
        for ext in (".ecx", ".ecj", ".vif"):
            p = self.base_file_name + ext
            if os.path.exists(p):
                os.remove(p)
