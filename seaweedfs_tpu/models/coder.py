"""ErasureCoder interface — the pluggable codec seam.

This is the interface BASELINE.json asks for: the reference hard-wires
klauspost/reedsolomon (`reedsolomon.New(10, 4)` at
reference weed/storage/erasure_coding/ec_encoder.go:199); we instead route
every encode/reconstruct through an `ErasureCoder` so the CPU path stays the
default and the device path is selected by configuration. Which coder
serves a volume of ANOTHER scheme than the one a coder was built for is
the coder's own answer (`ErasureCoder.for_scheme`): the store, the batch
scheduler's facade and the repair queue ask, none of them chooses.

Semantics mirror the reference codec's contract:
  - encode(shards): shards is a list of `total` equal-length byte buffers;
    the first `data` ones are inputs; parity buffers are overwritten.
  - reconstruct(shards): missing entries are None; all missing shards are
    recomputed in place (requires >= data present).
  - reconstruct_data(shards): only the first `data` entries are guaranteed
    to be filled afterwards (cheaper on the degraded-read path, matching
    reference weed/storage/store_ec.go:328-382).
"""

from __future__ import annotations

import abc
import importlib
from typing import Optional, Sequence


class RSScheme:
    """An (data, parity) Reed-Solomon scheme. Default RS(10,4) like the
    reference (weed/storage/erasure_coding/ec_encoder.go:17-23)."""

    __slots__ = ("data_shards", "parity_shards")

    def __init__(self, data_shards: int = 10, parity_shards: int = 4):
        if not (0 < data_shards and 0 < parity_shards
                and data_shards + parity_shards <= 256):
            raise ValueError(f"invalid RS scheme ({data_shards},{parity_shards})")
        self.data_shards = data_shards
        self.parity_shards = parity_shards

    @property
    def total_shards(self) -> int:
        return self.data_shards + self.parity_shards

    def __repr__(self):
        return f"RS({self.data_shards},{self.parity_shards})"

    def __eq__(self, other):
        # type identity, not isinstance: an LrcScheme with the same
        # (data, parity) counts is a DIFFERENT code family
        return (type(other) is type(self)
                and other.data_shards == self.data_shards
                and other.parity_shards == self.parity_shards)

    def __hash__(self):
        return hash((self.data_shards, self.parity_shards))


DEFAULT_SCHEME = RSScheme(10, 4)


class LrcScheme(RSScheme):
    """LRC(k, l, g): k data shards split into l local groups, one local
    parity per group (the group's part of an RS parity row, ops/lrc.py:
    GF(256) coefficients, not ones), g global RS parities. Shard ids are
    laid out data-first so the plumbing of any code (.ecNN extensions,
    ecx indexes, a volume's own shard count) carries over: [0..k) data,
    [k..k+l) local parities (group i's parity is shard k+i),
    [k+l..k+l+g) global parities. The default LRC(10,2,2) has 14 shards
    like RS(10,4); Azure's LRC(12,2,2) has 16."""

    __slots__ = ("local_groups", "global_parities")

    def __init__(self, data_shards: int = 10, local_groups: int = 2,
                 global_parities: int = 2):
        if local_groups <= 0 or data_shards % local_groups:
            raise ValueError(
                f"LRC: {local_groups} groups must evenly divide "
                f"{data_shards} data shards")
        super().__init__(data_shards, local_groups + global_parities)
        self.local_groups = local_groups
        self.global_parities = global_parities

    @property
    def group_size(self) -> int:
        return self.data_shards // self.local_groups

    def group_of(self, sid: int) -> Optional[int]:
        """Local group index of a shard id, or None for global parities."""
        if sid < self.data_shards:
            return sid // self.group_size
        if sid < self.data_shards + self.local_groups:
            return sid - self.data_shards
        return None

    def group_members(self, g: int) -> list[int]:
        """Data shard ids + the local parity id of group g."""
        lo = g * self.group_size
        return list(range(lo, lo + self.group_size)) + [self.data_shards + g]

    def local_parity_ids(self) -> list[int]:
        return list(range(self.data_shards,
                          self.data_shards + self.local_groups))

    def global_parity_ids(self) -> list[int]:
        return list(range(self.data_shards + self.local_groups,
                          self.total_shards))

    def __repr__(self):
        return (f"LRC({self.data_shards},{self.local_groups},"
                f"{self.global_parities})")

    def __eq__(self, other):
        return (type(other) is type(self)
                and other.data_shards == self.data_shards
                and other.local_groups == self.local_groups
                and other.global_parities == self.global_parities)

    def __hash__(self):
        return hash((self.data_shards, self.local_groups,
                     self.global_parities, "lrc"))


def scheme_to_dict(scheme: RSScheme) -> dict:
    """Serializable CodeSpec for volume metadata (.vif) — lets mixed-code
    clusters pick the right coder per volume at load time."""
    if isinstance(scheme, LrcScheme):
        return {"family": "lrc", "data_shards": scheme.data_shards,
                "local_groups": scheme.local_groups,
                "global_parities": scheme.global_parities}
    return {"family": "rs", "data_shards": scheme.data_shards,
            "parity_shards": scheme.parity_shards}


def scheme_from_dict(d: Optional[dict]) -> RSScheme:
    """Inverse of scheme_to_dict; None / empty -> the RS default (volumes
    encoded before CodeSpec persistence are RS(10,4))."""
    if not d:
        return DEFAULT_SCHEME
    if d.get("family") == "lrc":
        return LrcScheme(int(d.get("data_shards", 10)),
                         int(d.get("local_groups", 2)),
                         int(d.get("global_parities", 2)))
    return RSScheme(int(d.get("data_shards", 10)),
                    int(d.get("parity_shards", 4)))


# a served volume's shard ids are bits of a 32-bit mask in heartbeats
# (ShardBits, master.proto ec_index_bits) and two digits of a file name
MAX_VOLUME_SHARDS = 32


class CodeSpecError(ValueError):
    """A request's ``code`` names no scheme a volume can carry (the
    HTTP edge answers 400)."""


def code_spec_name(scheme: RSScheme) -> str:
    """The spec string of a scheme: ``rs-6-3``, ``lrc-10-2-2``.  What
    ``parse_code_spec`` reads back, and the key of per-geometry counters
    (``by_spec`` in the batch scheduler's stats)."""
    if isinstance(scheme, LrcScheme):
        return (f"lrc-{scheme.data_shards}-{scheme.local_groups}-"
                f"{scheme.global_parities}")
    return f"rs-{scheme.data_shards}-{scheme.parity_shards}"


def parse_code_spec(spec: str, default: RSScheme = DEFAULT_SCHEME
                    ) -> RSScheme:
    """The one parser from a request's ``code`` to a scheme: ``""`` /
    ``rs`` -> ``default`` (the server's own), ``rs-<k>-<m>`` ->
    RSScheme(k, m), ``lrc`` -> LRC(10,2,2), ``lrc-<k>-<l>-<g>`` ->
    LrcScheme(k, l, g) (what ``code_spec_name`` writes).  Anything else
    — a coder registry name, a geometry no volume can carry, groups that
    do not divide the data shards — raises CodeSpecError."""
    spec = (spec or "").strip().lower()
    if spec in ("", "rs"):
        return default
    if spec == "lrc":
        return LrcScheme()
    family, *sizes = spec.split("-")
    if (family, len(sizes)) in (("rs", 2), ("lrc", 3)) \
            and all(p.isdigit() for p in sizes):
        sizes = [int(p) for p in sizes]
        if min(sizes) <= 0 or sizes[0] + sum(sizes[1:]) > MAX_VOLUME_SHARDS:
            raise CodeSpecError(
                f"code {spec!r}: a volume carries 1..{MAX_VOLUME_SHARDS} "
                "shards, at least one of them data and one of each kind "
                "of parity")
        if family == "rs":
            return RSScheme(*sizes)
        if sizes[0] % sizes[1]:
            raise CodeSpecError(
                f"code {spec!r}: {sizes[1]} local groups do not divide "
                f"{sizes[0]} data shards evenly")
        return LrcScheme(*sizes)
    raise CodeSpecError(
        f"unknown code {spec!r}: expected '', 'rs', 'rs-<k>-<m>', 'lrc' "
        "or 'lrc-<k>-<l>-<g>'")


def host_coder(scheme: RSScheme, threaded: bool) -> "ErasureCoder":
    """The host coder of a scheme's code family (``cpu`` / ``lrc``), or
    its multi-threaded sibling (``cpu-mt`` / ``lrc-mt``)."""
    family = "lrc" if isinstance(scheme, LrcScheme) else "cpu"
    return make_coder(family + ("-mt" if threaded else ""), scheme)


class Encoded:
    """What ``ErasureCoder.encode_begin`` hands back: ``result()`` is the
    caller's ``out``, filled; ``done()`` says whether it is filled
    already.  This one is: the result of a coder that does its work in
    the begin."""

    __slots__ = ("_out",)

    def __init__(self, out):
        self._out = out

    def done(self) -> bool:
        return True

    def result(self):
        return self._out


class ErasureCoder(abc.ABC):
    """Codec over byte buffers, of ONE scheme. Implementations: CpuCoder /
    LrcCoder (numpy / native C++, on the host), JaxCoder and MeshCoder (on
    the device), BatchCoder (the batch scheduler's facade)."""

    def __init__(self, scheme: RSScheme = DEFAULT_SCHEME):
        self.scheme = scheme

    def for_scheme(self, scheme: RSScheme) -> "ErasureCoder":
        """The coder that serves a volume of `scheme` beside this one:
        itself for its own scheme, else the multi-threaded host coder of
        the scheme's family. Overridden where a coder knows better: a
        host coder keeps its own threading (CpuCoder), the batch
        scheduler's facade keeps every scheme, of whatever family, on
        its device queue (BatchCoder)."""
        if scheme == self.scheme:
            return self
        return host_coder(scheme, threaded=True)

    @abc.abstractmethod
    def encode(self, shards: Sequence[bytearray | bytes | memoryview]) -> list[bytes]:
        """Compute parity. Returns the full list of `total` shard buffers
        (data shards passed through, parity freshly computed)."""

    @abc.abstractmethod
    def reconstruct(self, shards: Sequence[Optional[bytes]]) -> list[bytes]:
        """Fill in every None shard. Returns complete shard list."""

    def reconstruct_data(self, shards: Sequence[Optional[bytes]]) -> list[Optional[bytes]]:
        """Fill in only missing *data* shards (parity may remain None)."""
        full = self.reconstruct(shards)
        k = self.scheme.data_shards
        return list(full[:k]) + [
            full[i] if shards[i] is not None else None
            for i in range(k, self.scheme.total_shards)
        ]

    def encode_array(self, data) -> "np.ndarray":
        """(k, n) uint8 -> (m, n) uint8 parity. Default goes through the
        bytes API; coders override with a zero-copy path."""
        import numpy as np
        full = self.encode([np.ascontiguousarray(row).tobytes() for row in data])
        k = self.scheme.data_shards
        return np.stack([np.frombuffer(full[k + i], dtype=np.uint8)
                         for i in range(self.scheme.parity_shards)])

    def encode_into(self, data, out) -> "np.ndarray":
        """encode_array into a caller's (m, n) uint8 buffer (the EC
        pipeline recycles its parity buffers); returns `out`."""
        out[:] = self.encode_array(data)
        return out

    def encode_begin(self, data, out) -> Encoded:
        """encode_into in two steps: begin a batch, ask for ``out``
        filled later (``.result()``), and meanwhile begin the next.  A
        coder that works on the caller's thread has done it all when
        this returns; one that hands the batch to another thread (the
        batch scheduler's facade) returns while it is in flight, and the
        EC pipeline keeps a second batch in the coder behind it."""
        return Encoded(self.encode_into(data, out))

    def reconstruct_arrays(self, present: dict, n: int) -> list:
        """present: {shard_id: (n,) uint8 array}. Returns all `total` shards
        as uint8 arrays (missing ones reconstructed)."""
        import numpy as np
        shards = [None] * self.scheme.total_shards
        for i, a in present.items():
            shards[i] = np.ascontiguousarray(a).tobytes()
        full = self.reconstruct(shards)
        return [np.frombuffer(s, dtype=np.uint8) for s in full]

    def verify(self, shards: Sequence[bytes]) -> bool:
        """True iff parity shards are consistent with data shards."""
        redone = self.encode([bytes(s) for s in shards])
        k = self.scheme.data_shards
        return all(bytes(redone[i]) == bytes(shards[i])
                   for i in range(k, self.scheme.total_shards))


_REGISTRY: dict[str, type] = {}

# every coder name there is, and the ops/ module that registers it when
# imported; a DEVICE coder dispatches to what JAX finds
_CODER_MODULES = {"cpu": "rs_cpu", "cpu-mt": "rs_cpu", "lrc": "lrc",
                 "lrc-mt": "lrc", "jax": "rs_jax", "mesh": "rs_mesh"}
DEVICE_CODERS = ("jax", "mesh")


def register_coder(name: str):
    def deco(cls):
        _REGISTRY[name] = cls
        return cls
    return deco


def make_coder(name: str = "cpu", scheme: RSScheme = DEFAULT_SCHEME) -> ErasureCoder:
    """Factory over the six registered names: 'cpu' (default, like the
    reference) and 'cpu-mt' (ops/rs_cpu.py), 'jax' (ops/rs_jax.py), 'mesh'
    (batched multi-device dispatch, ops/rs_mesh.py), 'lrc' and 'lrc-mt'
    (locally repairable code, ops/lrc.py)."""
    if name not in _CODER_MODULES:
        raise KeyError(
            f"unknown coder {name!r}; known: {sorted(_CODER_MODULES)}")
    if _CODER_MODULES[name] == "lrc" and not isinstance(scheme, LrcScheme):
        scheme = LrcScheme()
    if name in DEVICE_CODERS:
        # refuse (with the reason) where JAX found only the CPU and the
        # CPU was not asked for by name, and place the compile cache
        # before the first jit
        from seaweedfs_tpu.parallel import mesh as mesh_mod
        mesh_mod.ensure_compile_cache()
        mesh_mod.require_accelerator(f"coder {name!r}")
    # imported for its registration side effect
    importlib.import_module("seaweedfs_tpu.ops." + _CODER_MODULES[name])
    return _REGISTRY[name](scheme)
