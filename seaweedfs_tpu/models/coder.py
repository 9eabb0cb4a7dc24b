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
    # the pieces a unit of a shard is cut into: 1 for a scalar code
    substripes = 1

    def __init__(self, data_shards: int = 10, parity_shards: int = 4):
        if not (0 < data_shards and 0 < parity_shards
                and data_shards + parity_shards <= 256):
            raise ValueError(f"invalid RS scheme ({data_shards},{parity_shards})")
        self.data_shards = data_shards
        self.parity_shards = parity_shards

    @property
    def total_shards(self) -> int:
        return self.data_shards + self.parity_shards

    # the most rows a repair job reads: k shards for a scalar code (one
    # row a shard), 2k half-rows for a piggybacked one, d helpers' runs
    # of sub-chunks for a regenerating one
    @property
    def max_sources(self) -> int:
        return self.data_shards

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


class HitchhikerScheme(RSScheme):
    """Hitchhiker-XOR over RS(k, r) (Rashmi et al., SIGCOMM 2014;
    Hadoop 3's ``hhxor`` codec): a VECTOR code.  Every ``unit`` bytes of
    every shard (one 1 MiB block; a 1 GiB block is 1,024 units) are two
    substripes, a = the first half and b = the second, coupled byte for
    byte.  The data shards hold (a_i, b_i) unchanged; parity shard k
    holds (f1(a), f1(b)); parity k+j (j = 1 .. r-1) holds (f_{j+1}(a),
    f_{j+1}(b) XOR the a-halves of group j), f the store's RS(k, r)
    parity rows and the k data shards split into r - 1 ``groups``.  A
    lost data block is rebuilt from k + |group| half-blocks (13 of a
    1 MiB block under RS(10,4): 6.5 MiB, not 10), and any r losses still
    decode, at RS(k, r)'s storage (ops/hitchhiker.py)."""

    __slots__ = ("groups", "unit")
    substripes = 2
    # vector_ops(scheme); the strategies of its degraded reads
    ops_module = "hitchhiker"
    strategies = ("piggyback", "substripe")

    def __init__(self, data_shards: int = 10, parity_shards: int = 4,
                 groups=None, unit: int = 1 << 20):
        super().__init__(data_shards, parity_shards)
        n = parity_shards - 1
        if n <= 0 or data_shards < n or unit <= 0 or unit % 8:
            raise ValueError(
                f"Hitchhiker-XOR: ({data_shards},{parity_shards}) needs "
                "at least 2 parities and a data shard a group, and a "
                f"unit a multiple of 8 bytes, not {unit}")
        if groups is None:
            # r - 1 groups, the larger ones last: (10, 4) -> 3, 3, 4
            sizes = [data_shards // n + (i >= n - data_shards % n)
                     for i in range(n)]
            bounds = [sum(sizes[:i]) for i in range(n + 1)]
            groups = [range(bounds[i], bounds[i + 1]) for i in range(n)]
        self.groups = tuple(tuple(int(s) for s in g) for g in groups)
        if len(self.groups) != n or sorted(
                s for g in self.groups for s in g) != list(range(data_shards)):
            raise ValueError(f"Hitchhiker-XOR: groups {self.groups} are not "
                             f"{n} parts of the {data_shards} data shards")
        self.unit = int(unit)

    @property
    def max_sources(self) -> int:
        return 2 * self.data_shards

    @property
    def repair_columns(self) -> int:
        """The columns of a whole unit's repair job: a half-unit."""
        return self.unit // 2

    def group_of(self, sid: int) -> int:
        """Index j of the group holding data shard ``sid``: its a-half is
        piggybacked on parity shard k + 1 + j."""
        return next(j for j, g in enumerate(self.groups) if sid in g)

    def __repr__(self):
        return f"HitchhikerXOR({self.data_shards},{self.parity_shards})"

    def __eq__(self, other):
        return (type(other) is type(self)
                and other.data_shards == self.data_shards
                and other.parity_shards == self.parity_shards
                and other.groups == self.groups and other.unit == self.unit)

    def __hash__(self):
        return hash((self.data_shards, self.parity_shards, self.groups,
                     self.unit, "hhxor"))


class ClayScheme(RSScheme):
    """Clay(k, m, d) (Vajha et al., "Clay Codes: Moulding MDS Codes to
    Yield an MSR Code", FAST 2018; Ceph's ``clay`` plugin): a
    minimum-storage regenerating VECTOR code.  With q = d - k + 1 and
    t = ceil((k + m) / q), the k + m shards are nodes (x, y) = (i mod q,
    i div q) of a q x t grid, nu = q t - k - m of them virtual (never
    stored, zero): data shards are nodes 0 .. k-1, the virtual ones
    follow, the m parities are the last column.  Every ``unit`` bytes of
    a shard (one 1 MiB block) are alpha = q^t sub-chunks, one a plane
    z in Z_q^t; a lost shard is rebuilt from beta = alpha / q sub-chunks
    of each of its d helpers, and any m losses decode (MDS), at RS(k,
    m)'s storage (ops/clay.py).  Only the MSR point d = k + m - 1 (the
    plugin's default) is implemented: every other node is a helper."""

    __slots__ = ("helpers", "unit", "gamma")
    ops_module = "clay"
    strategies = ("repair_planes", "planes_partial", "layered")

    def __init__(self, data_shards: int = 10, parity_shards: int = 4,
                 helpers: Optional[int] = None, unit: int = 1 << 20,
                 gamma: int = 2):
        super().__init__(data_shards, parity_shards)
        d = data_shards + parity_shards - 1 if helpers is None else helpers
        if parity_shards < 2 or d != data_shards + parity_shards - 1:
            raise ValueError(
                f"Clay: ({data_shards},{parity_shards},{d}) is not the MSR "
                "point d = k + m - 1 with m >= 2, the one implemented")
        self.helpers = d
        self.unit = int(unit)
        self.gamma = int(gamma)
        if self.n_nodes > 256 or self.unit % (4 * self.alpha) \
                or self.gamma in (0, 1) or not 0 < self.gamma < 256:
            raise ValueError(
                f"Clay({data_shards},{parity_shards},{d}): {self.alpha} "
                f"sub-chunks of whole 32-bit words in a unit of {unit} "
                f"bytes, at most 256 nodes, gamma {gamma} not 0 or 1")

    @property
    def q(self) -> int:
        return self.helpers - self.data_shards + 1

    @property
    def t(self) -> int:
        return -(-self.total_shards // self.q)

    @property
    def n_nodes(self) -> int:
        return self.q * self.t

    @property
    def nu(self) -> int:
        return self.n_nodes - self.total_shards

    @property
    def alpha(self) -> int:
        return self.q ** self.t

    @property
    def beta(self) -> int:
        return self.alpha // self.q

    @property
    def substripes(self) -> int:
        return self.alpha

    @property
    def max_sources(self) -> int:
        return self.helpers

    @property
    def sub_bytes(self) -> int:
        return self.unit // self.alpha

    @property
    def repair_columns(self) -> int:
        """The columns of a whole unit's repair job: beta sub-chunks."""
        return self.beta * self.sub_bytes

    def node_of(self, sid: int) -> int:
        """The grid node of shard ``sid``: parities skip the virtual
        nodes."""
        return sid if sid < self.data_shards else sid + self.nu

    def __repr__(self):
        return f"Clay({self.data_shards},{self.parity_shards},{self.helpers})"

    def __eq__(self, other):
        return (type(other) is type(self)
                and other.data_shards == self.data_shards
                and other.parity_shards == self.parity_shards
                and other.helpers == self.helpers
                and other.unit == self.unit and other.gamma == self.gamma)

    def __hash__(self):
        return hash((self.data_shards, self.parity_shards, self.helpers,
                     self.unit, self.gamma, "clay"))


def vector_ops(scheme: RSScheme):
    """The ops/ module of a vector family (``substripes`` > 1) that
    plans its repairs and whole-shard rebuilds: ``rebuild_from``,
    ``rebuild_rows``."""
    return importlib.import_module("seaweedfs_tpu.ops." + scheme.ops_module)


def scheme_to_dict(scheme: RSScheme) -> dict:
    """Serializable CodeSpec for volume metadata (.vif) — lets mixed-code
    clusters pick the right coder per volume at load time."""
    if isinstance(scheme, ClayScheme):
        return {"family": "clay", "data_shards": scheme.data_shards,
                "parity_shards": scheme.parity_shards,
                "d": scheme.helpers, "substripes": scheme.substripes,
                "gamma": scheme.gamma, "unit_bytes": scheme.unit}
    if isinstance(scheme, HitchhikerScheme):
        return {"family": "hhxor", "data_shards": scheme.data_shards,
                "parity_shards": scheme.parity_shards,
                "substripes": scheme.substripes,
                "groups": [list(g) for g in scheme.groups],
                "unit_bytes": scheme.unit}
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
    if d.get("family") == "clay":
        return ClayScheme(int(d.get("data_shards", 10)),
                          int(d.get("parity_shards", 4)),
                          int(d["d"]) if "d" in d else None,
                          int(d.get("unit_bytes", 1 << 20)),
                          int(d.get("gamma", 2)))
    if d.get("family") == "hhxor":
        return HitchhikerScheme(int(d.get("data_shards", 10)),
                                int(d.get("parity_shards", 4)),
                                d.get("groups"),
                                int(d.get("unit_bytes", 1 << 20)))
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


class SubstripedCodeError(ValueError):
    """A path that decodes whole shards at one offset (one row a shard)
    was handed a volume of a vector code, whose sources are pieces of a
    unit at several offsets (``refuse_substriped``)."""


def refuse_substriped(scheme: RSScheme, path: str) -> None:
    """Raise SubstripedCodeError, naming the code and ``path``, where
    ``scheme`` is a vector code (Hitchhiker-XOR, Clay): a path that
    would take its coupled rows for plain RS rows returns wrong bytes
    silently."""
    if scheme.substripes > 1:
        raise SubstripedCodeError(
            f"{code_spec_name(scheme)}: {path} decodes whole shards at "
            "one offset and cannot take a vector-coded volume")


def code_spec_name(scheme: RSScheme) -> str:
    """The spec string of a scheme: ``rs-6-3``, ``lrc-10-2-2``,
    ``hhxor-10-4``, ``clay-10-4-13``.  What ``parse_code_spec`` reads
    back, and the key of per-geometry counters (``by_spec`` in the batch
    scheduler's stats)."""
    if isinstance(scheme, ClayScheme):
        return (f"clay-{scheme.data_shards}-{scheme.parity_shards}-"
                f"{scheme.helpers}")
    if isinstance(scheme, HitchhikerScheme):
        return f"hhxor-{scheme.data_shards}-{scheme.parity_shards}"
    if isinstance(scheme, LrcScheme):
        return (f"lrc-{scheme.data_shards}-{scheme.local_groups}-"
                f"{scheme.global_parities}")
    return f"rs-{scheme.data_shards}-{scheme.parity_shards}"


def parse_code_spec(spec: str, default: RSScheme = DEFAULT_SCHEME
                    ) -> RSScheme:
    """The one parser from a request's ``code`` to a scheme: ``""`` /
    ``rs`` -> ``default`` (the server's own), ``rs-<k>-<m>`` ->
    RSScheme(k, m), ``lrc`` -> LRC(10,2,2), ``lrc-<k>-<l>-<g>`` ->
    LrcScheme(k, l, g), ``hhxor`` -> Hitchhiker-XOR over RS(10,4),
    ``hhxor-<k>-<m>`` -> HitchhikerScheme(k, m), ``clay`` -> Clay(10,4,13),
    ``clay-<k>-<m>[-<d>]`` -> ClayScheme(k, m, d), d = k + m - 1 when
    not given (what ``code_spec_name`` writes).  Anything else — a coder
    registry name, a geometry no volume can carry, groups that do not
    divide the data shards — raises CodeSpecError."""
    spec = (spec or "").strip().lower()
    if spec in ("", "rs"):
        return default
    if spec == "lrc":
        return LrcScheme()
    if spec == "hhxor":
        return HitchhikerScheme()
    if spec == "clay":
        return ClayScheme()
    family, *sizes = spec.split("-")
    if (family, len(sizes)) in (("rs", 2), ("lrc", 3), ("hhxor", 2),
                                ("clay", 2), ("clay", 3)) \
            and all(p.isdigit() for p in sizes):
        sizes = [int(p) for p in sizes]
        if min(sizes) <= 0 or sizes[0] + sum(sizes[1:]) > MAX_VOLUME_SHARDS:
            raise CodeSpecError(
                f"code {spec!r}: a volume carries 1..{MAX_VOLUME_SHARDS} "
                "shards, at least one of them data and one of each kind "
                "of parity")
        if family == "rs":
            return RSScheme(*sizes)
        if family in ("hhxor", "clay"):
            try:
                return (HitchhikerScheme if family == "hhxor"
                        else ClayScheme)(*sizes)
            except ValueError as e:
                raise CodeSpecError(f"code {spec!r}: {e}") from None
        if sizes[0] % sizes[1]:
            raise CodeSpecError(
                f"code {spec!r}: {sizes[1]} local groups do not divide "
                f"{sizes[0]} data shards evenly")
        return LrcScheme(*sizes)
    raise CodeSpecError(
        f"unknown code {spec!r}: expected '', 'rs', 'rs-<k>-<m>', 'lrc', "
        "'lrc-<k>-<l>-<g>', 'hhxor', 'hhxor-<k>-<m>', 'clay' or "
        "'clay-<k>-<m>[-<d>]'")


def host_coder(scheme: RSScheme, threaded: bool) -> "ErasureCoder":
    """The host coder of a scheme's code family (``cpu`` / ``lrc`` /
    ``hhxor`` / ``clay``), or its multi-threaded sibling (``cpu-mt`` /
    ...)."""
    family = "clay" if isinstance(scheme, ClayScheme) \
        else "hhxor" if isinstance(scheme, HitchhikerScheme) \
        else "lrc" if isinstance(scheme, LrcScheme) else "cpu"
    return make_coder(family + ("-mt" if threaded else ""), scheme)


class Encoded:
    """What ``ErasureCoder.encode_begin`` hands back: ``result()`` is the
    parity, ``done()`` says whether it is there already.  This one is:
    the result of a coder that does its work in the begin."""

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
        """encode_array into a caller's (m, n) uint8 buffer; returns
        `out`."""
        out[:] = self.encode_array(data)
        return out

    def encode_begin(self, data) -> Encoded:
        """encode_array in two steps: begin a batch, ask for its parity
        later (``.result()``), and meanwhile begin the next.  A coder
        that works on the caller's thread has done it all when this
        returns; one that hands the batch to another thread (the batch
        scheduler's facade) returns while it is in flight, and the EC
        pipeline keeps a second batch in the coder behind it.

        The parity is an (m, n) uint8 array of the coder's own,
        C-contiguous, possibly read-only, which the coder keeps no hold
        on: here one this begin allocates and ``encode_into`` fills;
        under the batch scheduler the job's rows of the array the
        device's output was fetched into, so the batch's parity reaches
        host memory once."""
        import numpy as np
        out = np.empty((self.scheme.parity_shards, data.shape[1]),
                       dtype=np.uint8)
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
                 "lrc-mt": "lrc", "hhxor": "hitchhiker",
                 "hhxor-mt": "hitchhiker", "clay": "clay", "clay-mt": "clay",
                 "jax": "rs_jax", "mesh": "rs_mesh"}
DEVICE_CODERS = ("jax", "mesh")


def register_coder(name: str):
    def deco(cls):
        _REGISTRY[name] = cls
        return cls
    return deco


def make_coder(name: str = "cpu", scheme: RSScheme = DEFAULT_SCHEME) -> ErasureCoder:
    """Factory over the ten registered names: 'cpu' (default, like the
    reference) and 'cpu-mt' (ops/rs_cpu.py), 'jax' (ops/rs_jax.py), 'mesh'
    (batched multi-device dispatch, ops/rs_mesh.py), 'lrc' and 'lrc-mt'
    (locally repairable code, ops/lrc.py), 'hhxor' and 'hhxor-mt'
    (piggybacked code, ops/hitchhiker.py), 'clay' and 'clay-mt'
    (regenerating code, ops/clay.py)."""
    if name not in _CODER_MODULES:
        raise KeyError(
            f"unknown coder {name!r}; known: {sorted(_CODER_MODULES)}")
    if _CODER_MODULES[name] == "lrc" and not isinstance(scheme, LrcScheme):
        scheme = LrcScheme()
    if _CODER_MODULES[name] == "hitchhiker" \
            and not isinstance(scheme, HitchhikerScheme):
        scheme = HitchhikerScheme()
    if _CODER_MODULES[name] == "clay" and not isinstance(scheme, ClayScheme):
        scheme = ClayScheme()
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
