"""The coder seam (models/coder.py): which coder serves a volume of
another scheme is the coder's own answer (`ErasureCoder.for_scheme`),
the registry knows six names, and the kernel primitive of the rebuild
program multiplies as ops/gf256.py's table says.

The store, the batch scheduler's facade and the repair queue all ask
`for_scheme`; tests/test_code_geometry.py holds the store to it end to
end, this file holds each kind of coder to the rule."""

import argparse
import ast
import os

import numpy as np
import pytest

from seaweedfs_tpu.models.coder import (DEFAULT_SCHEME, DEVICE_CODERS,
                                        LrcScheme, RSScheme, make_coder)
from seaweedfs_tpu.ops import gf256, lrc
from seaweedfs_tpu.ops.rs_cpu import CpuCoder, auto_workers
from seaweedfs_tpu.parallel.batcher import BatchCoder, EcBatchScheduler

RS63 = RSScheme(6, 3)
LRC = LrcScheme(10, 2, 2)
SCHEMES = {"rs-10-4": DEFAULT_SCHEME, "rs-6-3": RS63, "lrc-10-2-2": LRC}
NAMES = ("cpu", "cpu-mt", "jax", "lrc", "lrc-mt", "mesh")


def _parity_matrix(scheme) -> np.ndarray:
    if isinstance(scheme, LrcScheme):
        return lrc.generator_matrix(scheme)[scheme.data_shards:]
    return np.asarray(gf256.parity_matrix(scheme.data_shards,
                                          scheme.parity_shards))


@pytest.fixture(scope="module")
def sched():
    s = EcBatchScheduler()
    yield s
    s.stop()


@pytest.mark.parametrize("spec", sorted(SCHEMES))
@pytest.mark.parametrize("kind", ["cpu", "cpu-mt", "batch"])
def test_for_scheme_answers_with_a_coder_of_that_scheme(kind, spec, sched):
    scheme = SCHEMES[spec]
    own = BatchCoder(sched) if kind == "batch" else make_coder(kind)
    assert own.for_scheme(own.scheme) is own
    c = own.for_scheme(scheme)
    assert c.scheme == scheme and type(c.scheme) is type(scheme)
    # the same code as ops/gf256.py's matrices say, whoever computes it
    data = np.random.default_rng(3).integers(
        0, 256, (scheme.data_shards, 4096), dtype=np.uint8)
    jobs = sched.stats()["jobs_total"]
    assert np.array_equal(c.encode_array(data),
                          gf256.gf_matmul(_parity_matrix(scheme), data))
    submitted = sched.stats()["jobs_total"] - jobs
    if kind == "batch":
        # every scheme, plain RS or another family: a facade over the
        # SAME scheduler, its jobs under its own scheme
        assert isinstance(c, BatchCoder) and c.scheduler is sched
        assert submitted == 1
        assert sched.stats()["by_spec"][spec]["jobs"] >= 1
        # only a family that plans its sources offers a plan
        assert hasattr(c, "plan_rebuild") == isinstance(scheme, LrcScheme)
    else:
        # a host coder answers with its family's host coder, threaded
        # as itself
        assert isinstance(c, lrc.LrcCoder) == isinstance(scheme, LrcScheme)
        assert isinstance(c, CpuCoder) and submitted == 0
        assert c.workers == (1 if kind == "cpu" else auto_workers())


@pytest.mark.parametrize("name", DEVICE_CODERS)
def test_a_device_coder_hands_other_schemes_to_the_host(name):
    """JaxCoder and MeshCoder are of one scheme; a volume of another is
    served by the multi-threaded host coder of its family."""
    own = make_coder(name)
    assert own.for_scheme(DEFAULT_SCHEME) is own
    assert type(own.for_scheme(RS63)).__name__ == "CpuCoderMT"
    assert type(own.for_scheme(LRC)) is lrc.LrcCoderMT


def test_registry_is_six_names_and_the_cli_offers_the_served_ones():
    from seaweedfs_tpu.cli import _add_common_volume_args
    from seaweedfs_tpu.models import coder as coder_mod
    for name in NAMES:
        make_coder(name)
    assert tuple(sorted(coder_mod._REGISTRY)) == NAMES
    with pytest.raises(KeyError) as e:
        make_coder("pallas")
    assert all(repr(n) in str(e.value) for n in NAMES)
    p = argparse.ArgumentParser()
    _add_common_volume_args(p)
    (choices,) = [a.choices for a in p._actions if a.dest == "coder"]
    # the host-only names (-mt, the LRC family) are not a server's pick:
    # a family is a volume's property, threading the store's default
    assert sorted(choices) == ["cpu", "jax", "mesh"]
    assert DEVICE_CODERS == ("jax", "mesh")
    assert set(choices) | {"cpu-mt", "lrc", "lrc-mt"} == set(NAMES)


def test_gf_mul_dynamic_is_the_fields_multiplication():
    """All 256 coefficients as TRACED scalars against gf256.MUL_TABLE,
    over every byte value in every lane of the packed uint32 words."""
    import jax
    import jax.numpy as jnp

    from seaweedfs_tpu.ops.rs_jax import _gf_mul_dynamic
    rng = np.random.default_rng(5)
    data = np.concatenate([np.arange(256, dtype=np.uint8),
                           rng.integers(0, 256, 3840, dtype=np.uint8)])
    rng.shuffle(data)
    coeffs = jnp.arange(256, dtype=jnp.uint32)
    got = np.asarray(jax.jit(jax.vmap(_gf_mul_dynamic, in_axes=(0, None)))(
        coeffs, jnp.asarray(data.view(np.uint32))))
    assert np.array_equal(got.view(np.uint8), gf256.MUL_TABLE[:, data])


def test_ops_reach_jax_devices_through_parallel_mesh_only():
    """The layering of the EC path: nothing under ops/ imports from
    parallel/ except parallel.mesh (the one module that talks to JAX
    about devices), and the pipeline imports no jax."""
    import seaweedfs_tpu
    root = os.path.dirname(seaweedfs_tpu.__file__)

    def imported(path):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module:
                yield from (f"{node.module}.{a.name}" for a in node.names)
            elif isinstance(node, ast.Import):
                yield from (a.name for a in node.names)

    for fn in sorted(os.listdir(os.path.join(root, "ops"))):
        if fn.endswith(".py"):
            up = [m for m in imported(os.path.join(root, "ops", fn))
                  if m.startswith("seaweedfs_tpu.parallel")
                  and not m.startswith("seaweedfs_tpu.parallel.mesh")]
            assert up == [], (fn, up)
    assert not [m for m in imported(
        os.path.join(root, "parallel", "streaming.py"))
        if m.split(".")[0] == "jax"]
