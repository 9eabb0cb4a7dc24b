"""Streaming EC pipeline correctness vs the reference layout."""

import numpy as np
import pytest

from seaweedfs_tpu.models.coder import make_coder
from seaweedfs_tpu.parallel.streaming import pipelined_encode_file
from seaweedfs_tpu.storage.erasure_coding import encoder as ecenc
from seaweedfs_tpu.storage.erasure_coding import layout

LB, SB = 640, 160


@pytest.mark.parametrize("coder_name", ["cpu", "jax", "mesh"])
def test_pipelined_encode_matches_reference_layout(tmp_path, coder_name):
    """The pipeline against the serial walk, with a host coder and with
    each device coder in the coder seat."""
    rng = np.random.default_rng(0)
    dat = rng.integers(0, 256, 2 * LB * 10 + 3 * SB * 10 + 77,
                       dtype=np.uint8).tobytes()
    for name in ("a", "b"):
        with open(tmp_path / f"{name}.dat", "wb") as f:
            f.write(dat)

    ecenc.write_ec_files(str(tmp_path / "a"), make_coder("cpu"), LB, SB,
                         batch_size=SB)
    pipelined_encode_file(str(tmp_path / "b"), make_coder(coder_name),
                          LB, SB, batch_size=SB)
    for i in range(14):
        with open(tmp_path / ("a" + layout.shard_ext(i)), "rb") as f:
            want = f.read()
        with open(tmp_path / ("b" + layout.shard_ext(i)), "rb") as f:
            got = f.read()
        assert got == want, f"shard {i} differs"
