"""The native library's row I/O: ``pread_rows`` (a row from each of n
descriptors, at one offset or a fixed step apart) and ``pwrite_rows``
(its mirror), each ONE foreign call whatever the number of rows.

The seal's pipeline reads a batch's k rows of the .dat with one
``pread_rows`` (step = the block size) and each writer writes its rows
with one ``pwrite_rows``; a rebuilt read gathers its survivors with one
``pread_rows`` at step 0 (``ec_volume.read_shards_into``).
"""

import os

import numpy as np
import pytest

from seaweedfs_tpu.native import rs_native

pytestmark = pytest.mark.skipif(not rs_native.available(),
                                reason="no native library on this machine")


@pytest.fixture
def blob(tmp_path):
    """A file of 5,000 seeded bytes, open read-only: (fd, its bytes)."""
    data = np.random.default_rng(1).integers(
        0, 256, 5000, dtype=np.uint8).tobytes()
    path = tmp_path / "blob"
    path.write_bytes(data)
    fd = os.open(path, os.O_RDONLY)
    yield fd, data
    os.close(fd)


@pytest.mark.parametrize("offset,step,size", [
    (0, 100, 100),          # adjacent rows: a small-block row of the .dat
    (7, 1000, 300),         # rows far apart: a large-block row
    (123, 333, 64),
])
def test_pread_rows_reads_each_row_from_its_own_offset(blob, offset, step,
                                                       size):
    fd, _data = blob
    rows = np.full((4, size + 16), 9, dtype=np.uint8)
    got = rs_native.pread_rows([fd] * 4, offset, rows, size,
                               offset_step=step)
    assert got == [size] * 4
    for r in range(4):
        assert rows[r, :size].tobytes() == os.pread(fd, size,
                                                    offset + r * step)
        assert (rows[r, size:] == 9).all()


def test_pread_rows_leaves_a_negative_fds_row_as_it_was(blob):
    fd, data = blob
    rows = np.full((3, 50), 7, dtype=np.uint8)
    assert rs_native.pread_rows([fd, -1, fd], 10, rows, 50,
                                offset_step=1000) == [50, 0, 50]
    assert rows[0].tobytes() == data[10:60]
    assert (rows[1] == 7).all()
    assert rows[2].tobytes() == data[2010:2060]


def test_pread_rows_is_short_at_the_end_of_the_file(blob):
    """A row that reaches past the end comes back short by exactly what
    the file lacks; a row that starts past it reads nothing."""
    fd, data = blob
    rows = np.zeros((3, 800), dtype=np.uint8)
    got = rs_native.pread_rows([fd] * 3, 4000, rows, 800, offset_step=600)
    assert got == [800, 400, 0]
    assert rows[1, :400].tobytes() == data[4600:]


def test_pread_rows_at_step_zero_is_the_gather_it_was(tmp_path):
    """Step 0 (the default): every row from the SAME offset of its own
    file, the rebuilt read's gather, byte for byte as a pread each."""
    fds, want = [], []
    for i in range(5):
        p = tmp_path / f"s{i}"
        p.write_bytes(bytes([i]) * 100 + os.urandom(900))
        fds.append(os.open(p, os.O_RDONLY))
    try:
        rows = np.zeros((5, 256), dtype=np.uint8)
        assert rs_native.pread_rows(fds, 90, rows, 256) == [256] * 5
        assert rs_native.pread_rows(fds, 90, rows, 256, 0) == [256] * 5
        for r, fd in enumerate(fds):
            assert rows[r].tobytes() == os.pread(fd, 256, 90)
    finally:
        for fd in fds:
            os.close(fd)


def test_pread_rows_refuses_a_negative_step_and_raises_on_a_bad_fd(blob):
    fd, _data = blob
    rows = np.zeros((2, 8), dtype=np.uint8)
    with pytest.raises(ValueError):
        rs_native.pread_rows([fd, fd], 0, rows, 8, offset_step=-1)
    closed = os.dup(fd)
    os.close(closed)
    with pytest.raises(OSError):
        rs_native.pread_rows([fd, closed], 0, rows, 8, offset_step=8)


def test_pwrite_rows_round_trips_at_a_running_offset(tmp_path):
    """Three batches of four rows, each written by one call at the
    offset the batches before it reached: every file is exactly the
    batches' width long and holds its rows in order."""
    rng = np.random.default_rng(2)
    paths = [tmp_path / f"out{i}" for i in range(4)]
    fds = [os.open(p, os.O_WRONLY | os.O_CREAT) for p in paths]
    batches = [rng.integers(0, 256, (4, w), dtype=np.uint8)
               for w in (160, 160, 37)]
    try:
        offset = 0
        for rows in batches:
            assert rs_native.pwrite_rows(fds, offset, rows,
                                         rows.shape[1]) is None
            offset += rows.shape[1]
    finally:
        for fd in fds:
            os.close(fd)
    for i, p in enumerate(paths):
        assert p.read_bytes() == b"".join(b[i].tobytes() for b in batches)
        assert os.path.getsize(p) == 160 + 160 + 37


def test_pwrite_rows_writes_a_prefix_of_read_only_rows(tmp_path):
    rows = np.arange(20, dtype=np.uint8).reshape(2, 10)
    rows.flags.writeable = False
    paths = [tmp_path / "a", tmp_path / "b"]
    fds = [os.open(p, os.O_WRONLY | os.O_CREAT) for p in paths]
    try:
        rs_native.pwrite_rows(fds, 5, rows, 6)
    finally:
        for fd in fds:
            os.close(fd)
    for r, p in enumerate(paths):
        assert p.read_bytes() == bytes(5) + rows[r, :6].tobytes()


def test_pwrite_rows_raises_on_a_bad_fd_and_refuses_what_does_not_fit(
        tmp_path):
    rows = np.zeros((2, 8), dtype=np.uint8)
    ro = tmp_path / "ro"
    ro.write_bytes(b"")
    fd = os.open(ro, os.O_RDONLY)       # open, but not for writing
    try:
        with pytest.raises(OSError):
            rs_native.pwrite_rows([fd, fd], 0, rows, 8)
        with pytest.raises(OSError):
            rs_native.pwrite_rows([fd, -1], 0, rows, 8)
    finally:
        os.close(fd)
    for bad in [dict(fds=[fd]), dict(size=9), dict(offset=-1),
                dict(rows=rows.astype(np.uint16)), dict(rows=rows[:, ::2])]:
        kw = {**dict(fds=[fd, fd], offset=0, rows=rows, size=8), **bad}
        with pytest.raises(ValueError):
            rs_native.pwrite_rows(kw["fds"], kw["offset"], kw["rows"],
                                  kw["size"])
