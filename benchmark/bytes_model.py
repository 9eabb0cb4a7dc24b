"""The least bytes a request needs moved, from the sizes the REQUEST
states (a volume's ``.dat`` size, the lost interval's length) and never
from the padded or batched shapes the program dispatches.

Sealing a ``.dat`` with RS(k, m): every row of the layout reads the data
bytes it really holds (not the zero fill past the end of the file) and
writes m parity columns as wide as its widest data block.  Moved once
through the chip's memory at its peak rate, that is the least time the
code can take: the kernel is bytes-bound (a GF(2^8) multiply-accumulate
is a few integer operations a byte against 14/10 bytes moved per input
byte; the v5e's vector units outrun its HBM on that).
"""

from __future__ import annotations

from benchmark.reference import encode_rows


def seal_min_bytes(dat_size: int, k: int, m: int, large_block: int,
                   small_block: int) -> int:
    """Bytes read plus bytes written by the parity computation of one
    seal of a ``.dat`` of ``dat_size`` bytes.  The same formula for every
    code family: k data columns in, m = ``parity_shards`` columns out
    (under LRC the local and the global parities together), whatever the
    rows' coefficients; a local parity row reads only its group, but the
    request still has every data byte read once."""
    total = 0
    for row_off, block in encode_rows(dat_size, k, large_block,
                                      small_block):
        held = min(dat_size - row_off, block * k)   # data bytes in the row
        widest = min(held, block)                   # block 0 is the fullest
        total += held + m * widest
    return total


def min_seconds(n_bytes: float, peak_bytes_per_s: float) -> float:
    return n_bytes / peak_bytes_per_s
