"""The least bytes a Clay repair needs moved, from the sizes the REQUEST
states (the code block and the lost block) and never from the padded or
batched shapes the program dispatches; ``bytes_model.py``'s rule for a
seal, here for the repair of one whole lost block of a Clay code
(``reference_clay.py``).

A whole block of any shard is rebuilt from beta = alpha / q sub-chunks
of each of its d helpers (``reference_clay.repair_reads``) and gives
the block: (d beta + alpha) sub-chunks.  Under Clay(10,4,13) with 1 MiB
blocks, 13 x 256 KiB in and 1 MiB out, 4,456,448 bytes, the
``bytes_per_count`` of ``metrics/clay_roofline.read.json``; the same
for every shard, data or parity.
"""

from __future__ import annotations

from benchmark.reference_clay import Geometry, repair_reads


def repair_bytes(code: dict, sid: int) -> int:
    """Bytes read plus bytes written by the repair of one whole lost
    small block of shard ``sid``."""
    g = Geometry(code)
    if not 0 <= sid < g.k + g.m:
        raise ValueError(f"shard {sid} is not one of the code's "
                         f"{g.k + g.m}")
    return (len(repair_reads(code, sid)) + g.alpha) * g.sub
