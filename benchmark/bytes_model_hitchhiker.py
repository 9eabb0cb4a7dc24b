"""The least bytes a piggyback repair needs moved, from the sizes the
REQUEST states (the code block and the lost block) and never from the
padded or batched shapes the program dispatches; ``bytes_model.py``'s
rule for a seal, here for the repair of one whole lost block of a
Hitchhiker-XOR code (``reference_hitchhiker.py``).

A whole block of data shard s of group j is rebuilt from k + |group j|
half-blocks (``reference_hitchhiker.piggyback_reads``) and gives 2
half-blocks: under RS(10,4) with 1 MiB blocks, 13 x 512 KiB in and
2 x 512 KiB out for a shard of the first two groups, 7,864,320 bytes,
the ``bytes_per_count`` of ``metrics/hhxor_roofline.read.json``; 14 in
for a shard of the third (8,388,608 bytes).
"""

from __future__ import annotations

from benchmark.reference_hitchhiker import piggyback_reads


def piggyback_repair_bytes(code: dict, sid: int) -> int:
    """Bytes read plus bytes written by the repair of one whole lost
    small block of data shard ``sid``."""
    if not 0 <= sid < code["data_shards"]:
        raise ValueError(f"shard {sid} is not a data shard: a whole-block "
                         "repair of a parity has no piggyback plan")
    half = code["small_block_bytes"] // 2
    return (len(piggyback_reads(code, sid)) + 2) * half
