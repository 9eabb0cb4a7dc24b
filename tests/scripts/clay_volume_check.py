#!/usr/bin/env python3
"""The sealed volume of a Clay cell against ``benchmark/reference_clay.py``:
``hhxor_volume_check.py``'s check (it takes the reference of the
configuration's code family), under this name.

    python3 tests/scripts/clay_volume_check.py \
        --workload reads.degraded1.chunks.clay10-4 --seed N

On a machine with the chip; on the CPU backend with
``JAX_PLATFORMS=cpu`` and ``--manifest benchmark/tests/cells-clay10-4.json
--workload tiny.reads.degraded.chunks.clay10-4``.  Prints one JSON line:
``differing`` (shard ids, [] when every file is the reference's).
"""

import os
import runpy
import sys

if __name__ == "__main__":
    sys.exit(runpy.run_path(os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "hhxor_volume_check.py"))["main"]())
