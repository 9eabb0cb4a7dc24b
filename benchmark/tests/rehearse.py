#!/usr/bin/env python3
"""Rehearsals and controls: ``run.py`` with the cells of
``benchmark/tests/cells.json`` (tiny sizes; the controls at full size),
another wrapper round the volume server, or the look for a chip off.

    JAX_PLATFORMS=cpu python3 benchmark/tests/rehearse.py \
        --workload tiny.seal.single --seed 1 --seconds 3 --trace 1
    python3 benchmark/tests/rehearse.py --require-platform tpu \
        --workload control.seal.single.lrc --seed 5 --seconds 5

Never a fallback of the benchmark: BENCHMARK.json's command is run.py,
which has none of these options.  The device named in the result is what
the volume server reported, so a CPU rehearsal says ``cpu``.
"""

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--manifest", default=os.path.join(HERE, "cells.json"))
    ap.add_argument("--wrapper", default="benchmark.served_volume")
    ap.add_argument("--require-platform", default="none")
    args, rest = ap.parse_known_args()
    return run.main(
        rest, manifest_path=args.manifest, volume_module=args.wrapper,
        require_platform=None if args.require_platform == "none"
        else args.require_platform)


if __name__ == "__main__":
    sys.exit(main())
