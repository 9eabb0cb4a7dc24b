"""The volume server with its timed path broken underneath, for the tests
that have to see ``correct`` come out false, and the control of the read
cell.  Same wrapper as ``benchmark/served_volume.py``; before it hands over
to the CLI it plants ONE fault in the program, by name:

    python -m benchmark.tests.faulty_volume <fault> <control-dir> volume ...

- ``unchanged``: from its second call on, ``Store.generate_ec_shards``
  returns without writing anything (a step that returns its state as it
  was);
- ``half_batch``: ``MeshCoder.encode_batch`` leaves the second half of its
  work out (of the lanes, or of the columns where there is one lane);
- ``half_batch_early``: the same, but only during the third and fourth
  seal of the process: what the window's LAST call leaves is sound, so
  only the look at every call can see it;
- ``altered_seal`` / ``altered_read``: one byte of what ``encode_batch`` /
  ``rebuild_batch`` produce is flipped where it is produced;
- ``xor_rebuild``: the CONTROL of the read cell: a lost block is rebuilt as
  the plain XOR of the survivors (single-parity arithmetic, cheaper than
  GF(2^8) multiply-accumulate and wrong for RS(10,4)).
"""

from __future__ import annotations

import sys


def plant(fault: str) -> None:
    import numpy as np
    from seaweedfs_tpu.ops.rs_mesh import MeshCoder
    from seaweedfs_tpu.storage.store import Store

    if fault == "unchanged":
        real = Store.generate_ec_shards
        calls = {"n": 0}

        def generate(self, vid, *a, **kw):
            calls["n"] += 1
            if calls["n"] == 1:
                return real(self, vid, *a, **kw)
            return self.find_volume(vid).file_name()
        Store.generate_ec_shards = generate
    elif fault in ("half_batch", "half_batch_early"):
        real_enc = MeshCoder.encode_batch
        real_gen = Store.generate_ec_shards
        seals = {"n": 0}

        def generate(self, vid, *a, **kw):
            seals["n"] += 1
            return real_gen(self, vid, *a, **kw)
        Store.generate_ec_shards = generate

        def encode_batch(self, batch):
            out = np.array(real_enc(self, batch))
            if fault == "half_batch_early" and seals["n"] not in (3, 4):
                return out
            if out.shape[0] > 1:
                out[out.shape[0] // 2:] = 0
            else:
                out[:, :, out.shape[2] // 2:] = 0
            return out
        MeshCoder.encode_batch = encode_batch
    elif fault == "altered_seal":
        real_enc = MeshCoder.encode_batch

        def encode_batch(self, batch):
            out = np.array(real_enc(self, batch))
            out[0, 0, 0] ^= 1
            return out
        MeshCoder.encode_batch = encode_batch
    elif fault == "altered_read":
        real_reb = MeshCoder.rebuild_batch

        def rebuild_batch(self, srcdata, mats):
            recs = [np.array(r) for r in real_reb(self, srcdata, mats)]
            for r in recs:
                r[0, 0] ^= 1
            return recs
        MeshCoder.rebuild_batch = rebuild_batch
    elif fault == "xor_rebuild":
        def rebuild_batch(self, srcdata, mats):
            self.programs.add(("apply",) + srcdata.shape)
            folded = np.bitwise_xor.reduce(srcdata, axis=1)
            return [np.repeat(folded[i][None], np.asarray(m).shape[0], 0)
                    for i, m in enumerate(mats)]
        MeshCoder.rebuild_batch = rebuild_batch
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    from benchmark import served_volume
    fault, control_dir = sys.argv[1], sys.argv[2]
    counted = served_volume.CompileCount()
    served_volume.warm(control_dir)   # the real programs, before the fault
    plant(fault)
    served_volume.serve(control_dir, sys.argv[3:], counted)
