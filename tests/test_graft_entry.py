"""The driver's two entry points (__graft_entry__.py) on the virtual CPU
devices tests/conftest.py sets up."""

import json

import __graft_entry__ as graft


def test_entry_lowers_the_flat_row_parity_kernel():
    fn, rows = graft.entry()
    assert len(rows) == 10 and all(r.shape == (262144,) for r in rows)
    text = fn.lower(*rows).as_text()
    # ten uint32 rows in, four parity rows out
    assert text.count("tensor<262144xui32>") >= 14


def test_dryrun_multichip_runs_the_served_path_on_eight_devices(capsys):
    graft.dryrun_multichip(8)
    lines = dict(ln.split(" ", 1)
                 for ln in capsys.readouterr().out.splitlines()
                 if ln.startswith("MULTICHIP_"))
    diag = json.loads(lines["MULTICHIP_DIAG"])
    assert diag["probe_ok"] and diag["n_devices"] >= 8
    sc = json.loads(lines["MULTICHIP_SCALING"])
    assert sc["bit_identical"] is True
    assert [r["devices"] for r in sc["rows"]] == [1, 2, 8]
