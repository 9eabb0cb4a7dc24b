"""chip_smoke.py rehearsed on the CPU at a tiny size: every phase runs
through the CLI servers, and the script still exits non-zero — naming
the device check — because what the volume server reports is not a TPU.
A phase made to fail exits non-zero with its name and the server logs'
tails on stdout.  (The pass itself can only be seen on the chip.)"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OK_LINE = '{"ok": true'


def _run(argv, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one CPU device, like one chip
    proc = subprocess.run([sys.executable, *argv], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)
    return proc.returncode, proc.stdout


def test_cpu_rehearsal_runs_every_phase_and_fails_the_device_check():
    rc, out = _run(["chip_smoke.py", "--volume-mib", "8"])
    assert rc != 0, out
    assert OK_LINE not in out
    assert "[device] FAILED: the coder's platform is 'cpu'" in out
    assert "FAILED phase(s): size, device" in out
    for phase in ("preflight", "servers", "load", "encode", "serve",
                  "degrade", "restore", "batcher", "stop", "kernels"):
        assert f"[{phase}] ok" in out, (phase, out)
    assert "sha256-equal to the reference" in out
    assert "redundancy brought back by" in out
    assert '"cpu_batches": 0' in out and '"coder_fallbacks": 0' in out
    assert "last 50 lines of volume.log" in out
    assert "[stop] volume exited with code 0" in out
    assert out.rstrip().splitlines()[-1].startswith("[summary] wall")


def test_a_failed_phase_is_named_with_the_server_log_tails():
    code = ("import sys, chip_smoke\n"
            "def boom(self):\n"
            "    raise RuntimeError('made to fail by the test')\n"
            "chip_smoke.Smoke.load = boom\n"
            "sys.exit(chip_smoke.main(['--volume-mib', '8']))\n")
    rc, out = _run(["-c", code])
    assert rc != 0, out
    assert OK_LINE not in out
    assert "[load] FAILED" in out and "made to fail by the test" in out
    assert "FAILED phase: load" in out
    assert "[encode] start" not in out  # nothing after the failed phase
    # the tails of both server logs, with what the servers logged
    assert "last 50 lines of master.log" in out
    assert "last 50 lines of volume.log" in out
    assert "volume server up at" in out
    # and every child stopped
    assert "[stop] volume exited" in out and "[stop] master exited" in out
