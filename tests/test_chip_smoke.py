"""chip_smoke.py must keep walking its phases, and must never pass
without a chip.

The driver runs the script bare on a machine with a TPU; nothing else
runs it, so a refactor could break it unseen until a chip-minute budget
pays for the discovery. The rehearsal drives the same phases — server
child, import-roaring load, every query kind against the numpy
reference, the /debug proofs, clean stop — at 5 shards on the suite's
CPU devices. Its children are started with JAX_PLATFORMS=cpu, so they
leave a chip alone on a host that has one.
"""

import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd=REPO, script=SMOKE):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one CPU device, as on a plain host
    env.pop("PYTHONPATH", None)  # the script finds the repo beside itself
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_rehearsal_walks_every_phase_and_is_not_a_result():
    out = _run(["--rehearse", "cpu", "--shards", "5", "--workers", "2"])
    assert "all phases passed" in out.stdout, out.stdout[-3000:] + out.stderr[-3000:]
    assert out.returncode == 3
    assert '"ok"' not in out.stdout


def test_no_accelerator_fails_without_a_result():
    """Bare, as the driver runs it, where the server child comes up on
    CPU devices: refused on the platform /debug/diagnostics reports,
    before any data is loaded."""
    out = _run([])
    assert out.returncode not in (0, 3)
    assert "not 'tpu'" in out.stderr
    assert '"ok"' not in out.stdout and "load:" not in out.stdout


def test_alone_in_a_directory_fails_without_a_result(tmp_path):
    shutil.copy(SMOKE, tmp_path)
    out = _run([], cwd=tmp_path, script=str(tmp_path / "chip_smoke.py"))
    assert out.returncode not in (0, 3)
    assert out.stdout == ""
