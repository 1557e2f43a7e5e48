"""``chip_smoke.py`` must refuse any platform but TPU: a CPU run is never
reported as a chip run."""
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("alone", [False, True], ids=["checkout", "alone"])
def test_chip_smoke_refuses_cpu(tmp_path, alone):
    """On the CPU the script exits non-zero before building anything, names
    the platform it found, and prints no ``"ok": true`` result -- from the
    checkout and from a directory holding nothing else of the repo."""
    script = os.path.join(REPO, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, str(script)], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok": true' not in proc.stdout
