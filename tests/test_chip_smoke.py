"""``chip_smoke.py`` refuses to run, and claims nothing, without a TPU."""
import os
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")


def test_chip_smoke_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")], env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode != 0, r.stdout[-2000:]
    assert '"ok": true' not in r.stdout
    assert "needs a TPU" in r.stderr
