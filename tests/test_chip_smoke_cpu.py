"""``chip_smoke.py`` on a host without a TPU: it must fail at its device
phase, exit non-zero and print no result line."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def test_chip_smoke_fails_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py"), "--out",
         str(tmp_path / "out")],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        if line.startswith("{"):
            assert not json.loads(line).get("ok"), line
    assert not (tmp_path / "out" / "summary.json").exists()
