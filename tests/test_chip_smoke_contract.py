"""chip_smoke.py has no CPU mode: without a TPU it prints one
`{"ok": false, ...}` line and exits non-zero at the platform check."""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_to_run_without_a_chip():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    assert len(lines) == 1, proc.stdout  # the verdict and nothing else
    verdict = json.loads(lines[0])
    assert verdict["ok"] is False
    assert verdict["device"]["platform"] == "cpu"
