"""Fresh-subprocess isolation for compile-heavy JAX test bodies.

This image's jaxlib flakily segfaults (de)serializing large XLA:CPU
executables to the persistent cache once a process has accumulated many
compiled programs (CI.md "Known environment flake") — the reliable
trigger is a fresh compile landing LATE in a program-heavy run. Tests
that would do that execute their body here instead: a fresh process with
the platform pinned to CPU (a test never claims an accelerator) and the
shared persistent cache.
"""

from __future__ import annotations

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ISOLATED_HEADER = """
import jax

jax.config.update("jax_platforms", "cpu")
# host-keyed CPU cache dir, same as conftest (charon_tpu/jaxcache.py) —
# isolated subprocesses and in-process tests must share entries
from charon_tpu import jaxcache as _jc

_jc.configure(jax, cpu=True)
"""


# Under REAL_PROGRAM_LIMIT, the `limit` of the tests that wait for such a
# child: the child dies before its parent's limit, and the assertion
# shows the child's stderr and not the parent's stack.
REAL_PROGRAM_LIMIT = 1200.0
DEFAULT_TIMEOUT = REAL_PROGRAM_LIMIT - 100.0


def run_isolated(
    script: str, marker: str, timeout: float = DEFAULT_TIMEOUT
) -> str:
    """Run `script` (usually ISOLATED_HEADER + body) in a fresh python;
    assert exit 0 and that `marker` was printed. Returns its stdout.
    A child that outlives `timeout` is killed, and the assertion says so
    with what it had written."""
    try:
        proc = subprocess.run(
            [sys.executable, "-c", script],
            capture_output=True,
            text=True,
            timeout=timeout,
            # tests/ on the path too: scripts share workload helpers with
            # their in-process siblings (e.g. tests/meshwork.py)
            env={
                **os.environ,
                "PYTHONPATH": REPO + os.pathsep + os.path.join(REPO, "tests"),
            },
            cwd=REPO,
        )
    except subprocess.TimeoutExpired as e:
        # what was read so far: bytes, or None
        out, err = (
            (x if isinstance(x, str) else (x or b"").decode(errors="replace"))[-2000:]
            for x in (e.stdout, e.stderr)
        )
        raise AssertionError(
            f"isolated test killed after its {timeout:g} s:\n{out}\n{err}"
        ) from None
    assert proc.returncode == 0, (
        f"isolated test failed rc={proc.returncode}:\n"
        f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
    )
    assert marker in proc.stdout
    return proc.stdout
