"""A deadline of the run's own: every phase is announced on stderr, held
to a budget, and on expiry the run ends at once with one well-formed
failing last line. Nothing waits out a compile or a hung teardown."""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import traceback


def failing_line(reason: str, device: dict | None = None, **extra) -> str:
    """The contract's last line for a run that has no result."""
    line = {
        "correct": False, "attempted": 0, "failed": 0, "metrics": {},
        "device": device or {"platform": "none", "kind": "none", "count": 0,
                             "memory_peak_bytes": 0},
        "error": reason,
    }
    line.update(extra)
    return json.dumps(line)


class Watchdog:
    """`with wd.phase("name", seconds):` around every phase. A thread
    checks the open phase and the whole run against their budgets."""

    def __init__(self, total_seconds: float, exit_code: int = 3,
                 out=sys.stdout, err=sys.stderr, exit_fn=os._exit):
        self.t0 = time.monotonic()
        self.total = total_seconds
        self.exit_code = exit_code
        self.out, self.err, self._exit = out, err, exit_fn
        self.device: dict | None = None
        self.dumpers: list = []  # callables -> str, printed on expiry
        self.phases: list[tuple[str, float, float | None]] = []
        self._open: tuple[str, float, float] | None = None
        self._lock = threading.Lock()
        self._dead = False
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True,
                                        name="bench-watchdog")
        self._thread.start()

    def note(self, text: str) -> None:
        print(f"[bench +{time.monotonic() - self.t0:7.2f}s] {text}",
              file=self.err, flush=True)

    def phase(self, name: str, seconds: float):
        return _Phase(self, name, seconds)

    def extend_total(self, seconds: float) -> None:
        self.total = seconds

    def fail(self, reason: str, **extra) -> None:
        """End the run NOW: evidence to stderr, the failing last line to
        stdout, then exit without running any teardown."""
        with self._lock:
            if self._dead:
                return
            self._dead = True
        self.note(f"FAIL: {reason}")
        for dump in self.dumpers:
            try:
                self.err.write(dump() + "\n")
            except Exception:  # noqa: BLE001 — evidence only
                traceback.print_exc(file=self.err)
        self.err.flush()
        extra.setdefault("phase_seconds", self.phase_seconds())
        print(failing_line(reason, self.device, **extra), file=self.out, flush=True)
        self._exit(self.exit_code)

    def phase_seconds(self) -> dict:
        """What every finished phase took, the open one so far (`open:`
        before its name) and the run as a whole (`run`): on a failing last
        line they say WHICH second ran out."""
        now = time.monotonic()
        out = {name: round(took, 2) for name, _start, took in self.phases}
        open_ = self._open
        if open_ is not None:
            out[f"open:{open_[0]}"] = round(now - open_[1], 2)
        out["run"] = round(now - self.t0, 2)
        return out

    def close(self) -> None:
        self._stop.set()

    def _watch(self) -> None:
        while not self._stop.wait(0.25):
            now = time.monotonic()
            open_ = self._open
            if open_ is not None and now > open_[2]:
                self.fail(f"phase '{open_[0]}' exceeded its budget of "
                          f"{open_[2] - open_[1]:.0f} s", phase=open_[0])
                return
            if now - self.t0 > self.total:
                name = open_[0] if open_ else "between phases"
                self.fail(f"run exceeded its own deadline of {self.total:.0f} s "
                          f"in phase '{name}'", phase=name)
                return


class _Phase:
    def __init__(self, wd: Watchdog, name: str, seconds: float):
        self.wd, self.name, self.seconds = wd, name, seconds

    def __enter__(self):
        now = time.monotonic()
        self.wd._open = (self.name, now, now + self.seconds)
        self.wd.note(f"phase {self.name}: start (budget {self.seconds:.0f} s)")
        return self

    def __exit__(self, exc_type, exc, tb):
        name, start, _ = self.wd._open or (self.name, time.monotonic(), 0)
        took = time.monotonic() - start
        self.wd._open = None
        self.wd.phases.append((name, start - self.wd.t0, took))
        self.wd.note(f"phase {name}: end after {took:.2f} s"
                     + (f" ({exc_type.__name__})" if exc_type else ""))
        return False


def task_stacks(loop) -> str:
    """The event loop's task stacks, for the expiry dump (called from
    the watchdog thread: reads only)."""
    import asyncio
    import io

    buf = io.StringIO()
    try:
        tasks = asyncio.all_tasks(loop)
    except RuntimeError:
        return "no tasks"
    buf.write(f"{len(tasks)} asyncio tasks:\n")
    for t in list(tasks)[:40]:
        frames = t.get_stack(limit=3)
        where = " <- ".join(
            f"{f.f_code.co_filename.rsplit('/', 1)[-1]}:{f.f_lineno}:{f.f_code.co_name}"
            for f in frames
        )
        buf.write(f"  {t.get_name()}: {where}\n")
    return buf.getvalue()
