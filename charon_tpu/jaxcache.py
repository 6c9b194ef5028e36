"""Persistent XLA compile-cache placement.

XLA:CPU serializes ahead-of-time executables that embed the *compile*
machine's CPU feature list; loading one on a host with a different
feature set fails ("machine features don't match ... could SIGILL",
cpu_aot_loader.cc) and forces a full recompile. That is how the round-4
multichip dryrun timed out: a 578 MB cache primed on the TPU-window
host was useless on the driver's host, so the dryrun drowned in loader
errors while recompiling everything inside its timeout
(MULTICHIP_r04.json tail).

Placement rule:

* `JAX_COMPILATION_CACHE_DIR` set: that directory, for every platform.
  JAX reads the variable itself; `configure` sets no directory in code,
  so whoever launches the process (an operator, a harness that carries
  the cache between runs) decides where the entries live.
* unset, CPU-platform runs: `<repo>/.jax_cache/cpu-<fingerprint>`,
  keyed by a host fingerprint (hash of the /proc/cpuinfo flags line) —
  entries compiled on another machine are simply *invisible* instead of
  noisily rejected, and same-host re-runs still hit warm.
* unset, TPU-platform runs: `<repo>/.jax_cache`. Device programs are
  not host AOT code, so the entries are host-portable, and they are
  expensive to lose (minutes of compile per pairing program). The path
  is fixed because it is part of the cache key: a directory that moves
  never hits.

The tuner profile (core/autotune) and cache_stats() follow cache_dir().

Shared by tests/conftest.py, bench_common.py, __graft_entry__.py and
app/run.py so every harness on one host hits the same entries.
"""

from __future__ import annotations

import hashlib
import os

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SHARED = os.path.join(_ROOT, ".jax_cache")


def host_fingerprint() -> str:
    """Stable id for this host's CPU feature set (what the XLA:CPU AOT
    loader actually checks)."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    # sort: flag ORDER is not guaranteed stable across
                    # kernel versions, the feature SET is what matters
                    flags = " ".join(sorted(line.split(":", 1)[1].split()))
                    return hashlib.sha256(flags.encode()).hexdigest()[:12]
    except OSError:
        pass
    return "unknown-host"


def _env_dir() -> str | None:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or None


def cache_dir(cpu: bool) -> str:
    """Cache dir for the given effective platform (see module doc)."""
    env = _env_dir()
    if env:
        return env
    if cpu:
        return os.path.join(_SHARED, "cpu-" + host_fingerprint())
    return _SHARED


# Cache-effectiveness counters (ISSUE 18): jax emits a monitoring event
# per compilation that consulted the persistent cache and one per hit;
# misses = requests - hits. Registered once in configure(); the module
# stays importable without jax so app/metrics.py can scrape
# cache_stats() from any host process.
_EVENTS = {"hits": 0, "requests": 0}
_CONFIGURED_DIR: str | None = None


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _EVENTS["hits"] += 1
    elif event == "/jax/compilation_cache/compile_requests_use_cache":
        _EVENTS["requests"] += 1


def configure(jax_mod, *, cpu: bool) -> str:
    """Point jax's persistent compilation cache at the right dir (jax
    reads JAX_COMPILATION_CACHE_DIR itself: nothing is set over it).

    Must run before any compilation; safe before backend init."""
    global _CONFIGURED_DIR
    d = cache_dir(cpu)
    if _env_dir() is None:
        jax_mod.config.update("jax_compilation_cache_dir", d)
    jax_mod.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    if _CONFIGURED_DIR is None:
        from jax import monitoring

        monitoring.register_event_listener(_on_event)
    _CONFIGURED_DIR = d
    return d


def cache_stats() -> dict | None:
    """Persistent-cache effectiveness for this process: entry count and
    bytes on disk plus hit/miss counts since configure(). None until
    configure() ran (host-only processes have no compile cache to
    report — app/metrics.observe_compile_cache skips the gauges then).
    """
    if _CONFIGURED_DIR is None:
        return None
    entries = 0
    nbytes = 0
    try:
        for root, _dirs, files in os.walk(_CONFIGURED_DIR):
            for name in files:
                if name.endswith(".json") or name.endswith(".tmp"):
                    continue  # the tuner profile, not an XLA artifact
                entries += 1
                try:
                    nbytes += os.stat(os.path.join(root, name)).st_size
                except OSError:
                    pass
    except OSError:
        pass
    return {
        "dir": _CONFIGURED_DIR,
        "entries": entries,
        "bytes": nbytes,
        "hits": _EVENTS["hits"],
        "misses": max(0, _EVENTS["requests"] - _EVENTS["hits"]),
    }
