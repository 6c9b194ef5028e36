"""Where the compile cache lives, and what a run found there.

The cache jax uses is ALWAYS `benchmark/.cache/jax` inside the checkout
(a fixed path; git-ignored). `jax_persistent_cache_enable_xla_caches` is
set to "none": jax 0.9.0 otherwise writes `<cache_dir>/xla_gpu_per_fusion_
autotune_cache_dir` into the compile options and so hashes the absolute
path of the cache directory into every key. A marker per cell lists the
keys of the cell's stored programs, with the versions and a hash of the
sources they were compiled from: a change to the program or the harness
voids the marker (the run compiles, with a compiling run's budget), and a
run that finds a marker of its own sources and still misses a plane
program fails at once instead of compiling for minutes."""

from __future__ import annotations

import hashlib
import json
import logging
import os
import re
import threading
from pathlib import Path

# plane programs are jit(shard_map(local...)): their module names start so
# (jit_local for verify and g1dec, jit_local_step for the recombine step)
PLANE_PREFIX = "jit_local"
LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
TRACED = "/jax/core/compile/jaxpr_trace_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"


def is_plane(module: str) -> bool:
    """`jit_local...` in the cache's log lines, `jit(local...)` in the
    monitoring events."""
    return module.replace("(", "_").startswith(PLANE_PREFIX)

_MISS = re.compile(r"CACHE MISS for '([^']+)' with key '([^']+)'")
_HIT = re.compile(r"cache hit for '([^']+)' with key '([^']+)'")
_WRITE = re.compile(r"Writing (\S+) to persistent compilation cache with key '([^']+)'")


class CacheLog(logging.Handler):
    """Reads jax's own DEBUG lines: which key missed, which was written."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.misses: list[tuple[str, str, int]] = []  # (module, key, thread)
        self.written: list[tuple[str, str]] = []
        self.hits: list[tuple[str, str, int]] = []
        self.on_plane_miss = None  # callable(module, key) | None

    def emit(self, record):
        text = record.getMessage()
        m = _MISS.search(text)
        if m:
            self.misses.append((m.group(1), m.group(2), threading.get_ident()))
            if is_plane(m.group(1)) and self.on_plane_miss is not None:
                self.on_plane_miss(m.group(1), m.group(2))
            return
        m = _HIT.search(text)
        if m:
            self.hits.append((m.group(1), m.group(2), threading.get_ident()))
            return
        m = _WRITE.search(text)
        if m:
            self.written.append((m.group(1), m.group(2)))


class Events:
    """jax.monitoring: cache requests and hits by compiling thread, and how
    long each plane program took to trace, to lower, and to compile."""

    def __init__(self):
        self.hits: dict[int, int] = {}
        self.requests: dict[int, int] = {}
        self.total_requests = 0
        self.durations: list[tuple[str, str, float, int]] = []
        self.on_plane_lowered = None  # callable(): a plane module is lowered

    def duration(self, event: str, seconds: float, **kw) -> None:
        name = str(kw.get("fun_name", ""))
        if event in (LOWERED, BACKEND) and not is_plane(name):
            return
        if event == TRACED and seconds < 1.0:
            return
        if event in (LOWERED, TRACED, BACKEND):
            self.durations.append((event.rsplit("/", 1)[-1], name, seconds,
                                   threading.get_ident()))
        if event == LOWERED and self.on_plane_lowered is not None:
            self.on_plane_lowered()

    def __call__(self, event: str, **_kw) -> None:
        me = threading.get_ident()
        if event == "/jax/compilation_cache/cache_hits":
            self.hits[me] = self.hits.get(me, 0) + 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests[me] = self.requests.get(me, 0) + 1
            self.total_requests += 1


def checkout_cache(root: Path) -> Path:
    return root / "benchmark" / ".cache" / "jax"


def marker_path(cache: Path, cell: str) -> Path:
    return cache / f"{cell}.marker.json"


def sources_hash(root: Path, config_file: Path) -> str:
    """What a plane program's key can change with, besides jax: every
    source file of the program and of the harness (a Pallas kernel carries
    its callers' file names and line numbers into the module), and the
    cell's configuration. The tests' files are on no tracing stack."""
    h = hashlib.sha256()
    files = sorted(
        p for top in ("charon_tpu", "benchmark") for p in (root / top).rglob("*.py")
        if "tests" not in p.relative_to(root).parts)
    for p in files:
        h.update(str(p.relative_to(root)).encode() + b"\0")
        h.update(hashlib.sha256(p.read_bytes()).digest())
    h.update(hashlib.sha256(config_file.read_bytes()).digest())
    return h.hexdigest()


def read_marker(cache: Path, cell: str, versions: dict, sources: str) -> dict | None:
    """The cell's marker, if its keys' files are all present and it was
    written by the same jax / jaxlib / libtpu from the same sources."""
    try:
        marker = json.loads(marker_path(cache, cell).read_text())
    except (OSError, ValueError):
        return None
    if marker.get("versions") != versions or marker.get("sources") != sources:
        return None
    keys = marker.get("keys", [])
    if not keys or not all((cache / f"{k}-cache").exists() for k in keys):
        return None
    return marker


def write_marker(cache: Path, cell: str, versions: dict, sources: str,
                 keys: list[str]) -> None:
    tmp = marker_path(cache, cell).with_suffix(".tmp")
    tmp.write_text(json.dumps({"cell": cell, "versions": versions, "sources": sources,
                               "keys": sorted(set(keys))}, indent=1))
    os.replace(tmp, marker_path(cache, cell))


def drop_marker(cache: Path, cell: str) -> None:
    """After a warm miss: the next run of this checkout compiles instead
    of failing the same way."""
    try:
        marker_path(cache, cell).unlink()
    except OSError:
        pass


def miss_reason(cell: str, marker: dict | None, in_window: bool, module: str,
                key: str) -> str | None:
    """Why a plane program's cache miss ends the run — or None where it
    may compile (the cell's compiling run, before the window)."""
    if in_window:
        return (f"a plane program ({module}) missed the compile cache INSIDE the "
                f"window: key {key}; nothing may compile there")
    if marker is not None:
        return (f"the cache holds {cell}'s marker, written from these very sources, "
                f"but a plane program missed: this run's key {key}, the marker's keys "
                f"{marker['keys']}; the marker is dropped, so the next run here compiles")
    return None


def configure(root: Path, environ=os.environ) -> tuple[Path, str | None]:
    """Before jax is imported: fix the cache inside the checkout, for the
    program too (charon_tpu/jaxcache.py sets no directory over the
    variable). Returns (cache, the directory the machine had named)."""
    cache = checkout_cache(root)
    cache.mkdir(parents=True, exist_ok=True)
    named = environ.get("JAX_COMPILATION_CACHE_DIR") or None
    environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    # the chip machine sets 192 MiB; one pairing program's entry is
    # 345-405 MB and would never be stored (lru_cache.py: put refuses it)
    environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    return cache, named


def configure_jax(jax) -> tuple[CacheLog, Events]:
    """After import, before any compile: the path leaves the key, and
    the cache's own log lines and events are read, not printed."""
    jax.config.update("jax_persistent_cache_enable_xla_caches", "none")
    jax.config.update("jax_compilation_cache_max_size", -1)
    log = CacheLog()
    for name in ("jax._src.compiler", "jax._src.compilation_cache"):
        lg = logging.getLogger(name)
        lg.setLevel(logging.DEBUG)
        lg.propagate = False
        lg.addHandler(log)
    events = Events()
    jax.monitoring.register_event_listener(events)
    jax.monitoring.register_event_duration_secs_listener(events.duration)
    return log, events
