"""Startup kernel auto-tuner + ahead-of-time compile-artifact cache.

ROADMAP item 3: three device-side multipliers (Pippenger/Straus MSM,
int8-MXU Montgomery, fused-Fp2 Pallas) are built and validated but were
hand-toggled per deployment via env vars. This module makes kernel
choice SELF-TUNING and cold start ARTIFACT-CACHED:

  * `KernelConfig` — the one typed source of truth for kernel routing.
    `apply()` pushes it into the trace-time dispatch flags
    (`ops/msm.set_msm`, `ops/limb.set_mxu`/`set_pallas`,
    `ops/fptower.set_fp2_fusion`) and drops the jitted-kernel caches so
    the flip actually takes effect. The legacy `CHARON_MSM` /
    `CHARON_MXU_MONT` env toggles are folded in as explicit overrides
    (`env_overrides`) that outrank the tuned profile — the ops/ hot
    paths no longer read the environment.

  * `resolve()` — the startup tuner. It walks
    `core/cryptoplane.kernel_inventory()` (the PR 11 registry of engine
    families + mesh program variants), micro-benches each CANDIDATE
    axis on canonical bucket-ladder shapes for the detected platform,
    and persists the winning profile (JSON, schema-versioned, keyed by
    platform + jax version + the same `ops/*.py` + `parallel/mesh.py`
    source digest the blessed kernel manifest uses —
    `analysis/jaxpr_check.source_digest`, reused, not duplicated) next
    to the jit cache managed by `jaxcache.py`. A second boot loads the
    profile, SKIPS the micro-bench, and dispatches warm; a stale digest
    (kernel sources actually changed) falls back to re-tune.

  * `aot_prewarm()` — the compile-artifact story. After tuning, the
    chosen variants are lowered + compiled for the prewarm shape ladder
    so the persistent compilation cache absorbs the binaries; the next
    boot replays those compiles as cache loads (seconds, not the 327 s
    XLA:CPU measured cold for one h2c program — PERF.md).

Failure policy (app/run.py wiring): tuning failures degrade to
`KernelConfig()` defaults and never block boot. Hosts without jax skip
loudly in `auto` mode and raise `PlaneConfigError` in `on`/`force`
(asking for a device tune without a device stack is a deploy mistake).
All timing in this module uses the monotonic clocks (core/ invariant).
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable

from charon_tpu.app import log

# Canonical micro-bench / prewarm shapes: the blsops bucket ladder the
# coalescer pads to (4-lane floor; parallel/mesh.py prewarms the same
# 256-lane burst ceiling).
TUNE_LANES = 8
TUNE_REPS = 3
PREWARM_LANES = (4, 16, 64, 256)

PROFILE_VERSION = 1
PROFILE_BASENAME = "autotune_profile.json"
# written by mark_prewarmed() after a SUCCESSFUL crypto-plane prewarm;
# warm_boot_ready requires it (a warm micro-bench cache alone does not
# make the duty pairing programs cheap)
PREWARM_MARKER_BASENAME = "prewarm_complete.json"
# Append-only field ledger (mirrors analysis/schema_check.py): existing
# fields never move or vanish, new fields append, and a NEW field may
# only join PROFILE_REQUIRED together with a version bump. The blessed
# snapshot lives in tests/testdata/autotune_schema.json and
# tests/test_autotune.py gates the contract with a seeded-violation
# battery.
PROFILE_FIELDS = (
    "version",
    "platform",
    "jax_version",
    "source_digest",
    "host",
    "config",
    "sources",
    "timings",
    "families",
    "tune_lanes",
    "prewarm_lanes",
)
PROFILE_REQUIRED = (
    "version",
    "platform",
    "jax_version",
    "source_digest",
    "config",
)

# Legacy env toggles, folded in as explicit KernelConfig overrides
# (deploy-pinned; they outrank the tuned profile). Kept for the dryrun
# env contract (CI.md pins CHARON_MSM=0 + CHARON_MXU_MONT=0) and live
# fleet rollbacks; new deployments should pin via --crypto-autotune.
_ENV_TOGGLES = (
    ("CHARON_MSM", "msm", lambda v: v != "0"),
    ("CHARON_MXU_MONT", "mxu_mont", lambda v: v == "1"),
)
_ENV_WARNED = False


class ProfileError(ValueError):
    """A kernel profile that cannot be used (typed-errors invariant:
    distinguishable from crypto/wire failures — the resolver degrades
    to defaults or re-tunes, never crashes the boot path on one).

    `reason` is one of: missing | unreadable | corrupt | schema |
    version.
    """

    def __init__(self, reason: str, msg: str):
        super().__init__(msg)
        self.reason = reason


@dataclass(frozen=True)
class KernelConfig:
    """Typed kernel-routing choice — THE source of truth the tuner,
    the env overrides, and the CLI all resolve into.

    `pallas` keeps three-state semantics (None = auto: on for the
    uint32 geometry on a real TPU backend) because forcing it on a CPU
    host would route into Mosaic kernels that cannot lower there; the
    tuner treats it as a platform fact, not a tunable axis.
    """

    msm: bool = True  # Straus joint windowed mul in threshold recombine
    mxu_mont: bool = False  # int8-MXU Montgomery decomposition
    fp2_fusion: bool = True  # fused-Fp2 Pallas kernels (needs pallas)
    pallas: bool | None = None  # None = auto (TPU + uint32 geometry)
    ceremony_straus: bool = True  # Straus vs per-lane in commitment eval
    ceremony_msm_w8: bool = True  # Pippenger window 8 (else 4) in g1_msm

    # the axes resolve()/micro_bench() may tune (bool-valued)
    TUNABLE = (
        "msm",
        "mxu_mont",
        "fp2_fusion",
        "ceremony_straus",
        "ceremony_msm_w8",
    )

    def apply(self) -> bool:
        """Push this config into the trace-time dispatch flags and drop
        the jitted-kernel caches (the flip is trace-time routing — a
        cached executable would silently ignore it). Returns False on
        hosts without jax, where there are no device kernels to route.
        """
        try:
            from charon_tpu.ops import blsops, fptower, limb
            from charon_tpu.ops import msm as MSM
        except ImportError:
            return False
        MSM.set_msm(self.msm)
        MSM.set_ceremony_straus(self.ceremony_straus)
        MSM.set_ceremony_window(8 if self.ceremony_msm_w8 else 4)
        limb.set_mxu(self.mxu_mont)
        limb.set_pallas(self.pallas)
        fptower.set_fp2_fusion(self.fp2_fusion)
        blsops.clear_kernel_caches()
        return True

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclass(frozen=True)
class Candidate:
    """One tunable axis: how to decide whether it applies on this
    platform/geometry and how to micro-bench a value for it.

    `builder(lanes)` must return a zero-arg closure that runs ONE
    device dispatch of a kernel dominated by this axis (first call
    compiles; see docs/development.md "add a tuner candidate").
    """

    field: str
    doc: str
    applicable: Callable[[], bool]
    builder: Callable[[int], Callable[[], None]]
    values: tuple = (True, False)


@dataclass(frozen=True)
class TuneResult:
    """What `resolve()` decided and why — the run.py lifecycle hook
    logs it and app/metrics.autotune_hook turns the observer events
    into counters."""

    config: KernelConfig
    outcome: str  # hit | tuned | off | skipped
    applied: bool  # False only on hosts without jax
    bench_runs: int  # 0 on a pure profile load
    sources: dict  # axis -> profile|tuned|env|default|inapplicable
    timings: dict  # axis -> {"on"/"off": seconds}
    overrides: dict  # env-derived field overrides in force
    profile_path: str | None


def env_overrides(environ=None) -> dict:
    """Explicit KernelConfig overrides from the legacy env toggles.

    Deploy-pinned and therefore ranked ABOVE the tuned profile: an
    operator who exported CHARON_MSM=0 to dodge a compiler regression
    must not have the tuner silently re-enable the kernel.
    """
    env = os.environ if environ is None else environ
    out = {}
    for var, field, decode in _ENV_TOGGLES:
        if var in env:
            out[field] = decode(env[var])
    return out


def apply_env(environ=None) -> KernelConfig:
    """Defaults + env overrides, applied. The entry point for harnesses
    that pin kernels by env instead of running the tuner
    (__graft_entry__'s canonical dryrun env)."""
    cfg = dataclasses.replace(KernelConfig(), **env_overrides(environ))
    cfg.apply()
    return cfg


# ---------------------------------------------------------------------------
# Candidate axes + their micro-bench kernels
# ---------------------------------------------------------------------------


def _recombine_builder(lanes: int, t: int = 3) -> Callable[[], None]:
    """Threshold recombination burst — the kernel whose routing the msm
    axis decides (blsops.threshold_recombine: Straus joint windowed mul
    vs per-lane double-and-add)."""
    import jax
    import numpy as np

    from charon_tpu.crypto.g1g2 import G2_GEN
    from charon_tpu.ops import blsops, limb
    from charon_tpu.ops import curve as C

    ctx, fr_ctx = limb.default_fp_ctx(), limb.default_fr_ctx()
    n = blsops.bucket_lanes(lanes)
    sig = C.g2_pack(ctx, [G2_GEN] * (n * t))
    sig = jax.tree_util.tree_map(
        lambda a: a.reshape((n, t) + a.shape[1:]), sig
    )
    idx = np.tile(np.arange(1, t + 1, dtype=np.int32), (n, 1))
    fn = jax.jit(
        lambda s, i: blsops.threshold_recombine(ctx, fr_ctx, t, s, i)
    )

    def run() -> None:
        jax.block_until_ready(fn(sig, idx))

    return run


def _mont_mul_builder(lanes: int) -> Callable[[], None]:
    """Stacked base-field Montgomery multiply — the kernel the mxu_mont
    axis reroutes (XLA conv / Pallas VMEM / int8-MXU Toeplitz)."""
    import jax
    import jax.numpy as jnp

    from charon_tpu.ops import blsops, limb

    ctx = limb.default_fp_ctx()
    n = blsops.bucket_lanes(lanes)
    a = jnp.asarray(
        limb.ctx_pack(
            ctx, [(i * 2654435761 + 1) % ctx.modulus for i in range(n)]
        )
    )
    fn = jax.jit(lambda x, y: limb.mont_mul(ctx, x, y))

    def run() -> None:
        jax.block_until_ready(fn(a, a))

    return run


def _fp2_batch_builder(lanes: int) -> Callable[[], None]:
    """Batched Fp2 mul/sqr level — fused Pallas kernels vs the stacked
    XLA path (fptower.fp2_batch)."""
    import jax
    import jax.numpy as jnp

    from charon_tpu.ops import blsops, fptower, limb

    ctx = limb.default_fp_ctx()
    n = blsops.bucket_lanes(lanes)
    a = jnp.asarray(limb.ctx_pack(ctx, [i + 1 for i in range(n)]))

    def level(x):
        e = (x, x)
        return fptower.fp2_batch(
            ctx, [("mul", e, e), ("sqr", e), ("mul", e, e), ("sqr", e)]
        )

    fn = jax.jit(level)

    def run() -> None:
        jax.block_until_ready(fn(a))

    return run


def _ceremony_eval_builder(lanes: int, t: int = 3) -> Callable[[], None]:
    """DKG commitment-polynomial evaluation wave — the kernel the
    ceremony_straus axis routes (blsops._commitment_eval_kernel: Straus
    joint windowed mul vs per-lane double-and-add + fold)."""
    import jax
    import numpy as np

    from charon_tpu.crypto.g1g2 import G1_GEN
    from charon_tpu.ops import blsops, limb
    from charon_tpu.ops import curve as C

    ctx, fr_ctx = limb.default_fp_ctx(), limb.default_fr_ctx()
    n = blsops.bucket_lanes(lanes)
    commits = C.g1_pack(ctx, [G1_GEN] * (n * t))
    commits = jax.tree_util.tree_map(
        lambda a: a.reshape((n, t) + a.shape[1:]), commits
    )
    xs = np.arange(1, n + 1, dtype=np.int32)
    fn = blsops._commitment_eval_kernel(ctx, fr_ctx, 1, t, 32)

    def run() -> None:
        jax.block_until_ready(fn(commits, xs))

    return run


def _ceremony_msm_builder(lanes: int) -> Callable[[], None]:
    """Segmented G1 MSM burst — the kernel the ceremony_msm_w8 axis
    sizes (Pippenger bucket window 8 vs 4 in blsops._g1_msm_kernel)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from charon_tpu.crypto.g1g2 import G1_GEN
    from charon_tpu.ops import blsops, limb
    from charon_tpu.ops import curve as C

    ctx, fr_ctx = limb.default_fp_ctx(), limb.default_fr_ctx()
    n = blsops.bucket_lanes(lanes)
    pts = C.g1_pack(ctx, [G1_GEN] * n)
    scalars = jnp.asarray(
        limb.ctx_pack(fr_ctx, [i + 1 for i in range(n)])
    )
    seg = jnp.asarray(np.zeros(n, dtype=np.int32))
    fn = blsops._g1_msm_kernel(ctx, fr_ctx, 1, 255)

    def run() -> None:
        jax.block_until_ready(fn(pts, scalars, seg))

    return run


def _always(_=None) -> bool:
    return True


def _mxu_applicable() -> bool:
    from charon_tpu.ops import limb

    # the int8-MXU decomposition only exists for the 12-bit geometry
    # (the CPU-fallback profile packs 24-bit limbs — bench.py guards
    # the same way)
    return limb.default_fp_ctx().limb_bits == 12


def _fp2_applicable() -> bool:
    from charon_tpu.ops import limb

    # fusion only reroutes anything when the Pallas rung is live
    return limb._pallas_active(limb.default_fp_ctx())


CANDIDATES: dict[str, Candidate] = {}


def register_candidate(cand: Candidate) -> None:
    """Register a tunable axis (idempotent by field name). New kernels
    register here instead of growing another env var — see
    docs/development.md."""
    if cand.field not in KernelConfig.TUNABLE:
        raise ValueError(
            f"candidate field {cand.field!r} is not a tunable "
            f"KernelConfig axis {KernelConfig.TUNABLE}"
        )
    CANDIDATES[cand.field] = cand


register_candidate(
    Candidate(
        field="msm",
        doc="Straus joint windowed mul vs per-lane double-and-add",
        applicable=_always,
        builder=_recombine_builder,
    )
)
register_candidate(
    Candidate(
        field="mxu_mont",
        doc="int8-MXU Montgomery decomposition vs Pallas/XLA mont_mul",
        applicable=_mxu_applicable,
        builder=_mont_mul_builder,
    )
)
register_candidate(
    Candidate(
        field="fp2_fusion",
        doc="fused-Fp2 Pallas kernels vs stacked-XLA fp2 level",
        applicable=_fp2_applicable,
        builder=_fp2_batch_builder,
    )
)
register_candidate(
    Candidate(
        field="ceremony_straus",
        doc="Straus joint mul vs per-lane in DKG commitment eval",
        applicable=_always,
        builder=_ceremony_eval_builder,
    )
)
register_candidate(
    Candidate(
        field="ceremony_msm_w8",
        doc="Pippenger window 8 vs 4 in ceremony segmented G1 MSM",
        applicable=_always,
        builder=_ceremony_msm_builder,
    )
)


def _label(value) -> str:
    if value is True:
        return "on"
    if value is False:
        return "off"
    return str(value)


def micro_bench(
    candidates=None,
    lanes: int = TUNE_LANES,
    reps: int = TUNE_REPS,
    base: KernelConfig | None = None,
    observer=None,
):
    """Greedily tune each applicable candidate axis: apply the value,
    rebuild + compile the axis's bench kernel, time `reps` dispatches
    (min wins), carry the winner into the next axis's baseline.

    Returns (choices, timings, bench_runs) where choices maps field ->
    (winning value, source) and source is "tuned" or "inapplicable".
    """
    obs = observer or (lambda kind, **fields: None)
    cfg = base or KernelConfig()
    choices: dict = {}
    timings: dict = {}
    bench_runs = 0
    for field, cand in (candidates or CANDIDATES).items():
        if not cand.applicable():
            choices[field] = (getattr(cfg, field), "inapplicable")
            continue
        per_value: dict = {}
        for value in cand.values:
            trial = dataclasses.replace(cfg, **{field: value})
            trial.apply()
            run = cand.builder(lanes)
            run()  # compile + warm (absorbed by the persistent cache)
            best = min(
                _timed(run) for _ in range(max(1, reps))
            )
            per_value[_label(value)] = best
            bench_runs += 1
            obs("bench", axis=field, choice=_label(value), seconds=best)
        win = min(cand.values, key=lambda v: per_value[_label(v)])
        cfg = dataclasses.replace(cfg, **{field: win})
        choices[field] = (win, "tuned")
        timings[field] = per_value
    return choices, timings, bench_runs


def _timed(run: Callable[[], None]) -> float:
    t0 = time.perf_counter()
    run()
    return time.perf_counter() - t0


def aot_prewarm(
    config: KernelConfig | None = None,
    lanes=PREWARM_LANES,
    candidates=None,
    observer=None,
) -> list[tuple[str, int, float]]:
    """Lower + compile the CHOSEN kernel variants across the prewarm
    shape ladder so the persistent compilation cache (jaxcache.py)
    absorbs the binaries. Cold, each entry pays a real XLA compile;
    warm, the same call replays as cache loads — which is the whole
    artifact story. Returns [(axis, bucket_lanes, seconds)]."""
    from charon_tpu.ops import blsops

    obs = observer or (lambda kind, **fields: None)
    if config is not None:
        config.apply()
    report = []
    for field, cand in (candidates or CANDIDATES).items():
        if not cand.applicable():
            continue
        for n in lanes:
            t0 = time.perf_counter()
            cand.builder(n)()
            dt = time.perf_counter() - t0
            bucket = blsops.bucket_lanes(n)
            report.append((field, bucket, dt))
            obs("prewarm", axis=field, lanes=bucket, seconds=dt)
    return report


# ---------------------------------------------------------------------------
# Profile persistence
# ---------------------------------------------------------------------------


def profile_schema() -> dict:
    """Current profile schema snapshot (tests/testdata/autotune_schema
    .json holds the blessed copy; compare_profile_schema gates it)."""
    return {
        "version": PROFILE_VERSION,
        "fields": list(PROFILE_FIELDS),
        "required": list(PROFILE_REQUIRED),
    }


def compare_profile_schema(golden: dict, current: dict) -> list[str]:
    """Append-only contract between profile writers and readers, in the
    analysis/schema_check.py style: a non-empty return is the CI
    failure message."""
    errs: list[str] = []
    gv, cv = int(golden["version"]), int(current["version"])
    if cv < gv:
        errs.append(f"profile schema version regressed: {gv} -> {cv}")
    gf, cf = list(golden["fields"]), list(current["fields"])
    if cf[: len(gf)] != gf:
        errs.append(
            "profile fields removed or reordered (append-only): "
            f"{gf} -> {cf}"
        )
    added_req = set(current["required"]) - set(golden["required"])
    if added_req and cv == gv:
        errs.append(
            f"new required field(s) {sorted(added_req)} need a schema "
            "version bump (old writers omit them)"
        )
    return errs


def fingerprint() -> dict:
    """The profile staleness key: platform + jax version + the SAME
    ops/mesh source digest the blessed kernel manifest is keyed by
    (analysis/jaxpr_check.source_digest — reused, not duplicated), plus
    the informational host fingerprint."""
    import jax

    from charon_tpu import jaxcache
    from charon_tpu.analysis.jaxpr_check import source_digest

    return {
        "platform": jax.default_backend(),
        "jax_version": jax.__version__,
        "source_digest": source_digest(),
        "host": jaxcache.host_fingerprint(),
    }


def default_profile_path() -> Path:
    """Next to the jit cache for this platform (jaxcache placement
    rules: CPU dirs are host-fingerprinted, TPU shares one dir)."""
    import jax

    from charon_tpu import jaxcache

    cpu = jax.default_backend() == "cpu"
    return Path(jaxcache.cache_dir(cpu)) / PROFILE_BASENAME


def load_profile(path) -> dict:
    """Read + validate a persisted profile. Raises ProfileError (typed;
    `reason` attribute) — never returns a half-usable dict."""
    p = Path(path)
    try:
        raw = p.read_text()
    except FileNotFoundError:
        raise ProfileError("missing", f"no kernel profile at {p}") from None
    except OSError as e:
        raise ProfileError("unreadable", f"kernel profile {p}: {e}") from e
    try:
        prof = json.loads(raw)
    except ValueError as e:
        raise ProfileError(
            "corrupt", f"kernel profile {p} is not valid JSON: {e}"
        ) from e
    if not isinstance(prof, dict):
        raise ProfileError("corrupt", f"kernel profile {p}: not an object")
    missing = [f for f in PROFILE_REQUIRED if f not in prof]
    if missing:
        raise ProfileError(
            "schema", f"kernel profile {p} missing fields {missing}"
        )
    if not isinstance(prof["version"], int) or prof["version"] < 1:
        raise ProfileError(
            "schema", f"kernel profile {p}: bad version {prof['version']!r}"
        )
    if prof["version"] > PROFILE_VERSION:
        raise ProfileError(
            "version",
            f"kernel profile {p} is v{prof['version']} (this build reads "
            f"<= v{PROFILE_VERSION})",
        )
    cfg = prof["config"]
    known = {f.name for f in dataclasses.fields(KernelConfig)}
    if not isinstance(cfg, dict) or not set(cfg) <= known:
        raise ProfileError(
            "schema", f"kernel profile {p}: bad config block {cfg!r}"
        )
    for k, v in cfg.items():
        if v is not None and not isinstance(v, bool):
            raise ProfileError(
                "schema", f"kernel profile {p}: config.{k}={v!r} not bool"
            )
    return prof


def save_profile(prof: dict, path) -> None:
    """Atomic write (tmp + rename) — a crash mid-save must leave either
    the old profile or none, never a truncated one. The tmp name is
    per-writer (pid): two nodes cold-booting against a shared cache dir
    must not interleave write_text/os.replace on ONE tmp file and
    publish a torn profile."""
    p = Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    tmp = p.with_name(f"{p.name}.{os.getpid()}.tmp")
    try:
        tmp.write_text(json.dumps(prof, indent=1, sort_keys=True) + "\n")
        os.replace(tmp, p)
    except BaseException:
        try:
            tmp.unlink()
        except OSError:
            pass
        raise


def staleness(prof: dict, fp: dict | None = None) -> str | None:
    """Why a loaded profile cannot be trusted on this boot (None =
    fresh). Host is informational only — kernel CHOICE is a platform
    fact, unlike the host-keyed XLA:CPU AOT artifacts."""
    fp = fp or fingerprint()
    for key in ("platform", "jax_version", "source_digest"):
        if prof.get(key) != fp[key]:
            return key
    return None


def prewarm_marker_path(path=None) -> Path:
    """The prewarm-completion marker lives NEXT TO the profile (same
    placement override rules), so wiping the cache dir wipes both."""
    p = Path(path) if path else default_profile_path()
    return p.with_name(PREWARM_MARKER_BASENAME)


def mark_prewarmed(path=None) -> Path:
    """Record that a crypto-plane prewarm COMPLETED under the current
    fingerprint (app/run.py writes this after a successful
    `crypto_plane.prewarm()`). This is the evidence `warm_boot_ready`
    needs: a fresh tuned profile only proves the tuner's micro-bench
    kernels are in the compile cache — the minutes-long duty pairing
    programs land there only once a real prewarm (or explicit
    `--crypto-plane-prewarm on` boot) has run to completion."""
    m = prewarm_marker_path(path)
    save_profile({"version": PROFILE_VERSION, **fingerprint()}, m)
    return m


def _read_marker(m: Path) -> dict | None:
    try:
        d = json.loads(m.read_text())
    except (OSError, ValueError):
        return None
    return d if isinstance(d, dict) else None


def warm_boot_ready(path=None) -> bool:
    """True when a fresh tuned profile AND a same-fingerprint prewarm
    marker exist — the signal that makes `--crypto-plane-prewarm auto`
    worthwhile off-TPU (app/run.py): prewarm then replays the duty
    pairing programs as cache loads, not compiles. A non-empty cache
    dir is NOT enough: after a first tuned boot it holds only the
    tuner's micro-bench/prewarm kernels, and flipping prewarm on would
    pay the full XLA:CPU pairing compiles the auto gate exists to
    avoid. The marker is written only after a prewarm actually
    completed (mark_prewarmed), and a platform/jax/source-digest change
    distrusts it exactly like the profile."""
    try:
        fp = fingerprint()
        p = Path(path) if path else default_profile_path()
        if staleness(load_profile(p), fp) is not None:
            return False
        mark = _read_marker(prewarm_marker_path(path))
        return mark is not None and staleness(mark, fp) is None
    except (ImportError, ProfileError, OSError):
        return False


# ---------------------------------------------------------------------------
# The startup resolver
# ---------------------------------------------------------------------------


def resolve(
    mode: str = "auto",
    path=None,
    *,
    observer=None,
    lanes: int = TUNE_LANES,
    reps: int = TUNE_REPS,
    candidates=None,
    bench=None,
    environ=None,
) -> TuneResult:
    """Resolve the kernel config for this boot and APPLY it.

    mode: "off" = defaults + env overrides, no profile IO, no bench;
    "auto"/"on" = load a fresh profile (pure load, zero bench runs) or
    micro-bench and persist one; "force" = always re-tune (re-bless a
    suspicious profile). On hosts without jax, "auto" skips loudly and
    "on"/"force" raise PlaneConfigError.

    `observer(kind, **fields)` receives "profile" (event=hit|miss|
    stale|corrupt|rebuilt|off|skipped), "decision" (axis/choice/
    source), "bench" and "prewarm" events — app/metrics.autotune_hook
    adapts them onto the counter families. `bench` injects a
    micro_bench-compatible callable (tests).
    """
    from charon_tpu.core.cryptoplane import PlaneConfigError

    global _ENV_WARNED
    if mode not in ("auto", "on", "off", "force"):
        raise PlaneConfigError(f"unknown autotune mode {mode!r}")
    obs = observer or (lambda kind, **fields: None)
    overrides = env_overrides(environ)
    if overrides and not _ENV_WARNED:
        _ENV_WARNED = True
        log.warn(
            "CHARON_MSM/CHARON_MXU_MONT env toggles are deprecated; they "
            "now act as KernelConfig overrides that outrank the tuned "
            "profile — prefer --crypto-autotune / set_* for harnesses",
            topic="autotune",
            overrides={k: v for k, v in sorted(overrides.items())},
        )
    sources = {f: "default" for f in KernelConfig.TUNABLE}

    if mode == "off":
        cfg = dataclasses.replace(KernelConfig(), **overrides)
        applied = cfg.apply()
        sources.update({f: "env" for f in overrides})
        obs("profile", event="off")
        _emit_decisions(obs, cfg, sources)
        return TuneResult(
            config=cfg,
            outcome="off",
            applied=applied,
            bench_runs=0,
            sources=sources,
            timings={},
            overrides=overrides,
            profile_path=None,
        )

    try:
        from charon_tpu.core.cryptoplane import kernel_inventory

        families = sorted(kernel_inventory())
        fp = fingerprint()
    except (ImportError, PlaneConfigError) as e:
        if mode in ("on", "force"):
            raise PlaneConfigError(
                f"--crypto-autotune {mode} requires the device stack: {e}"
            ) from e
        log.warn(
            "kernel auto-tune skipped: device stack unavailable on this "
            "host; running KernelConfig defaults",
            topic="autotune",
            err=str(e),
        )
        cfg = dataclasses.replace(KernelConfig(), **overrides)
        sources.update({f: "env" for f in overrides})
        obs("profile", event="skipped")
        _emit_decisions(obs, cfg, sources)
        return TuneResult(
            config=cfg,
            outcome="skipped",
            applied=cfg.apply(),
            bench_runs=0,
            sources=sources,
            timings={},
            overrides=overrides,
            profile_path=None,
        )

    p = Path(path) if path else default_profile_path()
    prof = None
    if mode != "force":
        try:
            prof = load_profile(p)
        except ProfileError as e:
            if e.reason == "missing":
                obs("profile", event="miss")
            else:
                log.warn(
                    "kernel profile unusable; re-tuning",
                    topic="autotune",
                    path=str(p),
                    reason=e.reason,
                    err=str(e),
                )
                obs("profile", event="corrupt")
        if prof is not None:
            stale = staleness(prof, fp)
            if stale is not None:
                log.info(
                    "kernel profile stale; re-tuning",
                    topic="autotune",
                    path=str(p),
                    key=stale,
                )
                obs("profile", event="stale")
                prof = None

    timings: dict = {}
    bench_runs = 0
    if prof is not None:
        obs("profile", event="hit")
        outcome = "hit"
        cfg = dataclasses.replace(
            KernelConfig(), **{k: v for k, v in prof["config"].items()}
        )
        sources.update({f: "profile" for f in KernelConfig.TUNABLE})
        timings = prof.get("timings", {})
    else:
        run_bench = bench or micro_bench
        choices, timings, bench_runs = run_bench(
            candidates=candidates,
            lanes=lanes,
            reps=reps,
            base=KernelConfig(),
            observer=obs,
        )
        cfg = dataclasses.replace(
            KernelConfig(), **{f: v for f, (v, _src) in choices.items()}
        )
        sources.update({f: src for f, (_v, src) in choices.items()})
        prof = dict(
            version=PROFILE_VERSION,
            **fp,
            config=cfg.as_dict(),
            sources={f: sources[f] for f in KernelConfig.TUNABLE},
            timings=timings,
            families=families,
            tune_lanes=lanes,
            prewarm_lanes=list(PREWARM_LANES),
        )
        save_profile(prof, p)
        obs("profile", event="rebuilt")
        outcome = "tuned"

    # deploy-pinned env overrides outrank whatever won above
    cfg = dataclasses.replace(cfg, **overrides)
    sources.update({f: "env" for f in overrides})
    applied = cfg.apply()
    _emit_decisions(obs, cfg, sources)
    return TuneResult(
        config=cfg,
        outcome=outcome,
        applied=applied,
        bench_runs=bench_runs,
        sources=sources,
        timings=timings,
        overrides=overrides,
        profile_path=str(p),
    )


def _emit_decisions(obs, cfg: KernelConfig, sources: dict) -> None:
    for field in KernelConfig.TUNABLE:
        obs(
            "decision",
            axis=field,
            choice=_label(getattr(cfg, field)),
            source=sources.get(field, "default"),
        )
