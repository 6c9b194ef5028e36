"""BENCHMARK.json and the data files it names. The harness finds a
configuration, a traffic mix, a duty kind, a metric and its reader BY NAME:
a later PR adds a cell, a configuration, a kind of duty or a per-layer
metric by adding files and one manifest entry, and edits nothing here."""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pkgutil
import re
from pathlib import Path

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
LAST_LINE_KEYS = ("correct", "attempted", "failed", "metrics", "device")


class ManifestError(ValueError):
    pass


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    reader: str
    params: dict
    layer: str | None = None
    moves: str | None = None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    config_file: Path
    traffic_name: str
    traffic: dict
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


def _json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except OSError as e:
        raise ManifestError(f"{path}: {e}") from e
    except ValueError as e:
        raise ManifestError(f"{path}: not JSON: {e}") from e


def load_manifest(root: Path) -> dict:
    return _json(root / "BENCHMARK.json")


def bench_dir(root: Path, manifest: dict) -> Path:
    return root / manifest["paths"][0]


def _metric(bdir: Path, entry: dict) -> Metric:
    """The manifest's entry joined with benchmark/metrics/<name>.json,
    which names the reader and its parameters."""
    spec = _json(bdir / "metrics" / f"{entry['name']}.json")
    for key in ("unit", "better", "source"):
        if spec.get(key) != entry.get(key):
            raise ManifestError(
                f"metric {entry['name']}: {key} differs between BENCHMARK.json "
                f"({entry.get(key)!r}) and its file ({spec.get(key)!r})")
    return Metric(
        name=entry["name"], unit=entry["unit"], better=entry["better"],
        source=entry["source"], reader=spec["reader"],
        params=dict(spec.get("params", {})),
        layer=entry.get("layer"), moves=entry.get("moves"),
    )


def load_cell(root: Path, name: str, manifest: dict | None = None) -> Cell:
    manifest = manifest or load_manifest(root)
    bdir = bench_dir(root, manifest)
    by_name = {w["name"]: w for w in manifest["workloads"]}
    if name not in by_name:
        raise ManifestError(f"no workload {name!r} in BENCHMARK.json "
                            f"(have: {', '.join(sorted(by_name))})")
    w = by_name[name]
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == w["config"])
    config = _json(root / cfg_entry["file"])
    traffic = _json(bdir / "mixes" / f"{w['traffic']}.json")

    def wanted(entry):
        return "workloads" not in entry or name in entry["workloads"]

    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"], config=config,
        config_file=root / cfg_entry["file"],
        traffic_name=w["traffic"], traffic=traffic,
        end_to_end=tuple(_metric(bdir, e) for e in manifest["end_to_end"] if wanted(e)),
        per_layer=tuple(_metric(bdir, e) for e in manifest["per_layer"] if wanted(e)),
    )


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(root: Path, manifest: dict, reader: str):
    """benchmark/readers/<reader>.py -> its `read(run, **params)`."""
    path = bench_dir(root, manifest) / "readers" / f"{reader}.py"
    if not path.exists():
        raise ManifestError(f"no reader {reader!r} at {path}")
    return _module(path, f"bench_reader_{reader}").read


def load_duty(kind: str, bdir: Path | None = None):
    """duties/<kind>.py of the benchmark's directory (default: the one this
    file is in) -> the module a mix's `duties` names; README.md, "Adding
    things", says what it gives. One module a kind and a directory, however
    many plans ask for it."""
    return _load_duty(str(kind), Path(bdir or Path(__file__).parent).resolve())


@functools.lru_cache(maxsize=None)
def _load_duty(kind: str, bdir: Path):
    path = bdir / "duties" / f"{kind}.py"
    if not NAME.match(kind) or not path.exists():
        raise ManifestError(f"no duty kind {kind!r} at {path}")
    return _module(path, f"bench_duty_{kind}")


def unresolved(names) -> list[str]:
    """Of a configuration's `requires` — dotted names of what it needs of
    the program (`package.module.Class.attribute`) — those that do not
    resolve: the longest importable prefix, then attributes."""
    missing = []
    for name in names:
        try:
            pkgutil.resolve_name(str(name))
        except (ImportError, AttributeError, ValueError):
            missing.append(str(name))
    return missing


def validate(manifest: dict) -> list[str]:
    """The contract's limits on names and units that a file can break
    unseen; returns the faults found."""
    faults = []

    def name_ok(what, value):
        if not isinstance(value, str) or not NAME.match(value):
            faults.append(f"{what}: bad name {value!r}")

    for c in manifest.get("configs", []):
        name_ok("config", c.get("name"))
        for key in c.get("reduced", []):
            name_ok(f"config {c.get('name')} reduced", key)
    seen = set()
    for w in manifest.get("workloads", []):
        name_ok("workload", w.get("name"))
        name_ok("traffic", w.get("traffic"))
        if w.get("chips") not in (1, 4):
            faults.append(f"workload {w.get('name')}: chips {w.get('chips')!r}")
        pair = (w.get("config"), w.get("traffic"))
        if pair in seen:
            faults.append(f"workload pair {pair} appears twice")
        seen.add(pair)
        if not 1 <= len(w.get("why", "")) <= 200:
            faults.append(f"workload {w.get('name')}: why is not 1..200 characters")
    names = set()
    e2e = {m.get("name") for m in manifest.get("end_to_end", [])}
    for group in ("end_to_end", "per_layer"):
        for m in manifest.get(group, []):
            name_ok(group, m.get("name"))
            if m.get("name") in names:
                faults.append(f"metric {m.get('name')} appears twice")
            names.add(m.get("name"))
            if not UNIT.match(str(m.get("unit", ""))):
                faults.append(f"metric {m.get('name')}: bad unit {m.get('unit')!r}")
            if m.get("better") not in ("lower", "higher"):
                faults.append(f"metric {m.get('name')}: better {m.get('better')!r}")
            if m.get("source") not in SOURCES:
                faults.append(f"metric {m.get('name')}: source {m.get('source')!r}")
            if group == "per_layer" and m.get("moves") not in e2e:
                faults.append(f"metric {m.get('name')}: moves {m.get('moves')!r}")
    if "setup_s" not in e2e:
        faults.append("no setup_s among end_to_end")
    return faults
