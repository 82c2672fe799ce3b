"""`BENCHMARK.json` and the files it names, found by name.

A cell (`workloads` entry) names a configuration and a traffic mix.  The
configuration's file is the entry's `file`; the traffic mix is
`bench/traffic/<traffic>.json`.  Code is found by the name its data
gives, one file each: a metric's reader `bench/metrics/<name>.py`, an
arrival process or key chooser `bench/traffic/<name>.py`, an op
`bench/ops/<name>.py`, a control `bench/controls/<name>.py`, a data
generator `bench/datagen/<name>.py`, a deployment builder
`bench/deploys/<name>.py`.  Adding any of them adds files and entries
and edits none.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import pathlib
import re
from typing import Any, Dict, List

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class SpecError(ValueError):
    """A name, file or entry of the benchmark that cannot be used."""


def load_benchmark(root: pathlib.Path = ROOT) -> Dict[str, Any]:
    """The parsed `BENCHMARK.json` at `root`."""
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _by_name(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    """The `workloads` entry named `name`."""
    return _by_name(bench["workloads"], name, "workload")


def config(bench: dict, name: str, root: pathlib.Path = ROOT) -> dict:
    """The configuration named `name`: its entry's file, parsed."""
    entry = _by_name(bench["configs"], name, "configuration")
    with open(root / entry["file"]) as f:
        cfg = json.load(f)
    if cfg.get("name") != name:
        raise SpecError(f"{entry['file']} names {cfg.get('name')!r}, "
                        f"not {name!r}")
    return cfg


def traffic(name: str, bench_dir: pathlib.Path = BENCH_DIR) -> dict:
    """The traffic mix `bench/traffic/<name>.json`, parsed."""
    path = bench_dir / "traffic" / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no traffic file {path}")
    with open(path) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def load_module(kind: str, name: str, bench_dir: pathlib.Path = BENCH_DIR):
    """Import `bench/<kind>/<name>.py` by file path (names may hold
    dots), once per process."""
    if not NAME_RE.match(name):
        raise SpecError(f"bad {kind} name {name!r}")
    path = bench_dir / kind / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(bench: dict, cell: str, traced: bool) -> List[dict]:
    """The metric entries a run of `cell` reports: the end-to-end ones
    untraced, the per-layer ones traced; an entry with a `workloads`
    list applies only to the cells it lists."""
    entries = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in entries
            if "workloads" not in m or cell in m["workloads"]]


def check_names(bench: dict) -> List[str]:
    """Every name and unit of `bench` that breaks the naming rules
    (empty when all are sound)."""
    bad = []
    for c in bench["configs"]:
        bad += [f"config {c['name']!r}"] if not NAME_RE.match(c["name"]) else []
        bad += [f"reduced key {k!r}" for k in c["reduced"]
                if not NAME_RE.match(k)]
    for w in bench["workloads"]:
        for key in ("name", "config", "traffic"):
            if not NAME_RE.match(w[key]):
                bad.append(f"workload {key} {w[key]!r}")
    for m in bench["end_to_end"] + bench["per_layer"]:
        if not NAME_RE.match(m["name"]):
            bad.append(f"metric {m['name']!r}")
        if not UNIT_RE.match(m["unit"]):
            bad.append(f"unit {m['unit']!r} of {m['name']}")
    return bad

