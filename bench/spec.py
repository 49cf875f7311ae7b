"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration and a traffic mix; the configuration's file
is the one ``BENCHMARK.json`` gives, the traffic mix is
``bench/traffic/<traffic>.json``, every metric is read by
``bench/metrics/<metric name>.py`` and an LLM configuration's plain
reference and counts are ``bench/reference/<its "reference">.py``.  A later
cell, mix, metric or architecture is a new entry and new files: nothing
here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

ROOT = Path(__file__).resolve().parents[1]  # the checkout: BENCHMARK.json here
BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict  # the configuration file's contents
    traffic: dict  # the traffic file's contents
    end_to_end: List[dict]  # this cell's end-to-end metric entries
    per_layer: List[dict]  # this cell's per-layer metric entries
    bench_dir: Path  # where its files were found


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reported_by(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: Path = ROOT, bench_dir: Path = BENCH) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in spec["end_to_end"] if _reported_by(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [
        m for m in spec["per_layer"]
        if name in m.get("workloads", ()) or (
            "workloads" not in m and m["moves"] in reported
        )
    ]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer, bench_dir)


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, bench_dir: Path = BENCH) -> Callable:
    """The ``read(run) -> float | None`` function of ``metrics/<metric>.py``."""
    return _load(bench_dir / "metrics" / f"{metric}.py", f"bench_metric_{metric}").read


def reference(config: dict, bench_dir: Path = BENCH):
    """The module ``reference/<name>.py`` that an LLM configuration names by
    its ``"reference"`` key, which has no default.  It gives the program's
    ``ModelConfig`` fields, the seeded weights, the plain reference and its
    lower-precision control, and the counts of operations and bytes that
    the readers divide by."""
    name = config["reference"]
    return _load(bench_dir / "reference" / f"{name}.py", f"bench_reference_{name}")


def read_metrics(entries: List[dict], run, bench_dir: Path = BENCH) -> Dict[str, dict]:
    """Each entry's value as its reader computes it from ``run``; a reader
    that finds nothing to read leaves its metric out."""
    out = {}
    for m in entries:
        v = reader(m["name"], bench_dir)(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
