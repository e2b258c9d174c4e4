"""Find a cell's pieces by name. Nothing here knows any cell.

``BENCHMARK.json`` names each cell's configuration and traffic mix; they are
``bench/configs/<config>.json`` and ``bench/traffic/<traffic>.json``. The
correctness limits of a cell are ``bench/limits/<cell>.json``, and each
per-layer metric is read by ``bench/metrics/<metric>.py``, whose ``read(ctx)``
returns a number, or None when the run gave it nothing to read. Adding a
cell, a configuration, a mix or a metric is adding files and entries.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Callable, Dict, List

ROOT = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: Dict
    mix: Dict
    limits: Dict
    end_to_end: List[Dict]      # the end-to-end metrics this cell reports
    per_layer: List[Dict]       # the per-layer metrics this cell reports


def _reports(metric: Dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    root = pathlib.Path(root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {sorted(cells)}")
    w = cells[name]
    bench = root / "bench"
    e2e = [m for m in spec["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [
        m for m in spec["per_layer"]
        if (name in m["workloads"] if "workloads" in m
            else m["moves"] in e2e_names)
    ]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        conf=json.loads((bench / "configs" / f"{w['config']}.json").read_text()),
        mix=json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads((bench / "limits" / f"{name}.json").read_text()),
        end_to_end=e2e,
        per_layer=per_layer,
    )


def load_reader(metric: str, root: pathlib.Path = ROOT) -> Callable:
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = pathlib.Path(root) / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
