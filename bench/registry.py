"""Find a cell's pieces by name. Nothing here knows any cell.

``BENCHMARK.json`` names each cell's configuration and traffic mix; they are
``bench/configs/<config>.json`` and ``bench/traffic/<traffic>.json``. The
correctness limits of a cell are ``bench/limits/<cell>.json``, and each
per-layer metric is read by ``bench/metrics/<metric>.py``, whose ``read(ctx)``
returns a number, or None when the run gave it nothing to read.

A configuration file's ``"family"`` names ``bench/families/<family>.py``, the
code that computes that architecture; without the key it is
``dense_decoder``. A family module provides three functions:

- ``model_config(conf)``: the program's ``ModelConfig``; it raises on a file
  that names a mechanism the family does not compute, and is called when the
  cell is loaded, before any set-up;
- ``work_stack(conf)``: a ``work.Stack``, the widths the per-layer readers
  count operations and bytes with;
- ``forward(params, tokens, score_pos, conf, quantized)``: the plain float32
  reference logits (``quantized=True``: the float8 control), jitted by the
  family, which ``reference.logits`` pads, blocks and calls.

Adding a cell, a configuration, a family, a mix or a metric is adding files
and entries.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
import zlib
from types import ModuleType
from typing import Callable, Dict, List

ROOT = pathlib.Path(__file__).resolve().parents[1]
#: The family of a configuration file that names none.
DEFAULT_FAMILY = "dense_decoder"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: Dict
    mix: Dict
    limits: Dict
    end_to_end: List[Dict]      # the end-to-end metrics this cell reports
    per_layer: List[Dict]       # the per-layer metrics this cell reports
    family: ModuleType          # computes the configuration (``load_family``)


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
    conf = json.loads((bench / "configs" / f"{w['config']}.json").read_text())
    family = load_family(conf, root)
    family.model_config(conf)   # refuses a file it does not compute, before set-up
    return Cell(
        name=name,
        chips=int(w["chips"]),
        conf=conf,
        mix=json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads((bench / "limits" / f"{name}.json").read_text()),
        end_to_end=e2e,
        per_layer=per_layer,
        family=family,
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


def load_family(conf: Dict, root: pathlib.Path = ROOT) -> ModuleType:
    """The module ``bench/families/<family>.py`` that ``conf`` names, loaded
    once per file in a process (so its jitted reference is traced once), and
    entered in ``sys.modules`` (which a dataclass defined in it needs)."""
    name = conf.get("family", DEFAULT_FAMILY)
    path = pathlib.Path(root).resolve() / "bench" / "families" / f"{name}.py"
    key = f"bench_family_{name}_{zlib.crc32(str(path).encode()):08x}"
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[key]
        raise
    return mod
