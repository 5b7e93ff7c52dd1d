"""What a run reads: ``BENCHMARK.json`` and the files its entries name.

A cell (``workloads`` entry) names a configuration (``configs/<name>.json``
by the ``file`` of its ``configs`` entry) and a traffic mix
(``traffic/<mix>.json``); its correctness limits are ``limits/<cell>.json``;
each metric is read by ``metrics/<metric>.py``; the configuration's
``"architecture"`` names the module ``arch/<architecture>.py`` that knows
its block (``model.module`` loads it and gives the interface it must
supply).  Nothing here knows any cell, mix, metric or architecture by name:
a later change adds one by adding files and entries.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> Dict:
    return load_json(root / "BENCHMARK.json")


def cell(bench: Dict, name: str, root: Path = ROOT) -> Dict:
    """The cell's entry with its configuration, traffic and limits loaded,
    and the metrics it reports (end-to-end and per-layer)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    conf = cfgs[w["config"]]
    here = root / BENCH.name
    return {"name": name, "chips": int(w["chips"]),
            "config": load_json(root / conf["file"]),
            "traffic": load_json(here / "traffic" / f"{w['traffic']}.json"),
            "limits": load_json(here / "limits" / f"{name}.json"),
            "end_to_end": metrics_for(bench["end_to_end"], name),
            "per_layer": metrics_for(bench["per_layer"], name)}


def metrics_for(entries: List[Dict], cell_name: str) -> List[Dict]:
    """Entries that list the cell, or list no cells at all."""
    return [m for m in entries
            if "workloads" not in m or cell_name in m["workloads"]]


def reader(metric: str, root: Path = ROOT):
    """The ``read(run) -> float | None`` of ``metrics/<metric>.py``."""
    path = root / BENCH.name / "metrics" / f"{metric}.py"
    sp = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read
