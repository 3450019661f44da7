"""Lookup of a cell's parts by name: everything is data under ``perfbench/``.

* ``BENCHMARK.json`` (checkout root): the cells and the metrics;
* ``configs/<config>.json``: one deployment;
* ``traffic/<traffic>.json``: one traffic mix, naming the entry it drives;
* ``limits/<workload>.json``: the limits of the cell's output check;
* ``metrics/<metric>.py``: one per-layer metric reader, ``read(ctx)``;
* ``harness/peaks.json``: the chip peaks, keyed by ``device_kind``.

A cell is added by adding files and a ``workloads`` entry; nothing here
names a cell.
"""
from __future__ import annotations

import importlib
import json
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]          # perfbench/
ROOT = HERE.parent                                  # the checkout


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return _load(root / "BENCHMARK.json")


def cell(name: str, bench: dict, base: Path = HERE) -> dict:
    """Everything one workload needs, resolved by name."""
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; have {sorted(work)}")
    w = work[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _load(base.parent / cfg["file"])
    traffic = _load(base / "traffic" / f"{w['traffic']}.json")
    lim = base / "limits" / f"{name}.json"
    limits = _load(lim)["limits"] if lim.exists() else {}
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [name])]
    return {"workload": w, "config": config, "traffic": traffic,
            "limits": limits, "end_to_end": e2e, "per_layer": layer}


def reader(metric: str):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    mod = importlib.import_module(f"perfbench.metrics.{metric}")
    return mod.read


def peaks(device_kind: str, base: Path = HERE) -> dict:
    table = _load(base / "harness" / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
