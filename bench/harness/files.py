"""Where the benchmark's data lives, and how a name finds its file.

Every cell, configuration, traffic generator, reference, weight layout and
metric reader is a file of its own under ``bench/``, found by the name that
``BENCHMARK.json`` or a cell's file gives it.  Adding one adds a file; no
file here changes.
"""

from __future__ import annotations

import importlib
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(name: str) -> dict:
    """A cell's entry in ``BENCHMARK.json`` with its own file (limits of
    the comparison) merged in."""
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return {**w, **_json("workloads", name + ".json")}
    raise KeyError(f"no cell named {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    """A traffic mix: the parameters its generator reads."""
    return _json("traffic", name + ".json")


def config(name: str) -> dict:
    return _json("configs", name + ".json")


def module(kind: str, name: str):
    """``traffic``, ``reference``, ``layouts`` or ``metrics`` module by name."""
    return importlib.import_module(f"{kind}.{name}")


def reader(metric: str):
    """A metric's reader: ``step_mfu.serve`` and ``step_mfu.offline`` share
    ``metrics/step_mfu.py``; the suffix only splits what each one moves."""
    return module("metrics", metric.split(".")[0])


def metrics_for(cell: str, trace: bool) -> list:
    """The cell's metrics from ``BENCHMARK.json``: end-to-end ones with
    ``trace`` off, per-layer ones with it on.  A metric without a
    ``workloads`` list belongs to every cell that reports what it moves."""
    b = benchmark()
    e2e = [m for m in b["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in b["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in names
                             else [])]
