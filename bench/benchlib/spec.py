"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each is a file of its
own (``configs/<config>.json`` through the configuration's ``file``,
``traffic/<traffic>.json``), the cell's correctness limits are in
``workloads/<cell>.json`` and each metric is read by
``metrics/<metric>.py``. Adding a cell, configuration or metric is
adding files and entries, never editing one of these.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class SpecError(ValueError):
    """BENCHMARK.json or one of the files it names is malformed."""


def _load(path: Path) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def _by_name(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"no {what} named {name!r} in BENCHMARK.json")


def _applies(metric: dict, cell: str, reported: List[str]) -> bool:
    """A metric with a ``workloads`` key is reported by those cells; a
    per-layer metric without one by every cell reporting what it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]        #: the configuration file, as run
    traffic: Dict[str, Any]       #: the traffic file
    limits: Dict[str, float]      #: workloads/<cell>.json "limits"
    end_to_end: List[dict]        #: BENCHMARK.json metrics this cell reports
    per_layer: List[dict]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _load(root / "BENCHMARK.json")
    wl = _by_name(bench["workloads"], name, "workload")
    cfg_entry = _by_name(bench["configs"], wl["config"], "config")
    config = _load(root / cfg_entry["file"])
    traffic = _load(root / "bench" / "traffic" / f"{wl['traffic']}.json")
    limits = _load(root / "bench" / "workloads" / f"{name}.json")["limits"]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, reported)]
    return Cell(name=name, chips=int(wl["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer)
