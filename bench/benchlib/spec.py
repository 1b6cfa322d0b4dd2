"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each is a file of its
own (``configs/<config>.json`` through the configuration's ``file``,
``traffic/<traffic>.json``), the cell's correctness limits are in
``workloads/<cell>.json``, each metric is read by
``metrics/<metric>.py``, and a configuration's architecture (its weight
layout, reference loss and FLOP count) is ``references/<reference>.py``
by the configuration's optional ``reference`` key, ``dense`` without
it. Adding a cell, configuration, metric or architecture is adding files
and entries, never editing one of these.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


#: what a configuration without a ``reference`` key is
DEFAULT_REFERENCE = "dense"
#: what every ``references/<name>.py`` provides
REFERENCE_API = ("param_layout", "loss_fn", "matmul_params",
                 "attention_flops_per_token", "PROGRAM_IMPLIED")
_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
_REFERENCES: Dict[str, ModuleType] = {}


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


def load_reference(name: str, root: Path = ROOT) -> ModuleType:
    """``<root>/bench/references/<name>.py``, loaded and checked; later
    look-ups by name (``reference_of``) return this module."""
    if not isinstance(name, str) or not _NAME.fullmatch(name):
        raise SpecError(f"reference {name!r} is not a name")
    path = root / "bench" / "references" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reference module {path} for reference "
                        f"{name!r}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_reference_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    missing = [a for a in REFERENCE_API if not hasattr(mod, a)]
    if missing:
        raise SpecError(f"reference module {path} lacks {missing}")
    _REFERENCES[name] = mod
    return mod


def reference_of(cfg: Dict[str, Any]) -> ModuleType:
    """The architecture module the configuration names: the one a
    ``load_cell`` or ``load_reference`` loaded under that name, else the
    checkout's own."""
    name = cfg.get("reference", DEFAULT_REFERENCE)
    mod = _REFERENCES.get(name)
    return mod if mod is not None else load_reference(name)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]        #: the configuration file, as run
    traffic: Dict[str, Any]       #: the traffic file
    limits: Dict[str, float]      #: workloads/<cell>.json "limits"
    end_to_end: List[dict]        #: BENCHMARK.json metrics this cell reports
    per_layer: List[dict]
    arch: ModuleType              #: references/<reference>.py


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _load(root / "BENCHMARK.json")
    wl = _by_name(bench["workloads"], name, "workload")
    cfg_entry = _by_name(bench["configs"], wl["config"], "config")
    config = _load(root / cfg_entry["file"])
    arch = load_reference(config.get("reference", DEFAULT_REFERENCE), root)
    traffic = _load(root / "bench" / "traffic" / f"{wl['traffic']}.json")
    limits = _load(root / "bench" / "workloads" / f"{name}.json")["limits"]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, reported)]
    return Cell(name=name, chips=int(wl["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer, arch=arch)
