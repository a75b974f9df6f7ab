"""Loads ``BENCHMARK.json`` and the data files it names by convention:

    configs/<config>.json    a model configuration (registry arch,
                             overrides, the stated sizes, the cut)
    traffic/<traffic>.json   a traffic mix: the driver and its parameters
    limits/<cell>.json       the correctness limits of one cell
    metrics/<metric>.py      the reader of one per-layer metric
    families/<family>.py     a model family's reference, control and
                             step FLOPs, and its stated keys
    launches/<kind>.py       a kernel kind: its ``pallas_call`` name,
                             census dims, operations and bytes

A later cell, configuration, mix, metric, family or kernel kind is a new
file; nothing here names one of them.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib
import re
from typing import Optional

__all__ = [
    "HERE", "ROOT", "Cell", "Benchmark", "load", "load_metric_reader", "load_family",
    "load_launch", "launch_kinds",
]

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _check_name(kind: str, name: str) -> str:
    if not isinstance(name, str) or not _NAME.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    return name


def _read_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict  # configs/<config>.json
    traffic: dict  # traffic/<traffic>.json
    limits: dict  # limits/<cell>.json
    end_to_end: tuple  # BENCHMARK.json entries this cell reports
    per_layer: tuple


@dataclasses.dataclass(frozen=True)
class Benchmark:
    raw: dict

    def _applies(self, metric: dict, cell: str) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    def cell_names(self) -> list[str]:
        return [w["name"] for w in self.raw["workloads"]]

    def cell(self, name: str) -> Cell:
        match = [w for w in self.raw["workloads"] if w["name"] == name]
        if not match:
            raise KeyError(f"no workload {name!r}; known: {self.cell_names()}")
        w = match[0]
        cfg_entry = [c for c in self.raw["configs"] if c["name"] == w["config"]]
        if not cfg_entry:
            raise KeyError(f"workload {name!r} names unknown config {w['config']!r}")
        config = _read_json(ROOT / cfg_entry[0]["file"])
        traffic = _read_json(HERE / "traffic" / f"{_check_name('traffic', w['traffic'])}.json")
        limits = _read_json(HERE / "limits" / f"{_check_name('cell', name)}.json")
        return Cell(
            name=name,
            config_name=w["config"],
            traffic_name=w["traffic"],
            chips=int(w["chips"]),
            config=config,
            traffic=traffic,
            limits=limits,
            end_to_end=tuple(m for m in self.raw["end_to_end"] if self._applies(m, name)),
            per_layer=tuple(m for m in self.raw["per_layer"] if self._applies(m, name)),
        )


def load(path: Optional[pathlib.Path] = None) -> Benchmark:
    return Benchmark(_read_json(path or ROOT / "BENCHMARK.json"))


def _load_module(folder: str, kind: str, name: str, needs: str):
    """The module ``<folder>/<name>.py``, loaded by path; a missing one is
    an error that names the file to add and what it must define."""
    path = HERE / folder / f"{_check_name(kind, name)}.py"
    if not path.is_file():
        raise LookupError(f"no {kind} {name!r}: add {path.relative_to(ROOT)} with {needs}")
    mod_spec = importlib.util.spec_from_file_location(f"_{folder}_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_metric_reader(name: str):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    return _load_module("metrics", "metric", name, "read(run)").read


@functools.cache
def load_family(name: str):
    """``families/<name>.py``: the family's ``served_gaps`` and
    ``control_gaps``, ``prefill_flops`` and ``decode_flops``, and
    ``FIELDS``, its stated keys beyond the shared ones."""
    return _load_module(
        "families", "family", name,
        "served_gaps, control_gaps, prefill_flops, decode_flops and FIELDS")


@functools.cache
def load_launch(kind: str):
    """``launches/<kind>.py``: the kernel's ``pallas_call`` ``NAME``, and
    ``dims(shapes)``, ``flops(dims)`` and ``bytes(dims, dtype)``."""
    return _load_module("launches", "kernel kind", kind,
                        "NAME, dims(shapes), flops(dims) and bytes(dims, dtype)")


@functools.cache
def launch_kinds() -> dict:
    """``pallas_call`` name -> kernel kind, for every file in ``launches/``."""
    kinds = sorted(p.stem for p in (HERE / "launches").glob("*.py"))
    return {load_launch(k).NAME: k for k in kinds}
