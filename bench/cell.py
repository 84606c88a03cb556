"""Find a cell and everything it names, by name, from files alone.

``BENCHMARK.json`` names a workload's configuration and traffic mix;
each lives in a file of its own (``bench/configs/<config>.json``,
``bench/traffic/<mix>.json``), the mix names its entry
(``bench/entries/<entry>.py``) and the configuration its plain reference
(``bench/reference/<reference>.py``), and each per-layer metric is a
reader ``bench/metrics/<metric>.py``.  A later cell, configuration or
metric is new files and new entries, never an edit.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path

__all__ = ["BENCH", "Cell", "find_cell", "load_metric", "load_entry",
           "load_reference"]

BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    mix: dict               # bench/traffic/<traffic>.json
    end_to_end: list[dict]  # the BENCHMARK.json metrics this cell reports
    per_layer: list[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, bench: Path = BENCH) -> Cell:
    spec = json.loads((bench.parent / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(work)}")
    w = work[name]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((bench / "configs" / f"{w['config']}.json")
                          .read_text()),
        mix=json.loads((bench / "traffic" / f"{w['traffic']}.json")
                       .read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def load_metric(name: str, bench: Path = BENCH):
    """The reader module ``bench/metrics/<name>.py`` (names may hold
    dots, so it is loaded from its path)."""
    path = bench / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_entry(name: str):
    return importlib.import_module(f"bench.entries.{name}")


def load_reference(name: str):
    return importlib.import_module(f"bench.reference.{name}")
