"""Find a cell's pieces by name: everything is data, found under this dir.

* ``BENCHMARK.json`` (checkout root): which metrics a cell reports;
* ``workloads/<cell>.json``: config, mix, rate or clients, chips, limits;
* ``configs/<config>.json``: published sizes, pool geometry, batch;
* ``traffic/<mix>.json``: the mix's parameters for ``traffic/gen.py``;
* ``metrics/<metric>.py``: one reader per metric, ``read(run) -> float |
  None`` (``None``: nothing to read in this run, the metric is left out);
* ``refs/<reference>.py``: the configuration's plain reference;
* ``peaks.json``: the chip's peaks by ``device_kind``;
* ``opnames.json``: which compiled programs make up which layer.

A later cell, config, mix or metric is new files here plus its entry in
``BENCHMARK.json``; nothing in the harness changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))


def _json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    spec: dict          # workloads/<cell>.json
    config: dict        # configs/<config>.json
    mix: dict           # traffic/<mix>.json
    end_to_end: list    # BENCHMARK.json entries this cell reports
    per_layer: list

    @property
    def chips(self) -> int:
        return int(self.spec["chips"])

    def metrics(self, trace: bool) -> list[dict]:
        return self.per_layer if trace else self.end_to_end


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ".", data: str = HERE) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, its data files found
    under ``data`` (this directory)."""
    bench = _json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json "
                       f"(cells: {sorted(cells)})")
    spec = _json(data, "workloads", f"{name}.json")
    entry = cells[name]
    for key in ("config", "traffic", "chips"):
        if spec[key] != entry[key]:
            raise ValueError(f"{name}: {key} is {spec[key]!r} in the cell "
                             f"file but {entry[key]!r} in BENCHMARK.json")
    config = _json(data, "configs", f"{spec['config']}.json")
    mix = _json(data, "traffic", f"{spec['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    layer = [m for m in bench["per_layer"] if _applies(m, name)]
    return Cell(name, spec, config, mix, e2e, layer)


def reader(metric: str, here: str = HERE):
    """The metric's reader: ``metrics/<metric>.py``'s ``read``."""
    path = os.path.join(here, "metrics", f"{metric}.py")
    return load_module(path, f"metric_{metric.replace('.', '_')}").read


def reference(config: dict, here: str = HERE):
    return load_module(os.path.join(here, "refs", f"{config['reference']}.py"),
                       f"ref_{config['reference']}")


def peaks(device_kind: str, here: str = HERE) -> dict:
    table = _json(here, "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"({sorted(table)})")
    return table[device_kind]


def opnames(here: str = HERE) -> dict[str, list[str]]:
    return _json(here, "opnames.json")["layers"]
