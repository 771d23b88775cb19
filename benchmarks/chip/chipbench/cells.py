"""Find a cell's pieces by name: ``BENCHMARK.json`` at the checkout's root
names the cell; its configuration, traffic mix, reference and per-layer
metrics are files of their own under ``benchmarks/chip``:

  configs/<config>.json      sizes, deployment, and the check's limit
  traffic/<traffic>.json     parameters of the general generator
  references/<ref>.py        the configuration's plain reference
  metrics/<metric>.py        one per-layer metric: ``read(ctx)``; a
                             metric ``<base>.<part>`` without a file of
                             its own is read by ``metrics/<base>.py``

Adding a cell, a mix, a configuration or a metric adds files and entries;
nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: "list[dict]"  # the metrics this cell reports, trace off
    per_layer: "list[dict]"  # ... and trace on
    here: str = HERE  # where its reference and metric files are found

    def module(self, kind: str, name: str):
        return load_module(kind, name, self.here)

    def metric(self, name: str):
        """The reader of per-layer metric ``name``."""
        if not os.path.exists(os.path.join(self.here, "metrics",
                                           name + ".py")):
            name = name.split(".")[0]
        return self.module("metrics", name)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: "str | None" = None,
              here: "str | None" = None) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, its files read from
    ``here`` (default: this checkout and its ``benchmarks/chip``)."""
    root, here = root or ROOT, here or HERE
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    config = load_json(os.path.join(here, "configs", w["config"] + ".json"))
    mix = load_json(os.path.join(here, "traffic", w["traffic"] + ".json"))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                mix=mix,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)], here=here)


def load_module(kind: str, name: str, here: str = HERE):
    """``<here>/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(here, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
