"""What ``BENCHMARK.json`` and the files beside it say about one cell.

A cell names a configuration and a traffic mix; both, and every
per-layer metric, are found by name under ``chipbench/``:

  configs/<config>.json       sizes, fuel, limits, source, assumed, reduced
  configs/<config>_ref.py     seeded inputs, numpy reference, work counts
  configs/<config>_kernel.py  the kernel in VOLT's OpenCL dialect
  traffic/<traffic>.json      the parameters of one general driver
  metrics/<metric>.py         ``read(run)``: the metric, or None

Nothing here imports the program under test or JAX.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    ref: object                 # the config's reference module
    traffic: dict
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)
    readers: dict = field(default_factory=dict)

    def kernel(self):
        """The kernel handle; imports the program's front end."""
        path = HERE / "configs" / f"{self.config['name']}_kernel.py"
        return load_module(path, f"chipbench_kernel_{self.config['name']}"
                           ).KERNEL


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"cells: {sorted(cells)}")
    w = cells[name]
    cdir = HERE / "configs"
    config = json.loads((cdir / f"{w['config']}.json").read_text())
    ref = load_module(cdir / f"{w['config']}_ref.py",
                      f"chipbench_ref_{w['config']}")
    traffic = json.loads(
        (HERE / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    # a per-layer metric without a list of cells is read wherever the
    # end-to-end metric it moves is reported
    layer = [m for m in spec["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in moved)]
    readers = {m["name"]: load_module(HERE / "metrics" / f"{m['name']}.py",
                                      f"chipbench_metric_{m['name']}").read
               for m in layer}
    return Cell(name, w["chips"], config, ref, traffic, e2e, layer,
                readers)
