"""Find what a cell is made of by name.

``BENCHMARK.json`` at the root of the checkout names the cells; each
name leads to files of its own under ``bench/``:

* ``configs/<config>.json``: the sizes as run (the ``file`` of the
  configuration), and ``configs/<config>.py`` beside it: the plain
  reference, the parameter layout, and the model FLOP count;
* ``traffic/<traffic>.json``: the training job (generator ``kind``,
  strategy, optimizer, learning-rate schedule, learners, batch, lengths,
  distinct batches, the steps the window keeps dispatched ahead, and
  ``program``: further fields of the program's
  configuration, such as its ``comm_*`` knobs);
* ``generators/<kind>.py``, ``strategies/<strategy>.py``,
  ``optimizers/<optimizer>.py``, ``schedules/<schedule>.py``: the one
  generator a traffic kind reads, and the reference's own strategy,
  optimizer and learning rate, each named by the traffic file;
* ``metrics/<metric>.py``: one reader per per-layer metric;
* ``limits/<workload>.json``: the limits of the comparison that decides
  ``correct``.

A later change adds a cell, a configuration or a metric by adding such
files and entries; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
_LOADED = {}


def load_module(path: Path, prefix: str):
    """Import a file by path (its name may hold '-' and '.'), once."""
    path = Path(path).resolve()
    if path in _LOADED:
        return _LOADED[path]
    name = f"_bench_{prefix}_" + "".join(
        c if c.isalnum() else "_" for c in path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _LOADED[path] = mod
    return mod


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """Everything one ``--workload`` needs, found by name."""

    bench: Path
    spec: dict
    workload: dict
    config: dict            # the configuration's entry in BENCHMARK.json
    sizes: dict             # configs/<config>.json
    model: object           # configs/<config>.py
    traffic: dict           # traffic/<traffic>.json
    limits: dict            # limits/<workload>.json

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def end_to_end(self) -> list:
        return [m for m in self.spec["end_to_end"] if self.name in
                m.get("workloads", [self.name])]

    def per_layer(self) -> list:
        """Per-layer metrics this cell reports: those that list it, and
        those without a list whose end-to-end metric the cell reports."""
        mine = {m["name"] for m in self.end_to_end()}
        out = []
        for m in self.spec["per_layer"]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    out.append(m)
            elif m["moves"] in mine:
                out.append(m)
        return out

    def metric_reader(self, name: str):
        return self.part("metrics", name)

    def part(self, kind: str, name: str):
        """The module ``<kind>/<name>.py`` of the bench directory."""
        return load_module(self.bench / kind / f"{name}.py", kind)

    @property
    def strategy(self):
        return self.part("strategies", self.traffic["strategy"])

    @property
    def optimizer(self):
        return self.part("optimizers", self.traffic["optimizer"])

    @property
    def schedule(self):
        return self.part("schedules", self.traffic["schedule"])

    def batches(self, seed: int) -> list:
        """The traffic's ring of distinct host batches for ``seed``."""
        gen = self.part("generators", self.traffic["kind"])
        return gen.batches(self.traffic, self.model.input_width(self.sizes),
                           seed)


def load_cell(root: Path, workload: str, bench: Path = None) -> Cell:
    """The cell named ``workload`` of ``root/BENCHMARK.json``; ``bench``
    (default: this directory) holds its configuration, traffic, metric
    and limit files."""
    root = Path(root)
    bench = Path(bench) if bench is not None else BENCH
    spec = _read_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(by_name)})")
    wl = by_name[workload]
    conf = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    sizes_path = root / conf["file"]
    return Cell(
        bench=bench, spec=spec, workload=wl, config=conf,
        sizes=_read_json(sizes_path),
        model=load_module(sizes_path.with_suffix(".py"), "config"),
        traffic=_read_json(bench / "traffic" / f"{wl['traffic']}.json"),
        limits=_read_json(bench / "limits" / f"{workload}.json"))
