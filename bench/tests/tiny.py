"""A tiny copy of a cell for tests on the CPU: a checkout-like root with
its own ``BENCHMARK.json`` and a bench directory holding the tiny
configuration, the traffic, the metric readers and the limits."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
for p in (str(REPO / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

SIZES = {"arch": "swb2000-blstm", "input_dim": 12, "n_layers": 2,
         "d_model": 16, "lstm_hidden": 8, "lstm_bottleneck": 8,
         "vocab": 64, "param_dtype": "bfloat16", "kernel_impl": "jax",
         "frame_seconds": 0.01}
TRAFFIC = {"kind": "asr_frames", "strategy": "ad_psgd", "optimizer": "sgd",
           "schedule": "paper_recipe", "learners": 4,
           "batch_per_learner": 8, "frames": 5, "classes": 8, "noise": 0.5,
           "microbatches": 1, "distinct_batches": 4, "ahead_steps": 2,
           "lr": {"base": 0.5, "peak": 1.0, "steps_per_epoch": 10},
           "program": {}}
# the tiny cell's own limits: its leaves of a few hundred elements read
# the bfloat16 rounding of the state far more loudly than the cell's
LIMITS = {"loss_gap": 1e-5, "grad_gap": 0.15, "change_gap": 0.08}
# the limits the benchmark's cells are held to
COMMITTED = {p.stem: json.loads(p.read_text())
             for p in sorted((BENCH / "limits").glob("*.json"))}
# a made-up device for the readers that divide by a peak
PEAKS = {"source": "test", "devices": {"cpu": {
    "bf16_flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}}}


PARTS = ("metrics", "generators", "strategies", "optimizers", "schedules")


def make_root(tmp: Path, *, sizes=None, traffic=None, limits=None,
              name="tiny-cell") -> Path:
    """A root whose BENCHMARK.json names one cell ``name`` of the tiny
    configuration, with the benchmark's own generators, strategies,
    optimizers, schedules and metric readers; returns the root (its bench
    directory is root/bench)."""
    bench = tmp / "bench"
    for d in ("configs", "traffic", "limits"):
        (bench / d).mkdir(parents=True, exist_ok=True)
    for d in PARTS:
        shutil.copytree(BENCH / d, bench / d, dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH / "configs" / "swb2000-blstm.py",
                bench / "configs" / "tiny-blstm.py")
    (bench / "configs" / "tiny-blstm.json").write_text(
        json.dumps(sizes or SIZES))
    (bench / "traffic" / "tiny-mix.json").write_text(
        json.dumps(traffic or TRAFFIC))
    (bench / "limits" / f"{name}.json").write_text(
        json.dumps(limits or LIMITS))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny-blstm", "source": "test",
                        "file": "bench/configs/tiny-blstm.json",
                        "reduced": [], "why": "test"}]
    spec["workloads"] = [{"name": name, "config": "tiny-blstm",
                          "traffic": "tiny-mix", "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def fails_everywhere(checks: dict) -> bool:
    """Whether a run's numbers fail the tiny limits and every cell's
    committed limits."""
    from harness import compare

    nums = {k: c["value"] for k, c in checks.items()}
    return all(not compare.passed(compare.checks(nums, lim))
               for lim in [LIMITS, *COMMITTED.values()])


def run_cell(root: Path, name="tiny-cell", seed=7, seconds=0.5,
             wrap_step=None, traced=False):
    from harness import spec, train_cell

    cell = spec.load_cell(root, name, bench=root / "bench")
    return train_cell.run(cell, seed, seconds, traced, 0.0,
                          require_tpu=False, wrap_step=wrap_step,
                          peaks=PEAKS, log=lambda *_: None)
