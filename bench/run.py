#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout.  The cell's configuration, traffic,
metrics and limits are the files that ``BENCHMARK.json`` names.  Set-up
(loading, weights from the seed, compiling, the first three steps that
``correct`` reads) counts as ``setup_s``; then the window is timed for
``--seconds``.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` traces the window and reports its per-layer metrics.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit).
The last lines of standard error repeat the checks.  Without a TPU, or
with fewer chips than the cell asks for, it exits 1 and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]
# the TPU runtime would otherwise log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from harness import spec, train_cell  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def finite(x):
    """The result with every non-finite number as null (strict JSON)."""
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(ROOT, args.workload)
    with open(BENCH / "harness" / "peaks.json") as f:
        peaks = json.load(f)
    try:
        result = train_cell.run(cell, args.seed, args.seconds,
                                bool(args.trace), T0, peaks=peaks, log=log)
    except train_cell.NoChip as e:
        log(f"bench: {e}")
        return 1
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    log(f"correct {result['correct']}")
    print(json.dumps(finite(result), allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
