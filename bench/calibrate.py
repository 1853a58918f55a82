#!/usr/bin/env python3
"""Readings for the limits of ``correct``, on the chip, at a cell's size.

    python3 bench/calibrate.py --workload <name> --seeds 11,12,... \
        [--variants fp8,half_batch,no_exchange] [--variant-seeds 3]

For each seed, one process builds the program's step once (as
``bench/run.py`` does), drives it from that seed's weights through its
first three steps, lets the plain reference follow them, and prints the
numbers ``correct`` compares: the program's lower readings.  For the
first ``--variant-seeds`` seeds it also puts each variant in the
program's place (``harness.reference``: the float8 control and the
planted faults) and prints its numbers against the same reference: the
upper readings.  No measured window is needed for training readings.
One JSON object per line, each with the (learner, leaf) entries that
make its numbers; the limits are set from them by hand.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]
# the TPU runtime would otherwise log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from harness import compare, reference, spec, train_cell  # noqa: E402


def readings(got: dict, want: dict) -> dict:
    q = compare.quiet(want)
    return {"numbers": compare.numbers(got, want),
            "losses": got["losses"],
            "worst_grad": compare.worst(got["grad"], want["grad"]),
            "worst_change": compare.worst(got["change"], want["change"],
                                          skip=q),
            "quiet_leaves": len(q)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="fp8,half_batch,no_exchange")
    ap.add_argument("--variant-seeds", type=int, default=3)
    args = ap.parse_args(argv)

    from repro.data.pipeline import Prefetcher

    cell = spec.load_cell(ROOT, args.workload)
    devices = train_cell.devices_for(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",")]
    variants = [v for v in args.variants.split(",") if v]
    job = None
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        if job is None:
            job = train_cell.build(cell, devices, seed)
        else:
            train_cell.reseed(job, seed)
        ring = cell.batches(seed)
        pf = Prefetcher(train_cell.Ring(ring))
        got = train_cell.first_steps(job, seed, pf)
        pf.close()
        t_prog = time.perf_counter() - t
        t = time.perf_counter()
        want = reference.run(cell, seed, ring[:3], devices)
        t_ref = time.perf_counter() - t
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "job": "program", **readings(got, want),
                          "ref_losses": want["losses"],
                          "program_s": t_prog, "reference_s": t_ref}),
              flush=True)
        if i >= args.variant_seeds:
            continue
        for v in variants:
            t = time.perf_counter()
            alt = reference.run(cell, seed, ring[:3], devices, variant=v)
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "job": v, **readings(alt, want),
                              "seconds": time.perf_counter() - t}),
                  flush=True)
    print(json.dumps({"done": True, "seconds": time.perf_counter() - T0}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
