"""Bring-up smoke of the paper's BLSTM trainer on a TPU.

Drives the swb2000-blstm training job at its published widths (6 BLSTM
layers of 512 cells per direction, 260-dim features, a 256 bottleneck,
a 32,000-way softmax, 256 x 21 frames per learner) through the trainer's
own entry point ``repro.launch.train.main``, with random weights and
synthetic batches made from a seed, and checks what comes out.

One chip (no arguments):

1. ring AD-PSGD, 4 learners, batch 1024, 5 steps, Pallas kernels: every
   loss is finite, and the compiled step holds one Mosaic kernel
   (``tpu_custom_call``) per LSTM pass — 3 per layer — and the fused
   output layer's, so no layer became an XLA scan or an interpreted
   kernel;
2. the same job at 3 learners (batch 768), once with ``--kernel-impl
   pallas`` and once with ``--kernel-impl jax``, same seed: every loss
   agrees within LOSS_RTOL.  The XLA-scan reference needs about 4 GB of
   temporaries per learner, so 4 learners exceed the chip's 16 GB; 3 is
   the largest count at which both implementations fit;
3. one inference forward (``models.lstm.forward``) at B=256, T=21: it
   prints the stack path it took, its logits are finite and match the
   jax forward within FWD_TOL (normalized max-abs).

``--chips 4`` runs only the multi-chip path and what it is compared
with: ring AD-PSGD with its 4 learners one per chip against the same
seed with the 4 learners stacked on one device, and SC-PSGD allreduce
over 4 chips against one.  It asserts that each learner's parameter
slice lives on its own device and that no chip holds the whole batch.

Every phase prints its compile seconds (the trainer's compile counter:
trace, lowering, backend compile or cache load of ``train_step``) and
its steady milliseconds per step (information only).
The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU, or when any check fails, the script exits non-zero and
prints no such line.

    python3 chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

STEPS = 5
SEED = 0
# one bf16 ulp of the loss value: the loss is an f32 mean over >= 16k
# frames of a model whose matmul inputs and activations are bf16
LOSS_RTOL = 2.0 ** -8
# the repo's bf16 oracle tolerance (docs/kernels.md, normalized max-abs)
FWD_TOL = 2e-2
KERNEL = 'custom_call_target="tpu_custom_call"'


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {msg}")


def train(*extra: str) -> dict:
    """One trainer run through the CLI entry point; checks its losses."""
    from repro.launch.train import main

    argv = ["--arch", "swb2000-blstm", "--seq-len", "21", "--steps",
            str(STEPS), "--log-every", "1", "--seed", str(SEED), *extra]
    print("== train " + " ".join(argv), flush=True)
    t0 = time.perf_counter()
    res = main(argv)
    c = res["compiles"]
    print(f"phase seconds: compile {c['seconds']:.3f} ({c['n_compiles']} "
          f"compile(s) of train_step: trace {c['trace_s']:.3f}, lower "
          f"{c['lower_s']:.3f}, backend {c['compile_s']:.3f}, "
          f"{c['cache_hits']} from the persistent cache), steady "
          f"{res['steady_ms_per_step']:.1f} ms/step, wall "
          f"{time.perf_counter() - t0:.3f}", flush=True)
    losses = res["losses"]
    check(len(losses) == STEPS and all(map(math.isfinite, losses)),
          f"expected {STEPS} finite losses, got {losses}")
    return res


def agree(name: str, got: list, want: list) -> None:
    diff = max(abs(a - b) for a, b in zip(got, want))
    tol = LOSS_RTOL * max(abs(b) for b in want)
    print(f"{name}: max |loss diff| {diff:.6g} (tolerance {tol:.6g})",
          flush=True)
    check(len(got) == len(want) and diff <= tol,
          f"{name}: loss trajectories differ: {got} vs {want}")


def step_kernels(res: dict) -> int:
    """Mosaic kernels in the compiled train step of a run."""
    import jax

    with jax.set_mesh(res["meta"]["mesh"]):
        hlo = res["step"].lower(res["state"], res["batch"]).compile()
    return hlo.as_text().count(KERNEL)


def one_chip(n_layers: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch
    from repro.models import lstm as LS
    from repro.sharding import init_spec_tree

    res = train("--strategy", "ad_psgd", "--learners", "4", "--batch",
                "1024", "--kernel-impl", "pallas")
    n = step_kernels(res)
    print(f"pallas train step: {n} tpu_custom_call kernels "
          f"(expected {3 * n_layers + 1})", flush=True)
    check(n == 3 * n_layers + 1, f"{n} Mosaic kernels in the pallas step")
    del res

    pallas = train("--strategy", "ad_psgd", "--learners", "3", "--batch",
                   "768", "--kernel-impl", "pallas")["losses"]
    ref = train("--strategy", "ad_psgd", "--learners", "3", "--batch",
                "768", "--kernel-impl", "jax")["losses"]
    agree("pallas vs jax, 3 learners", pallas, ref)

    cfg = get_arch("swb2000-blstm")
    params = init_spec_tree(LS.param_specs(cfg), jax.random.PRNGKey(SEED))
    feats = jax.random.normal(jax.random.PRNGKey(SEED + 1),
                              (256, 21, cfg.input_dim), jnp.float32)
    print("== inference forward B=256 T=21", flush=True)
    logits = {}
    for impl in ("pallas", "jax"):
        t0 = time.perf_counter()
        fwd = jax.jit(lambda p, f, impl=impl: LS.forward(
            cfg, p, f, kernel_impl=impl)).lower(params, feats).compile()
        compile_s = time.perf_counter() - t0
        if impl == "pallas":
            n = fwd.as_text().count(KERNEL)
            path = "fused" if n == 1 else "per-layer"
            print(f"inference stack path: {path} ({n} tpu_custom_call "
                  f"kernels)", flush=True)
            check(n in (1, cfg.n_layers), f"{n} Mosaic kernels in the "
                  f"pallas forward")
        out = fwd(params, feats).block_until_ready()
        t0 = time.perf_counter()
        fwd(params, feats).block_until_ready()
        print(f"phase seconds ({impl} forward): compile {compile_s:.3f}, "
              f"steady {time.perf_counter() - t0:.3f}", flush=True)
        check(out.shape == (256, 21, cfg.vocab)
              and bool(jnp.all(jnp.isfinite(out))),
              f"{impl} logits: shape {out.shape} or non-finite values")
        logits[impl] = out
    err = float(jnp.max(jnp.abs(logits["pallas"] - logits["jax"]))
                / jnp.max(jnp.abs(logits["jax"])))
    print(f"pallas vs jax forward: normalized max-abs {err:.6g} "
          f"(tolerance {FWD_TOL})", flush=True)
    check(err <= FWD_TOL, "pallas forward differs from the jax forward")


def placed_per_device(arr, n: int) -> bool:
    """``arr``'s leading dim is split into n slices on n distinct
    devices (no device holds a copy of the whole)."""
    shards = arr.addressable_shards
    devices = {s.device for s in shards}
    rows = sorted(s.index[0].indices(arr.shape[0])[:2] for s in shards)
    step = arr.shape[0] // n
    return (len(devices) == n and len(shards) == n
            and rows == [(i * step, (i + 1) * step) for i in range(n)])


def four_chips() -> None:
    import jax

    n = len(jax.devices())
    check(n == 4, f"--chips 4 needs 4 devices, JAX found {n}")
    common = ("--batch", "1024", "--kernel-impl", "pallas")

    ring = train("--strategy", "ad_psgd", "--learners", "4", "--devices",
                 "4", *common)
    params = jax.tree.leaves(ring["state"]["params"])
    check(all(placed_per_device(w, 4) for w in params),
          "a learner's parameter slice is not on its own device")
    check(placed_per_device(ring["batch"]["features"], 4),
          "the AD-PSGD batch is not split over the chips")
    print("ring AD-PSGD: each learner's parameters on its own chip, "
          "batch split 4 ways", flush=True)
    ring4 = ring["losses"]
    del ring, params
    ring1 = train("--strategy", "ad_psgd", "--learners", "4", "--devices",
                  "1", *common)["losses"]
    agree("ring AD-PSGD, 4 chips vs 4 learners on one", ring4, ring1)

    sc = train("--strategy", "sc_psgd", "--devices", "4", *common)
    check(placed_per_device(sc["batch"]["features"], 4),
          "the SC-PSGD batch is not split over the chips")
    print("SC-PSGD: batch split 4 ways", flush=True)
    sc4 = sc["losses"]
    del sc
    sc1 = train("--strategy", "sc_psgd", "--devices", "1",
                *common)["losses"]
    agree("SC-PSGD allreduce, 4 chips vs one", sc4, sc1)


def run(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (backend "
                 f"{jax.default_backend()!r}); there is no CPU fallback")
    from repro.configs import get_arch
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.chips == 4:
        four_chips()
    else:
        one_chip(get_arch("swb2000-blstm").n_layers)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    run()
