"""Training launcher.

Library entry point: :func:`setup_training` builds (state, step_fn, meta)
for any (arch, strategy, mesh); the CLI runs the loop with prefetching,
logging and checkpointing.

Examples
--------
# paper's acoustic model, AD-PSGD, 4 simulated learners, reduced size:
PYTHONPATH=src python -m repro.launch.train --arch swb2000-blstm \
    --reduced --learners 4 --strategy ad_psgd --steps 200

# any assigned arch:
PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \
    --strategy sd_psgd --steps 50 --seq-len 128 --batch 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.checkpoint import restore, save
from repro.configs import get_arch
from repro.core import strategies as ST
from repro.data import make_dataset
from repro.data.pipeline import Prefetcher
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import (make_local_mesh, make_production_mesh,
                               rules_for)
from repro.models import build_model
from repro.optim.optimizers import get_optimizer
from repro.optim.schedules import paper_recipe, warmup_then_anneal
from repro.sharding import init_spec_tree, spec_tree_shardings


def setup_training(cfg, mesh, *, strategy_name: str = None,
                   n_learners: int = None, optimizer_name: str = "sgd",
                   lr_schedule=None, seed: int = 0, multi_pod: bool = False,
                   with_consensus: bool = False, kernel_impl: str = "jax",
                   microbatches: int = None, transport=None,
                   elastic: bool = False, fault_seed: int = 0,
                   with_corruption: bool = False,
                   with_grad_norm: bool = False):
    """Build sharded train state + jitted step for one arch on one mesh.

    ``transport`` overrides the communication substrate (topology × wire
    × bucketing); default: the cfg's ``comm_*`` knobs resolved against
    the strategy (see repro.core.transport and docs/strategies.md).

    ``elastic=True`` builds the fault-tolerant step instead
    (``ST.make_elastic_train_step``): it takes a third ``faults``
    argument — one ``FaultPlan.step_inputs`` dict per step — and runs
    the strategy under elastic membership with staleness-aware mixing
    (docs/fault_tolerance.md).

    When the mesh axis that holds the learners (replicated strategies) or
    the batch (plain data-parallel) spans several devices, the step
    computes its gradients under ``shard_map`` over that axis — the only
    way a Pallas kernel runs on more than one chip (repro.core.strategies).

    It installs the compile counter (``repro.obs.compile_records``), so
    the step's trace, lowering and compile at its first call are
    recorded under the name ``train_step``.
    """
    obs.install_compile_counter()
    strategy = ST.get_strategy(strategy_name or cfg.train_strategy)
    n_learners = n_learners if n_learners is not None else cfg.n_learners
    if not strategy.replicated:
        n_learners = 1
    microbatches = (microbatches if microbatches is not None
                    else cfg.microbatches)
    if transport is None:
        transport = ST.transport_from_cfg(cfg, strategy)
    model = build_model(cfg)
    rules = rules_for(cfg, mesh, multi_pod=multi_pod)
    axis = rules.rules["learner" if strategy.replicated else "batch"][0]
    n_axis = mesh.shape.get(axis, 1)
    shard = ((mesh, axis) if n_axis > 1 and (
        not strategy.replicated or n_learners % n_axis == 0) else None)
    opt = get_optimizer(optimizer_name)
    lr_schedule = lr_schedule or warmup_then_anneal(0.1, 0.5, 100, 10_000,
                                                    1 / np.sqrt(2))

    def loss_fn(params, batch):
        return model.loss_fn(params, batch, kernel_impl=kernel_impl)

    if elastic:
        step_fn = ST.make_elastic_train_step(
            strategy, loss_fn, opt, lr_schedule,
            n_learners=n_learners, microbatches=microbatches,
            with_consensus=with_consensus, transport=transport,
            fault_seed=fault_seed, with_corruption=with_corruption,
            with_grad_norm=with_grad_norm, shard=shard)
    else:
        step_fn = ST.make_train_step(
            strategy, loss_fn, opt, lr_schedule,
            n_learners=n_learners, microbatches=microbatches,
            with_consensus=with_consensus, transport=transport,
            with_grad_norm=with_grad_norm, shard=shard)

    pspecs = model.param_specs()
    lead = ((n_learners, "learner"),) if strategy.replicated else ()
    param_shardings = spec_tree_shardings(pspecs, rules, extra_leading=lead)

    with jax.set_mesh(mesh):
        with obs.span("setup/params"):
            params = init_spec_tree(pspecs, jax.random.PRNGKey(seed))
            if strategy.replicated:
                params = ST.stack_for_learners(params, n_learners)
            params = jax.tree.map(jax.device_put, params, param_shardings)
        with obs.span("setup/state"):
            if elastic:
                state = ST.init_elastic_state(strategy, params, opt,
                                              transport=transport)
            else:
                state = ST.init_state(strategy, params, opt,
                                      transport=transport)
        with obs.span("setup/jit"):
            jit_step = jax.jit(step_fn, donate_argnums=(0,))

    meta = dict(model=model, rules=rules, strategy=strategy,
                n_learners=n_learners, mesh=mesh, transport=transport)
    return state, jit_step, meta


def place_batch(batch, rules):
    """Put a host batch on the mesh with its leading (batch) dim split by
    the 'batch' rule, so no device holds the whole global batch."""
    with obs.span("data/place"):
        return {k: jax.device_put(
                    v, rules.sharding(v.shape,
                                      ("batch",) + (None,) * (v.ndim - 1)))
                for k, v in batch.items()}


def main(argv=None):
    """Run the training CLI.  Returns the run's record for callers that
    drive it in-process: ``losses`` (one per logged step), the final
    ``state``, the jitted ``step``, the last placed ``batch``, ``meta``
    of :func:`setup_training`, the compile counter's summary of
    ``train_step`` over this run (``compiles``,
    :func:`repro.obs.compile_summary`) and ``steady_ms_per_step``.

    Steps are dispatched without waiting for the device: the loop reads
    device values (``jax.device_get``) only at log steps, where it also
    emits the interval's ``train/step`` events while tracing, at
    checkpoints, and at the end of the run.  Steady time per step is
    the wall time from the first logged step's outputs being ready to
    the last step's, over the steps between."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--strategy", default=None,
                    choices=[None] + sorted(ST.STRATEGIES))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--learners", type=int, default=None)
    ap.add_argument("--optimizer", default="sgd")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale variant of the arch (CPU-friendly)")
    ap.add_argument("--mesh", default="local",
                    choices=["local", "pod", "multipod"])
    ap.add_argument("--devices", type=int, default=0,
                    help="local mesh: how many of the local devices it "
                         "spans, all on the 'data' axis (0 = all)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--consensus", action="store_true")
    ap.add_argument("--kernel-impl", default="jax",
                    choices=["jax", "pallas"])
    ap.add_argument("--block-b", type=int, default=0,
                    help="Pallas LSTM batch tile (0 = auto from VMEM)")
    ap.add_argument("--vmem-budget-mb", type=int, default=0,
                    help="VMEM budget for kernel auto-tiling (0 = cfg)")
    ap.add_argument("--stash-dtype", default="",
                    choices=["", "float32", "bfloat16"],
                    help="Pallas LSTM residual-stash dtype (bfloat16 "
                         "halves the gate/cell stash HBM)")
    ap.add_argument("--seq-chunk", type=int, default=0,
                    help="Pallas LSTM sequence-chunked recompute: stash "
                         "only (h, c) carries every K frames and rebuild "
                         "gate residuals in VMEM in the backward (0 = "
                         "off, -1 = auto from the VMEM budget); cuts the "
                         "O(T) residual stash to O(T/K) for long "
                         "utterances")
    ap.add_argument("--comm-topology", default="",
                    choices=["", "uniform", "ring", "hierarchical", "exp",
                             "none"],
                    help="mixing topology override (default: the "
                         "strategy's own; docs/strategies.md)")
    ap.add_argument("--comm-wire", default="",
                    choices=["", "f32", "bf16", "int8", "topk"],
                    help="wire codec for mixing payloads (default: the "
                         "strategy's own, f32 for all paper strategies)")
    ap.add_argument("--comm-intra-wire", default="",
                    choices=["", "f32", "bf16", "int8"],
                    help="hierarchical topology: codec of the intra-pod "
                         "allreduce (inter-pod uses --comm-wire; topk is "
                         "gossip-only and not valid here)")
    ap.add_argument("--comm-bucket-mb", type=int, default=0,
                    help="chunk mixing payloads into buckets of this many "
                         "MB so XLA can interleave them with backward "
                         "compute (0 = one fused payload per tensor)")
    ap.add_argument("--comm-pod-size", type=int, default=0,
                    help="hierarchical topology: learners per pod (0 = "
                         "cfg value)")
    ap.add_argument("--comm-topk-frac", type=float, default=0.0,
                    help="topk wire: fraction of entries shipped (0 = "
                         "cfg value, 0.01)")
    ap.add_argument("--comm-staleness-lambda", type=float, default=0.0,
                    help="elastic mixing: staleness damping λ — a "
                         "learner s steps behind mixes with confidence "
                         "1/(1 + λ·s); 0 = cfg value "
                         "(docs/fault_tolerance.md)")
    ap.add_argument("--resume", action="store_true",
                    help="require and restore the latest checkpoint in "
                         "--ckpt-dir: optimizer state, comm "
                         "error-feedback residuals and the data cursor "
                         "all resume bit-exactly (recovery contract in "
                         "docs/fault_tolerance.md); fails if nothing to "
                         "resume")
    ap.add_argument("--fault-stragglers", default="",
                    help="fault plan: 'learner:factor,...' — e.g. '0:4' "
                         "makes learner 0 contribute a gradient only "
                         "every 4th step (docs/fault_tolerance.md); any "
                         "--fault-* flag switches to the elastic "
                         "fault-tolerant step")
    ap.add_argument("--fault-departures", default="",
                    help="fault plan: 'learner:step[:rejoin],...' — "
                         "e.g. '1:30:60' crashes learner 1 at step 30 "
                         "and rejoins it (re-seeded from the survivors' "
                         "consensus) at step 60")
    ap.add_argument("--fault-drop-prob", type=float, default=0.0,
                    help="fault plan: per-step probability that an "
                         "undirected gossip edge drops (both endpoints "
                         "fall back to themselves)")
    ap.add_argument("--fault-stall-prob", type=float, default=0.0,
                    help="fault plan: per-step probability a learner "
                         "enters a heavy-tailed (Pareto) stall")
    ap.add_argument("--fault-corrupt-prob", type=float, default=0.0,
                    help="fault plan: per-step probability a learner's "
                         "outgoing payload picks up noise (receivers "
                         "only; needs --fault-corrupt-scale > 0)")
    ap.add_argument("--fault-corrupt-scale", type=float, default=0.0,
                    help="fault plan: corruption noise RMS relative to "
                         "the payload RMS")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="fault plan: seed of the deterministic fault "
                         "schedule (same seed = same cluster weather)")
    ap.add_argument("--var-len", action="store_true",
                    help="variable-length utterances: batches carry a "
                         "'lengths' key, loss/BLSTM/aggregation mask "
                         "padded frames (lstm family only)")
    ap.add_argument("--bucket", action="store_true",
                    help="length-bucketed batching (implies --var-len): "
                         "sort utterances within a shuffle window so each "
                         "batch pads to its own rounded max length; "
                         "distinct padded lengths each compile once")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default="",
                    help="enable observability and write the run's "
                         "flight-recorder JSONL here (schema in "
                         "docs/observability.md; render with "
                         "repro.launch.obsreport); also records "
                         "per-step grad-norm")
    ap.add_argument("--trace-deterministic", action="store_true",
                    help="strip wall-clock fields from the JSONL so "
                         "two seeded runs emit byte-identical traces")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.trace_out:
        obs.configure()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    import dataclasses
    changes = {}
    if args.block_b:
        changes["lstm_block_b"] = args.block_b
    if args.vmem_budget_mb:
        changes["lstm_vmem_budget_mb"] = args.vmem_budget_mb
    if args.stash_dtype:
        changes["lstm_stash_dtype"] = args.stash_dtype
    if args.seq_chunk:
        changes["lstm_seq_chunk"] = args.seq_chunk
    if args.comm_topology:
        changes["comm_topology"] = args.comm_topology
    if args.comm_wire:
        changes["comm_wire"] = args.comm_wire
    if args.comm_intra_wire:
        changes["comm_intra_wire"] = args.comm_intra_wire
    if args.comm_bucket_mb:
        changes["comm_bucket_mb"] = args.comm_bucket_mb
    if args.comm_pod_size:
        changes["comm_pod_size"] = args.comm_pod_size
    if args.comm_topk_frac:
        changes["comm_topk_frac"] = args.comm_topk_frac
    if args.comm_staleness_lambda:
        changes["comm_staleness_lambda"] = args.comm_staleness_lambda
    if changes:
        cfg = dataclasses.replace(cfg, **changes)
    seq_len = args.seq_len or (21 if cfg.family == "lstm" else 128)
    n_learners = args.learners if args.learners is not None else cfg.n_learners
    strategy = ST.get_strategy(args.strategy or cfg.train_strategy)
    if not strategy.replicated:
        n_learners = 1
    batch = args.batch or max(8, 2 * n_learners)

    # any --fault-* flag switches to the elastic fault-tolerant step,
    # driven by one deterministic FaultPlan (docs/fault_tolerance.md)
    from repro.core.faults import (FaultPlan, parse_departures,
                                   parse_stragglers)
    elastic = bool(args.fault_stragglers or args.fault_departures
                   or args.fault_drop_prob or args.fault_stall_prob
                   or args.fault_corrupt_prob)
    plan = None
    if elastic:
        plan = FaultPlan(
            n_learners, seed=args.fault_seed,
            stragglers=parse_stragglers(args.fault_stragglers),
            departures=parse_departures(args.fault_departures),
            drop_prob=args.fault_drop_prob,
            stall_prob=args.fault_stall_prob,
            corrupt_prob=args.fault_corrupt_prob,
            corrupt_scale=args.fault_corrupt_scale)
        print(plan.describe(), flush=True)

    if args.mesh == "local":
        devices = jax.devices()[:args.devices or None]
        mesh = make_local_mesh(data=len(devices), devices=devices)
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "multipod")

    state, jit_step, meta = setup_training(
        cfg, mesh, strategy_name=strategy.name, n_learners=n_learners,
        optimizer_name=args.optimizer, seed=args.seed,
        multi_pod=args.mesh == "multipod", with_consensus=args.consensus,
        kernel_impl=args.kernel_impl,
        lr_schedule=paper_recipe(steps_per_epoch=max(args.steps // 16, 1),
                                 base_lr=0.05, peak_lr=0.2),
        elastic=elastic, fault_seed=args.fault_seed,
        with_corruption=args.fault_corrupt_prob > 0,
        with_grad_norm=obs.enabled())
    dev = jax.devices()[0]
    print(f"mesh {dict(mesh.shape)} over {dev.platform} ({dev.device_kind}); "
          f"kernel-impl {args.kernel_impl}"
          + (" in Pallas interpret mode (no TPU)"
             if args.kernel_impl == "pallas" and dev.platform != "tpu"
             else ""), flush=True)

    if args.resume and not args.ckpt_dir:
        raise SystemExit("--resume needs --ckpt-dir")
    start = 0
    if args.ckpt_dir:
        try:
            state, start = restore(args.ckpt_dir, state)
            print(f"restored checkpoint at step {start}")
        except FileNotFoundError:
            if args.resume:
                raise SystemExit(
                    f"--resume: no checkpoint under {args.ckpt_dir}")

    if obs.enabled() and cfg.family == "lstm":
        # runtime collection of the BLSTM residual-stash HBM accounting
        # single-source (repro.kernels.lstm_cell.stash_bytes)
        from repro.kernels.lstm_cell import stash_bytes
        obs.gauge("kernel/stash_bytes", impl=args.kernel_impl).set(
            stash_bytes(max(batch // max(n_learners, 1), 1), seq_len,
                        cfg.d_model, n_dir=2,
                        stash_itemsize=(2 if cfg.lstm_stash_dtype
                                        == "bfloat16" else 4),
                        seq_chunk=max(cfg.lstm_seq_chunk, 0)))

    ds = make_dataset(cfg, seq_len=seq_len, batch=batch, seed=args.seed,
                      var_len=args.var_len or args.bucket,
                      bucket=args.bucket)
    pf = Prefetcher(ds, start_step=start)

    t0 = time.time()
    valid_frames = padded_frames = 0
    metrics = batch = None
    losses = []
    pending = []            # (step, metrics, pad_eff) not yet read
    first = None            # (step, perf_counter) of the first read step

    def emit(rows):
        """The interval's per-step records, read in one transfer; returns
        the last step's values."""
        vals = jax.device_get([m for _, m, _ in rows])
        for (k2, _, pad_eff), scal in zip(rows, vals):
            scal = {n: float(v) for n, v in scal.items()}
            obs.event("train/step", step=k2, **scal)
            obs.histogram("train/loss").observe(scal["loss"])
            if "grad_norm" in scal:
                obs.histogram("train/grad_norm").observe(scal["grad_norm"])
            if "wire_bytes" in scal:
                obs.counter("train/wire_bytes",
                            strategy=meta["strategy"].name
                            ).inc(scal["wire_bytes"])
            if "n_active" in scal:
                obs.gauge("train/n_active").set(scal["n_active"])
                obs.histogram("train/staleness_max").observe(
                    scal["staleness_max"])
            if pad_eff is not None:
                obs.gauge("train/pad_eff").set(pad_eff)
        return vals[-1]

    with jax.set_mesh(meta["mesh"]):
        for k in range(start, args.steps):
            batch_np = pf.next()
            if "lengths" in batch_np:
                valid_frames += int(batch_np["lengths"].sum())
                padded_frames += (batch_np["features"].shape[0]
                                  * batch_np["features"].shape[1])
            batch = place_batch(batch_np, meta["rules"])
            if plan is not None:
                faults = plan.step_inputs(k)
                ST.check_active(faults["active"])
                state, metrics = jit_step(state, batch, faults)
            else:
                state, metrics = jit_step(state, batch)
            if obs.enabled():
                pending.append((k, metrics, valid_frames / padded_frames
                                if padded_frames else None))
            if k % args.log_every == 0:
                vals = emit(pending) if pending else jax.device_get(metrics)
                pending = []
                if first is None:
                    first = (k, time.perf_counter())
                loss = float(vals["loss"])
                losses.append(loss)
                line = (f"step {k:5d} loss {loss:.4f} "
                        f"({(time.time()-t0):.1f}s)")
                if padded_frames:
                    # padding efficiency: valid / (B * Tpad) frames —
                    # bucketing exists to push this toward 1.0
                    line += f" pad_eff {valid_frames/padded_frames:.2f}"
                if "wire_bytes" in vals:
                    # analytic bytes sent per learner this step
                    # (Transport.wire_bytes; docs/strategies.md)
                    wb = float(vals["wire_bytes"])
                    line += f" wire {wb/2**20:.2f}MB"
                if "n_active" in vals:
                    line += (f" act {int(vals['n_active'])}/"
                             f"{meta['n_learners']}"
                             f" stale {int(vals['staleness_max'])}")
                if "consensus" in vals:
                    line += f" consensus {float(vals['consensus']):.3e}"
                print(line, flush=True)
            if args.ckpt_dir and args.ckpt_every and \
                    (k + 1) % args.ckpt_every == 0:
                save(args.ckpt_dir, k + 1, state)
        if metrics is not None:
            jax.block_until_ready(state)
            final = jax.device_get(metrics)
            t_last = time.perf_counter()
            if pending:
                emit(pending)
    pf.close()
    if metrics is not None:
        # one parseable line for kill-and-resume / fault-smoke comparisons
        print(f"final loss {float(final['loss']):.6f}")
    # compile (trace + lowering + XLA compile or cache load of the step,
    # from the compile counter) and steady-state step time are different
    # regimes: report both instead of one conflated total
    compiles = obs.compile_summary("train_step", since=t0)
    n_steady = args.steps - 1 - first[0] if first is not None else 0
    steady_s = t_last - first[1] if n_steady > 0 else 0.0
    steady_ms = 1e3 * steady_s / n_steady if n_steady > 0 else float("nan")
    if n_steady > 0:
        # the steady regime as one span, beside the counter's compile/*
        # spans of the same function (repro.launch.obsreport)
        obs.add_span("train/steady", first[1], steady_s, wall=True,
                     fn="train_step", phase="steady", calls=n_steady)
    print(f"done: {args.steps - start} steps in {time.time()-t0:.1f}s "
          f"[{meta['strategy'].name}, L={meta['n_learners']}]")
    print(f"timing: compile {compiles['seconds']:.1f}s "
          f"({compiles['n_compiles']} compile(s) of train_step), steady "
          f"{steady_s:.1f}s over {n_steady} steps"
          + (f" ({steady_ms:.1f} ms/step)" if n_steady > 0 else ""),
          flush=True)
    if args.trace_out:
        n = obs.dump(args.trace_out,
                     deterministic=args.trace_deterministic)
        print(f"trace: {n} events -> {args.trace_out}")
        obs.reset()
    return dict(losses=losses, state=state, step=jit_step, batch=batch,
                meta=meta, compiles=compiles, steady_ms_per_step=steady_ms)


if __name__ == "__main__":
    main()
