import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# ^ MUST precede any jax import: jax locks the device count on first init.
"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape) pair this lowers + compiles the
real train/serve step against ShapeDtypeStruct stand-ins (no allocation)
on the production meshes:

* single pod  (16, 16)    = 256 chips, axes ('data', 'model')
* multi-pod   (2, 16, 16) = 512 chips, axes ('pod', 'data', 'model')

and records memory_analysis(), cost_analysis(), and the trip-count-correct
HLO analysis (FLOPs / bytes / per-collective bytes) into
``experiments/dryrun/<arch>__<shape>__<mesh>.json``.

Train shapes lower the arch's own distributed strategy (the paper's
technique: learner replicas + ring mixing); multi-pod train uses the
paper's H-ring (sync within pod, AD-PSGD ring over the 'pod' axis).
Decode shapes lower ``serve_step`` (1 token against a seq_len KV cache).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all
  PYTHONPATH=src python -m repro.launch.dryrun --arch phi3-medium-14b \
      --shape train_4k --multipod --save-hlo
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.analysis.hlo import analyze_hlo
from repro.analysis.params import count_active_params, count_params
from repro.analysis.roofline import model_flops, roofline_terms
from repro.configs import ASSIGNED_ARCHS, get_arch, get_shape
from repro.core import strategies as ST
from repro.launch.mesh import make_production_mesh, rules_for
from repro.models import build_model
from repro.optim.optimizers import sgd
from repro.sharding import spec_tree_to_sds

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _sds_scalar(dtype=jnp.int32):
    return jax.ShapeDtypeStruct((), dtype)


def build_train_dryrun(cfg, mesh, rules, shape, *, multi_pod: bool):
    """(callable, args) for the strategy train step, all-SDS."""
    model = build_model(cfg)
    if multi_pod:
        strategy = ST.get_strategy("hring")
        n_learners = mesh.shape["pod"]
    else:
        strategy = ST.get_strategy(cfg.train_strategy)
        n_learners = cfg.n_learners if strategy.replicated else 1

    import functools
    loss_fn = functools.partial(
        model.loss_fn, batch_axis="" if strategy.replicated else "data")
    # the same substrate train.py would run (comm_* knobs), so the
    # HLO/collective picture matches the real step
    transport = ST.transport_from_cfg(cfg, strategy)
    step = ST.make_train_step(
        strategy, loss_fn, sgd(), lambda s: jnp.float32(0.1),
        n_learners=n_learners, microbatches=cfg.microbatches,
        pre_split=strategy.replicated, transport=transport)

    lead = ((n_learners, "learner"),) if strategy.replicated else ()
    params = spec_tree_to_sds(model.param_specs(), rules, extra_leading=lead)
    state = {"params": params, "opt": (), "step": _sds_scalar()}
    if strategy.stale:
        state["prev_params"] = params
    if strategy.replicated and transport.needs_state:
        # error-feedback trees as SDS (init_comm only reads leaf shapes)
        state["comm"] = jax.eval_shape(transport.init_comm, params)
    inputs = model.input_specs(shape, "train")
    if strategy.replicated:
        # pre-split the global batch: (B, ...) -> (L, B/L, ...) with the
        # learner dim explicitly sharded (data axis / pod axis for H-ring)
        from repro.sharding import ParamSpec

        def split(ps: ParamSpec):
            B = ps.shape[0]
            assert B % n_learners == 0, (B, n_learners)
            return ParamSpec((n_learners, B // n_learners) + ps.shape[1:],
                             ps.dtype, ("learner",) + ps.axes, ps.init,
                             ps.init_scale)

        inputs = jax.tree.map(split, inputs,
                              is_leaf=lambda x: isinstance(x, ParamSpec))
    batch = spec_tree_to_sds(inputs, rules)
    return step, (state, batch), {"strategy": strategy.name,
                                  "n_learners": n_learners}


def build_prefill_dryrun(cfg, mesh, rules, shape):
    model = build_model(cfg)
    long_ctx = shape.name == "long_500k"

    def step(params, batch):
        return model.prefill_fn(params, batch, cache_len=shape.seq_len,
                                long_context=long_ctx)

    params = spec_tree_to_sds(model.param_specs(), rules)
    batch = spec_tree_to_sds(model.input_specs(shape, "prefill"), rules)
    return step, (params, batch), {"strategy": "serve"}


def build_decode_dryrun(cfg, mesh, rules, shape):
    model = build_model(cfg)
    long_ctx = shape.name == "long_500k"

    def step(params, cache, tokens, pos):
        return model.decode_fn(params, cache, tokens, pos,
                               long_context=long_ctx)

    params = spec_tree_to_sds(model.param_specs(), rules)
    cache = spec_tree_to_sds(model.cache_specs(shape), rules)
    inp = spec_tree_to_sds(model.input_specs(shape, "decode"), rules)
    return step, (params, cache, inp["tokens"], inp["pos"]), \
        {"strategy": "serve"}


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            save_hlo: bool = False, out_dir: str = OUT_DIR,
            opt: bool = False, cfg_override=None) -> dict:
    cfg = cfg_override or get_arch(arch)
    if opt and cfg_override is None:
        cfg = cfg.optimized()
    shape = get_shape(shape_name)
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    if opt:
        mesh_name += "_opt"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "variant": "optimized" if opt else "baseline",
           "status": "skipped"}

    if not cfg.supports_shape(shape_name):
        rec["reason"] = "skipped per DESIGN.md §Arch-applicability"
        return rec
    if shape.is_decode and not cfg.supports_decode:
        rec["reason"] = "no decode step for this family"
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules_for(cfg, mesh, multi_pod=multi_pod)

    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            fn, args, meta = build_train_dryrun(cfg, mesh, rules, shape,
                                                multi_pod=multi_pod)
        elif shape.kind == "prefill":
            fn, args, meta = build_prefill_dryrun(cfg, mesh, rules, shape)
        else:
            fn, args, meta = build_decode_dryrun(cfg, mesh, rules, shape)
        lowered = jax.jit(fn).lower(*args)
        rec["lower_s"] = round(time.time() - t0, 2)
        t0 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 2)

    ma = compiled.memory_analysis()
    rec.update(meta)
    rec["memory"] = {
        "argument_gb": ma.argument_size_in_bytes / 1e9,
        "output_gb": ma.output_size_in_bytes / 1e9,
        "temp_gb": ma.temp_size_in_bytes / 1e9,
        "code_gb": ma.generated_code_size_in_bytes / 1e9,
    }
    ca = compiled.cost_analysis()
    rec["cost_analysis"] = {"flops": ca.get("flops", 0.0),
                            "bytes": ca.get("bytes accessed", 0.0)}

    txt = compiled.as_text()
    st = analyze_hlo(txt)
    rec["hlo"] = st.to_json()

    chips = 512 if multi_pod else 256
    rec["chips"] = chips
    rec["roofline"] = roofline_terms(
        {"flops": st.flops, "bytes": st.bytes,
         "collective_bytes": st.collective_bytes}, chips=chips)

    model = build_model(cfg)
    specs = model.param_specs()
    n_total = count_params(specs)
    n_active = count_active_params(cfg, specs)
    rec["params_total"] = n_total
    rec["params_active_nonembed"] = n_active
    mf = model_flops(cfg, shape, n_active, shape.kind)
    rec["model_flops"] = mf
    hlo_global = st.flops * chips
    rec["model_flops_ratio"] = mf / hlo_global if hlo_global else 0.0
    rec["status"] = "ok"

    if save_hlo:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(
                out_dir, f"{arch}__{shape_name}__{mesh_name}.hlo.txt"),
                "w") as f:
            f.write(txt)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--opt", action="store_true",
                    help="apply the §Perf optimized overlay "
                         "(ArchConfig.optimized())")
    ap.add_argument("--out-dir", default=OUT_DIR)
    args = ap.parse_args(argv)

    archs = list(ASSIGNED_ARCHS) if args.arch == "all" else [args.arch]
    shapes = (["train_4k", "prefill_32k", "decode_32k", "long_500k"]
              if args.shape == "all" else [args.shape])
    meshes = [False, True] if args.both_meshes else [args.multipod]

    os.makedirs(args.out_dir, exist_ok=True)
    failures = 0
    for multi_pod in meshes:
        for arch in archs:
            for shape in shapes:
                tag = (f"{arch}__{shape}__"
                       f"{'multipod_2x16x16' if multi_pod else 'pod_16x16'}"
                       f"{'_opt' if args.opt else ''}")
                try:
                    rec = run_one(arch, shape, multi_pod=multi_pod,
                                  save_hlo=args.save_hlo,
                                  out_dir=args.out_dir, opt=args.opt)
                except Exception as e:  # a failure here is a sharding bug
                    failures += 1
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "multipod" if multi_pod else "pod",
                           "status": "FAIL", "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-3000:]}
                with open(os.path.join(args.out_dir, tag + ".json"), "w") as f:
                    json.dump(rec, f, indent=1)
                if rec["status"] == "ok":
                    r = rec["roofline"]
                    print(f"{tag:70s} ok  lower {rec['lower_s']:6.1f}s "
                          f"compile {rec['compile_s']:6.1f}s "
                          f"dom={r['dominant']:10s} bound={r['bound_s']:.3e}s",
                          flush=True)
                else:
                    print(f"{tag:70s} {rec['status']}: "
                          f"{rec.get('reason', rec.get('error', ''))[:110]}",
                          flush=True)
    if failures:
        raise SystemExit(f"{failures} dry-run failures")
    print("all dry-runs passed")


if __name__ == "__main__":
    main()
