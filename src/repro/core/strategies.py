"""Distributed training strategies (the paper's contribution, §IV-V).

Implemented strategies, all expressed in the decentralized formalism of
paper Eq. 14  (W_{k+1} = W_k·T − α·g(Φ_k, ξ_k)):

==========  =====================  =========  =============================
name        T (mixing)             Φ_k        paper reference
==========  =====================  =========  =============================
sc_psgd     T_u (allreduce)        W_k        §IV-B1 sync centralized; with
                                              L=1 replicas this is plain
                                              data-parallel SGD + psum
                                              (Eq. 13 equivalence)
sd_psgd     T_1 (ring permute)     W_k        §IV-C sync decentralized
ad_psgd     T_1 (ring permute)     W_{k-1}    §IV-C async decentralized:
                                              one-step-stale gradients let
                                              XLA overlap the mixing
                                              collective with compute
bmuf        block-level T_u        W_k local  §IV-B1 (Chen & Huo): local SGD
                                              for a block, then blockwise
                                              model-update filtering with
                                              block momentum
downpour    PS (simulated)         W_{k-1}    §IV-B2 async centralized
hring       T_1 over pods +        W_{k-1}    §V second experiment: NCCL
            T_u within pod                    allreduce inside a node
                                              (super-learner), AD-PSGD ring
                                              across nodes -> 'pod' axis
==========  =====================  =========  =============================

TPU/SPMD adaptation (DESIGN.md §Asynchrony): true wall-clock asynchrony
does not exist in a single SPMD program, so AD-PSGD's asynchrony is modeled
*deterministically* as bounded staleness — gradients are evaluated at the
previous iterate while the mixing of the current iterate proceeds in
parallel.  This is exactly the communication/computation overlap the paper
credits for AD-PSGD's speedup, and it preserves the algorithm's convergence
analysis (staleness tau=1..tau_max).  Stragglers, crashes and dropped
gossip enter through a ``FaultPlan`` given to :func:`make_train_step`
(docs/fault_tolerance.md).

Learner replicas are a stacked leading axis sharded over the mesh
('data' axis on one pod; 'pod' axis for hring), so each chip only ever
holds its own learner's shard — replication costs no extra HBM per chip.

Communication is factored out into the unified substrate of
``repro.core.transport``: every strategy takes a :class:`Transport`
(topology × wire codec × bucketing) and only contributes its *defaults*
(``Strategy.topology``/``Strategy.wire``).  Previously-inexpressible
combinations — BMUF with int8 block sync, hring with bf16 intra-pod +
topk inter-pod, allreduce with sparsified payloads — are one config away
(``comm_topology``/``comm_wire``/... knobs in configs/base.py, ``--comm-*``
train flags; matrix in docs/strategies.md).  With the default f32 wire the
substrate delegates to the exact mixers in ``repro.core.mixing`` and the
update trajectories are bit-identical to the pre-substrate step.  Each
replicated step also emits ``wire_bytes`` telemetry (analytic bytes sent
per learner per round, from ``Transport.wire_bytes``).

Variable-length batches (the ``lengths`` key of repro.data.pipeline) are
aggregated with *frame weights*: each learner's/microbatch's masked-mean
gradient is scaled by its valid-frame share so uniform mixing equals the
global masked gradient — the normative contract lives in docs/data.md.

On a mesh whose learner (or, for plain data-parallel sc_psgd, batch)
axis spans several devices, the step builders take ``shard=(mesh,
axis)`` and compute the gradients under ``shard_map`` over that axis:
each device runs the loss on its own learners or batch shard.  GSPMD
cannot partition a Pallas (Mosaic) kernel by itself, so this is what
lets ``kernel_impl="pallas"`` run on more than one chip.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.transport import Transport
from repro.optim.optimizers import Optimizer


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def split_learner_batch(batch, n_learners: int):
    """(B, ...) -> (L, B/L, ...) on every input leaf.

    Raises a ValueError (not a silent misshape) when the global batch is
    not divisible by the learner count, or when the learner count itself
    is empty (the all-inactive edge — see :func:`check_active`)."""
    if n_learners < 1:
        raise ValueError(
            f"n_learners={n_learners}: cannot split a batch over an "
            f"empty learner set — at least one learner must be active "
            f"(see check_active / FaultPlan membership validation)")

    def one(path, x):
        B = x.shape[0]
        if B % n_learners != 0:
            key = jax.tree_util.keystr(path)
            raise ValueError(
                f"global batch size B={B} (batch key {key!r}) is not "
                f"divisible by n_learners={n_learners}; every batch leaf "
                f"needs leading dim a multiple of the learner count so "
                f"each learner gets an equal shard (got remainder "
                f"{B % n_learners})")
        return x.reshape(n_learners, B // n_learners, *x.shape[1:])

    return jax.tree_util.tree_map_with_path(one, batch)


def check_active(active) -> int:
    """Host-side guard for the all-inactive-learner edge: frame-weighted
    aggregation over an empty learner set is 0/0, and the jitted step
    only *clamps* the denominator (traced values cannot raise).  Call
    this on the activity mask before each call of a step built with a
    fault plan; returns the live count.  ``FaultPlan`` applies the same
    rule to every membership event at plan construction."""
    n = int(np.asarray(active).sum())
    if n <= 0:
        raise ValueError(
            "no active learners this step: frame-weighted aggregation "
            "over an empty learner set is 0/0 and mixing has no "
            "survivor to freeze toward — fix the fault plan so at least "
            "one learner stays alive (FaultPlan raises the same error "
            "at construction)")
    return n


def _valid_frames(batch):
    """Per-example valid-frame counts summed over the batch, or None for
    rectangular batches (the ``lengths`` contract of repro.data.pipeline)."""
    if isinstance(batch, dict) and "lengths" in batch:
        return jnp.sum(batch["lengths"].astype(jnp.float32))
    return None


def _accumulated_grad(loss_fn, params, batch, n_micro: int):
    """Gradient with optional microbatch accumulation (memory knob).

    When the batch carries ``lengths``, microbatches are combined with
    frame weights (each microbatch's masked-mean loss/grad scaled by its
    valid-frame count) so the result equals the masked mean over the
    whole batch, not the mean-of-means."""
    if n_micro <= 1:
        loss, g = jax.value_and_grad(loss_fn)(params, batch)
        return loss, g

    def slice_micro(x):
        # split on the MINOR position of the batch dim (strided microbatches)
        # so a data/pod-sharded batch axis stays GSPMD-representable after
        # the reshape; (n_micro, B, ...) major-split is not when the shard
        # size doesn't divide B/n_micro contiguously.
        B = x.shape[0]
        x = x.reshape(B // n_micro, n_micro, *x.shape[1:])
        return jnp.moveaxis(x, 1, 0)

    mb = jax.tree.map(slice_micro, batch)
    weighted = _valid_frames(batch) is not None

    def body(carry, mbatch):
        acc, loss_acc, wsum = carry
        loss, g = jax.value_and_grad(loss_fn)(params, mbatch)
        w = _valid_frames(mbatch) if weighted else jnp.float32(1.0)
        acc = jax.tree.map(lambda a, b: a + w * b.astype(a.dtype), acc, g)
        return (acc, loss_acc + w * loss, wsum + w), None

    g0 = jax.tree.map(lambda w: jnp.zeros(w.shape, jnp.float32), params)
    (g, loss, wsum), _ = jax.lax.scan(
        body, (g0, jnp.float32(0.0), jnp.float32(0.0)), mb)
    scale = 1.0 / jnp.maximum(wsum, 1e-6)
    return loss * scale, jax.tree.map(lambda x: x * scale, g)


def _learner_grads(grad_one, shard):
    """Per-learner (loss, grad) over stacked params and a pre-split batch:
    ``vmap`` over the learner axis, under ``shard_map`` over the mesh
    axis of ``shard = (mesh, axis)`` when given, so each device vmaps
    only over the learners it holds."""
    per_learner = jax.vmap(grad_one)
    if shard is None:
        return per_learner
    mesh, axis = shard
    # check_vma=False: pallas_call outputs carry no varying-axes type
    return jax.shard_map(per_learner, mesh=mesh,
                         in_specs=(P(axis), P(axis)),
                         out_specs=(P(axis), P(axis)), check_vma=False)


def _data_parallel_grad(grad_one, shard):
    """(loss, grad) of one replicated model over a batch sharded on the
    mesh axis of ``shard = (mesh, axis)``: each device differentiates its
    own batch shard and the results are averaged over the axis with
    frame weights (a masked loss is a mean over valid frames), which is
    the mean over the whole batch.  Without ``shard`` GSPMD partitions
    the plain gradient."""
    if shard is None:
        return grad_one
    mesh, axis = shard

    def local(params, batch):
        loss, g = grad_one(params, batch)
        w = _valid_frames(batch)
        w = jnp.float32(1.0) if w is None else w
        total = jax.lax.psum(w, axis)

        def mean(x):
            return (jax.lax.psum(x.astype(jnp.float32) * w, axis)
                    / total).astype(x.dtype)

        return mean(loss), jax.tree.map(mean, g)

    return jax.shard_map(local, mesh=mesh, in_specs=(P(), P(axis)),
                         out_specs=(P(), P()), check_vma=False)


def consensus_distance(params, active=None):
    """Mean L2 distance of learner replicas from their average — the
    consensus diagnostic for decentralized SGD (paper §IV-C).  Given the
    (L,) ``active`` mask, over the live learners only (a crashed
    learner's frozen replica is cluster weather, not disagreement)."""
    def one(w):
        if w.ndim == 0 or w.shape[0] == 1:
            return jnp.float32(0.0), jnp.float32(1.0)
        wf = w.astype(jnp.float32)
        if active is None:
            mu = jnp.mean(wf, axis=0, keepdims=True)
            return jnp.sum(jnp.square(wf - mu)), jnp.float32(wf.size)
        n_act = jnp.maximum(jnp.sum(active), 1.0)
        a = active.reshape((-1,) + (1,) * (w.ndim - 1))
        mu = jnp.sum(wf * a, axis=0, keepdims=True) / n_act
        per = jnp.float32(wf.size) / wf.shape[0]
        return jnp.sum(jnp.square(wf - mu) * a), n_act * per

    parts = [one(w) for w in jax.tree.leaves(params)]
    num = sum(p[0] for p in parts)
    den = sum(p[1] for p in parts)
    return jnp.sqrt(num / den)


# ---------------------------------------------------------------------------
# Strategy definitions
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Strategy:
    """A distributed training strategy built around paper Eq. 14.

    ``topology``/``wire`` are only the DEFAULT Transport of the strategy
    (what you get when no explicit transport/config override is passed);
    any strategy runs over any substrate configuration."""

    name: str
    topology: str               # default Transport topology
    wire: str = "f32"           # default Transport wire codec
    stale: bool = False         # gradients at W_{k-1} (async modeling)
    replicated: bool = True     # params carry a leading learner axis
    block_size: int = 0         # >0: BMUF block length (in steps)
    block_momentum: float = 0.9
    block_lr: float = 1.0


STRATEGIES = {
    "sc_psgd": Strategy("sc_psgd", topology="uniform", replicated=False),
    "sc_psgd_replicated": Strategy("sc_psgd_replicated", topology="uniform"),
    "sd_psgd": Strategy("sd_psgd", topology="ring"),
    "ad_psgd": Strategy("ad_psgd", topology="ring", stale=True),
    "downpour": Strategy("downpour", topology="uniform", stale=True),
    # BMUF mixes only at block boundaries; 'uniform' is the block-sync
    # topology (overridable like any other via the transport)
    "bmuf": Strategy("bmuf", topology="uniform", block_size=16),
    "hring": Strategy("hring", topology="hierarchical", stale=True),
    # beyond-paper (anchored in §IV-D comm-reduction survey), now plain
    # substrate configurations rather than bespoke mixers:
    "ad_psgd_q8": Strategy("ad_psgd_q8", topology="ring", wire="int8",
                           stale=True),
    "ad_psgd_exp": Strategy("ad_psgd_exp", topology="exp", stale=True),
}


def get_strategy(name: str) -> Strategy:
    return STRATEGIES[name]


def default_transport(strategy: Strategy) -> Transport:
    """The strategy's native substrate configuration (f32 wire, fused
    payloads) — bit-identical to the pre-substrate mixers."""
    return Transport(topology=strategy.topology, wire=strategy.wire)


def transport_from_cfg(cfg, strategy: Strategy) -> Transport:
    """Resolve the ``comm_*`` knobs of an ArchConfig against the
    strategy defaults (empty string = keep the strategy default)."""
    return Transport(
        topology=getattr(cfg, "comm_topology", "") or strategy.topology,
        wire=getattr(cfg, "comm_wire", "") or strategy.wire,
        intra_wire=getattr(cfg, "comm_intra_wire", "") or "f32",
        bucket_bytes=int(getattr(cfg, "comm_bucket_mb", 0) * 2 ** 20),
        pod_size=getattr(cfg, "comm_pod_size", 1) or 1,
        topk_frac=getattr(cfg, "comm_topk_frac", 0.01),
        staleness_lambda=getattr(cfg, "comm_staleness_lambda", 0.0),
    )


# ---------------------------------------------------------------------------
# Train state / step builder
# ---------------------------------------------------------------------------

def init_state(strategy: Strategy, params, optimizer: Optimizer,
               transport: Optional[Transport] = None, fault_plan=None):
    """params: already stacked with the learner dim if strategy.replicated.

    Pass the SAME ``transport`` and ``fault_plan`` given to
    :func:`make_train_step`: wires with error feedback (topk) carry their
    residuals in ``state['comm']`` (f32 regardless of the parameter
    dtype), and a fault plan adds the per-learner ``staleness`` counters
    (steps since the learner last contributed a gradient) that drive
    staleness-aware mixing weights."""
    transport = transport if transport is not None \
        else default_transport(strategy)
    state = {
        "params": params,
        "opt": (jax.vmap(optimizer.init)(params)
                if strategy.replicated and _learner_dim(params) > 1
                else optimizer.init(params)),
        "step": jnp.zeros((), jnp.int32),
    }
    # distinct buffers (not aliases of params) so the whole state is donatable
    copy = lambda t: jax.tree.map(jnp.copy, t)
    if strategy.stale:
        state["prev_params"] = copy(params)
    if strategy.block_size:
        state["anchor"] = copy(params)
        state["block_mom"] = jax.tree.map(
            lambda w: jnp.zeros(w.shape, jnp.float32), params)
    if strategy.replicated and transport.needs_state:
        state["comm"] = transport.init_comm(params)
    if fault_plan is not None:
        state["staleness"] = jnp.zeros((_learner_dim(params),), jnp.int32)
    return state


def _learner_dim(params) -> int:
    return jax.tree.leaves(params)[0].shape[0]


def _grad_norm(g):
    """Global L2 norm of a gradient tree (f32 accumulation)."""
    sq = sum(jnp.sum(jnp.square(w.astype(jnp.float32)))
             for w in jax.tree.leaves(g))
    return jnp.sqrt(sq)


def _grad_norm_stacked(g_l):
    """(L,) per-learner L2 norms of a stacked gradient tree."""
    sq = sum(jnp.sum(jnp.square(w.astype(jnp.float32)),
                     axis=tuple(range(1, w.ndim)))
             for w in jax.tree.leaves(g_l))
    return jnp.sqrt(sq)


# Each mask-dependent helper below is the identity (or the plain
# function) when its mask is None, so a step built without a fault plan
# traces none of their ops.

def _sel(mask, a, b):
    """Per-learner select over stacked trees: leaf rows where the (L,)
    ``mask`` is set come from ``a``, the rest from ``b``; ``a`` when
    there is no mask."""
    if mask is None:
        return a

    def one(x, y):
        m = (mask > 0).reshape((-1,) + (1,) * (x.ndim - 1))
        return jnp.where(m, x, y)
    return jax.tree.map(one, a, b)


def _reseed_rejoiners(params, faults):
    """Rejoining learners re-enter at the incumbents' consensus mean —
    elastic membership never resurrects a crashed learner's dead weights
    (docs/fault_tolerance.md)."""
    if faults is None:
        return params
    rejoin = faults["rejoin"]
    incumbent = faults["active"] * (1.0 - rejoin)
    n_inc = jnp.maximum(jnp.sum(incumbent), 1.0)

    def one(w):
        wf = w.astype(jnp.float32)
        inc = incumbent.reshape((-1,) + (1,) * (w.ndim - 1))
        mu = jnp.sum(wf * inc, axis=0, keepdims=True) / n_inc
        rj = (rejoin > 0).reshape((-1,) + (1,) * (w.ndim - 1))
        return jnp.where(rj, mu, wf).astype(w.dtype)

    return jax.tree.map(one, params)


def _frame_weighted(loss_l, g_l, lbatch, gmask, n_act):
    """Frame-weighted aggregation: each learner's masked-mean gradient
    is scaled by its valid-frame share among the contributors (``gmask``;
    everyone without a plan), so the uniform 1/L combination (sc_psgd
    mixing) — and proportionally the sd/ad_psgd ring updates — equals the
    gradient of the GLOBAL masked loss over the contributors:
    sum_l f_l g_l / sum_l f_l.  Returns (loss, g_l), the loss being the
    frame-weighted mean.  Rectangular batches without a plan keep their
    gradients and the plain mean loss."""
    lengths = lbatch.get("lengths") if isinstance(lbatch, dict) else None
    if lengths is None and gmask is None:
        return jnp.mean(loss_l), g_l
    if lengths is None:
        frames = jnp.ones(loss_l.shape, jnp.float32)
    else:
        frames = jnp.sum(lengths.astype(jnp.float32),
                         axis=tuple(range(1, lengths.ndim)))
    if gmask is None:
        mean = jnp.mean(frames)
    else:
        frames = gmask * frames
        mean = jnp.sum(frames) / n_act
    w = frames / jnp.maximum(mean, 1e-6)
    g_l = jax.tree.map(
        lambda g: (g.astype(jnp.float32)
                   * w.reshape((-1,) + (1,) * (g.ndim - 1))
                   ).astype(g.dtype), g_l)
    return (jnp.sum(loss_l * frames)
            / jnp.maximum(jnp.sum(frames), 1e-6)), g_l


def make_train_step(strategy: Strategy, loss_fn: Callable,
                    optimizer: Optimizer, lr_schedule: Callable,
                    *, n_learners: int = 1, microbatches: int = 1,
                    with_consensus: bool = False, pre_split: bool = False,
                    transport: Optional[Transport] = None,
                    with_grad_norm: bool = False, shard=None,
                    fault_plan=None):
    """Build the jittable train step.

    loss_fn(params, batch) -> scalar, over UNstacked params/batch.
    Batches carrying a ``lengths`` key (variable-length utterances; see
    repro.data.pipeline) get frame-weighted aggregation: learner
    gradients are scaled by their valid-frame share before mixing, and
    the reported loss is the frame-weighted mean.
    For replicated strategies the step expects state['params'] stacked
    (L, ...) and the global batch either pre-split to (L, B/L, ...) with an
    explicit ('learner','batch',...) sharding (``pre_split=True`` — required
    when the learner axis is 'pod': an in-step reshape of a data-sharded
    batch dim into (pod, data) is not GSPMD-representable and silently
    replicates the learner work), or flat (B, ...) to be reshaped here.

    ``transport`` configures the communication substrate (topology ×
    wire × bucketing; default: the strategy's native f32 configuration,
    bit-identical to the pre-substrate step).  Replicated steps emit
    ``metrics['wire_bytes']`` — analytic bytes sent per learner this
    step (0 on non-sync BMUF steps).  Non-replicated sc_psgd averages
    gradients through GSPMD, not the substrate, so it carries no
    wire-byte telemetry (see docs/strategies.md).

    ``with_grad_norm`` adds ``metrics['grad_norm']`` — the L2 norm of
    the applied gradient (mean of the per-learner norms on replicated
    strategies).  Off by default: the extra reduction changes the jit
    graph, and the observability layer's zero-overhead contract is
    that uninstrumented runs stay bit-identical.

    ``shard = (mesh, axis)`` computes the gradients under ``shard_map``
    over that mesh axis (module docstring) — the learner axis for
    replicated strategies, the batch axis otherwise.

    ``fault_plan`` (a :class:`repro.core.faults.FaultPlan`) makes the
    step fault-tolerant, ``step(state, batch, faults)``, with ``faults``
    one :meth:`FaultPlan.step_inputs` dict (traced: one compile covers
    any schedule) and the state from :func:`init_state` with the same
    plan.  Mixing then runs over the live set through
    :meth:`Transport.make_elastic_mixer` (dead learners frozen
    bit-for-bit); rejoiners re-enter at the incumbents' consensus mean
    with a fresh optimizer state; learners that do not contribute apply
    no gradient, keep their optimizer state and grow stale; frame
    weights renormalize over the contributors.  The normative text is
    docs/fault_tolerance.md.  With everyone active the trajectory
    matches the plan-less step to f32 matmul tolerance (a matrix
    contraction where the plain mixer uses rolls/means).  Only
    replicated strategies take a plan.  Without one the step is
    ``step(state, batch)`` and traces no mask.

    The returned function is named ``train_step``, so its jit's HLO
    module (``jit_train_step``) and compile records say which program
    they are; its parts run under ``jax.named_scope`` ``grad``,
    ``mixing``, ``update``, ``grad_norm`` and ``consensus``, which land
    in the compiled ops' metadata (docs/observability.md).
    """
    transport = transport if transport is not None \
        else default_transport(strategy)
    if not strategy.replicated:
        if fault_plan is not None:
            raise ValueError(
                f"strategy {strategy.name!r} is not replicated: elastic "
                f"membership needs a stacked learner axis to mask — use "
                f"'sc_psgd_replicated' for an elastic allreduce baseline")
        mixer = None
    elif fault_plan is None:
        mixer = transport.make_mixer(n_learners)
    else:
        mixer = transport.make_elastic_mixer(
            n_learners, fault_seed=fault_plan.seed,
            with_corruption=fault_plan.corrupt_prob > 0)

    def grad_one(params, batch):
        return _accumulated_grad(loss_fn, params, batch, microbatches)

    learner_grads = _learner_grads(grad_one, shard)
    data_parallel_grad = _data_parallel_grad(grad_one, shard)

    def train_step(state, batch, faults=None):
        if (faults is None) != (fault_plan is None):
            raise TypeError("a step built with a fault plan takes one "
                            "FaultPlan.step_inputs dict per call; a step "
                            "built without one takes none")
        lr = lr_schedule(state["step"])
        metrics = {}

        if not strategy.replicated:
            # plain data-parallel SGD: gradient averaging over the data axis
            # (GSPMD, or the psum under ``shard``; batch sharded, params
            # replicated/FSDP) — the allreduce realization of the PS
            # (paper Eq. 13).
            with jax.named_scope("grad"):
                loss, g = data_parallel_grad(state["params"], batch)
            with jax.named_scope("update"):
                new_params, opt = optimizer.update(g, state["opt"],
                                                   state["params"], lr)
            out = {"params": new_params, "opt": opt,
                   "step": state["step"] + 1}
            metrics["loss"] = loss
            if with_grad_norm:
                with jax.named_scope("grad_norm"):
                    metrics["grad_norm"] = _grad_norm(g)
            return out, metrics

        # membership first: rejoiners re-enter at the incumbents' mean
        # with a fresh optimizer state and zero staleness
        params, opt = state["params"], state["opt"]
        active = gmask = n_act = staleness = None
        if faults is not None:
            active = faults["active"]
            gmask = active * faults["contrib"]
            n_act = jnp.maximum(jnp.sum(active), 1.0)
            params = _reseed_rejoiners(params, faults)
            opt = _sel(faults["rejoin"], jax.vmap(optimizer.init)(params),
                       opt)
            staleness = jnp.where(faults["rejoin"] > 0, 0,
                                  state["staleness"])

        lbatch = batch if pre_split else split_learner_batch(batch, n_learners)
        grad_at = (_reseed_rejoiners(state["prev_params"], faults)
                   if strategy.stale else params)
        with jax.named_scope("grad"):
            loss_l, g_l = learner_grads(grad_at, lbatch)
        metrics["loss"], g_l = _frame_weighted(loss_l, g_l, lbatch, gmask,
                                               n_act)
        if with_grad_norm:
            # mean applied-gradient norm over the contributors
            with jax.named_scope("grad_norm"):
                norms = _grad_norm_stacked(g_l)
                metrics["grad_norm"] = (
                    jnp.mean(norms) if gmask is None else
                    jnp.sum(norms * gmask) / jnp.maximum(jnp.sum(gmask), 1.0))

        comm = state.get("comm", {})
        wire_bytes = jnp.float32(transport.wire_bytes(params))
        if faults is not None:
            wire_bytes = wire_bytes * n_act / n_learners

        def mix(p, step_no, comm):
            with jax.named_scope("mixing"):
                if faults is None:
                    return mixer(p, step_no, comm)
                # the elastic mixer carries no comm state (topk refused)
                return mixer(p, step_no, active, staleness,
                             faults["edge_ok"], faults["corrupt"]), comm

        if strategy.block_size:
            # BMUF: local SGD inside a block; blockwise model-update
            # filtering at block boundaries.  The block sync goes through
            # the substrate, so e.g. int8 block sync is one config away.
            # Under a plan, contributors run the local SGD, and at block
            # boundaries the survivors sync while the dead keep
            # params/anchor/momentum frozen.
            anchor = _reseed_rejoiners(state["anchor"], faults)
            mom = state["block_mom"]
            if faults is not None:
                mom = _sel(faults["rejoin"],
                           jax.tree.map(jnp.zeros_like, mom), mom)
            with jax.named_scope("update"):
                upd_params, new_opt = jax.vmap(
                    optimizer.update, in_axes=(0, 0, 0, None)
                )(g_l, opt, params, lr)
            upd_params = _sel(gmask, upd_params, params)
            new_opt = _sel(gmask, new_opt, opt)
            step_no = state["step"] + 1
            is_sync = (step_no % strategy.block_size) == 0

            def do_sync(args):
                p, anchor, mom, comm = args
                avg, comm = mix(p, step_no, comm)
                delta = jax.tree.map(
                    lambda a, b: (a.astype(jnp.float32)
                                  - b.astype(jnp.float32)), avg, anchor)
                new_mom = jax.tree.map(
                    lambda m, d: strategy.block_momentum * m
                    + strategy.block_lr * d, mom, delta)
                new = jax.tree.map(
                    lambda b, m: (b.astype(jnp.float32) + m).astype(b.dtype),
                    anchor, new_mom)
                return (_sel(active, new, p), _sel(active, new, anchor),
                        _sel(active, new_mom, mom), comm)

            def no_sync(args):
                return args

            new_params, anchor, mom, comm = jax.lax.cond(
                is_sync, do_sync, no_sync, (upd_params, anchor, mom, comm))
            out = {"params": new_params, "opt": new_opt, "step": step_no,
                   "anchor": anchor, "block_mom": mom}
            metrics["wire_bytes"] = jnp.where(is_sync, wire_bytes, 0.0)
        else:
            # Eq. 14: mixing of the current iterate is data-independent of
            # the gradient (evaluated at prev iterate when stale) -> XLA can
            # schedule the collective concurrently with compute; chunked
            # buckets (transport.bucket_bytes) deepen that interleaving.
            mixed, comm = mix(params, state["step"], comm)
            with jax.named_scope("update"):
                upd_params, new_opt = jax.vmap(
                    optimizer.update, in_axes=(0, 0, 0, None)
                )(g_l, opt, mixed, lr)
            # contributors step from the mixed iterate; alive
            # non-contributors keep the mixed iterate (they gossiped but
            # computed nothing); the dead stay exactly where they were
            new_params = _sel(active, _sel(gmask, upd_params, mixed), params)
            new_opt = _sel(gmask, new_opt, opt)
            out = {"params": new_params, "opt": new_opt,
                   "step": state["step"] + 1}
            metrics["wire_bytes"] = wire_bytes

        if "comm" in state:
            out["comm"] = comm
        if strategy.stale:
            out["prev_params"] = params
        if faults is not None:
            out["staleness"] = jnp.where(gmask > 0, 0, staleness + 1
                                         ).astype(jnp.int32)
            metrics["n_active"] = n_act
            metrics["n_contrib"] = jnp.sum(gmask)
            metrics["staleness_max"] = jnp.max(out["staleness"]
                                               * (active > 0))
        if with_consensus:
            with jax.named_scope("consensus"):
                metrics["consensus"] = consensus_distance(out["params"],
                                                          active)
        return out, metrics

    return train_step


def stack_for_learners(params, n_learners: int):
    """Replicate freshly-initialized params into the stacked learner axis."""
    return jax.tree.map(
        lambda w: jnp.broadcast_to(w[None], (n_learners,) + w.shape), params)


def average_learners(params):
    """Collapse replicas to the consensus model (for eval/checkpoint)."""
    return jax.tree.map(
        lambda w: jnp.mean(w.astype(jnp.float32), axis=0).astype(w.dtype),
        params)
