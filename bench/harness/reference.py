"""The plain reference job, and the readings that ``correct`` compares.

The reference follows the first three steps of the cell's training job
with nothing of the program: its own model (``configs/<config>.py``),
strategy (``strategies/<strategy>.py``), optimizer
(``optimizers/<optimizer>.py``) and learning rate
(``schedules/<schedule>.py``), in float32 arithmetic at ``highest``
matmul precision.  Parameters are held between operations in the dtype
the configuration states (bfloat16 weights, float32 biases), as the job
holds them: the mixed iterate and the updated one are both rounded to
it.

``variant`` puts a broken or lower-precision job in the program's place
(the control and the planted faults of the comparison's limits):
``fp8`` rounds every matmul operand to float8 e4m3 under a per-tensor
scale in the forward pass (the step below the bfloat16 that the
configuration states); ``half_batch`` takes each learner's gradient
over the first half of its rows; ``no_exchange`` leaves the learners'
mixing out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from harness import weights

HIGHEST = jax.lax.Precision.HIGHEST
VARIANTS = ("reference", "fp8", "half_batch", "no_exchange")


def einsum_f32(spec, a, b):
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _fp8(x):
    """x rounded to float8 e4m3 under a per-tensor scale in the forward
    pass; the backward pass sees the rounding as the identity."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def einsum_fp8(spec, a, b):
    return einsum_f32(spec, _fp8(a), _fp8(b))


def lr_at(cell, k: int) -> float:
    """The job's learning rate at step k."""
    return cell.schedule.lr_at(cell.traffic["lr"], k)


def lead(cell) -> int:
    """Learner replicas stacked on the parameters."""
    if not cell.strategy.STACKED:
        raise NotImplementedError(
            f"strategy {cell.traffic['strategy']!r}: the reference holds "
            f"stacked learner replicas only")
    return int(cell.traffic["learners"])


def _norms(tree):
    """{path: (L,) float32} per-learner L2 norms."""
    return {p: jnp.sqrt(jnp.sum(jnp.square(
        w.astype(jnp.float32).reshape(w.shape[0], -1)), 1))
        for p, w in tree.items()}


def readings_fns(cell, steps: int):
    """Jitted (grad_norms, change_norms) of a job's state.  The first
    gradient as the optimizer got it is ``(T W_0 - W_1) / lr_0``: the
    update applied to the mixed iterate.  The change after ``steps`` steps
    is ``W_steps - T^steps W_0``: what the gradients moved, net of the
    mixing alone.  T is the strategy's mixing, held in the parameters'
    dtype after each application as the job holds it; W_0 is made again
    from the key inside the call, so no copy of it is kept."""
    make = weights.make_fn(cell.model.layout(cell.sizes), lead(cell))
    mix = cell.strategy.mix

    def grad(key, p1, lr0):
        m0 = mix(make(key))
        return _norms({k: (m0[k].astype(jnp.float32)
                           - p1[k].astype(jnp.float32)) / lr0 for k in m0})

    def change(key, pn):
        m = make(key)
        for _ in range(steps):
            m = mix(m)
        return _norms({k: pn[k].astype(jnp.float32)
                       - m[k].astype(jnp.float32) for k in m})

    return jax.jit(grad), jax.jit(change)


def to_host(norms: dict) -> dict:
    """{(learner, path): float} from {path: (L,) array}."""
    host = jax.device_get(norms)
    return {(i, p): float(v) for p, arr in host.items()
            for i, v in enumerate(np.asarray(arr).reshape(-1))}


def make_step(cell, mesh, variant: str = "reference"):
    """The reference's jitted step on ``mesh`` (axis ``x`` holds the
    learners), and the sharding of its learner-stacked arrays.  The state
    is ``(W_k, W_{k-1})`` for a stale strategy and ``(W_k,)`` otherwise."""
    assert variant in VARIANTS, variant
    model, sizes = cell.model, cell.sizes
    strategy, optimizer = cell.strategy, cell.optimizer
    lspec = NamedSharding(mesh, P("x"))
    einsum = einsum_fp8 if variant == "fp8" else einsum_f32

    def vg(params, batch):
        if variant == "half_batch":
            batch = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
        p32 = {k: v.astype(jnp.float32) for k, v in params.items()}
        return jax.value_and_grad(
            lambda p: model.loss(sizes, p, batch, einsum=einsum))(p32)

    # one learner at a time on each chip, the chips in parallel
    grads = jax.shard_map(
        lambda pp, bb: jax.lax.map(lambda a: vg(*a), (pp, bb)),
        mesh=mesh, in_specs=(P("x"), P("x")), out_specs=(P("x"), P("x")),
        check_vma=False)
    mix = (lambda q: q) if variant == "no_exchange" else strategy.mix

    def update(p, at, batch, lr):
        with jax.default_matmul_precision("highest"):
            loss, g = grads(at, batch)
            new = optimizer.update(mix(p), g, lr)
        return new, jnp.mean(loss)

    if strategy.STALE:
        # (W_k, W_{k-1}) -> (W_{k+1}, W_k)
        step = jax.jit(lambda s, b, lr: (*update(s[0], s[1], b, lr), s[0]),
                       donate_argnums=(0,))
    else:
        step = jax.jit(lambda s, b, lr: update(s[0], s[0], b, lr),
                       donate_argnums=(0,))
    return step, lspec


def run(cell, seed: int, host_batches: list, devices,
        variant: str = "reference", steps: int = 3) -> dict:
    """Follow ``steps`` steps of the job on ``host_batches[:steps]``;
    returns the readings ``losses``, ``grad`` and ``change``."""
    layout = cell.model.layout(cell.sizes)
    L = lead(cell)
    assert L % len(devices) == 0, (L, len(devices))
    mesh = Mesh(np.asarray(devices), ("x",))
    step, lspec = make_step(cell, mesh, variant)
    key = weights.key_of(seed)
    p = weights.make_params(layout, seed, L, {k: lspec for k in layout})

    def place(b):
        return {k: jax.device_put(
            v.reshape(L, v.shape[0] // L, *v.shape[1:]), lspec)
            for k, v in b.items()}

    stale = cell.strategy.STALE
    gfn, cfn = readings_fns(cell, steps)
    state = (p, jax.tree.map(jnp.copy, p)) if stale else (p,)
    losses, grad = [], None
    for k in range(steps):
        out = step(state, place(host_batches[k]), jnp.float32(lr_at(cell, k)))
        p, loss = out[0], out[1]
        state = (p, out[2]) if stale else (p,)
        losses.append(float(loss))
        if k == 0:
            grad = to_host(gfn(key, p, jnp.float32(lr_at(cell, 0))))
    change = to_host(cfn(key, p))
    for a in jax.tree.leaves(state):
        a.delete()
    return {"losses": losses, "grad": grad, "change": change}
