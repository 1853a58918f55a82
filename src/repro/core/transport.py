"""Unified communication substrate: topology × wire × overlap (paper §IV-D).

The paper's thesis is that distributed ASR training is won by "striking
the balance between communication and computation" (§IV-D, §V), and the
winning configurations in practice are *combinations* — hierarchical
topology + compressed payloads + overlapped collectives.  This module
factors communication out of the strategies into one composable
:class:`Transport` that every mixing/aggregation site goes through:

* ``topology`` — who exchanges with whom, all expressed as doubly-
  stochastic mixing matrices over the stacked learner axis (Eq. 14):

  =============  ========================================================
  ``uniform``    T_u global averaging — the allreduce realization of a
                 parameter server (Eq. 13); used by SC-PSGD / downpour
                 and BMUF block sync.
  ``ring``       T_1 neighbor averaging — a pair of collective-permutes;
                 SD/AD-PSGD.
  ``hierarchical``  T_u inside each pod of ``pod_size`` learners, T_1
                 ring across pods (the paper's §V H-ring as a topology,
                 no longer a bespoke strategy); as a matrix this is
                 kron(ring(L/p), uniform(p)) — see
                 ``mixing.hierarchical_matrix``.
  ``exp``        one-peer exponential graph [Assran'19]: hypercube
                 gossip, exact consensus every log2(L) rounds.
  ``none``       identity (local SGD; BMUF between block boundaries).
  =============  ========================================================

* ``wire`` — the codec applied to every payload that crosses the wire
  (neighbor permutes, allreduce contributions, inter-pod exchanges).
  On the flat topologies the local replica stays full precision — only
  what a *peer* receives is coded.  The one exception is the
  hierarchical INTRA-pod stage: it models an allreduce, where every
  member's contribution is reduced remotely, so the pod mean is taken
  over coded payloads (own included):

  =========  =========================================================
  ``f32``    4 B/elem, exact (default; bit-identical to the
             pre-substrate mixers).
  ``bf16``   2 B/elem truncation.
  ``int8``   1 B/elem symmetric linear quantization, one f32 scale per
             sender per bucket (per-tensor when unbucketed).  Rounding
             error is <= scale/2 per round and is re-averaged by the
             mixing contraction, so no residual state is needed.
  ``topk``   magnitude sparsification: each sender ships the largest
             ``topk_frac`` fraction of entries (8 B per kept entry:
             value + index).  Sparsifying raw weights would shrink
             peers toward zero, so topk uses DIFFERENCE CODING against
             a shared public estimate [CHOCO-SGD, Koloskova'19]: every
             node tracks each sender's estimate ŵ (reconstructible
             from the payload stream alone), the sender ships
             C(w − ŵ), all trackers apply ŵ ← ŵ + C(·), and mixing
             becomes the damped gossip  w += γ·(T·ŵ − ŵ)  with
             consensus step ``gossip_gamma``.  The un-shipped mass
             r = (w − ŵ) − C(w − ŵ) is the ERROR-FEEDBACK residual:
             it stays inside w − ŵ (the estimate only advances by what
             was sent) and is re-offered every round [Seide'14,
             Aji'17]; it is also materialized in ``state['comm']`` so
             tests/telemetry can assert the EF contract.  ŵ and r
             accumulate in f32 regardless of the parameter dtype.
             Because T is doubly stochastic, γ-damped gossip preserves
             the replica mean exactly — compression error never leaks
             into the consensus average.
  =========  =========================================================

* ``bucket_bytes`` — chunked collectives: payloads larger than this are
  split into buckets that are coded/exchanged independently, giving XLA
  a stream of small independent collectives it can interleave with
  backward compute instead of one monolithic transfer (0 = one fused
  payload per tensor).  f32 bucketing is bit-exact; int8/topk code each
  bucket independently (per-bucket scales/top-k, the standard bucketed
  formulation).

``Transport.wire_bytes`` is the single source for wire-byte telemetry:
analytic bytes SENT per learner per mixing round, from the leaf shapes
and the codec — emitted into train metrics as ``wire_bytes``.
The accounting conventions (per-topology multipliers, codec overheads)
are documented in docs/strategies.md.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mixing

TOPOLOGIES = ("none", "uniform", "ring", "hierarchical", "exp")
WIRES = ("f32", "bf16", "int8", "topk")

# wires that carry an error-feedback residual in strategy state
_EF_WIRES = ("topk",)


def _needs_ef(wire: str) -> bool:
    return wire in _EF_WIRES


# ---------------------------------------------------------------------------
# Wire codecs (per-sender; operate on (G, n) f32 payload buckets)
# ---------------------------------------------------------------------------

def decode_payload(wire: str, x, topk_frac: float = 0.01):
    """What the receivers see of the (G, n) f32 payload ``x``: each of the
    G senders' rows is coded independently (per-sender scales/top-k)."""
    if wire == "f32":
        return x
    if wire == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if wire == "int8":
        amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        return q.astype(jnp.float32) * scale
    if wire == "topk":
        n = x.shape[1]
        k = _topk_k(n, topk_frac)
        if k >= n:
            return x
        kth = jax.lax.top_k(jnp.abs(x), k)[0][:, -1:]
        # >= keeps ties (may ship slightly more than k on degenerate
        # inputs); the wire accounting uses the nominal k
        return jnp.where(jnp.abs(x) >= kth, x, 0.0)
    raise ValueError(f"unknown wire {wire!r}; expected one of {WIRES}")


def _topk_k(n: int, frac: float) -> int:
    return min(n, max(1, int(np.ceil(frac * n))))


def _ring_sends(G: int) -> float:
    """Payloads each member sends per T_1 round: both neighbors (2), the
    single neighbor when G==2, nothing when alone."""
    return 0.0 if G <= 1 else (1.0 if G == 2 else 2.0)


# ---------------------------------------------------------------------------
# Topology combines: local replica w (full precision) + decoded peers d
# ---------------------------------------------------------------------------

def _combine_ring(w, d):
    G = w.shape[0]
    if G == 1:
        return w
    if G == 2:
        return (2.0 * w + jnp.roll(d, 1, axis=0)) / 3.0
    return (w + jnp.roll(d, 1, axis=0) + jnp.roll(d, -1, axis=0)) / 3.0


def _combine_uniform(w, d):
    G = w.shape[0]
    if G == 1:
        return w
    # own contribution stays exact; peers' arrive decoded
    return (w - d + jnp.sum(d, axis=0, keepdims=True)) / G


def _combine_exp(w, d, step, G):
    if G == 1:
        return w
    m = int(np.log2(G))
    branches = [
        (lambda s: lambda: (w + jnp.roll(d, s, axis=0)) / 2.0)(2 ** i)
        for i in range(m)
    ]
    return jax.lax.switch(step % m, branches)


# ---------------------------------------------------------------------------
# Transport
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Transport:
    """One composable communication configuration (see module docstring)."""

    topology: str = "ring"
    wire: str = "f32"
    # hierarchical only: codec of the intra-pod averaging stage (the
    # inter-pod ring uses ``wire``) — e.g. bf16 intra-pod + topk inter-pod
    intra_wire: str = "f32"
    bucket_bytes: int = 0        # 0 = one fused payload per tensor
    pod_size: int = 1            # hierarchical: learners per pod
    topk_frac: float = 0.01      # topk wire: fraction of entries shipped
    # consensus step of the difference-coded (topk) gossip.  0 = auto:
    # min(0.5, topk_frac) — CHOCO theory wants gamma = O(compression
    # quality), and empirically gamma ≲ 2·topk_frac is the stable region
    # (pure-gossip divergence beyond it); 1.0 (plain mixing) is only safe
    # for near-exact wires.
    gossip_gamma: float = 0.0
    # elastic mixing only: staleness damping λ — a learner whose params
    # are s steps behind gets confidence 1/(1 + λ·s) in the mixing
    # matrix (mixing.staleness_damped; docs/fault_tolerance.md).  0
    # disables damping.  Ignored by the non-elastic make_mixer path.
    staleness_lambda: float = 0.0

    def __post_init__(self):
        if self.topology not in TOPOLOGIES:
            raise ValueError(f"unknown topology {self.topology!r}; "
                             f"expected one of {TOPOLOGIES}")
        for w in (self.wire, self.intra_wire):
            if w not in WIRES:
                raise ValueError(f"unknown wire {w!r}; "
                                 f"expected one of {WIRES}")
        if self.intra_wire in _EF_WIRES:
            raise ValueError(
                f"intra_wire {self.intra_wire!r} is not supported: "
                f"difference-coded wires are gossip-only (they need the "
                f"γ-damped update against a tracked estimate) and cannot "
                f"realize the intra-pod allreduce — use f32/bf16/int8 "
                f"intra-pod and save topk for the inter-pod ring")
        if self.pod_size < 1:
            raise ValueError(f"pod_size must be >= 1, got {self.pod_size}")
        if not 0.0 < self.topk_frac <= 1.0:
            raise ValueError(f"topk_frac must be in (0, 1], "
                             f"got {self.topk_frac}")
        if not 0.0 <= self.gossip_gamma <= 1.0:
            raise ValueError(f"gossip_gamma must be in [0, 1] (0 = auto), "
                             f"got {self.gossip_gamma}")
        if self.staleness_lambda < 0.0:
            raise ValueError(f"staleness_lambda must be >= 0, "
                             f"got {self.staleness_lambda}")

    @property
    def resolved_gamma(self) -> float:
        return self.gossip_gamma or min(0.5, self.topk_frac)

    # -- state ----------------------------------------------------------
    @property
    def needs_state(self) -> bool:
        """True when the wire carries an error-feedback residual that must
        live in the strategy state (threaded through the train step)."""
        return _needs_ef(self.wire)

    def init_comm(self, params) -> dict:
        """Error-feedback state: per-sender residual + shared public
        estimate (difference coding), ALWAYS f32 zeros regardless of the
        parameter dtype (bf16 accumulation of tiny per-round errors
        stalls: the residual magnitude quickly falls below the bf16 ulp
        of the running sum and silently stops accumulating)."""
        comm = {}
        if _needs_ef(self.wire):
            def main_shape(w):
                s = tuple(w.shape)
                if self.topology == "hierarchical":
                    s = (s[0] // self.pod_size,) + s[1:]
                return jnp.zeros(s, jnp.float32)
            comm["residual"] = jax.tree.map(main_shape, params)
            comm["estimate"] = jax.tree.map(main_shape, params)
        return comm

    # -- mixing ---------------------------------------------------------
    def make_mixer(self, n_learners: int):
        """Returns ``mix(params, step, comm) -> (mixed, comm)`` over the
        stacked learner axis.  With ``wire='f32'`` and no bucketing the
        fast path delegates to the pure-topology mixers in
        ``repro.core.mixing`` and is bit-identical to them."""
        t = self
        if t.topology == "hierarchical" and n_learners % t.pod_size:
            raise ValueError(
                f"hierarchical topology needs pod_size ({t.pod_size}) to "
                f"divide n_learners ({n_learners})")
        if t.topology == "exp":
            m = max(int(np.log2(max(n_learners, 1))), 1)
            if 2 ** m != n_learners and n_learners != 1:
                raise ValueError("exp topology wants power-of-2 learners, "
                                 f"got {n_learners}")

        # the fast path must also rule out a lossy INTRA-pod codec, which
        # only bites when the hierarchical intra stage actually exists
        plain_intra = (t.topology != "hierarchical" or t.pod_size == 1
                       or t.intra_wire == "f32")
        plain_wire = (t.wire == "f32" and t.bucket_bytes == 0
                      and plain_intra)
        if plain_wire and not t.needs_state:
            if t.topology == "none":
                return lambda p, step, comm: (p, comm)
            if t.topology == "uniform":
                return lambda p, step, comm: (mixing.mix_uniform(p), comm)
            if t.topology == "ring" or (t.topology == "hierarchical"
                                        and t.pod_size == 1):
                return lambda p, step, comm: (mixing.mix_ring(p), comm)
            if t.topology == "hierarchical" and t.pod_size == n_learners:
                return lambda p, step, comm: (mixing.mix_uniform(p), comm)
            if t.topology == "hierarchical":
                mix_h = functools.partial(mixing.mix_hierarchical,
                                          pod_size=t.pod_size)
                return lambda p, step, comm: (mix_h(p), comm)
            if t.topology == "exp":
                exp = mixing.make_exp_mixer(n_learners)
                return lambda p, step, comm: (exp(p, step), comm)

        return functools.partial(_general_mix, t, n_learners)

    def make_elastic_mixer(self, n_learners: int, *, fault_seed: int = 0,
                           with_corruption: bool = False):
        """Elastic-membership mixing (docs/fault_tolerance.md): returns

            ``mix(params, step, active, staleness, edge_ok, corrupt)
              -> mixed``

        where the masks come from ``repro.core.faults.FaultPlan.
        step_inputs`` plus the per-learner staleness counters carried in
        strategy state.  The topology is rebuilt every step over the
        live set (``mixing.elastic_matrix``): dead learners are identity
        rows (their replicas frozen bit-for-bit), dropped gossip edges
        return their mass to the diagonal, and with ``staleness_lambda``
        > 0 learners s steps behind are down-weighted by 1/(1 + λ·s).
        All inputs may be traced — one jit compile covers the whole run.

        Differences from :meth:`make_mixer`:

        * single-stage matrix contraction — ``intra_wire`` does not
          apply (the hierarchical intra/inter stages collapse into one
          doubly-stochastic matrix, coded uniformly with ``wire``);
        * no comm state — difference-coded wires (topk) are REJECTED:
          their shared public estimate assumes every tracker sees every
          payload, which elastic membership breaks (a rejoiner's
          estimate is stale-by-unknown), so there is no correct EF
          residual to carry.  Use f32/bf16/int8 wires under faults.
        * the local replica always stays exact: only the peer view is
          wire-coded, and (``with_corruption``) only the peer view picks
          up the fault plan's payload noise — deterministic per
          (fault_seed, step, leaf).
        """
        t = self
        if t.needs_state:
            raise ValueError(
                f"wire {t.wire!r} is difference-coded (error-feedback "
                f"state) and cannot run under elastic membership: the "
                f"shared public estimate desynchronizes when learners "
                f"crash or rejoin — use an f32/bf16/int8 wire with "
                f"--fault-* runs")
        if t.topology == "hierarchical" and n_learners % t.pod_size:
            raise ValueError(
                f"hierarchical topology needs pod_size ({t.pod_size}) to "
                f"divide n_learners ({n_learners})")

        def mix(params, step, active, staleness, edge_ok, corrupt):
            if t.topology == "none":
                return params
            T = mixing.elastic_matrix(
                active, t.topology, step=step, pod_size=t.pod_size,
                staleness=staleness, staleness_lambda=t.staleness_lambda,
                edge_ok=edge_ok)
            diag = jnp.diag(T)
            off = T - jnp.diag(diag)

            def one(i, w):
                wf = w.astype(jnp.float32).reshape(n_learners, -1)
                d = _coded(t, t.wire, wf)
                if with_corruption:
                    key = jax.random.fold_in(
                        jax.random.fold_in(
                            jax.random.PRNGKey(fault_seed), step), i)
                    rms = jnp.sqrt(jnp.mean(d * d, axis=1, keepdims=True))
                    noise = jax.random.normal(key, d.shape, jnp.float32)
                    d = d + corrupt[:, None] * rms * noise
                # peers' views arrive through the (coded, possibly
                # corrupted) wire; the local replica contributes exactly
                out = off @ d + diag[:, None] * wf
                return out.reshape(w.shape).astype(w.dtype)

            leaves, treedef = jax.tree.flatten(params)
            return jax.tree.unflatten(
                treedef, [one(i, w) for i, w in enumerate(leaves)])

        return mix

    # -- telemetry ------------------------------------------------------
    def wire_bytes(self, params) -> float:
        """Analytic bytes SENT per learner per mixing round, from leaf
        shapes only (works on ShapeDtypeStructs).  Conventions in
        docs/strategies.md: ring = 2 payloads (1 when L==2), uniform =
        2(L-1)/L (ring-allreduce schedule regardless of codec),
        exp = 1, hierarchical = intra uniform over the pod + the pod
        ring amortized over its members."""
        total = 0.0
        for leaf in jax.tree.leaves(params):
            L = int(leaf.shape[0])
            n = int(np.prod(leaf.shape[1:])) if len(leaf.shape) > 1 else 1
            if self.topology == "hierarchical":
                p = self.pod_size
                pods = L // p
                intra = (0.0 if p == 1 else
                         2.0 * (p - 1) / p
                         * self._payload_bytes(self.intra_wire, n))
                inter = (0.0 if pods == 1 else
                         _ring_sends(pods)
                         * self._payload_bytes(self.wire, n) / p)
                total += intra + inter
            else:
                mult = {
                    "none": 0.0,
                    "ring": _ring_sends(L),
                    "uniform": 2.0 * (L - 1) / L,
                    "exp": 1.0 if L > 1 else 0.0,
                }[self.topology]
                total += mult * self._payload_bytes(self.wire, n)
        return total

    def _payload_bytes(self, wire: str, n: int) -> float:
        """Coded size of one sender's n-element tensor, incl. per-bucket
        codec overheads (int8 scale, topk value+index pairs)."""
        sizes = _bucket_sizes(n, self.bucket_bytes)
        if wire == "f32":
            return 4.0 * n
        if wire == "bf16":
            return 2.0 * n
        if wire == "int8":
            return float(n + 4 * len(sizes))
        if wire == "topk":
            return float(sum(8 * _topk_k(s, self.topk_frac) for s in sizes))
        raise ValueError(wire)


# ---------------------------------------------------------------------------
# General (coded / bucketed) mixing path
# ---------------------------------------------------------------------------

def _bucket_sizes(n: int, bucket_bytes: int) -> list:
    """Column-bucket sizes of an n-element f32 payload — the single
    source of the bucketing rule, shared by the codec splitter and the
    wire-byte accounting so the two cannot drift apart."""
    if bucket_bytes <= 0 or n * 4 <= bucket_bytes:
        return [n]
    per = max(1, bucket_bytes // 4)
    return [min(per, n - i) for i in range(0, n, per)]


def _split_cols(x, bucket_bytes: int):
    """Split (G, n) into column buckets of <= bucket_bytes f32 payload."""
    sizes = _bucket_sizes(x.shape[1], bucket_bytes)
    if len(sizes) == 1:
        return [x]
    return jnp.split(x, list(np.cumsum(sizes[:-1])), axis=1)


def _coded(t: Transport, wire: str, x):
    """Bucket-wise decode; returns the decoded full (G, n) tensor."""
    parts = [decode_payload(wire, c, t.topk_frac)
             for c in _split_cols(x, t.bucket_bytes)]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _wire_stage(t: Transport, wire: str, x, ef):
    """One coded exchange of the (G, n) payload ``x``.

    Returns ``(peer_view, ef')`` — what the receivers hold for each
    sender afterwards, plus the updated error-feedback state.  Without
    error feedback the peer view is simply the decoded payload.  With it
    (topk), difference coding against the shared estimate [CHOCO-SGD]:
    payload = C(x − ŵ); every tracker applies ŵ ← ŵ + payload; the
    dropped mass (x − ŵ') − the f32 residual — stays inside the next
    round's difference and is re-offered automatically."""
    if not _needs_ef(wire):
        return _coded(t, wire, x), ef
    if ef is None:
        raise ValueError(
            f"wire {wire!r} carries error-feedback state: pass the same "
            f"Transport to init_state(...) so state['comm'] holds the "
            f"residual/estimate trees")
    _, est = ef
    delta = x - est
    d = _coded(t, wire, delta)
    est = est + d
    return est, (delta - d, est)


def _general_mix(t: Transport, n_learners: int, params, step, comm):
    comm = comm or {}

    def leaves_or_none(key):
        tree = comm.get(key)
        return (jax.tree.leaves(tree) if tree is not None else None)

    leaves, treedef = jax.tree.flatten(params)
    n = len(leaves)
    ef_main = _zip_ef(leaves_or_none("residual"),
                      leaves_or_none("estimate"), n)

    outs = [_mix_leaf(t, w, step, a) for w, a in zip(leaves, ef_main)]

    mixed = jax.tree.unflatten(treedef, [o[0] for o in outs])
    new_comm = dict(comm)
    for key, idx in (("residual", 1), ("estimate", 2)):
        if key in comm:
            new_comm[key] = jax.tree.unflatten(
                treedef, [o[idx] for o in outs])
    return mixed, new_comm


def _zip_ef(residuals, estimates, n):
    if residuals is None:
        return [None] * n
    return list(zip(residuals, estimates))


def _flat_ef(ef, G):
    """Error-feedback pair reshaped to the (G, n) payload domain."""
    if ef is None:
        return None
    return tuple(a.astype(jnp.float32).reshape(G, -1) for a in ef)


def _shaped_ef(ef_new, ef_orig):
    """Back to the stored leaf shapes (passthrough when no EF state)."""
    if ef_orig is None:
        return None, None
    if ef_new is None:
        return ef_orig
    return tuple(a.reshape(o.shape) for a, o in zip(ef_new, ef_orig))


def _combine(t: Transport, topology: str, ef_wire: bool, local, d, step):
    """Topology combine of the local (full-precision) value with the
    peer view ``d``.  Exact wires substitute peers' decoded payloads
    directly; difference-coded wires use the γ-damped CHOCO gossip
    ``local + γ·(T·ŵ − ŵ)``, which preserves the replica mean exactly
    (T doubly stochastic) and is stable under aggressive sparsity."""
    G = local.shape[0]
    if ef_wire:
        if topology == "ring":
            gossip = _combine_ring(d, d) - d
        elif topology == "uniform":
            gossip = jnp.mean(d, axis=0, keepdims=True) - d
        elif topology == "exp":
            gossip = _combine_exp(d, d, step, G) - d
        else:
            raise ValueError(topology)
        return local + t.resolved_gamma * gossip
    if topology == "ring":
        return _combine_ring(local, d)
    if topology == "uniform":
        return _combine_uniform(local, d)
    if topology == "exp":
        return _combine_exp(local, d, step, G)
    raise ValueError(topology)


def _mix_leaf(t: Transport, w, step, ef_main):
    """One leaf through the coded substrate.  Returns
    (mixed, r_main', est_main')."""
    L = w.shape[0]
    dtype = w.dtype
    new_main = None

    if L == 1 or t.topology == "none":
        mixed = w
    elif t.topology == "hierarchical":
        wf = w.astype(jnp.float32).reshape(L, -1)
        p = t.pod_size
        pods = L // p
        # intra-pod allreduce: contributions are reduced remotely, so the
        # pod mean is over coded payloads, own included (unlike the flat
        # uniform topology's gossip model, which keeps the local replica
        # exact); difference-coded intra wires are rejected at
        # construction (docs/strategies.md)
        if p == 1:
            pm = wf
        else:
            di = _coded(t, t.intra_wire, wf)
            pm = jnp.mean(di.reshape(pods, p, -1), axis=1)
        # inter-pod ring on the pod means
        if pods == 1:
            mixed_pm = pm
        else:
            d2, new_main = _wire_stage(t, t.wire, pm,
                                       _flat_ef(ef_main, pods))
            mixed_pm = _combine(t, "ring", _needs_ef(t.wire), pm, d2,
                                step)
        out = jnp.broadcast_to(mixed_pm[:, None, :],
                               (pods, p, mixed_pm.shape[-1]))
        mixed = out.reshape(w.shape).astype(dtype)
    else:
        wf = w.astype(jnp.float32).reshape(L, -1)
        d, new_main = _wire_stage(t, t.wire, wf, _flat_ef(ef_main, L))
        mixed = _combine(t, t.topology, _needs_ef(t.wire), wf, d, step)
        mixed = mixed.reshape(w.shape).astype(dtype)

    rm, em = _shaped_ef(new_main, ef_main)
    return mixed, rm, em
