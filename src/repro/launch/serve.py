"""Batched serving launcher: continuous-batching decode loops.

Two request families share the slot-pool pattern (admit into free slots,
advance all active slots together, free and refill on completion):

* **LM** (decoder-only families): single-request prefill scatters cache
  rows into a stacked KV/SSM cache, then the jitted one-token
  ``decode_step`` advances every active slot.  Under ``--kernel-impl
  pallas`` the flag covers the whole request loop: prefill (flash
  attention), the decode step's per-layer attention (the streaming
  cache kernel in ``repro.kernels.decode_attention``, fused delta
  variant) and the next-token selection
  (``repro.decode.kernel.argmax_tokens``, bit-identical to
  ``jnp.argmax``).
* **ASR** (the paper's lstm family): requests are variable-length
  utterances; admission runs the BLSTM forward once (``--kernel-impl``
  selects the fused Pallas stack), and the decode loop streams the
  CD-state posteriors through the chunked CTC prefix beam search of
  ``repro.decode`` — one :class:`repro.decode.BeamState` batched over
  the slot pool IS the decode carry, advanced ``--chunk-frames`` frames
  per wave (docs/decoding.md).

Both servers implement the multi-tenant slot-pool duck contract of
``repro.serving`` (docs/serving.md): ``admit``/``submit`` return a
*typed* :class:`~repro.serving.admission.AdmitResult` (``pool_full`` is
retryable; ``prompt_too_long``/``no_budget`` are terminal),
``preempt``/``restore`` snapshot a running request's full decode state
(LM: the cache row; ASR: the :class:`~repro.decode.BeamState` row via
``gather_rows``/``scatter_rows``) so a preempted-then-resumed request
decodes bit-for-bit identically to an uninterrupted one, ``step_wave``
reports per-wave progress for SLO accounting, and every slot
transition lands in ``server.events`` as a structured per-request
event instead of an ad-hoc stats line.  ``repro.launch.load`` drives
these servers through seeded traffic with SLO accounting.

PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduced \
    --requests 6 --slots 2 --max-new 16
PYTHONPATH=src python -m repro.launch.serve --arch swb2000-blstm \
    --reduced --requests 6 --slots 2 --chunk-frames 8 --beam-width 4
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import decode as DC
from repro import obs
from repro.configs import get_arch
from repro.configs.base import ShapeConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, rules_for
from repro.models import build_model
from repro.serving.admission import (NO_BUDGET, OK, POOL_FULL,
                                     PROMPT_TOO_LONG, AdmitResult,
                                     prompt_capacity)
from repro.serving.kvpool import PagePool, cdiv
from repro.sharding import ParamSpec, init_spec_tree


def _profile_jits(server, names):
    """Wrap the server's jitted entry points in compile/steady
    :class:`~repro.obs.ProfiledFn` wall-time wrappers (only while
    observability is on — the wrapper blocks on results, which the
    uninstrumented hot path must not pay)."""
    server._profiled = []
    if not obs.enabled():
        return
    for attr in names:
        p = obs.profiled(getattr(server, attr),
                         f"serve/{attr.removeprefix('_jit_')}",
                         metrics=obs.get_metrics(),
                         recorder=obs.get_recorder())
        setattr(server, attr, p)
        server._profiled.append(p)


def zeros_from_specs(spec_tree):
    return jax.tree.map(
        lambda ps: jnp.zeros(ps.shape, ps.dtype),
        spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec))


def scatter_slot(pool, row, slot):
    """Write a single-request cache row (batch dim 1) into pool slot."""
    def one(dst, src):
        # batch is axis 1 (layer-stacked caches: (L, B, ...))
        return jax.lax.dynamic_update_slice_in_dim(
            dst, src.astype(dst.dtype), slot, axis=1)
    return jax.tree.map(one, pool, row)


def scatter_slots(pool, rows, slots):
    """Write gathered cache rows (batch = len(slots)) back into the
    (possibly non-contiguous) pool slots — the batched-wave counterpart
    of :func:`scatter_slot`."""
    idx = jnp.asarray(slots, jnp.int32)
    return jax.tree.map(
        lambda dst, src: dst.at[:, idx].set(src.astype(dst.dtype)),
        pool, rows)


class _SlotPool:
    """Shared slot-pool bookkeeping: typed admission helpers, the
    structured per-request event stream, and the rid -> slot map."""

    emits_on_admit = False

    def __init__(self, slots: int, verbose: bool = False):
        self.slots = slots
        self.active = np.zeros(slots, bool)
        self.req_ids = [-1] * slots
        self.events = []
        self.verbose = verbose

    def _event(self, kind: str, rid: int, **kw):
        self.events.append((kind, rid, kw))
        obs.event(f"serve/{kind}", rid=rid, **kw)
        if self.verbose:
            extra = "".join(f" {k}={v}" for k, v in kw.items())
            print(f"[req] {kind} rid={rid}{extra}", flush=True)

    def _free_slot(self):
        free = np.where(~self.active)[0]
        return int(free[0]) if len(free) else -1

    def _slot_of(self, rid: int) -> int:
        for slot in np.where(self.active)[0]:
            if self.req_ids[slot] == rid:
                return int(slot)
        raise KeyError(f"request {rid} is not active in the pool")

    def active_requests(self):
        return [self.req_ids[s] for s in np.where(self.active)[0]]


class Server(_SlotPool):
    """LM continuous batching over a stacked KV/SSM cache."""

    emits_on_admit = True      # prefill emits the first token at admission

    def __init__(self, cfg, *, slots: int, max_len: int, seed: int = 0,
                 kernel_impl: str = "jax", batched: bool = True,
                 verbose: bool = False):
        # kernel_impl covers the whole request loop: prefill, the decode
        # step's attention (repro.kernels.decode_attention via
        # models.api.decode_fn; cfg.attn_decode_impl overrides) and the
        # token selection (repro.decode.kernel.argmax_tokens)
        assert cfg.supports_decode and cfg.family != "encdec", \
            "demo server covers decoder-only families"
        super().__init__(slots, verbose)
        self.cfg = cfg
        self.model = build_model(cfg)
        self.max_len = max_len
        self.batched = batched
        self.params = init_spec_tree(self.model.param_specs(),
                                     jax.random.PRNGKey(seed))
        shape = ShapeConfig("serve", max_len, slots, "decode")
        self._cache_specs = self.model.cache_specs(shape)
        self.cache = zeros_from_specs(self._cache_specs)
        self.pos = np.zeros(slots, np.int32)          # next write position
        self.tokens = np.zeros((slots, 1), np.int32)  # last emitted token
        self.budget = np.zeros(slots, np.int32)
        self.outputs = [[] for _ in range(slots)]

        self._jit_prefill = jax.jit(
            lambda params, batch: self.model.prefill_fn(
                params, batch, cache_len=max_len,
                kernel_impl=kernel_impl))
        self._jit_decode = jax.jit(
            lambda params, cache, tok, pos: self.model.decode_fn(
                params, cache, tok, pos, kernel_impl=kernel_impl))
        if kernel_impl == "pallas":
            self._select = lambda row: int(DC.argmax_tokens(row[None])[0])
        else:
            self._select = lambda row: int(jnp.argmax(row))
        _profile_jits(self, ("_jit_prefill", "_jit_decode"))
        if obs.enabled() and cfg.family in ("dense", "moe", "vlm"):
            # runtime collection of the kernel's VMEM accounting
            # single-source (repro.kernels.decode_attention)
            from repro.kernels.decode_attention import (
                auto_block_s_decode, decode_attn_vmem_bytes)
            M, E = cfg.n_heads, cfg.head_dim
            bs = auto_block_s_decode(max_len, M, E)
            obs.gauge("kernel/decode_attn_vmem_bytes",
                      block_s=bs).set(decode_attn_vmem_bytes(bs, M, E))

    # ------------------------------------------------------------------
    def admit(self, req_id: int, prompt: np.ndarray,
              max_new: int) -> AdmitResult:
        """Claim a free slot, prefill, emit the first token.  Typed
        rejection: ``pool_full`` (retryable), ``prompt_too_long`` (the
        cache write position must stay inside the slot's max_len row,
        one position reserved for the first generated token) or
        ``no_budget`` (max_new <= 0) — each is a distinct cause, not a
        silent False."""
        prompt = np.asarray(prompt)
        if len(prompt) > prompt_capacity(self.max_len, "lm"):
            self._event("reject", req_id, reason=PROMPT_TOO_LONG,
                        prompt=len(prompt))
            return AdmitResult(PROMPT_TOO_LONG)
        if max_new <= 0:
            self._event("reject", req_id, reason=NO_BUDGET)
            return AdmitResult(NO_BUDGET)
        slot = self._free_slot()
        if slot < 0:
            return AdmitResult(POOL_FULL)
        logits, row_cache = self._jit_prefill(
            self.params, {"tokens": jnp.asarray(prompt[None, :])})
        self.cache = scatter_slot(self.cache, row_cache, slot)
        nxt = self._select(logits[0, -1])
        self.pos[slot] = len(prompt)
        self.tokens[slot, 0] = nxt
        self.active[slot] = True
        self.budget[slot] = max_new - 1
        self.outputs[slot] = [nxt]
        self.req_ids[slot] = req_id
        self._event("admit", req_id, slot=slot, prompt=len(prompt))
        return AdmitResult(OK, slot)

    # ----------------------------------------------------- duck contract
    def submit(self, req, payload) -> AdmitResult:
        return self.admit(req.rid, payload, req.max_new)

    def step_wave(self):
        """One decode wave: ``(completed, progressed_rids, work)`` —
        every active slot advances one token, so work = active count."""
        progressed = self.active_requests()
        done = self.step()
        return done, progressed, len(progressed)

    def preempt(self, rid: int):
        """Evict ``rid``: snapshot its cache row (host-side) plus the
        position/budget/output bookkeeping, free the slot."""
        slot = self._slot_of(rid)
        snap = {
            "rid": rid,
            "pos": int(self.pos[slot]),
            "token": int(self.tokens[slot, 0]),
            "budget": int(self.budget[slot]),
            "outputs": list(self.outputs[slot]),
            "row": jax.tree.map(lambda c: np.asarray(c[:, slot:slot + 1]),
                                self.cache),
        }
        self.active[slot] = False
        self.req_ids[slot] = -1
        self._event("preempt", rid, slot=slot, pos=snap["pos"])
        return snap

    def restore(self, snap) -> AdmitResult:
        """Resume a preempted request in any free slot — the cache row
        round-trips exactly, so the continued decode is bit-for-bit the
        uninterrupted one."""
        slot = self._free_slot()
        if slot < 0:
            return AdmitResult(POOL_FULL)
        row = jax.tree.map(jnp.asarray, snap["row"])
        self.cache = scatter_slot(self.cache, row, slot)
        self.pos[slot] = snap["pos"]
        self.tokens[slot, 0] = snap["token"]
        self.budget[slot] = snap["budget"]
        self.outputs[slot] = list(snap["outputs"])
        self.active[slot] = True
        self.req_ids[slot] = snap["rid"]
        self._event("restore", snap["rid"], slot=slot, pos=snap["pos"])
        return AdmitResult(OK, slot)

    def reset(self):
        """Clear every slot (jitted executables survive — the capacity
        search replays many traffic levels on one server)."""
        self.cache = jax.tree.map(jnp.zeros_like, self.cache)
        self.pos[:] = 0
        self.active[:] = False
        self.tokens[:] = 0
        self.budget[:] = 0
        self.outputs = [[] for _ in range(self.slots)]
        self.req_ids = [-1] * self.slots
        self.events.clear()

    # ------------------------------------------------------------------
    def step(self):
        """Advance every active slot by one token.

        Slots share one jitted decode at a common position frontier:
        the cache write position differs per slot, so slots are grouped
        by position and each group decodes as ONE batched call (gather
        rows -> decode -> scatter back) — bit-identical to the
        sequential per-slot decode (parity-tested), with
        ``batched=False`` keeping the reference loop."""
        if not self.batched:
            return self._step_sequential()
        done = []
        active = np.where(self.active)[0]
        for p in sorted({int(self.pos[s]) for s in active}):
            group = np.array([s for s in active if self.pos[s] == p],
                             np.int32)
            toks = jnp.asarray(self.tokens[group])
            rows = jax.tree.map(lambda c: c[:, group], self.cache)
            logits, rows = self._jit_decode(self.params, rows, toks,
                                            jnp.int32(p))
            self.cache = scatter_slots(self.cache, rows, group)
            for i, slot in enumerate(map(int, group)):
                self._advance_slot(slot, logits[i, -1], done)
        return done

    def _step_sequential(self):
        done = []
        for slot in np.where(self.active)[0]:
            slot = int(slot)
            tok = jnp.asarray(self.tokens[slot:slot + 1])
            row = jax.tree.map(lambda c: c[:, slot:slot + 1], self.cache)
            logits, row = self._jit_decode(self.params, row, tok,
                                           jnp.int32(int(self.pos[slot])))
            self.cache = scatter_slot(self.cache, row, slot)
            self._advance_slot(slot, logits[0, -1], done)
        return done

    def _advance_slot(self, slot: int, logit_row, done):
        nxt = self._select(logit_row)
        self.outputs[slot].append(nxt)
        self.tokens[slot, 0] = nxt
        self.pos[slot] += 1
        self.budget[slot] -= 1
        if self.budget[slot] <= 0 or self.pos[slot] >= self.max_len - 1:
            self.active[slot] = False
            rid = self.req_ids[slot]
            done.append((rid, list(self.outputs[slot])))
            self._event("done", rid, slot=slot,
                        tokens=len(self.outputs[slot]))


class PagedServer:
    """LM continuous batching over a PAGED KV cache (``--cache paged``).

    Same duck contract and decode loop as :class:`Server`, but the
    physical cache is one shared pool of ``pool_pages`` pages of
    ``page_size`` positions (models/transformer.py ``page_specs``) and
    capacity is the *page budget*, not a slot count: a short request
    pins ``ceil((plen + max_new) / P)`` pages instead of a full
    ``max_len`` row, so many more short requests fit the same HBM.
    Host-side bookkeeping (refcounts, the prompt-prefix trie, COW) lives
    in :class:`repro.serving.kvpool.PagePool`; this class owns the
    device page arrays and applies the pool's decisions:

    * **admit** — pages are reserved eagerly (all-or-nothing; admitted
      requests never OOM mid-decode).  Worst-case demand beyond the
      whole pool is the *terminal* ``no_budget``; insufficient free
      pages right now is the retryable ``pool_full``.  Prefill runs at
      page-rounded length and its cache rows scatter into the owned
      pages only — trie-shared prefix pages already hold the bytes.
    * **step** — equal-position groups decode as one batched call, the
      per-request page tables stacked into the (Bg, W) table the paged
      attention walks.  Before the wave's cache write,
      ``pool.ensure_writable`` COWs any shared page (device page copy
      here, refcount moves in the pool).
    * **preempt/restore** — the snapshot is the page *table* plus the
      owned pages' contents; restore re-allocates through the trie, so
      a resumed request may re-share prompt pages and is still
      bit-exact: shared pages are only read below the request's
      position, where content is verified-identical prompt.
    """

    emits_on_admit = True

    def __init__(self, cfg, *, pool_pages: int, page_size: int,
                 max_len: int, seed: int = 0, kernel_impl: str = "jax",
                 share: bool = True, verbose: bool = False):
        assert cfg.supports_decode and cfg.family in ("dense", "moe", "vlm"), \
            "paged KV cache covers attention-only decoder families"
        if max_len % page_size:
            raise ValueError(f"max_len {max_len} must be a multiple of "
                             f"page_size {page_size}")
        self.cfg = cfg
        self.model = build_model(cfg)
        self.max_len = max_len
        self.page_size = page_size
        self.table_w = cdiv(max_len, page_size)
        self.pool = PagePool(pool_pages, page_size, seed=seed, share=share)
        self.events = []
        self.verbose = verbose
        self.peak_sharing = 0.0
        self.params = init_spec_tree(self.model.param_specs(),
                                     jax.random.PRNGKey(seed))
        pages = zeros_from_specs(
            self.model.page_specs(pool_pages, page_size))
        self.k_pages = pages["attn"]["k"]
        self.v_pages = pages["attn"]["v"]
        self.reqs = {}    # rid -> {pos, token, budget, outputs, ...}

        self._jit_prefill = jax.jit(
            lambda params, batch, cl: self.model.prefill_fn(
                params, batch, cache_len=cl, kernel_impl=kernel_impl),
            static_argnums=2)
        self._jit_decode = jax.jit(
            lambda params, kp, vp, tbl, tok, pos: self.model.decode_fn(
                params, {"attn": {"k": kp, "v": vp}}, tok, pos,
                kernel_impl=kernel_impl, page_table=tbl,
                page_size=page_size))
        self._jit_write = jax.jit(
            lambda pool, rows, idx: pool.at[:, idx].set(
                rows.astype(pool.dtype)))
        self._jit_copy_page = jax.jit(
            lambda pool, src, dst: pool.at[:, dst].set(pool[:, src]))
        if kernel_impl == "pallas":
            self._select = lambda row: int(DC.argmax_tokens(row[None])[0])
        else:
            self._select = lambda row: int(jnp.argmax(row))
        _profile_jits(self, ("_jit_prefill", "_jit_decode",
                             "_jit_write", "_jit_copy_page"))
        if obs.enabled():
            from repro.kernels.decode_attention import paged_attn_vmem_bytes
            M, E = cfg.n_heads, cfg.head_dim
            obs.gauge("kernel/paged_attn_vmem_bytes",
                      page_size=page_size).set(
                paged_attn_vmem_bytes(page_size, M, E, self.table_w))

    # ------------------------------------------------------------------
    def _event(self, kind: str, rid: int, **kw):
        self.events.append((kind, rid, kw))
        obs.event(f"serve/{kind}", rid=rid, **kw)
        if self.verbose:
            extra = "".join(f" {k}={v}" for k, v in kw.items())
            print(f"[req] {kind} rid={rid}{extra}", flush=True)

    @property
    def active(self):
        """In-flight mask (duck compat with the slot servers' loops —
        one entry per live request, not per slot)."""
        return np.ones(len(self.reqs), bool)

    def active_requests(self):
        return list(self.reqs)

    def occupancy(self) -> float:
        return self.pool.pages_in_use / self.pool.n_pages

    # ------------------------------------------------------------------
    def admit(self, req_id: int, prompt: np.ndarray,
              max_new: int) -> AdmitResult:
        """Page-budget admission.  Typed rejection: ``prompt_too_long``
        (prompt exceeds the LM capacity contract), ``no_budget``
        (max_new <= 0, OR worst-case page demand exceeds the whole pool
        — the request can never fit, terminal), ``pool_full`` (not
        enough free pages right now, retryable)."""
        prompt = np.asarray(prompt)
        plen = len(prompt)
        if plen > prompt_capacity(self.max_len, "lm"):
            self._event("reject", req_id, reason=PROMPT_TOO_LONG,
                        prompt=plen)
            return AdmitResult(PROMPT_TOO_LONG)
        total = min(plen + max_new, self.max_len)
        if max_new <= 0 or self.pool.pages_for(total) > self.pool.n_pages:
            self._event("reject", req_id, reason=NO_BUDGET,
                        pages=self.pool.pages_for(max(total, 0)),
                        pool=self.pool.n_pages)
            return AdmitResult(NO_BUDGET)
        alloc = self.pool.alloc_request(req_id, prompt, total)
        if alloc is None:
            return AdmitResult(POOL_FULL)
        P = self.page_size
        pp = cdiv(plen, P) * P          # page-rounded prefill length
        logits, row_cache = self._jit_prefill(
            self.params, {"tokens": jnp.asarray(prompt[None, :])}, pp)
        self._write_owned(row_cache, alloc.table, alloc.owned,
                          n_pages=cdiv(plen, P))
        nxt = self._select(logits[0, -1])
        self.reqs[req_id] = {
            "pos": plen, "token": nxt, "budget": max_new - 1,
            "outputs": [nxt], "prompt": tuple(int(t) for t in prompt),
            "total": total,
        }
        self.peak_sharing = max(self.peak_sharing, self.pool.sharing_ratio)
        self._event("admit", req_id, prompt=plen,
                    pages=alloc.n_pages, shared=alloc.n_shared,
                    in_use=self.pool.pages_in_use)
        return AdmitResult(OK, 0)

    def _write_owned(self, row_cache, table, owned, n_pages):
        """Scatter an (L, 1, n_pages*P, KV, E) prefill row into the OWNED
        physical pages of the first ``n_pages`` table entries (shared
        pages already hold identical prompt bytes)."""
        own = [j for j in range(n_pages) if owned[j]]
        if not own:
            return
        phys = jnp.asarray([table[j] for j in own], jnp.int32)
        P = self.page_size

        def rows(arr):   # (L, 1, pp, KV, E) -> (L, n_own, P, KV, E)
            L, _, pp, KV, E = arr.shape
            return arr[:, 0].reshape(L, pp // P, P, KV, E)[:, own]

        self.k_pages = self._jit_write(self.k_pages,
                                       rows(row_cache["attn"]["k"]), phys)
        self.v_pages = self._jit_write(self.v_pages,
                                       rows(row_cache["attn"]["v"]), phys)

    # ----------------------------------------------------- duck contract
    def submit(self, req, payload) -> AdmitResult:
        return self.admit(req.rid, payload, req.max_new)

    def step_wave(self):
        progressed = self.active_requests()
        done = self.step()
        return done, progressed, len(progressed)

    def preempt(self, rid: int):
        """Evict ``rid``: snapshot its page table's OWNED pages (host)
        plus the bookkeeping, release the pages to the pool."""
        r = self.reqs.pop(rid)
        table = self.pool.table_of(rid)
        snap = {
            "rid": rid, "pos": r["pos"], "token": r["token"],
            "budget": r["budget"], "outputs": list(r["outputs"]),
            "prompt": r["prompt"], "total": r["total"],
            "pages_k": np.asarray(self.k_pages[:, jnp.asarray(table)]),
            "pages_v": np.asarray(self.v_pages[:, jnp.asarray(table)]),
        }
        self.pool.free_request(rid)
        self._event("preempt", rid, pos=r["pos"], pages=len(table))
        return snap

    def restore(self, snap) -> AdmitResult:
        """Resume a preempted request: re-allocate through the trie
        (prompt pages may re-share; pages holding decode output never
        do) and scatter the snapshot into the owned pages."""
        rid = snap["rid"]
        alloc = self.pool.alloc_request(rid, snap["prompt"], snap["total"],
                                        written_upto=snap["pos"])
        if alloc is None:
            return AdmitResult(POOL_FULL)
        own = [j for j in range(alloc.n_pages) if alloc.owned[j]]
        if own:
            phys = jnp.asarray([alloc.table[j] for j in own], jnp.int32)
            self.k_pages = self._jit_write(
                self.k_pages, jnp.asarray(snap["pages_k"][:, own]), phys)
            self.v_pages = self._jit_write(
                self.v_pages, jnp.asarray(snap["pages_v"][:, own]), phys)
        self.reqs[rid] = {k: snap[k] for k in
                          ("pos", "token", "budget", "prompt", "total")}
        self.reqs[rid]["outputs"] = list(snap["outputs"])
        self.peak_sharing = max(self.peak_sharing, self.pool.sharing_ratio)
        self._event("restore", rid, pos=snap["pos"],
                    shared=alloc.n_shared)
        return AdmitResult(OK, 0)

    def reset(self):
        self.pool.reset()
        self.k_pages = jnp.zeros_like(self.k_pages)
        self.v_pages = jnp.zeros_like(self.v_pages)
        self.reqs.clear()
        self.events.clear()
        self.peak_sharing = 0.0

    # ------------------------------------------------------------------
    def step(self):
        """Advance every in-flight request one token: equal-position
        groups share one batched decode (same grouping rule as the dense
        server, so outputs are bit-identical to it given equal logits);
        shared pages COW before the wave's cache write."""
        done = []
        for p in sorted({r["pos"] for r in self.reqs.values()}):
            group = [rid for rid, r in self.reqs.items()
                     if r["pos"] == p]
            for rid in group:    # COW before the device write at p
                moved = self.pool.ensure_writable(rid, p)
                if moved is not None:
                    src, dst = moved
                    self.k_pages = self._jit_copy_page(self.k_pages,
                                                       src, dst)
                    self.v_pages = self._jit_copy_page(self.v_pages,
                                                       src, dst)
                    self._event("cow", rid, pos=p, src=src, dst=dst)
            # Attend only the pages the group can reach: the logical
            # width is the widest request's page count, rounded up to a
            # power of two (bounded retraces).  Short requests stream
            # ceil(total/P) pages, not max_len positions — value-exact
            # because masked tiles contribute exact zeros.
            w_need = max(cdiv(self.reqs[rid]["total"], self.page_size)
                         for rid in group)
            w_use = min(self.table_w, 1 << max(w_need - 1, 0).bit_length())
            tbl = np.zeros((len(group), w_use), np.int32)
            for i, rid in enumerate(group):
                t = self.pool.table_of(rid)
                tbl[i, :len(t)] = t[:w_use]
            toks = jnp.asarray([[self.reqs[rid]["token"]]
                                for rid in group], jnp.int32)
            logits, cache = self._jit_decode(
                self.params, self.k_pages, self.v_pages,
                jnp.asarray(tbl), toks, jnp.int32(p))
            self.k_pages = cache["attn"]["k"]
            self.v_pages = cache["attn"]["v"]
            for i, rid in enumerate(group):
                self._advance(rid, logits[i, -1], done)
        return done

    def _advance(self, rid, logit_row, done):
        r = self.reqs[rid]
        nxt = self._select(logit_row)
        r["outputs"].append(nxt)
        r["token"] = nxt
        r["pos"] += 1
        r["budget"] -= 1
        # same finish rule as the dense Server -> bit-identical outputs
        if r["budget"] <= 0 or r["pos"] >= self.max_len - 1:
            done.append((rid, list(r["outputs"])))
            self._event("done", rid, tokens=len(r["outputs"]),
                        in_use=self.pool.pages_in_use)
            self.pool.free_request(rid)
            del self.reqs[rid]


class AsrServer(_SlotPool):
    """Streaming-ASR slot pool for the paper's acoustic model.

    Admission runs the BLSTM forward once over the utterance (masked to
    its valid frames; ``kernel_impl='pallas'`` selects the fused Pallas
    stack) and parks the CD-state posteriors host-side.  The decode loop
    then advances every active slot by ``chunk`` frames per wave through
    ONE batched :class:`repro.decode.BeamState` — the beam state is the
    streaming carry, per-slot frame counters freeze exhausted rows, and
    ``reset_rows`` re-arms a slot on admission.  Completion = all valid
    frames consumed; the hypothesis is the finalized best beam entry.
    Preemption snapshots the slot's beam row
    (``decode.gather_rows``/``scatter_rows``) plus its parked
    posteriors, so resume continues the identical beam trajectory.
    """

    def __init__(self, cfg, *, slots: int, max_frames: int, chunk: int,
                 beam: int = 0, seed: int = 0, kernel_impl: str = "jax",
                 topc: int = None, verbose: bool = False):
        from repro.models import lstm as LS

        super().__init__(slots, verbose)
        self.cfg = cfg
        self.max_frames = max_frames
        self.chunk = chunk
        self.beam = beam or getattr(cfg, "beam_width", 8)
        self.semiring = getattr(cfg, "beam_semiring", "max")
        self.len_norm = getattr(cfg, "beam_len_norm", 0.0)
        self.topc = (getattr(cfg, "beam_topc", 0) if topc is None
                     else topc)
        self.impl = "pallas" if kernel_impl == "pallas" else "jax"
        print(f"[decode] beam step: {self.impl} (beam {self.beam}, "
              f"topc {self.topc or 'off'})", flush=True)
        model = build_model(cfg)
        self.params = init_spec_tree(model.param_specs(),
                                     jax.random.PRNGKey(seed))
        self._jit_fwd = jax.jit(
            lambda p, feats, n: LS.forward(cfg, p, feats, n,
                                           kernel_impl=kernel_impl))
        self.logits = np.zeros((slots, max_frames, cfg.vocab), np.float32)
        self.lens = np.zeros(slots, np.int32)     # valid frames per slot
        self.pos = np.zeros(slots, np.int32)      # frames consumed
        self.state = DC.init_state(slots, self.beam, max_frames)
        # fixed (state, wave, lens) shapes -> jit once, no per-wave retrace
        self._jit_decode = jax.jit(
            lambda st, wave, lens: DC.decode_chunk(
                st, wave, lens, semiring=self.semiring, impl=self.impl,
                topc=self.topc))
        self._jit_finalize = jax.jit(
            lambda st: DC.finalize(st, len_norm=self.len_norm,
                                   semiring=self.semiring))
        self._jit_occ = jax.jit(DC.beam_occupancy)
        _profile_jits(self, ("_jit_fwd", "_jit_decode", "_jit_finalize"))
        if obs.enabled():
            obs.gauge("kernel/beam_cand_bytes", beam=self.beam,
                      topc=self.topc).set(
                DC.beam_cand_bytes(self.beam, cfg.vocab, self.topc))

    def admit(self, req_id: int, feats: np.ndarray) -> AdmitResult:
        """Typed admission: ``pool_full`` (retryable), ``prompt_too_long``
        (more frames than the slot's posterior buffer) or ``no_budget``
        (an empty utterance has nothing to decode)."""
        feats = np.asarray(feats, np.float32)
        n = len(feats)
        if n > prompt_capacity(self.max_frames, "asr"):
            self._event("reject", req_id, reason=PROMPT_TOO_LONG, frames=n)
            return AdmitResult(PROMPT_TOO_LONG)
        if n == 0:
            self._event("reject", req_id, reason=NO_BUDGET)
            return AdmitResult(NO_BUDGET)
        slot = self._free_slot()
        if slot < 0:
            return AdmitResult(POOL_FULL)
        padded = np.zeros((1, self.max_frames, feats.shape[-1]), np.float32)
        padded[0, :n] = feats
        logits = self._jit_fwd(self.params, jnp.asarray(padded),
                               jnp.asarray([n], jnp.int32))
        self.logits[slot] = np.asarray(logits[0], np.float32)
        self.lens[slot] = n
        self.pos[slot] = 0
        self.active[slot] = True
        self.req_ids[slot] = req_id
        mask = np.zeros(self.slots, bool)
        mask[slot] = True
        self.state = DC.reset_rows(self.state, jnp.asarray(mask))
        self._event("admit", req_id, slot=slot, frames=n)
        return AdmitResult(OK, slot)

    # ----------------------------------------------------- duck contract
    def submit(self, req, payload) -> AdmitResult:
        return self.admit(req.rid, payload)

    def step_wave(self):
        """One decode wave: ``(completed, progressed_rids, work)`` with
        work = valid frames consumed across the pool this wave."""
        active = np.where(self.active)[0]
        progressed = [self.req_ids[s] for s in active]
        work = int(np.minimum(
            self.chunk,
            np.maximum(self.lens[active] - self.pos[active], 0)).sum())
        done, _ = self.step()
        return done, progressed, work

    def preempt(self, rid: int):
        """Evict ``rid``: snapshot its beam row + parked posteriors,
        freeze the vacated row (lens = 0 so ``state.t >= lens``), free
        the slot."""
        slot = self._slot_of(rid)
        snap = {
            "rid": rid,
            "logits": self.logits[slot].copy(),
            "len": int(self.lens[slot]),
            "pos": int(self.pos[slot]),
            "beam": jax.tree.map(np.asarray,
                                 DC.gather_rows(self.state, [slot])),
        }
        self.active[slot] = False
        self.req_ids[slot] = -1
        self.lens[slot] = 0        # freezes the stale beam row
        self.pos[slot] = 0
        self._event("preempt", rid, slot=slot, pos=snap["pos"])
        return snap

    def restore(self, snap) -> AdmitResult:
        """Resume in any free slot: scatter the beam row back
        (``decode.scatter_rows``) — the continued chunked decode is
        bit-identical to the uninterrupted stream (BeamState contract,
        docs/decoding.md)."""
        slot = self._free_slot()
        if slot < 0:
            return AdmitResult(POOL_FULL)
        self.logits[slot] = snap["logits"]
        self.lens[slot] = snap["len"]
        self.pos[slot] = snap["pos"]
        self.state = DC.scatter_rows(self.state, snap["beam"], [slot])
        self.active[slot] = True
        self.req_ids[slot] = snap["rid"]
        self._event("restore", snap["rid"], slot=slot, pos=snap["pos"])
        return AdmitResult(OK, slot)

    def reset(self):
        self.logits[:] = 0.0
        self.lens[:] = 0
        self.pos[:] = 0
        self.active[:] = False
        self.req_ids = [-1] * self.slots
        self.state = DC.init_state(self.slots, self.beam, self.max_frames)
        self.events.clear()

    # ------------------------------------------------------------------
    def step(self):
        """Advance every active slot by one chunk of frames.  Returns
        ``[(req_id, tokens), ...]`` for slots that finished and
        the live-beam occupancy of this wave."""
        C = self.chunk
        idx = np.minimum(self.pos[:, None] + np.arange(C)[None, :],
                         self.max_frames - 1)
        wave = self.logits[np.arange(self.slots)[:, None], idx]
        # per-row freeze: state.t >= lens stops exhausted/empty rows
        self.state = self._jit_decode(self.state, jnp.asarray(wave),
                                      jnp.asarray(self.lens))
        occ = float(np.mean(np.asarray(
            self._jit_occ(self.state))[self.active])) \
            if self.active.any() else 0.0
        self.pos = np.where(self.active,
                            np.minimum(self.pos + C, self.lens), self.pos)
        done = []
        finished = np.where(self.active & (self.pos >= self.lens))[0]
        if len(finished):
            toks, lens, _ = self._jit_finalize(self.state)
            toks = np.asarray(toks)
            for slot in finished:
                hyp = list(map(int, toks[slot][:int(lens[slot])]))
                rid = self.req_ids[slot]
                done.append((rid, hyp))
                self.active[slot] = False
                self._event("done", rid, slot=int(slot), tokens=len(hyp))
        return done, occ


def _finish_trace(server, args):
    """End-of-run observability: per-entry-point compile/steady rows
    (the regimes a single wall-clock total conflates) and the JSONL
    flight-recorder dump."""
    for p in getattr(server, "_profiled", []):
        n = p.n_calls - p.n_compiles
        print(f"timing: {p.name} compile {p.compile_s:.2f}s "
              f"({p.n_compiles} compile(s)), steady {p.steady_s:.3f}s "
              f"over {n} calls", flush=True)
    if args.trace_out:
        n = obs.dump(args.trace_out,
                     deterministic=args.trace_deterministic)
        print(f"trace: {n} events -> {args.trace_out}")
        obs.reset()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="prompt tokens (LM) / nominal utterance frames "
                         "(ASR) per request (clamped to --max-len)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64,
                    help="cache capacity (LM) / max utterance frames "
                         "(ASR) per slot")
    ap.add_argument("--cache", default="",
                    choices=["", "dense", "paged"],
                    help="LM KV-cache layout: dense per-slot rows or the "
                         "paged page-pool server with prompt-prefix "
                         "sharing (default: cfg.cache_mode)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="cache positions per KV page in --cache paged "
                         "(0 = cfg.page_size; must divide --max-len)")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="physical pages in the paged pool (0 = the "
                         "dense-equivalent HBM: slots * max_len / "
                         "page_size)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="LM mode: length of a common prompt prefix "
                         "shared by all generated requests (exercises "
                         "prefix sharing under --cache paged; 0 = fully "
                         "random prompts)")
    ap.add_argument("--kernel-impl", default="jax",
                    choices=["jax", "pallas"],
                    help="kernels for prefill/the BLSTM forward AND the "
                         "decode loop (LM: decode-attention + argmax "
                         "selection kernels; ASR: the prefix-beam "
                         "inner-step kernel)")
    ap.add_argument("--sequential", action="store_true",
                    help="LM mode: decode active slots one at a time "
                         "instead of batching equal-position groups "
                         "(the bit-identical reference path)")
    ap.add_argument("--chunk-frames", type=int, default=8,
                    help="ASR mode: frames decoded per wave (the "
                         "streaming chunk of the beam-state carry)")
    ap.add_argument("--beam-width", type=int, default=0,
                    help="ASR mode: CTC prefix-beam width (0 = cfg "
                         "beam_width)")
    ap.add_argument("--beam-topc", type=int, default=-1,
                    help="ASR mode: per-frame top-C vocab pruning of the "
                         "beam candidate grid (0 = off, -1 = cfg "
                         "beam_topc); exact when C covers the frame "
                         "support (docs/decoding.md)")
    ap.add_argument("--trace-out", default="",
                    help="enable observability and write the run's "
                         "flight-recorder JSONL here (per-request "
                         "events, compile/steady kernel timings, VMEM "
                         "accounting gauges; docs/observability.md)")
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
    if cfg.family == "lstm":
        return _main_asr(cfg, args)

    rng = np.random.default_rng(0)
    cache_mode = args.cache or cfg.cache_mode
    if cache_mode == "paged":
        page = args.page_size or cfg.page_size
        pool_pages = args.pool_pages or args.slots * cdiv(args.max_len,
                                                          page)
        server = PagedServer(cfg, pool_pages=pool_pages, page_size=page,
                             max_len=args.max_len,
                             kernel_impl=args.kernel_impl, verbose=True)
    else:
        server = Server(cfg, slots=args.slots, max_len=args.max_len,
                        kernel_impl=args.kernel_impl,
                        batched=not args.sequential, verbose=True)
    plen = min(args.prompt_len, prompt_capacity(args.max_len, "lm"))
    shared = min(args.shared_prefix, plen)
    prefix = rng.integers(0, cfg.vocab, size=shared)
    pending = [(i, np.concatenate([prefix,
                                   rng.integers(0, cfg.vocab,
                                                size=plen - shared)]))
               for i in range(args.requests)]
    finished, t0, steps, occ = [], time.time(), 0, 0.0
    while pending or server.active.any():
        while pending:
            res = server.admit(pending[0][0], pending[0][1], args.max_new)
            if res.reason == POOL_FULL:
                break
            pending.pop(0)      # admitted or terminally rejected (event
            # stream carries the per-request outcome either way)
        occ += (server.occupancy() if cache_mode == "paged"
                else server.active.mean())
        with obs.span("serve/wave", wave=steps):
            finished += server.step()
        steps += 1
    dt = time.time() - t0
    toks = sum(len(o) for _, o in finished)
    # decoded tokens/s + occupancy: the shared throughput convention of
    # launch/evaluate.py (occupancy = slot-pool utilization per wave;
    # paged mode reports page-pool utilization instead)
    print(f"served {len(finished)} requests, {toks} tokens, "
          f"{steps} decode waves in {dt:.1f}s ({toks/dt:.1f} tok/s, "
          f"occupancy {occ/max(steps, 1):.2f})")
    if cache_mode == "paged":
        print(f"[kv] pool={server.pool.n_pages} pages x "
              f"{server.page_size} positions, peak "
              f"sharing_ratio={server.peak_sharing:.3f}, "
              f"cow={server.pool.n_cow}, "
              f"shared_hits={server.pool.n_shared_hits}")
    for rid, out in finished:
        print(f"  req {rid}: {out[:8]}{'...' if len(out) > 8 else ''}")
    _finish_trace(server, args)


def _main_asr(cfg, args):
    """Streaming-ASR serving: variable-length synthetic utterances from
    the data pipeline's length distribution, chunked beam decode."""
    from repro.data import make_dataset

    seq_len = min(args.prompt_len, prompt_capacity(args.max_len, "asr"))
    ds = make_dataset(cfg, seq_len=seq_len, batch=max(args.requests, 1),
                      seed=0, var_len=True)
    batch = ds.batch_at(0)
    pending = [(i, batch["features"][i, :batch["lengths"][i]])
               for i in range(args.requests)]
    server = AsrServer(cfg, slots=args.slots, max_frames=args.max_len,
                       chunk=args.chunk_frames, beam=args.beam_width,
                       kernel_impl=args.kernel_impl,
                       topc=None if args.beam_topc < 0 else args.beam_topc,
                       verbose=True)
    finished, t0, steps, occ = [], time.time(), 0, 0.0
    frames = sum(len(f) for _, f in pending)
    while pending or server.active.any():
        while pending:
            res = server.admit(*pending[0])
            if res.reason == POOL_FULL:
                break
            pending.pop(0)
        with obs.span("serve/wave", wave=steps):
            done, wave_occ = server.step()
        finished += done
        occ += wave_occ
        steps += 1
    dt = time.time() - t0
    toks = sum(len(o) for _, o in finished)
    print(f"served {len(finished)} requests, {toks} tokens, "
          f"{steps} decode waves in {dt:.1f}s ({toks/dt:.1f} tok/s, "
          f"{frames/dt:.1f} frames/s, beam {server.beam} "
          f"occupancy {occ/max(steps, 1):.2f})")
    for rid, out in finished:
        print(f"  req {rid}: {out[:8]}{'...' if len(out) > 8 else ''}")
    _finish_trace(server, args)


if __name__ == "__main__":
    main()
