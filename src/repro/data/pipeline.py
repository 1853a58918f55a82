"""Synthetic-but-learnable data pipelines.

The paper trains on SWB2000 (1,975 h of telephone speech).  That corpus is
licensed and not available offline, so each family gets a deterministic
synthetic generator with real structure to learn — enough for the
convergence comparisons of §V (heldout-loss curves across strategies are
about optimizer dynamics, not acoustics):

* ASR frames  — features drawn from per-class Gaussian clusters with label
  context (emulating CD-HMM state targets with phone-class imbalance: class
  priors are Zipf-distributed like CD-state occupancy).
* LM tokens   — a fixed random first-order Markov chain (low-entropy rows)
  so next-token prediction is learnable well below uniform entropy.
* seq2seq     — target tokens derived from pooled input-frame statistics.

Batches are generated on the fly from the step index (infinite, resumable,
no storage I/O); a host-side prefetch thread emulates the paper's
overlapped data-loading workers (§IV-D).

The ``lengths`` batch contract (variable-length utterances)
-----------------------------------------------------------
With ``var_len=True`` the ASR dataset emits *utterances* instead of
rectangular frame blocks: per-sequence valid lengths are drawn from a
clipped lognormal (SWB-like heavy spread), and every batch carries a
``lengths`` key:

* ``features``: (B, Tpad, D) f32 — zero beyond each row's length;
* ``labels``:   (B, Tpad)   i32 — 0 beyond each row's length;
* ``lengths``:  (B,)        i32 — valid frame count per row, >= 1.

Downstream consumers (``models/lstm.py``, ``models/common.cross_entropy``,
``core/strategies.py``) treat frames at t >= lengths[b] as padding: they
are masked out of the loss, frozen out of the BLSTM recurrence, and
excluded from gradient aggregation.  Fixed-length batches simply omit the
key — the absence of ``lengths`` *is* the rectangular contract.  The
normative statement of the contract (and the frame-weighted aggregation
it implies) is docs/data.md; this docstring is the emitter's view.

Length-bucketed batch construction (``bucket=True``) mirrors the paper's
loader (§IV-D) and Zhang et al. 1907.05701: utterances are generated in a
shuffle window of ``bucket_window`` batches, sorted by length within the
window, and carved into batches of near-equal length; each batch is padded
only to its own max length rounded up to ``pad_multiple`` (bounding the
number of distinct XLA compilations).  Utterance content is a pure
function of (seed, window) regardless of bucketing, so fixed-pad and
bucketed runs see the same workload — only the padding waste differs.
"""
from __future__ import annotations

import queue
import threading
from dataclasses import dataclass

import numpy as np

from repro import obs


def _rng(seed, step):
    return np.random.default_rng(np.uint64(seed * 1_000_003 + step))


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


@dataclass
class SyntheticASRDataset:
    """Frame-classification data for the paper's BLSTM acoustic model.

    ``var_len=True`` switches to variable-length utterances carrying a
    ``lengths`` key; ``bucket=True`` additionally sorts utterances by
    length inside a ``bucket_window``-batch shuffle window so batches pad
    to their own (rounded) max length instead of ``seq_len`` — see the
    module docstring for the full batch contract.
    """

    input_dim: int
    n_classes: int
    seq_len: int
    batch: int
    seed: int = 0
    n_effective_classes: int = 64   # rank of the learnable structure
    # --- variable-length utterances (module docstring: batch contract) ---
    var_len: bool = False
    min_len: int = 4
    len_sigma: float = 0.6          # lognormal spread of utterance lengths
    bucket: bool = False            # sort-within-shuffle-window batching
    bucket_window: int = 16         # shuffle window, in batches
    pad_multiple: int = 8           # bucketed Tpad rounds up to this

    def __post_init__(self):
        r = np.random.default_rng(self.seed)
        k = min(self.n_effective_classes, self.n_classes)
        self.centroids = r.normal(size=(k, self.input_dim)).astype(np.float32)
        # Zipf-like priors: CD-state occupancy is hugely uneven (paper §IV-A)
        pri = 1.0 / np.arange(1, k + 1)
        self.priors = pri / pri.sum()
        self.k = k
        self._wcache = None          # (window_idx, lens, feats, cls)

    def _window(self, w: int):
        """All utterances of shuffle window ``w`` (vectorized, cached).

        Utterance content is a pure function of (seed, w): fixed-pad and
        bucketed batching carve the same utterance stream differently."""
        if self._wcache is not None and self._wcache[0] == w:
            return self._wcache[1:]
        N = self.bucket_window * self.batch
        r = np.random.default_rng((np.uint64(self.seed), np.uint64(w), 2))
        med = max(self.min_len, int(0.6 * self.seq_len))
        lens = np.clip(
            np.rint(r.lognormal(np.log(med), self.len_sigma, size=N)),
            self.min_len, self.seq_len).astype(np.int32)
        cls = r.choice(self.k, size=(N, self.seq_len), p=self.priors)
        feats = (self.centroids[cls]
                 + 0.5 * r.normal(size=(N, self.seq_len,
                                        self.input_dim))).astype(np.float32)
        valid = np.arange(self.seq_len)[None, :] < lens[:, None]
        feats *= valid[..., None]
        cls = np.where(valid, cls, 0).astype(np.int32)
        self._wcache = (w, lens, feats, cls)
        return lens, feats, cls

    def batch_at(self, step: int):
        if not self.var_len:
            r = _rng(self.seed, step)
            cls = r.choice(self.k, size=(self.batch, self.seq_len),
                           p=self.priors)
            feats = (self.centroids[cls]
                     + 0.5 * r.normal(size=(self.batch, self.seq_len,
                                            self.input_dim))
                     ).astype(np.float32)
            return {"features": feats, "labels": cls.astype(np.int32)}

        w, j = divmod(step, self.bucket_window)
        lens, feats, cls = self._window(w)
        order = (np.argsort(lens, kind="stable") if self.bucket
                 else np.arange(len(lens)))
        rows = order[j * self.batch:(j + 1) * self.batch]
        blens = lens[rows]
        tpad = (min(self.seq_len,
                    _round_up(int(blens.max()), self.pad_multiple))
                if self.bucket else self.seq_len)
        return {"features": feats[rows, :tpad],
                "labels": cls[rows, :tpad],
                "lengths": blens}


@dataclass
class SyntheticLMDataset:
    """First-order Markov token streams (learnable next-token structure)."""

    vocab: int
    seq_len: int
    batch: int
    seed: int = 0
    effective_vocab: int = 256
    temperature: float = 0.3

    def __post_init__(self):
        r = np.random.default_rng(self.seed)
        k = min(self.effective_vocab, self.vocab)
        logits = r.normal(size=(k, k)) / self.temperature
        e = np.exp(logits - logits.max(-1, keepdims=True))
        self.trans = (e / e.sum(-1, keepdims=True)).astype(np.float64)
        self.k = k

    def batch_at(self, step: int):
        r = _rng(self.seed, step)
        B, S = self.batch, self.seq_len
        toks = np.zeros((B, S + 1), np.int32)
        toks[:, 0] = r.integers(0, self.k, size=B)
        # vectorized Markov sampling via inverse-CDF
        cdf = np.cumsum(self.trans, axis=-1)
        u = r.random((B, S))
        for t in range(S):
            toks[:, t + 1] = (cdf[toks[:, t]] > u[:, t:t + 1]).argmax(-1)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


@dataclass
class SyntheticSeq2SeqDataset:
    """Log-mel frames -> token transcripts (whisper): ``mel`` (B, frames,
    n_mels), teacher-forced ``tokens`` and next-token ``labels``
    (B, dec_len), dec_len <= frames."""

    n_mels: int
    vocab: int
    frames: int
    dec_len: int
    batch: int
    seed: int = 0
    effective_vocab: int = 128

    def __post_init__(self):
        r = np.random.default_rng(self.seed)
        k = min(self.effective_vocab, self.vocab)
        self.readout = r.normal(size=(self.n_mels, k)).astype(np.float32)
        self.k = k

    def batch_at(self, step: int):
        r = _rng(self.seed, step)
        mel = r.normal(size=(self.batch, self.frames,
                             self.n_mels)).astype(np.float32)
        # pooled frame windows determine target tokens (learnable alignment)
        pool = self.frames // self.dec_len
        pooled = mel[:, :pool * self.dec_len].reshape(
            self.batch, self.dec_len, pool, self.n_mels).mean(2)
        labels = (pooled @ self.readout).argmax(-1).astype(np.int32)
        tokens = np.concatenate(
            [np.zeros((self.batch, 1), np.int32), labels[:, :-1]], axis=1)
        return {"mel": mel, "tokens": tokens, "labels": labels}


@dataclass
class SyntheticVLMDataset:
    """Patch-embedding prefix + Markov text (internvl-style early fusion)."""

    d_model: int
    vocab: int
    n_patches: int
    text_len: int
    batch: int
    seed: int = 0

    def __post_init__(self):
        self.lm = SyntheticLMDataset(self.vocab, self.text_len, self.batch,
                                     seed=self.seed)

    def batch_at(self, step: int):
        r = _rng(self.seed, step)
        out = self.lm.batch_at(step)
        out["patches"] = r.normal(
            size=(self.batch, self.n_patches, self.d_model)
        ).astype(np.float32)
        return out


def make_dataset(cfg, *, seq_len: int, batch: int, seed: int = 0,
                 var_len: bool = False, bucket: bool = False):
    """Family-appropriate synthetic dataset for an ArchConfig.

    ``var_len``/``bucket`` select variable-length utterances with optional
    length-bucketed batching (lstm family only; see module docstring)."""
    fam = cfg.family
    if (var_len or bucket) and fam != "lstm":
        raise ValueError(f"var_len/bucket batching is only defined for the "
                         f"lstm (utterance) family, not {fam!r}")
    if fam == "lstm":
        return SyntheticASRDataset(cfg.input_dim, cfg.vocab, seq_len, batch,
                                   seed=seed, var_len=var_len or bucket,
                                   bucket=bucket)
    if fam == "encdec":
        return SyntheticSeq2SeqDataset(cfg.n_mels, cfg.vocab,
                                       2 * cfg.n_audio_ctx,
                                       min(seq_len, cfg.n_text_ctx), batch,
                                       seed=seed)
    if fam == "vlm":
        sp = int(seq_len * cfg.vlm_patch_frac)
        return SyntheticVLMDataset(cfg.d_model, cfg.vocab, sp, seq_len - sp,
                                   batch, seed=seed)
    return SyntheticLMDataset(cfg.vocab, seq_len, batch, seed=seed)


class Prefetcher:
    """Host-side prefetch thread: overlaps batch synthesis with the device
    step, the way the paper overlaps data loading with gradient compute
    (§IV-D 'run data loaders in multiple processes').

    Lifecycle: exceptions raised inside the worker are captured and
    re-raised from :meth:`next` (after any already-synthesized batches
    drain), so a consumer never blocks forever on a dead worker; and
    :meth:`close` joins the worker thread (bounded by ``join_timeout``)
    instead of abandoning it."""

    def __init__(self, dataset, start_step: int = 0, depth: int = 2,
                 join_timeout: float = 5.0):
        self.dataset = dataset
        self.q = queue.Queue(maxsize=depth)
        self.step = start_step
        self.join_timeout = join_timeout
        self.stop = threading.Event()
        self.error = None
        self.thread = threading.Thread(target=self._work, daemon=True)
        self.thread.start()

    def _work(self):
        s = self.step
        while not self.stop.is_set():
            try:
                batch = self.dataset.batch_at(s)
            except BaseException as e:       # re-raised on the consumer side
                self.error = e
                return
            while not self.stop.is_set():
                try:
                    self.q.put(batch, timeout=0.5)
                    s += 1
                    break
                except queue.Full:
                    continue

    def next(self):
        """The next batch; the wait for it is the span ``data/wait``."""
        with obs.span("data/wait"):
            while True:
                try:
                    return self.q.get(timeout=0.5)
                except queue.Empty:
                    if self.error is not None:
                        raise RuntimeError(
                            "prefetch worker failed") from self.error
                    if not self.thread.is_alive():
                        raise RuntimeError(
                            "prefetch worker exited unexpectedly")

    def close(self):
        self.stop.set()
        self.thread.join(timeout=self.join_timeout)
