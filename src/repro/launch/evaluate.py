"""Recognition-quality evaluation launcher: checkpoint -> TER/FER table.

The paper's third axis (alongside convergence and speedup) is
recognition performance — WER on Hub5'00; the companion 1904.04956
reports (A)D-PSGD vs sync SGD as WER deltas.  This CLI is that table's
synthetic analogue: it restores a training checkpoint written by
``repro.launch.train`` (same strategy/learners/optimizer so the state
pytree matches), averages the learner replicas to the consensus model,
runs the BLSTM forward over a held-out synthetic set (respecting the
``lengths`` batch contract), and scores it with

* **FER** — masked frame error rate (padding excluded),
* **TER** — token error rate (the WER formula) of greedy best-path vs
  CTC prefix beam search (``repro.decode``; ``--beam-*`` knobs),
* throughput — valid frames/s through forward+decode and decoded
  tokens/s + beam occupancy, the same conventions ``launch/serve.py``
  prints.

Output is the ``name,value,derived`` CSV of benchmarks/run.py so rows
drop straight into the paper-tables flow.

  PYTHONPATH=src python -m repro.launch.train --arch swb2000-blstm \
      --reduced --learners 2 --strategy ad_psgd --steps 40 \
      --ckpt-dir /tmp/ck --ckpt-every 20
  PYTHONPATH=src python -m repro.launch.evaluate --arch swb2000-blstm \
      --reduced --learners 2 --strategy ad_psgd --ckpt-dir /tmp/ck \
      --beam-width 8
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import decode as DC
from repro import obs
from repro.checkpoint import restore
from repro.configs import get_arch
from repro.core import strategies as ST
from repro.data import make_dataset
from repro.eval.metrics import (collapse_labels, frame_error_rate,
                                greedy_ctc_decode, token_error_rate)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.launch.train import setup_training
from repro.models import lstm as LS

HELDOUT_OFFSET = 1_000_000      # batch_at() index space disjoint from train


def restore_consensus(cfg, *, ckpt_dir: str, strategy_name: str = None,
                      n_learners: int = None, optimizer_name: str = "sgd",
                      step: int = None, kernel_impl: str = "jax"):
    """Rebuild the exact train-state pytree (strategy x learners x
    optimizer must match the training run), restore the checkpoint into
    it, and collapse learner replicas to the consensus params."""
    mesh = make_local_mesh()
    with jax.set_mesh(mesh):
        state, _, meta = setup_training(
            cfg, mesh, strategy_name=strategy_name, n_learners=n_learners,
            optimizer_name=optimizer_name, kernel_impl=kernel_impl)
    state, step = restore(ckpt_dir, state, step=step)
    params = state["params"]
    if meta["strategy"].replicated:
        params = ST.average_learners(params)
    return params, step, meta


def evaluate_params(cfg, params, *, batches: int = 4, batch: int = 8,
                    seq_len: int = None, var_len: bool = False,
                    bucket: bool = False, seed: int = 0,
                    kernel_impl: str = "jax", beam: int = None,
                    semiring: str = None, len_norm: float = None,
                    blank: int = 0, decode_chunk: int = 0,
                    topc: int = None):
    """Decode a held-out synthetic set and return the metrics dict.

    ``decode_chunk`` > 0 streams each batch through the chunked decode
    (carry = beam state) in windows of that many frames — bit-identical
    to the one-shot decode, exercised here so evaluate and the serving
    loop share one code path."""
    beam = beam or getattr(cfg, "beam_width", 8)
    semiring = semiring or getattr(cfg, "beam_semiring", "max")
    len_norm = (getattr(cfg, "beam_len_norm", 0.0)
                if len_norm is None else len_norm)
    topc = getattr(cfg, "beam_topc", 0) if topc is None else topc
    seq_len = seq_len or 21
    impl = "pallas" if kernel_impl == "pallas" else "jax"

    ds = make_dataset(cfg, seq_len=seq_len, batch=batch, seed=seed,
                      var_len=var_len or bucket, bucket=bucket)

    @jax.jit
    def fwd(p, feats, lengths=None):
        return LS.forward(cfg, p, feats, lengths, kernel_impl=kernel_impl)

    @jax.jit
    def decode_batch(logits, lengths):
        """Jitted chunked decode of one batch (lengths always supplied:
        full-T lengths reproduce the rectangular decode exactly)."""
        B, T, _ = logits.shape
        chunk = decode_chunk if decode_chunk > 0 else T
        st = DC.init_state(B, beam, T)
        for t in range(0, T, chunk):
            st = DC.decode_chunk(st, logits[:, t:t + chunk], lengths,
                                 blank=blank, semiring=semiring, impl=impl,
                                 topc=topc)
        toks, lens, _ = DC.finalize(st, len_norm=len_norm,
                                    semiring=semiring)
        return toks, lens, DC.beam_occupancy(st)

    def run_batch(b):
        lengths = b.get("lengths")
        lens_j = (jnp.full(b["features"].shape[0], b["features"].shape[1],
                           jnp.int32) if lengths is None
                  else jnp.asarray(lengths))
        t0 = time.perf_counter()
        logits = jax.block_until_ready(
            fwd(params, jnp.asarray(b["features"]),
                None if lengths is None else lens_j))
        dt_fwd = time.perf_counter() - t0
        t1 = time.perf_counter()
        toks, lens, occ = jax.tree.map(
            jax.block_until_ready, decode_batch(logits, lens_j))
        dt_dec = time.perf_counter() - t1
        obs.add_span("eval/fwd", t0, dt_fwd, wall=True)
        obs.add_span("eval/decode", t1, dt_dec, wall=True)
        obs.histogram("eval/fwd_s", wall=True).observe(dt_fwd)
        obs.histogram("eval/decode_s", wall=True).observe(dt_dec)
        return logits, lengths, toks, lens, occ, dt_fwd, dt_dec

    # warm-up compile on every distinct padded shape (bucketed batches
    # pad to their own rounded max T) so the throughput rows measure
    # forward+decode, not XLA compilation
    batch_list = [ds.batch_at(HELDOUT_OFFSET + i) for i in range(batches)]
    for shape in {b["features"].shape for b in batch_list}:
        run_batch(next(b for b in batch_list
                       if b["features"].shape == shape))

    fer_n = fer_d = 0.0
    refs, hyps_g, hyps_b = [], [], []
    valid_frames = 0
    occupancy = []
    t_fwd = t_dec = 0.0
    for b in batch_list:
        logits, lengths, toks, lens, occ, dt_fwd, dt_dec = run_batch(b)
        t_fwd += dt_fwd
        t_dec += dt_dec
        logits_np = np.asarray(logits, np.float32)
        B, T, _ = logits_np.shape
        n_valid = int(lengths.sum()) if lengths is not None else B * T
        valid_frames += n_valid

        fer = frame_error_rate(logits_np, b["labels"], lengths)
        fer_n += fer * n_valid
        fer_d += n_valid
        refs += collapse_labels(b["labels"], lengths, blank=blank)
        hyps_g += greedy_ctc_decode(logits_np, lengths, blank=blank)

        occupancy.append(float(np.mean(np.asarray(occ))))
        toks, lens = np.asarray(toks), np.asarray(lens)
        hyps_b += [list(map(int, r[:n])) for r, n in zip(toks, lens)]

    decoded = sum(len(h) for h in hyps_b)
    return {
        "fer": fer_n / max(fer_d, 1),
        "ter_greedy": token_error_rate(refs, hyps_g),
        "ter_beam": token_error_rate(refs, hyps_b),
        "beam": beam,
        "semiring": semiring,
        "valid_frames": valid_frames,
        "frames_per_s": valid_frames / max(t_fwd + t_dec, 1e-9),
        "decoded_tok_per_s": decoded / max(t_dec, 1e-9),
        "beam_occupancy": float(np.mean(occupancy)) if occupancy else 0.0,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--ckpt-dir", required=True,
                    help="checkpoint directory written by repro.launch."
                         "train (state restores only when --strategy/"
                         "--learners/--optimizer match the training run)")
    ap.add_argument("--step", type=int, default=0,
                    help="checkpoint step to restore (0 = latest)")
    ap.add_argument("--strategy", default=None,
                    choices=[None] + sorted(ST.STRATEGIES))
    ap.add_argument("--learners", type=int, default=None)
    ap.add_argument("--optimizer", default="sgd")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale variant of the arch (CPU-friendly)")
    ap.add_argument("--batches", type=int, default=4,
                    help="held-out batches to decode")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=21)
    ap.add_argument("--var-len", action="store_true",
                    help="held-out set carries per-utterance lengths "
                         "(masked FER + length-aware decode)")
    ap.add_argument("--bucket", action="store_true",
                    help="length-bucketed held-out batches (implies "
                         "--var-len)")
    ap.add_argument("--kernel-impl", default="jax",
                    choices=["jax", "pallas"],
                    help="BLSTM forward AND beam inner-step kernels")
    ap.add_argument("--beam-width", type=int, default=0,
                    help="CTC prefix-beam width (0 = cfg beam_width)")
    ap.add_argument("--beam-semiring", default="",
                    choices=["", "max", "sum"],
                    help="prefix-score merge: 'max' (Viterbi; beam=1 == "
                         "greedy) or 'sum' (log-semiring) ('' = cfg)")
    ap.add_argument("--beam-len-norm", type=float, default=-1.0,
                    help="length-normalization alpha for final ranking "
                         "(-1 = cfg beam_len_norm)")
    ap.add_argument("--beam-topc", type=int, default=-1,
                    help="per-frame top-C vocab pruning of the beam "
                         "candidate grid (0 = off, -1 = cfg beam_topc); "
                         "exact when C covers the frame support "
                         "(docs/decoding.md)")
    ap.add_argument("--decode-chunk", type=int, default=0,
                    help="stream the decode in chunks of this many "
                         "frames, carry = beam state (0 = one shot)")
    ap.add_argument("--blank", type=int, default=0,
                    help="blank/silence class id of the TER convention")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default="",
                    help="enable observability and write the run's "
                         "flight-recorder JSONL here (per-batch "
                         "forward/decode timing spans; "
                         "docs/observability.md)")
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
    if cfg.family != "lstm":
        raise SystemExit("evaluate covers the acoustic (lstm) family; "
                         f"--arch {args.arch} is {cfg.family!r}")
    changes = {}
    if args.beam_width:
        changes["beam_width"] = args.beam_width
    if args.beam_semiring:
        changes["beam_semiring"] = args.beam_semiring
    if args.beam_len_norm >= 0:
        changes["beam_len_norm"] = args.beam_len_norm
    if args.beam_topc >= 0:
        changes["beam_topc"] = args.beam_topc
    if changes:
        cfg = dataclasses.replace(cfg, **changes)

    strategy = ST.get_strategy(args.strategy or cfg.train_strategy)
    params, step, meta = restore_consensus(
        cfg, ckpt_dir=args.ckpt_dir, strategy_name=strategy.name,
        n_learners=args.learners, optimizer_name=args.optimizer,
        step=args.step or None, kernel_impl=args.kernel_impl)
    print(f"restored {strategy.name} checkpoint at step {step} "
          f"(L={meta['n_learners']}, consensus params)")

    m = evaluate_params(
        cfg, params, batches=args.batches, batch=args.batch,
        seq_len=args.seq_len, var_len=args.var_len, bucket=args.bucket,
        seed=args.seed, kernel_impl=args.kernel_impl,
        blank=args.blank, decode_chunk=args.decode_chunk)

    from repro.obs import print_csv_rows

    tag = f"evaluate/{strategy.name}"
    rows = [
        (f"{tag}/fer", m["fer"], f"masked frame error rate, step {step}"),
        (f"{tag}/ter_greedy", m["ter_greedy"],
         "token error rate, best-path decode"),
        (f"{tag}/ter_beam{m['beam']}", m["ter_beam"],
         f"prefix beam, {m['semiring']} semiring"),
        (f"{tag}/frames_per_s", m["frames_per_s"],
         f"{m['valid_frames']} valid frames, forward+decode"),
        (f"{tag}/decoded_tok_per_s", m["decoded_tok_per_s"],
         "serve.py throughput convention"),
        (f"{tag}/beam_occupancy", m["beam_occupancy"],
         "live beam slots / beam width"),
    ]
    # the shared name,value,derived schema (repro.obs)
    print_csv_rows(rows, header=True)
    if args.trace_out:
        n = obs.dump(args.trace_out,
                     deterministic=args.trace_deterministic)
        print(f"trace: {n} events -> {args.trace_out}")
        obs.reset()


if __name__ == "__main__":
    main()
