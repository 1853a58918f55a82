"""swb2000-blstm — the paper's own acoustic model (§V Experiments).

6 bi-directional LSTM layers with 1,024 cells each (512 per direction), a
256-unit linear bottleneck, and a 32,000-way softmax over CD-HMM states.
Input is a 260-dim acoustic feature vector (PLP 40 + i-vector 100 +
logMel/delta/double-delta 120), unrolled 21 frames, batch 256, trained
with frame-level cross-entropy.  [Cui et al., IEEE SPM 2020, §V]
"""
from repro.configs.base import ArchConfig, register

SWB2000_BLSTM = register(
    ArchConfig(
        name="swb2000-blstm",
        family="lstm",
        n_layers=6,
        d_model=1024,          # LSTM cells per layer (512 per direction)
        n_heads=0,
        n_kv_heads=0,
        d_ff=0,
        vocab=32000,           # CD-HMM state targets
        citation="Cui et al., IEEE Signal Processing Magazine 2020, §V",
        norm="none",
        tie_embeddings=False,
        lstm_hidden=512,       # per direction
        lstm_bottleneck=256,
        input_dim=260,
        # Pallas BLSTM kernel: every layer's training and inference
        # kernels auto-tune to bB=256 (one tile per learner batch) at the
        # 96MiB VMEM budget (kernels/lstm_cell.py docstring: byte math).
        lstm_block_b=0,        # 0 -> auto from the VMEM budget
        lstm_vmem_budget_mb=96,
        # at the paper's T=21 the per-step residual stash is cheap; for
        # long-utterance runs set lstm_seq_chunk (--seq-chunk) to trade
        # one recompute forward for an O(T/K) stash (docs/kernels.md)
        lstm_seq_chunk=0,
        # recognition scoring (launch/evaluate.py, docs/decoding.md):
        # Viterbi prefix beam over the CD-state posteriors; width 8 is
        # the quality/latency knee at the synthetic vocab scale
        beam_width=8,
        beam_semiring="max",
        # frame classifier: no autoregressive decode step
        skip_shapes=("prefill_32k", "decode_32k", "long_500k"),
        train_strategy="ad_psgd",
        n_learners=16,
        microbatches=1,
    )
)
