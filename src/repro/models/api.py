"""Model facade: one uniform interface over all architecture families.

``build_model(cfg)`` returns a :class:`Model` exposing

* ``param_specs()``            — ParamSpec tree (shapes/axes/init)
* ``loss_fn(params, batch)``   — scalar training loss
* ``prefill_fn / decode_fn``   — serving steps (KV/SSM caches)
* ``cache_specs(shape)``       — decode-state ParamSpec tree
* ``input_specs(shape, mode)`` — ParamSpec tree describing batch inputs

Everything is ParamSpec-based so the same definition drives (a) real
initialization for smoke tests/examples and (b) ShapeDtypeStruct stand-ins
for the multi-pod dry-run.
"""
from __future__ import annotations

from dataclasses import dataclass

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import encdec as ED
from repro.models import lstm as LS
from repro.models import transformer as TF
from repro.sharding import ParamSpec


def _i32(shape, axes):
    return ParamSpec(shape, "int32", axes, "zeros")


def _emb(shape, axes):
    return ParamSpec(shape, "bfloat16", axes, "normal", 1.0)


@dataclass(frozen=True)
class Model:
    cfg: ArchConfig

    # ------------------------------------------------------------- params
    def param_specs(self):
        fam = self.cfg.family
        if fam == "encdec":
            return ED.param_specs(self.cfg)
        if fam == "lstm":
            return LS.param_specs(self.cfg)
        return TF.param_specs(self.cfg)

    # --------------------------------------------------------------- train
    def loss_fn(self, params, batch, *, kernel_impl: str = "jax",
                batch_axis: str = ""):
        fam = self.cfg.family
        if fam == "encdec":
            return ED.loss_train(self.cfg, params, batch,
                                 batch_axis=batch_axis)
        if fam == "lstm":
            return LS.loss_train(self.cfg, params, batch,
                                 kernel_impl=kernel_impl)
        return TF.loss_train(self.cfg, params, batch,
                             kernel_impl=kernel_impl, batch_axis=batch_axis)

    # --------------------------------------------------------------- serve
    def prefill_fn(self, params, batch, *, cache_len: int = 0,
                   long_context: bool = False, kernel_impl: str = "jax"):
        fam = self.cfg.family
        if fam == "encdec":
            return ED.prefill(self.cfg, params, batch["mel"],
                              batch["tokens"], cache_len=cache_len)
        patches = batch.get("patches")
        return TF.prefill(self.cfg, params, batch["tokens"],
                          cache_len=cache_len, patches=patches,
                          long_context=long_context,
                          kernel_impl=kernel_impl)

    def decode_fn(self, params, cache, tokens, pos, *,
                  long_context: bool = False, kernel_impl: str = "jax",
                  page_table=None, page_size: int = 0):
        fam = self.cfg.family
        if fam == "encdec":
            if page_table is not None:
                raise ValueError("paged KV cache: decoder-only families")
            return ED.decode_step(self.cfg, params, cache, tokens, pos)
        return TF.decode_step(self.cfg, params, cache, tokens, pos,
                              long_context=long_context,
                              kernel_impl=kernel_impl,
                              page_table=page_table, page_size=page_size)

    # --------------------------------------------------------------- specs
    def cache_specs(self, shape: ShapeConfig):
        cfg = self.cfg
        B = shape.global_batch
        if cfg.family == "encdec":
            return ED.cache_specs(cfg, B, min(shape.seq_len, cfg.n_text_ctx),
                                  cfg.n_audio_ctx)
        return TF.cache_specs(cfg, B, shape.seq_len)

    def page_specs(self, n_pages: int, page_size: int):
        """Paged decode-state specs (one shared page pool; serve.py
        ``--cache paged``)."""
        return TF.page_specs(self.cfg, n_pages, page_size)

    def input_specs(self, shape: ShapeConfig, mode: str = None):
        """ParamSpec tree of the model inputs for one assigned shape.

        mode: 'train' | 'prefill' | 'decode' (default: shape.kind).
        """
        cfg = self.cfg
        mode = mode or shape.kind
        B, S = shape.global_batch, shape.seq_len
        fam = cfg.family

        if fam == "lstm":
            assert mode == "train", "frame classifier has no decode/prefill"
            return {
                "features": _emb((B, S, cfg.input_dim),
                                 ("batch", "seq", "feature")),
                "labels": _i32((B, S), ("batch", "seq")),
            }

        if fam == "encdec":
            # the encoder's whole window of log-mel frames (30 s for
            # whisper); seq_len tokens for the decoder, at most n_text_ctx
            if mode in ("train", "prefill"):
                T = min(S, cfg.n_text_ctx)
                d = {"mel": _emb((B, 2 * cfg.n_audio_ctx, cfg.n_mels),
                                 ("batch", "frames", "feature")),
                     "tokens": _i32((B, T), ("batch", "seq"))}
                if mode == "train":
                    d["labels"] = _i32((B, T), ("batch", "seq"))
                return d
            return {"tokens": _i32((B, 1), ("batch", None)),
                    "pos": _i32((), ())}

        if fam == "vlm" and mode in ("train", "prefill"):
            sp = int(S * cfg.vlm_patch_frac)
            st = S - sp
            d = {
                "patches": _emb((B, sp, cfg.d_model),
                                ("batch", "seq", "embed")),
                "tokens": _i32((B, st), ("batch", "seq")),
            }
            if mode == "train":
                d["labels"] = _i32((B, st), ("batch", "seq"))
            return d

        if mode in ("train", "prefill"):
            d = {"tokens": _i32((B, S), ("batch", "seq"))}
            if mode == "train":
                d["labels"] = _i32((B, S), ("batch", "seq"))
            return d

        # decode: one new token against a seq_len cache
        return {"tokens": _i32((B, 1), ("batch", None)),
                "pos": _i32((), ())}


def build_model(cfg: ArchConfig) -> Model:
    return Model(cfg)
