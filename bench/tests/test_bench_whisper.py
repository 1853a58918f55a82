"""The whisper-large-v3 configuration on the CPU at a tiny size (2 + 2
layers, d_model 64): the program's loss and gradients against the plain
reference of ``configs/whisper-large-v3.py``; a run of a tiny copy of the
cell ``whisper-seg30s-1chip`` through the harness is correct, and the
float8 control and the exchange left out are not; the FLOP count by
hand."""
import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from harness import compare, reference, spec, weights

REPO = tiny.REPO
# 520 encoder positions: the query chunks of 512 leave a last one of 8
SIZES = {"arch": "whisper-large-v3", "n_mels": 8, "n_audio_ctx": 520,
         "n_text_ctx": 16, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
         "head_dim": 16, "d_ff": 128, "vocab": 96, "n_enc_layers": 2,
         "n_layers": 2, "param_dtype": "bfloat16", "kernel_impl": "jax",
         "frame_seconds": 0.01}
TRAFFIC = {"kind": "seq2seq_audio", "strategy": "ad_psgd", "optimizer": "sgd",
           "schedule": "paper_recipe", "learners": 4, "batch_per_learner": 2,
           "frames": 1040, "tokens": 12, "vocab": 96, "classes": 8,
           "noise": 0.5, "microbatches": 1, "distinct_batches": 4,
           "ahead_steps": 2,
           "lr": {"base": 0.01, "peak": 0.01, "steps_per_epoch": 1000},
           "program": {}}
# The tiny cell's own limits.  The program keeps its activations and
# residual stream in bfloat16 where the reference is float32; at this
# size that reads 5e-6 - 4e-5 on the losses (seeds 7-9, CPU), against
# 3e-4 - 6e-4 for the float8 control, so the loss limit sits between.
# Leaves of a few thousand elements make the gradient and the change
# read bfloat16 rounding over the small rate (up to 0.1 and 0.05), as
# loudly as the control does, so only the loss separates them here; the
# faults are three to five orders of magnitude out.
LIMITS = {"loss_gap": 1.5e-4, "grad_gap": 0.5, "change_gap": 0.25}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A tiny copy of the cell: tiny.py's root with the whisper sizes,
    traffic and reference in place of the BLSTM's."""
    r = tiny.make_root(tmp_path_factory.mktemp("whisper"), sizes=SIZES,
                       traffic=TRAFFIC, limits=LIMITS, name="tiny-whisper")
    shutil.copy(REPO / "bench" / "configs" / "whisper-large-v3.py",
                r / "bench" / "configs" / "tiny-blstm.py")
    return r


@pytest.fixture(scope="module")
def cell(root):
    return spec.load_cell(root, "tiny-whisper", bench=root / "bench")


def _nest(flat: dict) -> dict:
    """{"a/b/c": x} -> {"a": {"b": {"c": x}}}, the program's tree."""
    tree = {}
    for path, a in flat.items():
        *head, last = path.split("/")
        node = tree
        for h in head:
            node = node.setdefault(h, {})
        node[last] = a
    return tree


def test_program_loss_and_grads_match_reference(cell):
    """One learner's loss and gradient, the program's model against the
    reference on the same seeded weights and batch.  The program computes
    in bfloat16 (activations, residual stream, logits); 2e-3 of the loss
    is about four bfloat16 ulps of it, and 4e-2 of each leaf's gradient
    norm (or of the median leaf's, for leaves near zero) is the rounding
    of two layers' bfloat16 backward at these widths."""
    from harness.train_cell import program_config, _paths
    from repro.models import build_model

    model = build_model(program_config(cell.sizes, cell.traffic))
    layout = cell.model.layout(cell.sizes)
    flat = weights.make_params(layout, 11)
    batch = {k: jnp.asarray(v[:2]) for k, v in cell.batches(11)[0].items()}
    got, g = jax.value_and_grad(model.loss_fn)(_nest(flat), batch)
    names, leaves, _ = _paths(g)
    g_got = dict(zip(names, leaves))
    with jax.default_matmul_precision("highest"):
        want, g_want = jax.value_and_grad(
            lambda p: cell.model.loss(cell.sizes, p, batch,
                                      einsum=reference.einsum_f32))(
            {k: v.astype(jnp.float32) for k, v in flat.items()})
    assert abs(float(got) - float(want)) <= 2e-3 * abs(float(want))
    assert sorted(g_got) == sorted(layout)

    def norm(x):
        return float(jnp.linalg.norm(x.astype(jnp.float32)))

    med = float(np.median([norm(v) for v in g_want.values()]))
    gaps = {k: norm(g_got[k].astype(jnp.float32) - g_want[k])
            / max(norm(g_want[k]), med) for k in layout}
    assert max(gaps.values()) <= 4e-2, sorted(gaps.items(),
                                              key=lambda kv: -kv[1])[:3]


def test_tiny_cell_is_correct(root):
    r = tiny.run_cell(root, name="tiny-whisper", seed=7)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["metrics"]["audio_s_per_s_per_chip"]["value"] > 0


@pytest.mark.parametrize("variant", ["fp8", "no_exchange"])
def test_control_and_fault_fail(cell, variant):
    args = (cell, 7, cell.batches(7)[:3], jax.devices()[:1])
    want = reference.run(*args)
    got = reference.run(*args, variant=variant)
    chk = compare.checks(compare.numbers(got, want), cell.limits)
    assert not compare.passed(chk), chk


def test_whisper_flops_and_audio_by_hand():
    c = spec.load_cell(REPO, "whisper-seg30s-1chip")
    s, tr = c.sizes, c.traffic
    # one 30 s segment: 3000 mel frames, 1500 encoder positions, 448 tokens
    front = 2 * 3000 * 3 * 128 * 1280 + 2 * 1500 * 3 * 1280 * 1280
    enc = (2 * 1500 * 4 * 1280 ** 2 + 2 * 2 * 1500 ** 2 * 1280
           + 2 * 1500 * 2 * 1280 * 5120)
    dec = (2 * 448 * 4 * 1280 ** 2 + 2 * 2 * 448 ** 2 * 1280
           + 2 * 448 * 2 * 1280 ** 2 + 2 * 1500 * 2 * 1280 ** 2
           + 2 * 2 * 448 * 1500 * 1280 + 2 * 448 * 2 * 1280 * 5120)
    head = 2 * 448 * 1280 * 51866
    fwd = front + 4 * enc + 4 * dec + head
    assert c.model.fwd_flops_per_segment(s, 3000, 448) == fwd
    # 8 segments: 3.99 TFLOP forward, 11.97 TFLOP with the backward
    assert 8 * fwd == pytest.approx(3.989e12, rel=1e-3)
    assert c.model.model_flops_per_step(s, tr) == 3 * 8 * fwd
    assert c.model.audio_seconds_per_step(s, tr) == pytest.approx(240.0)


def test_cell_keeps_the_published_widths():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    conf = {c["name"]: c for c in bench["configs"]}["whisper-large-v3"]
    assert conf["reduced"] == ["n_enc_layers", "n_layers"]
    c = spec.load_cell(REPO, "whisper-seg30s-1chip")
    from repro.configs import get_arch
    arch = get_arch("whisper-large-v3")
    for k in ("d_model", "n_heads", "head_dim", "d_ff", "vocab", "n_mels",
              "n_audio_ctx", "n_text_ctx"):
        assert c.sizes[k] == getattr(arch, k), k
    assert c.traffic["frames"] == 2 * c.sizes["n_audio_ctx"]
    assert c.traffic["tokens"] == c.sizes["n_text_ctx"]
    assert c.traffic["vocab"] == c.sizes["vocab"]
    assert reference.lead(c) == 4
