"""Real-size compiles of the BLSTM kernels for a described TPU v5e chip.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: block shapes off the (8, 128) tiling, or more VMEM than
the kernel's limit.  These tests compile the Pallas BLSTM at the paper's
widths (H=512 per direction, B=256, T=21, auto ``block_b``) for a
``v5e:2x2`` topology that is described, not attached, so they run on a
CPU-only machine.  Nothing executes; each test asserts the Mosaic kernels
are in the compiled program (``tpu_custom_call``).  The last compiles the
training cell's whole step and checks the names the device trace and the
op metadata will carry: the kernels' (``blstm_fwd``, ``lstm_bwd``,
``softmax_ce_train``) and the layer scopes'.  The fused output layer
(``kernels/softmax_ce.py``) compiles at the cell's 4 x 5376 frames x
32,000 states, and the step holds no logits-sized array.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest-xdist workers all
import this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as FA
from repro.kernels import lstm_cell as LC
from repro.kernels import softmax_ce as SCE

B, T, H = 256, 21, 512
KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A described chip, with the persistent compilation cache off: a
    compile for it is written to the cache but cannot be read back
    without the chip."""
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _layer(sharding, D):
    """(wx_fwd, wh_fwd, b_fwd, wx_bwd, wh_bwd, b_bwd) stand-ins."""
    return ((_sds(sharding, (D, 4 * H), jnp.bfloat16),
             _sds(sharding, (H, 4 * H), jnp.bfloat16),
             _sds(sharding, (4 * H,), jnp.float32)) * 2)


def _kernels(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text().count(KERNEL)


def _train_kernels(sharding, D, T, **kw):
    """Compile value_and_grad through the fused BLSTM layer (stashing
    forward + one backward per direction) and count its kernels."""
    lengths = kw.pop("lengths", None)

    def loss(*a):
        y = LC.blstm_sequence(*a, interpret=False, **kw)
        return jnp.sum(y.astype(jnp.float32))

    args = _layer(sharding, D) + (_sds(sharding, (B, T, D), jnp.bfloat16),)
    if lengths is not None:
        args += (_sds(sharding, (B,), jnp.int32),)
    return _kernels(jax.value_and_grad(loss, argnums=tuple(range(7))),
                    *args)


@pytest.mark.parametrize("D", [260, 1024])
def test_blstm_train_compiles(one_chip, D):
    """Layer 0 (D=260) and layers 1-5 (D=1024): the tuner keeps one
    256-row tile per learner batch, and the stashing forward and both
    backward kernels compile."""
    assert LC.auto_block_b(B, D, H, 2, n_dir=2, training=True) == B
    assert _train_kernels(one_chip, D, T) == 3


def test_blstm_masked_train_compiles(one_chip):
    """The variable-length kernels, whose (bB, 1) lengths block rides the
    batch grid axis."""
    assert _train_kernels(one_chip, 1024, T, lengths=True) == 3


def test_blstm_chunked_train_compiles(one_chip):
    """The sequence-chunked recompute kernels of long-utterance training
    (auto (block_b, K) at T=2000)."""
    assert _train_kernels(one_chip, 1024, 2000, seq_chunk=-1) == 3


def test_blstm_stack_inference_compiles(one_chip):
    """The six-layer inference stack (260 -> 1024 -> ... features) fits
    VMEM as ONE fused kernel at the paper's B and T."""
    params = (_layer(one_chip, 260),) + tuple(
        _layer(one_chip, 1024) for _ in range(5))
    x = _sds(one_chip, (B, T, 260), jnp.bfloat16)
    n = _kernels(lambda p, x: LC.blstm_stack_sequence(p, x, interpret=False),
                 params, x)
    assert n == 1


@pytest.mark.parametrize("rule", ["primal", "train"])
def test_softmax_ce_compiles(one_chip, rule):
    """The fused output layer at the cell's shapes (4 learners x 5376
    frames, 256 -> 32,000): its W, f32 dW accumulator and the tile's
    f32 exponentials fit VMEM under the kernel's limit, for the loss
    alone (primal rule) and under value_and_grad, each one Mosaic
    kernel."""
    L, R, K, V = 4, 256 * T, 256, 32000
    args = (_sds(one_chip, (L, R, K), jnp.bfloat16),
            _sds(one_chip, (L, K, V), jnp.bfloat16),
            _sds(one_chip, (L, V), jnp.float32),
            _sds(one_chip, (L, R), jnp.int32))

    def loss(z, w, b, labels):
        return SCE.softmax_ce(z, w, b, labels, interpret=False)

    if rule == "train":
        loss = jax.value_and_grad(loss, argnums=(0, 1, 2))
    hlo = jax.jit(jax.vmap(loss)).lower(*args).compile().as_text()
    assert hlo.count(KERNEL) == 1
    assert "softmax_ce_train" in hlo


def _step_for(device, cfg, n_learners, kernel_impl, schedule, batch):
    """The ring AD-PSGD train step of ``setup_training`` for
    ``n_learners`` stacked learners of ``cfg`` on one described chip,
    with its state's and ``batch``'s shapes ({name: (shape, dtype)})."""
    from repro.core import strategies as ST
    from repro.launch.mesh import make_local_mesh, rules_for
    from repro.models import build_model
    from repro.optim.optimizers import get_optimizer
    from repro.sharding import init_spec_tree, spec_tree_shardings

    mesh = make_local_mesh(data=1, devices=[device])
    rules = rules_for(cfg, mesh)
    strategy, opt = ST.get_strategy("ad_psgd"), get_optimizer("sgd")
    transport = ST.transport_from_cfg(cfg, strategy)
    model = build_model(cfg)
    step = ST.make_train_step(
        strategy, lambda p, b: model.loss_fn(p, b, kernel_impl=kernel_impl),
        opt, schedule, n_learners=n_learners, transport=transport)
    pspecs = model.param_specs()
    lead = ((n_learners, "learner"),)
    shardings = spec_tree_shardings(pspecs, rules, extra_leading=lead)

    def init():
        params = ST.stack_for_learners(
            init_spec_tree(pspecs, jax.random.PRNGKey(0)), n_learners)
        return ST.init_state(strategy, params, opt, transport=transport)

    one = SingleDeviceSharding(device)
    state = {k: jax.tree.map(
        lambda s, sh=None: _sds(sh or one, s.shape, s.dtype), v,
        *([shardings] if k in ("params", "prev_params") else []))
        for k, v in jax.eval_shape(init).items()}
    return mesh, step, state, {k: _sds(one, *v) for k, v in batch.items()}


def _cell_step(device, n_learners):
    """The 1-chip BLSTM training cell's step: 4 learners of 256 x 21
    frames at published widths, Pallas kernels."""
    import dataclasses

    from repro.configs import get_arch
    from repro.optim.schedules import paper_recipe

    cfg = dataclasses.replace(get_arch("swb2000-blstm"),
                              lstm_vmem_budget_mb=96)
    rows = 256 * n_learners
    return _step_for(
        device, cfg, n_learners, "pallas",
        paper_recipe(steps_per_epoch=1000, base_lr=0.05, peak_lr=0.2),
        {"features": ((rows, T, 260), jnp.float32),
         "labels": ((rows, T), jnp.int32)})


def _kernel_names(hlo):
    return sorted(re.match(r"\s*(?:ROOT )?%([\w-]+)\.\d+ = ", ln).group(1)
                  for ln in hlo.splitlines() if KERNEL in ln)


def test_train_step_names_its_kernels_and_scopes(topo, one_chip,
                                                 monkeypatch):
    """The training cell's whole step: every kernel keeps its name in
    the compiled program's instruction names (what the device trace
    shows), the layer scopes reach the op metadata of its fusions (the
    output layer's, of its kernel too), and no array the size of the logits is left: none ends in
    the 32,000 states with more elements than the learners' dW."""
    import math

    for kernels in (LC, SCE):
        monkeypatch.setattr(kernels, "_resolve_interpret",
                            lambda i: False if i is None else i)
    mesh, step, state, batch = _cell_step(topo.devices[0], 4)
    with jax.set_mesh(mesh):
        hlo = jax.jit(step, donate_argnums=(0,)).lower(
            state, batch).compile().as_text()
    assert hlo.startswith("HloModule jit_train_step")
    # six forward kernels (both directions of a layer), one backward
    # kernel per direction and layer, the fused output layer
    assert _kernel_names(hlo) == (["blstm_fwd"] * 6 + ["lstm_bwd"] * 12
                                  + ["softmax_ce_train"])
    fusions = [ln for ln in hlo.splitlines()
               if re.match(r"\s*(?:ROOT )?%[\w.-]*fusion[\w.-]* = ", ln)]
    # the output layer's work is its kernel: its scope may sit on the
    # custom call alone
    output_layer = fusions + [ln for ln in hlo.splitlines() if KERNEL in ln]
    for scope in ("softmax_ce", "mixing", "update", "grad", "bottleneck",
                  "blstm_l0", "blstm_l5"):
        lines = output_layer if scope == "softmax_ce" else fusions
        # a transform wraps the scope it runs in: vmap(jvp(softmax_ce))
        assert any(re.search(rf'op_name="[^"]*[/(]{scope}[)/]', ln)
                   for ln in lines), scope
    start = hlo.index("\nENTRY ")
    entry = hlo[start:hlo.index("\n}\n", start)]
    dw = 4 * 256 * 32000
    logits = {(dt, dims) for dt, dims in re.findall(
        r"\b(bf16|f32)\[([\d,]+)\]", entry)
        if dims.endswith(",32000")
        and math.prod(int(d) for d in dims.split(",")) > dw}
    assert not logits, logits


@pytest.mark.parametrize("sq,sk,causal", [(1500, 1500, False),
                                          (448, 448, True),
                                          (448, 1500, False)])
def test_attention_kernels_compile(one_chip, sq, sk, causal):
    """whisper-large-v3's three attentions at the cell's shapes (4
    learners x 2 segments, 20 heads of 64): the encoder's 1500 frames,
    the decoder's causal 448 tokens, and its cross-attention of 448
    tokens over 1500 frames.  Under value_and_grad the forward and the
    backward kernel compile: the whole key row of a head and a 256-row
    query block's f32 scores fit VMEM, with the key length off the
    128-lane tiling."""
    L, B, H, E = 4, 2, 20, 64

    def loss(q, k, v):
        o = FA.flash_attention(q, k, v, causal=causal, interpret=False)
        return jnp.sum(o.astype(jnp.float32))

    args = [_sds(one_chip, (L, B, s, H, E), jnp.bfloat16)
            for s in (sq, sk, sk)]
    hlo = jax.jit(jax.vmap(jax.value_and_grad(loss, argnums=(0, 1, 2)))
                  ).lower(*args).compile().as_text()
    # a transform may prefix the instruction name: vmap_jvp_attn_fwd
    names = _kernel_names(hlo)
    assert len(names) == 2
    assert [n for n in names if "attn_fwd" in n] != []
    assert [n for n in names if "attn_bwd" in n] != []


# temporaries of the whisper cell's step before its attention kernel
# (``memory_analysis`` for a described v5e; PERF.md section 4)
WHISPER_TEMP_BYTES = 7_715_267_072


def test_whisper_train_step_takes_the_attention_kernels(topo, monkeypatch):
    """The whisper cell's whole step (4 learners x 2 segments of 30 s,
    4 + 4 layers at published widths), traced for a TPU: each attention
    is one ``attn_fwd`` and one ``attn_bwd`` (encoder, decoder self and
    cross; the layer remat keeps the log-sum-exp, so no forward kernel
    reruns in the backward), no f32 score-shaped array of (..., 20 heads,
    448 or more queries, 1500 keys) is left in the program, and its
    planned temporaries do not grow."""
    import dataclasses

    from repro.configs import get_arch
    from repro.optim.schedules import paper_recipe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(FA, "_resolve_interpret", lambda i: False)
    cfg = dataclasses.replace(get_arch("whisper-large-v3"), n_enc_layers=4,
                              n_layers=4)
    rows = 2 * 4
    mesh, step, state, batch = _step_for(
        topo.devices[0], cfg, 4, "jax",
        paper_recipe(steps_per_epoch=100000, base_lr=0.01, peak_lr=0.01),
        {"mel": ((rows, 3000, 128), jnp.float32),
         "tokens": ((rows, 448), jnp.int32),
         "labels": ((rows, 448), jnp.int32)})
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, donate_argnums=(0,)).lower(
            state, batch).compile()
    hlo = compiled.as_text()
    assert _kernel_names(hlo) == ["attn_bwd"] * 3 + ["attn_fwd"] * 3
    scores = {dims for dims in re.findall(r"\bf32\[([\d,]+)\]", hlo)
              if re.search(r"(^|,)20,(\d+,)*\d+,1500$", dims)
              and int(dims.split(",")[-2]) >= 448}
    assert not scores, scores
    assert compiled.memory_analysis().temp_size_in_bytes <= WHISPER_TEMP_BYTES
