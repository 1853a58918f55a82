"""Real-size compiles of the BLSTM kernels for a described TPU v5e chip.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: block shapes off the (8, 128) tiling, or more VMEM than
the kernel's limit.  These tests compile the Pallas BLSTM at the paper's
widths (H=512 per direction, B=256, T=21, auto ``block_b``) for a
``v5e:2x2`` topology that is described, not attached, so they run on a
CPU-only machine.  Nothing executes; each test asserts the Mosaic kernels
are in the compiled program (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest-xdist workers all
import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import lstm_cell as LC

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
