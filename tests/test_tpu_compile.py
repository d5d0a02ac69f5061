"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Interpret mode (every other kernel test) runs the kernel bodies on the CPU
and cannot see what Mosaic refuses: unaligned blocks, lane-splitting
reshapes, value-level strided slices, unsigned reductions, VMEM overruns.
These tests compile each kernel through its ``kernels.ops`` wrapper — so
tile legalization and padding are exercised too — at ResNet-50/224 shapes
for one chip of a ``v5e:2x2`` topology that is described, not attached.
Nothing runs; a passing compile is not a chip run.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without that chip; keep it out of the cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return compiled


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("m,k", [
    (1000, 2048),           # the head's weight codes, transposed
    (8 * 58 * 58, 64),      # a stage-0 map: N*Hp*Wp rows, C=64 channels
])
def test_bitplane_pack_compiles(one_chip, m, k):
    _compile(functools.partial(ops.pack_planes, bits=8, interpret=False),
             _sds((m, k), jnp.int32, one_chip))


@pytest.mark.parametrize("m,k,n", [
    (8, 2048, 1000),        # the head: N pads to a lane multiple
    (25088, 256, 64),       # a stage-0 1x1 conv, 8 x 56 x 56 rows
    (8 * 112 * 112, 147, 64),   # the 7x7/2 stem's im2col GEMM, K = 7*7*3
])
def test_bitserial_matmul_fused_compiles(one_chip, m, k, n):
    fn = functools.partial(ops.bitserial_matmul, a_bits=8, w_bits=8,
                           interpret=False)
    _compile(lambda qa, pw: fn(qa, pw=pw),
             _sds((m, k), jnp.int32, one_chip),
             _sds((8, n, -(-k // 32)), jnp.uint32, one_chip))


@pytest.mark.parametrize("hw,kern,c,o,stride,pad", [
    (224, 7, 3, 64, 2, 3),  # the 7x7/2 stem, forced fused
    (56, 3, 64, 64, 1, 1),  # a stage-0 3x3
])
def test_conv2d_bitserial_fused_compiles(one_chip, hw, kern, c, o, stride,
                                         pad):
    hp = hw + 2 * pad
    fn = functools.partial(ops.conv2d_bitserial, a_bits=8, stride=stride,
                           interpret=False)
    _compile(fn, _sds((8, hp, hp, c), jnp.int32, one_chip),
             _sds((kern, 8, kern, -(-c // 32), o), jnp.uint32, one_chip))


def test_stem_conv_lowers_to_named_im2col_gemm(one_chip, monkeypatch):
    """The stem's quantized conv, as ``pim_conv2d`` lowers it by default:
    one Mosaic kernel, the im2col GEMM, named ``eq1_conv_k7s2`` in the
    compiled program (the name a trace shows). A 32-px map keeps the
    compile short; the patch-size rule is switched off, so the word-fill
    rule alone picks the path, as at 224 px."""
    from repro.core import (PIMQuantConfig, pim_conv2d, pim_layers,
                            prepack_conv2d)

    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    monkeypatch.setattr(pim_layers, "_FUSE_MIN_BYTES", 0)
    cfg = PIMQuantConfig(w_bits=8, a_bits=8, backend="pallas")
    pk = jax.eval_shape(lambda: prepack_conv2d(
        jnp.zeros((7, 7, 3, 64), jnp.float32), cfg))
    pk = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), pk)
    text = _compile(
        lambda x, w: pim_conv2d(x, w, stride=2, padding=3, cfg=cfg),
        _sds((8, 32, 32, 3), jnp.float32, one_chip), pk).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "eq1_conv_k7s2" in text and "eq1_matmul" not in text
