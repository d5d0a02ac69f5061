"""Hypothesis property tests on system invariants.

``hypothesis`` is an optional dev dependency (installed in CI); the whole
module skips cleanly when it is absent so tier-1 collection never breaks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import bitslice
from repro.core.bitserial import int_matmul_direct, int_matmul_popcount
from repro.core.quantize import calibrate_minmax, dequantize, quantize
from repro.models.lm.config import ModelConfig
from repro.training.data import DataConfig, SyntheticLM
from repro.training.optimizer import OptimizerConfig, schedule


@settings(max_examples=20, deadline=None)
@given(m=st.integers(1, 8), k=st.integers(1, 64), n=st.integers(1, 8),
       ab=st.integers(1, 8), wb=st.integers(1, 8), seed=st.integers(0, 2**16))
def test_eq1_identity(m, k, n, ab, wb, seed):
    """Paper Eq. 1: the bit-plane decomposition is an exact identity."""
    key = jax.random.PRNGKey(seed)
    qa = jax.random.randint(key, (m, k), 0, 2**ab)
    qw = jax.random.randint(jax.random.fold_in(key, 1), (k, n), 0, 2**wb)
    assert (int_matmul_popcount(qa, qw, ab, wb) == int_matmul_direct(qa, qw)).all()


@settings(max_examples=20, deadline=None)
@given(bits=st.integers(1, 12), k=st.integers(1, 200))
def test_pack_is_lossless(bits, k):
    q = jax.random.randint(jax.random.PRNGKey(k), (3, k), 0, 2**bits)
    planes = bitslice.slice_and_pack(q, bits)
    assert planes.shape == (bits, 3, bitslice.pad_to_lanes(k) // 32)
    back = sum(bitslice.unpack_bits(planes[b], k).astype(jnp.int32) << b
               for b in range(bits))
    assert (back == q).all()


@settings(max_examples=25, deadline=None)
@given(
    bits=st.integers(1, 8),
    lo=st.floats(-100, 99, allow_nan=False),
    span=st.floats(0.01, 200, allow_nan=False),
)
def test_quantize_roundtrip_bound(bits, lo, span):
    """|dequant(quant(x)) - x| <= scale/2 for x within the calibration range.

    Tolerance includes an f32-cancellation allowance proportional to the
    offset magnitude ((x - qmin) loses bits when span << |lo|)."""
    from repro.core.quantize import dequantize as dq

    x = jnp.linspace(lo, lo + span, 97)
    qp = calibrate_minmax(x, bits)
    err = jnp.abs(dq(quantize(x, qp), qp) - x)
    tol = float(qp.scale) / 2 + 1e-5 + 2e-5 * abs(lo)
    assert float(err.max()) <= tol


@settings(max_examples=25, deadline=None)
@given(bits=st.integers(1, 8), lo=st.floats(-1e3, 1e3, allow_nan=False),
       span=st.floats(1e-3, 1e3))
def test_quantize_monotonic(bits, lo, span):
    """Eq. 2 preserves ordering (monotone non-decreasing codes).

    Spans below f32 resolution at the offset magnitude are cancellation
    territory (x - qmin loses all signal) — outside Eq. 2's domain."""
    from hypothesis import assume

    assume(span > abs(lo) * 1e-4 + 1e-3)
    x = jnp.linspace(lo, lo + span, 64)
    qp = calibrate_minmax(x, bits)
    q = quantize(x, qp)
    assert (jnp.diff(q) >= 0).all()
    err = jnp.abs(dequantize(q, qp) - x).max()
    assert float(err) <= float(qp.scale) / 2 + 1e-4 * max(1.0, abs(lo) + span)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), step=st.integers(0, 500))
def test_data_determinism(seed, step):
    """(seed, step) fully determines batch content; host slices tile it."""
    cfg = DataConfig(vocab=64, seq_len=16, global_batch=4, seed=seed)
    src = SyntheticLM(cfg)
    b1, b2 = src.batch(step), src.batch(step)
    assert (b1["tokens"] == b2["tokens"]).all()
    sl0 = src.host_slice(step, 0, 2)
    sl1 = src.host_slice(step, 1, 2)
    assert (np.concatenate([sl0["tokens"], sl1["tokens"]]) == b1["tokens"]).all()
    assert (b1["labels"][:, :-1] == b1["tokens"][:, 1:]).all()


@settings(max_examples=20, deadline=None)
@given(warm=st.integers(1, 50), total=st.integers(60, 500),
       step=st.integers(0, 600))
def test_lr_schedule_bounds(warm, total, step):
    cfg = OptimizerConfig(lr=1e-3, warmup_steps=warm, total_steps=total)
    lr = float(schedule(cfg, jnp.asarray(step)))
    assert 0.0 <= lr <= cfg.lr + 1e-9
    if step >= total:
        assert lr == pytest.approx(cfg.lr * cfg.min_lr_frac, rel=1e-3)


@settings(max_examples=15, deadline=None)
@given(n_layers=st.integers(1, 12), every=st.integers(0, 4))
def test_block_schedule_invariants(n_layers, every):
    cfg = ModelConfig(n_layers=n_layers, cross_attn_every=every,
                      n_image_tokens=8 if every else 0)
    blocks = cfg.blocks
    assert len(blocks) == n_layers
    if every:
        # no two adjacent cross-attn layers
        for a, b in zip(blocks, blocks[1:]):
            assert not (a == b == "cross_attn")


@settings(max_examples=15, deadline=None)
@given(bits=st.integers(2, 8), seed=st.integers(0, 100))
def test_compressed_psum_errorbound(bits, seed):
    """int-k compression error is bounded by the quantization step."""
    from repro.distributed.collectives import compress_decompress

    g = jax.random.normal(jax.random.PRNGKey(seed), (128,))
    err0 = jnp.zeros_like(g)
    g_hat, err = compress_decompress(g, err0, bits)
    step = float(jnp.abs(g).max()) / (2 ** (bits - 1) - 1)
    assert float(jnp.abs(g_hat - g).max()) <= step * 0.5 + 1e-6
    # error feedback: residual equals exactly what was lost
    assert jnp.allclose(g_hat + err, g, atol=1e-6)


@settings(max_examples=40, deadline=None)
@given(m=st.integers(1, 300), n=st.integers(1, 300), kw=st.integers(1, 64),
       ab=st.integers(1, 8), wb=st.integers(1, 8),
       bm=st.one_of(st.none(), st.integers(1, 512)),
       bn=st.one_of(st.none(), st.integers(1, 512)),
       bkw=st.one_of(st.none(), st.integers(1, 512)))
def test_autotune_tile_requests_always_legal(m, n, kw, ab, wb, bm, bn, bkw):
    """Any tile request — autotuner decision or caller whim — legalizes to
    blocks the Pallas kernel's ``_check_blocks`` accepts on the padded
    operands, and that Mosaic accepts on the chip (sublane dim: whole or a
    multiple of 8; lane dims: whole or a multiple of 128): the tuned path
    can never produce an illegal BlockSpec."""
    from repro.kernels.bitserial_matmul import _check_blocks
    from repro.kernels.ops import matmul_tiles, padded

    lb, ln, lk = matmul_tiles(m, n, kw, ab, wb, bm, bn, bkw)
    _check_blocks(padded(m, lb), padded(n, ln), padded(kw, lk),
                  lb, ln, lk)    # must not raise
    assert 1 <= lb <= m and 1 <= ln <= n and 1 <= lk <= kw
    assert lb == m or lb % 8 == 0
    assert ln == n or ln % 128 == 0
    assert lk == kw or lk % 128 == 0


@settings(max_examples=15, deadline=None)
@given(m=st.integers(1, 64), k=st.integers(1, 512), n=st.integers(1, 256),
       ab=st.sampled_from([2, 4, 8]), wb=st.sampled_from([2, 4, 8]))
def test_autotune_decision_deterministic(m, k, n, ab, wb):
    """decide_gemm is a pure function of (shape, precision, candidate set):
    rerunning it — fresh or through a warm cache — returns the same pick."""
    from repro.pim import autotune as at

    cache = at.TuningCache(None)
    d1 = at.decide_gemm(m, k, n, ab, wb, cache=cache, hlo_tiebreak=False)
    d2 = at.decide_gemm(m, k, n, ab, wb, cache=cache, hlo_tiebreak=False)
    d3 = at.decide_gemm(m, k, n, ab, wb, hlo_tiebreak=False)
    assert d1 == d2 == d3
    assert d1.backend in at.XLA_BACKENDS
