"""Quantization primitives from the paper (§4.2, Eqs. 2-3).

Eq. 2 (min/max affine quantization):
    Q_o = round((Q_i - Q_min) * (2^k - 1) / (Q_max - Q_min))

Dequantization is the affine inverse:  Q_i ~= Q_o * scale + Q_min  with
``scale = (Q_max - Q_min) / (2^k - 1)``.

Eq. 3 (batch normalization) is an affine transform at inference time; we fold
it into a (scale, bias) pair that the PIM pipeline applies with in-memory
addition/multiplication (here: a fused multiply-add).

The dot-product algebra used throughout the bit-serial path: with
``a = qa * sa + ma`` and ``w = qw * sw + mw`` (per-tensor affine),

    sum_k a_k w_k = sa*sw * P + sa*mw * Sa + sw*ma * Sw + K * ma * mw

where ``P = sum_k qa_k qw_k`` is the integer matmul computed bit-serially
(Eq. 1), ``Sa = sum_k qa_k`` and ``Sw = sum_k qw_k`` are cheap marginals.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QuantParams:
    """Affine quantization parameters for one tensor.

    ``q = round((x - qmin) / scale)``;  ``x ~= q * scale + qmin``.
    """

    scale: jax.Array  # scalar or broadcastable, f32
    qmin: jax.Array  # scalar or broadcastable, f32 (the paper's Q_min offset)
    bits: int = dataclasses.field(metadata=dict(static=True), default=8)


def calibrate_minmax(x: jax.Array, bits: int, axis=None) -> QuantParams:
    """Paper Eq. 2 calibration: per-tensor (or per-axis) min/max."""
    qmin = jnp.min(x, axis=axis, keepdims=axis is not None)
    qmax = jnp.max(x, axis=axis, keepdims=axis is not None)
    # Guard the degenerate all-constant tensor; scale must stay positive.
    span = jnp.maximum(qmax - qmin, jnp.finfo(jnp.float32).tiny)
    scale = span.astype(jnp.float32) / float(2**bits - 1)
    return QuantParams(scale=scale, qmin=qmin.astype(jnp.float32), bits=bits)


def quantize(x: jax.Array, qp: QuantParams) -> jax.Array:
    """Eq. 2 forward: float -> unsigned integer codes in [0, 2^bits).

    The codes are materialized (``optimization_barrier``): every consumer —
    the Eq. 1 product, the ``Sa`` marginal — reads the same array. Fused
    into each consumer instead, the rounding would be recomputed in
    different fusion contexts, which on TPU need not agree to the last
    bit, and the backends would stop agreeing bit for bit.
    """
    q = jnp.round((x.astype(jnp.float32) - qp.qmin) / qp.scale)
    return jax.lax.optimization_barrier(
        jnp.clip(q, 0.0, float(2**qp.bits - 1)).astype(jnp.int32))


def dequantize(q: jax.Array, qp: QuantParams) -> jax.Array:
    return q.astype(jnp.float32) * qp.scale + qp.qmin


@jax.custom_vjp
def _ste_round(x):
    return jnp.round(x)


def _ste_round_fwd(x):
    return jnp.round(x), None


def _ste_round_bwd(_, g):
    return (g,)


_ste_round.defvjp(_ste_round_fwd, _ste_round_bwd)


def fake_quant(x: jax.Array, bits: int, axis=None) -> jax.Array:
    """Quantize-dequantize with a straight-through estimator.

    Used for quantization-aware *training* of PIM layers (beyond-paper: the
    paper is inference-only; QAT is what makes the technique a first-class
    feature of the training framework).
    """
    qp = calibrate_minmax(jax.lax.stop_gradient(x), bits, axis=axis)
    q = _ste_round((x - qp.qmin) / qp.scale)
    q = jnp.clip(q, 0.0, float(2**bits - 1))
    # preserve the input dtype: QAT must not promote bf16 residuals to f32
    # (scan carries are typed on the compute dtype)
    return (q * qp.scale + qp.qmin).astype(x.dtype)


def fold_batchnorm(gamma, beta, mean, var, eps=1e-5):
    """Eq. 3 as an inference-time affine: returns (scale, bias) such that
    ``y = x * scale + bias`` reproduces batch normalization."""
    inv = gamma / jnp.sqrt(var + eps)
    return inv, beta - mean * inv


def affine_correction(
    prod: jax.Array,  # integer matmul P = qa @ qw, shape (..., N)
    sa: jax.Array,  # row-sums of qa along K, shape (..., 1)
    sw: jax.Array,  # col-sums of qw along K, (N,) or broadcastable (..., N)
    k,              # contraction length: int, or broadcastable (..., 1) array
    aq: QuantParams,
    wq: QuantParams,
) -> jax.Array:
    """Recover the float dot product from integer pieces (module docstring).

    ``sw`` and ``k`` may vary per output position (broadcastable arrays):
    a spatially-padded convolution treats padded taps as contributing
    *exactly zero*, so near borders the effective weight-code sum and the
    effective contraction length shrink per patch (see ``pim_conv2d``).

    ``prod`` is materialized first, so the float epilogue fuses the same
    way whichever backend produced P (an XLA dot would otherwise take it
    into its output fusion and a Pallas kernel could not): the backends
    then agree bit for bit, as the autotuner's contract requires.
    """
    p = jax.lax.optimization_barrier(prod).astype(jnp.float32)
    return (
        aq.scale * wq.scale * p
        + aq.scale * wq.qmin * sa.astype(jnp.float32)
        + wq.scale * aq.qmin * sw.astype(jnp.float32)
        + jnp.asarray(k, jnp.float32) * aq.qmin * wq.qmin
    )
