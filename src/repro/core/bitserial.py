"""Bit-serial arithmetic built from AND + bitcount + shift (paper Eq. 1).

    I * W = sum_n sum_m 2^(n+m) * bitcount(AND(c_n(I), c_m(W)))

Three interchangeable execution backends, all bit-exact w.r.t. each other:

``popcount``  the paper-faithful dataflow: packed uint32 planes, lane-wise
              AND, ``population_count``, accumulate with the 2^(n+m) shift
              weights. This is what the Pallas kernel
              (:mod:`repro.kernels.bitserial_matmul`) implements with VMEM
              blocking; the version here is the XLA expression of the same
              algorithm and doubles as its oracle. The (B, N, Kp) broadcast
              is chunked over output columns (``_N_CHUNK``) so the oracle
              stays compute- rather than memory-bound.

``mxu-plane`` the TPU-codesign alternative: each (n, m) plane pair is a
              {0,1} matrix contraction, which the MXU executes natively —
              ``bitcount(AND(a, w))`` over a K axis *is* a dot product of
              0/1 vectors. Same arithmetic, systolic-array execution.

``int-direct`` reference: one integer matmul of the multi-bit codes. This is
              what Eq. 1 decomposes; used to validate the other two and as
              the fast path when the target supports int8 MXU contractions.

Accumulation is int32 and exact while ``sum_k qa*qw < 2^31`` (K up to ~32k at
<8:8>); overflow wraps identically in every backend (two's complement), so
cross-backend equivalence holds mod 2^32 unconditionally.

Weights may arrive as a :class:`repro.core.packed.PackedWeight` — the
deployment fast path where codes, planes and column sums were computed once
at prepack time (the paper's "program subarrays once"); see DESIGN.md §3.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from . import bitslice
from .packed import PackedWeight, prepack
from .quantize import QuantParams, affine_correction, calibrate_minmax, quantize

Backend = ("popcount", "mxu-plane", "int-direct")

# Output-column chunk of the popcount oracle: bounds the (B, chunk, Kp)
# broadcast intermediate to one lane group per step.
_N_CHUNK = 128


# ---------------------------------------------------------------------------
# Integer core: P = qa @ qw  (qa: (..., K) codes, qw: (K, N) codes)
# ---------------------------------------------------------------------------

def int_matmul_popcount_packed(pa: jax.Array, pw: jax.Array,
                               a_bits: int, w_bits: int) -> jax.Array:
    """Eq. 1 on prepacked planes. pa (a_bits, B, Kp), pw (w_bits, N, Kp).

    Output columns are processed in ``_N_CHUNK`` groups via ``lax.map`` so
    the broadcast AND intermediate is (B, _N_CHUNK, Kp), not (B, N, Kp) —
    the full-width broadcast made the XLA oracle memory-bound at large N.
    """
    b = pa.shape[1]
    n = pw.shape[1]
    nc = min(_N_CHUNK, n)
    pad = -n % nc
    if pad:
        pw = jnp.pad(pw, ((0, 0), (0, pad), (0, 0)))
    chunks = jnp.moveaxis(  # (n_chunks, w_bits, nc, Kp)
        pw.reshape(w_bits, (n + pad) // nc, nc, pw.shape[-1]), 1, 0)

    nm = jnp.stack(jnp.meshgrid(jnp.arange(a_bits), jnp.arange(w_bits),
                                indexing="ij"), -1).reshape(-1, 2)

    def one_chunk(pw_c):
        def plane_pair(carry, i):
            nb, mb = i[0], i[1]
            # The sense-amp AND against the stored plane, per-column bitcount.
            cnt = bitslice.popcount(pa[nb][:, None, :] & pw_c[mb][None, :, :]).sum(-1)
            return carry + (cnt << (nb + mb)), None

        out, _ = jax.lax.scan(plane_pair, jnp.zeros((b, nc), jnp.int32), nm)
        return out

    out = jax.lax.map(one_chunk, chunks)          # (n_chunks, B, nc)
    out = jnp.moveaxis(out, 0, 1).reshape(b, n + pad)
    return out[:, :n]


def int_matmul_popcount(qa: jax.Array, qw: jax.Array, a_bits: int, w_bits: int) -> jax.Array:
    """Eq. 1 with packed planes + popcount. qa (B, K), qw (K, N) -> (B, N) i32."""
    pa = bitslice.slice_and_pack(qa, a_bits)    # (a_bits, B, Kp)
    pw = bitslice.slice_and_pack(qw.T, w_bits)  # (w_bits, N, Kp)
    return int_matmul_popcount_packed(pa, pw, a_bits, w_bits)


def int_matmul_mxu_plane(qa: jax.Array, qw: jax.Array, a_bits: int, w_bits: int) -> jax.Array:
    """Eq. 1 with each plane pair contracted as a {0,1} matmul (MXU path)."""
    pa = bitslice.bitplanes(qa, a_bits)  # (a_bits, B, K) 0/1
    pw = bitslice.bitplanes(qw, w_bits)  # (w_bits, K, N) 0/1
    # Contract all plane pairs in one batched einsum; XLA maps each (n, m)
    # contraction onto the MXU. f32 accumulate is exact for 0/1 entries up to
    # K < 2^24; fold the 2^(n+m) shifts afterwards.
    cnt = jnp.einsum(
        "nbk,mko->nmbo", pa.astype(jnp.bfloat16), pw.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )
    w = bitslice.plane_weights(a_bits, w_bits)[:, :, None, None]
    return (cnt * w).sum((0, 1)).astype(jnp.int32)


def int_matmul_direct(qa: jax.Array, qw: jax.Array, a_bits: int = 0, w_bits: int = 0) -> jax.Array:
    """Direct integer contraction of the codes (what Eq. 1 decomposes)."""
    return jax.lax.dot_general(
        qa.astype(jnp.int32), qw.astype(jnp.int32),
        (((qa.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


_BACKENDS = {
    "popcount": int_matmul_popcount,
    "mxu-plane": int_matmul_mxu_plane,
    "int-direct": int_matmul_direct,
}


def int_matmul(qa, qw, a_bits, w_bits, backend="popcount"):
    if backend == "pallas":  # resolved lazily to avoid a circular import
        from repro.kernels import ops as _kops

        return _kops.bitserial_matmul(qa, qw, a_bits=a_bits, w_bits=w_bits)
    return _BACKENDS[backend](qa, qw, a_bits, w_bits)


def int_matmul_prepacked(qa: jax.Array, w: PackedWeight, a_bits: int,
                         backend: str = "popcount",
                         name: str = "eq1_matmul") -> jax.Array:
    """P = qa @ w.codes using whatever representation the backend wants.

    The popcount/pallas backends consume the prepacked planes directly —
    the weight side of quantize->slice->pack never re-runs (the in-array
    operand-reuse property the paper's subarray programming buys).

    Under an active :func:`repro.pim.faults.read_disturb_scope` every call
    sees a freshly disturbed view of the stored planes (STT-MRAM read
    disturb); the import is lazy and the check is a trace-time no-op when
    the scope is inactive, so fault-free programs lower to identical HLO.

    A :class:`~repro.core.packed.TuneDecision` attached at prepack time
    (``w.tune``, see :mod:`repro.pim.autotune`) overrides ``backend`` and
    supplies Pallas tile requests — tuning redirects dispatch only; every
    backend computes the same P bit-exactly, so the result is invariant.

    ``name`` names the Pallas kernel in a trace; the XLA backends ignore it.
    """
    from repro.pim import faults as _faults  # lazy: pim imports core

    tune = w.tune
    if tune is not None:
        backend = tune.backend
    if _faults.read_disturb_active():
        w = _faults.disturb_packed(w)
    if backend == "int-direct":
        return int_matmul_direct(qa, w.codes)
    if backend == "mxu-plane":
        return int_matmul_mxu_plane(qa, w.codes, a_bits, w.bits)
    if backend == "popcount":
        pa = bitslice.slice_and_pack(qa, a_bits)
        return int_matmul_popcount_packed(pa, w.planes, a_bits, w.bits)
    if backend == "pallas":
        from repro.kernels import ops as _kops

        tiles = {} if tune is None else dict(bm=tune.bm, bn=tune.bn,
                                             bkw=tune.bkw)
        return _kops.bitserial_matmul(qa, a_bits=a_bits, w_bits=w.bits,
                                      pw=w.planes, name=name, **tiles)
    raise ValueError(f"unknown backend {backend!r}")


# ---------------------------------------------------------------------------
# Float-facing quantized matmul (Eq. 2 calibration + Eq. 1 core + correction)
# ---------------------------------------------------------------------------

def quantized_matmul(
    a: jax.Array,                    # (..., K) float
    w,                               # (K, N) float | PackedWeight
    a_bits: int = 8,
    w_bits: int = 8,
    backend: str = "popcount",
    wq: QuantParams | None = None,
    qw: jax.Array | None = None,
) -> jax.Array:
    """Full paper pipeline: calibrate -> quantize -> bit-serial P -> dequantize.

    Weights may be a :class:`PackedWeight` (the deployment mode: codes,
    planes and column sums live in memory and only activations quantize on
    the fly — the paper's weights are programmed into subarrays once), or a
    float array, optionally with legacy pre-quantized ``wq``/``qw``.
    """
    lead = a.shape[:-1]
    k = a.shape[-1]
    a2 = a.reshape(-1, k)
    aq = calibrate_minmax(a2, a_bits)
    qa = quantize(a2, aq)
    if isinstance(w, PackedWeight):
        packed = w
    elif qw is not None:
        packed = PackedWeight(codes=qw, planes=bitslice.slice_and_pack(qw.T, wq.bits),
                              col_sums=qw.sum(0).astype(jnp.int32), wq=wq)
    else:
        packed = prepack(w, w_bits)
    p = int_matmul_prepacked(qa, packed, a_bits, backend)
    sa = qa.sum(-1, keepdims=True)
    y = affine_correction(p, sa, packed.col_sums, k, aq, packed.wq)
    return y.reshape(*lead, packed.shape[-1])
