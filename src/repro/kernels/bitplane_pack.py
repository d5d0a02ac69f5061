"""Pallas kernel: fused bit-plane slice + uint32 lane pack.

Takes integer codes (M, K) int32 (K % 32 == 0) and emits the packed planes
(bits, M, K//32) uint32 consumed by :mod:`.bitserial_matmul`. One pass over
the codes produces all planes — on NAND-SPIN this is the "program each
bit-plane into its subarray" step; on TPU it is a single VMEM-resident
pass, so quantize->pack never spills intermediates to HBM.

Packing word ``j`` means summing ``bit(q[:, 32j + l]) << l`` over the 32
lanes of its group. Mosaic can neither split the lane axis into (words, 32)
nor load with a lane stride, so the sum runs on the MXU: the 0/1 plane
times a block-diagonal matrix of powers of two. Products and sums are exact
in f32 as long as each stays below 2^24, so the 32 bits go through as two
16-bit halves and are OR-ed together in int32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _half_weights(bkw: int, half: int) -> jax.Array:
    """(32*bkw, bkw) bf16: 2^(l-16*half) where lane l of word j's group is
    in this half, 0 elsewhere. Every entry is a power of two <= 2^15, exact
    in bf16."""
    r = jax.lax.broadcasted_iota(jnp.int32, (32 * bkw, bkw), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (32 * bkw, bkw), 1)
    lane = r % 32
    on = ((r // 32) == c) & ((lane // 16) == half)
    return jnp.where(on, jnp.left_shift(1, lane % 16), 0).astype(
        jnp.float32).astype(jnp.bfloat16)


def pack_tile(q: jax.Array, bits: int) -> list[jax.Array]:
    """An in-VMEM (bm, 32*bkw) int32 code tile -> ``bits`` planes, each
    (bm, bkw) uint32."""
    bkw = q.shape[1] // 32
    halves = [_half_weights(bkw, h) for h in (0, 1)]
    planes = []
    for b in range(bits):                         # static unroll over planes
        bit = ((q >> b) & 1).astype(jnp.bfloat16)
        # Explicit DEFAULT: bf16 operands are exact here, and a global
        # "float32"/"highest" matmul precision would ask Mosaic for an
        # fp32 contraction of bf16 operands, which it refuses.
        lo, hi = (jnp.dot(bit, w, precision=jax.lax.Precision.DEFAULT,
                          preferred_element_type=jnp.float32
                          ).astype(jnp.int32) for w in halves)
        planes.append((lo | (hi << 16)).astype(jnp.uint32))
    return planes


def _kernel(q_ref, o_ref, *, bits: int):
    for b, plane in enumerate(pack_tile(q_ref[...].astype(jnp.int32), bits)):
        o_ref[b] = plane


@functools.partial(jax.jit, static_argnames=("bits", "bm", "bkw", "interpret"))
def bitplane_pack(
    q: jax.Array,  # (M, K) int32 codes in [0, 2^bits)
    *,
    bits: int,
    bm: int = 256,
    bkw: int = 128,
    interpret: bool = False,
) -> jax.Array:
    m, k = q.shape
    if k % 32:
        raise ValueError("K must be a multiple of 32 (pad with zeros first)")
    kw = k // 32
    bm = min(bm, m)
    bkw = min(bkw, kw)
    if m % bm or kw % bkw:
        raise ValueError(f"({m},{kw}) not divisible by blocks ({bm},{bkw})")
    return pl.pallas_call(
        functools.partial(_kernel, bits=bits),
        grid=(m // bm, kw // bkw),
        in_specs=[pl.BlockSpec((bm, bkw * 32), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bits, bm, bkw), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((bits, m, kw), jnp.uint32),
        interpret=interpret,
    )(q)
