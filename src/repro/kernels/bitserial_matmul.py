"""Pallas TPU kernels for the paper's Eq. 1 bit-serial matmul.

Computes ``P[b, o] = sum_{n,m} 2^(n+m) * popcount(pa[n, b, :] & pw[m, o, :])``
over packed uint32 bit-planes — the NAND-SPIN subarray dataflow mapped onto
the TPU memory hierarchy:

  HBM             packed activation planes + packed weight planes
  VMEM (BlockSpec)  one (bm x bkw) activation tile per plane, one (bn x bkw)
                    weight tile per plane  (== the paper's weight buffer)
  VREG/VPU        lane-wise AND + population_count  (== sense-amp AND + column
                    bit-counter)
  VMEM accumulator  output tile revisited across the K grid axis (== the
                    paper's cross-written partial sums staying in-mat)

Grid = (m_tiles, n_tiles, k_tiles) with K innermost, so the int32 output
block stays resident in VMEM while partial popcounts accumulate — partial
sums never round-trip to HBM, which is exactly the property the paper's
cross-writing scheme buys on NAND-SPIN.

Two entry points:

``bitserial_matmul_packed``  both operands pre-packed (a_bits/w_bits, ·, KW)
                             uint32 planes.
``bitserial_matmul_fused``   activations arrive as raw integer *codes*; the
                             kernel bit-slices and lane-packs each K tile in
                             VMEM before the AND+popcount loop, so
                             quantize->pack->popcount is ONE ``pallas_call``
                             and the packed activation planes never
                             round-trip through HBM. Weight planes arrive
                             prepacked (see ``repro.core.packed`` — the
                             paper's program-subarrays-once step).

Both kernels accumulate Eq. 1 one packed K word at a time on 2-D
(bm, bn) tiles, output columns on lanes: word ``kk`` of the activation
plane is a (bm, 1) column broadcast across lanes, word ``kk`` of the weight
plane a (1, bn) row broadcast across sublanes, and their AND + popcount
adds into the tile. The weight block arrives (bn, bkw) and is transposed
once per grid step into a VMEM scratch; the (plane, plane) pairs run in a
``fori_loop`` so compile time does not grow with ``a_bits * w_bits``. The
MXU is idle in these kernels apart from the activation pack (see
:mod:`.bitplane_pack`) — Eq. 1 is a VPU bit-op pipeline. See ``mxu_plane``
in :mod:`repro.core.bitserial` for the systolic alternative, and DESIGN.md
§2 for the trade-off experiment.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bitplane_pack import pack_tile


def _accumulate(planes_ref, w_ref, o_ref, wt_ref, *, a_bits: int,
                w_bits: int):
    """Eq. 1 accumulation into ``o_ref`` (bm, bn); the packed-plane kernel.

    planes_ref (a_bits, bm, bkw) activation planes; w_ref (w_bits, bn, bkw)
    weight planes; wt_ref (w_bits, bkw, bn) scratch for their transpose.
    """
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bm, bn = o_ref.shape
    bkw = planes_ref.shape[-1]
    for m in range(w_bits):
        wt_ref[m] = w_ref[m].T

    def pair(t, acc):
        n, m = t // w_bits, t % w_bits
        a = planes_ref[n]                         # (bm, bkw)
        w = wt_ref[m]                             # (bkw, bn)
        cnt = jnp.zeros((bm, bn), jnp.int32)
        for kk in range(bkw):                     # static unroll: K words
            # sense-amp AND + per-column bitcount, 32 cells per word
            x = (jnp.broadcast_to(a[:, kk:kk + 1], (bm, bn))
                 & jnp.broadcast_to(w[kk:kk + 1, :], (bm, bn)))
            cnt += jax.lax.population_count(x).astype(jnp.int32)
        return acc + (cnt << (n + m))

    o_ref[...] += jax.lax.fori_loop(0, a_bits * w_bits, pair,
                                    jnp.zeros((bm, bn), jnp.int32))


def _fused_kernel(qa_ref, w_ref, o_ref, planes_ref, wt_ref, *, a_bits: int,
                  w_bits: int):
    # Bit-slice + lane-pack the activation K tile in VMEM: the packed planes
    # are kernel-local, never written to HBM (vs. the 3-launch pipeline).
    for n, plane in enumerate(pack_tile(qa_ref[...].astype(jnp.int32),
                                        a_bits)):
        planes_ref[n] = plane
    _accumulate(planes_ref, w_ref, o_ref, wt_ref, a_bits=a_bits,
                w_bits=w_bits)


def _check_blocks(m, n, kw, bm, bn, bkw):
    if m % bm or n % bn or kw % bkw:
        raise ValueError(
            f"shape ({m},{n},{kw}) not divisible by blocks ({bm},{bn},{bkw})")


@functools.partial(
    jax.jit, static_argnames=("a_bits", "w_bits", "bm", "bn", "bkw", "interpret")
)
def bitserial_matmul_packed(
    pa: jax.Array,  # (a_bits, M, KW) uint32 packed activation planes
    pw: jax.Array,  # (w_bits, N, KW) uint32 packed weight planes
    *,
    a_bits: int,
    w_bits: int,
    bm: int = 128,
    bn: int = 128,
    bkw: int = 64,
    interpret: bool = False,
) -> jax.Array:
    """Packed-plane bit-serial matmul -> (M, N) int32."""
    _, m, kw = pa.shape
    _, n, _ = pw.shape
    bm = min(bm, m)
    bn = min(bn, n)
    bkw = min(bkw, kw)
    _check_blocks(m, n, kw, bm, bn, bkw)

    return pl.pallas_call(
        functools.partial(_accumulate, a_bits=a_bits, w_bits=w_bits),
        grid=(m // bm, n // bn, kw // bkw),
        in_specs=[
            pl.BlockSpec((a_bits, bm, bkw), lambda i, j, k: (0, i, k)),
            pl.BlockSpec((w_bits, bn, bkw), lambda i, j, k: (0, j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((w_bits, bkw, bn), jnp.uint32)],
        interpret=interpret,
        name="eq1_matmul_packed",
    )(pa, pw)


@functools.partial(
    jax.jit,
    static_argnames=("a_bits", "w_bits", "bm", "bn", "bkw", "interpret",
                     "name"))
def bitserial_matmul_fused(
    qa: jax.Array,  # (M, K) int32 activation codes, K % 32 == 0
    pw: jax.Array,  # (w_bits, N, K//32) uint32 prepacked weight planes
    *,
    a_bits: int,
    w_bits: int,
    bm: int = 128,
    bn: int = 128,
    bkw: int = 64,
    interpret: bool = False,
    name: str = "eq1_matmul",
) -> jax.Array:
    """Fused pack+matmul: activation codes in, (M, N) int32 out, one launch.

    ``name`` is the kernel's name in a trace (a conv's GEMM takes its
    conv's name)."""
    m, k = qa.shape
    _, n, kw = pw.shape
    if k != kw * 32:
        raise ValueError(f"K={k} does not match packed weight KW={kw}")
    bm = min(bm, m)
    bn = min(bn, n)
    bkw = min(bkw, kw)
    _check_blocks(m, n, kw, bm, bn, bkw)

    return pl.pallas_call(
        functools.partial(_fused_kernel, a_bits=a_bits, w_bits=w_bits),
        grid=(m // bm, n // bn, kw // bkw),
        in_specs=[
            pl.BlockSpec((bm, bkw * 32), lambda i, j, k: (i, k)),
            pl.BlockSpec((w_bits, bn, bkw), lambda i, j, k: (0, j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((a_bits, bm, bkw), jnp.uint32),
                        pltpu.VMEM((w_bits, bkw, bn), jnp.uint32)],
        interpret=interpret,
        name=name,
    )(qa, pw)


def bitserial_matmul_sharded(
    qa: jax.Array,  # (M, K) int32 activation codes, K = KW*32
    pw: jax.Array,  # (w_bits, N, KW) uint32 prepacked weight planes
    *,
    a_bits: int,
    w_bits: int,
    mesh,
    axis: str = "model",
    bm: int = 128,
    bn: int = 128,
    bkw: int = 64,
    interpret: bool = False,
) -> jax.Array:
    """Mesh-sharded Eq. 1: the paper's cross-subarray accumulation.

    The packed contraction (KW uint32 words == K/32 input columns) is split
    across mesh ``axis`` — each shard holds a contiguous group of subarray
    rows (``core.packed.shard_packed(..., split="k")`` lays weights out this
    way) and runs the fused single-launch kernel on its resident planes.
    The per-shard int32 popcount partials then reduce losslessly via
    ``distributed.collectives.exact_psum`` — the one collective this matmul
    needs, mirroring how the paper accumulates cross-written partial sums
    across subarrays. ``shard_map`` is required because ``pallas_call`` has
    no GSPMD partitioning rule: under plain jit a sharded operand would
    silently gather.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.collectives import exact_psum

    m, k = qa.shape
    _, n, kw = pw.shape
    if k != kw * 32:
        raise ValueError(f"K={k} does not match packed weight KW={kw}")
    size = mesh.shape[axis]
    if kw % size:
        raise ValueError(
            f"packed K words {kw} not divisible by mesh axis {axis!r}={size}")

    def local(qa_l, pw_l):
        p = bitserial_matmul_fused(qa_l, pw_l, a_bits=a_bits, w_bits=w_bits,
                                   bm=bm, bn=bn, bkw=bkw, interpret=interpret)
        return exact_psum(p, axis)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, axis), P(None, None, axis)),
        out_specs=P(None, None),
        check_vma=False,   # pallas_call has no replication rule
    )(qa, pw)
