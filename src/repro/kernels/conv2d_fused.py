"""Pallas kernel: fused implicit-im2col bit-serial convolution.

The materialized conv lowering builds the (N*OH*OW, KH*KW*C) patch matrix in
HBM — a KH*KW-fold blow-up of the activation that the paper's architecture
never pays: NAND-SPIN slides the weight buffer over *resident* input planes
(Fig. 8's row-activation schedule). This kernel reproduces that property on
TPU: the grid's K axis walks the KH kernel-row offsets, and each grid step
streams exactly one padded input row of activation codes from HBM, packs
its bit-planes in VMEM (:func:`.bitplane_pack.pack_tile`), and walks the
KW offsets *inside* the kernel with strided loads from the packed row. No
patch matrix, and no packed activation plane, ever exists in HBM.

Layouts (built by :func:`repro.kernels.ops.conv2d_bitserial`):

  q   (N*Hp, Wp, 32*CW) int32 — activation codes, channels zero-padded to
      CW = ceil(C/32) words; spatial padding applied beforehand with the
      ZERO code (which ANDs to zero popcount — padded taps contribute
      nothing to P), so patches match the materialized path bit-exactly.
  pw  (KH, w_bits, KW, CW, O) uint32 — per-kernel-row weight planes
      (``PackedConvWeight.fused_planes``), output channels on lanes.
  out (N*OH, OW, O) int32 — P tiles; the (OW, bo) accumulator stays in VMEM
      across the KH grid axis (cross-writing, as in the matmul kernel).

Grid = (N*OH, O//bo, KH) with KH innermost. The activation BlockSpec uses a
size-1 block on the row axis, so the index map addresses the *element* row
(n*Hp + oh*stride + kh) directly — that arithmetic is the whole implicit
im2col. Inside a step, each channel word is a (OW, 1) activation column
broadcast across lanes, AND-ed with a (1, bo) weight row broadcast across
sublanes, as in :mod:`.bitserial_matmul`.

Where it stops paying: only channels share a 32-bit word, so each output
and plane pair costs KH*KW*CW words. An input with few channels leaves
most of each word empty — ResNet's 7x7 stem on C = 3 fills 3 bits of 32,
49 words where the im2col GEMM packs the same 147-element patch into 5.
:func:`repro.core.pim_layers.fuse_conv_heuristic` sends such convs to the
im2col GEMM (:mod:`.bitserial_matmul`) instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bitplane_pack import pack_tile


def _pad_o_blocks(o: int, bo: int) -> tuple[int, int]:
    """Output-channel tiling: pick the block and the zero-padding of O.

    O sits on lanes, so a block is either all of O or a multiple of 128.
    The old fallback shrank ``bo`` until it divided O, which degenerates to
    ``bo = 1`` for prime O (an O-sized grid of tiny kernels). Instead an
    O up to one lane group is one tile, and a larger O pads up to the
    next multiple of the (lane-rounded) block — zero weight planes AND to
    zero popcounts, so the padded columns cost one wasted tile and are
    sliced off after the call.
    """
    if o <= max(bo, 128):
        return o, 0
    bo = max(128, bo // 128 * 128)
    return bo, -o % bo


def kernel_name(kh: int, kw: int, stride: int) -> str:
    """The name a KHxKW/stride conv's Eq. 1 kernel carries in a trace."""
    ksize = kh if kh == kw else f"{kh}x{kw}"
    return f"eq1_conv_k{ksize}s{stride}"


def _kernel(q_ref, w_ref, o_ref, a_ref, *, a_bits: int, w_bits: int,
            kw_sz: int, ow: int, stride: int, cw: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    for n, plane in enumerate(pack_tile(q_ref[0], a_bits)):
        a_ref[n] = plane                           # (Wp, CW) packed row
    bo = o_ref.shape[-1]

    def pair(t, acc):
        n, m = t // w_bits, t % w_bits
        cnt = jnp.zeros((ow, bo), jnp.int32)
        for dx in range(kw_sz):                    # implicit im2col: KW walk
            # Output positions ow_i read words [dx + ow_i*stride] of the row.
            cols = pl.ds(dx, ow, stride=stride) if stride > 1 else pl.ds(dx, ow)
            a = a_ref[n, cols, :]                  # (ow, CW)
            w = w_ref[0, m, dx]                    # (CW, bo)
            for c in range(cw):
                x = (jnp.broadcast_to(a[:, c:c + 1], (ow, bo))
                     & jnp.broadcast_to(w[c:c + 1, :], (ow, bo)))
                cnt += jax.lax.population_count(x).astype(jnp.int32)
        return acc + (cnt << (n + m))

    o_ref[0] += jax.lax.fori_loop(0, a_bits * w_bits, pair,
                                  jnp.zeros((ow, bo), jnp.int32))


@functools.partial(jax.jit, static_argnames=(
    "a_bits", "n", "hp", "oh", "ow", "stride", "bo", "interpret"))
def conv2d_bitserial_fused(
    q: jax.Array,   # (N*Hp, Wp, 32*CW) int32 activation codes
    pw: jax.Array,  # (KH, w_bits, KW, CW, O) uint32 packed weight planes
    *,
    a_bits: int,
    n: int,
    hp: int,
    oh: int,
    ow: int,
    stride: int = 1,
    bo: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Fused bit-serial conv -> P (N, OH, OW, O) int32 (integer part of Eq. 1)."""
    rows, wp, c32 = q.shape
    kh, w_bits, kw_sz, cw, o = pw.shape
    if rows != n * hp:
        raise ValueError(f"q rows {rows} != n*hp {n * hp}")
    if c32 != 32 * cw:
        raise ValueError(f"code channels {c32} != 32 * weight words {cw}")
    if wp < (ow - 1) * stride + kw_sz:
        raise ValueError(f"padded width {wp} too small for ow={ow}")
    bo, o_pad = _pad_o_blocks(o, bo)
    if o_pad:
        pw = jnp.pad(pw, ((0, 0), (0, 0), (0, 0), (0, 0), (0, o_pad)))
    op = o + o_pad

    grid = (n * oh, op // bo, kh)
    kern = functools.partial(_kernel, a_bits=a_bits, w_bits=w_bits,
                             kw_sz=kw_sz, ow=ow, stride=stride, cw=cw)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            # Element-addressed row (block size 1 on the row axis):
            # row = n*Hp + oh*stride + kh — the implicit im2col index.
            pl.BlockSpec(
                (1, wp, c32),
                lambda i, j, k: ((i // oh) * hp + (i % oh) * stride + k, 0, 0),
            ),
            pl.BlockSpec((1, w_bits, kw_sz, cw, bo),
                         lambda i, j, k: (k, 0, 0, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, ow, bo), lambda i, j, k: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n * oh, ow, op), jnp.int32),
        scratch_shapes=[pltpu.VMEM((a_bits, wp, cw), jnp.uint32)],
        interpret=interpret,
        name=kernel_name(kh, kw_sz, stride),   # its op's name in a trace
    )(q, pw)
    if o_pad:
        out = out[..., :o]
    return out.reshape(n, oh, ow, o)
