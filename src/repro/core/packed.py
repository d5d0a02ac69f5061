"""Prepacked weights — the paper's "program subarrays once" step as a pytree.

On NAND-SPIN, weights are written into the subarrays exactly once at
deployment; every inference afterwards only streams activations. The TPU
analog is :class:`PackedWeight`: the weight's integer codes, its packed
uint32 bit-planes (the subarray image), the Eq. 2 quantization parameters
and the precomputed column sums of the affine correction, bundled as one
registered pytree so it jits, shards and scans like any parameter leaf.

``prepack`` builds it for a (K, N) matmul weight; ``prepack_conv`` for a
(KH, KW, C, O) convolution weight, which additionally carries the
channel-packed per-kernel-row planes consumed by the fused implicit-im2col
kernel (:mod:`repro.kernels.conv2d_fused`). See DESIGN.md §3.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from . import bitslice
from .quantize import QuantParams, calibrate_minmax, dequantize, quantize


@dataclasses.dataclass(frozen=True)
class TuneDecision:
    """Autotuner verdict carried as static metadata on a packed weight.

    ``backend`` overrides the config's Eq. 1 execution strategy at use
    time; ``bm``/``bn``/``bkw`` are tile *requests* for the Pallas matmul
    kernel (legalized against the actual operand shapes by
    ``kernels.ops.matmul_tiles``, so a decision can never produce an
    illegal BlockSpec); ``conv_mode``/``bo`` steer ``pim_conv2d``'s
    lowering path and fused O-block. ``None`` fields defer to the existing
    planner/heuristic defaults — attaching ``TuneDecision()`` with only a
    backend changes dispatch and nothing else.

    Frozen + hashable: it rides the static (aux-data) side of the pytree,
    so attaching or changing it never alters leaf buffers, shardings or
    checkpoint layouts — only which compiled program consumes them.
    """

    backend: str = "popcount"
    bm: int | None = None
    bn: int | None = None
    bkw: int | None = None
    conv_mode: str | None = None   # "fused" | "im2col" (conv weights only)
    bo: int | None = None          # fused-conv O block (conv weights only)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PackedWeight:
    """A (K, N) weight quantized and bit-plane-packed once.

    codes     (K, N) int32   — Eq. 2 codes (the multi-bit matrix)
    planes    (bits, N, KW) uint32 — K-packed planes of ``codes.T`` (the
              subarray image the popcount/pallas backends AND against)
    col_sums  (N,) int32     — sum_k codes[k, n], precomputed for the affine
              correction (Sw in quantize.py's dot-product algebra)
    wq        QuantParams    — scale/qmin/bits of the weight quantization
    tune      TuneDecision | None — static per-weight autotuner verdict
              (repro.pim.autotune); None keeps the config-selected backend
              and planner-default tiles
    """

    codes: jax.Array
    planes: jax.Array
    col_sums: jax.Array
    wq: QuantParams
    tune: TuneDecision | None = dataclasses.field(
        metadata=dict(static=True), default=None)

    @property
    def bits(self) -> int:
        return self.wq.bits

    @property
    def shape(self) -> tuple:
        return self.codes.shape

    def to_float(self) -> jax.Array:
        """Dequantized master weight (fallback for non-quantized paths).

        Works on stacked prepacks too (scan reps and/or expert banks): every
        leading axis beyond the (K, N) matrix carries its own ``wq`` entry,
        so dequantization vmaps over the stack."""
        fn = dequantize
        for _ in range(self.codes.ndim - 2):
            fn = jax.vmap(fn)
        return fn(self.codes, self.wq)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PackedConvWeight:
    """A (KH, KW, C, O) conv weight prepacked for both conv lowering paths.

    mat          PackedWeight over the (KH*KW*C, O) im2col matrix — drives
                 the materialized path and the affine correction.
    fused_planes (KH, bits, KW, CW, O) uint32 — channel-packed planes per
                 kernel row, the layout the fused implicit-im2col kernel
                 streams one (kh) slab at a time (output channels last, on
                 the kernel's lanes).
    """

    mat: PackedWeight
    fused_planes: jax.Array
    kernel_shape: tuple = dataclasses.field(metadata=dict(static=True),
                                            default=(1, 1, 1, 1))
    tune: TuneDecision | None = dataclasses.field(
        metadata=dict(static=True), default=None)

    @property
    def bits(self) -> int:
        return self.mat.bits

    @property
    def wq(self) -> QuantParams:
        return self.mat.wq

    def to_float(self) -> jax.Array:
        return self.mat.to_float().reshape(self.kernel_shape)


def prepack(w: jax.Array, w_bits: int, mesh=None, axis: str = "model",
            split: str = "n") -> PackedWeight:
    """Quantize + bit-slice + lane-pack a (K, N) weight once.

    Everything here is jnp, so ``jax.vmap(prepack)`` prepacks scan-stacked
    (R, K, N) parameter leaves (the LM layer stack) in one shot — and
    ``jax.vmap`` again for MoE expert banks: an (E, K, N) expert stack
    packs to codes (E, K, N), planes (E, bits, N, KW), col_sums (E, N)
    with per-expert ``wq`` leaves of shape (E,), the layout
    ``shard_packed(split="e")`` deals out expert-wise (experts = the
    paper's chips) and ``moe_ffn`` contracts per expert under ``vmap``.

    ``mesh``: distribute the packed planes across a device mesh right after
    packing (the paper's banks each receiving their weight columns) — see
    :func:`shard_packed` for the ``axis``/``split`` semantics. ``mesh`` is
    an eager-only convenience (``device_put`` cannot run under a trace):
    under ``vmap``/``jit`` leave it None and call :func:`shard_packed` on
    the stacked result instead — it handles the leading reps axis.
    """
    wq = calibrate_minmax(w, w_bits)
    codes = quantize(w, wq)
    planes = bitslice.slice_and_pack(codes.T, w_bits)  # (bits, N, KW)
    out = PackedWeight(codes=codes, planes=planes,
                       col_sums=codes.sum(0).astype(jnp.int32), wq=wq)
    if mesh is not None:
        out = shard_packed(out, mesh, axis=axis, split=split)
    return out


def shard_packed(pw: PackedWeight | PackedConvWeight, mesh,
                 axis: str = "model", split: str = "n"):
    """Distribute a :class:`PackedWeight`/:class:`PackedConvWeight` across a
    device mesh.

    ``split="n"`` — the paper's *bank* mapping: output columns are dealt
    out across ``axis`` (planes split on their N dim, along with codes and
    the correction ``col_sums``); each shard's matmul is complete for its
    columns, no reduction needed. For a conv weight this is the
    output-channel (O) split: the im2col ``mat`` splits on its N dim AND
    the ``fused_planes`` on their O dim — both lowering paths land the same
    output channels on the same shard.

    ``split="k"`` — the *subarray-group* mapping: the packed contraction
    words split across ``axis`` (planes on KW, codes on K); each shard
    produces int32 partial sums that must reduce via
    ``distributed.collectives.exact_psum`` (see
    ``kernels.bitserial_matmul.bitserial_matmul_sharded``). Conv weights
    only support the bank split: their contraction dim (KH*KW*C) has no
    aligned per-kernel-row decomposition across shards.

    ``split="e"`` — the *chip* mapping for expert-stacked prepacks (a
    ``jax.vmap(prepack)`` over an (E, K, N) expert bank): whole experts are
    dealt out across ``axis``, every field — codes, planes, col_sums and
    the per-expert ``wq`` leaves — splitting on its leading E dim. Each
    shard holds complete subarray images for its experts, so the per-expert
    GEMMs run collective-free and only the token dispatch/combine
    communicates (expert parallelism; DESIGN.md §11). Requires a stacked
    prepack (codes ndim >= 3); scan-stacked expert banks ((R, E, K, N))
    split the E dim one position in.

    Dims that do not divide the axis stay replicated via the sharding-rule
    guard — which warns once per drop, so a "bank-sharded" deployment that
    actually replicated (non-divisible N or KW) is visible. Scan-stacked
    prepacks (leading reps axis) shard the same logical dims shifted by one.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed.sharding import _guard

    if split not in ("n", "k", "e"):
        raise ValueError(
            f"split {split!r}: want 'n' (banks) | 'k' (subarrays) | "
            "'e' (expert chips)")
    if isinstance(pw, PackedConvWeight):
        if split != "n":
            raise ValueError(
                "PackedConvWeight shards on the bank (output-channel) "
                "mapping only; split='k' has no conv layout")
        fused_spec = _guard((None, None, None, None, axis),
                            pw.fused_planes.shape, mesh,
                            label="shard_packed:fused_planes")
        return PackedConvWeight(
            mat=shard_packed(pw.mat, mesh, axis=axis, split="n"),
            fused_planes=jax.device_put(
                pw.fused_planes, NamedSharding(mesh, fused_spec)),
            kernel_shape=pw.kernel_shape,
            tune=pw.tune,
        )

    def put(leaf, spec, field):
        stack = leaf.ndim - len(spec)          # 1 when vmap-prepacked
        spec = _guard((None,) * stack + tuple(spec), leaf.shape, mesh,
                      label=f"shard_packed:{field}")
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    if split == "e":
        if pw.codes.ndim < 3:
            raise ValueError(
                "split='e' needs an expert-stacked prepack "
                f"(codes ndim >= 3, got {pw.codes.ndim})")

        def put_e(leaf, rank, field):
            # Expert dim sits just above the per-expert logical rank; any
            # further leading dims (scan reps) stay replicated.
            pos = leaf.ndim - rank - 1
            spec = _guard((None,) * pos + (axis,) + (None,) * rank,
                          leaf.shape, mesh, label=f"shard_packed:{field}")
            return jax.device_put(leaf, NamedSharding(mesh, spec))

        return PackedWeight(
            codes=put_e(pw.codes, 2, "codes"),
            planes=put_e(pw.planes, 3, "planes"),
            col_sums=put_e(pw.col_sums, 1, "col_sums"),
            wq=jax.tree.map(lambda l: put_e(l, 0, "wq"), pw.wq),
            tune=pw.tune,
        )

    k_ax, n_ax = (axis, None) if split == "k" else (None, axis)
    return PackedWeight(
        codes=put(pw.codes, (k_ax, n_ax), "codes"),
        planes=put(pw.planes, (None, n_ax, k_ax), "planes"),
        col_sums=put(pw.col_sums, (n_ax,), "col_sums"),
        wq=jax.tree.map(
            lambda l: jax.device_put(l, NamedSharding(mesh, P())), pw.wq),
        tune=pw.tune,
    )


def repack_codes(pw: PackedWeight, codes: jax.Array) -> PackedWeight:
    """Re-program a packed weight's subarrays with new integer codes.

    Planes are re-derived from ``codes``; the digital periphery state
    (``col_sums``, ``wq``) is kept as-is. This is the primitive behind
    fault injection and spare-column repair (repro.pim.faults): the array
    image changes, the periphery's golden Sw register does not.
    """
    return PackedWeight(codes=codes,
                        planes=bitslice.slice_and_pack(codes.T, pw.bits),
                        col_sums=pw.col_sums, wq=pw.wq, tune=pw.tune)


def fused_conv_planes(codes: jax.Array, bits: int) -> jax.Array:
    """(KH, KW, C, O) conv codes -> the fused kernel's (KH, bits, KW, CW, O)
    planes: per kernel row, channels packed into words, O last."""
    planes = bitslice.slice_and_pack(codes.transpose(0, 1, 3, 2), bits)
    return planes.transpose(1, 0, 2, 4, 3)       # from (bits, KH, KW, O, CW)


def repack_conv_codes(pcw: PackedConvWeight, flat_codes: jax.Array
                      ) -> PackedConvWeight:
    """Conv analog of :func:`repack_codes`: new (KH*KW*C, O) im2col codes,
    both lowering layouts rebuilt so they describe the same device state."""
    return PackedConvWeight(mat=repack_codes(pcw.mat, flat_codes),
                            fused_planes=fused_conv_planes(
                                flat_codes.reshape(pcw.kernel_shape),
                                pcw.bits),
                            kernel_shape=pcw.kernel_shape, tune=pcw.tune)


def prepack_conv(w: jax.Array, w_bits: int) -> PackedConvWeight:
    """Prepack a (KH, KW, C, O) conv weight for both lowering paths."""
    kh, kw, c, o = w.shape
    wq = calibrate_minmax(w, w_bits)
    codes = quantize(w, wq)                              # (KH, KW, C, O)
    flat = codes.reshape(kh * kw * c, o)                 # im2col order
    mat = PackedWeight(
        codes=flat,
        planes=bitslice.slice_and_pack(flat.T, w_bits),
        col_sums=flat.sum(0).astype(jnp.int32),
        wq=wq,
    )
    return PackedConvWeight(mat=mat, fused_planes=fused_conv_planes(codes,
                                                                    w_bits),
                            kernel_shape=(kh, kw, c, o))
