"""Public jit'd wrappers for the Pallas kernels.

On a TPU backend the kernels run compiled; on the CPU backend (the test
suite) they run in ``interpret=True`` mode, which executes the kernel body
in Python with identical semantics. No other backend falls back to the
interpreter: there the kernels compile or fail.

Every block these wrappers hand to a kernel is legal for Mosaic: a block's
last dim is the whole array dim or a multiple of 128, its second-to-last
the whole dim or a multiple of 8. Where no such block divides a dim, the
operands are zero-padded up to a multiple of the block — zero codes and
zero planes add nothing to a popcount — and the result is sliced back.

``bitserial_matmul`` is ONE kernel launch when the weight planes arrive
prepacked (``pw=``, from :class:`repro.core.packed.PackedWeight`): the
activation codes are bit-sliced and lane-packed inside the matmul kernel's
K-tile loop, so no packed plane ever round-trips through HBM. With raw
weight codes it is two launches (weight pack + fused matmul) — still down
from the historical three (pack A, pack W, matmul).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.core import bitslice
from repro.core.mapping import plan_matmul

from . import bitplane_pack as _pack
from . import bitserial_matmul as _bsm
from . import conv2d_fused as _conv


def _interpret_default() -> bool:
    return jax.default_backend() == "cpu"


# Block caps that keep every kernel's VMEM working set (double-buffered
# in/out blocks plus scratch) under Mosaic's default scoped limit.
_MAX_BM = 128
_MAX_BN = 256
_MAX_BKW = 128


def _legal_block(dim: int, want: int, align: int) -> int:
    """A Mosaic-legal block for ``dim``: all of it, or a multiple of
    ``align`` near ``want``. A divisor of ``dim`` down to a quarter of the
    request is preferred; otherwise the caller pads ``dim`` up (see
    :func:`padded`)."""
    b = max(align, want // align * align)
    if b >= dim:
        return dim
    for cand in range(b, max(align, b // 4) - 1, -align):
        if dim % cand == 0:
            return cand
    return b


def padded(dim: int, block: int) -> int:
    """``dim`` rounded up to a whole number of ``block``s."""
    return -(-dim // block) * block


def pack_planes(q: jax.Array, bits: int, interpret: bool | None = None) -> jax.Array:
    """Integer codes (M, K) -> packed planes (bits, M, ceil32(K)/32) uint32."""
    if interpret is None:
        interpret = _interpret_default()
    m, k = q.shape
    kw = bitslice.pad_to_lanes(k) // 32
    bm = _legal_block(m, _MAX_BM, 8)
    bkw = _legal_block(kw, _MAX_BKW, 128)
    q = jnp.pad(q, ((0, padded(m, bm) - m), (0, padded(kw, bkw) * 32 - k)))
    return _pack.bitplane_pack(q, bits=bits, bm=bm, bkw=bkw,
                               interpret=interpret)[:, :m, :kw]


def matmul_tiles(m: int, n: int, kw: int, a_bits: int, w_bits: int,
                 bm: int | None = None, bn: int | None = None,
                 bkw: int | None = None) -> tuple:
    """Legal (bm, bn, bkw) blocks for an (M, N, KW-words) bit-serial matmul.

    ``bm``/``bn``/``bkw`` are *requests* — autotuner overrides
    (:class:`repro.core.packed.TuneDecision`) or caller choices; ``None``
    falls back to the :func:`plan_matmul` planner. Every request is
    legalized by :func:`_legal_block` (bm on sublanes: all of M or a
    multiple of 8; bn and bkw on lanes: all of the dim or a multiple of
    128) and capped to bound VMEM, so any request yields blocks Mosaic
    accepts. The operands are padded to :func:`padded` multiples of them.
    """
    plan = plan_matmul(m, kw * 32, n, a_bits, w_bits)
    return (_legal_block(m, min(bm or plan.bm, _MAX_BM), 8),
            _legal_block(n, min(bn or plan.bn, _MAX_BN), 128),
            _legal_block(kw, min(bkw or plan.bk_words, _MAX_BKW), 128))


def bitserial_matmul(
    qa: jax.Array,            # (M, K) int codes
    qw: jax.Array | None = None,  # (K, N) int codes (omit when pw given)
    *,
    a_bits: int,
    w_bits: int,
    pw: jax.Array | None = None,  # (w_bits, N, ceil32(K)/32) prepacked planes
    bm: int | None = None,
    bn: int | None = None,
    bkw: int | None = None,
    interpret: bool | None = None,
    name: str = "eq1_matmul",
) -> jax.Array:
    """Eq. 1 bit-serial integer matmul via the Pallas kernels -> (M, N) i32.

    Activation packing is fused into the matmul kernel; pass ``pw`` (the
    prepacked weight planes of a ``PackedWeight``) to make the whole product
    a single ``pallas_call``. ``bm``/``bn``/``bkw`` override the planner's
    tile choices (see :func:`matmul_tiles`); the autotuner threads its
    decisions through here. ``name`` names the kernel in a trace.
    """
    if interpret is None:
        interpret = _interpret_default()
    m, k = qa.shape
    if pw is None:
        if qw is None:
            raise ValueError("need either qw codes or pw prepacked planes")
        pw = pack_planes(qw.T, w_bits, interpret)
    n = pw.shape[1]
    kw = pw.shape[-1]
    if k > kw * 32:
        raise ValueError(
            f"activation K={k} exceeds packed weight K={kw * 32} words*32")
    bm, bn, bkw = matmul_tiles(m, n, kw, a_bits, w_bits, bm, bn, bkw)
    kwp = padded(kw, bkw)
    qa = jnp.pad(qa, ((0, padded(m, bm) - m), (0, kwp * 32 - k)))
    pw = jnp.pad(pw, ((0, 0), (0, padded(n, bn) - n), (0, kwp - kw)))
    return _bsm.bitserial_matmul_fused(
        qa, pw, a_bits=a_bits, w_bits=w_bits, bm=bm, bn=bn, bkw=bkw,
        interpret=interpret, name=name,
    )[:m, :n]


def conv2d_bitserial(
    qx: jax.Array,   # (N, Hp, Wp, C) int32 activation codes, spatially padded
    pw: jax.Array,   # (KH, w_bits, KW, CW, O) PackedConvWeight.fused_planes
    *,
    a_bits: int,
    stride: int = 1,
    bo: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Implicit-im2col bit-serial conv -> P (N, OH, OW, O) int32.

    Runs the fused kernel on the already-padded activation codes, which it
    packs along the channel axis in VMEM; neither the (N*OH*OW, KH*KW*C)
    patch matrix nor a packed activation plane is ever built in HBM.
    ``bo`` overrides the kernel's output-channel block (autotuner hook);
    None keeps the lane-width default.
    """
    if interpret is None:
        interpret = _interpret_default()
    n, hp, wp, c = qx.shape
    kh, _, kw_sz, cw, o = pw.shape
    # STT-MRAM read disturb: under an active fault scope each launch senses
    # a freshly disturbed view of the stored planes. Trace-time no-op (and
    # HLO-identical) when the scope is inactive.
    from repro.pim import faults as _faults

    if _faults.read_disturb_active():
        pw = _faults.disturb_fused_planes(pw, (kh, kw_sz, c, o))
    oh = (hp - kh) // stride + 1
    ow = (wp - kw_sz) // stride + 1
    # The kernel packs one input row at a time in VMEM, so no full-size
    # (a_bits, N, Hp, Wp, C) bit-plane broadcast ever exists — the XLA
    # slice_and_pack would allocate one as large as the im2col matrix
    # itself (see tests/test_fastpath.py jaxpr assertion) — and no packed
    # plane with a one-word minor dim is padded to 128 lanes in HBM.
    if c > 32 * cw:
        raise ValueError(f"{c} channels exceed weight words {cw} * 32")
    qx = jnp.pad(qx, ((0, 0), (0, 0), (0, 0), (0, 32 * cw - c)))
    kw_conv = {} if bo is None else {"bo": bo}
    return _conv.conv2d_bitserial_fused(
        qx.reshape(n * hp, wp, 32 * cw), pw, a_bits=a_bits, n=n, hp=hp,
        oh=oh, ow=ow, stride=stride, interpret=interpret, **kw_conv)

