"""PIM-style quantized layers — the paper's technique as drop-in modules.

``PIMLinear``/``PIMConv2D`` are what the framework exposes to model code:
any dense projection (CNN conv, transformer QKVO/FFN) can be switched to the
paper's bit-serial execution by config (`PIMQuantConfig` on an arch config).

Execution modes:
  * training      -> fake-quant with STE (QAT; beyond-paper, see DESIGN.md)
  * inference     -> Eq. 1 bit-serial matmul on the selected backend
                     ("popcount" | "mxu-plane" | "int-direct" | "pallas")

Weights may be float master arrays (quantized per call) or prepacked
:class:`PackedWeight`/:class:`PackedConvWeight` pytrees built once at
deployment by :func:`prepack_linear`/:func:`prepack_conv2d` — the paper's
"program subarrays once" step. See DESIGN.md §3.

Conv2D lowers to the same integer matmul two ways: a materialized im2col
patch matrix (cheap for 1x1 kernels, small maps, and inputs with few
channels), or the fused implicit-im2col Pallas kernel that walks patch
offsets inside the grid and never builds the (N*OH*OW, KH*KW*C) matrix —
exactly how the paper slides the weight buffer over resident input planes
(Fig. 8). The choice is a shape-dispatch heuristic
(:func:`fuse_conv_heuristic`) or forced via ``conv_mode``.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses

import jax
import jax.numpy as jnp

from .bitserial import int_matmul_prepacked, quantized_matmul
from .packed import PackedConvWeight, PackedWeight, prepack, prepack_conv
from .quantize import affine_correction, calibrate_minmax, fake_quant, quantize


@dataclasses.dataclass(frozen=True)
class PIMQuantConfig:
    w_bits: int = 8
    a_bits: int = 8
    backend: str = "int-direct"  # cheapest exact backend; "popcount"/"pallas" = paper dataflow
    enabled: bool = True

    @property
    def tag(self) -> str:
        return f"<{self.w_bits}:{self.a_bits}>"


def _constrain_weight(w: jax.Array, role: str) -> jax.Array:
    """Pin a 2D weight's at-use sharding so GSPMD gathers the FSDP shards
    instead of partial-reducing the (much larger) activation outputs.

    role "io": (d_in, d_out) — d_in is FSDP-sharded at rest: gather it;
               keep d_out on the TP axis (output stays head/hidden-sharded).
    role "tp_in": (d_hidden, d_out) — d_hidden stays TP-sharded (the
               contraction's partial-sum all-reduce is the inherent TP
               collective); the FSDP axis on d_out gathers.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as sh

    mesh = sh.get_mesh()
    if mesh is None or w.ndim != 2 or "model" not in mesh.axis_names:
        return w
    tp = sh.axis_size(mesh, "model")
    if role == "tp_in":
        spec = P("model" if w.shape[0] % tp == 0 else None, None)
    else:
        spec = P(None, "model" if w.shape[1] % tp == 0 else None)
    return sh.constrain(w, spec)


def prepack_linear(w: jax.Array, cfg: PIMQuantConfig) -> PackedWeight:
    """Quantize + pack a (K, N) weight once for repeated ``pim_linear`` calls."""
    return prepack(w, cfg.w_bits)


def prepack_conv2d(w: jax.Array, cfg: PIMQuantConfig) -> PackedConvWeight:
    """Quantize + pack a (KH, KW, C, O) conv weight once for ``pim_conv2d``."""
    return prepack_conv(w, cfg.w_bits)


def pim_linear(
    x: jax.Array,
    w: jax.Array | PackedWeight,
    b: jax.Array | None = None,
    cfg: PIMQuantConfig | None = None,
    train: bool = False,
    role: str = "io",
) -> jax.Array:
    """y = x @ w (+ b) through the paper's bit-serial pipeline.

    ``x``: (..., K) float; ``w``: (K, N) float master weights or a
    :class:`PackedWeight` prepacked at deployment. ``role`` picks the at-use
    sharding policy (see ``_constrain_weight``; prepacked weights keep the
    sharding they were packed with).
    """
    packed = isinstance(w, PackedWeight)
    if not packed:
        w = _constrain_weight(w, role)
    if cfg is None or not cfg.enabled:
        wf = w.to_float() if packed else w
        y = x @ wf.astype(x.dtype)
    elif train:
        # QAT: quantization error in the forward pass, STE gradients.
        # Prepacked weights are an inference artifact; train on the masters.
        xq = fake_quant(x, cfg.a_bits)
        wq = fake_quant(w.to_float() if packed else w, cfg.w_bits)
        y = xq @ wq.astype(xq.dtype)
    else:
        y = quantized_matmul(
            x, w, a_bits=cfg.a_bits, w_bits=cfg.w_bits, backend=cfg.backend
        ).astype(x.dtype)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def _im2col(x: jax.Array, kh: int, kw: int, stride: int, padding: int
            ) -> tuple[jax.Array, int, int]:
    """NHWC -> (N*OH*OW, KH*KW*C) patches (float x or integer codes).

    Built from KH*KW slices of the padded map, one per tap, concatenated
    on the channel axis in (kh, kw, c) order: a copy at memory bandwidth,
    where indexing by patch offsets lowers to a gather. A strided conv
    first splits the map into its stride x stride phases (one strided
    slice each), so that each tap is a unit-stride slice of one phase.
    """
    n, h, w, c = x.shape
    x = jnp.pad(x, ((0, 0), (padding, padding), (padding, padding), (0, 0)))
    oh = (h + 2 * padding - kh) // stride + 1
    ow = (w + 2 * padding - kw) // stride + 1
    phases = {(a, b): jax.lax.slice(x, (0, a, b, 0), x.shape,
                                    (1, stride, stride, 1))
              for a in range(min(kh, stride)) for b in range(min(kw, stride))}
    taps = [jax.lax.slice(phases[i % stride, j % stride],
                          (0, i // stride, j // stride, 0),
                          (n, i // stride + oh, j // stride + ow, c))
            for i in range(kh) for j in range(kw)]
    patches = jnp.concatenate(taps, axis=-1)
    return patches.reshape(n * oh * ow, kh * kw * c), oh, ow


# Fused-conv dispatch: below this patch-matrix size the materialized path's
# single big GEMM beats the fused kernel's per-row streaming.
_FUSE_MIN_BYTES = 4 << 20


def sparse_channel_words(kh: int, kw: int, c: int) -> bool:
    """Does the fused kernel issue more than twice the packed words of the
    im2col GEMM? It packs only channels into a 32-bit word, KH*KW*ceil(C/32)
    words per output and plane pair, where the GEMM packs the whole patch
    into ceil(KH*KW*C/32). Never true for C >= 32."""
    return kh * kw * -(-c // 32) > 2 * -(-(kh * kw * c) // 32)


def fuse_conv_heuristic(n: int, oh: int, ow: int, kh: int, kw: int, c: int,
                        backend: str) -> bool:
    """Should ``pim_conv2d`` take the fused implicit-im2col path?

    Fused pays when (a) the backend runs the paper dataflow on the Pallas
    kernels (the fused kernel *is* that dataflow; the XLA backends have no
    kernel to fuse into), (b) the materialized (N*OH*OW, KH*KW*C) patch
    matrix is a real HBM blow-up — 1x1 kernels materialize for free (the
    patch matrix is a reshape) and tiny maps fit in cache anyway — and (c)
    its channel words are mostly full (:func:`sparse_channel_words`). An
    input with few channels (a first conv on RGB: ResNet's 7x7 stem, 49
    words against the im2col GEMM's 5) leaves most of each fused word
    empty, and past twice the im2col count the fused path stops paying.
    """
    if backend != "pallas":
        return False
    if kh == kw == 1:
        return False
    if sparse_channel_words(kh, kw, c):
        return False
    return 4 * n * oh * ow * kh * kw * c >= _FUSE_MIN_BYTES


# Conv lowerings counted while a forward is traced (``conv_lowering_log``).
_LOWERING_LOGS: list = []
LOWERINGS = ("fused", "im2col", "pointwise")


@contextlib.contextmanager
def conv_lowering_log():
    """Count the quantized convs ``pim_conv2d`` lowers inside the block, by
    path: ``fused``, ``im2col`` (KH*KW > 1) and ``pointwise`` (1x1).

    Counted on the host while a conv is traced, so a jitted forward counts
    on the call that traces it and not on later calls; nothing reaches the
    device. Yields a ``collections.Counter``.
    """
    log = collections.Counter()
    _LOWERING_LOGS.append(log)
    try:
        yield log
    finally:
        _LOWERING_LOGS.remove(log)


def pim_conv2d(
    x: jax.Array,          # NHWC
    w: jax.Array | PackedConvWeight,   # (KH, KW, C, O) or prepacked
    b: jax.Array | None = None,
    stride: int = 1,
    padding: int = 0,
    cfg: PIMQuantConfig | None = None,
    train: bool = False,
    conv_mode: str = "auto",           # "auto" | "fused" | "im2col"
) -> jax.Array:
    packed = isinstance(w, PackedConvWeight)
    kh, kw, c, o = w.kernel_shape if packed else w.shape
    if cfg is None or not cfg.enabled:
        wf = w.to_float() if packed else w
        y = jax.lax.conv_general_dilated(
            x, wf.astype(x.dtype), (stride, stride), [(padding, padding)] * 2,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return y + b.astype(y.dtype) if b is not None else y
    if train:
        wf = w.to_float() if packed else w
        cols, oh, ow = _im2col(x, kh, kw, stride, padding)
        y = pim_linear(cols, wf.reshape(kh * kw * c, o), b, cfg, train=True)
        return y.reshape(x.shape[0], oh, ow, o)

    # -- quantized inference: one calibrate+quantize, two lowering paths ----
    from repro.distributed import sharding as _sh

    # Under the CNN serving layout (VisionEngine on a mesh) the bank
    # redistribution between two O-split convs happens here, on the input
    # map — never on the patch matrix (DESIGN.md §6); identity otherwise.
    x = _sh.constrain_cnn_conv_input(x)
    n = x.shape[0]
    # Calibrate on the REAL activations, not the padded tensor: calibrating
    # on the padded map stretched a strictly-positive range (post-ReLU
    # features) down to the padding zeros, wasting code space on values
    # that never occur. Padding enters as the zero CODE — which contributes
    # nothing to P or Sa — and the affine correction below charges padded
    # taps exactly zero, so border semantics stay exact for any input range.
    aq = calibrate_minmax(x, cfg.a_bits)
    qx = jnp.pad(quantize(x, aq),
                 ((0, 0), (padding, padding), (padding, padding), (0, 0)))
    hp, wp = qx.shape[1], qx.shape[2]
    oh = (hp - kh) // stride + 1
    ow = (wp - kw) // stride + 1
    if not packed:
        # At-use sharding for float masters (as the old im2col->pim_linear
        # path applied); prepacked weights keep their packing-time layout.
        w = _constrain_weight(w.reshape(kh * kw * c, o), "io").reshape(w.shape)
        w = prepack_conv(w, cfg.w_bits)

    if conv_mode not in ("auto", "fused", "im2col"):
        raise ValueError(f"conv_mode {conv_mode!r}: want auto|fused|im2col")
    # A conv-level TuneDecision (repro.pim.autotune) resolves "auto" and
    # supplies the fused O-block; an explicit conv_mode still wins, and the
    # im2col matmul's backend rides on w.mat.tune inside
    # int_matmul_prepacked — tuning never changes bits, only dispatch.
    tune = w.tune
    if conv_mode == "auto" and tune is not None and tune.conv_mode:
        conv_mode = tune.conv_mode
    fused = {"fused": True, "im2col": False}.get(
        conv_mode, fuse_conv_heuristic(n, oh, ow, kh, kw, c, cfg.backend))
    if fused:
        from repro.kernels import ops as _kops

        p = _kops.conv2d_bitserial(qx, w.fused_planes, a_bits=cfg.a_bits,
                                   stride=stride,
                                   bo=tune.bo if tune is not None else None)
    else:
        qcols, _, _ = _im2col(qx, kh, kw, stride, 0)
        # A KxK conv's GEMM takes the fused kernel's name, so the trace
        # shows each conv under one name whichever path it takes.
        from repro.kernels.conv2d_fused import kernel_name

        name = "eq1_matmul" if kh == kw == 1 else kernel_name(kh, kw, stride)
        p = int_matmul_prepacked(qcols, w.mat, cfg.a_bits, cfg.backend,
                                 name=name)
        p = p.reshape(n, oh, ow, o)
    lowering = ("fused" if fused else "pointwise" if kh == kw == 1
                else "im2col")
    for log in _LOWERING_LOGS:
        log[lowering] += 1
    # Patch-wise activation code sums for the affine correction: a strided
    # box sum over the per-pixel channel sums — no patch matrix needed.
    sa = jax.lax.reduce_window(
        qx.sum(-1), jnp.int32(0), jax.lax.add, (1, kh, kw),
        (1, stride, stride), "VALID")
    if padding:
        # Padded taps contribute exactly zero to the dot product, so near
        # the border the correction's weight-code sum Sw and contraction
        # length K shrink per patch: a validity-mask pass computes both —
        # one (1, Hp, Wp, 1) x (KH, KW, 1, O) conv against the per-tap
        # channel-summed weight codes and one box count, both trivial next
        # to the conv itself. Interior patches recover col_sums / K*K*C.
        mask = jnp.pad(jnp.ones((1, x.shape[1], x.shape[2], 1), jnp.float32),
                       ((0, 0), (padding, padding), (padding, padding),
                        (0, 0)))
        wsum = w.mat.codes.reshape(kh, kw, c, o).sum(2)          # (KH, KW, O)
        # HIGHEST: the integer code sums (< 2^24) must stay exact — a TPU
        # conv at default precision rounds them to bf16.
        sw = jax.lax.conv_general_dilated(
            mask, wsum[:, :, None, :].astype(jnp.float32),
            (stride, stride), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST)                 # (1,OH,OW,O)
        k_real = c * jax.lax.reduce_window(
            mask[..., 0], 0.0, jax.lax.add, (1, kh, kw),
            (1, stride, stride), "VALID")[..., None]             # (1,OH,OW,1)
    else:
        sw, k_real = w.mat.col_sums, kh * kw * c
    y = affine_correction(p, sa[..., None], sw, k_real,
                          aq, w.wq).astype(x.dtype)
    # Pin the output to the bank split (O on "model") so each shard computes
    # exactly its own output channels; identity off the CNN serving layout.
    y = _sh.constrain_cnn_conv_output(y)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y
