"""The cached/fused inference fast path (DESIGN.md §3).

Covers the acceptance criteria of the prepack/fusion PR:
  * PackedWeight round-trips bit-exactly vs the int-direct oracle on every
    backend, including the single-launch fused Pallas kernel;
  * the fused implicit-im2col conv agrees with lax.conv_general_dilated
    (within quantization error) and with the materialized im2col path
    bit-exactly across stride/padding;
  * the fused conv never materializes the (N*OH*OW, KH*KW*C) patch matrix
    (jaxpr inspection);
  * repeated serving calls neither recompile nor re-quantize/re-pack the
    weights.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    PackedConvWeight,
    PackedWeight,
    PIMQuantConfig,
    fuse_conv_heuristic,
    pim_conv2d,
    pim_linear,
    prepack_conv2d,
    prepack_linear,
)
from repro.core.bitserial import int_matmul_direct, int_matmul_prepacked

ALL_BACKENDS = ("int-direct", "mxu-plane", "popcount", "pallas")


# ---------------------------------------------------------------------------
# PackedWeight matmul fast path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ALL_BACKENDS)
@pytest.mark.parametrize("ab,wb", [(8, 8), (4, 2)])
def test_packed_weight_bit_exact_vs_int_direct(backend, ab, wb):
    """P through the prepacked planes == the oracle on the same codes."""
    w = jax.random.normal(jax.random.PRNGKey(0), (96, 40))
    pk = prepack_linear(w, PIMQuantConfig(w_bits=wb, a_bits=ab))
    qa = jax.random.randint(jax.random.PRNGKey(1), (6, 96), 0, 2**ab)
    got = int_matmul_prepacked(qa, pk, ab, backend)
    want = int_matmul_direct(qa, pk.codes)
    assert got.dtype == jnp.int32
    assert (got == want).all()


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_packed_weight_matches_per_call_quantized_matmul(backend):
    """Deployment path (prepack once) == seed path (quantize every call)."""
    a = jax.random.normal(jax.random.PRNGKey(2), (5, 160))
    w = jax.random.normal(jax.random.PRNGKey(3), (160, 24))
    cfg = PIMQuantConfig(w_bits=8, a_bits=8, backend=backend)
    pk = prepack_linear(w, cfg)
    y_cached = pim_linear(a, pk, cfg=cfg)
    y_percall = pim_linear(a, w, cfg=cfg)
    assert jnp.array_equal(y_cached, y_percall)


def test_packed_weight_col_sums_and_roundtrip():
    w = jax.random.normal(jax.random.PRNGKey(4), (70, 12))
    pk = prepack_linear(w, PIMQuantConfig(w_bits=8, a_bits=8))
    assert (pk.col_sums == pk.codes.sum(0)).all()
    # dequantized master within one quantization step of the original
    assert float(jnp.abs(pk.to_float() - w).max()) <= float(pk.wq.scale)


def test_packed_weight_is_a_pytree():
    """PackedWeight jits, vmaps and scans like any parameter leaf."""
    w = jax.random.normal(jax.random.PRNGKey(5), (3, 64, 16))  # stacked reps
    from functools import partial

    from repro.core.packed import prepack

    pk = jax.vmap(partial(prepack, w_bits=8))(w)
    assert pk.codes.shape == (3, 64, 16)
    for r in range(3):
        ref = prepack(w[r], 8)
        sl = jax.tree.map(lambda l: l[r], pk)
        assert (sl.codes == ref.codes).all()
        assert (sl.planes == ref.planes).all()


# ---------------------------------------------------------------------------
# Fused implicit-im2col conv
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stride,padding", [(1, 0), (1, 1), (2, 1), (2, 0),
                                            (1, 2)])
def test_fused_conv_matches_materialized_bit_exact(stride, padding):
    """Same codes through both lowerings -> identical outputs, any geometry."""
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 9, 9, 33))  # odd C: pad
    w = jax.random.normal(jax.random.PRNGKey(7), (3, 3, 33, 16)) * 0.2
    cfg = PIMQuantConfig(w_bits=8, a_bits=8, backend="pallas")
    pk = prepack_conv2d(w, cfg)
    y_fused = pim_conv2d(x, pk, stride=stride, padding=padding, cfg=cfg,
                         conv_mode="fused")
    cfg_i = PIMQuantConfig(w_bits=8, a_bits=8, backend="int-direct")
    y_mat = pim_conv2d(x, pk, stride=stride, padding=padding, cfg=cfg_i,
                       conv_mode="im2col")
    assert jnp.array_equal(y_fused, y_mat)


@pytest.mark.parametrize("stride,padding", [(1, 1), (2, 1)])
def test_fused_conv_tracks_lax_conv(stride, padding):
    """8-bit fused conv stays within quantization error of the float conv."""
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 10, 10, 32))
    w = jax.random.normal(jax.random.PRNGKey(9), (3, 3, 32, 16)) * 0.1
    cfg = PIMQuantConfig(w_bits=8, a_bits=8, backend="pallas")
    y = pim_conv2d(x, prepack_conv2d(w, cfg), stride=stride, padding=padding,
                   cfg=cfg, conv_mode="fused")
    ref = jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(padding, padding)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    assert y.shape == ref.shape
    scale = float(jnp.abs(ref).max())
    assert float(jnp.abs(y - ref).max()) <= 0.05 * scale + 1e-3


def _jaxpr_avals(jaxpr):
    """All intermediate avals, recursing into sub-jaxprs (pjit/scan/pallas)."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            if hasattr(v, "aval") and hasattr(v.aval, "shape"):
                yield v.aval
        for val in eqn.params.values():
            inner = getattr(val, "jaxpr", None)
            if inner is not None:
                yield from _jaxpr_avals(inner)


def test_fused_conv_never_materializes_patch_matrix():
    """No intermediate anywhere in the jaxpr is as large as the im2col
    matrix — the defining property of the implicit-im2col kernel."""
    n, h, c, o, kk, pad = 2, 16, 32, 16, 3, 1
    x = jax.random.normal(jax.random.PRNGKey(10), (n, h, h, c))
    w = jax.random.normal(jax.random.PRNGKey(11), (kk, kk, c, o)) * 0.1
    cfg = PIMQuantConfig(w_bits=8, a_bits=8, backend="pallas")
    pk = prepack_conv2d(w, cfg)
    oh = h + 2 * pad - kk + 1
    im2col_elems = n * oh * oh * kk * kk * c

    fused = jax.make_jaxpr(lambda xx: pim_conv2d(
        xx, pk, stride=1, padding=pad, cfg=cfg, conv_mode="fused"))(x)
    big = [a for a in _jaxpr_avals(fused.jaxpr)
           if int(np.prod(a.shape)) >= im2col_elems]
    assert not big, f"fused path materialized {[a.shape for a in big]}"

    # positive control: the materialized path DOES build the patch matrix
    cfg_i = PIMQuantConfig(w_bits=8, a_bits=8, backend="int-direct")
    mat = jax.make_jaxpr(lambda xx: pim_conv2d(
        xx, pk, stride=1, padding=pad, cfg=cfg_i, conv_mode="im2col"))(x)
    assert any(int(np.prod(a.shape)) >= im2col_elems
               for a in _jaxpr_avals(mat.jaxpr))


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("stride,padding", [(2, 1), (2, 2), (1, 2)])
def test_fused_conv_stride2_nonsquare_odd_width(bits, stride, padding):
    """Fused == materialized bit-exactly on non-square, odd-width inputs
    with stride 2 and padding > 0 (the fastpath suite above only walked
    stride-1 geometries), across the paper's <2:2>/<4:4>/<8:8> sweep."""
    x = jax.random.normal(jax.random.PRNGKey(20), (2, 9, 13, 5))
    w = jax.random.normal(jax.random.PRNGKey(21), (3, 3, 5, 8)) * 0.2
    cfg_f = PIMQuantConfig(w_bits=bits, a_bits=bits, backend="pallas")
    pk = prepack_conv2d(w, cfg_f)
    y_fused = pim_conv2d(x, pk, stride=stride, padding=padding, cfg=cfg_f,
                         conv_mode="fused")
    cfg_i = PIMQuantConfig(w_bits=bits, a_bits=bits, backend="int-direct")
    y_mat = pim_conv2d(x, pk, stride=stride, padding=padding, cfg=cfg_i,
                       conv_mode="im2col")
    assert y_fused.shape == y_mat.shape
    assert jnp.array_equal(y_fused, y_mat)


def test_fused_conv_odd_o_pads_not_degenerates():
    """Regression: prime O used to shrink the output block to bo=1 (an
    O-sized grid of tiny kernels). Now O pads up to the requested block and
    the result is sliced — same bits, bounded grid. O sits on lanes, so a
    block is all of O or a multiple of 128."""
    from repro.kernels.conv2d_fused import _pad_o_blocks

    # prime O with the default block: one padded 128-block step, not 131.
    assert _pad_o_blocks(131, 128) == (128, 125)
    assert _pad_o_blocks(67, 32) == (67, 0)      # one lane group: one tile
    assert _pad_o_blocks(65, 128) == (65, 0)     # O < block: single tile
    assert _pad_o_blocks(128, 128) == (128, 0)   # exact fit: no padding
    assert _pad_o_blocks(300, 32) == (128, 84)   # block rounds up to lanes
    for o, bo in [(131, 128), (67, 32), (193, 128), (300, 32)]:
        b, pad = _pad_o_blocks(o, bo)
        assert (o + pad) % b == 0
        assert b == o + pad or b % 128 == 0       # Mosaic-legal lane block
        assert (o + pad) // b <= -(-o // b)      # never more tiles than ceil

    x = jax.random.normal(jax.random.PRNGKey(22), (1, 6, 6, 8))
    w = jax.random.normal(jax.random.PRNGKey(23), (3, 3, 8, 131)) * 0.2
    cfg_f = PIMQuantConfig(w_bits=4, a_bits=4, backend="pallas")
    pk = prepack_conv2d(w, cfg_f)
    y_fused = pim_conv2d(x, pk, stride=1, padding=1, cfg=cfg_f,
                         conv_mode="fused")
    cfg_i = PIMQuantConfig(w_bits=4, a_bits=4, backend="int-direct")
    y_mat = pim_conv2d(x, pk, stride=1, padding=1, cfg=cfg_i,
                       conv_mode="im2col")
    assert y_fused.shape == (1, 6, 6, 131)
    assert jnp.array_equal(y_fused, y_mat)


def test_conv_activation_calibration_ignores_padding():
    """Regression: activation quantization used to calibrate on the padded
    tensor, so a strictly-positive input range (post-ReLU features) was
    stretched down to the padding zeros — wasted code space, inflated
    error. Calibrating on the real input must beat the old behavior.

    8-bit weights with 4-bit activations, so the activation calibration's
    error sets the max (at 4-bit weights their error ties the two)."""
    key = jax.random.PRNGKey(24)
    # post-ReLU-like features in [2, 5]: zero is far outside the range
    x = jax.random.uniform(key, (2, 8, 8, 16), minval=2.0, maxval=5.0)
    w = jax.random.normal(jax.random.PRNGKey(25), (3, 3, 16, 8)) * 0.1
    cfg = PIMQuantConfig(w_bits=8, a_bits=4, backend="int-direct")
    ref = jax.lax.conv_general_dilated(
        x, w, (1, 1), [(1, 1)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    y_new = pim_conv2d(x, w, stride=1, padding=1, cfg=cfg)
    # Old behavior, reconstructed: pre-pad the input so calibration sees the
    # zeros (exactly what calibrate_minmax(xp) did before the fix).
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    y_old = pim_conv2d(xp, w, stride=1, padding=0, cfg=cfg)
    assert y_new.shape == y_old.shape == ref.shape
    err_new = float(jnp.abs(y_new - ref).max())
    err_old = float(jnp.abs(y_old - ref).max())
    assert err_new < err_old, (err_new, err_old)


def test_unquantized_conv_bias_preserves_dtype():
    """Regression: the cfg=None fallback added a float32 bias without a
    cast, silently upcasting a bf16 model's activations on that path only."""
    x = jax.random.normal(jax.random.PRNGKey(26), (2, 8, 8, 4)).astype(
        jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(27), (3, 3, 4, 8))
    b = jnp.ones((8,), jnp.float32)
    y = pim_conv2d(x, w, b, stride=1, padding=1, cfg=None)
    assert y.dtype == jnp.bfloat16
    # packed weights take the same fallback when cfg is disabled
    pk = prepack_conv2d(w, PIMQuantConfig(w_bits=8, a_bits=8))
    y2 = pim_conv2d(x, pk, b, stride=1, padding=1, cfg=None)
    assert y2.dtype == jnp.bfloat16


def test_fuse_heuristic_dispatch():
    """auto mode: big maps fuse on the pallas backend, 1x1 and XLA don't."""
    assert fuse_conv_heuristic(64, 112, 112, 3, 3, 64, "pallas")
    assert not fuse_conv_heuristic(64, 112, 112, 1, 1, 64, "pallas")
    assert not fuse_conv_heuristic(64, 112, 112, 3, 3, 64, "int-direct")
    assert not fuse_conv_heuristic(1, 4, 4, 3, 3, 8, "pallas")  # tiny map


@pytest.mark.parametrize("shape,fused", [
    ((16, 112, 112, 7, 7, 3), False),   # ResNet's 224-px stem: 49 vs 5 words
    ((16, 55, 55, 11, 11, 3), False),   # AlexNet's 11x11/4: 121 vs 12
    ((16, 56, 56, 3, 3, 64), True),     # full channel words: 18 vs 18
    ((16, 56, 56, 3, 3, 48), True),     # half-empty second word: 18 vs 14
])
def test_fuse_heuristic_word_fill(shape, fused):
    """Convs whose channel words are mostly empty take the im2col GEMM:
    the fused kernel would issue more than twice its packed words."""
    assert fuse_conv_heuristic(*shape, "pallas") is fused


def test_stem_geometry_lowerings_bit_identical(monkeypatch):
    """The stem's 7x7/2, C = 3 conv on a small map: fused, im2col and auto
    give the same P-derived outputs, and auto takes the im2col GEMM by the
    word-fill rule alone (the size rule is switched off), its kernel named
    as the fused one would be."""
    from repro.core import pim_layers

    monkeypatch.setattr(pim_layers, "_FUSE_MIN_BYTES", 0)
    x = jax.random.normal(jax.random.PRNGKey(40), (1, 32, 32, 3))
    w = jax.random.normal(jax.random.PRNGKey(41), (7, 7, 3, 64)) * 0.1
    cfg = PIMQuantConfig(w_bits=8, a_bits=8, backend="pallas")
    pk = prepack_conv2d(w, cfg)

    def conv(mode):
        return lambda x: pim_conv2d(x, pk, stride=2, padding=3, cfg=cfg,
                                    conv_mode=mode)

    outs = {m: conv(m)(x) for m in ("fused", "im2col", "auto")}
    assert jnp.array_equal(outs["fused"], outs["im2col"])
    assert jnp.array_equal(outs["auto"], outs["im2col"])
    with pim_layers.conv_lowering_log() as log:
        text = str(jax.make_jaxpr(conv("auto"))(x))
    assert dict(log) == {"im2col": 1}
    assert "name=eq1_conv_k7s2" in text
    assert "eq1_matmul" not in text
    # the same name as the fused path's kernel
    assert "eq1_conv_k7s2" in str(jax.make_jaxpr(conv("fused"))(x))


def _im2col_gather(x, kh, kw, stride, padding):
    """The patch build by patch-offset indexing (a gather), kept as the
    reference of the slice-built ``_im2col``."""
    n, h, w, c = x.shape
    x = jnp.pad(x, ((0, 0), (padding, padding), (padding, padding), (0, 0)))
    oh = (h + 2 * padding - kh) // stride + 1
    ow = (w + 2 * padding - kw) // stride + 1
    idx_h = stride * jnp.arange(oh)[:, None] + jnp.arange(kh)[None, :]
    idx_w = stride * jnp.arange(ow)[:, None] + jnp.arange(kw)[None, :]
    patches = x[:, idx_h[:, None, :, None], idx_w[None, :, None, :], :]
    return patches.reshape(n * oh * ow, kh * kw * c), oh, ow


@pytest.mark.parametrize("k,stride,padding", [
    (1, 1, 0), (1, 2, 0), (3, 2, 1), (7, 2, 3)])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_im2col_slices_match_gather(k, stride, padding, dtype):
    """The slice-built patch matrix equals the gather-built one, element
    for element, at odd widths (a stride that does not divide the map)."""
    from repro.core.pim_layers import _im2col

    x = jax.random.randint(jax.random.PRNGKey(42), (2, 13, 11, 3), -128,
                           128).astype(dtype)
    got, oh, ow = _im2col(x, k, k, stride, padding)
    want, oh_r, ow_r = _im2col_gather(x, k, k, stride, padding)
    assert (oh, ow) == (oh_r, ow_r)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert jnp.array_equal(got, want)
    jaxpr = str(jax.make_jaxpr(lambda x: _im2col(x, k, k, stride,
                                                 padding)[0])(x))
    assert "gather" not in jaxpr


def test_resnet50_224_conv_lowering_counts():
    """ResNet-50 at 224 px on the Pallas backend lowers 16 convs fused
    (the 3x3s), one through the im2col GEMM (the 7x7 stem) and 36
    pointwise; counted as the forward is traced, without running it."""
    from repro.core import pim_layers
    from repro.models.cnn import resnet

    cfg = PIMQuantConfig(w_bits=8, a_bits=8, backend="pallas")
    params = jax.eval_shape(lambda: resnet.prepack(
        resnet.init(jax.random.PRNGKey(0), 1000, 224), cfg))
    x = jax.ShapeDtypeStruct((16, 224, 224, 3), jnp.float32)
    with pim_layers.conv_lowering_log() as log:
        jax.eval_shape(lambda p, x: resnet.apply(p, x, cfg=cfg), params, x)
    assert dict(log) == {"fused": 16, "im2col": 1, "pointwise": 36}


# ---------------------------------------------------------------------------
# Serving: quantize+pack exactly once, no recompilation
# ---------------------------------------------------------------------------

def test_no_repack_no_recompile_on_repeated_calls(monkeypatch):
    """After prepack, repeated jitted calls never re-calibrate the weight
    and never re-trace: the paper's program-subarrays-once property."""
    from repro.core import bitserial as bs

    w = jax.random.normal(jax.random.PRNGKey(12), (128, 64))
    cfg = PIMQuantConfig(w_bits=8, a_bits=8, backend="popcount")
    pk = prepack_linear(w, cfg)

    seen = []
    orig = bs.calibrate_minmax
    monkeypatch.setattr(bs, "calibrate_minmax",
                        lambda x, bits, **kw: (seen.append(x.shape),
                                               orig(x, bits, **kw))[1])
    step = jax.jit(lambda x: pim_linear(x, pk, cfg=cfg))
    for i in range(4):
        step(jax.random.normal(jax.random.PRNGKey(i), (8, 128))).block_until_ready()
    # Traced once (one activation-side calibration), zero weight-side ones.
    assert step._cache_size() == 1
    assert seen == [(8, 128)]


def test_engine_prepacks_weights_once():
    """ServeEngine with a pim config serves from PackedWeight params and
    matches the whole-sequence prepacked forward greedily."""
    from repro.models.lm import ModelConfig, forward, init, prepack_params
    from repro.serving import Request, SamplerConfig, ServeEngine

    pim = PIMQuantConfig(w_bits=8, a_bits=8, backend="int-direct")
    cfg = ModelConfig(n_layers=2, d_model=32, n_heads=2, n_kv_heads=1,
                      d_ff=64, vocab=41, remat="none", dtype="float32",
                      pim=pim)
    params = init(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_batch=2, max_len=32,
                      sampler=SamplerConfig(temperature=0.0))
    # the engine's param tree holds PackedWeight leaves, not float masters
    leaves = jax.tree.leaves(eng.params, is_leaf=lambda l: isinstance(l, PackedWeight))
    assert any(isinstance(l, PackedWeight) for l in leaves)

    pk = prepack_params(params, pim)
    prompt = np.array([3, 1, 4, 1, 5], np.int32)
    toks = list(prompt)
    for _ in range(5):
        lg, _ = forward(pk, cfg, jnp.asarray([toks], jnp.int32))
        toks.append(int(jnp.argmax(lg[0, -1])))
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=5))
    done = eng.run()
    assert done[0].tokens == toks[len(prompt):]
    # repeated decode steps reuse the compiled drain programs: one cache
    # entry per power-of-two scan length, each compiled exactly once
    assert all(fn._cache_size() == 1 for fn in eng._decode.values())


def test_prepack_packs_moe_expert_banks():
    """MoE expert banks ride the prepacked fast path: (E, d, f) leaves in
    router-bearing dicts pack per expert (one vmap level deeper than the
    scan stack), the router stays float, and forward runs the packed
    bit-serial expert FFN end to end — both for scan-stacked (R, E, d, f)
    banks and for raw (E, d, f) banks in remainder layers."""
    from repro.models.lm import ModelConfig, MoEConfig, forward, init, prepack_params

    pim = PIMQuantConfig(w_bits=8, a_bits=8, backend="int-direct")
    # 8x attn + rglru: the scan unit caps at 8 blocks, so the 9th layer
    # lands in "rest" with its raw (E, d, f) MoE expert bank.
    cfg = ModelConfig(n_layers=9, d_model=32, n_heads=2, n_kv_heads=2,
                      d_ff=64, vocab=31, remat="none", dtype="float32",
                      family="moe", moe=MoEConfig(n_experts=4, top_k=2),
                      block_pattern=("attn",) * 8 + ("rglru",),
                      pim=pim)
    params = init(cfg, jax.random.PRNGKey(0))
    pk = prepack_params(params, pim)
    rest_ffn = pk["rest"][0]["ffn"]
    e, d, f = params["rest"][0]["ffn"]["w_in"].shape
    assert isinstance(rest_ffn["w_in"], PackedWeight)
    assert rest_ffn["w_in"].codes.shape == (e, d, f)         # expert-stacked
    assert rest_ffn["w_in"].col_sums.shape == (e, f)
    assert not isinstance(rest_ffn["router"], PackedWeight)  # router float
    scan_ffn = pk["scan"][0]["ffn"]
    assert isinstance(scan_ffn["w_in"], PackedWeight)
    assert scan_ffn["w_in"].codes.shape == (8, e, d, f)      # scan + experts
    assert isinstance(pk["rest"][0]["rglru"]["w_x"], PackedWeight)
    x = jnp.asarray([[3, 1, 4, 1, 5]], jnp.int32)
    logits, _ = forward(pk, cfg, x)
    assert jnp.isfinite(logits).all()
    # Prepacked at deploy time == packed per call from the same masters:
    # prepack is deterministic, so the fast path's codes are exactly the
    # ones a fresh pack of the float masters would produce.
    logits2, _ = forward(prepack_params(params, pim), cfg, x)
    assert jnp.array_equal(logits, logits2)


def test_cnn_prepack_bit_exact_and_conv_weights_packed():
    from repro.models.cnn import alexnet

    params = alexnet.init(jax.random.PRNGKey(0), image=64, num_classes=10)
    cfg = PIMQuantConfig(w_bits=8, a_bits=8, backend="int-direct")
    pk = alexnet.prepack(params, cfg)
    assert isinstance(pk["conv1"]["w"], PackedConvWeight)
    assert isinstance(pk["fc1"]["w"], PackedWeight)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64, 3))
    assert jnp.array_equal(alexnet.apply(params, x, cfg=cfg),
                           alexnet.apply(pk, x, cfg=cfg))
