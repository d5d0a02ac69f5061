"""Plain float32 ResNet-50 and its weights, independent of ``src/``.

The network the vision engine serves: ResNet-50 v1.5 (He et al.,
arXiv:1512.03385, Table 1; the stride on the 3x3 conv of each bottleneck)
with the program's one departure from it kept, since this is the model it
is compared with: the stem's 3x3 max-pool is unpadded (112 -> 55 maps, not
56). Batch normalization is folded, at inference, into a per-channel affine.

``forward(params, x, bits=None)`` is that network in float32 at "highest"
matmul precision. With ``bits=(w, a)`` every conv and the fc first pass
their weights and their input through the paper's Eq. 2 min/max
quantizer, dequantized: per tensor, the input calibrated over the whole
batch it rides in, as the engine calibrates each dispatched bucket.
Padded taps contribute zero. That is the semantics of the engine's
``<W:I>`` path, computed in float instead of by Eq. 1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

STAGES = ((3, 64), (4, 128), (6, 256), (3, 512))   # (blocks, mid channels)
BN_EPS = 1e-5


def init(key, image: int, classes: int) -> dict:
    """He-normal conv/fc weights and a random folded-BN affine per conv,
    float32, in the engine's tree layout. Call under ``jax.jit``."""
    del image   # the weights do not depend on the input size
    keys = iter(jax.random.split(key, 512))

    def conv(k, cin, cout):
        fan_in = k * k * cin
        return {
            "w": jax.random.normal(next(keys), (k, k, cin, cout))
            * (2.0 / fan_in) ** 0.5,
            "gamma": jax.random.uniform(next(keys), (cout,), minval=0.8,
                                        maxval=1.2),
            "beta": 0.1 * jax.random.normal(next(keys), (cout,)),
            "mean": 0.1 * jax.random.normal(next(keys), (cout,)),
            "var": jax.random.uniform(next(keys), (cout,), minval=0.8,
                                      maxval=1.2),
        }

    params = {"stem": conv(7, 3, 64)}
    cin = 64
    for s, (blocks, mid) in enumerate(STAGES):
        for b in range(blocks):
            blk = {"c1": conv(1, cin, mid), "c2": conv(3, mid, mid),
                   "c3": conv(1, mid, 4 * mid)}
            if b == 0:
                blk["proj"] = conv(1, cin, 4 * mid)
            params[f"s{s}b{b}"] = blk
            cin = 4 * mid
    params["head"] = {
        "w": jax.random.normal(next(keys), (cin, classes)) * (2.0 / cin) ** 0.5,
        "b": 0.01 * jax.random.normal(next(keys), (classes,)),
    }
    return params


def fake_quant(x, bits: int):
    """Eq. 2 per tensor: round((x - min) (2^k - 1) / (max - min)), back to
    float."""
    lo, hi = jnp.min(x), jnp.max(x)
    scale = jnp.maximum(hi - lo, jnp.finfo(jnp.float32).tiny) / (2**bits - 1)
    q = jnp.clip(jnp.round((x - lo) / scale), 0, 2**bits - 1)
    return q * scale + lo


def _conv(p, x, stride, pad, bits, relu=True):
    w = p["w"]
    if bits is not None:
        w, x = fake_quant(w, bits[0]), fake_quant(x, bits[1])
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    inv = p["gamma"] / jnp.sqrt(p["var"] + BN_EPS)
    y = y * inv + (p["beta"] - p["mean"] * inv)
    return jax.nn.relu(y) if relu else y


def forward(params, x, bits=None):
    """Logits (B, classes) of images ``x`` (B, H, W, 3), float32."""
    x = _conv(params["stem"], x.astype(jnp.float32), 2, 3, bits)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "VALID")
    for s, (blocks, _) in enumerate(STAGES):
        for b in range(blocks):
            p = params[f"s{s}b{b}"]
            stride = 2 if (b == 0 and s > 0) else 1
            y = _conv(p["c1"], x, 1, 0, bits)
            y = _conv(p["c2"], y, stride, 1, bits)
            y = _conv(p["c3"], y, 1, 0, bits, relu=False)
            if "proj" in p:
                x = _conv(p["proj"], x, stride, 0, bits, relu=False)
            x = jax.nn.relu(x + y)
    x = x.mean(axis=(1, 2))
    w = params["head"]["w"]
    if bits is not None:
        w, x = fake_quant(w, bits[0]), fake_quant(x, bits[1])
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST) \
        + params["head"]["b"]
