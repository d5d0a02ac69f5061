"""Operations and bytes the benchmarked models need, from their shapes, and
the chip's peaks. Kept with the benchmark so that no change to the program
moves the yardstick.

Operations count a multiply-add as two. Bytes count what the algorithm has
to move at least once between HBM and the chip, at the bit widths the
configuration states.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a kind not in the table is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def roofline_s(ops: float, nbytes: float, peak_ops: float, bw: float
               ) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops, t_mem = ops / peak_ops, nbytes / bw
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


# -- ResNet-50 ----------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Gemm:
    """One conv or fc as a matrix product per image: (m, k) x (k, n).

    ``in_elems`` is the size of its input map per image, which the
    algorithm has to read once (the patch matrix is m x k, but it repeats
    the map's elements)."""
    name: str
    m: int
    n: int
    k: int
    in_elems: int


def resnet50_gemms(image: int = 224, classes: int = 1000,
                   stages=((3, 64), (4, 128), (6, 256), (3, 512))) -> list:
    """Every conv and the fc of the served ResNet-50 (7x7/2 stem, unpadded
    3x3/2 max-pool, bottlenecks with the stride on the 3x3)."""
    def out(h, k, s, p):
        return (h + 2 * p - k) // s + 1

    gemms = []
    h = out(image, 7, 2, 3)
    gemms.append(Gemm("stem", h * h, 64, 7 * 7 * 3, image * image * 3))
    h = out(h, 3, 2, 0)
    cin = 64
    for s, (blocks, mid) in enumerate(stages):
        for b in range(blocks):
            st = 2 if (b == 0 and s > 0) else 1
            h2 = out(h, 3, st, 1)
            pre = f"s{s}b{b}"
            gemms += [Gemm(f"{pre}.c1", h * h, mid, cin, h * h * cin),
                      Gemm(f"{pre}.c2", h2 * h2, mid, 9 * mid, h * h * mid),
                      Gemm(f"{pre}.c3", h2 * h2, 4 * mid, mid, h2 * h2 * mid)]
            if b == 0:
                gemms.append(Gemm(f"{pre}.proj", h2 * h2, 4 * mid, cin,
                                  h * h * cin))
            h, cin = h2, 4 * mid
    gemms.append(Gemm("head", 1, classes, cin, cin))
    return gemms


def gemm_ops(g: Gemm, batch: int) -> int:
    return 2 * batch * g.m * g.n * g.k


def gemm_bytes(g: Gemm, batch: int, w_bits: int, a_bits: int,
               out_bytes: int = 4) -> float:
    """Input map at ``a_bits``, weights at ``w_bits``, and the int32 Eq. 1
    result, each once."""
    return (batch * g.in_elems * a_bits / 8 + g.k * g.n * w_bits / 8
            + batch * g.m * g.n * out_bytes)


def model_ops(gemms: list, batch: int = 1) -> int:
    return sum(gemm_ops(g, batch) for g in gemms)


def eq1_roofline_s(gemms: list, batch: int, bits: tuple, pk: dict
                   ) -> tuple[float, dict]:
    """Summed per-GEMM roofline time of one ``batch`` at ``<W:I>`` = bits
    on int8 peak and HBM bandwidth; and how many GEMMs each bound set."""
    total, bounds = 0.0, {"compute": 0, "memory": 0}
    for g in gemms:
        t, b = roofline_s(gemm_ops(g, batch),
                          gemm_bytes(g, batch, bits[0], bits[1]),
                          pk["int8_ops_per_s"], pk["hbm_bytes_per_s"])
        total += t
        bounds[b] += 1
    return total, bounds


# -- Qwen3 ----------------------------------------------------------------------

def qwen3_proj_params(cfg: dict) -> int:
    """Weights of the quantized projections, all layers."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    per_layer = d * (hq + 2 * hkv) * hd + hq * hd * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer


def qwen3_norm_params(cfg: dict) -> int:
    d, hd, L = cfg["hidden_size"], cfg["head_dim"], cfg["num_hidden_layers"]
    return L * (2 * d + 2 * hd) + d


def qwen3_token_ops(cfg: dict, context: int, head: bool = True) -> int:
    """Operations of one token at position ``context`` (it attends to
    ``context`` + 1 keys): projections, attention scores and values, and
    the tied output head where the token's logits are needed."""
    hq, hd, L = cfg["num_attention_heads"], cfg["head_dim"], \
        cfg["num_hidden_layers"]
    ops = 2 * qwen3_proj_params(cfg) + L * 4 * hq * hd * (context + 1)
    if head:
        ops += 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return ops


def qwen3_prefill_ops(cfg: dict, prompt: int) -> int:
    """A prompt of ``prompt`` tokens, logits for its last one only."""
    n = prompt
    attn = cfg["num_hidden_layers"] * 4 * cfg["num_attention_heads"] \
        * cfg["head_dim"] * n * (n + 1) // 2
    return (n * 2 * qwen3_proj_params(cfg) + attn
            + 2 * cfg["hidden_size"] * cfg["vocab_size"])


def kv_bytes_per_token(cfg: dict, kv_bytes: int = 2) -> int:
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * kv_bytes)


def qwen3_decode_bytes(cfg: dict, w_bits: int, live_kv_tokens: float,
                       head_bytes: int = 2) -> float:
    """The HBM-bound minimum of one decode step: every projection weight at
    ``w_bits``, the tied head at ``head_bytes`` per element (bfloat16),
    the norm scales (float32), and the keys and values of the live
    context (bfloat16)."""
    return (qwen3_proj_params(cfg) * w_bits / 8
            + cfg["vocab_size"] * cfg["hidden_size"] * head_bytes
            + qwen3_norm_params(cfg) * 4
            + live_kv_tokens * kv_bytes_per_token(cfg))
