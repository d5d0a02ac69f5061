"""Cost-model-driven backend/tiling autotuner (DESIGN.md §9).

BENCH_kernels.json shows the best Eq. 1 backend *flips* with shape and
precision: the popcount dataflow scales with the W*I plane-pair count, the
direct integer matmul is precision-flat, and the MXU plane path sits in
between — so a fixed backend constant leaves 2-10x on the table somewhere
in every deployment. This module closes the loop the paper's architecture
already has: the chip/bank/subarray mapper (:func:`repro.pim.mapper.
map_gemm`) and its price list (:class:`repro.pim.cost_model.CostModel`)
rank the *real* kernel candidates, and the verdict ships to prepack time
as a :class:`~repro.core.packed.TuneDecision` on each packed weight.

Pipeline per (m, k, n, <W:I>) GEMM:

  1. enumerate candidates — one per XLA backend, plus a legalized Pallas
     tile lattice (bm, bn, bkw) when "pallas" is allowed;
  2. rank analytically: ``map_gemm`` expands the candidate's schedule into
     subarray micro-ops (plane pairs for the bit-serial backends, a single
     full-width pass for int-direct) and ``CostModel`` prices them; a
     per-backend throughput factor (``_RATES``, fitted once against the
     committed BENCH_kernels.json trends per device kind) converts the
     NAND-SPIN price into a relative execution-time estimate;
  3. near-ties (within ``_TIE_BAND``) are broken by
     :func:`repro.roofline.hlo_cost.analyze` on the *compiled* XLA
     candidate — a roofline max(flops/peak, bytes/bw) of the lowered HLO;
  4. ``mode="measure"`` refines the top candidate per backend by actual
     wall-clock measurement (injectable ``measure`` fn; the default
     synthesizes operands once);
  5. the decision persists in a :class:`TuningCache` — a JSON file keyed
     by (shape, precision, backend-set, device-kind) and stamped with a
     code version hashed from the modules that define the kernels'
     semantics, so editing the kernels stales the cache instead of
     silently serving outdated picks.

Tuning may change speed, never bits: every backend computes the identical
integer P (mod 2^32), asserted across the candidate set in
tests/test_autotune.py.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import math
import os
import time
import warnings

from repro.core.packed import (PackedConvWeight, PackedWeight, TuneDecision,
                               prepack)
from repro.core.pim_layers import sparse_channel_words
from repro.models.cnn.specs import GemmSpec

from .cost_model import CostModel
from .hierarchy import Geometry
from .mapper import map_gemm

# Backends with an XLA lowering — always safe candidates. "pallas" joins
# the set only when requested explicitly or on a real TPU backend: in
# interpret mode (CPU) the kernel runs the Python loop body, which is a
# semantics oracle, not a contender.
XLA_BACKENDS = ("popcount", "mxu-plane", "int-direct")
ALL_BACKENDS = XLA_BACKENDS + ("pallas",)

# Pallas tile request lattice; every point is legalized against the actual
# (m, n, kw) by kernels.ops.matmul_tiles before it becomes a candidate, so
# the set collapses for small operands.
_TILE_BM = (8, 32, 128, 256)
_TILE_BN = (128, 256, 512)
_TILE_BKW = (32, 128, 512)

# Relative schedule drain rates per backend and device kind: each
# candidate's time estimate is its mapper price divided by this factor
# (popcount = 1.0 defines the unit). int-direct's single full-width pass
# is priced by map_gemm(ab=wb=1), whose cost relative to the full
# plane-pair sweep *shrinks* as W*I grows (the sweep's extra row-ops are
# only partly absorbed by the residency parallel width) — so one flat
# rate reproduces the measured precision crossover: 0.2 puts it where
# BENCH_kernels.json flips from popcount (low-precision, few pairs) to
# int-direct (<8:8>, 64 pairs), right for 14/15 of the committed
# backend_comparison grid. mxu-plane pays bf16 plane materialization it
# never earns back off-TPU; on TPU the systolic array flips both
# relations. Calibration constants of the *ranking*, not the simulator:
# measure mode bypasses them entirely.
_RATES = {
    "default": {"popcount": 1.0, "mxu-plane": 0.4, "int-direct": 0.2,
                "pallas": 0.9},
    "tpu": {"popcount": 1.0, "mxu-plane": 4.0, "int-direct": 0.5,
            "pallas": 2.5},
}

_TIE_BAND = 1.10          # analytic near-tie band feeding the HLO tie-break
_VMEM_BUDGET = 8 << 20    # matches core.mapping.plan_matmul's default
_GEO = Geometry()


# ---------------------------------------------------------------------------
# Environment fingerprints
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def device_kind() -> str:
    import jax

    # A failed backend start raises here: tuning decisions are keyed by
    # device, so a guessed kind would file them under the wrong one.
    return jax.devices()[0].device_kind.replace(" ", "-").lower()


@functools.lru_cache(maxsize=1)
def code_version() -> str:
    """Hash of the modules defining kernel semantics + this ranker.

    A cache entry is only as good as the code that produced and consumes
    it: editing the kernels, the tile planner or the autotuner itself must
    stale every persisted decision (fall back to fresh cost-model picks),
    never silently serve them.
    """
    import importlib

    mods = [importlib.import_module(m) for m in
            ("repro.core.bitserial", "repro.core.mapping",
             "repro.kernels.ops", "repro.kernels.bitserial_matmul",
             "repro.kernels.conv2d_fused")]
    h = hashlib.md5()
    for mod in mods:
        try:
            with open(mod.__file__, "rb") as fh:
                h.update(fh.read())
        except OSError:  # pragma: no cover - frozen/zipped install
            h.update(mod.__name__.encode())
    with open(__file__, "rb") as fh:
        h.update(fh.read())
    return h.hexdigest()[:12]


def _rates() -> dict:
    import jax

    key = "tpu" if jax.default_backend() == "tpu" else "default"
    return _RATES[key]


def default_backends(mesh=None) -> tuple:
    """Candidate set for engine prepack: the XLA backends everywhere, plus
    pallas on a real TPU without a mesh (pallas_call has no GSPMD rule —
    the same restriction ServeEngine/VisionEngine enforce on their
    configured backend)."""
    import jax

    out = XLA_BACKENDS
    if mesh is None and jax.default_backend() == "tpu":
        out = out + ("pallas",)
    return out


# ---------------------------------------------------------------------------
# Candidate enumeration + analytic ranking
# ---------------------------------------------------------------------------

def gemm_candidates(m: int, k: int, n: int, a_bits: int, w_bits: int,
                    backends=XLA_BACKENDS) -> list:
    """One TuneDecision per XLA backend + the legalized Pallas tile set."""
    from repro.kernels import ops as _kops

    out = []
    for be in backends:
        if be != "pallas":
            out.append(TuneDecision(backend=be))
            continue
        kw = max(1, -(-k // 32))
        seen = set()
        for bm in _TILE_BM:
            for bn in _TILE_BN:
                for bkw in _TILE_BKW:
                    t = _kops.matmul_tiles(m, n, kw, a_bits, w_bits,
                                           bm, bn, bkw)
                    if t in seen:
                        continue
                    seen.add(t)
                    out.append(TuneDecision(backend="pallas", bm=t[0],
                                            bn=t[1], bkw=t[2]))
    return out


def _gemm_spec(m: int, k: int, n: int) -> GemmSpec:
    return GemmSpec(name="autotune", kind="fc", m=m, k=k, n=n,
                    out_elems=m * n, in_elems=m * k, weight_elems=k * n)


def _price(spec: GemmSpec, ab: int, wb: int) -> float:
    """NAND-SPIN schedule latency for one (ab x wb)-plane GEMM pass."""
    cm = CostModel(_GEO)
    oc = map_gemm(spec, _GEO, ab, wb)
    c = cm.price_rowops(oc)
    c += cm.price_programs(oc)
    c += cm.price_bus(oc)
    c += cm.price_local(oc)
    return c.latency


def _tile_factor(m: int, k: int, n: int, a_bits: int, w_bits: int,
                 d: TuneDecision) -> float:
    """Pallas tile quality multiplier: grid-step overhead, output tiles
    that fill only part of a lane group, and VMEM overflow. Purely
    relative — it orders tile candidates of one shape, nothing else."""
    kw = max(1, -(-k // 32))
    bm, bn, bkw = d.bm or m, d.bn or n, d.bkw or kw
    steps = (math.ceil(m / bm) * math.ceil(n / bn) * math.ceil(kw / bkw))
    ws = (a_bits * bm * bkw + w_bits * bn * bkw + bm * bn) * 4
    f = 1.0 + 0.002 * (steps - 1)
    if bn % 128:
        f *= 1.5          # a partial lane group idles part of every vreg
    if ws > _VMEM_BUDGET:
        f *= 4.0          # working set spills the per-step VMEM budget
    return f


def analytic_gemm_cost(m: int, k: int, n: int, a_bits: int, w_bits: int,
                       d: TuneDecision) -> float:
    """Relative execution-time estimate of one candidate (see module doc).

    The bit-serial backends run the full ab x wb plane-pair schedule; the
    direct integer matmul is one full-width pass (ab = wb = 1 in the
    mapper's schedule) whose row-ops retire at the backend's own rate.
    """
    spec = _gemm_spec(m, k, n)
    if d.backend == "int-direct":
        base = _price(spec, 1, 1)
    else:
        base = _price(spec, a_bits, w_bits)
    t = base / _rates()[d.backend]
    if d.backend == "pallas":
        t *= _tile_factor(m, k, n, a_bits, w_bits, d)
    return t


# ---------------------------------------------------------------------------
# HLO roofline tie-break + measurement refinement
# ---------------------------------------------------------------------------

def roofline_time(m: int, k: int, n: int, a_bits: int, w_bits: int,
                  backend: str) -> float | None:
    """Roofline time of the compiled XLA candidate (tie-break only).

    Lowers the exact prepacked dispatch the serving path runs, walks the
    optimized HLO with :func:`repro.roofline.hlo_cost.analyze`, and prices
    it at the roofline max(flops/peak, bytes/bw). None when the candidate
    has no analyzable HLO (pallas interpret mode lowers to a callback) or
    lowering fails — callers fall back to the analytic order.
    """
    if backend == "pallas":
        return None
    try:
        import jax
        import jax.numpy as jnp

        from repro.core import bitserial
        from repro.core.quantize import QuantParams
        from repro.roofline import hlo_cost, hw

        kw = max(1, -(-k // 32))
        w = PackedWeight(
            codes=jax.ShapeDtypeStruct((k, n), jnp.int32),
            planes=jax.ShapeDtypeStruct((w_bits, n, kw), jnp.uint32),
            col_sums=jax.ShapeDtypeStruct((n,), jnp.int32),
            wq=QuantParams(scale=jax.ShapeDtypeStruct((), jnp.float32),
                           qmin=jax.ShapeDtypeStruct((), jnp.float32),
                           bits=w_bits))
        qa = jax.ShapeDtypeStruct((m, k), jnp.int32)
        fn = jax.jit(functools.partial(bitserial.int_matmul_prepacked,
                                       a_bits=a_bits, backend=backend))
        txt = fn.lower(qa, w).compile().as_text()
        c = hlo_cost.analyze(txt)
        return max(c.flops / hw.PEAK_FLOPS_BF16, c.bytes / hw.HBM_BW)
    except Exception:
        return None


def measure_gemm(d: TuneDecision, m: int, k: int, n: int, a_bits: int,
                 w_bits: int, iters: int = 2) -> float | None:
    """Default measurement hook: wall-clock one candidate on synthetic
    operands through the real prepacked dispatch. Returns seconds, or None
    when the candidate fails to run (it is then dropped, not picked)."""
    try:
        import jax
        import jax.numpy as jnp

        from repro.core.bitserial import int_matmul_prepacked

        key = jax.random.PRNGKey(0)
        qa = jax.random.randint(key, (m, k), 0, 2 ** a_bits, jnp.int32)
        pk = attach(prepack(jax.random.normal(
            jax.random.fold_in(key, 1), (k, n)), w_bits), d)
        fn = jax.jit(functools.partial(int_matmul_prepacked, a_bits=a_bits))
        jax.block_until_ready(fn(qa, pk))       # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(fn(qa, pk))
        return (time.perf_counter() - t0) / iters
    except Exception:
        return None


# ---------------------------------------------------------------------------
# Decisions
# ---------------------------------------------------------------------------

def gemm_key(m: int, k: int, n: int, a_bits: int, w_bits: int,
             backends) -> str:
    return (f"gemm:{m}x{k}x{n}:<{w_bits}:{a_bits}>:"
            f"be={'+'.join(sorted(backends))}:dev={device_kind()}")


def conv_key(n: int, h: int, w: int, c: int, o: int, kh: int, kw: int,
             stride: int, padding: int, a_bits: int, w_bits: int,
             backends) -> str:
    return (f"conv:{n}x{h}x{w}x{c}:o{o}:k{kh}x{kw}:s{stride}p{padding}:"
            f"<{w_bits}:{a_bits}>:be={'+'.join(sorted(backends))}:"
            f"dev={device_kind()}")


def decide_gemm(m: int, k: int, n: int, a_bits: int, w_bits: int, *,
                backends=None, mode: str = "cost", cache=None,
                measure=None, hlo_tiebreak: bool = True) -> TuneDecision:
    """Pick (backend, tiles) for an (m, k, n) <W:I> GEMM.

    Deterministic for a fixed cache and candidate set: the analytic
    ranking is pure arithmetic, ties within the band resolve by the HLO
    roofline (itself deterministic) and finally by enumeration order.
    ``mode="measure"`` additionally times the best candidate per backend
    (``measure(decision, m, k, n, a_bits, w_bits) -> seconds | None``;
    default :func:`measure_gemm`) and picks the fastest.
    """
    if mode not in ("cost", "measure"):
        raise ValueError(f"autotune mode {mode!r}: want 'cost' | 'measure'")
    backends = tuple(backends) if backends else XLA_BACKENDS
    key = gemm_key(m, k, n, a_bits, w_bits, backends)
    if cache is not None:
        hit = cache.get(key)
        if hit is not None:
            return hit
    cands = gemm_candidates(m, k, n, a_bits, w_bits, backends)
    scored = sorted(
        (analytic_gemm_cost(m, k, n, a_bits, w_bits, d), i, d)
        for i, d in enumerate(cands))
    best_cost, _, best = scored[0]

    if hlo_tiebreak:
        ties = [d for c, _, d in scored
                if c <= best_cost * _TIE_BAND and d.backend != "pallas"]
        if len({d.backend for d in ties}) > 1:
            rt = [(roofline_time(m, k, n, a_bits, w_bits, d.backend), i, d)
                  for i, d in enumerate(ties)]
            rt = [x for x in rt if x[0] is not None]
            if rt:
                best = min(rt)[2]

    if mode == "measure":
        measure = measure or measure_gemm
        # Top analytic candidate per backend; measurement settles between
        # backends, the analytic order settles tiles within one.
        heads = {}
        for c, i, d in scored:
            heads.setdefault(d.backend, d)
        timed = [(t, i, d) for i, d in enumerate(heads.values())
                 if (t := measure(d, m, k, n, a_bits, w_bits)) is not None]
        if timed:
            best = min(timed)[2]

    if cache is not None:
        cache.put(key, best, mode=mode)
    return best


def decide_conv(n: int, h: int, w: int, c: int, o: int, kh: int, kw: int,
                *, stride: int = 1, padding: int = 0, a_bits: int = 8,
                w_bits: int = 8, backends=None, mode: str = "cost",
                cache=None, measure=None) -> tuple:
    """Pick (conv_mode, bo, backend) for a conv layer; returns the pair
    (conv decision, im2col-matmul decision) that :func:`attach_conv`
    installs on a :class:`PackedConvWeight`.

    Candidates: the materialized im2col path per allowed backend (priced
    as the underlying GEMM plus the patch-matrix bus traffic the paper's
    fused schedule never pays — zero for 1x1 kernels, where im2col is a
    reshape), and the fused implicit-im2col kernel per O-block when
    "pallas" is allowed. The fused kernel is no candidate where its
    channel words are mostly empty (:func:`repro.core.pim_layers.
    sparse_channel_words`, the rule :func:`fuse_conv_heuristic` applies):
    the mapper prices the dense GEMM either way and charges only the
    im2col path for streaming its input, so it cannot see the empty
    words (ResNet's 7x7 stem on C = 3: 49 words, 5 for the im2col GEMM).
    """
    if mode not in ("cost", "measure"):
        raise ValueError(f"autotune mode {mode!r}: want 'cost' | 'measure'")
    backends = tuple(backends) if backends else XLA_BACKENDS
    ckey = conv_key(n, h, w, c, o, kh, kw, stride, padding, a_bits, w_bits,
                    backends)
    if cache is not None:
        hit = cache.get(ckey)
        if hit is not None and isinstance(hit, tuple):
            return hit
    oh = (h + 2 * padding - kh) // stride + 1
    ow = (w + 2 * padding - kw) // stride + 1
    m, kdim = n * oh * ow, kh * kw * c
    spec = _gemm_spec(m, kdim, o)
    cm = CostModel(_GEO)
    # Patch-matrix blow-up the materialized path streams (int32 codes),
    # priced on the same global bus as the mapper's weight broadcasts.
    patch_bits = 0 if kh == kw == 1 else m * kdim * 32
    patch_t = cm.bus_time(patch_bits)

    scored = []
    for i, be in enumerate(backends):
        if be == "pallas":
            continue
        d = TuneDecision(backend=be, conv_mode="im2col")
        t = analytic_gemm_cost(m, kdim, o, a_bits, w_bits, d) + patch_t
        scored.append((t, i, d))
    if "pallas" in backends:
        d = TuneDecision(backend="pallas", conv_mode="im2col")
        scored.append((analytic_gemm_cost(m, kdim, o, a_bits, w_bits, d)
                       + patch_t, len(backends), d))
    if "pallas" in backends and not sparse_channel_words(kh, kw, c):
        base = _price(spec, a_bits, w_bits) / _rates()["pallas"]
        for j, bo in enumerate((64, 128, 256)):
            steps = math.ceil(o / min(bo, o))
            t = base * (1.0 + 0.002 * (steps - 1))
            if bo % 128 and bo < min(o, 128):
                t *= 1.2
            scored.append((t, len(backends) + 1 + j,
                           TuneDecision(backend="pallas", conv_mode="fused",
                                        bo=bo)))
    scored.sort()
    best = scored[0][2]
    if mode == "measure" and measure is not None:
        heads, seen = [], set()
        for t, i, d in scored:
            hk = (d.backend, d.conv_mode)
            if hk not in seen:
                seen.add(hk)
                heads.append(d)
        timed = [(t, i, d) for i, d in enumerate(heads)
                 if (t := measure(d)) is not None]
        if timed:
            best = min(timed)[2]
    mat = TuneDecision(backend=best.backend if best.conv_mode == "im2col"
                       else "popcount")
    out = (best, mat)
    if cache is not None:
        cache.put(ckey, out, mode=mode)
    return out


# ---------------------------------------------------------------------------
# Attachment: decisions -> packed-weight trees
# ---------------------------------------------------------------------------

def attach(pw: PackedWeight, d: TuneDecision | None) -> PackedWeight:
    """Install a decision on a packed weight (static metadata only — the
    leaf buffers, shardings and checkpoint layout are untouched)."""
    return dataclasses.replace(pw, tune=d)


def attach_conv(pcw: PackedConvWeight, d: TuneDecision | None,
                mat: TuneDecision | None = None) -> PackedConvWeight:
    return dataclasses.replace(pcw, tune=d,
                               mat=dataclasses.replace(pcw.mat, tune=mat))


_MOE_EXPERT_NAMES = ("w_in", "w_out", "w_gate")


def _is_expert_path(path) -> bool:
    """True for packed leaves living at ``...['ffn']...['w_in'|'w_out'|
    'w_gate']`` — the expert-stacked MoE banks (callers only enable the
    check for MoE configs, where every ffn projection is an expert bank)."""
    keys = [getattr(k, "key", None) for k in path]
    return "ffn" in keys and keys and keys[-1] in _MOE_EXPERT_NAMES


def tune_tree(tree, *, m_hint: int, a_bits: int, backends=None,
              mode: str = "cost", cache=None, conv_m_hint: int | None = None,
              measure=None, moe_m_hint: int | None = None):
    """Attach decisions to every packed leaf of a prepacked param tree.

    ``m_hint`` is the GEMM row count the deployment runs (the serving
    batch for LM decode / the vision FC head); ``conv_m_hint`` bounds the
    conv im2col row count (batch * input map, the stride-1 upper bound —
    the backend crossover is driven by the plane-pair count, which this
    estimate preserves). Decisions dedupe through the cache: scan-stacked
    layer leaves with equal (k, n, bits) decide once.

    ``moe_m_hint`` (MoE deployments): the expert banks' GEMMs run batched
    over every expert's capacity buffer, so their decisions key on the
    E*C dispatch row count instead of the token batch — and their
    candidate set drops "pallas" (the per-expert dispatch runs under
    ``vmap``, which the interpret-mode kernel does not batch).
    """
    import jax

    backends = tuple(backends) if backends else XLA_BACKENDS
    xla_only = tuple(b for b in backends if b != "pallas") or backends

    def visit(path, leaf):
        if isinstance(leaf, PackedConvWeight):
            _, _, _, o = leaf.kernel_shape
            kdim = leaf.mat.codes.shape[-2]
            m = conv_m_hint if conv_m_hint is not None else m_hint
            # Conv decisions from the weight alone: rank the im2col GEMM
            # (the spatial dims ride in conv_m_hint); the fused-vs-im2col
            # split stays with the shape heuristic (tune.conv_mode=None).
            d = decide_gemm(m, kdim, o, a_bits, leaf.bits,
                            backends=xla_only, mode="cost", cache=cache)
            return attach_conv(leaf, TuneDecision(backend=d.backend),
                               mat=d)
        if isinstance(leaf, PackedWeight):
            *_, k, n = leaf.codes.shape
            m, be = m_hint, backends
            if moe_m_hint is not None and _is_expert_path(path):
                m, be = moe_m_hint, xla_only
            d = decide_gemm(m, k, n, a_bits, leaf.bits,
                            backends=be, mode=mode, cache=cache,
                            measure=measure)
            return attach(leaf, d)
        return leaf

    return jax.tree_util.tree_map_with_path(
        visit, tree,
        is_leaf=lambda x: isinstance(x, (PackedWeight, PackedConvWeight)))


# ---------------------------------------------------------------------------
# The on-disk tuning cache
# ---------------------------------------------------------------------------

_FIELDS = tuple(f.name for f in dataclasses.fields(TuneDecision))


def _decision_to(d: TuneDecision) -> dict:
    return {f: getattr(d, f) for f in _FIELDS}


def _decision_from(blob: dict) -> TuneDecision:
    kw = {f: blob[f] for f in _FIELDS if f in blob}
    if not isinstance(kw.get("backend"), str):
        raise ValueError(f"bad cached decision {blob!r}")
    return TuneDecision(**kw)


class TuningCache:
    """Persisted autotune decisions with fail-safe loading.

    The file format carries a schema ``VERSION``, the :func:`code_version`
    of the kernels that produced the entries, and the decisions keyed by
    :func:`gemm_key`/:func:`conv_key` strings (which bake in shape,
    precision, backend-set and device kind). Any load problem — corrupt
    JSON, truncation, stale versions, unreadable entries — degrades to an
    empty in-memory cache with a single RuntimeWarning: decisions fall
    back to fresh cost-model picks, are re-memoized immediately (no retune
    storm — one computation per key per process), and the next save
    self-heals the file. ``path=None`` is a process-local memo.
    """

    VERSION = 1

    def __init__(self, path: str | None = None):
        self.path = path
        self.entries: dict = {}
        self._warned = False
        if path:
            self._load()

    # -- robust IO ----------------------------------------------------------

    def _warn(self, msg: str):
        if not self._warned:
            warnings.warn(f"tuning cache {self.path!r}: {msg}; "
                          "falling back to cost-model picks",
                          RuntimeWarning, stacklevel=3)
            self._warned = True

    def _load(self):
        if not os.path.exists(self.path):
            return
        try:
            with open(self.path) as fh:
                blob = json.load(fh)
            if blob.get("version") != self.VERSION:
                raise ValueError(f"schema version {blob.get('version')!r} "
                                 f"!= {self.VERSION}")
            if blob.get("code_version") != code_version():
                raise ValueError(
                    f"stale code_version {blob.get('code_version')!r}")
            self.entries = {k: self._entry_from(v)
                            for k, v in blob["entries"].items()}
        except Exception as e:
            self.entries = {}
            self._warn(f"unusable ({e!r})")

    @staticmethod
    def _entry_from(v: dict) -> dict:
        if "pair" in v:      # conv entries hold (conv, mat) decision pairs
            pair = tuple(_decision_from(p) for p in v["pair"])
            return {"decision": pair, "mode": v.get("mode", "cost")}
        return {"decision": _decision_from(v["decision"]),
                "mode": v.get("mode", "cost")}

    @staticmethod
    def _entry_to(e: dict) -> dict:
        d = e["decision"]
        if isinstance(d, tuple):
            return {"pair": [_decision_to(x) for x in d], "mode": e["mode"]}
        return {"decision": _decision_to(d), "mode": e["mode"]}

    def save(self):
        if not self.path:
            return
        blob = {"version": self.VERSION, "code_version": code_version(),
                "device_kind": device_kind(),
                "entries": {k: self._entry_to(e)
                            for k, e in self.entries.items()}}
        try:
            tmp = f"{self.path}.tmp"
            with open(tmp, "w") as fh:
                json.dump(blob, fh, indent=1)
            os.replace(tmp, self.path)   # atomic: no truncated cache files
        except OSError as e:
            self._warn(f"unwritable ({e!r})")

    def reset(self):
        """Drop the in-memory state and re-read the backing file.

        The single-warning fallback memo (``_warned``) sticks for the life
        of the instance: once a corrupt file degraded the cache, later
        ``get``s silently serve the empty memo even after the file on disk
        is repaired. Engine teardown (``ServeEngine.close`` /
        ``VisionEngine.close``) calls this so a second deploy sharing the
        cache object actually reloads the repaired file instead of
        re-tuning from scratch behind a stale warning flag."""
        self.entries = {}
        self._warned = False
        if self.path:
            self._load()

    # -- decisions ----------------------------------------------------------

    def get(self, key: str):
        e = self.entries.get(key)
        return e["decision"] if e else None

    def put(self, key: str, decision, mode: str = "cost"):
        self.entries[key] = {"decision": decision, "mode": mode}
        self.save()

    def __len__(self) -> int:
        return len(self.entries)

    # -- checkpoint round-trip (training.checkpoint extra dict) -------------

    def to_extra(self) -> dict:
        """JSON-clean payload for a checkpoint manifest's ``extra``."""
        return {"version": self.VERSION, "code_version": code_version(),
                "entries": {k: self._entry_to(e)
                            for k, e in self.entries.items()}}

    def merge_extra(self, extra: dict | None):
        """Merge a snapshot's decisions back (restore path). Version or
        code mismatches are dropped with the same single-warning fallback
        as a stale file — restored engines then re-tune from cost."""
        if not extra:
            return
        try:
            if extra.get("version") != self.VERSION:
                raise ValueError(f"schema version {extra.get('version')!r}")
            if extra.get("code_version") != code_version():
                raise ValueError("stale code_version")
            for k, v in extra["entries"].items():
                self.entries.setdefault(k, self._entry_from(v))
        except Exception as e:
            self._warn(f"snapshot entries unusable ({e!r})")
        else:
            self.save()


def as_cache(cache) -> TuningCache:
    """Coerce an engine's ``tuning_cache`` argument (path | TuningCache |
    None) into a TuningCache instance."""
    if isinstance(cache, TuningCache):
        return cache
    return TuningCache(cache)
