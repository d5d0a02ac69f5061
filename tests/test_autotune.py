"""Autotuner (repro.pim.autotune): backend-equivalence matrix, tuning-cache
robustness, checkpoint round-trip, and engine integration.

The core contract: tuning redirects *dispatch only*. Whatever backend and
tiles the autotuner picks, the integer product P is bit-identical (mod
2^32) to every backend it didn't pick — asserted across the full candidate
set including prime-N and bn%128≠0 shapes. The cache is fail-safe: any
unusable file (corrupt, truncated, stale schema or kernel version)
degrades to fresh cost-model picks with one warning — never a crash,
never a per-call retune storm.
"""
import dataclasses
import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bitserial import int_matmul_prepacked
from repro.core.packed import PackedWeight, TuneDecision, prepack
from repro.pim import autotune as at

# Deliberately awkward shapes: prime K/N, N below one lane group, N just
# over the popcount column chunk (bn % 128 != 0 on the pallas path).
SHAPES = [(4, 64, 128), (5, 67, 33), (8, 96, 130)]
BITS = [2, 4, 8]


def _operands(m, k, n, bits, seed=0):
    key = jax.random.PRNGKey(seed)
    qa = jax.random.randint(key, (m, k), 0, 2 ** bits, jnp.int32)
    pk = prepack(jax.random.normal(jax.random.fold_in(key, 1), (k, n)), bits)
    return qa, pk


# ---------------------------------------------------------------------------
# Backend-equivalence matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", SHAPES)
@pytest.mark.parametrize("bits", BITS)
def test_autotuned_output_bit_identical_across_candidates(m, k, n, bits):
    """Every candidate decision — all four backends, every legalized pallas
    tile — computes the identical P; the pick can affect speed only."""
    qa, pk = _operands(m, k, n, bits)
    ref = np.asarray(int_matmul_prepacked(qa, pk, bits, "popcount"))
    cands = at.gemm_candidates(m, k, n, bits, bits, backends=at.ALL_BACKENDS)
    assert {d.backend for d in cands} == set(at.ALL_BACKENDS)
    for d in cands:
        out = np.asarray(int_matmul_prepacked(qa, at.attach(pk, d), bits))
        assert np.array_equal(ref, out), f"backend mismatch for {d}"


def test_decision_overrides_config_backend():
    """An attached decision wins over the call-site backend argument."""
    qa, pk = _operands(4, 64, 128, 4)
    tuned = at.attach(pk, TuneDecision(backend="int-direct"))
    ref = np.asarray(int_matmul_prepacked(qa, pk, 4, "popcount"))
    out = np.asarray(int_matmul_prepacked(qa, tuned, 4, "popcount"))
    assert np.array_equal(ref, out)
    assert tuned.tune.backend == "int-direct"


def test_decision_is_static_metadata():
    """Attaching a decision changes no leaves — shardings, donation and
    checkpoint layouts are untouched; only the treedef differs."""
    _, pk = _operands(4, 64, 128, 4)
    tuned = at.attach(pk, TuneDecision(backend="pallas", bm=8, bn=128))
    for a, b in zip(jax.tree_util.tree_leaves(pk),
                    jax.tree_util.tree_leaves(tuned)):
        assert a is b
    assert (jax.tree_util.tree_structure(pk)
            != jax.tree_util.tree_structure(tuned))


# ---------------------------------------------------------------------------
# Cache robustness
# ---------------------------------------------------------------------------

def _count_ranks(monkeypatch):
    calls = {"n": 0}
    real = at.gemm_candidates

    def counted(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(at, "gemm_candidates", counted)
    return calls


@pytest.mark.parametrize("blob", [
    "{ this is not json",                       # corrupt
    '{"version": 1, "code_version": "x", "ent', # truncated
    json.dumps({"version": 99, "code_version": "x", "entries": {}}),  # schema
    json.dumps({"version": 1, "code_version": "stale", "entries": {}}),
])
def test_unusable_cache_falls_back_with_single_warning(tmp_path, blob,
                                                       monkeypatch):
    path = tmp_path / "tune.json"
    path.write_text(blob)
    calls = _count_ranks(monkeypatch)
    with pytest.warns(RuntimeWarning, match="falling back to cost-model"):
        cache = at.TuningCache(str(path))
    # Fallback picks still happen — and each key ranks exactly once (the
    # in-memory memo absorbs repeats: no retune storm after a bad load).
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # a second warning would fail
        d1 = at.decide_gemm(4, 64, 128, 4, 4, cache=cache,
                            hlo_tiebreak=False)
        for _ in range(5):
            assert at.decide_gemm(4, 64, 128, 4, 4, cache=cache,
                                  hlo_tiebreak=False) == d1
    assert calls["n"] == 1
    # The next save self-heals the file: a fresh cache loads it cleanly.
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        fresh = at.TuningCache(str(path))
    assert fresh.get(at.gemm_key(4, 64, 128, 4, 4, at.XLA_BACKENDS)) == d1


def test_cache_persists_and_round_trips(tmp_path):
    path = str(tmp_path / "tune.json")
    c1 = at.TuningCache(path)
    d = at.decide_gemm(8, 96, 130, 8, 8, cache=c1, hlo_tiebreak=False)
    c2 = at.TuningCache(path)
    assert c2.get(at.gemm_key(8, 96, 130, 8, 8, at.XLA_BACKENDS)) == d
    blob = json.load(open(path))
    assert blob["version"] == at.TuningCache.VERSION
    assert blob["code_version"] == at.code_version()


def test_cache_checkpoint_round_trip(tmp_path):
    """Decisions survive training/checkpoint.py's manifest extra dict."""
    from repro.training import checkpoint as ckpt

    cache = at.TuningCache(None)
    d = at.decide_gemm(4, 64, 128, 4, 4, cache=cache, hlo_tiebreak=False)
    tree = {"w": jnp.zeros((2, 2))}
    ckpt.save(str(tmp_path), 0, tree, extra={"tuning": cache.to_extra()})
    _, manifest = ckpt.restore(str(tmp_path), tree)
    fresh = at.TuningCache(None)
    fresh.merge_extra(manifest["extra"]["tuning"])
    assert fresh.get(at.gemm_key(4, 64, 128, 4, 4, at.XLA_BACKENDS)) == d


def test_reset_reloads_repaired_file_and_rearms_warning(tmp_path):
    """The single-warning fallback memo used to stick for the instance
    lifetime: a cache that degraded on a corrupt file kept serving the
    empty memo — silently — even after the file on disk was repaired.
    ``reset()`` drops the memo and re-reads the backing file."""
    path = str(tmp_path / "tune.json")
    good = at.TuningCache(path)
    d = at.decide_gemm(4, 64, 128, 4, 4, cache=good, hlo_tiebreak=False)
    key = at.gemm_key(4, 64, 128, 4, 4, at.XLA_BACKENDS)
    blob = open(path).read()

    open(path, "w").write("{ corrupt")
    with pytest.warns(RuntimeWarning, match="falling back"):
        cache = at.TuningCache(path)
    assert cache.get(key) is None and cache._warned

    open(path, "w").write(blob)        # repair on disk
    assert cache.get(key) is None      # stale memo: still empty, still silent
    cache.reset()
    assert cache.get(key) == d         # repaired file actually reloaded
    assert not cache._warned           # and the fallback warning is re-armed


def test_engine_close_resets_shared_cache(tmp_path):
    """Engine teardown resets its tuning cache, so a second deploy sharing
    the cache object reloads the (self-healed) backing file instead of
    serving the stale degraded memo."""
    from repro.serving import ServeEngine

    path = str(tmp_path / "tune.json")
    open(path, "w").write("{ corrupt")
    with pytest.warns(RuntimeWarning, match="falling back"):
        cache = at.TuningCache(path)
    cfg, params = _lm_setup()
    eng = ServeEngine(cfg, params, max_batch=2, max_len=32,
                      autotune="cost", tuning_cache=cache)
    assert eng.tune_cache is cache
    assert len(cache) > 0              # tuning self-healed the file on save
    eng.close()
    assert not cache._warned           # close() re-armed the fallback path
    assert len(cache) > 0              # reload picked up the healed file


def test_stale_snapshot_extra_dropped_with_warning():
    cache = at.TuningCache(None)
    with pytest.warns(RuntimeWarning, match="falling back"):
        cache.merge_extra({"version": 1, "code_version": "stale",
                           "entries": {}})
    assert len(cache) == 0


def test_measure_mode_uses_injected_measurer():
    times = {"popcount": 3.0, "mxu-plane": 2.0, "int-direct": 1.0}
    d = at.decide_gemm(8, 256, 256, 4, 4, mode="measure",
                       measure=lambda dec, *a: times[dec.backend],
                       hlo_tiebreak=False)
    assert d.backend == "int-direct"
    # A measurer that fails everywhere degrades to the analytic pick.
    d2 = at.decide_gemm(8, 256, 256, 4, 4, mode="measure",
                        measure=lambda dec, *a: None, hlo_tiebreak=False)
    assert d2.backend in at.XLA_BACKENDS


# ---------------------------------------------------------------------------
# Engine integration
# ---------------------------------------------------------------------------

def _lm_setup():
    from repro.core.pim_layers import PIMQuantConfig
    from repro.models.lm import ModelConfig, init

    pim = PIMQuantConfig(w_bits=4, a_bits=4, backend="popcount",
                         enabled=True)
    cfg = ModelConfig(n_layers=2, d_model=32, n_heads=2, n_kv_heads=1,
                      d_ff=64, vocab=51, remat="none", dtype="float32",
                      pim=pim)
    return cfg, init(cfg, jax.random.PRNGKey(0))


def _decode_tokens(eng):
    from repro.serving import Request

    eng.submit(Request(rid=0, prompt=np.array([3, 1, 4, 1, 5], np.int32),
                       max_new_tokens=6))
    return eng.run()[0].tokens


def test_serve_engine_autotune_token_parity(tmp_path):
    from repro.serving import ServeEngine

    cfg, params = _lm_setup()
    base = _decode_tokens(ServeEngine(cfg, params, max_batch=2, max_len=64))
    path = str(tmp_path / "tune.json")
    eng = ServeEngine(cfg, params, max_batch=2, max_len=64,
                      autotune="cost", tuning_cache=path)
    assert _decode_tokens(eng) == base
    leaves = [l for l in jax.tree_util.tree_leaves(
        eng.params, is_leaf=lambda x: isinstance(x, PackedWeight))
        if isinstance(l, PackedWeight)]
    assert leaves and all(l.tune is not None for l in leaves)
    assert os.path.exists(path) and len(eng.tune_cache) > 0


def test_serve_engine_redeploy_retunes(tmp_path):
    from repro.serving import ServeEngine

    cfg, params = _lm_setup()
    eng = ServeEngine(cfg, params, max_batch=2, max_len=64,
                      autotune="cost", keep_masters=True)
    n0 = len(eng.tune_cache)
    eng.redeploy(dataclasses.replace(cfg.pim, w_bits=8, a_bits=8))
    assert len(eng.tune_cache) > n0      # new precision, new decisions
    leaves = [l for l in jax.tree_util.tree_leaves(
        eng.params, is_leaf=lambda x: isinstance(x, PackedWeight))
        if isinstance(l, PackedWeight)]
    assert all(l.tune is not None for l in leaves)


def test_serve_engine_snapshot_carries_tuning(tmp_path):
    from repro.serving import ServeEngine

    cfg, params = _lm_setup()
    eng = ServeEngine(cfg, params, max_batch=2, max_len=64, autotune="cost")
    eng.snapshot(str(tmp_path), step=1)
    eng2 = ServeEngine(cfg, params, max_batch=2, max_len=64,
                       autotune="cost")
    manifest = eng2.restore(str(tmp_path))
    assert "tuning" in manifest["extra"]
    assert len(eng2.tune_cache) >= len(eng.tune_cache)


@pytest.mark.parametrize("backends", [("pallas",), at.ALL_BACKENDS])
def test_stem_conv_tuned_to_im2col(backends):
    """ResNet's 7x7/2 stem on C = 3 channels: the fused kernel would fill 3
    bits of each 32-bit word (49 words against the im2col GEMM's 5), so it
    is no candidate and the tuner picks the im2col path, as
    fuse_conv_heuristic does; a 3x3 on C = 64 may still fuse."""
    conv, _ = at.decide_conv(16, 224, 224, 3, 64, 7, 7, stride=2, padding=3,
                             backends=backends)
    assert conv.conv_mode == "im2col"
    conv, _ = at.decide_conv(16, 56, 56, 64, 64, 3, 3, stride=1, padding=1,
                             backends=("pallas",))
    assert conv.conv_mode == "fused"


def test_vision_engine_autotune_parity(tmp_path):
    from repro.models.cnn import alexnet
    from repro.serving.vision import VisionEngine, VisionRequest

    key = jax.random.PRNGKey(0)
    params = alexnet.init(key, num_classes=10, image=64)
    imgs = [np.asarray(jax.random.normal(jax.random.fold_in(key, i),
                                         (64, 64, 3))) for i in range(4)]

    def run(engine):
        for i, im in enumerate(imgs):
            engine.submit(VisionRequest(rid=i, image=im, model="alexnet",
                                        precision="<4:4>"))
        return [c.logits for c in engine.run()]

    base = run(VisionEngine({"alexnet": params}, backend="int-direct",
                            max_batch=4))
    path = str(tmp_path / "tune.json")
    ve = VisionEngine({"alexnet": params}, backend="int-direct",
                      max_batch=4, autotune="cost", tuning_cache=path)
    got = run(ve)
    for a, b in zip(base, got):
        assert np.allclose(a, b, atol=1e-4)
    assert len(ve.tune_cache) > 0 and os.path.exists(path)
    assert ve._tuned                     # tuned tree derived at dispatch


# ---------------------------------------------------------------------------
# Mesh (tier1-mesh8 job)
# ---------------------------------------------------------------------------

needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@needs8
def test_tuned_picks_respect_mesh_sharding():
    """Autotuned serving on the (data=4, model=2) mesh: decisions exclude
    pallas (no GSPMD rule), the committed bank-split layouts are untouched,
    and decode tokens match the untuned mesh engine."""
    from repro.launch.mesh import make_serve_mesh
    from repro.serving import ServeEngine

    cfg, params = _lm_setup()
    mesh = make_serve_mesh(2)
    base = _decode_tokens(ServeEngine(cfg, params, max_batch=2, max_len=64,
                                      mesh=mesh))
    eng = ServeEngine(cfg, params, max_batch=2, max_len=64, mesh=mesh,
                      autotune="cost")
    assert _decode_tokens(eng) == base
    leaves = [l for l in jax.tree_util.tree_leaves(
        eng.params, is_leaf=lambda x: isinstance(x, PackedWeight))
        if isinstance(l, PackedWeight)]
    assert leaves
    for l in leaves:
        assert l.tune is not None and l.tune.backend != "pallas"
        # The decision wrapped the committed shards as-is: the planes
        # still carry their bank-split (or guarded-replicated) sharding.
        assert l.planes.sharding is not None
