"""Vision serving engine (DESIGN.md §6): bit-identity with direct
``model.apply``, power-of-two micro-batch bucketing, prepack-once caching,
and the mesh-sharded conv layout with its no-large-all-gather invariant.

Mesh-path coverage mirrors tests/test_serve_sharded.py: in-process tests
need a multi-device host (the mesh8 CI job), and an always-run subprocess
forces an 8-device world so the default tier-1 suite covers the sharded
vision path too.
"""
import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PIMQuantConfig
from repro.models.cnn import alexnet
from repro.models.cnn import layers as L
from repro.serving import VisionEngine, VisionRequest, parse_precision

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

needs2 = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


# -- a small CNN so quantized forwards stay cheap ---------------------------

def _mini_init(key, image=16, num_classes=16):
    return {
        "c1": L.init_conv(jax.random.fold_in(key, 0), 3, 3, 32),
        "c2": L.init_conv(jax.random.fold_in(key, 1), 3, 32, 64, bn=False),
        "head": L.init_fc(jax.random.fold_in(key, 2), 64, num_classes),
    }


def _mini_apply(params, x, cfg=None, train=False):
    x = L.conv_block(params["c1"], x, stride=1, padding=1, cfg=cfg, train=train)
    x = L.conv_block(params["c2"], x, stride=2, padding=1, cfg=cfg, train=train)
    x = L.avg_pool_global(x)
    return L.fc_block(params["head"], x, cfg=cfg, relu=False, train=train)


MINI = types.SimpleNamespace(init=_mini_init, apply=_mini_apply)


@pytest.fixture(scope="module")
def mini_params():
    return _mini_init(jax.random.PRNGKey(0))


def _images(n, image=16, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, image, image, 3)).astype(np.float32)


# -- bit-identity vs direct model.apply -------------------------------------

@pytest.mark.parametrize("backend", ["int-direct", "popcount"])
def test_engine_bit_identical_to_direct_apply_quantized(mini_params, backend):
    """A bucket's logits == jitted model.apply on the same stacked batch
    with the same PIMQuantConfig and the same prepacked weights (prepack is
    deterministic, so an independent prepack is the same tree)."""
    cfg = PIMQuantConfig(w_bits=4, a_bits=4, backend=backend)
    imgs = _images(4)
    eng = VisionEngine({"mini": (MINI, mini_params)}, backend=backend,
                       max_batch=4)
    for i in range(4):
        eng.submit(VisionRequest(rid=i, image=imgs[i], model="mini",
                                 precision="<4:4>"))
    got = {c.rid: c.logits for c in eng.run()}
    pk = L.prepack_params(mini_params, cfg)
    ref = jax.jit(lambda p, x: _mini_apply(p, x, cfg=cfg))(
        pk, jnp.asarray(imgs))
    for i in range(4):
        assert np.array_equal(got[i], np.asarray(ref[i]))


def test_engine_bit_identical_to_direct_apply_float(mini_params):
    """precision=None serves the float forward, bit-identical to jitted
    model.apply with cfg=None."""
    imgs = _images(4, seed=1)
    eng = VisionEngine({"mini": (MINI, mini_params)}, max_batch=4)
    for i in range(4):
        eng.submit(VisionRequest(rid=i, image=imgs[i], model="mini",
                                 precision=None))
    got = {c.rid: c.logits for c in eng.run()}
    ref = jax.jit(lambda p, x: _mini_apply(p, x, cfg=None))(
        mini_params, jnp.asarray(imgs))
    for i in range(4):
        assert np.array_equal(got[i], np.asarray(ref[i]))


def test_engine_zoo_model_bit_identical():
    """Zoo registry path (params-only, name resolved): alexnet through the
    engine == jitted alexnet.apply on the prepacked tree."""
    params = alexnet.init(jax.random.PRNGKey(0), image=64, num_classes=10)
    cfg = PIMQuantConfig(w_bits=8, a_bits=8, backend="int-direct")
    imgs = _images(2, image=64, seed=2)
    eng = VisionEngine({"alexnet": params}, max_batch=2)
    for i in range(2):
        eng.submit(VisionRequest(rid=i, image=imgs[i], model="alexnet",
                                 precision="<8:8>"))
    got = {c.rid: c.logits for c in eng.run()}
    ref = jax.jit(lambda p, x: alexnet.apply(p, x, cfg=cfg))(
        alexnet.prepack(params, cfg), jnp.asarray(imgs))
    for i in range(2):
        assert np.array_equal(got[i], np.asarray(ref[i]))


# -- micro-batching ----------------------------------------------------------

def test_pow2_bucketing_and_bounded_compiles(mini_params):
    """6 queued -> buckets of 4 and 2; a varied load compiles at most
    log2(max_batch)+1 forward variants per (model, precision)."""
    eng = VisionEngine({"mini": (MINI, mini_params)}, max_batch=4)
    imgs = _images(6, seed=3)
    for i in range(6):
        eng.submit(VisionRequest(rid=i, image=imgs[i], model="mini",
                                 precision="<4:4>"))
    done = eng.run()
    buckets = [c.batch for c in sorted(done, key=lambda c: c.rid)]
    assert buckets == [4, 4, 4, 4, 2, 2]
    assert sorted(b for (_, _, b) in eng._fwd) == [2, 4]
    # same-shaped traffic reuses the compiled variants
    for i in range(6):
        eng.submit(VisionRequest(rid=10 + i, image=imgs[i], model="mini",
                                 precision="<4:4>"))
    eng.run()
    assert sorted(b for (_, _, b) in eng._fwd) == [2, 4]


def test_conv_lowering_counted_per_compiled_forward(mini_params):
    """stats()["counters"]["conv_lowering"] counts the convs of each
    compiled quantized forward by lowering, once, as the forward is traced:
    later dispatches of the same program add nothing, and float forwards
    lower no quantized conv."""
    eng = VisionEngine({"mini": (MINI, mini_params)}, max_batch=4)
    imgs = _images(6, seed=3)
    for rnd in range(2):       # the second round reuses both programs
        for i in range(6):
            eng.submit(VisionRequest(rid=10 * rnd + i, image=imgs[i],
                                     model="mini", precision="<4:4>"))
        eng.submit(VisionRequest(rid=100 + rnd, image=imgs[0], model="mini",
                                 precision=None))
        eng.run()
        # buckets of 4 and 2, each compiled once: two convs apiece
        want = {"fused": 0, "im2col": 4, "pointwise": 0}
        ctr = eng.stats()["counters"]
        assert ctr["conv_lowering"] == want
    ctr["conv_lowering"]["im2col"] = 0                     # a copy
    assert eng.stats()["counters"]["conv_lowering"] == want


def test_mixed_precision_cohorts_group_separately(mini_params):
    """Interleaved precisions serve in per-(model, precision) buckets."""
    eng = VisionEngine({"mini": (MINI, mini_params)}, max_batch=8)
    imgs = _images(8, seed=4)
    precs = ["<4:4>", "<8:8>", "<4:4>", None, "<4:4>", "<8:8>", "<4:4>", None]
    for i in range(8):
        eng.submit(VisionRequest(rid=i, image=imgs[i], model="mini",
                                 precision=precs[i]))
    done = {c.rid: c for c in eng.run()}
    assert len(done) == 8
    # the 4-strong <4:4> cohort rides one bucket of 4; the pairs ride 2s
    assert [done[i].batch for i in (0, 2, 4, 6)] == [4, 4, 4, 4]
    assert [done[i].batch for i in (1, 5)] == [2, 2]
    assert [done[i].batch for i in (3, 7)] == [2, 2]


def test_prepack_exactly_once_per_model_cfg(mini_params, monkeypatch):
    """Repeated buckets of one (model, precision) quantize+pack weights
    exactly once — the paper's program-subarrays-once property."""
    from repro.serving import vision as V

    calls = []
    orig = V._prepack_cnn
    monkeypatch.setattr(V, "_prepack_cnn",
                        lambda p, cfg: (calls.append(1), orig(p, cfg))[1])
    eng = VisionEngine({"mini": (MINI, mini_params)}, max_batch=2)
    imgs = _images(6, seed=5)
    for i in range(6):
        eng.submit(VisionRequest(rid=i, image=imgs[i], model="mini",
                                 precision="<4:4>"))
    eng.run()
    assert len(calls) == 1
    # a second precision packs its own tree, again exactly once
    for i in range(4):
        eng.submit(VisionRequest(rid=10 + i, image=imgs[i], model="mini",
                                 precision="<8:8>"))
    eng.run()
    assert len(calls) == 2


# -- admission validation ----------------------------------------------------

def test_admission_validation(mini_params):
    eng = VisionEngine({"mini": (MINI, mini_params)})
    with pytest.raises(ValueError, match="unknown model"):
        eng.submit(VisionRequest(rid=0, image=_images(1)[0], model="nope"))
    with pytest.raises(ValueError, match="precision"):
        eng.submit(VisionRequest(rid=0, image=_images(1)[0], model="mini",
                                 precision="8x8"))
    assert parse_precision("<8:4>") == (8, 4)
    assert parse_precision(None) is None
    with pytest.raises(ValueError, match="unknown model"):
        VisionEngine({"not-in-zoo": mini_params})


def test_pallas_backend_rejected_on_mesh(mini_params):
    """pallas_call has no GSPMD rule — the engine must refuse it with a
    mesh instead of silently all-gathering the split planes per bucket."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices (mesh8 CI job)")
    from repro.launch.mesh import make_serve_mesh

    with pytest.raises(ValueError, match="pallas"):
        VisionEngine({"mini": (MINI, mini_params)}, backend="pallas",
                     mesh=make_serve_mesh(2))


# -- mesh-sharded path (multi-device host) ----------------------------------

@needs2
def test_shard_packed_conv_layout(mini_params):
    """PackedConvWeight shards on the bank (output-channel) mapping: mat
    planes/codes/col_sums on N, fused_planes on O; split='k' is rejected."""
    from jax.sharding import PartitionSpec as P

    from repro.core.packed import prepack_conv, shard_packed
    from repro.launch.mesh import make_serve_mesh

    mesh = make_serve_mesh(2)
    w = jax.random.normal(jax.random.PRNGKey(6), (3, 3, 16, 32))
    pk = prepack_conv(w, 4)
    pks = shard_packed(pk, mesh, axis="model", split="n")
    assert pks.fused_planes.sharding.spec == P(None, None, None, None, "model")
    assert pks.mat.planes.sharding.spec == P(None, "model", None)
    assert pks.mat.codes.sharding.spec == P(None, "model")
    assert pks.mat.col_sums.sharding.spec == P("model")
    assert np.array_equal(np.asarray(pks.to_float()), np.asarray(pk.to_float()))
    with pytest.raises(ValueError, match="split"):
        shard_packed(pk, mesh, split="k")


@needs2
def test_serve_cnn_param_shardings_rules(mini_params):
    """Quantized trees split every weight representation and the per-channel
    epilogue vectors on "model"; float trees replicate (DP-only serving)."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as sh
    from repro.launch.mesh import make_serve_mesh

    mesh = make_serve_mesh(2)
    cfg = PIMQuantConfig(w_bits=4, a_bits=4, backend="int-direct")
    pk = L.prepack_params(mini_params, cfg)
    shardings = sh.serve_cnn_param_shardings(pk, mesh, quantized=True)
    assert shardings["c1"]["w"].fused_planes.spec == \
        P(None, None, None, None, "model")
    assert shardings["c1"]["w"].mat.planes.spec == P(None, "model", None)
    assert shardings["c1"]["gamma"].spec == P("model")
    assert shardings["head"]["w"].planes.spec == P(None, "model", None)
    flt = sh.serve_cnn_param_shardings(mini_params, mesh, quantized=False)
    assert all(s.spec == P() for s in jax.tree.leaves(flt))


@needs2
@pytest.mark.parametrize("backend,precision", [
    ("int-direct", "<4:4>"), ("popcount", "<4:4>"), ("int-direct", None)])
def test_mesh_engine_matches_direct_apply_and_single_device(
        mini_params, backend, precision):
    """On the mesh the serving machinery stays numerics-transparent: bucket
    logits are bit-identical to direct jitted ``model.apply`` under the
    same deployment shardings. Across device topologies, the float path
    (fully replicated) stays bit-identical to the single-device engine; the
    quantized paths' integer core is partition-exact but their float
    dequantization epilogue is compiled with topology-dependent FMA
    contraction (ULP-level), so cross-topology parity there is top-1 plus
    allclose — same contract as the LM engine's token-level parity."""
    from repro.distributed import sharding as sh
    from repro.launch.mesh import make_serve_mesh

    imgs = _images(8, seed=7)

    def run(mesh):
        eng = VisionEngine({"mini": (MINI, mini_params)}, backend=backend,
                           max_batch=8, mesh=mesh)
        for i in range(8):
            eng.submit(VisionRequest(rid=i, image=imgs[i], model="mini",
                                     precision=precision))
        return eng, {c.rid: c.logits for c in eng.run()}

    mesh = make_serve_mesh(2)
    eng, shard = run(mesh)
    assert sh.get_mesh() is None, "engine leaked its mesh into global state"
    assert not sh.get_cnn_serve_layout(), "engine leaked the CNN layout flag"

    # direct model.apply, jitted under the engine's deployment shardings —
    # bit-identical: batching/caching/donation add no numerics.
    cfg = eng._cfg(precision)
    quantized = cfg is not None
    params = eng._packed_params("mini", precision)
    if quantized:
        batch_sh = sh.serve_cnn_batch_sharding(mesh, 8)
        out_sh = sh.serve_cnn_logits_sharding(mesh, 8)
    else:
        batch_sh = out_sh = sh.replicated(mesh)
    with eng._activate(quantized):
        ref = jax.jit(lambda p, x: _mini_apply(p, x, cfg=cfg),
                      in_shardings=(eng._param_sh[("mini", precision)],
                                    batch_sh),
                      out_shardings=out_sh)(
            params, jax.device_put(jnp.asarray(imgs), batch_sh))
    ref = np.asarray(ref)
    for i in range(8):
        assert np.array_equal(shard[i], ref[i]), (backend, precision, i)

    _, plain = run(None)
    for i in range(8):
        if precision is None:
            assert np.array_equal(shard[i], plain[i]), i
        else:
            assert np.argmax(shard[i]) == np.argmax(plain[i]), i
            np.testing.assert_allclose(shard[i], plain[i], rtol=1e-4,
                                       atol=1e-3)


# -- the no-resharding HLO invariant ----------------------------------------
#
# The gather-size regex that used to live here is now
# repro.analysis.hlo.gather_sizes, and the bound/no-all-to-all assertions
# are the `collective-budget` rule run over the engine's own HotPath
# declarations — one implementation shared with the CI lint gate.


@needs2
def test_cnn_forward_hlo_no_large_gather(mini_params):
    """The bucket forward keeps weights resident: the only cross-shard
    movement is the activation-map redistribution between bank-split convs
    (the paper's transfer phase). Nothing patch-matrix- or weight-sized
    gathers, and there is no all-to-all. The float forward is fully
    replicated — zero all-gathers."""
    from repro import analysis
    from repro.analysis import hlo
    from repro.launch.mesh import make_serve_mesh

    mesh = make_serve_mesh(2)
    eng = VisionEngine({"mini": (MINI, mini_params)}, backend="int-direct",
                       max_batch=8, mesh=mesh)
    try:
        hps = eng.hot_paths(shapes={("mini", "<4:4>", 8): (16, 16, 3),
                                    ("mini", None, 8): (16, 16, 3)})
        caps = {hp.name: hp.budget.max_gather_bytes for hp in hps}
        # float path declares full replication: zero gathers allowed
        assert caps["cnn.fwd[mini,float,b=8]"] == 0
        # quantized budget = one activation map at the widest channel count
        # (c2's 64 outputs); the 9x-larger patch matrix is far beyond it
        assert caps["cnn.fwd[mini,<4:4>,b=8]"] == 4 * 8 * 16 * 16 * 64
        viols = analysis.lint_hot_paths(hps)
        assert not viols, analysis.format_report(viols)

        # the executed program in fact stays within the tighter regime of
        # c2's 32-channel *input* map — check via the shared size parser
        act_bytes = 4 * 8 * 16 * 16 * 32
        quant = next(hp for hp in hps if "<4:4>" in hp.name)
        sizes = hlo.gather_sizes(quant.programs[0].compiled_text())
        assert all(s <= act_bytes for s in sizes), \
            f"gather larger than an activation map: {sorted(sizes)[-3:]}"
    finally:
        eng.close()


# -- always-run subprocess coverage -----------------------------------------

_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, numpy as np, jax.numpy as jnp
from repro import analysis
from repro.distributed import sharding as sh
from repro.launch.mesh import make_serve_mesh
from tests.test_vision_engine import MINI, _images, _mini_init
from repro.serving import VisionEngine, VisionRequest

params = _mini_init(jax.random.PRNGKey(0))
imgs = _images(8, seed=7)

def run(mesh, backend, precision):
    eng = VisionEngine({"mini": (MINI, params)}, backend=backend,
                       max_batch=8, mesh=mesh)
    for i in range(8):
        eng.submit(VisionRequest(rid=i, image=imgs[i], model="mini",
                                 precision=precision))
    return eng, {c.rid: c.logits for c in eng.run()}

out = {"parity": {}, "violations": [], "leak": False}
mesh = make_serve_mesh(2)
for backend, prec in [("int-direct", "<4:4>"), ("popcount", "<4:4>"),
                      ("int-direct", None)]:
    eng, shard = run(mesh, backend, prec)
    out["leak"] = out["leak"] or sh.get_mesh() is not None
    cfg = eng._cfg(prec)
    quantized = cfg is not None
    tree = eng._packed_params("mini", prec)   # do NOT shadow global params
    if quantized:
        batch_sh = sh.serve_cnn_batch_sharding(mesh, 8)
        out_sh = sh.serve_cnn_logits_sharding(mesh, 8)
    else:
        batch_sh = out_sh = sh.replicated(mesh)
    with eng._activate(quantized):
        ref = jax.jit(lambda p, x: MINI.apply(p, x, cfg=cfg),
                      in_shardings=(eng._param_sh[("mini", prec)], batch_sh),
                      out_shardings=out_sh)(
            tree, jax.device_put(jnp.asarray(imgs), batch_sh))
    ref = np.asarray(ref)
    _, plain = run(None, backend, prec)
    # engine == direct apply under the same shardings, bitwise; across
    # topologies float is bitwise, quantized is top1 + allclose (the int
    # core is partition-exact; the dequant epilogue is FMA-sensitive).
    cross = (all(np.array_equal(shard[i], plain[i]) for i in range(8))
             if prec is None else
             all(np.argmax(shard[i]) == np.argmax(plain[i])
                 and np.allclose(shard[i], plain[i], rtol=1e-4, atol=1e-3)
                 for i in range(8)))
    out["parity"][f"{backend}/{prec}"] = cross and all(
        np.array_equal(shard[i], ref[i]) for i in range(8))

# lint every dispatched bucket of the sharded engine with the shared
# collective-budget rule (gather bound + no all-to-all)
eng, _ = run(mesh, "int-direct", "<4:4>")
viols = analysis.lint_hot_paths(eng.hot_paths(),
                                rules=("collective-budget",))
out["violations"] = [str(v) for v in viols]
print(json.dumps(out))
"""


def test_mesh_vision_subprocess():
    """Tier-1 coverage without a multi-device parent: force 8 host devices
    in a child and check bit-parity (int-direct, popcount, float) plus the
    collective-budget invariant."""
    env = dict(os.environ, PYTHONPATH="src" + os.pathsep + ".",
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SUBPROC],
                         capture_output=True, text=True, env=env, cwd=_REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert not res["leak"], "engine leaked its mesh"
    assert all(res["parity"].values()), res["parity"]
    assert not res["violations"], res["violations"]
