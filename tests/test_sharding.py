"""Sharding rules + a miniature multi-device dry-run.

The production 512-device dry-run lives in repro.launch.dryrun (and its
results in results/dryrun/). Here we verify the *rules*: spec construction,
divisibility guards, MoE expert-vs-ffn fallback, and an actual 8-device
lower+compile in a subprocess (the main test process must stay at 1 device
so smoke tests see an unsharded world)."""
import json
import os
import subprocess
import sys

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed import sharding as sh
from repro.models.lm import abstract_params


def _mesh_stub(shape, names):
    """A Mesh over 1 real device can't have size>1 — use jax.sharding.Mesh
    abstract construction via AbstractMesh for spec-only tests."""
    return jax.sharding.AbstractMesh(
        shape, names, axis_types=(jax.sharding.AxisType.Auto,) * len(names))


def test_param_specs_dense():
    mesh = _mesh_stub((16, 16), ("data", "model"))
    cfg = get_config("llama3.2-3b").model
    tree = abstract_params(cfg)
    # llama3.2-3b ties embeddings: vocab stays on the TP axis (lm_head use)
    sh.set_tied_embeddings(True)
    specs = jax.tree_util.tree_map_with_path(
        lambda p, l: sh._param_spec(p, l, mesh, None), tree)
    assert specs["embed"] == P("model", "data")
    # untied models shard vocab on FSDP only (cheap token gather)
    sh.set_tied_embeddings(False)
    specs = jax.tree_util.tree_map_with_path(
        lambda p, l: sh._param_spec(p, l, mesh, None), tree)
    assert specs["embed"] == P(None, "data")
    blk = specs["scan"][0]
    assert blk["attn"]["wq"] == P(None, "data", "model")
    assert blk["attn"]["wo"] == P(None, "model", "data")
    assert blk["ffn"]["w_in"] == P(None, "data", "model")
    assert blk["ffn"]["w_out"] == P(None, "model", "data")
    assert blk["norm1"]["scale"] == P(None, None)  # stacked, replicated


def test_param_specs_moe_expert_parallel_vs_tp():
    mesh = _mesh_stub((16, 16), ("data", "model"))
    phi = get_config("phi3.5-moe-42b-a6.6b").model   # 16 experts: EP
    tree = jax.tree_util.tree_map_with_path(
        lambda p, l: sh._param_spec(p, l, mesh, None), abstract_params(phi))
    assert tree["scan"][0]["ffn"]["w_in"] == P(None, "model", "data", None)
    grok = get_config("grok-1-314b").model            # 8 experts: TP inside
    tree = jax.tree_util.tree_map_with_path(
        lambda p, l: sh._param_spec(p, l, mesh, None), abstract_params(grok))
    assert tree["scan"][0]["ffn"]["w_in"] == P(None, None, "data", "model")


def test_divisibility_guard_drops_axes():
    mesh = _mesh_stub((16, 16), ("data", "model"))
    # vocab 49155 = 3*5*29*113 is not divisible by 16 -> replicated
    cfg = get_config("granite-3-2b").model
    tree = abstract_params(cfg)
    specs = jax.tree_util.tree_map_with_path(
        lambda p, l: sh._param_spec(p, l, mesh, None), tree)
    assert specs["embed"] == P(None, "data")


def test_divisibility_guard_warns_once(caplog):
    """A dropped rule axis must be visible (warn), but exactly once per
    (leaf, axis, dim) — the guard runs per tree leaf, so an unthrottled
    warning would flood a misconfigured-mesh launch."""
    mesh = _mesh_stub((16, 16), ("data", "model"))
    sh.reset_drop_warnings()
    with caplog.at_level("WARNING", logger="repro.distributed.sharding"):
        spec = sh._guard(("model",), (61,), mesh, label="serve-param:head")
        assert spec == P(None)
        sh._guard(("model",), (61,), mesh, label="serve-param:head")  # dup
    drops = [r for r in caplog.records if "dropping to replication" in r.message]
    assert len(drops) == 1, [r.message for r in drops]
    assert "serve-param:head" in drops[0].message
    with caplog.at_level("WARNING", logger="repro.distributed.sharding"):
        caplog.clear()
        # axis of size 1 (or absent) is not a misconfiguration: no warning
        sh._guard(("model",), (61,), _mesh_stub((16, 1), ("data", "model")),
                  label="x")
        sh._guard(("missing",), (61,), mesh, label="x")
    assert not [r for r in caplog.records
                if "dropping to replication" in r.message]
    sh.reset_drop_warnings()


def test_multipod_fsdp_spans_pods():
    mesh = _mesh_stub((2, 16, 16), ("pod", "data", "model"))
    cfg = get_config("llama3.2-3b").model
    specs = jax.tree_util.tree_map_with_path(
        lambda p, l: sh._param_spec(p, l, mesh, None), abstract_params(cfg))
    assert specs["scan"][0]["attn"]["wq"] == P(None, ("pod", "data"), "model")


def test_batch_spec_fallbacks():
    mesh = _mesh_stub((16, 16), ("data", "model"))
    assert sh.batch_spec(mesh, 256) == P(("data",), None)
    assert sh.batch_spec(mesh, 1) == P(None, None)   # long_500k B=1


_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, json
from functools import partial
from repro.configs import get_config
from repro.distributed import sharding as sh
from repro.models.lm import init as minit, loss_fn
from repro.models.lm.model import cast_params
from repro.training.optimizer import OptimizerConfig, init_opt_state
from repro.training.train_loop import make_train_step

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
sh.set_mesh(mesh)
cfg = get_config("qwen3-0.6b").model.reduced(vocab=512, d_model=128)
params = cast_params(minit(cfg, jax.random.PRNGKey(0)), jnp.bfloat16)
p_sh = sh.param_shardings(params, mesh)
params = jax.device_put(params, p_sh)
ocfg = OptimizerConfig(warmup_steps=1, total_steps=10)
opt = init_opt_state(ocfg, params)
o_sh = sh.param_shardings(opt, mesh); o_sh["step"] = sh.replicated(mesh)
opt = jax.device_put(opt, o_sh)
batch = {"tokens": jnp.zeros((8, 32), jnp.int32),
         "labels": jnp.zeros((8, 32), jnp.int32)}
b_sh = sh.batch_shardings(batch, mesh, 8)
batch = jax.device_put(batch, b_sh)
step = jax.jit(make_train_step(cfg, ocfg), in_shardings=(p_sh, o_sh, b_sh),
               out_shardings=(p_sh, o_sh, None), donate_argnums=(0, 1))
params, opt, m = step(params, opt, batch)
params, opt, m = step(params, opt, batch)
print(json.dumps({"loss": float(m["loss"]), "ok": bool(jnp.isfinite(m["loss"]))}))
"""


def test_real_8device_sharded_train_step():
    """End-to-end sharded train step on an actual 4x2 CPU mesh."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _SUBPROC], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ok"], res


def test_dryrun_results_exist_and_pass():
    """The committed dry-run artifacts cover all 40 cells on both meshes."""
    d = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "results", "dryrun")
    if not os.path.isdir(d) or len(os.listdir(d)) < 80:
        pytest.skip("dry-run sweep not yet complete")
    bad = []
    for f in os.listdir(d):
        if not f.endswith(".json"):
            continue
        with open(os.path.join(d, f)) as fh:
            r = json.load(fh)
        if "skipped" not in r and "roofline" not in r:
            bad.append(f)
    assert not bad, bad
