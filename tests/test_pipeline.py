"""Pipeline parallelism: the fill-drain schedule equals sequential stage
application, and the pipelined decode path is a bit-exact drop-in for
``decode_step`` on per-example-independent (dense float) models. The
multi-device cases run on a real 4-device CPU mesh in a subprocess (the
main test process stays single-device)."""
import json
import os
import subprocess
import sys

import pytest


def _run(script: str) -> dict:
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, json
from repro.distributed.pipeline import pipeline_forward, split_stages
from repro.models.lm.config import ModelConfig
from repro.models.lm.model import init, layer_plan, apply_block

cfg = ModelConfig(n_layers=8, d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                  vocab=61, remat="none", dtype="float32")
params = init(cfg, jax.random.PRNGKey(0))
unit, reps, rest = layer_plan(cfg)
assert reps == 8 and not rest

mesh = jax.make_mesh((4,), ("stage",),
                     axis_types=(jax.sharding.AxisType.Auto,))
res = {}
for M in (6, 2):       # M=2 < S=4: the pipe never fully fills
    mb, S, D = 2, 16, cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, S, D)) * 0.3
    q_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (mb, S))

    def stage_fn(p_slice, xb):
        def unit_fn(xc, p_list):
            for j, kind in enumerate(unit):
                xc, _, _ = apply_block(kind, p_list[j], cfg, xc, q_pos)
            return xc, None
        xb, _ = jax.lax.scan(unit_fn, xb, p_slice)
        return xb

    def ref_apply(xb):
        return stage_fn(jax.tree.map(lambda l: l, params["scan"]), xb)

    ref = jax.vmap(ref_apply)(x)
    stage_params = split_stages(params["scan"], 4)
    got = pipeline_forward(stage_params, x, stage_fn, mesh)
    res[f"rel_err_M{M}"] = float(
        jnp.abs(got - ref).max() / (jnp.abs(ref).max() + 1e-9))
print(json.dumps(res))
"""


def test_pipeline_matches_sequential():
    res = _run(_SUBPROC)
    assert res["rel_err_M6"] < 1e-5, res
    assert res["rel_err_M2"] < 1e-5, res   # M < S: fill-drain only


def test_split_stages_non_divisible_raises():
    import jax.numpy as jnp

    from repro.distributed.pipeline import split_stages

    with pytest.raises(ValueError, match="do not factor"):
        split_stages({"w": jnp.zeros((8, 3))}, 3)


_DECODE_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import json
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.distributed.pipeline import pipeline_decode_step
from repro.models.lm import model as M
from repro.models.lm.config import ModelConfig

cfg = ModelConfig(n_layers=8, d_model=128, n_heads=4, n_kv_heads=4,
                  head_dim=32, d_ff=256, vocab=512, dtype="float32",
                  remat="none")
key = jax.random.PRNGKey(0)
params = M.init(cfg, key)
B, L = 8, 32
toks = jax.random.randint(jax.random.fold_in(key, 1), (B, 1), 0, cfg.vocab,
                          jnp.int32)
lg0, st0 = jax.jit(M.decode_step, static_argnums=1)(
    params, cfg, toks, M.init_state(cfg, B, L))
mesh = Mesh(np.asarray(jax.devices()[:4]), ("stage",))
res = {}
# bit-parity at M == S and M < S (fewer microbatches than stages)
for n_micro in (4, 2):
    lg1, st1 = pipeline_decode_step(params, cfg, toks, M.init_state(cfg, B, L),
                                    mesh=mesh, n_stages=4,
                                    n_microbatch=n_micro)
    eq = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), st0, st1)
    res[f"logits_bitwise_m{n_micro}"] = bool(jnp.array_equal(lg0, lg1))
    res[f"state_bitwise_m{n_micro}"] = all(jax.tree.leaves(eq))
try:
    pipeline_decode_step(params, cfg, toks, M.init_state(cfg, B, L),
                         mesh=mesh, n_stages=3)
    res["raises"] = False
except ValueError as e:
    res["raises"] = "do not factor" in str(e)
print(json.dumps(res))
"""


def test_pipeline_decode_bit_parity():
    """Pipelined decode == sequential decode bitwise on a dense float
    model (microbatching only slices the batch axis), including the
    M < S fill-drain-only schedule; non-factoring depth raises."""
    res = _run(_DECODE_SUBPROC)
    assert res["logits_bitwise_m4"] and res["state_bitwise_m4"], res
    assert res["logits_bitwise_m2"] and res["state_bitwise_m2"], res
    assert res["raises"] is True, res


_ENGINE_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import json
import numpy as np
import jax
from repro.analysis import hlo
from repro.analysis.rules import run_rules
from repro.models.lm import model as M
from repro.models.lm.config import ModelConfig
from repro.serving import Request, SamplerConfig, ServeEngine

cfg = ModelConfig(n_layers=8, d_model=128, n_heads=4, n_kv_heads=4,
                  head_dim=32, d_ff=256, vocab=512, dtype="float32",
                  remat="none")
params = M.init(cfg, jax.random.PRNGKey(0))

def reqs():
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=5)
                    .astype(np.int32), max_new_tokens=6) for i in range(4)]

res = {}
e0 = ServeEngine(cfg, params, max_batch=4, max_len=64,
                 sampler=SamplerConfig(temperature=0.0))
for r in reqs():
    e0.submit(r)
out0 = {c.rid: c.tokens for c in e0.run()}
e0.close()
e1 = ServeEngine(cfg, params, max_batch=4, max_len=64,
                 sampler=SamplerConfig(temperature=0.0),
                 pipeline_stages=4, pipeline_microbatches=2)
for r in reqs():
    e1.submit(r)
out1 = {c.rid: c.tokens for c in e1.run()}
res["token_parity"] = out0 == out1
hps = e1.hot_paths()
res["decode_family"] = [h.name for h in hps if "decode" in h.name]
res["violations"] = [f"{h.name}:{v.rule}:{v.msg[:80]}"
                     for h in hps for v in run_rules(h)]
dec = next(h for h in hps if "decode" in h.name)
counts = [hlo.collective_counts(p.compiled_text()) for p in dec.programs]
res["permutes"] = counts[0].get("collective-permute", 0)
res["permute_cap"] = dict(dec.budget.collectives).get("collective-permute")
res["flat"] = all(c == counts[0] for c in counts)
try:
    ServeEngine(cfg, params, max_batch=4, max_len=64, pipeline_stages=3)
    res["bad_stage_raises"] = False
except ValueError:
    res["bad_stage_raises"] = True
e1.close()
print(json.dumps(res))
"""


def test_pipeline_engine_decode():
    """`pipeline_stages=N` serves the same tokens as the sequential
    engine, registers a `lm.decode.pipelined` family whose permute count
    stays in budget and flat across the drain family, and rejects depths
    that do not factor."""
    res = _run(_ENGINE_SUBPROC)
    assert res["token_parity"], res
    assert res["decode_family"] == ["lm.decode.pipelined"], res
    assert res["violations"] == [], res["violations"]
    assert res["permute_cap"] is not None
    assert 0 < res["permutes"] <= res["permute_cap"], res
    assert res["flat"], res
    assert res["bad_stage_raises"], res
