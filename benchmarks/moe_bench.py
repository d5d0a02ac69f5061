"""MoE fast-path benchmark: packed expert banks vs the float-einsum path.

Two sweeps over the phi3.5-MoE family (reduced to CPU scale), both landing
in ``BENCH_serving.json``:

``moe_layer_comparison`` — per-layer decode-shape latency of ``moe_ffn``
with prepacked expert banks (``prepack_params``: expert-stacked (E, K, N)
bit-plane layout, fused quantize->pack dispatch) against the same routing
over the float einsum path (the pre-packing behavior: router-bearing dicts
served as f32), across <2:2>/<4:4>/<8:8> and two expert widths. At the
reduced width the call is dispatch-bound on CPU; at the wide shape the
bit-serial GEMMs dominate and the packed path's advantage is the paper's
many-planes-in-parallel story (packed >= 1.5x float at <4:4>, asserted by
``--smoke``). Long-context prefill shapes favor float on CPU — the packed
win is a *decode* (tokens-per-step ~ batch) property, which is exactly the
serving hot loop.

``moe_device_scaling`` — engine decode tokens/sec per device count
(1/2/4/8, each cell a subprocess so XLA_FLAGS can force the host device
count) on the expert-parallel mesh ("model" axis divides E: experts =
chips, DESIGN.md §11), plus a pipeline-composed cell (``pipeline_stages``)
where depth factors. Rows carry the routing-overflow telemetry
(``stats()["moe_drop_frac"]``) so the sweep also exercises the drop ring
end to end. As with ``serve_device_scaling``, CPU cells share cores — the
gate is mechanism (flat collective counts, EP layout), not speedup.

Run standalone (merges its keys into BENCH_serving.json):

  PYTHONPATH=src python -m benchmarks.moe_bench --smoke

or through ``benchmarks.run --only serve``.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp


def _moe_cfg(w_bits: int = 4, a_bits: int = 4, wide: bool = False,
             backend: str = "popcount", **overrides):
    """phi3.5-MoE reduced to CPU scale (4 experts, top-2), float32 masters,
    bit-serial expert banks at the given precision. ``wide=True`` doubles
    the expert GEMMs to the regime where the bit-plane kernels dominate
    the dispatch overhead."""
    from repro.configs import get_config
    from repro.core.pim_layers import PIMQuantConfig

    arch = get_config("phi3.5-moe-42b-a6.6b")
    if wide:
        overrides = dict(d_model=256, d_ff=512, **overrides)
    return arch.model.reduced(
        dtype="float32",
        pim=PIMQuantConfig(w_bits=w_bits, a_bits=a_bits, backend=backend),
        **overrides)


def _time_layer(cfg, params, x, reps: int) -> float:
    """Best-of-3 mean latency (ms) of one jitted ``moe_ffn`` call."""
    from repro.models.lm.moe import moe_ffn

    f = jax.jit(lambda p, xr: moe_ffn(p, cfg, xr)[0])
    f(params, x).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            f(params, x).block_until_ready()
        best = min(best, (time.perf_counter() - t0) / reps)
    return best * 1e3


def moe_layer_comparison(smoke: bool = False):
    """Per-layer packed-vs-float latency rows (decode shape, batch 8)."""
    from repro.models.lm.model import prepack_params
    from repro.models.lm.moe import init_moe

    reps = 20 if smoke else 60
    rows = []
    for wide in (False, True):
        for bits in (2, 4, 8):
            cfg = _moe_cfg(w_bits=bits, a_bits=bits, wide=wide)
            params = init_moe(cfg, jax.random.PRNGKey(0))
            packed = prepack_params(params, cfg.pim)
            x = jax.random.normal(jax.random.PRNGKey(1),
                                  (8, 1, cfg.d_model), jnp.float32) * 0.3
            t_float = _time_layer(cfg, params, x, reps)
            t_packed = _time_layer(cfg, packed, x, reps)
            rows.append({
                "precision": cfg.pim.tag,
                "experts": f"{cfg.moe.n_experts}top{cfg.moe.top_k}",
                "d_model": cfg.d_model, "d_ff": cfg.d_ff,
                "tokens": 8, "backend": cfg.pim.backend,
                "float_ms": round(t_float, 3),
                "packed_ms": round(t_packed, 3),
                "packed_speedup": round(t_float / t_packed, 2),
            })
    return rows


_MOE_SCALE_SCRIPT = r"""
import sys
n, model_par, stages, smoke = (int(v) for v in sys.argv[1:5])
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d" % n
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import json
from functools import partial
import jax
import numpy as np
from benchmarks.moe_bench import _moe_cfg
from benchmarks.serve_bench import _measure, _workload
from repro.launch.mesh import make_serve_mesh
from repro.models.lm import init
from repro.serving import SamplerConfig, ServeEngine

cfg = _moe_cfg(w_bits=4, a_bits=4, wide=not smoke, n_layers=4)
params = init(cfg, jax.random.PRNGKey(0))
mesh = make_serve_mesh(model_par) if model_par > 1 else None
eng = ServeEngine(cfg, params, max_batch=8, max_len=64,
                  sampler=SamplerConfig(temperature=0.0), mesh=mesh,
                  pipeline_stages=stages)
rng = np.random.default_rng(0)
max_new = 8 if smoke else 24
make_reqs = partial(_workload, 8, cfg.vocab, max_new, rng)
ttft_prompt = (np.arange(1, 6, dtype=np.int32) % cfg.vocab).astype(np.int32)
gen, dec, ttft = _measure(eng, make_reqs, ttft_prompt)
drop = eng.stats()["moe_drop_frac"]
if stages > 1:
    mode, mesh_s = "pipeline", "%d stages" % stages
elif mesh is not None:
    mode = "expert-parallel" if cfg.moe.n_experts % model_par == 0 else "tp"
    mesh_s = "%dx%d (data x model)" % (n // model_par, model_par)
else:
    mode, mesh_s = "single", "-"
print(json.dumps({
    "devices": n, "mode": mode, "mesh": mesh_s,
    "gen_tok_s": round(gen, 1), "decode_tok_s": round(dec, 1),
    "ttft_ms": round(ttft * 1e3, 1),
    "moe_drop_frac_mean": drop["mean"] and round(drop["mean"], 4)}))
"""


def moe_device_scaling(smoke: bool = False):
    """MoE engine decode throughput per device count on the EP mesh.

    Cells: 1 device (mesh-free baseline), 2/4/8 devices with 2-way "model"
    parallelism (E=4 experts split 2-way: the experts=chips mapping), and
    a 2-stage pipelined cell (depth 4 factors into 2 stages)."""
    from benchmarks.serve_bench import require_cpu_parent

    require_cpu_parent("moe_device_scaling")
    cells = [(1, 1, 1), (2, 2, 1), (2, 1, 2)] if smoke else \
        [(1, 1, 1), (2, 2, 1), (4, 2, 1), (8, 2, 1), (2, 1, 2)]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH="src" + os.pathsep + ".",
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    rows = []
    for n, model_par, stages in cells:
        out = subprocess.run(
            [sys.executable, "-c", _MOE_SCALE_SCRIPT, str(n),
             str(model_par), str(stages), str(int(smoke))],
            capture_output=True, text=True, env=env, cwd=repo)
        if out.returncode != 0:
            raise RuntimeError(
                f"moe-scaling cell n={n} mp={model_par} s={stages} "
                f"failed: {out.stderr[-2000:]}")
        rows.append(json.loads(out.stdout.strip().splitlines()[-1]))
    base = rows[0]["decode_tok_s"] or 1.0
    for r in rows:
        r["decode_speedup_vs_1dev"] = round(r["decode_tok_s"] / base, 2)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(prog="python -m benchmarks.moe_bench")
    ap.add_argument("--smoke", action="store_true",
                    help="CI scale + assert packed beats float at <4:4> "
                    "(>= 1.5x at the wide expert shape)")
    args = ap.parse_args(argv)

    from .run import render

    layer = moe_layer_comparison(smoke=args.smoke)
    render("serve: MoE expert FFN packed vs float einsum (per-layer)", layer)
    scale = moe_device_scaling(smoke=args.smoke)
    render("serve: MoE engine scaling (experts=chips / pipeline)", scale)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "BENCH_serving.json")
    data = {}
    if os.path.exists(path):
        with open(path) as fh:
            data = json.load(fh)
    data["moe_layer"] = layer
    data["moe_device_scaling"] = scale
    with open(path, "w") as fh:
        json.dump(data, fh, indent=1)
    print(f"\nwrote {path}")

    if args.smoke:
        at44 = [r for r in layer if r["precision"] == "<4:4>"]
        assert at44, layer
        worst = min(r["packed_speedup"] for r in at44)
        best = max(r["packed_speedup"] for r in at44)
        assert worst > 1.0, ("packed expert FFN must beat the float "
                            "einsum at <4:4>", at44)
        assert best >= 1.5, ("packed expert FFN must reach 1.5x float "
                             "at the wide <4:4> shape", at44)
        print(f"moe smoke OK: packed {worst:.2f}x..{best:.2f}x "
              f"float at <4:4>")
    return 0


if __name__ == "__main__":
    sys.exit(main())
