"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Wires together configs -> mesh -> sharded init -> resilient step loop
(checkpoint/restart, straggler detection) -> metrics log. On this CPU
container it runs reduced configs end-to-end; on a real fleet the same
entry point runs the full configs (jax.distributed handles multi-host).

Example (CPU, ~100M-param reduced llama):
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --reduced \
      --steps 200 --batch 8 --seq 256
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.distributed import sharding as sh
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh, make_test_mesh
from repro.models.lm import init as model_init
from repro.models.lm.model import cast_params
from repro.training.data import DataConfig, make_source
from repro.training.fault_tolerance import FTConfig, run_resilient
from repro.training.optimizer import OptimizerConfig, init_opt_state
from repro.training.train_loop import make_train_step


def build(arch_id: str, reduced: bool, batch: int, seq: int, steps: int,
          lr: float, accum: int, production_mesh: bool, pim: bool = False):
    arch = get_config(arch_id)
    cfg = arch.model.reduced() if reduced else arch.model
    if pim:
        from repro.core.pim_layers import PIMQuantConfig
        import dataclasses
        cfg = dataclasses.replace(cfg, pim=PIMQuantConfig(backend="int-direct"))
    mesh = make_production_mesh() if production_mesh else make_test_mesh()
    sh.set_mesh(mesh)
    sh.set_tied_embeddings(cfg.tie_embeddings)

    key = jax.random.PRNGKey(0)
    params = cast_params(model_init(cfg, key), jnp.dtype(cfg.dtype))
    p_sh = sh.param_shardings(params, mesh)
    params = jax.device_put(params, p_sh)

    ocfg = OptimizerConfig(lr=lr, warmup_steps=min(100, steps // 10 + 1),
                           total_steps=steps)
    opt_state = init_opt_state(ocfg, params)
    o_sh = sh.param_shardings(opt_state, mesh)
    o_sh["step"] = sh.replicated(mesh)
    opt_state = jax.device_put(opt_state, o_sh)

    dcfg = DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch)
    source = make_source(dcfg)
    b_example = source.batch(0)
    b_sh = sh.batch_shardings(b_example, mesh, batch)

    step = jax.jit(
        make_train_step(cfg, ocfg, accum=accum),
        in_shardings=(p_sh, o_sh, b_sh),
        out_shardings=(p_sh, o_sh, None),
        donate_argnums=(0, 1),
    )

    def put(host_batch):
        return jax.tree.map(
            lambda x, s: jax.device_put(jnp.asarray(x), s), host_batch, b_sh)

    return cfg, mesh, params, opt_state, step, source, put


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-scale)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--pim", action="store_true",
                    help="run projections through the bit-serial PIM pipeline")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg, mesh, params, opt_state, step, source, put = build(
        args.arch, args.reduced, args.batch, args.seq, args.steps, args.lr,
        args.accum, args.production_mesh, args.pim)

    print(f"arch={args.arch} reduced={args.reduced} mesh={dict(mesh.shape)} "
          f"params={sum(l.size for l in jax.tree.leaves(params)):,}")

    history = []

    def on_metrics(s, m):
        if s % args.log_every == 0:
            loss = float(m["loss"])
            history.append((s, loss))
            print(f"step {s:5d}  loss {loss:.4f}  gnorm {float(m['grad_norm']):.3f} "
                  f"lr {float(m['lr']):.2e}", flush=True)

    ft = FTConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)
    t0 = time.time()
    params, opt_state, stats = run_resilient(
        step, params, opt_state, source, args.steps, ft,
        put_batch=put, on_metrics=on_metrics)
    dt = time.time() - t0
    print(f"done: {stats} in {dt:.1f}s "
          f"({args.steps * args.batch * args.seq / dt:.0f} tok/s)")
    if len(history) >= 2:
        print(f"loss: first {history[0][1]:.4f} -> last {history[-1][1]:.4f}")


if __name__ == "__main__":
    main()
