"""Serving launcher: batched generation with the continuous-batching engine.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --requests 6 --max-new 16

``--workload cnn`` drives the batched *vision* engine instead (the paper's
own workload): random images through the prepacked bit-serial conv path in
power-of-two micro-batch buckets —

  PYTHONPATH=src python -m repro.launch.serve --workload cnn \
      --cnn-model resnet50 --image 64 --requests 16 --precision '<8:8>'

Multi-device serving maps the paper's chip→bank hierarchy onto a
("data", "model") mesh (DESIGN.md §5/§6): ``--model-par N`` puts N-way
tensor/bank parallelism on the "model" axis and shards the decode-slot
grid (LM) or the image micro-batch (CNN) across the rest of the devices on
"data". On a CPU-only box, force a multi-device host *before any jax
import* (XLA reads the flag at backend init):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --model-par 2 --max-batch 8

Without ``--model-par`` (or ``--pipeline-stages``) the engines run
mesh-free on the first device, however many the host has.

``--gateway`` puts the asyncio overload gateway (DESIGN.md §8) in front of
the LM engine: Poisson arrivals at ``--rate`` req/s into bounded per-tenant
queues (``--queue-depth``), per-request deadlines (``--deadline-ms``), load
shedding with retry-after hints, and a final telemetry snapshot —

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --gateway --requests 16 --rate 50 --deadline-ms 2000 --queue-depth 4
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_serve_mesh
from repro.models.lm import init as model_init
from repro.models.lm.model import cast_params
from repro.serving import Request, SamplerConfig, ServeEngine
from repro.serving.vision import MODEL_ZOO

CNN_MODELS = tuple(sorted(MODEL_ZOO))


def serve_cnn(args, mesh):
    """Vision workload: micro-batched CNN inference (DESIGN.md §6)."""
    from repro.serving import VisionEngine, VisionRequest

    module = MODEL_ZOO[args.cnn_model]
    params = module.init(jax.random.PRNGKey(0), image=args.image,
                         num_classes=args.classes)
    eng = VisionEngine({args.cnn_model: params}, backend=args.backend,
                       max_batch=args.max_batch, mesh=mesh,
                       autotune=args.autotune,
                       tuning_cache=args.tuning_cache)
    rng = np.random.default_rng(0)
    imgs = rng.standard_normal(
        (args.requests, args.image, args.image, 3)).astype(np.float32)
    precision = None if args.precision in ("float", "fp32") else args.precision
    # Warm run populates the prepack + compile caches; the timed run then
    # measures the serving path, not deployment cost.
    for rid in range(args.requests):
        eng.submit(VisionRequest(rid=rid, image=imgs[rid],
                                 model=args.cnn_model, precision=precision))
    eng.run()
    for rid in range(args.requests):
        eng.submit(VisionRequest(rid=rid, image=imgs[rid],
                                 model=args.cnn_model, precision=precision))
    t0 = time.time()
    done = eng.run()
    dt = time.time() - t0
    for c in sorted(done, key=lambda c: c.rid)[:8]:
        print(f"req {c.rid}: top1={c.top1} (bucket {c.batch})")
    print(f"{len(done)} images in {dt:.2f}s ({len(done) / dt:.1f} img/s, "
          f"model={args.cnn_model}@{args.image}px, "
          f"precision={args.precision}, backend={args.backend})")


def serve_gateway(args, mesh, cfg, params):
    """``--gateway``: drive the LM engine through the asyncio gateway
    (DESIGN.md §8) with Poisson arrivals, deadlines, bounded per-tenant
    queues, and a final telemetry snapshot."""
    import asyncio

    from repro.serving import (DeadlineExceeded, Gateway, GatewayConfig,
                               ShedError)

    eng = ServeEngine(cfg, params, max_batch=args.max_batch,
                      max_len=args.max_len,
                      sampler=SamplerConfig(temperature=args.temperature),
                      mesh=mesh, autotune=args.autotune,
                      tuning_cache=args.tuning_cache,
                      pipeline_stages=args.pipeline_stages,
                      pipeline_microbatches=args.pipeline_microbatches)
    gw_cfg = GatewayConfig(queue_depth=args.queue_depth,
                           default_deadline_ms=args.deadline_ms)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=int(rng.integers(4, 17)))
               .astype(np.int32) for _ in range(args.requests)]
    # Warm run populates the prefill/decode compile caches so deadlines
    # measure serving, not XLA compilation.
    for rid, p in enumerate(prompts[:args.max_batch]):
        eng.submit(Request(rid=rid, prompt=p,
                           max_new_tokens=args.max_new))
    eng.run()

    async def run():
        gw = Gateway(lm=eng, cfg=gw_cfg)
        gw.start()
        done = shed = expired = n_tok = 0

        async def eat(rid, stream):
            nonlocal done, expired, n_tok
            try:
                toks = await stream.result()
                done += 1
                n_tok += len(toks)
                print(f"req {rid}: {len(toks)} tokens -> {toks[:8]}...")
            except DeadlineExceeded:
                expired += 1
                print(f"req {rid}: deadline exceeded "
                      f"({len(stream.tokens)} tokens streamed)")

        tasks = []
        t0 = time.time()
        for rid, p in enumerate(prompts):
            if args.rate > 0:
                await asyncio.sleep(float(rng.exponential(1.0 / args.rate)))
            try:
                s = await gw.submit_lm(p, max_new_tokens=args.max_new,
                                       tenant=f"t{rid % 2}", rid=rid)
                tasks.append(asyncio.ensure_future(eat(rid, s)))
            except ShedError as e:
                shed += 1
                print(f"req {rid}: shed ({e.reason}), "
                      f"retry after {e.retry_after_s:.3f}s")
        await asyncio.gather(*tasks)
        await gw.drain(timeout=120)
        dt = time.time() - t0
        st = gw.stats()
        gw.stop()
        print(f"{done} completions ({n_tok} tokens), {shed} shed, "
              f"{expired} expired in {dt:.1f}s ({n_tok / dt:.1f} tok/s)")
        print(f"gateway: tier={st['tier']} "
              f"ttft_p95={st['ttft_ms']['p95']} ms "
              f"tpot_p95={st['tpot_ms']['p95']} ms "
              f"max_depth={st['queue']['max_depth']}/{st['queue']['bound']} "
              f"shed_rate={st['shed_rate']:.3f}")
        print(f"lm counters: {st['lm_counters']}")

    asyncio.run(run())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", choices=("lm", "cnn"), default="lm")
    ap.add_argument("--gateway", action="store_true",
                    help="serve through the asyncio overload gateway "
                    "(bounded queues, deadlines, shedding; LM workload)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="gateway Poisson arrival rate in req/s "
                    "(0 = submit everything at once)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="gateway per-request deadline")
    ap.add_argument("--queue-depth", type=int, default=32,
                    help="gateway bounded per-tenant queue depth")
    ap.add_argument("--arch", choices=ARCH_IDS,
                    help="LM architecture (required for --workload lm)")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--model-par", type=int, default=1,
                    help="devices per model replica (the mesh's 'model' "
                    "axis); the rest shard decode slots / image batches "
                    "on 'data'")
    ap.add_argument("--pipeline-stages", type=int, default=1,
                    help="pipeline the scanned layer stack over N devices "
                    "on a ('stage',) mesh (GPipe fill-drain decode; "
                    "DESIGN.md §11). Mutually exclusive with --model-par")
    ap.add_argument("--pipeline-microbatches", type=int, default=None,
                    help="microbatches streamed through the pipe per decode "
                    "step (default: --pipeline-stages)")
    # --workload cnn
    ap.add_argument("--cnn-model", choices=CNN_MODELS, default="resnet50")
    ap.add_argument("--image", type=int, default=64)
    ap.add_argument("--classes", type=int, default=1000)
    ap.add_argument("--precision", default="<8:8>",
                    help="'<W:I>' bit-widths, or 'float' for the fp path")
    ap.add_argument("--backend", default="int-direct",
                    choices=("int-direct", "popcount", "mxu-plane", "pallas"))
    ap.add_argument("--autotune", default="off",
                    choices=("off", "cost", "measure"),
                    help="per-weight backend/tile autotuning at prepack "
                         "(repro.pim.autotune): 'cost' ranks candidates with "
                         "the NAND-SPIN cost model, 'measure' refines the "
                         "finalists by wall clock")
    ap.add_argument("--tuning-cache", default=None, metavar="PATH",
                    help="JSON tuning-cache file persisting autotune "
                         "decisions across launches (default: in-memory)")
    args = ap.parse_args()
    enable_compile_cache()

    mesh = None
    if args.pipeline_stages > 1:
        if args.model_par > 1:
            raise SystemExit("--pipeline-stages and --model-par are "
                             "alternative decode compositions; pick one")
        print(f"pipelined decode over {args.pipeline_stages} stage(s), "
              f"{args.pipeline_microbatches or args.pipeline_stages} "
              "microbatch(es)")
    elif args.model_par > 1:
        mesh = make_serve_mesh(args.model_par)
        print(f"serving on mesh {dict(mesh.shape)} "
              f"({len(mesh.devices.ravel())} devices)")
    if args.workload == "cnn":
        serve_cnn(args, mesh)
        return
    if args.arch is None:
        raise SystemExit("--workload lm requires --arch")

    arch = get_config(args.arch)
    cfg = arch.model.reduced() if args.reduced else arch.model
    if not cfg.embed_inputs or cfg.cross_attn_every:
        raise SystemExit("serve launcher drives token-in archs; "
                         "musicgen/vlm need frontend-stub drivers (see examples)")
    params = cast_params(model_init(cfg, jax.random.PRNGKey(0)),
                         jnp.dtype(cfg.dtype))
    if args.gateway:
        serve_gateway(args, mesh, cfg, params)
        return
    eng = ServeEngine(cfg, params, max_batch=args.max_batch,
                      max_len=args.max_len,
                      sampler=SamplerConfig(temperature=args.temperature),
                      mesh=mesh, autotune=args.autotune,
                      tuning_cache=args.tuning_cache,
                      pipeline_stages=args.pipeline_stages,
                      pipeline_microbatches=args.pipeline_microbatches)
    rng = np.random.default_rng(0)
    t0 = time.time()
    for rid in range(args.requests):
        L = int(rng.integers(4, 17))
        eng.submit(Request(rid=rid, prompt=rng.integers(
            0, cfg.vocab, size=L).astype(np.int32), max_new_tokens=args.max_new))
    done = eng.run()
    dt = time.time() - t0
    n_tok = sum(len(c.tokens) for c in done)
    for c in sorted(done, key=lambda c: c.rid):
        print(f"req {c.rid}: {len(c.tokens)} tokens -> {c.tokens[:8]}...")
    print(f"{len(done)} completions, {n_tok} tokens in {dt:.1f}s "
          f"({n_tok / dt:.1f} tok/s)")


if __name__ == "__main__":
    main()
