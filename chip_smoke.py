#!/usr/bin/env python3
"""Chip smoke test: the serving engines once, at full width, on a TPU.

    python chip_smoke.py               # one chip (jax.devices()[:1])
    python chip_smoke.py --four-chips  # mesh-sharded serving on a 2x2 mesh
                                       # against the same engines on one chip

One chip: ResNet-50 at 224x224x3 with 1000 classes through ``VisionEngine``
(8 images from a fixed seed, ``max_batch=8``) in the float, <8:8> and <4:4>
int-direct cohorts and the <8:8> and <4:4> Pallas cohorts (the paper's
AND+popcount kernels), each as a warm dispatch and then a served one; then
qwen3-0.6b (bf16, all 28 layers) through ``ServeEngine``: 4 requests with
16-128-token prompts and 16 greedy new tokens, float and <8:8> int-direct.
Weights are random, made from ``--seed``.

Four chips: ResNet-50 <8:8> int-direct and qwen3-0.6b (float32, float path)
on ``make_serve_mesh(model_par=2)``, each compared with its one-chip run by
the criterion of tests/test_vision_engine.py and tests/test_serve_sharded.py.

Every check prints one line; timings are labelled "smoke, not a benchmark".
The last stdout line is one JSON object naming the device. The script exits
nonzero, without that line, when JAX finds no TPU or any check fails. One
process drives every device; it starts no other.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

SMOKE = "smoke, not a benchmark"
IMAGE, CLASSES, BATCH = 224, 1000, 8
PROMPT_LENS = (16, 40, 77, 128)
MAX_NEW, MAX_LEN = 16, 256
# Float logits of the engines (default TPU matmul precision: bf16 passes)
# against a float32 forward at "highest", as max|diff| / max|ref|.
VISION_FLOAT_TOL = 5e-2
LM_FLOAT_TOL = 5e-2
# <8:8> prefill logits against the same float32 reference: 8-bit Eq. 2
# quantization of every projection adds error the float path does not have.
LM_PIM_TOL = 2.5e-1


class Checks:
    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        print(f"check {'PASS' if ok else 'FAIL'} {name}: {detail}", flush=True)
        if not ok:
            self.failed.append(name)
        return ok


def rel_err(got, ref) -> float:
    import numpy as np

    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))


# -- vision ---------------------------------------------------------------

def serve_images(eng, imgs, precision):
    """Submit every image and drain; returns (rid-ordered logits, seconds)."""
    import numpy as np

    from repro.serving import VisionRequest

    for rid, img in enumerate(imgs):
        eng.submit(VisionRequest(rid=rid, image=img, model="resnet50",
                                 precision=precision))
    t0 = time.perf_counter()
    done = eng.run(strict=True)
    dt = time.perf_counter() - t0
    return np.stack([c.logits for c in sorted(done, key=lambda c: c.rid)]), dt


def vision_cohort(check, eng, imgs, precision, tag):
    import numpy as np

    _, warm = serve_images(eng, imgs, precision)
    logits, served = serve_images(eng, imgs, precision)
    print(f"time vision {tag}: warm dispatch (compile + run) {warm:.3f} s, "
          f"served {len(imgs)} images {served:.4f} s ({SMOKE})", flush=True)
    check(f"vision {tag} finite", bool(np.isfinite(logits).all()),
          f"shape {logits.shape}")
    check(f"vision {tag} not degraded", not eng.health["degraded"],
          str(eng.health["degraded"]))
    return logits


def vision_phase(check, *, image=IMAGE, classes=CLASSES, batch=BATCH,
                 seed=0):
    import jax
    import numpy as np

    from repro.core import PIMQuantConfig
    from repro.models.cnn import resnet
    from repro.serving import VisionEngine

    params = resnet.init(jax.random.PRNGKey(seed), num_classes=classes,
                         image=image)
    imgs = np.random.default_rng(seed).standard_normal(
        (batch, image, image, 3)).astype(np.float32)
    eng = {b: VisionEngine({"resnet50": params}, backend=b, max_batch=batch)
           for b in ("int-direct", "pallas")}
    out = {}
    for backend, prec in [("int-direct", None), ("int-direct", "<8:8>"),
                          ("int-direct", "<4:4>"), ("pallas", "<8:8>"),
                          ("pallas", "<4:4>")]:
        tag = f"{prec or 'float'}/{backend}" if prec else "float"
        out[(backend, prec)] = vision_cohort(check, eng[backend], imgs, prec,
                                             tag)

    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(resnet.apply)(params, imgs))
    err = rel_err(out[("int-direct", None)], ref)
    check("vision float vs f32 'highest' resnet.apply", err <= VISION_FLOAT_TOL,
          f"max|diff|/max|ref| = {err!r} (tol {VISION_FLOAT_TOL})")
    for prec in ("<8:8>", "<4:4>"):
        a, b = out[("pallas", prec)], out[("int-direct", prec)]
        check(f"vision {prec} pallas bit-identical to int-direct",
              bool(np.array_equal(a, b)),
              f"max|diff| = {float(np.abs(a - b).max())!r}")
        hp = next(h for h in eng["pallas"].hot_paths()
                  if f",{prec},b={batch}]" in h.name)
        text = hp.programs[0].compiled_text()
        check(f"vision {prec} pallas forward compiled with Mosaic kernels",
              "tpu_custom_call" in text,
              f"{text.count('tpu_custom_call')} tpu_custom_call in the "
              "compiled HLO")
    top1 = [out[("int-direct", p)].argmax(-1) for p in (None, "<8:8>")]
    print(f"info vision <8:8> top-1 agreement with float: "
          f"{float((top1[0] == top1[1]).mean())!r} over {batch} images "
          "(random weights)", flush=True)
    for e in eng.values():
        e.close()
    return params, imgs, out


# -- LM -------------------------------------------------------------------

def lm_prompts(vocab: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32)
            for n in PROMPT_LENS]


def serve_lm(eng, prompts):
    from repro.serving import Request

    for rid, p in enumerate(prompts):
        eng.submit(Request(rid=rid, prompt=p, max_new_tokens=MAX_NEW))
    t0 = time.perf_counter()
    done = eng.run(strict=True)
    dt = time.perf_counter() - t0
    return {c.rid: c.tokens for c in done}, dt


def engine_prefill_logits(eng, prompt):
    """The last-token logits the engine samples a request's first token
    from: its own prefill program over its power-of-two chunks."""
    import jax.numpy as jnp
    import numpy as np

    from repro.models.lm import init_state
    from repro.serving.engine import _pow2_chunks

    state = init_state(eng.cfg, eng.max_batch, eng.max_len)
    pos = 0
    for c in _pow2_chunks(len(prompt)):
        logits, state = eng._prefill(
            eng.params, state, jnp.asarray(prompt[pos:pos + c])[None], 0, pos)
        pos += c
    return np.asarray(logits, np.float32)[0, -1]


def lm_engine(cfg, params, pim, mesh=None):
    from repro.serving import SamplerConfig, ServeEngine

    return ServeEngine(dataclasses.replace(cfg, pim=pim), params,
                       max_batch=len(PROMPT_LENS), max_len=MAX_LEN,
                       sampler=SamplerConfig(temperature=0.0), mesh=mesh)


def lm_cohort(check, eng, prompts, tag):
    from repro.serving.engine import _pow2_chunks

    _, warm = serve_lm(eng, prompts)
    toks, served = serve_lm(eng, prompts)
    n_tok = sum(len(t) for t in toks.values())
    print(f"time lm {tag}: warm run (compile + run) {warm:.3f} s, served "
          f"{len(prompts)} requests / {n_tok} tokens {served:.4f} s "
          f"({SMOKE})", flush=True)
    check(f"lm {tag} all requests complete",
          sorted(toks) == list(range(len(prompts)))
          and all(len(t) == MAX_NEW for t in toks.values()),
          f"token counts {[len(toks.get(r, [])) for r in range(len(prompts))]}")
    chunks = {c for p in prompts for c in _pow2_chunks(len(p))}
    n_pf = eng._prefill._cache_size()
    n_dec = {n: fn._cache_size() for n, fn in eng._decode.items()}
    check(f"lm {tag} compile count within the pow2 bound",
          n_pf <= len(chunks) and all(v == 1 for v in n_dec.values())
          and all(n & (n - 1) == 0 for n in n_dec),
          f"prefill {n_pf} programs for {len(chunks)} chunk lengths, "
          f"decode {n_dec}")
    return toks


def lm_phase(check, cfg, *, seed=0):
    import jax
    import numpy as np

    from repro.core import PIMQuantConfig
    from repro.models.lm import forward, init
    from repro.models.lm.model import cast_params

    masters = init(cfg, jax.random.PRNGKey(seed))
    params = cast_params(masters, jax.numpy.dtype(cfg.dtype))
    prompts = lm_prompts(cfg.vocab, seed)
    cfg32 = dataclasses.replace(cfg, dtype="float32", pim=None)
    fwd32 = jax.jit(lambda p, t: forward(p, cfg32, t)[0][0, -1])
    with jax.default_matmul_precision("highest"):
        refs = [np.asarray(fwd32(masters, p[None])) for p in prompts]
    out = {}
    for tag, pim, tol in [("float", None, LM_FLOAT_TOL),
                          ("<8:8>/int-direct",
                           PIMQuantConfig(8, 8, backend="int-direct"),
                           LM_PIM_TOL)]:
        eng = lm_engine(cfg, params, pim)
        out[tag] = lm_cohort(check, eng, prompts, tag)
        errs = [rel_err(engine_prefill_logits(eng, p), r)
                for p, r in zip(prompts, refs)]
        check(f"lm {tag} prefill logits vs f32 'highest' forward",
              max(errs) <= tol,
              f"max|diff|/max|ref| per request {errs!r} (tol {tol})")
        eng.close()
        del eng
    return masters, params, prompts, out


# -- four chips -----------------------------------------------------------

def four_chip_phase(check, lm_cfg, *, image=IMAGE, classes=CLASSES,
                    batch=BATCH, seed=0):
    """Mesh-sharded serving on a 2x2 ("data", "model") mesh against the
    same engine on one chip."""
    import jax
    import numpy as np

    from repro.launch.mesh import make_serve_mesh
    from repro.models.cnn import resnet
    from repro.models.lm import init
    from repro.serving import VisionEngine

    mesh = make_serve_mesh(model_par=2, n_devices=4)
    print(f"info mesh {dict(mesh.shape)} over "
          f"{[d.id for d in mesh.devices.ravel()]}", flush=True)

    params = resnet.init(jax.random.PRNGKey(seed), num_classes=classes,
                         image=image)
    imgs = np.random.default_rng(seed).standard_normal(
        (batch, image, image, 3)).astype(np.float32)
    logits = {}
    for label, m in (("1 chip", None), ("2x2 mesh", mesh)):
        eng = VisionEngine({"resnet50": params}, backend="int-direct",
                           max_batch=batch, mesh=m)
        logits[label] = vision_cohort(check, eng, imgs, "<8:8>",
                                      f"<8:8>/int-direct {label}")
        eng.close()
    a, b = logits["2x2 mesh"], logits["1 chip"]
    check("vision <8:8> 2x2 mesh vs 1 chip: top-1 equal, allclose("
          "rtol=1e-4, atol=1e-3)",
          bool((a.argmax(-1) == b.argmax(-1)).all()
               and np.allclose(a, b, rtol=1e-4, atol=1e-3)),
          f"max|diff| = {float(np.abs(a - b).max())!r}")

    # The LM comparison is tests/test_serve_sharded.py's: a float32 model on
    # the float path, greedy tokens identical. "highest" keeps float32
    # matmuls float32 on the chip, as they are on the CPU the test runs on.
    cfg32 = dataclasses.replace(lm_cfg, dtype="float32")
    lm_params = init(cfg32, jax.random.PRNGKey(seed))
    prompts = lm_prompts(lm_cfg.vocab, seed)
    toks = {}
    with jax.default_matmul_precision("highest"):
        for label, m in (("1 chip", None), ("2x2 mesh", mesh)):
            eng = lm_engine(cfg32, lm_params, None, mesh=m)
            toks[label] = lm_cohort(check, eng, prompts, f"float32 {label}")
            eng.close()
            del eng
    same = [toks["2x2 mesh"][r] == toks["1 chip"][r] for r in toks["1 chip"]]
    check("lm float32 2x2 mesh vs 1 chip: greedy tokens identical", all(same),
          f"per request {same}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh-sharded serving path on 4 chips "
                         "and what it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    cache = enable_compile_cache()

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX backend {jax.default_backend()!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(jax.devices()) < want:
        print(f"chip_smoke: need {want} TPU devices, found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2
    devices = jax.devices()[:want]
    d0 = devices[0]
    print(f"info devices {[str(d) for d in devices]} ({d0.device_kind}); "
          f"compile cache {cache}", flush=True)

    from repro.configs import get_config

    lm_cfg = get_config("qwen3-0.6b").model
    check = Checks()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(check, lm_cfg, seed=args.seed)
    else:
        with jax.default_device(d0):
            vision_phase(check, seed=args.seed)
            lm_phase(check, lm_cfg, seed=args.seed)
    print(f"time total {time.perf_counter() - t0:.1f} s ({SMOKE})",
          flush=True)
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed: "
              f"{check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
