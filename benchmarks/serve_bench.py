"""Serving throughput benchmark: seed engine hot loop vs the fused one,
plus a device-count scaling sweep over the serving mesh.

``_LegacyEngine`` reproduces the pre-overhaul ``ServeEngine`` faithfully:
unjitted batch-1 prefill + host-side graft (rebuilds every leaf of the full
(max_batch, max_len) grid with ``at[].set`` per admission), a jitted decode
that transfers the full (B, vocab) logits to host every token, eager
host-side sampling keyed by ``PRNGKey(slot_pos.sum())``, and a per-step
host->device upload of the position array. The current engine replaces all
of that with donated in-jit programs (see ``repro/serving/engine.py`` and
DESIGN.md §4); this module quantifies the difference.

Measured per batch size, same prompt-length mix on both paths:
  * ``gen_tok_s``  — generated tokens/sec over a full continuous-batching
    run on a warm engine (compile caches populated by a first run);
  * ``ttft_ms``    — time-to-first-token for one admission into a warm
    engine (prompt prefill + first sampled token).

``serve_device_scaling`` sweeps the mesh-sharded engine across forced
host-device counts (each cell is a subprocess: XLA fixes the device count
at backend init), recording decode tokens/sec per (data × model) mesh —
the paper's chips × banks mapping (DESIGN.md §5). On a CPU host the forced
devices share the same cores, so this tracks the *mechanism* (collective
overhead, layout stability), not real speedup; on a TPU slice the same
rows measure actual scaling.

``benchmarks.run --only serve`` renders the tables and writes
``BENCH_serving.json`` at the repo root; ``--smoke`` shrinks the model and
token counts to CI scale (the artifact shape is identical).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.lm import (
    ModelConfig, decode_step, init, init_state, prefill, prepack_params,
)
from repro.serving import Request, SamplerConfig, ServeEngine
from repro.serving.sampler import sample


class _LegacyEngine:
    """The seed ``ServeEngine`` hot loop, kept verbatim as the baseline."""

    def __init__(self, cfg, params, max_batch=8, max_len=512, sampler=None):
        self.cfg = cfg
        self.params = prepack_params(params, cfg.pim)
        self.max_batch = max_batch
        self.max_len = max_len
        self.sampler = sampler or SamplerConfig()
        self.state = init_state(cfg, max_batch, max_len)
        self.slot_req = [None] * max_batch
        self.slot_remaining = np.zeros(max_batch, np.int32)
        self.slot_last_tok = np.zeros(max_batch, np.int32)
        self.queue = []
        self.done = []
        self.slot_pos = np.zeros(max_batch, np.int32)
        self._decode = jax.jit(partial(self._decode_impl, cfg))

    @staticmethod
    def _decode_impl(cfg, params, tokens, state):
        return decode_step(params, cfg, tokens, state)

    def submit(self, req):
        self.queue.append(req)

    def _admit(self):
        for slot in [i for i, r in enumerate(self.slot_req) if r is None]:
            if not self.queue:
                break
            req = self.queue.pop(0)
            L = len(req.prompt)
            tokens = jnp.asarray(req.prompt, jnp.int32)[None]
            s1 = init_state(self.cfg, 1, self.max_len)
            logits, s1 = prefill(self.params, self.cfg, tokens, s1)
            self._graft(s1, slot)
            nxt = int(sample(logits[:, -1], self.sampler,
                             jax.random.PRNGKey(req.rid))[0])
            self.slot_req[slot] = req
            self.slot_remaining[slot] = req.max_new_tokens - 1
            self.slot_last_tok[slot] = nxt
            self.slot_pos[slot] = L

    def _graft(self, s1, slot):
        def graft_leaf(big, small):
            for ax in range(min(big.ndim, 2)):
                if big.shape[ax] == self.max_batch and small.shape[ax] == 1:
                    idx = (slice(None),) * ax + (slot,)
                    src = (slice(None),) * ax + (0,)
                    return big.at[idx].set(small[src])
            return big

        new_scan = [jax.tree.map(graft_leaf, bl, sl)
                    for bl, sl in zip(self.state["scan"], s1["scan"])]
        new_rest = [jax.tree.map(graft_leaf, bl, sl)
                    for bl, sl in zip(self.state["rest"], s1["rest"])]
        self.state = dict(self.state, scan=new_scan, rest=new_rest)

    def step(self):
        self._admit()
        live = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not live:
            return self._drain_done()
        toks = jnp.asarray(self.slot_last_tok, jnp.int32)[:, None]
        self.state["length"] = jnp.asarray(self.slot_pos, jnp.int32)
        logits, self.state = self._decode(self.params, toks, self.state)
        nxt = np.asarray(sample(logits[:, 0], self.sampler, jax.random.PRNGKey(
            int(self.slot_pos.sum()))))
        for i in live:
            req = self.slot_req[i]
            tok = int(nxt[i])
            if not hasattr(req, "_out"):
                req._out = [int(self.slot_last_tok[i])]
            req._out.append(tok)
            self.slot_last_tok[i] = tok
            self.slot_pos[i] += 1
            self.slot_remaining[i] -= 1
            if tok == req.eos_id or self.slot_remaining[i] <= 0:
                self.done.append((req.rid, req._out))
                self.slot_req[i] = None
        return self._drain_done()

    def _drain_done(self):
        out, self.done = self.done, []
        return out

    def run(self, max_steps=10_000):
        out = []
        for _ in range(max_steps):
            out.extend(self.step())
            if not self.queue and all(r is None for r in self.slot_req):
                break
        return out


def _workload(batch, vocab, max_new, rng):
    lens = [5, 9, 12, 17, 23, 28, 33, 40]
    reqs = []
    for rid in range(batch):
        L = lens[rid % len(lens)]
        reqs.append(Request(rid=rid, prompt=rng.integers(
            0, vocab, size=L).astype(np.int32), max_new_tokens=max_new))
    return reqs


def _measure(eng, make_reqs, ttft_prompt):
    """Warm run (compiles), then timed admission + steady-state decode.

    Returns (gen_tok_s, decode_tok_s, ttft_s): overall generated tokens/sec
    including admissions, decode-only tokens/sec with all slots admitted
    (the steady-state rate), and time-to-first-token for one warm
    admission."""
    for r in make_reqs():
        eng.submit(r)
    eng.run()
    reqs = make_reqs()
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    eng._admit()                       # per-slot prefill + first tokens
    t_admit = time.perf_counter() - t0
    t0 = time.perf_counter()
    done = eng.run()
    t_dec = time.perf_counter() - t0
    n_tok = sum(len(t[1] if isinstance(t, tuple) else t.tokens) for t in done)
    t0 = time.perf_counter()
    eng.submit(Request(rid=10_000, prompt=ttft_prompt, max_new_tokens=2))
    eng._admit()                       # prefill + first sampled token
    ttft = time.perf_counter() - t0
    eng.run()                          # drain the probe request
    return (n_tok / (t_admit + t_dec),
            (n_tok - len(reqs)) / t_dec,   # first tokens fell in admission
            ttft)


def _scaling_cfg(smoke: bool):
    """Model/workload for the device sweep. Head and hidden dims divide the
    2-way model axis so the TP split is clean at every device count."""
    if smoke:
        cfg = ModelConfig(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                          d_ff=128, vocab=256, remat="none", dtype="float32")
        return cfg, 8, 64
    cfg = ModelConfig(n_layers=3, d_model=128, n_heads=4, n_kv_heads=2,
                      d_ff=256, vocab=2048, remat="none", dtype="float32")
    return cfg, 32, 128


_SCALE_SCRIPT = r"""
import sys
n, model_par, smoke = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d" % n
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import json
from functools import partial
import jax
import numpy as np
from benchmarks.serve_bench import _measure, _scaling_cfg, _workload
from repro.launch.mesh import make_serve_mesh
from repro.models.lm import init
from repro.serving import SamplerConfig, ServeEngine

cfg, max_new, max_len = _scaling_cfg(bool(smoke))
params = init(cfg, jax.random.PRNGKey(0))
mesh = make_serve_mesh(model_par) if n > 1 else None
eng = ServeEngine(cfg, params, max_batch=8, max_len=max_len,
                  sampler=SamplerConfig(temperature=0.0), mesh=mesh)
rng = np.random.default_rng(0)
make_reqs = partial(_workload, 8, cfg.vocab, max_new, rng)
ttft_prompt = (np.arange(1, 6, dtype=np.int32) % cfg.vocab).astype(np.int32)
gen, dec, ttft = _measure(eng, make_reqs, ttft_prompt)
# The mechanism gate: textual collective counts flat across the decode
# drain family (n=1 vs n=drain_steps) proves every collective sits outside
# the scan body — the property that survives on real accelerators, unlike
# CPU-cell speedup (see serve_device_scaling's rationale).
from repro.analysis import hlo
hp = next(h for h in eng.hot_paths() if h.name.startswith("lm.decode"))
counts = [hlo.collective_counts(p.compiled_text()) for p in hp.programs]
print(json.dumps({
    "devices": n,
    "mesh": "-" if mesh is None else "%dx%d (data x model)" % (
        n // model_par, model_par),
    "gen_tok_s": round(gen, 1), "decode_tok_s": round(dec, 1),
    "ttft_ms": round(ttft * 1e3, 1),
    "decode_collectives": counts[0],
    "collectives_flat": all(c == counts[0] for c in counts)}))
"""


def require_cpu_parent(section: str) -> None:
    """The device-scaling sections start ``JAX_PLATFORMS=cpu`` children:
    they check the sharding mechanism on forced host devices and time the
    CPU. Run from a parent on an accelerator, they would file CPU timings
    beside its numbers, so they refuse."""
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"{section} times forced CPU host devices; it runs only from a "
            f"CPU parent, not from {jax.default_backend()!r}")


def serve_device_scaling(smoke: bool = False):
    """Decode throughput of the mesh-sharded engine per device count.

    Each cell runs in a subprocess so XLA_FLAGS can force that cell's host
    device count before jax initializes; the 1-device cell is the mesh-free
    engine (the baseline the speedup column normalizes against).

    Expected regression on this CPU host: the 2-device cell decodes at
    ~0.85x of 1 device. Forced host devices share the same cores, the
    per-device shapes are tiny (d_model <= 128 decode GEMMs), and every
    step pays a fixed collective-dispatch floor — so splitting the model
    axis adds overhead without adding compute. This is the *mechanism*
    sweep, not a speedup claim; the property CI gates on is
    ``collectives_flat`` (textual collective counts identical across the
    n=1 / n=drain_steps decode family, i.e. no collective inside the scan
    body), which is what transfers to a real multi-chip deployment.
    """
    require_cpu_parent("serve_device_scaling")
    cells = [(1, 1), (2, 2)] if smoke else [(1, 1), (2, 2), (4, 2), (8, 2)]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH="src" + os.pathsep + ".",
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    rows = []
    for n, model_par in cells:
        out = subprocess.run(
            [sys.executable, "-c", _SCALE_SCRIPT, str(n), str(model_par),
             str(int(smoke))],
            capture_output=True, text=True, env=env, cwd=repo)
        if out.returncode != 0:
            raise RuntimeError(
                f"device-scaling cell n={n} failed: {out.stderr[-2000:]}")
        rows.append(json.loads(out.stdout.strip().splitlines()[-1]))
    base = rows[0]["decode_tok_s"] or 1.0
    for r in rows:
        r["decode_speedup_vs_1dev"] = round(r["decode_tok_s"] / base, 2)
    print("note: forced host devices share CPU cores — ~0.85x decode at "
          "2 devices is the expected regression (tiny per-device shapes, "
          "fixed collective-dispatch floor). The gated invariant is "
          "collectives_flat, not speedup.")
    return rows


def serve_throughput(smoke: bool = False):
    """tokens/sec + TTFT across batch sizes, legacy vs fused hot loop."""
    if smoke:
        cfg = ModelConfig(n_layers=2, d_model=32, n_heads=2, n_kv_heads=1,
                          d_ff=64, vocab=256, remat="none", dtype="float32")
        batches, max_new, max_len = [1, 8], 8, 64
    else:
        # CPU-reference shape: small enough that the per-token model math
        # does not drown the orchestration costs this benchmark isolates
        # (dispatch count, logits transfer, state copies, host sampling).
        cfg = ModelConfig(n_layers=3, d_model=64, n_heads=4, n_kv_heads=2,
                          d_ff=128, vocab=2048, remat="none", dtype="float32")
        batches, max_new, max_len = [1, 4, 8], 64, 128
    params = init(cfg, jax.random.PRNGKey(0))
    sampler = SamplerConfig(temperature=0.0)
    # Probe length 5 = the first workload length, so its prefill chunk
    # shapes ({4, 1}) are warm at every batch size — TTFT measures the
    # admission path, not a compile.
    ttft_prompt = (np.arange(1, 6, dtype=np.int32) % cfg.vocab).astype(np.int32)

    rows = []
    for b in batches:
        nprng = np.random.default_rng(0)
        make_reqs = partial(_workload, b, cfg.vocab, max_new, nprng)
        legacy = _LegacyEngine(cfg, params, max_batch=b, max_len=max_len,
                               sampler=sampler)
        gen_old, dec_old, ttft_old = _measure(legacy, make_reqs, ttft_prompt)
        fused = ServeEngine(cfg, params, max_batch=b, max_len=max_len,
                            sampler=sampler)
        gen_new, dec_new, ttft_new = _measure(fused, make_reqs, ttft_prompt)
        base = {"batch": b, "prompt_mix": "5..40", "max_new": max_new}
        rows.append(dict(base, path="legacy",
                         gen_tok_s=round(gen_old, 1),
                         decode_tok_s=round(dec_old, 1),
                         ttft_ms=round(ttft_old * 1e3, 1),
                         decode_speedup=1.0))
        rows.append(dict(base, path="fused",
                         gen_tok_s=round(gen_new, 1),
                         decode_tok_s=round(dec_new, 1),
                         ttft_ms=round(ttft_new * 1e3, 1),
                         decode_speedup=round(dec_new / dec_old, 2)))
    return rows


# -- gateway overload benchmark ----------------------------------------------
#
# Poisson-arrival mixed LM + vision load through repro.serving.gateway:
#   capacity  — every request submitted at once into a deep queue; measures
#               the sustainable service rate and the no-overload goodput
#               (and pins the golden token streams for the bit-identity
#               check).
#   unloaded  — Poisson arrivals at ~0.4x the measured capacity; bounded
#               queues stay shallow, TTFT here is the tail-latency baseline.
#   overload  — Poisson arrivals at 2x capacity with bounded per-tenant
#               queues and deadlines: the gateway must shed (with
#               retry-after hints) instead of growing the queue, keep
#               admitted streams bit-identical to the capacity run, and
#               keep goodput at the engine's service rate.


def _gw_cnn():
    """Tiny 2-conv CNN for the vision share of the mixed workload."""
    import types

    from repro.models.cnn import layers as L

    def cnn_init(key, num_classes=10):
        k1, k2, k3 = jax.random.split(key, 3)
        return {"c1": L.init_conv(k1, 3, 3, 8),
                "c2": L.init_conv(k2, 3, 8, 16),
                "head": L.init_fc(k3, 16, num_classes)}

    def cnn_apply(params, x, cfg=None, train=False):
        x = L.conv_block(params["c1"], x, stride=2, padding=1, cfg=cfg,
                         train=train)
        x = L.conv_block(params["c2"], x, stride=2, padding=1, cfg=cfg,
                         train=train)
        x = L.avg_pool_global(x)
        return L.fc_block(params["head"], x, cfg=cfg, relu=False,
                          train=train)

    module = types.SimpleNamespace(init=cnn_init, apply=cnn_apply)
    return module, cnn_init(jax.random.PRNGKey(0))


def _gw_workload(n_req, vocab, max_new, max_len, vision_every=5):
    """Deterministic rid -> request table (same across the three runs, so
    the capacity run's outputs are the golden streams for the others)."""
    rng = np.random.default_rng(7)
    items = []
    for rid in range(n_req):
        if vision_every and rid % vision_every == vision_every - 1:
            img = rng.standard_normal((16, 16, 3)).astype(np.float32)
            items.append(("vision", rid, img))
        else:
            hi = min(25, max_len - max_new - 1)
            L = int(rng.integers(3, hi))
            items.append(("lm", rid, rng.integers(
                0, vocab, size=L).astype(np.int32)))
    return items


async def _gw_run(gw, items, rate_req_s, max_new, deadline_ms, seed,
                  sequential=False):
    """Drive one load-generator run; returns raw outcomes + stats().

    ``rate_req_s`` schedules Poisson arrivals against *absolute* target
    times (sleep only the remaining delta, never re-accumulating sleep
    overshoot): event-loop jitter then produces catch-up bursts instead of
    silently lowering the offered rate, so "2x capacity" stays 2x capacity.
    ``sequential`` is the closed-loop no-queueing baseline: one request in
    flight at a time (arrival rate == completion rate by construction).
    """
    import asyncio

    from repro.serving import DeadlineExceeded, ShedError

    rng = np.random.default_rng(seed)
    tokens, top1 = {}, {}
    sheds, expired = [], []

    async def eat_lm(rid, s):
        try:
            tokens[rid] = await s.result()
        except DeadlineExceeded:
            expired.append(rid)
        except ShedError as e:           # tier-3 shed after queueing
            sheds.append((rid, e.retry_after_s))

    async def eat_vi(rid, t):
        try:
            top1[rid] = int((await t.result()).top1)
        except DeadlineExceeded:
            expired.append(rid)
        except ShedError as e:
            sheds.append((rid, e.retry_after_s))

    tasks = []
    deadlocks = 0
    t0 = time.perf_counter()
    next_arrival = t0
    for kind, rid, payload in items:
        if rate_req_s:
            next_arrival += float(rng.exponential(1.0 / rate_req_s))
            delay = next_arrival - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
        tenant = "gold" if rid % 2 == 0 else "bronze"
        try:
            if kind == "lm":
                s = await gw.submit_lm(payload, max_new_tokens=max_new,
                                       tenant=tenant, deadline_ms=deadline_ms,
                                       rid=rid)
                coro = eat_lm(rid, s)
            else:
                t = await gw.submit_vision(payload, model="tiny",
                                           precision="<4:4>", tenant=tenant,
                                           deadline_ms=deadline_ms, rid=rid)
                coro = eat_vi(rid, t)
        except ShedError as e:           # shed at admission (the common case)
            sheds.append((rid, e.retry_after_s))
            continue
        if sequential:
            await coro
        else:
            tasks.append(asyncio.ensure_future(coro))
    try:
        await asyncio.wait_for(asyncio.gather(*tasks), timeout=300)
        await gw.drain(timeout=60)
    except (asyncio.TimeoutError, TimeoutError):
        deadlocks = 1                    # a stuck stream IS the failure mode
    wall = time.perf_counter() - t0
    return dict(tokens=tokens, top1=top1, sheds=sheds, expired=expired,
                wall=wall, deadlocks=deadlocks, stats=gw.stats())


def gateway_bench(smoke: bool = False):
    import asyncio

    from repro.serving import (Gateway, GatewayConfig, SamplerConfig,
                               ServeEngine, VisionEngine)

    if smoke:
        cfg = ModelConfig(n_layers=2, d_model=32, n_heads=2, n_kv_heads=1,
                          d_ff=64, vocab=256, remat="none", dtype="float32")
        n_req, max_new, max_len, max_batch = 48, 8, 64, 4
    else:
        cfg = ModelConfig(n_layers=3, d_model=64, n_heads=4, n_kv_heads=2,
                          d_ff=128, vocab=2048, remat="none", dtype="float32")
        n_req, max_new, max_len, max_batch = 96, 16, 128, 8
    params = init(cfg, jax.random.PRNGKey(0))
    lm = ServeEngine(cfg, params, max_batch=max_batch, max_len=max_len,
                     sampler=SamplerConfig(temperature=0.0))
    orig_drain = lm.drain_steps
    vision = VisionEngine({"tiny": _gw_cnn()}, backend="int-direct",
                          max_batch=max_batch)
    items = _gw_workload(n_req, cfg.vocab, max_new, max_len)
    weights = {"gold": 2.0, "bronze": 1.0}
    prio = {"gold": 1, "bronze": 0}

    def run_once(rate, queue_depth, deadline_ms, seed, sequential=False):
        gw_cfg = GatewayConfig(queue_depth=queue_depth,
                               tenant_weights=weights, tenant_priority=prio)

        async def main():
            gw = Gateway(lm=lm, vision=vision, cfg=gw_cfg)
            gw.start()
            try:
                return await _gw_run(gw, items, rate, max_new, deadline_ms,
                                     seed, sequential=sequential)
            finally:
                gw.stop()
        out = asyncio.run(main())
        lm.drain_steps = orig_drain      # undo any leftover tier-1 lever
        return out

    # Warm run (populates every prefill-chunk/decode/vision compile) so the
    # timed runs measure serving, not XLA compilation.
    run_once(rate=None, queue_depth=n_req, deadline_ms=None, seed=1)

    # Sustainable rate: everything queued at once into a deep bound — the
    # engine batches maximally, so completed/wall is the service capacity.
    cap = run_once(rate=None, queue_depth=n_req, deadline_ms=None, seed=2)
    n_lm = sum(1 for k, _, _ in items if k == "lm")
    cap_req_s = n_req / cap["wall"]
    deadline = 2_000.0 if smoke else 4_000.0
    # No-overload tail-latency baseline: closed-loop, one request in
    # flight — TTFT here is pure admission + first token, zero queue wait.
    unl = run_once(rate=None, queue_depth=8, deadline_ms=deadline, seed=3,
                   sequential=True)
    # No-overload *goodput* baseline: Poisson at 1x capacity — the same
    # arrival process (and so the same vision micro-batch fragmentation)
    # as the overload run, without sustained excess.
    lod = run_once(rate=1.0 * cap_req_s, queue_depth=2 * max_batch,
                   deadline_ms=deadline, seed=5)
    # 2x sustained overload into tight bounded queues: the gateway must
    # shed (with hints), keep depth bounded, and keep goodput at the
    # no-overload level instead of collapsing under congestion.
    ovl = run_once(rate=2.0 * cap_req_s, queue_depth=2 * max_batch,
                   deadline_ms=deadline, seed=4)

    golden = cap["tokens"], cap["top1"]
    assert len(golden[0]) == n_lm, "capacity run must complete every request"

    def row(name, r, offered_req_s):
        st = r["stats"]
        done_tok = sum(len(t) for t in r["tokens"].values())
        n_done = len(r["tokens"]) + len(r["top1"])
        bit_ok = (all(t == golden[0][rid] for rid, t in r["tokens"].items())
                  and all(v == golden[1][rid] for rid, v in r["top1"].items()))
        return {
            "run": name,
            "offered_req_s": round(offered_req_s, 1),
            "n_req": len(items), "done": n_done,
            "shed": len(r["sheds"]), "expired": len(r["expired"]),
            "shed_rate": round(len(r["sheds"]) / len(items), 3),
            "goodput_tok_s": round(done_tok / r["wall"], 1),
            "ttft_p95_ms": st["ttft_ms"]["p95"] and round(
                st["ttft_ms"]["p95"], 1),
            "ttft_admit_p95_ms": st["ttft_admit_ms"]["p95"] and round(
                st["ttft_admit_ms"]["p95"], 1),
            "max_queue_depth": st["queue"]["max_depth"],
            "queue_bound": st["queue"]["bound"],
            "tier_max": max([e["tier"] for e in st["events"]
                             if "tier" in e], default=0),
            "deadlocks": r["deadlocks"],
            "tokens_bit_identical": bit_ok,
            "retry_after_hints_ok": all(ra > 0 for _, ra in r["sheds"]),
        }

    rows = [row("capacity", cap, cap_req_s),
            row("unloaded-seq", unl, len(items) / unl["wall"]),
            row("loaded-1x", lod, cap_req_s),
            row("overload-2x", ovl, 2.0 * cap_req_s)]
    # Acceptance ratios (PR 7): overload goodput vs the load-matched
    # no-overload (1x) run, and admission-referenced TTFT tail vs the
    # unloaded baseline (submit-referenced TTFT under overload includes
    # the bounded queue wait, which the deadline/shed knobs govern —
    # reported, not ratioed).
    unl_admit = rows[1]["ttft_admit_p95_ms"] or float("nan")
    ovl_admit = rows[3]["ttft_admit_p95_ms"] or float("nan")
    rows[3]["goodput_x_vs_no_overload"] = round(
        rows[3]["goodput_tok_s"] / max(rows[2]["goodput_tok_s"], 1e-9), 3)
    rows[3]["ttft_admit_p95_x_vs_unloaded"] = round(ovl_admit / unl_admit, 2)
    return rows
