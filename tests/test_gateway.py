"""Overload-safe gateway (DESIGN.md §8): bounded weighted-fair admission,
deadline expiry + mid-generation cancellation, load shedding with
retry-after, degradation-ladder levers and reversibility, telemetry rings,
and token parity vs the bare engine."""
import asyncio
import time
import types

import jax
import numpy as np
import pytest

from repro.models.lm import ModelConfig, init
from repro.serving import (DeadlineExceeded, Gateway, GatewayConfig, Request,
                           Ring, SamplerConfig, ServeEngine, ShedError,
                           VisionEngine, VisionRequest)
from repro.serving.gateway import _FairQueues, _Handle

CFG = ModelConfig(n_layers=2, d_model=32, n_heads=2, n_kv_heads=1, d_ff=64,
                  vocab=51, remat="none", dtype="float32")


@pytest.fixture(scope="module")
def params():
    return init(CFG, jax.random.PRNGKey(0))


def _engine(params, max_batch=2, max_len=64, **kw):
    return ServeEngine(CFG, params, max_batch=max_batch, max_len=max_len,
                       sampler=SamplerConfig(temperature=0.0), **kw)


def _prompts(n, rng=None, lo=2, hi=9):
    rng = rng or np.random.default_rng(0)
    return [rng.integers(0, CFG.vocab, size=int(rng.integers(lo, hi)))
            .astype(np.int32) for _ in range(n)]


# -- fair admission (unit) ---------------------------------------------------

def _fake_handle(tenant, rid=0):
    return _Handle(loop=None, rid=rid, tenant=tenant, kind="lm",
                   payload=None, deadline_t=None)


def test_stride_scheduling_matches_weights():
    """Weights 2:1 under saturation admit exactly 2:1 (stride scheduling)."""
    cfg = GatewayConfig(queue_depth=16, tenant_weights={"a": 2.0, "b": 1.0})
    fq = _FairQueues(cfg)
    for i in range(12):
        fq.push(_fake_handle("a", i))
        fq.push(_fake_handle("b", 100 + i))
    order = [fq.pop_next(0.0).tenant for _ in range(9)]
    assert order.count("a") == 6 and order.count("b") == 3, order
    # An idle tenant's share redistributes: drain b, a still admits.
    while fq.depth("b"):
        fq.pop_next(0.0)
    assert all(fq.pop_next(0.0).tenant == "a" for _ in range(fq.depth("a")))


def test_fair_queue_new_tenant_no_catchup():
    """A late-arriving tenant starts at the current min pass — it neither
    starves the incumbents nor claims retroactive catch-up credit."""
    fq = _FairQueues(GatewayConfig(queue_depth=16))
    for i in range(8):
        fq.push(_fake_handle("a", i))
    for _ in range(4):
        fq.pop_next(0.0)
    for i in range(8):
        fq.push(_fake_handle("late", 100 + i))
    order = [fq.pop_next(0.0).tenant for _ in range(4)]
    # Equal weights from here on: strict alternation, not a "late" monopoly.
    assert sorted(order.count(t) for t in ("a", "late")) == [2, 2], order


# -- shedding + bounded queues ----------------------------------------------

def test_full_queue_sheds_with_retry_after(params):
    async def main():
        eng = _engine(params, max_batch=1)
        gw = Gateway(lm=eng, cfg=GatewayConfig(queue_depth=2))
        gw.start()
        prompts = _prompts(16)
        streams, sheds = [], []
        # Flood without yielding: the worker can admit at most max_batch=1
        # concurrently, so the depth-2 tenant queue must overflow.
        for rid, p in enumerate(prompts):
            try:
                streams.append(await gw.submit_lm(p, max_new_tokens=4,
                                                  rid=rid))
            except ShedError as e:
                sheds.append(e)
        assert sheds, "expected at least one shed from a depth-2 queue"
        assert all(e.retry_after_s > 0 for e in sheds)
        assert all(e.reason == "queue_full" for e in sheds)
        outs = await asyncio.gather(*[s.result() for s in streams])
        await gw.drain(timeout=60)
        st = gw.stats()
        gw.stop()
        # Bounded by construction: the recorded high-water mark respects it.
        assert st["queue"]["max_depth"] <= st["queue"]["bound"]
        assert st["shed_rate"] > 0
        assert all(len(o) == 4 for o in outs)

    asyncio.run(main())


# -- token parity ------------------------------------------------------------

def test_gateway_token_parity_vs_bare_engine(params):
    """The gateway adds zero numerics: streamed tokens are bit-identical to
    the bare engine run with the same prompts (greedy)."""
    prompts = _prompts(6)
    eng = _engine(params)
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid=rid, prompt=p, max_new_tokens=5))
    want = {c.rid: c.tokens for c in eng.run()}

    async def main():
        gw = Gateway(lm=_engine(params), cfg=GatewayConfig(queue_depth=8))
        gw.start()
        streams = [await gw.submit_lm(p, max_new_tokens=5, rid=rid)
                   for rid, p in enumerate(prompts)]
        outs = await asyncio.gather(*[s.result() for s in streams])
        await gw.drain(timeout=60)
        gw.stop()
        return {s.rid: o for s, o in zip(streams, outs)}

    got = asyncio.run(main())
    assert got == want


# -- deadlines ---------------------------------------------------------------

def test_deadline_expires_while_queued(params):
    async def main():
        eng = _engine(params, max_batch=1)
        gw = Gateway(lm=eng, cfg=GatewayConfig(queue_depth=8))
        gw.start()
        # Occupy the only slot with a long generation, then queue a request
        # whose deadline cannot survive the wait.
        long_s = await gw.submit_lm(_prompts(1)[0], max_new_tokens=40,
                                    rid=0)
        doomed = await gw.submit_lm(_prompts(1)[0], max_new_tokens=4,
                                    rid=1, deadline_ms=1.0)
        with pytest.raises(DeadlineExceeded):
            await doomed.result()
        assert doomed.status == "expired"
        out = await long_s.result()
        assert len(out) == 40, "survivor must be unaffected by the expiry"
        await gw.drain(timeout=60)
        gw.stop()

    asyncio.run(main())


def test_deadline_cancels_mid_generation_and_frees_slot(params):
    async def main():
        eng = _engine(params, max_batch=1, drain_steps=1)
        gw = Gateway(lm=eng, cfg=GatewayConfig(queue_depth=8))
        gw.start()
        s = await gw.submit_lm(_prompts(1)[0], max_new_tokens=55,
                               rid=0, deadline_ms=150.0)
        with pytest.raises(DeadlineExceeded):
            await s.result()
        assert s.status == "expired"
        assert s.tokens, "some tokens must have streamed before expiry"
        # The slot frees at the next token boundary: a follow-up request
        # admits and completes, token-identical to a fresh engine.
        follow = await gw.submit_lm(np.array([3, 1, 4], np.int32),
                                    max_new_tokens=6, rid=1)
        got = await follow.result()
        await gw.drain(timeout=60)
        gw.stop()
        assert all(r is None for r in eng.slot_req)
        return got

    got = asyncio.run(main())
    fresh = _engine(params, max_batch=1)
    fresh.submit(Request(rid=0, prompt=np.array([3, 1, 4], np.int32),
                         max_new_tokens=6))
    assert got == fresh.run()[0].tokens


def test_submit_lm_validates_on_caller_thread(params):
    async def main():
        gw = Gateway(lm=_engine(params, max_len=32),
                     cfg=GatewayConfig(queue_depth=4))
        gw.start()
        with pytest.raises(ValueError, match="empty prompt"):
            await gw.submit_lm(np.zeros(0, np.int32), max_new_tokens=4)
        with pytest.raises(ValueError, match="exceeds the decode grid"):
            await gw.submit_lm(np.arange(30, dtype=np.int32) % CFG.vocab,
                               max_new_tokens=8)
        gw.stop()

    asyncio.run(main())


# -- degradation ladder ------------------------------------------------------

def test_ladder_tier1_engages_and_reverses(params):
    async def main():
        eng = _engine(params, max_batch=1, drain_steps=8)
        gw = Gateway(lm=eng, cfg=GatewayConfig(
            queue_depth=4, tier_hold_s=0.03, overload_enter=0.5,
            overload_exit=0.25, degraded_drain_steps=1))
        gw.start()
        tasks, t0 = [], time.monotonic()
        saw_tier = 0
        while time.monotonic() - t0 < 4.0:
            try:
                s = await gw.submit_lm(_prompts(1)[0], max_new_tokens=16)
                tasks.append(asyncio.ensure_future(s.result()))
            except ShedError:
                await asyncio.sleep(0.01)
            saw_tier = max(saw_tier, gw.stats()["tier"])
            if saw_tier >= 1 and eng.drain_steps == 1:
                break
        assert saw_tier >= 1, "sustained overload never escalated the ladder"
        assert eng.drain_steps == 1, "tier-1 lever did not shrink drain_steps"
        # Load drops: the ladder walks back and restores the lever.
        await asyncio.gather(*tasks, return_exceptions=True)
        await gw.drain(timeout=60)
        t0 = time.monotonic()
        while gw.stats()["tier"] > 0 and time.monotonic() - t0 < 5.0:
            await asyncio.sleep(0.02)
        st = gw.stats()
        gw.stop()
        assert st["tier"] == 0, "ladder did not de-escalate after drain"
        assert eng.drain_steps == 8, "tier-1 lever was not reversed"
        assert any(e.get("tier") == 1 for e in st["events"]), st["events"]

    asyncio.run(main())


def test_tier2_precision_redeploy_reversible(params):
    """Tier 2 re-deploys the LM engine on a cheaper path via the PR 5
    re-prepack machinery and reverses on de-escalation (lever unit test —
    the ladder's timing is exercised by the tier-1 test)."""
    from repro.core import PIMQuantConfig
    import dataclasses as dc

    cfg = dc.replace(CFG, pim=PIMQuantConfig(w_bits=4, a_bits=4,
                                             backend="int-direct"))
    pim_params = init(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, pim_params, max_batch=2, max_len=64,
                      sampler=SamplerConfig(temperature=0.0),
                      keep_masters=True)
    gw = Gateway(lm=eng, cfg=GatewayConfig(degrade_precision=True))
    assert eng.cfg.pim.enabled
    gw._set_tier(2, "test")
    assert not eng.cfg.pim.enabled, "tier 2 must re-deploy off the PIM path"
    gw._set_tier(1, "test")
    assert eng.cfg.pim.enabled, "de-escalation must restore the precision"
    # The re-deployed engine still serves correctly end to end.
    eng.submit(Request(rid=0, prompt=np.array([3, 1, 4], np.int32),
                       max_new_tokens=4))
    assert len(eng.run()[0].tokens) == 4


def test_tier3_sheds_lowest_priority_tenant(params):
    async def main():
        eng = _engine(params, max_batch=1)
        # tier_hold_s=60: pin the ladder so only the explicit _set_tier
        # calls below move it (the load here is far below overload_enter).
        gw = Gateway(lm=eng, cfg=GatewayConfig(
            queue_depth=8, tier_hold_s=60.0,
            tenant_priority={"gold": 1, "bronze": 0}))
        gw.start()
        # Park one doomed bronze request in the queue behind a long one.
        blocker = await gw.submit_lm(_prompts(1)[0], max_new_tokens=30,
                                     tenant="gold")
        parked = await gw.submit_lm(_prompts(1)[0], max_new_tokens=4,
                                    tenant="bronze")
        parked_task = asyncio.ensure_future(parked.result())
        await asyncio.sleep(0)
        gw._set_tier(3, "test")
        with pytest.raises(ShedError):
            await parked_task
        with pytest.raises(ShedError):   # new bronze submissions rejected
            await gw.submit_lm(_prompts(1)[0], max_new_tokens=4,
                               tenant="bronze")
        gold = await gw.submit_lm(_prompts(1)[0], max_new_tokens=4,
                                  tenant="gold")   # gold still admitted
        assert len(await gold.result()) == 4
        gw._set_tier(0, "test")
        bronze = await gw.submit_lm(_prompts(1)[0], max_new_tokens=4,
                                    tenant="bronze")
        assert len(await bronze.result()) == 4, "tier-3 shed must reverse"
        await blocker.result()
        await gw.drain(timeout=60)
        gw.stop()

    asyncio.run(main())


# -- vision path -------------------------------------------------------------

def _tiny_cnn():
    from repro.models.cnn import layers as L

    def cnn_init(key, image=16, num_classes=10):
        k1, k2 = jax.random.split(key)
        return {"c1": L.init_conv(k1, 3, 3, 8),
                "head": L.init_fc(k2, 8, num_classes)}

    def cnn_apply(params, x, cfg=None, train=False):
        x = L.conv_block(params["c1"], x, stride=2, padding=1, cfg=cfg,
                         train=train)
        x = L.avg_pool_global(x)
        return L.fc_block(params["head"], x, cfg=cfg, relu=False,
                          train=train)

    module = types.SimpleNamespace(init=cnn_init, apply=cnn_apply)
    return module, cnn_init(jax.random.PRNGKey(0))


def test_vision_gateway_roundtrip_matches_engine():
    module, vparams = _tiny_cnn()
    rng = np.random.default_rng(0)
    imgs = rng.standard_normal((4, 16, 16, 3)).astype(np.float32)

    eng = VisionEngine({"tiny": (module, vparams)}, backend="int-direct",
                       max_batch=4)
    for rid in range(4):
        eng.submit(VisionRequest(rid=rid, image=imgs[rid], model="tiny",
                                 precision="<4:4>"))
    want = {c.rid: (c.top1, c.logits) for c in eng.run()}

    async def main():
        gw = Gateway(vision=VisionEngine({"tiny": (module, vparams)},
                                         backend="int-direct", max_batch=4),
                     cfg=GatewayConfig(queue_depth=8))
        gw.start()
        tickets = [await gw.submit_vision(imgs[rid], model="tiny",
                                          precision="<4:4>", rid=rid)
                   for rid in range(4)]
        outs = await asyncio.gather(*[t.result() for t in tickets])
        await gw.drain(timeout=60)
        st = gw.stats()
        gw.stop()
        assert st["ttft_ms"]["p50"] is not None
        lowered = st["vision_counters"]["conv_lowering"]
        assert lowered["im2col"] >= 1       # one per compiled bucket
        assert lowered["fused"] == lowered["pointwise"] == 0
        return {c.rid: (c.top1, c.logits) for c in outs}

    got = asyncio.run(main())
    assert got.keys() == want.keys()
    for rid in want:
        assert got[rid][0] == want[rid][0]
        np.testing.assert_array_equal(got[rid][1], want[rid][1])


def test_vision_deadline_expires_queued():
    module, vparams = _tiny_cnn()
    img = np.zeros((16, 16, 3), np.float32)

    async def main():
        gw = Gateway(vision=VisionEngine({"tiny": (module, vparams)},
                                         max_batch=2),
                     cfg=GatewayConfig(queue_depth=8))
        gw.start()
        # Deadline already burned at submission time.
        t = await gw.submit_vision(img, model="tiny", precision=None,
                                   deadline_ms=0.0)
        with pytest.raises(DeadlineExceeded):
            await t.result()
        ok = await gw.submit_vision(img, model="tiny", precision=None)
        c = await ok.result()
        assert c.logits.shape == (10,)
        await gw.drain(timeout=60)
        gw.stop()

    asyncio.run(main())


# -- telemetry ---------------------------------------------------------------

def test_ring_is_fixed_size():
    r = Ring(16)
    for i in range(1000):
        r.push(float(i))
    assert len(r) == 16
    assert r.values().min() == 984.0   # only the newest window survives
    p = r.percentiles()
    assert set(p) == {"p50", "p95", "p99"} and p["p50"] >= 984.0
    assert Ring(8).percentiles() == {"p50": None, "p95": None, "p99": None}


def test_stats_snapshot_shape(params):
    async def main():
        gw = Gateway(lm=_engine(params), cfg=GatewayConfig(queue_depth=4))
        gw.start()
        s = await gw.submit_lm(_prompts(1)[0], max_new_tokens=4,
                               tenant="acme")
        await s.result()
        await gw.drain(timeout=60)
        st = gw.stats()
        gw.stop()
        return st

    st = asyncio.run(main())
    for key in ("tier", "queue", "ttft_ms", "ttft_admit_ms", "tpot_ms",
                "tok_s", "shed", "shed_rate", "goodput_tok_s_by_tenant",
                "events", "errors", "lm_health", "lm_counters"):
        assert key in st, key
    assert st["lm_counters"]["admitted"] == st["lm_counters"]["submitted"] == 1
    assert st["queue"]["bound"] > 0
    assert "acme" in st["goodput_tok_s_by_tenant"]
    assert st["shed_rate"] == 0.0
    assert st["errors"] == []
