"""The engines' own spans and counters, on the tiny cells of
``test_correct.py``: the counters over a window equal the driver's
reckoning, the spans carry what a reader of the window needs, a warm
window compiles nothing and a cold prefill chunk length compiles; and the
stem conv kernel found by its name in a trace."""
import collections

import pytest

from bench import harness, trace
from bench.tests.test_correct import SECONDS, Prepared, _tiny
from repro import obs
from repro.serving.engine import _pow2_chunks


@pytest.fixture(scope="module")
def chat():
    return Prepared("qwen3-0.6b.w8a8-chat-poisson")


@pytest.fixture(scope="module")
def backlog():
    return Prepared("resnet50-224.w8a8-backlog")


def _window(driver, record: bool):
    """One measured window: the engine's counters over it, and the spans
    recorded in it."""
    before = driver.engine.stats()["counters"]
    if record:
        obs.start()
    try:
        win = driver.window(SECONDS, harness.Tracer(False, SECONDS, None))
    finally:
        spans = obs.stop() if record else []
    after = driver.engine.stats()["counters"]
    ctr = {k: (after[k] - before[k] if isinstance(after[k], int) else
               {b: n - before[k].get(b, 0) for b, n in after[k].items()})
           for k in after}
    return win, ctr, spans


def _named(spans, name):
    return [s for s in spans if s.name == name]


def test_lm_counters_match_the_drivers_reckoning(chat):
    win, ctr, _ = _window(chat.driver, record=False)
    drv = chat.driver
    rids = sorted(drv.tokens)
    assert len(rids) == win.attempted and drv.failed == 0
    prompts = [len(drv.reqs.prompts[r]) for r in rids]
    lens = [len(drv.tokens[r]) for r in rids]
    dec = win.counters["decode"]
    assert ctr["submitted"] == ctr["admitted"] == len(rids)
    assert sorted(win.counters["prompts"]) == sorted(prompts)
    assert ctr["prefill_tokens"] == sum(prompts)
    assert ctr["prefill_chunks"] == sum(len(_pow2_chunks(p)) for p in prompts)
    assert ctr["decode_dispatches"] == len(dec)
    assert ctr["decode_steps"] == sum(s for s, _, _ in dec)
    assert ctr["slot_steps"] == sum(s * n for s, n, _ in dec)
    assert ctr["tokens_out"] == sum(lens)
    # The t-th output token (t >= 2) is decoded attending p + t - 1
    # positions: the prompt and the t - 1 tokens before it.
    assert ctr["kv_tokens"] == sum((t - 1) * p + t * (t - 1) // 2
                                   for p, t in zip(prompts, lens))


def test_lm_spans_of_a_warm_window(chat):
    _, _, spans = _window(chat.driver, record=True)
    drv = chat.driver
    steps = {s.id: s for s in _named(spans, "serve.step")}
    admits = {s.attrs["rid"]: s for s in _named(spans, "serve.admit")}
    queued = {s.attrs["rid"]: s for s in _named(spans, "serve.queued")}
    assert sorted(admits) == sorted(queued) == sorted(drv.tokens)
    for rid, a in admits.items():
        prompt = len(drv.reqs.prompts[rid])
        assert a.attrs["prompt_len"] == prompt
        assert a.attrs["chunks"] == len(_pow2_chunks(prompt))
        assert 0 <= a.attrs["live"] < drv.max_batch
        # the first token waits in its step from here to the step's end
        step = steps[a.parent]
        assert step.start <= a.start <= a.end <= step.end
        assert queued[rid].end <= a.start
        assert queued[rid].parent == a.parent
    fetches = _named(spans, "serve.fetch")
    assert fetches and all(f.parent in steps for f in fetches)
    assert _named(spans, "jax.compile") == []


def test_a_cold_prefill_chunk_compiles_in_the_window():
    cell = _tiny("qwen3-0.6b.w8a8-chat-poisson")
    drv = harness.driver_class(cell)(cell, 2**31 + 5)
    longest = cell.traffic["prompt_len"]["max"]
    cell.traffic["prompt_len"]["max"] = 8      # warm chunks up to 8 only
    drv.setup()
    cell.traffic["prompt_len"]["max"] = longest
    try:
        _, _, spans = _window(drv, record=True)
    finally:
        drv.free()
    compiles = _named(spans, "jax.compile")
    assert compiles
    admits = {s.id for s in _named(spans, "serve.admit")}
    assert any(c.parent in admits for c in compiles)


def test_vision_counters_and_spans(backlog):
    win, ctr, spans = _window(backlog.driver, record=True)
    c = win.counters
    assert ctr["dispatches"] == c["dispatches"] > 0
    assert ctr["images"] == c["images"]
    assert ctr["by_bucket"] == {b: n for b, n in
                                collections.Counter(c["batches"]).items()}
    steps = {s.id: s for s in _named(spans, "vision.step")}
    assert len(steps) == c["dispatches"]
    prep = {s.parent: s for s in _named(spans, "vision.prepare")}
    fetch = {s.parent: s for s in _named(spans, "vision.fetch")}
    assert set(prep) == set(fetch) == set(steps)
    for sid, st in steps.items():
        assert st.start <= prep[sid].start <= prep[sid].end \
            <= fetch[sid].start <= fetch[sid].end <= st.end
    assert _named(spans, "jax.compile") == []


# -- vision.stem_conv_ms ---------------------------------------------------------

def _stem_reader():
    return harness.load_module(harness.BENCH / "metrics" /
                               "vision.stem_conv_ms.py", "stem_conv_ms")


def test_stem_conv_found_by_its_name():
    ev = trace.Event
    stem = ('%eq1_conv_k7s2.{} = s32[1792,112,64] custom-call(), '
            'custom_call_target="tpu_custom_call"')
    ops = [ev(stem.format(17), 10, 40, 0), ev(stem.format(3), 60, 80, 0),
           ev('%eq1_conv_k3s1.18 = s32[8] custom-call(), custom_call_target='
              '"tpu_custom_call"', 40, 60, 0)]
    tr = trace.Trace(ops=ops, modules=[], spans=[], devices=[0],
                     window=(0, 100))
    run = harness.Run(cell=None, window=harness.Window(
        {}, 0, 0, {}, {"dispatches": 2}), trace=tr, device_kind="")
    assert _stem_reader().read(run) == pytest.approx(25e-6)   # ms


def test_stem_conv_silent_without_named_kernels():
    """The recorded trace predates the kernels' names: nothing to read."""
    path = harness.BENCH / "testdata" / "backlog.xplane.pb"
    rec = harness.load_json(harness.BENCH / "testdata" / "backlog.json")
    tr = trace.load(str(path), None, rec["host_spans"])
    run = harness.Run(cell=None, window=harness.Window(
        {}, 0, 0, {}, rec["traced"]), trace=tr, device_kind="")
    assert _stem_reader().read(run) is None
