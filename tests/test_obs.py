"""The span recorder (``repro.obs``): silent and clock-free when off; when
on, spans with ids, parents, attributes and explicit starts, nested per
thread, and backend compiles as ``jax.compile`` spans."""
import threading

import jax
import jax.numpy as jnp
import pytest

from repro import obs


@pytest.fixture
def recording():
    obs.start()
    try:
        yield
    finally:
        obs.stop()


class _NoClock:
    def time_ns(self):
        raise AssertionError("the recorder read the clock while off")


def test_off_records_nothing_and_reads_no_clock(monkeypatch):
    obs.stop()
    monkeypatch.setattr(obs, "time", _NoClock())
    assert obs.now() is None
    a = obs.span("serve.step")
    with a:
        with obs.span("serve.admit", rid=3, live=1):
            pass
    obs.span("serve.queued", obs.now(), rid=3).close()
    assert obs.span("vision.step") is a          # one shared no-op
    assert obs.stop() == []


def test_spans_nest_with_ids_parents_and_attributes(recording):
    with obs.span("serve.step") as step:
        t = obs.now()
        obs.span("serve.queued", t, rid=7).close()
        with obs.span("serve.admit", rid=7, prompt_len=5):
            pass
    spans = {s.name: s for s in obs.stop()}
    assert set(spans) == {"serve.step", "serve.queued", "serve.admit"}
    st = spans["serve.step"]
    assert st.id == step.id and st.parent is None
    assert spans["serve.queued"].parent == st.id
    assert spans["serve.queued"].start == t
    assert spans["serve.admit"].parent == st.id
    assert spans["serve.admit"].attrs == {"rid": 7, "prompt_len": 5}
    assert len({s.id for s in spans.values()}) == 3
    for s in spans.values():
        assert st.start <= s.start <= s.end <= st.end


def test_threads_nest_separately(recording):
    inner = threading.Event()
    done = threading.Event()

    def worker():
        with obs.span("vision.step"):
            with obs.span("vision.fetch"):
                inner.set()
                done.wait(10)

    with obs.span("serve.step"):
        th = threading.Thread(target=worker)
        th.start()
        assert inner.wait(10)
        with obs.span("serve.fetch"):
            pass
        done.set()
        th.join(10)
    assert not th.is_alive()
    spans = {s.name: s for s in obs.stop()}
    assert spans["vision.step"].parent is None
    assert spans["vision.fetch"].parent == spans["vision.step"].id
    assert spans["serve.fetch"].parent == spans["serve.step"].id


def test_a_fresh_jit_compiles_inside_a_span(recording):
    with obs.span("serve.admit", rid=1):
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    spans = obs.stop()
    admit = next(s for s in spans if s.name == "serve.admit")
    compiles = [s for s in spans if s.name == "jax.compile"]
    assert compiles and all(c.parent == admit.id for c in compiles)
    assert any("lambda" in c.attrs["fun"] for c in compiles)
    for c in compiles:
        assert admit.start <= c.start <= c.end <= admit.end


def test_stop_ends_recording():
    obs.start()
    with obs.span("a"):
        pass
    spans = obs.stop()
    with obs.span("b"):
        pass
    assert [s.name for s in spans] == ["a"]
    assert [s.name for s in obs.stop()] == ["a"]   # "b" ran unrecorded
