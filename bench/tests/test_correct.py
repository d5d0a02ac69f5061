"""The comparison that decides ``correct``: it passes a sound run and fails
the control and every planted fault.

Each cell runs here at a small size on the CPU, through the harness's own
``run_cell`` with its look for a chip skipped (the vision engine on its
int-direct backend, which computes the same Eq. 1 product as the Pallas
kernels without the interpreter's cost). The faults are planted in the
engine, under the timed path: an answer or a token altered where it is
produced, and half of each batch left out.
"""
import time

import jax
import numpy as np
import pytest

from bench import harness, work

# Limits for these small sizes (the cells' own are set on the chip). The
# float path reads near 0 here, where XLA:CPU computes float32 exactly.
TINY = {"resnet50-224.w8a8-backlog": {"logit_err": 0.2},
        "float-poisson": {"logit_err": 0.01},
        "qwen3-0.6b.w8a8-chat-poisson": {"token_gap": 1.5}}
# An open-loop float mix on the vision engine, as a later cell would add it.
FLOAT_POISSON = {"kind": "poisson", "precision": None, "rate": 60.0}
SECONDS = 1.5


def _tiny(name):
    cell = harness.load_cell("resnet50-224.w8a8-backlog"
                             if name == "float-poisson" else name)
    cfg = dict(cell.config, engine=dict(cell.config["engine"]))
    mix = dict(FLOAT_POISSON if name == "float-poisson" else cell.traffic)
    if cfg["driver"] == "vision":
        cfg.update(image_size=32, num_labels=10)
        cfg["engine"].update(backend="int-direct", max_batch=4)
    else:
        cfg.update(num_hidden_layers=2, hidden_size=64, intermediate_size=128,
                   num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                   vocab_size=512)
        cfg["engine"].update(max_batch=4, max_len=64)
        mix.update(rate=40.0,
                   prompt_len=dict(median=8, sigma=0.8, min=2, max=40),
                   output_len=dict(median=12, sigma=0.7, min=4, max=20))
    cell.config, cell.traffic = cfg, mix
    if name == "float-poisson":
        cell.end_to_end = [{"name": "image_latency_p95_ms", "unit": "ms"},
                           {"name": "setup_s", "unit": "s"}]
        cell.per_layer = []
    cell.limits = {k: {"limit": v} for k, v in TINY[name].items()}
    return cell


class Prepared:
    """One driver per cell, set up once and run again for each case."""

    def __init__(self, name, seed=2**31 + 99):
        self.cell = _tiny(name)
        self.seed = seed
        self.driver = harness.driver_class(self.cell)(self.cell, seed)
        self.driver.setup()
        self.driver.setup = self.driver.free = lambda: None

    def run(self):
        res, checks = harness.run_cell(
            self.cell, self.seed, SECONDS, False, jax.devices()[:1],
            time.perf_counter(), driver_cls=lambda cell, seed: self.driver)
        return res, {c["name"]: c for c in checks}


@pytest.fixture(scope="module")
def backlog():
    return Prepared("resnet50-224.w8a8-backlog")


@pytest.fixture(scope="module")
def float_poisson():
    return Prepared("float-poisson")


@pytest.fixture(scope="module")
def chat():
    return Prepared("qwen3-0.6b.w8a8-chat-poisson")


def _patch(monkeypatch, obj, name, wrap):
    monkeypatch.setattr(obj, name, wrap(getattr(obj, name)))


# -- faults of the vision engine ------------------------------------------------

def _answer_altered(dispatch):
    def f(*a, **k):
        out = dispatch(*a, **k)
        out[0].logits = out[0].logits[::-1].copy()
        return out
    return f


def _half_left_out(dispatch):
    def f(*a, **k):
        out = dispatch(*a, **k)
        h = (len(out) + 1) // 2
        for c, src in zip(out[h:], out):
            c.logits = src.logits.copy()
        return out
    return f


@pytest.mark.parametrize("fault", [None, _answer_altered, _half_left_out],
                         ids=["sound", "answer-altered", "half-left-out"])
@pytest.mark.parametrize("cell", ["backlog", "float_poisson"])
def test_vision_faults_fail(cell, fault, request, monkeypatch):
    prep = request.getfixturevalue(cell)
    if fault is not None:
        _patch(monkeypatch, prep.driver.engine, "_dispatch", fault)
    res, checks = prep.run()
    assert checks["unanswered"]["value"] == 0
    assert res["correct"] is (fault is None), checks
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", ["backlog", "float_poisson"])
def test_vision_control_fails(cell, request):
    prep = request.getfixturevalue(cell)
    prep.run()
    got = prep.driver.control()
    assert got["logit_err"] > 3 * prep.cell.limits["logit_err"]["limit"], got


# -- faults of the LM engine ------------------------------------------------------

def _token_altered(admit, vocab):
    def f(*a, **k):
        ctrl, tok = admit(*a, **k)
        return ctrl, (tok + 1) % vocab
    return f


def _half_slots_left_out(decode_fn):
    def f(n):
        fn = decode_fn(n)

        def g(*a):
            out = list(fn(*a))
            toks = np.asarray(out[2]).copy()
            toks[:, 1::2] = toks[:, 0::2]
            out[2] = toks
            return tuple(out)
        return g
    return f


@pytest.mark.parametrize("fault", [None, "token-altered", "half-left-out"])
def test_lm_faults_fail(chat, fault, monkeypatch):
    eng = chat.driver.engine
    if fault == "token-altered":
        v = chat.cell.config["vocab_size"]
        _patch(monkeypatch, eng, "_admit_ctrl", lambda f: _token_altered(f, v))
    elif fault == "half-left-out":
        _patch(monkeypatch, eng, "_decode_fn", _half_slots_left_out)
    res, checks = chat.run()
    assert checks["unanswered"]["value"] == 0
    assert res["correct"] is (fault is None), checks


def test_lm_control_fails(chat):
    chat.run()
    got = chat.driver.control()
    assert got["token_gap"] > 3 * got["program_token_gap"], got
    assert got["token_gap"] > chat.cell.limits["token_gap"]["limit"], got


def test_readers_on_counters(chat, monkeypatch):
    """The per-layer readers that need no trace read the window's counters
    (the trace ones stay silent without a chip)."""
    monkeypatch.setattr(work, "peaks",
                        lambda kind, p=work.peaks: p("TPU v5 lite"))
    res, _ = harness.run_cell(
        chat.cell, chat.seed, SECONDS, True, jax.devices()[:1],
        time.perf_counter(), driver_cls=lambda cell, seed: chat.driver)
    m = res["metrics"]
    assert 0 < m["lm.batch_occupancy"]["value"] <= 100
    assert 0 < m["mfu.lm"]["value"] < 100
    assert "lm.decode_roofline" not in m
