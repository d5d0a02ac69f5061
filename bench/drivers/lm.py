"""Driver of the LM engine (``repro.serving.ServeEngine``).

Weights come from ``bench/reference/qwen3.py`` (one jitted call from the
seed, on the device, in the served type); the engine is built from the
configuration file's sizes and engine settings, with the mix's precision
on the configuration's backend. The window drives ``submit`` and ``step``
only, with requests due at their open-loop arrival times; after each
``step`` the driver reads which output tokens reached the host
(``slot_out`` / ``slot_req`` and the returned completions).
"""
from __future__ import annotations

import time

import numpy as np

from bench import seeds, traffic
from bench.harness import Window, percentile

GRACE_S = 60.0
# Served tokens compared with the reference: at least this many, drawn
# from the seed with the longest answer among them.
CHECK_TOKENS = 384
CHECK_REQUESTS = 8


class Driver:
    # Prefill and decode are told apart by the names the profiler records
    # on the host when each program is launched.
    TRACE_HOST = True

    def __init__(self, cell, seed: int):
        self.cell, self.seed = cell, seed
        self.cfg, self.mix = cell.config, cell.traffic
        self.engine_cfg = self.cfg["engine"]
        self.bits = traffic.bits(self.mix["precision"])

    def model_config(self):
        from repro.core import PIMQuantConfig
        from repro.models.lm.config import ModelConfig

        c = self.cfg
        pim = None
        if self.bits is not None:
            pim = PIMQuantConfig(self.bits[0], self.bits[1],
                                 backend=self.engine_cfg["backend"])
        return ModelConfig(
            name=self.cell.config_name, family="dense",
            n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            d_ff=c["intermediate_size"], vocab=c["vocab_size"],
            qk_norm=True, act="silu_gated", rope_theta=float(c["rope_theta"]),
            norm_eps=c["rms_norm_eps"],
            tie_embeddings=c["tie_word_embeddings"], dtype=c["torch_dtype"],
            pim=pim)

    def weights(self):
        import jax

        from bench.reference import qwen3

        return jax.jit(qwen3.init, static_argnums=(1,))(
            seeds.jax_key(self.seed, "weights"), _Frozen(self.cfg))

    # -- set-up ---------------------------------------------------------------

    def setup(self):
        from repro.serving import Request, SamplerConfig, ServeEngine

        e = self.engine_cfg
        self.max_batch, self.max_len = e["max_batch"], e["max_len"]
        self.engine = ServeEngine(
            self.model_config(), self.weights(), max_batch=self.max_batch,
            max_len=self.max_len, sampler=SamplerConfig(temperature=0.0))
        # Warm the cell's shapes: every power-of-two prefill chunk up to the
        # longest prompt's, and the decode drains 1, 2, 4, 8 (a 16-token
        # answer steps through them in that order, longest first).
        longest = self.mix["prompt_len"]["max"]
        chunks = [1 << i for i in range(longest.bit_length())]
        warm = [np.zeros(sum(chunks[:-1]), np.int32),
                np.zeros(chunks[-1], np.int32)]
        for i, p in enumerate(warm):
            self.engine.submit(Request(rid=-1 - i, prompt=p,
                                       max_new_tokens=16))
        self.engine.run(strict=True)
        self.reqs = None

    # -- window ---------------------------------------------------------------

    def window(self, seconds: float, tracer, rate: float | None = None
               ) -> Window:
        from repro.serving import Request

        reqs = traffic.lm_requests(self.mix, self.cfg["vocab_size"], seconds,
                                   self.seed, rate)
        self.reqs = reqs
        n = len(reqs.due)
        seen = np.zeros(n, np.int64)           # tokens on the host, per rid
        first = np.full(n, np.nan)
        last = np.zeros(n)
        gaps = []
        self.tokens = {}
        counters = _counters()
        self.traced = _counters()
        nxt, late = 0, 0.0
        eng = self.engine
        t0 = time.perf_counter()
        while True:
            t = time.perf_counter() - t0
            if t > seconds + GRACE_S:
                break
            active = tracer.tick(t)
            with tracer.span("bench.submit"):
                while nxt < n and reqs.due[nxt] <= t:
                    late = max(late, t - reqs.due[nxt])
                    eng.submit(Request(rid=nxt, prompt=reqs.prompts[nxt],
                                       max_new_tokens=int(reqs.max_new[nxt])))
                    nxt += 1
            live_before = {r.rid for r in eng.slot_req if r is not None}
            if not eng.queue and not live_before:
                if nxt >= n:
                    break
                with tracer.span("bench.wait_arrival"):
                    time.sleep(max(0.0, min(reqs.due[nxt] - t, 0.005)))
                continue
            ts = time.perf_counter()
            with tracer.span("bench.step"):
                done = eng.step()
            te = time.perf_counter()
            with tracer.span("bench.record"):
                now = te - t0
                counts = {r.rid: len(eng.slot_out[i])
                          for i, r in enumerate(eng.slot_req) if r is not None}
                for c in done:
                    counts[c.rid] = len(c.tokens)
                    self.tokens[c.rid] = list(c.tokens)
                steps, decoding, kv, prompts = 0, 0, 0, []
                for rid, k in counts.items():
                    new = k - seen[rid]
                    if new <= 0:
                        continue
                    if seen[rid] == 0:
                        first[rid] = now
                        prompts.append(len(reqs.prompts[rid]))
                        gaps.extend([0.0] * (new - 1))
                        new -= 1
                    else:
                        gaps.append(1e3 * (now - last[rid]))
                        gaps.extend([0.0] * (new - 1))
                    if new > 0:
                        decoding += 1
                        kv += len(reqs.prompts[rid]) + int(seen[rid])
                    steps = max(steps, new)
                    seen[rid] = k
                    last[rid] = now
                for ctr in (counters,) + ((self.traced,) if active else ()):
                    ctr["steps"] += 1
                    ctr["step_s"] += te - ts
                    ctr["prompts"] += prompts
                    if steps:
                        ctr["decode"].append(
                            (steps, decoding,
                             kv * steps + decoding * steps * (steps - 1) // 2))
        due = reqs.due
        cap = seconds + GRACE_S
        ttft = [1e3 * ((cap if np.isnan(f) else f) - d)
                for f, d in zip(first, due)]
        self.latencies = ttft
        self.failed = n - len(self.tokens)
        counters["generator_late_s"] = late
        counters["window_s"] = seconds
        return Window(
            metrics={"ttft_p85_ms": percentile(ttft, 85),
                     "itl_p95_ms": percentile(gaps, 95) if gaps else 0.0},
            attempted=n, failed=self.failed, counters=counters,
            traced=self.traced)

    def free(self):
        self.engine.close()
        del self.engine

    # -- comparison with the reference ----------------------------------------

    def _sample(self) -> list:
        """Finished requests drawn from the seed, the longest answer first,
        until ``CHECK_TOKENS`` served tokens or ``CHECK_REQUESTS``."""
        rids = sorted(self.tokens)
        if not rids:
            return []
        longest = max(rids, key=lambda r: len(self.tokens[r]))
        order = [rids[i] for i in
                 seeds.rng(self.seed, "check").permutation(len(rids))]
        out, n = [longest], len(self.tokens[longest])
        for r in order:
            if n >= CHECK_TOKENS or len(out) >= CHECK_REQUESTS:
                break
            if r != longest:
                out.append(r)
                n += len(self.tokens[r])
        return out

    def _gaps(self, control_bits=None) -> tuple[list, list]:
        """Per served token, how far its logit lies below the reference's
        best, in units of the reference logits' standard deviation at that
        position; and, with ``control_bits``, the same for the token the
        reference at that precision puts first."""
        import jax
        import jax.numpy as jnp

        from bench.reference import qwen3

        cfg = _Frozen(self.cfg)
        params = self.weights()
        L = self.max_len

        @jax.jit
        def gaps(params, seq, pos, served):
            h = qwen3.hidden(params, cfg, seq)[pos]
            ref = qwen3.logits(params, h)
            best = ref.max(-1)
            std = ref.std(-1)
            rows = jnp.arange(pos.shape[0])
            g = (best - ref[rows, served]) / std
            if control_bits is None:
                return g, g
            hc = qwen3.hidden(params, cfg, seq, control_bits)[pos]
            tc = qwen3.logits(params, hc, control_bits).argmax(-1)
            return g, (best - ref[rows, tc]) / std

        prog, ctrl = [], []
        for rid in self._sample():
            prompt = self.reqs.prompts[rid]
            served = np.asarray(self.tokens[rid], np.int32)
            seq = np.zeros(L, np.int32)
            full = np.concatenate([prompt, served[:-1]])
            seq[:len(full)] = full
            pos = len(prompt) - 1 + np.arange(len(served))
            # One shape for every request: pad positions to the longest
            # answer the mix can ask for.
            m = self.mix["output_len"]["max"]
            pos_p = np.zeros(m, np.int32)
            srv_p = np.zeros(m, np.int32)
            pos_p[:len(pos)], srv_p[:len(served)] = pos, served
            g, c = gaps(params, jnp.asarray(seq), jnp.asarray(pos_p),
                        jnp.asarray(srv_p))
            prog += list(np.asarray(g)[:len(served)])
            ctrl += list(np.asarray(c)[:len(served)])
        return prog, ctrl

    def check(self) -> list:
        prog, _ = self._gaps()
        return [
            {"name": "unanswered", "value": self.failed, "limit": 0},
            # No token compared reads as the widest gap there could be.
            {"name": "token_gap", "value": float(max(prog, default=1e9)),
             "limit": self.cell.limits["token_gap"]["limit"]},
        ]

    def control(self) -> dict:
        low = (4, 4) if self.bits == (8, 8) else (8, 8)
        prog, ctrl = self._gaps(low)
        return {"token_gap": float(max(ctrl)), "program_token_gap":
                float(max(prog)), "control_bits": list(low)}


def _counters():
    """``prompts``: the prompt length of each admission; ``decode``: per
    step that decoded, (decode steps, slots decoding, key-value tokens
    those slots attended to over the steps)."""
    return {"steps": 0, "step_s": 0.0, "prompts": [], "decode": []}


class _Frozen(dict):
    """A configuration dict that jit can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))
