"""Driver of the vision engine (``repro.serving.VisionEngine``).

Weights come from ``bench/reference/resnet50.py`` (one jitted call from the
seed, on the device), images from a pool made in set-up. The window drives
``VisionEngine.submit`` and ``step`` only: a closed loop for a ``backlog``
mix, open-loop arrivals for a ``poisson`` mix. Each ``step`` dispatches one
bucket, whose requests the driver records as one group: the quantized
path calibrates its activations over the bucket, and the reference
replays the same groups.
"""
from __future__ import annotations

import time

import numpy as np

from bench import seeds, traffic
from bench.harness import Window, percentile

# Past the window's close, requests still due get this long to finish.
GRACE_S = 60.0
# Images compared with the reference, at most.
CHECK_IMAGES = 128
POOL = 64


class Driver:
    # Each dispatch copies its images to the chip, whose runtime would trace
    # every chunk of the copy: the profiler records no host spans here.
    TRACE_HOST = False

    def __init__(self, cell, seed: int):
        self.cell, self.seed = cell, seed
        self.cfg, self.mix = cell.config, cell.traffic
        self.engine_cfg = self.cfg["engine"]
        self.max_batch = self.engine_cfg["max_batch"]
        self.precision = self.mix["precision"]
        self.bits = traffic.bits(self.precision)

    # -- set-up ---------------------------------------------------------------

    def weights(self):
        import jax

        from bench.reference import resnet50

        key = seeds.jax_key(self.seed, "weights")
        return jax.jit(resnet50.init, static_argnums=(1, 2))(
            key, self.cfg["image_size"], self.cfg["num_labels"])

    def setup(self):
        from repro.serving import VisionEngine

        self.engine = VisionEngine({"resnet50": self.weights()},
                                   backend=self.engine_cfg["backend"],
                                   max_batch=self.max_batch)
        self.pool = traffic.image_pool(POOL, self.cfg["image_size"], self.seed)
        self._rid = 0
        if self.mix["kind"] == "backlog":
            buckets = [self.max_batch]
        else:
            buckets = [1 << i for i in range(self.max_batch.bit_length())]
        for b in buckets:          # the cell's own shapes, and no others
            for i in range(b):
                self._submit(i % POOL, warm=True)
            self.engine.step()

    def _submit(self, img: int, warm: bool = False) -> int:
        from repro.serving import VisionRequest

        rid = self._rid
        self._rid += 1
        if not warm:
            self.images[rid] = img
        self.engine.submit(VisionRequest(rid=rid, image=self.pool[img],
                                         model="resnet50",
                                         precision=self.precision))
        return rid

    def _step(self, tracer, traced: bool, counters: dict) -> list:
        t0 = time.perf_counter()
        with tracer.span("bench.step"):
            done = self.engine.step()
        dt = time.perf_counter() - t0
        with tracer.span("bench.record"):
            self.groups.append([c.rid for c in done])
            for c in done:
                self.logits[c.rid] = c.logits
            for ctr in (counters,) + ((self.traced,) if traced else ()):
                ctr["dispatches"] += 1
                ctr["images"] += len(done)
                ctr["batches"].append(len(done))
                ctr["step_s"] += dt
        return done

    def _counters(self):
        return {"dispatches": 0, "images": 0, "step_s": 0.0, "batches": []}

    # -- window ---------------------------------------------------------------

    def window(self, seconds: float, tracer, rate: float | None = None
               ) -> Window:
        self.images = {}           # rid -> pool index
        self.logits = {}           # rid -> served logits
        self.groups = []           # rids of each dispatched bucket
        self.traced = self._counters()
        counters = self._counters()
        if self.mix["kind"] == "backlog":
            return self._backlog(seconds, tracer, counters)
        return self._poisson(seconds, tracer, counters,
                             rate or self.mix["rate"])

    def _backlog(self, seconds, tracer, counters) -> Window:
        """Closed loop: ``depth_batches`` buckets always queued. The window
        closes with the first dispatch that ends past ``seconds``, so every
        image counted ran whole inside it."""
        depth = self.mix["depth_batches"] * self.max_batch
        choice = traffic.image_choice(1 << 16, POOL, self.seed)
        n = 0
        t0 = time.perf_counter()
        t = 0.0
        while t < seconds:
            active = tracer.tick(t)
            with tracer.span("bench.submit"):
                while len(self.engine.queue) < depth:
                    self._submit(int(choice[self._rid % len(choice)]))
            n += len(self._step(tracer, active, counters))
            t = time.perf_counter() - t0
        counters["window_s"] = t
        self.failed = 0
        return Window(metrics={"images_per_s": n / t}, attempted=n, failed=0,
                      counters=counters, traced=self.traced)

    def _poisson(self, seconds, tracer, counters, rate) -> Window:
        """Open loop: each request due at its arrival time; its latency runs
        from that time to its logits on the host."""
        due = traffic.arrival_times(rate, seconds, self.seed)
        choice = traffic.image_choice(len(due), POOL, self.seed)
        rids, done_at = [], {}
        nxt = 0
        late = 0.0
        t0 = time.perf_counter()
        while True:
            t = time.perf_counter() - t0
            if t > seconds + GRACE_S:
                break
            active = tracer.tick(t)
            with tracer.span("bench.submit"):
                while nxt < len(due) and due[nxt] <= t:
                    late = max(late, t - due[nxt])
                    rids.append(self._submit(int(choice[nxt])))
                    nxt += 1
            if self.engine.queue:
                for c in self._step(tracer, active, counters):
                    done_at[c.rid] = time.perf_counter() - t0
            elif nxt < len(due):
                with tracer.span("bench.wait_arrival"):
                    time.sleep(max(0.0, min(due[nxt] - t, 0.01)))
            else:
                break
        cap = seconds + GRACE_S
        lat = [1e3 * (done_at.get(r, cap) - d) for r, d in zip(rids, due)]
        lat += [1e3 * (cap - d) for d in due[len(rids):]]
        self.latencies = lat
        self.failed = failed = len(due) - len(done_at)
        counters["window_s"] = seconds
        counters["generator_late_s"] = late
        return Window(metrics={"image_latency_p95_ms": percentile(lat, 95)},
                      attempted=len(due), failed=failed, counters=counters,
                      traced=self.traced)

    def free(self):
        self.engine.close()
        del self.engine

    # -- comparison with the reference ----------------------------------------

    def _sample_groups(self) -> list:
        """Dispatched groups drawn from the seed, whole, up to
        ``CHECK_IMAGES`` images."""
        groups = [g for g in self.groups if g and g[0] in self.images]
        order = seeds.rng(self.seed, "check").permutation(len(groups))
        out, n = [], 0
        for i in order:
            if n + len(groups[i]) > CHECK_IMAGES and out:
                break
            out.append(groups[i])
            n += len(groups[i])
        return out

    def _reference(self, bits) -> dict:
        """rid -> reference logits for the sampled groups."""
        import jax
        import jax.numpy as jnp

        from bench.reference import resnet50

        params = self.weights()
        fwd = jax.jit(resnet50.forward, static_argnums=(2,))
        groups = self._sample_groups()
        if bits is None:
            # Float logits do not depend on the batch: one shape will do.
            rids = [r for g in groups for r in g]
            groups = [rids[i:i + self.max_batch]
                      for i in range(0, len(rids), self.max_batch)]
        out = {}
        for g in groups:
            idx = [self.images[r] for r in g]
            if bits is None:
                idx += idx[:1] * (self.max_batch - len(idx))
            ref = np.asarray(fwd(params, jnp.asarray(self.pool[idx]), bits))
            out.update(zip(g, ref))
        return out

    def _err(self, ref: dict, got: dict) -> float:
        """Widest logit error over the compared images, as a share of each
        image's largest reference logit."""
        return max(float(np.abs(got[r] - ref[r]).max() / np.abs(ref[r]).max())
                   for r in ref)

    def check(self) -> list:
        ref = self._reference(self.bits)
        lim = self.cell.limits
        return [
            {"name": "unanswered", "value": self.failed, "limit": 0},
            {"name": "logit_err", "value": self._err(ref, self.logits),
             "limit": lim["logit_err"]["limit"]},
        ]

    def control(self) -> dict:
        """The comparison's readings with the reference at the next lower
        precision put in the program's place."""
        low = (4, 4) if self.bits == (8, 8) else (8, 8)
        ref = self._reference(self.bits)
        return {"logit_err": self._err(ref, self._reference(low)),
                "control_bits": list(low)}
