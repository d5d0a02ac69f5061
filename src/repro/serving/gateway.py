"""Overload-safe asyncio serving gateway (DESIGN.md §8).

The engines (:class:`~repro.serving.engine.ServeEngine`,
:class:`~repro.serving.vision.VisionEngine`) are fast, donation-clean hot
loops fed by a bare in-process deque — no admission limits, no deadlines,
no behavior under overload. This module is the serving front line in front
of them, built so the donated jitted loops stay saturated while the system
degrades *gracefully* instead of falling over:

  * **Worker threads per engine.** Each engine is driven on its own worker
    thread; the asyncio event loop only touches bounded queues and
    ``asyncio.Queue`` token streams (fed via ``call_soon_threadsafe``), so
    a jitted dispatch never blocks the loop and a slow caller never blocks
    the grid.
  * **Bounded per-tenant queues + weighted-fair admission.** Every tenant
    gets a bounded FIFO; admission into free grid slots picks tenants by
    stride scheduling (virtual pass times advance by 1/weight), so a
    weight-2 tenant gets 2× the admissions of a weight-1 tenant under
    saturation and an idle tenant's unused share is redistributed. The
    engines' own internal queues are kept empty (LM) or at most one bucket
    deep (vision): the gateway queues are the only place requests wait, so
    every shedding decision happens in one place.
  * **Deadline propagation.** ``deadline_ms`` (per request, or the config
    default) starts at submission. Expired requests are cancelled while
    queued *and* mid-generation — the worker calls ``engine.cancel`` and
    the slot is released at the next token boundary through the same
    slot-free path a natural completion takes.
  * **Backpressure + load shedding.** A full tenant queue (or a shed tier)
    rejects at submission with :class:`ShedError` carrying a retry-after
    hint computed from the observed service rate — never silent unbounded
    growth. Queue depth is bounded by construction.
  * **Graceful degradation tiers.** Sustained overload walks a ladder, one
    tier per sustained-hold period, each transition logged and reversed
    when load drops: tier 1 shrinks the LM engine's ``drain_steps`` (a
    freed slot is re-admitted at the next token boundary instead of after
    a multi-step drain); tier 2 re-deploys to a cheaper precision via the
    PR 5 re-prepack machinery (``ServeEngine.redeploy`` /
    ``VisionEngine.degrade_cohort``) when configured; tier 3 sheds the
    lowest-priority tenants outright.
  * **Live telemetry.** Fixed-size ring buffers (the rolling-window logging
    idiom) for queue depth, TTFT (submit- and admission-referenced), TPOT,
    and a completion window for tokens/s + per-tenant goodput; ``stats()``
    returns a consistent snapshot with p50/p95/p99 percentiles, shed
    counters by reason, the degradation tier, and the transition log.

Numerics: the gateway adds zero. Admission order only picks *which* slot a
request lands in, and slots are isolated (tested since PR 2/3), so an
admitted request's token stream is bit-identical to the same request on an
unloaded engine — asserted under 2× overload in benchmarks/serve_bench.py.

Thread-ownership rule (machine-checked by ``repro.analysis.threads``, see
tests/test_analysis.py): the engines referenced by ``self._lm`` /
``self._vision`` are **owned by their worker threads**. Code reachable from
the event-loop entry points (``submit_lm``/``submit_vision``/``start``/
``stop``/``drain``/``stats``/``__aenter__``/``__aexit__``) must not call
engine methods or assign engine attributes — the only loop-side engine
access allowed is the read-only ``validate``/``n_free_slots`` pair used at
admission. Everything else (submit/step/cancel/redeploy/drain_steps
mutation, the degradation-tier actions) happens on the worker, which is
also the only side that touches jax. Handing the engine *object* around
(thread targets, ``_guard`` wrappers) is fine; calling into it from the
loop is not. The AST lint walks ``self.<method>()`` call edges from the
loop roots and flags any engine call or store outside the allowlist, so a
refactor that accidentally moves engine work onto the loop fails CI.
"""
from __future__ import annotations

import asyncio
import collections
import dataclasses
import itertools
import threading
import time

import numpy as np

from .engine import Request, ServeEngine
from .vision import VisionEngine, VisionRequest

_END = object()          # token-stream sentinel

# Shed reasons (ShedError.reason / stats()["shed"] keys).
SHED_QUEUE_FULL = "queue_full"
SHED_OVERLOAD = "overload"       # tier-3: tenant priority shed
SHED_EXPIRED = "expired"         # deadline passed (queued or mid-generation)


class ShedError(RuntimeError):
    """Request rejected at admission; retry after ``retry_after_s``."""

    def __init__(self, reason: str, retry_after_s: float):
        super().__init__(f"request shed ({reason}); "
                         f"retry after {retry_after_s:.3f}s")
        self.reason = reason
        self.retry_after_s = retry_after_s


class DeadlineExceeded(RuntimeError):
    """The request's ``deadline_ms`` passed before completion."""


class Ring:
    """Fixed-size float ring buffer with percentile snapshots.

    The telemetry backbone: O(1) push, O(size) snapshot, constant memory —
    a long-running gateway never grows its metrics state.
    """

    def __init__(self, size: int = 512):
        self._buf = np.zeros(size, np.float64)
        self._n = 0            # total pushes (monotonic)
        self._size = size

    def push(self, v: float):
        self._buf[self._n % self._size] = v
        self._n += 1

    def __len__(self):
        return min(self._n, self._size)

    def values(self) -> np.ndarray:
        return self._buf[:len(self)].copy()

    def percentiles(self, qs=(50, 95, 99)) -> dict:
        if not len(self):
            return {f"p{q}": None for q in qs}
        v = self.values()
        return {f"p{q}": float(np.percentile(v, q)) for q in qs}


@dataclasses.dataclass
class GatewayConfig:
    """Admission, deadline, shedding and degradation knobs."""

    queue_depth: int = 32                  # per-tenant bound (per engine)
    tenant_weights: dict = dataclasses.field(default_factory=dict)
    tenant_priority: dict = dataclasses.field(default_factory=dict)
    default_deadline_ms: float | None = None
    telemetry_window: int = 512            # ring size / completion window
    # Degradation ladder: escalate one tier per ``tier_hold_s`` of total
    # queue fullness >= ``overload_enter``; de-escalate one tier per hold
    # period of fullness <= ``overload_exit`` (hysteresis band between).
    overload_enter: float = 0.75
    overload_exit: float = 0.25
    tier_hold_s: float = 0.25
    # Admissions per worker iteration: each admission costs a prefill
    # before the group's next decode, so an unbounded burst makes the
    # first-popped request wait behind max_batch-1 prefills for its first
    # token. Pacing bounds that group to admit_burst (waiting requests
    # accrue bounded *queue* time instead, which deadlines/shedding govern).
    admit_burst: int = 2
    degraded_drain_steps: int = 1          # tier-1 lever (LM)
    degrade_precision: bool = False        # tier-2 lever: re-prepack cheaper
    poll_interval_s: float = 0.002         # idle worker wait
    retry_after_floor_s: float = 0.01


class _Handle:
    """Per-request gateway state, shared worker-thread <-> event-loop.

    The worker only writes plain fields and feeds ``q`` via
    ``call_soon_threadsafe``; the event loop only reads.
    """

    __slots__ = ("rid", "tenant", "kind", "payload", "deadline_t", "loop",
                 "q", "status", "submit_t", "admit_t", "first_tok_t",
                 "last_tok_t", "done_t", "n_streamed", "tokens", "result")

    def __init__(self, loop, rid, tenant, kind, payload, deadline_t):
        self.rid, self.tenant, self.kind = rid, tenant, kind
        self.payload = payload               # Request | VisionRequest
        self.deadline_t = deadline_t         # monotonic seconds, or None
        self.loop = loop
        self.q: asyncio.Queue = asyncio.Queue()
        self.status = "queued"  # queued|running|done|expired|shed|error
        self.submit_t = time.monotonic()
        self.admit_t = self.first_tok_t = self.last_tok_t = self.done_t = None
        self.n_streamed = 0
        self.tokens: list = []
        self.result = None                   # VisionCompletion

    def expired(self, now: float) -> bool:
        return self.deadline_t is not None and now > self.deadline_t

    def push(self, item):
        """Thread-safe feed into the caller's stream."""
        try:
            self.loop.call_soon_threadsafe(self.q.put_nowait, item)
        except RuntimeError:
            pass   # loop closed mid-shutdown; caller is gone


class TokenStream:
    """Async iterator over one LM request's tokens.

    ``async for tok in stream`` yields ints as the grid produces them and
    raises :class:`DeadlineExceeded` if the request expires mid-generation
    (tokens streamed so far stay in ``stream.tokens``). ``await
    stream.result()`` drains to completion and returns the full list.
    """

    def __init__(self, handle: _Handle):
        self._h = handle

    rid = property(lambda self: self._h.rid)
    status = property(lambda self: self._h.status)
    tokens = property(lambda self: list(self._h.tokens))

    def __aiter__(self):
        return self

    async def __anext__(self):
        item = await self._h.q.get()
        if item is _END:
            raise StopAsyncIteration
        if isinstance(item, Exception):
            raise item
        return item

    async def result(self) -> list:
        async for _ in self:
            pass
        return self.tokens


class VisionTicket:
    """Awaitable handle for one vision request."""

    def __init__(self, handle: _Handle):
        self._h = handle

    rid = property(lambda self: self._h.rid)
    status = property(lambda self: self._h.status)

    async def result(self):
        """The :class:`VisionCompletion` (raises on deadline/engine error)."""
        item = await self._h.q.get()
        if isinstance(item, Exception):
            raise item
        return item


class _FairQueues:
    """Bounded per-tenant FIFOs drained by stride scheduling.

    Each tenant carries a virtual ``pass`` value advanced by
    ``1 / weight`` per admission; ``pop_next`` serves the non-empty tenant
    with the smallest pass. A newly active tenant starts at the current
    minimum pass so it neither starves others nor claims catch-up credit.
    All methods run under the gateway lock.
    """

    def __init__(self, cfg: GatewayConfig):
        self.cfg = cfg
        self.queues: dict[str, collections.deque] = {}
        self.pass_: dict[str, float] = {}

    def _weight(self, tenant: str) -> float:
        return max(float(self.cfg.tenant_weights.get(tenant, 1.0)), 1e-6)

    def depth(self, tenant: str) -> int:
        q = self.queues.get(tenant)
        return len(q) if q else 0

    def total(self) -> int:
        return sum(len(q) for q in self.queues.values())

    def capacity(self) -> int:
        return max(1, len(self.queues)) * self.cfg.queue_depth

    def full(self, tenant: str) -> bool:
        return self.depth(tenant) >= self.cfg.queue_depth

    def push(self, h: _Handle):
        q = self.queues.get(h.tenant)
        if q is None:
            q = self.queues[h.tenant] = collections.deque()
            base = min(self.pass_.values()) if self.pass_ else 0.0
            self.pass_[h.tenant] = base
        q.append(h)

    def pop_next(self, now: float) -> _Handle | None:
        """Next admission by weighted fairness, skipping expired heads
        (expired handles are returned to the caller via ``cull``)."""
        live = [(self.pass_[t], t) for t, q in self.queues.items() if q]
        for _, t in sorted(live):
            q = self.queues[t]
            while q:
                h = q.popleft()
                if h.expired(now):
                    # Put back for cull() to resolve uniformly.
                    q.appendleft(h)
                    break
                self.pass_[t] += 1.0 / self._weight(t)
                return h
        return None

    def cull(self, now: float) -> list[_Handle]:
        """Remove and return every expired queued handle."""
        out = []
        for q in self.queues.values():
            keep = collections.deque()
            while q:
                h = q.popleft()
                (out if h.expired(now) else keep).append(h)
            q.extend(keep)
        return out

    def drop_tenants(self, tenants: set) -> list[_Handle]:
        """Tier-3 shed: empty the given tenants' queues."""
        out = []
        for t in tenants:
            q = self.queues.get(t)
            if q:
                out.extend(q)
                q.clear()
        return out


class Gateway:
    """Asyncio front line over a :class:`ServeEngine` and/or
    :class:`VisionEngine` (either may be None).

    Usage::

        gw = Gateway(lm=engine, vision=veng, cfg=GatewayConfig(...))
        gw.start()                      # needs a running event loop
        stream = await gw.submit_lm(prompt, max_new_tokens=32,
                                    tenant="acme", deadline_ms=500)
        async for tok in stream: ...
        ticket = await gw.submit_vision(image, model="resnet50")
        completion = await ticket.result()
        gw.stats()                      # telemetry snapshot
        await gw.drain(); gw.stop()

    Or ``async with Gateway(...) as gw:`` for start/stop bracketing.
    """

    def __init__(self, lm: ServeEngine | None = None,
                 vision: VisionEngine | None = None,
                 cfg: GatewayConfig | None = None):
        if lm is None and vision is None:
            raise ValueError("gateway needs at least one engine")
        self.cfg = cfg or GatewayConfig()
        self._lm, self._vision = lm, vision
        self._lock = threading.Lock()
        self._loop: asyncio.AbstractEventLoop | None = None
        self._stop_evt = threading.Event()
        self._threads: list[threading.Thread] = []
        self._rids = itertools.count(1_000_000)   # auto rids (caller may pass)
        self._lm_q = _FairQueues(self.cfg)
        self._vi_q = _FairQueues(self.cfg)
        self._wake = threading.Event()
        self._inflight: dict[int, _Handle] = {}   # rid -> handle (both kinds)
        self._errors: list[str] = []
        # Telemetry (rings + windowed completion log; all under _lock).
        w = self.cfg.telemetry_window
        self._ttft = Ring(w)           # submit -> first token, ms
        self._ttft_admit = Ring(w)     # admission -> first token, ms
        self._tpot = Ring(w)           # inter-token gap, ms
        self._depth_ring = Ring(w)     # sampled total queue depth
        self._completions = collections.deque(maxlen=w)  # (t, tenant, ntok)
        self._max_depth = 0
        self._submits = 0
        self._shed = collections.Counter()
        self._svc_rate = 0.0           # completions/s EWMA
        self._last_done_t: float | None = None
        # Degradation ladder state.
        self._tier = 0
        self._above_since: float | None = None
        self._below_since: float | None = None
        self._events = collections.deque(maxlen=64)
        self._orig_drain = lm.drain_steps if lm is not None else None
        self._orig_pim = (lm.cfg.pim if lm is not None else None)
        self._shed_tenants: set = set()

    # -- lifecycle -----------------------------------------------------------

    def start(self, loop: asyncio.AbstractEventLoop | None = None):
        """Start the worker threads. Must run inside (or be handed) the
        event loop that will consume the streams."""
        if self._threads:
            return
        self._loop = loop or asyncio.get_running_loop()
        self._stop_evt.clear()
        for eng, name, fn in ((self._lm, "lm", self._lm_worker),
                              (self._vision, "vision", self._vision_worker)):
            if eng is None:
                continue
            t = threading.Thread(target=self._guard(fn), name=f"gw-{name}",
                                 daemon=True)
            t.start()
            self._threads.append(t)

    def _guard(self, fn):
        """Fail loudly: a worker crash resolves every owned request with the
        error and surfaces it in stats()/drain() instead of hanging callers."""
        def run():
            try:
                fn()
            except BaseException as e:                     # noqa: BLE001
                msg = f"{threading.current_thread().name} died: {e!r}"
                with self._lock:
                    self._errors.append(msg)
                    stranded = ([h for q in (self._lm_q, self._vi_q)
                                 for dq in q.queues.values() for h in dq]
                                + list(self._inflight.values()))
                    for q in (self._lm_q, self._vi_q):
                        for dq in q.queues.values():
                            dq.clear()
                    self._inflight.clear()
                for h in stranded:
                    h.status = "error"
                    h.push(RuntimeError(msg))
                    h.push(_END)
                print(f"[gateway] {msg}", flush=True)
        return run

    def stop(self):
        """Stop the workers (does not drain; see :meth:`drain`)."""
        self._stop_evt.set()
        self._wake.set()
        for t in self._threads:
            t.join(timeout=10)
        self._threads.clear()

    async def drain(self, timeout: float | None = None):
        """Wait until every queued + in-flight request resolves."""
        t0 = time.monotonic()
        while True:
            with self._lock:
                busy = (self._lm_q.total() + self._vi_q.total()
                        + len(self._inflight))
                if self._errors:
                    raise RuntimeError("; ".join(self._errors))
            if not busy:
                return
            if timeout is not None and time.monotonic() - t0 > timeout:
                raise TimeoutError(f"gateway drain: {busy} request(s) "
                                   f"unresolved after {timeout}s")
            await asyncio.sleep(self.cfg.poll_interval_s)

    async def __aenter__(self):
        self.start()
        return self

    async def __aexit__(self, *exc):
        self.stop()

    # -- submission (event-loop side) ---------------------------------------

    def _retry_after(self, queued_ahead: int) -> float:
        """Retry-after hint from the observed service rate: roughly when a
        queue slot should free. Floors early (cold EWMA) to a config bound."""
        rate = self._svc_rate
        if rate <= 0:
            return max(self.cfg.retry_after_floor_s, 0.1)
        return max(self.cfg.retry_after_floor_s, (queued_ahead + 1) / rate)

    def _admission_check(self, fq: _FairQueues, tenant: str):
        """Shed-at-submission policy; raises ShedError. Under _lock."""
        if self._tier >= 3 and tenant in self._shed_tenants:
            self._shed[SHED_OVERLOAD] += 1
            raise ShedError(SHED_OVERLOAD, self._retry_after(fq.total()))
        if fq.full(tenant):
            self._shed[SHED_QUEUE_FULL] += 1
            raise ShedError(SHED_QUEUE_FULL, self._retry_after(fq.depth(tenant)))

    def _register(self, fq: _FairQueues, h: _Handle):
        with self._lock:
            self._submits += 1
            self._admission_check(fq, h.tenant)
            fq.push(h)
            d = self._lm_q.total() + self._vi_q.total()
            self._max_depth = max(self._max_depth, d)
        self._wake.set()

    def _deadline_t(self, deadline_ms) -> float | None:
        if deadline_ms is None:
            deadline_ms = self.cfg.default_deadline_ms
        if deadline_ms is None:
            return None
        return time.monotonic() + deadline_ms / 1e3

    async def submit_lm(self, prompt, max_new_tokens: int = 32, *,
                        tenant: str = "default", deadline_ms: float | None = None,
                        eos_id: int = -1, rid: int | None = None) -> TokenStream:
        """Admit an LM request; returns a :class:`TokenStream` or raises
        :class:`ShedError` immediately (full queue / shed tier)."""
        if self._lm is None:
            raise ValueError("gateway has no LM engine")
        self._require_started()
        rid = next(self._rids) if rid is None else rid
        req = Request(rid=rid, prompt=np.asarray(prompt, np.int32),
                      max_new_tokens=max_new_tokens, eos_id=eos_id,
                      deadline_ms=deadline_ms)
        # Validate on the caller's thread: a malformed request must raise
        # here, not inside the worker loop.
        self._lm.validate(req.prompt, req.max_new_tokens)
        h = _Handle(self._loop, rid, tenant, "lm", req,
                    self._deadline_t(deadline_ms))
        self._register(self._lm_q, h)
        return TokenStream(h)

    async def submit_vision(self, image, *, model: str = "resnet50",
                            precision: str | None = "<8:8>",
                            tenant: str = "default",
                            deadline_ms: float | None = None,
                            rid: int | None = None) -> VisionTicket:
        """Admit a vision request; returns a :class:`VisionTicket` or raises
        :class:`ShedError` immediately."""
        if self._vision is None:
            raise ValueError("gateway has no vision engine")
        self._require_started()
        rid = next(self._rids) if rid is None else rid
        req = VisionRequest(rid=rid, image=np.asarray(image, np.float32),
                            model=model, precision=precision,
                            deadline_ms=deadline_ms)
        if model not in self._vision._models:
            raise ValueError(f"unknown model {model!r}")
        h = _Handle(self._loop, rid, tenant, "vision", req,
                    self._deadline_t(deadline_ms))
        self._register(self._vi_q, h)
        return VisionTicket(h)

    def _require_started(self):
        if not self._threads:
            raise RuntimeError("gateway not started; call start() first")
        if self._errors:
            raise RuntimeError("; ".join(self._errors))

    # -- resolution helpers (worker side) -----------------------------------

    def _resolve_expired(self, h: _Handle):
        h.status = "expired"
        h.done_t = time.monotonic()
        with self._lock:
            self._shed[SHED_EXPIRED] += 1
        h.push(DeadlineExceeded(
            f"rid {h.rid}: deadline passed "
            f"({'mid-generation' if h.admit_t else 'queued'})"))
        h.push(_END)

    def _finish_lm(self, h: _Handle, tokens: list):
        now = time.monotonic()
        self._stream_lm(h, tokens, now)
        h.status = "done"
        h.done_t = now
        # Telemetry before the END sentinel: a caller awoken by END may
        # immediately drain() + stats(), and must see this completion.
        with self._lock:
            self._completions.append((now, h.tenant, len(tokens)))
            self._observe_service(now)
        h.push(_END)

    def _stream_lm(self, h: _Handle, tokens: list, now: float):
        """Forward tokens beyond what the caller has seen; telemetry on the
        producer side so event-loop scheduling doesn't skew TTFT/TPOT."""
        new = tokens[h.n_streamed:]
        if not new:
            return
        if h.first_tok_t is None:
            h.first_tok_t = now
            with self._lock:
                self._ttft.push((now - h.submit_t) * 1e3)
                if h.admit_t is not None:
                    self._ttft_admit.push((now - h.admit_t) * 1e3)
        elif h.last_tok_t is not None:
            # A drain dispatch emits n tokens in one host visit: spread the
            # gap over the batch for a per-token gap estimate.
            gap_ms = (now - h.last_tok_t) * 1e3 / len(new)
            with self._lock:
                for _ in new:
                    self._tpot.push(gap_ms)
        h.last_tok_t = now
        h.tokens.extend(int(t) for t in new)
        h.n_streamed = len(tokens)
        for t in new:
            h.push(int(t))

    def _observe_service(self, now: float):
        """Completion-rate EWMA feeding the retry-after hint. Under _lock."""
        if self._last_done_t is not None:
            dt = max(now - self._last_done_t, 1e-6)
            inst = 1.0 / dt
            a = 0.2
            self._svc_rate = (inst if self._svc_rate == 0.0
                              else a * inst + (1 - a) * self._svc_rate)
        self._last_done_t = now

    # -- degradation ladder --------------------------------------------------

    def _load_ratio(self) -> float:
        """Total queued / total bounded capacity, across both engines."""
        with self._lock:
            tot = self._lm_q.total() + self._vi_q.total()
            cap = 0
            if self._lm is not None:
                cap += self._lm_q.capacity()
            if self._vision is not None:
                cap += self._vi_q.capacity()
        return tot / max(cap, 1)

    def _ladder_tick(self, now: float):
        r = self._load_ratio()
        if r >= self.cfg.overload_enter:
            self._below_since = None
            if self._above_since is None:
                self._above_since = now
            elif (now - self._above_since >= self.cfg.tier_hold_s
                  and self._tier < 3):
                self._set_tier(self._tier + 1, f"load {r:.2f} sustained")
                self._above_since = now   # next tier needs a fresh hold
        elif r <= self.cfg.overload_exit:
            self._above_since = None
            if self._below_since is None:
                self._below_since = now
            elif (now - self._below_since >= self.cfg.tier_hold_s
                  and self._tier > 0):
                self._set_tier(self._tier - 1, f"load {r:.2f} dropped")
                self._below_since = now
        else:
            self._above_since = self._below_since = None

    def _set_tier(self, new: int, why: str):
        """Apply the levers between the current tier and ``new``. Each
        transition is logged and reversible; levers are idempotent."""
        old, self._tier = self._tier, new
        evt = {"t": time.monotonic(), "tier": new, "from": old, "why": why}
        self._events.append(evt)
        print(f"[gateway] degradation tier {old} -> {new} ({why})",
              flush=True)
        lm = self._lm
        # Tier 1: admission responsiveness — shrink the drain so freed
        # slots are refilled at the next token boundary.
        if lm is not None:
            lm.drain_steps = (max(1, self.cfg.degraded_drain_steps)
                              if new >= 1 else self._orig_drain)
        # Tier 2: cheaper precision via the PR 5 re-prepack machinery.
        if self.cfg.degrade_precision:
            self._apply_precision_tier(new >= 2)
        elif (new >= 2 and old < 2) or (new < 2 <= old):
            self._events.append({"t": time.monotonic(), "tier": new,
                                 "note": "precision tier disabled by config"})
        # Tier 3: shed lowest-priority tenants first.
        if new >= 3:
            dropped = self._enter_tenant_shed()
            for h in dropped:
                h.status = "shed"
                h.push(ShedError(SHED_OVERLOAD, self._retry_after(0)))
                h.push(_END)
        else:
            with self._lock:
                self._shed_tenants.clear()

    def _apply_precision_tier(self, on: bool):
        lm = self._lm
        if lm is not None and self._orig_pim is not None \
                and getattr(self._orig_pim, "enabled", False):
            try:
                if on and lm.cfg.pim.enabled:
                    lm.redeploy(dataclasses.replace(self._orig_pim,
                                                    enabled=False))
                elif not on and not lm.cfg.pim.enabled:
                    lm.redeploy(self._orig_pim)
            except RuntimeError as e:   # no masters kept: log, keep serving
                self._events.append({"t": time.monotonic(),
                                     "note": f"precision tier skipped: {e}"})
        if self._vision is not None:
            cohorts = [k for k in self._vision._packed if k[1] is not None]
            for model, prec in cohorts:
                if on:
                    self._vision.degrade_cohort(model, prec)
                else:
                    self._vision.restore_cohort(model, prec)

    def _enter_tenant_shed(self) -> list[_Handle]:
        """Pick the lowest-priority tenant cohort and drop its queues."""
        with self._lock:
            tenants = (set(self._lm_q.queues) | set(self._vi_q.queues)
                       | set(self.cfg.tenant_priority))
            if not tenants:
                return []
            prio = {t: self.cfg.tenant_priority.get(t, 0) for t in tenants}
            lowest = min(prio.values())
            shed = {t for t, p in prio.items() if p == lowest}
            if len(shed) == len(tenants):   # never shed everyone
                shed = set()
            self._shed_tenants = shed
            dropped = (self._lm_q.drop_tenants(shed)
                       + self._vi_q.drop_tenants(shed))
            self._shed[SHED_OVERLOAD] += len(dropped)
        return dropped

    # -- workers -------------------------------------------------------------

    def _cull_and_cancel(self, eng, fq: _FairQueues, now: float):
        """Deadline enforcement: expired queued handles resolve now; expired
        in-flight handles are cancelled in the engine (slot released at the
        next token boundary) and resolve immediately."""
        with self._lock:
            expired = fq.cull(now)
            for rid, h in list(self._inflight.items()):
                if h.kind == ("lm" if eng is self._lm else "vision") \
                        and h.expired(now):
                    eng.cancel(rid)
                    del self._inflight[rid]
                    expired.append(h)
        for h in expired:
            self._resolve_expired(h)

    def _sample_depth(self):
        with self._lock:
            d = self._lm_q.total() + self._vi_q.total()
            self._depth_ring.push(d)
            self._max_depth = max(self._max_depth, d)

    def _lm_worker(self):
        eng, fq = self._lm, self._lm_q
        while not self._stop_evt.is_set():
            now = time.monotonic()
            self._ladder_tick(now)
            self._cull_and_cancel(eng, fq, now)
            # Admit what the grid can take (paced by admit_burst); gateway
            # queues are the only waiting room.
            admitted = 0
            while eng.n_free_slots > 0 and admitted < self.cfg.admit_burst:
                with self._lock:
                    h = fq.pop_next(now)
                if h is None:
                    break
                h.admit_t = time.monotonic()
                h.status = "running"
                eng.submit(h.payload)
                self._inflight[h.rid] = h
                admitted += 1
            busy = bool(admitted) or any(r is not None for r in eng.slot_req) \
                or bool(eng.queue)
            if busy:
                # Drain length: multi-step drains amortize dispatch overhead
                # on an idle queue, but while gateway work is pending a long
                # drain delays the refill of slots that free mid-drain —
                # decode one step at a time, exactly the rule the engine
                # applies to its own queue. Tier >= 1 pins the short drain
                # even through transient empty-queue windows.
                with self._lock:
                    pending = fq.total() > 0
                base = (max(1, self.cfg.degraded_drain_steps)
                        if self._tier >= 1 else self._orig_drain)
                eng.drain_steps = 1 if pending else base
                done = eng.step()
                now = time.monotonic()
                for i, r in enumerate(eng.slot_req):
                    if r is not None:
                        h = self._inflight.get(r.rid)
                        if h is not None:
                            self._stream_lm(h, eng.slot_out[i], now)
                for c in done:
                    h = self._inflight.pop(c.rid, None)
                    if h is not None:
                        self._finish_lm(h, c.tokens)
            else:
                self._wake.wait(self.cfg.poll_interval_s)
                self._wake.clear()
            self._sample_depth()

    def _vision_worker(self):
        eng, fq = self._vision, self._vi_q
        while not self._stop_evt.is_set():
            now = time.monotonic()
            if self._lm is None:      # otherwise the LM worker ticks it
                self._ladder_tick(now)
            self._cull_and_cancel(eng, fq, now)
            admitted = False
            while eng.n_free_slots > 0:
                with self._lock:
                    h = fq.pop_next(now)
                if h is None:
                    break
                h.admit_t = time.monotonic()
                h.status = "running"
                eng.submit(h.payload)
                self._inflight[h.rid] = h
                admitted = True
            if admitted or eng.queue:
                done = eng.step()
                now = time.monotonic()
                for c in done:
                    h = self._inflight.pop(c.rid, None)
                    if h is None:
                        continue
                    h.status = "done"
                    h.done_t = h.first_tok_t = now
                    h.result = c
                    with self._lock:
                        self._ttft.push((now - h.submit_t) * 1e3)
                        if h.admit_t is not None:
                            self._ttft_admit.push((now - h.admit_t) * 1e3)
                        self._completions.append((now, h.tenant, 1))
                        self._observe_service(now)
                    h.push(c)
            else:
                self._wake.wait(self.cfg.poll_interval_s)
                self._wake.clear()
            self._sample_depth()

    # -- telemetry -----------------------------------------------------------

    def stats(self) -> dict:
        """Consistent snapshot of the live telemetry."""
        now = time.monotonic()
        with self._lock:
            comp = list(self._completions)
            window_tok = sum(n for _, _, n in comp)
            span = (now - comp[0][0]) if comp else 0.0
            by_tenant: dict = {}
            for t_, tenant, n in comp:
                by_tenant[tenant] = by_tenant.get(tenant, 0) + n
            goodput = {t: round(n / span, 2) if span > 0 else None
                       for t, n in sorted(by_tenant.items())}
            sheds = dict(self._shed)
            submits = self._submits
            depth_now = self._lm_q.total() + self._vi_q.total()
            snapshot = {
                "tier": self._tier,
                "queue": {
                    "depth": depth_now,
                    "max_depth": self._max_depth,
                    "bound": (self._lm_q.capacity()
                              if self._lm is not None else 0)
                    + (self._vi_q.capacity()
                       if self._vision is not None else 0),
                    "sampled": self._depth_ring.percentiles(),
                },
                "ttft_ms": self._ttft.percentiles(),
                "ttft_admit_ms": self._ttft_admit.percentiles(),
                "tpot_ms": self._tpot.percentiles(),
                "tok_s": round(window_tok / span, 2) if span > 0 else None,
                "svc_rate_req_s": round(self._svc_rate, 2),
                "submits": submits,
                "inflight": len(self._inflight),
                "shed": sheds,
                "shed_rate": (sum(sheds.values()) / submits
                              if submits else 0.0),
                "goodput_tok_s_by_tenant": goodput,
                "events": list(self._events),
                "errors": list(self._errors),
            }
        for name, eng in (("lm", self._lm), ("vision", self._vision)):
            if eng is not None:
                st = eng.stats()
                snapshot[f"{name}_health"] = st["health"]
                snapshot[f"{name}_counters"] = st["counters"]
        return snapshot
