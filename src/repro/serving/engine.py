"""Batched serving engine: continuous batching over a fixed decode grid.

The engine owns one device-resident decode state of shape
``(max_batch, max_len)`` plus a device-resident per-slot control block
(last token, eos id, remaining budget, live flag, PRNG key) and runs three
jitted programs, all with **buffer donation** so XLA updates the KV /
recurrent state in place instead of allocating a copy per call:

  * ``prefill_into_slot`` — admission path. The prompt is split into its
    binary decomposition of power-of-two chunks (13 -> 8 + 4 + 1) and each
    chunk prefills into slot ``i`` via ``dynamic_update_slice`` under jit;
    chunk lengths are the only shape that varies, so a varied-length
    workload compiles at most ceil(log2(max_len)) prefill variants.
    Chunking (instead of right-padding to a bucket) keeps recurrent
    (RG-LRU / RWKV) and ring-buffer states exact: carry state threads
    across chunks and no pad token ever enters the recurrence.
  * ``decode_n`` — steady state. A ``jax.lax.scan`` runs up to
    ``drain_steps`` decode steps per dispatch when no admissions are
    pending; **sampling is fused into the jitted step** (one engine key
    split per step, then per slot), so only the (n, B) sampled tokens and
    done flags cross to host — never the (B, vocab) logits. Dead slots
    decode into a frozen trash position; the grid never reshapes.
  * ``admit_ctrl`` — writes a freshly-prefilled slot's control entries and
    samples its first token in-jit.

Continuous batching: when a sequence finishes (EOS or budget), its slot is
released and the next queued request prefills into it — the decode grid
keeps running; there is no global drain. While the queue is non-empty the
engine decodes one step at a time so a freed slot is refilled at the next
token boundary; once the queue drains it switches to multi-step dispatches.

Fault tolerance: ``snapshot``/``restore`` round-trip the device state +
control block through the checkpoint module and carry the per-slot host
bookkeeping AND the queued-but-unadmitted requests in the manifest, so a
preempted server resumes mid-generation with nothing resubmitted.

Self-healing (DESIGN.md §7): ``faults`` injects the NAND-SPIN device-fault
model — persistent write/stuck-at/retention faults corrupt the packed
planes at prepack, transient read disturb strikes inside the jitted decode
step (each step derives a disturb key from the engine key and activates
``repro.pim.faults.read_disturb_scope`` around the bit-serial matmuls).
``watchdog`` arms per-dispatch supervision: an in-memory shadow snapshot
before each dispatch, rollback + bounded-backoff retry (the training
stack's ``RestartPolicy``) on injected faults / device errors / non-finite
logits / blown deadlines, durable disk snapshots on a cadence, and — when
the failure budget is exhausted — graceful degradation to the float
fallback path so the bank keeps serving instead of crashing. Both default
to None, in which case every hot-loop program lowers to byte-identical HLO
(asserted in tests/test_faults.py).

PIM deployment: when ``cfg.pim`` is enabled the constructor prepacks every
projection weight into :class:`repro.core.packed.PackedWeight` — the
paper's program-subarrays-once step — so prefill/decode never re-calibrate,
re-quantize or re-pack a weight (DESIGN.md §3/§4).

Mesh-sharded serving (DESIGN.md §5): pass ``mesh`` (a ("data", "model")
mesh, e.g. ``repro.launch.mesh.make_serve_mesh``) and the engine maps the
paper's chip→bank→subarray hierarchy onto it — batch slots (chips) shard
on "data", every projection's output columns and the PackedWeight planes
(banks) on "model", and the bit-serial kernels tile subarrays into VMEM.
All three hot-loop programs compile with explicit in/out shardings equal to
the committed layouts, so under donation the steady-state decode loop never
inserts a resharding transfer — the only collectives are the tensor-parallel
partial-sum all-reduces and KB-scale scatter-index broadcasts (asserted on
compiled HLO in tests/test_serve_sharded.py).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models.lm import (
    decode_step, init_state, prefill_into_slot, prepack_params,
)
from repro.models.lm.config import ModelConfig

from .sampler import SamplerConfig, sample_per_slot


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray              # (L,) int32
    max_new_tokens: int = 32
    eos_id: int = -1                # -1: never
    deadline_ms: float | None = None   # end-to-end latency budget; enforced
                                       # by the gateway (queued AND
                                       # mid-generation), None = no deadline


@dataclasses.dataclass
class Completion:
    rid: int
    tokens: list


def _pow2_chunks(n: int) -> list[int]:
    """Binary decomposition, largest first: 13 -> [8, 4, 1]."""
    out = []
    b = 1 << max(n.bit_length() - 1, 0)
    while n:
        if n >= b:
            out.append(b)
            n -= b
        b >>= 1
    return out


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, max_batch: int = 8,
                 max_len: int = 512, sampler: SamplerConfig | None = None,
                 seed: int = 0, drain_steps: int = 8, mesh=None,
                 faults=None, watchdog=None, fault_injector=None,
                 keep_masters: bool = False, autotune: str = "off",
                 tuning_cache=None, pipeline_stages: int = 1,
                 pipeline_microbatches: int | None = None):
        if autotune not in ("off", "cost", "measure"):
            raise ValueError(
                f"autotune {autotune!r}: want 'off' | 'cost' | 'measure'")
        self.cfg = cfg
        self.mesh = mesh
        self.autotune = autotune
        self.pipeline_stages = max(1, int(pipeline_stages))
        self._pipe_mesh = None
        self._step_fn = decode_step
        if self.pipeline_stages > 1:
            # Pipeline-composed decode (DESIGN.md §11): the scanned unit
            # repetitions split over a dedicated 1-D ("stage",) mesh and
            # microbatches stream through GPipe-style. Mutually exclusive
            # with the ("data", "model") serving mesh — stage permutes and
            # GSPMD resharding do not compose in one program here.
            if mesh is not None:
                raise ValueError(
                    "pipeline_stages > 1 builds its own ('stage',) mesh; "
                    "pass mesh=None (data/model sharding and the pipeline "
                    "schedule are alternative decode compositions)")
            from repro.models.lm.model import layer_plan

            _, reps, _ = layer_plan(cfg)
            if reps % self.pipeline_stages:
                raise ValueError(
                    f"cannot pipeline: {reps} scanned repetition(s) do not "
                    f"factor into {self.pipeline_stages} equal stages")
            n_micro = pipeline_microbatches or self.pipeline_stages
            if max_batch % n_micro:
                raise ValueError(
                    f"cannot pipeline: max_batch {max_batch} does not split "
                    f"into {n_micro} equal microbatches")
            devs = jax.devices()
            if len(devs) < self.pipeline_stages:
                raise ValueError(
                    f"pipeline_stages={self.pipeline_stages} needs that many "
                    f"devices; have {len(devs)}")
            from jax.sharding import Mesh

            from repro.distributed.pipeline import pipeline_decode_step

            self._pipe_mesh = Mesh(
                np.asarray(devs[:self.pipeline_stages]), ("stage",))
            self._step_fn = partial(pipeline_decode_step,
                                    mesh=self._pipe_mesh,
                                    n_stages=self.pipeline_stages,
                                    n_microbatch=n_micro)
        # Routing telemetry (MoE only): per-step dropped-assignment fraction
        # ring buffers surfaced through :meth:`stats` for the gateway.
        self._moe_stats = bool(cfg.moe)
        if self._moe_stats:
            from .gateway import Ring

            self.rings = {"moe_drop_frac": Ring(512)}
        else:
            self.rings = {}
        self._tuning_cache_arg = tuning_cache
        self.tune_cache = None
        self.faults = faults
        self.watchdog = watchdog
        self.fault_injector = fault_injector   # test hook: raises per dispatch
        if mesh is not None and getattr(cfg.pim, "enabled", False) \
                and getattr(cfg.pim, "backend", "") == "pallas":
            # pallas_call has no GSPMD partitioning rule: under plain jit the
            # "model"-split planes would silently all-gather every step.
            # (kernels.bitserial_matmul_sharded is the shard_map primitive
            # for mesh-level pallas use; it is not wired into pim_linear yet.)
            raise ValueError(
                "mesh-sharded serving does not support pim backend 'pallas'; "
                "use 'popcount' or 'int-direct' (both partition under GSPMD)")
        # Deployment-time weight quantize+pack, exactly once (the paper
        # programs subarrays once): every prefill/decode after this reuses
        # the PackedWeight planes — no per-call re-calibration or re-pack.
        # With a mesh, the tree is committed to the serving layout here
        # (banks = "model"-axis column split; DESIGN.md §5). Persistent
        # device faults strike this programming pass (and, with
        # faults.checksum, repair from spares) before the tree ships.
        self.max_batch = max_batch
        self.params = prepack_params(params, cfg.pim, mesh=mesh,
                                     faults=faults)
        self._maybe_autotune()
        # The float masters survive under supervision (the degrade-to-float
        # fallback re-deploys from them) or on request (``keep_masters`` —
        # the gateway's precision-degradation tier calls :meth:`redeploy`).
        self._raw_params = params if (watchdog is not None
                                      or keep_masters) else None
        self.max_len = max_len
        self.sampler = sampler or SamplerConfig()
        self.drain_steps = max(1, drain_steps)
        self.state = init_state(cfg, max_batch, max_len)
        # Device-resident per-slot control block: consumed and produced by
        # the jitted decode under donation, so steady state moves no
        # control data between host and device.
        self.ctrl = {
            "last_tok": jnp.zeros((max_batch,), jnp.int32),
            "eos": jnp.full((max_batch,), -1, jnp.int32),
            "remaining": jnp.zeros((max_batch,), jnp.int32),
            "live": jnp.zeros((max_batch,), bool),
            "key": jax.random.PRNGKey(seed),
        }
        # Host bookkeeping mirrors (admission decisions + output assembly).
        self.slot_req: list = [None] * max_batch
        self.slot_out: list = [[] for _ in range(max_batch)]
        self.slot_remaining = np.zeros(max_batch, np.int32)
        self.queue: collections.deque = collections.deque()
        self.done: list = []
        self._cancelled: set = set()   # rids to release at the next boundary
        self._queued_at: dict = {}     # rid -> obs.now() at submit, recording
        # Work counters (stats()["counters"]): plain integer increments.
        self.counters = dict.fromkeys(
            ("submitted", "admitted", "prefill_chunks", "prefill_tokens",
             "decode_dispatches", "decode_steps", "slot_steps", "tokens_out",
             "kv_tokens"), 0)

        # Supervision state (inert unless watchdog/fault_injector set).
        from repro.training.fault_tolerance import (RestartPolicy,
                                                    StragglerDetector,
                                                    WatchdogConfig)

        wd = watchdog or WatchdogConfig()
        self._policy = RestartPolicy(wd.max_failures, wd.backoff_s)
        self._detector = StragglerDetector(wd.straggler_z)
        self._last_ok = True
        self.health = {"dispatches": 0, "rollbacks": 0, "stragglers": 0,
                       "snapshots": 0, "degraded": False}

        self._build_programs()

        # Lint-gate registration (repro.analysis; DESIGN.md §10): the
        # engine's jitted program families become lintable hot paths for
        # the CLI/CI gate. Weakly held — close() or GC unregisters.
        from repro import analysis as _analysis
        _analysis.register(self)

    def _maybe_autotune(self):
        """Attach per-weight TuneDecisions to the prepacked tree.

        Runs right after prepack (``__init__`` and every :meth:`redeploy`):
        the autotuner (repro.pim.autotune) picks backend + tiles per packed
        GEMM for this deployment's decode shape (m = max_batch) and records
        them in the tuning cache. Decisions are static pytree metadata —
        shardings, donation and checkpoint layouts are untouched; only
        which compiled program runs changes. The candidate set comes from
        ``autotune.default_backends(mesh)``, which already excludes pallas
        wherever the engine's own backend validation would (no GSPMD rule
        under a mesh, interpret-only off-TPU).
        """
        if self.autotune == "off" or not getattr(self.cfg.pim, "enabled",
                                                 False):
            return
        from repro.pim import autotune as _at

        if self.tune_cache is None:
            self.tune_cache = _at.as_cache(self._tuning_cache_arg)
        moe_kw = {}
        if self.cfg.moe:
            # Expert GEMMs batch every expert's capacity rows through one
            # vmapped dispatch — key their decisions on the (E*C, d, f)
            # batched shape, not the token batch (DESIGN.md §11).
            from repro.models.lm.moe import _capacity

            moe_kw["moe_m_hint"] = (self.cfg.moe.n_experts
                                    * _capacity(self.max_batch, self.cfg))
        self.params = _at.tune_tree(
            self.params, m_hint=self.max_batch,
            a_bits=self.cfg.pim.a_bits,
            backends=_at.default_backends(self.mesh),
            mode=self.autotune, cache=self.tune_cache, **moe_kw)

    def _build_programs(self):
        """(Re)compile the three hot-loop programs for the current cfg/params.

        Split out of ``__init__`` because the degrade-to-float fallback
        swaps ``cfg.pim``/``params`` and must rebuild against the new tree.

        With a mesh, every hot-loop program compiles with explicit in/out
        shardings equal to the committed layouts: the donated state/ctrl
        buffers then alias in place AND keep one stable layout across
        calls, so steady-state decode inserts no resharding transfer
        (asserted on HLO in tests/test_serve_sharded.py).
        """
        pf_kw, ad_kw, self._dec_kw = {}, {}, {}
        if self.mesh is not None:
            from repro.distributed import sharding as _sh

            mesh = self.mesh
            p_sh = _sh.serve_param_shardings(self.params, mesh)
            s_sh = _sh.serve_state_shardings(self.state, mesh)
            c_sh = _sh.serve_ctrl_shardings(self.ctrl, mesh)
            repl = _sh.replicated(mesh)
            self.state = jax.device_put(self.state, s_sh)
            self.ctrl = jax.device_put(self.ctrl, c_sh)
            self._shardings = (p_sh, s_sh, c_sh)
            stream = _sh.serve_stream_sharding(mesh, self.max_batch)
            pf_kw = dict(in_shardings=(p_sh, s_sh, repl, repl, repl),
                         out_shardings=(repl, s_sh))
            ad_kw = dict(in_shardings=(c_sh, repl, repl, repl, repl),
                         out_shardings=(c_sh, repl))
            dec_out = (s_sh, c_sh, stream, stream)
            if self._moe_stats:
                dec_out = dec_out + (repl,)        # (n,) drop-frac telemetry
            if self._transient:
                dec_out = dec_out + (repl,)        # the in-jit health flag
            self._dec_kw = dict(in_shardings=(p_sh, s_sh, c_sh),
                                out_shardings=dec_out)

        self._prefill = jax.jit(partial(self._prefill_impl, self.cfg),
                                donate_argnums=(1,), **pf_kw)
        self._admit_ctrl = jax.jit(partial(self._admit_impl, self.sampler),
                                   donate_argnums=(0,), **ad_kw)
        self._decode = {}   # scan length -> jitted decode_n program

    @property
    def _transient(self) -> bool:
        return self.faults is not None and self.faults.transient

    @contextlib.contextmanager
    def _activate(self):
        """Scope the engine's mesh to its own program calls.

        The sharding module's mesh is process-global (model code stays
        mesh-agnostic); tracing happens inside the jitted calls, so the
        mesh — and the serving KV layout flag consumed by
        ``constrain_kv_update`` — is activated around each call and
        restored after, instead of leaking into every later trace in the
        process (a mesh-free engine built afterwards must not inherit it).
        Mesh-free engines leave the global state alone entirely."""
        if self.mesh is None:
            yield
            return
        from repro.distributed import sharding as _sh

        prev_mesh, prev_serve = _sh.get_mesh(), _sh.get_serve_layout()
        _sh.set_mesh(self.mesh)
        _sh.set_serve_layout(True)
        try:
            yield
        finally:
            _sh.set_mesh(prev_mesh)
            _sh.set_serve_layout(prev_serve)

    # -- jitted bodies ------------------------------------------------------

    @staticmethod
    def _prefill_impl(cfg, params, state, tokens, slot, start):
        return prefill_into_slot(params, cfg, tokens, state, slot, start)

    @staticmethod
    def _admit_impl(sampler, ctrl, logits, slot, eos_id, n_new):
        """Sample the first token and write slot ``slot``'s control entries."""
        key, sub = jax.random.split(ctrl["key"])
        tok = sample_per_slot(logits[:, -1], sampler, sub[None])[0]
        eos_id = jnp.asarray(eos_id, jnp.int32)
        alive = (jnp.asarray(n_new, jnp.int32) > 1) & (tok != eos_id)

        def put(ref, val):
            return jax.lax.dynamic_update_slice(
                ref, jnp.asarray(val, ref.dtype)[None], (slot,))

        ctrl = dict(
            ctrl, key=key,
            last_tok=put(ctrl["last_tok"], tok),
            eos=put(ctrl["eos"], eos_id),
            remaining=put(ctrl["remaining"], jnp.asarray(n_new, jnp.int32) - 1),
            live=put(ctrl["live"], alive),
        )
        return ctrl, tok

    @staticmethod
    def _step_core(cfg, sampler, params, state, ctrl, faults=None,
                   step_fn=decode_step, want_stats=False):
        """One fused decode+sample step. Only (B,) tokens/flags leave jit.

        With transient faults, a disturb key splits off the engine key and
        the decode runs under ``read_disturb_scope`` — every bit-serial
        matmul senses a freshly disturbed view of its planes; an extra
        output reports in-jit logit health (the NaN watchdog probe). With
        ``faults=None`` the traced program is byte-identical to before.

        ``step_fn`` is the decode-step implementation — the sequential
        ``decode_step`` or the pipeline-composed
        ``distributed.pipeline.pipeline_decode_step`` partial.
        ``want_stats`` (MoE engines) appends the per-step routing
        drop-fraction scalar to the outputs. Extra-output order is fixed:
        (state, ctrl, tok, done[, drop][, ok]).
        """
        def run(st):
            return step_fn(params, cfg, ctrl["last_tok"][:, None], st,
                           return_stats=want_stats)

        if faults is not None and faults.transient:
            from repro.pim.faults import read_disturb_scope

            key0, dkey = jax.random.split(ctrl["key"])
            ctrl = dict(ctrl, key=key0)
            with read_disturb_scope(faults, dkey):
                out = run(state)
        else:
            out = run(state)
        if want_stats:
            logits, new_state, st_stats = out
        else:
            logits, new_state = out
        key, sub = jax.random.split(ctrl["key"])
        keys = jax.random.split(sub, ctrl["last_tok"].shape[0])
        nxt = sample_per_slot(logits[:, 0], sampler, keys)
        nxt = jnp.where(ctrl["live"], nxt, ctrl["last_tok"])
        remaining = ctrl["remaining"] - ctrl["live"].astype(jnp.int32)
        done = ctrl["live"] & ((nxt == ctrl["eos"]) | (remaining <= 0))
        # Dead slots do not advance: their trash KV writes land on one row,
        # which the next occupant overwrites before it becomes attendable.
        new_state["length"] = jnp.where(ctrl["live"], new_state["length"],
                                        state["length"])
        ctrl = dict(ctrl, key=key, last_tok=nxt, remaining=remaining,
                    live=ctrl["live"] & ~done)
        extra = ()
        if want_stats:
            extra = extra + (st_stats["moe_drop_frac"],)
        if faults is not None and faults.transient:
            extra = extra + (jnp.isfinite(logits).all(),)
        return (new_state, ctrl, nxt, done) + extra

    @staticmethod
    def _decode_impl(cfg, sampler, faults, step_fn, want_stats, n,
                     params, state, ctrl):
        """``n`` fused decode steps per dispatch; emits (n, B) tokens/flags
        (+ the (n,) per-step drop fractions on MoE engines, + one
        dispatch-level health flag when transient faults are on)."""
        transient = faults is not None and faults.transient

        def body(carry, _):
            st, ct = carry
            out = ServeEngine._step_core(cfg, sampler, params, st, ct,
                                         faults, step_fn, want_stats)
            return (out[0], out[1]), out[2:]

        (state, ctrl), ys = jax.lax.scan(body, (state, ctrl), None, length=n)
        ys = list(ys)
        out = [state, ctrl, ys.pop(0), ys.pop(0)]
        if want_stats:
            out.append(ys.pop(0))           # (n,) per-step drop fractions
        if transient:
            out.append(ys.pop(0).all())
        return tuple(out)

    def _decode_fn(self, n: int):
        fn = self._decode.get(n)
        if fn is None:
            fn = jax.jit(partial(self._decode_impl, self.cfg, self.sampler,
                                 self.faults, self._step_fn,
                                 self._moe_stats, n),
                         donate_argnums=(1, 2), **self._dec_kw)
            self._decode[n] = fn
        return fn

    def hot_paths(self):
        """Declare the three hot-loop program families for the lint gate.

        Budgets encode the serving performance story (DESIGN.md §5/§10):
        decode must stay free of all-to-all and weight/KV-sized gathers
        with collective counts flat in the drain length, every donated
        state/ctrl buffer must actually alias, and no host sync, f64 or
        illegal autotune tile may appear in any hot program. Programs
        lower under :meth:`_activate`, exactly like the real dispatch."""
        from repro import analysis as _an

        # The all-to-all budget is 0 — decode must not reshard — except on
        # the packed expert-parallel MoE layout (mesh "model" axis divides
        # E, weights prepacked): there the dispatch/combine all-to-all is
        # the *designed* collective (DESIGN.md §11), budgeted per FFN site
        # (dispatch + combine + the small occupancy mask per MoE layer).
        a2a_cap = 0
        if self.cfg.moe and self.mesh is not None \
                and getattr(self.cfg.pim, "enabled", False):
            from repro.distributed import sharding as _sh
            from repro.models.lm.model import layer_plan

            ms = _sh.axis_size(self.mesh, "model")
            if ms > 1 and self.cfg.moe.n_experts % ms == 0:
                unit, _, rest = layer_plan(self.cfg)
                sites = sum(k != "rwkv" for k in unit + rest)
                a2a_cap = 4 * max(sites, 1)
        base = dict(
            collectives=(("all-to-all", a2a_cap),),
            compute_dtype="bf16" if str(self.cfg.dtype) == "bfloat16"
            else None,
            m_hint=self.max_batch,
            pallas_ok=self.mesh is None,
        )
        # Pipelined decode adds exactly one collective class of its own:
        # the inter-stage permute (plus the drain psum all-reduces, which
        # the byte bound and scan-flatness already police). Cap it so a
        # permute can never creep inside the per-rep layer scan.
        dec_coll = base["collectives"]
        if self.pipeline_stages > 1:
            dec_coll = dec_coll + (("collective-permute", 4),)
        tokens = jnp.zeros((1, 1), jnp.int32)
        logits = jnp.zeros((1, 1, self.cfg.vocab),
                           jnp.dtype(self.cfg.dtype))
        dec_name = ("lm.decode.pipelined" if self.pipeline_stages > 1
                    else "lm.decode")
        return [
            _an.HotPath(
                "lm.prefill", "lm",
                _an.Budget(donate=(1,), max_gather_bytes=None, **base),
                [_an.Program("chunk=1", self._prefill,
                             (self.params, self.state, tokens, 0, 0))],
                context=self._activate),
            _an.HotPath(
                "lm.admit", "lm",
                _an.Budget(donate=(0,), max_gather_bytes=None, **base),
                [_an.Program("slot", self._admit_ctrl,
                             (self.ctrl, logits, 0, -1, 4))],
                context=self._activate),
            _an.HotPath(
                dec_name, "lm",
                _an.Budget(donate=(1, 2), max_gather_bytes=16384,
                           scan_flat=True,
                           **dict(base, collectives=dec_coll)),
                [_an.Program(f"n={n}", self._decode_fn(n),
                             (self.params, self.state, self.ctrl))
                 for n in sorted({1, self.drain_steps})],
                context=self._activate),
        ]

    def close(self):
        """Engine teardown: deregister from the lint gate and reset the
        tuning cache so a later deploy sharing the cache object re-reads
        its (possibly repaired) backing file instead of serving this
        deployment's stale fallback memo."""
        from repro import analysis as _analysis
        _analysis.unregister(self)
        if self.tune_cache is not None:
            self.tune_cache.reset()

    # -- public API ---------------------------------------------------------

    def validate(self, prompt, max_new_tokens: int):
        """Admission-time request validation. ``_admit`` writes the prompt
        into the (max_batch, max_len) decode grid at positions 0..L-1 and
        each generated token's KV at the running length, so a request with
        ``L + max_new_tokens > max_len`` would silently write past the grid
        (``dynamic_update_slice`` clamps — the tail tokens corrupt the last
        row instead of raising). Reject it here, with the empty prompt (no
        logits to sample the first token from) and a non-positive budget."""
        n = len(prompt)
        if n == 0:
            raise ValueError("empty prompt: nothing to prefill, no final "
                             "logits to sample the first token from")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens={max_new_tokens} must be >= 1")
        if n + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({n} tokens) + max_new_tokens ({max_new_tokens}) "
                f"exceeds the decode grid (max_len={self.max_len}); the "
                "overflow would clamp into the grid's last row")

    def submit(self, req: Request):
        self.validate(req.prompt, req.max_new_tokens)
        t = obs.now()
        if t is not None:
            self._queued_at[req.rid] = t
        self.queue.append(req)
        self.counters["submitted"] += 1

    def cancel(self, rid: int) -> str | None:
        """Cancel a request. Queued: removed immediately. Mid-generation:
        its slot is released at the next token boundary through the same
        slot-free path a natural completion takes — the dead slot decodes
        into its frozen trash position until then, and the next occupant's
        prefill zeroes the recurrent carries (the PR 3 slot-reuse guard).
        Returns "queued" / "active" for what was cancelled, None if the rid
        is unknown (already completed or never submitted)."""
        for i, r in enumerate(self.queue):
            if r.rid == rid:
                del self.queue[i]
                self._queued_at.pop(rid, None)
                return "queued"
        for r in self.slot_req:
            if r is not None and r.rid == rid:
                self._cancelled.add(rid)
                return "active"
        return None

    @property
    def n_free_slots(self) -> int:
        """Slots an admission could land in right now: free grid slots not
        already spoken for by queued requests. The gateway uses this to
        admit exactly what the grid can take (its own queues stay the only
        place requests wait, so shedding decisions are centralized)."""
        free = sum(r is None for r in self.slot_req)
        return max(0, free - len(self.queue))

    def _release_cancelled(self):
        """Free cancelled slots at a token boundary: clear the host slot
        (continuous batching refills it on the next ``_admit``) and kill the
        slot's device liveness so the grid decodes it into the trash row."""
        hit = [i for i, r in enumerate(self.slot_req)
               if r is not None and r.rid in self._cancelled]
        self._cancelled.clear()
        if not hit:
            return
        mask = np.zeros(self.max_batch, bool)
        mask[hit] = True
        mask = jnp.asarray(mask)
        ctrl = dict(self.ctrl,
                    live=self.ctrl["live"] & ~mask,
                    remaining=jnp.where(mask, 0, self.ctrl["remaining"]))
        if self.mesh is not None:
            # Keep the control block committed to the canonical layout —
            # the hot-loop programs' in_shardings reject drifted buffers.
            _, _, c_sh = self._shardings
            ctrl = jax.device_put(ctrl, c_sh)
        self.ctrl = ctrl
        for i in hit:
            self.slot_req[i] = None
            self.slot_out[i] = []
            self.slot_remaining[i] = 0

    def _free_slots(self):
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def _admit(self):
        """Prefill queued requests into free slots, chunked power-of-two."""
        for slot in self._free_slots():
            if not self.queue:
                break
            req = self.queue.popleft()
            t = self._queued_at.pop(req.rid, None)
            if t is not None:
                obs.span("serve.queued", t, rid=req.rid).close()
            prompt = np.asarray(req.prompt, np.int32)
            chunks = _pow2_chunks(len(prompt))
            pos, logits = 0, None
            with obs.span("serve.admit", rid=req.rid, prompt_len=len(prompt),
                          chunks=len(chunks),
                          live=sum(r is not None for r in self.slot_req)):
                with self._activate():
                    for c in chunks:
                        tokens = jnp.asarray(prompt[pos:pos + c],
                                             jnp.int32)[None]
                        logits, self.state = self._prefill(
                            self.params, self.state, tokens, slot, pos)
                        pos += c
                    self.ctrl, tok = self._admit_ctrl(
                        self.ctrl, logits, slot, req.eos_id,
                        req.max_new_tokens)
                first = int(tok)
            ctr = self.counters
            ctr["admitted"] += 1
            ctr["prefill_chunks"] += len(chunks)
            ctr["prefill_tokens"] += len(prompt)
            ctr["tokens_out"] += 1
            self.slot_out[slot] = [first]
            if req.max_new_tokens <= 1 or first == req.eos_id:
                self.done.append(Completion(req.rid, self.slot_out[slot]))
                continue
            self.slot_req[slot] = req
            self.slot_remaining[slot] = req.max_new_tokens - 1

    def step(self) -> list:
        """Admit + decode (one step, or a drain of up to ``drain_steps``
        fused steps when no admissions are pending); returns completions.

        With a watchdog (or fault injector) armed, the dispatch runs
        supervised: shadow snapshot -> dispatch -> health checks, with
        rollback + backoff retry on failure and degradation to the float
        path once the failure budget is spent (see :meth:`_step_supervised`).
        """
        with obs.span("serve.step"):
            if self._cancelled:
                # Before the supervised shadow: a rollback must not resurrect
                # a cancelled request (the shadow then captures post-cancel
                # state).
                self._release_cancelled()
            if self.watchdog is None and self.fault_injector is None:
                return self._step_once()
            return self._step_supervised()

    def _step_once(self) -> list:
        """One unsupervised dispatch (the pre-watchdog ``step()`` body)."""
        self._admit()
        live = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not live:
            return self._drain_done()
        if self.queue:
            n = 1   # keep admissions responsive: a slot may free next token
        else:
            cap = max(1, min(self.drain_steps,
                             int(max(self.slot_remaining[i] for i in live))))
            n = 1 << (cap.bit_length() - 1)   # pow2 -> bounded compile count
        with self._activate():
            res = self._decode_fn(n)(self.params, self.state, self.ctrl)
        res = list(res)
        self.state, self.ctrl, toks, dones = res[:4]
        res = res[4:]
        if self._moe_stats:
            for v in np.asarray(res.pop(0)):
                self.rings["moe_drop_frac"].push(float(v))
        if self._transient:
            self._last_ok = bool(res.pop(0))
        with obs.span("serve.fetch"):
            toks = np.asarray(toks)
        dones = np.asarray(dones)
        ctr = self.counters
        ctr["decode_dispatches"] += 1
        ctr["decode_steps"] += n
        ctr["slot_steps"] += n * len(live)
        for k in range(n):
            for i in list(live):
                req = self.slot_req[i]
                # The token decoded from the slot's last one attends the
                # prompt and every output token so far.
                ctr["kv_tokens"] += len(req.prompt) + len(self.slot_out[i])
                ctr["tokens_out"] += 1
                self.slot_out[i].append(int(toks[k, i]))
                self.slot_remaining[i] -= 1
                if dones[k, i]:
                    self.done.append(Completion(req.rid, self.slot_out[i]))
                    self.slot_req[i] = None
                    live.remove(i)
        return self._drain_done()

    def _drain_done(self):
        out, self.done = self.done, []
        return out

    def stats(self) -> dict:
        """Live telemetry snapshot: supervision health, the work counters
        (requests submitted and admitted, prefill chunks and tokens, decode
        dispatches and steps, live slots x steps, output tokens, and the
        context length attended summed over decoded tokens), plus the
        ring-buffer channels (MoE engines: ``moe_drop_frac`` — per-decode-
        step fraction of top-k routing assignments dropped at expert
        capacity). :meth:`Gateway.stats` carries the health and the
        counters as ``lm_health`` / ``lm_counters``."""
        out = {"health": dict(self.health), "counters": dict(self.counters)}
        for name, ring in self.rings.items():
            v = ring.values()
            out[name] = dict(ring.percentiles(),
                             n=len(ring),
                             mean=float(v.mean()) if len(ring) else None)
        return out

    # -- watchdog supervision (DESIGN.md §7) --------------------------------

    def _shadow(self):
        """In-memory rollback point: device buffers copied (the dispatch
        consumes the originals under donation) + host bookkeeping."""
        dev = jax.tree.map(jnp.copy, {"state": self.state, "ctrl": self.ctrl})
        return (dev, list(self.slot_req), [list(o) for o in self.slot_out],
                self.slot_remaining.copy(), collections.deque(self.queue),
                list(self.done))

    def _restore_shadow(self, shadow):
        dev, reqs, outs, rem, queue, done = shadow
        self.state, self.ctrl = dev["state"], dev["ctrl"]
        self.slot_req, self.slot_out = reqs, outs
        self.slot_remaining, self.queue, self.done = rem, queue, done

    def _step_supervised(self) -> list:
        """Shadow -> dispatch -> health checks, rollback + retry on failure.

        Failure channels: the ``fault_injector`` test hook raising, a device
        runtime error, the in-jit non-finite-logit flag (transient faults),
        and a dispatch exceeding ``deadline_s``. Each failure restores the
        shadow (no token is double-emitted: completions drained by the
        failed dispatch are part of the shadow) and retries after
        ``RestartPolicy`` backoff; a spent budget degrades to the float
        path (``degrade=True``) or re-raises.
        """
        wd = self.watchdog
        while True:
            shadow = self._shadow()
            # Monotonic: an NTP step of the wall clock must not blow the
            # dispatch deadline and burn the failure budget spuriously.
            t0 = time.monotonic()
            try:
                if self.fault_injector is not None:
                    self.fault_injector(self.health["dispatches"])
                out = self._step_once()
                dt = time.monotonic() - t0
                if self._detector.observe(dt):
                    self.health["stragglers"] += 1
                if wd is not None and wd.deadline_s is not None \
                        and dt > wd.deadline_s:
                    raise RuntimeError(
                        f"watchdog: dispatch took {dt:.3f}s "
                        f"> deadline {wd.deadline_s}s")
                if not self._last_ok:
                    raise RuntimeError(
                        "watchdog: non-finite logits in dispatch")
            except (RuntimeError, jax.errors.JaxRuntimeError) as e:
                self._restore_shadow(shadow)
                self._last_ok = True
                self.health["rollbacks"] += 1
                try:
                    wait = self._policy.on_failure()
                except RuntimeError:
                    if wd is not None and wd.degrade \
                            and self._raw_params is not None \
                            and getattr(self.cfg.pim, "enabled", False):
                        print(f"[serve-watchdog] budget spent ({e!r}); "
                              "degrading to float path", flush=True)
                        self._degrade_to_float()
                        continue
                    raise
                print(f"[serve-watchdog] dispatch failed: {e!r}; "
                      f"rollback + retry in {wait:.2f}s", flush=True)
                time.sleep(min(wait, 0.05))  # bounded for tests; real: full
                continue
            self.health["dispatches"] += 1
            self._policy.record_progress(self.health["dispatches"])
            if wd is not None and wd.snap_every and wd.ckpt_dir \
                    and self.health["dispatches"] % wd.snap_every == 0:
                self.snapshot(wd.ckpt_dir, step=self.health["dispatches"])
                self.health["snapshots"] += 1
            return out

    def redeploy(self, pim_cfg):
        """Re-prepack from the float masters under a new PIM config and
        rebuild the hot-loop programs — the PR 5 degrade machinery,
        parameterized so the gateway's degradation ladder can move a serving
        cohort to a cheaper precision (or back) under sustained overload.
        Decode state/ctrl carry over — the KV grid is representation-
        independent — so in-flight generations continue on the new path.
        Requires the float masters (``keep_masters=True`` or a watchdog)."""
        if self._raw_params is None:
            raise RuntimeError(
                "redeploy needs the float masters; construct the engine "
                "with keep_masters=True (or a watchdog)")
        self.cfg = dataclasses.replace(self.cfg, pim=pim_cfg)
        self.params = prepack_params(self._raw_params, pim_cfg,
                                     mesh=self.mesh, faults=self.faults)
        self._maybe_autotune()   # new precision -> fresh (cached) decisions
        self._build_programs()

    def _degrade_to_float(self):
        """Sustained fault pressure: re-deploy this bank on the float
        fallback from the golden masters and keep serving (graceful
        degradation instead of a crash)."""
        from repro.training.fault_tolerance import RestartPolicy

        self.faults = None
        self._last_ok = True
        self.redeploy(dataclasses.replace(self.cfg.pim, enabled=False))
        wd = self.watchdog
        self._policy = RestartPolicy(wd.max_failures, wd.backoff_s)
        self.health["degraded"] = True

    def run(self, max_steps: int = 10_000, strict: bool = False) -> list:
        """Drive until queue + slots drain; returns all completions.

        Exhausting ``max_steps`` with work still in flight emits a
        ``RuntimeWarning`` naming the stranded requests — or raises when
        ``strict=True`` — instead of returning silently as if drained.
        """
        out = []
        for _ in range(max_steps):
            out.extend(self.step())
            if not self.queue and all(r is None for r in self.slot_req):
                return out
        live = [r.rid for r in self.slot_req if r is not None]
        queued = [r.rid for r in self.queue]
        if live or queued:
            msg = (f"run(max_steps={max_steps}) exited with "
                   f"{len(live) + len(queued)} stranded request(s): "
                   f"rids {live} mid-generation, rids {queued} queued")
            if strict:
                raise RuntimeError(msg)
            warnings.warn(msg, RuntimeWarning, stacklevel=2)
        return out

    # -- fault tolerance ----------------------------------------------------

    @staticmethod
    def _req_dict(r: Request) -> dict:
        return {"rid": r.rid, "prompt": np.asarray(r.prompt).tolist(),
                "max_new_tokens": r.max_new_tokens, "eos_id": r.eos_id,
                "deadline_ms": r.deadline_ms}

    @staticmethod
    def _req_from(s: dict) -> Request:
        return Request(rid=s["rid"], prompt=np.asarray(s["prompt"], np.int32),
                       max_new_tokens=s["max_new_tokens"], eos_id=s["eos_id"],
                       deadline_ms=s.get("deadline_ms"))

    def snapshot(self, ckpt_dir: str, step: int = 0):
        """Checkpoint device state + control block + slot bookkeeping +
        the queued-but-unadmitted requests (re-enqueued by ``restore``, so
        nothing needs resubmitting). Safe mid-generation: saving copies to
        host, it does not consume the donated device buffers."""
        from repro.training import checkpoint as ckpt

        slots = []
        for i, r in enumerate(self.slot_req):
            slots.append(None if r is None else dict(
                self._req_dict(r),
                out=list(self.slot_out[i]),
                remaining=self.slot_remaining[i],
            ))
        extra = {"slots": slots,
                 "queue": [self._req_dict(r) for r in self.queue],
                 "max_batch": self.max_batch,
                 "max_len": self.max_len}
        if self.tune_cache is not None:
            # Tuning decisions ride the manifest so a restored engine skips
            # re-ranking (and re-measuring) every deployment GEMM.
            extra["tuning"] = self.tune_cache.to_extra()
        ckpt.save(ckpt_dir, step, {"state": self.state, "ctrl": self.ctrl},
                  extra=extra)

    def restore(self, ckpt_dir: str, step: int | None = None):
        """Resume mid-generation from :meth:`snapshot` (same cfg/geometry)."""
        from repro.training import checkpoint as ckpt

        like = {"state": self.state, "ctrl": self.ctrl}
        tree, manifest = ckpt.restore(ckpt_dir, like, step=step)
        if self.mesh is not None:
            # Commit straight to the canonical serving layout — the hot-loop
            # programs' in_shardings reject differently-committed buffers.
            _, s_sh, c_sh = self._shardings
            tree = jax.device_put(tree, {"state": s_sh, "ctrl": c_sh})
        else:
            tree = jax.tree.map(jnp.asarray, tree)   # host -> device once
        self.state, self.ctrl = tree["state"], tree["ctrl"]
        for i, s in enumerate(manifest["extra"]["slots"]):
            if s is None:
                self.slot_req[i] = None
                self.slot_out[i] = []
                self.slot_remaining[i] = 0
            else:
                self.slot_req[i] = self._req_from(s)
                self.slot_out[i] = list(s["out"])
                self.slot_remaining[i] = s["remaining"]
        # Re-enqueue requests that were queued but unadmitted at snapshot
        # time (absent in pre-queue-persistence checkpoints).
        self.queue = collections.deque(
            self._req_from(s) for s in manifest["extra"].get("queue", []))
        if self.tune_cache is not None:
            self.tune_cache.merge_extra(manifest["extra"].get("tuning"))
        return manifest
