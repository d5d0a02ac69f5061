"""Batched CNN serving engine: micro-batched vision inference on the fused
conv path (DESIGN.md §6).

The LM :class:`~repro.serving.engine.ServeEngine` gives the paper's LM
deployment its production properties — prepack-once weights, donated jitted
hot programs, the ("data", "model") serving mesh. The paper itself is a
*CNN* accelerator, and this engine gives the conv stack the same treatment:

  * **Queue + power-of-two micro-batching.** Requests carry (image, model,
    precision ``<W:I>``). The engine groups the queue head's (model,
    precision, image-shape) cohort and dispatches the largest power-of-two
    bucket that fits (5 queued -> 4 + 1), so a varied load compiles at most
    ``log2(max_batch) + 1`` forward variants per (model, cfg) — the same
    bounded-compile-count argument as the LM engine's pow2 prompt chunks.
  * **Prepack exactly once per (model, cfg).** The first request of a
    (model, precision) pair quantizes + packs every conv/fc weight into
    :class:`PackedConvWeight`/:class:`PackedWeight` (the paper's
    program-subarrays-once step) and caches the tree; every later bucket of
    that pair reuses it — no per-call weight calibration, quantization or
    bit-plane packing. Conv layers then run the prepacked fast path:
    materialized im2col for 1x1/small maps, the fused implicit-im2col
    Pallas kernel where :func:`repro.core.fuse_conv_heuristic` fires
    (``backend="pallas"``).
  * **Donated jitted forward.** Each bucket's forward is one jitted program
    with the image batch donated, so XLA reuses the input buffer for
    activations instead of holding both alive.
  * **Mesh-sharded serving.** With a ("data", "model") mesh
    (``repro.launch.mesh.make_serve_mesh``) the paper's chip→bank mapping
    applies to vision exactly as to LM decode: the micro-batch (chips)
    shards on "data", and every conv's output channels O / every FC's
    output columns (banks) on "model" — including both packed
    representations (``PackedConvWeight.mat`` planes/codes/col_sums on
    their N dim and the ``fused_planes`` on O; see
    ``distributed/sharding.py::serve_cnn_param_shardings`` and
    ``core/packed.py::shard_packed``). Forwards compile with explicit
    in/out shardings, and the no-large-all-gather HLO invariant is asserted
    in tests/test_vision_engine.py, mirroring tests/test_serve_sharded.py.
    ``backend="pallas"`` is rejected with a mesh for the same reason as the
    LM engine: ``pallas_call`` has no GSPMD rule.

Numerics: a bucket's logits are bit-identical to jitted ``model.apply`` on
the same stacked batch with the same ``PIMQuantConfig`` under the same
device topology — prepacking produces the exact codes per-call
quantization would, activation calibration is per-batch in both cases, and
the serving machinery (bucketing, caching, donation) adds zero numerics.
Across topologies the quantized integer core is partition-exact and the
float path replicates (bitwise); only the quantized paths' float
dequantization epilogue picks up ULP-level topology-dependent FMA
differences (DESIGN.md §6). Asserted in tests/test_vision_engine.py for
the float, int-direct and popcount paths, single-device and on a forced
8-device mesh.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import re
import time
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import PIMQuantConfig
from repro.core.pim_layers import LOWERINGS, conv_lowering_log
from repro.models.cnn import alexnet, resnet, vgg
from repro.models.cnn.layers import prepack_params as _prepack_cnn

# The paper CNN zoo, keyed by serving name — the single registry the
# engine, launcher (--cnn-model) and cnn benchmark all resolve against.
MODEL_ZOO = {"alexnet": alexnet, "resnet50": resnet, "vgg19": vgg}

_PRECISION = re.compile(r"^<(\d+):(\d+)>$")


def parse_precision(precision: str | None) -> tuple[int, int] | None:
    """``"<W:I>"`` -> (w_bits, a_bits); None/"float" -> None (fp path)."""
    if precision is None or precision in ("float", "fp32"):
        return None
    m = _PRECISION.match(precision)
    if not m:
        raise ValueError(
            f"precision {precision!r}: want '<W:I>' (e.g. '<8:8>') or None")
    return int(m.group(1)), int(m.group(2))


@dataclasses.dataclass(eq=False)   # identity equality: ndarray fields make
class VisionRequest:               # field-wise __eq__ ambiguous, and the
    rid: int                       # queue removes by identity anyway
    image: np.ndarray               # (H, W, C) float
    model: str = "resnet50"
    precision: str | None = "<8:8>"  # "<W:I>" | None (float forward)
    deadline_ms: float | None = None  # latency budget; gateway-enforced


@dataclasses.dataclass
class VisionCompletion:
    rid: int
    logits: np.ndarray              # (num_classes,)
    top1: int
    batch: int                      # bucket size this request rode in


class VisionEngine:
    """Continuous micro-batched CNN inference over a model registry.

    ``models`` maps a model name to its float param tree (names resolve
    against the paper zoo: alexnet / resnet50 / vgg19) or to an explicit
    ``(module, params)`` pair for custom CNNs — any module exposing
    ``apply(params, x, cfg=...)`` over ``repro.core.pim_conv2d`` works.

    ``backend`` picks the Eq. 1 execution strategy for every quantized
    request ("int-direct" | "popcount" | "mxu-plane" | "pallas"); requests
    pick their own precision. ``max_batch`` is the largest micro-batch
    bucket (rounded down to a power of two).
    """

    def __init__(self, models: dict, backend: str = "int-direct",
                 max_batch: int = 8, mesh=None, faults=None, watchdog=None,
                 fault_injector=None, seed: int = 0, autotune: str = "off",
                 tuning_cache=None):
        if autotune not in ("off", "cost", "measure"):
            raise ValueError(
                f"autotune {autotune!r}: want 'off' | 'cost' | 'measure'")
        if mesh is not None and backend == "pallas":
            # Same rule as ServeEngine: pallas_call has no GSPMD partitioning
            # rule, so the "model"-split planes would silently all-gather on
            # every bucket. Use "popcount" or "int-direct" on a mesh.
            raise ValueError(
                "mesh-sharded vision serving does not support backend "
                "'pallas'; use 'popcount' or 'int-direct'")
        self._models = {}
        for name, entry in models.items():
            if isinstance(entry, tuple):
                module, params = entry
            else:
                if name not in MODEL_ZOO:
                    raise ValueError(
                        f"unknown model {name!r} (zoo: {sorted(MODEL_ZOO)}); "
                        "pass (module, params) for custom CNNs")
                module, params = MODEL_ZOO[name], entry
            self._models[name] = (module, params)
        self.backend = backend
        self.max_batch = 1 << (max(1, max_batch).bit_length() - 1)
        self.mesh = mesh
        # Autotune (repro.pim.autotune): conv GEMM shapes depend on the
        # image size, known only at dispatch, so tuned trees are derived
        # lazily per (model, precision, image-hw, bucket) — cheap static-
        # metadata wrappers over the packed tree, cached in ``_tuned``.
        # Vision always ranks by cost model (measurement is a GEMM-level
        # facility; the "measure" knob still upgrades the FC decisions).
        self.autotune = autotune
        self._tuning_cache_arg = tuning_cache
        self.tune_cache = None
        self._tuned: dict = {}      # (model, precision, h, w, bucket) -> tree
        self.queue: collections.deque = collections.deque()
        self._packed: dict = {}     # (model, precision) -> param tree
        self._golden: dict = {}     # (model, precision) -> fault-free tree
        self._param_sh: dict = {}   # (model, precision) -> sharding tree
        self._fwd: dict = {}        # (model, precision, bucket) -> jitted fn
        # Self-healing (DESIGN.md §7): persistent faults strike each
        # (model, precision) programming pass; transient read disturb
        # strikes every quantized dispatch via a per-dispatch key. The
        # watchdog retries failed buckets (repairing flagged columns from
        # the golden tree when the checksum is armed) and degrades a cohort
        # to the float path once its failure budget is spent.
        from repro.training.fault_tolerance import (RestartPolicy,
                                                    WatchdogConfig)

        self.faults = faults
        self.watchdog = watchdog
        self.fault_injector = fault_injector   # test hook: raises per dispatch
        self._wd = wd = watchdog or WatchdogConfig()
        self._policy = RestartPolicy(wd.max_failures, wd.backoff_s)
        self._degraded: set = set()            # (model, precision) cohorts
        self._fault_key = jax.random.PRNGKey(seed)
        self.health = {"dispatches": 0, "rollbacks": 0, "repairs": 0,
                       "repaired_cols": 0, "degraded": []}
        # Work counters (stats()["counters"]): launches, images, launches
        # per bucket size, and the convs of the compiled quantized forwards
        # by lowering (pim_layers.LOWERINGS), each forward counted once, as
        # it is traced.
        self.counters = {"dispatches": 0, "images": 0, "by_bucket": {},
                         "conv_lowering": dict.fromkeys(LOWERINGS, 0)}
        # Lint-gate registration (repro.analysis; DESIGN.md §10). Image
        # shapes are only known at dispatch, so _dispatch records each
        # (model, precision, bucket) -> image shape for hot_paths().
        self._hot_shapes: dict = {}
        from repro import analysis as _analysis
        _analysis.register(self)

    # -- mesh scoping (same contract as ServeEngine._activate) --------------

    @contextlib.contextmanager
    def _activate(self, quantized: bool = True):
        """Scope the mesh (and the CNN serving layout flag consumed by
        ``constrain_cnn_conv_input``/``_output``) to the engine's own
        program calls, like ``ServeEngine._activate``.

        Float buckets never activate it: their jit is fully replicated, and
        tracing them under the global mesh would let ``_constrain_weight``
        split the FC contractions — a float partial-sum reorder that breaks
        the bit-identity contract."""
        if self.mesh is None or not quantized:
            yield
            return
        from repro.distributed import sharding as _sh

        prev_mesh, prev_cnn = _sh.get_mesh(), _sh.get_cnn_serve_layout()
        _sh.set_mesh(self.mesh)
        _sh.set_cnn_serve_layout(True)
        try:
            yield
        finally:
            _sh.set_mesh(prev_mesh)
            _sh.set_cnn_serve_layout(prev_cnn)

    # -- caches --------------------------------------------------------------

    def _cfg(self, precision: str | None) -> PIMQuantConfig | None:
        bits = parse_precision(precision)
        if bits is None:
            return None
        return PIMQuantConfig(w_bits=bits[0], a_bits=bits[1],
                              backend=self.backend)

    def _packed_params(self, model: str, precision: str | None):
        """Quantize+pack (and mesh-commit) exactly once per (model, cfg).

        With a fault model, the freshly programmed quantized tree is
        corrupted by the persistent fault mechanisms (each (model,
        precision) pair gets its own key fold); the fault-free tree is kept
        as the golden master the checksum-repair path re-programs from.
        """
        mkey = (model, precision)
        tree = self._packed.get(mkey)
        if tree is None:
            module, params = self._models[model]
            cfg = self._cfg(precision)
            tree = _prepack_cnn(params, cfg) if cfg is not None else params
            if cfg is not None and self.faults is not None \
                    and self.faults.persistent:
                from repro.pim.faults import inject_tree

                self._golden[mkey] = tree
                key = jax.random.fold_in(self.faults.key(),
                                         len(self._golden))
                tree, _ = inject_tree(tree, self.faults, key)
            if self.mesh is not None:
                from repro.distributed import sharding as _sh

                p_sh = _sh.serve_cnn_param_shardings(
                    tree, self.mesh, quantized=cfg is not None)
                tree = jax.device_put(tree, p_sh)
                self._param_sh[mkey] = p_sh
            self._packed[mkey] = tree
        return tree

    def _repair(self, model: str, precision: str | None) -> int:
        """Checksum-scan the cohort's packed tree and re-program flagged
        columns from the golden master (bounded by the spare budget).
        Returns the number of repaired columns."""
        mkey = (model, precision)
        golden = self._golden.get(mkey)
        if golden is None or self.faults is None or not self.faults.checksum:
            return 0
        from repro.pim.faults import repair_tree

        tree, report = repair_tree(self._packed[mkey], golden,
                                   self.faults.spare_cols,
                                   self.faults.subarray_cols)
        if self.mesh is not None:
            tree = jax.device_put(tree, self._param_sh[mkey])
        self._packed[mkey] = tree
        # Tuned wrappers hold references to the pre-repair arrays; drop
        # them so the next dispatch re-derives from the repaired tree (the
        # decisions themselves come back instantly from the tuning cache).
        self._tuned = {k: v for k, v in self._tuned.items()
                       if k[:2] != mkey}
        return report["repaired_cols"]

    def _tuned_params(self, model: str, precision: str | None, shape):
        """Tuned view of the packed tree for one (cohort, image, bucket).

        Decisions are per-GEMM: FC weights tune on the bucket's row count,
        conv weights on the im2col row bound ``batch * H * W`` (the
        stride-1 upper bound — the backend crossover is driven by the
        plane-pair count, which the bound preserves). Attaching decisions
        is ``dataclasses.replace`` on static metadata, so the committed
        (possibly mesh-sharded) buffers are reused as-is.
        """
        n, h, w, _ = shape
        tkey = (model, precision, h, w, n)
        tree = self._tuned.get(tkey)
        if tree is None:
            from repro.pim import autotune as _at

            if self.tune_cache is None:
                self.tune_cache = _at.as_cache(self._tuning_cache_arg)
            bits = parse_precision(precision)
            tree = _at.tune_tree(
                self._packed[(model, precision)], m_hint=n, a_bits=bits[1],
                backends=_at.default_backends(self.mesh),
                mode=self.autotune if self.autotune != "off" else "cost",
                cache=self.tune_cache, conv_m_hint=n * h * w)
            self._tuned[tkey] = tree
        return tree

    @property
    def _transient(self) -> bool:
        return self.faults is not None and self.faults.transient

    def _fwd_fn(self, model: str, precision: str | None, bucket: int,
                params=None):
        # Tuned trees differ from the base packed tree only in static
        # TuneDecision metadata, but that metadata IS part of the treedef —
        # key the compiled program (and build its in_shardings) from the
        # actual tree being dispatched so decisions recompile cleanly.
        # The untuned path keeps the historical 3-tuple key (one compile
        # per (model, precision, bucket)); tuned trees append their treedef.
        key = (model, precision, bucket)
        if params is not None:
            key = key + (jax.tree_util.tree_structure(params),)
        fn = self._fwd.get(key)
        if fn is None:
            module, _ = self._models[model]
            cfg = self._cfg(precision)
            faulty = cfg is not None and self._transient
            kw = {}
            if self.mesh is not None:
                from repro.distributed import sharding as _sh

                self._packed_params(model, precision)  # ensure sharding tree
                if cfg is None:
                    # Float reference path: fully replicated. CPU float convs
                    # are not bit-stable across batch shapes, so sharding the
                    # batch would break the bit-identity contract; the
                    # quantized deployment (exact integer core) is what
                    # shards chips x banks.
                    batch_sh = logits_sh = _sh.replicated(self.mesh)
                else:
                    batch_sh = _sh.serve_cnn_batch_sharding(self.mesh, bucket)
                    logits_sh = _sh.serve_cnn_logits_sharding(self.mesh,
                                                              bucket)
                p_sh = self._param_sh[(model, precision)]
                if params is not None:
                    # Mirror the committed shardings onto the dispatched
                    # tree's structure (identical leaves, tuned treedef).
                    p_sh = _sh.serve_cnn_param_shardings(
                        params, self.mesh, quantized=cfg is not None)
                in_sh = (p_sh, batch_sh)
                if faulty:
                    in_sh = in_sh + (_sh.replicated(self.mesh),)
                kw = dict(in_shardings=in_sh, out_shardings=logits_sh)
            if faulty:
                impl = partial(self._fwd_impl_faulty, module.apply, cfg,
                               self.faults)
            else:
                impl = partial(self._fwd_impl, module.apply, cfg)
            fn = jax.jit(impl, donate_argnums=(1,), **kw)
            self._fwd[key] = fn
        return fn

    @staticmethod
    def _fwd_impl(apply_fn, cfg, params, batch):
        return apply_fn(params, batch, cfg=cfg)

    @staticmethod
    def _fwd_impl_faulty(apply_fn, cfg, faults, params, batch, key):
        """Quantized forward with transient read disturb armed: every
        bit-serial weight read inside the trace draws its flip field from
        ``key`` (same scoped-context mechanism as ``ServeEngine._step_core``,
        so fused and im2col conv paths disturb identically)."""
        from repro.pim.faults import read_disturb_scope

        with read_disturb_scope(faults, key):
            return apply_fn(params, batch, cfg=cfg)

    def _act_gather_bound(self, params, bucket: int, h: int, w: int) -> int:
        """Largest legal all-gather in a quantized bucket forward: one
        activation map at the widest conv channel count (the paper's
        transfer phase redistributes activations between bank-split convs;
        nothing patch-matrix- or weight-sized may cross shards)."""
        from repro.core.packed import PackedConvWeight

        cmax = 1
        for leaf in jax.tree_util.tree_leaves(
                params, is_leaf=lambda x: isinstance(x, PackedConvWeight)):
            if isinstance(leaf, PackedConvWeight):
                _, _, c, o = leaf.kernel_shape
                cmax = max(cmax, int(c), int(o))
        return 4 * bucket * h * w * cmax

    def hot_paths(self, shapes=None):
        """Declare every dispatched bucket forward for the lint gate.

        ``shapes`` optionally supplies/overrides image shapes as
        ``{(model, precision, bucket): (h, w, c)}`` for callers that lint
        before any dispatch. Quantized mesh forwards budget their gathers
        at one widest-channel activation map; float forwards are fully
        replicated (zero gathers). The donated image batch is a
        free-the-buffer donation (it cannot alias the smaller logits), so
        no aliasing is demanded of it."""
        from functools import partial as _partial

        from repro import analysis as _an

        merged = dict(self._hot_shapes)
        merged.update(shapes or {})
        out = []
        for (model, precision, bucket), (h, w, c) in sorted(
                merged.items(), key=str):
            quantized = parse_precision(precision) is not None
            params = self._packed_params(model, precision)
            tuned = quantized and self.autotune != "off"
            if tuned:
                params = self._tuned_params(model, precision,
                                            (bucket, h, w, c))
            fn = self._fwd_fn(model, precision, bucket,
                              params if tuned else None)
            args = (params, jax.ShapeDtypeStruct((bucket, h, w, c),
                                                 jnp.float32))
            if quantized and self._transient:
                args = args + (jax.random.PRNGKey(0),)
            if self.mesh is None:
                gather_cap = None
            elif quantized:
                gather_cap = self._act_gather_bound(params, bucket, h, w)
            else:
                gather_cap = 0   # float path: fully replicated
            budget = _an.Budget(collectives=(("all-to-all", 0),),
                                max_gather_bytes=gather_cap,
                                m_hint=bucket,
                                pallas_ok=self.mesh is None)
            out.append(_an.HotPath(
                f"cnn.fwd[{model},{precision or 'float'},b={bucket}]",
                "cnn", budget, [_an.Program("fwd", fn, args)],
                context=_partial(self._activate, quantized)))
        return out

    def stats(self) -> dict:
        """Live telemetry snapshot: supervision health and the work
        counters. :meth:`Gateway.stats` carries them as ``vision_health`` /
        ``vision_counters``."""
        ctr = self.counters
        return {"health": dict(self.health),
                "counters": dict(ctr, by_bucket=dict(ctr["by_bucket"]),
                                 conv_lowering=dict(ctr["conv_lowering"]))}

    def close(self):
        """Engine teardown: deregister from the lint gate and reset the
        tuning cache (see ServeEngine.close)."""
        from repro import analysis as _analysis
        _analysis.unregister(self)
        if self.tune_cache is not None:
            self.tune_cache.reset()

    # -- public API ----------------------------------------------------------

    def submit(self, req: VisionRequest):
        if req.model not in self._models:
            raise ValueError(f"unknown model {req.model!r} "
                             f"(registered: {sorted(self._models)})")
        # Validate at admission, not dispatch, and canonicalize the float
        # spellings so "float"/"fp32"/None requests share one cohort.
        if parse_precision(req.precision) is None:
            req.precision = None
        self.queue.append(req)

    def cancel(self, rid: int) -> bool:
        """Remove a queued request (deadline expiry / caller cancel). Vision
        dispatches are atomic — a bucket in flight has no mid-generation
        state to release — so cancellation is queue surgery only."""
        for i, r in enumerate(self.queue):
            if r.rid == rid:
                del self.queue[i]
                return True
        return False

    @property
    def n_free_slots(self) -> int:
        """Admission headroom the gateway fills before the next dispatch:
        the engine buckets at most ``max_batch`` per step, so the gateway
        keeps at most one bucket's worth staged in the engine queue."""
        return max(0, self.max_batch - len(self.queue))

    def degrade_cohort(self, model: str, precision: str | None) -> bool:
        """Move a (model, precision) cohort to the float fallback path —
        the watchdog's budget-spent action, exposed as a lever for the
        gateway's degradation ladder. Returns True if newly degraded."""
        mkey = (model, precision)
        if precision is None or mkey in self._degraded:
            return False
        self._degraded.add(mkey)
        self.health["degraded"].append(mkey)
        return True

    def restore_cohort(self, model: str, precision: str | None) -> bool:
        """Reverse :meth:`degrade_cohort` once load/fault pressure drops
        (the health log keeps the transition history). Returns True if the
        cohort was degraded."""
        mkey = (model, precision)
        if mkey not in self._degraded:
            return False
        self._degraded.discard(mkey)
        return True

    def _group_key(self, req: VisionRequest):
        return (req.model, req.precision, np.asarray(req.image).shape)

    def step(self) -> list:
        """Dispatch one micro-batch bucket; returns its completions.

        The queue head picks the (model, precision, shape) cohort; the
        bucket is the largest power of two ≤ min(cohort, max_batch).
        """
        if not self.queue:
            return []
        with obs.span("vision.step"):
            t0 = obs.now()   # vision.prepare: cohort pick to launch
            key = self._group_key(self.queue[0])
            # Two O(Q) passes, no per-request deque.remove: size the cohort,
            # then split taken / kept preserving the queue order of the rest.
            m = 0
            for r in self.queue:
                if self._group_key(r) == key:
                    m += 1
                    if m == self.max_batch:
                        break
            bucket = 1 << (m.bit_length() - 1)
            group, kept = [], []
            for r in self.queue:
                if len(group) < bucket and self._group_key(r) == key:
                    group.append(r)
                else:
                    kept.append(r)
            self.queue = collections.deque(kept)
            model, precision, _ = key
            if (model, precision) in self._degraded:
                # Degraded cohort: serve on the float fallback path
                # (completions keep their original rids; only the numerics
                # path changes).
                precision = None
            if self.watchdog is None and self.fault_injector is None:
                return self._dispatch(group, model, precision, t0)
            return self._dispatch_supervised(group, model, precision)

    def _dispatch(self, group, model: str, precision: str | None,
                  t0: int | None = None) -> list:
        """Stack, copy and launch one bucket; ``t0`` (an ``obs.now()``)
        opens its ``vision.prepare`` span earlier than here."""
        if t0 is None:
            t0 = obs.now()
        bucket = len(group)
        batch = jnp.asarray(
            np.stack([np.asarray(r.image, np.float32) for r in group]))
        self._hot_shapes[(model, precision, bucket)] = tuple(batch.shape[1:])
        params = self._packed_params(model, precision)
        quantized = parse_precision(precision) is not None
        if quantized and self.autotune != "off":
            params = self._tuned_params(model, precision, batch.shape)
        with self._activate(quantized), warnings.catch_warnings():
            # The donated image batch cannot alias the (much smaller) logits
            # output on every backend; the donation is still declared so
            # backends that can reuse the buffer do. Silence the known-benign
            # "not usable" notice instead of spamming every bucket.
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            fn = self._fwd_fn(
                model, precision, bucket,
                params if quantized and self.autotune != "off" else None)
            if quantized and self._transient:
                self._fault_key, dkey = jax.random.split(self._fault_key)
                args = (params, batch, dkey)
            else:
                args = (params, batch)
            obs.span("vision.prepare", t0).close()
            with conv_lowering_log() as lowered:
                logits = fn(*args)
        ctr = self.counters
        for k in LOWERINGS:
            ctr["conv_lowering"][k] += lowered[k]
        ctr["dispatches"] += 1
        ctr["images"] += bucket
        ctr["by_bucket"][bucket] = ctr["by_bucket"].get(bucket, 0) + 1
        with obs.span("vision.fetch"):
            logits = np.asarray(logits)
        return [
            VisionCompletion(rid=r.rid, logits=logits[i],
                             top1=int(logits[i].argmax()), batch=bucket)
            for i, r in enumerate(group)
        ]

    def _dispatch_supervised(self, group, model: str,
                             precision: str | None) -> list:
        """Supervised bucket dispatch (DESIGN.md §7): retry under backoff on
        injected faults / device errors / non-finite logits / blown deadline,
        attempting a checksum repair before each retry; once the failure
        budget is spent, degrade the cohort to the float path and re-serve.

        The group is held locally (already split off the queue), so a retry
        is a pure re-dispatch — no queue surgery, no duplicated completions.
        """
        wd = self._wd
        while True:
            try:
                # Monotonic: an NTP wall-clock step must not blow the
                # dispatch deadline and burn the failure budget spuriously.
                t0 = time.monotonic()
                if self.fault_injector is not None:
                    self.fault_injector(self.health["dispatches"])
                out = self._dispatch(group, model, precision)
                dt = time.monotonic() - t0
                if wd.deadline_s is not None and dt > wd.deadline_s:
                    raise RuntimeError(
                        "vision dispatch exceeded deadline "
                        f"({dt:.3f}s > {wd.deadline_s:.3f}s)")
                if any(not np.isfinite(c.logits).all() for c in out):
                    raise RuntimeError("non-finite logits in vision dispatch")
                self.health["dispatches"] += 1
                self._policy.record_progress(self.health["dispatches"])
                return out
            except (RuntimeError, jax.errors.JaxRuntimeError) as e:
                self.health["rollbacks"] += 1
                try:
                    wait = self._policy.on_failure()
                except RuntimeError:
                    # Failure budget spent. Float path failing, or degrade
                    # disabled: surface the error (orchestrator restarts).
                    if precision is None or not wd.degrade:
                        raise
                    mkey = (model, precision)
                    self._degraded.add(mkey)
                    self.health["degraded"].append(mkey)
                    from repro.training.fault_tolerance import RestartPolicy

                    self._policy = RestartPolicy(wd.max_failures, wd.backoff_s)
                    print(f"[vision-watchdog] cohort {mkey} degraded to the "
                          f"float path after {wd.max_failures} failures",
                          flush=True)
                    return self._dispatch(group, model, None)
                fixed = self._repair(model, precision)
                if fixed:
                    self.health["repairs"] += 1
                    self.health["repaired_cols"] += fixed
                print(f"[vision-watchdog] dispatch failed ({e!r}); "
                      f"repaired {fixed} col(s), retrying in {wait:.3f}s",
                      flush=True)
                time.sleep(min(wait, 0.05))  # bounded for tests

    def run(self, max_steps: int = 10_000, strict: bool = False) -> list:
        """Drain the queue; returns all completions.

        If the step budget runs out with requests still queued, raise
        (``strict=True``) or emit a ``RuntimeWarning`` naming the stranded
        rids — silent drops are how serving bugs hide.
        """
        out = []
        for _ in range(max_steps):
            if not self.queue:
                return out
            out.extend(self.step())
        if self.queue:
            rids = [r.rid for r in self.queue]
            msg = (f"VisionEngine.run: {len(rids)} request(s) still queued "
                   f"after {max_steps} steps (rids {rids[:8]})")
            if strict:
                raise RuntimeError(msg)
            warnings.warn(msg, RuntimeWarning)
        return out
