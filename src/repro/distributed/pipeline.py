"""Pipeline parallelism: GPipe-style microbatch schedule over a "stage"
mesh axis, built on shard_map + collective_permute.

Scope: homogeneous stages (each stage applies the same ``stage_fn`` with
its own slice of stacked parameters) — which matches this framework's
scan-over-repeating-units models exactly: a stage is a contiguous run of
unit repetitions, so any arch whose depth factors into n_stages pipelines
without new code. The schedule is the classic (M microbatches, S stages,
M + S − 1 ticks) fill-drain pipeline; bubble fraction (S−1)/(M+S−1).

At production scale the stage axis maps onto the `pod` axis (cross-pod
point-to-point permutes ride DCN, the cheapest pattern for that fabric);
on this container it is exercised on a 4-device CPU mesh
(tests/test_pipeline.py) and the schedule's output is verified against the
sequential application of all stages.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _shard_map(f, mesh, in_specs, out_specs):
    # The replication checker is off: the ppermute/psum pattern below is
    # device-varying by design.
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def pipeline_forward(stage_params, x_microbatches, stage_fn, mesh,
                     stage_axis: str = "stage"):
    """Run the fill-drain pipeline.

    stage_params: pytree, leaves (S, ...) — stage-major stacked params.
    x_microbatches: (M, mb, ...) — microbatched input.
    stage_fn(params_slice, x) -> y with y.shape == x.shape (residual stages).
    Returns (M, mb, ...) outputs, equal to applying all S stages in order.
    """
    n_stages = mesh.shape[stage_axis]
    n_micro = x_microbatches.shape[0]
    ticks = n_micro + n_stages - 1
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def per_stage(params_local, xs):
        # params_local leaves: (1, ...) local stage slice; xs: (M, mb, ...)
        params_local = jax.tree.map(lambda l: l[0], params_local)
        stage_id = jax.lax.axis_index(stage_axis)
        mb_shape = xs.shape[1:]
        # carries become device-varying inside the loop (ppermute/axis_index)
        # — mark the initial values as varying for shard_map's vma typing.
        outputs = jax.lax.pvary(jnp.zeros_like(xs), (stage_axis,))
        carry_in = jax.lax.pvary(jnp.zeros(mb_shape, xs.dtype), (stage_axis,))

        def tick(t, state):
            outputs, carry_in = state
            # Stage 0 ingests microbatch t (while available); others take
            # the permuted output of their predecessor.
            feed = jnp.where(t < n_micro,
                             xs[jnp.minimum(t, n_micro - 1)],
                             jnp.zeros(mb_shape, xs.dtype))
            x_in = jnp.where(stage_id == 0, feed, carry_in)
            y = stage_fn(params_local, x_in)
            # Last stage emits microbatch t-(S-1) once the pipe is full.
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            emit = (stage_id == n_stages - 1) & (t >= n_stages - 1)
            outputs = jnp.where(
                emit,
                jax.lax.dynamic_update_index_in_dim(outputs, y, out_idx, 0),
                outputs)
            carry_in = jax.lax.ppermute(y, stage_axis, perm)
            return outputs, carry_in

        outputs, _ = jax.lax.fori_loop(0, ticks, tick, (outputs, carry_in))
        # Only the last stage holds real outputs; psum-mask to share them.
        outputs = jnp.where(stage_id == n_stages - 1, outputs, 0.0)
        return jax.lax.psum(outputs, stage_axis)

    return _shard_map(
        per_stage, mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
    )(stage_params, x_microbatches)


def split_stages(stacked_params, n_stages: int):
    """(R, ...) scan-stacked params -> (S, R/S, ...) stage-major view."""
    def re(l):
        r = l.shape[0]
        if r % n_stages:
            raise ValueError(
                f"cannot pipeline: {r} scanned repetition(s) do not factor "
                f"into {n_stages} equal stages (reps % n_stages must be 0)")
        return l.reshape(n_stages, r // n_stages, *l.shape[1:])
    return jax.tree.map(re, stacked_params)


def pipeline_decode_step(params, cfg, tokens, state, *, mesh,
                         n_stages: int, n_microbatch: int | None = None,
                         stage_axis: str = "stage", image_embeds=None,
                         return_stats: bool = False):
    """One decode step with the scanned repetitions pipelined over stages.

    Drop-in for ``models.lm.decode_step`` (same signature prefix, same
    return contract) on a 1-D ``(stage,)`` mesh: the scan-stacked unit
    repetitions split into ``n_stages`` contiguous stages (``split_stages``
    semantics), the batch splits into ``n_microbatch`` microbatches
    (default ``n_stages``), and the classic fill-drain schedule streams
    microbatches through the stages with one ``collective_permute`` hop per
    tick. Each stage holds only its own layers' parameters and KV/recurrent
    state slice — the model-parallel memory story — and updates the decode
    state in place per microbatch column, masked on pipeline-bubble ticks
    so invalid ticks write nothing. Embedding, remainder layers, final norm
    and the LM head run replicated outside the pipe (they are depth-1).

    Bit-parity: for per-example-independent models (dense float) the
    result is bitwise equal to sequential ``decode_step`` — microbatching
    only slices the batch axis. MoE capacity and PIM activation calibration
    are batch-shape-dependent by definition (per-group capacity, per-tensor
    calibration), so those paths are numerically equivalent per-microbatch
    semantics, not bitwise reproductions of the full-batch step.
    """
    from repro.models.lm.model import (_zero_aux, apply_block, apply_norm,
                                       embed_inputs, layer_plan, lm_head)

    unit, reps, rest = layer_plan(cfg)
    if reps % n_stages:
        raise ValueError(
            f"cannot pipeline: {reps} scanned repetition(s) do not factor "
            f"into {n_stages} equal stages (reps % n_stages must be 0)")
    n_micro = n_microbatch or n_stages
    b = tokens.shape[0]
    if b % n_micro:
        raise ValueError(
            f"cannot pipeline: batch {b} does not split into "
            f"{n_micro} equal microbatches")
    mb = b // n_micro
    ticks = n_micro + n_stages - 1
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    x = embed_inputs(params, cfg, tokens)                    # (B, 1, d)
    idx = jnp.broadcast_to(state["length"], (b,)).astype(jnp.int32)
    q_pos = idx[:, None]
    d = x.shape[-1]

    # P(stage) in_specs split the leading (R,) reps axis into S contiguous
    # chunks of R/S — exactly ``split_stages``'s stage-major factoring, with
    # no host-side reshape of the (donated) decode state.
    xm = x.reshape(n_micro, mb, 1, d)
    qm = q_pos.reshape(n_micro, mb, 1)
    im = idx.reshape(n_micro, mb)

    def per_stage(sp_l, ss_l, xm, qm, im):
        # sp_l leaves (R/S, ...), ss_l leaves (R/S, B, ...): this stage's
        # contiguous run of unit repetitions and their decode state.
        stage_id = jax.lax.axis_index(stage_axis)
        outputs = jax.lax.pvary(jnp.zeros_like(xm), (stage_axis,))
        carry = jax.lax.pvary(jnp.zeros((mb, 1, d), x.dtype), (stage_axis,))
        aux0 = jax.tree.map(lambda v: jax.lax.pvary(v, (stage_axis,)), _zero_aux())

        def unit_scan(x_in, ss_slice, qp, ci):
            def unit_fn(xc, per_rep):
                p_list, s_list = per_rep
                new_states, a = [], _zero_aux()
                for j, kind in enumerate(unit):
                    xc, ns, a1 = apply_block(kind, p_list[j], cfg, xc, qp,
                                             s_list[j], ci, image_embeds)
                    new_states.append(ns)
                    a = jax.tree.map(jnp.add, a, a1)
                return xc, (new_states, a)
            y, (new_s, a_reps) = jax.lax.scan(unit_fn, x_in, (sp_l, ss_slice))
            return y, new_s, jax.tree.map(jnp.sum, a_reps)

        def tick(t, loop):
            outputs, carry, ss_l, aux = loop
            m = t - stage_id
            valid = (m >= 0) & (m < n_micro)
            mc = jnp.clip(m, 0, n_micro - 1)
            x_in = jnp.where(stage_id == 0,
                             jax.lax.dynamic_index_in_dim(xm, mc, 0,
                                                          keepdims=False),
                             carry)
            qp = jax.lax.dynamic_index_in_dim(qm, mc, 0, keepdims=False)
            ci = jax.lax.dynamic_index_in_dim(im, mc, 0, keepdims=False)
            # This stage's state columns for microbatch mc (batch axis 1 on
            # scan-stacked decode-state leaves, by cache construction).
            ss_slice = jax.tree.map(
                lambda l: jax.lax.dynamic_slice_in_dim(l, mc * mb, mb, 1),
                ss_l)
            y, new_s, a = unit_scan(x_in, ss_slice, qp, ci)
            # Bubble ticks (fill/drain) must not touch state or outputs.
            ss_l = jax.tree.map(
                lambda big, sm: jnp.where(
                    valid,
                    jax.lax.dynamic_update_slice_in_dim(
                        big, sm.astype(big.dtype), mc * mb, 1),
                    big),
                ss_l, new_s)
            emit = valid & (stage_id == n_stages - 1)
            outputs = jnp.where(
                emit,
                jax.lax.dynamic_update_index_in_dim(outputs, y, mc, 0),
                outputs)
            aux = jax.tree.map(
                lambda acc, v: acc + jnp.where(valid, v, 0.0), aux, a)
            carry = jax.lax.ppermute(y, stage_axis, perm)
            return outputs, carry, ss_l, aux

        outputs, _, ss_l, aux = jax.lax.fori_loop(
            0, ticks, tick, (outputs, carry, ss_l, aux0))
        # Only the last stage holds real outputs; every stage holds the aux
        # of its own layers — psum shares/accumulates them across the pipe.
        outputs = jnp.where(stage_id == n_stages - 1, outputs, 0.0)
        outputs = jax.lax.psum(outputs, stage_axis)
        aux = jax.tree.map(lambda v: jax.lax.psum(v, stage_axis), aux)
        return outputs, ss_l, aux

    outputs, new_scan, aux = _shard_map(
        per_stage, mesh,
        in_specs=(P(stage_axis), P(stage_axis), P(), P(), P()),
        out_specs=(P(), P(stage_axis), P()),
    )(params["scan"], state["scan"], xm, qm, im)

    x = outputs.reshape(b, 1, d)

    new_rest = []
    for i, kind in enumerate(rest):
        x, ns, a = apply_block(kind, params["rest"][i], cfg, x, q_pos,
                               state["rest"][i], idx, image_embeds)
        new_rest.append(ns)
        aux = jax.tree.map(jnp.add, aux, a)

    x = apply_norm(cfg.norm, params["final_norm"], x, cfg.norm_eps)
    logits = lm_head(params, cfg, x)
    new_state = dict(state, scan=new_scan, rest=new_rest,
                     length=state["length"] + 1)
    if return_stats:
        stats = {"moe_drop_frac": aux["drop"]
                 / jnp.maximum(aux["layers"], 1.0)}
        return logits, new_state, stats
    return logits, new_state


def make_unit_stage_fn(cfg, unit, q_pos):
    """Stage body for scanned-unit LM models: applies R/S unit reps."""
    from repro.models.lm.model import apply_block

    def stage_fn(params_slice, x):
        def unit_fn(x, p_list):
            for j, kind in enumerate(unit):
                x, _, _ = apply_block(kind, p_list[j], cfg, x, q_pos)
            return x, None
        x, _ = jax.lax.scan(unit_fn, x, params_slice)
        return x

    return stage_fn
