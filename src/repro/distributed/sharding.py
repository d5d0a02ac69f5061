"""Sharding rules: parameter/optimizer/activation/state PartitionSpecs.

Axis semantics on the production mesh (see ``repro.launch.mesh``):

  pod    pure data parallelism across pods (gradient all-reduce via ICI/DCN)
  data   FSDP: batch sharding for activations AND parameter/optimizer-state
         sharding (ZeRO-3 style) — params gather on use, grads reduce-scatter
  model  tensor parallelism: attention heads / FFN hidden / expert dim

Rules are name-based (we own every init function, so names are total) with
a divisibility guard: any rule axis that does not divide the corresponding
dimension is dropped (replicated) rather than relying on GSPMD padding —
keeps the dry-run portable and the collective schedule predictable.

MoE experts: the expert dim shards on "model" when it divides the axis
(phi3.5: 16e on 16-way TP = pure expert parallelism); otherwise the expert
FFN hidden dim shards instead (grok: 8e -> TP inside every expert).
"""
from __future__ import annotations

import logging

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_log = logging.getLogger(__name__)

# ---------------------------------------------------------------------------
# Mesh context (set by launchers; model code stays mesh-agnostic)
# ---------------------------------------------------------------------------

_MESH: Mesh | None = None
_TIED = False
_SERVE_LAYOUT = False
_CNN_SERVE_LAYOUT = False


def set_mesh(mesh: Mesh | None):
    global _MESH
    _MESH = mesh


def set_cnn_serve_layout(on: bool):
    """Select the CNN serving layout (conv banks on "model", DESIGN.md §6)
    for the at-use constraints ``constrain_cnn_conv_input``/``_output``
    inside ``pim_conv2d``. ``VisionEngine`` scopes this (with the mesh)
    around its forward calls; training/dry-run traces never see it."""
    global _CNN_SERVE_LAYOUT
    _CNN_SERVE_LAYOUT = bool(on)


def get_cnn_serve_layout() -> bool:
    return _CNN_SERVE_LAYOUT


def set_serve_layout(on: bool):
    """Select the serving KV-cache layout (heads on "model", DESIGN.md §5)
    for at-use constraints like ``constrain_kv_update`` — the training
    layout shards the KV *sequence* instead. ``ServeEngine`` scopes this
    (with the mesh) around its program calls."""
    global _SERVE_LAYOUT
    _SERVE_LAYOUT = bool(on)


def get_serve_layout() -> bool:
    return _SERVE_LAYOUT


def set_tied_embeddings(tied: bool):
    """Tied-embedding models keep vocab on the TP axis (the lm_head matmul
    wants it); untied models shard vocab on FSDP only (cheap token gather)."""
    global _TIED
    _TIED = tied


def get_mesh() -> Mesh | None:
    return _MESH


def dp_axes(mesh: Mesh) -> tuple:
    """Axes that shard the batch (pure DP + FSDP axes)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_size(mesh: Mesh, *axes) -> int:
    out = 1
    for a in axes:
        if a in mesh.axis_names:
            out *= mesh.shape[a]
    return out


def constrain(x, spec: P):
    """with_sharding_constraint if a mesh is active, else identity."""
    if _MESH is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(_MESH, spec))


def constrain_batch(x, batch_dim: int = 0):
    """Pin an activation's batch dim to the DP axes (identity without mesh).

    GSPMD occasionally replicates the batch through scan carries when a
    badly-sharded producer (e.g. a vocab-sharded embedding gather) feeds the
    loop — a silent n_data x compute blowup that this constraint prevents.
    Skipped when the batch does not divide the DP axes (long_500k's B=1).
    """
    if _MESH is None:
        return x
    dp = dp_axes(_MESH)
    if not dp or x.shape[batch_dim] % axis_size(_MESH, *dp) != 0:
        return x
    spec = [None] * x.ndim
    spec[batch_dim] = dp
    return jax.lax.with_sharding_constraint(x, NamedSharding(_MESH, P(*spec)))


# ---------------------------------------------------------------------------
# Parameter rules
# ---------------------------------------------------------------------------

# name -> spec template over the *logical* dims of that parameter.
# "fsdp" -> data axis, "tp" -> model axis, None -> replicated dim.
_PARAM_RULES = {
    # embeddings / head. The untied embedding shards vocab on FSDP only:
    # a TP-sharded vocab makes the token gather reshard through a full
    # rematerialization (measured in the grok §Perf iterations). Tied
    # embeddings switch back to vocab-on-TP via ``set_tied_embeddings``.
    "embed": (None, "fsdp"),          # (vocab, d)
    "head": ("fsdp", "tp"),           # (d, vocab)
    # attention
    "wq": ("fsdp", "tp"),
    "wk": ("fsdp", "tp"),
    "wv": ("fsdp", "tp"),
    "wo": ("tp", "fsdp"),
    "bq": ("tp",), "bk": ("tp",), "bv": ("tp",),
    "q_norm": (None,), "k_norm": (None,), "gate": (),
    # mlp
    "w_in": ("fsdp", "tp"),
    "w_gate": ("fsdp", "tp"),
    "w_out": ("tp", "fsdp"),
    # moe (3D expert weights handled specially below)
    "router": ("fsdp", None),
    # rglru
    "w_x": ("fsdp", "tp"),
    "conv": (None, "tp"),
    "w_a": ("fsdp", "tp"),
    "w_i": ("fsdp", "tp"),
    "b_a": ("tp",), "b_i": ("tp",), "lam": ("tp",),
    # rwkv
    "w_r": ("fsdp", "tp"),
    "w_k": ("fsdp", "tp"),
    "w_v": ("fsdp", "tp"),
    "w_g": ("fsdp", "tp"),
    "w_o": ("tp", "fsdp"),
    "decay_a": ("fsdp", None),
    "decay_b": (None, "fsdp"),
    "w0": (None,), "mu": (None, None), "u": (None, None), "ln_scale": (None, None),
    # norms
    "scale": (None,), "bias": (None,),
}

_MOE_3D = {"w_in", "w_gate", "w_out"}


def _axis_for(tag, mesh: Mesh):
    if tag == "fsdp":
        # Multi-pod: params/optimizer shard across pods too (ZeRO across the
        # full fleet); the cross-pod all-gather overlaps with compute.
        if "pod" in mesh.axis_names and "data" in mesh.axis_names:
            return ("pod", "data")
        return "data" if "data" in mesh.axis_names else None
    if tag == "tp":
        return "model" if "model" in mesh.axis_names else None
    return None


# (label, axis, dim, size) tuples already reported — the guard drops axes
# during every tree_map over every leaf, so an unthrottled warning would
# print thousands of identical lines for one misconfigured mesh.
_warned_drops: set = set()


def reset_drop_warnings():
    """Clear the warn-once cache (tests; or after switching meshes)."""
    _warned_drops.clear()


def _warn_drop(label: str, ax, dim: int, sz: int):
    key = (label, str(ax), int(dim), int(sz))
    if key in _warned_drops:
        return
    _warned_drops.add(key)
    _log.warning(
        "sharding: %s dim %d not divisible by mesh axis %r (size %d) — "
        "dropping to replication; this leaf will not shard on this mesh",
        label or "<leaf>", dim, ax, sz)


def _guard(spec_axes: tuple, shape: tuple, mesh: Mesh, label: str = "") -> P:
    """Drop axes that don't divide the dim; pad spec to the leaf's rank.

    Each drop of a *real* axis (mesh size > 1) logs a one-time warning so a
    misconfigured mesh (nothing actually sharding) is visible instead of
    silently replicating everything."""
    spec = list(spec_axes) + [None] * (len(shape) - len(spec_axes))
    out = []
    for dim, ax in zip(shape, spec):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        sz = axis_size(mesh, *axes)
        if sz > 1 and dim % sz == 0:
            out.append(ax)
        else:
            if sz > 1:
                _warn_drop(label, ax, dim, sz)
            out.append(None)
    return P(*out)


def _param_spec(path, leaf, mesh: Mesh, n_experts: int | None) -> P:
    names = [k.key for k in path if hasattr(k, "key")]
    name = names[-1] if names else ""
    stacked = "scan" in names  # scan-stacked params carry a leading reps axis
    in_moe = "ffn" in names and leaf.ndim - (1 if stacked else 0) == 3

    if in_moe and name in _MOE_3D:
        # (E, d, f) or (E, f, d): expert-parallel when E divides the TP axis,
        # else TP inside each expert on the f dim.
        tp = axis_size(mesh, "model")
        e = leaf.shape[1 if stacked else 0]
        if tp > 1 and e % tp == 0:
            spec = ("tp", "fsdp", None) if name != "w_out" else ("tp", None, "fsdp")
        else:
            spec = (None, "fsdp", "tp") if name != "w_out" else (None, "tp", "fsdp")
    elif "channel_mix" in names and name == "w_v":
        spec = ("tp", "fsdp")          # rwkv channel-mix down-proj is (f, d)
    elif name == "embed" and _TIED:
        spec = ("tp", "fsdp")
    elif name in _PARAM_RULES:
        spec = _PARAM_RULES[name]
    else:
        spec = tuple(None for _ in leaf.shape)

    spec = tuple(_axis_for(t, mesh) for t in spec)
    if stacked:
        spec = (None,) + spec
        shape = leaf.shape
    else:
        shape = leaf.shape
    return _guard(spec, shape, mesh, label=f"param:{name}")


def param_shardings(params_tree, mesh: Mesh, n_experts: int | None = None):
    """Map a param pytree (arrays or ShapeDtypeStructs) -> NamedShardings."""
    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, _param_spec(p, l, mesh, n_experts)),
        params_tree)


# ---------------------------------------------------------------------------
# Batch / state rules
# ---------------------------------------------------------------------------

def batch_spec(mesh: Mesh, global_batch: int, rank: int = 2) -> P:
    """Tokens/labels (B, S, ...) — batch over DP axes when divisible."""
    dp = dp_axes(mesh)
    if dp and global_batch % axis_size(mesh, *dp) == 0:
        return P(dp, *(None,) * (rank - 1))
    return P(*(None,) * rank)


def batch_shardings(batch_tree, mesh: Mesh, global_batch: int):
    return jax.tree.map(
        lambda l: NamedSharding(mesh, batch_spec(mesh, global_batch, l.ndim)),
        batch_tree)


def _state_spec(path, leaf, mesh: Mesh, global_batch: int) -> P:
    names = [k.key for k in path if hasattr(k, "key")]
    name = names[-1] if names else ""
    dp = dp_axes(mesh)
    b_ok = dp and global_batch % axis_size(mesh, *dp) == 0
    # Layer states may be scan-stacked (leading reps axis) — detect by rank.
    if name in ("k", "v"):
        # KV cache (B, S, H, hd): shard the SEQUENCE on the TP axis
        # (flash-decoding layout) — every model shard owns a contiguous
        # KV chunk, attention softmax combines via tiny partial-stat
        # all-reduces, and the per-token scatter update lands on one
        # shard. Sharding head_dim instead forced whole-cache gathers
        # (measured: 28 GB/step on llama decode_32k, §Perf).
        seq_dim = len(leaf.shape) - 3
        seq_ok = leaf.shape[seq_dim] % axis_size(mesh, "model") == 0
        spec = (dp if b_ok else None, "model" if seq_ok else None, None, None)
    elif name in ("k_scale", "v_scale"):   # int8 KV scales (B, S, H)
        seq_ok = leaf.shape[-2] % axis_size(mesh, "model") == 0
        spec = (dp if b_ok else None, "model" if seq_ok else None, None)
    elif name == "wkv":          # (B, H, D, D)
        spec = (dp if b_ok else None, None, None, None)
    elif name in ("tm_shift", "cm_shift", "h"):   # (B, d)
        spec = (dp if b_ok else None, "model")
    elif name == "conv":         # (B, K-1, W)
        spec = (dp if b_ok else None, None, "model")
    elif name == "length":
        return P()
    else:
        spec = tuple(None for _ in leaf.shape)
    if len(spec) < leaf.ndim:    # stacked: prepend None for the reps axis
        spec = (None,) * (leaf.ndim - len(spec)) + tuple(spec)
    return _guard(tuple(spec), leaf.shape, mesh, label=f"state:{name}")


def state_shardings(state_tree, mesh: Mesh, global_batch: int):
    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, _state_spec(p, l, mesh, global_batch)),
        state_tree)


def constrain_kv_update(k_new):
    """Pin a multi-token KV update (B, S_new, H, hd) to the cache's layout
    BEFORE the scatter — otherwise GSPMD reshards the whole prefill KV
    through the scatter (measured: 2-5x collective-term regressions on
    prefill cells).

    Training/dry-run layout: batch on DP, sequence on TP (flash-decoding).
    Serving layout (``set_serve_layout``): *heads* on TP, matching
    ``serve_state_shardings`` — pinning the training layout here instead
    would force a reshard against the heads-split serving cache on every
    admission chunk."""
    if _MESH is None or k_new.ndim != 4 or k_new.shape[1] == 1:
        return k_new
    dp = dp_axes(_MESH)
    b_ok = dp and k_new.shape[0] % axis_size(_MESH, *dp) == 0
    tp = axis_size(_MESH, "model")
    if _SERVE_LAYOUT:
        heads_ok = tp > 1 and k_new.shape[2] % tp == 0
        spec = P(dp if b_ok else None, None,
                 "model" if heads_ok else None, None)
    else:
        seq_ok = tp > 1 and k_new.shape[1] % tp == 0
        spec = P(dp if b_ok else None, "model" if seq_ok else None,
                 None, None)
    return jax.lax.with_sharding_constraint(k_new, NamedSharding(_MESH, spec))


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Serving rules (mesh-sharded ServeEngine — DESIGN.md §5)
# ---------------------------------------------------------------------------
# The serving mesh maps the paper's chip→bank→subarray hierarchy:
#
#   chips     -> "data"  axis: continuous-batching slots (the decode-state
#                grid's batch dim and the per-slot ctrl block)
#   banks     -> "model" axis: N-dim column split of every projection weight
#                — for prepacked weights that means the PackedWeight planes
#                (bits, N, K/32), codes and correction col_sums split on N
#   subarrays -> VMEM tiles inside the bit-serial kernels (BlockSpec)
#
# Parameters shard on "model" ONLY. Serving never takes the FSDP rules:
# ZeRO-style parameter sharding would all-gather every weight every decode
# step, which is exactly the data movement the paper's mapping avoids.
# KV-cache heads and recurrent hidden dims ride "model" so the TP-sharded
# projections write decode state without any resharding in the hot loop.

def _serve_param_spec(path, leaf, mesh: Mesh) -> P:
    dicts = [k.key for k in path if hasattr(k, "key")]
    attrs = [k.name for k in path if hasattr(k, "name")]
    name = dicts[-1] if dicts else ""
    if not hasattr(leaf, "ndim"):
        return P()
    # embed stays replicated: its primary op is the token gather, and the
    # tied-head GEMM on a TP-sharded vocab would gather logits anyway.
    rule = None if name == "embed" else _PARAM_RULES.get(name)
    if rule is None:
        return P(*(None,) * leaf.ndim)
    if "ffn" in dicts and name in _MOE_3D:
        # Expert-stacked MoE bank (float (E, d, f) or an expert-vmapped
        # PackedWeight, possibly under a scan-reps axis). Experts = the
        # paper's chips: when E divides the "model" axis, whole experts
        # deal out across it — every field, including the per-expert wq
        # leaves — so each bank's GEMMs are collective-free and only the
        # token dispatch/combine communicates (DESIGN.md §11). When E
        # doesn't divide (grok's 8e on a wider axis), fall through to the
        # padded TP mapping: d_ff splits inside every expert.
        stacked = 1 if (dicts and dicts[0] == "scan") else 0
        field = attrs[0] if attrs else None
        rank = {"codes": 2, "planes": 3, "col_sums": 1, "wq": 0,
                None: 2}.get(field)
        if rank is not None and leaf.ndim == rank + stacked + 1:
            e = leaf.shape[stacked]
            ms = axis_size(mesh, "model")
            if ms > 1 and e % ms == 0:
                return _guard((None,) * stacked + ("model",) + (None,) * rank,
                              leaf.shape, mesh, label=f"serve-param:{name}:ep")
    base = tuple("model" if t == "tp" else None for t in rule)
    if attrs:
        # Inside a PackedWeight: map the logical (K, N) rule onto the packed
        # representation. attrs[0] == "wq" means QuantParams scale/qmin
        # (per-tensor scalars) and conv extras stay replicated.
        k_ax, n_ax = (base + (None, None))[:2]
        field = attrs[0]
        if field == "codes":
            spec = (k_ax, n_ax)
        elif field == "planes":
            spec = (None, n_ax, k_ax)          # (bits, N, K//32)
        elif field == "col_sums":
            spec = (n_ax,)
        else:
            return P(*(None,) * leaf.ndim)
    else:
        spec = base
    spec = tuple(spec)[:leaf.ndim]
    if leaf.ndim > len(spec):                  # scan-stacked leading reps axis
        spec = (None,) * (leaf.ndim - len(spec)) + spec
    return _guard(spec, leaf.shape, mesh, label=f"serve-param:{name}")


def serve_param_shardings(params_tree, mesh: Mesh):
    """Serving shardings for a (possibly prepacked) param tree: TP on
    "model" only, PackedWeight planes/col_sums split on their N dim."""
    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, _serve_param_spec(p, l, mesh)),
        params_tree)


def _serve_state_spec(path, leaf, mesh: Mesh) -> P:
    names = [k.key for k in path if hasattr(k, "key")]
    name = names[-1] if names else ""
    stacked = bool(names) and names[0] == "scan"
    if name in ("k", "v"):                     # (B, S, H, hd): heads on TP —
        spec = ("data", None, "model", None)   # aligned with the wk/wv column
    elif name in ("k_scale", "v_scale"):       # split, so the per-token KV
        spec = ("data", None, "model")         # write never reshards
    elif name == "wkv":                        # (B, H, D, D)
        spec = ("data", "model", None, None)
    elif name in ("tm_shift", "cm_shift", "h"):
        spec = ("data", "model")
    elif name == "conv":                       # (B, K-1, W)
        spec = ("data", None, "model")
    elif name == "length":
        spec = ("data",)
    else:
        spec = (None,) * leaf.ndim
    if stacked:
        spec = (None,) + tuple(spec)
    return _guard(tuple(spec), leaf.shape, mesh, label=f"serve-state:{name}")


def serve_state_shardings(state_tree, mesh: Mesh):
    """Decode-state grid shardings: batch slots (the paper's chips) on
    "data", KV heads / recurrent hidden dims on "model"."""
    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, _serve_state_spec(p, l, mesh)),
        state_tree)


def serve_ctrl_shardings(ctrl_tree, mesh: Mesh):
    """Per-slot ctrl block: (max_batch,) vectors on "data"; the engine PRNG
    key (and anything non-slot-shaped) replicated."""
    def spec(path, leaf):
        name = path[-1].key if path and hasattr(path[-1], "key") else ""
        if name == "key" or leaf.ndim != 1:
            return P(*(None,) * leaf.ndim)
        return _guard(("data",), leaf.shape, mesh, label=f"serve-ctrl:{name}")
    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, spec(p, l)), ctrl_tree)


# ---------------------------------------------------------------------------
# CNN serving rules (mesh-sharded VisionEngine — DESIGN.md §6)
# ---------------------------------------------------------------------------
# Same chip→bank mapping as the LM rules, applied to the conv stack:
#
#   chips     -> "data"  axis: the micro-batch bucket (image batch dim)
#   banks     -> "model" axis: output channels O of every conv / N of every
#                FC — for prepacked weights the PackedConvWeight.mat planes,
#                codes, col_sums AND the fused per-kernel-row planes all
#                split on their O dim, so the fused kernel's weight slab and
#                the materialized path's column split agree
#
# Per-channel BN/bias vectors ride "model" with the conv output, so the
# affine+ReLU epilogue is shard-local. The next conv contracts over the
# O-sharded channels: the partial-sum all-reduce is the inherent TP
# collective (the paper's cross-bank accumulation) — nothing weight- or
# activation-map-sized ever gathers in steady state.

def constrain_cnn_conv_input(x):
    """Pin a conv input (B, H, W, C) to batch-on-"data", channels
    replicated, under the CNN serving layout (identity otherwise).

    Between two bank-split convs the activation must redistribute (the
    previous layer's O shards are the next layer's contraction channels) —
    the paper pays the same movement in its *transfer* phase. Constraining
    the INPUT map forces GSPMD to move the (B, H, W, C) activation, never
    the KH*KW-times-larger patch matrix it otherwise gathers after im2col
    (the reshape cannot carry a minor-dim channel sharding, so the whole
    patch matrix replicates in one gather)."""
    if _MESH is None or not _CNN_SERVE_LAYOUT or x.ndim != 4:
        return x
    dp = dp_axes(_MESH)
    b_ok = dp and x.shape[0] % axis_size(_MESH, *dp) == 0
    spec = P(dp if b_ok else None, None, None, None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(_MESH, spec))


def constrain_cnn_conv_output(y):
    """Pin a conv output (B, OH, OW, O) to the bank split — O on "model" —
    under the CNN serving layout (identity otherwise). With the input map
    replicated per shard, each bank then computes exactly its own output
    channels from the resident weight planes: the matmul itself needs no
    collective, and the per-channel BN/ReLU epilogue stays shard-local."""
    if _MESH is None or not _CNN_SERVE_LAYOUT or y.ndim != 4:
        return y
    dp = dp_axes(_MESH)
    b_ok = dp and y.shape[0] % axis_size(_MESH, *dp) == 0
    tp = axis_size(_MESH, "model")
    o_ok = tp > 1 and y.shape[-1] % tp == 0
    spec = P(dp if b_ok else None, None, None, "model" if o_ok else None)
    return jax.lax.with_sharding_constraint(y, NamedSharding(_MESH, spec))


def _serve_cnn_param_spec(path, leaf, mesh: Mesh) -> P:
    attrs = [k.name for k in path if hasattr(k, "name")]
    dicts = [k.key for k in path if hasattr(k, "key")]
    name = dicts[-1] if dicts else ""
    if not hasattr(leaf, "ndim"):
        return P()
    if attrs:
        # Inside a PackedWeight / PackedConvWeight: split every
        # representation of the weight on its output-channel dim.
        field = attrs[-1]
        if field == "codes":            # (K, O)
            spec = (None, "model")
        elif field == "planes":         # (bits, O, KW)
            spec = (None, "model", None)
        elif field == "col_sums":       # (O,)
            spec = ("model",)
        elif field == "fused_planes":   # (KH, bits, KW, CW, O)
            spec = (None, None, None, None, "model")
        else:                           # QuantParams scale/qmin
            return P(*(None,) * leaf.ndim)
    elif name in ("b", "gamma", "beta", "mean", "var") and leaf.ndim == 1:
        spec = ("model",)               # per-output-channel epilogue vectors
    else:
        return P(*(None,) * leaf.ndim)
    return _guard(tuple(spec), leaf.shape, mesh, label=f"serve-cnn:{name}")


def serve_cnn_param_shardings(params_tree, mesh: Mesh, quantized: bool = True):
    """CNN serving shardings (DESIGN.md §6).

    ``quantized=True`` (a prepacked tree): every representation of every
    conv/fc weight — ``PackedConvWeight.mat`` codes/planes/col_sums, the
    ``fused_planes``, FC ``PackedWeight`` leaves — splits on its
    output-channel dim (the paper's banks on "model"), along with the
    per-channel BN/bias epilogue vectors.

    ``quantized=False`` (float masters): everything replicates and serving
    is data-parallel only. The bank split is a property of the *bit-serial*
    deployment: its integer partials stay exact under any partitioning,
    while splitting a float contraction would reorder partial sums and
    break the engine's bit-identity contract with ``model.apply``."""
    if not quantized:
        return jax.tree.map(lambda l: NamedSharding(mesh, P()), params_tree)
    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, _serve_cnn_param_spec(p, l, mesh)),
        params_tree)


def serve_cnn_batch_sharding(mesh: Mesh, batch: int, rank: int = 4):
    """Image micro-batch (B, H, W, C): batch on "data" (the paper's chips)
    when the bucket divides the axis, else replicated."""
    spec = [None] * rank
    if "data" in mesh.axis_names and axis_size(mesh, "data") > 1 \
            and batch % axis_size(mesh, "data") == 0:
        spec[0] = "data"
    return NamedSharding(mesh, P(*spec))


def serve_cnn_logits_sharding(mesh: Mesh, batch: int):
    """Engine forward output (B, classes): batch stays on "data"; the class
    dim is host-bound (top-1 / completion assembly) and small, so it is
    never worth sharding."""
    return serve_cnn_batch_sharding(mesh, batch, rank=2)


def serve_stream_sharding(mesh: Mesh, n_slots: int, rank: int = 2,
                          slot_dim: int = 1):
    """Sharding for the (steps, slots) token/done streams a decode dispatch
    emits: slots on "data" so the hot loop ends with no gather — the host
    assembles the (tiny) stream after the dispatch returns."""
    spec = [None] * rank
    if "data" in mesh.axis_names and axis_size(mesh, "data") > 1 \
            and n_slots % axis_size(mesh, "data") == 0:
        spec[slot_dim] = "data"
    return NamedSharding(mesh, P(*spec))
