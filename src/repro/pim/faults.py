"""NAND-SPIN device-fault model + ECC-style mitigation (DESIGN.md §7).

The paper's cells are STT-MRAM devices: programming is stochastic (a write
burst leaves the MTJ in the wrong state with probability ``write_ber``),
reads disturb the stored state (``read_disturb_ber`` per sensed bit),
retention flips accumulate, and manufacturing leaves stuck-at cells and
occasionally whole dead subarrays. A shipped accelerator wraps the
bank/subarray hierarchy in redundancy; this module models both halves:

**Fault taxonomy → where it strikes.** Every weight bit lives in exactly one
bit-plane subarray (``PackedWeight.planes``), so all faults are expressed on
the per-bit-plane decomposition of the integer codes and rendered into
whatever representation a backend consumes (codes for int-direct/mxu-plane,
packed uint32 planes for popcount/pallas, the fused conv layout for the
implicit-im2col kernel) — the corrupted codes and corrupted planes always
describe the *same* device state, so cross-backend bit-parity survives
injection.

  * persistent (strike once, at subarray programming — :func:`inject_packed`):
    write errors, retention flips, stuck-at-0/1 cells, whole-subarray
    failures (a dead subarray reads all-zero for its column group).
  * transient (strike per read — :func:`read_disturb_scope` +
    :func:`disturb_packed` inside the bit-serial matmul path): read-disturb
    flips, freshly drawn from the PRNG key threaded through the hot loop.

**Mitigation → the paper's hierarchy.**

  * *Bit-plane-weighted protection*: Eq. 1 weighs plane ``m`` by ``2^m``, so
    an MSB flip costs exponentially more than an LSB flip. The top
    ``protect_msb`` weight planes are stored ``vote_copies`` times (each
    copy its own subarray) and majority-voted at the sense amps; the cheap
    planes stay bare. Modeled exactly: each copy is corrupted independently
    and the surviving plane is the bitwise majority.
  * *Column-sum checksum*: the prepack already stores ``col_sums`` (the
    affine correction's Sw) in the digital periphery; recomputing the sum
    from the stored planes and comparing flags any column whose codes
    changed — :func:`verify_columns`. (Sum-preserving flip pairs within one
    column escape; probability falls off quadratically in BER.)
  * *Spare remap + re-program*: :func:`repair_packed` remaps up to
    ``spare_cols`` flagged columns onto spare subarrays and re-programs them
    from the golden weights — in simulation, those columns are restored
    bit-exactly from the uncorrupted prepack.

Everything is pure ``jnp`` over ``jax.random`` (threefry), so injection is
value-deterministic: the same :class:`FaultConfig` + key produces
bit-identical corruption on one device or sharded across the
("data", "model") serving mesh, under jit, vmap (scan-stacked LM weights)
and shard_map alike. With faults disabled nothing here is ever traced —
the hot loops compile to the exact same HLO (asserted in
tests/test_faults.py).
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp

from repro.core import bitslice
from repro.core.packed import (PackedConvWeight, PackedWeight,
                               fused_conv_planes, repack_codes,
                               repack_conv_codes)

# Key-derivation tags: one disjoint fold_in stream per fault mechanism.
_TAG_WRITE, _TAG_RETAIN, _TAG_DISTURB = 0x57, 0x52, 0x44
_TAG_STUCK0, _TAG_STUCK1, _TAG_SUBFAIL = 0x50, 0x51, 0x5F


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """Device fault rates + mitigation knobs for one deployment.

    Rates are per-bit probabilities; ``subarray_fail_rate`` is per
    (bit-plane, column-group) — a failed subarray zeroes its whole extent.
    ``protect_msb`` counts weight planes from the MSB down that are stored
    ``vote_copies``-redundant and majority-voted. ``checksum`` arms the
    col_sums integrity probe; ``spare_cols`` bounds how many flagged
    columns :func:`repair_packed` may remap per weight matrix (the spare
    subarray budget).
    """

    write_ber: float = 0.0
    read_disturb_ber: float = 0.0
    retention_ber: float = 0.0
    stuck0_rate: float = 0.0
    stuck1_rate: float = 0.0
    subarray_fail_rate: float = 0.0
    subarray_cols: int = 128          # columns per subarray (Geometry.cols)
    seed: int = 0
    # -- mitigation -----------------------------------------------------
    protect_msb: int = 0
    vote_copies: int = 3
    checksum: bool = False
    spare_cols: int = 0

    @property
    def persistent(self) -> bool:
        """Any programming-time fault mechanism enabled?"""
        return (self.write_ber > 0 or self.retention_ber > 0
                or self.stuck0_rate > 0 or self.stuck1_rate > 0
                or self.subarray_fail_rate > 0)

    @property
    def transient(self) -> bool:
        """Per-read disturb enabled (needs a key threaded through the loop)?"""
        return self.read_disturb_ber > 0

    def key(self) -> jax.Array:
        return jax.random.PRNGKey(self.seed)


# ---------------------------------------------------------------------------
# Corruption core: everything on the (bits, K, N) plane decomposition
# ---------------------------------------------------------------------------

def _majority(vals: list) -> jax.Array:
    """Bitwise majority of an odd number of equal-shape int planes."""
    n = len(vals)
    if n == 1:
        return vals[0]
    acc = sum(v.astype(jnp.int32) for v in vals)
    return (acc > n // 2).astype(vals[0].dtype)


def _flip(key, rate: float, shape) -> jax.Array:
    if rate <= 0:
        return jnp.zeros(shape, jnp.int32)
    return jax.random.bernoulli(key, rate, shape).astype(jnp.int32)


def _subarray_mask(key, cfg: FaultConfig, k: int, n: int) -> jax.Array:
    """(K, N) 0/1 mask of cells inside failed subarrays (stuck-at-0)."""
    groups = -(-n // cfg.subarray_cols)
    hit = jax.random.bernoulli(key, cfg.subarray_fail_rate, (groups,))
    cols = jnp.repeat(hit, cfg.subarray_cols)[:n]
    return jnp.broadcast_to(cols[None, :], (k, n)).astype(jnp.int32)


def corrupt_codes(codes: jax.Array, bits: int, cfg: FaultConfig,
                  key: jax.Array) -> jax.Array:
    """Apply every persistent fault mechanism to (K, N) weight codes.

    Per plane ``b``: each stored copy independently picks up write +
    retention flips (XOR — a double flip self-cancels), then stuck-at and
    dead-subarray cells override whatever was written; protected planes
    majority-vote their copies. Returns int32 codes of the same shape.
    """
    k, n = codes.shape[-2], codes.shape[-1]
    out = jnp.zeros_like(codes)
    for b in range(bits):
        plane = (codes >> b) & 1
        copies = cfg.vote_copies if b >= bits - cfg.protect_msb else 1
        kb = jax.random.fold_in(key, b)
        stored = []
        for r in range(copies):
            kr = jax.random.fold_in(kb, r)
            v = plane
            v = v ^ _flip(jax.random.fold_in(kr, _TAG_WRITE),
                          cfg.write_ber, (k, n))
            v = v ^ _flip(jax.random.fold_in(kr, _TAG_RETAIN),
                          cfg.retention_ber, (k, n))
            s0 = _flip(jax.random.fold_in(kr, _TAG_STUCK0),
                       cfg.stuck0_rate, (k, n))
            if cfg.subarray_fail_rate > 0:
                s0 = s0 | _subarray_mask(
                    jax.random.fold_in(kr, _TAG_SUBFAIL), cfg, k, n)
            s1 = _flip(jax.random.fold_in(kr, _TAG_STUCK1),
                       cfg.stuck1_rate, (k, n))
            stored.append((v & (1 - s0)) | s1)
        out = out | (_majority(stored) << b)
    return out.astype(codes.dtype)


def transient_flip_field(shape_kn, bits: int, cfg: FaultConfig,
                         key: jax.Array) -> jax.Array:
    """(K, N) int32 XOR field of one read's disturb flips.

    Bit ``b`` of the field is set where plane ``b``'s sensed value flips
    this read. Protected planes sense all copies and vote, so their
    effective flip needs a majority of copies disturbed at once.
    """
    k, n = shape_kn
    field = jnp.zeros((k, n), jnp.int32)
    for b in range(bits):
        copies = cfg.vote_copies if b >= bits - cfg.protect_msb else 1
        kb = jax.random.fold_in(jax.random.fold_in(key, _TAG_DISTURB), b)
        flips = [_flip(jax.random.fold_in(kb, r), cfg.read_disturb_ber,
                       (k, n)) for r in range(copies)]
        field = field | (_majority(flips) << b)
    return field


# ---------------------------------------------------------------------------
# Rendering one code-space fault field into every packed representation
# ---------------------------------------------------------------------------

def inject_packed(pw, cfg: FaultConfig, key: jax.Array):
    """Persistent-fault injection at subarray programming time.

    Accepts a :class:`PackedWeight` or :class:`PackedConvWeight`; returns
    the same type with corrupted codes AND consistently corrupted planes
    (plus the fused conv layout), so every backend sees the same device
    state. Scan-stacked weights (leading reps axis on ``codes``) inject
    under ``vmap`` with per-rep keys.
    """
    if isinstance(pw, PackedConvWeight):
        return repack_conv_codes(
            pw, corrupt_codes(pw.mat.codes, pw.bits, cfg, key))
    if pw.codes.ndim == 3:              # vmap-prepacked LM scan stack
        keys = jax.random.split(key, pw.codes.shape[0])
        return jax.vmap(lambda p, k: inject_packed(p, cfg, k))(pw, keys)
    return repack_codes(pw, corrupt_codes(pw.codes, pw.bits, cfg, key))


def inject_tree(tree, cfg: FaultConfig | None, key: jax.Array | None = None):
    """Inject persistent faults into every packed leaf of a param tree.

    Each :class:`PackedWeight`/:class:`PackedConvWeight` gets its own key
    folded from a stable depth-first leaf counter, so adding unrelated
    leaves upstream does not re-roll an existing layer's faults only if the
    walk order is unchanged — good enough for a deployment artifact that is
    injected exactly once. When ``cfg.checksum`` is armed the flagged
    columns are immediately remapped to spares (bounded by
    ``cfg.spare_cols``) and re-programmed from the golden tree, modeling
    the deployment-time test-and-repair pass. Returns ``(tree, report)``.
    """
    if cfg is None or not cfg.persistent:
        return tree, {"injected": 0, "bad_cols": 0, "repaired_cols": 0}
    key = cfg.key() if key is None else key
    count = {"i": 0}
    report = {"injected": 0, "bad_cols": 0, "repaired_cols": 0}

    def walk(p):
        if isinstance(p, (PackedWeight, PackedConvWeight)):
            leaf_key = jax.random.fold_in(key, count["i"])
            count["i"] += 1
            bad = inject_packed(p, cfg, leaf_key)
            report["injected"] += 1
            if cfg.checksum:
                bad, n_bad, n_fix = repair_packed(bad, p, cfg.spare_cols,
                                                  cfg.subarray_cols)
                report["bad_cols"] += n_bad
                report["repaired_cols"] += n_fix
            return bad
        if isinstance(p, dict):
            return {k: walk(v) for k, v in p.items()}
        if isinstance(p, (list, tuple)):
            return type(p)(walk(v) for v in p)
        return p

    return walk(tree), report


# ---------------------------------------------------------------------------
# Checksum detection + spare-column repair
# ---------------------------------------------------------------------------

def verify_columns(pw) -> jax.Array:
    """Integrity probe: (..., N) bool mask of columns whose stored codes no
    longer sum to the periphery's golden ``col_sums`` (Sw register)."""
    if isinstance(pw, PackedConvWeight):
        pw = pw.mat
    return pw.codes.sum(-2).astype(jnp.int32) != pw.col_sums


def _repair_codes(codes, golden_codes, col_sums, spare_cols: int,
                  subarray_cols: int | None = None):
    bad = codes.sum(-2).astype(jnp.int32) != col_sums            # (..., N)
    badi = bad.astype(jnp.int32)
    if subarray_cols:
        # Spares are per-subarray hardware: a leaf spanning S column groups
        # of ``subarray_cols`` gets ``spare_cols`` repairs in *each* group,
        # not a flat leaf-wide budget.
        n = badi.shape[-1]
        pad = (-n) % subarray_cols
        grp = jnp.pad(badi, [(0, 0)] * (badi.ndim - 1) + [(0, pad)])
        grp = grp.reshape(*badi.shape[:-1], -1, subarray_cols)
        budget = (jnp.cumsum(grp, axis=-1) <= spare_cols).reshape(
            *badi.shape[:-1], -1)[..., :n]
    else:
        budget = jnp.cumsum(badi, axis=-1) <= spare_cols
    fix = bad & budget
    repaired = jnp.where(fix[..., None, :], golden_codes, codes)
    return repaired, bad.sum(), fix.sum()


def repair_packed(pw, golden, spare_cols: int,
                  subarray_cols: int | None = None):
    """Remap up to ``spare_cols`` checksum-flagged columns to spares and
    re-program them from the golden weights.

    Returns ``(repaired, n_bad, n_repaired)`` — counts as python ints (the
    call is an eager deployment-time pass, like prepack itself). With
    ``subarray_cols`` the budget applies per group of that many columns
    (each physical subarray carries its own spares); without it the budget
    is leaf-wide. Columns beyond the budget stay faulty.
    """
    if isinstance(pw, PackedConvWeight):
        codes, n_bad, n_fix = _repair_codes(
            pw.mat.codes, golden.mat.codes, pw.mat.col_sums, spare_cols,
            subarray_cols)
        return repack_conv_codes(pw, codes), int(n_bad), int(n_fix)
    codes, n_bad, n_fix = _repair_codes(
        pw.codes, golden.codes, pw.col_sums, spare_cols, subarray_cols)
    if pw.codes.ndim == 3:
        rebuilt = jax.vmap(repack_codes)(pw, codes)
    else:
        rebuilt = repack_codes(pw, codes)
    return rebuilt, int(n_bad), int(n_fix)


def repair_tree(tree, golden, spare_cols: int,
                subarray_cols: int | None = None):
    """Checksum-scan every packed leaf against its golden twin and remap
    flagged columns onto spares (per-subarray budget when ``subarray_cols``
    is given). Returns ``(repaired_tree, {"bad_cols", "repaired_cols"})`` —
    the field-service pass a deployment runs when the watchdog suspects
    silent corruption."""
    report = {"bad_cols": 0, "repaired_cols": 0}

    def walk(p, g):
        if isinstance(p, (PackedWeight, PackedConvWeight)):
            fixed, n_bad, n_fix = repair_packed(p, g, spare_cols,
                                                subarray_cols)
            report["bad_cols"] += n_bad
            report["repaired_cols"] += n_fix
            return fixed
        if isinstance(p, dict):
            return {k: walk(v, g[k]) for k, v in p.items()}
        if isinstance(p, (list, tuple)):
            return type(p)(walk(v, gv) for v, gv in zip(p, g))
        return p

    return walk(tree, golden), report


# ---------------------------------------------------------------------------
# Transient read disturb: scoped per hot-loop step, keyed per call site
# ---------------------------------------------------------------------------
# The idiom mirrors repro.distributed.sharding's process-global mesh scope:
# model code stays fault-agnostic, the engine activates the scope around its
# (traced) program body, and the bit-serial matmul entry points consult it.
# The key placed in the scope is a *tracer* when activation happens inside a
# jitted step — each pim_linear call site folds in a trace-time counter, so
# distinct projections draw distinct disturb fields and the per-step key
# threads fresh randomness into every decode step. Scan-stacked layers share
# one call site, hence one field per step (documented simplification).

_READ_CFG: FaultConfig | None = None
_READ_KEY = None
_READ_SITE = 0


@contextlib.contextmanager
def read_disturb_scope(cfg: FaultConfig | None, key):
    """Activate transient read-disturb for the programs traced inside."""
    global _READ_CFG, _READ_KEY, _READ_SITE
    if cfg is None or not cfg.transient:
        yield
        return
    prev = (_READ_CFG, _READ_KEY, _READ_SITE)
    _READ_CFG, _READ_KEY, _READ_SITE = cfg, key, 0
    try:
        yield
    finally:
        _READ_CFG, _READ_KEY, _READ_SITE = prev


def read_disturb_active() -> bool:
    return _READ_CFG is not None


def _site_key():
    global _READ_SITE
    k = jax.random.fold_in(_READ_KEY, _READ_SITE)
    _READ_SITE += 1
    return k


def disturb_packed(pw: PackedWeight) -> PackedWeight:
    """One read's disturbed view of a packed weight (scope must be active).

    Codes and planes are XOR-ed with the same flip field, so whichever
    representation the backend consumes sees the same disturbed bits; the
    unused rendering is dead code XLA eliminates. ``col_sums`` stays golden
    (periphery register — reads of it are digital).
    """
    cfg = _READ_CFG
    k = pw.codes.shape[-2]
    field = transient_flip_field((k, pw.codes.shape[-1]), pw.bits, cfg,
                                 _site_key())
    planes_mask = bitslice.slice_and_pack(field.T, pw.bits)
    pad = pw.planes.shape[-1] - planes_mask.shape[-1]
    if pad:
        planes_mask = jnp.pad(planes_mask, ((0, 0),) * (planes_mask.ndim - 1)
                              + ((0, pad),))
    return PackedWeight(codes=pw.codes ^ field.astype(pw.codes.dtype),
                        planes=pw.planes ^ planes_mask,
                        col_sums=pw.col_sums, wq=pw.wq, tune=pw.tune)


def disturb_fused_planes(fused: jax.Array, kernel_shape) -> jax.Array:
    """One read's disturbed view of a fused conv layout (scope active).

    The flip field is drawn in im2col code space — the exact shape the
    materialized path's :func:`disturb_packed` draws at the same site — so
    the fused implicit-im2col kernel and the im2col matmul see identical
    disturbed device state and stay bit-parity under injection.
    """
    cfg = _READ_CFG
    kh, kw, c, o = kernel_shape
    bits = fused.shape[1]
    field = transient_flip_field((kh * kw * c, o), bits, cfg, _site_key())
    return fused ^ fused_conv_planes(field.reshape(kh, kw, c, o), bits)
