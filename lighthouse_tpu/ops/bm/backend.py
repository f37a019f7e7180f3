"""Batch-minor staged batch verification: ops/backend.py's device graph in
the batch-minor layout, with same-message PAIR COMBINING.

Pipeline (hash-consed h2c -> aggregation/validity/weighting + segmented
same-message combine -> Miller loops over DISTINCT messages -> one final
exponentiation, a stage of its own shared by every bucket shape):

The blst batch equation prod_i e([r_i] A_i, H(m_i)) * e(-g1, S) == 1 is
evaluated after grouping by message: bilinearity gives

    prod_{i: m_i = m} e([r_i] A_i, H(m)) = e(sum_{i: m_i = m} [r_i] A_i, H(m))

so the Miller loop runs over the m DISTINCT messages (+1 signature pair)
instead of all n sets — the exact same field value, with the per-set
random weighting applied BEFORE combining (the anti-cancellation argument
is unchanged set-for-set). Gossip-firehose batches (one committee's
attestations share AttestationData; reference shape
attestation_verification/batch.rs:187-197) collapse ~256x; all-distinct
batches pay only a log2(n)-depth segmented scan (~11 G1 adds).

Tensors put to the device:

    u         (2, 2, L, m)     distinct-message field elements, minor m
    inv_idx   (n,) int32       set -> distinct-message row
    row_mask  (m,) bool        True for rows backed by a real message
    pk_proj   (K, 3, L, n)     projective pubkeys (K slots, infinity-padded)
    sig_proj  (3, 2, L, n)     projective signatures
    sig_checked / set_mask (n,) bool ; scalars (n,) uint64

Same host-side early-out and poisoned-batch fallback semantics as
ops/backend.py, which drives the staging and dispatches here.
"""

import os
from functools import lru_cache
from typing import Optional

import jax
import jax.numpy as jnp

from lighthouse_tpu.crypto.bls import curves as _oc
from lighthouse_tpu.crypto.bls.constants import P as _P

from . import curves as cv
from . import h2c
from . import limbs as lb
from . import pairing as pr
from . import tower as tw

# -g1 generator, batch-minor projective with a minor batch axis of 1.
_NEG_G1 = cv.g1_from_affine([(_oc.G1_GEN[0], _P - _oc.G1_GEN[1])])


def _h2g2(u):
    """Distinct-message SSWU/isogeny/cofactor map: (2, 2, L, m) ->
    (3, 2, L, m). No per-set gather — the pairing runs on distinct rows."""
    return h2c.hash_to_g2_device(u)


def _segment_combine(pts, inv_idx, m_bucket: int):
    """Sum weighted G1 points by message id: (3, L, n) x (n,) int32 ->
    (3, L, m_bucket) where out[j] = sum_{i: inv_idx[i] = j} pts[i].

    Sort by id (gather), then an inclusive segmented scan with the
    classical associative (value, first-of-segment flag) operator over
    the minor axis — log2(n) complete G1 adds — and gather each
    segment's last position (searchsorted on the sorted ids). Rows with
    no members yield garbage gathers; the caller masks them (row_mask)."""
    n = pts.shape[-1]
    order = jnp.argsort(inv_idx)
    ids = jnp.take(inv_idx, order)
    sorted_pts = jnp.take(pts, order, axis=-1)
    first = jnp.concatenate(
        [jnp.ones((1,), bool), ids[1:] != ids[:-1]]
    ).reshape(1, 1, n)

    def op(a, b):
        va, fa = a
        vb, fb = b
        v = cv.G1.select(fb[0, 0], vb, cv.G1.add(va, vb))
        return v, jnp.logical_or(fa, fb)

    summed, _ = jax.lax.associative_scan(op, (sorted_pts, first), axis=2)
    last_pos = jnp.searchsorted(
        ids, jnp.arange(m_bucket, dtype=inv_idx.dtype), side="right"
    ) - 1
    return jnp.take(summed, jnp.clip(last_pos, 0, n - 1), axis=-1)


def _dual_var_ladder(p1, p2, k, nbits: int = 64):
    """[k]P1 (G1) and [k]P2 (G2) with the SAME per-element scalars in ONE
    2-bit-windowed scan: both groups' double-double-add steps share one
    scan body, halving scan overhead and widening the fusion domain vs
    two back-to-back ladders (curves._Group.mul_var_scalar semantics)."""
    assert nbits % 2 == 0
    g1, g2 = cv.G1, cv.G2
    p1_2 = g1.double(p1)
    p1_3 = g1.add(p1_2, p1)
    p2_2 = g2.double(p2)
    p2_3 = g2.add(p2_2, p2)
    inf1 = jnp.broadcast_to(g1.infinity, p1.shape)
    inf2 = jnp.broadcast_to(g2.infinity, p2.shape)
    positions = jnp.arange(nbits - 2, -1, -2, dtype=jnp.uint64)

    def step(carry, pos):
        a1, a2 = carry
        a1 = g1.double(g1.double(a1))
        a2 = g2.double(g2.double(a2))
        digit = (k >> pos) & jnp.uint64(3)
        e1 = g1.select(
            digit == 1, p1,
            g1.select(digit == 2, p1_2, g1.select(digit == 3, p1_3, inf1)),
        )
        e2 = g2.select(
            digit == 1, p2,
            g2.select(digit == 2, p2_2, g2.select(digit == 3, p2_3, inf2)),
        )
        return (g1.add(a1, e1), g2.add(a2, e2)), None

    (a1, a2), _ = jax.lax.scan(step, (inf1, inf2), positions)
    return a1, a2


# Default fixed chunk width for the CHUNKED prep stage. 4096 is the
# measured peak monolithic bucket (NOTES round-5 table): buckets up to
# 4096 keep the single-pass graph; 8192/16384 run as 2/4 ladder passes
# whose per-element outputs are reassembled bit-exactly (see
# _make_prepare). Override with LIGHTHOUSE_TPU_PREP_CHUNK (0 disables
# chunking entirely — every bucket stays monolithic).
DEFAULT_PREP_CHUNK = 4096


def prep_chunk_width(n_bucket: int, n_devices: int = 1) -> int:
    """Resolve the prep-stage chunk width for an n_bucket: 0 = monolithic,
    otherwise a power-of-two GLOBAL width dividing n_bucket. Under a
    sharded mesh the configured width is PER DEVICE (each chunk keeps a
    resident `width` slab on every chip), so the global chunk scales with
    the device count."""
    try:
        base = int(os.environ.get("LIGHTHOUSE_TPU_PREP_CHUNK", "")
                   or DEFAULT_PREP_CHUNK)
    except ValueError:
        base = DEFAULT_PREP_CHUNK
    if base <= 0:
        return 0
    width = base * max(1, int(n_devices))
    if width >= n_bucket or n_bucket % width:
        return 0
    return width


def _make_prepare(m_bucket: int, prep_chunk: int = 0):
    """Build stage 2 (aggregation + validity + random-scalar weighting +
    same-message combine — backend._prepare_pairs semantics, then the
    segmented combine documented at module top).

    prep_chunk > 0 runs the LADDER BLOCK — the subgroup checks and the
    fused dual scalar ladder, the two 64-step width-n scans whose working
    set spills past n=4096 — as a lax.scan over n/prep_chunk fixed-width
    slabs. Every per-element value (weighted aggregate pubkeys, weighted
    signatures, validity bits) is BIT-IDENTICAL to the monolithic pass:
    the ladders are elementwise along the minor axis, chunk outputs are
    restacked into the full-width tensors, and the cross-element
    reductions (signature tree-sum, segment combine) then run exactly as
    in the monolithic graph. tests/test_ops_bm.py pins this
    differentially."""

    def _ladder_block(pk_proj, sig_proj, sig_checked, set_mask, scalars):
        agg = lb.tree_reduce(
            pk_proj, cv.G1.add, cv.G1.infinity, pk_proj.shape[0]
        )                                               # (3, L, c)
        agg_inf = cv.G1.is_infinity(agg)
        sig_ok = jnp.logical_or(sig_checked, cv.g2_in_subgroup(sig_proj))
        a_proj, rsig = _dual_var_ladder(agg, sig_proj, scalars)
        inf1 = jnp.broadcast_to(cv.G1.infinity, a_proj.shape)
        a_masked = cv.G1.select(set_mask, a_proj, inf1)
        ok = jnp.where(set_mask, jnp.logical_and(sig_ok, ~agg_inf), True)
        return a_masked, rsig, ok

    def _prepare_pairs(pk_proj, sig_proj, sig_checked, set_mask, scalars,
                       inv_idx):
        n = sig_proj.shape[-1]
        if prep_chunk and prep_chunk < n:
            n_chunks = n // prep_chunk

            def split(x):
                """(..., n) -> (n_chunks, ..., c): the minor axis splits
                chunk-major (element i -> chunk i // c, lane i % c)."""
                y = x.reshape(x.shape[:-1] + (n_chunks, prep_chunk))
                return jnp.moveaxis(y, -2, 0)

            def join(y):
                return jnp.moveaxis(y, 0, -2).reshape(
                    y.shape[1:-1] + (n,)
                )

            def body(carry, xs):
                return carry, _ladder_block(*xs)

            _, (a_chunks, r_chunks, ok_chunks) = jax.lax.scan(
                body, None,
                (split(pk_proj), split(sig_proj), split(sig_checked),
                 split(set_mask), split(scalars)),
            )
            a_masked = join(a_chunks)
            rsig = join(r_chunks)
            ok = join(ok_chunks)
        else:
            a_masked, rsig, ok = _ladder_block(
                pk_proj, sig_proj, sig_checked, set_mask, scalars
            )

        s_proj = cv.G2.msm_reduce_minor(rsig, n)        # (3, 2, L, 1)
        a_comb = _segment_combine(a_masked, inv_idx, m_bucket)
        p_proj = jnp.concatenate([a_comb, _NEG_G1], axis=-1)
        sets_valid = jnp.all(ok)
        return p_proj, s_proj, sets_valid

    return _prepare_pairs


def _miller_product(p_proj, h_unique, s_proj, row_mask):
    """Miller loops over the m distinct messages + the signature pair
    (all-projective), masked and multiplied into one Fp12."""
    q_proj = jnp.concatenate([h_unique, s_proj], axis=-1)
    mask = jnp.concatenate([row_mask, jnp.ones((1,), dtype=bool)])
    return pr.miller_product_proj(p_proj, q_proj, mask)


def _final_check(prod, sets_valid):
    """The one final exponentiation == 1, ANDed with per-set validity.
    Its input is a single Fp12 whatever the bucket, so one executable
    serves every (n, k, m): split from the Miller stage because the
    exponentiation is most of the pairing's compile (~2.5 of ~3 minutes
    for one v5e), which every new m used to pay again."""
    return jnp.logical_and(
        tw.fp12_is_one(pr.final_exponentiation(prod))[..., 0], sets_valid)


# Stage 1/3/4 jits are MODULE-LEVEL singletons: their graphs depend only
# on the distinct-message bucket m (stage 1 maps u, stage 3 pairs m+1
# rows) or on nothing (stage 4), so sharing one jit wrapper across every
# (n, k) core lets jax's own executable cache dedupe them — the warm grid
# compiles each m once instead of once per bucket shape.
FP12_SHAPE = tw.FP12_ONE.shape            # stage 4's input: one Fp12
_stage1_jit = jax.jit(_h2g2)
_stage3_jit = jax.jit(_miller_product)
_stage4_jit = jax.jit(_final_check)


@lru_cache(maxsize=None)
def _prepare_jit(m_bucket: int, prep_chunk: int):
    return jax.jit(_make_prepare(m_bucket, prep_chunk))


def _warm_dispatch(stage_id: str, fallback):
    """Route a stage through the AOT warm bundle when one is active (see
    ops/backend._warm_dispatch; the BM prep stage id carries its chunk
    width because the scan structure isn't visible in the avals)."""
    try:
        from lighthouse_tpu.serving import aot
    except ImportError:
        return fallback
    return aot.stage_dispatch("bm", stage_id, fallback)


def _traced(stage: str, fn, **static_args):
    """Observability stage wrapper (see ops/backend._traced), engine
    label "bm"."""
    try:
        from lighthouse_tpu.observability import stages as _obs_stages
    except ImportError:
        return fn
    return _obs_stages.traced("bm", stage, fn, **static_args)


def jitted_core(n_bucket: int, k_bucket: int, m_bucket: int,
                prep_chunk: Optional[int] = None, sharded: bool = False,
                n_devices: Optional[int] = None):
    """Three separately-jitted stages (the monolithic-executable
    serialization rationale of backend._jitted_core).

    prep_chunk: fixed chunk width for the prep-stage ladder scans (None =
    resolve from LIGHTHOUSE_TPU_PREP_CHUNK / the 4096 default; 0 =
    monolithic). sharded: constrain stage 1/2 inputs to the mesh's
    MINOR-axis sharding (the BM layout's batch axis is the last axis) over
    `n_devices` devices (default: all)."""
    if prep_chunk is None:
        prep_chunk = prep_chunk_width(
            n_bucket,
            (n_devices or len(jax.devices())) if sharded else 1,
        )
    return _jitted_core(n_bucket, k_bucket, m_bucket, int(prep_chunk),
                        bool(sharded), n_devices)


@lru_cache(maxsize=None)
def _jitted_core(n_bucket: int, k_bucket: int, m_bucket: int,
                 prep_chunk: int, sharded: bool,
                 n_devices: Optional[int]):
    shape_args = dict(n=n_bucket, k=k_bucket, m=m_bucket,
                      chunk=prep_chunk, sharded=sharded)
    del n_bucket, k_bucket  # cache keys; shapes live in the arguments
    if not sharded:
        stage1 = _warm_dispatch("h2g2", _stage1_jit)
        stage2 = _warm_dispatch(f"prepare:c{prep_chunk}",
                                _prepare_jit(m_bucket, prep_chunk))
        stage3 = _warm_dispatch("pairing", _stage3_jit)
        stage4 = _warm_dispatch("final_exp", _stage4_jit)
    else:
        from lighthouse_tpu.parallel import mesh as pm

        def constrained(fn):
            def wrapped(*args):
                mesh = pm.get_mesh(n_devices)
                args = [
                    jax.lax.with_sharding_constraint(
                        x, pm.minor_sharding(mesh, x.ndim)
                    )
                    if hasattr(x, "ndim") and x.ndim >= 1 else x
                    for x in args
                ]
                return fn(*args)
            return wrapped

        # No fused.disabled() here: the BM stages are pure XLA (no Pallas
        # kernels), so every op partitions under the mesh. Stage 3's
        # m+1 pair axis is indivisible — leave its layout to XLA, as the
        # major sharded path does.
        stage1 = jax.jit(constrained(_h2g2))
        stage2 = jax.jit(constrained(_make_prepare(m_bucket, prep_chunk)))
        stage3 = jax.jit(_miller_product)
        stage4 = jax.jit(_final_check)

    stage1 = _traced("h2g2", stage1, **shape_args)
    stage2 = _traced("prepare", stage2, **shape_args)
    stage3 = _traced("pairing", stage3, **shape_args)
    stage4 = _traced("final_exp", stage4, **shape_args)

    def core(u, inv_idx, row_mask, pk_proj, sig_proj, sig_checked,
             set_mask, scalars):
        h_unique = stage1(u)
        p_proj, s_proj, sets_valid = stage2(
            pk_proj, sig_proj, sig_checked, set_mask, scalars, inv_idx
        )
        return stage4(stage3(p_proj, h_unique, s_proj, row_mask),
                      sets_valid)

    core.stages = (stage1, stage2, stage3, stage4)
    return core
