"""The TPU batch-verification backend — the north-star entry point.

Implements `verify_signature_sets` (BASELINE.md) on device, semantics of
blst's random-scalar batch verification as driven by the reference
(crypto/bls/src/impls/blst.rs:36-118):

    prod_i e([r_i] agg_pk_i, H(m_i)) * e(-g1, sum_i [r_i] sig_i) == 1

with r_i nonzero 64-bit scalars from the HOST CSPRNG (device kernels stay
deterministic; SURVEY.md §7.3 item 2).

Staging design (the SignatureSet -> tensor ABI, SURVEY.md §7.1):
  * sets are padded to power-of-two buckets on both axes — set count and
    pubkeys-per-set — so each (n_bucket, k_bucket) shape compiles once and
    is reused forever (persistent cache);
  * pubkey padding is the INFINITY point: the complete RCB group law absorbs
    it in the per-set aggregation tree with no masking;
  * padded sets ride a mask into the pairing (contribute 1 to the product);
  * per-set validity (signature subgroup membership, non-infinity aggregate
    pubkey) is computed on device and ANDed with the pairing bit — one bool
    comes back to the host.

Fallback semantics on False match the reference: the caller re-verifies
per-set to find the poisoned item (attestation_verification/batch.rs:123-134).
"""

import os
import secrets
from functools import lru_cache
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from lighthouse_tpu.crypto.bls import api as _api
from lighthouse_tpu.crypto.bls import curves as _oc
from lighthouse_tpu.crypto.bls.constants import P as _P
from lighthouse_tpu.crypto.bls.constants import RAND_BITS as _RAND_BITS

from . import curves as cv
from . import h2c
from . import limbs as lb
from . import pairing as pr
from . import tower as tw

# -g1 generator, staged once (the constant pair of the batch equation),
# projective with Z = 1 (the Miller loop is projective since round 4).
_NEG_G1_PROJ = lb.ints_to_mont(
    [(_oc.G1_GEN[0]), (_P - _oc.G1_GEN[1]), 1]
).reshape(3, lb.L)


def _next_pow2(n: int, floor: int = 1) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def _warm_dispatch(stage_id: str, fallback):
    """Route a stage through the AOT warm bundle when one is active
    (serving/aot.py): a restarted process serves bundle-covered shapes
    from deserialized exports instead of re-tracing the ~60k-op graphs.
    No bundle (the default) = one None check per call, then `fallback`.
    Only a missing serving layer is tolerated: a broken bundle or seam
    must surface, not silently fall back."""
    try:
        from lighthouse_tpu.serving import aot
    except ImportError:
        return fallback
    return aot.stage_dispatch("major", stage_id, fallback)


def _traced(stage: str, fn, **static_args):
    """Observability stage wrapper (observability/stages.traced): a
    no-op attribute check unless tracing/stage-timing is active, in
    which case the stage blocks until ready and reports its true wall
    time. Tolerates only a missing module, as _warm_dispatch does."""
    try:
        from lighthouse_tpu.observability import stages as _obs_stages
    except ImportError:
        return fn
    return _obs_stages.traced("major", stage, fn, **static_args)


# ---------------------------------------------------------------------------
# Jitted core (cached per bucket shape)
# ---------------------------------------------------------------------------


def _prepare_pairs(pk_proj, sig_proj, sig_checked, set_mask, scalars):
    """Aggregation + validity + random-scalar weighting (stage 2).

    pk_proj:     (n, K, 3, L)    projective pubkeys, padded with infinity
    sig_proj:    (n, 3, 2, L)    projective signatures (infinity for padding)
    sig_checked: (n,) bool       host-side subgroup-check amortization flag
    set_mask:    (n,) bool       True for real sets
    scalars:     (n,) uint64     nonzero random batch coefficients
    -> (p_proj (n+1,3,L), s_proj (3,2,L), sets_valid ())

    Round 4: outputs stay PROJECTIVE — the Miller loop homogenizes its
    lines, so the to_affine inversion ladders (381 squarings each) that
    used to close this stage are gone.
    """
    n = pk_proj.shape[0]
    # Aggregate pubkeys per set: tree over the K axis (complete adds absorb
    # the infinity padding).
    agg = lb.tree_reduce(
        jnp.moveaxis(pk_proj, 1, 0), cv.G1.add, cv.G1.infinity, pk_proj.shape[1]
    )                                                             # (n, 3, L)
    agg_inf = cv.G1.is_infinity(agg)

    # Signature subgroup membership (skipped where the host already paid it —
    # mirrors Signature.subgroup_checked amortization in the oracle API).
    sig_ok = jnp.logical_or(sig_checked, cv.g2_in_subgroup(sig_proj))

    # Random-scalar weighting: A_i = [r_i] agg_pk_i ; S = sum_i [r_i] sig_i.
    a_proj = cv.G1.mul_var_scalar(agg, scalars)                   # (n, 3, L)
    rsig = cv.G2.mul_var_scalar(sig_proj, scalars)                # (n, 3, 2, L)
    s_proj = lb.tree_reduce(rsig, cv.G2.add, cv.G2.infinity, n)   # (3, 2, L)

    p_proj = jnp.concatenate(
        [a_proj, jnp.broadcast_to(_NEG_G1_PROJ, (1, 3, lb.L))]
    )
    sets_valid = jnp.all(
        jnp.where(set_mask, jnp.logical_and(sig_ok, ~agg_inf), True)
    )
    return p_proj, s_proj, sets_valid


def _pairing_check(p_proj, h_proj, s_proj, set_mask, sets_valid):
    """Final product-of-pairings check (stage 3, all-projective)."""
    q_proj = jnp.concatenate([h_proj, s_proj[None]])
    mask = jnp.concatenate([set_mask, jnp.ones((1,), dtype=bool)])
    pairing_ok = pr.multi_pairing_is_one_proj(p_proj, q_proj, mask)
    return jnp.logical_and(pairing_ok, sets_valid)


def _h2g2_gather(u_unique, inv_idx):
    """Hash-cons H(m) (round 5, VERDICT #2): run the expensive SSWU map /
    isogeny / cofactor clearing over the DISTINCT messages only and gather
    per-set rows. Gossip-firehose batches repeat `AttestationData` across a
    whole committee (reference builds one set per attestation over shared
    data, attestation_verification/batch.rs:187-197), so h2c — ~31% of
    device time on distinct-message shapes — collapses to ~#committees
    rows.

    u_unique: (m, 2, 2, L) field elements of distinct messages;
    inv_idx:  (n,) int32 map set -> distinct row. -> (n, 3, 2, L)."""
    h_unique = h2c.hash_to_g2_device(u_unique)
    return jnp.take(h_unique, inv_idx, axis=0)


def _verify_core(u, inv_idx, pk_proj, sig_proj, sig_checked, set_mask,
                 scalars):
    """The full device graph as one function (jittable; the production path
    runs it as three separately-jitted stages — see _jitted_core — because
    XLA:CPU crashes serializing the monolithic executable into the
    persistent cache, and the staged split costs nothing: arrays never
    leave the device between stages)."""
    h_proj = _h2g2_gather(u, inv_idx)                             # (n, 3, 2, L)
    p_proj, s_proj, sets_valid = _prepare_pairs(
        pk_proj, sig_proj, sig_checked, set_mask, scalars
    )
    return _pairing_check(p_proj, h_proj, s_proj, set_mask, sets_valid)


@lru_cache(maxsize=None)
def _jitted_core(n_bucket: int, k_bucket: int, sharded: bool,
                 n_devices: Optional[int] = None):
    """Three-stage pipeline, each stage its own jit (own cache entry).
    `n_devices` bounds the sharded mesh (default: all devices)."""
    shape_args = dict(n=n_bucket, k=k_bucket, sharded=sharded)
    if not sharded:
        stage1 = _traced("h2g2", _warm_dispatch("h2g2", jax.jit(_h2g2_gather)),
                         **shape_args)
        stage2 = _traced("prepare",
                         _warm_dispatch("prepare", jax.jit(_prepare_pairs)),
                         **shape_args)
        stage3 = _traced("pairing",
                         _warm_dispatch("pairing", jax.jit(_pairing_check)),
                         **shape_args)

        def core(u, inv_idx, pk_proj, sig_proj, sig_checked, set_mask,
                 scalars):
            h_proj = stage1(u, inv_idx)
            p_proj, s_proj, sets_valid = stage2(
                pk_proj, sig_proj, sig_checked, set_mask, scalars
            )
            return stage3(p_proj, h_proj, s_proj, set_mask, sets_valid)

        core.stages = (stage1, stage2, stage3)
        return core

    from lighthouse_tpu.parallel import mesh as pm
    from . import fused

    def constrained(fn):
        def wrapped(*args):
            sh = pm.batch_sharding(pm.get_mesh(n_devices))
            args = [
                jax.lax.with_sharding_constraint(x, sh)
                if hasattr(x, "ndim") and x.ndim >= 1 else x
                for x in args
            ]
            # Pallas kernels do not partition under the mesh — trace the
            # sharded graph with the XLA fallback (fused.disabled()).
            with fused.disabled():
                return fn(*args)
        return wrapped

    def unfused(fn):
        def wrapped(*args):
            with fused.disabled():
                return fn(*args)
        return wrapped

    stage1 = _traced("h2g2", jax.jit(constrained(_h2g2_gather)), **shape_args)
    stage2 = _traced("prepare", jax.jit(constrained(_prepare_pairs)),
                     **shape_args)
    # (n+1): leave layout to XLA
    stage3 = _traced("pairing", jax.jit(unfused(_pairing_check)), **shape_args)

    def core(u, inv_idx, pk_proj, sig_proj, sig_checked, set_mask, scalars):
        h_proj = stage1(u, inv_idx)
        p_proj, s_proj, sets_valid = stage2(
            pk_proj, sig_proj, sig_checked, set_mask, scalars
        )
        return stage3(p_proj, h_proj, s_proj, set_mask, sets_valid)

    core.stages = (stage1, stage2, stage3)
    return core


# ---------------------------------------------------------------------------
# Host staging
# ---------------------------------------------------------------------------


def verify_signature_sets_tpu_async(
    sets: Sequence["_api.SignatureSet"], sharded: Optional[bool] = None
):
    """Dispatch the device check WITHOUT blocking: returns a () bool jax
    array (or a python bool for host-side early-outs / the small-batch
    native fallback). The staging for the NEXT batch overlaps the device
    execution of this one — the double-buffering lever of NOTES #2;
    bench.py and the beacon processor's staging worker drive it."""
    return _verify_tpu_impl(sets, sharded)


def verify_signature_sets_tpu(
    sets: Sequence["_api.SignatureSet"], sharded: Optional[bool] = None
) -> bool:
    """Stage SignatureSets into bucket tensors and run the device check.

    Host-side early-outs replicate the oracle/blst rejects exactly
    (api.verify_signature_sets_oracle): empty batch, empty signing_keys,
    infinity signature.
    """
    return bool(_verify_tpu_impl(sets, sharded))


def cpu_fallback_max() -> int:
    """Small-batch host fallback (SURVEY §7.3 item 3 / VERDICT r2 #2): a
    handful of gossip-latency sets should not pay device dispatch +
    bucket padding; the native C++ verifier answers in ~2-7 ms/set.
    Batches of at most this many sets answer natively.
    LIGHTHOUSE_TPU_CPU_FALLBACK_MAX=0 disables (the device-path tests pin
    it to 0 so small shapes still exercise the JAX kernels)."""
    try:
        return int(os.environ.get("LIGHTHOUSE_TPU_CPU_FALLBACK_MAX", "16"))
    except ValueError:
        return 16


def _host_rejects(s) -> bool:
    """The oracle/blst rejects decided before any device work."""
    return not s.signing_keys or s.signature.point is None


def _buckets(sets, sharded, floors=(1, 1, 1)):
    """(sharded, n_devices, n_bucket, k_bucket, m_floor) for a batch;
    `floors` (n, k, m) pins buckets from below (find_invalid_sets pins a
    poisoned batch's sub-batches to the root's shapes)."""
    if sharded is None:
        sharded = len(jax.devices()) > 1
    n_devices = len(jax.devices()) if sharded else None
    floor_n = n_devices or 1
    n_bucket = _next_pow2(len(sets), floor=max(floor_n, floors[0]))
    k_bucket = _next_pow2(max(len(s.signing_keys) for s in sets),
                          floor=floors[1])
    m_floor = max(_next_pow2(floor_n), floors[2])
    if _layout() == "bm":
        m_floor = max(m_floor, BM_M_FLOOR)
    return bool(sharded), n_devices, n_bucket, k_bucket, m_floor


def _verify_tpu_impl(sets, sharded, floors=(1, 1, 1)):
    sets = list(sets)
    if not sets:
        return False
    if any(_host_rejects(s) for s in sets):
        return False

    if len(sets) <= cpu_fallback_max():
        try:
            from lighthouse_tpu.crypto.bls import cpu_backend
            return cpu_backend.verify_signature_sets_cpu(sets)
        except Exception:
            pass  # no native toolchain: stay on the device path

    n = len(sets)
    sharded, n_devices, n_bucket, k_bucket, m_floor = _buckets(
        sets, sharded, floors)

    # Engine layout: "bm" stages batch-minor tensors (the round-5 tile-
    # utilization re-layout, ops/bm/). Since round 6 the SHARDED path runs
    # it too — the mesh shards the trailing (minor) batch axis
    # (parallel.mesh.minor_sharding) instead of falling back to the
    # batch-major engine and forfeiting the ~2.4-2.9x layout win.
    if _layout() == "bm":
        return _verify_bm_impl(sets, n, n_bucket, k_bucket, m_floor,
                               n_devices)

    # --- stage tensors (host ints -> device limbs) ------------------------
    # Hash-cons identical messages BEFORE the host SHA and the device h2c
    # map: a committee's unaggregated attestations share AttestationData,
    # so both the host hash_to_field and the device SSWU/cofactor work run
    # once per distinct message (round 5, VERDICT #2).
    uniq: dict = {}
    inv_idx = np.zeros((n_bucket,), dtype=np.int32)
    for i, s in enumerate(sets):
        inv_idx[i] = uniq.setdefault(bytes(s.message), len(uniq))
    # Quantized m bucket (same menu as the BM path): stage 1's jit is
    # shaped by m, so an unquantized next-pow2 would recompile per
    # committee count here too. Padding rows map through h2c but are
    # never gathered (inv_idx only points at real rows). The sharded
    # floor keeps every shard non-empty.
    m_bucket = max(_m_bucket_for(n_bucket, len(uniq)), m_floor)
    u = np.zeros((m_bucket, 2, 2, lb.L), dtype=lb.NP_DTYPE)
    u_real = h2c.hash_to_field_device(list(uniq.keys()))
    u[: len(uniq)] = np.asarray(u_real)

    pk_pts = []
    for s in sets:
        pts = [pk.point for pk in s.signing_keys]
        pts += [None] * (k_bucket - len(pts))
        pk_pts.extend(pts)
    pk_pts += [None] * ((n_bucket - n) * k_bucket)
    pk_proj = cv.g1_from_affine(pk_pts).reshape(n_bucket, k_bucket, 3, lb.L)

    sig_pts = [s.signature.point for s in sets] + [None] * (n_bucket - n)
    sig_proj = cv.g2_from_affine(sig_pts)

    sig_checked = np.zeros((n_bucket,), dtype=bool)
    sig_checked[:n] = [s.signature.subgroup_checked for s in sets]
    sig_checked[n:] = True  # padding: skip the device check

    set_mask = np.zeros((n_bucket,), dtype=bool)
    set_mask[:n] = True

    scalars = np.ones((n_bucket,), dtype=np.uint64)
    for i in range(n):
        r = 0
        while r == 0:
            r = secrets.randbits(_RAND_BITS)
        scalars[i] = r

    core = _jitted_core(n_bucket, k_bucket, bool(sharded))
    # Returned WITHOUT bool(): async dispatch — callers that need the
    # answer now take bool() (verify_signature_sets_tpu); pipelining
    # callers keep staging the next batch first.
    return core(
        jnp.asarray(u),
        jnp.asarray(inv_idx),
        pk_proj,
        sig_proj,
        jnp.asarray(sig_checked),
        jnp.asarray(set_mask),
        jnp.asarray(scalars),
    )


def _layout() -> str:
    """Engine layout: "bm" | "major" | "auto" (default). Auto selects the
    batch-minor engine on real accelerators — where its full (8, 128)
    tiles are the point, on sharded meshes too since the minor-axis
    sharding landed (round 6) — and the batch-major engine on CPU, where
    the test suite's warmed XLA:CPU cache lives."""
    mode = os.environ.get("LIGHTHOUSE_TPU_LAYOUT", "auto")
    if mode == "auto":
        return "bm" if jax.default_backend() != "cpu" else "major"
    return mode


# The distinct-message bucket menu, as shifts off n_bucket (m = n >> s):
# n/256, n/64, n/16, n/4, n. SHARED between _m_bucket_for (staging) and
# the ShapeWarmer's per-bucket menu walk (beacon_processor/warming.py) so
# the warmer can never silently desync from the staging menu (ADVICE r5
# #2). Being relative to n_bucket, the menu extends to the new chunked-
# prep buckets (8192/16384) with no extra entries: 16384 warms
# {64, 256, 1024, 4096, 16384}, covering the 64-committee firehose shape
# exactly.
M_BUCKET_SHIFTS = (8, 6, 4, 2, 0)

# The batch-minor engine's m bucket never drops below 64: its distinct-
# message axis is the vreg LANE axis (128 wide), so m = 1..64 costs the
# same vregs in h2c and the m + 1 Miller rows still fit one lane tile —
# while each distinct m is its own h2g2 and Miller-stage compile (about a
# minute each for one v5e, compiled on this repo's CPU sandbox). A gossip
# slot's batches (1-18 committees each) then share one executable of each
# instead of six.
BM_M_FLOOR = 64


def bm_m_menu(n_bucket: int, n_devices: int = 1) -> list:
    """Every m bucket BM staging can produce for an n bucket (the warmer
    and the AOT bundle walk exactly this list)."""
    floor = max(_next_pow2(max(1, n_devices)), BM_M_FLOOR)
    return sorted({
        max(_m_bucket_for(n_bucket, max(1, n_bucket >> s)), floor)
        for s in M_BUCKET_SHIFTS
    })


def max_n_bucket() -> int:
    """Largest production/warmed n bucket. 4096 is the measured peak
    MONOLITHIC bucket (NOTES round-5: the prep stage's width-n ladder
    scans spill past it); with the chunked prep stage enabled (the
    default, ops/bm/backend.prep_chunk_width) larger buckets run as
    fixed-width ladder passes and the menu extends to 16384."""
    from .bm.backend import prep_chunk_width

    return 16384 if prep_chunk_width(16384) else 4096


def _m_bucket_for(n_bucket: int, n_uniq: int) -> int:
    """Quantize the distinct-message bucket to the M_BUCKET_SHIFTS menu
    per n_bucket. The BM core's jit key includes m_bucket (stage 2 closes
    over it, stage 3's pair count is m+1), so an unquantized m would
    compile a fresh graph per committee-count — the 500k firehose probe
    hit minutes-long cold compiles per batch. The menu bounds graphs at
    len(M_BUCKET_SHIFTS) per (n, k); padded rows ride the row_mask into
    the pairing as identity pairs."""
    assert n_uniq <= n_bucket, (n_uniq, n_bucket)
    for shift in M_BUCKET_SHIFTS:
        m = max(1, n_bucket >> shift)
        if n_uniq <= m:
            return m
    raise AssertionError("menu ends at n_bucket >= n_uniq")


def stage_bm(sets, n, n_bucket, k_bucket, scalars=None, m_floor: int = 1):
    """Stage a batch into batch-minor tensors (the argument tuple of
    bm.backend.jitted_core) and return (args, m_bucket). Same
    hash-consing, padding, and random-scalar semantics as the batch-major
    staging above; `scalars` overrides the CSPRNG draw (deterministic
    callers: __graft_entry__); `m_floor` bounds the distinct-message
    bucket from below (sharded meshes: every shard of the minor m axis
    must be non-empty)."""
    from .bm import curves as bmc
    from .bm import h2c as bmh

    uniq: dict = {}
    inv_idx = np.zeros((n_bucket,), dtype=np.int32)
    for i, s in enumerate(sets):
        inv_idx[i] = uniq.setdefault(bytes(s.message), len(uniq))
    m_bucket = max(
        _m_bucket_for(n_bucket, len(uniq)), _next_pow2(max(1, m_floor))
    )
    u = np.zeros((2, 2, lb.L, m_bucket), dtype=lb.NP_DTYPE)
    u[..., : len(uniq)] = bmh.hash_to_field_bm_np(list(uniq.keys()))
    row_mask = np.zeros((m_bucket,), dtype=bool)
    row_mask[: len(uniq)] = True

    pk_pts = []
    for s in sets:
        pts = [pk.point for pk in s.signing_keys]
        pts += [None] * (k_bucket - len(pts))
        pk_pts.extend(pts)
    pk_pts += [None] * ((n_bucket - n) * k_bucket)
    # Flat minor order is (set, slot) with slot fastest: split the minor
    # axis and move the slot axis to the front -> (K, 3, L, n).
    pk_flat = bmc.g1_from_affine_np(pk_pts)              # (3, L, n*K)
    pk_proj = np.ascontiguousarray(np.moveaxis(
        pk_flat.reshape(3, lb.L, n_bucket, k_bucket), -1, 0
    ))

    sig_pts = [s.signature.point for s in sets] + [None] * (n_bucket - n)
    sig_proj = bmc.g2_from_affine_np(sig_pts)

    sig_checked = np.zeros((n_bucket,), dtype=bool)
    sig_checked[:n] = [s.signature.subgroup_checked for s in sets]
    sig_checked[n:] = True

    set_mask = np.zeros((n_bucket,), dtype=bool)
    set_mask[:n] = True

    if scalars is None:
        scalars = np.ones((n_bucket,), dtype=np.uint64)
        for i in range(n):
            r = 0
            while r == 0:
                r = secrets.randbits(_RAND_BITS)
            scalars[i] = r

    args = (
        jnp.asarray(u),
        jnp.asarray(inv_idx),
        jnp.asarray(row_mask),
        jnp.asarray(pk_proj),
        jnp.asarray(sig_proj),
        jnp.asarray(sig_checked),
        jnp.asarray(set_mask),
        jnp.asarray(scalars),
    )
    return args, m_bucket


def _verify_bm_impl(sets, n, n_bucket, k_bucket, m_floor: int,
                    n_devices: Optional[int] = None):
    """Run the batch-minor core (ops/bm/backend.py) on a staged batch.
    A mesh size (`n_devices`) places every staged tensor with its trailing
    (minor) batch axis sharded over the mesh and compiles the
    mesh-constrained core."""
    from .bm import backend as bmb

    sharded = n_devices is not None
    args, m_bucket = stage_bm(sets, n, n_bucket, k_bucket, m_floor=m_floor)
    if sharded:
        from lighthouse_tpu.parallel import mesh as pm

        mesh = pm.get_mesh(n_devices)
        args = tuple(pm.shard_batch_minor(a, mesh) for a in args)
    core = bmb.jitted_core(n_bucket, k_bucket, m_bucket, sharded=sharded,
                           n_devices=n_devices)
    return core(*args)


def pinned_verifier(sets, sharded: Optional[bool] = None):
    """find_invalid_sets' sub-batch verifier: each sub-batch is staged at
    the ROOT batch's (n, k, m) buckets, so isolating a poisoned set runs
    only the shapes the root batch compiled. Halving an all-distinct
    batch would otherwise compile three new stages per level (minutes
    each on the chip)."""
    good = [s for s in sets if not _host_rejects(s)]
    if not good:
        return verify_signature_sets_tpu
    sharded, _, n_bucket, k_bucket, m_floor = _buckets(good, sharded)
    n_uniq = len({bytes(s.message) for s in good})
    floors = (n_bucket, k_bucket,
              max(_m_bucket_for(n_bucket, n_uniq), m_floor))
    return lambda sub: bool(_verify_tpu_impl(sub, sharded, floors))


# Register with the API seam (mirrors define_mod! backend instantiation,
# crypto/bls/src/lib.rs:99-140).
_api.register_backend("tpu", verify_signature_sets_tpu)
_api.register_bisect_verifier("tpu", pinned_verifier)
