"""Batch-minor optimal ate pairing: ops/pairing.py re-laid out.

Same projective inversion-free line functions, segmented Miller loop and
x-chain final exponentiation as ops/pairing.py (whose derivation comments
are authoritative). Pair batches ride the MINOR axis: P (..., 3, L, n),
Q (..., 3, 2, L, n); the per-pair Fp12 Miller values are tree-multiplied
along the minor axis into ONE final exponentiation."""

import jax
import jax.numpy as jnp

from lighthouse_tpu.crypto.bls.constants import BLS_X_ABS, P

from . import curves as cv
from . import limbs as lb
from . import tower as tw
from .. import pairing as _maj

_DBL_RUNS = _maj._DBL_RUNS
_TAIL_DBLS = _maj._TAIL_DBLS
_E_EXP = _maj._E_EXP


def _dbl_step(t, px, py, pz):
    """pairing._dbl_step batch-minor (same fused RCB doubling + line)."""
    X, Y, Z = cv.G2.coords(t)
    m1 = tw.fp2_mul(
        jnp.stack([Y, Y, Z, X, X], axis=-4),
        jnp.stack([Y, Z, Z, Y, X], axis=-4),
    )
    Y2, YZ, Z2 = m1[..., 0, :, :, :], m1[..., 1, :, :, :], m1[..., 2, :, :, :]
    XY, X2 = m1[..., 3, :, :, :], m1[..., 4, :, :, :]

    t2b = cv._b3_g2(Z2)
    z8 = cv.FP2.mul_small(Y2, 8)
    y3s = lb.add(Y2, t2b)
    t0p = lb.sub(Y2, cv.FP2.mul_small(t2b, 3))

    m2 = tw.fp2_mul(
        jnp.stack([t2b, YZ, t0p, t0p, X2, YZ, Y2, X2], axis=-4),
        jnp.stack([z8, z8, y3s, XY, X, Z, Z, Z], axis=-4),
    )
    q0, q1 = m2[..., 0, :, :, :], m2[..., 1, :, :, :]
    q2, q3 = m2[..., 2, :, :, :], m2[..., 3, :, :, :]
    X3c, YZ2 = m2[..., 4, :, :, :], m2[..., 5, :, :, :]
    Y2Z, X2Z = m2[..., 6, :, :, :], m2[..., 7, :, :, :]

    t_next = cv.G2.pack(lb.add(q3, q3), lb.add(q0, q2), q1)

    l1_raw = lb.sub(cv.FP2.mul_small(X3c, 3), lb.add(Y2Z, Y2Z))
    two_yz2 = lb.add(YZ2, YZ2)
    scaled = tw.fp2_mul_fp(
        jnp.stack([tw.fp2_mul_by_xi(two_yz2), cv.FP2.mul_small(X2Z, 3),
                   l1_raw], axis=-4),
        jnp.stack([py, px, pz], axis=-3),
    )
    l0 = scaled[..., 0, :, :, :]
    l2 = lb.neg(scaled[..., 1, :, :, :])
    l1 = scaled[..., 2, :, :, :]
    return t_next, (l0, l1, l2)


def _add_step(t, q, px, py, pz):
    """pairing._add_step batch-minor."""
    X1, Y1, Z1 = cv.G2.coords(t)
    xq, yq, zq = cv.G2.coords(q)
    m1 = tw.fp2_mul(
        jnp.stack([yq, xq, Y1, X1], axis=-4),
        jnp.stack([Z1, Z1, zq, zq], axis=-4),
    )
    n = lb.sub(m1[..., 0, :, :, :], m1[..., 2, :, :, :])
    d = lb.sub(m1[..., 1, :, :, :], m1[..., 3, :, :, :])
    m2 = tw.fp2_mul(
        jnp.stack([d, n, n, d], axis=-4),
        jnp.stack([Z1, X1, Z1, Y1], axis=-4),
    )
    dZ1, nX1, nZ1, dY1 = (m2[..., i, :, :, :] for i in range(4))
    scaled = tw.fp2_mul_fp(
        jnp.stack([tw.fp2_mul_by_xi(dZ1), nZ1, lb.sub(nX1, dY1)], axis=-4),
        jnp.stack([py, px, pz], axis=-3),
    )
    l0 = scaled[..., 0, :, :, :]
    l2 = lb.neg(scaled[..., 1, :, :, :])
    l1 = scaled[..., 2, :, :, :]
    return cv.G2.add(t, q), (l0, l1, l2)


def miller_loop_proj(p_proj, q_proj):
    """Batch-minor Miller loop on projective inputs: p (..., 3, L, n),
    q (..., 3, 2, L, n) -> f (..., 2, 3, 2, L, n)."""
    px = p_proj[..., 0, :, :]
    py = p_proj[..., 1, :, :]
    pz = p_proj[..., 2, :, :]
    t0 = q_proj
    acc0 = jnp.broadcast_to(
        tw.FP12_ONE, px.shape[:-2] + (2, 3, 2, lb.L) + px.shape[-1:]
    )

    def dbl_body(carry, _):
        acc, t = carry
        acc = tw.fp12_sqr(acc)
        t, (l0, l1, l2) = _dbl_step(t, px, py, pz)
        return (tw.fp12_mul_sparse_line(acc, l0, l1, l2), t), None

    carry = (acc0, t0)
    for run in _DBL_RUNS:
        carry, _ = jax.lax.scan(dbl_body, carry, None, length=run)
        acc, t = carry
        t, (l0, l1, l2) = _add_step(t, q_proj, px, py, pz)
        carry = (tw.fp12_mul_sparse_line(acc, l0, l1, l2), t)
    if _TAIL_DBLS:
        carry, _ = jax.lax.scan(dbl_body, carry, None, length=_TAIL_DBLS)
    acc, _t = carry
    return tw.fp12_conj(acc)


def _fp12_pow_abs(f, k: int):
    bits = bin(k)[2:]

    def sqr_body(acc, _):
        return tw.fp12_sqr(acc), None

    acc = f
    i = 1
    while i < len(bits):
        j = i
        while j < len(bits) and bits[j] == "0":
            j += 1
        run = (j - i) + (1 if j < len(bits) else 0)
        if run == 1:
            acc = tw.fp12_sqr(acc)
        elif run > 1:
            acc, _ = jax.lax.scan(sqr_body, acc, None, length=run)
        if j < len(bits):
            acc = tw.fp12_mul(acc, f)
        i = j + 1
    return acc


def _fp12_pow_window(f, k: int):
    """f^k for a DENSE fixed exponent: one 4-bit-window scan (table of
    f^0..f^15, then 4 squarings + 1 multiply per digit). Same multiply
    count as _fp12_pow_abs's square-and-multiply on the hard part's
    126-bit, 48-one-bit exponent (125 sqr + 44 mul vs 125 + 47), but one
    loop body instead of ~47 unrolled multiplies and separate squaring
    scans — the difference was ~80 s of a v5e compile (my CPU run, PR 21)."""
    digits = []
    while k:
        digits.append(k & 15)
        k >>= 4
    digits = digits[::-1]
    pows = [jnp.broadcast_to(tw.FP12_ONE, f.shape), f, tw.fp12_sqr(f)]
    for _ in range(13):
        pows.append(tw.fp12_mul(pows[-1], f))
    table = jnp.stack(pows, axis=0)

    def body(acc, digit):
        for _ in range(4):
            acc = tw.fp12_sqr(acc)
        return tw.fp12_mul(acc, table[digit]), None

    acc, _ = jax.lax.scan(body, table[digits[0]],
                          jnp.asarray(digits[1:], dtype=jnp.int32))
    return acc


def final_exponentiation(f):
    """pairing.final_exponentiation (x-chain decomposition), batch-minor."""
    t = tw.fp12_mul(tw.fp12_conj(f), tw.fp12_inv(f))
    t = tw.fp12_mul(tw.fp12_frob_n(t, 2), t)

    g1 = _fp12_pow_window(t, _E_EXP)
    g2 = tw.fp12_mul(
        tw.fp12_conj(_fp12_pow_abs(g1, BLS_X_ABS)), tw.fp12_frob(g1)
    )
    g2x2 = _fp12_pow_abs(_fp12_pow_abs(g2, BLS_X_ABS), BLS_X_ABS)
    g3 = tw.fp12_mul(
        tw.fp12_mul(g2x2, tw.fp12_frob_n(g2, 2)), tw.fp12_conj(g2)
    )
    return tw.fp12_mul(g3, t)


def miller_product_proj(p_proj, q_proj, mask):
    """prod_{i: mask} f_i of the Miller values, before the final
    exponentiation: p (3, L, n), q (3, 2, L, n), mask (n,) -> one Fp12
    with a trailing batch axis of 1."""
    f = miller_loop_proj(p_proj, q_proj)
    f = jnp.where(mask, f, jnp.broadcast_to(tw.FP12_ONE, f.shape))
    return lb.tree_reduce_minor(f, tw.fp12_mul, tw.FP12_ONE, f.shape[-1])


def multi_pairing_product_proj(p_proj, q_proj, mask):
    """prod_{i: mask} e(P_i, Q_i) with the pair axis MINOR:
    p (3, L, n), q (3, 2, L, n), mask (n,) -> raw Fp12 (final-exponentiated,
    trailing batch axis of 1). Renamed from multi_pairing_is_one_proj
    (ADVICE r5 #3): that name returns a BOOL in the major engine, and a
    caller porting code between engines would treat this truthy array as
    the check result."""
    return final_exponentiation(miller_product_proj(p_proj, q_proj, mask))


def multi_pairing_is_one_proj(p_proj, q_proj, mask):
    """prod_{i: mask} e(P_i, Q_i) == 1 -> () bool — the major engine's
    (ops/pairing.py) contract, so code ports between engines unchanged."""
    return tw.fp12_is_one(
        multi_pairing_product_proj(p_proj, q_proj, mask)
    )[..., 0]


multi_pairing_check = multi_pairing_is_one_proj
