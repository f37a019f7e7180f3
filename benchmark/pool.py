"""The signature triples (public key, message, signature) that the bulk
cells send, made from the run's seed.

A pool holds `calls` slices of `sets_per_call` valid triples, one slice
for each call of a pass over the pool, a further slice for the warm-up,
and invalid triples of two kinds:

  wrong_message  signed by its key over another message than it carries;
  subgroup       a valid signature plus a point of order 13: on the curve
                 but outside G2, so that the verifier's subgroup check
                 rejects it. A verifier without that check would accept
                 it whenever its random scalar for the set is a multiple
                 of 13 (the pairing alone rejects it otherwise).

No message repeats within a pool, and pools of different seeds share
none. Secret keys are 64-bit: the size of a secret key changes no
verified work. Each triple's verdict follows from how it was made; the
plain reference (`reference.bls.verify`) re-verifies a sample of them,
drawn from the seed, after the window.

Signing runs in worker processes that never touch JAX. Their hash to G2
gives the reference's points (tests/test_pool.py), faster: square roots
in Fp2 from two in Fp, and the effective cofactor through the psi
endomorphism (RFC 9380 section 8.8.2) instead of a 636-bit multiply.
"""

from __future__ import annotations

import hashlib
import multiprocessing
import os
import random
import time

from .reference.bls12_381 import curves as c
from .reference.bls12_381 import fields as f
from .reference.bls12_381 import hash_to_curve as h2c
from .reference.bls12_381.constants import (BLS_X_ABS, H2, P, R, SSWU_A2,
                                            SSWU_B2, SSWU_Z2)

_INV2 = (P + 1) // 2


def _sqrt_fp(a: int):
    r = pow(a, (P + 1) // 4, P)          # p = 3 mod 4
    return r if r * r % P == a % P else None


def _sqrt_fp2(a):
    """A square root of a0 + a1*u (u^2 = -1) in Fp2, or None."""
    a0, a1 = a
    if a1 == 0:
        r = _sqrt_fp(a0)
        if r is not None:
            return (r, 0)
        r = _sqrt_fp(-a0 % P)
        return None if r is None else (0, r)
    gamma = _sqrt_fp((a0 * a0 + a1 * a1) % P)
    if gamma is None:
        return None
    x0 = _sqrt_fp((a0 + gamma) * _INV2 % P)
    if x0 is None:
        x0 = _sqrt_fp((a0 - gamma) * _INV2 % P)
        if x0 is None:
            return None
    x1 = a1 * pow(2 * x0, -1, P) % P
    return (x0, x1)


def _clear_cofactor(pt):
    """h_eff * P as [x^2 - x - 1]P + [x - 1]psi(P) + psi^2(2P)."""
    xp = c.g2_neg(c.g2_mul(pt, BLS_X_ABS))        # x is negative
    x2p = c.g2_neg(c.g2_mul(xp, BLS_X_ABS))
    t = c.g2_add(c.g2_add(x2p, c.g2_neg(xp)), c.g2_neg(pt))
    u = c.g2_psi(c.g2_add(xp, c.g2_neg(pt)))
    v = c.g2_psi(c.g2_psi(c.g2_add(pt, pt)))
    return c.g2_add(c.g2_add(t, u), v)


def _map_to_curve(u):
    """`hash_to_curve.map_to_curve_simple_swu_g2` with the faster root."""
    A, B, Z = SSWU_A2, SSWU_B2, SSWU_Z2
    zu2 = f.fp2_mul(Z, f.fp2_sqr(u))
    tv = f.fp2_add(f.fp2_sqr(zu2), zu2)
    if f.fp2_is_zero(tv):
        x1 = f.fp2_mul(B, f.fp2_inv(f.fp2_mul(Z, A)))
    else:
        x1 = f.fp2_mul(f.fp2_mul(f.fp2_neg(B), f.fp2_inv(A)),
                       f.fp2_add(f.FP2_ONE, f.fp2_inv(tv)))
    x, y = x1, _sqrt_fp2(f.fp2_add(f.fp2_mul(f.fp2_add(f.fp2_sqr(x1), A),
                                              x1), B))
    if y is None:
        x = f.fp2_mul(zu2, x1)
        y = _sqrt_fp2(f.fp2_add(f.fp2_mul(f.fp2_add(f.fp2_sqr(x), A), x),
                                B))
    return (x, f.fp2_neg(y) if f.fp2_sgn0(u) != f.fp2_sgn0(y) else y)


def hash_to_g2(msg: bytes):
    """The reference's `hash_to_curve.hash_to_g2(msg)`, faster."""
    u0, u1 = h2c.hash_to_field_fp2(msg, 2)
    return _clear_cofactor(c.g2_add(h2c.iso_map_g2(_map_to_curve(u0)),
                                    h2c.iso_map_g2(_map_to_curve(u1))))


def order13_point():
    """A point of order 13 on E2, made from fixed bytes."""
    order = R * H2
    while order % 13 == 0:
        order //= 13
    for i in range(64):
        u0, _ = h2c.hash_to_field_fp2(b"order13:%d" % i, 2)
        q = c.g2_mul(h2c.iso_map_g2(h2c.map_to_curve_simple_swu_g2(u0)),
                     order)
        while q is not None:
            q13 = c.g2_mul(q, 13)
            if q13 is None:
                return q
            q = q13
    raise RuntimeError("no point of order 13 found")


def message(seed: int, i: int) -> bytes:
    return hashlib.sha256(f"msg:{seed}:{i}".encode()).digest()


def _triple(job):
    """(seed, index, kind) -> (pk, sig)."""
    seed, i, kind = job
    sk = random.Random(f"sk:{seed}:{i}").getrandbits(64) | 1
    msg = message(seed, i)
    signed = hashlib.sha256(b"other:" + msg).digest() \
        if kind == "wrong_message" else msg
    sig = c.g2_mul(hash_to_g2(signed), sk)
    if kind == "subgroup":
        sig = c.g2_add(sig, _T13)
    return c.g1_mul(c.G1_GEN, sk), sig


_T13 = None


def _init_worker():
    global _T13
    _T13 = order13_point()


def make(seed: int, sets_per_call: int, calls: int, invalid: dict,
         workers: int = 0) -> dict:
    """{"triples": [(pk, msg, sig, valid)], "slices": [[index] * calls],
    "warm": [index], "invalid": {kind: [index]}, "made_s": seconds}.
    `workers` 0: all cores but two."""
    t0 = time.perf_counter()
    n_valid = sets_per_call * (calls + 1)
    kinds = ["valid"] * n_valid
    for kind in ("wrong_message", "subgroup"):
        kinds += [kind] * invalid.get(kind, 0)
    jobs = [(seed, i, k) for i, k in enumerate(kinds)]
    workers = workers or max(1, (os.cpu_count() or 3) - 2)
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(min(workers, len(jobs)), initializer=_init_worker) as pl:
        rows = pl.map(_triple, jobs, chunksize=max(1, len(jobs)
                                                  // (8 * workers)))
        pl.close()
        pl.join()
    triples = [(pk, message(seed, i), sig, kinds[i] == "valid")
               for i, (pk, sig) in enumerate(rows)]
    cut = list(range(0, n_valid + 1, sets_per_call))
    slices = [list(range(a, b)) for a, b in zip(cut, cut[1:])]
    return {
        "triples": triples,
        "slices": slices[:calls],
        "warm": slices[calls],
        "invalid": {k: [i for i, kk in enumerate(kinds) if kk == k]
                    for k in ("wrong_message", "subgroup")},
        "made_s": time.perf_counter() - t0,
    }
