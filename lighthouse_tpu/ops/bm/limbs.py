"""Batch-minor limb layer: ops/limbs.py arithmetic with limbs at axis -2.

Every function here is the batch-minor twin of the same-named function in
ops/limbs.py; the digit bounds, carry-pass structure, NTT/CRT plan and
non-negativity offsets are IMPORTED from there (the exactness proofs in
that module's docstrings apply verbatim — the arithmetic per (limb, batch
element) pair is identical, only the axis the limbs live on changes).

Element layout: (..., L, n) — limb axis -2, batch axis -1 (minor/lanes).
Matmuls against constant matrices contract from the left so the batch
stays minor end to end (see ops/bm/__init__.py).
"""

import numpy as np

import jax
import jax.numpy as jnp

from lighthouse_tpu.crypto.bls.constants import P

from .. import limbs as _maj

# Shared layout constants (identical values; re-exported for the BM tower).
B = _maj.B
L = _maj.L
RADIX = _maj.RADIX
W_IN = _maj.W_IN
NCOLS = _maj.NCOLS
DTYPE = _maj.DTYPE
NP_DTYPE = _maj.NP_DTYPE
_INV_RADIX = _maj._INV_RADIX

int_to_limbs = _maj.int_to_limbs

# Module constants with a trailing singleton batch dim (broadcast-ready).
P_LIMBS = _maj.P_LIMBS[:, None]
ZERO = jnp.zeros((L, 1), dtype=DTYPE)
ONE_MONT = jnp.zeros((L, 1), dtype=DTYPE).at[0, 0].set(1.0)
_T_FOLD = _maj._T_FOLD                      # (R, L): contracted from the left
_OFFSET_SQ = _maj._OFFSET_SQ[:, None]       # (W_IN, 1)
_SQ_BIAS = _maj._SQ_BIAS


# --- Host staging ---------------------------------------------------------------


def ints_to_bm_np(xs) -> np.ndarray:
    """Host staging: iterable of Python ints -> (L, n) canonical digits
    (batch minor, numpy). Same byte-view vectorization as
    limbs.ints_to_mont."""
    assert B == 8
    buf = b"".join((x % P).to_bytes(L, "little") for x in xs)
    arr = np.frombuffer(buf, dtype=np.uint8).reshape(-1, L)
    return np.ascontiguousarray(arr.T).astype(NP_DTYPE)


def ints_to_bm(xs) -> jnp.ndarray:
    return jnp.asarray(ints_to_bm_np(xs), dtype=DTYPE)


def bm_to_ints(v) -> list:
    """(..., L, n) lazy limbs -> flat list of canonical ints (batch order:
    trailing axis fastest within each leading index)."""
    arr = np.asarray(v, dtype=np.float64)
    arr = np.moveaxis(arr, -2, -1)           # (..., n, L)
    flat = arr.reshape(-1, L)
    return [
        sum(int(row[i]) << (B * i) for i in range(L)) % P for row in flat
    ]


# --- Carry machinery (axis -2) --------------------------------------------------


def _pad_limbs(x, width: int):
    if x.shape[-2] >= width:
        return x
    pad = jnp.zeros(
        x.shape[:-2] + (width - x.shape[-2],) + x.shape[-1:], dtype=x.dtype
    )
    return jnp.concatenate([x, pad], axis=-2)


def _carry_pass(x):
    hi = jnp.floor(x * _INV_RADIX)
    lo = x - hi * RADIX
    return lo + jnp.concatenate(
        [jnp.zeros_like(hi[..., :1, :]), hi[..., :-1, :]], axis=-2
    )


def _passes(x, n: int):
    for _ in range(n):
        x = _carry_pass(x)
    return x


import os as _os

# Constant-matmul formulation: "matmul" (broadcast batched jnp.matmul) or
# "einsum" — A/B'd on chip by scripts/probe_bm.py; both contract the limb
# axis from the left with the batch minor. Default is platform-keyed:
# XLA:CPU's eager thunk runtime cannot execute a BATCHED bf16 dot
# (DotThunk "BF16 x BF16 = F32" — the same limitation behind the
# per-prime dots in limbs._inv_gammas), so CPU uses the einsum lowering.
def _default_mm():
    return "einsum" if jax.default_backend() == "cpu" else "matmul"


# Resolved LAZILY on the first _matmul_const call (ADVICE r5 #1): reading
# jax.default_backend() at import time both forced backend initialization
# on import and froze a stale choice when the platform was selected after
# `import lighthouse_tpu.ops.bm` — on CPU the frozen "matmul" path then
# hit the batched-bf16 DotThunk failure at runtime.
_MM = None


def _mm_mode() -> str:
    global _MM
    if _MM is None:
        _MM = _os.environ.get("LIGHTHOUSE_TPU_BM_MM", "") or _default_mm()
    return _MM


def _matmul_const(m, x):
    """out[..., c, n] = sum_k m[c, k] * x[..., k, n] (bf16 x bf16 -> f32
    on the MXU); m is pre-transposed (out_cols, k)."""
    if _mm_mode() == "einsum":
        return jnp.einsum(
            "ck,...kn->...cn", m, x.astype(jnp.bfloat16),
            preferred_element_type=DTYPE,
        )
    return jnp.matmul(
        m, x.astype(jnp.bfloat16), preferred_element_type=DTYPE
    )


def _fold_dot(hi, nrows: int):
    """(..., nrows, n) high columns x (nrows, L) fold rows -> (..., L, n),
    contracted on the MXU with the batch minor (bounds: limbs._fold_dot)."""
    rows = _T_FOLD[:nrows]
    return _matmul_const(rows.T.astype(jnp.bfloat16), hi)


def _squeeze(x):
    """Batch-minor twin of limbs._squeeze (same digit-bound proof)."""
    y = _passes(_pad_limbs(x, W_IN) + _OFFSET_SQ, 2)
    return _carry_pass(y + _SQ_BIAS)


def _fold_small(x, nrows: int):
    out = x[..., :L, :]
    for j in range(nrows):
        out = out + x[..., L + j : L + j + 1, :] * _T_FOLD[j][:, None]
    return out


def _reduce_light(x):
    """Batch-minor twin of limbs._reduce_light (same round structure and
    2^388.4 output bound; see that docstring and tests/test_limbs_headroom)."""
    w = x.shape[-2]
    x = _passes(_pad_limbs(x, w + 3), 3)
    x = x[..., :L, :] + _fold_dot(x[..., L:, :], w + 3 - L)
    for _ in range(2):
        x = _passes(_pad_limbs(x, L + 3), 2)
        x = _fold_small(x, 3)
    x = _passes(_pad_limbs(x, L + 3), 2)
    return _fold_small(x, 3)


def _reduce(x, folds: int = 5):
    """Batch-minor twin of limbs._reduce (same worst-case round bounds)."""
    w = x.shape[-2]
    x = _passes(_pad_limbs(x, w + 3), 3)
    x = x[..., :L, :] + _fold_dot(x[..., L:, :], w + 3 - L)
    for _ in range(folds):
        x = _passes(_pad_limbs(x, L + 3), 2)
        x = _fold_small(x, 3)
    return _passes(_pad_limbs(x, L + 3), 2)[..., :L, :]


# --- NTT / CRT (plans shared with the standard engine) --------------------------

_PLAN3 = _maj._PLAN3
plan4 = _maj.plan4


def _p_col(plan):
    return plan.p_col[..., None]             # (n_p, 1, 1)


def _inv_p_col(plan):
    return plan.inv_p_col[..., None]


def _v_all_t(plan):
    """(n_p*NCOLS, W_IN) transposed forward-evaluation matrix (cached on
    the plan object; entries bf16-exact)."""
    vt = getattr(plan, "_bm_v_all_t", None)
    if vt is None:
        vt = jnp.asarray(plan.v_all_np.T, dtype=jnp.bfloat16)
        plan._bm_v_all_t = vt
    return vt


def _w_blocks_t(plan):
    wt = getattr(plan, "_bm_w_blocks_t", None)
    if wt is None:
        wt = [
            jnp.asarray(plan.w_np[j].T, dtype=jnp.bfloat16)
            for j in range(plan.n_p)
        ]
        plan._bm_w_blocks_t = wt
    return wt


def ntt_fwd(x, plan=_PLAN3):
    """Squeezed digits (..., W_IN, n) -> centered residues
    (..., n_p, NCOLS, n). Bounds: limbs.ntt_fwd."""
    e = _matmul_const(_v_all_t(plan), x)
    e = e.reshape(e.shape[:-2] + (plan.n_p, NCOLS) + e.shape[-1:])
    return e - _p_col(plan) * jnp.round(e * _inv_p_col(plan))


def ntt_center(x, plan=_PLAN3):
    return x - _p_col(plan) * jnp.round(x * _inv_p_col(plan))


def ntt_fwd_lazy(x, plan=_PLAN3):
    return ntt_fwd(_squeeze(x), plan)


def _crt_renorm(limbs):
    out = []
    carry = 0.0
    for v in limbs[:-1]:
        v = v + carry
        c = jnp.floor(v * _INV_RADIX)
        out.append(v - c * RADIX)
        carry = c
    out.append(limbs[-1] + carry)
    return out


def _inv_gammas(prod, plan):
    """(..., n_p, NCOLS, n) centered residues -> n_p gammas (..., NCOLS, n).
    Bounds: limbs._inv_gammas (CRT weight folded into the matrices)."""
    wt = _w_blocks_t(plan)
    gs = []
    for j, p in enumerate(plan.primes):
        gj = _matmul_const(wt[j], prod[..., j, :, :])
        gs.append(gj - float(p) * jnp.round(gj * float(1.0 / p)))
    return gs


def ntt_inv_cols_fast(prod, plan=_PLAN3):
    """Exact-floor CRT reconstruction, batch-minor. The margin contract and
    the exactness proof are limbs.ntt_inv_cols_fast's verbatim; columns
    live on axis -2 here."""
    gs = _inv_gammas(prod, plan)
    nl = plan.NL
    S = [
        sum(gs[j] * float(plan.m_digits[j, l]) for j in range(plan.n_p))
        for l in range(nl)
    ]
    qhat = sum(gs[j] * float(1.0 / p) for j, p in enumerate(plan.primes))
    t = jnp.floor(qhat)
    md = list(plan.M_digits)
    r = _crt_renorm(
        [s - t * float(m) for s, m in zip(S, md)] + [jnp.zeros_like(S[0])]
    )
    nd = r[0].ndim
    parts = []
    for l, v in enumerate(r):
        pad = [(0, 0)] * (nd - 2) + [(l, nl - l), (0, 0)]
        parts.append(jnp.pad(v, pad))
    return sum(parts)


# Domain offsets with the trailing batch dim (cached: device constants
# must exist BEFORE any jit trace — a constant created lazily inside a
# trace leaks that trace's buffer, the UnexpectedTracerError documented
# at ops/tower.py's eager-constant block).
_OFFSETS = {}


def offset_dom3():
    if "d3" not in _OFFSETS:
        _OFFSETS["d3"] = jnp.asarray(
            _maj.offset_dom3_np()[..., None], dtype=DTYPE
        )
    return _OFFSETS["d3"]


def offset_dom4():
    if "d4" not in _OFFSETS:
        _OFFSETS["d4"] = jnp.asarray(
            _maj.offset_dom4_np()[..., None], dtype=DTYPE
        )
    return _OFFSETS["d4"]


def _offset_dom3_mul():
    if "d3m" not in _OFFSETS:
        _OFFSETS["d3m"] = _maj.offset_dom3_mul()[..., None]
    return _OFFSETS["d3m"]


def ntt_dom_to_limbs(c, plan, offset_dom, light: bool = False):
    """Signed domain combination -> loose-canonical limbs (..., L, n).
    Margin contract: limbs.ntt_dom_to_limbs."""
    cols = ntt_inv_cols_fast(ntt_center(c + offset_dom, plan), plan)
    return _reduce_light(cols) if light else _reduce(cols)


# --- Core multiply --------------------------------------------------------------


def mul(a, b):
    a, b = jnp.broadcast_arrays(a, b)
    fa = ntt_fwd(_squeeze(a))
    fb = ntt_fwd(_squeeze(b))
    return _reduce(
        ntt_inv_cols_fast(ntt_center(fa * fb + _offset_dom3_mul()))
    )


def sqr(a):
    fa = ntt_fwd(_squeeze(a))
    return _reduce(
        ntt_inv_cols_fast(ntt_center(fa * fa + _offset_dom3_mul()))
    )


mont_mul = mul
mont_sqr = sqr


def add(a, b):
    return a + b


def sub(a, b):
    return a - b


def neg(a):
    return -a


# --- Canonicalization & comparisons ---------------------------------------------

_CP_DIGITS = [_maj._CP_DIGITS[i][:, None] for i in range(len(_maj._CP_ROUNDS))]


def _lookahead(g, p):
    def comb(x, y):
        gx, px = x
        gy, py = y
        return jnp.logical_or(gy, jnp.logical_and(py, gx)), \
            jnp.logical_and(px, py)

    return jax.lax.associative_scan(comb, (g, p), axis=-2)[0]


def _borrow_sub(x, c_digits):
    d = x - c_digits
    borrow = _lookahead(d < 0, d == 0)
    b_prev = jnp.concatenate(
        [jnp.zeros_like(borrow[..., :1, :]), borrow[..., :-1, :]], axis=-2
    )
    r = d - b_prev.astype(DTYPE) + borrow.astype(DTYPE) * RADIX
    return r, borrow[..., -1, :]


def _unique_digits(x):
    carry = _lookahead(x >= RADIX, x == RADIX - 1)
    c_prev = jnp.concatenate(
        [jnp.zeros_like(carry[..., :1, :]), carry[..., :-1, :]], axis=-2
    )
    return x + c_prev.astype(DTYPE) - carry.astype(DTYPE) * RADIX


def canonicalize(a):
    x = _reduce(_squeeze(a))
    for cd in _CP_DIGITS:
        r, under = _borrow_sub(x, cd)
        x = jnp.where(under[..., None, :], x, r)
    return _unique_digits(x)


def is_zero(a):
    return jnp.all(canonicalize(a) == 0, axis=-2)


def eq(a, b):
    return is_zero(a - b)


def select(mask, a, b):
    """mask (..., n) bool -> limbwise select over (..., L, n)."""
    return jnp.where(mask[..., None, :], a, b)


# Leading-axis tree reduction (the K/pubkey axis): the standard engine's
# implementation is layout-agnostic given a broadcastable identity.
tree_reduce = _maj.tree_reduce


def tree_reduce_minor(vals, combine, identity, axis_size: int):
    """Reduce (..., n) along the trailing batch axis in log2 depth, padding
    with `identity` (shape broadcastable with trailing 1). Returns the
    combined element with a trailing batch axis of size 1."""
    n = 1
    while n < axis_size:
        n *= 2
    if n != axis_size:
        pad = jnp.broadcast_to(
            identity, vals.shape[:-1] + (n - axis_size,)
        )
        vals = jnp.concatenate([vals, pad], axis=-1)
    while n > 1:
        half = n // 2
        vals = combine(vals[..., :half], vals[..., half:])
        n = half
    return vals


def pow_fixed(a, exponent: int):
    """Batch-minor twin of limbs.pow_fixed (4-bit windowed scan)."""
    if exponent == 0:
        return jnp.broadcast_to(ONE_MONT, a.shape)
    if exponent < 16:
        acc = a
        for c in bin(exponent)[3:]:
            acc = sqr(acc)
            if c == "1":
                acc = mul(acc, a)
        return acc
    digits = []
    e = exponent
    while e:
        digits.append(e & 15)
        e >>= 4
    digits = digits[::-1]

    pows = [jnp.broadcast_to(ONE_MONT, a.shape), a, sqr(a)]
    for _ in range(13):
        pows.append(mul(pows[-1], a))
    table = jnp.stack(pows, axis=0)

    def body(acc, digit):
        acc = sqr(sqr(sqr(sqr(acc))))
        return mul(acc, table[digit]), None

    init = table[digits[0]]
    ds = jnp.asarray(digits[1:], dtype=jnp.int32)
    acc, _ = jax.lax.scan(body, init, ds)
    return acc


def inv(a):
    return pow_fixed(a, P - 2)


# Eager constant materialization (see the offset-cache comment above):
# every device constant this module can reach inside a traced function is
# built here, at import, outside any trace.
for _plan in (_PLAN3, plan4()):
    _v_all_t(_plan)
    _w_blocks_t(_plan)
offset_dom3()
offset_dom4()
_offset_dom3_mul()


def batch_inv(x):
    """Invert every trailing-axis element of (..., L, n) with one Fermat
    ladder (limbs.batch_inv, scans over the batch axis = -1 here). Rows
    must be nonzero (same zero caveat)."""
    n = x.shape[-1]
    if n == 1:
        return inv(x)
    ax = x.ndim - 1
    pre = jax.lax.associative_scan(mul, x, axis=ax)
    suf = jax.lax.associative_scan(mul, x, axis=ax, reverse=True)
    t = inv(pre[..., -1:])
    one = jnp.broadcast_to(ONE_MONT, x.shape[:-1] + (1,))
    left = jnp.concatenate([one, pre[..., :-1]], axis=-1)
    right = jnp.concatenate([suf[..., 1:], one], axis=-1)
    return mul(mul(left, right), t)
