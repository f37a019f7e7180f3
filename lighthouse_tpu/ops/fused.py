"""Pallas-fused NTT transform kernels (round 4, NOTES lever #3).

The round-3 profile showed the field layer HBM-bound, not MXU-bound: a
single multiply's squeeze -> forward -> interpolate -> CRT -> reduce
chain is ~25 small XLA ops, each a full HBM round-trip over the batch
(the matmuls themselves are a few percent of the time). These kernels
collapse the two elementwise-heavy chains into one VMEM-resident pass
each:

  * ``squeeze_fwd(x, plan)``  — digit squeeze (3 carry passes) + forward
    evaluation matmul + centering: HBM traffic drops from ~8 round trips
    to read-digits/write-residues.
  * ``inv_out(c, plan, offset)`` — centering (+ optional non-negativity
    offset polynomial), per-prime Lagrange interpolation matmuls, exact
    CRT recombination, and the full fold/reduce chain (~25 round trips)
    to read-residues/write-digits.

Semantics are IDENTICAL to the limbs.py reference implementations (the
exactness proofs live there; the constant tables are passed as kernel
operands — Pallas does not allow captured array constants — and the
small-prime scalars ride as python-float literals). Differential tests:
tests/test_ops_fused.py runs both paths on the same inputs (interpret
mode on CPU, compiled on TPU).

Enable/disable with LIGHTHOUSE_TPU_PALLAS:
  * "0"  (the DEFAULT, everywhere) — XLA implementations; the round-4
    chip A/B showed the kernels win standalone (11.0 vs 14.8 ms per
    multiply at 12288 rows) but LOSE in the full pipeline (0.776 s vs
    0.534 s at n=1024) because they break XLA's cross-op fusion domain
    (see _default_mode and NOTES_TPU_PERF.md);
  * "1"  — compiled Pallas kernels (experiments);
  * "interpret" — run the kernels through the Pallas interpreter
    (correctness testing on CPU; slow).
"""

import os
from functools import lru_cache

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import limbs as lb

# --------------------------------------------------------------------------
# Mode selection
# --------------------------------------------------------------------------

_MODE = os.environ.get("LIGHTHOUSE_TPU_PALLAS", "")

# Trace-time disable depth: pallas_call does not partition under a pjit
# mesh (it would force a gather), so the sharded verify path traces with
# fusion off (ops/backend.py wraps its sharded stages in `disabled()`).
_DISABLE = 0


class disabled:
    """Context manager: force the XLA fallback within the scope (used
    while TRACING graphs that run under a sharding mesh)."""

    def __enter__(self):
        global _DISABLE
        _DISABLE += 1
        return self

    def __exit__(self, *exc):
        global _DISABLE
        _DISABLE -= 1
        return False


def _default_mode() -> str:
    # Default OFF (round-4 A/B on the chip): the two-kernel split wins
    # ~26% on a standalone multiply (11.0 vs 14.8 ms at 12288 rows,
    # fetch-verified) but LOSES in the full three-stage pipeline (0.776s
    # vs 0.534s at n=1024) — XLA's cross-op fusion over the big stage
    # graphs beats the per-op kernels. Set LIGHTHOUSE_TPU_PALLAS=1 to
    # re-enable for experiments; "interpret" for CPU correctness tests.
    return "0"


def enabled() -> bool:
    global _MODE
    if _DISABLE:
        return False
    if _MODE == "":
        _MODE = _default_mode()
    return _MODE in ("1", "interpret")


def _interpret() -> bool:
    return _MODE == "interpret"


# --------------------------------------------------------------------------
# Kernel bodies (pure jnp on VMEM-resident values; constant tables arrive
# as operands, small primes as python-float literals). Logic mirrors
# limbs.py bit-for-bit — see the exactness-bound docstrings there.
# --------------------------------------------------------------------------

_L = lb.L
_W = lb.W_IN
_N = lb.NCOLS


def _fwd_body(x, off, v, p_row, inv_row):
    """(BLK, L) digits -> (BLK, n_p * NCOLS) FLAT centered residues.

    The prime axis stays flat inside the kernel: Mosaic cannot shape-cast
    the lane dimension (404 -> (4, 101)); the wrapper reshapes in XLA.
    Centering rides flat per-lane constant rows (p_row / inv_row)."""
    y = lb._passes(lb._pad_cols(x, _W) + off, 2)
    y = lb._carry_pass(y + lb._SQ_BIAS)                 # squeezed [0, 256]
    e = jax.lax.dot_general(
        y.astype(jnp.bfloat16), v,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                   # (BLK, n_p*N)
    return e - p_row * jnp.round(e * inv_row)


def _crt_renorm(limbs):
    out = []
    carry = 0.0
    for v in limbs[:-1]:
        v = v + carry
        c = jnp.floor(v * (1.0 / 256.0))
        out.append(v - c * 256.0)
        carry = c
    out.append(limbs[-1] + carry)
    return out


def _reduce_body(x, tfold):
    """limbs._reduce with the fold table as an operand (same rounds)."""
    w = x.shape[-1]
    x = lb._passes(lb._pad_cols(x, w + 3), 3)
    hi = x[..., _L:]
    fold = jax.lax.dot_general(
        hi.astype(jnp.bfloat16), tfold[:hi.shape[-1]].astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    x = x[..., :_L] + fold
    for _ in range(5):
        x = lb._passes(lb._pad_cols(x, _L + 3), 2)
        out = x[..., :_L]
        for j in range(3):
            # slice_in_dim, not integer indexing: jnp's int-index lowers
            # to a gather, which Mosaic cannot lower.
            col = jax.lax.slice_in_dim(x, _L + j, _L + j + 1, axis=-1)
            row = jax.lax.slice_in_dim(tfold, j, j + 1, axis=0)
            out = out + col * row
        x = out
    return lb._passes(lb._pad_cols(x, _L + 3), 2)[..., :_L]


def _inv_body(c, w, tfold, plan, offset):
    """(BLK, n_p, NCOLS) residues -> (BLK, L) loose-canonical digits.

    Mirrors ntt_center(+offset) -> ntt_inv_cols -> _reduce. The prime
    axis is indexed (never reshaped — Mosaic lane-dim constraint); each
    per-prime slice is a plain (BLK, NCOLS) tile."""
    gs = []
    for j, p in enumerate(plan.primes):
        cj = c[:, j, :]
        if offset is not None:
            cj = cj + offset[:, j, :]           # (1, N): 2D broadcast
        cj = cj - float(p) * jnp.round(cj * float(1.0 / p))
        gj = jax.lax.dot_general(
            cj.astype(jnp.bfloat16), w[j].astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        gs.append(gj - float(p) * jnp.round(gj * float(1.0 / p)))
    nl = plan.NL
    S = [
        sum(gs[j] * float(plan.m_digits[j, l]) for j in range(plan.n_p))
        for l in range(nl)
    ]
    S.append(jnp.zeros_like(S[0]))
    S = _crt_renorm(S)
    s_f = sum(s * float(256.0 ** l) for l, s in enumerate(S))
    t = jnp.floor(s_f * plan.inv_M)
    md = [float(m) for m in plan.M_digits] + [0.0]
    r = _crt_renorm([s - t * m for s, m in zip(S, md)])
    neg = (r[-1] < 0).astype(jnp.float32)
    r = _crt_renorm([v + neg * m for v, m in zip(r, md)])
    ge = r[-1] > 0
    eq_run = r[-1] == 0
    for l in range(nl - 1, 0, -1):
        ge = ge | (eq_run & (r[l] > md[l]))
        eq_run = eq_run & (r[l] == md[l])
    ge = (ge | (eq_run & (r[0] >= md[0]))).astype(jnp.float32)
    r = _crt_renorm([v - ge * m for v, m in zip(r, md)])
    # Assemble columns: limb l of column k lands at column k + l
    # (concatenate-based — jnp.pad does not lower in Mosaic).
    blk = r[0].shape[0]

    def shifted(v, l):
        parts = []
        if l:
            parts.append(jnp.zeros((blk, l), dtype=v.dtype))
        parts.append(v)
        if nl - l:
            parts.append(jnp.zeros((blk, nl - l), dtype=v.dtype))
        # (zero-width segments are skipped: Mosaic rejects 0-sized dims)
        return jnp.concatenate(parts, axis=-1) if len(parts) > 1 else v

    cols = shifted(r[0], 0)
    for l in range(1, len(r)):
        cols = cols + shifted(r[l], l)
    return _reduce_body(cols, tfold)


# --------------------------------------------------------------------------
# pallas_call wrappers (rows-blocked grid; constant tables as operands)
# --------------------------------------------------------------------------


def _pick_blk(rows: int) -> int:
    for cand in (512, 256, 128, 64, 32, 16):
        if rows >= cand:
            return cand
    return 8


def _plan(n_p: int):
    return lb._PLAN3 if n_p == 3 else lb.plan4()


def _const(spec_shape):
    """BlockSpec for a full-array constant operand (same block each step)."""
    nd = len(spec_shape)
    return pl.BlockSpec(spec_shape, lambda i: (0,) * nd)


@lru_cache(maxsize=None)
def _fwd_consts(n_p: int):
    # NUMPY (not jnp) so a first call inside a jit trace cannot cache a
    # tracer (round-3's UnexpectedTracerError lesson, tower.py:70-78);
    # np operands become per-executable constants.
    plan = _plan(n_p)
    off = np.asarray(lb._OFFSET_SQ_NP[None, :], dtype=np.float32)  # (1, W)
    v = np.asarray(plan.v_all_np, dtype=jnp.bfloat16)  # (W, n_p*N)
    p_row = np.repeat(np.asarray(plan.primes, dtype=np.float32), _N)
    p_row = p_row[None, :]                             # (1, n_p*N)
    inv_row = (1.0 / p_row).astype(np.float32)
    return off, v, p_row, inv_row


def _note_kernel_build(kernel: str, **shape_args) -> None:
    """One event per distinct Pallas kernel instantiation (fires at
    lru_cache miss inside the builders, i.e. at trace time, never inside
    the compiled graph). Guarded: observability must not break kernels."""
    try:
        from lighthouse_tpu.common.metrics import REGISTRY
        from lighthouse_tpu.observability import trace

        REGISTRY.counter_vec(
            "engine_pallas_kernel_builds_total",
            "Distinct Pallas kernel instantiations, by kernel",
            "kernel").labels(kernel).inc()
        trace.instant(f"pallas_build:{kernel}", cat="compile",
                      **shape_args)
    except Exception:
        pass


@lru_cache(maxsize=None)
def _fwd_call(rows_p: int, blk: int, n_p: int, interpret: bool):
    _note_kernel_build("ntt_fwd", rows_p=rows_p, blk=blk, n_p=n_p)

    def kernel(x_ref, off_ref, v_ref, p_ref, ip_ref, o_ref):
        # Constants stay 2D ((1, n) broadcasts): Mosaic rejects 1D vectors.
        o_ref[:, :] = _fwd_body(
            x_ref[:, :], off_ref[:, :], v_ref[:, :],
            p_ref[:, :], ip_ref[:, :],
        )

    return pl.pallas_call(
        kernel,
        grid=(rows_p // blk,),
        in_specs=[
            pl.BlockSpec((blk, _L), lambda i: (i, 0)),
            _const((1, _W)),
            _const((_W, n_p * _N)),
            _const((1, n_p * _N)),
            _const((1, n_p * _N)),
        ],
        out_specs=pl.BlockSpec((blk, n_p * _N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, n_p * _N), jnp.float32),
        interpret=interpret,
    )


@lru_cache(maxsize=None)
def _inv_consts(n_p: int, with_offset: bool):
    # NUMPY for the same tracer-safety reason as _fwd_consts.
    plan = _plan(n_p)
    w = np.asarray(plan.w_np, dtype=jnp.bfloat16)           # (n_p, N, N)
    tfold = np.asarray(lb._T_FOLD_NP, dtype=np.float32)     # (rows, L)
    if with_offset:
        off_np = lb.offset_dom3_np() if n_p == 3 else lb.offset_dom4_np()
        off = np.asarray(off_np[None], dtype=np.float32)    # (1, n_p, N)
        return w, tfold, off
    return w, tfold, None


@lru_cache(maxsize=None)
def _inv_call(rows_p: int, blk: int, n_p: int, with_offset: bool,
              interpret: bool):
    _note_kernel_build("ntt_inv", rows_p=rows_p, blk=blk, n_p=n_p,
                       with_offset=with_offset)
    plan = _plan(n_p)
    nfold = lb._T_FOLD_NP.shape[0]

    if with_offset:
        def kernel(c_ref, w_ref, t_ref, off_ref, o_ref):
            o_ref[:, :] = _inv_body(
                c_ref[:, :, :], w_ref, t_ref[:, :], plan, off_ref
            )
    else:
        def kernel(c_ref, w_ref, t_ref, o_ref):
            o_ref[:, :] = _inv_body(
                c_ref[:, :, :], w_ref, t_ref[:, :], plan, None
            )

    in_specs = [
        pl.BlockSpec((blk, n_p, _N), lambda i: (i, 0, 0)),
        _const((n_p, _N, _N)),
        _const((nfold, _L)),
    ]
    if with_offset:
        in_specs.append(_const((1, n_p, _N)))

    return pl.pallas_call(
        kernel,
        grid=(rows_p // blk,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((blk, _L), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, _L), jnp.float32),
        interpret=interpret,
    )


def _pad_rows(x, blk: int):
    rows = x.shape[0]
    rows_p = ((rows + blk - 1) // blk) * blk
    if rows_p != rows:
        pad = jnp.zeros((rows_p - rows,) + x.shape[1:], dtype=x.dtype)
        x = jnp.concatenate([x, pad], axis=0)
    return x, rows_p


def squeeze_fwd(x, plan):
    """Fused limbs.ntt_fwd_lazy: (..., L) lazy digits -> (..., n_p, NCOLS)
    centered residues. (Kernel emits the prime axis FLAT; the reshape
    happens here, in XLA, where lane splits are legal.)"""
    shape = x.shape[:-1]
    xf = x.reshape((-1, _L))
    rows = xf.shape[0]
    blk = _pick_blk(rows)
    xf, rows_p = _pad_rows(xf, blk)
    off, v, p_row, inv_row = _fwd_consts(plan.n_p)
    # x64 must be OFF while tracing the kernel: the package enables
    # jax_enable_x64 globally (ops/__init__.py) and Mosaic cannot
    # legalize the 64-bit index/literal types it injects.
    with jax.enable_x64(False):
        out = _fwd_call(rows_p, blk, plan.n_p, _interpret())(
            xf, off, v, p_row, inv_row)
    return out[:rows].reshape(shape + (plan.n_p, _N))


def inv_out(c, plan, with_offset: bool):
    """Fused ntt_center(+offset) -> ntt_inv_cols -> _reduce:
    (..., n_p, NCOLS) residues -> (..., L) loose-canonical digits."""
    shape = c.shape[:-2]
    cf = c.reshape((-1, plan.n_p, _N))
    rows = cf.shape[0]
    blk = _pick_blk(rows)
    cf, rows_p = _pad_rows(cf, blk)
    consts = _inv_consts(plan.n_p, with_offset)
    args = [cf] + [a for a in consts if a is not None]
    with jax.enable_x64(False):        # see squeeze_fwd
        out = _inv_call(
            rows_p, blk, plan.n_p, with_offset, _interpret())(*args)
    return out[:rows].reshape(shape + (_L,))


# ==========================================================================
# Whole-op fused tower kernels (round 4 "K3"): one pallas_call per tower
# multiply — squeeze/forward, the NTT-domain schoolbook combination, and
# interpolation/CRT/reduce all happen in VMEM. At production batch sizes
# the XLA path's domain tensors (n, 12, n_p, 101) are tens of MB and every
# pointwise combination op round-trips HBM; here they never leave the
# chip. Residues ride PER-PRIME lists of (blk, NCOLS) tiles, so no lane
# reshapes/slices ever happen (Mosaic constraints).
# ==========================================================================

_K3_BLK = 128


def _k3_consts(n_p: int):
    plan = _plan(n_p)
    off = np.asarray(lb._OFFSET_SQ_NP[None, :], dtype=np.float32)
    # Forward matrices per prime: (n_p, W, N) bf16.
    v = np.asarray(
        plan.v_all_np.reshape(_W, n_p, _N).transpose(1, 0, 2),
        dtype=jnp.bfloat16,
    )
    w = np.asarray(plan.w_np, dtype=jnp.bfloat16)           # (n_p, N, N)
    tfold = np.asarray(lb._T_FOLD_NP, dtype=np.float32)     # (rows, L)
    off_np = lb.offset_dom3_np() if n_p == 3 else lb.offset_dom4_np()
    offd = np.asarray(off_np[None], dtype=np.float32)       # (1, n_p, N)
    return off, v, w, tfold, offd


def _k3_fwd_el(x, off, v_ref, plan):
    """One Fp coordinate (blk, L) -> per-prime centered residue list."""
    y = lb._passes(lb._pad_cols(x, _W) + off, 2)
    y = lb._carry_pass(y + lb._SQ_BIAS).astype(jnp.bfloat16)
    out = []
    for j, p in enumerate(plan.primes):
        e = jax.lax.dot_general(
            y, v_ref[j],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        out.append(e - float(p) * jnp.round(e * float(1.0 / p)))
    return out


def _k3_inv_el(dom, w_ref, tfold, offd_ref, plan):
    """Per-prime signed combination list -> (blk, L) loose-canonical
    digits (offset polynomial + center + interpolate + CRT + reduce)."""
    gs = []
    for j, p in enumerate(plan.primes):
        cj = dom[j] + offd_ref[0, j, :]
        cj = cj - float(p) * jnp.round(cj * float(1.0 / p))
        gj = jax.lax.dot_general(
            cj.astype(jnp.bfloat16), w_ref[j].astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        gs.append(gj - float(p) * jnp.round(gj * float(1.0 / p)))
    nl = plan.NL
    S = [
        sum(gs[j] * float(plan.m_digits[j, l]) for j in range(plan.n_p))
        for l in range(nl)
    ]
    S.append(jnp.zeros_like(S[0]))
    S = _crt_renorm(S)
    s_f = sum(s * float(256.0 ** l) for l, s in enumerate(S))
    t = jnp.floor(s_f * plan.inv_M)
    md = [float(m) for m in plan.M_digits] + [0.0]
    r = _crt_renorm([s - t * m for s, m in zip(S, md)])
    neg = (r[-1] < 0).astype(jnp.float32)
    r = _crt_renorm([v + neg * m for v, m in zip(r, md)])
    ge = r[-1] > 0
    eq_run = r[-1] == 0
    for l in range(nl - 1, 0, -1):
        ge = ge | (eq_run & (r[l] > md[l]))
        eq_run = eq_run & (r[l] == md[l])
    ge = (ge | (eq_run & (r[0] >= md[0]))).astype(jnp.float32)
    r = _crt_renorm([v - ge * m for v, m in zip(r, md)])
    blk = r[0].shape[0]

    def shifted(v, l):
        parts = []
        if l:
            parts.append(jnp.zeros((blk, l), dtype=v.dtype))
        parts.append(v)
        if nl - l:
            parts.append(jnp.zeros((blk, nl - l), dtype=v.dtype))
        return jnp.concatenate(parts, axis=-1) if len(parts) > 1 else v

    cols = shifted(r[0], 0)
    for l in range(1, len(r)):
        cols = cols + shifted(r[l], l)
    return _reduce_body(cols, tfold)


# -- per-prime-list domain algebra (mirrors tower._d2mul/_d6mul/_dxi) ------


def _dl_mul(a, b):
    return [x * y for x, y in zip(a, b)]


def _dl_add(a, b):
    return [x + y for x, y in zip(a, b)]


def _dl_sub(a, b):
    return [x - y for x, y in zip(a, b)]


def _dl_scale(a, k: float):
    return [x * k for x in a]


def _d2mul_l(a, b):
    """Fp2 domain schoolbook on per-prime lists: a, b = (c0, c1)."""
    a0, a1 = a
    b0, b1 = b
    return (_dl_sub(_dl_mul(a0, b0), _dl_mul(a1, b1)),
            _dl_add(_dl_mul(a0, b1), _dl_mul(a1, b0)))


def _d2sqr_l(a):
    a0, a1 = a
    p = _dl_mul(a0, a1)
    return (_dl_sub(_dl_mul(a0, a0), _dl_mul(a1, a1)), _dl_add(p, p))


def _dxi_l(a):
    a0, a1 = a
    return (_dl_sub(a0, a1), _dl_add(a0, a1))


def _d2add_l(a, b):
    return (_dl_add(a[0], b[0]), _dl_add(a[1], b[1]))


def _d6mul_l(A, B):
    a0, a1, a2 = A
    b0, b1, b2 = B
    c0 = _d2add_l(_d2mul_l(a0, b0),
                  _dxi_l(_d2add_l(_d2mul_l(a1, b2), _d2mul_l(a2, b1))))
    c1 = _d2add_l(_d2add_l(_d2mul_l(a0, b1), _d2mul_l(a1, b0)),
                  _dxi_l(_d2mul_l(a2, b2)))
    c2 = _d2add_l(_d2add_l(_d2mul_l(a0, b2), _d2mul_l(a1, b1)),
                  _d2mul_l(a2, b0))
    return (c0, c1, c2)


def _d6mul_by_v_l(A):
    return (_dxi_l(A[2]), A[0], A[1])


def _d6add_l(A, B):
    return tuple(_d2add_l(a, b) for a, b in zip(A, B))


def _fwd_fp12_l(ref, off, v_ref, plan, base=0):
    """Read 12 coordinates from (blk, 12+, L) ref -> nested per-prime
    domain ((c0..c2 Fp2 pairs) x 2 Fp6 halves)."""
    def fp2(c):
        return (_k3_fwd_el(ref[:, base + 2 * c, :], off, v_ref, plan),
                _k3_fwd_el(ref[:, base + 2 * c + 1, :], off, v_ref, plan))

    h0 = (fp2(0), fp2(1), fp2(2))
    h1 = (fp2(3), fp2(4), fp2(5))
    return (h0, h1)


def _write_fp12_l(o_ref, dom12, w_ref, tfold, offd_ref, plan):
    """Interpolate+reduce the 12 output coordinates into (blk, 12, L)."""
    h0, h1 = dom12
    coords = []
    for h in (h0, h1):
        for fp2c in h:
            coords.extend([fp2c[0], fp2c[1]])
    for c, dom in enumerate(coords):
        o_ref[:, c, :] = _k3_inv_el(dom, w_ref, tfold, offd_ref, plan)


@lru_cache(maxsize=None)
def _k3_fp12_call(rows_p: int, kind: str, interpret: bool):
    """kind: 'sqr' | 'mul' | 'line'. Operates on (rows, 12, L) fp12
    tensors (plus (rows, 3, 2, L) lines for 'line')."""
    plan = lb.plan4()
    n_p = plan.n_p
    blk = _K3_BLK
    nfold = lb._T_FOLD_NP.shape[0]

    def sqr_kernel(a_ref, off_ref, v_ref, w_ref, t_ref, offd_ref, o_ref):
        off = off_ref[:, :]
        t = t_ref[:, :]
        A0, A1 = _fwd_fp12_l(a_ref, off, v_ref, plan)
        t0 = _d6mul_l(A0, A0)
        t1 = _d6mul_l(A1, A1)
        c0 = _d6add_l(t0, _d6mul_by_v_l(t1))
        a01 = _d6mul_l(A0, A1)
        c1 = tuple((_dl_scale(x[0], 2.0), _dl_scale(x[1], 2.0))
                   for x in a01)
        _write_fp12_l(o_ref, (c0, c1), w_ref, t, offd_ref, plan)

    def mul_kernel(a_ref, b_ref, off_ref, v_ref, w_ref, t_ref, offd_ref,
                   o_ref):
        off = off_ref[:, :]
        t = t_ref[:, :]
        A0, A1 = _fwd_fp12_l(a_ref, off, v_ref, plan)
        B0, B1 = _fwd_fp12_l(b_ref, off, v_ref, plan)
        t0 = _d6mul_l(A0, B0)
        t1 = _d6mul_l(A1, B1)
        c0 = _d6add_l(t0, _d6mul_by_v_l(t1))
        c1 = _d6add_l(_d6mul_l(A0, B1), _d6mul_l(A1, B0))
        _write_fp12_l(o_ref, (c0, c1), w_ref, t, offd_ref, plan)

    def line_kernel(a_ref, l_ref, off_ref, v_ref, w_ref, t_ref, offd_ref,
                    o_ref):
        # Sparse line l0 + l1 w^3 + l2 w^5 = Fp6 pair ((l0,0,0),(0,l1,l2));
        # tower.fp12_mul_sparse_line's exact combination on domain lists.
        off = off_ref[:, :]
        t = t_ref[:, :]
        A0, A1 = _fwd_fp12_l(a_ref, off, v_ref, plan)

        def fp2_of_l(c):
            return (_k3_fwd_el(l_ref[:, c, 0, :], off, v_ref, plan),
                    _k3_fwd_el(l_ref[:, c, 1, :], off, v_ref, plan))

        d0, d1, d2 = fp2_of_l(0), fp2_of_l(1), fp2_of_l(2)
        a00, a01, a02 = A0
        b0, b1, b2 = A1
        t0 = (_d2mul_l(a00, d0), _d2mul_l(a01, d0), _d2mul_l(a02, d0))
        t1 = (_dxi_l(_d2add_l(_d2mul_l(b1, d2), _d2mul_l(b2, d1))),
              _d2add_l(_d2mul_l(b0, d1), _dxi_l(_d2mul_l(b2, d2))),
              _d2add_l(_d2mul_l(b0, d2), _d2mul_l(b1, d1)))
        t2 = (_dxi_l(_d2add_l(_d2mul_l(a01, d2), _d2mul_l(a02, d1))),
              _d2add_l(_d2mul_l(a00, d1), _dxi_l(_d2mul_l(a02, d2))),
              _d2add_l(_d2mul_l(a00, d2), _d2mul_l(a01, d1)))
        t3 = (_d2mul_l(b0, d0), _d2mul_l(b1, d0), _d2mul_l(b2, d0))
        c0 = _d6add_l(t0, _d6mul_by_v_l(t1))
        c1 = _d6add_l(t2, t3)
        _write_fp12_l(o_ref, (c0, c1), w_ref, t, offd_ref, plan)

    kernels = {"sqr": sqr_kernel, "mul": mul_kernel, "line": line_kernel}
    n_in = {"sqr": 1, "mul": 2, "line": 1}[kind]
    in_specs = [pl.BlockSpec((blk, 12, _L), lambda i: (i, 0, 0))
                for _ in range(n_in)]
    if kind == "line":
        in_specs.append(pl.BlockSpec((blk, 3, 2, _L),
                                     lambda i: (i, 0, 0, 0)))
    in_specs += [
        _const((1, _W)),
        _const((n_p, _W, _N)),
        _const((n_p, _N, _N)),
        _const((nfold, _L)),
        _const((1, n_p, _N)),
    ]
    return pl.pallas_call(
        kernels[kind],
        grid=(rows_p // blk,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((blk, 12, _L), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, 12, _L), jnp.float32),
        interpret=interpret,
    )


def _k3_args(n_p: int):
    off, v, w, tfold, offd = _k3_consts(n_p)
    return off, v, w, tfold, offd


def k3_enabled() -> bool:
    """Whole-op kernels: LIGHTHOUSE_TPU_K3=1 (or PALLAS=interpret for CPU
    tests). Default OFF — the chip A/B (fetch-verified, chained fp12_sqr
    at n=1024) measured K3 at 22.2 ms vs XLA's 18.8 ms: even with the
    domain tensors VMEM-resident, Mosaic's schedule for these small-lane
    shapes loses to XLA's fused pipeline. Kept for re-evaluation on
    future toolchains."""
    if _DISABLE:
        return False
    if os.environ.get("LIGHTHOUSE_TPU_K3", "") == "1":
        return True
    return _MODE == "interpret"


def _fp12_flat(a):
    """(..., 2, 3, 2, L) fp12 tensor -> (rows, 12, L) + leading shape."""
    shape = a.shape[:-4]
    return a.reshape((-1, 12, _L)), shape


def fp12_op(kind: str, a, b=None, line=None):
    """Dispatch a whole-op fused fp12 kernel. a/b: (..., 2, 3, 2, L);
    line: tuple of three (..., 2, L) Fp2 coefficients for 'line'."""
    af, shape = _fp12_flat(a)
    rows = af.shape[0]
    blk = _K3_BLK
    af, rows_p = _pad_rows(af, blk)
    args = [af]
    if kind == "mul":
        bf, _ = _fp12_flat(b)
        bf, _ = _pad_rows(bf, blk)
        args.append(bf)
    elif kind == "line":
        l0, l1, l2 = line
        lf = jnp.stack([l0, l1, l2], axis=-3).reshape((-1, 3, 2, _L))
        lf, _ = _pad_rows(lf, blk)
        args.append(lf)
    args += list(_k3_args(lb.plan4().n_p))
    with jax.enable_x64(False):
        out = _k3_fp12_call(rows_p, kind, _interpret())(*args)
    return out[:rows].reshape(shape + (2, 3, 2, _L))
