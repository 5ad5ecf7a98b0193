"""vmap-clean single-problem solve cores with in-graph escalation.

The eager recovery ladders (robust/recovery.py) branch on HOST health
values, which a vmapped problem cannot do — every problem in a batch
shares one trace.  These cores are the serving-layer counterpart: the
fast attempt runs first, ``health.acceptable`` gates a ``lax.cond``
into the safe attempt, and under ``vmap`` that cond lowers to a
per-problem select — both rungs execute batched, each problem keeps
whichever its own health chose.  That is the deliberate trade: a
factor-of-two worst case on the escalating bucket instead of a host
round-trip that would serialize the whole batch (docs/SERVING.md).

Ladders (mirroring the eager ones, truncated to two rungs so the cond
stays one level):

- ``solve``                NoPiv LU (speculative, growth-gated)  -> PartialPiv LU
- ``chol_solve``           Cholesky                              -> PartialPiv LU
- ``least_squares_solve``  CholQR semi-normal equations          -> Householder QR

Every core returns ``(x_dense, HealthInfo, escalated)``; vmapped, the
HealthInfo comes back as a leading-axis pytree (one scalar per problem
per field — including the per-problem ABFT counters when
``Option.Abft`` is on) and ``escalated`` as a per-problem bool.

Ragged fast rungs: when the tune/ plan cache resolves a Pallas plan for
``batch_potrf`` / ``batch_getrf`` / ``batch_geqrf`` at the bucket size
(`_ragged_plan`), the batch's fast rung runs as ONE ragged batched
Pallas factorization (internal/batched.py) whose grid consumes the
per-problem size vector via scalar prefetch — each problem computes
only its own tiles instead of the full identity-padded bucket.  The
escalation ladder is unchanged: the batched fast-rung health feeds the
same per-problem ``lax.cond`` (`_vmap_escalate`), whose safe rung is
the identical per-problem driver attempt.  A plan miss (or a dtype /
option the ragged rung does not implement) falls back to the vmapped
cores; both routes share one ``fn(a, b, sizes)`` executable signature,
so routing never costs the warm server a retrace.

Precision rung (``Option.Precision = bf16``, or bf16 operands): one
more rung BELOW the ladders above — factor in bf16 storage with f32
accumulation (the bf16 batched Pallas kernels when the plan cache
resolves one under the ``bfloat16`` plan key, a whole-bucket XLA factor
of the bf16-rounded operand otherwise), refine with one-two f32 IR
sweeps against the ORIGINAL operands, and accept each problem only on
an a-posteriori certificate (robust/certify.certify_solve /
certify_lstsq).  A failed certificate escalates that problem — and only
that problem — to the f32 route, whose result is computed by the
UNCHANGED code above and is therefore bit-identical to serving with the
rung disabled.  Dtypes are canonicalized once at the boundary
(robust/precision.normalize_dtype); an unsupported dtype raises
``SlateUnsupportedDtypeError`` instead of quietly taking a slow route.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..core.matrix import HermitianMatrix, Matrix
from ..core.storage import TileStorage
from ..options import ErrorPolicy, Option, Options, resolve_abft
from ..robust import certify as _cert
from ..robust import health as _h
from ..robust import precision as _prec
from ..types import Uplo
from ..util.runtime import pallas_interpret

_TILE = 128

# dtypes the serving boundary accepts: f32 (both routes), bf16 (the
# certified precision rung), f64 (vmapped XLA cores only).  Anything
# else raises SlateUnsupportedDtypeError at the boundary.
SERVE_DTYPES = ("float32", "bfloat16", "float64")

# Products the serving programs trace without an explicit precision.  A
# served answer is certified at 50 n eps of f32 (robust/certify.py); the
# TPU's default f32 product (one bf16 pass) errs by ~2^-9 per operand,
# which at serving sizes (n of a few hundred) is the same order as that
# tolerance, and it caps iterative refinement at bf16 accuracy since the
# residual b - A x is itself such a product.  Small problems are
# latency-bound, so the extra MXU passes cost little.  The bf16 Pallas
# panels pin their bf16 x bf16 dots to DEFAULT explicitly (Mosaic
# refuses HIGHEST there).
PRODUCT_PRECISION = "highest"


def _tile(n: int) -> int:
    """Static tile edge for bucket-shaped operands: one tile up to
    _TILE, then the largest divisor-free cap the drivers pad anyway."""
    return min(int(n), _TILE)


def _info(opts: Options | None) -> dict:
    o = dict(opts or {})
    o[Option.ErrorPolicy] = ErrorPolicy.Info
    return o


def _demote(h, dtype):
    """The bounded_retry growth gate, in-graph: catastrophic pivot
    growth reads as not-converged so it both escalates and stays
    visible in the returned health."""
    return h._replace(
        converged=h.converged & (h.growth <= _h.growth_limit(dtype)))


def _mat(dense, t: int) -> Matrix:
    return Matrix(TileStorage.from_dense(dense, t, t))


def _cond_escalate(h1, x1, safe, operands, dtype):
    """Shared escalation seam: keep the fast attempt where its health is
    acceptable, run ``safe`` where not.  jit: one branch executes.
    vmap: both branches run batched, selected per problem."""
    escalated = ~_h.acceptable(h1, dtype)
    x, h = lax.cond(escalated, safe, lambda ops: (x1, h1), operands)
    return x, h, escalated


# ------------------------------------------------------------------- cores


def _live_tol(dtype, n, bucket: int):
    """Certificate tolerance 50 n eps at each problem's live size ``n``
    (traced); filler slots (n = 0) and callers without sizes take the
    bucket's."""
    n = bucket if n is None else jnp.where(n > 0, n, bucket)
    return _cert.tolerance(dtype, 1) * jnp.asarray(n, jnp.float32)


def _lu_attempt(factor, ad, bd, tol, o):
    """One LU rung of ``solve``: factor, solve, two sweeps of iterative
    refinement in the original system, and the residual certificate at
    ``tol`` (the ``_rbt_attempt`` recipe).  Growth beyond
    ``health.growth_limit`` demotes it like the eager speculative path."""
    from ..drivers import lu as _lu
    t = _tile(ad.shape[0])
    F, fh = factor(_mat(ad, t), o)
    xd = _lu.getrs(F, _mat(bd, t), o).to_dense()
    for _ in range(2):                     # r = b - A x, dx through F
        xd = xd + _lu.getrs(F, _mat(bd - ad @ xd, t), o).to_dense()
    cert = _cert.certify_solve(jnp.linalg.norm(ad), xd, bd, bd - ad @ xd,
                               tol=tol, iters=2)
    return xd, _demote(_h.merge(fh, cert, _h.from_result(xd)), ad.dtype)


def solve_core(a: jax.Array, b: jax.Array, opts: Options | None = None,
               n=None):
    """General solve A x = b on bucket-shaped dense operands; ``n`` is
    the live size the certificate is held at (the bucket's if None).

    Fast rung: NoPiv LU — no pivot search, the serving speculation.
    Safe rung: partial-pivot LU.  Both refine and certify
    (:func:`_lu_attempt`): a NoPiv factor accurate enough to pass the
    growth gate can still leave refinement short of the certificate."""
    from ..drivers import lu as _lu
    o = _info(opts)
    tol = _live_tol(a.dtype, n, a.shape[0])
    x1, h1 = _lu_attempt(_lu.getrf_nopiv, a, b, tol, o)
    return _cond_escalate(h1, x1,
                          lambda ops: _lu_attempt(_lu.getrf, *ops, o),
                          (a, b, tol), a.dtype)


def chol_solve_core(a: jax.Array, b: jax.Array,
                    opts: Options | None = None, n=None):
    """HPD solve on bucket-shaped dense operands (full symmetric ``a``).

    Fast rung: Cholesky — an indefinite problem NaN-fills its factor,
    reads ``nonfinite`` and escalates.  Safe rung: partial-pivot LU,
    which solves any nonsingular Hermitian system.  ``n`` is unused: no
    rung here carries a residual certificate."""
    from ..drivers import cholesky as _chol
    from ..drivers import lu as _lu
    t = _tile(a.shape[0])
    o = _info(opts)

    def chol(ops):
        ad, bd = ops
        H = HermitianMatrix._from_view(_mat(ad, t), Uplo.Lower)
        L, fh = _chol.potrf(H, o)
        X = _chol.potrs(L, _mat(bd, t), o)
        h = _h.merge(fh, _h.from_result(X.storage.data))
        return X.to_dense(), _demote(h, ad.dtype)

    def lu(ops):
        ad, bd = ops
        F, fh = _lu.getrf(_mat(ad, t), o)
        X = _lu.getrs(F, _mat(bd, t), o)
        h = _h.merge(fh, _h.from_result(X.storage.data))
        return X.to_dense(), _demote(h, ad.dtype)

    x1, h1 = chol((a, b))
    return _cond_escalate(h1, x1, lu, (a, b), a.dtype)


def least_squares_core(a: jax.Array, b: jax.Array,
                       opts: Options | None = None, n=None):
    """Least squares min ||A x - b|| on bucket-shaped (mb, nb) operands.

    Fast rung: CholQR semi-normal equations — rank deficiency or squared
    conditioning fails the Gram Cholesky and escalates.  Safe rung:
    Householder QR.  Returns x of shape (nb, kb).  ``n`` is unused: the
    CholQR attempt certifies at the bucket (drivers/qr.py)."""
    from ..drivers import qr as _qr
    t = _tile(a.shape[1])
    o = _info(opts)

    def cholqr(ops):
        ad, bd = ops
        X, h = _qr._gels_cholqr_attempt(_mat(ad, t), _mat(bd, t), o)
        return X.to_dense(), _demote(h, ad.dtype)

    def house(ops):
        ad, bd = ops
        X, h = _qr._gels_qr_attempt(_mat(ad, t), _mat(bd, t), o)
        return X.to_dense(), _demote(h, ad.dtype)

    x1, h1 = cholqr((a, b))
    return _cond_escalate(h1, x1, house, (a, b), a.dtype)


CORES = {
    "solve": solve_core,
    "chol_solve": chol_solve_core,
    "least_squares_solve": least_squares_core,
}

# ------------------------------------------------------------ ragged route

# plan-cache op implementing each serve op's fast rung as ONE ragged
# batched Pallas factorization (internal/batched.py) instead of a
# vmapped full-bucket XLA driver
RAGGED_OPS = {
    "solve": "batch_getrf",
    "chol_solve": "batch_potrf",
    "least_squares_solve": "batch_geqrf",
}


def _ragged_plan(op: str, a: jax.Array, opts: Options | None,
                 dtype: str | None = None):
    """The measured routing decision, taken at TRACE time from static
    shape/dtype/plan data: the ragged batched kernel runs only when the
    tune/ plan cache (or a plan_override) hands back a Pallas plan for
    this op's batch kernel at this bucket size — `tune.resolve_plan` is
    the ONLY selection seam (SEAM011), exactly as for the single-shot
    drivers.  ``dtype`` overrides the plan-key dtype (the precision
    rung factors in bf16 while ``a`` itself stays f32); spellings are
    canonicalized so a ``jnp.bfloat16`` object and the ``"bfloat16"``
    string hit the same plan row.  Returns the plan with nb clamped to
    the bucket, or None for the vmapped-XLA fallback (plan miss, a
    dtype the Pallas panels don't implement, or an option the ragged
    rung does not implement)."""
    from .. import tune as _tune
    nb_bucket = int(a.shape[2] if op == "least_squares_solve"
                    else a.shape[1])
    dtype = _prec.normalize_dtype(a.dtype if dtype is None else dtype)
    if dtype not in (_prec.HIGH, _prec.LOW):
        return None
    if resolve_abft(opts) and op != "chol_solve":
        # only batch_potrf carries the checksum rungs in-batch; the
        # other ops honor Abft through the vmapped driver cores
        return None
    plan = _tune.resolve_plan(RAGGED_OPS[op], nb_bucket, dtype)
    if plan.kernel != "pallas":
        return None
    nb = min(int(plan.nb), nb_bucket)
    if nb_bucket % nb or nb % max(int(plan.bw), 8):
        return None
    return plan._replace(nb=nb)


def _vmap_escalate(h1, x1, safe, operands, dtype):
    """Batched escalation seam: per-problem lax.cond against the ragged
    fast rung's batched health — identical branch pytrees to the
    vmapped cores', so escalating problems get exactly the safe rung
    they would have gotten on the vmapped route."""
    return jax.vmap(
        lambda h1i, x1i, *ops: _cond_escalate(h1i, x1i, safe, ops, dtype)
    )(h1, x1, *operands)


def _ragged_solve(a, b, sizes, plan, opts: Options | None):
    """solve fast rung via batch_getrf (ragged NoPiv LU + 2 IR sweeps,
    certified at each live size), safe rung solve_core's partial-pivot
    LU attempt."""
    from ..drivers import lu as _lu
    from ..internal import batched as _bk
    o = _info(opts)
    tol = _live_tol(a.dtype, sizes, a.shape[1])
    fa = _bk.batch_getrf(a, sizes, nb=plan.nb, bw=plan.bw,
                         interpret=pallas_interpret())
    x = _bk.batch_getrs(fa, b)
    for _ in range(2):                     # r = b - A x, dx through fa
        x = x + _bk.batch_getrs(fa, b - a @ x)
    h1 = _h.merge(_bk.batch_lu_health(a, fa), _certify_batch(a, x, b, tol),
                  jax.vmap(_h.from_result)(x))
    h1 = _demote(h1, a.dtype)
    return _vmap_escalate(h1, x, lambda ops: _lu_attempt(_lu.getrf, *ops, o),
                          (a, b, tol), a.dtype)


def _ragged_chol(a, b, sizes, plan, opts: Options | None):
    """chol_solve fast rung via batch_potrf (with the in-batch ABFT
    rungs when Option.Abft is on), safe rung the per-problem
    partial-pivot LU — the same ladder as chol_solve_core."""
    from ..drivers import lu as _lu
    from ..internal import batched as _bk
    t = _tile(a.shape[1])
    o = _info(opts)
    fa, counts = _bk.batch_potrf(a, sizes, nb=plan.nb, bw=plan.bw,
                                 interpret=pallas_interpret(),
                                 abft=resolve_abft(opts))
    y = lax.linalg.triangular_solve(fa, b, left_side=True, lower=True)
    x = lax.linalg.triangular_solve(fa, y, left_side=True, lower=True,
                                    transpose_a=True)
    h1 = _bk.batch_chol_health(fa)._replace(
        abft_detected=counts.detected, abft_corrected=counts.corrected,
        abft_site=counts.site)
    h1 = _demote(_h.merge(h1, jax.vmap(_h.from_result)(x)), a.dtype)

    def lu(ops):
        ad, bd = ops
        F, fh = _lu.getrf(_mat(ad, t), o)
        X = _lu.getrs(F, _mat(bd, t), o)
        h = _h.merge(fh, _h.from_result(X.storage.data))
        return X.to_dense(), _demote(h, ad.dtype)

    return _vmap_escalate(h1, x, lu, (a, b), a.dtype)


def _ragged_lstsq(a, b, sizes, plan, opts: Options | None):
    """least_squares_solve fast rung via batch_geqrf (ragged Householder
    QR — rank-revealing on |diag R|), safe rung the per-problem
    Householder QR driver."""
    from ..drivers import qr as _qr
    from ..internal import batched as _bk
    nb = a.shape[2]
    t = _tile(nb)
    o = _info(opts)
    x, packed = _bk.batch_gels(a, b, sizes, nb=plan.nb,
                               interpret=pallas_interpret())

    def hone(p, xi):
        d = jnp.abs(jnp.diagonal(p[:nb, :nb]))
        return _h.merge(_h.from_pivots(d), _h.from_result(xi))

    h1 = _demote(jax.vmap(hone)(packed, x), a.dtype)

    def house(ops):
        ad, bd = ops
        X, h = _qr._gels_qr_attempt(_mat(ad, t), _mat(bd, t), o)
        return X.to_dense(), _demote(h, ad.dtype)

    return _vmap_escalate(h1, x, house, (a, b), a.dtype)


RAGGED_CORES = {
    "solve": _ragged_solve,
    "chol_solve": _ragged_chol,
    "least_squares_solve": _ragged_lstsq,
}

# ---------------------------------------------------------- precision rung


def _certify_batch(a, x, b, tol):
    """Per-problem certify_solve of a refined batch, each at its own
    tolerance ``tol`` [B]."""
    return jax.vmap(
        lambda an, xi, bi, ri, ti: _cert.certify_solve(an, xi, bi, ri,
                                                       tol=ti, iters=2)
    )(_fro_batch(a), x, b, b - a @ x, tol)


def _fro_batch(v):
    """Per-problem Frobenius norms of a [B, m, n] stack, f32."""
    v = _prec.promote(v)
    return jnp.sqrt(jnp.sum(v * v, axis=(1, 2)))


def _bf16_chol_attempt(a, b, sizes, plan, opts: Options | None):
    """bf16 Cholesky attempt: factor the demoted bucket (ragged Pallas
    when ``plan`` keys a bf16 kernel, whole-bucket XLA otherwise), solve
    + 2 IR sweeps in f32 against the ORIGINAL operands, certify per
    problem.  Returns ``(x, h)`` with the certificate folded in."""
    from ..internal import batched as _bk
    al = _prec.demote(a)
    if plan is not None:
        fal, counts = _bk.batch_potrf(al, sizes, nb=plan.nb, bw=plan.bw,
                                      interpret=pallas_interpret(),
                                      abft=resolve_abft(opts))
    else:
        # bf16 factor storage emulated around the batched XLA factor
        fal = _prec.demote(lax.linalg.cholesky(_prec.promote(al)))
        counts = None
    fa = _prec.promote(fal)

    def solve(rhs):
        y = lax.linalg.triangular_solve(fa, rhs, left_side=True, lower=True)
        return lax.linalg.triangular_solve(fa, y, left_side=True,
                                           lower=True, transpose_a=True)

    x = solve(b)
    for _ in range(2):                     # f32 IR against the ORIGINAL a
        x = x + solve(b - a @ x)
    cert = _certify_batch(a, x, b, _live_tol(a.dtype, sizes, a.shape[1]))
    h1 = _bk.batch_chol_health(fa)
    if counts is not None:
        h1 = h1._replace(abft_detected=counts.detected,
                         abft_corrected=counts.corrected,
                         abft_site=counts.site)
    h1 = _h.merge(h1, cert, jax.vmap(_h.from_result)(x))
    return x, _demote(h1, a.dtype)


def _bf16_solve_attempt(a, b, sizes, plan, opts: Options | None):
    """bf16 LU attempt: ragged NoPiv batch_getrf on the demoted bucket
    (partial-pivot XLA LU when no bf16 plan resolves), f32 solves + 2 IR
    sweeps against the original operands, per-problem certificate."""
    from ..internal import batched as _bk
    al = _prec.demote(a)
    if plan is not None:
        fal = _bk.batch_getrf(al, sizes, nb=plan.nb, bw=plan.bw,
                              interpret=pallas_interpret())
        getrs = lambda rhs: _bk.batch_getrs(fal, rhs)  # noqa: E731
        fh = _bk.batch_lu_health(a, _prec.promote(fal))
    else:
        lu, _, perm = lax.linalg.lu(_prec.promote(al))
        fa = _prec.promote(_prec.demote(lu))   # bf16 factor storage

        def getrs(rhs):
            pb = jnp.take_along_axis(rhs, perm[:, :, None], axis=1)
            y = lax.linalg.triangular_solve(fa, pb, left_side=True,
                                            lower=True, unit_diagonal=True)
            return lax.linalg.triangular_solve(fa, y, left_side=True,
                                               lower=False)

        fh = _bk.batch_lu_health(a, fa)
    x = getrs(b)
    for _ in range(2):                     # f32 IR against the ORIGINAL a
        x = x + getrs(b - a @ x)
    cert = _certify_batch(a, x, b, _live_tol(a.dtype, sizes, a.shape[1]))
    h1 = _h.merge(fh, cert, jax.vmap(_h.from_result)(x))
    return x, _demote(h1, a.dtype)


def _bf16_lstsq_attempt(a, b, sizes, plan, opts: Options | None):
    """bf16 least-squares attempt: ragged batch_gels on the demoted
    bucket (whole-bucket XLA QR when no bf16 plan resolves), one
    corrected-semi-normal-equations sweep through the bf16 R in f32
    against the original operands, per-problem normal-equations
    certificate (certify_lstsq)."""
    from ..internal import batched as _bk
    nb = a.shape[2]
    al = _prec.demote(a)
    if plan is not None:
        x, packed = _bk.batch_gels(al, b, sizes, nb=plan.nb,
                                   interpret=pallas_interpret())
        R = _prec.promote(packed[:, :nb, :nb])
    else:
        q, r = lax.linalg.qr(_prec.promote(al), full_matrices=False)
        R = _prec.promote(_prec.demote(r))     # bf16 factor storage
        qtb = jnp.matmul(jnp.swapaxes(_prec.promote(_prec.demote(q)), 1, 2),
                         b)
        x = lax.linalg.triangular_solve(R, qtb, left_side=True, lower=False)
    at = jnp.swapaxes(a, 1, 2)

    def csne(rhs):                          # R^T R dx = A^T rhs (Björck)
        g = at @ rhs
        z = lax.linalg.triangular_solve(R, g, left_side=True, lower=False,
                                        transpose_a=True)
        return lax.linalg.triangular_solve(R, z, left_side=True,
                                           lower=False)

    for _ in range(2):                      # f32 CSNE against ORIGINAL a
        x = x + csne(b - a @ x)
    rn = at @ (b - a @ x)
    cert = jax.vmap(_cert.certify_lstsq)(_fro_batch(a), x, b, rn)
    d = jnp.abs(jnp.diagonal(R, axis1=1, axis2=2))
    # normal-equations certification is a backward-error gate that a
    # rank-collapsed rounding can pass trivially (huge ||x|| swamps the
    # denominator); fold a conditioning estimate through R's diagonal
    # into ``growth`` so health.acceptable escalates those problems
    piv = jax.vmap(_h.from_pivots)(d)
    piv = piv._replace(growth=_fro_batch(a) / jnp.maximum(
        jnp.min(d, axis=1), jnp.finfo(R.dtype).tiny))
    h1 = _h.merge(piv, cert, jax.vmap(_h.from_result)(x))
    return x, _demote(h1, a.dtype)


BF16_ATTEMPTS = {
    "solve": _bf16_solve_attempt,
    "chol_solve": _bf16_chol_attempt,
    "least_squares_solve": _bf16_lstsq_attempt,
}


def _bf16_rung(op: str, a, b, sizes, opts: Options | None):
    """The certified precision rung: bf16 fast attempt below the f32
    ladders.  The f32 route — ragged or vmapped, picked by the SAME plan
    logic as with the rung disabled — computes every problem's
    escalation target with unchanged code, so a certificate failure
    escalates that problem (and only that problem, via the per-problem
    ``lax.cond``) onto a result bit-identical to the f32-only route.
    The returned ``escalated`` flags certificate failures: the rung's
    accept-rate is ``1 - mean(escalated)`` over live slots."""
    plan_lo = _ragged_plan(op, a, opts, dtype=_prec.LOW)
    x1, h1 = BF16_ATTEMPTS[op](a, b, sizes, plan_lo, opts)
    plan32 = _ragged_plan(op, a, opts)
    if plan32 is not None:
        x32, h32, _ = RAGGED_CORES[op](a, b, sizes, plan32, opts)
    else:
        core = CORES[op]
        x32, h32, _ = jax.vmap(lambda ai, bi, si: core(ai, bi, opts, si))(
            a, b, sizes)
    return _vmap_escalate(h1, x1, lambda ops: ops, (x32, h32), a.dtype)


def make_batched(op: str, opts: Options | None = None):
    """The leading-axis-batched core for one op: ``fn(a, b, sizes)``.

    ``sizes`` is the per-problem live-size vector ([B] int32: n for
    square solves, m + (nb - n) live rows for least squares, 0 for
    filler slots).  At trace time `_ragged_plan` consults the tune/
    plan cache: a Pallas plan routes the fast rung through the ragged
    batched kernels (each problem computes only its own tiles), a miss
    vmaps the per-problem cores over the full bucket — which read
    ``sizes`` only for solve's certificate tolerance — so both routes
    share one executable signature and the warm server stays
    retrace-free whichever is picked.  ``opts``
    is closed over as static configuration (it participates in the
    executable-cache fingerprint, never in the traced data).

    ``Option.Precision = bf16`` (resolved ONCE here, the seam contract)
    inserts the certified bf16 rung below the f32 ladder for f32
    buckets; bf16 operands take the same rung unconditionally (promoted
    working copies, results demoted back).  f64 serves on the vmapped
    XLA cores; any other dtype raises SlateUnsupportedDtypeError at the
    boundary instead of quietly taking a slow route."""
    core = CORES[op]
    bf16_rung = _prec.resolve_precision(opts)

    def fn(a, b, sizes):
        with jax.default_matmul_precision(PRODUCT_PRECISION):
            return _fn(a, b, sizes)

    def _fn(a, b, sizes):
        dtype = _prec.normalize_dtype(a.dtype, supported=SERVE_DTYPES)
        low = dtype == _prec.LOW
        if low:
            a, b = _prec.promote(a), _prec.promote(b)
        if low or (bf16_rung and dtype == _prec.HIGH):
            x, h, esc = _bf16_rung(op, a, b, sizes, opts)
            return (_prec.demote(x) if low else x), h, esc
        plan = _ragged_plan(op, a, opts)
        if plan is not None:
            return RAGGED_CORES[op](a, b, sizes, plan, opts)
        # the vmapped route factors the whole padded bucket; sizes only
        # set each problem's certificate tolerance
        return jax.vmap(lambda ai, bi, si: core(ai, bi, opts, si))(
            a, b, sizes)

    return fn
