"""Driver-level graceful degradation: escalate, fall back, retry — bounded.

The reference has exactly one recovery path (gesv_mixed's full-precision
fallback after itermax, ref: src/gesv_mixed.cc).  Production service wants
the same shape everywhere a cheap method can fail on hard inputs:

- :func:`gesv_with_recovery` — LU pivoting escalation
  NoPiv -> PartialPiv -> CALU, keyed on non-finite factors, zero pivots,
  or pivot growth beyond ``health.growth_limit`` (a NoPiv factor of a
  row-scaled matrix, or a bit-flipped panel, explodes the growth ratio
  long before the residual is ever formed).
- :func:`posv_with_recovery` — non-HPD input falls back to the Aasen
  ``hesv`` (Hermitian indefinite), then to plain ``gesv``, when
  ``Option.UseFallbackSolver`` is set.
- :func:`bounded_retry` — the shared policy: at most ``max_retries``
  fallback attempts, eager-only (a traced call cannot branch on health;
  it reports the HealthInfo instead), each attempt health-checked.
- :func:`heev_with_recovery` / :func:`svd_with_recovery` —
  certification-gated METHOD escalation for the spectral drivers:
  when the a-posteriori certificate (:mod:`certify`) fails, heev walks
  ``MethodEig`` Auto -> DC -> QR and svd walks ``MethodSvd``
  Auto -> Bidiag (ScaLAPACK's documented ladder: D&C falls back to QR
  iteration on non-convergence), re-certifying each attempt.
- :func:`hesv_with_recovery` — a singular band T (Aasen's tridiagonal
  factor has no pivoting to save it) falls back to plain LU ``gesv``
  on the densified Hermitian matrix.
- Speculation (``Option.Speculate = on``, resolved once per driver
  boundary like ErrorPolicy): the same ladders run FORWARDS as a
  performance feature — gesv tries the RBT-preconditioned NoPiv fast
  path (:func:`_rbt_attempt`), gels tries CholQR2 semi-normal equations
  (:func:`gels_with_recovery`), hesv tries Cholesky first
  (:func:`hesv_with_recovery`) — each attempt certified a-posteriori
  (:mod:`certify`) so a wrong fast answer escalates instead of escaping.
- Precision (``Option.Precision = bf16``, resolved once per boundary via
  :func:`precision.resolve_precision`): with Speculate also on, posv and
  gels grow a rung BELOW the f32 ladder — factor the bf16-ROUNDED
  operand (:func:`_chol_bf16_attempt` / :func:`_gels_bf16_attempt`),
  refine in the ORIGINAL f32 system, and certify at the f32 tolerance;
  a failed certificate escalates to the unchanged f32 chain.

Escalation requires host control flow, so it engages only on EAGER calls;
traced calls run the requested method once and surface health per
``Option.ErrorPolicy`` (docs/ROBUSTNESS.md has the full contract table).
"""

from __future__ import annotations

from ..exceptions import (SlateNotConvergedError,
                          SlateNotPositiveDefiniteError, SlateSingularError)
from ..obs import events as _obs
from ..options import (ErrorPolicy, MethodEig, MethodGels, MethodLU,
                       MethodSvd, Option, Options, get_option, resolve_abft,
                       resolve_speculate, select_gels_method,
                       select_lu_method)
from ..util.trace import span
from . import health as _h
from .precision import resolve_precision


def _with(opts: Options | None, **kv) -> dict:
    o = dict(opts or {})
    for name, v in kv.items():
        o[Option[name]] = v
    return o


def bounded_retry(first, fallbacks, *, dtype, max_retries: int = 2):
    """Run ``fallbacks`` (closures returning ``(result, HealthInfo)``) in
    order until a health passes :func:`health.acceptable`, trying at most
    ``max_retries`` of them.  ``first`` is the already-computed
    ``(result, HealthInfo)`` of the primary attempt.  Traced health →
    return ``first`` (no host branch exists under jit).
    Returns ``(result, health, retries_used)``.

    The returned health holds the same growth bound the retry loop selects
    on: ``converged`` is demoted when growth exceeds
    :func:`health.growth_limit`, so a finite-but-catastrophically-grown
    final attempt (e.g. a silently bit-flipped panel — finite values,
    ``info == 0``) can never read as ``h.ok`` from a recovering entry
    point.  jit-safe — pure jnp ops on the health leaves."""

    def demote(hh):
        return hh._replace(
            converged=hh.converged & (hh.growth <= _h.growth_limit(dtype)))

    result, h = first
    if h.is_traced():
        return result, demote(h), 0
    used = 0
    for fb in fallbacks:
        if bool(_h.acceptable(h, dtype)) or used >= max_retries:
            break
        result, h = fb()
        used += 1
    return result, demote(h), used


# ------------------------------------------------------------------ gesv

_LU_CHAIN = {
    MethodLU.NoPiv: (MethodLU.NoPiv, MethodLU.PartialPiv, MethodLU.CALU),
    MethodLU.PartialPiv: (MethodLU.PartialPiv, MethodLU.CALU),
    MethodLU.CALU: (MethodLU.CALU,),
}


def _lu_attempt(A, B, opts, method):
    """One factor+solve attempt under ErrorPolicy.Info; health merges the
    factor's pivot record with the solution's finiteness."""
    from ..drivers import lu as _lu
    o = _with(opts, MethodLU=method, ErrorPolicy=ErrorPolicy.Info)
    factor = {MethodLU.NoPiv: _lu.getrf_nopiv,
              MethodLU.CALU: _lu.getrf_tntpiv}.get(method, _lu.getrf)
    F, fh = factor(A, o)
    X = _lu.getrs(F, B, o)
    h = _h.merge(fh, _h.from_result(X.storage.data))
    return (F, X), h


def _rbt_attempt(A, B, opts, ir_steps: int = 2):
    """The speculative gesv fast path: RBT-preconditioned NoPiv LU
    (drivers/lu.py getrf_rbt), ``ir_steps`` rounds of iterative refinement
    in the ORIGINAL system, then an a-posteriori residual certificate
    (certify.certify_solve) merged into the factor health — a wrong
    fast-path solve (adversarial growth, a post_rbt bit flip) reads as
    ``converged=False`` and escalates in gesv_with_recovery.

    Fully traceable: the attempt itself is pure jnp + drivers; only the
    escalation branch (bounded_retry) needs eager health."""
    from ..drivers import auxiliary as _aux
    from ..drivers import lu as _lu
    from ..drivers.blas3 import gemm
    from ..types import Norm
    from . import certify as _certify
    o = _with(opts, ErrorPolicy=ErrorPolicy.Info)
    F, fh = _lu.getrf_rbt(A, o)
    X = _lu.getrs(F, B, o)
    for _ in range(ir_steps):
        R = gemm(-1.0, A, X, 1.0, B, opts)         # r = B - A X, mesh-aware
        X = _aux.add(1.0, _lu.getrs(F, R, o), 1.0, X)
    R = gemm(-1.0, A, X, 1.0, B, opts)
    anorm = _aux.norm(Norm.Fro, A)
    cert = _certify.certify_solve(anorm, X.to_dense(), B.to_dense(),
                                  R.to_dense(), iters=ir_steps)
    return (F, X), _h.merge(fh, cert)


def gesv_with_recovery(A, B, opts: Options | None = None):
    """gesv body with pivoting escalation (drivers/lu.py delegates here).

    Default order is the safety ladder: requested method first, escalate
    on unhealthy factors.  ``Option.Speculate = on`` (resolved ONCE here,
    like ErrorPolicy) inverts it into a performance feature: the first
    attempt is the certified RBT NoPiv fast path and the pivoted chain
    only runs when the certificate fails — eagerly, as always.

    With ``Option.Abft`` the ladder grows a rung BELOW method
    escalation: the drivers' in-place checksum repair handles a single
    struck tile silently, and an UNREPAIRED detection (a multi-tile
    strike reads ``abft_detected > abft_corrected``, which fails
    ``health.acceptable``) retries the SAME method once — a transient
    strike will not repeat — before the pivoted chain engages.

    Return shape matches gesv's ErrorPolicy contract: ``(F, X)`` under
    Raise/Nan, ``(F, X, HealthInfo)`` under Info."""
    method = select_lu_method(opts)
    speculate = resolve_speculate(opts)
    abft = resolve_abft(opts)  # the one Option.Abft read (like Speculate)
    chain = _LU_CHAIN[method]
    if speculate:
        # the RBT attempt IS the NoPiv rung — escalation goes pivoted
        fb_methods = tuple(m for m in chain if m is not MethodLU.NoPiv)
        first = _rbt_attempt(A, B, opts)
        same = lambda: _rbt_attempt(A, B, opts)            # noqa: E731
    else:
        fb_methods = chain[1:]
        first = _lu_attempt(A, B, opts, chain[0])
        same = lambda: _lu_attempt(A, B, opts, chain[0])   # noqa: E731
    if not get_option(opts, Option.UseFallbackSolver):
        fb_methods = ()
    retry_same = [same] if (abft and fb_methods) else []
    # bounded_retry demotes `converged` on growth beyond the limit: the raw
    # drivers keep growth out of .ok, the recovering solver does not.
    (F, X), h, used = bounded_retry(
        first,
        retry_same + [lambda m=m: _lu_attempt(A, B, opts, m)
                      for m in fb_methods],
        dtype=A.dtype,
        max_retries=max(len(fb_methods) + len(retry_same), 1))
    _obs.note_path("rbt" if speculate else chain[0].name,
                   (["retry_same"] if retry_same else [])
                   + [m.name for m in fb_methods], used, speculate)
    return _finalize_solve("gesv", F, X, h, opts, _singular_exc("gesv"))


def gesv_nopiv_raw(A, B, opts: Options | None = None):
    """gesv_nopiv body: single NoPiv attempt, NO escalation and NO growth
    demotion — the historical contract is that a finite (if catastrophic)
    NoPiv solve returns rather than raises."""
    (F, X), h = _lu_attempt(A, B, opts, MethodLU.NoPiv)
    _obs.note_path("NoPiv", (), 0, False)
    return _finalize_solve("gesv_nopiv", F, X, h, opts,
                           _singular_exc("gesv_nopiv"))


# ------------------------------------------------------------------ posv

def _chol_attempt(A, B, opts):
    """One potrf+potrs attempt under Info.  Shared between posv's primary
    try and hesv's HPD speculation: an indefinite input NaN-fills the
    Cholesky factor, which reads as ``nonfinite`` and falls through the
    retry ladder — no extra certificate needed."""
    from ..drivers import cholesky as _chol
    o = _with(opts, ErrorPolicy=ErrorPolicy.Info)
    L, fh = _chol.potrf(A, o)
    X = _chol.potrs(L, B, o)
    # on a mesh, X's finiteness is a reduction across chips
    with span("slate.potrs/health"):
        xh = _h.from_result(X.storage.data)
    return (L, X), _h.merge(fh, xh)


def _round_bf16(M):
    """Round a matrix's values through bf16 storage (precision.py
    round_through) — the dense model of factor-low storage: the values a
    bf16-resident copy would hold, kept in the caller's dtype so every
    driver below runs unchanged.  ``with_dense`` preserves the concrete
    matrix class, so triangular factors stay triangular."""
    from .precision import round_through
    return M.with_dense(round_through(M.to_dense()))


def _chol_bf16_attempt(A, B, opts, ir_steps: int = 2):
    """The speculative posv fast path one precision lower
    (Option.Speculate + Option.Precision = bf16): Cholesky of the
    bf16-ROUNDED operand with the factor itself bf16-rounded — the dense
    model of the serving rung's bf16-stored factor (serve/batched.py) —
    then ``ir_steps`` refinement sweeps in the ORIGINAL f32 system and an
    a-posteriori residual certificate at the f32 tolerance.  A failed
    certificate (or a non-HPD rounding) escalates to the unchanged f32
    Cholesky attempt in posv_with_recovery."""
    from ..drivers import auxiliary as _aux
    from ..drivers import cholesky as _chol
    from ..drivers.blas3 import gemm
    from ..types import Norm
    from . import certify as _certify
    o = _with(opts, ErrorPolicy=ErrorPolicy.Info)
    L, fh = _chol.potrf(_round_bf16(A), o)
    L = _round_bf16(L)
    X = _chol.potrs(L, B, o)
    for _ in range(ir_steps):
        R = gemm(-1.0, A, X, 1.0, B, opts)     # r = B - A X, ORIGINAL A
        X = _aux.add(1.0, _chol.potrs(L, R, o), 1.0, X)
    R = gemm(-1.0, A, X, 1.0, B, opts)
    anorm = _aux.norm(Norm.Fro, A)
    cert = _certify.certify_solve(anorm, X.to_dense(), B.to_dense(),
                                  R.to_dense(), iters=ir_steps)
    return (L, X), _h.merge(fh, cert)


def posv_with_recovery(A, B, opts: Options | None = None):
    """posv body with non-HPD fallback (drivers/cholesky.py delegates).

    On an eager non-HPD failure with Option.UseFallbackSolver set, retries
    the solve as Hermitian-indefinite (hesv), then as plain LU (gesv).
    posv is already speculation-shaped in f32 — Cholesky (the cheapest
    factor) first, certified by its own pivots — so ``Option.Speculate``
    alone changes nothing here.  With ``Option.Precision = bf16`` as well
    (both resolved ONCE at this boundary, like ErrorPolicy) the ladder
    grows a rung BELOW f32: factor the bf16-rounded operand, refine in
    the original system, accept only on the residual certificate
    (:func:`_chol_bf16_attempt`); the f32 Cholesky attempt is always the
    first escalation target, so anything posv could solve before it
    still solves.
    With ``Option.Abft`` an unrepaired checksum detection retries the
    SAME attempt once before the indefinite fallbacks — the
    localized-repair-then-retry rung below full escalation (see
    gesv_with_recovery).

    The first returned element is the factor object of whichever method
    succeeded (TriangularMatrix / HEFactors / LUFactors)."""
    speculate = resolve_speculate(opts)   # resolved ONCE, like ErrorPolicy
    low = resolve_precision(opts)         # the one Option.Precision read
    bf16 = speculate and low
    if bf16:
        first_name = "cholesky_bf16"
        first = _chol_bf16_attempt(A, B, opts)
        same = lambda: _chol_bf16_attempt(A, B, opts)      # noqa: E731
        fallbacks = [lambda: _chol_attempt(A, B, opts)]
        rungs = ["cholesky"]
    else:
        first_name = "cholesky"
        first = _chol_attempt(A, B, opts)
        same = lambda: _chol_attempt(A, B, opts)           # noqa: E731
        fallbacks, rungs = [], []
    if get_option(opts, Option.UseFallbackSolver):
        fallbacks += [lambda: _hesv_attempt(A, B, opts),
                      lambda: _gesv_attempt(A, B, opts)]
        rungs += ["hesv", "gesv"]
        if resolve_abft(opts):  # the one Option.Abft read here
            fallbacks.insert(0, same)
            rungs.insert(0, "retry_same")
    (F, X), h, used = bounded_retry(first, fallbacks, dtype=A.dtype,
                                    max_retries=max(len(fallbacks), 2))
    _obs.note_path(first_name, rungs, used, bf16)
    return _finalize_solve(
        "posv", F, X, h, opts,
        lambda hh: SlateNotPositiveDefiniteError(
            f"posv: not positive definite and fallback failed "
            f"({hh.describe()})", info=int(hh.info)))


def _hesv_attempt(A, B, opts):
    from ..drivers import hetrf as _he
    o = _with(opts, ErrorPolicy=ErrorPolicy.Raise)
    try:
        F, X = _he.hesv(A, B, o)
    except (SlateSingularError, SlateNotConvergedError):
        # the library's own numerical verdicts make the rung unhealthy;
        # anything else (a compiler or runtime refusal) propagates
        return (None, None), _h.healthy(A.dtype)._replace(
            converged=_false())
    h = _h.from_result(X.storage.data)
    return (F, X), h


def _gesv_attempt(A, B, opts):
    from ..core.matrix import Matrix
    from ..core.storage import TileStorage
    from ..drivers import lu as _lu
    Ag = Matrix(TileStorage.from_dense(A.to_dense(), A.nb, A.nb, A.grid))
    o = _with(opts, ErrorPolicy=ErrorPolicy.Info)
    F, fh = _lu.getrf(Ag, o)
    X = _lu.getrs(F, B, o)
    return (F, X), _h.merge(fh, _h.from_result(X.storage.data))


# ------------------------------------------------------------- heev / svd

# ScaLAPACK's documented spectral ladder: divide-and-conquer falls back to
# QR iteration on non-convergence.  Auto tries the vendor band eigensolver
# first, then the explicit two-stage routes.
_EIG_CHAIN = {
    MethodEig.Auto: (MethodEig.Auto, MethodEig.DC, MethodEig.QR),
    MethodEig.DC: (MethodEig.DC, MethodEig.QR),
    MethodEig.QR: (MethodEig.QR,),
}

_SVD_CHAIN = {
    MethodSvd.Auto: (MethodSvd.Auto, MethodSvd.Bidiag),
    MethodSvd.Bidiag: (MethodSvd.Bidiag,),
}


def _notconverged_exc(name):
    return lambda h: SlateNotConvergedError(
        f"{name}: spectral result failed certification and escalation "
        f"was exhausted ({h.describe()})", iters=int(h.iters))


def heev_with_recovery(A, opts: Options | None = None, *, jobz: bool = True):
    """heev body with certification-gated MethodEig escalation
    (drivers/heev.py delegates here).

    Each attempt returns ``((w, Z), HealthInfo)`` with the a-posteriori
    eigen-certificate merged in (``certify.certify_eig``); a failed
    certificate reads as ``converged=False`` so :func:`bounded_retry`
    walks the Auto -> DC -> QR ladder.  Return shape: ``(w, Z)`` under
    Raise/Nan, ``(w, Z, HealthInfo)`` under Info."""
    from ..drivers import heev as _heev
    chain = _EIG_CHAIN[get_option(opts, Option.MethodEig)]
    if not get_option(opts, Option.UseFallbackSolver):
        chain = chain[:1]

    def attempt(m):
        return _heev.heev_info(A, _with(opts, MethodEig=m), jobz=jobz)

    (w, Z), h, used = bounded_retry(
        attempt(chain[0]),
        [lambda m=m: attempt(m) for m in chain[1:]],
        dtype=A.dtype, max_retries=len(chain))
    _obs.note_path(chain[0].name, [m.name for m in chain[1:]], used, False)
    return _h.finalize_flat("heev", (w, Z), h, opts,
                            _notconverged_exc("heev"))


def svd_with_recovery(A, opts: Options | None = None, *, jobu: bool = True):
    """svd body with certification-gated MethodSvd escalation
    (drivers/svd.py delegates here): Auto -> Bidiag, re-certified per
    attempt.  Return shape: ``(s, U, V)`` under Raise/Nan,
    ``(s, U, V, HealthInfo)`` under Info."""
    from ..drivers import svd as _svd
    chain = _SVD_CHAIN[get_option(opts, Option.MethodSvd)]
    if not get_option(opts, Option.UseFallbackSolver):
        chain = chain[:1]

    def attempt(m):
        return _svd.svd_info(A, _with(opts, MethodSvd=m), jobu=jobu)

    (s, U, V), h, used = bounded_retry(
        attempt(chain[0]),
        [lambda m=m: attempt(m) for m in chain[1:]],
        dtype=A.dtype, max_retries=len(chain))
    _obs.note_path(chain[0].name, [m.name for m in chain[1:]], used, False)
    return _h.finalize_flat("svd", (s, U, V), h, opts,
                            _notconverged_exc("svd"))


# ------------------------------------------------------------------ hesv

def hesv_with_recovery(A, B, opts: Options | None = None):
    """hesv body with singular-band-T fallback (drivers/hetrf.py
    delegates here): Aasen's tridiagonal T is factored without pivoting
    beyond its band, so a singular T poisons the solve — fall back to
    densified LU ``gesv`` when ``Option.UseFallbackSolver`` is set.

    ``Option.Speculate = on`` (resolved ONCE here) runs the posv ordering
    forward as speculation: Cholesky first — the cheapest Hermitian
    factorization, self-certifying through its pivots — with the Aasen
    method as the guaranteed fallback for indefinite inputs, then
    densified gesv.  The Aasen rung is always present when speculating
    (the baseline contract: any Hermitian input hesv could solve before,
    it still solves), gesv only with UseFallbackSolver.

    Return shape matches gesv's contract: ``(F, X)`` under Raise/Nan,
    ``(F, X, HealthInfo)`` under Info."""
    from ..drivers import hetrf as _he

    def aasen():
        o = _with(opts, ErrorPolicy=ErrorPolicy.Info)
        F, fh = _he.hetrf(A, o)
        X = _he.hetrs(F, B, o)
        return (F, X), _h.merge(fh, _h.from_result(X.storage.data))

    use_fb = get_option(opts, Option.UseFallbackSolver)
    speculate = resolve_speculate(opts)
    if speculate:
        first_name, first = "cholesky", _chol_attempt(A, B, opts)
        fallbacks, rungs = [aasen], ["aasen"]
        if use_fb:
            fallbacks.append(lambda: _gesv_attempt(A, B, opts))
            rungs.append("gesv")
    else:
        first_name, first = "aasen", aasen()
        fallbacks = [lambda: _gesv_attempt(A, B, opts)] if use_fb else []
        rungs = ["gesv"] if use_fb else []
    (F, X), h, used = bounded_retry(first, fallbacks, dtype=A.dtype,
                                    max_retries=max(len(fallbacks), 1))
    _obs.note_path(first_name, rungs, used, speculate)
    return _finalize_solve("hesv", F, X, h, opts, _singular_exc("hesv"))


# ------------------------------------------------------------------ gels

def _gels_bf16_attempt(A, B, opts, refine: int = 2):
    """The speculative gels fast path one precision lower
    (Option.Speculate + Option.Precision = bf16): Householder QR of the
    bf16-ROUNDED operand with R itself bf16-rounded — QR rather than
    CholQR so the low-precision factor error enters the refinement at
    cond(A), not cond(A)^2 — then Björck CSNE sweeps through R against
    the ORIGINAL system and the normal-equations certificate at the f32
    working tolerance.  A failed certificate escalates to the unchanged
    f32 chain in gels_with_recovery."""
    import jax.numpy as jnp
    from jax import lax
    from ..drivers import auxiliary as _aux
    from ..drivers import qr as _qr
    from ..drivers.blas3 import gemm
    from ..types import Norm
    from . import certify as _certify
    from .precision import round_through
    n = A.n
    F = _qr.geqrf(_round_bf16(A), opts)
    rd = round_through(jnp.triu(F.QR.to_dense()[:n, :n]))

    def sne(Rhs):
        # dx = R^-1 R^-T (A^H rhs): semi-normal equations through the low
        # factor; the dense triangular solves mirror gels_qr's idiom
        Z = gemm(1.0, A.conj_transpose(), Rhs, 0.0, None, opts)
        y = lax.linalg.triangular_solve(rd, Z.to_dense(), left_side=True,
                                        lower=False, transpose_a=True)
        return Z.with_dense(lax.linalg.triangular_solve(
            rd, y, left_side=True, lower=False))

    X = sne(B)
    for _ in range(refine):
        R = gemm(-1.0, A, X, 1.0, B, opts)     # r = B - A X, ORIGINAL A
        X = _aux.add(1.0, sne(R), 1.0, X)
    R = gemm(-1.0, A, X, 1.0, B, opts)
    Rn = gemm(1.0, A.conj_transpose(), R, 0.0, None, opts)
    anorm = _aux.norm(Norm.Fro, A)
    cert = _certify.certify_lstsq(
        anorm, X.to_dense(), B.to_dense(), Rn.to_dense(),
        tol=_certify.tolerance(A.dtype, max(A.m, A.n)))
    # the normal-equations certificate is a backward-error gate; a
    # rank-collapsed rounding (huge ||x|| from a tiny R pivot) can pass it
    # trivially, so fold a conditioning estimate through R's diagonal into
    # ``growth`` — bounded_retry's growth demotion then escalates it
    d = jnp.abs(jnp.diagonal(rd))
    piv = _h.from_pivots(d)._replace(
        growth=anorm / jnp.maximum(jnp.min(d), jnp.finfo(rd.dtype).tiny))
    h = _h.merge(piv, _h.merge(
        _h.from_result(X.storage.data),
        cert._replace(iters=jnp.asarray(refine, jnp.int32))))
    return X, h


def gels_with_recovery(A, B, opts: Options | None = None):
    """gels (m >= n) body with CholQR2 speculation and QR fallback
    (drivers/qr.py delegates here), unifying the previously ad-hoc
    CholQR -> QR fallback under bounded_retry.

    Method resolution (select_gels_method) picks CholQR for tall-skinny
    problems; ``Option.Speculate = on`` (resolved ONCE here) forces the
    CholQR2 semi-normal-equations fast path FIRST for any shape, with one
    refinement sweep and an a-posteriori normal-equations certificate
    (certify.certify_lstsq) merged into its health.  A failed certificate
    — squaring the condition number lost too much, or the Gram matrix was
    not numerically HPD — escalates to full Householder QR eagerly.

    ``Option.Precision = bf16`` (resolved ONCE here too) adds a rung
    BELOW that when speculating: the bf16-rounded-QR CSNE attempt
    (:func:`_gels_bf16_attempt`) runs first and the certified f32
    CholQR2 rung is always its escalation target, then Householder QR
    under Option.UseFallbackSolver as before.

    Return shape: ``X`` under Raise/Nan, ``(X, HealthInfo)`` under Info."""
    from ..drivers import qr as _qr
    speculate = resolve_speculate(opts)
    low = resolve_precision(opts)         # the one Option.Precision read
    method = select_gels_method(opts, A.m, A.n)
    fallbacks, rungs = [], []
    if speculate and low:
        first_name = "qr_bf16"
        first = _gels_bf16_attempt(A, B, opts)
        fallbacks = [lambda: _qr._gels_cholqr_attempt(A, B, opts, refine=1,
                                                      certify=True)]
        rungs = ["cholqr2"]
        exc = _qr._gram_exc("gels")
    elif speculate:
        first_name = "cholqr2"
        first = _qr._gels_cholqr_attempt(A, B, opts, refine=1, certify=True)
        exc = _qr._gram_exc("gels")
    elif method is MethodGels.CholQR:
        first_name = "cholqr"
        first = _qr._gels_cholqr_attempt(A, B, opts)
        exc = _qr._gram_exc("gels")
    else:
        # Householder QR directly — no speculation rung, but ErrorPolicy
        # still resolves at THIS boundary: an Info caller (or a vmapped
        # one) gets (X, h) here exactly as on the CholQR routes, not a
        # bare X.  bounded_retry with no fallbacks is just the growth
        # demotion, which QR should also be subject to.
        first_name = "qr"
        first = _qr._gels_qr_attempt(A, B, opts)
        exc = _singular_exc("gels")
    if first_name != "qr" and get_option(opts, Option.UseFallbackSolver):
        fallbacks += [lambda: _qr._gels_qr_attempt(A, B, opts)]
        rungs += ["qr"]
    X, h, used = bounded_retry(first, fallbacks, dtype=A.dtype,
                               max_retries=max(len(fallbacks), 1))
    _obs.note_path(first_name, rungs, used, speculate)
    return _h.finalize("gels", X, h, opts, exc)


# ------------------------------------------------------------------ shared

def _false():
    import jax.numpy as jnp
    return jnp.asarray(False)


def _singular_exc(name):
    return lambda h: SlateSingularError(
        f"{name}: singular or numerically unusable factor "
        f"({h.describe()})", info=int(h.info))


def _finalize_solve(name, F, X, h, opts, make_exc):
    res = _h.finalize(name, (F, X), h, opts, make_exc)
    if _h.error_policy(opts) is ErrorPolicy.Info:
        (F, X), h = res
        return F, X, h
    return res
