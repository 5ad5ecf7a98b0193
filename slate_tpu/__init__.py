"""slate_tpu — TPU-native distributed dense linear algebra.

A from-scratch framework with the capabilities of the reference SLATE library
(distributed tiled BLAS-3, linear solvers, least squares, eigensolvers, SVD;
ref: /root/reference README.md:15-37), re-designed for TPU:

- tiles live as one blocked, 2D-block-cyclic-sharded array per matrix in HBM,
- drivers compile to single XLA programs (jit) with MXU-shaped contractions,
- the distributed backend is jax.shard_map + ICI collectives over a
  ``jax.sharding.Mesh`` process grid,
- mixed-precision (f32 factor + f64 refine) is the native high-precision path.
"""

from .version import __version__, id, version  # noqa: F401
from .types import Diag, Layout, Norm, Op, Side, TileKind, Uplo  # noqa: F401
from .options import (  # noqa: F401
    ErrorPolicy, GridOrder, MethodCholQR, MethodEig, MethodGels, MethodGemm,
    MethodHemm, MethodLU, MethodSvd, MethodTrsm, NormScope, Option,
    Precision, Speculate, Target,
)
from .exceptions import (  # noqa: F401
    SlateError, SlateNotConvergedError, SlateNotPositiveDefiniteError,
    SlateSingularError, SlateUnsupportedDtypeError, SlateValueError,
)
from . import robust  # noqa: F401
from .robust.health import HealthInfo  # noqa: F401
from .core.grid import Grid, make_grid  # noqa: F401
from .core.storage import TileStorage  # noqa: F401
from .core.matrix import (  # noqa: F401
    BandMatrix, BaseBandMatrix, BaseMatrix, BaseTrapezoidMatrix,
    HermitianBandMatrix, HermitianMatrix, Matrix, SymmetricMatrix,
    TrapezoidMatrix, TriangularBandMatrix, TriangularMatrix,
)
from .drivers.blas3 import (  # noqa: F401
    gemm, gemmA, gemmC, hemm, hemmA, her2k, herk, symm, syr2k, syrk, trmm,
    trsm,
)
from .drivers.auxiliary import (  # noqa: F401
    add, col_norms, copy, norm, redistribute, scale, scale_row_col, set,
)
from .drivers.cholesky import (  # noqa: F401
    posv, potrf, potrf_ooc, potri, potrs,
)
from .drivers.inverse import trtri, trtrm  # noqa: F401
from .drivers.lu import (  # noqa: F401
    LUFactors, OocLUFactors, RBTFactors, gesv, gesv_nopiv, getrf,
    getrf_nopiv, getrf_ooc, getrf_rbt, getrf_tntpiv, getri, getriOOP,
    getrs,
)
from .drivers.qr import (  # noqa: F401
    CAQRFactors, LQFactors, QRFactors, cholqr, gelqf, gels, gels_cholqr,
    gels_qr, geqrf, qr_multiply, unmlq, unmqr,
)
from .drivers.band import (  # noqa: F401
    GBFactors, PBFactors, gbmm, gbsv, gbtrf, gbtrs, hbmm, pbsv, pbtrf,
    pbtrs, tbsm,
)
from .drivers.heev import (  # noqa: F401
    heev, heev_vals, heevd, hegst, hegv, hb2st, steqr, sterf,
)
from .drivers.stedc import stedc  # noqa: F401
from .drivers.printing import format_matrix, print_matrix  # noqa: F401
from .drivers.condest import gecondest, norm1est, trcondest  # noqa: F401
from .drivers.hetrf import HEFactors, hesv, hetrf, hetrs  # noqa: F401
from .drivers.svd import bdsqr, svd, svd_vals, tb2bd  # noqa: F401
from .drivers.mixed import (  # noqa: F401
    MixedResult, gesv_mixed, gesv_mixed_gmres, posv_mixed, posv_mixed_gmres,
)
from .util.generator import generate_hermitian, generate_matrix  # noqa: F401
from . import api, compat, obs, serve  # noqa: F401
