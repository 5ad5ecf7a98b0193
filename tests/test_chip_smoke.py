"""chip_smoke.py's jobs and gates, driven at toy sizes on the CPU.

chip_smoke.py is the on-chip check for what no benchmark cell covers:
gels, heev, serving, the Pallas routes and the 2x2 gemm and gesv.  These
tests import it as a module, run its own jobs through the public
drivers (``Job.lower`` / ``compile`` / ``run``) and hold every answer to
its own gates (``require``, ``certify``, ``certify_ls``, ``hpl_scaled``,
``run_pairs``), so a fault in a job or a gate shows here before a chip
call.  The last group hands the gates wrong answers and checks that they
refuse them.

The certificates are normwise (50 n eps of f32), so how far one wrong
entry moves them shrinks with n: x with its largest entry moved by
|x|/10 is refused by ``certify`` with a margin of about 5x at n=64 and
passes it from n of about 192 up; ``certify_ls`` refuses such an x at
64 x 16 and passes it at 256 x 64.  The gate cases use those small
sizes.
"""

import importlib.util
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import obs, serve, tune
from slate_tpu.robust import certify as cert
from slate_tpu.serve import bucket

REPO = Path(__file__).resolve().parent.parent
LU, GELS = st.MethodLU, st.MethodGels


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module", autouse=True)
def _checkout_plans(tmp_path_factory):
    """chip_smoke points the plan cache at the checkout's absent
    tune_plans.json, never ~/.cache: every seam takes its XLA plan
    unless a job overrides it."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("SLATE_TUNE_CACHE",
                  str(tmp_path_factory.mktemp("plans") / "plans.json"))
        yield


@pytest.fixture(autouse=True)
def _fresh_sentinel():
    """Every job traces a fresh lambda for a signature seen before."""
    obs.reset_sentinel()
    yield
    obs.reset_sentinel()


def _last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _moved(x):
    """x with its largest entry moved by |x|/10."""
    x = np.array(x, np.float32)
    i = np.unravel_index(np.argmax(np.abs(x)), x.shape)
    x[i] += np.abs(x).max() / np.float32(10.0)
    return x


# ------------------------------------------------------ one chip, 1 x 1

ONE_CHIP = {
    "gemm": lambda cs, g: cs.gemm_job(st, jax, g, "gemm", 128, 64),
    "posv": lambda cs, g: cs.posv_job(st, jax, g, "posv", 128, 64, 4),
    "gesv": lambda cs, g: cs.gesv_job(st, jax, g, "gesv", 128, 64, 4,
                                      LU.PartialPiv),
    "gesv_calu": lambda cs, g: cs.gesv_job(st, jax, g, "gesv_calu", 128,
                                           64, 4, LU.CALU, dominant=True),
    "gels": lambda cs, g: cs.gels_job(st, jax, g, "gels", 256, 64, 64, 4,
                                      GELS.Auto),
    "gels_qr": lambda cs, g: cs.gels_job(st, jax, g, "gels_qr", 256, 64,
                                         64, 4, GELS.QR, consistent=True),
    "heev": lambda cs, g: cs.heev_job(st, jax, g, 128, 64),
    # n % nb != 0: the last tile row and column are partial
    "posv_ragged": lambda cs, g: cs.posv_job(st, jax, g, "posv_ragged",
                                             100, 64, 4),
    "gesv_ragged": lambda cs, g: cs.gesv_job(st, jax, g, "gesv_ragged",
                                             100, 64, 4, LU.PartialPiv),
    "gels_ragged": lambda cs, g: cs.gels_job(st, jax, g, "gels_ragged",
                                             200, 100, 64, 4, GELS.Auto),
    # one right-hand side, the shape the benchmark's cells solve
    "posv_nrhs1": lambda cs, g: cs.posv_job(st, jax, g, "posv_nrhs1", 128,
                                            64, 1),
    "gesv_nrhs1": lambda cs, g: cs.gesv_job(st, jax, g, "gesv_nrhs1", 128,
                                            64, 1, LU.PartialPiv),
    "gels_nrhs1": lambda cs, g: cs.gels_job(st, jax, g, "gels_nrhs1", 256,
                                            64, 64, 1, GELS.Auto),
}


@pytest.mark.parametrize("case", list(ONE_CHIP))
def test_one_chip_job_is_certified(cs, rng, capsys, case):
    job = ONE_CHIP[case](cs, st.Grid(1, 1))
    if "ragged" in case:
        assert job.shapes["A"][1] % job.shapes["nb"] != 0
    job.lower(st, jax)
    job.compile()
    job.run(jax, rng)       # require()s a direct path and certify()
    line = _last_line(capsys)
    assert line["phase"] == job.name
    assert line["cert"] <= line["cert_tol"]
    if "hpl_bound" in line:
        assert line["resid"] < line["hpl_bound"]


# ------------------------------------------- Grid(1, 1) vs Grid(2, 2)

# 128: two tiles of 64, one on each process row and column; 160: three
# tiles, the last 32 wide, so process row and column 0 hold two
MESH = {
    "gemm": lambda cs, g, t: cs.gemm_job(st, jax, g, t, 128, 64),
    "posv": lambda cs, g, t: cs.posv_job(st, jax, g, t, 128, 64, 2),
    "gesv": lambda cs, g, t: cs.gesv_job(st, jax, g, t, 128, 64, 2,
                                         LU.PartialPiv, dominant=True),
    "posv_ragged": lambda cs, g, t: cs.posv_job(st, jax, g, t, 160, 64, 2),
    "gesv_ragged": lambda cs, g, t: cs.gesv_job(
        st, jax, g, t, 160, 64, 2, LU.PartialPiv, dominant=True),
}


def _grid22():
    return st.Grid(2, 2, devices=jax.devices()[:4])


@pytest.mark.parametrize("case", list(MESH))
def test_grid2x2_matches_grid1x1(cs, rng, capsys, case):
    pair = (MESH[case](cs, st.Grid(1, 1), f"{case}:grid1x1"),
            MESH[case](cs, _grid22(), f"{case}:grid2x2"))
    for job in pair:
        job.lower(st, jax)
        job.compile()
    cs.run_pairs(jax, rng, [pair], "grid1x1")
    line = _last_line(capsys)
    assert line["phase"] == f"{case}:grid2x2:vs_grid1x1"
    assert line["rel_diff"] <= line["tol"]


def test_grid2x2_gesv_general_meets_the_host_certificate(cs, rng, capsys):
    """Partial pivoting on a Gaussian matrix: gesv_job's check require()s
    that pivot rows crossed process rows."""
    job = cs.gesv_job(st, jax, _grid22(), "gesv_general:grid2x2", 128, 64,
                      2, LU.PartialPiv)
    job.lower(st, jax)
    job.compile()
    job.run(jax, rng)
    line = _last_line(capsys)
    assert line["rows_across"] > 0
    assert line["cert"] <= line["cert_tol"]


# ------------------------------------------- Pallas plans vs XLA plans

PALLAS = {
    "posv": lambda cs, g, t, o: cs.posv_job(st, jax, g, t, 256, 128, 4,
                                            overrides=o),
    "gesv_calu": lambda cs, g, t, o: cs.gesv_job(
        st, jax, g, t, 256, 128, 4, LU.CALU, dominant=True, overrides=o),
    "gels_qr": lambda cs, g, t, o: cs.gels_job(
        st, jax, g, t, 384, 256, 128, 4, GELS.QR, consistent=True,
        overrides=o),
}


def _kernels(job):
    """The kernels of the plans the job's driver event resolved."""
    return {p["kernel"] for p in job.event.get("plans", [])}


@pytest.mark.parametrize("case", list(PALLAS))
def test_pallas_route_matches_xla_route(cs, rng, capsys, case):
    grid = st.Grid(1, 1)
    pallas = [(op, tune.TilePlan("pallas", 128, 8)) for op in cs.PALLAS_OPS]
    ref = PALLAS[case](cs, grid, f"pallas/{case}:xla", ())
    job = PALLAS[case](cs, grid, f"pallas/{case}", pallas)
    # interpret mode runs the kernels as XLA ops and emits no Mosaic
    # call, so there is no tpu_custom_call for Job.run to count; the
    # driver event's plans show the route instead
    job.pallas = False
    for j in (ref, job):
        j.lower(st, jax)
        j.compile()
    assert "pallas" in _kernels(job)
    assert "pallas" not in _kernels(ref)
    cs.run_pairs(jax, rng, [(ref, job)], "xla", bf16_products=True)
    line = _last_line(capsys)
    assert line["phase"] == f"pallas/{case}:vs_xla"
    assert line["rel_diff"] <= line["tol"]


# --------------------------------------------------------------- serving

BUCKET, SERVE_NB = 32, 16


def _ragged_requests(cs, rng):
    return cs.serve_requests(rng, 8, 2, (1, BUCKET // 3, BUCKET - 17,
                                         BUCKET),
                             square_ls=True, well_conditioned=True)


def _ladder():
    return bucket.BucketLadder((BUCKET,), "tuned")


@pytest.fixture(scope="module")
def xla_route(cs):
    """The ragged stream served on the single-rung ladder's XLA plans:
    the reference the Pallas servers are held to."""
    reqs = _ragged_requests(cs, np.random.default_rng(7))
    srv = serve.Server(ladder=_ladder(), cache=serve.ExecutableCache())
    return reqs, cs.serve_pass(srv, reqs)[0]


def test_serve_mixed_cold_then_warm(cs, rng):
    srv = serve.Server(cache=serve.ExecutableCache())
    reqs = cs.serve_requests(rng, 6, 2, (8, 16, 24))
    _, cold, _ = cs.serve_pass(srv, reqs)
    assert cold and all(e["compiled"] for e in cold)
    results, warm, _ = cs.serve_pass(srv, reqs)
    cs.serve_check_warm(srv, warm, "serve")
    fields = cs.serve_fields(reqs, results, "serve")
    assert fields["cert_over_tol"] <= 1.0


def test_serve_xla_route_is_certified(cs, xla_route):
    reqs, results = xla_route
    assert {op for op, _, _ in reqs} == {"solve", "chol_solve",
                                         "least_squares_solve"}
    fields = cs.serve_fields(reqs, results, "pallas/serve_xla")
    assert fields["cert_over_tol"] <= 1.0
    kappa = max(np.linalg.cond(a.astype(np.float64)) for _, a, _ in reqs)
    cs.require(kappa <= 10.0, f"pallas/serve: kappa(A) {kappa} > 10")


@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_serve_ragged_pallas_matches_xla_route(cs, xla_route, precision):
    """The one_chip ragged servers at toy size, held to cert.tolerance
    at the bucket against the XLA route (without the Mosaic count)."""
    reqs, ref = xla_route
    opts = ({st.Option.Precision: st.Precision.Bf16}
            if precision == "bf16" else None)
    srv = serve.Server(opts=opts, ladder=_ladder(),
                       cache=serve.ExecutableCache())
    ragged = [(op, tune.TilePlan("pallas", SERVE_NB, 8))
              for op in cs.BATCH_OPS]
    cs.serve_pass(srv, reqs, ragged)          # cold: compiles the bucket
    results, batches, _ = cs.serve_pass(srv, reqs)
    cs.serve_check_warm(srv, batches, f"pallas/serve_{precision}")
    tol = cert.tolerance(np.float32, BUCKET)
    diff = max(cs.rel_diff(r.x, q.x) for r, q in zip(results, ref))
    cs.require(diff <= tol, f"pallas/serve_{precision}: differs from the "
               f"XLA route by {diff} > {tol}")
    cs.serve_fields(reqs, results, f"pallas/serve_{precision}")


# ------------------------------------------- the gates refuse wrong x

def _system(cs, rng, kind, n=64, nrhs=4):
    a = cs.spd(rng, n) if kind == "spd" else cs.general(rng, n)
    b = rng.standard_normal((n, nrhs), np.float32)
    x = np.linalg.solve(a.astype(np.float64), b).astype(np.float32)
    return a, x, b


@pytest.mark.parametrize("kind", ["spd", "general"])
def test_certify_refuses_a_solve_that_returns_b(cs, rng, kind):
    a, x, b = _system(cs, rng, kind)
    assert cs.certify(a, x, b)[2]
    ratio, tol, ok = cs.certify(a, b, b)
    assert not ok and ratio > tol


@pytest.mark.parametrize("kind", ["spd", "general"])
def test_certify_refuses_one_moved_entry(cs, rng, kind):
    a, x, b = _system(cs, rng, kind)
    assert cs.certify(a, x, b)[2]
    ratio, tol, ok = cs.certify(a, _moved(x), b)
    assert not ok and ratio > tol


def test_certify_ls_refuses_a_perturbed_x(cs, rng):
    a = rng.standard_normal((64, 16), np.float32)
    b = rng.standard_normal((64, 4), np.float32)
    x = np.linalg.lstsq(a.astype(np.float64), b, rcond=None)[0]
    assert cs.certify_ls(a, x.astype(np.float32), b)[2]
    ratio, tol, ok = cs.certify_ls(a, _moved(x), b)
    assert not ok and ratio > tol


@pytest.mark.parametrize("nrhs", [1, 3])
def test_hpl_scaled_is_hpls_formula(cs, rng, nrhs):
    n = 48
    a = cs.general(rng, n)
    b = rng.standard_normal((n, nrhs), np.float32)
    x = _moved(np.linalg.solve(a.astype(np.float64), b))
    want = 0.0
    for j in range(nrhs):   # ||b - A x||_inf / (||A||_inf ||x||_inf n eps)
        r = b[:, j].astype(np.float64) - a.astype(np.float64) @ x[:, j]
        want = max(want, np.linalg.norm(r, np.inf) / (
            np.linalg.norm(a.astype(np.float64), np.inf)
            * np.linalg.norm(x[:, j].astype(np.float64), np.inf)
            * n * np.finfo(np.float32).eps))
    assert cs.hpl_scaled(a, x, b) == pytest.approx(want, rel=1e-12)


class _Answer:
    """A job stand-in for run_pairs: returns a fixed X on the given
    host operands."""

    def __init__(self, name, x, host):
        self.name, self.x, self.host = name, x, host

    def run(self, jax, rng):
        return st.Matrix.from_numpy(self.x, 32), self.host


@pytest.mark.parametrize("bf16_products", [False, True])
def test_run_pairs_refuses_a_candidate_beyond_tolerance(cs, rng, capsys,
                                                        bf16_products):
    a, x, b = _system(cs, rng, "general")
    tol = (2.0 * np.linalg.cond(a.astype(np.float64)) * cs.EPS_BF16
           if bf16_products else cert.tolerance(np.float32, x.shape[0]))
    ref = _Answer("ref", x, (a, b))
    near = _Answer("near", x * np.float32(1.0 + tol / 10), (a, b))
    cs.run_pairs(jax, rng, [(ref, near)], "ref", bf16_products)
    assert _last_line(capsys)["rel_diff"] <= tol
    far = _Answer("far", x * np.float32(1.0 + 10 * tol), (a, b))
    with pytest.raises(SystemExit, match="far: differs from ref"):
        cs.run_pairs(jax, rng, [(ref, far)], "ref", bf16_products)


@pytest.mark.parametrize("status,path,escalations", [
    ("ok", "escalated:PartialPiv", 1),
    ("ok", "direct:PartialPiv", 2),
    ("ok", "speculated:rbt", 0),
    ("error", "direct:PartialPiv", 0),
])
def test_job_run_refuses_an_escalated_driver_event(cs, rng, capsys, status,
                                                   path, escalations):
    job = cs.Job("stub", None, (), lambda rng: ((), None),
                 lambda out, host: {}, {})
    job.compiled = lambda: np.zeros(())
    job.event = {"status": "ok", "path": "direct:PartialPiv",
                 "escalations": 0}
    job.run(jax, rng)
    assert _last_line(capsys)["phase"] == "stub"
    job.compiled = lambda: np.zeros(())
    job.event = {"status": status, "path": path, "escalations": escalations}
    with pytest.raises(SystemExit, match="stub: obs path"):
        job.run(jax, rng)


@pytest.mark.parametrize("wrong", [0, 1, 2])
def test_serve_fields_refuses_a_wrong_served_x(cs, rng, wrong):
    reqs = cs.serve_requests(rng, 3, 2, (16,))
    xs = [np.linalg.lstsq(a.astype(np.float64), b, rcond=None)[0]
          for _, a, b in reqs]
    served = lambda xs: [SimpleNamespace(x=x.astype(np.float32))  # noqa: E731
                         for x in xs]
    assert cs.serve_fields(reqs, served(xs), "serve")["cert_over_tol"] <= 1
    xs[wrong] = _moved(xs[wrong])
    op = reqs[wrong][0]
    with pytest.raises(SystemExit, match=f"problem {wrong} \\({op}"):
        cs.serve_fields(reqs, served(xs), "serve")


def test_require_raises_on_a_false_condition(cs):
    cs.require(True, "never shown")
    with pytest.raises(SystemExit, match="chip_smoke: FAILED: the reason"):
        cs.require(False, "the reason")
