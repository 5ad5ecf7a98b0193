"""slate-lint test suite (tools/slate_lint/).

Every rule has at least one *bad* fixture that demonstrably fires and one
*good* fixture that stays silent, plus: reachability/taint unit coverage,
suppression + baseline + CLI mechanics, legacy seam-report text fidelity,
and the tier-1 repo-wide clean run.

Fixtures are synthesized mini-repos under tmp_path — never the live tree
— so they are free to violate every contract on purpose.
"""

import json
import pathlib
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tools.slate_lint import cli, load_project, reachability  # noqa: E402
from tools.slate_lint.model import REGISTRY, parse_suppressions  # noqa: E402
from tools.slate_lint.rules import seams  # noqa: E402

cli.load_rules()

SEAM_IDS = {r for r in REGISTRY if r.startswith("SEAM")}

# --------------------------------------------------------------------------
# helpers


def mini_repo(tmp_path, files):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return tmp_path


def lint(root, select):
    project = load_project(root)
    return cli.run_rules(project, select=set(select))


def rule_ids(findings):
    return {f.rule for f in findings}


GRID = """\
    AXIS_P = "p"
    AXIS_Q = "q"
    """

# --------------------------------------------------------------------------
# trace-safety pack (TRC001-TRC006)


def _jit_mod(body):
    return ("import jax\nimport jax.numpy as jnp\n"
            "from jax import lax\nimport numpy as np\n\n\n"
            "@jax.jit\ndef entry(x):\n" + textwrap.indent(
                textwrap.dedent(body), "    "))


def test_trc001_fires_on_traced_branch(tmp_path):
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": _jit_mod("""\
        y = jnp.sum(x)
        if y > 0:
            return y
        return -y
        """)})
    fs = lint(root, {"TRC001"})
    assert [f.line for f in fs] == [10]


def test_trc001_silent_on_static_branch(tmp_path):
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": _jit_mod("""\
        if x.ndim > 2:              # .ndim is static under tracing
            return jnp.sum(x)
        if x.shape[0] == 4:         # so is .shape
            return x
        if x is None:               # identity never concretizes
            return x
        return x
        """)})
    assert lint(root, {"TRC001"}) == []


def test_trc002_fires_on_traced_loop(tmp_path):
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": _jit_mod("""\
        while jnp.sum(x) > 0:
            x = x - 1
        for v in jnp.abs(x):
            x = x + v
        return x
        """)})
    fs = lint(root, {"TRC002"})
    assert [f.line for f in fs] == [9, 11]


def test_trc002_silent_on_static_loop(tmp_path):
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": _jit_mod("""\
        for i in range(x.shape[0]):
            x = x + i
        while getattr(x, "ndim", 0) > 3:   # static-result builtin
            x = jnp.sum(x, axis=0)
        return x
        """)})
    assert lint(root, {"TRC002"}) == []


def test_trc003_fires_on_traced_assert(tmp_path):
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": _jit_mod("""\
        assert jnp.all(x > 0)
        return x
        """)})
    assert rule_ids(lint(root, {"TRC003"})) == {"TRC003"}


def test_trc003_silent_on_static_assert(tmp_path):
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": _jit_mod("""\
        assert x.ndim == 2
        return x
        """)})
    assert lint(root, {"TRC003"}) == []


def test_trc004_fires_on_concretization(tmp_path):
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": _jit_mod("""\
        a = float(jnp.sum(x))
        b = x.item()
        return a + b
        """)})
    assert len(lint(root, {"TRC004"})) == 2


def test_trc004_silent_on_static_concretization(tmp_path):
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": _jit_mod("""\
        n = int(x.shape[0])
        return x * float(n)
        """)})
    assert lint(root, {"TRC004"}) == []


def test_trc005_fires_on_numpy_on_traced(tmp_path):
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": _jit_mod("""\
        return np.linalg.norm(jnp.sum(x))
        """)})
    assert rule_ids(lint(root, {"TRC005"})) == {"TRC005"}


def test_trc005_silent_on_numpy_on_static(tmp_path):
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": _jit_mod("""\
        idx = np.arange(x.shape[0])    # static shape math is fine
        return x * jnp.asarray(idx)
        """)})
    assert lint(root, {"TRC005"}) == []


def test_trc006_fires_on_raise_in_traced(tmp_path):
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": _jit_mod("""\
        raise ValueError("boom")
        """)})
    assert rule_ids(lint(root, {"TRC006"})) == {"TRC006"}


def test_trc006_silent_outside_traced_set_and_at_boundaries(tmp_path):
    root = mini_repo(tmp_path, {
        # eager helper: never traced, free to raise
        "slate_tpu/mod.py": "def helper(x):\n    raise ValueError(x)\n",
        # registered eager boundary module: raises allowed
        "slate_tpu/robust/health.py": (
            "import jax\n\n\n@jax.jit\ndef finalize(x):\n"
            "    raise ValueError(x)\n"),
    })
    assert lint(root, {"TRC006"}) == []


def test_traced_set_follows_fori_loop_body(tmp_path):
    """Transitive tracing: a fori_loop body referenced (not called) from a
    jit entry is traced, and its closure inherits the entry's taint."""
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": _jit_mod("""\
        def body(i, c):
            if c > 0:           # c is the traced carry
                return c
            return c + 1
        return lax.fori_loop(0, 3, body, jnp.sum(x))
        """)})
    fs = lint(root, {"TRC001"})
    assert [f.line for f in fs] == [10]


def test_shard_map_lambda_closure_args_stay_static(tmp_path):
    """The repo's shard_map idiom: statics are closure-bound through a
    lambda (``lambda a: body(a, Nt=Nt)``); only lambda params are traced."""
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": """\
        import jax
        import jax.numpy as jnp


        def _local(a, *, Nt, method):
            if Nt > 2:                   # static: closure-bound int
                a = a * 2
            if method == "fast":         # static: closure-bound str
                a = a + 1
            if jnp.sum(a) > 0:           # traced: fed from lambda param
                a = -a
            return a


        def driver(a_data, Nt, method):
            fn = jax.shard_map(lambda a: _local(a, Nt=Nt, method=method),
                               mesh=None, in_specs=(), out_specs=())
            return fn(a_data)
        """})
    fs = lint(root, {"TRC001"})
    assert [f.line for f in fs] == [10]


def test_vmap_is_a_traced_entry(tmp_path):
    """``jax.vmap(f)`` runs f under a batching trace: everything f
    reaches is traced exactly as under jit, so a host branch on its
    argument fires TRC001 (the serving layer enters drivers this way)."""
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": """\
        import jax
        import jax.numpy as jnp


        def solve_one(a, b):
            if jnp.sum(a) > 0:           # traced under the batching trace
                return a + b
            return a - b


        def batched(a, b):
            return jax.vmap(solve_one)(a, b)
        """})
    fs = lint(root, {"TRC001"})
    assert [f.line for f in fs] == [6]


def test_vmap_lambda_closure_args_stay_static(tmp_path):
    """The serve/batched.py idiom — ``jax.vmap(lambda a, b: core(a, b,
    opts))`` — traces only the lambda's params; the closure-bound opts
    stays a static config the core may branch on."""
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": """\
        import jax
        import jax.numpy as jnp


        def core(a, b, opts):
            if opts.get("fast"):         # static: closure-bound dict
                a = a * 2
            if jnp.sum(b) > 0:           # traced: fed from lambda param
                a = -a
            return a


        def make_batched(opts):
            return jax.vmap(lambda a, b: core(a, b, opts))
        """})
    fs = lint(root, {"TRC001"})
    assert [f.line for f in fs] == [8]


def test_defaulted_params_of_loop_bodies_stay_static(tmp_path):
    """``def step(k, c, W0=W0)`` static-capture idiom: defaulted params of
    non-entry nested defs are not tainted."""
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": _jit_mod("""\
        W0 = 4

        def step(k, c, W0=W0):
            if W0 > 2:          # static capture
                return c + k
            return c
        return lax.fori_loop(0, 3, step, jnp.sum(x))
        """)})
    assert lint(root, {"TRC001"}) == []


# --------------------------------------------------------------------------
# collective-discipline pack (COL001-COL004)


COL_HEADER = """\
    from jax import lax

    from .core.grid import AXIS_P, AXIS_Q

    """


def test_col001_fires_on_unknown_axis(tmp_path):
    root = mini_repo(tmp_path, {
        "slate_tpu/core/grid.py": GRID,
        "slate_tpu/mod.py": COL_HEADER + """\

    def f(x):
        ax = mystery()
        return lax.psum(x, ax)
    """})
    assert rule_ids(lint(root, {"COL001"})) == {"COL001"}


def test_col001_silent_on_constants_and_wrapper_params(tmp_path):
    root = mini_repo(tmp_path, {
        "slate_tpu/core/grid.py": GRID,
        "slate_tpu/mod.py": COL_HEADER + """\

    def f(x):
        return lax.psum(lax.psum(x, AXIS_P), AXIS_Q)


    def generic(x, axis):
        # the comm/collectives.py pattern: axis is a wrapper parameter
        return lax.psum(x, axis), lax.axis_index(axis)


    def local_alias(x):
        ax = AXIS_P
        return lax.pmax(x, ax)


    def tuple_axes(x):
        return lax.psum(x, (AXIS_P, AXIS_Q))
    """})
    assert lint(root, {"COL001"}) == []


def test_col002_fires_on_vocabulary_literal(tmp_path):
    root = mini_repo(tmp_path, {
        "slate_tpu/core/grid.py": GRID,
        "slate_tpu/mod.py": COL_HEADER + """\

    def f(x):
        return lax.psum(x, "p")
    """})
    assert rule_ids(lint(root, {"COL002"})) == {"COL002"}


def test_col002_silent_on_constant(tmp_path):
    root = mini_repo(tmp_path, {
        "slate_tpu/core/grid.py": GRID,
        "slate_tpu/mod.py": COL_HEADER + """\

    def f(x):
        return lax.psum(x, AXIS_P)
    """})
    assert lint(root, {"COL002"}) == []


def test_col003_fires_on_one_sided_collective(tmp_path):
    root = mini_repo(tmp_path, {
        "slate_tpu/core/grid.py": GRID,
        "slate_tpu/mod.py": COL_HEADER + """\

    def f(x, pred):
        return lax.cond(pred, lambda c: lax.psum(c, AXIS_P),
                        lambda c: c, x)
    """})
    assert rule_ids(lint(root, {"COL003"})) == {"COL003"}


def test_col003_fires_through_named_branch_functions(tmp_path):
    root = mini_repo(tmp_path, {
        "slate_tpu/core/grid.py": GRID,
        "slate_tpu/mod.py": COL_HEADER + """\

    def hot(c):
        return lax.psum(c, AXIS_P)


    def cold(c):
        return c


    def f(x, pred):
        return lax.cond(pred, hot, cold, x)
    """})
    assert rule_ids(lint(root, {"COL003"})) == {"COL003"}


def test_col003_silent_when_both_branches_collective(tmp_path):
    root = mini_repo(tmp_path, {
        "slate_tpu/core/grid.py": GRID,
        "slate_tpu/mod.py": COL_HEADER + """\

    def f(x, pred):
        return lax.cond(pred, lambda c: lax.psum(c, AXIS_P),
                        lambda c: lax.pmax(c, AXIS_P), x)


    def g(x, pred):
        # collective-free cond: nothing to diverge on
        return lax.cond(pred, lambda c: c + 1, lambda c: c - 1, x)
    """})
    assert lint(root, {"COL003"}) == []


def test_col004_fires_outside_fault_seam(tmp_path):
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": """\
        from jax.experimental import io_callback


        def f(x):
            return io_callback(print, None, x)
        """})
    assert rule_ids(lint(root, {"COL004"})) == {"COL004"}


def test_col004_silent_inside_fault_seam(tmp_path):
    root = mini_repo(tmp_path, {"slate_tpu/robust/faults.py": """\
        from jax.experimental import io_callback


        def consume(x):
            return io_callback(print, None, x)
        """})
    assert lint(root, {"COL004"}) == []


# --------------------------------------------------------------------------
# seam pack (SEAM001-SEAM010): a clean skeleton, mutated per rule


def _driver(fn):
    return (f"from ..robust import health\n\n\n"
            f"def {fn}(a, opts=None):\n    return health.finalize(a)\n")


def seam_skeleton():
    files = {
        "slate_tpu/internal/rbt.py": "def butterfly(a):\n    return a\n",
        "slate_tpu/robust/abft.py": (
            "def tile_check(a):\n    return a, 0\n"),
        "slate_tpu/robust/faults.py": (
            'SITES = ("site_a", "site_b")\n\n\n'
            "def maybe_corrupt(site, x):\n    return x\n"),
        "slate_tpu/robust/recovery.py": """\
            def gesv_with_recovery(a, opts=None):
                spec = resolve_speculate(opts)
                ab = resolve_abft(opts)
                r = bounded_retry(a)
                return finalize(r)


            def gels_with_recovery(a, opts=None):
                spec = resolve_speculate(opts)
                low = resolve_precision(opts)
                r = bounded_retry(a)
                return finalize(r)


            def hesv_with_recovery(a, opts=None):
                spec = resolve_speculate(opts)
                r = bounded_retry(a)
                return finalize(r)


            def posv_with_recovery(a, opts=None):
                spec = resolve_speculate(opts)
                low = resolve_precision(opts)
                ab = resolve_abft(opts)
                r = bounded_retry(a)
                return finalize(r)
            """,
        "slate_tpu/serve/batched.py": """\
            def make_batched(op, shape, dtype, batch, opts=None):
                low = resolve_precision(opts)
                return op
            """,
        "slate_tpu/drivers/blas3.py": """\
            def gemm(a, b):
                ok = resolve_abft(None)
                return a


            def trsm(a, b):
                ok = resolve_abft(None)
                return a
            """,
        "slate_tpu/drivers/lu.py": (
            "from ..robust import health\n\n\n"
            "def _getrf(a):\n    ok = resolve_abft(None)\n    return a\n\n\n"
            "def getrf(a, opts=None):\n    return health.finalize(a)\n"),
        "slate_tpu/drivers/cholesky.py": (
            "from ..robust import health\n\n\n"
            "def potrf(a, opts=None):\n    ok = resolve_abft(None)\n"
            "    return health.finalize(a)\n"),
        "slate_tpu/drivers/mixed.py": (
            "from ..robust import health\n\n\n"
            "def gesv_mixed(a, opts=None):\n"
            "    spec = resolve_speculate(opts)\n"
            "    return health.finalize(a)\n"),
    }
    for name in ("band.py", "qr.py", "heev.py", "svd.py", "stedc.py",
                 "hetrf.py", "inverse.py", "condest.py"):
        files[f"slate_tpu/drivers/{name}"] = _driver(name[:-3])
    return files


def test_seam_skeleton_is_clean(tmp_path):
    root = mini_repo(tmp_path, seam_skeleton())
    assert lint(root, SEAM_IDS) == []


def _mutated(tmp_path, rel, src):
    files = seam_skeleton()
    files[rel] = src
    return mini_repo(tmp_path, files)


def test_seam001_fires_on_driver_without_opts(tmp_path):
    root = _mutated(tmp_path, "slate_tpu/drivers/qr.py",
                    _driver("qr") + "\n\ndef geqrf(a):\n    return a\n")
    fs = lint(root, SEAM_IDS)
    assert rule_ids(fs) == {"SEAM001"}
    assert fs[0].legacy == (
        f"qr.py:{fs[0].line}: public driver `geqrf` does not accept "
        f"`opts` — Option.ErrorPolicy cannot reach it")


def test_seam001_silent_on_exempt_names(tmp_path):
    root = _mutated(tmp_path, "slate_tpu/drivers/qr.py",
                    _driver("qr") + "\n\ndef lower(a):\n    return a\n")
    assert lint(root, SEAM_IDS) == []


def test_seam002_fires_without_robust_import(tmp_path):
    root = _mutated(tmp_path, "slate_tpu/drivers/band.py",
                    "def band(a, opts=None):\n    return a\n")
    fs = lint(root, SEAM_IDS)
    assert rule_ids(fs) == {"SEAM002"}
    assert "does not import the robust layer" in fs[0].legacy


def test_seam003_fires_on_import_without_health_reference(tmp_path):
    root = _mutated(tmp_path, "slate_tpu/drivers/band.py",
                    "from ..robust import health\n\n\n"
                    "def band(a, opts=None):\n    return a\n")
    fs = lint(root, SEAM_IDS)
    assert rule_ids(fs) == {"SEAM003"}


def test_seam004_fires_on_rbt_policy_import(tmp_path):
    root = _mutated(tmp_path, "slate_tpu/internal/rbt.py",
                    "from ..robust import recovery\n\n\n"
                    "def butterfly(a):\n    return a\n")
    fs = lint(root, SEAM_IDS)
    assert rule_ids(fs) == {"SEAM004"}
    assert fs[0].legacy == (
        "internal/rbt.py:1: imports the options/robust layer — the "
        "butterfly mechanism must stay policy-free (the seam is "
        "drivers/lu.py + robust/recovery.py)")


def test_seam005_fires_on_double_resolve(tmp_path):
    files = seam_skeleton()
    src = textwrap.dedent(files["slate_tpu/robust/recovery.py"]).replace(
        "def gesv_with_recovery(a, opts=None):\n"
        "    spec = resolve_speculate(opts)\n",
        "def gesv_with_recovery(a, opts=None):\n"
        "    spec = resolve_speculate(opts)\n"
        "    spec = resolve_speculate(opts)\n", 1)
    root = _mutated(tmp_path, "slate_tpu/robust/recovery.py", src)
    fs = lint(root, SEAM_IDS)
    assert rule_ids(fs) == {"SEAM005"}
    assert "resolve_speculate 2x" in fs[0].legacy


def test_seam005_fires_on_missing_escalation(tmp_path):
    files = seam_skeleton()
    src = textwrap.dedent(files["slate_tpu/robust/recovery.py"]).replace(
        "def hesv_with_recovery(a, opts=None):\n"
        "    spec = resolve_speculate(opts)\n"
        "    r = bounded_retry(a)\n",
        "def hesv_with_recovery(a, opts=None):\n"
        "    spec = resolve_speculate(opts)\n"
        "    r = a\n", 1)
    root = _mutated(tmp_path, "slate_tpu/robust/recovery.py", src)
    fs = lint(root, SEAM_IDS)
    assert "never routes through bounded_retry" in fs[0].legacy


def test_seam006_fires_on_speculate_knob_in_driver(tmp_path):
    root = _mutated(tmp_path, "slate_tpu/drivers/svd.py",
                    _driver("svd") +
                    "\n\ndef peek(a, opts=None):\n"
                    "    return Option.Speculate\n")
    fs = lint(root, SEAM_IDS)
    assert rule_ids(fs) == {"SEAM006"}
    assert fs[0].legacy.startswith("drivers/svd.py:")


def test_seam007_fires_on_abft_raise(tmp_path):
    root = _mutated(tmp_path, "slate_tpu/robust/abft.py",
                    "def tile_check(a):\n"
                    "    raise ValueError('detected')\n")
    fs = lint(root, SEAM_IDS)
    assert rule_ids(fs) == {"SEAM007"}
    assert "detection is DATA" in fs[0].legacy


def test_seam008_fires_on_double_resolve_abft(tmp_path):
    root = _mutated(tmp_path, "slate_tpu/drivers/cholesky.py",
                    "from ..robust import health\n\n\n"
                    "def potrf(a, opts=None):\n"
                    "    ok = resolve_abft(None)\n"
                    "    ok = resolve_abft(None)\n"
                    "    return health.finalize(a)\n")
    fs = lint(root, SEAM_IDS)
    assert rule_ids(fs) == {"SEAM008"}
    assert "resolve_abft 2x" in fs[0].legacy


def test_seam009_fires_on_unknown_or_computed_site(tmp_path):
    root = _mutated(tmp_path, "slate_tpu/drivers/band.py",
                    _driver("band") +
                    "\n\ndef inject(a, s, opts=None):\n"
                    "    a = maybe_corrupt('not_a_site', a)\n"
                    "    return maybe_corrupt(s, a)\n")
    fs = lint(root, SEAM_IDS)
    assert rule_ids(fs) == {"SEAM009"}
    msgs = " ".join(f.legacy for f in fs)
    assert "'not_a_site' not in faults.SITES" in msgs
    assert "not a string literal" in msgs


def test_seam009_silent_on_vocabulary_site(tmp_path):
    root = _mutated(tmp_path, "slate_tpu/drivers/band.py",
                    _driver("band") +
                    "\n\ndef inject(a, opts=None):\n"
                    "    return maybe_corrupt('site_a', a)\n")
    assert lint(root, SEAM_IDS) == []


def test_seam010_fires_on_abft_knob_in_driver(tmp_path):
    root = _mutated(tmp_path, "slate_tpu/drivers/hetrf.py",
                    _driver("hetrf") +
                    "\n\ndef peek(a, opts=None):\n"
                    "    return Option.Abft\n")
    fs = lint(root, SEAM_IDS)
    assert rule_ids(fs) == {"SEAM010"}


def test_legacy_report_order_matches_old_checker(tmp_path):
    """The shim's report groups speculation -> abft -> per-module, exactly
    the pre-migration ordering (tools/check_error_contracts.py)."""
    files = seam_skeleton()
    files["slate_tpu/internal/rbt.py"] = (
        "from ..robust import recovery\n\ndef butterfly(a):\n    return a\n")
    files["slate_tpu/drivers/band.py"] = (
        "def band(a):\n    return a\n")
    root = mini_repo(tmp_path, files)
    report = seams.legacy_report(load_project(root))
    assert len(report) == 3
    assert report[0].startswith("internal/rbt.py:1:")         # point 4
    assert report[1].startswith("band.py: does not import")   # point 2
    assert report[2].startswith("band.py:1: public driver")   # point 1


# --------------------------------------------------------------------------
# suppressions, baseline, CLI


def test_inline_and_standalone_suppressions(tmp_path):
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": _jit_mod("""\
        y = jnp.sum(x)
        if y > 0:  # slate-lint: disable=TRC001 -- demo reason
            x = -x
        # slate-lint: disable=TRC001 -- standalone form
        if y > 1:
            x = x + 1
        if y > 2:
            x = x * 2
        return x
        """)})
    fs = lint(root, {"TRC001"})
    assert [f.line for f in fs] == [15]   # only the unsuppressed branch


def test_suppression_parsing_units():
    sup = parse_suppressions([
        (3, "# slate-lint: disable=TRC001,COL002 -- why", False),
        (7, "# slate-lint: disable=all", True),
    ])
    assert sup[3] == {"TRC001", "COL002"}
    assert sup[7] == {"all"} and sup[8] == {"all"}


def test_cli_baseline_roundtrip(tmp_path, capsys):
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": _jit_mod("""\
        if jnp.sum(x) > 0:
            return x
        return -x
        """)})
    bl = tmp_path / "baseline.json"
    args = ["--root", str(root), "--select", "TRC001",
            "--baseline", str(bl)]
    assert cli.main(args) == 1
    assert cli.main(args + ["--update-baseline"]) == 0
    assert json.loads(bl.read_text())          # non-empty fingerprints
    assert cli.main(args) == 0                 # baselined -> clean
    capsys.readouterr()


def test_cli_json_format(tmp_path, capsys):
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": _jit_mod("""\
        if jnp.sum(x) > 0:
            return x
        return -x
        """)})
    bl = tmp_path / "baseline.json"
    assert cli.main(["--root", str(root), "--select", "TRC001",
                     "--baseline", str(bl), "--format", "json"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["findings"][0]["rule"] == "TRC001"
    assert out["baselined"] == 0


def test_cli_rejects_unknown_rule(tmp_path, capsys):
    assert cli.main(["--root", str(tmp_path), "--select", "NOPE9"]) == 2
    capsys.readouterr()


def test_cli_list_rules(capsys):
    assert cli.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    assert "TRC001" in out and "COL003" in out and "SEAM010" in out


# --------------------------------------------------------------------------
# engine units


def test_reachability_entry_forms(tmp_path):
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": """\
        import jax
        from functools import partial


        @jax.jit
        def a(x):
            return b(x)


        def b(x):
            return x


        @partial(jax.jit, static_argnames=("n",))
        def c(x, n):
            return x


        def never(x):
            return x
        """})
    reach = reachability.compute(load_project(root))
    t = {k.split("::")[1] for k in reach.traced}
    assert t == {"a", "b", "c"}
    assert reach.functions["slate_tpu/mod.py::c"].static_params == {"n"}


PALLAS_PARTIAL = """\
    from functools import partial

    import jax.experimental.pallas as pl


    def _kernel(a_ref, o_ref, *, bw):
        o_ref[...] = a_ref[...] * bw


    def run(a, bw):
        return pl.pallas_call(
            partial(_kernel, bw=bw),
            out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype))(a)
    """


def test_pallas_call_partial_marks_kernel_entry(tmp_path):
    """pallas_call(partial(_kernel, bw=bw), ...) must mark _kernel as a
    traced entry with partial's keywords static — the fused-kernel idiom
    (pallas_chol/pallas_lu) was invisible to reachability before."""
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": PALLAS_PARTIAL})
    reach = reachability.compute(load_project(root))
    info = reach.functions["slate_tpu/mod.py::_kernel"]
    assert info.is_entry
    assert info.static_params == {"bw"}


def test_trc_fires_inside_partial_wrapped_kernel(tmp_path):
    """A trace hazard INSIDE a partial-wrapped kernel body is now caught:
    branching on ref data is TRC001, but branching on the partial-bound
    static keyword is fine."""
    bad = PALLAS_PARTIAL.replace(
        "        o_ref[...] = a_ref[...] * bw\n",
        "        if a_ref[0, 0] > 0:\n"
        "            o_ref[...] = a_ref[...]\n")
    bad_dir = tmp_path / "bad"
    bad_dir.mkdir()
    root = mini_repo(bad_dir, {"slate_tpu/mod.py": bad})
    assert "TRC001" in rule_ids(lint(root, {"TRC001"}))

    good = PALLAS_PARTIAL.replace(
        "        o_ref[...] = a_ref[...] * bw\n",
        "        if bw > 4:\n"
        "            o_ref[...] = a_ref[...]\n")
    good_dir = tmp_path / "good"
    good_dir.mkdir()
    root2 = mini_repo(good_dir, {"slate_tpu/mod.py": good})
    assert lint(root2, {"TRC001"}) == []


PALLAS_PREFETCH = """\
    from functools import partial

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu


    def _kernel(tiles_ref, a_ref, o_ref, *, bw):
        if bw > 4:
            o_ref[...] = a_ref[...]
        if tiles_ref[0] > 0:
            o_ref[...] = a_ref[...] * 2.0


    def run(a, tiles, bw):
        return pl.pallas_call(
            partial(_kernel, bw=bw),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 8), lambda i, tiles: (i, 0))],
                out_specs=pl.BlockSpec((8, 8), lambda i, tiles: (i, 0))),
            out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype))(tiles, a)
    """


def test_prefetch_grid_spec_marks_scalar_refs_static(tmp_path):
    """An inline PrefetchScalarGridSpec(num_scalar_prefetch=N) makes the
    kernel's first N params scalar-prefetch refs: reachability records
    them static alongside partial-bound keywords, so the ragged batched
    kernels' size-vector reads do not fire trace rules."""
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": PALLAS_PREFETCH})
    reach = reachability.compute(load_project(root))
    info = reach.functions["slate_tpu/mod.py::_kernel"]
    assert info.is_entry
    assert {"tiles_ref", "bw"} <= info.static_params
    assert lint(root, {"TRC001"}) == []

    # bare-Name kernels (no partial) get the same treatment
    bare = PALLAS_PREFETCH.replace("partial(_kernel, bw=bw),",
                                   "_kernel,").replace(", *, bw", "")
    bare = bare.replace("        if bw > 4:\n"
                        "            o_ref[...] = a_ref[...]\n", "")
    bare_dir = tmp_path / "bare"
    bare_dir.mkdir()
    root2 = mini_repo(bare_dir, {"slate_tpu/mod.py": bare})
    reach2 = reachability.compute(load_project(root2))
    assert "tiles_ref" in \
        reach2.functions["slate_tpu/mod.py::_kernel"].static_params
    assert lint(root2, {"TRC001"}) == []


def test_seam011_fires_on_raw_plan_cache_outside_tune(tmp_path):
    """A driver touching the raw autotuner plan cache (instead of
    resolve_plan) fires SEAM011; the tune package itself is exempt."""
    files = seam_skeleton()
    files["slate_tpu/drivers/qr.py"] = (
        "from ..robust import health\n"
        "from ..tune.plans import load_cache\n\n\n"
        "def qr(a, opts=None):\n"
        "    plans = load_cache()\n"
        "    return health.finalize(a)\n")
    fs = lint(mini_repo(tmp_path, files), SEAM_IDS)
    assert rule_ids(fs) == {"SEAM011"}
    assert "load_cache" in fs[0].message


def test_seam011_silent_inside_tune_and_via_resolver(tmp_path):
    files = seam_skeleton()
    files["slate_tpu/tune/plans.py"] = (
        "def load_cache():\n    return {}\n\n\n"
        "def resolve_plan(op, n, dtype='float32'):\n"
        "    return load_cache().get(op)\n")
    files["slate_tpu/drivers/qr.py"] = (
        "from ..robust import health\n"
        "from ..tune.plans import resolve_plan\n\n\n"
        "def qr(a, opts=None):\n"
        "    plan = resolve_plan('geqrf_panel', 128)\n"
        "    return health.finalize(a)\n")
    assert lint(mini_repo(tmp_path, files), SEAM_IDS) == []


def test_seam012_fires_on_direct_compile_in_serve(tmp_path):
    """serve/ modules other than cache.py compiling for themselves
    (jax.jit / lower / compile) bypass the executable-cache accounting
    and fire SEAM012."""
    files = seam_skeleton()
    files["slate_tpu/serve/server.py"] = (
        "import jax\n\n\n"
        "def run(fn, a):\n"
        "    exe = jax.jit(fn).lower(a).compile()\n"
        "    return exe(a)\n")
    fs = lint(mini_repo(tmp_path, files), SEAM_IDS)
    assert rule_ids(fs) == {"SEAM012"}
    assert any("jit" in f.message for f in fs)


def test_seam012_silent_in_cache_and_via_cache(tmp_path):
    """serve/cache.py is the one sanctioned compile site; a server that
    gets executables from it stays clean."""
    files = seam_skeleton()
    files["slate_tpu/serve/cache.py"] = (
        "import jax\n\n\n"
        "def get_or_compile(fn, spec):\n"
        "    return jax.jit(fn).lower(spec).compile()\n")
    files["slate_tpu/serve/server.py"] = (
        "from .cache import get_or_compile\n\n\n"
        "def run(fn, a):\n"
        "    exe = get_or_compile(fn, a)\n"
        "    return exe(a)\n")
    assert lint(mini_repo(tmp_path, files), SEAM_IDS) == []


def test_seam014_fires_on_low_precision_cast_in_driver(tmp_path):
    """An astype to a literal low-precision spelling inside drivers/
    bypasses the robust/precision.py seam (and its f32-accumulation
    contract) and fires SEAM014."""
    files = seam_skeleton()
    files["slate_tpu/drivers/qr.py"] = (
        "from ..robust import health\n"
        "import jax.numpy as jnp\n\n\n"
        "def qr(a, opts=None):\n"
        "    low = a.astype(jnp.bfloat16)\n"
        "    return health.finalize(low)\n")
    fs = lint(mini_repo(tmp_path, files), SEAM_IDS)
    assert rule_ids(fs) == {"SEAM014"}
    assert "bfloat16" in fs[0].message


def test_seam014_fires_on_dtype_kwarg_in_serve(tmp_path):
    """A dtype= keyword spelling low precision inside serve/ is the same
    bypass in allocation form ('bf16' string alias included)."""
    files = seam_skeleton()
    files["slate_tpu/serve/server.py"] = (
        "import jax.numpy as jnp\n\n\n"
        "def pack(n):\n"
        "    return jnp.zeros((n, n), dtype='bf16')\n")
    fs = lint(mini_repo(tmp_path, files), SEAM_IDS)
    assert rule_ids(fs) == {"SEAM014"}


def test_seam014_fires_on_raw_precision_knob(tmp_path):
    """Reading Option.Precision outside robust/precision.py (and the enum
    definition in options.py) fires SEAM014: boundaries consume
    resolve_precision's boolean, resolved exactly once."""
    files = seam_skeleton()
    files["slate_tpu/drivers/hetrf.py"] = (
        _driver("hetrf") +
        "\n\ndef peek(a, opts=None):\n"
        "    return opts.get(Option.Precision)\n")
    fs = lint(mini_repo(tmp_path, files), SEAM_IDS)
    assert rule_ids(fs) == {"SEAM014"}


def test_seam014_fires_on_double_resolve_precision(tmp_path):
    """A precision boundary resolving the knob twice breaks the
    resolve-exactly-once contract, same as SEAM005/SEAM008."""
    files = seam_skeleton()
    files["slate_tpu/serve/batched.py"] = (
        "def make_batched(op, shape, dtype, batch, opts=None):\n"
        "    low = resolve_precision(opts)\n"
        "    low2 = resolve_precision(opts)\n"
        "    return op\n")
    fs = lint(mini_repo(tmp_path, files), SEAM_IDS)
    assert rule_ids(fs) == {"SEAM014"}
    assert "EXACTLY once" in fs[0].message


def test_seam014_silent_on_lax_precision_and_high_casts(tmp_path):
    """jax's own lax.Precision attribute and high-precision casts
    (astype(jnp.float32)) must NOT trip the rule — the knob match is
    exact on the `Option` base name, the cast ban only on low spellings.
    The precision seam itself (robust/precision.py) may demote freely."""
    files = seam_skeleton()
    files["slate_tpu/drivers/qr.py"] = (
        "from ..robust import health\n"
        "import jax.numpy as jnp\n"
        "from jax import lax\n\n\n"
        "def qr(a, opts=None):\n"
        "    p = lax.Precision.HIGHEST\n"
        "    up = a.astype(jnp.float32)\n"
        "    return health.finalize(up)\n")
    files["slate_tpu/robust/precision.py"] = (
        "import jax.numpy as jnp\n\n\n"
        "def demote(x):\n"
        "    return x.astype(jnp.bfloat16)\n\n\n"
        "def resolve_precision(opts):\n"
        "    return bool(opts and opts.get(Option.Precision))\n")
    assert lint(mini_repo(tmp_path, files), SEAM_IDS) == []


# --------------------------------------------------------------------------
# observability pack (OBS001)


def test_obs001_fires_on_adhoc_telemetry(tmp_path):
    """print / logging / io_callback in drivers, internal, or parallel
    modules bypass the obs spine and fire OBS001."""
    root = mini_repo(tmp_path, {
        "slate_tpu/drivers/qr.py": (
            "def qr(a, opts=None):\n"
            "    print('factoring', a)\n"
            "    return a\n"),
        "slate_tpu/internal/gemm.py": (
            "import logging\n\n"
            "log = logging.getLogger(__name__)\n\n\n"
            "def gemm(a, b):\n"
            "    log.info('gemm')\n"
            "    return a\n"),
        "slate_tpu/parallel/dist_lu.py": (
            "from jax.experimental import io_callback\n\n\n"
            "def dist_getrf(a):\n"
            "    io_callback(lambda x: x, None, a)\n"
            "    return a\n"),
    })
    fs = lint(root, {"OBS001"})
    assert rule_ids(fs) == {"OBS001"}
    paths = {f.path for f in fs}
    assert paths == {"slate_tpu/drivers/qr.py",
                     "slate_tpu/internal/gemm.py",
                     "slate_tpu/parallel/dist_lu.py"}


def test_obs001_silent_on_obs_spine_and_printing(tmp_path):
    """The sanctioned telemetry routes stay silent: annotate/span from
    util.trace, and drivers/printing.py (stdout IS its contract)."""
    root = mini_repo(tmp_path, {
        "slate_tpu/drivers/qr.py": (
            "from ..util.trace import annotate, span\n\n\n"
            "@annotate('slate.geqrf')\n"
            "def geqrf(a, opts=None):\n"
            "    with span('slate.geqrf/panel'):\n"
            "        return a\n"),
        "slate_tpu/drivers/printing.py": (
            "def pprint(a):\n"
            "    print(a)\n"),
        "slate_tpu/obs/events.py": (
            "def emit(line):\n"
            "    print(line)\n"),
    })
    assert lint(root, {"OBS001"}) == []


def test_registry_has_required_rule_surface():
    assert len(REGISTRY) >= 30
    packs = {"TRC", "COL", "SEAM", "OBS", "CON"}
    assert {r[:3] if not r.startswith("SEAM") else "SEAM"
            for r in REGISTRY} == packs


# --------------------------------------------------------------------------
# tier-1: the live repo is lint-clean with an empty baseline diff


def test_repo_is_lint_clean(tmp_path, capsys):
    """The tier-1 gate AND artifact: the repo is clean under the full
    rule surface (all packs, call graph enabled) and the JSON report CI
    archives says so explicitly."""
    artifact = tmp_path / "slate-lint.json"
    assert cli.main(["--root", str(REPO), "--output", str(artifact)]) == 0
    capsys.readouterr()
    report = json.loads(artifact.read_text())
    assert report["findings"] == []
    assert report["baselined"] == 0 and report["stale_baseline"] == []
    assert len(report["rules"]) >= 30
    for pack in ("TRC", "COL", "SEAM", "OBS", "CON"):
        assert any(r.startswith(pack) for r in report["rules"])


def test_repo_baseline_is_empty():
    assert json.loads(
        (REPO / "tools/slate_lint/baseline.json").read_text()) == []


# --------------------------------------------------------------------------
# observability pack (OBS002)


FLOPS_FIXTURE = """\
    def register(*names):
        def deco(fn):
            return fn
        return deco


    @register("gesv", "posv")
    def _f(shapes, sizes):
        return 1.0
    """


def test_obs002_fires_on_unpriced_driver(tmp_path):
    """An @annotate-decorated driver whose op has no flops model in
    obs/flops.py means a silent `mfu: n/a` forever — OBS002 flags the
    decorator line."""
    root = mini_repo(tmp_path, {
        "slate_tpu/obs/flops.py": FLOPS_FIXTURE,
        "slate_tpu/drivers/qr.py": (
            "from ..util.trace import annotate\n\n\n"
            "@annotate('slate.geqrf')\n"
            "def geqrf(a, opts=None):\n"
            "    return a\n"),
    })
    fs = lint(root, {"OBS002"})
    assert rule_ids(fs) == {"OBS002"}
    (f,) = fs
    assert f.path == "slate_tpu/drivers/qr.py" and f.line == 4
    assert "geqrf" in f.message and "flops model" in f.message


def test_obs002_silent_on_registered_or_disabled(tmp_path):
    """Registered ops pass; unregistered ops with an explicit reasoned
    disable (the band-driver pattern) pass too."""
    root = mini_repo(tmp_path, {
        "slate_tpu/obs/flops.py": FLOPS_FIXTURE,
        "slate_tpu/drivers/lu.py": (
            "from ..util.trace import annotate\n\n\n"
            "@annotate('slate.gesv')\n"
            "def gesv(a, b, opts=None):\n"
            "    return a\n"),
        "slate_tpu/drivers/band.py": (
            "from ..util.trace import annotate\n\n\n"
            "@annotate('slate.pbsv')  "
            "# slate-lint: disable=OBS002 -- needs bandwidth, not shapes\n"
            "def pbsv(a, b, opts=None):\n"
            "    return a\n"),
    })
    assert lint(root, {"OBS002"}) == []


def test_obs002_silent_without_flops_module(tmp_path):
    """Mini-repos with no obs/flops.py have no registry to check against;
    the rule stands down instead of flagging everything."""
    root = mini_repo(tmp_path, {
        "slate_tpu/drivers/qr.py": (
            "from ..util.trace import annotate\n\n\n"
            "@annotate('slate.geqrf')\n"
            "def geqrf(a, opts=None):\n"
            "    return a\n"),
    })
    assert lint(root, {"OBS002"}) == []


def test_obs002_clean_on_live_repo():
    """The real tree holds the invariant: every annotate-decorated driver
    is either priced in obs/flops.py or carries a reasoned disable."""
    assert lint(REPO, {"OBS002"}) == []


# --------------------------------------------------------------------------
# call graph: re-export, dict-dispatch, and method edges


def test_reexport_edge_traces_through_init(tmp_path):
    """pkg.work where pkg/__init__.py merely re-exports work from a
    submodule: dotted resolution follows the import chain to the def."""
    root = mini_repo(tmp_path, {
        "slate_tpu/pkg/__init__.py": "from .impl import work\n",
        "slate_tpu/pkg/impl.py": "def work(x):\n    return x\n",
        "slate_tpu/mod.py": """\
            import jax
            from . import pkg


            @jax.jit
            def entry(x):
                return pkg.work(x)
            """,
    })
    reach = reachability.compute(load_project(root))
    assert "slate_tpu/pkg/impl.py::work" in reach.traced


def test_dispatch_table_call_and_alias_edges(tmp_path):
    """The serve.CORES idiom: CORES[op](...) and the two-step
    core = CORES[op]; vmap(lambda ...: core(...)) both reach EVERY
    table value."""
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": """\
        import jax


        def solve_core(a):
            return a


        def chol_core(a):
            return a


        CORES = {"solve": solve_core, "chol": chol_core}


        def direct(op, a):
            return CORES[op](a)


        def via_alias(op, a):
            core = CORES[op]
            return jax.vmap(lambda ai: core(ai))(a)
        """})
    reach = reachability.compute(load_project(root))
    assert reach.dispatch_tables["slate_tpu/mod.py"]["CORES"] == (
        "slate_tpu/mod.py::solve_core", "slate_tpu/mod.py::chol_core")
    direct = reach.functions["slate_tpu/mod.py::direct"]
    assert {"slate_tpu/mod.py::solve_core",
            "slate_tpu/mod.py::chol_core"} <= direct.resolved_calls
    # the vmap(lambda: core(...)) closure marks the table values ENTRIES
    assert reach.functions["slate_tpu/mod.py::solve_core"].is_entry
    assert "vmap" in reach.entry_kinds["slate_tpu/mod.py::chol_core"]


def test_callgraph_facade_method_and_reverse_edges(tmp_path):
    from tools.slate_lint import callgraph
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": """\
        def helper(x):
            return x


        class Box:
            def outer(self):
                return self.inner()

            def inner(self):
                return helper(1)
        """})
    cg = callgraph.compute(load_project(root))
    outer = "slate_tpu/mod.py::Box.outer"
    inner = "slate_tpu/mod.py::Box.inner"
    helper = "slate_tpu/mod.py::helper"
    assert inner in cg.callees(outer)
    assert helper in cg.callees(inner)
    assert outer in cg.callers(inner)
    assert inner in cg.callers(helper)


# --------------------------------------------------------------------------
# interprocedural taint


def test_interprocedural_taint_crosses_modules(tmp_path):
    """A traced entry passing a traced value into a helper in ANOTHER
    module taints the helper's parameter: the branch inside fires."""
    root = mini_repo(tmp_path, {
        "slate_tpu/helper.py": """\
            def branchy(v):
                if v > 0:
                    return v
                return -v
            """,
        "slate_tpu/mod.py": """\
            import jax
            import jax.numpy as jnp
            from . import helper


            @jax.jit
            def entry(x):
                return helper.branchy(jnp.sum(x))
            """,
    })
    fs = lint(root, {"TRC001"})
    assert [(f.path, f.line) for f in fs] == [("slate_tpu/helper.py", 2)]


def test_interprocedural_taint_respects_annotations(tmp_path):
    """A parameter annotated with a non-array host type (int) is never
    interprocedurally seeded — annotations declare the eager contract."""
    root = mini_repo(tmp_path, {
        "slate_tpu/helper.py": """\
            def branchy(v: int):
                if v > 0:
                    return v
                return -v
            """,
        "slate_tpu/mod.py": """\
            import jax
            from . import helper


            @jax.jit
            def entry(x):
                return helper.branchy(x.shape[0])
            """,
    })
    assert lint(root, {"TRC001"}) == []


def test_return_taint_summary_distinguishes_static(tmp_path):
    """Branching on a callee's return fires only when the callee
    actually returns traced data — a static .shape return stays clean."""
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": """\
        import jax
        import jax.numpy as jnp


        def size_of(x):
            return x.shape[0]


        def total(x):
            return jnp.sum(x)


        @jax.jit
        def entry(x):
            if size_of(x) > 2:
                x = x * 2
            if total(x) > 0:
                x = x + 1
            return x
        """})
    fs = lint(root, {"TRC001"})
    assert [f.line for f in fs] == [17]


def test_return_taint_tuple_elements_are_elementwise(tmp_path):
    """Tuple-returning callees get element-wise summaries: destructured
    static elements never taint."""
    root = mini_repo(tmp_path, {"slate_tpu/mod.py": """\
        import jax


        def padded(x):
            return x * 2, x.shape[0]


        @jax.jit
        def entry(x):
            y, n = padded(x)
            if n > 4:
                y = y + 1
            return y
        """})
    assert lint(root, {"TRC001"}) == []


# --------------------------------------------------------------------------
# collective-sequence pack (COL005-COL008)


COL_GRID = {"slate_tpu/core/grid.py": GRID}


def _col_mod(body):
    return ("import jax\nimport jax.numpy as jnp\n"
            "from jax import lax\n"
            "from .core.grid import AXIS_P\n\n\n" + textwrap.dedent(body))


def test_col005_fires_on_tainted_predicate(tmp_path):
    root = mini_repo(tmp_path, {**COL_GRID, "slate_tpu/mod.py": _col_mod("""\
        def _yes(x):
            return lax.psum(x, AXIS_P)


        def _no(x):
            return x


        @jax.jit
        def entry(x):
            pred = jnp.sum(x) > 0
            return lax.cond(pred, _yes, _no, x)
        """)})
    fs = lint(root, {"COL005"})
    assert [f.rule for f in fs] == ["COL005"]


def test_col005_silent_on_static_predicate(tmp_path):
    root = mini_repo(tmp_path, {**COL_GRID, "slate_tpu/mod.py": _col_mod("""\
        def _yes(x):
            return lax.psum(x, AXIS_P)


        def _no(x):
            return x


        @jax.jit
        def entry(x):
            return lax.cond(x.ndim > 1, _yes, _no, x)
        """)})
    assert lint(root, {"COL005"}) == []


def test_col006_fires_on_differing_branch_sequences(tmp_path):
    root = mini_repo(tmp_path, {**COL_GRID, "slate_tpu/mod.py": _col_mod("""\
        def _a(x):
            return lax.psum(x, AXIS_P)


        def _b(x):
            return lax.pmax(x, AXIS_P)


        @jax.jit
        def entry(x):
            return lax.cond(x.ndim > 1, _a, _b, x)
        """)})
    fs = lint(root, {"COL006"})
    assert [f.rule for f in fs] == ["COL006"]
    assert "psum@p" in fs[0].message and "pmax@p" in fs[0].message


def test_col006_silent_on_matching_sequences(tmp_path):
    root = mini_repo(tmp_path, {**COL_GRID, "slate_tpu/mod.py": _col_mod("""\
        def _a(x):
            return lax.psum(x, AXIS_P) * 2


        def _b(x):
            return lax.psum(x, AXIS_P) + 1


        @jax.jit
        def entry(x):
            return lax.cond(x.ndim > 1, _a, _b, x)
        """)})
    assert lint(root, {"COL006"}) == []


def test_col007_fires_on_collective_in_while_loop(tmp_path):
    root = mini_repo(tmp_path, {**COL_GRID, "slate_tpu/mod.py": _col_mod("""\
        def _cond(s):
            return jnp.sum(s) > 0


        def _body(s):
            return s - lax.psum(s, AXIS_P)


        @jax.jit
        def entry(x):
            return lax.while_loop(_cond, _body, x)
        """)})
    fs = lint(root, {"COL007"})
    assert [f.rule for f in fs] == ["COL007"]


def test_col007_fires_on_fori_with_tainted_bounds(tmp_path):
    root = mini_repo(tmp_path, {**COL_GRID, "slate_tpu/mod.py": _col_mod("""\
        def _body(i, s):
            return s + lax.psum(s, AXIS_P)


        @jax.jit
        def entry(x, n):
            return lax.fori_loop(0, n, _body, x)
        """)})
    fs = lint(root, {"COL007"})
    assert [f.rule for f in fs] == ["COL007"]


def test_col007_silent_on_static_bounds_and_plain_loops(tmp_path):
    root = mini_repo(tmp_path, {**COL_GRID, "slate_tpu/mod.py": _col_mod("""\
        def _body(i, s):
            return s + lax.psum(s, AXIS_P)


        def _dense_cond(s):
            return jnp.sum(s) > 0


        def _dense_body(s):
            return s * 0.5


        @jax.jit
        def entry(x):
            x = lax.fori_loop(0, 8, _body, x)
            return lax.while_loop(_dense_cond, _dense_body, x)
        """)})
    assert lint(root, {"COL007"}) == []


def test_col008_fires_on_mismatched_ring_shifts(tmp_path):
    root = mini_repo(tmp_path, {**COL_GRID, "slate_tpu/mod.py": _col_mod("""\
        def step(x):
            y = lax.ppermute(x, AXIS_P,
                             [(i, (i + 1) % 4) for i in range(4)])
            z = lax.ppermute(x, AXIS_P,
                             [(i, (i - 1) % 4) for i in range(4)])
            return y + z
        """)})
    fs = lint(root, {"COL008"})
    assert [f.rule for f in fs] == ["COL008"]
    assert fs[0].line == 10                  # anchored at the later site


def test_col008_silent_on_consistent_ring(tmp_path):
    root = mini_repo(tmp_path, {**COL_GRID, "slate_tpu/mod.py": _col_mod("""\
        def step(x):
            y = lax.ppermute(x, AXIS_P,
                             [(i, (i + 1) % 4) for i in range(4)])
            z = lax.ppermute(x, AXIS_P,
                             [(i, (i + 1) % 4) for i in range(4)])
            return y + z
        """)})
    assert lint(root, {"COL008"}) == []


def test_col008_silent_on_double_buffered_loop_body(tmp_path):
    # the lookahead idiom: the pipeline's loop body ring-shifts BOTH
    # the live panel and the in-flight prefetch buffer along the same +1
    # ring — two same-direction hops per axis are one consistent ring
    root = mini_repo(tmp_path, {**COL_GRID, "slate_tpu/mod.py": _col_mod("""\
        def body(k, carry):
            cur, nxt = carry
            cur = lax.ppermute(cur, AXIS_P,
                               [(i, (i + 1) % 4) for i in range(4)])
            nxt = lax.ppermute(nxt, AXIS_P,
                               [(i, (i + 1) % 4) for i in range(4)])
            return (nxt, cur)


        @jax.jit
        def entry(x):
            return lax.fori_loop(0, 8, body, (x, x))
        """)})
    assert lint(root, {"COL008"}) == []


def test_col008_fires_on_double_buffer_direction_mismatch(tmp_path):
    # ...but a prefetch buffer shifted AGAINST the live panel's ring
    # means the two buffers' send/recv partners never pair up
    root = mini_repo(tmp_path, {**COL_GRID, "slate_tpu/mod.py": _col_mod("""\
        def body(k, carry):
            cur, nxt = carry
            cur = lax.ppermute(cur, AXIS_P,
                               [(i, (i + 1) % 4) for i in range(4)])
            nxt = lax.ppermute(nxt, AXIS_P,
                               [(i, (i - 1) % 4) for i in range(4)])
            return (nxt, cur)


        @jax.jit
        def entry(x):
            return lax.fori_loop(0, 8, body, (x, x))
        """)})
    fs = lint(root, {"COL008"})
    assert [f.rule for f in fs] == ["COL008"]


def test_col006_pipeline_epilogue_must_keep_ring_sequence(tmp_path):
    # lookahead pipeline shape: prologue ring hop, then a steady-state
    # cond whose taken arm rings the NEXT panel and psums the update.
    # An epilogue arm that drops the ring (instead of only local work)
    # diverges the branch collective sequences and fires.
    root = mini_repo(tmp_path, {**COL_GRID, "slate_tpu/mod.py": _col_mod("""\
        def _steady(x):
            nxt = lax.ppermute(x, AXIS_P,
                               [(i, (i + 1) % 4) for i in range(4)])
            return nxt + lax.psum(x, AXIS_P)


        def _epilogue(x):
            return x + lax.psum(x, AXIS_P)


        @jax.jit
        def entry(x):
            x = lax.ppermute(x, AXIS_P,
                             [(i, (i + 1) % 4) for i in range(4)])
            return lax.cond(x.ndim > 1, _steady, _epilogue, x)
        """)})
    fs = lint(root, {"COL006"})
    assert [f.rule for f in fs] == ["COL006"]
    assert "ppermute@p" in fs[0].message


def test_col006_silent_on_uniform_pipeline_sequences(tmp_path):
    # the CORRECT epilogue keeps the ring (a dead hop on zeroed data,
    # exactly how the pipelined kernels retire their final clamped
    # issue) so prologue/steady-state/epilogue all run one sequence
    root = mini_repo(tmp_path, {**COL_GRID, "slate_tpu/mod.py": _col_mod("""\
        def _steady(x):
            nxt = lax.ppermute(x, AXIS_P,
                               [(i, (i + 1) % 4) for i in range(4)])
            return nxt + lax.psum(x, AXIS_P)


        def _epilogue(x):
            dead = lax.ppermute(x * 0.0, AXIS_P,
                                [(i, (i + 1) % 4) for i in range(4)])
            return dead + lax.psum(x, AXIS_P)


        @jax.jit
        def entry(x):
            x = lax.ppermute(x, AXIS_P,
                             [(i, (i + 1) % 4) for i in range(4)])
            return lax.cond(x.ndim > 1, _steady, _epilogue, x)
        """)})
    assert lint(root, {"COL006"}) == []


# --------------------------------------------------------------------------
# lock-discipline pack (CON001-CON003)


EVENTS_FIXTURE_HEADER = """\
import threading

_LOCK = threading.Lock()
_CFG = {"enabled": False}
_RING = []
_COLLECTORS = []


"""


def test_con001_fires_on_unlocked_module_state(tmp_path):
    root = mini_repo(tmp_path, {
        "slate_tpu/obs/events.py": EVENTS_FIXTURE_HEADER + (
            "def toggle(on):\n"
            "    _CFG[\"enabled\"] = on\n"),
    })
    fs = lint(root, {"CON001"})
    assert [f.rule for f in fs] == ["CON001"]
    assert "_CFG" in fs[0].message


def test_con001_silent_when_locked_or_suppressed(tmp_path):
    root = mini_repo(tmp_path, {
        "slate_tpu/obs/events.py": EVENTS_FIXTURE_HEADER + (
            "def toggle(on):\n"
            "    with _LOCK:\n"
            "        _CFG[\"enabled\"] = on\n\n\n"
            "def peek():\n"
            "    # slate-lint: disable=CON001 -- lock-free fast-path peek\n"
            "    return _CFG[\"enabled\"]\n"),
    })
    assert lint(root, {"CON001"}) == []


def test_con001_mutation_of_real_server_is_caught(tmp_path):
    """The acceptance mutation: drop one `with self._lock:` from the real
    server.py and CON001 must fire; the pristine text stays clean."""
    real = (REPO / "slate_tpu/serve/server.py").read_text()
    good = mini_repo(tmp_path / "good",
                     {"slate_tpu/serve/server.py": real})
    assert lint(good, {"CON001"}) == []
    mutated = real.replace("with self._lock:", "if True:", 1)
    assert mutated != real
    bad = mini_repo(tmp_path / "bad",
                    {"slate_tpu/serve/server.py": mutated})
    fs = lint(bad, {"CON001"})
    assert fs and all(f.rule == "CON001" for f in fs)
    guards = ("_inflight", "_flush_deadline", "_wedged", "_flush_error",
              "_quarantined", "_flusher", "_watchdog", "_ladders",
              "_sizes", "_retunes", "_retuning", "_last_retune")
    assert all(any(g in f.message for g in guards) for f in fs)


def test_con001_mutation_of_real_admission_queue_is_caught(tmp_path):
    """Same acceptance mutation for the survival layer's intake: unlock
    take_all()'s item swap in the real admission.py and CON001 fires on
    the queue state."""
    real = (REPO / "slate_tpu/serve/admission.py").read_text()
    good = mini_repo(tmp_path / "good",
                     {"slate_tpu/serve/admission.py": real})
    assert lint(good, {"CON001"}) == []
    locked = ("        with self._lock:\n"
              "            items, self._items = self._items, []")
    assert locked in real
    mutated = real.replace(
        locked, "        if True:\n"
                "            items, self._items = self._items, []", 1)
    bad = mini_repo(tmp_path / "bad",
                    {"slate_tpu/serve/admission.py": mutated})
    fs = lint(bad, {"CON001"})
    assert fs and all(f.rule == "CON001" for f in fs)
    assert all("_items" in f.message for f in fs)


ADMISSION_FIXTURE = """\
import threading


class AdmissionQueue:
    def __init__(self):
        self._lock = threading.Condition()
        self._items = []
        self._shed = 0
        self._closed = None

    def depth(self):
        with self._lock:
            return len(self._items)
"""


def test_con001_fires_on_unlocked_queue_state(tmp_path):
    root = mini_repo(tmp_path, {
        "slate_tpu/serve/admission.py": ADMISSION_FIXTURE + (
            "\n"
            "    def sneak(self):\n"
            "        self._shed += 1\n")})
    fs = lint(root, {"CON001"})
    assert [f.rule for f in fs] == ["CON001"]
    assert "_shed" in fs[0].message


def test_con001_silent_on_locked_queue_state(tmp_path):
    root = mini_repo(tmp_path, {
        "slate_tpu/serve/admission.py": ADMISSION_FIXTURE + (
            "\n"
            "    def sneak(self):\n"
            "        with self._lock:\n"
            "            self._shed += 1\n")})
    assert lint(root, {"CON001"}) == []


def test_con002_fires_on_lock_order_inversion(tmp_path, monkeypatch):
    from tools.slate_lint.rules import concurrency as con
    monkeypatch.setattr(con, "LOCK_REGISTRY", (
        con.LockSpec("slate_tpu/a.py", None, "_LA", ("_SA",)),
        con.LockSpec("slate_tpu/b.py", None, "_LB", ("_SB",)),
    ))
    root = mini_repo(tmp_path, {
        "slate_tpu/a.py": """\
            import threading
            from . import b

            _LA = threading.Lock()
            _SA = []


            def take_a():
                with _LA:
                    _SA.append(1)


            def cross():
                with _LA:
                    b.take_b()
            """,
        "slate_tpu/b.py": """\
            import threading
            from . import a

            _LB = threading.Lock()
            _SB = []


            def take_b():
                with _LB:
                    _SB.append(1)


            def cross():
                with _LB:
                    a.take_a()
            """,
    })
    fs = lint(root, {"CON002"})
    assert [f.rule for f in fs] == ["CON002"]
    assert "inversion" in fs[0].message


def test_con002_fires_on_self_reacquire(tmp_path):
    root = mini_repo(tmp_path, {
        "slate_tpu/obs/events.py": EVENTS_FIXTURE_HEADER + (
            "def set_on():\n"
            "    with _LOCK:\n"
            "        _CFG[\"enabled\"] = True\n\n\n"
            "def flip():\n"
            "    with _LOCK:\n"
            "        set_on()\n"),
    })
    fs = lint(root, {"CON002"})
    assert [f.rule for f in fs] == ["CON002"]
    assert "re-acquires" in fs[0].message


def test_con002_silent_on_consistent_order(tmp_path, monkeypatch):
    from tools.slate_lint.rules import concurrency as con
    monkeypatch.setattr(con, "LOCK_REGISTRY", (
        con.LockSpec("slate_tpu/a.py", None, "_LA", ("_SA",)),
        con.LockSpec("slate_tpu/b.py", None, "_LB", ("_SB",)),
    ))
    root = mini_repo(tmp_path, {
        "slate_tpu/a.py": """\
            import threading
            from . import b

            _LA = threading.Lock()
            _SA = []


            def cross():
                with _LA:
                    b.take_b()
            """,
        "slate_tpu/b.py": """\
            import threading

            _LB = threading.Lock()
            _SB = []


            def take_b():
                with _LB:
                    _SB.append(1)
            """,
    })
    assert lint(root, {"CON002"}) == []


def test_con003_fires_on_compile_under_lock(tmp_path):
    root = mini_repo(tmp_path, {
        "slate_tpu/serve/cache.py": """\
            import threading

            import jax


            class ExecutableCache:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._exes = {}

                def get(self, key, fn, spec):
                    with self._lock:
                        exe = self._exes.get(key)
                        if exe is None:
                            exe = jax.jit(fn).lower(spec)
                            self._exes[key] = exe
                    return exe
            """,
    })
    fs = lint(root, {"CON003"})
    assert [f.rule for f in fs] == ["CON003"]
    assert "lower" in fs[0].message


def test_con003_silent_on_compile_outside_lock(tmp_path):
    root = mini_repo(tmp_path, {
        "slate_tpu/serve/cache.py": """\
            import threading

            import jax


            class ExecutableCache:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._exes = {}

                def get(self, key, fn, spec):
                    with self._lock:
                        exe = self._exes.get(key)
                    if exe is not None:
                        return exe
                    exe = jax.jit(fn).lower(spec)
                    with self._lock:
                        return self._exes.setdefault(key, exe)
            """,
    })
    assert lint(root, {"CON003"}) == []


# ------------------------------------------------------- device pool lock

POOL_FIXTURE = """\
import threading


class DevicePool:
    def __init__(self, devices):
        self._lock = threading.Lock()
        self._members = list(devices)
        self._rr = 0
        self._failovers = 0
        self._quarantines = 0
        self._readmissions = 0

    def stats(self):
        with self._lock:
            return {"failovers": self._failovers,
                    "quarantines": self._quarantines}
"""


def test_con001_fires_on_unlocked_pool_rotation(tmp_path):
    root = mini_repo(tmp_path, {
        "slate_tpu/serve/pool.py": POOL_FIXTURE + (
            "\n"
            "    def select(self):\n"
            "        m = self._members[self._rr]\n"
            "        self._rr += 1\n"
            "        return m\n")})
    fs = lint(root, {"CON001"})
    assert fs and all(f.rule == "CON001" for f in fs)
    assert any("_rr" in f.message for f in fs)
    assert any("_members" in f.message for f in fs)


def test_con001_silent_on_locked_pool_rotation(tmp_path):
    root = mini_repo(tmp_path, {
        "slate_tpu/serve/pool.py": POOL_FIXTURE + (
            "\n"
            "    def select(self):\n"
            "        with self._lock:\n"
            "            m = self._members[self._rr]\n"
            "            self._rr += 1\n"
            "        return m\n")})
    assert lint(root, {"CON001"}) == []


def test_con001_mutation_of_real_pool_is_caught(tmp_path):
    """The acceptance mutation for the device pool: drop one
    `with self._lock:` from the real pool.py and CON001 must fire on
    the member/rotation state; the pristine text stays clean."""
    real = (REPO / "slate_tpu/serve/pool.py").read_text()
    good = mini_repo(tmp_path / "good",
                     {"slate_tpu/serve/pool.py": real})
    assert lint(good, {"CON001"}) == []
    mutated = real.replace("with self._lock:", "if True:", 1)
    assert mutated != real
    bad = mini_repo(tmp_path / "bad",
                    {"slate_tpu/serve/pool.py": mutated})
    fs = lint(bad, {"CON001"})
    assert fs and all(f.rule == "CON001" for f in fs)
    guards = ("_members", "_rr", "_failovers", "_quarantines",
              "_readmissions")
    assert all(any(g in f.message for g in guards) for f in fs)


def test_con003_fires_on_compile_under_pool_lock(tmp_path):
    """A warm-the-executable call under the pool's member lock is the
    compile-under-lock bug class: every dispatcher thread would stall
    behind one cold compile.  get_or_compile IS the serving compile
    entry (SEAM012), so CON003 must treat it as blocking."""
    root = mini_repo(tmp_path, {
        "slate_tpu/serve/pool.py": POOL_FIXTURE + (
            "\n"
            "    def warm(self, cache, op, shape, dtype, batch):\n"
            "        with self._lock:\n"
            "            for m in self._members:\n"
            "                cache.get_or_compile(op, shape, dtype,\n"
            "                                     batch, device=m)\n")})
    fs = lint(root, {"CON003"})
    assert [f.rule for f in fs] == ["CON003"]
    assert "get_or_compile" in fs[0].message


def test_con003_real_pool_and_server_compile_outside_locks():
    """The real serving layer holds no registry lock across a compile:
    the warm pass, the canary probe and the retune warmer all call
    get_or_compile outside critical sections."""
    fs = lint(REPO, {"CON003"})
    assert fs == []


# --------------------------------------------------------------------------
# CLI: findings cache, --changed-only, --output artifact


CACHE_MINI = {
    "slate_tpu/mod.py": (
        "import jax\nimport jax.numpy as jnp\n\n\n"
        "@jax.jit\ndef entry(x):\n"
        "    if jnp.sum(x) > 0:\n"
        "        return x\n"
        "    return -x\n"),
}


def _trc_findings(report):
    """A mini repo also fires the SEAM layout rules (it has none of the
    expected modules); the cache tests key on the TRC001 finding only."""
    return [f for f in report["findings"] if f["rule"] == "TRC001"]


def test_findings_cache_replays_and_invalidates(tmp_path, capsys):
    root = mini_repo(tmp_path, CACHE_MINI)
    cache = tmp_path / "cache.json"
    out = tmp_path / "report.json"
    base = ["--root", str(root), "--cache", str(cache),
            "--output", str(out)]
    assert cli.main(base) == 1
    cold = json.loads(out.read_text())
    assert cold["cached"] is False and len(_trc_findings(cold)) == 1
    assert cli.main(base) == 1                       # warm: replayed
    warm = json.loads(out.read_text())
    assert warm["cached"] is True
    assert warm["findings"] == cold["findings"]
    # ANY file drift invalidates the whole cache (interprocedural safety)
    (root / "slate_tpu/mod.py").write_text(
        CACHE_MINI["slate_tpu/mod.py"].replace("jnp.sum(x) > 0",
                                               "x.ndim > 0"))
    assert cli.main(base) == 1      # SEAM layout findings remain
    fresh = json.loads(out.read_text())
    assert fresh["cached"] is False and _trc_findings(fresh) == []
    capsys.readouterr()


def test_findings_cache_select_runs_bypass(tmp_path, capsys):
    """--select subsets must never write or read the full-run cache."""
    root = mini_repo(tmp_path, CACHE_MINI)
    cache = tmp_path / "cache.json"
    assert cli.main(["--root", str(root), "--cache", str(cache),
                     "--select", "COL001"]) == 0
    assert not cache.exists()
    capsys.readouterr()


def test_findings_cache_wall_time_budget(tmp_path, capsys):
    """The tier-1 budget: a warm full repo run replays from the cache in
    a fraction of the cold analysis time."""
    import time
    cache = tmp_path / "cache.json"
    t0 = time.perf_counter()
    assert cli.main(["--root", str(REPO), "--cache", str(cache)]) == 0
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    assert cli.main(["--root", str(REPO), "--cache", str(cache)]) == 0
    warm = time.perf_counter() - t0
    capsys.readouterr()
    assert warm < max(2.5, 0.7 * cold)


def test_changed_only_filters_to_git_diff(tmp_path, capsys):
    import shutil
    import subprocess
    if shutil.which("git") is None:
        pytest.skip("git unavailable")
    root = mini_repo(tmp_path, {
        **CACHE_MINI,
        "slate_tpu/clean.py": "def ok():\n    return 1\n",
    })
    env = {"GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
           "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t",
           "HOME": str(tmp_path), "PATH": __import__("os").environ["PATH"]}
    for cmd in (["git", "init", "-q"], ["git", "add", "-A"],
                ["git", "commit", "-qm", "seed"]):
        subprocess.run(cmd, cwd=root, env=env, check=True)
    # committed violation, no changes: --changed-only hides it, exit 0
    assert cli.main(["--root", str(root), "--changed-only"]) == 0
    # touch the offending file: the finding is in the changed set again
    p = root / "slate_tpu/mod.py"
    p.write_text(p.read_text() + "\n")
    assert cli.main(["--root", str(root), "--changed-only"]) == 1
    # a full run still reports it regardless of git state
    assert cli.main(["--root", str(root)]) == 1
    capsys.readouterr()


def test_changed_only_falls_back_without_git(tmp_path, capsys):
    """No git repo: --changed-only degrades to reporting everything
    rather than silently hiding findings."""
    root = mini_repo(tmp_path, CACHE_MINI)
    assert cli.main(["--root", str(root), "--changed-only"]) == 1
    out = capsys.readouterr()
    assert "git unavailable" in out.err


def test_output_artifact_schema(tmp_path, capsys):
    root = mini_repo(tmp_path, CACHE_MINI)
    out = tmp_path / "report.json"
    assert cli.main(["--root", str(root), "--output", str(out)]) == 1
    report = json.loads(out.read_text())
    assert set(report) == {"findings", "baselined", "stale_baseline",
                           "rules", "files", "changed_only", "cached"}
    assert report["rules"] == sorted(REGISTRY)
    assert report["files"] == 1
    assert [f["rule"] for f in _trc_findings(report)] == ["TRC001"]
    capsys.readouterr()


# --------------------------------------------------------------------------
# durability seams (SEAM013) and the TileMap/CheckpointManager locks


def test_seam013_fires_on_raw_checkpoint_io_outside_manager(tmp_path):
    """A driver serializing checkpoint payloads itself (instead of going
    through CheckpointManager) bypasses the verify ladder and fires
    SEAM013."""
    files = seam_skeleton()
    files["slate_tpu/drivers/lu.py"] = (
        "from ..robust import health\n"
        "from ..robust.checkpoint import write_payload\n\n\n"
        "def _getrf(a):\n    ok = resolve_abft(None)\n    return a\n\n\n"
        "def getrf(a, opts=None):\n"
        "    write_payload('/tmp/p', {}, {})\n"
        "    return health.finalize(a)\n")
    fs = lint(mini_repo(tmp_path, files), SEAM_IDS)
    assert rule_ids(fs) == {"SEAM013"}
    assert "write_payload" in fs[0].message


def test_seam013_silent_inside_checkpoint_and_via_manager(tmp_path):
    """robust/checkpoint.py is the one sanctioned serialization site; a
    driver that snapshots through CheckpointManager stays clean."""
    files = seam_skeleton()
    files["slate_tpu/robust/checkpoint.py"] = (
        "def write_payload(path, header, arrays):\n"
        "    return 'sha', 0\n\n\n"
        "def read_manifest(d):\n    return {}\n\n\n"
        "class CheckpointManager:\n"
        "    def save(self, op, step, m):\n"
        "        return write_payload('p', {}, {})\n")
    files["slate_tpu/drivers/lu.py"] = (
        "from ..robust import health\n"
        "from ..robust.checkpoint import CheckpointManager\n\n\n"
        "def _getrf(a):\n    ok = resolve_abft(None)\n    return a\n\n\n"
        "def getrf(a, opts=None, checkpoint=None):\n"
        "    if checkpoint is not None:\n"
        "        checkpoint.save('getrf', 0, a)\n"
        "    return health.finalize(a)\n")
    assert lint(mini_repo(tmp_path, files), SEAM_IDS) == []


TILEMAP_FIXTURE = """\
import threading


class TileMap:
    def __init__(self):
        self._lock = threading.Lock()
        self._res = {}
        self._device = {}
        self._pending = {}

    def residency(self, key):
        with self._lock:
            return self._res.get(key, "host")
"""


def test_con001_fires_on_unlocked_tilemap_residency(tmp_path):
    root = mini_repo(tmp_path, {
        "slate_tpu/core/storage.py": TILEMAP_FIXTURE + (
            "\n"
            "    def sneak(self, key, dev):\n"
            "        self._device[key] = dev\n"
            "        self._res[key] = 'device'\n")})
    fs = lint(root, {"CON001"})
    assert fs and all(f.rule == "CON001" for f in fs)
    assert any("_res" in f.message or "_device" in f.message for f in fs)


def test_con001_silent_on_locked_tilemap_residency(tmp_path):
    root = mini_repo(tmp_path, {
        "slate_tpu/core/storage.py": TILEMAP_FIXTURE + (
            "\n"
            "    def move(self, key, dev):\n"
            "        with self._lock:\n"
            "            self._device[key] = dev\n"
            "            self._res[key] = 'device'\n")})
    assert lint(root, {"CON001"}) == []


def test_con001_mutation_of_real_tilemap_is_caught(tmp_path):
    """Acceptance mutation for the out-of-core layer: unlock one
    residency-map access in the real core/storage.py and CON001 fires on
    the TileMap guard set."""
    real = (REPO / "slate_tpu/core/storage.py").read_text()
    good = mini_repo(tmp_path / "good",
                     {"slate_tpu/core/storage.py": real})
    assert lint(good, {"CON001"}) == []
    mutated = real.replace("with self._lock:", "if True:", 1)
    assert mutated != real
    bad = mini_repo(tmp_path / "bad",
                    {"slate_tpu/core/storage.py": mutated})
    fs = lint(bad, {"CON001"})
    assert fs and all(f.rule == "CON001" for f in fs)
    guards = ("_res", "_device", "_pending")
    assert all(any(g in f.message for g in guards) for f in fs)


def test_con001_mutation_of_real_checkpoint_seq_is_caught(tmp_path):
    """Unlock the manifest sequence counter in the real checkpoint.py:
    a torn _seq is exactly the stale-read hazard the verify ladder keys
    on, so the lint must hold the line."""
    real = (REPO / "slate_tpu/robust/checkpoint.py").read_text()
    good = mini_repo(tmp_path / "good",
                     {"slate_tpu/robust/checkpoint.py": real})
    assert lint(good, {"CON001"}) == []
    mutated = real.replace("with self._lock:", "if True:", 1)
    assert mutated != real
    bad = mini_repo(tmp_path / "bad",
                    {"slate_tpu/robust/checkpoint.py": mutated})
    fs = lint(bad, {"CON001"})
    assert fs and all(f.rule == "CON001" for f in fs)
    assert all("_seq" in f.message for f in fs)
