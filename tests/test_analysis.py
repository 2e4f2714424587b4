"""repro.analysis: the REPxxx lint rules and the declarative contracts.

Rule-by-rule fixture files with *known* violations, the inline-allow and
baseline workflows, and negative Contract tests — a deliberately broken
function (extra collective round, d x m materialization, counter over cap)
must FAIL its contract, and the correct one must pass. The collective-round
pair runs in a subprocess on 8 fake CPU devices, like the pins it backs.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import contracts, lint
from repro.analysis.contracts import Contract, ContractViolation


def _lint_src(tmp_path: Path, rel: str, source: str):
    """Write ``source`` at tmp_path/rel and lint it rooted at tmp_path."""
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    return lint.lint_paths([p], root=tmp_path)


def _codes(findings):
    return [f.code for f in findings]


# ---------------------------------------------------------------------------
# REP001 — raw collectives outside repro/comm
# ---------------------------------------------------------------------------


def test_rep001_flags_raw_collectives_and_from_imports(tmp_path):
    findings = _lint_src(tmp_path, "core/grad.py", """
        import jax
        from jax.lax import psum, all_gather

        def agg(x):
            y = jax.lax.psum(x, "data")
            return jax.lax.pmax(y, "data")
    """)
    rep1 = [f for f in findings if f.code == "REP001"]
    assert len(rep1) == 3  # the import line + the two call sites
    assert {f.line for f in rep1} == {3, 6, 7}
    assert "psum/all_gather" in rep1[0].message


def test_rep001_exempts_the_comm_layer(tmp_path):
    findings = _lint_src(tmp_path, "comm/base.py", """
        import jax

        def psum(x, axis_name):
            return jax.lax.psum(x, axis_name)
    """)
    assert _codes(findings) == []


def test_rep001_inline_allow_requires_a_reason(tmp_path):
    bare = _lint_src(tmp_path, "core/a.py", """
        import jax

        def f(x):
            return jax.lax.psum(x, "data")  # REP001-ok:
    """)
    assert _codes(bare) == ["REP001"]  # bare marker: not suppressed
    justified = _lint_src(tmp_path, "core/b.py", """
        import jax

        def f(x):
            return jax.lax.psum(x, "data")  # REP001-ok: comm bootstrap probe
    """)
    assert _codes(justified) == []


# ---------------------------------------------------------------------------
# REP002 — implicit host syncs in hot paths
# ---------------------------------------------------------------------------


def test_rep002_flags_implicit_syncs_in_hot_paths_only(tmp_path):
    src = """
        import numpy as np

        def pull(x):
            a = float(x.sum())
            b = x.mean().item()
            c = np.asarray(x)
            return a, b, c
    """
    hot = _lint_src(tmp_path, "core/loop.py", src)
    assert _codes(hot) == ["REP002"] * 3
    cold = _lint_src(tmp_path, "viz/plot.py", src)
    assert _codes(cold) == []  # host-side analysis code is out of scope


def test_rep002_literal_and_name_args_are_fine(tmp_path):
    findings = _lint_src(tmp_path, "core/cfg.py", """
        def parse(tok, n):
            return float(tok), bool(n), float("1e-3")
    """)
    assert _codes(findings) == []


def test_rep002_device_get_boundary_suppresses(tmp_path):
    findings = _lint_src(tmp_path, "core/fetch.py", """
        import jax
        import numpy as np

        def pull(x):
            host = jax.device_get(x)
            return float(host.sum()), np.asarray(host)
    """)
    assert _codes(findings) == []  # explicit boundary established


# ---------------------------------------------------------------------------
# REP003 — kernel trio completeness (project-level)
# ---------------------------------------------------------------------------


def _kernel_pkg(tmp_path, name, files):
    pkg = tmp_path / "kernels" / name
    pkg.mkdir(parents=True)
    for fname, content in files.items():
        (pkg / fname).write_text(textwrap.dedent(content))
    return pkg


def test_rep003_complete_trio_is_clean(tmp_path):
    _kernel_pkg(tmp_path, "good", {
        "kernel.py": "def matvec_tpu(x):\n    return x\n",
        "ops.py": """
            from . import kernel, ref

            def matvec(x, use_pallas=False):
                return kernel.matvec_tpu(x) if use_pallas else ref.matvec(x)
        """,
        "ref.py": "def matvec(x):\n    return x\n",
    })
    findings = lint.lint_paths([tmp_path], root=tmp_path)
    assert _codes(findings) == []


def test_rep003_missing_ref_and_unrouted_ops_are_flagged(tmp_path):
    _kernel_pkg(tmp_path, "noref", {
        "kernel.py": "x = 1\n",
        "ops.py": "def f(x, use_pallas=True):\n    return x\n",
    })
    _kernel_pkg(tmp_path, "norouting", {
        "kernel.py": "x = 1\n",
        # trio present, but ops never falls back to ref off-TPU
        "ops.py": "def f(x):\n    return x\n",
        "ref.py": "def f(x):\n    return x\n",
    })
    findings = lint.lint_paths([tmp_path], root=tmp_path)
    rep3 = {f.path: f.message for f in findings if f.code == "REP003"}
    assert "kernels/noref" in rep3 and "ref.py" in rep3["kernels/noref"]
    assert "kernels/norouting/ops.py" in rep3


# ---------------------------------------------------------------------------
# REP004 — recompilation hazards at jit boundaries
# ---------------------------------------------------------------------------


def test_rep004_branch_on_nonstatic_param(tmp_path):
    findings = _lint_src(tmp_path, "core/step.py", """
        import functools
        import jax

        @jax.jit
        def bad(x, mode):
            if mode:
                return -x
            return x

        @functools.partial(jax.jit, static_argnames=("mode",))
        def good(x, mode):
            if mode:
                return -x
            return x
    """)
    assert _codes(findings) == ["REP004"]
    assert "bad" in findings[0].message and "mode" in findings[0].message


# ---------------------------------------------------------------------------
# REP005 — print / f-string on tracers inside jit
# ---------------------------------------------------------------------------


def test_rep005_print_and_traced_fstring(tmp_path):
    findings = _lint_src(tmp_path, "core/dbg.py", """
        import jax

        @jax.jit
        def f(x, y):
            print("tracing")
            msg = f"x is {x}"
            return x + y

        def not_jitted(x):
            print(f"fine here {x}")
            return x
    """)
    # The jitted prints are REP005's domain (and exempt from REP006); the
    # bare print in the plain function is library-code output -> REP006.
    assert _codes(findings) == ["REP005", "REP005", "REP006"]
    assert {f.line for f in findings if f.code == "REP005"} == {6, 7}
    assert [f.line for f in findings if f.code == "REP006"] == [11]


# ---------------------------------------------------------------------------
# REP006 — bare print in library code
# ---------------------------------------------------------------------------


def test_rep006_flags_library_prints_only(tmp_path):
    src = """
        def helper(x):
            print("debug", x)
            return x
    """
    assert _codes(_lint_src(tmp_path, "core/util.py", src)) == ["REP006"]
    # tools/ and examples/ are CLI/demo surfaces — out of scope
    assert _codes(_lint_src(tmp_path, "tools/report.py", src)) == []
    assert _codes(_lint_src(tmp_path, "examples/demo.py", src)) == []


def test_rep006_exempts_main_bodies_and_dunder_main(tmp_path):
    findings = _lint_src(tmp_path, "launch/cli.py", """
        def work(x):
            return x * 2

        def main():
            print("result:", work(21))

        if __name__ == "__main__":
            print("starting")
            main()
    """)
    assert _codes(findings) == []


def test_rep006_inline_allow_requires_a_reason(tmp_path):
    bare = _lint_src(tmp_path, "core/a6.py", """
        def f(x):
            print(x)  # REP006-ok:
    """)
    assert _codes(bare) == ["REP006"]
    justified = _lint_src(tmp_path, "core/b6.py", """
        def f(x):
            print(x)  # REP006-ok: one-shot migration warning, stderr-free env
    """)
    assert _codes(justified) == []


# ---------------------------------------------------------------------------
# REP007 — imports of retired modules (deleted compat shims)
# ---------------------------------------------------------------------------


def test_rep007_flags_every_import_spelling(tmp_path):
    findings = _lint_src(tmp_path, "launch/old_importer.py", """
        import repro.launch.hlo_analysis
        from repro.launch import hlo_analysis
        from repro.launch.hlo_analysis import analyze
        from ..launch import hlo_analysis as ha
        from .hlo_analysis import COLLECTIVES
    """)
    rep7 = [f for f in findings if f.code == "REP007"]
    assert {f.line for f in rep7} == {2, 3, 4, 5, 6}
    assert "repro.analysis.hlo" in rep7[0].message  # names the replacement


def test_rep007_new_path_and_local_alias_are_clean(tmp_path):
    findings = _lint_src(tmp_path, "launch/new_importer.py", """
        from repro.analysis import hlo as hlo_analysis
        from repro.analysis.hlo import analyze

        res = hlo_analysis.analyze("HloModule m")
    """)
    assert _codes(findings) == []


def test_rep007_retired_shim_is_really_gone():
    with pytest.raises(ModuleNotFoundError):
        import repro.launch.hlo_analysis  # noqa: F401  # REP007-ok: asserting the shim stays deleted


# ---------------------------------------------------------------------------
# Baseline workflow: freeze debt, fail on new, report stale
# ---------------------------------------------------------------------------


def test_baseline_freezes_known_debt_and_catches_new(tmp_path):
    findings = _lint_src(tmp_path, "core/debt.py", """
        import jax

        def f(x):
            return jax.lax.psum(x, "data")
    """)
    assert len(findings) == 1
    bl_path = tmp_path / "baseline.json"
    lint.write_baseline(bl_path, findings, None)
    baseline = lint.load_baseline(bl_path)
    # the frozen finding is budgeted, new entries carry the review marker
    new, stale = lint.diff_baseline(findings, baseline)
    assert new == [] and stale == []
    assert list(baseline.values())[0]["why"].startswith("UNREVIEWED")

    # a second, different violation exceeds the budget -> new finding
    more = _lint_src(tmp_path, "core/debt2.py", """
        import jax

        def g(x):
            return jax.lax.pmax(x, "data")
    """)
    new, stale = lint.diff_baseline(findings + more, baseline)
    assert [f.path for f in new] == ["core/debt2.py"] and stale == []

    # fixing the original debt leaves a stale entry (baseline shrink prompt)
    new, stale = lint.diff_baseline([], baseline)
    assert new == [] and len(stale) == 1


def test_baseline_roundtrip_preserves_justifications(tmp_path):
    findings = _lint_src(tmp_path, "core/debt.py", """
        import jax

        def f(x):
            return jax.lax.psum(x, "data")
    """)
    bl_path = tmp_path / "baseline.json"
    lint.write_baseline(bl_path, findings, None)
    old = lint.load_baseline(bl_path)
    for e in old.values():
        e["why"] = "reviewed: bootstrap probe, off the epoch path"
    lint.write_baseline(bl_path, findings, old)
    again = lint.load_baseline(bl_path)
    assert [e["why"] for e in again.values()] == [
        "reviewed: bootstrap probe, off the epoch path"
    ]


def test_missing_baseline_is_empty_and_everything_is_new(tmp_path):
    baseline = lint.load_baseline(tmp_path / "nope.json")
    assert baseline == {}
    findings = _lint_src(tmp_path, "core/debt.py", """
        import jax

        def f(x):
            return jax.lax.psum(x, "data")
    """)
    new, stale = lint.diff_baseline(findings, baseline)
    assert len(new) == 1 and stale == []


# ---------------------------------------------------------------------------
# Contracts: a broken artifact must fail its declaration
# ---------------------------------------------------------------------------

_D, _M = 12, 7


def _factored_score(u, s, v, x):
    return ((x @ u.T) * s) @ v  # O(t(d+m)) — never forms (d, m)


def _dense_score(u, s, v, x):
    w = (u.T * s) @ v  # materializes the (d, m) matrix
    return x @ w


def _score_args(t=3, b=4):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    return (
        jax.random.normal(ks[0], (t, _D)),
        jax.random.normal(ks[1], (t,)),
        jax.random.normal(ks[2], (t, _M)),
        jax.random.normal(ks[3], (b, _D)),
    )


def test_forbid_shapes_passes_factored_fails_dense():
    c = Contract(name="t.never_materialize", forbid_shapes=((_D, _M), (_M, _D)))
    c.check_hlo(_factored_score, *_score_args())  # no (12,7) anywhere
    with pytest.raises(ContractViolation, match="forbid_shapes"):
        c.check_hlo(_dense_score, *_score_args())


def test_check_stats_caps_and_missing_counters():
    c = Contract(name="t.stats", max_dispatches=2, max_host_syncs=1)
    c.check_stats({"dispatches": 2, "host_syncs": 1})  # at the cap: fine
    with pytest.raises(ContractViolation, match="dispatches"):
        c.check_stats({"dispatches": 3, "host_syncs": 0})
    with pytest.raises(ContractViolation, match="host_syncs"):
        c.check_stats({"dispatches": 1})  # declared counter absent


def test_guard_is_the_transfer_guard_when_declared():
    """``guard()`` arms ``jax.transfer_guard_device_to_host`` only when the
    contract declares ``no_host_transfers``. (On CPU backends the guard is
    zero-copy-silent, so this checks the plumbing, not a raise — the raise
    is exercised on accelerator runs of the same contracts.)"""
    import contextlib

    armed = Contract(name="t.guard", no_host_transfers=True).guard()
    assert not isinstance(armed, contextlib.nullcontext)
    x = jnp.arange(8.0)
    with armed:
        _ = float(jax.device_get(x.sum()))  # explicit: always allowed
    # a contract without the clause is a no-op context
    noop = Contract(name="t.noop").guard()
    assert isinstance(noop, contextlib.nullcontext)
    with noop:
        float(jax.device_get(x.sum() + 2.0))


def test_measure_exposes_the_hlo_walk():
    res = contracts.measure(_factored_score, *_score_args())
    assert res["collective_count"] == {}
    assert res["flops"] > 0


def test_replica_groups_parsing_and_partition_crossing():
    """The topology-aware byte classifier: explicit and iota replica-group
    spellings parse, and crossing/local classification against a host
    partition matches what hier two-level reduce promises."""
    from repro.analysis import hlo

    ex = ("%ar = f32[256]{0} all-reduce(%x), "
          "replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add")
    assert hlo.parse_replica_groups(ex) == [[0, 1, 2, 3], [4, 5, 6, 7]]
    iota = "%ar = f32[8]{0} all-reduce(%x), replica_groups=[2,4]<=[8]"
    assert hlo.parse_replica_groups(iota) == [[0, 1, 2, 3], [4, 5, 6, 7]]
    # transposed iota is ambiguous here — refuse rather than guess
    assert hlo.parse_replica_groups(
        "replica_groups=[2,4]<=[4,2]T(1,0)") is None
    assert hlo.parse_replica_groups("%ar = f32[4] all-reduce(%x)") is None
    pairs = ("%cp = f32[4]{0} collective-permute(%x), "
             "source_target_pairs={{0,1},{1,2},{3,4}}")
    assert hlo.parse_replica_groups(pairs) == [[0, 1], [1, 2], [3, 4]]

    text = """
HloModule m

ENTRY %main (x: f32[256]) -> f32[256] {
  %x = f32[256]{0} parameter(0)
  %intra = f32[256]{0} all-reduce(%x), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add
  %inter = f32[256]{0} all-reduce(%intra), replica_groups={{0,4},{1,5},{2,6},{3,7}}, to_apply=%add
  ROOT %global = f32[256]{0} all-reduce(%inter), to_apply=%add
}
"""
    res = hlo.partition_crossing_bytes(text, [[0, 1, 2, 3], [4, 5, 6, 7]])
    # intra stays inside the cells; inter + group-less global cross
    assert res["local"] == 2048.0 and res["local_count"] == 1.0
    assert res["crossing"] == 4096.0 and res["crossing_count"] == 2.0
    assert res["by_op"] == {"all-reduce": 4096.0}
    # one cell: nothing can cross
    one = hlo.partition_crossing_bytes(text, [[0, 1, 2, 3, 4, 5, 6, 7]])
    assert one["crossing"] == 0.0 and one["local"] == 6144.0


def test_collective_rounds_contract_subprocess_8way():
    """The 2K-round contract passes on the real power method and FAILS on a
    doctored one paying an extra collective round — proof the declaration
    actually bites on compiled HLO, not on intent."""
    src = str(Path(__file__).resolve().parent.parent / "src")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = src
    script = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.analysis.contracts import ContractViolation
        from repro.compat import shard_map_compat
        from repro.core import power_method

        K, n, m = 2, 256, 32
        mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
        a = jax.ShapeDtypeStruct((n, m), jnp.float32)
        v0 = jax.ShapeDtypeStruct((m,), jnp.float32)
        contract = power_method.collective_rounds_contract(K)

        def wrap(fn):
            return shard_map_compat(
                fn, mesh, in_specs=(P("data"), P()),
                out_specs=power_method.PowerResult(u=P(), v=P(), sigma=P()))

        def good(a, v0):
            return power_method.power_iterations(
                lambda v: a @ v, lambda u: a.T @ u, v0, K, axis_name="data")

        def broken(a, v0):
            res = good(a, v0)
            # the pre-carried-sigma bug: one extra aggregation after the loop
            sigma = jnp.linalg.norm(
                jax.lax.psum(a.T @ res.u, "data"))  # REP001-ok: test fixture
            return power_method.PowerResult(u=res.u, v=res.v, sigma=sigma)

        contract.check_hlo(wrap(good), a, v0)
        try:
            contract.check_hlo(wrap(broken), a, v0)
        except ContractViolation as e:
            assert "collective_counts" in str(e), e
            print("verdicts OK")
        else:
            raise SystemExit("broken power method passed its contract")
    """)
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-4000:]}"
    assert "verdicts OK" in out.stdout
