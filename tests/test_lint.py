"""graftlint (lightgbm_tpu.lint) — the static-analysis CI gate.

Contracts under test:
  * every rule (GL001..GL010) FIRES on a seeded positive fixture and stays
    SILENT on the matching negative — the linter is pure ast, so fixtures
    are throwaway source trees written to tmp_path and never imported;
  * per-line ``# graftlint: disable[=CODES]`` suppression works and is
    rule-scoped;
  * the baseline round-trips: new findings fail the run, ``write_baseline``
    absorbs them, entries that stop firing go STALE and fail the run (a
    baseline may only shrink through review);
  * TaintWalker follows ``*args``/``**kwargs`` forwarding (and positional
    overflow into a bare ``*args``) — the GL003/GL010 call-graph gap;
  * mutation battery: re-seeding known bug shapes into copies of the REAL
    modules is caught by exactly the intended rule — the PR-3/PR-6
    aliased-ref read (GL002 on ops/pallas/partition.py), a one-sided psum
    in a lax.cond branch (GL007 on ops/grower.py), an axis_name literal
    mismatch (GL008 on ops/grower.py), and a dropped static_argnames
    entry (GL009 on ops/quantize.py);
  * the real tree is CLEAN against the committed lint_baseline.json and a
    full run fits its CPU budget (it is a hard gate in tools/run_tests.sh).
"""

import json
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from lightgbm_tpu.lint import (
    RULES,
    load_baseline,
    run_lint,
    write_baseline,
)
from lightgbm_tpu.lint.core import IR_RULE_CODES

REPO = Path(__file__).resolve().parents[1]
PKG = REPO / "lightgbm_tpu"


def make_project(tmp_path, files, name="fixpkg"):
    """Write a throwaway package tree and return its root."""
    root = tmp_path / name
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return root


def by_rule(result, rule):
    return [f for f in result.findings if f.rule == rule]


def idents(result, rule):
    return {f.ident for f in by_rule(result, rule)}


# ===================================================================== GL001
def test_gl001_flags_every_bare_jit_reference(tmp_path):
    """Call form, assignment form, and decorator form all fire; the ident
    is the enclosing function, so the baseline key survives line churn."""
    root = make_project(tmp_path, {
        "app.py": """\
            import jax

            j = jax.pmap

            def build(fn):
                return jax.jit(fn)

            @jax.jit
            def decorated(x):
                return x
            """,
    })
    res = run_lint(root)
    assert idents(res, "GL001") == {"<module>", "build", "decorated"}
    assert not res.ok  # no baseline: every finding is new -> gate fails


def test_gl001_silent_on_instrumented_jit_and_inside_wrapper_module(tmp_path):
    root = make_project(tmp_path, {
        "app.py": """\
            from .obs.jit import instrumented_jit

            @instrumented_jit
            def f(x):
                return x
            """,
        "obs/__init__.py": "",
        "obs/jit.py": """\
            import jax

            def instrumented_jit(fun, **kw):
                return jax.jit(fun, **kw)
            """,
    })
    assert by_rule(run_lint(root), "GL001") == []


# ===================================================================== GL002
_GL002_KERNEL = """\
    from jax.experimental import pallas as pl

    def _kern(x_ref, o_ref):
        o_ref[...] = {read} + 1.0

    def launch(x):
        return pl.pallas_call(
            _kern,
            out_shape=x,
            input_output_aliases={{0: 0}},
        )(x)
    """


def test_gl002_flags_direct_read_of_input_aliased_ref(tmp_path):
    root = make_project(
        tmp_path, {"k.py": _GL002_KERNEL.format(read="x_ref[...]")}
    )
    assert idents(run_lint(root), "GL002") == {"_kern:_kern:x_ref"}


def test_gl002_silent_on_output_ref_and_derived_values(tmp_path):
    """Reading the OUTPUT alias is the fix; subscripting a value that came
    FROM the ref is not a ref read (value taint is GL003's business)."""
    root = make_project(tmp_path, {
        "k.py": """\
            from jax.experimental import pallas as pl

            def _kern(x_ref, o_ref):
                v = o_ref[...]
                o_ref[...] = v[0] + v[1]

            def launch(x):
                return pl.pallas_call(
                    _kern,
                    out_shape=x,
                    input_output_aliases={0: 0},
                )(x)
            """,
    })
    assert by_rule(run_lint(root), "GL002") == []


def test_gl002_follows_conditional_alias_and_helper_calls(tmp_path):
    """The partition.py shape: the ref aliases through an IfExp into a
    local name, and separately flows BY NAME into an in-package helper
    whose read then fires."""
    root = make_project(tmp_path, {
        "k.py": """\
            from jax.experimental import pallas as pl

            def _read(src, o_ref):
                return src[0]

            def _kern(x_ref, o_ref, flag):
                src = x_ref if flag else o_ref
                tile = src[...]
                o_ref[...] = tile + _read(x_ref, o_ref)

            def launch(x, flag):
                return pl.pallas_call(
                    _kern,
                    out_shape=x,
                    input_output_aliases={0: 0},
                )(x, flag)
            """,
    })
    assert idents(run_lint(root), "GL002") == {
        "_kern:_kern:src",  # IfExp alias read in the kernel body
        "_kern:_read:src",  # exact-Name arg flow into the helper
    }


# ===================================================================== GL003
def test_gl003_flags_host_sync_through_the_call_graph(tmp_path):
    """float()/.item()/np.asarray/jax.device_get on tracer-flowing values,
    including one hop into an in-package helper."""
    root = make_project(tmp_path, {
        "app.py": """\
            import jax
            import numpy as np

            def _helper(v):
                s = v + 1
                return float(s)

            @instrumented_jit
            def entry(x):
                y = x * 2
                host = np.asarray(x)
                pulled = jax.device_get(x)
                return _helper(x) + y.item()
            """,
    })
    assert idents(run_lint(root), "GL003") == {
        "_helper:float:s",
        "entry:numpy.asarray:x",
        "entry:jax.device_get:",
        "entry:.item:y",
    }


def test_gl003_silent_on_static_argnames_and_unreachable_code(tmp_path):
    """static_argnames values never become tracers (the split_scan_pallas
    idiom: float(l1) on a static hyper-parameter is fine), and host code
    the call graph cannot reach from an entry is out of scope."""
    root = make_project(tmp_path, {
        "app.py": """\
            import functools

            @functools.partial(instrumented_jit, static_argnames=("n",))
            def entry(x, n):
                return x * int(n)

            def cold_path(v):
                return float(v)
            """,
    })
    assert by_rule(run_lint(root), "GL003") == []


# ===================================================================== GL004
def test_gl004_weak_float_closure_vs_pinned_and_int(tmp_path):
    root = make_project(tmp_path, {
        "app.py": """\
            import jax.numpy as jnp

            EPS = 1e-6
            SCALE = 2.5
            N_TILES = 4

            @instrumented_jit
            def bad(x):
                return x + EPS

            @instrumented_jit
            def good(x):
                return x * jnp.asarray(SCALE, jnp.float32) + N_TILES

            def unjitted(x):
                return x + EPS
            """,
    })
    assert idents(run_lint(root), "GL004") == {"bad:EPS"}


# ===================================================================== GL005
def test_gl005_block_and_contract_checks(tmp_path):
    """One enclosing function per defect so each ident isolates one check:
    lane alignment, dtype-aware sublane, index_map arity and rank,
    out_specs/out_shape count and rank."""
    root = make_project(tmp_path, {
        "k.py": """\
            import jax
            import jax.numpy as jnp
            from jax.experimental import pallas as pl

            LANES = 128

            def bad_lane(x):
                return pl.pallas_call(
                    kern,
                    grid=(4,),
                    out_shape=jax.ShapeDtypeStruct((8, 64), jnp.float32),
                    out_specs=pl.BlockSpec((8, 64), lambda i: (0, 0)),
                )(x)

            def bad_sublane_bf16(x):
                return pl.pallas_call(
                    kern,
                    grid=(4,),
                    out_shape=jax.ShapeDtypeStruct((64, LANES), jnp.bfloat16),
                    out_specs=pl.BlockSpec((8, LANES), lambda i: (0, 0)),
                )(x)

            def bad_arity(x):
                return pl.pallas_call(
                    kern,
                    grid=(2, 2),
                    out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
                    out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
                )(x)

            def bad_rank(x):
                return pl.pallas_call(
                    kern,
                    grid=(2,),
                    out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
                    out_specs=pl.BlockSpec((8, 128), lambda i: (0,)),
                )(x)

            def bad_count(x):
                return pl.pallas_call(
                    kern,
                    grid=(2,),
                    out_shape=[jax.ShapeDtypeStruct((8, 128), jnp.float32)],
                    out_specs=[
                        pl.BlockSpec((8, 128), lambda i: (0, 0)),
                        pl.BlockSpec((8, 128), lambda i: (0, 0)),
                    ],
                )(x)

            def bad_out_rank(x):
                return pl.pallas_call(
                    kern,
                    grid=(2,),
                    out_shape=jax.ShapeDtypeStruct((2, 8, 128), jnp.float32),
                    out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
                )(x)
            """,
    })
    assert idents(run_lint(root), "GL005") == {
        "bad_lane:out_specs[0]:lane",
        "bad_sublane_bf16:out_specs[0]:sublane",
        "bad_arity:out_specs[0]:arity",
        "bad_rank:out_specs[0]:rank",
        "bad_count:out_specs:count",
        "bad_out_rank:out_specs[0]:out_rank",
    }


def test_gl005_silent_on_aligned_smem_and_unresolvable_dims(tmp_path):
    """Aligned VMEM blocks pass; 1-row blocks are allowed; SMEM specs are
    exempt from tiling; dims the linter cannot resolve are skipped, never
    guessed."""
    root = make_project(tmp_path, {
        "k.py": """\
            import jax
            import jax.numpy as jnp
            from jax.experimental import pallas as pl

            LANES = 128

            def clean(x, n):
                return pl.pallas_call(
                    kern,
                    grid=(n, 2),
                    in_specs=[
                        pl.BlockSpec((1, 8, LANES), lambda i, j: (i, 0, 0)),
                        pl.BlockSpec((n, LANES), lambda i, j: (0, j)),
                        pl.BlockSpec(memory_space=pltpu.SMEM),
                        pl.BlockSpec((1, LANES), lambda i, j: (0, j)),
                    ],
                    out_shape=jax.ShapeDtypeStruct((16, 128), jnp.bfloat16),
                    out_specs=pl.BlockSpec((16, LANES), lambda i, j: (i, j)),
                )(x)
            """,
    })
    assert by_rule(run_lint(root), "GL005") == []


# ===================================================================== GL006
def test_gl006_orphan_config_field(tmp_path):
    root = make_project(tmp_path, {
        "config.py": """\
            class Config:
                used: int = 1
                getattr_used: int = 2
                orphan: int = 3
                raw: dict = None
            """,
        "consumer.py": """\
            def f(cfg, obj):
                return cfg.used + getattr(obj, "getattr_used", 0)
            """,
    })
    assert idents(run_lint(root), "GL006") == {"orphan"}


# ===================================================================== GL007
def test_gl007_flags_raw_lax_collective(tmp_path):
    """Raw jax.lax collectives outside obs/collectives.py break the
    every-site-is-measured invariant; the timed wrappers stay silent."""
    root = make_project(tmp_path, {
        "app.py": """\
            import jax

            def leaf_stats(x):
                s = jax.lax.psum(x, "data")
                return jax.lax.pmax(s, "data")

            def measured(x):
                return timed_psum(x, "data", site="s")
            """,
    })
    assert idents(run_lint(root), "GL007") == {
        "leaf_stats:raw-psum:1",
        "leaf_stats:raw-pmax:1",
    }


def test_gl007_flags_one_sided_collective_behind_plain_if(tmp_path):
    root = make_project(tmp_path, {
        "app.py": """\
            def grow(x, use_fast):
                if use_fast:
                    x = timed_psum(x, "data", site="s")
                return x
            """,
    })
    assert idents(run_lint(root), "GL007") == {"grow:if:use_fast"}


def test_gl007_silent_on_axis_derived_and_static_derived_guards(tmp_path):
    """The grower's guard idioms: a gate computed from the axis-name
    family (use_par) or from a jit entry's static argument (mode) is
    trace-static — every replica traces the same side."""
    root = make_project(tmp_path, {
        "app.py": """\
            import functools

            def grow(x, axis_name):
                use_par = axis_name is not None
                if use_par:
                    x = timed_psum(x, axis_name, site="s")
                return x

            @functools.partial(instrumented_jit, static_argnames=("mode",))
            def entry(x, mode):
                fast = mode == "seg"
                if fast:
                    x = timed_psum(x, "data", site="s")
                return x
            """,
    })
    assert by_rule(run_lint(root), "GL007") == []


def test_gl007_early_return_sibling_is_congruent(tmp_path):
    """`if skip: return psum(...)` followed by an unconditional psum is
    congruent (both paths post one psum); an early RAISE guard creates no
    sibling at all (validation raises must not fire)."""
    root = make_project(tmp_path, {
        "app.py": """\
            def grow(x, skip):
                if skip:
                    return timed_psum(x, "data", site="a")
                return timed_psum(x, "data", site="b")

            def checked(x, n):
                if n < 0:
                    raise ValueError("bad")
                return timed_psum(x, "data", site="s")
            """,
    })
    assert by_rule(run_lint(root), "GL007") == []


def test_gl007_lax_cond_branch_congruence(tmp_path):
    """A collective in only one lax.cond branch deadlocks for real (the
    predicate is traced); congruent branches stay silent, and a switch
    with an unresolvable branch list is skipped, never guessed."""
    root = make_project(tmp_path, {
        "app.py": """\
            from jax import lax

            def bad_gate(pred, x, axis_name):
                def _with(x):
                    return timed_psum(x, axis_name, site="s")
                def _without(x):
                    return x
                return lax.cond(pred, _with, _without, x)

            def good_gate(pred, x, axis_name):
                def _left(x):
                    return timed_psum(x, axis_name, site="l")
                def _right(x):
                    return timed_psum(x * 2, axis_name, site="r")
                return lax.cond(pred, _left, _right, x)

            def unresolvable(idx, branches, x):
                return lax.switch(idx, branches, x)
            """,
    })
    assert idents(run_lint(root), "GL007") == {"bad_gate:cond:1"}


# ===================================================================== GL008
def test_gl008_flags_mixed_axis_sources_in_one_jitted_region(tmp_path):
    root = make_project(tmp_path, {
        "app.py": """\
            @instrumented_jit
            def entry(x, axis_name):
                x = timed_psum(x, axis_name, site="a")
                return timed_pmax(x, "data", site="b")
            """,
    })
    assert idents(run_lint(root), "GL008") == {"entry:axis-sources"}


def test_gl008_flags_collective_reachable_with_none_axis(tmp_path):
    """An Optional axis source with no `is not None` dominator fires; the
    guarded spelling (the grower idiom) stays silent."""
    root = make_project(tmp_path, {
        "app.py": """\
            def unguarded(x, axis_name=None):
                return timed_psum(x, axis_name, site="s")

            def guarded(x, axis_name=None):
                if axis_name is not None:
                    x = timed_psum(x, axis_name, site="s")
                return x
            """,
    })
    assert idents(run_lint(root), "GL008") == {"unguarded:none-psum:1"}


def test_gl008_silent_on_single_source_through_helpers(tmp_path):
    """Axis-argument specialization: a helper whose site uses its own
    axis_name parameter takes the CALLER's source, so plumbing one literal
    through a helper is still one source."""
    root = make_project(tmp_path, {
        "app.py": """\
            def helper(x, axis_name):
                return timed_psum(x, axis_name, site="h")

            @instrumented_jit
            def entry(x):
                x = helper(x, "data")
                return timed_pmax(x, "data", site="b")
            """,
    })
    assert by_rule(run_lint(root), "GL008") == []


def test_gl008_mesh_axis_table_literals_are_one_source(tmp_path):
    """The two-axis world: literals drawn from the project's
    MESH_AXIS_NAMES table (parallel/mesh.py) are ONE consistent source —
    a 2-D grow path psums histograms over 'data' and elects the winner
    over 'feature' inside the same jitted region."""
    root = make_project(tmp_path, {
        "parallel/mesh.py": """\
            MESH_AXIS_NAMES = ("data", "feature")
            """,
        "app.py": """\
            @instrumented_jit
            def entry(x):
                x = timed_psum(x, "data", site="hist")
                return timed_psum(x, "feature", site="elect")
            """,
    })
    assert by_rule(run_lint(root), "GL008") == []


def test_gl008_mesh_table_does_not_launder_foreign_sources(tmp_path):
    """The collapse merges ONLY table literals: a typo'd axis next to a
    table literal, or a table literal mixed with the params plumbing,
    are still two sources."""
    root = make_project(tmp_path, {
        "parallel/mesh.py": """\
            MESH_AXIS_NAMES = ("data", "feature")
            """,
        "app.py": """\
            @instrumented_jit
            def typo(x):
                x = timed_psum(x, "data", site="hist")
                return timed_psum(x, "mdata", site="elect")

            @instrumented_jit
            def mixed(x, axis_name):
                x = timed_psum(x, axis_name, site="hist")
                return timed_psum(x, "feature", site="elect")
            """,
    })
    assert idents(run_lint(root), "GL008") == {
        "typo:axis-sources", "mixed:axis-sources",
    }


# ===================================================================== GL009
def test_gl009_flags_nonstatic_scalar_params(tmp_path):
    """Scalar-annotated params outside static_argnames retrace per value;
    declared statics, asarray-pinned scalars, unannotated params, and the
    bare-Tuple idiom (a tuple OF ARRAYS, grow_tree's forced) are exempt."""
    root = make_project(tmp_path, {
        "app.py": """\
            import functools
            from typing import Optional, Tuple

            import jax.numpy as jnp

            @functools.partial(instrumented_jit, static_argnames=("n",))
            def entry(x, n: int, lr: float, shape: Tuple[int, int],
                      forced: Optional[Tuple] = None, rng=None):
                return x * lr

            @instrumented_jit
            def pinned(x, lr: float):
                r = jnp.asarray(lr, jnp.float32)
                return x * r
            """,
    })
    assert idents(run_lint(root), "GL009") == {"entry:lr", "entry:shape"}


def test_gl009_flags_unordered_callbacks(tmp_path):
    root = make_project(tmp_path, {
        "app.py": """\
            from jax.experimental import io_callback

            def measured(x, shape, fn):
                t0 = io_callback(fn, shape, x)
                t1 = io_callback(fn, shape, x, ordered=True)
                return t0 + t1
            """,
    })
    assert idents(run_lint(root), "GL009") == {"measured:io_callback:1"}


# ===================================================================== GL010
def test_gl010_flags_process_index_gating_a_collective(tmp_path):
    root = make_project(tmp_path, {
        "app.py": """\
            import jax

            def sync(x):
                pidx = jax.process_index()
                if pidx == 0:
                    return process_allgather(x)
                return x
            """,
    })
    assert idents(run_lint(root), "GL010") == {"sync:pidx == 0"}


def test_gl010_silent_on_uniform_gates_and_seeded_rng(tmp_path):
    """process_count() is identical on every host, a seeded rng draws the
    same stream everywhere, and a divergent store onto self must not mark
    every later self.* gate divergent."""
    root = make_project(tmp_path, {
        "app.py": """\
            import time

            import jax
            import numpy as np

            def agg(x):
                if jax.process_count() <= 1:
                    return x
                return process_allgather(x)

            def bag(x):
                r = np.random.default_rng(0).random()
                if r > 0.5:
                    return timed_psum(x, "data", site="s")
                return timed_psum(x * 2, "data", site="s")

            class Booster:
                def setup(self, x):
                    self._t0 = time.monotonic()
                    if self._mesh is not None:
                        return process_allgather(x)
                    return x
            """,
    })
    assert by_rule(run_lint(root), "GL010") == []


def test_gl010_follows_divergent_taint_through_calls(tmp_path):
    root = make_project(tmp_path, {
        "app.py": """\
            import jax

            def _gather_if(flag, x):
                if flag:
                    return process_allgather(x)
                return x

            def sync(x):
                rank = jax.process_index()
                lead = rank == 0
                return _gather_if(lead, x)
            """,
    })
    assert idents(run_lint(root), "GL010") == {"_gather_if:flag"}


# ================================================= taint forwarding (GL003)
def test_gl003_taint_follows_star_args_forwarding(tmp_path):
    """Tainted values survive positional overflow into *args AND a *args
    re-splat into an in-package callee."""
    root = make_project(tmp_path, {
        "app.py": """\
            def _inner(a, b):
                return float(b)

            def _fwd(*args):
                return _inner(*args)

            @instrumented_jit
            def entry(x):
                return _fwd(0, x)
            """,
    })
    assert "_inner:float:b" in idents(run_lint(root), "GL003")


def test_gl003_taint_follows_kwargs_forwarding(tmp_path):
    root = make_project(tmp_path, {
        "app.py": """\
            def _inner(a=0, b=0):
                return b.item()

            def _fwd(**kw):
                return _inner(**kw)

            @instrumented_jit
            def entry(x):
                return _fwd(b=x)
            """,
    })
    assert "_inner:.item:b" in idents(run_lint(root), "GL003")


def test_gl003_forwarding_untainted_values_stays_silent(tmp_path):
    """Forwarding only STATIC values through *args/**kwargs must not
    invent taint (the over-approximation is per forwarded value, not per
    forwarding site)."""
    root = make_project(tmp_path, {
        "app.py": """\
            import functools

            def _inner(a, b):
                return float(b)

            def _fwd(*args, **kw):
                return _inner(*args, **kw)

            @functools.partial(instrumented_jit, static_argnames=("n", "m"))
            def entry(x, n, m):
                return x + _fwd(n, b=m)
            """,
    })
    assert by_rule(run_lint(root), "GL003") == []


# ================================================================ suppression
@pytest.mark.parametrize(
    "comment,fires",
    [
        ("# graftlint: disable=GL001", False),
        ("# graftlint: disable=GL002,GL001", False),
        ("# graftlint: disable", False),  # bare disable: all rules
        ("# graftlint: disable=GL005", True),  # wrong code: still fires
        ("", True),
    ],
)
def test_suppression_comment_is_rule_scoped(tmp_path, comment, fires):
    root = make_project(tmp_path, {
        "app.py": f"""\
            import jax

            def build(fn):
                return jax.jit(fn)  {comment}
            """,
    })
    assert bool(by_rule(run_lint(root), "GL001")) is fires


# =================================================================== baseline
def test_baseline_round_trip_and_stale_detection(tmp_path):
    files = {
        "app.py": """\
            import jax

            def build(fn):
                return jax.jit(fn)
            """,
    }
    root = make_project(tmp_path, files)
    bp = tmp_path / "baseline.json"

    # 1) no baseline: the finding is NEW and the gate fails
    first = run_lint(root)
    assert not first.ok and len(first.new) == 1

    # 2) absorb into the baseline: same tree is now clean
    write_baseline(bp, first.findings)
    entries = load_baseline(bp)
    assert [e["ident"] for e in entries] == ["build"]
    assert all("justification" in e for e in entries)
    absorbed = run_lint(root, baseline=bp)
    assert absorbed.ok and not absorbed.new and not absorbed.stale

    # 3) fix the code: the baseline entry goes STALE and fails the run —
    #    a baseline only shrinks through review, never silently
    (root / "app.py").write_text("def build(fn):\n    return fn\n")
    fixed = run_lint(root, baseline=bp)
    assert not fixed.ok
    assert not fixed.new
    assert [e["ident"] for e in fixed.stale] == ["build"]


def test_baseline_rejects_entries_without_justification(tmp_path):
    bp = tmp_path / "b.json"
    bp.write_text(json.dumps({
        "version": 1,
        "entries": [{"rule": "GL001", "path": "x.py", "ident": "f"}],
    }))
    with pytest.raises(SystemExit):
        load_baseline(bp)


# =========================================================== mutation battery
# Each mutation re-seeds a known bug shape into a copy of the REAL module
# and must be caught by exactly the intended rule — if a refactor of the
# analyzer stops catching one of these, the battery fails before the bug
# class can silently return.
_PARTITION = PKG / "ops" / "pallas" / "partition.py"
_ALIAS_LINE = "src = seg_in if read_via_input else seg_out"


def _partition_copy(tmp_path, mutate):
    src = _PARTITION.read_text()
    assert _ALIAS_LINE in src  # the mutation target still exists
    if mutate:
        # strip the inline suppression, then re-seed the PR-3 bug: read the
        # INPUT side of the alias unconditionally
        src = re.sub(r"#\s*graftlint:[^\n]*", "", src)
        src = src.replace(_ALIAS_LINE, "src = seg_in")
    return make_project(tmp_path, {"ops/pallas/partition.py": src})


def test_mutation_seeded_aliased_read_is_caught(tmp_path):
    """Re-introducing the aliasing bug into a copy of the REAL partition
    kernel fires GL002 through the _seg_partition_kernel ->
    _partition_window -> (its block reader) -> _aliased_cols chain, the one
    place the partition and aliased_tile_dma pick their DMA source."""
    res = run_lint(_partition_copy(tmp_path, mutate=True))
    assert "_seg_partition_kernel:_aliased_cols:src" in idents(
        res, "GL002"
    )
    assert not res.ok


def test_mutation_control_pristine_copy_is_clean(tmp_path):
    """The unmutated copy carries the reviewed inline suppression for the
    test-only read_via_input knob and produces no GL002."""
    res = run_lint(_partition_copy(tmp_path, mutate=False))
    assert by_rule(res, "GL002") == []


_GROWER = PKG / "ops" / "grower.py"
_QUANTIZE = PKG / "ops" / "quantize.py"
_SPMD_RULES = ("GL007", "GL008", "GL009", "GL010")

# a one-sided collective inside a lax.cond branch — the deadlock shape
# GL007 exists for (the guard family can't save you: pred is traced)
_MUTANT_GATE = '''

def _mutant_gate(pred, x, axis_name):
    def _with(x):
        return timed_psum(x, axis_name, site="mutant")

    def _without(x):
        return x

    return lax.cond(pred, _with, _without, x)
'''

# the voting-aggregation psum — unique anchor string in grow_tree
_AXIS_SITE = 'totals, p.axis_name, site="counts",'


def _grower_copy(tmp_path, mutate=None):
    src = _GROWER.read_text()
    if mutate == "cond":
        src += _MUTANT_GATE
    elif mutate == "axis":
        assert _AXIS_SITE in src  # the mutation target still exists
        src = src.replace(_AXIS_SITE, 'totals, "mdata", site="counts",', 1)
    return make_project(tmp_path, {"ops/grower.py": src})


def _spmd_idents(res):
    return {rule: idents(res, rule) for rule in _SPMD_RULES}


def test_mutation_control_pristine_grower_copy_is_clean(tmp_path):
    """grow_tree's real guard idioms (axis-derived use_par-style gates,
    static-argnames-derived use_seg/use_gather gates, congruent
    early-return psums) all stay silent on the unmutated copy."""
    res = run_lint(_grower_copy(tmp_path))
    assert _spmd_idents(res) == {rule: set() for rule in _SPMD_RULES}


def test_mutation_one_sided_cond_psum_is_caught_by_gl007_only(tmp_path):
    res = run_lint(_grower_copy(tmp_path, mutate="cond"))
    found = _spmd_idents(res)
    assert found["GL007"] == {"_mutant_gate:cond:1"}
    assert found["GL008"] == found["GL009"] == found["GL010"] == set()


def test_mutation_axis_literal_mismatch_is_caught_by_gl008_only(tmp_path):
    """Replacing one site's p.axis_name with a literal "mdata" puts two
    axis-name sources inside the grow_tree jitted region."""
    res = run_lint(_grower_copy(tmp_path, mutate="axis"))
    found = _spmd_idents(res)
    assert found["GL008"] == {"grow_tree:axis-sources"}
    assert found["GL007"] == found["GL009"] == found["GL010"] == set()


def _quantize_copy(tmp_path, mutate):
    src = _QUANTIZE.read_text()
    if mutate:
        assert '"num_leaves",' in src  # the mutation target still exists
        src = re.sub(r'\n\s*"num_leaves",', "", src, count=1)
    return make_project(tmp_path, {"ops/quantize.py": src})


def test_mutation_dropped_static_argname_is_caught_by_gl009_only(tmp_path):
    """Dropping num_leaves from renew_leaf_values' static_argnames makes a
    scalar-annotated param retrace per value — the exact hole the PR-7
    retrace accounting paid for at runtime."""
    clean = run_lint(_quantize_copy(tmp_path, mutate=False))
    assert _spmd_idents(clean) == {rule: set() for rule in _SPMD_RULES}

    res = run_lint(_quantize_copy(tmp_path, mutate=True))
    found = _spmd_idents(res)
    assert found["GL009"] == {"renew_leaf_values:num_leaves"}
    assert found["GL007"] == found["GL008"] == found["GL010"] == set()


# ================================================================== the gate
def test_real_tree_clean_against_committed_baseline():
    """THE gate: the shipped package has zero unbaselined findings and zero
    stale baseline entries, within a CPU budget (the shared SpmdIndex
    keeps GL007–GL010 to one walk, so the full ten-rule run must stay
    inside a dev-loop budget; a rule that rebuilds the index costs 3-4x).
    The budget is the CLI's own CPU accounting in a FRESH interpreter —
    how the tool is actually invoked (run_tests.sh, the dev loop) — not a
    wall clock inside this long-lived pytest process, where hundreds of
    earlier tests leave the allocator fragmented enough to roughly double
    the cost of the pointer-chasing ast walk.

    CPU seconds stretch when six xdist workers share the caches, which
    is how the driver runs this file: alone the CLI reads 2.98 s; under
    the driver's command (-n 6 --dist loadfile) three whole runs read
    4.59, 3.98 and 3.65 s (PR 31), and one of PR 28 read 6.002 s against
    the 6 s this test then allowed.  The budget is twice the worst of
    the three, rounded up: it clears every reading seen by 1.6x and
    still fails a lint that costs 3.4x what it does alone."""
    res = run_lint(PKG, baseline=REPO / "lint_baseline.json")
    assert res.ok, (
        "new findings:\n"
        + "\n".join(f.render() for f in res.new)
        + "\nstale baseline entries:\n"
        + "\n".join(str(e) for e in res.stale)
    )

    proc = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu.lint",
         "--baseline", str(REPO / "lint_baseline.json"), "--json"],
        cwd=REPO, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    cpu = json.loads(proc.stdout)["cpu_s"]
    assert cpu < 10.0, f"lint took {cpu:.1f}s CPU (budget: 10s)"


def test_cli_exit_codes():
    """``python -m lightgbm_tpu.lint`` is the CI entry point: exit 0
    against the committed baseline, exit 1 when the baseline is empty (all
    21 accepted exceptions become NEW findings); ``--json`` reports a
    wall-time entry per shipped AST rule (IR rules are timed only under
    ``--ir`` — see tests/test_lint_ir.py)."""
    ok = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu.lint",
         "--baseline", str(REPO / "lint_baseline.json")],
        cwd=REPO, capture_output=True, text=True,
    )
    assert ok.returncode == 0, ok.stdout + ok.stderr

    empty = REPO / "tests" / "golden"  # any dir; baseline file must not exist
    bad = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu.lint",
         "--baseline", str(empty / "no_such_baseline.json"), "--json"],
        cwd=REPO, capture_output=True, text=True,
    )
    assert bad.returncode == 1
    payload = json.loads(bad.stdout)
    assert payload["new"], "expected the baselined findings to surface"
    assert set(payload["rule_timings_s"]) == set(RULES) - IR_RULE_CODES
    assert all(t >= 0 for t in payload["rule_timings_s"].values())


def test_cli_changed_only_smoke():
    """--changed-only exits 0 whether or not anything is modified: a dirty
    checkout reports only changed-file findings against the baseline and a
    clean one short-circuits before analysis."""
    proc = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu.lint", "--changed-only",
         "--baseline", str(REPO / "lint_baseline.json")],
        cwd=REPO, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_rule_table_is_complete():
    """Every rule has a summary and an actionable autofix hint, and the
    sixteen shipped codes (ten AST + six IR) are exactly the documented
    set."""
    assert set(RULES) == {f"GL{i:03d}" for i in range(1, 17)}
    assert IR_RULE_CODES == {f"GL{i:03d}" for i in range(11, 17)}
    for code, (summary, hint) in RULES.items():
        assert summary and hint, code
