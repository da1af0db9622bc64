"""graftlint IR pass (lint.ir + rules_ir) — the GL011-GL016 jaxpr gate.

Contracts under test:
  * the REAL tree is clean: the full entry matrix traces and produces
    zero IR findings through the actual CLI gate
    (``python -m lightgbm_tpu.lint --ir``) within the 30 s CPU budget;
  * mutation battery on copies of the REAL modules, each traced and
    audited through the same CLI: a raw psum spliced into the grower's
    smaller-child election (spelled so the GL007 AST pass CANNOT see
    it) is caught by exactly GL011; dropping the dtype pin on
    quantize_gradients' stochastic-rounding row iota is caught by
    exactly GL012 (x64-invariance arm); stripping donate_argnums off
    the boosting score update is caught by exactly GL013; inflating a
    seg-kernel VMEM scratch block 16x past the v5e per-core arena is
    caught by exactly GL014;
  * IR findings round-trip through write_baseline/load_baseline on the
    (rule, path, ident) key, and the stale contract is full-matrix
    scoped: an IR baseline entry is exempt from stale detection when
    the IR pass is off or scoped down, and fails the run the moment a
    full matrix run shows it no longer fires;
  * the GL013 day-one triage holds at runtime: the donated score-update
    entry compiles exactly once across repeated same-shape calls
    (zero retrace delta).

The mutated copies must be IMPORTED to trace (unlike the pure-ast
battery in test_lint.py), so each mutation runs the CLI in a fresh
interpreter with cwd at the copy — the copy shadows the installed tree
on sys.path and PKG_ROOT resolves inside it.
"""

import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

from lightgbm_tpu.lint import (
    Finding,
    load_baseline,
    run_lint,
    write_baseline,
)
from lightgbm_tpu.lint.core import IR_RULE_CODES

REPO = Path(__file__).resolve().parents[1]
PKG = REPO / "lightgbm_tpu"


# ----------------------------------------------------------------- helpers
def _tree_copy(tmp_path):
    """Copy the real package (plus the committed baseline, so the AST
    pass stays fully baselined on the copy) into tmp and return its
    root."""
    root = tmp_path / "tree"
    shutil.copytree(
        PKG,
        root / "lightgbm_tpu",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    shutil.copy(REPO / "lint_baseline.json", root / "lint_baseline.json")
    return root


def _mutate(root, rel, old, new):
    p = root / "lightgbm_tpu" / rel
    src = p.read_text()
    assert old in src, f"mutation target vanished from {rel}: {old!r}"
    p.write_text(src.replace(old, new, 1))


def _run_cli(root, *args, timeout=240):
    proc = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu.lint", *args],
        cwd=root,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    return proc


def _ir_new(proc):
    """IR-rule findings from a --json CLI run."""
    data = json.loads(proc.stdout)
    return [f for f in data["new"] if f["rule"] in IR_RULE_CODES], data


# ================================================================ the gate
def test_real_tree_ir_clean_through_cli_under_budget():
    """The committed tree traces the FULL entry matrix and is IR-clean
    through the exact command tools/run_tests.sh gates on, inside the
    30 s CPU budget."""
    proc = _run_cli(REPO, "--ir", "--json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    ir_new, data = _ir_new(proc)
    assert ir_new == []
    assert data["stale"] == []
    assert data["cpu_s"] < 30.0
    # the IR pass actually ran: trace + per-rule timings are reported
    assert "ir_trace" in data["rule_timings_s"]
    for code in sorted(IR_RULE_CODES):
        assert code in data["rule_timings_s"]


# ======================================================== mutation battery
# Each mutation re-seeds a known bug shape into a copy of the REAL module
# and must be caught by exactly the intended IR rule when the copy is
# traced through the CLI.

# the smaller-child election psum in the sharded grow loop — a unique
# anchor in ops/grower.py (see test_lint.py for the AST-side anchors)
_PSUM_SITE = """nleft_g = timed_psum(
                    nleft, p.axis_name, site="counts",
                    measure=p.measure_collectives,
                )"""
# spelled via getattr so the GL007 AST raw-collective check CANNOT
# resolve the callee: only the traced jaxpr shows the psum eqn, which is
# exactly the blind spot GL011 exists to close
_PSUM_RAW = 'nleft_g = getattr(lax, "ps" + "um")(nleft, p.axis_name)'


def test_mutation_raw_psum_is_caught_by_gl011_only(tmp_path):
    root = _tree_copy(tmp_path)
    _mutate(root, "ops/grower.py", _PSUM_SITE, _PSUM_RAW)
    proc = _run_cli(
        root, "--ir", "--ir-entries", "grow/data8", "--json"
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    ir_new, _ = _ir_new(proc)
    assert len(ir_new) == 1
    f = ir_new[0]
    assert f["rule"] == "GL011"
    assert f["ident"].startswith("unsanctioned:psum:")
    assert f["path"] == "lightgbm_tpu/ops/grower.py"


_DTYPE_PIN = "rows = jnp.arange(n, dtype=jnp.uint32)"
_DTYPE_UNPINNED = "rows = jnp.arange(n)"


def test_mutation_unpinned_dtype_is_caught_by_gl012_only(tmp_path):
    """Dropping the dtype pin leaves the default trace identical (f32)
    but widens the whole rounding chain to f64 the moment enable_x64
    flips on — the x64-invariance arm catches it."""
    root = _tree_copy(tmp_path)
    _mutate(root, "ops/quantize.py", _DTYPE_PIN, _DTYPE_UNPINNED)
    proc = _run_cli(
        root, "--ir", "--ir-entries", "quant/quantize_gradients", "--json"
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    ir_new, _ = _ir_new(proc)
    assert len(ir_new) == 1
    f = ir_new[0]
    assert f["rule"] == "GL012"
    assert f["ident"] == "quant/quantize_gradients:x64"
    assert f["path"] == "lightgbm_tpu/ops/quantize.py"


_DONATED_DECOR = (
    '_tree_score_impl, label="_apply_tree_score", donate_argnums=(0,)\n'
)
_UNDONATED_DECOR = '_tree_score_impl, label="_apply_tree_score"\n'


def test_mutation_dropped_donation_is_caught_by_gl013_only(tmp_path):
    """Stripping donate_argnums off the per-iteration score update is
    caught with the wasted-bytes accounting, and --format=github
    renders the finding as a workflow annotation."""
    root = _tree_copy(tmp_path)
    _mutate(root, "boosting/gbdt.py", _DONATED_DECOR, _UNDONATED_DECOR)
    proc = _run_cli(
        root,
        "--ir",
        "--ir-entries",
        "boost/score_update",
        "--format=github",
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    annotations = [
        l for l in proc.stdout.splitlines() if l.startswith("::error")
    ]
    assert len(annotations) == 1
    assert re.match(
        r"::error file=lightgbm_tpu/boosting/gbdt\.py,line=\d+::"
        r"GL013 entry 'boost/score_update' rebinds carried state "
        r"'score'",
        annotations[0],
    ), annotations[0]


_LOOKUP = "return score.at[kk].add(leaf_lookup(leaf_value, leaf_id))"
_LOOKUP_GATHER = "return score.at[kk].add(leaf_value[leaf_id])"
_SCAN_LOOKUP = "cand = new_score.at[kk].add(leaf_lookup(shrunk, leaf_id))"
_SCAN_GATHER = "cand = new_score.at[kk].add(shrunk[leaf_id])"
_RF_LOOKUP = "return row + leaf_lookup(leaf_value, leaf_id)"
_RF_GATHER = "return row + leaf_value[leaf_id]"
_WALK_RULE = 'if cat_width > 1 or lookup_form(num_leaves) == "gather":'
_WALK_ALWAYS = "if True:"
_LEAF_FORM = 'and leaf_ids_form(p.num_leaves, f, Bm, p.feature_shard) == "walk"'
_LEAF_SEGMENT = "and False"


@pytest.mark.parametrize(
    "rel,old,new,entry,path",
    [
        ("boosting/gbdt.py", _LOOKUP, _LOOKUP_GATHER, "boost/score_update",
         "lightgbm_tpu/boosting/gbdt.py"),
        ("boosting/launch.py", _SCAN_LOOKUP, _SCAN_GATHER, "grow/scan4_data8",
         "lightgbm_tpu/boosting/launch.py"),
        ("boosting/rf.py", _RF_LOOKUP, _RF_GATHER, "rf/score_update",
         "lightgbm_tpu/boosting/rf.py"),
        ("predict.py", _WALK_RULE, _WALK_ALWAYS, "boost/valid_score_update",
         "lightgbm_tpu/predict.py"),
        ("predict.py", _WALK_RULE, _WALK_ALWAYS, "predict/add_tree_to_score",
         "lightgbm_tpu/predict.py"),
        # PR 36: the segment form's ``sorted_leaf[seg_ord]`` under scope
        # ``leaf_ids`` of a grow program whose shapes take the walk
        ("ops/grower.py", _LEAF_FORM, _LEAF_SEGMENT, "grow/seg_fused",
         "lightgbm_tpu/ops/segpart.py"),
    ],
    ids=["score_update", "launch_scan", "rf_score_update", "valid_score_update",
         "add_tree_to_score", "leaf_ids"],
)
def test_mutation_row_gather_in_the_score_update_is_caught_by_gl016_only(
    tmp_path, rel, old, new, entry, path
):
    """Putting ``leaf_value[leaf_id]`` (or the walker, for a numeric tree)
    back into a score-update entry is a gather of the table's rows under
    scope ``score_update``: 8 ns an element on the TPU (PR 34); so is the
    leaf of a segment position under scope ``leaf_ids`` (PR 36)."""
    root = _tree_copy(tmp_path)
    _mutate(root, rel, old, new)
    proc = _run_cli(root, "--ir", "--ir-entries", entry, "--json")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    ir_new, _ = _ir_new(proc)
    assert ir_new and {f["rule"] for f in ir_new} == {"GL016"}
    assert all(f["ident"].startswith(f"row_gather:{entry}:") for f in ir_new)
    assert {f["path"] for f in ir_new} == {path}


_SEG_TILE = "TILE = 512  # rows per DMA tile in seg_hist"
_SEG_TILE_BLOWN = "TILE = 16384  # rows per DMA tile in seg_hist"


def test_mutation_vmem_blowout_is_caught_by_gl014_only(tmp_path):
    """A 32x DMA-tile inflation keeps the kernel self-consistent (TILE
    is used symbolically throughout) but pushes the static working set
    (~27 MB of one-hot operand and staging scratch) past the 16 MiB v5e
    arena — and
    the caller-side seg_vmem_ok guard never sees a direct kernel call,
    which is exactly why GL014 audits the traced pallas_call itself."""
    root = _tree_copy(tmp_path)
    _mutate(root, "ops/pallas/seg.py", _SEG_TILE, _SEG_TILE_BLOWN)
    proc = _run_cli(
        root, "--ir", "--ir-entries", "pallas/seg_hist_batch", "--json"
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    ir_new, _ = _ir_new(proc)
    assert len(ir_new) == 1
    f = ir_new[0]
    assert f["rule"] == "GL014"
    assert f["ident"].startswith("vmem:")
    assert f["path"] == "lightgbm_tpu/ops/pallas/seg.py"


# ================================================= baseline round-trip/stale
def test_ir_findings_round_trip_through_baseline(tmp_path):
    f = Finding(
        rule="GL013",
        path="lightgbm_tpu/boosting/gbdt.py",
        line=63,
        ident="boost/score_update:score",
        message="synthetic",
    )
    path = tmp_path / "baseline.json"
    write_baseline(path, [f])
    entries = load_baseline(path)
    assert [(e["rule"], e["path"], e["ident"]) for e in entries] == [
        (f.rule, f.path, f.ident)
    ]


def _baseline_plus_ir_entry(tmp_path):
    """The committed baseline plus one IR entry that no longer fires
    (the donation IS wired, so boost/score_update:score is satisfied)."""
    entries = load_baseline(REPO / "lint_baseline.json")
    entries.append(
        {
            "rule": "GL013",
            "path": "lightgbm_tpu/boosting/gbdt.py",
            "ident": "boost/score_update:score",
            "justification": "synthetic stale entry for the test",
        }
    )
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({"version": 1, "entries": entries}))
    return path


def test_ir_baseline_entry_exempt_from_stale_when_ir_off(tmp_path):
    res = run_lint(PKG, baseline=_baseline_plus_ir_entry(tmp_path))
    assert res.stale == []
    assert res.ok


def test_ir_baseline_entry_exempt_when_matrix_scoped_down(tmp_path):
    res = run_lint(
        PKG,
        baseline=_baseline_plus_ir_entry(tmp_path),
        ir=True,
        ir_entry_filter=["quant/"],
    )
    assert res.stale == []
    assert res.ok


def test_ir_baseline_entry_goes_stale_on_full_matrix_run(tmp_path):
    res = run_lint(
        PKG, baseline=_baseline_plus_ir_entry(tmp_path), ir=True
    )
    assert [
        (e["rule"], e["ident"]) for e in res.stale
    ] == [("GL013", "boost/score_update:score")]
    assert not res.ok


# ====================================================== GL013 runtime proof
def test_donated_score_update_traces_once():
    """The donated score-update entry keeps a zero retrace delta across
    repeated same-shape calls (the satellite's byte-identity claim is
    covered by the golden model dumps; this pins the compile count)."""
    from lightgbm_tpu.boosting.gbdt import _apply_tree_score
    from lightgbm_tpu.obs.jit import compile_counts_by_label

    score = jnp.zeros((1, 32), jnp.float32)
    leaf_value = jnp.arange(7, dtype=jnp.float32)
    leaf_id = jnp.zeros((32,), jnp.int32)
    before = compile_counts_by_label().get("_apply_tree_score", 0)
    s1 = _apply_tree_score(score, leaf_value, leaf_id, jnp.int32(0))
    s2 = _apply_tree_score(s1, leaf_value, leaf_id, jnp.int32(0))
    after = compile_counts_by_label().get("_apply_tree_score", 0)
    assert after - before == 1  # donation does not perturb retrace count
    assert s2.shape == score.shape
    assert float(s2[0, 0]) == 0.0  # leaf 0 value added twice, still 0


def test_moved_traceback_api_fails_the_entry_not_the_frames(monkeypatch):
    """``_pkg_frames`` catches nothing: the day jax moves
    ``source_info_util.user_frames`` again (0.9 moved its argument from
    the SourceInfo to its Traceback, and a swallowed AttributeError left
    every IR finding without frames since the seed), the entry reports a
    trace error, which GL011 turns into a finding — and so does an eqn
    whose frames all lie outside the package."""
    from jax._src import source_info_util as siu

    from lightgbm_tpu.lint import ir

    (spec,) = [s for s in ir.build_entry_specs() if s.name == "pallas/histogram"]
    te = ir.trace_entry(spec)
    assert te.error is None and te.facts.pallas[0].frames

    def moved(_traceback):
        raise AttributeError("'Traceback' object has no attribute 'raw_frames'")

    for fake, said in ((moved, "AttributeError"), (lambda _tb: iter(()), "LookupError")):
        monkeypatch.setattr(siu, "user_frames", fake)
        assert said in (ir.trace_entry(spec).error or "")
