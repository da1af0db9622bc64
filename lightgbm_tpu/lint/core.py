"""graftlint core: project model, findings, suppressions, baseline.

The linter is pure ``ast`` — it never imports the modules it scans, so a
full-tree run costs parse time only (well under the 10 s budget) and cannot
be perturbed by import-time side effects (jax platform probing, config
globals).  Each rule gets a :class:`Project`: every module pre-parsed with
its import map and module-level integer/float constant table, which is what
lets rules resolve ``pl.pallas_call`` / ``jnp.asarray`` spellings and
constant block-shape dims (``LANES = 128``) without executing anything.

Suppression contract (per line, reviewed in-diff like the baseline):

    something_flagged()  # graftlint: disable=GL001
    other_flagged()      # graftlint: disable=GL002,GL005
    anything_flagged()   # graftlint: disable

Baseline contract: ``lint_baseline.json`` holds the explicit, justified
exceptions.  A finding matches an entry on ``(rule, path, ident)`` — the
ident is a per-rule stable key (function/field/spec slot), NOT a line
number, so baselines survive unrelated edits.  Entries that no longer fire
are STALE and fail the run: a baseline may only shrink through review, the
same discipline test_config_consumers.py applies to its allowlist.  The
end-state goal is an empty baseline.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

# rule code -> (one-line summary, autofix hint)
RULES: Dict[str, Tuple[str, str]] = {
    "GL001": (
        "bare jax.jit/jax.pmap outside obs/jit.py",
        "route through lightgbm_tpu.obs.jit.instrumented_jit(label=...) so "
        "compile_count() stays exact",
    ),
    "GL002": (
        "Pallas kernel reads a ref that is the input side of "
        "input_output_aliases",
        "read through the output-aliased ref instead (see "
        "ops/pallas/partition.aliased_tile_dma) — input-ref reads miss "
        "earlier writes in interpret mode and on re-read boundary tiles",
    ),
    "GL003": (
        "host-sync call on a tracer-flowing value inside a jit/pallas-"
        "reachable function",
        "keep the value on device (jnp ops) or hoist the host conversion "
        "out of the traced function",
    ),
    "GL004": (
        "weak-typed Python scalar constant closed over by a jitted function",
        "wrap at the use site as jnp.asarray(CONST, dtype=...) (or pass it "
        "as a typed argument) to pin the dtype across retraces",
    ),
    "GL005": (
        "pallas_call contract violation (block tiling / index_map arity / "
        "out_shape vs out_specs)",
        "align VMEM block shapes to (sublane, 128) for the dtype (f32: 8, "
        "bf16/i16: 16, i8: 32; a 1-row block is allowed), and keep "
        "grid/index_map/out_shape/out_specs consistent",
    ),
    "GL006": (
        "Config field declared in config.py but never read anywhere",
        "wire a consumer or add a baseline entry documenting why the TPU "
        "build deliberately ignores it",
    ),
    "GL007": (
        "collective not congruent across replicas (raw jax.lax collective, "
        "or a psum/pmax/pmin/all_gather reached on only one branch)",
        "route raw collectives through obs.collectives.timed_* (the every-"
        "site-is-measured invariant), and make every lax.cond / divergent "
        "if branch execute the SAME collective sequence — a replica that "
        "skips a collective deadlocks the ones that entered it",
    ),
    "GL008": (
        "axis_name inconsistency: mixed axis-name sources in one jitted "
        "region, or a collective reachable where the axis name can be None",
        "use ONE axis-name source per jitted region (the GrowerParams."
        "axis_name plumbing, not ad-hoc literals) and dominate every "
        "collective with an `axis_name is not None` guard",
    ),
    "GL009": (
        "retrace hazard: non-static Python scalar/tuple flowing into a jit "
        "entry, or an io_callback/pure_callback without ordered=True",
        "declare Python scalars in static_argnames (or pin them with "
        "jnp.asarray) so they stop retracing per value, and pass "
        "ordered=True to callbacks unless ordering is enforced by an "
        "explicit data dependency",
    ),
    "GL010": (
        "host-divergent value (process_index / time / os.environ / "
        "unseeded RNG) gates a branch containing a collective",
        "hoist the collective out of the divergent branch, or derive the "
        "gate from replicated data (psummed stats, static config) so every "
        "process takes the same path",
    ),
    # ---- IR-grade rules (lint.ir traces the real entries to jaxprs;
    # rules_ir.py audits the traced facts; run with --ir)
    "GL011": (
        "traced collective incongruent with the sanctioned wrappers, the "
        "entry's mesh axes, the analytic payload model, or the GL007 AST "
        "site model (or the entry failed to trace at all)",
        "route the collective through obs.collectives.timed_* on a "
        "declared mesh axis, and keep mesh_psum_bytes_per_iteration in "
        "sync with what the jaxpr actually moves",
    ),
    "GL012": (
        "64-bit aval traced in a hot entry (directly, or the moment "
        "enable_x64 flips on)",
        "pin the dtype at the producing op (dtype=jnp.float32 / "
        "jnp.int32 on arange, random.uniform, asarray) so the entry is "
        "invariant to the x64 flag",
    ),
    "GL013": (
        "per-iteration carried state rebound without donate_argnums",
        "declare donate_argnums on the instrumented_jit entry for every "
        "dead-after-call carried buffer so XLA reuses (or at least "
        "frees) the input allocation instead of doubling the HBM "
        "footprint",
    ),
    "GL014": (
        "pallas kernel's static VMEM working set (2x operand blocks + "
        "scratch) exceeds the per-core budget",
        "shrink the block shapes / grid so the double-buffered working "
        "set plus scratch fits the 16 MiB v5e per-core VMEM arena",
    ),
    "GL015": (
        "host callback compiled into a hot (per-iteration) entry outside "
        "the sanctioned obs.collectives wrappers",
        "drop the callback from the compiled hot path (aggregate on "
        "device, fetch after the loop) or route it through the timed "
        "obs.collectives wrappers so the transfer is measured and "
        "gated",
    ),
    "GL016": (
        "gather of the table's rows inside the score update of a "
        "score-update entry, or under scope leaf_ids of a grow program "
        "whose shapes take the walk",
        "look the tree's output up with ops.score_lookup.leaf_lookup / "
        "tree_values / tree_leaves (one-hot contractions): a TPU gather "
        "costs ~8 ns an element whatever the table's size",
    ),
}

# rules produced by the IR pass (rules_ir.py): their baseline entries are
# only checked for staleness when the FULL entry matrix was traced
IR_RULE_CODES = frozenset(
    {"GL011", "GL012", "GL013", "GL014", "GL015", "GL016"}
)

_SUPPRESS_RE = re.compile(
    r"#\s*graftlint:\s*disable(?:=(?P<codes>[A-Z0-9,\s]+))?"
)


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str  # posix path relative to the scan base (repo root)
    line: int
    ident: str  # per-rule stable baseline key (no line numbers)
    message: str

    @property
    def hint(self) -> str:
        return RULES[self.rule][1]

    def key(self) -> Tuple[str, str, str]:
        return (self.rule, self.path, self.ident)

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"


class Module:
    """One parsed source file plus the lookup tables rules share."""

    def __init__(self, path: Path, rel: str, source: str):
        self.path = path
        self.rel = rel  # posix, relative to scan base
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=str(path))
        # local name -> ("ext", dotted_module) | ("extobj", module, obj)
        #            | ("mod", rel_path)      | ("obj", rel_path, obj)
        self.imports: Dict[str, Tuple] = {}
        # module-level NAME = <int/float literal>
        self.consts: Dict[str, float] = {}
        # module-level NAME = "<str literal>" (axis-name source resolution)
        self.str_consts: Dict[str, str] = {}
        # module-level function defs by name
        self.functions: Dict[str, ast.FunctionDef] = {}
        for node in self.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions[node.name] = node
            elif isinstance(node, ast.Assign) and len(node.targets) == 1:
                t = node.targets[0]
                if isinstance(t, ast.Name) and isinstance(
                    node.value, ast.Constant
                ):
                    if isinstance(node.value.value, (int, float)) and not (
                        isinstance(node.value.value, bool)
                    ):
                        self.consts[t.id] = node.value.value
                    elif isinstance(node.value.value, str):
                        self.str_consts[t.id] = node.value.value

    def suppressed(self, line: int, rule: str) -> bool:
        if not (1 <= line <= len(self.lines)):
            return False
        m = _SUPPRESS_RE.search(self.lines[line - 1])
        if not m:
            return False
        codes = m.group("codes")
        if codes is None:
            return True  # bare disable: all rules
        return rule in {c.strip() for c in codes.split(",") if c.strip()}


class Project:
    """All modules under one package root, with import resolution."""

    def __init__(self, root: Path):
        self.root = Path(root).resolve()
        self.base = self.root.parent  # findings are relative to this
        self.pkg = self.root.name
        self.modules: Dict[str, Module] = {}  # rel-to-root posix -> Module
        for path in sorted(self.root.rglob("*.py")):
            rel_root = path.relative_to(self.root).as_posix()
            rel_base = path.relative_to(self.base).as_posix()
            try:
                mod = Module(path, rel_base, path.read_text())
            except SyntaxError as exc:  # pragma: no cover - tree is parseable
                raise SystemExit(f"graftlint: cannot parse {rel_base}: {exc}")
            self.modules[rel_root] = mod
            self._index_imports(rel_root, mod)

    # ----------------------------------------------------------- imports
    def _module_file(self, dotted: str) -> Optional[str]:
        """Resolve an in-package dotted module to a rel-to-root file path."""
        parts = dotted.split(".") if dotted else []
        for cand in (
            "/".join(parts) + ".py" if parts else None,
            "/".join(parts + ["__init__"]) + ".py",
        ):
            if cand and cand in self.modules:
                return cand
        return None

    def _index_imports(self, rel_root: str, mod: Module) -> None:
        pkg_parts = rel_root.split("/")[:-1]  # containing package dirs
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    name = alias.asname or alias.name.split(".")[0]
                    target = alias.name if alias.asname else alias.name.split(".")[0]
                    mod.imports[name] = ("ext", target)
            elif isinstance(node, ast.ImportFrom):
                src = node.module or ""
                if node.level:
                    base = pkg_parts[: len(pkg_parts) - (node.level - 1)]
                    dotted = ".".join(base + ([src] if src else []))
                    internal = True
                elif src == self.pkg or src.startswith(self.pkg + "."):
                    dotted = src[len(self.pkg) :].lstrip(".")
                    internal = True
                else:
                    dotted, internal = src, False
                for alias in node.names:
                    name = alias.asname or alias.name
                    if internal:
                        target = self._module_file(
                            (dotted + "." if dotted else "") + alias.name
                        )
                        if target is not None:  # `from . import mod`
                            mod.imports[name] = ("mod", target)
                            continue
                        owner = self._module_file(dotted)
                        if owner is not None:
                            mod.imports[name] = ("obj", owner, alias.name)
                    else:
                        mod.imports[name] = ("extobj", dotted, alias.name)

    # --------------------------------------------------------- resolution
    def dotted_callee(self, mod: Module, func: ast.AST) -> Optional[str]:
        """Canonical dotted name for an EXTERNAL callee expression, e.g.
        ``jnp.asarray`` -> ``jax.numpy.asarray``; None if not external."""
        parts: List[str] = []
        node = func
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        entry = mod.imports.get(node.id)
        if entry is None:
            return None
        if entry[0] == "ext":
            return ".".join([entry[1]] + list(reversed(parts)))
        if entry[0] == "extobj":
            return ".".join([entry[1], entry[2]] + list(reversed(parts)))
        return None

    def internal_callee(
        self, mod: Module, mod_rel: str, func: ast.AST
    ) -> Optional[Tuple[str, str]]:
        """Resolve a callee expression to an in-package (module_rel,
        function_name), or None."""
        if isinstance(func, ast.Name):
            entry = mod.imports.get(func.id)
            if entry is not None and entry[0] == "obj":
                return (entry[1], entry[2])
            if func.id in mod.functions:
                return (mod_rel, func.id)
            return None
        if isinstance(func, ast.Attribute) and isinstance(
            func.value, ast.Name
        ):
            entry = mod.imports.get(func.value.id)
            if entry is not None and entry[0] == "mod":
                owner = self.modules.get(entry[1])
                if owner is not None and func.attr in owner.functions:
                    return (entry[1], func.attr)
        return None

    def function(self, mod_rel: str, name: str) -> Optional[ast.FunctionDef]:
        owner = self.modules.get(mod_rel)
        return owner.functions.get(name) if owner else None


# ------------------------------------------------------------------ utils
def call_kwargs(call: ast.Call) -> Dict[str, ast.AST]:
    return {kw.arg: kw.value for kw in call.keywords if kw.arg}


def const_names(seq: ast.AST) -> Optional[List[str]]:
    """String elements of a literal tuple/list, else None."""
    if isinstance(seq, (ast.Tuple, ast.List)):
        out = []
        for elt in seq.elts:
            if not (
                isinstance(elt, ast.Constant) and isinstance(elt.value, str)
            ):
                return None
            out.append(elt.value)
        return out
    if isinstance(seq, ast.Constant) and isinstance(seq.value, str):
        return [seq.value]
    return None


def literal_dims(
    shape: ast.AST, consts: Dict[str, float]
) -> Optional[List[Optional[int]]]:
    """Per-dim ints for a literal tuple block shape; None entries for dims
    the linter cannot resolve statically (names that are not module-level
    int constants, arithmetic on dynamic values)."""
    if not isinstance(shape, (ast.Tuple, ast.List)):
        return None
    dims: List[Optional[int]] = []
    for elt in shape.elts:
        if isinstance(elt, ast.Constant) and isinstance(elt.value, int):
            dims.append(elt.value)
        elif isinstance(elt, ast.Name) and isinstance(
            consts.get(elt.id), int
        ):
            dims.append(int(consts[elt.id]))
        else:
            dims.append(None)
    return dims


def names_in(node: ast.AST) -> List[str]:
    return [
        n.id
        for n in ast.walk(node)
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
    ]


# --------------------------------------------------------------- baseline
def load_baseline(path: Optional[Path]) -> List[Dict]:
    if path is None or not Path(path).exists():
        return []
    data = json.loads(Path(path).read_text())
    entries = data.get("entries", data if isinstance(data, list) else [])
    for e in entries:
        for field in ("rule", "path", "ident", "justification"):
            if field not in e:
                raise SystemExit(
                    f"graftlint: baseline entry missing '{field}': {e}"
                )
    return entries


def write_baseline(path: Path, findings: Sequence[Finding]) -> None:
    entries = [
        {
            "rule": f.rule,
            "path": f.path,
            "ident": f.ident,
            "justification": "TODO: one line on why this exception is "
            "intentional",
        }
        for f in sorted(findings, key=lambda f: (f.rule, f.path, f.ident))
    ]
    Path(path).write_text(
        json.dumps({"version": 1, "entries": entries}, indent=2) + "\n"
    )


@dataclasses.dataclass
class LintResult:
    findings: List[Finding]  # everything that fired (unsuppressed)
    new: List[Finding]  # not covered by the baseline
    stale: List[Dict]  # baseline entries that no longer fire
    timings: Dict[str, float] = dataclasses.field(default_factory=dict)
    # per-rule wall seconds, keyed by rule code (GL001..), for --json

    @property
    def ok(self) -> bool:
        return not self.new and not self.stale


def run_lint(
    root: Path,
    baseline: Optional[Path] = None,
    only_paths: Sequence[str] = (),
    ir: bool = False,
    ir_entry_filter: Optional[Sequence[str]] = None,
    ir_changed_modules: Optional[Sequence[str]] = None,
) -> LintResult:
    """Scan the package at ``root`` and diff against ``baseline``.

    ``only_paths``: optional path-prefix filters (relative to the repo
    root, e.g. ``lightgbm_tpu/ops``) applied to REPORTING only — the whole
    package is always analyzed so the GL003 call graph stays complete.
    Baseline STALE detection is restricted to the same prefixes, so a
    filtered run (``--changed-only``, explicit paths) never misreads
    untouched entries as stale.

    ``ir=True`` additionally traces the lint.ir entry matrix and runs
    the GL011-GL016 jaxpr audits (this IMPORTS the package — see the
    ir.py docstring).  ``ir_entry_filter`` (name prefixes) and
    ``ir_changed_modules`` (package-relative paths) scope which entries
    are traced; when either scopes the matrix down, IR-rule baseline
    entries are exempt from stale detection (an untraced entry cannot
    re-fire its baselined findings).
    """
    import time

    from . import rules_config, rules_jit, rules_pallas, rules_spmd

    project = Project(root)
    findings: List[Finding] = []
    timings: Dict[str, float] = {}
    for rule_mod in (rules_jit, rules_pallas, rules_config, rules_spmd):
        for code, check in rule_mod.RULE_CHECKS.items():
            t0 = time.monotonic()
            findings.extend(check(project))
            timings[code] = timings.get(code, 0.0) + (
                time.monotonic() - t0
            )
    ir_ran_full = False
    if ir:
        from . import rules_ir

        ir_findings, ir_timings, trace_s = rules_ir.run_ir_rules(
            project,
            entry_filter=ir_entry_filter,
            changed_modules=ir_changed_modules,
        )
        findings.extend(ir_findings)
        for code, t in ir_timings.items():
            timings[code] = timings.get(code, 0.0) + t
        timings["ir_trace"] = trace_s
        ir_ran_full = (
            not ir_entry_filter and ir_changed_modules is None
        )
    # suppressions, dedup, stable order
    seen = set()
    kept: List[Finding] = []
    for f in sorted(findings, key=lambda f: (f.path, f.line, f.rule, f.ident)):
        mod = next(
            (m for m in project.modules.values() if m.rel == f.path), None
        )
        if mod is not None and mod.suppressed(f.line, f.rule):
            continue
        if f.key() in seen:
            continue
        seen.add(f.key())
        kept.append(f)

    def in_scope(path: str) -> bool:
        return not only_paths or any(
            path.startswith(p.rstrip("/")) for p in only_paths
        )

    if only_paths:
        kept = [f for f in kept if in_scope(f.path)]
    entries = load_baseline(baseline)
    covered = {(e["rule"], e["path"], e["ident"]) for e in entries}
    fired = {f.key() for f in kept}
    new = [f for f in kept if f.key() not in covered]
    stale = [
        e
        for e in entries
        if in_scope(e["path"])
        and (e["rule"], e["path"], e["ident"]) not in fired
        # IR-rule entries can only be judged stale by a FULL matrix run:
        # with the IR pass off (or scoped down) an entry simply was not
        # given the chance to fire
        and (ir_ran_full or e["rule"] not in IR_RULE_CODES)
    ]
    return LintResult(findings=kept, new=new, stale=stale, timings=timings)
