"""graftlint IR rules GL011-GL016: audits over traced jaxprs.

The AST pass (rules_spmd et al.) models what the source SAYS; this pass
checks what jax actually TRACES for the real entry points (lint.ir's
config matrix).  Each check consumes ``(project, entries)`` — the AST
project is still needed because GL011 cross-checks every traced
collective against the GL007 static site model (a collective the AST
cannot see is a blind spot worth failing on), and because findings flow
through the same suppression/baseline machinery as the AST rules.

Finding idents are stable per-rule keys (core.py baseline contract —
no line numbers): collective findings key on (arm, kind, enclosing
function) so one bad call site dedups across the entries that trace it;
per-entry findings (dtype widening, donation) key on the entry name.

One finding per traced collective eqn, first failed arm wins, in order:

(a) provenance — the innermost in-package frame must be the
    ``obs/collectives`` timed wrapper (the every-site-is-measured
    invariant GL007 enforces statically);
(b) axis containment — the eqn's axis names must be within the entry's
    declared mesh axes;
(c) payload congruence — psum/pmax/pmin payload bytes must be in the
    per-axis allowed set derived from the same formula pieces as
    ``mesh_psum_bytes_per_iteration`` (a payload the analytic model
    does not predict means model and code have drifted);
(d) AST congruence — the outermost user frame must land inside a GL007
    ``CollectiveSite`` span of that module (else the static SPMD rules
    are blind to a real collective).
"""

from __future__ import annotations

import time
from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

from .callgraph import spmd_index
from .core import Finding, Project
from . import ir as ir_mod
from .ir import (
    CollectiveFact,
    SrcFrame,
    TracedEntry,
    VMEM_LIMIT_BYTES,
    VMEM_TARGET,
    WideDtypeFact,
)

_SANCTIONED = ir_mod.PKG_NAME + "/obs/collectives.py"
# observability infrastructure (the timed wrappers, instrumented_jit):
# never "the client site" a finding should point at
_INFRA_PREFIX = ir_mod.PKG_NAME + "/obs/"

# traced primitive name -> the AST-side CollectiveSite kind (GL007 model)
_AST_KIND = {
    "psum": "psum",
    "psum2": "psum",
    "pmax": "pmax",
    "pmin": "pmin",
    "all_gather": "all_gather",
}
# kinds the analytic payload model covers (all_gather payloads scale with
# the axis size the jaxpr does not name statically — skipped)
_MODELED_KINDS = {"psum", "psum2", "pmax", "pmin"}


def _user_site(frames: Tuple[SrcFrame, ...]) -> Optional[SrcFrame]:
    """Innermost in-package frame OUTSIDE the obs/ infrastructure —
    the client call site a finding should point at."""
    for fr in frames:
        if not fr.path.startswith(_INFRA_PREFIX):
            return fr
    return None


def _ast_site_spans(
    project: Project,
) -> Dict[str, List[Tuple[str, int, int]]]:
    """(kind, lineno, end_lineno) spans of every GL007 CollectiveSite,
    keyed by base-relative module path (the SrcFrame.path format)."""
    spans: Dict[str, List[Tuple[str, int, int]]] = {}
    for scope in spmd_index(project).scopes:
        base = ir_mod.PKG_NAME + "/" + scope.rel
        for site in scope.sites:
            node = site.node
            end = getattr(node, "end_lineno", None) or node.lineno
            spans.setdefault(base, []).append(
                (site.kind, node.lineno, end)
            )
    return spans


# ------------------------------------------------------------------ GL011
def check_collective_congruence(
    project: Project, entries: Sequence[TracedEntry]
) -> List[Finding]:
    spans = _ast_site_spans(project)
    out: List[Finding] = []
    for te in entries:
        spec = te.spec
        if te.error:
            out.append(
                Finding(
                    "GL011",
                    spec.anchor[0],
                    spec.anchor[1],
                    f"{spec.name}:trace-error",
                    f"entry '{spec.name}' failed to trace: {te.error}",
                )
            )
            continue
        model = spec.psum_model() if spec.psum_model is not None else {}
        for c in te.facts.collectives:
            # frames are never empty: ir._pkg_frames fails the entry's
            # trace on an eqn it cannot place
            inner = c.frames[0]
            site = _user_site(c.frames)
            loc = site or inner
            path, line, func = loc.path, loc.line, loc.func
            # (a) provenance: must come out of the timed wrappers
            if inner.path != _SANCTIONED:
                at = f"{inner.path}:{inner.line}"
                out.append(
                    Finding(
                        "GL011",
                        path,
                        line,
                        f"unsanctioned:{c.kind}:{func}",
                        f"raw '{c.kind}' in entry '{spec.name}' does not "
                        f"route through obs.collectives timed_* "
                        f"(innermost frame {at})",
                    )
                )
                continue
            # (b) axis containment
            bad = [a for a in c.axes if a not in spec.axes]
            if bad:
                declared = sorted(spec.axes) if spec.axes else "none"
                out.append(
                    Finding(
                        "GL011",
                        path,
                        line,
                        f"axis:{c.kind}:{','.join(bad)}:{func}",
                        f"'{c.kind}' in entry '{spec.name}' reduces over "
                        f"axis {bad} outside the entry's declared mesh "
                        f"axes ({declared})",
                    )
                )
                continue
            # (c) payload congruence vs the analytic model
            if model and c.kind in _MODELED_KINDS and c.axes:
                allowed: FrozenSet[int] = frozenset().union(
                    *(model.get(a, frozenset()) for a in c.axes)
                )
                if allowed and c.payload_bytes not in allowed:
                    out.append(
                        Finding(
                            "GL011",
                            path,
                            line,
                            f"payload:{c.kind}:{','.join(c.axes)}:"
                            f"{c.payload_bytes}:{func}",
                            f"'{c.kind}' over {list(c.axes)} in entry "
                            f"'{spec.name}' moves {c.payload_bytes} B, "
                            f"which the analytic payload model "
                            f"(mesh_psum_bytes_per_iteration terms: "
                            f"{sorted(allowed)}) does not predict — "
                            f"model and code have drifted",
                        )
                    )
                    continue
            # (d) AST congruence: the GL007 model must see this site
            if site is not None:
                kind = _AST_KIND.get(c.kind)
                if kind is not None and not any(
                    k == kind and lo <= site.line <= hi
                    for k, lo, hi in spans.get(site.path, ())
                ):
                    out.append(
                        Finding(
                            "GL011",
                            site.path,
                            site.line,
                            f"ast-blind:{c.kind}:{func}",
                            f"'{c.kind}' traced in entry '{spec.name}' "
                            f"at {site.path}:{site.line} has no matching "
                            f"GL007 AST collective site — the static "
                            f"SPMD congruence rules are blind to it",
                        )
                    )
    return out


# ------------------------------------------------------------------ GL012
def _wide_sites(
    facts: Sequence[WideDtypeFact],
) -> List[Tuple[WideDtypeFact, SrcFrame]]:
    seen = set()
    client, infra = [], []
    for w in facts:
        site = _user_site(w.frames)
        fr = site or w.frames[0]
        key = (w.dtype, fr.path, fr.line)
        if key in seen:
            continue
        seen.add(key)
        # facts with a real client frame lead: the finding anchors on
        # the first listed site, and an obs/-internal frame (the outer
        # pjit eqn through instrumented_jit) is never the root cause
        (client if site is not None else infra).append((w, fr))
    return client + infra


def check_dtype_promotion(
    project: Project, entries: Sequence[TracedEntry]
) -> List[Finding]:
    out: List[Finding] = []
    for te in entries:
        if te.error:
            continue
        spec = te.spec
        for arm, facts, why in (
            (
                "wide",
                te.facts.wide,
                "computes in 64-bit on the hot path",
            ),
            (
                "x64",
                te.x64_wide,
                "widens to 64-bit the moment enable_x64 flips on "
                "(unpinned default dtype)",
            ),
        ):
            sites = _wide_sites(facts)
            if not sites:
                continue
            path, line = sites[0][1].path, sites[0][1].line
            detail = "; ".join(
                f"{w.dtype} ({w.prim}) at {fr.path}:{fr.line}"
                for w, fr in sites[:3]
            )
            extra = (
                f" (+{len(sites) - 3} more)" if len(sites) > 3 else ""
            )
            out.append(
                Finding(
                    "GL012",
                    path,
                    line,
                    f"{spec.name}:{arm}",
                    f"entry '{spec.name}' {why}: {detail}{extra}",
                )
            )
    return out


# ------------------------------------------------------------------ GL013
def check_donation(
    project: Project, entries: Sequence[TracedEntry]
) -> List[Finding]:
    out: List[Finding] = []
    for te in entries:
        if te.error:
            continue
        spec = te.spec
        donated = set(te.donate_argnums)
        for argnum, argname in spec.carried:
            if argnum in donated:
                continue
            nbytes = (
                te.arg_bytes[argnum]
                if argnum < len(te.arg_bytes)
                else 0
            )
            out.append(
                Finding(
                    "GL013",
                    spec.anchor[0],
                    spec.anchor[1],
                    f"{spec.name}:{argname}",
                    f"entry '{spec.name}' rebinds carried state "
                    f"'{argname}' (arg {argnum}, {nbytes} B) every "
                    f"iteration without donate_argnums — the dead input "
                    f"buffer stays live across the update, wasting "
                    f"{nbytes} B of HBM per live instance",
                )
            )
    return out


# ------------------------------------------------------------------ GL014
def check_vmem_budget(
    project: Project, entries: Sequence[TracedEntry]
) -> List[Finding]:
    limit = VMEM_LIMIT_BYTES[VMEM_TARGET]
    out: List[Finding] = []
    for te in entries:
        if te.error:
            continue
        for p in te.facts.pallas:
            est = p.vmem_estimate()
            if est <= limit:
                continue
            fr = p.frames[0]
            out.append(
                Finding(
                    "GL014",
                    fr.path,
                    fr.line,
                    f"vmem:{p.kernel}",
                    f"pallas kernel '{p.kernel}' (entry "
                    f"'{te.spec.name}') wants ~{est} B of VMEM "
                    f"(2x operand blocks {sum(p.block_bytes)} B + "
                    f"scratch {p.scratch_bytes} B, grid {p.grid}) > "
                    f"the {VMEM_TARGET} per-core limit of {limit} B",
                )
            )
    return out


# ------------------------------------------------------------------ GL015
def check_host_transfers(
    project: Project, entries: Sequence[TracedEntry]
) -> List[Finding]:
    out: List[Finding] = []
    for te in entries:
        if te.error or not te.spec.hot:
            continue
        for cb in te.facts.callbacks:
            inner = cb.frames[0]
            if inner.path == _SANCTIONED:
                continue
            path, line, func = inner.path, inner.line, inner.func
            out.append(
                Finding(
                    "GL015",
                    path,
                    line,
                    f"callback:{cb.kind}:{func}",
                    f"'{cb.kind}' compiled into hot entry "
                    f"'{te.spec.name}' forces a device->host round trip "
                    f"every iteration; only the obs.collectives timed "
                    f"wrappers are sanctioned callback sources",
                )
            )
    return out


# ------------------------------------------------------------------ GL016
def check_score_row_gathers(
    project: Project, entries: Sequence[TracedEntry]
) -> List[Finding]:
    out: List[Finding] = []
    for te in entries:
        rows = te.spec.score_rows
        if te.error or rows is None:
            continue
        for g in te.facts.gathers:
            scope = te.spec.score_scope
            if scope is not None and scope not in g.scope.split("/"):
                continue
            if rows not in g.out_shape:
                continue
            fr = g.frames[0]
            where = (
                "in its score update" if scope in (None, "score_update")
                else f"under scope '{scope}'"
            )
            out.append(
                Finding(
                    "GL016",
                    fr.path,
                    fr.line,
                    f"row_gather:{te.spec.name}:{fr.func}",
                    f"entry '{te.spec.name}' gathers {list(g.out_shape)} "
                    f"{where}: a gather of the "
                    f"table's {rows} rows costs ~8 ns an element on the "
                    f"TPU where ops/score_lookup.py's contractions run at "
                    f"memory speed",
                )
            )
    return out


RULE_CHECKS = {
    "GL011": check_collective_congruence,
    "GL012": check_dtype_promotion,
    "GL013": check_donation,
    "GL014": check_vmem_budget,
    "GL015": check_host_transfers,
    "GL016": check_score_row_gathers,
}


def run_ir_rules(
    project: Project,
    entry_filter: Optional[Sequence[str]] = None,
    changed_modules: Optional[Sequence[str]] = None,
) -> Tuple[List[Finding], Dict[str, float], float]:
    """Trace the entry matrix and run GL011-GL016.

    ``entry_filter``: optional entry-name prefixes.  ``changed_modules``:
    optional package-relative .py paths (the --changed-only set) — an
    entry is traced only when its transitive AST module closure
    intersects them.  Returns (findings, per-rule wall seconds, trace
    seconds).
    """
    ir_mod.ensure_virtual_devices()
    t0 = time.monotonic()
    specs = ir_mod.build_entry_specs()
    if entry_filter:
        specs = [
            s
            for s in specs
            if any(s.name.startswith(p) for p in entry_filter)
        ]
    if changed_modules is not None:
        changed = set(changed_modules)
        specs = [
            s
            for s in specs
            if ir_mod.transitive_modules(project, s.root_modules)
            & changed
        ]
    entries = [ir_mod.trace_entry(s) for s in specs]
    trace_s = time.monotonic() - t0
    findings: List[Finding] = []
    timings: Dict[str, float] = {}
    for code, check in RULE_CHECKS.items():
        t1 = time.monotonic()
        findings.extend(check(project, entries))
        timings[code] = time.monotonic() - t1
    return findings, timings, trace_s
