"""CLI: ``python -m lightgbm_tpu.lint``.

Exit status 0 when the tree is clean against the baseline (no new
findings, no stale baseline entries); 1 otherwise.  ``--write-baseline``
regenerates the baseline from the current findings with TODO
justifications for review.

``--ir`` additionally traces the real jit/shard_map entries to jaxprs
(lint.ir config matrix, CPU-only abstract tracing) and runs the
GL011-GL016 IR audits; with ``--changed-only`` the IR matrix is scoped
to entries whose transitive module closure intersects the changed
files (CI runs the full matrix).  ``--format=github`` emits
``::error file=...,line=...::`` annotations for both passes.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

from .core import RULES, run_lint, write_baseline

PKG_ROOT = Path(__file__).resolve().parents[1]  # the lightgbm_tpu package
REPO_ROOT = PKG_ROOT.parent


def _git_changed_files():
    """Repo-root-relative paths git sees as modified (vs HEAD) or
    untracked; None when git is unavailable or this is not a checkout."""
    import subprocess

    out = []
    for cmd in (
        ["git", "diff", "--name-only", "HEAD"],
        ["git", "ls-files", "--others", "--exclude-standard"],
    ):
        try:
            proc = subprocess.run(
                cmd,
                cwd=REPO_ROOT,
                capture_output=True,
                text=True,
                timeout=10,
            )
        except (OSError, subprocess.TimeoutExpired):
            return None
        if proc.returncode != 0:
            return None
        out.extend(l.strip() for l in proc.stdout.splitlines() if l.strip())
    return sorted(set(out))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m lightgbm_tpu.lint",
        description="graftlint: tracer-safety & Pallas-contract static "
        "analysis for the lightgbm_tpu tree",
    )
    parser.add_argument(
        "paths",
        nargs="*",
        help="optional path prefixes (relative to the repo root, e.g. "
        "lightgbm_tpu/ops) to filter REPORTED findings; the whole package "
        "is always analyzed so the call graph stays complete",
    )
    parser.add_argument(
        "--baseline",
        type=Path,
        default=None,
        help="baseline JSON of reviewed exceptions (default: "
        "lint_baseline.json next to the package, when present)",
    )
    parser.add_argument(
        "--write-baseline",
        type=Path,
        metavar="PATH",
        default=None,
        help="write the current findings as a fresh baseline and exit 0",
    )
    parser.add_argument(
        "--json", action="store_true", help="machine-readable output"
    )
    parser.add_argument(
        "--format",
        choices=("text", "github"),
        default="text",
        help="finding output format: 'github' prints ::error "
        "file=...,line=... workflow annotations",
    )
    parser.add_argument(
        "--ir",
        action="store_true",
        help="also trace the jit/shard_map entry matrix to jaxprs and "
        "run the GL011-GL016 IR audits (imports the package; still "
        "CPU-only abstract tracing, no device execution)",
    )
    parser.add_argument(
        "--ir-entries",
        nargs="+",
        metavar="PREFIX",
        default=None,
        help="with --ir: trace only entries whose name starts with one "
        "of these prefixes (e.g. grow/ pallas/histogram)",
    )
    parser.add_argument(
        "--changed-only",
        action="store_true",
        help="dev-loop fast mode: report only findings in files git sees "
        "as changed (staged, unstaged, or untracked); the whole package "
        "is still analyzed so the call graph stays complete, and stale "
        "detection is restricted to the same files — CI keeps the "
        "full-tree gate",
    )
    parser.add_argument(
        "--list-rules", action="store_true", help="print the rule table"
    )
    args = parser.parse_args(argv)

    if args.list_rules:
        for code, (title, hint) in sorted(RULES.items()):
            print(f"{code}  {title}\n       fix: {hint}")
        return 0

    baseline = args.baseline
    if baseline is None and args.write_baseline is None:
        cand = REPO_ROOT / "lint_baseline.json"
        baseline = cand if cand.exists() else None

    only_paths = list(args.paths)
    ir_changed_modules = None
    if args.changed_only:
        changed = _git_changed_files()
        if changed is None:
            print(
                "graftlint: --changed-only needs a git checkout; "
                "falling back to the full tree",
                file=sys.stderr,
            )
        else:
            pkg_prefix = PKG_ROOT.name + "/"
            changed = [
                c for c in changed
                if c.endswith(".py") and c.startswith(pkg_prefix)
            ]
            if not changed:
                print(
                    "graftlint: no changed python files under "
                    f"{pkg_prefix} — nothing to report"
                )
                return 0
            only_paths.extend(changed)
            if args.ir:
                # entries are scoped to the package-relative closure
                ir_changed_modules = [
                    c[len(pkg_prefix):] for c in changed
                ]

    t0 = time.monotonic()
    c0 = time.process_time()
    result = run_lint(
        PKG_ROOT,
        baseline=baseline,
        only_paths=only_paths,
        ir=args.ir,
        ir_entry_filter=args.ir_entries,
        ir_changed_modules=ir_changed_modules,
    )
    elapsed = time.monotonic() - t0
    cpu = time.process_time() - c0

    if args.write_baseline is not None:
        write_baseline(args.write_baseline, result.findings)
        print(
            f"graftlint: wrote {len(result.findings)} entries to "
            f"{args.write_baseline} — fill in the TODO justifications"
        )
        return 0

    if args.json:
        print(
            json.dumps(
                {
                    "new": [vars(f) for f in result.new],
                    "baselined": len(result.findings) - len(result.new),
                    "stale": result.stale,
                    "elapsed_s": round(elapsed, 3),
                    "cpu_s": round(cpu, 3),
                    "rule_timings_s": {
                        code: round(t, 4)
                        for code, t in sorted(result.timings.items())
                    },
                },
                indent=2,
            )
        )
        return 0 if result.ok else 1

    if args.format == "github":
        for f in result.new:
            print(
                f"::error file={f.path},line={f.line}::"
                f"{f.rule} {f.message}"
            )
        for e in result.stale:
            print(
                f"::error file={e['path']}::stale baseline entry "
                f"(no longer fires — remove it): {e['rule']} "
                f"ident={e['ident']}"
            )
    else:
        for f in result.new:
            print(f.render())
            print(f"    fix: {f.hint}")
        for e in result.stale:
            print(
                f"stale baseline entry (no longer fires — remove it): "
                f"{e['rule']} {e['path']} ident={e['ident']!r}"
            )
    n_base = len(result.findings) - len(result.new)
    print(
        f"graftlint: {len(result.findings)} finding(s) "
        f"({n_base} baselined, {len(result.new)} new), "
        f"{len(result.stale)} stale baseline entr(y/ies) "
        f"[{elapsed:.2f}s]"
    )
    return 0 if result.ok else 1


if __name__ == "__main__":
    sys.exit(main())
