"""graftlint — tracer-safety, Pallas-contract & SPMD static analysis.

Purpose-built for this JAX/Pallas codebase: the rule set encodes the bug
classes previous PRs paid for at runtime (interpret-mode aliased-ref
reads, bare-jit retrace-accounting holes, accept-and-ignore config
params) plus the silent multi-host SPMD classes the mesh refactor risks
(one-sided collectives, mismatched axis names, host-divergent gates) so
they become build-time errors instead.  Run it as

    python -m lightgbm_tpu.lint [--baseline lint_baseline.json] [paths...]
    python -m lightgbm_tpu.lint --changed-only   # dev-loop fast mode
    python -m lightgbm_tpu.lint --json           # incl. per-rule timings
    python -m lightgbm_tpu.lint --ir             # + GL011-GL016 jaxpr audit
    python -m lightgbm_tpu.lint --format=github  # ::error annotations

or through the pytest gate (tests/test_lint.py) and the hard CI gate at
the top of tools/run_tests.sh.  Rules:

=====  ==============================================================
GL001  bare ``jax.jit``/``jax.pmap`` outside obs/jit.py
GL002  Pallas kernel reads the input side of ``input_output_aliases``
GL003  host-sync call on a tracer-flowing value in jit-reachable code
GL004  weak-typed float constant closed over by a jitted function
GL005  ``pallas_call`` contract: block tiling, index_map arity,
       out_shape/out_specs consistency
GL006  Config field declared in config.py but never read
GL007  collective congruence: raw ``jax.lax`` collective outside
       obs/collectives.py, or a psum/pmax/pmin/all_gather reached on
       only one branch of a non-trace-static ``if`` / ``lax.cond``
GL008  axis-name consistency: mixed axis-name sources in one jitted
       region, or a collective reachable with ``axis_name=None``
GL009  retrace hazards: scalar-annotated jit params outside
       ``static_argnames``, callbacks without ``ordered=True``
GL010  host-divergent value (process_index / time / os.environ /
       unseeded RNG) gating a branch that executes a collective
-----  --------------------------------------------------------------
       IR-grade rules (``--ir``): ``lint.ir`` traces the real
       jit/shard_map entries to jaxprs under an abstract-input config
       matrix (``jax.make_jaxpr`` only — no device execution) and
       ``rules_ir`` audits the traced facts
GL011  traced collective incongruent with the sanctioned timed
       wrappers, the entry's declared mesh axes, the analytic
       ``mesh_psum_bytes_per_iteration`` payload model, or the GL007
       AST site model (incl. entries that fail to trace)
GL012  64-bit aval in a hot entry — directly, or the moment
       ``enable_x64`` flips on (the dtype-pin invariance contract)
GL013  per-iteration carried state rebound without ``donate_argnums``
       (wasted-HBM bytes reported per argument)
GL014  pallas kernel's static VMEM working set (2x operand blocks +
       scratch) exceeds the 16 MiB v5e per-core arena
GL015  host callback compiled into a hot entry outside the sanctioned
       obs.collectives wrappers (per-iteration device->host round trip)
GL016  gather of the table's rows in the score update of a
       score-update entry, or under scope leaf_ids of a grow program
       whose shapes take the walk (~8 ns an element on the TPU; the
       one-hot contractions of ops/score_lookup.py run at memory speed)
=====  ==============================================================

GL007–GL010 share one SPMD index (``callgraph.SpmdIndex``): a
path-sensitive walk of every function scope under "all replicas execute
this together" semantics, with guards derived from the axis-name family
or a jit entry's ``static_argnames`` treated as trace-static (replica-
uniform by the static-argument contract).

Per-line suppression: ``# graftlint: disable=GL001`` (comma-separated
codes, or bare ``disable`` for all).  Intentional exceptions live in
``lint_baseline.json`` with a one-line justification each; stale entries
fail the run.  See README "Static analysis".
"""

from .core import (  # noqa: F401
    Finding,
    LintResult,
    Project,
    RULES,
    load_baseline,
    run_lint,
    write_baseline,
)

__all__ = [
    "Finding",
    "LintResult",
    "Project",
    "RULES",
    "load_baseline",
    "run_lint",
    "write_baseline",
]
