"""graftlint IR pass: trace the real jit/shard_map entries to jaxprs and
collect the facts the GL011-GL016 rules audit.

Unlike the AST pass (core.py docstring: never imports the scanned
modules), the IR pass deliberately IMPORTS the library and traces its
actual entry points under an abstract-input config matrix derived from
the perf-gate scenarios (tools/perf_gate.py: N=512, F=10, num_leaves=7,
max_bin=63->padded 64; serial / 8-way data / hybrid (4,2) / quantized).
Tracing uses ``jax.make_jaxpr`` with ``jax.ShapeDtypeStruct`` inputs
only — shapes and dtypes, no device buffers, no execution — so a full
matrix run is pure CPU trace time and fits the <30 s gate budget.

What the walker extracts per entry (recursively through every inner
jaxpr: pjit, scan, while, cond branches, shard_map, pallas_call):

* collective eqns (``psum``/``psum2``/``pmax``/``pmin``/``all_gather``
  ...) with axis names, payload bytes and the in-package source frames
  jax recorded at trace time — GL011 checks them against the sanctioned
  ``obs/collectives`` wrappers, the entry's declared mesh axes, the
  AST-level GL007 site model and the ``mesh_psum_bytes_per_iteration``
  analytic payload model;
* callback eqns (``io_callback``/``pure_callback``/...) with frames —
  GL015's per-iteration host-transfer audit (the timed-collective
  wrappers are the one sanctioned source);
* ``pallas_call`` eqns with block shapes, grid and scratch avals —
  GL014's static VMEM budget arithmetic;
* every aval's dtype/weak_type plus an optional second trace under
  ``enable_x64`` for entries declared ``x64_strict`` — GL012's
  promotion audit (an unpinned ``arange``/``random.uniform`` goes i64/
  f64 the moment someone flips x64 on);
* the entry's ``donate_argnums`` (read off the ``instrumented_jit``
  wrapper) and per-argument byte sizes — GL013's donation audit of the
  per-iteration carried buffers declared in each spec;
* ``gather`` eqns with their output shape and ``jax.named_scope`` path —
  GL016 holds the score update of the entries that declare their table's
  rows (the whole entry, or the part under a named scope: ``score_update``
  in the launch scan, ``leaf_ids`` in the segment-path grow program where
  ``score_lookup.leaf_ids_form`` says "walk") to no gather of that many
  rows (8 ns an element on the TPU; ops/score_lookup.py has the
  contractions).

The entry registry is explicit: every spec names its expected collective
axes, its donation-required (carried) arguments and its root modules, so
``--changed-only`` can scope tracing to entries whose transitive module
set intersects the edited files.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import time
from pathlib import Path
from typing import Callable, Dict, FrozenSet, List, Optional, Sequence, Tuple

PKG_NAME = "lightgbm_tpu"

# perf-gate scenario geometry (tools/perf_gate.py collect()):
# X = rand(512, 10), num_leaves=7, max_bin=63 (padded bin axis 64)
N_ROWS = 512
N_FEATURES = 10
NUM_LEAVES = 7
MAX_BIN_PADDED = 64
N_TREES = 8  # predict-entry tree batch

# per-core VMEM budget table for GL014 (bytes).  ~16 MiB/core on every
# shipped TPU generation the repo targets (see /opt/skills guides); the
# rule's estimate is 2x the block working set (double buffering) plus
# scratch, so the limit is the full physical arena.
VMEM_LIMIT_BYTES = {
    "v5e": 16 * 1024 * 1024,
}
VMEM_TARGET = "v5e"


def ensure_virtual_devices(n: int = 8) -> None:
    """Set the CPU-mesh env for the mesh entries (8 virtual devices).

    XLA reads these at BACKEND INITIALIZATION (the first ``jax.devices()``
    call), not at ``import jax`` — so this works even after the package
    import chain has pulled jax in, as long as nothing touched a device
    yet.  If a backend is already live with fewer devices, the mesh
    entries degrade to per-entry trace errors rather than breaking the
    rest of the matrix."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()


# ------------------------------------------------------------------ facts
@dataclasses.dataclass(frozen=True)
class SrcFrame:
    path: str  # posix, relative to the repo root (lightgbm_tpu/...)
    line: int
    func: str


@dataclasses.dataclass
class CollectiveFact:
    kind: str  # psum | psum2 | pmax | pmin | all_gather | ...
    axes: Tuple[str, ...]
    payload_bytes: int
    frames: Tuple[SrcFrame, ...]  # in-package frames, innermost first


@dataclasses.dataclass
class CallbackFact:
    kind: str  # io_callback | pure_callback | debug_callback
    frames: Tuple[SrcFrame, ...]


@dataclasses.dataclass
class PallasFact:
    kernel: str
    grid: Tuple[int, ...]
    block_bytes: Tuple[int, ...]  # per in/out operand block
    scratch_bytes: int
    frames: Tuple[SrcFrame, ...]

    def vmem_estimate(self) -> int:
        # double-buffered operand blocks + scratch (resident for the
        # whole launch) — the standard Mosaic working-set model
        return 2 * sum(self.block_bytes) + self.scratch_bytes


@dataclasses.dataclass
class WideDtypeFact:
    dtype: str
    prim: str
    frames: Tuple[SrcFrame, ...]


@dataclasses.dataclass
class GatherFact:
    out_shape: Tuple[int, ...]
    scope: str  # the eqn's jax.named_scope path
    frames: Tuple[SrcFrame, ...]


@dataclasses.dataclass
class TraceFacts:
    collectives: List[CollectiveFact] = dataclasses.field(default_factory=list)
    gathers: List[GatherFact] = dataclasses.field(default_factory=list)
    callbacks: List[CallbackFact] = dataclasses.field(default_factory=list)
    pallas: List[PallasFact] = dataclasses.field(default_factory=list)
    wide: List[WideDtypeFact] = dataclasses.field(default_factory=list)
    weak_outputs: List[int] = dataclasses.field(default_factory=list)


# ------------------------------------------------------------------ specs
@dataclasses.dataclass
class EntrySpec:
    """One traced entry of the config matrix.

    ``build()`` returns ``(fn, args, kwargs)`` with abstract
    ShapeDtypeStruct leaves; ``axes`` is the complete set of mesh axis
    names collectives may legally reduce over; ``carried`` marks the
    positional arguments that are per-iteration dead state the caller
    always rebinds — GL013 requires each to be donated; ``x64_strict``
    entries are traced a second time under enable_x64 and must stay
    free of 64-bit avals (the dtype-pin contract); ``psum_model`` maps
    each axis to the byte payloads the analytic model allows."""

    name: str
    build: Callable[[], Tuple[Callable, tuple, dict]]
    anchor: Tuple[str, int]  # (repo-relative path, line) findings point at
    axes: FrozenSet[str] = frozenset()
    carried: Tuple[Tuple[int, str], ...] = ()  # (argnum, argname)
    x64_strict: bool = False
    psum_model: Optional[Callable[[], Dict[str, FrozenSet[int]]]] = None
    hot: bool = True  # reachable every training/predict iteration (GL015)
    root_modules: Tuple[str, ...] = ()  # package-relative .py paths
    # rows of the entry's table: GL016 holds the score update of such an
    # entry to no gather with that many rows — the whole entry, or where
    # the entry does more (the launch scan, a grow program) what lies
    # under ``score_scope``
    score_rows: Optional[int] = None
    score_scope: Optional[str] = None


@dataclasses.dataclass
class TracedEntry:
    spec: EntrySpec
    facts: TraceFacts
    x64_wide: List[WideDtypeFact]
    donate_argnums: Tuple[int, ...]
    arg_bytes: Tuple[int, ...]  # per positional arg (pytree-leaf sum)
    elapsed_s: float
    error: Optional[str] = None  # trace failure (reported as a finding)


# ----------------------------------------------------------------- walker
_COLLECTIVE_PRIMS = {
    "psum",
    "psum2",
    "pmax",
    "pmin",
    "all_gather",
    "all_to_all",
    "reduce_scatter",
    "ppermute",
}
_CALLBACK_PRIMS = {"io_callback", "pure_callback", "debug_callback"}
_WIDE_DTYPES = {"float64", "int64", "uint64", "complex128"}


def _pkg_frames(eqn) -> Tuple[SrcFrame, ...]:
    """In-package source frames for an eqn, innermost first, lint/
    excluded (the tracer itself must never be 'the source').

    Nothing is caught here: ``user_frames`` takes the eqn's
    ``Traceback`` as of jax 0.9, and a jax that moves the API again
    must fail the entry's trace (``trace_entry`` reports it as a
    finding), not blind every rule that asks where an eqn came from.
    Every entry of the matrix is package code, so an eqn with no
    in-package frame is the same failure."""
    from jax._src import source_info_util as siu

    frames = []
    marker = os.sep + PKG_NAME + os.sep
    for fr in siu.user_frames(eqn.source_info.traceback):
        fname = fr.file_name or ""
        if marker not in fname:
            continue
        rel = PKG_NAME + "/" + fname.split(marker, 1)[1].replace(os.sep, "/")
        if rel.startswith(PKG_NAME + "/lint/"):
            continue
        frames.append(
            SrcFrame(path=rel, line=int(fr.start_line), func=fr.function_name)
        )
    if not frames:
        raise LookupError(
            f"'{eqn.primitive.name}' eqn carries no source frame inside "
            f"{PKG_NAME}/: the lint cannot say where it came from"
        )
    return tuple(frames)


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None or not hasattr(dtype, "itemsize"):
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * int(dtype.itemsize)


def _dtype_name(aval) -> Optional[str]:
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return None
    try:
        # extended dtypes (prng keys) have no numpy name that matters here
        return str(dtype.name) if hasattr(dtype, "name") else str(dtype)
    except Exception:
        return None


def _subjaxprs(params: dict):
    for v in params.values():
        items = v if isinstance(v, (list, tuple)) else (v,)
        for item in items:
            if hasattr(item, "eqns"):
                yield item
            elif hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                yield item.jaxpr


def _pallas_fact(eqn) -> Optional[PallasFact]:
    frames = _pkg_frames(eqn)  # outside the try: see _pkg_frames
    try:
        gm = eqn.params.get("grid_mapping")
        nsi = eqn.params.get("name_and_src_info")
        kernel = getattr(nsi, "name", None) or "pallas_call"
        grid = tuple(int(g) for g in getattr(gm, "grid", ()) if isinstance(g, int))
        blocks = []
        for bm in getattr(gm, "block_mappings", ()):
            # only VMEM-resident operand blocks count toward the budget:
            # SMEM scalars are tiny and ANY operands stay in HBM (the
            # kernel DMAs windows into its own scratch, already counted)
            space = str(
                getattr(getattr(bm, "block_aval", None), "memory_space", "")
            ).lower()
            if "smem" in space or "any" in space:
                continue
            shape = [
                int(d) if isinstance(d, int) else 1
                for d in getattr(bm, "block_shape", ())
            ]
            asd = getattr(bm, "array_shape_dtype", None)
            itemsize = (
                int(asd.dtype.itemsize)
                if asd is not None and hasattr(asd.dtype, "itemsize")
                else 4
            )
            n = 1
            for d in shape:
                n *= d
            blocks.append(n * itemsize)
        scratch = 0
        inner = eqn.params.get("jaxpr")
        n_scratch = int(getattr(gm, "num_scratch_operands", 0) or 0)
        if inner is not None and n_scratch:
            for v in list(inner.invars)[-n_scratch:]:
                aval = getattr(v, "aval", None)
                base = getattr(aval, "inner_aval", aval)
                scratch += _aval_bytes(base)
        return PallasFact(
            kernel=str(kernel),
            grid=grid,
            block_bytes=tuple(blocks),
            scratch_bytes=scratch,
            frames=frames,
        )
    except Exception:
        return None


def walk_jaxpr(jaxpr, facts: TraceFacts) -> None:
    """Recursively collect facts from a (Closed)Jaxpr."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        name = eqn.primitive.name
        if name in _COLLECTIVE_PRIMS:
            params = eqn.params
            axes = params.get("axes", params.get("axis_name", ()))
            if not isinstance(axes, (tuple, list)):
                axes = (axes,)
            axes = tuple(str(a) for a in axes)
            payload = sum(
                _aval_bytes(getattr(v, "aval", None)) for v in eqn.invars
            )
            facts.collectives.append(
                CollectiveFact(
                    kind=name,
                    axes=axes,
                    payload_bytes=payload,
                    frames=_pkg_frames(eqn),
                )
            )
        elif name in _CALLBACK_PRIMS:
            facts.callbacks.append(
                CallbackFact(kind=name, frames=_pkg_frames(eqn))
            )
        elif name == "pallas_call":
            pf = _pallas_fact(eqn)
            if pf is not None:
                facts.pallas.append(pf)
        elif name == "gather":
            facts.gathers.append(
                GatherFact(
                    out_shape=tuple(
                        int(d) for d in eqn.outvars[0].aval.shape
                    ),
                    scope=str(eqn.source_info.name_stack),
                    frames=_pkg_frames(eqn),
                )
            )
        for v in eqn.outvars:
            dn = _dtype_name(getattr(v, "aval", None))
            if dn in _WIDE_DTYPES:
                facts.wide.append(
                    WideDtypeFact(dtype=dn, prim=name, frames=_pkg_frames(eqn))
                )
        for sub in _subjaxprs(eqn.params):
            walk_jaxpr(sub, facts)
    for i, v in enumerate(getattr(inner, "outvars", ())):
        aval = getattr(v, "aval", None)
        if getattr(aval, "weak_type", False):
            facts.weak_outputs.append(i)


# --------------------------------------------------------------- registry
def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


def _grower_params(**over):
    from ..ops.grower import GrowerParams

    base = dict(
        num_leaves=NUM_LEAVES,
        max_bin=MAX_BIN_PADDED,
        min_data_in_leaf=5,
        hist_mode="ordered",
    )
    base.update(over)
    return GrowerParams(**base)


def _grow_operands(n_local: int, f: int):
    """The 17 positional operands of the parallel/sharded_grow entry, in
    gbdt._grow_one_inner order, as abstract leaves (dummies statically
    gated off inside grow_tree, mirroring _setup_sharded_grower)."""
    import jax.numpy as jnp

    f32, i32 = jnp.float32, jnp.int32
    return (
        _sds((n_local, f), jnp.uint8),  # bins
        _sds((n_local,), f32),  # grad
        _sds((n_local,), f32),  # hess
        _sds((n_local,), f32),  # count_mask
        _sds((f,), i32),  # num_bins
        _sds((f,), i32),  # nan_bins
        _sds((f,), jnp.bool_),  # feature_mask
        _sds((f,), jnp.int8),  # monotone (dummy)
        _sds((1, f), jnp.bool_),  # interaction_sets (dummy)
        _sds((2,), jnp.uint32),  # rng
        _sds((f,), jnp.bool_),  # is_cat (dummy)
        None,  # forced
        _sds((f,), f32),  # cegb_penalty (dummy)
        _sds((f,), jnp.bool_),  # cegb_used (dummy)
        None,  # quant_scales (not a quantized booster)
        _sds((1, 1), i32),  # bundle_end (dummy)
        _sds((f,), f32),  # feature_contri (dummy)
    )


def _grow_psum_model(spec, leaf_batch: int) -> Dict[str, FrozenSet[int]]:
    """Per-axis allowed collective payload bytes, derived from the same
    formula pieces as ``mesh_psum_bytes_per_iteration`` — GL011's
    congruence contract.  The analytic model counts per-iteration
    TOTALS; statically a jaxpr shows each loop-body site once, so the
    allowed set holds the per-site payloads the model is built from:

    * 'data': the [K, 3, F_loc, B] frontier histogram psum (or its two
      db0/db1 halves under overlap), the [3, F_loc, B] root histogram,
      and the small per-step count payloads (2 x i32/f32 per member,
      plus the serial root [2]);
    * 'feature': the 11-value winner-election broadcast and the [3]
      root-totals psum.
    """
    f_loc = (
        N_FEATURES // spec.feature if spec.feature > 1 else N_FEATURES
    )
    hist = f_loc * MAX_BIN_PADDED * 3 * 4
    k = max(1, leaf_batch)
    allowed: Dict[str, FrozenSet[int]] = {}
    if spec.data > 1:
        allowed["data"] = frozenset(
            {
                hist,  # root / per-step smaller-child histogram
                k * hist,  # batched frontier histogram [K, 3, F_loc, B]
                k * hist // 2,  # overlap db0/db1 half-batch planes
                4,  # scalar count / stat psum (f32 or i32)
                8,  # [2] count pair
                k * 4,  # per-member scalar ([K])
                k * 2 * 4,  # per-member count pair [K, 2]
            }
        )
    if spec.feature > 1:
        allowed["feature"] = frozenset(
            {
                11 * 4,  # winner-election broadcast (11 packed values)
                k * 11 * 4,  # batched election [K, 11]
                3 * 4,  # root-totals (g, h, count)
                4,
                8,
                k * 4,
            }
        )
    return allowed


def _entry_mesh(layout: str, data: int, feature: int):
    from ..parallel.mesh import MeshSpec, build_mesh

    spec = MeshSpec(layout, data=data, feature=feature)
    return spec, build_mesh(spec)


def _anchor(module, obj_name: str) -> Tuple[str, int]:
    """(repo-relative path, def line) for a module-level callable, via
    the AST — stable even for decorated/wrapped objects."""
    import ast

    path = Path(module.__file__)
    marker = PKG_NAME
    parts = path.as_posix().split("/" + marker + "/")
    rel = marker + "/" + parts[-1] if len(parts) > 1 else path.name
    try:
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if (
                isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and node.name == obj_name
            ):
                return rel, node.lineno
            if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == obj_name
                for t in node.targets
            ):
                return rel, node.lineno
    except Exception:
        pass
    return rel, 1


def build_entry_specs() -> List[EntrySpec]:
    """The config matrix: every spec mirrors a perf-gate scenario (or a
    kernel wrapper the scenarios lower through on TPU)."""
    import jax.numpy as jnp

    from ..ops import grower as grower_mod
    from ..ops import quantize as quantize_mod
    from ..ops import tensor_forest as tf_mod
    from ..ops.pallas import histogram as ph_mod
    from ..ops.pallas import seg as seg_mod
    from .. import predict as predict_mod
    from ..boosting import gbdt as gbdt_mod

    f32, i32 = jnp.float32, jnp.int32
    N, F, L, T = N_ROWS, N_FEATURES, NUM_LEAVES, N_TREES
    specs: List[EntrySpec] = []

    # ---- grower entries (serial / data / batched+overlap / hybrid)
    def grow_entry(name, layout, data, feature, leaf_batch=1, overlap=False,
                   measure=False, hist_mode="ordered", score_rows=None,
                   score_scope=None):
        def build():
            from ..parallel.mesh import MeshSpec, make_mesh_grow

            if data * feature > 1:
                spec, mesh = _entry_mesh(layout, data, feature)
            else:
                spec, mesh = MeshSpec("data", data=1), None
            params = _grower_params(
                leaf_batch=leaf_batch,
                overlap_collectives=overlap,
                measure_collectives=measure,
                hist_mode=hist_mode,
                grow_fused=hist_mode == "seg",
            )
            fn = make_mesh_grow(mesh, params, spec)
            n_local = N  # shard_map operands are GLOBAL shapes
            return fn, _grow_operands(n_local, F), {}

        from ..parallel.mesh import MeshSpec

        spec = MeshSpec(layout if data * feature > 1 else "data",
                        data=data, feature=feature)
        axes = set()
        if data > 1:
            axes.add("data")
        if feature > 1:
            axes.add("feature")
        return EntrySpec(
            name=name,
            build=build,
            anchor=_anchor(grower_mod, "grow_tree"),
            axes=frozenset(axes),
            psum_model=lambda s=spec, k=leaf_batch: _grow_psum_model(s, k),
            root_modules=(
                "ops/grower.py",
                "parallel/mesh.py",
                "obs/collectives.py",
                "ops/histogram.py",
                "ops/split.py",
                "ops/score_lookup.py",
            ),
            score_rows=score_rows,
            score_scope=score_scope,
        )

    specs.append(grow_entry("grow/serial", "data", 1, 1))
    specs.append(
        grow_entry("grow/data8", "data", 8, 1, measure=True)
    )
    specs.append(
        grow_entry(
            "grow/data8_k4", "data", 8, 1, leaf_batch=4, overlap=True,
            measure=True,
        )
    )
    specs.append(
        grow_entry(
            "grow/hybrid42", "hybrid", 4, 2, measure=True,
            hist_mode="gather",
        )
    )
    # fused grow step (hist_mode="seg" implies grow_fused): the TPU
    # production path — traces the seg/partition pallas kernels for GL014
    # where score_lookup.leaf_ids_form says "walk" (these shapes do), GL016
    # holds the scope ``leaf_ids`` to no gather of the table's rows: the
    # segment form's ``sorted_leaf[seg_ord]`` is one
    from ..ops.score_lookup import leaf_ids_form

    walks = leaf_ids_form(L, F, 1, 1) == "walk"
    specs.append(
        grow_entry(
            "grow/seg_fused", "data", 1, 1, hist_mode="seg",
            score_rows=N if walks else None, score_scope="leaf_ids",
        )
    )

    # ---- fleet grow (perf-gate fleet scenario): the M=4 vmapped grow
    # step on the data mesh.  Every collective payload inside the member
    # vmap carries a leading [M] axis, so the sanctioned per-site bytes
    # are exactly M x the solo model (the same scaling
    # fleet_psum_bytes_per_iteration pins analytically).
    FLEET_M = 4

    def build_fleet_grow():
        from ..parallel.mesh import make_fleet_grow

        spec, mesh = _entry_mesh("data", 8, 1)
        params = _grower_params(measure_collectives=True)
        fn = make_fleet_grow(mesh, params, spec)
        ops = list(_grow_operands(N, F))
        for idx in (1, 2, 3, 6, 9):  # grad, hess, mask, feature_mask, rng
            o = ops[idx]
            ops[idx] = _sds((FLEET_M,) + o.shape, o.dtype)
        return fn, tuple(ops), {}

    def _fleet_psum_model():
        from ..parallel.mesh import MeshSpec

        solo = _grow_psum_model(MeshSpec("data", data=8), leaf_batch=1)
        model = {
            axis: frozenset(FLEET_M * v for v in vals)
            for axis, vals in solo.items()
        }
        # capacity-ladder pmax over the vmapped member axis: a scalar i32
        # bucket size per member (the only cross-member collective).  The
        # vmap batching rule rewrites the named-axis pmax into a
        # positional reduction, so the jaxpr records axis '0' with the
        # batched [M] operand
        for ax in ("fleet", "0"):
            model[ax] = frozenset({4, FLEET_M * 4})
        return model

    specs.append(
        EntrySpec(
            name="grow/fleet_m4_data8",
            build=build_fleet_grow,
            anchor=_anchor(grower_mod, "grow_tree"),
            axes=frozenset({"data", "fleet", "0"}),
            psum_model=_fleet_psum_model,
            root_modules=(
                "ops/grower.py",
                "parallel/mesh.py",
                "obs/collectives.py",
                "ops/histogram.py",
                "ops/split.py",
            ),
        )
    )

    # ---- device-resident launch scan (perf-gate launch scenario): the
    # REAL production N=4 launch body over the data-8 mesh, built from a
    # live Booster so the traced jaxpr is exactly what training runs.
    # GL011 walks into the lax.scan body (walk_jaxpr recurses through
    # sub-jaxprs) and must find each psum site once with the SAME
    # payloads as the solo grow/data8 model — the scan multiplies trip
    # count, never payload shape.  GL013 requires the scanned carry
    # (the donated score cache, arg 0) to hand its buffer back.
    def build_launch_scan():
        import numpy as np

        from ..boosting import create_booster
        from ..boosting.launch import LaunchRunner
        from ..dataset import Dataset

        rng = np.random.RandomState(3)
        Xl = rng.rand(N, F).astype(np.float32)
        yl = (Xl[:, 0] + 0.25 * Xl[:, 1]).astype(np.float32)
        b = create_booster(
            {
                "objective": "regression",
                "num_leaves": NUM_LEAVES,
                "max_bin": MAX_BIN_PADDED - 1,  # pads back to MAX_BIN_PADDED
                "min_data_in_leaf": 5,
                "verbosity": -1,
                "tree_learner": "data",
                "num_machines": 8,
            },
            Dataset(Xl, label=yl),
        )
        runner = LaunchRunner(b, 4)
        args = (
            _sds(tuple(b._score.shape), b._score.dtype),  # score (carried)
            _sds((2,), jnp.uint32),  # rng key
            _sds((1,), f32),  # bagging-mask carry (dummy: no sampling)
            _sds((4,), i32),  # iteration numbers
            _sds((4, b._bins.shape[1]), jnp.bool_),  # feature masks
            _sds(tuple(b._bins.shape), b._bins.dtype),  # bins
            _sds((b._bins.shape[0],), f32),  # ones_mask
            _sds((1,), f32),  # fixed-row mask (dummy)
        )
        return runner._fn, args, {}

    def _scan_psum_model():
        from ..parallel.mesh import MeshSpec

        return _grow_psum_model(MeshSpec("data", data=8), leaf_batch=1)

    from ..boosting import launch as launch_mod

    specs.append(
        EntrySpec(
            name="grow/scan4_data8",
            build=build_launch_scan,
            anchor=_anchor(launch_mod, "LaunchRunner"),
            axes=frozenset({"data"}),
            carried=((0, "score"),),
            psum_model=_scan_psum_model,
            root_modules=(
                "boosting/launch.py",
                "boosting/gbdt.py",
                "ops/grower.py",
                "ops/score_lookup.py",
                "parallel/mesh.py",
                "obs/collectives.py",
                "ops/histogram.py",
                "ops/split.py",
            ),
            score_rows=N,
            score_scope="score_update",
        )
    )

    # ---- quantized training entries (perf-gate quantized scenario)
    def build_quantize():
        fn = quantize_mod.quantize_gradients
        args = (
            _sds((N,), f32), _sds((N,), f32), _sds((), jnp.uint32),
            _sds((), i32),
        )
        return (
            lambda g, h, s, t: fn(g, h, s, t, num_bins=4, stochastic=True),
            args,
            {},
        )

    specs.append(
        EntrySpec(
            name="quant/quantize_gradients",
            build=build_quantize,
            anchor=_anchor(quantize_mod, "quantize_gradients"),
            x64_strict=True,
            root_modules=("ops/quantize.py",),
        )
    )

    def build_renew():
        fn = quantize_mod.renew_leaf_values
        args = (
            _sds((N,), i32),
            _sds((N,), f32),
            _sds((N,), f32),
            _sds((N,), f32),
            _sds((), i32),
        )
        return (
            lambda lid, g, h, m, nl: fn(
                lid, g, h, m, nl, NUM_LEAVES, 0.0, 0.0, 0.0
            ),
            args,
            {},
        )

    specs.append(
        EntrySpec(
            name="quant/renew_leaf_values",
            build=build_renew,
            anchor=_anchor(quantize_mod, "renew_leaf_values"),
            x64_strict=True,
            root_modules=("ops/quantize.py", "ops/split.py"),
        )
    )

    # ---- boosting score updates (per-iteration carried state: GL013)
    def build_score_update():
        fn = gbdt_mod._apply_tree_score
        args = (
            _sds((1, N), f32),
            _sds((L,), f32),
            _sds((N,), i32),
            _sds((), i32),
        )
        return fn, args, {}

    specs.append(
        EntrySpec(
            name="boost/score_update",
            build=build_score_update,
            anchor=_anchor(gbdt_mod, "_apply_tree_score"),
            carried=((0, "score"),),
            x64_strict=True,
            root_modules=("boosting/gbdt.py", "ops/score_lookup.py"),
            score_rows=N,
        )
    )

    from ..boosting import rf as rf_mod

    def build_rf_score_update():
        args = (_sds((N,), f32), _sds((L,), f32), _sds((N,), i32))
        return rf_mod._add_leaf_values, args, {}

    specs.append(
        EntrySpec(
            name="rf/score_update",
            build=build_rf_score_update,
            anchor=_anchor(rf_mod, "_add_leaf_values_impl"),
            carried=((0, "row"),),
            x64_strict=True,
            root_modules=("boosting/rf.py", "ops/score_lookup.py"),
            score_rows=N,
        )
    )

    def build_valid_score_update():
        fn = gbdt_mod._apply_tree_valid_score
        args = (
            _sds((1, N), f32),  # score (carried)
            _sds((N, F), jnp.uint8),  # bins
            _sds((F,), i32),  # nan_bins
            _sds((L - 1,), i32),  # split_feature
            _sds((L - 1,), i32),  # split_bin
            _sds((L - 1,), jnp.bool_),  # default_left
            _sds((L - 1,), i32),  # left_child
            _sds((L - 1,), i32),  # right_child
            _sds((L,), f32),  # leaf_value
            _sds((L - 1,), jnp.bool_),  # split_is_cat
            _sds((L - 1, 1), jnp.bool_),  # cat_mask
            _sds((), i32),  # kk
        )
        return fn, args, {}

    specs.append(
        EntrySpec(
            name="boost/valid_score_update",
            build=build_valid_score_update,
            anchor=_anchor(gbdt_mod, "_apply_tree_valid_score"),
            carried=((0, "score"),),
            x64_strict=True,
            root_modules=(
                "boosting/gbdt.py", "predict.py", "ops/score_lookup.py"
            ),
            score_rows=N,
        )
    )

    # ---- tree-state handoff (pipelined path donates its dead TreeArrays)
    def build_pack():
        from ..ops.grower import TreeArrays

        fn = grower_mod.pack_tree_arrays_donated
        nn = L - 1
        ta = grower_mod.TreeArrays(
            split_feature=_sds((nn,), i32),
            split_bin=_sds((nn,), i32),
            split_gain=_sds((nn,), f32),
            default_left=_sds((nn,), jnp.bool_),
            left_child=_sds((nn,), i32),
            right_child=_sds((nn,), i32),
            internal_value=_sds((nn,), f32),
            internal_weight=_sds((nn,), f32),
            internal_count=_sds((nn,), f32),
            leaf_value=_sds((L,), f32),
            leaf_weight=_sds((L,), f32),
            leaf_count=_sds((L,), f32),
            leaf_depth=_sds((L,), i32),
            num_leaves=_sds((), i32),
            grow_steps=_sds((), i32),
            refine_count=_sds((), i32),
            split_is_cat=_sds((nn,), jnp.bool_),
            cat_mask=_sds((nn, 1), jnp.bool_),
        )
        return fn, (ta,), {}

    specs.append(
        EntrySpec(
            name="grower/pack_tree_arrays",
            build=build_pack,
            anchor=_anchor(grower_mod, "pack_tree_arrays_donated"),
            carried=((0, "ta"),),
            x64_strict=True,
            root_modules=("ops/grower.py",),
        )
    )

    # ---- streaming predict entries + the donated score walk
    def build_predict(variant):
        def build():
            from ..predict import BinTreeBatch

            batch = BinTreeBatch(
                split_feature=_sds((T, L - 1), i32),
                split_bin=_sds((T, L - 1), i32),
                default_left=_sds((T, L - 1), jnp.bool_),
                left_child=_sds((T, L - 1), i32),
                right_child=_sds((T, L - 1), i32),
                leaf_value=_sds((T, L), f32),
                split_is_cat=_sds((T, L - 1), jnp.bool_),
                cat_mask=_sds((T, L - 1, 1), jnp.bool_),
            )
            fn = getattr(predict_mod, f"_predict_bins_{variant}_impl")
            args = (batch, _sds((N, F), jnp.uint8), _sds((F,), i32))
            return fn, args, {}

        return build

    for variant in ("raw", "leaves"):
        specs.append(
            EntrySpec(
                name=f"predict/bins_{variant}",
                build=build_predict(variant),
                anchor=_anchor(predict_mod, f"_predict_bins_{variant}_impl"),
                x64_strict=True,
                root_modules=("predict.py",),
            )
        )

    # ---- tensor-forest (pred_engine=matmul) contraction entries: the
    # direct compiler impls plus the streaming variant pulled out of the
    # engine's own dispatch table (_STREAM_IMPLS), so the audited callable
    # is exactly what the bucket ladder AOT-compiles.  Geometry mirrors the
    # eligibility sweet spot at gate scale: depth 3, 8 trees.
    TF_DEPTH = 3
    TF_PTREE = (1 << TF_DEPTH) - 1
    TF_LP = 1 << TF_DEPTH

    def build_tensor(fn_getter):
        def build():
            forest = tf_mod.TensorForest(
                sel=_sds((F, T * TF_PTREE), jnp.int8),
                thr=_sds((T * TF_PTREE,), i32),
                nanb=_sds((T * TF_PTREE,), i32),
                dleft=_sds((T * TF_PTREE,), jnp.bool_),
                routes=_sds((TF_PTREE, TF_LP), jnp.int8),
                leaf_val=_sds((T, TF_LP), f32),
                leaf_idx=_sds((T, TF_LP), i32),
            )
            args = (forest, _sds((N, F), i32))
            return fn_getter(), args, {}

        return build

    for kind in ("pertree", "leaves"):
        specs.append(
            EntrySpec(
                name=f"predict/tensor_{kind}",
                build=build_tensor(
                    lambda k=kind: getattr(tf_mod, f"_tensor_bins_{k}_impl")
                ),
                anchor=_anchor(tf_mod, f"_tensor_bins_{kind}_impl"),
                x64_strict=True,
                root_modules=("ops/tensor_forest.py",),
            )
        )
    specs.append(
        EntrySpec(
            name="predict/tensor_stream",
            build=build_tensor(
                lambda: predict_mod._STREAM_IMPLS[("tensor", "value")]
            ),
            anchor=_anchor(predict_mod, "_STREAM_IMPLS"),
            x64_strict=True,
            root_modules=("predict.py", "ops/tensor_forest.py"),
        )
    )

    def build_add_tree():
        fn = predict_mod._add_tree_to_score_jit
        args = (
            _sds((N,), f32),  # score_k (donated)
            _sds((N, F), jnp.uint8),
            _sds((F,), i32),
            _sds((L - 1,), i32),
            _sds((L - 1,), i32),
            _sds((L - 1,), jnp.bool_),
            _sds((L - 1,), i32),
            _sds((L - 1,), i32),
            _sds((L,), f32),
        )
        return fn, args, {}

    specs.append(
        EntrySpec(
            name="predict/add_tree_to_score",
            build=build_add_tree,
            anchor=_anchor(predict_mod, "add_tree_to_score"),
            carried=((0, "score_k"),),
            x64_strict=True,
            root_modules=("predict.py", "ops/score_lookup.py"),
            score_rows=N,
        )
    )

    # ---- Pallas kernel wrappers (GL014 VMEM arithmetic material).
    # Traced with interpret=False: make_jaxpr only records the pallas_call
    # eqn — Mosaic never runs, so this works on the CPU gate.
    def build_hist_pallas():
        fn = ph_mod.histogram_pallas

        def call(bins, grad, hess, mask):
            return fn(bins, grad, hess, mask, num_bins=MAX_BIN_PADDED)

        args = (
            _sds((N, F), i32),
            _sds((N,), f32),
            _sds((N,), f32),
            _sds((N,), f32),
        )
        return call, args, {}

    specs.append(
        EntrySpec(
            name="pallas/histogram",
            build=build_hist_pallas,
            anchor=_anchor(ph_mod, "histogram_pallas"),
            root_modules=("ops/pallas/histogram.py",),
        )
    )

    def build_seg_batch():
        fn = seg_mod.seg_hist_pallas_batch
        k = 4
        n_pad = seg_mod.padded_rows(N)
        lanes = seg_mod.storage_lanes(F)

        def call(seg, scal):
            return fn(seg, scal, f=F, num_bins=MAX_BIN_PADDED, n_pad=n_pad)

        args = (
            _sds((lanes, n_pad), jnp.int16),  # pack_rows plane-major layout
            _sds((k, 2), i32),  # (start, cnt) per batch member
        )
        return call, args, {}

    specs.append(
        EntrySpec(
            name="pallas/seg_hist_batch",
            build=build_seg_batch,
            anchor=_anchor(seg_mod, "seg_hist_pallas_batch"),
            root_modules=("ops/pallas/seg.py",),
        )
    )

    return specs


# ----------------------------------------------------------------- tracer
def _flat_arg_bytes(args) -> Tuple[int, ...]:
    import jax

    out = []
    for a in args:
        leaves = jax.tree_util.tree_leaves(a)
        out.append(sum(_aval_bytes(l) for l in leaves))
    return tuple(out)


def _donate_argnums(fn) -> Tuple[int, ...]:
    kw = getattr(fn, "jit_kwargs", None)
    if not isinstance(kw, dict):
        return ()
    dn = kw.get("donate_argnums", ())
    if isinstance(dn, int):
        dn = (dn,)
    return tuple(int(i) for i in dn)


def trace_entry(spec: EntrySpec) -> TracedEntry:
    import jax

    t0 = time.monotonic()
    try:
        fn, args, kwargs = spec.build()
        jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
        facts = TraceFacts()
        walk_jaxpr(jaxpr, facts)
        x64_wide: List[WideDtypeFact] = []
        if spec.x64_strict:
            with jax.enable_x64(True):
                jaxpr64 = jax.make_jaxpr(fn)(*args, **kwargs)
            f64 = TraceFacts()
            walk_jaxpr(jaxpr64, f64)
            x64_wide = f64.wide
        # donation is declared on the underlying instrumented_jit entry;
        # builders that wrap it in an adapter lambda tag the wrapper via
        # __wrapped_entry__ so the declaration stays readable
        donate = _donate_argnums(getattr(fn, "__wrapped_entry__", fn))
        return TracedEntry(
            spec=spec,
            facts=facts,
            x64_wide=x64_wide,
            donate_argnums=donate,
            arg_bytes=_flat_arg_bytes(args),
            elapsed_s=time.monotonic() - t0,
        )
    except Exception as exc:  # trace failure IS a finding (GL011 reports it)
        return TracedEntry(
            spec=spec,
            facts=TraceFacts(),
            x64_wide=[],
            donate_argnums=(),
            arg_bytes=(),
            elapsed_s=time.monotonic() - t0,
            error=f"{type(exc).__name__}: {exc}",
        )


def transitive_modules(
    project, roots: Sequence[str]
) -> FrozenSet[str]:
    """Package-relative module closure reachable from ``roots`` through
    the AST import graph (lint.core.Project.imports)."""
    seen = set()
    stack = [r for r in roots if r in project.modules]
    while stack:
        rel = stack.pop()
        if rel in seen:
            continue
        seen.add(rel)
        mod = project.modules[rel]
        for entry in mod.imports.values():
            target = None
            if entry[0] == "mod":
                target = entry[1]
            elif entry[0] == "obj":
                target = entry[1]
            if target is not None and target not in seen:
                stack.append(target)
    return frozenset(seen)


def trace_entries(
    names: Optional[Sequence[str]] = None,
) -> List[TracedEntry]:
    """Trace the matrix (or the name-prefix-filtered subset)."""
    specs = build_entry_specs()
    if names:
        specs = [
            s for s in specs if any(s.name.startswith(p) for p in names)
        ]
    return [trace_entry(s) for s in specs]


# ------------------------------------------------------------- debug dump
def _dump(entries: List[TracedEntry]) -> None:
    for te in entries:
        print(f"== {te.spec.name}  [{te.elapsed_s:.2f}s]")
        if te.error:
            print(f"   TRACE ERROR: {te.error}")
            continue
        print(f"   donate={te.donate_argnums} arg_bytes={te.arg_bytes}")
        for c in te.facts.collectives:
            src = c.frames[0]
            print(
                f"   {c.kind} axes={c.axes} payload={c.payload_bytes}B "
                f"@ {src.path}:{src.line} ({src.func})"
            )
        for cb in te.facts.callbacks:
            src = cb.frames[0]
            print(f"   callback {cb.kind} @ {src.path}:{src.line} ({src.func})")
        for p in te.facts.pallas:
            print(
                f"   pallas {p.kernel} grid={p.grid} blocks={p.block_bytes} "
                f"scratch={p.scratch_bytes} est={p.vmem_estimate()}"
            )
        for w in te.facts.wide:
            src = w.frames[0]
            print(f"   WIDE {w.dtype} in {w.prim} @ {src.path}:{src.line}")
        if te.facts.weak_outputs:
            print(f"   WEAK outputs: {te.facts.weak_outputs}")
        for w in te.x64_wide:
            src = w.frames[0]
            print(f"   X64-WIDE {w.dtype} in {w.prim} @ {src.path}:{src.line}")


if __name__ == "__main__":
    ensure_virtual_devices()
    t0 = time.monotonic()
    entries = trace_entries(sys.argv[1:] or None)
    _dump(entries)
    print(f"total: {time.monotonic() - t0:.2f}s for {len(entries)} entries")
