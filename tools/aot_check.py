"""Deviceless AOT Mosaic-compile check for the Pallas kernels and for the
whole grow programs the Booster dispatches on a TPU.

libtpu is installed in the sandbox, so
`jax.experimental.topologies.get_topology_desc` + ``.lower().compile()``
drives the real Mosaic/XLA:TPU compiler WITHOUT hardware — a
layout/lowering rejection shows up here, on a CPU-only machine, instead of
costing chip time.  It is compile-only evidence: it says nothing about
numerics, run-time VMEM/DMA faults or speed (chip_smoke.py covers those).
Reference analog for what's at stake: cuda_data_partition.cu:290-937,
cuda_best_split_finder.cu:776.

Usage: JAX_PLATFORMS=cpu python tools/aot_check.py [filter]
(exit 0 = all compile)
"""

import contextlib
import functools
import os
import re
import sys
import traceback

# A standalone run is deviceless by design: keep it off any attached chip
# (one process owns a chip, and this check does not need it).  Under pytest
# the conftest owns platform selection.
if "PYTEST_CURRENT_TEST" not in os.environ and __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lightgbm_tpu.ops.pallas.seg import (  # noqa: E402
    group_shape,
    pack_rows,  # noqa: F401  (layout doc)
    padded_rows,
    seg_hist_pallas,
    storage_lanes,
)
from lightgbm_tpu.ops.pallas.partition import seg_partition_pallas  # noqa: E402
from lightgbm_tpu.ops.pallas.histogram import histogram_pallas  # noqa: E402
from lightgbm_tpu.ops.pallas.histogram_int8 import histogram_pallas_int8  # noqa: E402


def _topo():
    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


def compile_on_topo(topo, fn, *args, **static):
    """AOT-compile fn(*args, **static) for one abstract TPU device."""
    mesh = Mesh(np.array(topo.devices[:1]), ("d",))
    sh = NamedSharding(mesh, P())

    def call(*a):
        return fn(*a, **static)

    lowered = jax.jit(call, in_shardings=[sh] * len(args)).lower(*args)
    return lowered.compile()


def s(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


CHECKS = {}
NOTES = {}  # entry name -> a note for its OK line
_ENTRY = [None]  # the entry that is running (its NOTES key)


def check(name):
    def deco(f):
        def run(topo):
            _ENTRY[0] = name
            return f(topo)

        CHECKS[name] = run
        return run

    return deco


@check("histogram_pallas bf16 hi/lo (n=1000,f=28,b=256)")
def _c1(topo):
    return compile_on_topo(
        topo, histogram_pallas,
        s((1000, 28), jnp.int32), s((1000,), jnp.float32),
        s((1000,), jnp.float32), s((1000,), jnp.float32), num_bins=256,
    )


@check("seg_hist_pallas f=28 b=256")
def _c2(topo):
    n_pad = padded_rows(5000)
    return compile_on_topo(
        topo, seg_hist_pallas,
        s((storage_lanes(28), n_pad), jnp.int16), s((2,), jnp.int32),
        f=28, num_bins=256, n_pad=n_pad,
    )


@check("seg_hist_pallas int8 quantized f=28 b=256")
def _c3(topo):
    n_pad = padded_rows(5000)
    return compile_on_topo(
        topo, seg_hist_pallas,
        s((storage_lanes(28), n_pad), jnp.int16), s((2,), jnp.int32),
        s((2,), jnp.float32),
        f=28, num_bins=256, n_pad=n_pad, quantized=True,
    )


@check("seg_hist_pallas u16 wide f=4 b=1024")
def _c4(topo):
    n_pad = padded_rows(5000)
    return compile_on_topo(
        topo, seg_hist_pallas,
        s((storage_lanes(4, wide=True), n_pad), jnp.int16),
        s((2,), jnp.int32),
        f=4, num_bins=1024, n_pad=n_pad, wide=True,
    )


@check("histogram_pallas_int8 grid (n=1200,f=30,b=255)")
def _c5(topo):
    n = 1200

    def call(bins, g, h, m, gs, hs):
        return histogram_pallas_int8(bins, g, h, m, 255, gs, hs)

    return compile_on_topo(
        topo, call,
        s((n, 30), jnp.int32), s((n,), jnp.float32), s((n,), jnp.float32),
        s((n,), jnp.float32), s((), jnp.float32), s((), jnp.float32),
    )


# The partition kernel's block-prefetching tile loop at the shapes that run
# it: both benchmark cells' (their rows set n_pad, so the DMAs' extents are
# the real ones), every plane there is (f=242: sub = 128, where the scratch
# is largest), u16 planes with the widest categorical table seg_vmem_ok
# admits, and the go-left-bits stream of feature-parallel seg.
_HIGGS_ROWS = 10_500_000
_CRITEO_ROWS = 8_000_000


def _partition(topo, f, rows, *, use_cat=False, wide=False, bmt=256, gl=False):
    n_pad = padded_rows(rows)
    args = [
        s((storage_lanes(f, wide), n_pad), jnp.int16), s((8,), jnp.int32),
        s((1, bmt), jnp.float32),
    ]
    if gl:
        args.append(s((n_pad,), jnp.float32))
    return compile_on_topo(
        topo, seg_partition_pallas, *args,
        f=f, n_pad=n_pad, use_cat=use_cat, wide=wide,
    )


@check("seg_partition_pallas column-read f=28 (toy rows: the clipped block)")
def _c6(topo):
    return _partition(topo, 28, 5000, use_cat=True)


@check("seg_partition_pallas u16 wide f=4")
def _c8(topo):
    return _partition(topo, 4, 5000, use_cat=True, wide=True, bmt=1024)


@check("seg_partition_pallas higgs.fit shape f=28 10.5M rows")
def _c17(topo):
    return _partition(topo, 28, _HIGGS_ROWS)


@check("seg_partition_pallas criteo67 shape f=67 8M rows")
def _c18(topo):
    return _partition(topo, 67, _CRITEO_ROWS)


@check("seg_partition_pallas sub=128 f=242 categorical")
def _c19(topo):
    return _partition(topo, 242, 1_000_000, use_cat=True)


@check("seg_partition_pallas u16 wide f=121 b=8192 categorical (largest admitted)")
def _c20(topo):
    return _partition(
        topo, 121, 1_000_000, use_cat=True, wide=True, bmt=8192
    )


_EPSILON_ROWS, _EPSILON_F = 400_000, 2_000


def _grouped_seg(f, rows, wide=False):
    g, sub = group_shape(f, wide)
    return s((g, sub, padded_rows(rows)), jnp.int16)


@check("seg_partition_pallas grouped (8 x 128 planes) epsilon shape f=2000 400k rows")
def _c24(topo):
    n_pad = padded_rows(_EPSILON_ROWS)
    return compile_on_topo(
        topo, seg_partition_pallas,
        _grouped_seg(_EPSILON_F, _EPSILON_ROWS), s((8,), jnp.int32),
        s((1, 256), jnp.float32), s((n_pad,), jnp.float32),
        f=_EPSILON_F, n_pad=n_pad, use_cat=False,
    )


@check("seg_partition_pallas grouped (2 x 80 planes) f=243")
def _c25(topo):
    n_pad = padded_rows(100_000)
    return compile_on_topo(
        topo, seg_partition_pallas,
        _grouped_seg(243, 100_000), s((8,), jnp.int32),
        s((1, 256), jnp.float32), s((n_pad,), jnp.float32),
        f=243, n_pad=n_pad, use_cat=False,
    )


@check("seg_hist_pallas grouped epsilon shape f=2000 b=256 (250 feature groups)")
def _c26(topo):
    NOTES[_ENTRY[0]] = _hist_step_note(_EPSILON_F)
    n_pad = padded_rows(_EPSILON_ROWS)
    return compile_on_topo(
        topo, seg_hist_pallas,
        _grouped_seg(_EPSILON_F, _EPSILON_ROWS), s((2,), jnp.int32),
        f=_EPSILON_F, num_bins=256, n_pad=n_pad,
    )


@check("seg_hist_pallas grouped int8 f=500 b=256")
def _c27(topo):
    n_pad = padded_rows(100_000)
    return compile_on_topo(
        topo, seg_hist_pallas,
        _grouped_seg(500, 100_000), s((2,), jnp.int32), s((2,), jnp.float32),
        f=500, num_bins=256, n_pad=n_pad, quantized=True,
    )


@check("seg_hist_pallas grouped u16 wide f=200 b=512")
def _c28(topo):
    n_pad = padded_rows(100_000)
    return compile_on_topo(
        topo, seg_hist_pallas,
        _grouped_seg(200, 100_000, wide=True), s((2,), jnp.int32),
        f=200, num_bins=512, n_pad=n_pad, wide=True,
    )


def _hist_step_note(f, num_bins=256, wide=False, long=True):
    """The step the histogram kernel resolves to at this shape
    (``seg.hist_step``: long steps where the form has two digits, the
    TILE-row loop alone where it is the full one-hot), for the OK line."""
    from lightgbm_tpu.ops.pallas.seg import (
        TILE, hist_bpad, hist_step, hist_sub, plane_groups,
    )

    step = hist_step(
        f, hist_bpad(num_bins), hist_sub(f, wide, plane_groups(f, wide) > 1))
    assert (step > TILE) == long, (f, num_bins, wide, step)
    return f"hist_step {step}+{TILE}" if long else f"hist_step {TILE}"


def _hist_batch(topo, f, rows, num_bins=256, k=1, quantized=False, wide=False,
                long=True):
    from lightgbm_tpu.ops.pallas.seg import seg_hist_pallas_batch

    NOTES[_ENTRY[0]] = _hist_step_note(f, num_bins, wide, long)
    n_pad = padded_rows(rows)
    return compile_on_topo(
        topo, seg_hist_pallas_batch,
        s((storage_lanes(f, wide), n_pad), jnp.int16), s((k, 2), jnp.int32),
        s((2,), jnp.float32),
        f=f, num_bins=num_bins, n_pad=n_pad, quantized=quantized, wide=wide,
    )


# The histogram kernel's two-digit one-hot at the cells' shapes through the
# two-launch caller (the fused caller at criteo67's and the grouped row's
# 250-program grid at epsilon's are above), and the digit widths the cells
# do not run: bpad 128, a u16 width that factors, one past 2048 (H = 1).
@check("seg_hist_pallas_batch higgs.fit shape bf16 f=28 10.5M rows")
def _c29(topo):
    return _hist_batch(topo, 28, _HIGGS_ROWS)


@check("seg_hist_pallas_batch criteo67.fit shape bf16 f=67 8M rows")
def _c30(topo):
    return _hist_batch(topo, 67, _CRITEO_ROWS)


@check("seg_hist_pallas_batch K=4 bf16 bpad=128 (4x32 digits) f=28")
def _c31(topo):
    return _hist_batch(topo, 28, 5000, num_bins=127, k=4)


@check("seg_hist_pallas_batch u16 wide int8 f=9 b=1000 (16x64 digits)")
def _c32(topo):
    return _hist_batch(topo, 9, 5000, num_bins=1000, quantized=True, wide=True)


@check("seg_hist_pallas_batch u16 wide f=3 b=4000 (H = 1: the full one-hot)")
def _c33(topo):
    return _hist_batch(topo, 3, 5000, num_bins=4000, wide=True, long=False)


@check("seg_hist_pallas_batch criteo67-quant.fit shape int8 f=67 8M rows")
def _c34(topo):
    return _hist_batch(topo, 67, _CRITEO_ROWS, quantized=True)


@check("seg_partition_pallas bits-fed (gl_vec) f=28 10.5M rows")
def _c7(topo):
    return _partition(topo, 28, _HIGGS_ROWS, gl=True)


@check("split_scan fused best-split (F=28, B=256)")
def _c10(topo):
    from lightgbm_tpu.ops.pallas.split_scan import split_scan_pallas

    return compile_on_topo(
        topo, split_scan_pallas,
        s((3, 28, 256), jnp.float32), s((3,), jnp.float32),
        s((28,), jnp.int32), s((28,), jnp.int32), s((28,), jnp.float32),
        f=28, num_bins_pad=256, l1=0.1, l2=1.0, min_data=20, min_hess=1e-3,
    )


@check("split_scan vmapped over both children, as the grower calls it")
def _c11(topo):
    from lightgbm_tpu.ops.pallas.split_scan import split_scan_pallas

    def call(hist2, par2, nb, nanb, fm2):
        return jax.vmap(
            lambda h, p, m: split_scan_pallas(
                h, p, nb, nanb, m, f=28, num_bins_pad=256, l1=0.0, l2=0.0,
                min_data=100, min_hess=1e-3,
            )
        )(hist2, par2, fm2)

    return compile_on_topo(
        topo, call,
        s((2, 3, 28, 256), jnp.float32), s((2, 3), jnp.float32),
        s((28,), jnp.int32), s((28,), jnp.int32), s((2, 28), jnp.float32),
    )


@check("seg_partition_pallas_batch K=4 f=28")
def _c12(topo):
    from lightgbm_tpu.ops.pallas.partition import seg_partition_pallas_batch

    n_pad = padded_rows(5000)
    return compile_on_topo(
        topo, seg_partition_pallas_batch,
        s((storage_lanes(28), n_pad), jnp.int16), s((4, 8), jnp.int32),
        s((4, 256), jnp.float32),
        f=28, n_pad=n_pad, use_cat=True,
    )


@check("seg_hist_pallas_batch K=4 int8 quantized + live mask f=28 b=256")
def _c13(topo):
    from lightgbm_tpu.ops.pallas.seg import (
        hist_bpad, hist_ngroups, seg_hist_pallas_batch,
    )

    n_pad = padded_rows(5000)
    return compile_on_topo(
        topo, seg_hist_pallas_batch,
        s((storage_lanes(28), n_pad), jnp.int16), s((4, 2), jnp.int32),
        s((2,), jnp.float32),
        s((hist_ngroups(28, hist_bpad(256)),), jnp.int32),
        f=28, num_bins=256, n_pad=n_pad, quantized=True,
    )


def _fused_step(topo, k, quantized, f=28, rows=5000):
    from lightgbm_tpu.ops.pallas.grow_step import fused_grow_step_pallas
    from lightgbm_tpu.ops.pallas.seg import hist_bpad, hist_ngroups

    n_pad = padded_rows(rows)
    return compile_on_topo(
        topo, fused_grow_step_pallas,
        s((storage_lanes(f), n_pad), jnp.int16), s((k, 8), jnp.int32),
        s((k, 256), jnp.float32), s((2,), jnp.float32),
        s((hist_ngroups(f, hist_bpad(256)),), jnp.int32),
        f=f, num_bins=256, n_pad=n_pad, use_cat=False, quantized=quantized,
    )


@check("fused_grow_step_pallas K=1 int8 f=28 b=256")
def _c14(topo):
    return _fused_step(topo, 1, True)


@check("fused_grow_step_pallas K=4 int8 f=28 b=256")
def _c15(topo):
    return _fused_step(topo, 4, True)


@check("fused_grow_step_pallas K=4 bf16 f=28 b=256")
def _c16(topo):
    return _fused_step(topo, 4, False)


@check("fused_grow_step_pallas criteo67.fit-eval shape K=1 bf16 f=67 8M rows")
def _c22(topo):
    NOTES[_ENTRY[0]] = _hist_step_note(67)
    return _fused_step(topo, 1, False, f=67, rows=_CRITEO_ROWS)


@check("fused_grow_step_pallas K=1 bf16 sub=128 f=242")
def _c23(topo):
    return _fused_step(topo, 1, False, f=242, rows=1_000_000)


@check("forest_walk predictor (T=64 trees, F=28, cat)")
def _c9(topo):
    from lightgbm_tpu.ops.pallas.forest_walk import (
        _forest_walk_jit, n_planes, CAT_WORDS,
    )

    t, h, n_tiles = 64, 2, 4
    p = n_planes(28)
    return compile_on_topo(
        topo, _forest_walk_jit,
        s((n_tiles, p, 8, 128), jnp.int32),
        s((t, h, 128), jnp.int32),
        s((t, h, 128), jnp.int32),
        s((t, h, 128), jnp.float32),
        s((t, CAT_WORDS, h, 128), jnp.int32),
        n_trees=t, max_depth=8, k=1, m_nodes=h * 128, has_cat=True,
        interpret=False,
    )


# ---------------------------------------------------------------------------
# whole grow programs, as the Booster dispatches them on a TPU
# ---------------------------------------------------------------------------

_HIGGS = dict(
    objective="binary", num_leaves=255, max_bin=255, min_data_in_leaf=100,
    verbosity=-1,
)
_ROWS = 1_000_000


@contextlib.contextmanager
def _as_tpu_process():
    """Make every ``jax.default_backend() == "tpu"`` test in the package
    take its TPU side in this CPU-only process: parameter resolution
    (hist_mode, grow_fused, int8 accumulation) and the kernel dispatchers
    then trace exactly what a process on the chip traces."""
    orig = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        yield
    finally:
        jax.default_backend = orig


def _grow_program(topo, n_devices=1, features=28, rows=_ROWS, **extra):
    """AOT-compile ``make_mesh_grow``'s program at ``features`` columns
    (Higgs width unless given): GrowerParams come from a real Booster (built
    on a small sample, resolved as on TPU), row-shaped operands are abstract
    at ``rows`` rows."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.parallel.mesh import (
        MeshSpec, build_mesh, make_mesh_grow, role_sharding,
    )

    rng = np.random.default_rng(0)
    x = rng.normal(size=(4000, features)).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.float32)
    with _as_tpu_process():
        booster = lgb.Booster(
            dict(_HIGGS, **extra), lgb.Dataset(x, y, params={"max_bin": 255})
        )
        params = booster._grower_params
        assert params.hist_mode == "seg", params.hist_mode
        spec = MeshSpec("data", data=n_devices)
        mesh = build_mesh(spec, list(topo.devices[:n_devices]))
        grow = make_mesh_grow(mesh if n_devices > 1 else None, params, spec)
        booster._mesh = None
        booster._setup_sharded_grower()  # fills the dummy optional operands
        if booster.config.use_quantized_grad:
            # a tree's scales: with them the grower takes the integer kernels
            booster._quant_scales = (jnp.float32(1.0), jnp.float32(1.0))

        def sds(a, role="replicated"):
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=role_sharding(mesh, role)
            )

        rows = jax.ShapeDtypeStruct(
            (rows,), jnp.float32, sharding=role_sharding(mesh, "rows")
        )
        bins = jax.ShapeDtypeStruct(
            (rows.shape[0], features), booster._bins.dtype,
            sharding=role_sharding(mesh, "bins"),
        )
        replicated = jax.tree.map(
            sds,
            (
                booster._num_bins, booster._nan_bins,
                booster._full_feature_mask, booster._mono_arg,
                booster._inter_arg, jax.random.PRNGKey(0),
                booster._iscat_arg, booster._forced, *booster._cegb_args(),
                booster._quant_scales_arg(), booster._bundle_end_arg,
                booster._contri_arg,
            ),
        )
        compiled = grow.lower(bins, rows, rows, rows, *replicated).compile()
    return compiled


# name -> _grow_program kwargs; every program must carry Mosaic calls, the
# sharded one collectives too
_PROGRAMS = {
    "two-launch K=1 (lgb.train scan body)": dict(grow_fused="off"),
    "fused K=1 (Booster.update default)": dict(),
    "fused K=4 (leaf_batch=4)": dict(leaf_batch=4),
    "two-launch K=4 (leaf_batch=4)": dict(leaf_batch=4, grow_fused="off"),
    "tree_learner=data over 4 devices": dict(n_devices=4),
    "tree_learner=data over 4 devices, quantized gradients": dict(
        n_devices=4, use_quantized_grad=True
    ),
}


_HLO_VALUE = re.compile(
    r"^\s*(?:ROOT )?%\S+ = f32\[([\d,]+)\](?:\{([\d,]*))?\S* ([\w-]+)\(", re.M
)


def hist_form_faults(text, features, leaves=255, bins=256):
    """What the optimized HLO of a grow program says against the histogram's
    form ([.., 3, F, B] planes; ``hist_buf`` [L + 1, 3, F, B] updated in
    place).  Two things: a ``copy`` as large as ``hist_buf`` (a read of the
    old loop carry after its first write costs one out and one back, every
    split), and an f32 value as large as one histogram whose minor-most
    dimension, as laid out, is the stat axis (length 3: whatever touches it
    runs on 3 of 128 lanes)."""
    one_hist = 3 * features * bins
    faults = set()
    for dims, layout, op in _HLO_VALUE.findall(text):
        shape = [int(d) for d in dims.split(",")]
        size = int(np.prod(shape))
        if op == "copy" and size >= leaves * one_hist:
            faults.add(f"a copy as large as hist_buf: f32[{dims}]")
        minor = shape[int(layout.split(",")[0])] if layout else shape[-1]
        if minor == 3 and size >= one_hist:
            faults.add(f"stat axis on the lanes: f32[{dims}]{{{layout}}} {op}")
    return sorted(faults)


def _checked_grow_program(topo, **kw):
    compiled = _grow_program(topo, **kw)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert kw.get("n_devices", 1) == 1 or "all-reduce" in text
    faults = hist_form_faults(text, kw.get("features", 28))
    assert not faults, faults
    return compiled


for _name, _kw in _PROGRAMS.items():
    CHECKS[f"grow program 1M x 28, 255 leaves: {_name}"] = functools.partial(
        _checked_grow_program, **_kw
    )


# the Epsilon shape (400,000 x 2,000: a packed row of 8 plane groups).  Both
# of lgb.train's grow programs there are the two-launch one: Booster.update
# (grow_fused resolves on, the grower sees the groups and takes the
# two-launch kernels) and the body of the eight-step launch scan (the scan's
# own wrapper needs a live Booster's operands and is not compiled here).
for _name, _kw in {
    "Booster.update (valid_sets)": dict(),
    "launch-scan body (grow_fused off)": dict(grow_fused="off"),
}.items():
    CHECKS[f"grow program 400k x 2000 (8 plane groups), 255 leaves: {_name}"] = (
        functools.partial(
            _checked_grow_program, features=_EPSILON_F, rows=_EPSILON_ROWS,
            hist_acc="bf16", **_kw,
        )
    )


def _launch_scan(topo, rows, features, **extra):
    """``(booster, span_args, compiled)``: ``lgb.train``'s eight-step
    ``lax.scan`` of a Higgs-shaped booster with ``extra`` parameters, whole,
    lowered for one abstract chip from a live Booster's operands (hence the
    small tables its callers pass); ``span_args`` are the forms the Booster
    names on its top spans, resolved as on a TPU."""
    import lightgbm_tpu as lgb
    from jax.sharding import SingleDeviceSharding
    from lightgbm_tpu.boosting.launch import LaunchRunner

    rng = np.random.default_rng(0)
    x = rng.normal(size=(rows, features)).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.float32)
    one_chip = SingleDeviceSharding(topo.devices[0])
    with _as_tpu_process():
        booster = lgb.Booster(
            dict(_HIGGS, **extra), lgb.Dataset(x, y, params={"max_bin": 255})
        )
        assert booster._grower_params.hist_mode == "seg"
        span_args = {**booster._seg_span_args(), **booster._score_span_args()}
        runner = LaunchRunner(booster, 8)
        operands = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            runner._operands(0)[0],
        )
        compiled = runner._fn.lower(*operands).compile()
    return booster, span_args, compiled


@check(
    "launch scan, quantized gradients, 200k x 67, 255 leaves: "
    "8 x (quantize + two-launch grow + renew)"
)
def _quantized_launch_scan(topo, rows=200_000, features=67):
    """``lgb.train``'s eight-step ``lax.scan`` of a booster with
    ``use_quantized_grad``, whole: a live Booster's operands (hence the
    small table), the wrapper's program lowered for one abstract chip.  Held
    to the grow programs' gate (no copy of ``hist_buf``'s size, no stat axis
    on the lanes), to the integer form of the histogram kernel (its raw
    planes are int32), and to a score update without a gather of the rows."""
    _, span_args, compiled = _launch_scan(
        topo, rows, features, use_quantized_grad=True, quant_train_renew_leaf=True
    )
    assert span_args["hist_int8"] is True
    text = compiled.as_text()
    kernels = [
        ln for ln in text.splitlines() if "tpu_custom_call" in ln and " = " in ln
    ]
    assert any(re.search(r"= s32\[\d+,\d+,8,\d+\]", ln) for ln in kernels), (
        "no histogram kernel with int32 raw planes"
    )
    faults = hist_form_faults(text, features)
    assert not faults, faults
    return compiled


_GOSS = dict(data_sample_strategy="goss", top_rate=0.2, other_rate=0.1)


def row_sorts(text, rows):
    """Sorts of the optimized HLO with an operand of ``rows`` rows (the
    segment form of ``leaf_ids`` has one a tree: ``leaf_id_from_seg``)."""
    return [
        ln.strip()[:160] for ln in text.splitlines()
        if re.search(r" sort\(", ln) and f"[{rows}]" in ln
    ]


def _kernel_names(text):
    return {
        m.group(1)
        for m in re.finditer(r"%([\w.\-]+?)(?:\.\d+)? = .*custom-call\(.*tpu_custom_call", text)
    }


def _goss_scan_body(topo, rows=8_000_000, features=67):
    """The grow program the launch scan's body traces for ``criteo67-goss``
    (two-launch kernels, ``bag_window``), at the cell's own size: the
    compaction kernel under its own name, the splits' partition under its,
    the out-of-bag walk without a gather of the rows."""
    compiled = _checked_grow_program(
        topo, features=features, rows=rows, grow_fused="off", hist_acc="bf16",
        **_GOSS,
    )
    text = compiled.as_text()
    kernels = _kernel_names(text)
    assert {"bag_compact_pallas", "seg_partition_pallas"} <= kernels, kernels
    # every row's leaf comes from the walk under scope oob_score: no sort of
    # the rows, and no gather of them under the window's scopes
    assert not row_sorts(text, rows), row_sorts(text, rows)
    faults = [
        f for scope in ("bag_compact", "oob_score")
        for f in row_gather_faults(text, rows, scope=scope)
    ]
    assert not faults, faults
    return compiled


CHECKS[
    "grow program 8M x 67, 255 leaves, GOSS (criteo67-goss): launch-scan body "
    "on the in-bag window"
] = _goss_scan_body


@check("GOSS sample 8M rows (sampling/goss_sample): no sort, no row gather")
def _goss_sample_entry(topo, rows=8_000_000):
    """The threshold is an exact selection by comparisons and sums: no sort
    of the rows (the parent's ``jnp.sort``), no gather of them, and
    temporaries that hold no [15, rows] compare."""
    from lightgbm_tpu.boosting.sampling import goss_sample

    compiled = compile_on_topo(
        topo, goss_sample,
        s((1, rows), jnp.float32), s((1, rows), jnp.float32),
        s((), jnp.uint32), s((), jnp.uint32),
        n=rows, top_k=rows // 5, other_k=rows // 10,
    )
    text = compiled.as_text()
    assert not re.search(r" sort\(", text), "a sort in goss_sample"
    faults = row_gather_faults(text, rows)
    assert not faults, faults
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 64 * rows, temp
    return compiled


@check(
    "launch scan, GOSS, 200k x 67, 255 leaves: 8 x (sample + compaction + "
    "two-launch grow on the window + out-of-bag walk)"
)
def _goss_launch_scan(topo, rows=200_000, features=67):
    """``lgb.train``'s eight-step ``lax.scan`` of a booster with
    ``data_sample_strategy=goss``, whole (a live Booster's operands, hence
    the small table): the compaction kernel is in it, no sort of the rows
    (the threshold is a selection, every row's leaf the walk's), no gather
    of them."""
    booster, _, compiled = _launch_scan(topo, rows, features, hist_acc="bf16", **_GOSS)
    assert booster._grower_params.bag_window
    text = compiled.as_text()
    assert "bag_compact_pallas" in _kernel_names(text), _kernel_names(text)
    assert not row_sorts(text, rows), row_sorts(text, rows)
    faults = hist_form_faults(text, features) + [
        f for scope in ("sample", "bag_compact", "oob_score", "score_update")
        for f in row_gather_faults(text, rows, scope=scope)
    ]
    assert not faults, faults
    return compiled


def _leaf_ids_grow_program(topo, walks, **kw):
    """A grow program of a booster that samples nothing, held to the form
    ``score_lookup.leaf_ids_form`` gives its shapes: the walk leaves no sort
    of the rows and no gather of them under scope ``leaf_ids``; the segment
    form keeps its one sort."""
    from lightgbm_tpu.ops.score_lookup import leaf_ids_form

    form = leaf_ids_form(kw.get("num_leaves", _HIGGS["num_leaves"]), kw["features"], 1, 0)
    assert (form == "walk") == walks, form
    compiled = _checked_grow_program(topo, **kw)
    text, rows = compiled.as_text(), kw["rows"]
    if walks:
        assert not row_sorts(text, rows), row_sorts(text, rows)
        faults = row_gather_faults(text, rows, scope="leaf_ids")
        assert not faults, faults
    else:
        assert len(row_sorts(text, rows)) == 1, row_sorts(text, rows)
    return compiled


CHECKS[
    "grow program 8M x 67, 255 leaves, no sampler (criteo67.fit): launch-scan "
    "body, every row's leaf from the walk"
] = functools.partial(
    _leaf_ids_grow_program, walks=True, features=67, rows=_CRITEO_ROWS,
    grow_fused="off", hist_acc="bf16",
)
CHECKS[
    "grow program 1M x 28, 1,023 leaves, no sampler: past the walk's size, "
    "leaf_ids keeps its one sort"
] = functools.partial(
    _leaf_ids_grow_program, walks=False, features=28, rows=_ROWS,
    grow_fused="off", num_leaves=1023,
)


@check(
    "launch scan, no sampler, 200k x 67, 255 leaves: 8 x (two-launch grow + "
    "the walk for every row's leaf + score update)"
)
def _plain_launch_scan(topo, rows=200_000, features=67):
    """``lgb.train``'s eight-step ``lax.scan`` of a booster that samples
    nothing, whole (a live Booster's operands, hence the small table): no
    sort of the rows, no gather of them under ``leaf_ids`` or the score
    update."""
    _, span_args, compiled = _launch_scan(topo, rows, features, hist_acc="bf16")
    assert span_args["leaf_ids"] == "walk"
    text = compiled.as_text()
    assert not row_sorts(text, rows), row_sorts(text, rows)
    faults = hist_form_faults(text, features) + [
        f for scope in ("leaf_ids", "score_update")
        for f in row_gather_faults(text, rows, scope=scope)
    ]
    assert not faults, faults
    return compiled


_HLO_GATHER = re.compile(r" = \w+\[([\d,]*)\]\S* gather\(")


def row_gather_faults(text, rows, scope=None):
    """Gathers of the optimized HLO whose output has ``rows`` rows (8 ns an
    element on the chip, PERF.md section 6, PR 34), in the whole program or
    under one ``jax.named_scope`` of it."""
    faults = set()
    for line in text.splitlines():
        m = _HLO_GATHER.search(line)
        if m is None or (scope is not None and f"/{scope}/" not in line):
            continue
        if rows in [int(d) for d in m.group(1).split(",") if d]:
            faults.add(line.strip()[:160])
    return sorted(faults)


def _checked_score_entry(topo, fn, rows, *args, **static):
    """A score-update entry at a cell's size: no gather of its rows, and
    temporaries that do not hold a [rows, leaves] one-hot (the compiler
    builds it inside the matmul's fusion; the contraction's row blocks keep
    the validation walk's temporaries from growing with the rows)."""
    compiled = compile_on_topo(topo, fn, *args, **static)
    faults = row_gather_faults(compiled.as_text(), rows)
    assert not faults, faults
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= max(64 * rows, 512 << 20), temp
    return compiled


def _numeric_tree(leaves):
    nn, i32 = leaves - 1, jnp.int32
    return (
        s((nn,), i32), s((nn,), i32), s((nn,), jnp.bool_), s((nn,), i32),
        s((nn,), i32), s((leaves,), jnp.float32),
    )


@check("score update 8M rows, 255 leaves (boost/score_update): no row gather")
def _score_update_entry(topo, rows=8_000_000, leaves=255):
    from lightgbm_tpu.boosting.gbdt import _tree_score_impl

    return _checked_score_entry(
        topo, _tree_score_impl, rows,
        s((1, rows), jnp.float32), s((leaves,), jnp.float32),
        s((rows,), jnp.int32), s((), jnp.int32),
    )


@check(
    "RF score update 8M rows x 255 leaves and 1M x 4,095 (rf/score_update): "
    "no row gather, no [leaves, rows] one-hot"
)
def _rf_score_update_entry(topo):
    from lightgbm_tpu.boosting.rf import _add_leaf_values_impl

    for rows, leaves in ((8_000_000, 255), (1_000_000, 4095)):
        compiled = _checked_score_entry(
            topo, _add_leaf_values_impl, rows,
            s((rows,), jnp.float32), s((leaves,), jnp.float32),
            s((rows,), jnp.int32),
        )
    return compiled


@check(
    "valid score update 400k x 67, 255 leaves, numeric tree "
    "(boost/valid_score_update): no row gather"
)
def _valid_score_update_entry(topo, rows=400_000, features=67, leaves=255):
    from lightgbm_tpu.boosting.gbdt import _tree_valid_score_impl

    nn = leaves - 1
    return _checked_score_entry(
        topo, _tree_valid_score_impl, rows,
        s((1, rows), jnp.float32), s((rows, features), jnp.uint8),
        s((features,), jnp.int32), *_numeric_tree(leaves),
        s((nn,), jnp.bool_), s((nn, 1), jnp.bool_), s((), jnp.int32),
    )


@check(
    "add_tree_to_score 8M x 67, 255 leaves, numeric tree "
    "(predict/add_tree_to_score): no row gather"
)
def _add_tree_entry(topo, rows=8_000_000, features=67, leaves=255):
    from lightgbm_tpu.predict import _add_tree_to_score_impl

    return _checked_score_entry(
        topo, _add_tree_to_score_impl, rows,
        s((rows,), jnp.float32), s((rows, features), jnp.uint8),
        s((features,), jnp.int32), *_numeric_tree(leaves),
    )


@check(
    "add_tree_to_score 4 x 400k x 67 rows over four chips (row_mesh): "
    "each shard's rows contracted locally, no collective, no row gather"
)
def _add_tree_row_sharded(topo, rows=1_600_000, features=67, leaves=255):
    from lightgbm_tpu.predict import _add_tree_to_score_impl

    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    by_rows, whole = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    tree = _numeric_tree(leaves)

    def call(score_k, bins, nan_bins, *t):
        return _add_tree_to_score_impl(
            score_k, bins, nan_bins, *t, row_mesh=(mesh, "data")
        )

    compiled = jax.jit(
        call,
        in_shardings=[by_rows, NamedSharding(mesh, P("data", None))]
        + [whole] * (1 + len(tree)),
        out_shardings=by_rows,
    ).lower(
        s((rows,), jnp.float32), s((rows, features), jnp.uint8),
        s((features,), jnp.int32), *tree,
    ).compile()
    text = compiled.as_text()
    assert not row_gather_faults(text, rows // 4), row_gather_faults(text, rows // 4)
    talk = re.findall(r"\b(all-reduce|all-gather|all-to-all|collective-permute)\(", text)
    assert not talk, sorted(set(talk))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 512 << 20, temp
    return compiled


def main(selected=None):
    topo = _topo()
    failures = []
    for name, fn in CHECKS.items():
        if selected and selected not in name:
            continue
        try:
            compiled = fn(topo)
            flops = None
            try:
                ca = compiled.cost_analysis()
                ca = ca[0] if isinstance(ca, (list, tuple)) else ca
                flops = ca.get("flops") if hasattr(ca, "get") else None
            except Exception:
                pass
            mem = ""
            if name.startswith("grow program"):
                ma = compiled.memory_analysis()
                mem = (f"  (arguments {ma.argument_size_in_bytes / 1e9:.2f} GB"
                       f" + temporaries {ma.temp_size_in_bytes / 1e9:.2f} GB)")
            note = f"  ({NOTES[name]})" if name in NOTES else ""
            print(f"OK   {name}" + (f"  (flops={flops:.3g})" if flops else "")
                  + note + mem)
        except Exception as e:
            failures.append(name)
            print(f"FAIL {name}: {type(e).__name__}")
            traceback.print_exc(limit=8)
    print(f"\n{len(CHECKS) - len(failures)}/{len(CHECKS)} entries compile on v5e topology")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else None))
