"""Named-mesh SPMD layouts: one sharding spec for data/feature/hybrid.

The t5x-style architecture: a 2-D device mesh
``Mesh(('data', 'feature'))`` plus a small logical-axis-rule table mapping
array ROLES (bin planes, per-row gradient state, score state, tree arrays)
to mesh axes via ``PartitionSpec``.  Every layout is then a mesh SHAPE, not
a code path:

* data-parallel      — ``(N, 1)``: rows sharded over ``'data'``, histogram
  and count psums over ``'data'`` (the reference's histogram ReduceScatter,
  data_parallel_tree_learner.cpp:225);
* feature-parallel   — ``(1, N)``: the ``'data'`` axis has size 1, so the
  SAME row rules degenerate to replication; features are sliced by
  ``axis_index('feature')`` inside the grower and the winner candidate is
  all-reduced over ``'feature'`` (feature_parallel_tree_learner.cpp:74);
* hybrid             — ``(D, F)``: rows sharded over ``'data'`` AND
  features sliced over ``'feature'``; histogram/count psums run over
  ``'data'`` on 1/F-width feature slices while the election broadcasts
  over ``'feature'`` — the 2-D layout a v5e-16 pod actually wants.

One ``shard_map``-wrapped ``grow_tree`` (``make_mesh_grow``) consumes the
spec; ``boosting/gbdt.py`` holds no per-layout forks.  On a trivial mesh
(1 device, or no mesh at all) the wrapper falls back to a plain ``jax.jit``
— the pjit-or-jit pattern — so the whole path stays testable
on the CI virtual CPU mesh.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.jit import instrumented_jit
from ..ops.grower import GrowerParams, TreeArrays, grow_tree
from . import _shard_map

# The two mesh axis names.  graftlint GL008 treats string literals drawn
# from this table as ONE consistent axis-name source per jitted region
# (lint/rules_spmd.py) — the sanctioned spelling for mesh-axis collectives.
MESH_AXIS_NAMES = ("data", "feature")
DATA_AXIS = MESH_AXIS_NAMES[0]
FEATURE_AXIS = MESH_AXIS_NAMES[1]

# ---- logical-axis rules: array role -> PartitionSpec over the 2-D mesh.
# Axes a spec does not mention are REPLICATED, so the same table serves
# every layout: on a (1, F) mesh the 'data' entries degenerate to
# replication and on a (D, 1) mesh the feature slicing is a no-op.
#   bins   [N, F]  — rows sharded; the grower slices features internally
#                    (a column slice by axis_index, not a mesh dim)
#   rows   [N]     — grad / hess / count_mask / leaf_id
#   score  [K, N]  — per-class score state, rows in the trailing dim
#   tree   [...]   — TreeArrays and split metadata: replicated (every
#                    shard computes the identical tree by construction)
AXIS_RULES = {
    "bins": P(DATA_AXIS, None),
    "rows": P(DATA_AXIS),
    "score": P(None, DATA_AXIS),
    "tree": P(),
    "replicated": P(),
}


def role_spec(role: str) -> P:
    """PartitionSpec for a logical array role (KeyError on unknown roles —
    a new array kind must be added to the table, never guessed)."""
    return AXIS_RULES[role]


def role_sharding(mesh: Mesh, role: str) -> NamedSharding:
    return NamedSharding(mesh, role_spec(role))


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """One distributed layout: the mesh shape plus its name.

    ``data`` / ``feature`` are the axis SIZES.  ``layout`` is the
    user-facing name ('data' | 'feature' | 'hybrid') — purely descriptive;
    every consumer reads the sizes.
    """

    layout: str
    data: int = 1
    feature: int = 1

    def __post_init__(self):
        if self.layout not in ("data", "feature", "hybrid"):
            raise ValueError(f"unknown mesh layout {self.layout!r}")
        if self.data < 1 or self.feature < 1:
            raise ValueError("mesh axis sizes must be >= 1")

    @property
    def size(self) -> int:
        return self.data * self.feature


def choose_spec(
    layout: str, n_devices: int, n_planes: int = 0
) -> Optional[MeshSpec]:
    """Pick a mesh shape for ``layout`` on ``n_devices`` devices.

    Returns None when the layout degenerates to serial (e.g. feature
    parallelism with no device count dividing the plane count — the
    reference likewise degrades to serial at num_machines==1, config.cpp).

    * 'data'    — all devices on the data axis.
    * 'feature' — the largest device count dividing ``n_planes`` (mirrors
      the pre-mesh gbdt selection so existing dryruns keep their shard
      count); rows replicated, so the data axis is 1.
    * 'hybrid'  — the largest feature-axis size ``fd`` with
      ``fd <= n_devices // fd``, ``fd | n_devices`` and
      ``fd | n_planes`` (feature slices must be equal); falls back to the
      data layout when no such factorization exists.
    """
    if n_devices < 2:
        return None
    if layout == "data":
        return MeshSpec("data", data=n_devices)
    if layout == "feature":
        for d in range(min(n_devices, max(n_planes, 1)), 1, -1):
            if n_planes % d == 0:
                return MeshSpec("feature", feature=d)
        return None
    if layout == "hybrid":
        for fd in range(int(n_devices**0.5), 1, -1):
            if n_devices % fd == 0 and n_planes > 0 and n_planes % fd == 0:
                return MeshSpec("hybrid", data=n_devices // fd, feature=fd)
        return MeshSpec("data", data=n_devices)
    raise ValueError(f"unknown mesh layout {layout!r}")


def build_mesh(spec: MeshSpec, devices: Optional[Sequence] = None) -> Mesh:
    """2-D device mesh for a spec: ``spec.size`` devices reshaped to
    ``(data, feature)`` with the canonical axis names."""
    if devices is None:
        devices = jax.devices()
    if len(devices) < spec.size:
        raise ValueError(
            f"mesh spec {spec} needs {spec.size} devices, "
            f"have {len(devices)}"
        )
    grid = np.asarray(devices[: spec.size]).reshape(spec.data, spec.feature)
    return Mesh(grid, MESH_AXIS_NAMES)


def grower_axis_params(params: GrowerParams, spec: MeshSpec) -> GrowerParams:
    """GrowerParams with the axis fields derived from the spec — the ONLY
    place layout becomes grower configuration:

    * ``axis_name``          — 'data' when rows are actually sharded;
    * ``feature_axis_name``  — 'feature' when features are sliced;
    * ``feature_shard``      — the feature-axis size (0 = off).

    A size-1 axis is dropped entirely so the grower traces the exact
    one-axis (or serial) program it always has — a (N, 1) mesh stays
    byte-identical to the pre-mesh data-parallel path.
    """
    return dataclasses.replace(
        params,
        axis_name=DATA_AXIS if spec.data > 1 else None,
        feature_axis_name=FEATURE_AXIS if spec.feature > 1 else None,
        feature_shard=spec.feature if spec.feature > 1 else 0,
    )


def make_mesh_grow(mesh: Optional[Mesh], params: GrowerParams,
                   spec: Optional[MeshSpec] = None):
    """The single jitted grow path: ``grow_tree`` shard_map'd over the 2-D
    mesh with in/out specs drawn from AXIS_RULES.

    All three layouts flow through THIS function — the spec (mesh shape +
    derived GrowerParams axis fields) is the only thing that changes.
    With no mesh (or a 1-device one) the same grower jits directly
    (the pjit-or-jit fallback), which is what CI exercises off the virtual
    mesh.  The jit label is kept at ``parallel/sharded_grow`` so the perf
    contract's retrace keys cover the mesh path unchanged.
    """
    if spec is None:
        spec = MeshSpec("data", data=mesh.size if mesh is not None else 1)
    p = grower_axis_params(params, spec)

    def local(bins, grad, hess, mask, num_bins, nan_bins, feature_mask,
              monotone, interaction_sets, rng, is_cat, forced, cegb_penalty,
              cegb_used, quant_scales, bundle_end, feature_contri):
        return grow_tree(
            bins, grad, hess, mask, num_bins, nan_bins, feature_mask, p,
            monotone=monotone, interaction_sets=interaction_sets, rng=rng,
            is_cat=is_cat, forced=forced, cegb_penalty=cegb_penalty,
            cegb_used=cegb_used, quant_scales=quant_scales,
            bundle_end=bundle_end, feature_contri=feature_contri,
        )

    if mesh is None or mesh.size == 1:
        return instrumented_jit(local, label="parallel/sharded_grow")

    rep = role_spec("replicated")
    rows = role_spec("rows")
    fn = _shard_map(
        local,
        mesh=mesh,
        in_specs=(role_spec("bins"), rows, rows, rows, rep, rep, rep, rep,
                  rep, rep, rep, rep, rep, rep, rep, rep, rep),
        out_specs=(
            jax.tree.map(
                lambda _: role_spec("tree"),
                TreeArrays(*([0] * len(TreeArrays._fields))),
            ),
            rows,
        ),
    )
    return instrumented_jit(fn, label="parallel/sharded_grow")


# vmap model-axis name for fleet training.  Distinct from the mesh axes:
# the fleet axis is a vmap batching axis INSIDE the shard_map body, used
# only to unmap capacity-bucket indices (GrowerParams.fleet_axis_name).
FLEET_AXIS = "fleet"


def make_fleet_grow(mesh: Optional[Mesh], params: GrowerParams,
                    spec: Optional[MeshSpec] = None):
    """The fleet grow path: ``grow_tree`` vmapped over a leading model axis
    M, composed INSIDE the same shard_map the solo path uses.

    Operand batching (leading [M] axis): grad, hess, count_mask,
    feature_mask, rng.  Everything else — the [N, P] bin planes, bin
    metadata, constraint tables — is shared across members, so the batched
    histogram builds reuse ONE resident bin matrix and the data-mesh
    histogram psum moves one stacked [M, K, 3, F, B] payload per step
    instead of M separate ones.  Outputs come back stacked: TreeArrays
    [M, ...] and leaf_id [M, N].

    Member arrays ride the mesh with the member axis REPLICATED and rows
    sharded (``P(None, 'data')``) — each shard holds its row slice of every
    member.  The vmap carries ``axis_name=FLEET_AXIS`` so the grower can
    pmax capacity-bucket indices across members (one shared ladder branch;
    see GrowerParams.fleet_axis_name).  Per-member byte parity vs the solo
    path is the acceptance oracle (tests/test_fleet.py).
    """
    if spec is None:
        spec = MeshSpec("data", data=mesh.size if mesh is not None else 1)
    p = dataclasses.replace(
        grower_axis_params(params, spec), fleet_axis_name=FLEET_AXIS
    )

    def local(bins, grad, hess, mask, num_bins, nan_bins, feature_mask,
              monotone, interaction_sets, rng, is_cat, forced, cegb_penalty,
              cegb_used, quant_scales, bundle_end, feature_contri):
        return grow_tree(
            bins, grad, hess, mask, num_bins, nan_bins, feature_mask, p,
            monotone=monotone, interaction_sets=interaction_sets, rng=rng,
            is_cat=is_cat, forced=forced, cegb_penalty=cegb_penalty,
            cegb_used=cegb_used, quant_scales=quant_scales,
            bundle_end=bundle_end, feature_contri=feature_contri,
        )

    # member axis on grad/hess/mask/feature_mask/rng; all else shared
    in_axes = (None, 0, 0, 0, None, None, 0, None, None, 0, None, None,
               None, None, None, None, None)
    batched = jax.vmap(local, in_axes=in_axes, axis_name=FLEET_AXIS)

    if mesh is None or mesh.size == 1:
        return instrumented_jit(batched, label="fleet/grow")

    rep = role_spec("replicated")
    mrows = P(None, DATA_AXIS)  # [M, N]: members replicated, rows sharded
    fn = _shard_map(
        batched,
        mesh=mesh,
        in_specs=(role_spec("bins"), mrows, mrows, mrows, rep, rep, rep, rep,
                  rep, rep, rep, rep, rep, rep, rep, rep, rep),
        out_specs=(
            jax.tree.map(
                lambda _: role_spec("tree"),
                TreeArrays(*([0] * len(TreeArrays._fields))),
            ),
            mrows,
        ),
    )
    return instrumented_jit(fn, label="fleet/grow")


def fleet_psum_bytes_per_iteration(
    n_splits: int,
    n_features: int,
    num_bins: int,
    fleet: int,
    leaf_batch: int = 1,
    spec: Optional[MeshSpec] = None,
) -> dict:
    """Analytic per-iteration psum bytes for an M-member fleet: the batched
    grow issues the SAME collective sites as one member with every payload
    carrying an extra leading [M] axis, so each entry is exactly M x the
    solo model.  Kept as its own function (not a multiplier at call sites)
    so the perf gate and the fleet bench pin one shared formula."""
    solo = mesh_psum_bytes_per_iteration(
        n_splits, n_features, num_bins, leaf_batch=leaf_batch, spec=spec
    )
    m = max(1, int(fleet))
    out = {k: v * m for k, v in solo.items()}
    out["steps"] = solo["steps"]  # lockstep: shared trip count, M x payload
    out["fleet"] = m
    return out


def mesh_psum_bytes_per_iteration(
    n_splits: int,
    n_features: int,
    num_bins: int,
    leaf_batch: int = 1,
    spec: Optional[MeshSpec] = None,
    launch_steps: int = 1,
) -> dict:
    """Layout-aware analytic psum bytes for one boosting iteration — the
    2-D generalization of ``parallel.psum_bytes_per_iteration`` (which it
    reproduces exactly on a pure-data spec).

    Per-axis traffic:

    * data axis (``spec.data > 1``): histogram psums on the LOCAL feature
      width ``F / feature`` plus the smaller-child count psums — the
      dominant volume, unchanged in total across overlap on/off (the
      double-buffered sites split one payload into two);
    * feature axis (``spec.feature > 1``): the per-candidate winner
      election — 11 scalar-ish broadcast psums per elected candidate
      (2 per split step + the root refresh) plus the root-totals
      broadcast.  O(100 B/step): negligible next to histograms but
      modeled so measured-vs-analytic stays a tight assertion on every
      layout.
    """
    if spec is None:
        spec = MeshSpec("data", data=1)
    f, b = int(n_features), int(num_bins)
    k = max(1, int(leaf_batch))
    splits = max(0, int(n_splits))
    steps = -(-splits // k) if splits else 0
    f_loc = f // spec.feature if spec.feature > 1 else f
    hist_bytes = 0
    count_bytes = 0
    elect_bytes = 0
    if spec.data > 1:
        hist_payload = f_loc * b * 3 * 4  # [3, F_loc, B] f32
        hist_bytes = (steps * k + 1) * hist_payload  # + 1 root histogram
        count_bytes = steps * k * 2 * 4 + (0 if spec.feature > 1 else 8)
    if spec.feature > 1:
        # winner election (bc() in ops/grower._featpar_reduce): 10 scalar
        # psums + the width-1 cat mask, for each of 2 candidate refreshes
        # per split step + 1 root candidate; plus the [3] root-totals
        # broadcast.  pmax/pmin ride separate measured keys.
        elections = 2 * steps + 1
        elect_bytes = elections * 11 * 4
        count_bytes += 3 * 4  # root-totals broadcast psum
    d = max(1, spec.size)
    ring = 2.0 * (d - 1) / d
    # device-resident boosting (boosting/launch.py): one compiled launch
    # scans ``launch_steps`` iterations, each issuing the SAME collective
    # sites — per-launch traffic is an exact multiple of the per-iteration
    # model (the scan body contains each psum site once; trip count and
    # payloads are iteration-invariant)
    ls = max(1, int(launch_steps))
    hist_bytes *= ls
    count_bytes *= ls
    elect_bytes *= ls
    total = hist_bytes + count_bytes + elect_bytes
    return {
        "steps": steps * ls,
        "hist_bytes": hist_bytes,
        "count_bytes": count_bytes,
        "elect_bytes": elect_bytes,
        "psum_bytes": total,
        "ring_bytes_per_device": total * ring,
    }
