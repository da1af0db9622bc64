"""Distributed training over a jax.sharding.Mesh.

Reference analogs: the Network layer (src/network/network.cpp — hand-rolled
Bruck allgather, recursive-halving reduce-scatter over TCP/MPI) and the
parallel tree learners (src/treelearner/data_parallel_tree_learner.cpp,
feature_parallel_tree_learner.cpp, voting_parallel_tree_learner.cpp).

TPU-native design (SURVEY §2.7/§2.8): rows are sharded over a mesh axis
``'data'``; the histogram ReduceScatter + best-split Allreduce become a single
``psum`` inside the jitted grower (XLA lowers it onto ICI rings / DCN between
hosts — no hand-rolled topology code).  Because every shard sees identical
psummed histograms, every shard computes the IDENTICAL tree — the best-split
Allreduce of SplitInfo (data_parallel_tree_learner.cpp:443) is subsumed by
determinism, and global leaf counts (:453) come out of the psummed counts for
free.  Multi-host: initialize ``jax.distributed`` and build the same Mesh over
all processes; the same shard_map then spans hosts (DCN) — the analog of the
reference's machine-list TCP setup (src/network/linkers_socket.cpp:25).

``tree_learner='feature'`` (features sharded, all rows everywhere) is a comm
optimization of the same semantics; on ICI bandwidth the plain psum is
usually fastest, so it is accepted and mapped onto the same path (results
are identical regardless).  ``tree_learner='voting'`` implements the real
PV-Tree election (ops/grower._candidate_for_leaf): histograms stay LOCAL,
each shard's top-``top_k`` weighted gains are pmax-merged, and only the
elected 2k features' ``[3, 2k, B]`` slices are psummed — engaged only when
``F > 2 * top_k`` (below that the dense psum is exact and cheaper, the
documented cutover; reference voting_parallel_tree_learner.cpp:152).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.jit import instrumented_jit
from ..ops.grower import GrowerParams, grow_tree
from ..ops.score_lookup import leaf_lookup

DATA_AXIS = "data"


def psum_bytes_per_iteration(
    n_splits: int,
    n_features: int,
    num_bins: int,
    leaf_batch: int = 1,
    mesh_size: int = 1,
) -> dict:
    """Analytic bytes moved by the grower's psums for one boosting iteration
    under ``tree_learner=data`` (recorded as telemetry gauges).

    The psums sit inside a jitted while_loop — traced once, executed per
    split step — so runtime interception can't count them; the payloads are
    fully determined by shapes instead:

    * root: one ``[3, F, B]`` f32 histogram psum per tree;
    * serial (``leaf_batch=1``): per split, one smaller-child ``[3, F, B]``
      f32 histogram psum plus a ``[2]`` i32 count psum;
    * batched (``leaf_batch=K``): per loop step, ONE ``[K, 3, F, B]``
      histogram psum plus ONE ``[K, 2]`` count psum.  The prefix-commit rule
      may commit fewer than K members per step, so ``ceil(splits / K)``
      steps is a lower bound — the model's documented approximation.

    ``ring_bytes_per_device`` scales the summed payload by the ring
    all-reduce factor ``2 * (D - 1) / D``.

    The timed-psum wrappers (obs/collectives, ``obs_collectives=True``)
    MEASURE the same traffic at runtime; tests/test_observability.py asserts
    the measured psum bytes land within 10% of ``hist_bytes + count_bytes``
    on an 8-device dryrun, and tools/perf_gate.py freezes both sides in the
    committed perf contract.
    """
    f, b, k = int(n_features), int(num_bins), max(1, int(leaf_batch))
    splits = max(0, int(n_splits))
    hist_payload = f * b * 3 * 4  # [3, F, B] f32
    steps = -(-splits // k) if splits else 0
    hist_bytes = (steps * k + 1) * hist_payload  # + 1 root histogram
    count_bytes = steps * k * 2 * 4 + 8  # [K, 2] i32 + root totals
    d = max(1, int(mesh_size))
    ring = 2.0 * (d - 1) / d
    return {
        "steps": steps,
        "hist_bytes": hist_bytes,
        "count_bytes": count_bytes,
        "ring_bytes_per_device": (hist_bytes + count_bytes) * ring,
    }


def _shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off — the one
    spelling every call site in the package uses."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def choose_devices(min_devices: int = 2):
    """Devices for distributed training: the default backend's devices, or
    None (with a log line) when it has fewer than ``min_devices``,
    signalling serial training (the reference likewise degrades
    ``tree_learner=data`` to serial when num_machines==1, config.cpp).
    Never another backend's devices: a mesh on CPU devices in a process
    whose default backend is the TPU would silently take every
    ``lax.platform_dependent`` ``default=`` branch.
    ``LGBM_TPU_FORCE_NDEV`` caps the mesh width (scaling experiments)."""
    import os

    cap = int(os.environ.get("LGBM_TPU_FORCE_NDEV", "0"))
    devices = jax.devices()
    if cap > 0:
        devices = devices[:cap]
    if len(devices) >= min_devices:
        return devices
    from ..utils.log import log_warning

    log_warning(
        f"distributed tree_learner requested but the {jax.default_backend()} "
        f"backend has {len(devices)} usable device(s) (< {min_devices}); "
        "training serially on one device"
    )
    return None


def data_axis_size(mesh: Optional[Mesh]) -> int:
    """Size of the row-sharding ('data') axis of ``mesh``.

    Row padding and per-shard row math must divide THIS, not the total
    device count: on a 2-D ``(data, feature)`` mesh rows are replicated
    over the feature axis, so a hybrid (4, 2) mesh needs rows % 4 == 0,
    not rows % 8.  A mesh without a 'data' axis (or no mesh) shards
    nothing, hence size 1."""
    if mesh is None:
        return 1
    return int(dict(mesh.shape).get(DATA_AXIS, 1))


def pad_rows_for(n_rows: int, mesh: Optional[Mesh]) -> int:
    """Rows of padding so ``n_rows`` divides the mesh's DATA axis."""
    return (-int(n_rows)) % data_axis_size(mesh)


def pad_rows_np(arr: np.ndarray, pad: int, fill=0):
    """Pad axis 0 of a host array with ``fill`` so rows divide the mesh's
    data axis (compute ``pad`` with ``pad_rows_for``)."""
    if pad == 0:
        return arr
    widths = [(0, pad)] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, widths, constant_values=fill)


def make_mesh(n_devices: Optional[int] = None, axis_name: str = DATA_AXIS) -> Mesh:
    """1-D device mesh over the data axis."""
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (axis_name,))


def shard_rows(
    arr, mesh: Mesh, axis_name: str = DATA_AXIS, process_local: bool = False
):
    """Place a host array with rows sharded over the mesh axis.

    ``process_local=True``: ``arr`` holds only THIS process's rows and the
    global array is their concatenation in process order — the reference's
    ``pre_partition`` contract (each machine loads its own partition,
    src/io/dataset_loader.cpp:210) via
    ``jax.make_array_from_process_local_data``; no process ever materializes
    the global matrix."""
    spec = P(axis_name, *([None] * (np.ndim(arr) - 1)))
    if process_local and jax.process_count() > 1:
        return jax.make_array_from_process_local_data(
            NamedSharding(mesh, spec), np.asarray(arr)
        )
    return jax.device_put(jnp.asarray(arr), NamedSharding(mesh, spec))


def shard_cols(
    arr, mesh: Mesh, axis_name: str = DATA_AXIS, process_local: bool = False
):
    """Place a host [K, N] array with COLUMNS (rows of the data) sharded."""
    if process_local and jax.process_count() > 1:
        return jax.make_array_from_process_local_data(
            NamedSharding(mesh, P(None, axis_name)), np.asarray(arr)
        )
    return jax.device_put(jnp.asarray(arr), NamedSharding(mesh, P(None, axis_name)))


def allgather_host_varlen(arr: np.ndarray, return_counts: bool = False):
    """Allgather variable-length per-process host rows; returns the global
    concatenation (process order) on every process — with ``return_counts``
    also the per-process row counts (to re-split the concat).

    The reference syncs init statistics with Network::Allreduce
    (objective_function.cpp ObtainAutomaticInitialScore); here the full
    label/weight columns are gathered instead — O(8 bytes/row), negligible
    next to the bin matrix that stays process-local."""
    from jax.experimental import multihost_utils

    arr = np.asarray(arr)
    counts = multihost_utils.process_allgather(
        np.asarray([arr.shape[0]], np.int32)
    ).reshape(-1)
    mx = int(counts.max())
    padded = np.zeros((mx,) + arr.shape[1:], arr.dtype)
    padded[: arr.shape[0]] = arr
    gathered = allgather_host_exact(padded)  # [nproc, mx, ...]
    out = np.concatenate(
        [gathered[i, : int(c)] for i, c in enumerate(counts)], axis=0
    )
    return (out, counts) if return_counts else out


def allgather_host_exact(arr: np.ndarray) -> np.ndarray:
    """process_allgather that preserves 64-bit payloads bit-exactly.

    ``multihost_utils.process_allgather`` routes through jax arrays, which
    (with x64 disabled) silently truncate float64/int64 to 32 bits — fatal
    for bin boundaries and label statistics.  64-bit inputs ride through as
    uint32 pairs instead."""
    from jax.experimental import multihost_utils

    arr = np.ascontiguousarray(arr)
    if arr.dtype.itemsize == 8:
        as32 = arr.view(np.uint32)  # [..., 2 * last]
        out = np.asarray(multihost_utils.process_allgather(as32))
        return out.view(arr.dtype)
    return np.asarray(multihost_utils.process_allgather(arr))


def replicate(arr, mesh: Mesh):
    return jax.device_put(jnp.asarray(arr), NamedSharding(mesh, P()))


def make_data_parallel_train_step(
    mesh: Mesh,
    params: GrowerParams,
    learning_rate: float,
    objective_grad: Callable[[jnp.ndarray, jnp.ndarray], Tuple[jnp.ndarray, jnp.ndarray]],
    axis_name: str = DATA_AXIS,
):
    """Build a jitted full training step over the mesh.

    The returned step takes row-sharded (bins, label, score) plus replicated
    (num_bins, nan_bins, feature_mask) and performs: gradients (local) ->
    grow_tree with psummed histograms (collectives over ICI) -> score update
    (local gather).  Semantics match DataParallelTreeLearner: local histogram,
    global reduction, global split selection, local partition.
    """
    p = params if params.axis_name == axis_name else GrowerParams(
        **{**params.__dict__, "axis_name": axis_name}
    )

    def step(bins, label, score, num_bins, nan_bins, feature_mask):
        grad, hess = objective_grad(score, label)
        mask = jnp.ones_like(grad)
        tree, leaf_id = grow_tree(
            bins, grad, hess, mask, num_bins, nan_bins, feature_mask, p
        )
        new_score = score + learning_rate * leaf_lookup(tree.leaf_value, leaf_id)
        return new_score, tree

    sharded = P(axis_name)
    sharded2 = P(axis_name, None)
    rep = P()
    fn = _shard_map(
        step,
        mesh=mesh,
        in_specs=(sharded2, sharded, sharded, rep, rep, rep),
        out_specs=(sharded, rep),
    )
    return instrumented_jit(fn, label="parallel/train_step")


def l2_gradients(score: jnp.ndarray, label: jnp.ndarray):
    return score - label, jnp.ones_like(score)


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    retries: int = 3,
    backoff: float = 1.0,
) -> None:
    """Multi-host initialization (the reference's machine-list / MPI init,
    src/network/linkers_socket.cpp:25 / linkers_mpi.cpp) via jax.distributed.

    Defaults come from the launcher's env vars when present
    (``python -m lightgbm_tpu.parallel.launcher -n N script.py``).

    Coordination-service startup is the flakiest moment of a multi-host
    run (coordinator not yet listening, port briefly in TIME_WAIT after a
    relaunch), so the initialize call retries up to ``retries`` times with
    exponential backoff starting at ``backoff`` seconds before giving up."""
    import time as _time

    from ..obs.registry import get_session
    from ..utils.log import log_warning
    from .launcher import env_distributed_config

    kwargs = env_distributed_config() or {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    attempts = max(1, int(retries))
    for attempt in range(attempts):
        try:
            jax.distributed.initialize(**kwargs)
            return
        except Exception as exc:
            if attempt + 1 >= attempts:
                raise
            delay = backoff * (2.0**attempt)
            get_session().record(
                {
                    "event": "init_distributed_retry",
                    "attempt": attempt + 1,
                    "delay_s": delay,
                    "error": f"{type(exc).__name__}: {exc}"[:300],
                }
            )
            log_warning(
                f"[resilience] jax.distributed.initialize failed "
                f"(attempt {attempt + 1}/{attempts}: {type(exc).__name__}); "
                f"retrying in {delay:.1f}s"
            )
            _time.sleep(delay)
