"""The reference's passes over every row, spread over the host's cores.

At 32M rows x 67 features one tree's sums are 201 ``np.bincount`` calls over
32M keys, and ``np.bincount`` holds the interpreter lock: threads run them
one after another.  ``RowPool.leaf_sums`` gives each of a set of worker
processes a fixed group of features instead.  The workers are started with
``spawn`` (never ``fork``: the parent holds the TPU client and its threads)
and import NumPy and this module alone.  Each keeps its features' level
columns in its own memory (sent once through its pipe: they do not change
from tree to tree) and reads the per-row leaf, g and h from shared memory
that lives as long as the pool, so every page is mapped once a worker and
not once a call; each sends its features' sums back through its pipe.

Every sum is the same to the last bit as ``gbdt.leaf_sums``': a worker adds
each row's g (h) into its (leaf, level) bin in row order, starting from 0.0,
which is what ``np.bincount`` does.  It walks the rows in chunks that stay in
cache and adds with ``np.add.at``, which accumulates in place and in order
(``np.bincount`` would start each chunk from 0.0 and change the association).

The walk, the level columns and the gradients are NumPy operations that
release the lock; ``map_blocks`` runs them a row block a thread.
"""

from __future__ import annotations

import mmap
import os
import traceback
from concurrent.futures import ThreadPoolExecutor
from multiprocessing import get_context
from multiprocessing.shared_memory import SharedMemory
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

# rows x features under which one process is quicker than starting a pool
# (on an 8-core x86 host 4M x 67 took 2.5 s of bincounts a tree serially;
# starting the pool takes about 1 s)
POOL_MIN_CELLS = 100_000_000
CHUNK_ROWS = 1 << 16  # a worker's rows at a time: keys, g and h stay in cache
SHARED_BYTES_PER_ROW = 17  # a call's leaf (uint8), g and h (float64)
# a feature's sums go back through a pipe: worth it only where its rows are
# many times its (leaf, level) bins (on the same host 100,000 rows x 2,000
# features of 255 x 241 bins read 13.9 s pooled against 3.5 s serially)
ROWS_PER_BIN = 16
BLOCK_ROWS = 1 << 20  # a thread's rows at a time in ``row_blocks``
_SHM_DIR = "/dev/shm"  # where POSIX shared memory lives on Linux


def host_cores() -> int:
    return len(os.sched_getaffinity(0))


def shm_free_bytes() -> int:
    try:
        st = os.statvfs(_SHM_DIR)
    except OSError:
        return 0
    return st.f_bavail * st.f_frsize


def sums_worth_processes(rows: int, n_leaves: int, n_levels: int) -> bool:
    return rows >= ROWS_PER_BIN * n_leaves * n_levels


def row_blocks(n: int, rows: int = BLOCK_ROWS) -> List[slice]:
    return [slice(a, min(n, a + rows)) for a in range(0, n, rows)]


# ------------------------------------------------------------- the workers


def _attach(spec) -> np.ndarray:
    """A read-only view of the parent's segment, its page table filled in
    one call (``MAP_POPULATE``) rather than a fault a page; the mapping goes
    with the last reference to the view."""
    name, shape, dtype = spec
    nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
    fd = os.open(os.path.join(_SHM_DIR, name), os.O_RDONLY)
    try:
        m = mmap.mmap(fd, nbytes, flags=mmap.MAP_SHARED | getattr(mmap, "MAP_POPULATE", 0),
                      prot=mmap.PROT_READ)
    finally:
        os.close(fd)
    return np.frombuffer(m, np.dtype(dtype), int(np.prod(shape))).reshape(shape)


def _feature_sums(cols: Dict[int, np.ndarray], leaf, g, h, n_leaves: int, n_levels: int,
                  counts: bool) -> Dict[int, Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]]:
    """The [n_leaves, n_levels] sums of g, h (and counts) of each feature in
    ``cols``, over all rows in row order."""
    size = n_leaves * n_levels
    acc = {j: (np.zeros(size), np.zeros(size), np.zeros(size, np.int64) if counts else None)
           for j in cols}
    for a in range(0, len(leaf), CHUNK_ROWS):
        b = min(len(leaf), a + CHUNK_ROWS)
        base = leaf[a:b].astype(np.int64) * n_levels
        gc, hc = g[a:b], h[a:b]
        for j, col in cols.items():
            key = base + col[a:b]
            G, H, C = acc[j]
            np.add.at(G, key, gc)
            np.add.at(H, key, hc)
            if counts:
                C += np.bincount(key, minlength=size)
    return {j: tuple(None if x is None else x.reshape(n_leaves, n_levels) for x in sums)
            for j, sums in acc.items()}


def _serve(conn) -> None:
    """A worker's loop: ("cols", {j: column}) keeps its features' columns;
    ("sums", specs, n_leaves, n_levels, counts) answers their sums over the
    shared leaf, g, h; ("stop",) ends it.  An error is answered, not raised."""
    cols: Dict[int, np.ndarray] = {}
    maps: Dict[Any, np.ndarray] = {}
    with conn:
        while True:
            msg = conn.recv()
            if msg[0] == "stop":
                return
            try:
                if msg[0] == "cols":
                    cols = msg[1]
                    conn.send(("ok", None))
                    continue
                _, specs, n_leaves, n_levels, counts = msg
                maps = {spec: maps[spec] if spec in maps else _attach(spec) for spec in specs}
                conn.send(("ok", _feature_sums(cols, *(maps[s] for s in specs),
                                               n_leaves, n_levels, counts)))
            except Exception:  # the parent raises it with the worker's traceback
                conn.send(("error", traceback.format_exc()))


# -------------------------------------------------------------- the parent


class _Segment:
    """A per-row array in shared memory for the life of the pool."""

    def __init__(self, n: int, dtype) -> None:
        self.shm = SharedMemory(create=True, size=max(1, n * np.dtype(dtype).itemsize))
        self.array = np.ndarray((n,), np.dtype(dtype), buffer=self.shm.buf)
        self.spec = (self.shm.name, (n,), np.dtype(dtype).str)

    def free(self) -> None:
        del self.array
        self.shm.close()
        self.shm.unlink()


class RowPool:
    """Threads over row blocks and worker processes over features, for the
    life of one reference call (``with RowPool(...) as pool``)."""

    def __init__(self, processes: int, threads: Optional[int] = None) -> None:
        if processes < 1:
            raise ValueError(f"processes must be >= 1, got {processes}")
        self.processes = int(processes)
        self._threads = ThreadPoolExecutor(max_workers=int(threads or processes))
        self._workers: List[Tuple[Any, Any]] = []  # (process, connection)
        # the level columns the workers hold, kept alive so their ids stay theirs
        self._cols: Optional[List[np.ndarray]] = None
        self._inputs: Dict[str, _Segment] = {}  # leaf, g, h

    @classmethod
    def for_table(cls, rows: int, features: int) -> Optional["RowPool"]:
        """A pool sized from the host's cores, or None where one process is
        quicker (small tables, one core) or shared memory cannot hold a
        table's leaf, g and h twice over."""
        cores = host_cores()
        if (rows * features < POOL_MIN_CELLS or cores < 2
                or shm_free_bytes() < 2 * SHARED_BYTES_PER_ROW * rows):
            return None
        return cls(processes=min(cores, features), threads=cores)

    def __enter__(self) -> "RowPool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self) -> None:
        """Stop the workers, waiting for each, and free every segment."""
        for proc, conn in self._workers:
            try:
                conn.send(("stop",))
            except OSError:
                pass
        for proc, conn in self._workers:
            proc.join(timeout=60)
            if proc.is_alive():
                proc.terminate()
                proc.join()
            conn.close()
        self._workers = []
        self._cols = None
        self._threads.shutdown(wait=True)
        for seg in self._inputs.values():
            seg.free()
        self._inputs = {}

    # ------------------------------------------------------------ threads
    def map_blocks(self, fn: Callable[[Any], Any], blocks) -> list:
        """``[fn(b) for b in blocks]``, a block a thread."""
        return list(self._threads.map(fn, blocks))

    def copy(self, dst: np.ndarray, src: np.ndarray) -> None:
        """``dst[...] = src`` for two [N] arrays, a block of rows a thread."""
        def one(s):
            dst[s] = src[s]
        self.map_blocks(one, row_blocks(len(src)))

    # ---------------------------------------------------------- processes
    def _ask(self, msgs: Sequence[tuple]) -> list:
        """One message to each worker (a thread a pipe: the columns are
        large), then every answer, in order."""
        self.map_blocks(lambda wm: wm[0][1].send(wm[1]), list(zip(self._workers, msgs)))
        answers = [(proc.pid,) + conn.recv() for proc, conn in self._workers]
        for pid, kind, value in answers:  # every answer read first: none is left behind
            if kind == "error":
                raise RuntimeError(f"reference worker {pid} failed:\n{value}")
        return [value for _, _, value in answers]

    def _hand_out(self, level_cols: Sequence[np.ndarray]) -> None:
        """Start the workers, or give them other columns; each keeps a
        contiguous group of features."""
        if self._cols is not None and len(self._cols) == len(level_cols) and all(
                a is b for a, b in zip(self._cols, level_cols)):
            return
        ctx = get_context("spawn")
        while len(self._workers) < self.processes:
            mine, theirs = ctx.Pipe()
            proc = ctx.Process(target=_serve, args=(theirs,), daemon=True)
            proc.start()
            theirs.close()
            self._workers.append((proc, mine))
        groups = np.array_split(np.arange(len(level_cols)), self.processes)
        self._cols = None
        self._ask([("cols", {int(j): level_cols[j] for j in grp}) for grp in groups])
        self._cols = list(level_cols)

    def _input(self, role: str, src: np.ndarray) -> _Segment:
        seg = self._inputs.get(role)
        if seg is None or seg.array.shape != src.shape or seg.array.dtype != src.dtype:
            if seg is not None:
                seg.free()
            seg = self._inputs[role] = _Segment(len(src), src.dtype)
        self.copy(seg.array, src)
        return seg

    def leaf_sums(self, level_cols, leaf_of_row, g, h, n_leaves: int, n_levels: int,
                  counts: bool = True):
        """``gbdt.leaf_sums``' [n_leaves, F, n_levels] triple, a group of
        features a worker process; equal to the serial one to the last bit."""
        f = len(level_cols)
        self._hand_out(level_cols)
        leaf_dtype = np.uint8 if n_leaves <= 256 else np.int32
        specs = tuple(self._input(role, np.asarray(a, dt)).spec for role, a, dt in (
            ("leaf", leaf_of_row, leaf_dtype), ("g", g, np.float64), ("h", h, np.float64)))
        G = np.empty((n_leaves, f, n_levels))
        H = np.empty((n_leaves, f, n_levels))
        C = np.empty((n_leaves, f, n_levels)) if counts else None
        for sums in self._ask([("sums", specs, n_leaves, n_levels, counts)] * len(self._workers)):
            for j, (gj, hj, cj) in sums.items():
                G[:, j, :], H[:, j, :] = gj, hj
                if counts:
                    C[:, j, :] = cj
        return G, H, C
