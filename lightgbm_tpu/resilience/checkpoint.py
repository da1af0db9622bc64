"""Iteration-granular atomic checkpointing for the training loop.

A checkpoint is a pickle of ``Booster._checkpoint_state()`` — the full
trainer state (model dump, device score cache, RNG key, bagging-mask
cache, adaptive ``leaf_batch`` EMA/cap, CEGB feature-usage set, telemetry
counters) — written with the tmp+fsync+rename idiom so a kill at ANY
byte offset leaves either the previous checkpoint or the new one, never a
torn file.  ``restore_checkpoint`` rehydrates a freshly constructed
training Booster to the exact post-iteration state, so the resumed run
replays the identical RNG stream and produces a byte-identical dump.
"""

from __future__ import annotations

import os
import pickle
import re
import tempfile
from typing import List, Optional, Tuple

from ..obs import get_flight, get_session
from ..utils.log import log_info

_CKPT_RE = re.compile(r"^ckpt_iter_(\d+)\.pkl$")


def _ckpt_name(iteration: int) -> str:
    return f"ckpt_iter_{iteration:08d}.pkl"


def atomic_write_bytes(path: str, payload: bytes) -> None:
    """Write ``payload`` to ``path`` via tmp file + fsync + rename.

    The tmp file lives in the destination directory so ``os.replace`` is
    a same-filesystem atomic rename; a crash mid-write can only leave a
    stray ``*.tmp``, never a truncated ``path``.
    """
    directory = os.path.dirname(os.path.abspath(path))
    fd, tmp = tempfile.mkstemp(
        prefix=os.path.basename(path) + ".", suffix=".tmp", dir=directory
    )
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(payload)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    # Best-effort directory fsync so the rename itself is durable.
    try:
        dfd = os.open(directory, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
    except OSError:
        pass


def atomic_write_text(path: str, text: str) -> None:
    atomic_write_bytes(path, text.encode("utf-8"))


def list_checkpoints(directory: str) -> List[Tuple[int, str]]:
    """All ``ckpt_iter_*.pkl`` files in ``directory`` as (iter, path),
    sorted by iteration ascending."""
    if not os.path.isdir(directory):
        return []
    out: List[Tuple[int, str]] = []
    for name in os.listdir(directory):
        m = _CKPT_RE.match(name)
        if m:
            out.append((int(m.group(1)), os.path.join(directory, name)))
    out.sort()
    return out


def latest_checkpoint(directory: str) -> Optional[str]:
    cks = list_checkpoints(directory)
    return cks[-1][1] if cks else None


def save_checkpoint(booster, directory: str, keep_last: Optional[int] = None) -> str:
    """Snapshot ``booster`` into ``directory`` and prune old checkpoints.

    Returns the checkpoint path.  ``keep_last`` defaults to the booster's
    ``checkpoint_keep`` config (older checkpoints beyond it are deleted;
    pass 0/None-config to keep everything).
    """
    from ..obs.trace import get_tracer

    with get_tracer().span(
        "lifecycle/checkpoint", "lifecycle", args={"directory": directory}
    ) as sp:
        state = booster._checkpoint_state()
        if keep_last is None:
            keep_last = int(getattr(booster.config, "checkpoint_keep", 0))
        os.makedirs(directory, exist_ok=True)
        path = os.path.join(directory, _ckpt_name(state["iter"]))
        atomic_write_bytes(path, pickle.dumps(state, protocol=4))
        if sp is not None:
            sp.args.update({"iter": state["iter"], "path": path})
    ses = get_session()
    event = {"event": "checkpoint", "iter": state["iter"], "path": path}
    ses.record(event, defer=True)
    # a fault dump names the newest durable checkpoint it pairs with
    flight = get_flight()
    flight.note_checkpoint(path)
    flight.note_event(event)
    if keep_last and keep_last > 0:
        for _, old in list_checkpoints(directory)[:-keep_last]:
            try:
                os.unlink(old)
            except OSError:
                pass
    return path


def restore_checkpoint(booster, path_or_dir: str) -> int:
    """Restore ``booster`` from a checkpoint file, or from the latest
    checkpoint when given a directory.  Returns the restored iteration."""
    path = path_or_dir
    if os.path.isdir(path_or_dir):
        latest = latest_checkpoint(path_or_dir)
        if latest is None:
            raise FileNotFoundError(
                f"no checkpoint (ckpt_iter_*.pkl) found in {path_or_dir!r}"
            )
        path = latest
    with open(path, "rb") as f:
        state = pickle.load(f)
    booster._restore_checkpoint_state(state)
    ses = get_session()
    ses.record(
        {"event": "checkpoint_restore", "iter": state["iter"], "path": path},
        defer=True,
    )
    log_info(f"[resilience] resumed from {path} at iteration {state['iter']}")
    return int(state["iter"])
