#!/usr/bin/env python3
"""The faults a training cell can have, planted underneath the timed path.

Not part of a benchmark run.  Two users: the tests under
``tests/benchmark/`` (small size, CPU), and the builder who reads each fault
on the chip at the cell's own size before setting a limit:

    python3 benchmark/faults.py <fault> --workload <name> --seed <n> --seconds <s> --trace 0

Every fault leaves ``benchmark/run.py`` as it is and breaks the PROGRAM it
drives; the run has to print ``correct: false``.
"""

from __future__ import annotations

import contextlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@contextlib.contextmanager
def _patched(obj, name, new):
    old = getattr(obj, name)
    setattr(obj, name, new)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def state_unchanged():
    """A step that returns its state unchanged: the score is put back after
    every update, so each tree is grown from the first one's gradients."""
    from lightgbm_tpu.boosting.gbdt import Booster

    real = Booster.update

    def stuck(self, *a, **k):
        before = self._score
        done = real(self, *a, **k)
        self._drain_pending()
        self._score = before
        return done

    with _patched(Booster, "update", stuck):
        yield


@contextlib.contextmanager
def half_rows():
    """Half of the batch left out, the mean taken over the rest: the program
    is handed the first half of the row blocks."""
    import numpy as np

    import lightgbm_tpu as lgb

    real = lgb.Dataset

    def half(data, label=None, **kw):
        if kw.get("reference") is None and isinstance(data, list) and len(data) > 1:
            keep = len(data) // 2
            rows = sum(b.shape[0] for b in data[:keep])
            return real(data[:keep], np.asarray(label)[:rows], **kw)
        return real(data, label, **kw)

    with _patched(lgb, "Dataset", half):
        yield


@contextlib.contextmanager
def answer_altered():
    """An answer altered where it is produced: one leaf of the second tree is
    0.01 off in the model the user gets (a sixth of a median leaf)."""
    from lightgbm_tpu.boosting.gbdt import Booster

    real = Booster.dump_model

    def altered(self, *a, **k):
        self.models_[1].leaf_value[2] += 0.01
        return real(self, *a, **k)

    with _patched(Booster, "dump_model", altered):
        yield


@contextlib.contextmanager
def exchange_left_out():
    """The exchange between chips left out: every psum of the grower returns
    its own shard's part."""
    from lightgbm_tpu.ops import grower

    with _patched(grower, "timed_psum", lambda x, axis_name, **kw: x):
        yield


FAULTS = {
    "state_unchanged": state_unchanged,
    "half_rows": half_rows,
    "answer_altered": answer_altered,
    "exchange_left_out": exchange_left_out,
}


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] not in FAULTS:
        print(f"usage: faults.py <{'|'.join(FAULTS)}> <run.py's arguments>",
              file=sys.stderr)
        return 2
    from benchmark import run

    with FAULTS[argv[0]]():
        return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main())
