"""Same-machine reference-CLI benchmark on bench.py's exact workload.

    python tools/ref_bench.py /path/to/lightgbm-cli [rows]

BASELINE.md's 3.8 iters/s was measured on a 16-core Xeon; this sandbox
has ONE core, so cross-machine comparison is meaningless.  This script
runs the REFERENCE on the identical synthetic workload bench.py uses
(same rng seed, shapes, params), on THIS machine, so a CPU-backend
number of this system has a denominator measured under the same
conditions.  Marginal-rep: wall(num_trees=N2) - wall(num_trees=N1)
over N2-N1 iterations cancels data loading/binning.
"""

import subprocess
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

CONF = """task = train
objective = binary
data = train.csv
label_column = 0
num_leaves = 255
max_bin = 255
learning_rate = 0.1
min_data_in_leaf = 100
metric = none
num_threads = {threads}
num_trees = {trees}
verbosity = -1
output_model = model.txt
"""


def run(cli, work, trees, threads):
    (work / "train.conf").write_text(CONF.format(trees=trees, threads=threads))
    t0 = time.perf_counter()
    p = subprocess.run(
        [cli, "config=train.conf"], cwd=work, capture_output=True, text=True
    )
    dt = time.perf_counter() - t0
    if p.returncode != 0:
        raise RuntimeError(p.stdout + p.stderr)
    return dt


def main(cli, rows=1_000_000):
    cli = str(Path(cli).resolve())
    from bench import _make_data  # identical data: same seed and shapes

    X, y = _make_data(rows, 28)
    with tempfile.TemporaryDirectory() as td:
        work = Path(td)
        arr = np.column_stack([y, X.astype(np.float64)])
        np.savetxt(work / "train.csv", arr, delimiter=",", fmt="%.7g")
        n1, n2, threads = 2, 12, 1
        t_small = run(cli, work, n1, threads)
        t_big = run(cli, work, n2, threads)
        per = (t_big - t_small) / (n2 - n1)
        print(
            f"reference CLI @{rows} rows, num_threads={threads}: "
            f"{1.0 / per:.4f} iters/s ({per * 1e3:.0f} ms/iter; "
            f"{n1}-tree run {t_small:.1f}s incl. load+bin)"
        )


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 1_000_000)
