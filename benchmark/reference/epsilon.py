"""Plain reference of the ``epsilon`` configuration (Epsilon 400,000 x 2,000, dense):
binary log loss, numerical features on the data recipe's grid, leaf-wise trees to
``num_leaves``.  Found by the configuration's name and called by
``benchmark/run.py``; the arithmetic is ``gbdt.py``'s (float64 NumPy)."""

from benchmark.reference.gbdt import follow_model  # noqa: F401
