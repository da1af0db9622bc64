"""The form a histogram travels in: three [F, B] planes (sum_grad, sum_hess,
count), stat axis first — [.., 3, F, B].  The NumPy oracles of these tests
build and read the record form [.., F, B, 3]; these two move the axis."""

import numpy as np


def planes(hist):
    """[.., F, B, 3] records -> [.., 3, F, B] planes."""
    return np.moveaxis(np.asarray(hist), -1, -3)


def records(hist):
    """[.., 3, F, B] planes -> [.., F, B, 3] records."""
    return np.moveaxis(np.asarray(hist), -3, -1)
