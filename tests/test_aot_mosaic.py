"""Deviceless Mosaic compile checks: every default-path Pallas kernel, in
the forms the grower calls them (vmapped, K-batched), and the whole grow
programs the Booster dispatches on a TPU.

Rounds 3-4 shipped TPU-gated kernels the Mosaic compiler had never seen and
its first contact found four distinct lowering rejections (value
dynamic_slice, f32 tpu.iota, i1 relayout/select, i1-result scf.if); PR 22
found three more in forms the registry did not cover (vmapped SMEM
operands, two (1, bmt) blocks of a (K, bmt) table).  libtpu's compiler runs
fine WITHOUT hardware via a topology descriptor, so every entry must
AOT-compile against a v5e topology in plain CPU CI.

The registry lives in tools/aot_check.py (also runnable standalone for
debugging: ``python tools/aot_check.py [filter]``).
"""

import importlib.util
import os

import pytest

pytestmark = pytest.mark.slow  # ~5 s/kernel, ~35 s/whole program

_SPEC = importlib.util.spec_from_file_location(
    "aot_check",
    os.path.join(os.path.dirname(__file__), "..", "tools", "aot_check.py"),
)
aot_check = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(aot_check)


@pytest.fixture(scope="module")
def topo():
    try:
        return aot_check._topo()
    except Exception as e:  # no local libtpu — nothing to check against
        pytest.skip(f"no deviceless TPU topology available: {e}")


@pytest.mark.parametrize("name", sorted(aot_check.CHECKS))
def test_kernel_mosaic_compiles(topo, name):
    compiled = aot_check.CHECKS[name](topo)
    assert compiled is not None
