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


def test_named_scopes_survive_the_tpu_compiler(topo):
    """``obs.op_scopes()`` rests on this: the optimized HLO of a v5e
    executable still carries ``jax.named_scope`` paths in its instructions'
    ``op_name``, through a ``while`` body, and a fusion's scope can be read
    from the computation it calls."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.obs.jit import parse_op_scopes

    def step(x):
        with jax.named_scope("split_scan"):
            best = jnp.argmax(jnp.cumsum(x, axis=0) * x, axis=1)

        def body(c, t):
            with jax.named_scope("leaf_loop"):
                with jax.named_scope("bookkeeping"):
                    c = c.at[t].set(c[t] * 2 + 1)
                with jax.named_scope("candidate_refresh"):
                    c = jnp.sort(c) + jnp.where(c > 0, c, -c)
            return c, c.sum()

        c, s = jax.lax.scan(body, x[:, 0], jnp.arange(8))
        return best, c, s

    compiled = aot_check.compile_on_topo(topo, step, aot_check.s((256, 128), jnp.float32))
    module, scopes = parse_op_scopes(compiled.as_text())
    assert module.startswith("jit_")
    paths = set(scopes.values())
    assert {"split_scan", "leaf_loop/bookkeeping", "leaf_loop/candidate_refresh"} <= paths
    assert any(name.startswith("fusion") or name.endswith("fusion") or "fusion." in name
               for name, path in scopes.items() if path)


def test_the_row_gather_gate_sees_the_gathers_it_replaced(topo):
    """``row_gather_faults`` on the parent's forms, compiled for the chip:
    ``leaf_value[leaf_id]`` and the walker both show gathers with the
    table's rows, whole-program and under the scope the launch scan uses."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.predict import _walk_tree_values

    rows, leaves, features = 100_000, 255, 28
    s = aot_check.s

    def lookup(score, leaf_value, leaf_id):
        with jax.named_scope("score_update"):
            return score + leaf_value[leaf_id]

    text = aot_check.compile_on_topo(
        topo, lookup, s((rows,), jnp.float32), s((leaves,), jnp.float32),
        s((rows,), jnp.int32),
    ).as_text()
    assert aot_check.row_gather_faults(text, rows)
    assert aot_check.row_gather_faults(text, rows, scope="score_update")
    assert not aot_check.row_gather_faults(text, rows, scope="histogram")
    assert not aot_check.row_gather_faults(text, rows + 1)
    walk = aot_check.compile_on_topo(
        topo, _walk_tree_values, s((rows, features), jnp.uint8),
        s((features,), jnp.int32), *aot_check._numeric_tree(leaves),
    ).as_text()
    assert aot_check.row_gather_faults(walk, rows)
