"""The reference's passes over every row on worker processes and threads
(``benchmark/reference/rows.py``) against the serial ones, on the program's
own trees at a small size: every number ``correct`` is decided by must be the
same to the last bit, with one worker and with several, for the sound path
and the control."""

import json

import numpy as np
import pytest

from benchmark import contract, data as bdata
from benchmark.reference import gbdt, rows

ROWS = 30_000
SEED = 2_147_483_923


@pytest.fixture(scope="module")
def model():
    import lightgbm_tpu as lgb

    cfg = json.load(open(contract.Manifest().path("configs", "criteo67.json")))
    params = dict(cfg["params"], min_sum_hessian_in_leaf=20, num_leaves=63)
    blocks, y = bdata.make_blocks(SEED, ROWS, int(cfg["features"]), recipe=cfg["data"])
    vblocks, vy = bdata.make_blocks(SEED, 2_000, int(cfg["features"]), valid=True,
                                    recipe=cfg["data"])
    booster = lgb.train(dict(params, verbosity=-1), lgb.Dataset(blocks, y, params=dict(params)), 3)
    dumps = [t["tree_structure"] for t in booster.dump_model()["tree_info"]]
    return dict(cfg=cfg, params=params, blocks=blocks, y=y, vblocks=vblocks, vy=vy,
                dumps=dumps)


@pytest.fixture
def tall(monkeypatch):
    """Sums on the workers whatever the rows per bin: the tables here are
    small, and the rule would keep them serial."""
    monkeypatch.setattr(rows, "ROWS_PER_BIN", 0)


def _follow(m, pool, control=None):
    cols, values = gbdt.levels_of(m["blocks"], m["cfg"]["data"], pool)
    trees = [gbdt.tree_from_dump(t) for t in m["dumps"]]
    detail = []
    nums = gbdt.follow(trees, m["blocks"], m["y"], cols, values, m["params"],
                       valid_blocks=m["vblocks"], valid_y=m["vy"], valid_metric=[0.6] * 3,
                       control=control, detail=detail, pool=pool)
    return nums, detail, cols


def _bits(d):
    return {k: float(v).hex() for k, v in d.items()}


@pytest.mark.parametrize("control", [None, "bfloat16"])
@pytest.mark.parametrize("processes", [1, 3])
def test_the_pool_gives_the_serial_numbers_to_the_last_bit(model, tall, processes, control):
    serial, serial_detail, serial_cols = _follow(model, None, control)
    with rows.RowPool(processes=processes, threads=processes + 1) as pool:
        pooled, pooled_detail, pooled_cols = _follow(model, pool, control)
    assert _bits(pooled) == _bits(serial)
    assert [_bits(d) for d in pooled_detail] == [_bits(d) for d in serial_detail]
    assert all(np.array_equal(a, b) for a, b in zip(pooled_cols, serial_cols))
    assert serial["leaf_value_rms_gap"] > 0  # a reading, not an empty comparison


def test_leaf_sums_and_walk_on_a_subset_and_after_the_columns_change(model, tall):
    """A row subset (the GOSS reference's in-bag columns) is uploaded anew,
    and the full columns again after it; the walk over blocks is the same."""
    cols, values = gbdt.levels_of(model["blocks"], model["cfg"]["data"])
    tree = gbdt.tree_from_dump(model["dumps"][1])
    leaf = gbdt.walk(tree, model["blocks"])
    rng = np.random.default_rng(3)
    g, h = rng.normal(size=ROWS), rng.random(ROWS)
    sub = np.flatnonzero(rng.random(ROWS) < 0.3)
    want_full = gbdt.leaf_sums(cols, leaf, g, h, tree.n_leaves, len(values))
    want_sub = gbdt.leaf_sums([c[sub] for c in cols], leaf[sub], g[sub], h[sub],
                              tree.n_leaves, len(values), counts=False)
    with rows.RowPool(processes=2) as pool:
        assert np.array_equal(gbdt.walk(tree, model["blocks"], pool), leaf)
        for want, args, counts in ((want_full, (cols, leaf, g, h), True),
                                   (want_sub, ([c[sub] for c in cols], leaf[sub], g[sub], h[sub]),
                                    False),
                                   (want_full, (cols, leaf, g, h), True)):
            got = gbdt.leaf_sums(*args, tree.n_leaves, len(values), counts=counts, pool=pool)
            assert got[2] is None if not counts else np.array_equal(got[2], want[2])
            assert np.array_equal(got[0], want[0]) and np.array_equal(got[1], want[1])


def test_follow_model_takes_the_pool_above_the_size_rule(model, tall, monkeypatch):
    """``follow_model`` sizes the pool from the host and the table: past
    ``POOL_MIN_CELLS`` it is used, and the numbers do not move."""
    kw = dict(blocks=model["blocks"], y=model["y"], params=model["params"],
              recipe=model["cfg"]["data"])
    serial = gbdt.follow_model(model["dumps"], **kw)
    made = []
    real = rows.RowPool.for_table.__func__

    def spy(cls, n, f):
        made.append(real(cls, n, f))
        return made[-1]

    monkeypatch.setattr(rows, "POOL_MIN_CELLS", 0)
    monkeypatch.setattr(rows, "host_cores", lambda: 2)
    monkeypatch.setattr(rows.RowPool, "for_table", classmethod(spy))
    pooled = gbdt.follow_model(model["dumps"], **kw)
    assert len(made) == 1 and made[0] is not None and made[0].processes == 2
    assert made[0]._workers == [] and made[0]._inputs == {}  # closed on the way out
    assert _bits(pooled) == _bits(serial)


def test_the_goss_reference_gives_its_serial_numbers_with_the_pool(tall, monkeypatch):
    """``criteo67-goss.py`` walks and sums on the pool too (judged trees on
    their in-bag columns, a new set of columns each): the same bits."""
    import lightgbm_tpu as lgb

    m = contract.Manifest()
    cfg = m.cell("criteo67-goss.fit-steady").config
    params = dict(cfg["params"], min_sum_hessian_in_leaf=20, num_leaves=31)
    blocks, y = bdata.make_blocks(SEED, 20_000, int(cfg["features"]), recipe=cfg["data"])
    booster = lgb.train(dict(params, verbosity=-1), lgb.Dataset(blocks, y, params=dict(params)), 12)
    dumps = [t["tree_structure"] for t in booster.dump_model()["tree_info"]]
    ref = contract.load_module(m.reference_path("criteo67-goss"), "benchmark_reference_goss_rows")
    kw = dict(blocks=blocks, y=y, params=params, recipe=cfg["data"])
    serial = [ref.follow_model(dumps, control=c, **kw) for c in (None, "bfloat16")]
    monkeypatch.setattr(rows, "POOL_MIN_CELLS", 0)
    monkeypatch.setattr(rows, "host_cores", lambda: 3)
    pooled = [ref.follow_model(dumps, control=c, **kw) for c in (None, "bfloat16")]
    assert [_bits(d) for d in pooled] == [_bits(d) for d in serial]
    assert serial[0]["count_mismatch"] == 0 and serial[1]["leaf_value_rms_gap"] > 0


@pytest.mark.parametrize("n", [1, 4095, 3 * (1 << 20) + 17])
def test_threaded_gradients_are_the_serial_ones_to_the_last_bit(n):
    """Elementwise, so a block a thread gives the same bits, whatever the
    blocks' ends (NumPy's vector loops and their tails)."""
    rng = np.random.default_rng(n)
    score, y = rng.normal(size=n) * 3, (rng.random(n) < 0.4).astype(np.float64)
    want = gbdt.gradients(score, y)
    with rows.RowPool(processes=1, threads=4) as pool:
        got = gbdt.gradients(score, y, pool)
    assert all(np.array_equal(a.view(np.int64), b.view(np.int64)) for a, b in zip(got, want))


def test_a_worker_s_error_is_raised_with_its_traceback(model):
    cols, values = gbdt.levels_of(model["blocks"], model["cfg"]["data"])
    leaf = np.zeros(ROWS, np.int32)
    g = h = np.ones(ROWS)
    with rows.RowPool(processes=2) as pool:
        with pytest.raises(RuntimeError, match="IndexError"):
            # a leaf id past n_leaves puts a key past the bins
            pool.leaf_sums(cols, leaf + 5, g, h, 3, len(values))
        # the pool still answers after it
        G, H, C = pool.leaf_sums(cols, leaf, g, h, 3, len(values))
        assert C[0].sum() == ROWS * len(cols) and G.sum() == H.sum() == ROWS * len(cols)


def test_wide_sums_stay_in_the_parent(model):
    """Rows fewer than sixteen a bin (a feature's sums would cost more to
    send back than to make): the pool's threads, and no worker process."""
    cols, values = gbdt.levels_of(model["blocks"], model["cfg"]["data"])
    tree = gbdt.tree_from_dump(model["dumps"][0])
    leaf = gbdt.walk(tree, model["blocks"])
    g = h = np.ones(ROWS)
    assert not rows.sums_worth_processes(ROWS, tree.n_leaves, len(values))
    with rows.RowPool(processes=2) as pool:
        got = gbdt.leaf_sums(cols, leaf, g, h, tree.n_leaves, len(values), pool=pool)
        assert pool._workers == []
    want = gbdt.leaf_sums(cols, leaf, g, h, tree.n_leaves, len(values))
    assert all(np.array_equal(a, b) for a, b in zip(got, want))
    assert rows.sums_worth_processes(8_000_000, 255, 241)  # the Criteo table's


def test_small_tables_and_one_core_stay_serial(monkeypatch):
    assert rows.RowPool.for_table(40_000, 67) is None
    monkeypatch.setattr(rows, "host_cores", lambda: 1)
    assert rows.RowPool.for_table(10**9, 67) is None
    monkeypatch.setattr(rows, "host_cores", lambda: 8)
    monkeypatch.setattr(rows, "shm_free_bytes", lambda: 64 << 20)  # a container's default
    assert rows.RowPool.for_table(8_000_000, 67) is None
    with pytest.raises(ValueError):
        rows.RowPool(processes=0)
