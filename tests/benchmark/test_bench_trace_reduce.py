"""The trace reduction: on built events, and on the recorded traces of this
benchmark's first chip runs (one chip and four chips, trimmed)."""

import os

import pytest

from benchmark import readers, trace_reduce as tr

B, E = tr.BEGIN_MARK, tr.END_MARK
MS = 1e6  # ns


def dev(events):
    return [(n, s * MS, d * MS, sc) for n, s, d, sc in events]


def host(window_ms=100.0, spans=()):
    return [(B, 0.0, 0.0)] + [(n, s * MS, d * MS) for n, s, d in spans] + [
        (E, window_ms * MS, 0.0)]


def four_devices():
    """Four devices each busy 60..90 of 100 ms: summed 300 ms, a number no
    window of 100 ms can hold."""
    planes = {}
    for i, busy in enumerate((60.0, 90.0, 70.0, 80.0)):
        planes[f"/device:TPU:{i}"] = dev([
            ("while.3", 0.0, 100.0, ""),  # container: holds the others
            ("fusion.1", 5.0, busy / 2, ""),
            ("seg_hist_pallas_batch.7", 5.0 + busy / 2, busy / 2, "mosaic f32[1,9,8,2048]"),
        ])
    return planes


def test_busy_is_averaged_over_devices_never_a_sum():
    t = tr.build(four_devices(), host(), ["/device:TPU:0"], n_devices=4)
    assert t.window_s == pytest.approx(0.1)
    assert t.busiest == "/device:TPU:1"
    assert t.busy_s == pytest.approx(0.075)  # the mean of 60, 90, 70, 80 ms
    assert 0 < t.busy_s <= t.window_s
    assert sum(t.busy_by_device().values()) == pytest.approx(0.3)  # the refused number


def test_containers_are_not_operations():
    t = tr.build({"/device:TPU:0": dev([
        ("while.1", 0.0, 100.0, ""), ("fusion.2", 10.0, 20.0, ""),
        ("conditional.4", 40.0, 30.0, ""), ("copy.5", 45.0, 5.0, ""),
    ])}, host(), [], n_devices=1)
    assert sorted(o.name for o in t.ops()) == ["copy.5", "fusion.2"]
    assert t.busy_s == pytest.approx(0.025)


def test_no_device_plane_is_an_error_that_names_the_planes():
    with pytest.raises(tr.TraceError, match="/host:CPU"):
        tr.build({}, host(), ["/host:CPU", "/host:metadata"], n_devices=1)
    t = tr.build({}, host(), ["/host:CPU"], n_devices=1, allow_no_device=True)
    assert t.busy_s is None and t.breakdown()["device_ops"] == []
    with pytest.raises(tr.TraceError, match="2 device planes"):
        two = dict(list(four_devices().items())[:2])
        tr.build(two, host(), list(two), n_devices=4)
    with pytest.raises(tr.TraceError, match="marks"):
        tr.build(four_devices(), [], [], n_devices=4)


def test_events_are_cut_to_the_window():
    t = tr.build({"/device:TPU:0": dev([("fusion.1", -10.0, 30.0, ""),
                                        ("fusion.2", 90.0, 30.0, "")])},
                 host(), [], n_devices=1)
    assert t.busy_s == pytest.approx(0.030)
    assert all(0 <= o.start and o.start + o.dur <= t.window_s + 1e-12 for o in t.ops())


def test_names_gaps_and_labels():
    text = ('%seg_hist_pallas_batch.16 = f32[2,9,8,2048]{3,2,1,0:T(8,128)S(1)} custom-call(s32[2,2]{1,0} '
            '%pad_add_fusion.12), custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert tr.parse_hlo(text) == ("seg_hist_pallas_batch.16", "mosaic f32[2,9,8,2048]")
    assert tr.parse_hlo("%fusion.37 = s32[34,8000000]{1,0:T(8,128)} fusion(s32[34] %x), kind=kLoop") == ("fusion.37", "")
    assert tr.parse_hlo("%while.3 = (s32[], f32[8]) while((s32[], f32[8]) %t)")[0] == "while.3"
    assert tr.kind_of("copy-done.458") == "copy-done" and tr.kind_of("fusion") == "fusion"
    t = tr.build({"/device:TPU:0": dev([("fused_grow_step_pallas.1", 0.0, 40.0, "mosaic s16[64,8001536]"),
                                        ("fusion.9", 70.0, 30.0, "")])},
                 host(spans=[("bench/boundary", 45.0, 20.0), ("train/run", 0.0, 100.0)]),
                 [], {"/device:TPU:0": dev([("jit_grow_tree", 0.0, 45.0, ""),
                                            ("jit__add_tree_to_score_impl", 70.0, 30.0, "")])},
                 n_devices=1)
    bd = t.breakdown()
    assert bd["idle_gaps"][0][0] == "host: bench/boundary"
    assert bd["idle_gaps"][0][1] == pytest.approx(0.030)
    assert bd["device_ops"][0] == ["fused_grow_step_pallas [mosaic s16[64,8001536]]",
                                   pytest.approx(0.040)]
    facts = {"trace": t, "trace_mark": [0.0, 3, 1.0, 5]}
    fused = {"mosaic": True, "names": "^fused_grow_step"}
    assert readers.ops_ms_per_iter(facts, fused) == pytest.approx(20.0)
    assert readers.ops_ms_per_iter(facts, {"mosaic": True, "names": "^seg_hist"}) is None
    assert readers.ops_ms_per_iter(facts, {"mosaic": False}) == pytest.approx(15.0)
    assert readers.program_ms_per_iter(facts, {"names": "grow"}) == pytest.approx(22.5)
    assert readers.idle_share(facts, {}) == pytest.approx(30.0)


def test_many_short_gaps_are_one_entry():
    evs = [("fusion.%d" % i, i * 0.1, 0.05, "") for i in range(1000)]
    t = tr.build({"/device:TPU:0": dev(evs)}, host(), [], n_devices=1)
    bd = t.breakdown()
    assert len(bd["idle_gaps"]) <= 10
    assert sum(v for _, v in bd["idle_gaps"]) == pytest.approx(t.window_s - t.busy_s)


def test_collectives_and_their_exposed_part():
    t = tr.build({"/device:TPU:0": dev([("fusion.1", 0.0, 50.0, ""),
                                        ("all-reduce.3", 40.0, 30.0, "")])},
                 host(), [], n_devices=1)
    facts = {"trace": t, "trace_mark": [0.0, 0, 1.0, 2]}
    assert readers.collective_ms_per_iter(facts, {}) == pytest.approx(15.0)
    assert readers.collective_ms_per_iter(facts, {"exposed": True}) == pytest.approx(10.0)
    t2 = tr.build({"/device:TPU:0": dev([("fusion.1", 0.0, 50.0, "")])}, host(), [],
                  n_devices=1)
    assert readers.collective_ms_per_iter({"trace": t2, "trace_mark": [0, 0, 1, 2]}, {}) is None


def test_recorded_round_trip(tmp_path):
    raw = (four_devices(), host(spans=[("bench/boundary", 95.0, 4.0)]), ["/device:TPU:0"],
           {"/device:TPU:0": dev([("jit_grow_tree", 1.0, 20.0, "")])})
    path = str(tmp_path / "rec.json.gz")
    tr.save_recorded(raw, path, max_ops=2)
    t = tr.load_recorded(path, n_devices=4)
    assert len(t.devices) == 4 and 0 < t.busy_s <= t.window_s


RECORDED = os.path.join(os.path.dirname(tr.__file__), "traces")


@pytest.mark.parametrize("name,n_devices", [("one_chip", 1), ("four_chip", 4)])
def test_recorded_chip_trace(name, n_devices):
    """Trimmed traces of this PR's own chip runs: the four-device case that
    PR 23 failed on is pinned without a chip."""
    path = os.path.join(RECORDED, name + ".json.gz")
    if not os.path.exists(path):
        pytest.skip(f"{path} was not recorded (PERF.md says why)")
    t = tr.load_recorded(path, n_devices=n_devices)
    assert len(t.devices) == n_devices
    assert 0 < t.busy_s <= t.window_s
    busy = t.busy_by_device()
    assert min(busy.values()) <= t.busy_s <= max(busy.values())
    bd = t.breakdown()
    assert 1 <= len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert any(o.mosaic for o in t.ops()), "no Pallas kernel among the operations"
    assert t.programs(), "no program (XLA module) in the window"


def test_rooflines_and_mfu_by_hand():
    """One traced iteration of the hand-built tree of the work-model test
    (1000 rows: 1450 histogrammed, 2000 partitioned), 28 features."""
    from benchmark import contract, work_model
    from tests.benchmark.test_bench_work_model import TREE

    t = tr.build({"/device:TPU:0": dev([
        ("seg_hist_pallas_batch.1", 0.0, 2.0, "mosaic f32[1,4,8,2048]"),
        ("seg_partition_pallas.2", 2.0, 1.0, "mosaic s16[32,1024]"),
        ("fused_grow_step_pallas.3", 3.0, 4.0, "mosaic s16[32,1024]"),
        ("fusion.4", 7.0, 1.0, ""),
    ])}, host(window_ms=10.0), [], n_devices=1)
    facts = {"trace": t, "trace_mark": [0.0, 0, 1.0, 1], "tree_dumps": [TREE],
             "device_kind": "TPU v5 lite", "chips": 1, "features": 28, "rows": 1000}
    m = contract.Manifest()
    peaks = work_model.peaks_for("TPU v5 lite")
    hist_least = 2 * 8 * 1450 * 28 * 256 / peaks["int8_ops_per_s"]
    part_least = 2000 * 32 * 2 * 2 / peaks["hbm_bytes_per_s"]
    got = readers.read_metric(m, "seg_hist_roofline", facts)
    assert got == pytest.approx(100 * hist_least / 2e-3)
    got = readers.read_metric(m, "seg_partition_roofline", facts)
    assert got == pytest.approx(100 * part_least / 1e-3)
    # the fused kernel: the children's MACs, or their rows' and the
    # partition's bytes, whichever takes the chip longer
    child_ops = 2 * 8 * 450 * 28 * 256 / peaks["int8_ops_per_s"]
    fused_bytes = (450 * (14 + 5) * 2 + 2000 * 32 * 2 * 2) / peaks["hbm_bytes_per_s"]
    got = readers.read_metric(m, "fused_grow_step_roofline", facts)
    assert got == pytest.approx(100 * max(child_ops, fused_bytes) / 4e-3)
    # all kernels together: 7 ms of Pallas time
    got = readers.read_metric(m, "grow_kernels_roofline", facts)
    assert 0 < got < 100
    ops, byts = work_model.step_work(1450, 2000, 28, 1000)
    mfu = readers.read_metric(m, "train_step_mfu", facts)
    assert mfu == pytest.approx(100 * (byts / peaks["hbm_bytes_per_s"]) / 10e-3)
    assert readers.read_metric(m, "xla_glue_ms_per_iter", facts) == pytest.approx(1.0)
    assert readers.read_metric(m, "histogram_ms_per_iter", facts) == pytest.approx(2.0)
    assert readers.read_metric(m, "partition_ms_per_iter", facts) == pytest.approx(1.0)
    assert readers.read_metric(m, "fused_step_ms_per_iter", facts) == pytest.approx(4.0)
    facts["marks"] = [[1.0, 3, 1.5], [4.0, 4, 9.0], [11.0, 5, 11.0]]
    facts["t0"] = 1.0
    assert readers.read_metric(m, "iter_wall_p50_ms", facts) == pytest.approx(2250.0)
