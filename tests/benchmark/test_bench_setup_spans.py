"""The readers of the set-up's timeline (``benchmark/setup_spans.py`` and the
five files under ``benchmark/layers/`` that PR 37 lists: ``program_import_s``,
``trace_lower_s``, ``cache_retrieval_s``, ``device_transfer_s``,
``booster_init_self_s``).

The driver runs the PARENT's program under these files, and a traced line
that lacks a listed metric is refused: so the rule pinned here is None where
the source is absent (no ring, no ``setup/booster_init`` of this call), an
error where the ring dropped what may have been the set-up, and a measured
0.0 where the ring holds the set-up and nothing under the reader's name.
"""

import json
import os

import pytest

from benchmark import contract, setup_spans

METRICS = ("program_import_s", "trace_lower_s", "cache_retrieval_s",
           "device_transfer_s", "booster_init_self_s")
LAYERS = {"program_import_s": "import", "trace_lower_s": "compilation",
          "cache_retrieval_s": "compilation",
          "device_transfer_s": "booster construction: parts",
          "booster_init_self_s": "booster construction: parts"}
S = 1_000_000  # the ring's clock: microseconds of time.perf_counter


def read(metric, facts):
    mod = contract.load_module(os.path.join(contract.BENCH_DIR, "layers", metric + ".py"),
                               "benchmark_layer_" + metric)
    return mod.read(dict(facts))


def span(name, t0_s, dur_s, **args):
    cat = "compile" if name.startswith("compile/") else name.split("/")[0]
    return {"name": name, "cat": cat, "ts": int(t0_s * S), "dur": int(dur_s * S),
            "args": args}


def parent_ring():
    """What a program older than PR 37 leaves: the dataset's spans, a
    ``setup/booster_init`` without children, the run's own spans."""
    return [
        span("dataset/bin_fit", 14.0, 3.0), span("dataset/pack", 17.0, 2.5),
        span("dataset/construct", 14.0, 6.0),
        span("setup/booster_init", 20.5, 4.25),
        span("train/iteration", 24.8, 7.0, iter=0),
        span("train/iteration", 31.9, 0.6, iter=1),
        span("train/iteration", 32.6, 0.6, iter=2),  # ends after the boundary
    ]


def change_ring():
    compiled = dict(trace_s=0.5, lower_s=0.25, backend_compile_s=1.0,
                    cache_retrieval_s=0.75, cache_hit=True)
    return [
        span("setup/import", 9.0, 2.75, jax_preloaded=True),
        span("dataset/construct", 14.0, 6.0),
        span("setup/transfer", 20.6, 0.5, what="objective.label", bytes=42_000_000),
        span("compile/uninstrumented", 21.1, 0.25, trace_s=0.01, lower_s=0.04,
             backend_compile_s=0.2, cache_retrieval_s=0.15, cache_hit=True),
        span("setup/objective_init", 20.55, 1.0),  # holds the two above
        span("setup/transfer", 21.6, 0.75, what="bins", bytes=294_000_000),
        span("setup/booster_init", 20.5, 4.25),
        span("compile/grow/step", 25.0, 3.0, **compiled),
        span("train/iteration", 24.8, 7.0, iter=0, first=True),
        # after the window opened: no part of the set-up
        span("compile/late", 33.0, 1.0, **compiled),
    ]


T0 = 32.5  # facts["t0"]: the window's first boundary, seconds on the same clock


def test_a_parent_shaped_ring_reads_four_zeros_and_the_whole_span():
    facts = {"spans": parent_ring(), "t0": T0}
    for metric in METRICS[:4]:
        assert read(metric, facts) == 0.0, metric
    assert read("booster_init_self_s", facts) == pytest.approx(4.25)


def test_the_changes_ring_reads_each_span_under_its_name():
    facts = {"spans": change_ring(), "t0": T0}
    assert read("program_import_s", facts) == pytest.approx(2.75)
    # both compile spans before the boundary; the one after it is left out
    assert read("trace_lower_s", facts) == pytest.approx(0.01 + 0.04 + 0.5 + 0.25)
    assert read("cache_retrieval_s", facts) == pytest.approx(0.15 + 0.75)
    assert read("device_transfer_s", facts) == pytest.approx(0.5 + 0.75)
    # 4.25 less the union of objective_init (1.0, holding a transfer and a
    # compile span) and the bins' transfer (0.75)
    assert read("booster_init_self_s", facts) == pytest.approx(4.25 - 1.0 - 0.75)


@pytest.mark.parametrize("facts", [
    {"spans": [], "t0": T0},  # a program without spans
    {"spans": [span("dataset/construct", 14.0, 6.0)], "t0": T0},  # no booster_init
    {"spans": parent_ring(), "t0": 22.0},  # booster_init ends after the boundary given
    {"spans": parent_ring()},  # an untraced run's facts have no boundary
])
def test_an_absent_source_reads_none(facts):
    for metric in METRICS:
        assert read(metric, facts) is None, metric


def test_an_earlier_train_call_of_the_process_is_not_this_calls_set_up():
    earlier = [
        span("setup/import", 1.0, 2.0), span("setup/transfer", 4.0, 0.5),
        span("setup/booster_init", 3.9, 1.0),
        span("compile/grow/step", 5.0, 2.0, trace_s=1.0, lower_s=0.5,
             backend_compile_s=0.4, cache_retrieval_s=0.0),
        span("train/run", 4.9, 4.0),
    ]
    facts = {"spans": earlier + change_ring()[1:], "t0": T0}
    assert read("trace_lower_s", facts) == pytest.approx(0.8)  # this call's alone
    assert read("device_transfer_s", facts) == pytest.approx(1.25)
    assert read("program_import_s", facts) == pytest.approx(2.0)  # paid once a process


def test_a_ring_that_dropped_spans_is_an_error_unless_older_spans_survive():
    facts = {"spans": change_ring(), "t0": T0, "spans_dropped": 7}
    for metric in METRICS:
        with pytest.raises(setup_spans.RingError):
            read(metric, facts)
    # a span older than this call's set-up is still there: first in, first
    # out, so nothing of the set-up is among the dropped
    survived = [span("train/run", 2.0, 5.0)] + change_ring()
    facts = {"spans": survived, "t0": T0, "spans_dropped": 7}
    assert read("device_transfer_s", facts) == pytest.approx(1.25)


def test_the_manifest_lists_each_with_its_reader_on_disk():
    manifest = contract.Manifest()
    listed = {m["name"]: m for m in manifest.doc["per_layer"]}
    cells = manifest.workload_names()
    assert [m["name"] for m in manifest.doc["per_layer"]][-5:] == list(METRICS)
    for metric in METRICS:
        m = listed[metric]
        assert m == {"name": metric, "unit": "s", "better": "lower",
                     "source": "program_span", "layer": LAYERS[metric],
                     "moves": "setup_s", "workloads": cells}
        assert manifest.layer_reader_path(metric).endswith(metric + ".py")
    for cell in cells:
        assert set(METRICS) <= {m["name"] for m in manifest.cell(cell).per_layer}
    with open(os.path.join(contract.ROOT, "BENCHMARK.json")) as fh:
        assert len(fh.read()) < 64 * 1024


def test_a_rehearsal_reads_all_five_from_the_programs_own_ring(capsys, monkeypatch):
    """One traced rehearsal in this process: the line carries the five, and
    the ring they were read from agrees with the harness's own clocks."""
    from benchmark import run
    from lightgbm_tpu.obs import get_tracer

    tracer = get_tracer()
    tracer.configure(active=True)
    tracer.reset()
    kept = {}
    reduce = run._traced_metrics

    def traced_metrics(cell, manifest, facts, *rest):
        kept.update(facts)
        return reduce(cell, manifest, facts, *rest)

    monkeypatch.setattr(run, "_traced_metrics", traced_metrics)
    rc = run.main(["--workload", "higgs.fit", "--seed", "2147483999", "--seconds", "0.5",
                   "--trace", "1", "--rehearse"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    got = {m: line["metrics"][m] for m in METRICS}
    assert all(v["unit"] == "s" and v["value"] >= 0 for v in got.values()), got
    assert got["trace_lower_s"]["value"] > 0 and got["device_transfer_s"]["value"] > 0
    setup = setup_spans.setup_spans({"t0": kept["t0"]})
    assert got["booster_init_self_s"]["value"] < setup["init"]["dur"] / S
    # every span of the set-up ends before the window opens, on the harness's clock
    assert all(s["ts"] + s["dur"] <= kept["t0"] * S for s in setup["spans"])
    # what the harness's CompileClock heard before the window is the sum of
    # backend_compile_s over the compile/* spans of the set-up
    compiled = [s for s in setup["spans"] if s["cat"] == "compile"]
    assert sum(s["args"]["backend_compile_s"] for s in compiled) == pytest.approx(
        kept["compile_s"], rel=0.02)
    assert {s["name"] for s in setup["spans"]} >= {
        "dataset/construct", "setup/objective_init", "setup/transfer"}
