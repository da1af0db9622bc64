"""The set-up's timeline from the program's span ring (PR 37).

``setup_s`` runs from the process's start to the window's first boundary.
The program names what it does in between as spans of its ``TraceRecorder``
(``lightgbm_tpu.obs.get_tracer()``), on ``time.perf_counter``'s clock, which
is the harness's own (``run.T0``, ``facts["t0"]``): ``setup/import``,
``dataset/*``, ``setup/booster_init`` with its children (``setup/transfer``,
``setup/objective_init``, ``setup/add_valid``), and one ``compile/<label>``
a program that traced or compiled, with its seconds by stage.  The readers
under ``layers/`` that move ``setup_s`` share this module.

What a reader returns (the rule ``layers/bag_compact_ms_per_iter.py`` set):
``None`` where the source is absent (no ring, or no ``setup/booster_init`` of
this ``lgb.train`` in it: a program without spans); an error where the ring
dropped spans and nothing older than this call's set-up survives (its spans
may be among the dropped); a measured 0.0 where the ring holds this call's
set-up and no span under the reader's name, which is what a program older
than PR 37 gives for every name but ``setup/booster_init``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from benchmark import trace_reduce

BOOSTER_INIT = "setup/booster_init"
IMPORT = "setup/import"
TRANSFER = "setup/transfer"
RUN = "train/run"
COMPILE = "compile/"


class RingError(RuntimeError):
    """The ring dropped spans that may have been this call's set-up."""


def _ring(facts) -> Optional[Tuple[List[Dict[str, Any]], int]]:
    """(spans, dropped_total) of the program's ring, or None without one."""
    if "spans" in facts:  # a test's, or a recorded ring
        return list(facts["spans"]), int(facts.get("spans_dropped", 0))
    try:
        from lightgbm_tpu.obs import get_tracer
    except ImportError:
        return None
    tracer = get_tracer()
    return tracer.spans(), int(tracer.stats()["dropped_total"])


def setup_spans(facts) -> Optional[Dict[str, Any]]:
    """The spans of this call's set-up: those that end at or before the
    window's first boundary (``facts["t0"]``) and start after the
    ``train/run`` before this one, if the process made an earlier
    ``lgb.train`` call.  ``{"spans": [...], "init": the setup/booster_init
    span, "import": the process's setup/import span or None}``; None where
    the source is absent."""
    if "_setup_spans" not in facts:  # one pass for the readers of a run
        facts["_setup_spans"] = _setup_spans(facts)
    return facts["_setup_spans"]


def _setup_spans(facts) -> Optional[Dict[str, Any]]:
    ring = _ring(facts)
    if ring is None or facts.get("t0") is None:
        return None
    spans, dropped = ring
    t0_us = float(facts["t0"]) * 1e6
    done = [s for s in spans if s.get("dur") is not None and s["ts"] + s["dur"] <= t0_us]
    inits = [s for s in done if s["name"] == BOOSTER_INIT]
    if not inits:
        return None
    init = max(inits, key=lambda s: s["ts"])
    earlier = [s["ts"] + s["dur"] for s in done if s["name"] == RUN]
    since = max(earlier, default=float("-inf"))
    # the ring drops its oldest first: with a span older than this call's
    # set-up still in it, nothing of the set-up is among the dropped
    if dropped and not earlier:
        raise RingError(
            f"the span ring dropped {dropped} spans and holds nothing older than "
            "this call's set-up: its spans may be among them (trace_capacity)")
    imports = [s for s in spans if s["name"] == IMPORT]
    return {
        "spans": [s for s in done if s["ts"] >= since],
        "init": init,
        "import": imports[-1] if imports else None,
    }


def span_seconds(facts, name: str) -> Optional[float]:
    """Sum of the set-up spans called ``name``; 0.0 where there is none."""
    got = setup_spans(facts)
    if got is None:
        return None
    return sum(s["dur"] for s in got["spans"] if s["name"] == name) * 1e-6


def compile_seconds(facts, *keys: str) -> Optional[float]:
    """Sum of the args ``keys`` over the ``compile/*`` spans of the set-up
    (``trace_s``, ``lower_s``, ``backend_compile_s``, ``cache_retrieval_s``)."""
    got = setup_spans(facts)
    if got is None:
        return None
    return float(sum(
        float(s.get("args", {}).get(k) or 0.0)
        for s in got["spans"] if s["name"].startswith(COMPILE) for k in keys
    ))


def import_seconds(facts) -> Optional[float]:
    """The package's import, paid once a process."""
    got = setup_spans(facts)
    if got is None:
        return None
    return got["import"]["dur"] * 1e-6 if got["import"] is not None else 0.0


def init_self_seconds(facts) -> Optional[float]:
    """``setup/booster_init`` less the union of the spans inside it
    (``compile/*`` included): the part of it that still has no name."""
    got = setup_spans(facts)
    if got is None:
        return None
    init = got["init"]
    a, b = init["ts"], init["ts"] + init["dur"]
    inside = [(max(a, s["ts"]), min(b, s["ts"] + s["dur"])) for s in got["spans"]
              if s is not init and s["ts"] < b and s["ts"] + s["dur"] > a]
    return (init["dur"] - trace_reduce.union_seconds(inside)) * 1e-6
