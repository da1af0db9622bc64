"""The benchmark's side of its contract with whoever runs it.

Two things live here and nowhere else:

* ``Manifest`` — ``BENCHMARK.json`` read once, with every file a cell needs
  found BY NAME under the benchmark's directory (``configs/<config>.json``,
  ``jobs/<traffic>.json``, ``layers/<metric>.json|.py``,
  ``reference/<config>.py``, and optionally ``limits/<cell>.json``).  A later PR adds a cell, a configuration or a
  per-layer metric by adding files and entries; nothing here names one.
* ``validate_line`` — the check every run passes its own last line through
  BEFORE printing it.  PR 23 was refused because a traced four-chip run
  printed a line the driver could not read (busy summed over devices); a
  run that cannot produce a conforming line now exits non-zero with the
  reason on stderr instead of printing a guess.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import re
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
_SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ContractError(ValueError):
    """The manifest, a file found by name, or a result line breaks the
    contract.  The message says which rule."""


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise ContractError(msg)


def _load_json(path: str) -> Any:
    try:
        with open(path, "r", encoding="utf-8") as fh:
            return json.load(fh)
    except FileNotFoundError as e:
        raise ContractError(f"file not found: {path}") from e
    except json.JSONDecodeError as e:
        raise ContractError(f"{path} is not JSON: {e}") from e


def load_module(path: str, name: str):
    """A file found by name (a reference, a layer reader) as a module."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with everything found for it."""

    name: str
    config_name: str
    traffic: str
    chips: int
    config: Dict[str, Any]  # configs/<config>.json
    job: Dict[str, Any]  # jobs/<traffic>.json
    end_to_end: List[Dict[str, Any]]  # metrics this cell reports, trace 0
    per_layer: List[Dict[str, Any]]  # metrics this cell may report, trace 1


class Manifest:
    """``BENCHMARK.json`` plus the by-name lookup of the benchmark's files."""

    def __init__(self, root: str = ROOT, bench_dir: Optional[str] = None) -> None:
        self.root = root
        self.bench_dir = bench_dir or os.path.join(root, "benchmark")
        self.doc = _load_json(os.path.join(root, "BENCHMARK.json"))
        for key in ("command", "paths", "run_seconds", "configs", "workloads",
                    "end_to_end", "per_layer"):
            _require(key in self.doc, f"BENCHMARK.json lacks {key!r}")
        names = [m["name"] for m in self.doc["end_to_end"] + self.doc["per_layer"]]
        _require(len(set(names)) == len(names), "two metrics share a name")
        for m in self.doc["end_to_end"] + self.doc["per_layer"]:
            _require(bool(_NAME.match(m["name"])), f"bad metric name {m['name']!r}")
            _require(bool(_UNIT.match(m["unit"])), f"bad unit {m['unit']!r}")
            _require(m["better"] in ("lower", "higher"), f"{m['name']}: better?")
            _require(m["source"] in _SOURCES, f"{m['name']}: source?")

    # ---- lookup by name
    def path(self, *parts: str) -> str:
        return os.path.join(self.bench_dir, *parts)

    def workload_names(self) -> List[str]:
        return [w["name"] for w in self.doc["workloads"]]

    def _metrics_for(self, kind: str, cell_name: str, reported: List[str]):
        out = []
        for m in self.doc[kind]:
            cells = m.get("workloads")
            if cells is not None:
                if cell_name in cells:
                    out.append(m)
            elif kind == "end_to_end" or m["moves"] in reported:
                out.append(m)
        return out

    def cell(self, name: str) -> Cell:
        entry = next((w for w in self.doc["workloads"] if w["name"] == name), None)
        _require(entry is not None,
                 f"no workload {name!r}; have {self.workload_names()}")
        cfg_entry = next(
            (c for c in self.doc["configs"] if c["name"] == entry["config"]), None
        )
        _require(cfg_entry is not None, f"{name}: no config {entry['config']!r}")
        config = _load_json(os.path.join(self.root, cfg_entry["file"]))
        job = _load_json(self.path("jobs", entry["traffic"] + ".json"))
        # a cell may bring limits of its own (a number its configuration's
        # file has none for, read at this cell's size): limits/<cell>.json
        own = self.path("limits", name + ".json")
        if os.path.exists(own):
            config = dict(config, limits=dict(config.get("limits", {}), **_load_json(own)))
        e2e = self._metrics_for("end_to_end", name, [])
        per_layer = self._metrics_for(
            "per_layer", name, [m["name"] for m in e2e]
        )
        return Cell(
            name=name, config_name=entry["config"], traffic=entry["traffic"],
            chips=int(entry["chips"]), config=config, job=job,
            end_to_end=e2e, per_layer=per_layer,
        )

    def layer_reader_path(self, metric: str) -> str:
        """``layers/<metric>.py`` (a reader of its own) or ``.json`` (the
        parameters of a stock reader in ``benchmark/readers.py``)."""
        for ext in (".py", ".json"):
            p = self.path("layers", metric + ext)
            if os.path.exists(p):
                return p
        raise ContractError(f"no reader for per-layer metric {metric!r} under "
                            f"{self.path('layers')}")

    def reference_path(self, config_name: str) -> str:
        p = self.path("reference", config_name + ".py")
        _require(os.path.exists(p), f"no plain reference {p}")
        return p


# --------------------------------------------------------------- result line


def _finite_number(x: Any) -> bool:
    return (
        isinstance(x, (int, float)) and not isinstance(x, bool) and math.isfinite(x)
    )


def validate_line(
    line: Any, *, required: List[Dict[str, Any]], traced: bool, chips: int,
    rehearse: bool = False,
) -> List[str]:
    """Every way ``line`` (the parsed last line) breaks the contract; an
    empty list means it conforms.

    ``required`` are the metrics this run must report, each with the
    manifest's unit: the cell's end-to-end metrics in an untraced run, its
    per-layer metrics in a traced one.  Any other metric is an error.  A
    rehearsal (no chip) may lack device metrics and the busy time; nothing
    else is relaxed."""
    errs: List[str] = []
    if not isinstance(line, dict):
        return [f"last line is not a JSON object: {str(line)[:200]!r}"]
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        if key not in line:
            errs.append(f"key {key!r} missing")
    if errs:
        return errs
    if not isinstance(line["correct"], bool):
        errs.append("correct is not a boolean")
    for key in ("attempted", "failed"):
        v = line[key]
        if not (isinstance(v, int) and not isinstance(v, bool) and v >= 0):
            errs.append(f"{key} is not a whole number >= 0: {v!r}")
    if not errs and line["failed"] > line["attempted"]:
        errs.append("failed > attempted")

    metrics = line["metrics"]
    if not isinstance(metrics, dict):
        errs.append("metrics is not an object")
        metrics = {}
    known = {m["name"]: m for m in required}
    for name, val in metrics.items():
        if not _NAME.match(name):
            errs.append(f"metric name {name!r} has characters outside the contract")
        if name not in known:
            errs.append(f"metric {name!r} is not one of this cell's")
            continue
        if not isinstance(val, dict) or set(val) != {"value", "unit"}:
            errs.append(f"metric {name!r} is not {{value, unit}}: {val!r}")
            continue
        if not _finite_number(val["value"]):
            errs.append(f"metric {name!r} value is not a finite number: {val['value']!r}")
        if val["unit"] != known[name]["unit"] or not _UNIT.match(str(val["unit"])):
            errs.append(f"metric {name!r} unit {val['unit']!r}, manifest says "
                        f"{known[name]['unit']!r}")
        if (
            _finite_number(val["value"])
            and val["unit"] == "%"
            and ("roofline" in name or "mfu" in name)
            and not 0 < val["value"] <= 105
        ):
            errs.append(f"{name} = {val['value']} is not a share in (0, 105] %")
    for m in required:
        if m["name"] not in metrics and not (rehearse and traced):
            errs.append(f"metric {m['name']!r} missing")
    if not metrics:
        errs.append("no metric reported")

    dev = line["device"]
    if not isinstance(dev, dict):
        return errs + ["device is not an object"]
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        if key not in dev:
            errs.append(f"device.{key} missing")
    cnt = dev.get("count")
    if "count" in dev and not (isinstance(cnt, int) and not isinstance(cnt, bool)
                               and cnt >= chips):
        errs.append(f"device.count {cnt!r} is under the cell's {chips} chips")
    mp = dev.get("memory_peak_bytes")
    if "memory_peak_bytes" in dev and not (
        isinstance(mp, int) and not isinstance(mp, bool) and mp > 0
    ):
        errs.append(f"device.memory_peak_bytes is not a whole number > 0: {mp!r}")
    if traced and not (rehearse and "busy_s" not in dev):
        busy, window = dev.get("busy_s"), dev.get("window_s")
        if not (_finite_number(busy) and _finite_number(window)):
            errs.append(f"traced run: busy_s={busy!r}, window_s={window!r} "
                        "are not finite numbers")
        elif not 0 < busy <= window:
            errs.append(f"traced run: need 0 < busy_s <= window_s, got "
                        f"busy_s={busy}, window_s={window}")
    bd = line.get("breakdown")
    if bd is not None:
        if not isinstance(bd, dict):
            errs.append("breakdown is not an object")
        else:
            for key in ("device_ops", "idle_gaps"):
                rows = bd.get(key, [])
                ok = isinstance(rows, list) and len(rows) <= 10 and all(
                    isinstance(r, list) and len(r) == 2 and isinstance(r[0], str)
                    and _finite_number(r[1]) for r in rows
                )
                if not ok:
                    errs.append(f"breakdown.{key} is not <= 10 [name, seconds] pairs")
    return errs


def dumps_line(line: Dict[str, Any]) -> str:
    """The line as printed: strict JSON (``NaN``/``Infinity`` raise), on one
    line."""
    return json.dumps(line, allow_nan=False, separators=(", ", ": "))
