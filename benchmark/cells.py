"""Cells by name: everything one configuration, one traffic mix, one loop
kind or one per-layer metric needs is a file of its own, found from the name
in ``BENCHMARK.json``.  A later PR adds a cell by adding files and one entry;
nothing here is edited.

    configs/<config>.json        sizes, kernel, array flags, guarantee, limits
    configs/<config>_ref.py      data recipe from the seed, plain reference,
                                 control, per-launch operations and bytes
    configs/<kernel_file>        the user's kernel source (a copy)
    traffic/<traffic>.json       loop kind, n, iterations per call, pins
    loops/<loop>.py              how one call is driven (window / per_call)
    layer_metrics/<metric>.py    one reader of trace / counters each
    peaks.json                   published device peaks, keyed by device kind

Interfaces (duck-typed, kept this small on purpose):

``configs/<config>_ref.py``
    ``inputs(cfg, params, rng) -> (arrays, values)``  host arrays by name
    (numpy, made in bulk from the seeded generator) and the kernel's scalar
    arguments.
    ``call_values(cfg, params, values) -> {"cycle": [...], "apart": ...}``
    (optional)  scalar arguments call by call: the window's calls go through
    ``cycle``; the last warm-up call and the fresh call take ``apart``, so
    that what the window leaves in the outputs cannot be what warm-up left.
    ``compare(cfg, params, arrays, values, observed, seed, precision=None)
    -> [Compared]``  every number compared with its limit.  ``observed`` is
    what the harness read back after the window closed (``run.read_back``):
    the outputs with the last call's arguments and the lanes' ranges after
    every call, and the same of the configuration's ``fresh_call`` (one more
    call of the timed loop into arrays filled anew); ``seed`` draws the
    sample.  ``precision`` names the lower precision of
    the control, which then stands in the program's place; None compares the
    program's outputs.
    ``kernel_cost(cfg, params, items) -> {"ops", "bytes"}``  per launch of
    ``items`` work-items, where a roofline metric reads the configuration.

``loops/<loop>.py``
    ``items_per_call(params)``, ``enter(ctx)``, ``make_call(ctx) -> call``,
    ``leave(ctx)`` (flushes results to the host).

``layer_metrics/<metric>.py``  (a name ``quantity.variant`` without a file
    of its own is read by ``layer_metrics/<quantity>.py``, see ``quantity``)
    ``read(ctx) -> float | None`` — None: nothing to read here, the metric is
    left out of the line.  ``ctx`` is the run's state (``run.build``): the
    cruncher, the call walls, ``ranges_log``, ``window_compiles``, and in a
    traced run ``reduced`` (``xplane.Reduced``) and ``peaks``.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, NamedTuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Compared(NamedTuple):
    """One number the correctness check compared, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        # NaN compares false: a check that produced no number has failed
        return bool(self.value <= self.limit)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts), encoding="utf-8") as f:
        return json.load(f)


def load_module(*parts: str):
    path = os.path.join(HERE, *parts)
    name = "bench_" + "_".join(parts).replace(".py", "").replace("/", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def quantity(metric: str) -> str:
    """``items_per_s.balanced`` is the quantity ``items_per_s``: a metric's
    name may carry a variant after a dot, so that cells which need a bound of
    their own, or report another end-to-end metric, can list the same
    quantity under a name of their own.  The quantity is what is computed."""
    return metric.partition(".")[0]


def load_reader(metric: str):
    """The reader of a per-layer metric: ``layer_metrics/<metric>.py``, or
    for a variant without a file of its own its quantity's reader."""
    for name in (metric, quantity(metric)):
        if os.path.exists(os.path.join(HERE, "layer_metrics", name + ".py")):
            return load_module("layer_metrics", name + ".py")
    raise FileNotFoundError(f"no reader layer_metrics/{metric}.py")


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


class Cell(NamedTuple):
    name: str
    chips: int
    cfg: dict        # configs/<config>.json
    params: dict     # traffic/<traffic>.json
    ref: Any         # configs/<config>_ref.py
    loop: Any        # loops/<loop>.py
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def _reported(entries: list, cell: str, moved: set | None = None) -> list:
    """The metrics this cell reports: those that list it, and those that
    list no cells — for a per-layer metric that means every cell reporting
    the end-to-end metric it moves (``moved``: the cell's own)."""
    return [m for m in entries
            if (cell in m["workloads"] if "workloads" in m
                else moved is None or m["moves"] in moved)]


def load_cell(name: str) -> Cell:
    man = manifest()
    rows = [w for w in man["workloads"] if w["name"] == name]
    if not rows:
        known = ", ".join(w["name"] for w in man["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")
    row = rows[0]
    conf = next(c for c in man["configs"] if c["name"] == row["config"])
    with open(os.path.join(ROOT, conf["file"]), encoding="utf-8") as f:
        cfg = json.load(f)
    params = load_json("traffic", row["traffic"] + ".json")
    end_to_end = _reported(man["end_to_end"], name)
    return Cell(
        name=name, chips=int(row["chips"]), cfg=cfg, params=params,
        ref=load_module("configs", cfg["reference"]),
        loop=load_module("loops", params["loop"] + ".py"),
        end_to_end=end_to_end,
        per_layer=_reported(man["per_layer"], name,
                            {m["name"] for m in end_to_end}))


def kernel_source(cfg: dict) -> str:
    with open(os.path.join(HERE, "configs", cfg["kernel_file"]),
              encoding="utf-8") as f:
        return f.read()


def peaks(device_kind: str) -> dict:
    table = load_json("peaks.json")
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            "benchmark/peaks.json (a device that is not in the table is an "
            "error, not a default)")
    return table[device_kind]
