"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns an ``.xplane.pb`` into plain lists (planes, lines, events
as ``[name, start_ns, duration_ns]``); ``reduce`` works on that form only,
so it is tested on a small committed fixture (``tests/trace_fixture.json``).

The traced window is the host annotation ``bench.window``. On each TPU
device plane the ``XLA Ops`` line gives the intervals in which an
operation ran; their union, clipped to the window, is the device's busy
time. A gap between busy intervals is labelled with the harness span
(``bench.dispatch``, ``bench.device_get``, ``bench.compile``) it overlaps
most, or ``host loop`` where it overlaps none.

A collective (an all-reduce, all-gather, reduce-scatter, all-to-all,
collective permute, or the start or done of an asynchronous one) is
*exposed* where it runs while no compute op runs on that device; ops that
only enclose others (``while``, ``conditional``, ``call``) are not compute.
"""

from __future__ import annotations

import dataclasses
import glob
import re
from pathlib import Path

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|collective")
ENCLOSING = {"while", "conditional", "call"}
OPCODE = re.compile(r"\s([a-z][a-z0-9_-]*)\(")


def load(path) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    return {"planes": [
        {"name": pl.name, "lines": [
            {"name": ln.name,
             "events": [[e.name, float(e.start_ns), float(e.duration_ns)] for e in ln.events]}
            for ln in pl.lines
        ]}
        for pl in pd.planes
    ]}


def find_trace(directory) -> Path | None:
    found = sorted(glob.glob(str(Path(directory) / "**" / "*.xplane.pb"), recursive=True))
    return Path(found[-1]) if found else None


def _union(intervals):
    """Merged, sorted [start, end] intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def annotations(trace: dict) -> list[tuple[str, float, float]]:
    out = []
    for pl in trace["planes"]:
        if not pl["name"].startswith("/host:"):
            continue
        for ln in pl["lines"]:
            for name, s, d in ln["events"]:
                if name.startswith("bench."):
                    out.append((name, s, s + d))
    return out


def reduce(trace: dict) -> dict:
    spans = annotations(trace)
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if not windows:
        raise ValueError("trace has no bench.window annotation")
    w0, w1 = windows[0]
    phases = [(n[len("bench."):], s, e) for n, s, e in spans if n != WINDOW]
    devices, op_totals, op_count, op_text, gaps = [], {}, {}, {}, []
    coll_ns, exposed_ns = [], []
    for pl in trace["planes"]:
        if not DEVICE_PLANE.match(pl["name"]):
            continue
        ops = [ln for ln in pl["lines"] if ln["name"] == OPS_LINE]
        intervals, coll, compute = [], [], []
        for ln in ops:
            for text, s, d in ln["events"]:
                s0, e0 = max(s, w0), min(s + d, w1)
                if e0 > s0:
                    intervals.append((s0, e0))
                    name = op_name(text)
                    op_totals[name] = op_totals.get(name, 0.0) + (e0 - s0)
                    op_count[name] = op_count.get(name, 0) + 1
                    op_text.setdefault(name, text)
                    kind = opcode(text)
                    if COLLECTIVE.search(name) or COLLECTIVE.search(kind):
                        coll.append((s0, e0))
                    elif kind not in ENCLOSING:
                        compute.append((s0, e0))
        busy = _union(intervals)
        devices.append(sum(e - s for s, e in busy))
        coll = _union(coll)
        coll_ns.append(sum(e - s for s, e in coll))
        exposed_ns.append(_length_outside(coll, _union(compute)))
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((_label(s, e, phases), e - s))
    n = max(len(devices), 1)
    return {
        "window_ns": w1 - w0,
        "busy_ns": devices,
        "op_ns": {k: v / n for k, v in op_totals.items()},
        "op_count": op_count,
        "op_text": op_text,
        "collective_ns": coll_ns,
        "exposed_collective_ns": exposed_ns,
        "gaps": sorted(gaps, key=lambda g: -g[1]),
    }


def _length_outside(intervals, cover) -> float:
    """Length of the merged ``intervals`` that no merged ``cover``
    interval overlaps."""
    total, j = 0.0, 0
    for s, e in intervals:
        while j < len(cover) and cover[j][1] <= s:
            j += 1
        k, at = j, s
        while k < len(cover) and cover[k][0] < e:
            total += max(0.0, cover[k][0] - at)
            at = max(at, cover[k][1])
            k += 1
        total += max(0.0, e - at)
    return total


def op_name(event_name: str) -> str:
    """The HLO instruction's name: a TPU trace names an op event by the
    instruction's whole text (``%fusion.12 = f32[...] fusion(...)``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def opcode(event_name: str) -> str:
    """The HLO opcode in an op event's text (``fusion``, ``all-reduce``,
    ``while``); empty where the event carries the name alone."""
    _, sep, rest = event_name.partition(" = ")
    m = OPCODE.search(rest) if sep else None
    return m.group(1) if m else ""


def _label(s, e, phases) -> str:
    best, overlap = "host loop", 0.0
    for name, ps, pe in phases:
        o = min(e, pe) - max(s, ps)
        if o > overlap:
            best, overlap = name, o
    return best


@dataclasses.dataclass
class Facts:
    """What a per-layer metric reader may read (``bench/metrics``)."""

    reduced: dict            # ``reduce`` of the traced window
    window_s: float          # traced window, device clock
    busy_s: float            # busy seconds, mean over the chips used
    rounds: int              # rounds or events completed inside the span
    sel: np.ndarray          # (rounds, C) selection masks of those rounds
    host_span_s: float       # traced span on the host clock
    host_phase_s: dict       # scheduler phase seconds inside the span
    config: dict
    recipe: dict
    peak: dict | None
    chips: int
    # the model's ``data_facts``, in its own unit (samples for har-mlp)
    n_train_valid: np.ndarray  # (C,) valid train samples
    n_train_rows: int          # rows of the train slab
    n_test_valid: np.ndarray   # (C,) valid test samples
    breakdown: dict
    model: object            # the configuration's model module (``bench.models``)
    data: object             # what the run handed ``run_federated``


def facts(tracer, window, model, data, config, recipe, peak, chips) -> Facts:
    path = find_trace(tracer.dir)
    if path is None:
        raise SystemExit(f"no trace written under {tracer.dir}")
    red = reduce(load(path))
    counts = model.data_facts(data)
    hooks = window.hooks[tracer.first_hook:tracer.last_hook]
    sel = (np.concatenate([h[2] for h in hooks]) if hooks
           else np.zeros((0, len(counts["n_train_valid"])), bool))
    phase_s = {}
    for name, s, e in window.profiler.spans:
        o = min(e, tracer.t1) - max(s, tracer.t0)
        if o > 0:
            phase_s[name] = phase_s.get(name, 0.0) + o
    busy = red["busy_ns"]
    top_ops = sorted(red["op_ns"].items(), key=lambda kv: -kv[1])[:10]
    return Facts(
        reduced=red,
        window_s=red["window_ns"] * 1e-9,
        busy_s=(sum(busy) / len(busy) * 1e-9) if busy else 0.0,
        rounds=int(sum(h[1] for h in hooks)),
        sel=sel,
        host_span_s=tracer.t1 - tracer.t0,
        host_phase_s=phase_s,
        config=config,
        recipe=recipe,
        peak=peak,
        chips=chips,
        **counts,
        breakdown={
            "device_ops": [[k, v * 1e-9] for k, v in top_ops],
            "idle_gaps": [[k, v * 1e-9] for k, v in red["gaps"][:10]],
        },
        model=model,
        data=data,
    )
