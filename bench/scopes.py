"""Device time per round phase, read from the ``fl.*`` named scopes the
program puts on the round step's ops (``repro.fl.phases``).

``load`` reads an ``.xplane.pb`` into the plain form of
``bench.trace_reduce.load`` (the harness's ``bench.*`` host annotations
and each TPU device's ``XLA Ops`` line), with a fourth field on every op
event: the op's ``fl.`` scope path, or "" where it has none. A TPU v5e
trace's op events carry no ``op_name`` in their stats (only the device
offset and duration), so the scope comes from the optimized HLO modules
the trace keeps under ``/host:metadata``: the program an op ran in is the
``XLA Modules`` event around it, and the instruction is named in the op's
text. ``reduce`` works on the plain form only, so it is tested on a
committed fixture (``tests/scope_fixture.json``).

An op's phase is the innermost ``fl.<phase>`` in its scope path; an op
inside ``fl.round``, ``fl.event`` or ``fl.chunk`` with no phase is
``body``, and an op in no ``fl.`` scope is ``none``. On each device every
instant of the window that some op covers goes to the innermost op
running then (the one that started last), so a ``while`` and the ops of
its body count once, and the phases' times add up to the busy time.
``scope_ms`` reads any scope by name, a mechanism's own nested in a phase
included: an instant counts for every scope on its op's path.

Facts do not carry the trace's path: ``for_facts`` takes the newest
``*.xplane.pb`` under ``.bench_cache/trace/`` and accepts it only if its
``bench.window`` is as long as the one the run reduced. Loads are cached,
so the readers parse a trace once. Where no op in the window carries an
``fl.`` scope (a program without them) every reader returns None.

    python3 -m bench.scopes [trace.xplane.pb]

prints each phase's share of the busy time, per device.
"""

from __future__ import annotations

import functools
import json
import re
import sys
from pathlib import Path

from bench.trace_reduce import DEVICE_PLANE, OPS_LINE, WINDOW, annotations, find_trace, op_name

ROOT = Path(__file__).resolve().parents[1]
TRACES = ROOT / ".bench_cache" / "trace"
PHASES = ("personalize", "train", "transmit", "aggregate", "eval", "select",
          "gather", "scatter")
BODIES = ("round", "event", "chunk")
KINDS = PHASES + ("body", "none")
SCOPE = re.compile(r"(?:^|/)fl\.([a-z]+)(?=/|$)")
METADATA_PLANE = "/host:metadata"
MODULES_LINE = "XLA Modules"


def phase_of(scope: str) -> str:
    """The innermost phase of an ``fl.`` scope path; ``body`` inside a
    round, event or chunk with no phase; ``none`` outside every scope."""
    names = SCOPE.findall(scope or "")
    for name in reversed(names):
        if name in PHASES:
            return name
    return "body" if any(n in BODIES for n in names) else "none"


# -- loading -------------------------------------------------------------------

def load(path) -> dict:
    """The plain form of a trace: ``bench.*`` host annotations, and each
    device's ``XLA Ops`` events as ``[text, start_ns, duration_ns, scope]``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    planes, found = [], []
    for pl in pd.planes:
        if pl.name.startswith("/host:"):
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for ln in pl.lines for e in ln.events if e.name.startswith("bench.")]
            planes.append({"name": pl.name, "lines": [{"name": "annotations", "events": events}]})
        elif DEVICE_PLANE.match(pl.name):
            modules = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for ln in pl.lines if ln.name == MODULES_LINE for e in ln.events)
            events = sorted(([e.name, float(e.start_ns), float(e.duration_ns), ""]
                             for ln in pl.lines if ln.name == OPS_LINE for e in ln.events),
                            key=lambda ev: ev[1])
            found.append((modules, events))
            planes.append({"name": pl.name, "lines": [{"name": OPS_LINE, "events": events}]})
    if found:
        by_module = hlo_scopes(path)
        for modules, events in found:
            for ev, module in zip(events, _enclosing(modules, events)):
                ev[3] = _lookup(by_module, module, op_name(ev[0]))
    return {"planes": planes}


def _enclosing(modules, events):
    """The name of the program run each (start-sorted) op event falls in."""
    k, out = 0, []
    for _, s, _, _ in events:
        while k < len(modules) and modules[k][1] <= s:
            k += 1
        out.append(modules[k][2] if k < len(modules) and modules[k][0] <= s else None)
    return out


def _module_key(name: str) -> tuple:
    m = re.fullmatch(r"(.*)\((\d+)\)", name)
    return (m.group(1), int(m.group(2))) if m else (name, None)


def _lookup(by_module: dict, module, instruction: str) -> str:
    """The scope of ``instruction`` in the program ``module`` (``name(id)``,
    as the trace names it); by name alone where that program is unknown and
    the name is unique over the programs."""
    if module is not None:
        key = _module_key(module)
        scopes = by_module.get(key)
        if scopes is None:
            same = [v for k, v in by_module.items() if k[0] == key[0]]
            scopes = same[0] if len(same) == 1 else None
        if scopes is not None:
            return scopes.get(instruction, "")
    found = {scopes[instruction] for scopes in by_module.values() if instruction in scopes}
    return found.pop() if len(found) == 1 else ""


# The xplane and HLO protos are read with a minimal wire-format reader:
# XSpace.planes = 1; XPlane.name = 2, event_metadata = 4, stat_metadata = 5;
# XEventMetadata.name = 2, stats = 5; XStat.metadata_id = 1, bytes = 6;
# HloProto.hlo_module = 1; HloModuleProto.computations = 3;
# HloComputationProto.instructions = 2, id = 5; HloInstructionProto.name = 1,
# metadata = 7, id = 35, operand_ids = 36, called_computation_ids = 38;
# OpMetadata.op_name = 2.

def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf):
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _first(buf, field, default=b""):
    return next((v for f, v in _fields(buf) if f == field), default)


def _ints(buf, field) -> list[int]:
    """A repeated integer field, packed or not."""
    out = []
    for f, v in _fields(buf):
        if f != field:
            continue
        if isinstance(v, int):
            out.append(v)
        else:
            i = 0
            while i < len(v):
                x, i = _varint(v, i)
                out.append(x)
    return out


def hlo_scopes(path) -> dict:
    """``{(module, program_id): {instruction: scope}}`` from the optimized
    HLO modules a trace keeps in its ``/host:metadata`` plane."""
    space = memoryview(Path(path).read_bytes())
    out = {}
    for field, plane in _fields(space):
        if field != 1 or bytes(_first(plane, 2)).decode() != METADATA_PLANE:
            continue
        stat_names = {}
        for f, entry in _fields(plane):
            if f == 5:
                meta = _first(entry, 2)
                stat_names[_first(meta, 1, 0)] = bytes(_first(meta, 2)).decode()
        for f, entry in _fields(plane):
            if f != 4:
                continue
            meta = _first(entry, 2)
            key = _module_key(bytes(_first(meta, 2)).decode())
            for g, stat in _fields(meta):
                if g == 5 and stat_names.get(_first(stat, 1, 0)) == "Hlo Proto":
                    out[key] = instruction_scopes(_first(stat, 6))
    return out


def instruction_scopes(hlo_proto) -> dict:
    """Each instruction's ``fl.`` scope (its ``op_name``). An instruction
    the compiler added without one (a copy or prefetch, an asynchronous
    slice, a layout change) takes the scope of the nearest instruction that
    consumes its result, else of the nearest one whose result it reads,
    else of the instruction that calls its computation (a loop body)."""
    names, scope, operands, comp_of, callers, fused = {}, {}, {}, {}, {}, set()
    for f, comp in _fields(_first(hlo_proto, 1)):
        if f != 3:
            continue
        cid = _first(comp, 5, 0)
        for g, inst in _fields(comp):
            if g != 2:
                continue
            iid = _first(inst, 35, 0)
            names[iid] = bytes(_first(inst, 1)).decode()
            name = bytes(_first(_first(inst, 7), 2)).decode()
            scope[iid] = name if SCOPE.search(name) else ""
            operands[iid] = _ints(inst, 36)
            comp_of[iid] = cid
            called = _ints(inst, 38)
            if bytes(_first(inst, 2)).decode() == "fusion":
                fused.update(called)  # its instructions never run as ops
            for c in called:
                callers.setdefault(c, iid)
    users: dict[int, list[int]] = {}
    for iid, ops in operands.items():
        for op in ops:
            users.setdefault(op, []).append(iid)

    def nearest(start, edges):
        seen, frontier = {start}, [start]
        while frontier:
            nxt = []
            for i in frontier:
                for j in edges.get(i, ()):
                    if j in seen:
                        continue
                    if scope.get(j):
                        return scope[j]
                    seen.add(j)
                    nxt.append(j)
            frontier = nxt
        return ""

    def resolve(iid, depth=0):
        if scope[iid]:
            return scope[iid]
        found = nearest(iid, users) or nearest(iid, operands)
        caller = callers.get(comp_of[iid])
        if not found and caller is not None and depth < 8:
            found = resolve(caller, depth + 1)
        return found

    return {names[iid]: s for iid in names
            if comp_of[iid] not in fused and (s := resolve(iid))}


# -- reduction -----------------------------------------------------------------

def reduce(trace: dict) -> dict:
    """Per device: each phase's time inside the window (``phase_ns``, a
    list per kind of ``KINDS``), each scope's (``scope_ns``, a list per
    component of any op's scope path), the busy time, and whether any op
    there carries an ``fl.`` scope."""
    windows = [(s, e) for n, s, e in annotations(trace) if n == WINDOW]
    if not windows:
        raise ValueError("trace has no bench.window annotation")
    w0, w1 = windows[0]
    phase_ns = {k: [] for k in KINDS}
    by_scope, busy_ns, scoped = [], [], False
    for pl in trace["planes"]:
        if not DEVICE_PLANE.match(pl["name"]):
            continue
        ops, paths = [], []
        for ln in pl["lines"]:
            if ln["name"] != OPS_LINE:
                continue
            for text, s, d, scope in ln["events"]:
                s0, e0 = max(s, w0), min(s + d, w1)
                if e0 > s0:
                    ops.append((s0, e0, phase_of(scope)))
                    paths.append((s0, e0, scope))
                    scoped = scoped or bool(scope)
        acc = _innermost(ops)
        for k in KINDS:
            phase_ns[k].append(acc.get(k, 0.0))
        busy_ns.append(sum(acc.values()))
        # the same instants, each counted for every scope on its innermost
        # op's path
        per_scope: dict[str, float] = {}
        for path, ns in _innermost(paths).items():
            for name in set(path.split("/")) - {""}:
                per_scope[name] = per_scope.get(name, 0.0) + ns
        by_scope.append(per_scope)
    names = set().union(*by_scope)
    scope_ns = {n: [d.get(n, 0.0) for d in by_scope] for n in sorted(names)}
    return {"window_ns": w1 - w0, "busy_ns": busy_ns, "phase_ns": phase_ns,
            "scope_ns": scope_ns, "scoped": scoped}


def _innermost(ops) -> dict:
    """Time per kind, each covered instant going to the op that started
    last among those running then (the innermost of nested ops)."""
    acc: dict[str, float] = {}
    stack: list[tuple[float, str]] = []   # (end, kind), in order of start
    t = float("-inf")

    def close_until(limit):
        nonlocal t
        while stack and stack[-1][0] <= limit:
            end, kind = stack.pop()
            if end > t:
                acc[kind] = acc.get(kind, 0.0) + end - t
                t = end

    for s, e, kind in sorted(ops, key=lambda op: (op[0], -op[1])):
        close_until(s)
        if stack and s > t:
            top = stack[-1][1]
            acc[top] = acc.get(top, 0.0) + s - t
        t = max(t, s)
        stack.append((e, kind))
    close_until(float("inf"))
    return acc


# -- the run's trace -----------------------------------------------------------

def newest_trace(directory=None) -> Path | None:
    found = list(Path(directory or TRACES).glob("**/*.xplane.pb"))
    return max(found, key=lambda p: p.stat().st_mtime) if found else None


@functools.lru_cache(maxsize=4)
def _reduced(path: str, mtime: float, size: int) -> dict:
    return reduce(load(path))


def for_facts(facts, directory=None) -> dict | None:
    """The scope reduction of the run's own trace, or None where its ops
    carry no ``fl.`` scope; raises if the newest trace is not the run's."""
    path = newest_trace(directory)
    if path is None:
        raise FileNotFoundError(f"no *.xplane.pb under {directory or TRACES}")
    st = path.stat()
    red = _reduced(str(path), st.st_mtime, st.st_size)
    if red["window_ns"] != facts.reduced["window_ns"]:
        raise ValueError(f"{path}: bench.window is {red['window_ns']} ns long, the run "
                         f"reduced {facts.reduced['window_ns']} ns: not this run's trace")
    return red if red["scoped"] else None


def phase_ms(facts, phase: str, directory=None) -> float | None:
    """Device milliseconds a round (or event) in ``phase``, mean over chips."""
    if facts.rounds == 0:
        return None
    red = for_facts(facts, directory)
    if red is None:
        return None
    ns = red["phase_ns"][phase]
    return 1e-6 * (sum(ns) / len(ns)) / facts.rounds


def scope_ms(facts, name: str, directory=None) -> float | None:
    """Device milliseconds a round (or event), mean over chips, of the ops
    whose scope path holds ``name`` as a whole component: a phase's scope
    such as ``fl.train``, or a mechanism's scope nested in one, such as
    ``moe.dispatch`` inside ``fl.train`` (only ops inside some ``fl.``
    scope keep a path). Each covered instant goes to the innermost op, as
    for the phases, and counts for every scope on that op's path, so
    nested scopes overlap. None where no op of the window is in ``name``."""
    if facts.rounds == 0:
        return None
    red = for_facts(facts, directory)
    if red is None or name not in red["scope_ns"]:
        return None
    ns = red["scope_ns"][name]
    return 1e-6 * (sum(ns) / len(ns)) / facts.rounds


def host_phase_ms(facts, name: str) -> float | None:
    """Host milliseconds a round (or event) in the scheduler's ``name``
    span; None where the program has no such span."""
    if facts.rounds == 0 or name not in facts.host_phase_s:
        return None
    return 1000.0 * facts.host_phase_s[name] / facts.rounds


def coverage(red: dict) -> dict:
    """Each kind's share of the busy time, per device, in percent."""
    out = {"window_ms": red["window_ns"] * 1e-6,
           "busy_ms": [b * 1e-6 for b in red["busy_ns"]], "share": {}}
    for k in KINDS:
        out["share"][k] = [100.0 * ns / b if b else 0.0
                           for ns, b in zip(red["phase_ns"][k], red["busy_ns"])]
    return out


def main(argv=None):
    args = sys.argv[1:] if argv is None else argv
    path = Path(args[0]) if args else newest_trace()
    if path is None or path.is_dir():
        path = find_trace(path or TRACES)
    red = reduce(load(path))
    print(json.dumps({"trace": str(path), "scoped": red["scoped"], **coverage(red)}))


if __name__ == "__main__":
    main()
