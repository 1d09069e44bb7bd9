"""Reduction of a profiler trace (``.xplane.pb``) to numbers.

``load`` reads the file with ``jax.profiler.ProfileData`` into plain tuples;
everything else works on those, so the arithmetic is checked on hand-made
events and on the small recorded trace under ``tests/benchmark/``.

A device plane is one whose name starts with ``/device:``. On it the line
``XLA Ops`` holds one event an executed operation (events nest: a ``while``
holds its body's operations), ``XLA Modules`` one a program, named
``jit_<function>(<fingerprint>)``. Busy time is the union of the operation
events' intervals; an operation's own time is its event less the events
nested in it; a program's device time is the sum of its ``XLA Modules``
events.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

#: Lines of a device plane that hold executed operations, in order of
#: preference. (``Steps``, ``XLA TraceMe`` and the like are not operations.)
OP_LINES = ("XLA Ops", "XLA Modules")

#: The line of a device plane that holds one event a program executed.
MODULE_LINE = "XLA Modules"

_FINGERPRINT = re.compile(r"\(\d+\)$")

_OP_SUFFIX = re.compile(r"\.\d+$")

_LAYOUT = re.compile(r"\{[^{}]*\}")


def short(name: str, limit: int = 160) -> str:
    """An operation's HLO text cut to what tells it from the others: its
    name, its result's shape, its opcode and its first operands' shapes,
    without the layouts."""
    text = _LAYOUT.sub("", name)
    return text if len(text) <= limit else text[: limit - 3] + "..."


@dataclasses.dataclass(frozen=True)
class Ev:
    name: str
    start: float  # nanoseconds
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> dict:
    """{plane name: {line name: [Ev]}} with events sorted by start."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes: dict = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            evs = [Ev(e.name, float(e.start_ns), float(e.duration_ns))
                   for e in line.events]
            evs.sort(key=lambda e: (e.start, -e.dur))
            lines.setdefault(line.name, []).extend(evs)
    return planes


def device_planes(planes: dict) -> dict:
    return {n: l for n, l in planes.items() if n.startswith("/device:")}


def op_events(lines: dict) -> list:
    for name in OP_LINES:
        if lines.get(name):
            return lines[name]
    return []


def program(name: str) -> str:
    """A program's name as jit gave it, without the fingerprint that tells
    its compiled shapes apart: ``jit_decode_chunk(1389...)`` -> ``jit_decode_chunk``."""
    return _FINGERPRINT.sub("", name)


def op_name(text: str) -> str:
    """An operation's name as the program gave it (a kernel's ``name``, a
    fusion's kind), from its event's HLO text, without the ``%`` and the
    number that tells its instances apart:
    ``%paged_flash_decode.9 = (bf16[4,8,4,128], ...) custom-call(...)`` ->
    ``paged_flash_decode``."""
    return _OP_SUFFIX.sub("", text.split(" = ", 1)[0].lstrip("%"))


def marks(planes: dict, marker: str) -> list:
    """[start, end] of every ``marker`` annotation on the host, in order."""
    for pname, lines in planes.items():
        if pname.startswith("/device:"):
            continue
        for evs in lines.values():
            found = [[e.start, e.end] for e in evs if e.name == marker]
            if found:
                return sorted(found)
    return []


def program_seconds(trace: dict, pattern) -> tuple[float, int]:
    """(device seconds, executions) of the programs in the trace whose name
    matches ``pattern`` (a compiled expression) in full. The harness starts
    and stops the profiler between two fenced steps of its one thread, so
    every program in the trace belongs to a traced step and every traced
    step's programs are in it; nothing here lays the device's clock against
    the host's, which differ by a millisecond in a recorded trace."""
    hit = [dur for name, _, dur in trace["programs"] if pattern.fullmatch(name)]
    return sum(hit), len(hit)


def op_seconds(trace: dict, pattern) -> tuple[float, int]:
    """(own device seconds, executions) of the operations in the trace whose
    name (:func:`op_name`) matches ``pattern`` (a compiled expression) in
    full: every one of them, not the ten that the result line prints. A
    kernel's roofline reads its seconds here, by the kernel's name."""
    hit = [v for text, v in trace["ops"].items() if pattern.fullmatch(op_name(text))]
    return sum(s for s, _ in hit), sum(n for _, n in hit)


def program_totals(trace: dict) -> dict:
    """{program: [device seconds, executions]} over the whole trace."""
    out: dict = {}
    for name, _, dur in trace["programs"]:
        tot = out.setdefault(name, [0.0, 0])
        tot[0] += dur
        tot[1] += 1
    return out


def cpu_rehearsal_planes(planes: dict) -> dict:
    """Off the chip there is no device plane: XLA's CPU client runs the
    operations on host threads (lines ``tf_XLA...``). A rehearsal reads
    those in a device's place, so that the traced path can be driven end to
    end on the CPU; no number from it is a device number."""
    out = {}
    for pname, lines in planes.items():
        evs = [e for lname, l in lines.items() if lname.startswith("tf_XLA")
               for e in l if e.dur > 0]
        if evs:
            out[pname] = {OP_LINES[0]: sorted(evs, key=lambda e: (e.start, -e.dur))}
    return out


def union(events) -> list:
    """Merged [start, end] intervals of ``events``."""
    out: list = []
    for e in sorted(events, key=lambda e: e.start):
        if e.dur <= 0:
            continue
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return out


def busy_ns(events) -> float:
    return sum(b - a for a, b in union(events))


def self_times(events) -> dict:
    """{name: nanoseconds} of each operation's own time: its events less the
    events nested inside them on the same line."""
    out: dict = {}
    stack: list = []  # [event, nanoseconds covered by its children]

    def close(upto: float):
        while stack and stack[-1][0].end <= upto:
            ev, covered = stack.pop()
            out[ev.name] = out.get(ev.name, 0.0) + max(ev.dur - covered, 0.0)

    for e in sorted(events, key=lambda e: (e.start, -e.dur)):
        close(e.start)
        if stack:
            stack[-1][1] += e.dur
        stack.append([e, 0.0])
    close(float("inf"))
    return out


def span(planes: dict) -> tuple[float, float]:
    """(start, end) of the traced window in nanoseconds: from the first to
    the last event on any plane that has events."""
    starts, ends = [], []
    for lines in planes.values():
        for evs in lines.values():
            if evs:
                starts.append(evs[0].start)
                ends.append(max(e.end for e in evs))
    if not starts:
        raise ValueError("the trace holds no event")
    return min(starts), max(ends)


def host_label(planes: dict, t: float, marker: str) -> str:
    """What the host was doing at time ``t``: the innermost event covering
    ``t`` on the host line that carries the harness's ``marker``
    annotations, or ``outside <marker>`` where none does."""
    for pname, lines in planes.items():
        if pname.startswith("/device:"):
            continue
        for evs in lines.values():
            if not any(e.name == marker for e in evs):
                continue
            best = None
            for e in evs:
                if e.start > t:
                    break
                if e.end >= t and (best is None or e.dur <= best.dur):
                    best = e
            return best.name if best is not None else f"outside {marker}"
    return "no host annotation"


def reduce(planes: dict, marker: str = "server.step", top: int = 10,
           rehearsal: bool = False) -> dict:
    """The numbers the harness reports from one trace.

    ``busy_s``: seconds in which an operation ran, averaged over the device
    planes that ran any; ``window_s``: the traced span; ``idle_pct``: of
    the busiest device; ``ops``: ``{HLO text: [own seconds, executions]}``
    of every operation it ran (:func:`op_seconds`) and ``device_ops``: the
    ``top`` of them by own time, for the result line; ``idle_gaps``: its idle
    time by what the host was doing, ``top`` labels by seconds;
    ``programs``: (name, start, seconds) of every program it executed and ``steps``: [start, end] of every ``marker`` annotation,
    both in seconds since the trace began (:func:`program_seconds`).
    """
    t0, t1 = span(planes)
    window = t1 - t0
    per_device = {}
    found = cpu_rehearsal_planes(planes) if rehearsal else device_planes(planes)
    for name, lines in found.items():
        evs = op_events(lines)
        if evs:
            per_device[name] = evs
    if not per_device:
        raise ValueError(
            "no operation ran on a device in the traced window "
            f"(planes: {sorted(planes)})")
    busy = {n: busy_ns(evs) for n, evs in per_device.items()}
    busiest = max(busy, key=busy.get)
    evs = per_device[busiest]
    own = self_times(evs)
    ops = sorted(own.items(), key=lambda kv: -kv[1])
    ran = collections.Counter(e.name for e in evs)
    gaps: dict = {}
    edges = [[t0, t0]] + union(evs) + [[t1, t1]]
    for (_, a), (b, _) in zip(edges, edges[1:]):
        if b > a:
            label = host_label(planes, (a + b) / 2, marker)
            gaps[label] = gaps.get(label, 0.0) + (b - a)
    return {
        "busy_s": sum(busy.values()) / len(busy) / 1e9,
        "window_s": window / 1e9,
        "idle_pct": 100.0 * (1.0 - busy[busiest] / window),
        "devices": len(per_device),
        "ops": {k: [v / 1e9, ran[k]] for k, v in ops},
        "device_ops": [[short(k), v / 1e9] for k, v in ops[:top]],
        "idle_gaps": [[k, v / 1e9] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
        "programs": [(program(e.name), (e.start - t0) / 1e9, e.dur / 1e9)
                     for e in found[busiest].get(MODULE_LINE, []) if e.dur > 0],
        "steps": [[(a - t0) / 1e9, (b - t0) / 1e9] for a, b in marks(planes, marker)],
    }
