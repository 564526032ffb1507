"""Reduce a profiler trace to device busy time, idle gaps and collective time.

A trace is read as planes of lines of events ``(name, start_ns,
duration_ns)``, host and device on one clock, as
``jax.profiler.ProfileData`` gives them (:func:`load_xplane`); tests feed
the same shape from a small synthetic file.

* The window is the host span ``bench.window`` that the runner opens
  around the measured calls.
* Busy time on a chip is the union of its ``XLA Ops`` events inside the
  window, leaving out control-flow containers (``while``, ``conditional``,
  ``call``), whose span covers the ops they run and the gaps between them.
* Each idle gap is attributed: the part inside an ``XLA Modules`` event
  (a program was running) to "in program, between ops", the rest to the
  benchmark's host span it overlaps (``bench.call``: dispatching a solve;
  ``bench.wait``: waiting on ``block_until_ready``; ``bench.next``: the
  harness's own bookkeeping between calls), and what no span covers to
  "outside bench spans".
* Exposed collective time on a chip is the time in which a collective op
  (from ``XLA Ops`` or ``Async XLA Ops``) runs and no other op does.

Times are summed per chip and averaged over the chips used.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path
from typing import Iterable

WINDOW_SPAN = "bench.window"
HOST_SPANS = ("bench.call", "bench.wait", "bench.next")
IN_PROGRAM = "in program, between ops"
OUTSIDE = "outside bench spans"
CONTAINERS = frozenset({"while", "conditional", "call"})
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute", "reduce-scatter",
               "all-to-all", "collective-broadcast")
TOP = 10

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
_SUFFIX = re.compile(r"(\.(\d+|clone))+$")


def classify(name: str) -> tuple[str, str]:
    """``(base name, opcode)`` of an XLA op event's name.

    The trace names an op by its HLO text, ``%fusion.75 = f32[...] fusion(...)``;
    the base name drops ``%`` and the numeric suffixes (Pallas kernels keep
    their function's name, e.g. ``_spmv``), the opcode is the word before
    the operand list. A bare name is its own opcode.
    """
    head, sep, rest = name.partition(" = ")
    base = _SUFFIX.sub("", head.strip().lstrip("%")) or head
    m = _OPCODE.search(" " + rest) if sep else None
    return base, (m.group(1) if m else base)


def _union(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(iv) -> float:
    return sum(e - s for s, e in iv)


def _intersect(a, b):
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _subtract(a, b):
    """``a`` minus ``b``; both sorted and disjoint."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


@dataclass
class TraceSummary:
    window_s: float
    busy_s: list[float]                 # per chip
    collective_exposed_s: list[float]   # per chip
    module_runs: list[int]              # per chip: program executions that start in the window
    device_ops: list[tuple[str, float]]  # top ops by time, averaged over chips
    idle_gaps: list[tuple[str, float]]   # idle time by what the host did, averaged over chips

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s)


class _Chip:
    def __init__(self):
        self.leaf = []        # (start, end) of every non-container op
        self.other = []       # ... of those that are not collectives
        self.collective = []
        self.modules = []
        self.by_name = defaultdict(list)


def summarize(planes: Iterable, chips: int) -> TraceSummary:
    """Reduce ``planes`` (see module doc) over the first ``chips`` TPU planes."""
    names: dict[str, tuple[str, str]] = {}
    devices: dict[int, _Chip] = {}
    host = defaultdict(list)
    for plane_name, lines in planes:
        m = _DEVICE_PLANE.match(plane_name)
        if m and int(m.group(1)) < chips:
            chip = devices.setdefault(int(m.group(1)), _Chip())
            for line_name, events in lines:
                for name, start, dur in events:
                    iv = (float(start), float(start) + float(dur))
                    if line_name == "XLA Modules":
                        chip.modules.append(iv)
                        continue
                    if line_name not in ("XLA Ops", "Async XLA Ops"):
                        continue
                    if name not in names:
                        names[name] = classify(name)
                    base, opcode = names[name]
                    is_coll = opcode.startswith(COLLECTIVES) or base.startswith(COLLECTIVES)
                    if is_coll:
                        chip.collective.append(iv)
                    if line_name == "Async XLA Ops" or opcode in CONTAINERS:
                        continue
                    chip.leaf.append(iv)
                    chip.by_name[base].append(iv)
                    if not is_coll:
                        chip.other.append(iv)
        elif not m:
            for _, events in lines:
                for name, start, dur in events:
                    if name == WINDOW_SPAN or name in HOST_SPANS:
                        host[name].append((float(start), float(start) + float(dur)))
    if not host[WINDOW_SPAN]:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} host span")
    if sorted(devices) != list(range(chips)):
        raise ValueError(f"the trace has TPU planes {sorted(devices)}, expected 0..{chips - 1}")
    lo, hi = max(host[WINDOW_SPAN], key=lambda iv: iv[1] - iv[0])
    window = [(lo, hi)]
    spans = {k: _clip(_union(host[k]), lo, hi) for k in HOST_SPANS}

    busy, exposed, runs = [], [], []
    op_time, gap_time = defaultdict(float), defaultdict(float)
    for c in range(chips):
        chip = devices[c]
        busy_iv = _clip(_union(chip.leaf), lo, hi)
        busy.append(_length(busy_iv) * 1e-9)
        other = _clip(_union(chip.other), lo, hi)
        coll = _clip(_union(chip.collective), lo, hi)
        exposed.append(_length(_subtract(coll, other)) * 1e-9)
        runs.append(sum(1 for s, _ in chip.modules if lo <= s < hi))
        for base, iv in chip.by_name.items():
            op_time[base] += _length(_clip(iv, lo, hi)) * 1e-9
        gaps = _subtract(window, busy_iv)
        modules = _clip(_union(chip.modules), lo, hi)
        gap_time[IN_PROGRAM] += _length(_intersect(gaps, modules)) * 1e-9
        outside = _subtract(gaps, modules)
        left = _length(outside)
        for k in HOST_SPANS:
            t = _length(_intersect(outside, spans[k]))
            gap_time[k] += t * 1e-9
            left -= t
        gap_time[OUTSIDE] += max(left, 0.0) * 1e-9

    def top(d):
        items = sorted(((k, v / chips) for k, v in d.items() if v > 0), key=lambda kv: -kv[1])
        return [[k, v] for k, v in items[:TOP]]

    return TraceSummary(window_s=(hi - lo) * 1e-9, busy_s=busy, collective_exposed_s=exposed,
                        module_runs=runs, device_ops=top(op_time), idle_gaps=top(gap_time))


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_xplane(path: Path):
    """The planes of a profiler trace, as :func:`summarize` reads them."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    for plane in data.planes:
        yield plane.name, ((line.name, ((e.name, e.start_ns, e.duration_ns) for e in line.events))
                           for line in plane.lines)
