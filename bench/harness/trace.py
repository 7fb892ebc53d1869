"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics and the breakdown read: device busy time over the
traced window, device time per compiled program, the operations that
took most time, and the idle gaps labelled by the host span open at
that moment.

Reading (``load``) is kept apart from the arithmetic (``reduce``), so
the arithmetic can be checked on a trace recorded on the CPU.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

Span = Tuple[float, float, str]          # start_ns, end_ns, name

TPU_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = re.compile(r"^XLA Ops$")
MODULES_LINE = re.compile(r"^XLA Modules$")
WINDOW = "bench.window"


@dataclass
class Events:
    ops: Dict[str, List[Span]]           # device plane -> op events
    modules: Dict[str, List[Span]]       # device plane -> program events
    host: List[Span]                     # host annotations


@dataclass
class Summary:
    busy_s: float                        # mean over devices
    window_s: float
    module_s: Dict[str, float]           # program -> device s, summed
    top_ops: List[list] = field(default_factory=list)
    idle_gaps: List[list] = field(default_factory=list)
    idle_by_label: Dict[str, float] = field(default_factory=dict)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, host_names, device_plane=TPU_PLANE,
         ops_line=OPS_LINE, modules_line=MODULES_LINE) -> Events:
    """Events of every device plane matching ``device_plane`` (its
    lines matching ``ops_line`` / ``modules_line``) and the host events
    named in ``host_names`` (any host thread)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ev = Events(ops={}, modules={}, host=[])
    names = set(host_names)
    for plane in pd.planes:
        if device_plane.match(plane.name):
            for line in plane.lines:
                for pat, into in ((ops_line, ev.ops),
                                  (modules_line, ev.modules)):
                    if pat is not None and pat.match(line.name):
                        into.setdefault(plane.name, []).extend(
                            (e.start_ns, e.end_ns, e.name)
                            for e in line.events)
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                ev.host.extend((e.start_ns, e.end_ns, e.name)
                               for e in line.events if e.name in names)
    if not ev.ops:
        raise ValueError("no device operations in the trace; planes: " +
                         "; ".join(f"{p.name}: {[ln.name for ln in p.lines]}"
                                   for p in pd.planes))
    return ev


def union(spans: List[Span], lo: float, hi: float):
    """Merged [start, end) intervals of ``spans`` clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e, _ in sorted(spans):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(merged, lo: float, hi: float):
    """The idle intervals between merged busy intervals in [lo, hi]."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label_at(t: float, host: List[Span]) -> str:
    """The innermost host span open at ``t`` (latest start wins)."""
    return labels([t], host)[0]


def labels(times: List[float], host: List[Span]) -> List[str]:
    """``label_at`` for each of ``times`` (ascending) in one sweep; the
    host spans of one thread nest, so the innermost open span is the
    top of a stack."""
    spans = sorted(host)
    out, stack, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][0] <= t:
            while stack and stack[-1][1] <= spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        out.append(stack[-1][2] if stack else "no span")
    return out


def op_name(name: str) -> str:
    """An op event's HLO instruction name, without the instruction
    text the TPU trace appends: "%while.44 = (...) while(...)" ->
    "while.44"."""
    return name.split(" = ", 1)[0].lstrip("%")


def program(name: str) -> str:
    """A program event's name without its run id: jit_f(123) -> jit_f."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce(ev: Events, lo: float, hi: float, top: int = 10) -> Summary:
    """Busy, idle and per-program time inside the window [lo, hi] ns."""
    busy, module_s = [], defaultdict(float)
    op_s = defaultdict(float)
    idle = []
    for dev, ops in ev.ops.items():
        merged = union(ops, lo, hi)
        busy.append(sum(e - s for s, e in merged))
        idle.extend(gaps(merged, lo, hi))
        mods = sorted(ev.modules.get(dev, []))
        starts = [m[0] for m in mods]
        for s, e, nm in ops:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            k = bisect.bisect_right(starts, s) - 1
            owner = mods[k][2] if k >= 0 and s < mods[k][1] else "?"
            op_s[(owner, nm)] += (e - s) / 1e9
        for s, e, nm in mods:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                module_s[program(nm)] += (e - s) / 1e9
    by_label = defaultdict(float)
    labelled = []
    idle.sort(key=lambda g: g[0] + g[1])
    for (s, e), lab in zip(idle, labels([(s + e) / 2 for s, e in idle],
                                        ev.host)):
        by_label[lab] += (e - s) / 1e9
        labelled.append([lab, (e - s) / 1e9])
    labelled.sort(key=lambda x: -x[1])
    return Summary(
        busy_s=sum(busy) / len(busy) / 1e9, window_s=(hi - lo) / 1e9,
        module_s=dict(module_s),
        top_ops=_top_ops(op_s, top),
        idle_gaps=labelled[:top],
        idle_by_label=dict(sorted(by_label.items(), key=lambda x: -x[1])))


def _top_ops(op_s, top: int) -> List[list]:
    """Device seconds by "program/op", most first; names are cut down
    once per distinct event name, not once per event."""
    by = defaultdict(float)
    for (owner, nm), v in op_s.items():
        by[f"{program(owner)}/{op_name(nm)}"] += v
    return [[k, v] for k, v in sorted(by.items(), key=lambda x: -x[1])[:top]]


def window(ev: Events) -> Tuple[float, float]:
    """The traced window: the harness's ``bench.window`` annotation."""
    w = [(s, e) for s, e, nm in ev.host if nm == WINDOW]
    if len(w) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(w)}")
    return w[0]
