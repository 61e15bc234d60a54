"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

* busy time: the union of the intervals in which an operation ran on a
  chip (``XLA Ops`` line of each ``/device:TPU:n`` plane; the ``XLA
  Modules`` line where a plane has no op line), inside the window;
* the idle share: 1 - busy / window, averaged over the chips;
* device time per engine program, by XLA module name (``programs.json``);
* the operations that took most device time (self time: a loop op less
  the ops of its body), and the idle gaps labelled
  by the innermost host span (the harness's ``TraceAnnotation`` names)
  that covers each gap's midpoint;
* host span totals by name.

The window is the host span named ``window`` (see ``harness.WINDOW``),
so host and device events are read on the trace's one clock.
"""
from __future__ import annotations

import bisect
import functools
import glob
import json
import os
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

HERE = Path(__file__).resolve().parent
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WINDOW = "window"
TOP = 10


def program_names() -> Dict[str, List[str]]:
    t = json.loads((HERE / "programs.json").read_text())
    return {k: v for k, v in t.items() if isinstance(v, list)}


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return hits[-1]


Event = Tuple[str, int, int]          # (name, start_ns, end_ns)


def load(path: str, host_names: Optional[set] = None) -> Dict:
    """Events of a trace as plain tuples: per chip its ops and modules,
    and the host spans (host-thread events with a duration, only those
    named in ``host_names`` when given)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(path))
    chips: Dict[int, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {}
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    lines[line.name] = [
                        (e.name, int(e.start_ns), int(e.end_ns))
                        for e in line.events]
            chips[int(m.group(1))] = lines
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0 and (host_names is None
                                              or e.name in host_names):
                        host.append((e.name, int(e.start_ns),
                                     int(e.end_ns)))
    return {"chips": chips, "host": host}


def _union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(ev: Sequence[Event], lo: int, hi: int) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in ev
            if e > lo and s < hi]


def _matches(name: str, prefixes: Sequence[str]) -> bool:
    for p in prefixes:
        if name.startswith(p) and (len(name) == len(p)
                                   or not (name[len(p)].isalnum()
                                           or name[len(p)] == "_")):
            return True
    return False


@functools.lru_cache(maxsize=None)
def op_label(hlo: str) -> str:
    """``%fusion.190 bf16[4096,8,8,128] fusion`` from an op's HLO text:
    its name, its result shape without layout, and its opcode."""
    name, eq, rest = hlo.partition(" = ")
    if not eq:
        return hlo[:120]
    i, depth = 0, 0
    if rest.startswith("("):             # a tuple-shaped result
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                break
        shape, rest = "tuple", rest[i + 1:]
    else:
        shape, _, rest = rest.partition(" ")
    shape = re.sub(r"\{[^}]*\}", "", shape)
    opcode = rest.strip().split("(")[0].split(" ")[-1]
    return f"{name} {shape} {opcode}"[:120]


def _self_times(ops: Sequence[Event]) -> Dict[str, float]:
    """Self time by op label: an op's duration less that of the ops
    nested in it (a loop op holds its body's ops on the same line)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List] = []              # [label, end, self_ns]
    for name, s, e in sorted(ops, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            lab, _, ns = stack.pop()
            out[lab] += ns
        if stack:
            stack[-1][2] -= e - s
        stack.append([op_label(name), e, e - s])
    for lab, _, ns in stack:
        out[lab] += ns
    return out


def _label(spans: List[Event], starts: List[int], t: int,
           depth: int = 256) -> str:
    """Name of the innermost host span covering ``t``: of nested spans,
    the covering one that started last (looked for among the ``depth``
    spans that started last before ``t``)."""
    i = bisect.bisect_right(starts, t)
    for n, s, e in reversed(spans[max(0, i - depth):i]):
        if e >= t:
            return n
    return "no host span"


def reduce(trace: Dict, window: Optional[Tuple[int, int]] = None,
           programs: Optional[Dict[str, List[str]]] = None,
           span_names: Optional[set] = None) -> Dict:
    """Device numbers of ``trace`` (as :func:`load` gives it) inside
    ``window`` (ns); by default the host span named ``window``.  Gaps are
    labelled by the host spans named in ``span_names`` (all, by
    default)."""
    programs = programs or program_names()
    host = trace["host"]
    if window is None:
        wins = [(s, e) for n, s, e in host if n == WINDOW]
        if not wins:
            raise ValueError(f"no host span named {WINDOW!r} in the trace")
        window = wins[-1]
    lo, hi = window
    chips = trace["chips"]
    if not chips:
        raise ValueError("no TPU device plane in the trace")
    spans = sorted((ev for ev in _clip(host, lo, hi) if ev[0] != WINDOW
                    and (span_names is None or ev[0] in span_names)),
                   key=lambda ev: ev[1])
    starts = [s for _, s, _ in spans]
    n = len(chips)
    busy_ns = 0
    prog_ns: Dict[str, float] = defaultdict(float)
    prog_runs: Dict[str, int] = defaultdict(int)
    op_ns: Dict[str, float] = defaultdict(float)
    gap_ns: Dict[str, float] = defaultdict(float)
    for lines in chips.values():
        mods = _clip(lines.get("XLA Modules", []), lo, hi)
        ops = _clip(lines.get("XLA Ops", []), lo, hi) or mods
        busy = _union([(s, e) for _, s, e in ops])
        busy_ns += sum(e - s for s, e in busy)
        for name, s, e in mods:
            for kind, prefixes in programs.items():
                if _matches(name, prefixes):
                    prog_ns[kind] += e - s
                    prog_runs[kind] += 1
        for lab, ns in _self_times(ops).items():
            op_ns[lab] += ns
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gap_ns[_label(spans, starts, (a + b) // 2)] += b - a
    host_ns: Dict[str, float] = defaultdict(float)
    for name, s, e in spans:
        host_ns[name] += e - s
    top = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(gap_ns.items(), key=lambda kv: -kv[1])[:TOP]
    window_s = (hi - lo) / 1e9
    busy_s = busy_ns / n / 1e9
    return {
        "chips": n,
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "program_s": {k: v / n / 1e9 for k, v in prog_ns.items()},
        "program_runs": {k: v / n for k, v in prog_runs.items()},
        "device_ops": [[k, v / n / 1e9] for k, v in top],
        "idle_gaps": [[k, v / n / 1e9] for k, v in gaps],
        "host_s": {k: v / 1e9 for k, v in host_ns.items()},
    }
