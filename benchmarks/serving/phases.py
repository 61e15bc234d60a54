"""Where a traced run's idle device time and decode time go, read from the
program's own profiler spans and named scopes (``spans.json``).

* ``idle_by_span``: every idle interval of the window split at span
  boundaries, each piece to the innermost host span covering it (``no
  host span`` outside them all), so the map sums to window - busy;
* ``idle_in_step``: the same pieces, only those under a ``sched.step``
  span, by innermost span; ``step_spans`` counts the ``sched.step``
  spans that started in the window;
* ``scope_s``: device self time by named scope (``unscoped`` for the
  rest) for each engine program kind of ``programs.json``.  The trace's
  op events carry no metadata, so an op's scope comes from the
  ``op_name`` metadata of its executable's HLO text: each module run is
  mapped to the text, among those of its module name, that names all of
  its ops.  A kind with a run that no text maps reads ``None``.

``tick_exposed_ms`` and ``attention_ms_per_step`` at the end read these.

    python3 benchmarks/serving/phases.py --workload <cell> --seed <n> \\
        --seconds <s> [--keep DIR] [--cut]

runs the cell once, traced, through ``harness.run_cell`` as ``run.py
--trace 1`` does, with the program's spans loaded from the trace and the
harness's per-dispatch bookkeeping inside a ``record`` span, and prints
its result line with a ``phases`` object added.  ``--keep`` writes the
profile and the HLO texts of the engine's programs to DIR, gzipped;
``--cut`` serves one layer on two slots, the size of the test profile in
``tests/data/v5e_phases``.
"""
from __future__ import annotations

import argparse
import bisect
import contextlib
import copy
import functools
import gzip
import heapq
import json
import re
import sys
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

T_START = time.perf_counter()
HERE = Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

from trace_reduce import (WINDOW, Event, _clip, _matches,  # noqa: E402
                          _self_times, _union, program_names)

RECORD = "record"              # the harness's bookkeeping before a dispatch
OUTSIDE = "no host span"
UNSCOPED = "unscoped"


def span_config() -> Dict:
    return json.loads((HERE / "spans.json").read_text())


def program_spans(cfg: Optional[Dict] = None) -> List[str]:
    cfg = cfg or span_config()
    return [cfg["step"]] + list(cfg["phases"])


def log(msg: str) -> None:
    print(f"[phases] {msg}", file=sys.stderr, flush=True)


def _window(host: Sequence[Event]) -> Tuple[int, int]:
    wins = [(s, e) for n, s, e in host if n == WINDOW]
    if not wins:
        raise ValueError(f"no host span named {WINDOW!r} in the trace")
    return wins[-1]


# --------------------------------------------------------------------------
# idle time by innermost span
# --------------------------------------------------------------------------
def _pieces(spans: Sequence[Event], lo: int, hi: int,
            step: str) -> List[Tuple[int, int, str, bool]]:
    """``[lo, hi)`` cut at every span boundary: ``(start, end, innermost
    span, under a step span)`` per piece.  Of the spans covering a piece
    the innermost is the one that started last (of two that started
    together, the one that ends first)."""
    marks = []
    for i, (_, s, e) in enumerate(spans):
        s, e = max(s, lo), min(e, hi)
        if e > s:
            marks.append((s, 1, i, e))
            marks.append((e, 0, i, e))
    marks.sort()                          # at one time, ends before starts
    heap: List[Tuple[int, int, int]] = []
    alive, steps = set(), 0
    out, t = [], lo

    def innermost() -> str:
        while heap and heap[0][2] not in alive:
            heapq.heappop(heap)
        return spans[heap[0][2]][0] if heap else OUTSIDE

    for x, starts, i, e in marks:
        if x > t:
            out.append((t, x, innermost(), steps > 0))
            t = x
        is_step = spans[i][0] == step
        if starts:
            alive.add(i)
            heapq.heappush(heap, (-x, e, i))
            steps += is_step
        else:
            alive.discard(i)
            steps -= is_step
    if hi > t:
        out.append((t, hi, innermost(), steps > 0))
    return out


def _gaps(busy: Sequence[Tuple[int, int]], lo: int,
          hi: int) -> List[Tuple[int, int]]:
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def idle_split(trace: Dict, window: Optional[Tuple[int, int]] = None,
               span_names: Optional[set] = None,
               step: str = "sched.step") -> Dict:
    """Idle seconds of ``trace`` (as ``trace_reduce.load`` gives it) by
    innermost host span, mean over chips: ``idle_by_span`` over the whole
    window, ``idle_in_step`` over the pieces under a ``step`` span, and
    ``step_spans``, the ``step`` spans that started in the window."""
    host = trace["host"]
    lo, hi = window or _window(host)
    spans = [ev for ev in host if ev[0] != WINDOW
             and (span_names is None or ev[0] in span_names)]
    pieces = _pieces(spans, lo, hi, step)
    by: Dict[str, float] = defaultdict(float)
    in_step: Dict[str, float] = defaultdict(float)
    chips = trace["chips"]
    for lines in chips.values():
        mods = _clip(lines.get("XLA Modules", []), lo, hi)
        ops = _clip(lines.get("XLA Ops", []), lo, hi) or mods
        j = 0
        for a, b in _gaps(_union([(s, e) for _, s, e in ops]), lo, hi):
            while j < len(pieces) and pieces[j][1] <= a:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < b:
                s, e, name, under = pieces[k]
                ns = min(e, b) - max(s, a)
                by[name] += ns
                if under:
                    in_step[name] += ns
                k += 1
    n = max(len(chips), 1)
    return {"idle_by_span": {k: v / n / 1e9 for k, v in by.items()},
            "idle_in_step": {k: v / n / 1e9 for k, v in in_step.items()},
            "step_spans": sum(1 for nm, s, _ in host
                              if nm == step and lo <= s < hi)}


# --------------------------------------------------------------------------
# device time by named scope
# --------------------------------------------------------------------------
_INSTR = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def _scope_of(op_name: str, scopes: Sequence[str]) -> Optional[str]:
    """The innermost of ``scopes`` on an ``op_name`` path."""
    for part in reversed(op_name.split("/")):
        if part in scopes:
            return part
    return None


def hlo_scopes(text: str, scopes: Sequence[str]
               ) -> Tuple[str, Dict[str, Optional[str]]]:
    """The module name of an HLO text and the named scope of each of its
    instructions: the one on its own ``op_name``, else, for a fusion, that
    of its fused computation's root."""
    module, comp = "", None
    own: Dict[str, Optional[str]] = {}
    calls: Dict[str, str] = {}
    root: Dict[str, str] = {}
    for line in text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
        elif line and not line[0].isspace() and line.rstrip().endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY ") else 0]
            comp = comp.lstrip("%")
        elif comp is not None and (m := _INSTR.match(line)):
            name = m.group(2)
            op = _OP_NAME.search(line)
            own[name] = _scope_of(op.group(1), scopes) if op else None
            c = _CALLS.search(line)
            if c:
                calls[name] = c.group(1)
            if m.group(1):
                root[comp] = name

    def scope(name: str, depth: int = 0) -> Optional[str]:
        s = own.get(name)
        if s is None and name in calls and depth < 8:
            r = root.get(calls[name])
            if r is not None:
                s = scope(r, depth + 1)
        return s

    return module, {n: scope(n) for n in own}


@functools.lru_cache(maxsize=None)
def _instr(op: str) -> str:
    """``fusion.190`` from an op event's HLO text or its label."""
    return op.partition(" = ")[0].strip().lstrip("%")


def scope_times(trace: Dict, texts: Sequence[str],
                window: Optional[Tuple[int, int]] = None,
                programs: Optional[Dict[str, List[str]]] = None,
                scopes: Optional[Sequence[str]] = None) -> Dict:
    """Device self seconds by named scope for each program kind, mean
    over chips (``scope_s``); ``scope_runs`` and ``scope_unmapped`` count
    the module runs of each kind, and those no text maps."""
    programs = programs or program_names()
    scopes = scopes or span_config()["scopes"]
    parsed: Dict[str, List[Dict[str, Optional[str]]]] = defaultdict(list)
    for t in texts:
        mod, m = hlo_scopes(t, scopes)
        parsed[mod].append(m)
    lo, hi = window or _window(trace["host"])
    ns: Dict[str, Dict[str, float]] = {k: defaultdict(float)
                                       for k in programs}
    runs: Dict[str, int] = defaultdict(int)
    unmapped: Dict[str, int] = defaultdict(int)
    chosen: Dict[Tuple[str, frozenset], Optional[Dict]] = {}
    chips = trace["chips"]
    for lines in chips.values():
        mods = sorted(_clip(lines.get("XLA Modules", []), lo, hi),
                      key=lambda ev: ev[1])
        starts = [s for _, s, _ in mods]
        groups: Dict[int, List[Event]] = defaultdict(list)
        for ev in _clip(lines.get("XLA Ops", []), lo, hi):
            i = bisect.bisect_right(starts, ev[1]) - 1
            if i >= 0 and ev[1] < mods[i][2]:
                groups[i].append(ev)
        for i, evs in groups.items():
            name = mods[i][0]
            kind = next((k for k, p in programs.items()
                         if _matches(name, p)), None)
            if kind is None:
                continue
            runs[kind] += 1
            key = (name.split("(")[0], frozenset(_instr(n) for n, _, _ in evs))
            if key not in chosen:
                cands = [m for m in parsed.get(key[0], [])
                         if key[1] <= m.keys()]
                ways = {tuple(m[x] for x in sorted(key[1])) for m in cands}
                chosen[key] = cands[0] if len(ways) == 1 else None
            m = chosen[key]
            if m is None:
                unmapped[kind] += 1
                continue
            for label, t in _self_times(evs).items():
                ns[kind][m[_instr(label.split(" ")[0])] or UNSCOPED] += t
    n = max(len(chips), 1)
    for kind in programs:
        if unmapped[kind]:
            log(f"{unmapped[kind]} of {runs[kind]} runs of the {kind} "
                f"programs match no HLO text (or more than one, with "
                f"different scopes): no scope times for {kind}")
    return {"scope_s": {k: (None if unmapped[k] else
                            {s: v / n / 1e9 for s, v in d.items()})
                        for k, d in ns.items()},
            "scope_runs": {k: runs[k] / n for k in programs},
            "scope_unmapped": {k: unmapped[k] / n for k in programs}}


def hlo_texts(engine, prefill_chunk: int) -> List[str]:
    """Optimized HLO of the engine's programs: the prefill chunk and each
    decode horizon it compiled, lowered against the live arguments'
    shapes and shardings (the compilation cache serves them)."""
    import jax
    import jax.numpy as jnp
    S = engine.max_seqs
    sh = getattr(engine, "_rep", None)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    i32 = jnp.int32
    fns = [(engine._prefill, (arg((S, prefill_chunk), i32), arg((S,), i32)))]
    fns += [(engine._decode_many[k],
             (arg((S,), i32), arg((S,), jnp.bool_), arg((S,), i32)))
            for k in sorted(engine._decode_many)]
    return [f.lower(engine.params, engine.state, *a).compile().as_text()
            for f, a in fns]


# --------------------------------------------------------------------------
# the per-layer readers
# --------------------------------------------------------------------------
def tick_exposed_ms(run) -> Optional[float]:
    """Device idle inside ``sched.step`` spans, less the pieces under the
    harness's ``record``, per ``sched.step`` span that started in the
    window."""
    d = run.device or {}
    idle, n = d.get("idle_in_step"), d.get("step_spans")
    if idle is None or not n:
        return None
    return 1e3 * sum(v for k, v in idle.items() if k != RECORD) / n


def attention_ms_per_step(run) -> Optional[float]:
    """Device self time of the ``attention``-scoped ops of the decode
    programs / fused decode steps run."""
    sc = ((run.device or {}).get("scope_s") or {}).get("decode")
    n = run.engine_stats.get("decode_steps", 0)
    if not sc or not sc.get("attention") or not n:
        return None
    return 1e3 * sc["attention"] / n


def longest_steps(run, n: int = 3, step: str = "sched.step") -> List[Dict]:
    """The ``n`` longest ``step`` spans begun in the window, by the
    program's own trace recorder, each with the seconds of the spans of
    its tick inside it."""
    spans = [e for e in run.events
             if e.get("type") == "span" and run.in_window(e["ts"])]
    out = []
    for st in sorted((e for e in spans if e["name"] == step),
                     key=lambda e: -e["dur"])[:n]:
        inner: Dict[str, float] = defaultdict(float)
        for e in spans:
            if (e["name"] != step and e.get("tick") == st["tick"]
                    and st["ts"] <= e["ts"] <= st["ts"] + st["dur"]):
                inner[e["name"]] += e["dur"]
        out.append({"tick": st["tick"], "s": st["dur"],
                    "phases": dict(sorted(inner.items(),
                                          key=lambda kv: -kv[1]))})
    return out


# --------------------------------------------------------------------------
# one traced run through the harness
# --------------------------------------------------------------------------
def _annotated(name: str, fn):
    import jax

    def inner(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)
    return inner


def _gz(path: Path, data: bytes) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(path, "wb", compresslevel=9) as f:
        f.write(data)


@contextlib.contextmanager
def _hooks(keep: Optional[Path]):
    """``harness.run_cell`` as it is, with the trace loaded with the
    program's spans, the extra reductions added to its device numbers,
    the bookkeeping of ``harness.Recorder`` inside a ``record`` span, and
    the run's data kept."""
    import harness
    import trace_reduce
    cfg = span_config()
    extra = set(program_spans(cfg)) | {RECORD}
    got: Dict = {}
    saved = (trace_reduce.load, trace_reduce.reduce, harness.Recorder,
             harness.RunData)
    load0, reduce0, recorder0, rundata0 = saved

    def load(path, host_names=None):
        if keep is not None:
            _gz(keep / "profile.xplane.pb.gz",
                Path(trace_reduce.find_xplane(path)).read_bytes())
        return load0(path, None if host_names is None
                     else set(host_names) | extra)

    def reduce(trace, window=None, programs=None, span_names=None):
        out = reduce0(trace, window, programs, span_names)
        t0 = time.perf_counter()
        names = None if span_names is None else set(span_names) | extra
        out.update(idle_split(trace, window, names, cfg["step"]))
        served = got["served"]
        texts = hlo_texts(served.engine, served.sched.prefill_chunk)
        if keep is not None:
            _gz(keep / "hlo.json.gz", json.dumps(texts).encode())
        out.update(scope_times(trace, texts, window, programs,
                               cfg["scopes"]))
        log(f"spans and scopes read in {time.perf_counter() - t0:.3f}s")
        return out

    class Recorder(recorder0):
        def __init__(self, served, trace):
            super().__init__(served, trace)
            got["served"] = served
            eng = served.engine
            for attr in ("prefill_chunk", "decode_many"):
                setattr(eng, attr, _annotated(RECORD, getattr(eng, attr)))

    def run_data(*a, **k):
        got["run"] = rundata0(*a, **k)
        return got["run"]

    trace_reduce.load, trace_reduce.reduce = load, reduce
    harness.Recorder, harness.RunData = Recorder, run_data
    try:
        yield got
    finally:
        (trace_reduce.load, trace_reduce.reduce, harness.Recorder,
         harness.RunData) = saved


def cut(cell):
    """The cell at the test profile's size: one layer, two slots."""
    cell = copy.deepcopy(cell)
    cell.hp["num_hidden_layers"] = 1
    dep = cell.hp["deployment"]
    dep["slots"] = 2
    dep["pool_pages"] = 1 + 4 * -(-cell.mix["max_context"]
                                  // dep["page_size"])
    return cell


def traced_run(cell, seed: int, seconds: float,
               keep: Optional[Path] = None) -> Dict:
    """One traced run of ``cell``: the harness's result with ``phases``."""
    import harness
    with _hooks(keep) as got:
        res = harness.run_cell(cell, seed, seconds, True, T_START)
    run = got["run"]
    d = run.device
    ph = {k: d[k] for k in ("idle_by_span", "idle_in_step", "step_spans",
                            "scope_s", "scope_runs", "scope_unmapped")}
    ph["tick_exposed_ms"] = tick_exposed_ms(run)
    ph["attention_ms_per_step"] = attention_ms_per_step(run)
    ph["decode_steps"] = run.engine_stats.get("decode_steps", 0)
    ph["longest_steps"] = longest_steps(run)
    res["phases"] = ph
    idle = sorted(d["idle_by_span"].items(), key=lambda kv: -kv[1])
    log(f"idle by innermost span: {idle} (sum "
        f"{sum(v for _, v in idle)} s, window - busy "
        f"{d['window_s'] - d['busy_s']} s)")
    step = sorted(d["idle_in_step"].items(), key=lambda kv: -kv[1])
    log(f"idle inside {d['step_spans']} sched.step spans: {step}")
    for kind, sc in d["scope_s"].items():
        if sc is not None:
            log(f"{kind} device time by scope: "
                f"{sorted(sc.items(), key=lambda kv: -kv[1])} (program "
                f"{d['program_s'].get(kind, 0.0)} s)")
    log(f"tick_exposed_ms {ph['tick_exposed_ms']} attention_ms_per_step "
        f"{ph['attention_ms_per_step']}")
    log(f"longest sched.step spans: {ph['longest_steps']}")
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", type=Path)
    ap.add_argument("--cut", action="store_true")
    args = ap.parse_args(argv)
    import harness
    from run import check_chips, use_compile_cache
    cell = harness.load_cell(args.workload)
    if args.cut:
        cell = cut(cell)
    check_chips(cell.chips)
    use_compile_cache()
    res = traced_run(cell, args.seed, args.seconds, args.keep)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
