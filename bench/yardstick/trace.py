"""Reduction of a profiler trace to the benchmark's device numbers.

A traced run records one JAX profiler trace over its measured window. The
benchmark marks, on the host, the window (``bench.window``) and the calls
into two layers: the router (``bench.router``, around ``apply_wave``) and
the tuner (``bench.tuner``, around ``after_wave`` and
``observe_inserts``). On the chip's plane the trace has one event per
device operation (line ``XLA Ops``) and one per program run (line
``XLA Modules``, named ``jit_<function>(<id>)``).

Everything here works on plain ``(name, start_ns, end_ns)`` tuples, so it
is tested on a small fixed trace; ``from_xspace`` reads a real one.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)
Interval = Tuple[float, float]

SPAN_PREFIX = "bench."
WINDOW = "window"
IDLE_LABEL_NONE = "gateway_or_generator"  # no benchmark span was open


@dataclasses.dataclass
class Trace:
    ops: List[Event]                      # device operations, one chip
    programs: List[Event]                 # device program runs, one chip
    spans: Dict[str, List[Interval]]      # host spans by label ("router", ...)

    @property
    def window(self) -> Interval:
        (w,) = self.spans[WINDOW]
        return w


def program_name(event_name: str) -> str:
    """``jit_sinsert(42)`` -> ``sinsert``: the jitted function's name."""
    name = re.sub(r"\(\d+\)$", "", event_name)
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """``%fusion.12 = (u32[...]) fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def merge(intervals: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy(tr: Trace) -> List[Interval]:
    """Union of the device-operation intervals inside the window."""
    lo, hi = tr.window
    return merge(clip([(s, e) for _, s, e in tr.ops], lo, hi))


def busy_seconds(tr: Trace) -> float:
    return sum(e - s for s, e in busy(tr)) * 1e-9


def window_seconds(tr: Trace) -> float:
    lo, hi = tr.window
    return (hi - lo) * 1e-9


def program_times(tr: Trace) -> Dict[str, Tuple[int, float]]:
    """Runs and device seconds of each program that started in the window."""
    lo, hi = tr.window
    out: Dict[str, Tuple[int, float]] = {}
    for name, s, e in tr.programs:
        if lo <= s < hi:
            p = program_name(name)
            n, t = out.get(p, (0, 0.0))
            out[p] = (n + 1, t + (e - s) * 1e-9)
    return out


def ms_per_run(tr: Trace, program: str) -> Optional[float]:
    n, t = program_times(tr).get(program, (0, 0.0))
    return 1e3 * t / n if n else None


def top_ops(tr: Trace, k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` device operations that took most time in the window, each
    named ``<program>/<operation>``."""
    lo, hi = tr.window
    progs = sorted((s, e, program_name(n)) for n, s, e in tr.programs)
    starts = [s for s, _, _ in progs]
    tot: Dict[str, float] = {}
    for name, s, e in tr.ops:
        if not (lo <= s < hi):
            continue
        i = bisect.bisect_right(starts, s) - 1
        prog = progs[i][2] if i >= 0 and progs[i][1] >= s else "?"
        key = f"{prog}/{op_name(name)}"
        tot[key] = tot.get(key, 0.0) + (min(e, hi) - s) * 1e-9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:k]


def idle_gaps(tr: Trace, k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` longest stretches of the window in which the chip ran no
    operation, each labelled by the benchmark span open on the host at its
    middle (``router`` or ``tuner``; ``gateway_or_generator`` if none)."""
    lo, hi = tr.window
    b = busy(tr)
    edges = [lo] + [x for iv in b for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    open_spans = {name: merge(ivs) for name, ivs in tr.spans.items()
                  if name != WINDOW}
    starts = {name: [a for a, _ in ivs] for name, ivs in open_spans.items()}

    def label(t: float) -> str:
        for name, ivs in open_spans.items():
            i = bisect.bisect_right(starts[name], t) - 1
            if i >= 0 and t < ivs[i][1]:
                return name
        return IDLE_LABEL_NONE

    labelled = [(label(0.5 * (s + e)), (e - s) * 1e-9) for s, e in gaps]
    return sorted(labelled, key=lambda g: -g[1])[:k]


def from_xspace(path: str) -> Trace:
    """Reads a profiler trace (``*.xplane.pb``): the device operations and
    program runs of the first TPU, and the benchmark's host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: List[Event] = []
    programs: List[Event] = []
    spans: Dict[str, List[Interval]] = {}
    tpus = sorted((p for p in pd.planes if p.name.startswith("/device:TPU:")),
                  key=lambda p: p.name)
    if tpus:
        for line in tpus[0].lines:
            dest = {"XLA Ops": ops, "XLA Modules": programs}.get(line.name)
            if dest is not None:
                dest.extend((e.name, e.start_ns, e.end_ns) for e in line.events)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.setdefault(e.name[len(SPAN_PREFIX):], []).append(
                        (e.start_ns, e.end_ns))
    return Trace(ops=ops, programs=programs, spans=spans)
