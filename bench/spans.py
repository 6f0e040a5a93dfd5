"""Reduction of the program's own host spans in a profiler trace of the
measured window: the readings a per-layer metric of split training
would give, and the window's idle gaps named after what the scientist
was doing.

The program's spans (``vfl.*``, named in ``repro.federation.spans``) are
``jax.profiler.TraceAnnotation``s on the host planes of the trace, one
host line per thread, each with its stats (``party``, ``step``,
``seq``, ...).  ``bench.trace.Trace`` keeps only the benchmark's own
``bench.*`` spans, so this module reads the file itself:

    python3 bench/spans.py TRACE.xplane.pb

prints one JSON line: the readings, the scientist's spans per step, the
steps' coverage and the ten longest idle gaps, each named after the
innermost span on the scientist's side that holds it (a ``bench.*``
span, or a program span whose ``party`` is the scientist; the owners'
threads run beside it, so their spans never name a gap).

Every reading uses only the spans inside ``bench.window`` and is None,
never 0, where its spans are absent (a program from before it had
spans reads nothing).
"""
from __future__ import annotations

import bisect
import copy
import json
import os
import statistics
import sys
from collections import defaultdict
from typing import NamedTuple

PROGRAM = "vfl."
WINDOW = "bench.window"
SCIENTIST = "scientist"


class HostSpan(NamedTuple):
    """One of the program's spans: start and end in nanoseconds, the
    host line (thread) that ran it, and its stats."""
    name: str
    start: float
    end: float
    thread: object
    stats: dict

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


def program_span_names():
    """The program's span names (``repro.federation.spans``), or None
    for a program from before it had spans."""
    try:
        from repro.federation import spans
    except ImportError:
        return None
    return spans


class ProgramSpans:
    """The program's spans of one trace, and the window they are read
    in (nanoseconds, as the trace has them)."""

    def __init__(self, spans, window):
        self.spans = sorted(spans, key=lambda sp: sp.start)
        self.lo, self.hi = window

    @classmethod
    def from_file(cls, path: str) -> "ProgramSpans":
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        spans, window = [], []
        for plane in data.planes:
            if plane.name.startswith("/device:"):
                continue
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(PROGRAM):
                        spans.append(HostSpan(
                            e.name, e.start_ns, e.start_ns + e.duration_ns,
                            (plane.name, i), dict(e.stats)))
                    elif e.name == WINDOW:
                        window.append((e.start_ns,
                                       e.start_ns + e.duration_ns))
        if not window:
            raise ValueError(f"the trace has no {WINDOW!r} span")
        return cls(spans, window[-1])

    def named(self, name: str):
        """The spans named ``name`` that lie inside the window, in order
        of start."""
        return [sp for sp in self.spans if sp.name == name
                and self.lo <= sp.start and sp.end <= self.hi]

    def self_seconds(self, name: str, less=()) -> float:
        """Seconds of the window's spans named ``name``, summed, less
        the time that spans named in ``less`` cover inside each on the
        same thread (spans of one thread nest)."""
        children = defaultdict(list)
        for sp in self.spans:
            if sp.name in less:
                children[sp.thread].append(sp)
        starts = {th: [c.start for c in cs] for th, cs in children.items()}
        total = 0.0
        for sp in self.named(name):
            cs = children.get(sp.thread, [])
            at = bisect.bisect_left(starts.get(sp.thread, []), sp.start)
            covered, reach = 0.0, sp.start
            while at < len(cs) and cs[at].start < sp.end:
                s, e = max(cs[at].start, reach), min(cs[at].end, sp.end)
                if e > s:
                    covered += e - s
                    reach = e
                at += 1
            total += sp.end - sp.start - covered
        return total * 1e-9

    def idle_gaps(self, tr, k: int = 10):
        """``tr.idle_gaps(k)`` of the ``bench.trace.Trace`` of the same
        file, each gap named after the innermost ``bench.*`` span or
        scientist's span that holds it."""
        named = copy.copy(tr)
        named.spans = list(tr.spans) + [
            (sp.name, sp.start, sp.end) for sp in self.spans
            if sp.stats.get("party") == SCIENTIST]
        return named.idle_gaps(k)


# ------------------------------------------------------------ readings


def _steps(ps, names) -> int:
    return len(ps.named(names.STEP))


def session_step_ms(ps):
    """The scientist's median step: the median ``vfl.step``, in ms."""
    names = program_span_names()
    steps = ps.named(names.STEP) if names else []
    if not steps:
        return None
    return 1e3 * statistics.median(sp.seconds for sp in steps)


def cut_wait_ms_per_step(ps):
    """The scientist blocked on the owners: the self time of its
    ``vfl.cut_exchange`` spans less the ``vfl.wire.unpack`` inside, per
    step, in ms."""
    names = program_span_names()
    if names is None or not ps.named(names.CUT_EXCHANGE):
        return None
    steps = _steps(ps, names)
    if not steps:
        return None
    wait = ps.self_seconds(names.CUT_EXCHANGE, less=(names.WIRE_UNPACK,))
    return 1e3 * wait / steps


def owner_step_ms(ps):
    """An owner's critical path: the median ``vfl.owner.cut_grad`` over
    steps and owners (backward, update, the next forward and its send),
    in ms."""
    names = program_span_names()
    spans = ps.named(names.OWNER_CUT_GRAD) if names else []
    if not spans:
        return None
    return 1e3 * statistics.median(sp.seconds for sp in spans)


def frame_us_per_step(ps):
    """Framing and CRC, all parties: the self time of ``vfl.wire.pack``
    less its ``vfl.host_read`` (counted by ``host_read_ms_per_step``),
    plus ``vfl.wire.unpack``, per step, in us."""
    names = program_span_names()
    if names is None or not (ps.named(names.WIRE_PACK)
                             or ps.named(names.WIRE_UNPACK)):
        return None
    steps = _steps(ps, names)
    if not steps:
        return None
    frame = (ps.self_seconds(names.WIRE_PACK, less=(names.HOST_READ,))
             + ps.self_seconds(names.WIRE_UNPACK))
    return 1e6 * frame / steps


def host_read_ms_per_step(ps):
    """Device-to-host reads, all parties: the ``vfl.host_read`` spans
    (each cut and cut gradient framed for the wire, each loss scalar)
    per step, in ms."""
    names = program_span_names()
    reads = ps.named(names.HOST_READ) if names else []
    steps = _steps(ps, names) if names else 0
    if not reads or not steps:
        return None
    return 1e3 * sum(sp.seconds for sp in reads) / steps


#: each reading under the name of the per-layer metric it would be
READINGS = {
    "session_step_ms.mlp": session_step_ms,
    "cut_wait_ms_per_step.mlp": cut_wait_ms_per_step,
    "owner_step_ms.mlp": owner_step_ms,
    "frame_us_per_step.mlp": frame_us_per_step,
    "host_read_ms_per_step.mlp": host_read_ms_per_step,
}


def step_coverage(ps):
    """``(steps, children)``: the share of ``bench.fit`` less
    ``vfl.fit_start`` and ``vfl.fit_end`` that the ``vfl.step`` spans
    cover, and the share of the median step that its scientist-side
    children cover."""
    names = program_span_names()
    steps = ps.named(names.STEP) if names else []
    if not steps:
        return None, None
    lo, hi = steps[0].start, steps[-1].end
    for sp in ps.named(names.FIT_START):
        lo = max(lo, sp.end)
    for sp in ps.named(names.FIT_END):
        hi = min(hi, sp.start)
    covered = sum(min(sp.end, hi) - max(sp.start, lo) for sp in steps
                  if min(sp.end, hi) > max(sp.start, lo))
    mid = sorted(steps, key=lambda sp: sp.end - sp.start)[len(steps) // 2]
    kids = [sp for sp in ps.spans if sp.thread == mid.thread
            and sp.name != names.STEP and mid.start <= sp.start
            and sp.end <= mid.end]
    reach, child = mid.start, 0.0
    for sp in kids:
        if sp.end > reach:
            child += sp.end - max(sp.start, reach)
            reach = sp.end
    return covered / max(hi - lo, 1), child / (mid.end - mid.start)


def scientist_ms_per_step(ps):
    """Each scientist's span other than ``vfl.step``: its summed
    duration in the window per step, in ms."""
    names = program_span_names()
    steps = _steps(ps, names) if names else 0
    if not steps:
        return {}
    acc = defaultdict(float)
    for sp in ps.spans:
        if (sp.stats.get("party") == SCIENTIST and sp.name != names.STEP
                and ps.lo <= sp.start and sp.end <= ps.hi):
            acc[sp.name] += sp.seconds
    return {n: 1e3 * s / steps for n, s in sorted(acc.items())}


def report(path: str) -> dict:
    from bench import trace
    ps = ProgramSpans.from_file(path)
    names = program_span_names()
    steps = _steps(ps, names) if names else 0
    coverage, children = step_coverage(ps)
    return {
        "steps": steps,
        "readings": {k: fn(ps) for k, fn in READINGS.items()},
        "host_reads_per_step": (len(ps.named(names.HOST_READ)) / steps
                                if steps else None),
        "step_coverage": coverage, "median_step_children": children,
        "scientist_ms_per_step": scientist_ms_per_step(ps),
        "idle_gaps": ps.idle_gaps(trace.Trace.from_file(path), 10),
    }


if __name__ == "__main__":
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "src")]
    if len(sys.argv) != 2:
        sys.exit("usage: python3 bench/spans.py TRACE.xplane.pb")
    print(json.dumps(report(sys.argv[1])))
