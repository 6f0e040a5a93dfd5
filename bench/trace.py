"""Reduction of a profiler trace of the measured window to the numbers
the per-layer metrics and ``breakdown`` read.

The trace is JAX's ``.xplane.pb``.  Device planes are named
``/device:TPU:<n>``; on each, the line ``XLA Modules`` holds one event
per execution of a compiled program (named after the jitted function,
e.g. ``jit_head_fwd(12)``) and ``XLA Ops`` one event per operation, an
operation that holds others (a ``while`` loop and its body) around
them.  The benchmark's own host spans (``jax.profiler.TraceAnnotation``
named ``bench.*``) sit on the host planes; ``bench.window`` bounds the
window.  A window of sLSTM scans holds millions of operations, so they
are kept as arrays.

- busy: the union of the operation intervals inside the window, per
  device, averaged over the devices;
- a program's device time: the sum of its module events inside the
  window, over all devices;
- idle gaps: the stretches of the window in which a device ran nothing,
  each named after the innermost ``bench.*`` span that covers it;
- top operations: the innermost operations (those that hold no other),
  by their summed time, under the program that ran them.
"""
from __future__ import annotations

import glob
from array import array
import os
import re
from collections import defaultdict

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES, OPS = "XLA Modules", "XLA Ops"
WINDOW = "bench.window"


class MissingModule(LookupError):
    """A program a metric names did not run in the window."""


def module_name(event_name: str) -> str:
    """``jit_head_fwd(12)`` -> ``head_fwd``."""
    name = re.sub(r"\(\d+\)$", "", event_name)
    return name[len("jit_"):] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[8]{0} fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


class _Ops:
    """One device's operations inside the window, sorted by start (an
    enclosing operation before those it holds)."""

    def __init__(self, names, ids, starts, ends, lo, hi):
        starts = np.clip(np.asarray(starts, np.float64), lo, hi)
        ends = np.clip(np.asarray(ends, np.float64), lo, hi)
        ids = np.asarray(ids, np.int64)
        keep = ends > starts
        order = np.lexsort((-ends[keep], starts[keep]))
        self.names = names
        self.ids = ids[keep][order]
        self.starts, self.ends = starts[keep][order], ends[keep][order]

    def merged(self):
        """The union of the intervals: (starts, ends) of its pieces."""
        if not len(self.starts):
            return self.starts, self.ends
        reach = np.maximum.accumulate(self.ends)
        new = np.ones(len(self.starts), bool)
        new[1:] = self.starts[1:] > reach[:-1]
        first = np.flatnonzero(new)
        last = np.r_[first[1:] - 1, len(self.starts) - 1]
        return self.starts[first], reach[last]

    def innermost(self):
        """Mask of the operations that hold no other."""
        leaf = np.ones(len(self.starts), bool)
        leaf[:-1] = self.starts[1:] >= self.ends[:-1]
        return leaf


class Trace:
    """The window of one trace.  Times are in nanoseconds internally;
    every number it returns is in seconds."""

    def __init__(self, modules, ops, spans, window=None):
        """``modules``/``ops``: ``{device: [(name, start, end)]}`` (or,
        for ``ops``, ready ``_Ops``); ``spans``: ``[(name, start,
        end)]`` host spans."""
        self.spans = spans
        if window is None:
            win = [(s, e) for n, s, e in spans if n == WINDOW]
            if not win:
                raise ValueError(f"the trace has no {WINDOW!r} span")
            window = win[-1]
        self.lo, self.hi = window
        self.modules = {d: self._inside(v) for d, v in modules.items()}
        self.ops = {d: v if isinstance(v, _Ops) else self._ops(v)
                    for d, v in ops.items()}
        if not self.ops:
            raise ValueError("the trace has no device operations")

    def _ops(self, events):
        intern = {}
        ids = [intern.setdefault(n, len(intern)) for n, _, _ in events]
        return _Ops(list(intern), ids, [s for _, s, _ in events],
                    [e for _, _, e in events], self.lo, self.hi)

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        modules, spans, raw = defaultdict(list), [], {}
        for plane in data.planes:
            device = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if device and line.name == MODULES:
                    modules[plane.name].extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
                elif device and line.name == OPS:
                    intern = {}
                    ids, starts, ends = array("q"), array("d"), array("d")
                    for e in line.events:
                        ids.append(intern.setdefault(e.name, len(intern)))
                        s = e.start_ns
                        starts.append(s)
                        ends.append(s + e.duration_ns)
                    raw[plane.name] = (list(intern), ids, starts, ends)
                elif not device:
                    spans.extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                        if e.name.startswith("bench."))
        win = [(s, e) for n, s, e in spans if n == WINDOW]
        if not win:
            raise ValueError(f"the trace has no {WINDOW!r} span")
        lo, hi = win[-1]
        ops = {d: _Ops(*r, lo, hi) for d, r in raw.items()}
        return cls(dict(modules), ops, spans, window=(lo, hi))

    def _inside(self, events):
        out = []
        for name, s, e in events:
            s, e = max(s, self.lo), min(e, self.hi)
            if e > s:
                out.append((name, s, e))
        return out

    # ------------------------------------------------------- readings

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def n_devices(self) -> int:
        return len(self.ops)

    def n_ops(self) -> int:
        return sum(len(o.starts) for o in self.ops.values())

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        total = 0
        for ops in self.ops.values():
            s, e = ops.merged()
            total += float(np.sum(e - s))
        return total * 1e-9 / self.n_devices

    def module_seconds(self, names) -> float:
        """Device seconds of the programs named ``names`` (summed over
        devices).  Raises ``MissingModule`` when any one of them did not
        run in the window: a program renamed or taken off the path fails
        loudly instead of dropping out of the sum."""
        names = set(names)
        missing = sorted(names - self.module_names())
        if missing:
            raise MissingModule(
                f"the programs {missing} did not run in the window; "
                f"programs seen: {sorted(self.module_names())}")
        total = sum(e - s for events in self.modules.values()
                    for n, s, e in events if module_name(n) in names)
        return total * 1e-9

    def module_calls(self, names) -> int:
        names = set(names)
        return sum(module_name(n) in names
                   for events in self.modules.values() for n, _, _ in events)

    def module_names(self) -> set:
        return {module_name(n) for events in self.modules.values()
                for n, _, _ in events}

    def top_ops(self, k: int = 10):
        """The ``k`` innermost operations that took most device time,
        summed over devices: ``[["program/op", seconds], ...]``."""
        acc = defaultdict(int)
        for dev, ops in self.ops.items():
            leaf = ops.innermost()
            mods = sorted((s, module_name(n))
                          for n, s, _ in self.modules.get(dev, []))
            at = np.searchsorted([s for s, _ in mods], ops.starts[leaf],
                                 side="right") - 1
            pairs = at * len(ops.names) + ops.ids[leaf]
            keys, inv = np.unique(pairs, return_inverse=True)
            sums = np.bincount(inv, weights=(ops.ends - ops.starts)[leaf])
            for key, t in zip(keys, sums):
                m, i = divmod(int(key), len(ops.names))
                prog = mods[m][1] if m >= 0 else "?"
                acc[prog + "/" + op_name(ops.names[i])] += float(t)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t * 1e-9] for n, t in top]

    def idle_gaps(self, k: int = 10):
        """The ``k`` longest idle stretches: ``[[what the host was in,
        seconds], ...]``, on the first device."""
        s, e = self.ops[sorted(self.ops)[0]].merged()
        lo = np.r_[self.lo, e]
        hi = np.r_[s, self.hi]
        length = hi - lo
        top = np.argsort(-length, kind="stable")[:k]
        return [[self.span_at((lo[i] + hi[i]) / 2), float(length[i]) * 1e-9]
                for i in top if length[i] > 0]

    def span_at(self, t) -> str:
        """The innermost ``bench.*`` span that holds time ``t``."""
        best = None
        for n, s, e in self.spans:
            if s <= t <= e and (best is None or e - s < best[2] - best[1]):
                best = (n, s, e)
        return best[0] if best else "(no span)"
