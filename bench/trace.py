"""Reduction of a profiler trace of the window to device times.

The profiler writes one ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Each chip is a plane ``/device:TPU:<n>`` whose line
``XLA Ops`` holds one event per operation executed and whose line
``XLA Modules`` holds one event per program executed (a jitted function
``f`` runs as the module ``jit_f``).  The host plane ``/host:CPU`` has a
line per thread; the Python thread carries the harness's spans
(``bench.window`` around the measured window, ``bench.step`` around each
``Engine.step``) and JAX's own events inside them.

From these: the device's busy time (the union of its operations'
intervals inside the window, averaged over chips), the device time of
each program and of matched operations, and the idle gaps between
operations, each named by the innermost host event open at its middle.
The reduction works on plain ``Event`` records, so a small recorded
trace (``bench/testdata``) tests it without a chip.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

__all__ = ["Event", "Trace", "load", "from_events", "rows"]

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# gaps shorter than this are the device's own issue latency, not idling
# worth naming
MIN_GAP_NS = 20_000


@dataclasses.dataclass(frozen=True)
class Event:
    kind: str            # "op", "module" or "host"
    where: str           # device plane name, or host thread line name
    name: str
    start_ns: float
    dur_ns: float
    out: str = ""        # an operation's result type, e.g. "bf16[2048,9728]"

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _base(name: str) -> str:
    """An operation's name without XLA's numeric suffix."""
    return re.sub(r"\.\d+$", "", name)


def op_name(text: str) -> str:
    """The instruction's name from an ``XLA Ops`` event, whose name is
    the instruction's HLO text (``%fusion.12 = bf16[...] fusion(...)``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_out(text: str) -> str:
    """The instruction's result type from its HLO text, without its
    layout: ``bf16[2048,9728]``; empty where the text has none."""
    if " = " not in text:
        return ""
    return re.split(r"[{ ]", text.split(" = ", 1)[1], maxsplit=1)[0]


def rows(out: str) -> int:
    """The leading dimension of a result type (``bf16[2048,9728]`` ->
    2048); 0 where it has none."""
    m = re.match(r"^\w+\[(\d+)", out)
    return int(m.group(1)) if m else 0


def _leaves(events) -> list:
    """The events that hold no other event inside them (a ``while`` op's
    event spans every operation of its body)."""
    evs = sorted(events, key=lambda e: (e.start_ns, -e.dur_ns))
    container = set()
    stack = []
    for i, e in enumerate(evs):
        while stack and evs[stack[-1]].end_ns <= e.start_ns:
            stack.pop()
        if stack:
            container.add(stack[-1])
        stack.append(i)
    return [e for i, e in enumerate(evs) if i not in container]


class Trace:
    """Device times inside the window of one traced run."""

    def __init__(self, events):
        self.events = list(events)
        spans = [e for e in self.events
                 if e.kind == "host" and e.name == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
        w = max(spans, key=lambda e: e.dur_ns)
        self.lo, self.hi = w.start_ns, w.end_ns
        self.host_line = w.where
        self.devices = sorted({e.where for e in self.events
                               if e.kind == "op"})

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def _inside(self, kind: str, where: str | None = None):
        for e in self.events:
            if (e.kind == kind and (where is None or e.where == where)
                    and e.end_ns > self.lo and e.start_ns < self.hi):
                yield e

    def _clip(self, e) -> tuple:
        return max(e.start_ns, self.lo), min(e.end_ns, self.hi)

    def busy_intervals(self, device: str) -> list:
        return _union(self._clip(e) for e in self._inside("op", device))

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        total = sum(e - s for d in self.devices
                    for s, e in self.busy_intervals(d))
        return total * 1e-9 / len(self.devices)

    def executions(self, name: str) -> list:
        """The executions (module events) of the program ``name`` (e.g.
        ``jit_chunk``) inside the window, on every chip."""
        pat = re.compile(rf"^{re.escape(name)}(\(\d+\))?$")
        return [e for e in self._inside("module") if pat.match(e.name)]

    def program(self, name: str) -> tuple:
        """``(seconds, executions)`` of the program ``name`` inside the
        window, summed over the chips."""
        runs = self.executions(name)
        return (sum(t - s for s, t in map(self._clip, runs)) * 1e-9,
                len(runs))

    def ops_within(self, execution, pattern: str) -> list:
        """The operations whose name matches ``pattern`` that ran during
        ``execution`` (a module event) on its chip."""
        pat = re.compile(pattern)
        return [e for e in self._inside("op", execution.where)
                if pat.match(e.name) and e.start_ns >= execution.start_ns
                and e.end_ns <= execution.end_ns]

    def ops_s(self, pattern: str) -> tuple:
        """``(seconds, count)`` of the operations whose name matches the
        regular expression ``pattern``, summed over the chips."""
        pat = re.compile(pattern)
        secs, n = 0.0, 0
        for e in self._inside("op"):
            if pat.match(e.name):
                s, t = self._clip(e)
                secs += (t - s) * 1e-9
                n += 1
        return secs, n

    def idle_gaps(self) -> list:
        """``(host activity, seconds)`` for every gap of at least
        ``MIN_GAP_NS`` between the first chip's operations inside the
        window; the activity is the innermost host event on the
        harness's thread open at the gap's middle."""
        if not self.devices:
            return []
        busy = self.busy_intervals(self.devices[0])
        edges = [self.lo] + [x for iv in busy for x in iv] + [self.hi]
        # one thread's events nest, so a sweep with a stack finds the
        # innermost event open at each gap's middle
        host = sorted(self._inside("host", self.host_line),
                      key=lambda e: (e.start_ns, -e.dur_ns))
        stack, i, out = [], 0, []
        for s, e in zip(edges[::2], edges[1::2]):
            if e - s < MIN_GAP_NS:
                continue
            mid = (s + e) / 2
            while i < len(host) and host[i].start_ns <= mid:
                while stack and stack[-1].end_ns < host[i].start_ns:
                    stack.pop()
                stack.append(host[i])
                i += 1
            while stack and stack[-1].end_ns < mid:
                stack.pop()
            name = stack[-1].name if stack else "(no host event)"
            out.append((name, (e - s) * 1e-9))
        return out

    def breakdown(self, top: int = 10) -> dict:
        """The operations that took most device time (those that hold no
        other, by name without the numeric suffix) and the idle seconds
        by host activity."""
        ops = collections.Counter()
        for e in _leaves(self._inside("op")):
            s, t = self._clip(e)
            ops[_base(e.name)] += (t - s) * 1e-9
        idle = collections.Counter()
        for name, secs in self.idle_gaps():
            idle[name] += secs
        return {"device_ops": [[n, s] for n, s in ops.most_common(top)],
                "idle_gaps": [[n, s] for n, s in idle.most_common(top)]}


def from_events(records) -> Trace:
    """A ``Trace`` from plain records (dicts with the ``Event`` fields),
    as kept in ``bench/testdata``."""
    return Trace(Event(**r) for r in records)


def _read_xplane(path: str):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                kind = {OPS_LINE: "op", MODULES_LINE: "module"}.get(
                    line.name)
                if kind is None:
                    continue
                for e in line.events:
                    if kind == "op":
                        yield Event(kind, plane.name, op_name(e.name),
                                    e.start_ns, e.duration_ns,
                                    op_out(e.name))
                    else:
                        yield Event(kind, plane.name, e.name, e.start_ns,
                                    e.duration_ns)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    yield Event("host", line.name, e.name, e.start_ns,
                                e.duration_ns)


def load(trace_dir: str) -> Trace | None:
    """The trace the profiler wrote under ``trace_dir``; ``None`` where
    it holds no device plane (a CPU run)."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        return None
    trace = Trace(_read_xplane(paths[-1]))
    return trace if trace.devices else None

