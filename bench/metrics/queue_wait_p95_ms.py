"""p95 over the requests due in the window of the time each waited
before its own placement started: from its due time to the start of the
engine's ``engine.prefill`` span that placed it.

Every request whose first token was stamped inside the window (from the
pre-roll or the window), taken in the order of those stamps, pairs one
to one with the ``engine.prefill`` spans that end inside the window,
taken in the order of their ends: a placement stamps its request's first
token just before its span closes, and a resume, which stamps none, is
an ``engine.resume``.  The wait of a request due in the window is then
its first token's time from its due time less its own span's duration:
each difference is taken on one clock (the host's stamps, the trace's
span), so no mapping between the clocks is needed.  Nothing without a
trace, without the spans, or where the two lists differ in length."""

import numpy as np

SPAN = "engine.prefill"


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    spans = sorted((e for e in t.events
                    if e.kind == "host" and e.where == t.host_line
                    and e.name == SPAN and t.lo <= e.end_ns <= t.hi),
                   key=lambda e: e.end_ns)
    firsts = sorted((r for r in ctx["requests"]
                     if r["t_first"] is not None
                     and 0.0 <= r["t_first"] <= ctx["t_close"]),
                    key=lambda r: r["t_first"])
    if not spans or len(spans) != len(firsts):
        return None
    waits = [r["t_first"] - r["arrival"] - 1e-9 * s.dur_ns
             for r, s in zip(firsts, spans) if "arrival" in r]
    if not waits:
        return None
    return 1e3 * float(np.percentile(waits, 95))
