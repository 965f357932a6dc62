"""The host's own busy time per decode chunk in the window: over the
engine's ``engine.step`` spans inside the window, each one's duration
less the time its ``engine.wait`` (the host blocked on the device) and
``engine.idle`` (asleep before an arrival) spans cover, summed, over the
number of ``engine.decode`` spans inside the window.  While the host
blocks on every stage, this is also time in which the device idles.
Nothing without a trace or without the spans."""

STEP = "engine.step"
DECODE = "engine.decode"
OFF = ("engine.wait", "engine.idle")


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    host = [e for e in t.events
            if e.kind == "host" and e.where == t.host_line
            and e.start_ns >= t.lo and e.end_ns <= t.hi]
    steps = [e for e in host if e.name == STEP]
    chunks = sum(e.name == DECODE for e in host)
    if not steps or not chunks:
        return None
    off = sorted((e.start_ns, e.end_ns) for e in host if e.name in OFF)
    busy = 0.0
    for step in steps:
        covered, reach = 0.0, step.start_ns
        for s, e in off:
            s, e = max(s, reach), min(e, step.end_ns)
            if e > s:
                covered += e - s
                reach = e
        busy += step.dur_ns - covered
    return 1e-6 * busy / chunks
