"""Device time of one decode step: the decode-chunk program's device
time in the window over its executions times the chunk's steps."""

PROGRAM = "jit_chunk"


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    secs, n = t.program(PROGRAM)
    if not n:
        return None
    return 1e3 * secs / (n * ctx["scfg"].decode_chunk)
