"""Device time of the prefill program in the window per thousand real
prompt tokens it prefilled (the padding is in the time, not in the
tokens)."""

from bench import window

PROGRAM = "jit_prefill_into_slot"


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    secs, n = t.program(PROGRAM)
    tokens = sum(window.prefills(ctx, ctx["t_close"]))
    if not n or not tokens:
        return None
    return 1e6 * secs / tokens
