"""Share of the rows that the prefill program dispatched that are not
prompt tokens: 1 - real prompt tokens prefilled in the window (the
engine's ``prefill_tokens`` counter) / rows dispatched, where each
execution of the prefill program in the trace, on the first chip,
dispatched as many rows as its widest nibble-kernel call (the leading
dimension of its result).  A prefill padded to the slot budget shows
here; one cut to the prompt's length does not."""

from bench import trace as tr

PROGRAM = "jit_prefill_into_slot"
KERNEL = r"^fused_nibble_matmul_pallas(\.\d+)?$"


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    runs = [x for x in t.executions(PROGRAM) if x.where == t.devices[0]]
    dispatched = sum(max((tr.rows(op.out) for op in t.ops_within(x, KERNEL)),
                         default=0) for x in runs)
    tokens = ctx["stats"]["prefill_tokens"]
    if not dispatched or not tokens:
        return None
    return 100.0 * (1.0 - tokens / dispatched)
