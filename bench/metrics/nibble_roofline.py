"""Share of its roofline that the nibble Pallas kernel reaches in the
window: the least time the chip needs for the quantized projections of
the real token rows (``bench.roofline.nibble_least_s``: prefills at
their real prompt lengths, decode steps at their active slots) over the
summed device time of the kernel's operations.

Decode rows are known in total, not per step; they are spread evenly
over the steps.  The least time is convex in the rows of a call, so the
even spread gives the smallest sum, and the share is never overstated.
"""

from bench import roofline, window

KERNEL = r"^fused_nibble_matmul_pallas(\.\d+)?$"


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    kernel_s, calls = t.ops_s(KERNEL)
    if not calls:
        return None
    steps = window.decode_steps(ctx)
    rows = len(window.decoded(ctx, ctx["t_close"]))
    per_step = [rows // steps + (i < rows % steps) for i in range(steps)] \
        if steps else []
    dispatches = window.prefills(ctx, ctx["t_close"]) + per_step
    least = roofline.nibble_least_s(ctx["config"]["model"], dispatches,
                                    ctx["peaks"])
    return 100.0 * least / kernel_s
