"""Model FLOPs of the work done inside the window (prefilled prompts,
decoded tokens with the context each attends, and the head for each
token emitted) over the window's time, to the end of its last step (as
``tok_s`` takes it), and the chip's peak for the configuration's
projection precision: int8 for the integer modes, bf16 otherwise."""

from bench import roofline, window


def read(ctx):
    secs = ctx["t_close"]
    model = ctx["config"]["model"]
    prompts = window.prefills(ctx, secs)
    contexts = window.decoded(ctx, secs)
    flops = roofline.model_flops(model, prompts, contexts,
                                 len(prompts) + len(contexts))
    quant = ctx["config"]["serving"]["quant"]
    peak = (ctx["peaks"]["int8_ops"] if quant.startswith(("w8", "w4"))
            else ctx["peaks"]["bf16_flops"])
    return 100.0 * flops / secs / peak
