"""The work a served window requires, counted from the model's logical
shapes and the real token rows: the yardstick of the roofline and
utilisation metrics.

It counts what the model needs, not what an implementation does: rows
padded up to a tile, the nibble planes concatenated along K (which
doubles the MACs the kernel issues) and a weight re-quantized on every
call are the implementation's cost, not work.  So one yardstick judges
any kernel, an XLA path or a pre-quantized weight tree alike.
"""

from __future__ import annotations

__all__ = ["projections", "nibble_least_s", "model_flops"]


def projections(model: dict) -> list:
    """``(K, N)`` of every projection of one layer that runs through the
    quantized matmul: q, k, v, o, gate, up, down."""
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    kvh = model["num_key_value_heads"]
    hd = model.get("head_dim") or d // h
    f = model["intermediate_size"]
    return [(d, h * hd), (d, kvh * hd), (d, kvh * hd), (h * hd, d),
            (d, f), (d, f), (f, d)]


def _call(k: int, n: int, m: float, peaks: dict) -> float:
    """Least seconds of one ``(m, k) x (k, n)`` int8 call: int8 weights
    read once, int8 activations in, bf16 out, f32 scales (one per output
    column, one for the tensor)."""
    ops = 2.0 * m * k * n
    nbytes = k * n + m * k + 2.0 * m * n + 4.0 * n + 4.0
    return max(ops / peaks["int8_ops"], nbytes / peaks["hbm_bytes_per_s"])


def nibble_least_s(model: dict, dispatches, peaks: dict) -> float:
    """Least seconds the chip needs for the quantized projections of
    ``dispatches``: an iterable of real row counts, one per pass through
    the layer stack (a prefill's prompt tokens, or a decode step's
    active slots)."""
    per_layer = projections(model)
    layers = model["num_hidden_layers"]
    return layers * sum(_call(k, n, m, peaks)
                        for m in dispatches for k, n in per_layer)


def model_flops(model: dict, prompt_lens, decode_contexts,
                emitted: int) -> float:
    """Model FLOPs of a window: prefills of ``prompt_lens`` tokens,
    decode steps at the context lengths ``decode_contexts`` (one entry
    per decoded token), and ``emitted`` tokens through the head.
    2 FLOPs per weight per token in the projections; attention costs
    4 * heads * head_dim FLOPs per attended position per layer."""
    layers = model["num_hidden_layers"]
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    hd = model.get("head_dim") or d // h
    proj = layers * sum(k * n for k, n in projections(model))
    attn = 4.0 * layers * h * hd
    flops = 0.0
    for p in prompt_lens:
        # causal: position i attends i + 1 positions
        flops += 2.0 * proj * p + attn * p * (p + 1) / 2.0
    for c in decode_contexts:
        flops += 2.0 * proj + attn * c
    return flops + 2.0 * d * model["vocab_size"] * emitted
