"""What the host saw inside the window, for the per-layer readers: the
prefills and decoded tokens stamped inside it (times in seconds from
the window's opening)."""

from __future__ import annotations

__all__ = ["prefills", "decoded", "decode_steps"]


def prefills(ctx: dict, until: float) -> list:
    """Prompt lengths of the requests whose prefill ended (first token
    stamped) inside the window, at or before ``until``."""
    return [r["prompt_len"] for r in ctx["requests"]
            if r["t_first"] is not None and 0.0 <= r["t_first"] <= until]


def decoded(ctx: dict, until: float) -> list:
    """Context length of every token decoded (after the first) and
    stamped inside the window at or before ``until``: the prompt plus
    the tokens before it."""
    return [r["prompt_len"] + j for r in ctx["requests"]
            for j, t in enumerate(r["t_tokens"][1:], start=1)
            if 0.0 <= t <= until]


def decode_steps(ctx: dict) -> int:
    """Decode steps the engine ran in the window: chunks times the
    chunk's length (every step of a chunk runs, active slots or not)."""
    return ctx["stats"]["decode_chunks"] * ctx["scfg"].decode_chunk
