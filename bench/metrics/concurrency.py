"""Mean requests admitted per decode chunk over the window, as the
engine counts them (``Engine.stats["concurrency"]``)."""


def read(ctx):
    return ctx["stats"]["concurrency"]
