"""Mean time of the engine's ``engine.deliver`` span per batch, read from
the profiler trace (spans that start in the traced window)."""

from annbench import spans


def read(ctx):
    if ctx.get("trace") is None:
        return None
    return spans.mean_ms(ctx["trace"], "engine.deliver")
