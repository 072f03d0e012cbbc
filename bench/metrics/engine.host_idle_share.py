"""Share of the traced window in which the device is idle while the
engine's worker is inside one of its spans other than
``engine.take_batch``: idle time the host's own work causes, as opposed to
waiting for traffic."""

from annbench import spans


def read(ctx):
    if ctx.get("trace") is None or ctx["window_s"] <= 0:
        return None
    split = spans.idle_split(ctx["trace"])
    return None if split is None else 100.0 * split["host"] / ctx["window_s"]
