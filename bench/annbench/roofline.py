"""A kernel's share of its roofline, from the trace and the call shapes.

The least time a call can take is the larger of its operations over the
peak FLOP/s and its bytes over the peak HBM bandwidth (``peaks.py``); the
operations and bytes come from the call's shapes, by the functions in
``bench/kernels/<kernel>.py``. Every served batch makes the same calls (the
engine pads to one compiled batch), so the traced events of the kernel are
``batches x calls per batch`` and the share is

    batches * sum(least time per call) / sum(traced kernel time).
"""

from __future__ import annotations

from annbench import peaks, spec, xtrace


def share(ctx: dict, kernel_name: str):
    """Percent of the roofline, or None when the kernel is not traced or
    not called in this cell."""
    trace = ctx.get("trace")
    if trace is None:
        return None
    km = spec.kernel(kernel_name)
    calls = km.calls(ctx["plan"])
    if not calls:
        return None
    events, seconds = xtrace.count_ops(trace["device"], km.TRACE_NAME)
    if events == 0 or seconds <= 0:
        return None
    peak = peaks.of(ctx["device_kind"])
    least = 0.0
    for c in calls:
        flops, nbytes = km.cost(c)
        least += max(flops / peak["flops_per_s"],
                     nbytes / peak["hbm_bytes_per_s"])
    batches = events / len(calls)
    return 100.0 * batches * least / seconds
