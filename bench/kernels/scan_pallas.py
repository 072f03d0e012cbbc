"""``scan_pallas``: stage-1 scan of the int8 payload codes (two-stage search).

One call reads the queries [b, d] f32, the gathered int8 codes [b, w, d],
their per-row scales [b, w] f32 and validity [b, w] int8, and writes the
top-r distances and slots [b, r]. Operations: dequantisation (b w d), the
Gram products (2 b w d) and a three-operation epilogue per candidate; the
extract-min merge is bookkeeping and is not counted.
"""

from annbench.pipeline import descent, rerank_width

TRACE_NAME = r"^scan_pallas(\.\d+)?$"  # the pallas_call's HLO instruction


def calls(plan: dict) -> list:
    """Shapes of every call one served batch makes."""
    if plan["execution"] != "two_stage":
        return []
    _, leaf_w = descent(plan)
    return [dict(b=plan["batch"], w=leaf_w, d=plan["d"],
                 k=rerank_width(plan, leaf_w))]


def cost(c: dict) -> tuple[float, float]:
    """(operations, bytes) of one call."""
    b, w, d, k = c["b"], c["w"], c["d"], c["k"]
    flops = 3.0 * b * w * d + 3.0 * b * w
    nbytes = 1.0 * b * w * d + 5.0 * b * w + 4.0 * b * d + 8.0 * b * k
    return flops, nbytes
