"""``rank_pallas``: fused masked ranking of per-query gathered candidates.

One call reads the queries [b, d] f32, the gathered candidates [b, w, d]
f32, their validity [b, w] int8 and squared norms [b, w] f32, and writes
the top-k distances and slots [b, k] (f32 + int32). Operations: the Gram
products (2 b w d) and a three-operation epilogue per candidate. The
extract-min merge is bookkeeping and is not counted.
"""

from annbench.pipeline import descent, rerank_width

TRACE_NAME = r"^rank_pallas(\.\d+)?$"  # the pallas_call's HLO instruction


def calls(plan: dict) -> list:
    """Shapes of every call one served batch makes."""
    b, d = plan["batch"], plan["d"]
    ranks, leaf_w = descent(plan)
    out = [dict(b=b, w=w, d=d, k=k) for w, k in ranks]
    if plan["execution"] == "beam":
        out.append(dict(b=b, w=leaf_w, d=d, k=min(plan["k"], leaf_w)))
    elif plan["execution"] == "two_stage":
        r = rerank_width(plan, leaf_w)
        out.append(dict(b=b, w=r, d=d, k=min(plan["k"], r)))
    return out


def cost(c: dict) -> tuple[float, float]:
    """(operations, bytes) of one call."""
    b, w, d, k = c["b"], c["w"], c["d"], c["k"]
    flops = 2.0 * b * w * d + 3.0 * b * w
    nbytes = 4.0 * b * w * d + 5.0 * b * w + 4.0 * b * d + 8.0 * b * k
    return flops, nbytes
