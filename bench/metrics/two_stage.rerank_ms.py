"""Mean time per batch of the two-stage store's second stage: the host
fetch of the survivors' exact rows (``granule_fetch`` span) plus the exact
rerank (``rerank`` span). Every request is traced in the traced run and a
batch's spans repeat in each of its requests, so each request's share is
weighted by 1 / its batch's size."""

STAGES = ("granule_fetch", "rerank")


def read(ctx):
    num = den = 0.0
    for r in ctx["spans"]:
        if not any(s in r["stages"] for s in STAGES):
            continue
        w = 1.0 / r["batch"]
        num += w * sum(r["stages"].get(s, 0.0) for s in STAGES)
        den += w
    return 1e3 * num / den if den else None
