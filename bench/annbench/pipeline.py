"""Shapes of the search pipeline's calls, from the built index's sizes.

``plan`` is the dict the harness fills after the build: ``batch``, ``d``,
``k``, ``beam``, ``execution``, ``rerank_width``, ``level_sizes`` (rows per
level, leaf first) and ``max_children`` (per level, leaf first; entry l
bounds the children of a level-l prototype).
"""


def descent(plan: dict):
    """The beam descent's ranks as [(w, k)] for levels L-1..1, and the
    candidate width of the leaf. The top level is ranked by a pairwise
    matrix and ``lax.top_k``, not by the rank kernel."""
    sizes, mc, beam = plan["level_sizes"], plan["max_children"], plan["beam"]
    top = len(sizes) - 1
    if top == 0:
        return [], sizes[0]
    w = min(beam, sizes[top]) * mc[top]
    ranks = []
    for level in range(top - 1, 0, -1):
        k = min(beam, w)
        ranks.append((w, k))
        w = k * mc[level]
    return ranks, w


def rerank_width(plan: dict, leaf_w: int) -> int:
    """Survivors of the two-stage scan: at least k, at most the leaf width."""
    return min(max(plan["rerank_width"], plan["k"]), leaf_w)
