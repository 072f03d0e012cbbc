"""The comparison that decides ``correct``, and recall@k.

Every answer the window returned is compared with the plain reference
(``reference.Database``); no number here comes from the program.

Numbers compared, each against its limit from the cell's traffic file:

``unanswered``  requests due in the window with no answer (an error, or
                none a minute past the close). Limit 0.
``malformed``   answers whose id list is not k distinct ids in [0, n) with
                finite, non-decreasing distances. Limit 0.
``dist_gap_max``   the widest gap between a returned distance and the
                   reference's direct-form distance of the same id, as a
                   share of max(that distance, the median reference k-th
                   distance). The floor keeps near-zero distances (near
                   duplicates) from turning rounding into a large share.
                   Catches a wrong answer anywhere in the window.
``dist_gap_mean``  the same gap, averaged over every returned slot. Steady
                   from seed to seed, so it separates the float32 rounding
                   of a sound run from a computation one precision step
                   down, which the widest gap alone does not.
``recall``      share of the returned slots that hold a true k-nearest
                neighbour (ties at the k-th distance count), held to a
                floor. The gaps only see the distance of each returned id;
                a descent down the wrong path, a smaller beam or a skipped
                level returns well-formed ids with true distances, and only
                recall sees it.

Upper limits are the traffic file's ``limits``; floors its ``floors``.
"""

from __future__ import annotations

import sys

import numpy as np

TIE_RTOL = 1e-6  # a returned id within this of the k-th distance is a hit


def malformed_rows(ids: np.ndarray, dists: np.ndarray, n: int) -> np.ndarray:
    """bool [m]: the row's ids are not k distinct ids in [0, n), or its
    distances are not finite and non-decreasing."""
    bad = ((ids < 0) | (ids >= n)).any(axis=1)
    s = np.sort(ids, axis=1)
    bad |= (s[:, 1:] == s[:, :-1]).any(axis=1)
    bad |= ~np.isfinite(dists).all(axis=1)
    bad |= (np.diff(dists, axis=1) < 0).any(axis=1)
    return bad


def compare(db, pool: np.ndarray, rows: np.ndarray, answered: np.ndarray,
            ids: np.ndarray, dists: np.ndarray, k: int) -> dict:
    """Compare the answers of the requests due in the window.

    ``rows`` [N]: the pool row each request asked for; ``answered`` [N]
    bool; ``ids``/``dists`` [N, k]: the answers (ignored where not
    answered). Returns the compared numbers and ``recall``."""
    ref_d, _ = db.neighbours(pool, k)
    kth = ref_d[:, k - 1]
    scale = float(np.median(kth))
    a = np.flatnonzero(answered)
    ids_a = np.asarray(ids[a])
    d_a = np.asarray(dists[a], np.float64)
    q_a = rows[a]
    bad = malformed_rows(ids_a, d_a, db.n)
    true_d = db.direct(pool[q_a], ids_a)
    live = np.isfinite(true_d) & np.isfinite(d_a)
    gap = np.abs(d_a - true_d) / np.maximum(true_d, scale)
    hit = live & (true_d <= kth[q_a][:, None] * (1 + TIE_RTOL) + 1e-12)
    return dict(
        unanswered=int(len(rows) - len(a)),
        malformed=int(bad.sum()),
        dist_gap_max=float(gap[live].max()) if live.any() else float("inf"),
        dist_gap_mean=float(gap[live].mean()) if live.any() else float("inf"),
        recall=float(hit.sum() / max(ids_a.size, 1)),
        scale=scale,
    )


def verdict(numbers: dict, limits: dict, floors: dict | None = None
            ) -> tuple[bool, dict]:
    """(correct, {name: {"value", "max" or "min"}}): each number in
    ``limits`` is at most its limit, each in ``floors`` at least its floor."""
    checks = {name: {"value": numbers[name], "max": limit}
              for name, limit in limits.items()}
    checks.update({name: {"value": numbers[name], "min": floor}
                   for name, floor in (floors or {}).items()})
    return all(passes(c) for c in checks.values()), checks


def passes(check: dict) -> bool:
    if "max" in check:
        return check["value"] <= check["max"]
    return check["value"] >= check["min"]


def print_checks(checks: dict, file=sys.stderr) -> None:
    for name, c in checks.items():
        side = "max" if "max" in c else "min"
        state = "ok" if passes(c) else "FAIL"
        print(f"check {name} {c['value']!r} {side} {c[side]!r} {state}",
              file=file, flush=True)
