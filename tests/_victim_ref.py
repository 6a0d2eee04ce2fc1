"""Brute-force reference for the cache's quota/capacity victim.

The masked argmin over every live slot that ``SemanticCache`` computed for
each victim before the victim index (core/victims.py) existed: score all
live slots of the category (all categories for None) with the active
scorer, take ``np.argmin`` over them in slot order (ties to the lowest
slot). The tests hold the index to it, victim for victim.
"""

from __future__ import annotations

import numpy as np

from repro.core.hnsw import INVALID


def brute_victim(cache, cid: int | None) -> tuple[int, float]:
    live = cache.slot_valid if cid is None else \
        cache.slot_valid & (cache.slot_category == cid)
    cand = np.where(live)[0]
    if not cand.size:
        return INVALID, np.inf
    s = cache._entry_score(cand)
    j = int(np.argmin(s))
    return int(cand[j]), float(s[j])


def brute_counts(cache) -> dict[int, int]:
    cats = cache.slot_category[cache.slot_valid]
    return {int(c): int((cats == c).sum()) for c in np.unique(cats)}


def assert_index_agrees(cache) -> None:
    """The victim index and the per-category counts agree with the brute
    force on every category the cache has seen, and over all of them."""
    counts = brute_counts(cache)
    for cid in sorted(cache._cat_names):
        assert cache._victims.count(cid) == counts.get(cid, 0), cid
        assert cache._live_victim(cid) == brute_victim(cache, cid), cid
    assert len(cache) == int(cache.slot_valid.sum())
    assert cache._live_victim(None) == brute_victim(cache, None)


class VictimRecorder:
    """Wraps a cache's ``_live_victim`` so every victim the write path
    asks for is also computed by the brute force on the same state."""

    def __init__(self, cache):
        self.got: list[tuple[int, float]] = []
        self.want: list[tuple[int, float]] = []
        self.ties = 0          # picks where several live slots share the min
        real = cache._live_victim

        def both(cid):
            want = brute_victim(cache, cid)
            live = cache.slot_valid if cid is None else \
                cache.slot_valid & (cache.slot_category == cid)
            if want[0] != INVALID:
                s = cache._entry_score(np.where(live)[0])
                self.ties += int((s == want[1]).sum() > 1)
            got = real(cid)
            self.want.append(want)
            self.got.append(got)
            return got

        cache._live_victim = both
