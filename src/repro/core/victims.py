"""Per-category live counts and the quota/capacity victim index.

Both eviction scorers (core/admission.py) rank an entry by
w(category) · (hits + 1) / max(age, 1e-3). Within one (category, hits)
bucket the lowest score therefore belongs to the OLDEST entry, and that
order never changes as time passes. So the lowest-scored entry of a
category is found among the oldest entry of each of its non-empty hit
buckets, and the capacity victim among those of every category: the
write path scores a handful of bucket heads per victim instead of every
live slot.

Each bucket holds its entries ordered by (inserted, slot), with lazy
deletion: a record keeps the (inserted, hits, doc id) its entry had when
it was filed, and a head whose slot no longer matches them (the entry
took a hit, was back-dated, evicted, or its slot reused) is dropped when
it surfaces. Zero-hit entries, which arrive in time order and are most of
a full cache, sit in one numpy FIFO ring per category; the rest (entries
with hits, and zero-hit entries filed out of order) sit in sorted lists
of tuples, one per hit count, so the oldest entry inserted after a given
time is one bisection away.

The cache is the index's only writer: ``SemanticCache._write_entries``
files every entry whose hits or insertion time change or that is
created, ``_evict_slot`` takes every removal.
"""

from __future__ import annotations

from bisect import bisect_right, insort

import numpy as np

from repro.core.hnsw import INVALID

_CHUNK = 64          # ring records checked for liveness per numpy pass
_SLACK = 4096        # stale records a category may hold before a rebuild


class _Ring:
    """Zero-hit records of one category, in (inserted, slot) order."""

    __slots__ = ("slot", "ins", "doc", "lo", "hi")

    def __init__(self, size: int = 1024):
        self.slot = np.empty(size, np.int64)
        self.ins = np.empty(size, np.float32)
        self.doc = np.empty(size, np.int64)
        self.lo = self.hi = 0

    def __len__(self) -> int:
        return self.hi - self.lo

    def last_key(self) -> tuple[float, int] | None:
        if self.hi == self.lo:
            return None
        return float(self.ins[self.hi - 1]), int(self.slot[self.hi - 1])

    def extend(self, slot: np.ndarray, ins: np.ndarray,
               doc: np.ndarray) -> None:
        n, k = len(slot), len(self)
        if self.hi + n > len(self.slot):
            size = len(self.slot)
            while k + n > size // 2:
                size *= 2
            for name in ("slot", "ins", "doc"):
                old = getattr(self, name)
                new = np.empty(size, old.dtype)
                new[:k] = old[self.lo:self.hi]
                setattr(self, name, new)
            self.lo, self.hi = 0, k
        self.slot[self.hi:self.hi + n] = slot
        self.ins[self.hi:self.hi + n] = ins
        self.doc[self.hi:self.hi + n] = doc
        self.hi += n


class _Category:
    """One category's buckets: the zero-hit ring plus a sorted list of
    (inserted, slot, doc) records per hit count (``lists[0]`` takes
    zero-hit records filed out of order)."""

    __slots__ = ("ring", "lists", "n_rec")

    def __init__(self):
        self.ring = _Ring()
        self.lists: dict[int, list] = {}
        self.n_rec = 0          # records held, live or stale


class VictimIndex:
    """Live counts per category and the exact lowest-scored live entry.

    ``counts[cid]`` is category ``cid``'s live entries and ``total`` all
    of them. ``pick(cid, score)`` returns the slot ``np.argmin`` over the
    slot-ordered live entries of ``cid`` (every category when ``cid`` is
    None) would return under ``score``, and that score. ``scored`` and
    ``stale`` count the candidates scored and the stale heads dropped.
    """

    def __init__(self, cache):
        self._cache = cache
        self.counts = np.zeros(8, np.int64)
        self.total = 0
        self._cats: dict[int, _Category] = {}
        self.scored = 0
        self.stale = 0

    # ------------------------------------------------------------- counts
    def count(self, cid: int) -> int:
        return int(self.counts[cid]) if 0 <= cid < len(self.counts) else 0

    def added(self, cids: np.ndarray) -> None:
        """Entries of ``cids`` became live (they are filed separately)."""
        cids = np.asarray(cids, np.int64)
        if not cids.size:
            return
        top = int(cids.max()) + 1
        if top > len(self.counts):
            grown = np.zeros(max(top, 2 * len(self.counts)), np.int64)
            grown[:len(self.counts)] = self.counts
            self.counts = grown
        np.add.at(self.counts, cids, 1)
        self.total += int(cids.size)

    def removed(self, cid: int) -> None:
        """One entry of ``cid`` left; its records go stale by themselves."""
        self.counts[cid] -= 1
        self.total -= 1

    # ------------------------------------------------------------- filing
    def file(self, slots: np.ndarray) -> None:
        """File the live entries at ``slots`` under their current
        (category, hits, inserted)."""
        c = self._cache
        slots = np.asarray(slots, np.int64)
        if not slots.size:
            return
        cats = c.slot_category[slots]
        for cid in np.unique(cats).tolist():
            sel = slots[cats == cid]
            self._file_cat(cid, sel, c.slot_inserted[sel], c.slot_hits[sel],
                           c.slot_doc[sel])

    def _file_cat(self, cid: int, slots, ins, hits, docs) -> None:
        cat = self._cats.get(cid)
        if cat is None:
            cat = self._cats[cid] = _Category()
        zero = hits == 0
        if zero.any():
            s, i, d = slots[zero], ins[zero], docs[zero]
            order = np.lexsort((s, i))
            s, i, d = s[order], i[order], d[order]
            # Records ordered before the ring's last go to lists[0].
            k = 0
            last = cat.ring.last_key()
            if last is not None:
                before = (i < last[0]) | ((i == last[0]) & (s < last[1]))
                k = int(np.searchsorted(~before, True))
            if k:
                l0 = cat.lists.setdefault(0, [])
                for rec in zip(i[:k].tolist(), s[:k].tolist(),
                               d[:k].tolist()):
                    insort(l0, rec)
            cat.ring.extend(s[k:], i[k:], d[k:])
        if not zero.all():
            for rec_i, rec_s, rec_d, h in zip(
                    ins[~zero].tolist(), slots[~zero].tolist(),
                    docs[~zero].tolist(), hits[~zero].tolist()):
                insort(cat.lists.setdefault(h, []), (rec_i, rec_s, rec_d))
        cat.n_rec += len(slots)
        if cat.n_rec > 2 * self.count(cid) + _SLACK:
            self._rebuild(cid, cat)

    def _rebuild(self, cid: int, cat: _Category) -> None:
        """Refile the category's live entries alone, dropping every stale
        record (amortized: at most once per ``_SLACK`` + live filings)."""
        c = self._cache
        live = np.flatnonzero(c.slot_valid & (c.slot_category == cid))
        ins, hits, docs = c.slot_inserted[live], c.slot_hits[live], \
            c.slot_doc[live]
        zero = hits == 0
        ring = _Ring(max(1024, 2 * int(zero.sum())))
        order = np.lexsort((live[zero], ins[zero]))
        ring.extend(live[zero][order], ins[zero][order], docs[zero][order])
        lists: dict[int, list] = {}
        for rec_i, rec_s, rec_d, h in zip(
                ins[~zero].tolist(), live[~zero].tolist(),
                docs[~zero].tolist(), hits[~zero].tolist()):
            lists.setdefault(h, []).append((rec_i, rec_s, rec_d))
        for lst in lists.values():
            lst.sort()
        cat.ring, cat.lists, cat.n_rec = ring, lists, len(live)

    # ----------------------------------------------------------- liveness
    def _live_ring(self, ring: _Ring, lo: int, hi: int) -> np.ndarray:
        c = self._cache
        s = ring.slot[lo:hi]
        return ((c.slot_doc[s] == ring.doc[lo:hi]) & (c.slot_hits[s] == 0)
                & (c.slot_inserted[s] == ring.ins[lo:hi]))

    def _live_rec(self, rec: tuple, h: int) -> bool:
        c = self._cache
        ins, slot, doc = rec
        return (c.slot_doc[slot] == doc and c.slot_hits[slot] == h
                and c.slot_inserted[slot] == ins)

    def _ring_from(self, cat: _Category, pos: int,
                   drop: bool) -> int | None:
        """Position of the first live ring record at or after ``pos``;
        with ``drop`` (``pos`` is the ring's head) stale heads go."""
        ring = cat.ring
        while pos < ring.hi:
            end = min(pos + _CHUNK, ring.hi)
            live = self._live_ring(ring, pos, end)
            j = int(np.argmax(live)) if live.any() else end - pos
            if drop:
                ring.lo += j
                cat.n_rec -= j
                self.stale += j
            if j < end - pos:
                return pos + j
            pos = end
        return None

    def _list_first(self, cat: _Category, h: int) -> tuple | None:
        """The oldest live record of ``lists[h]``, stale heads dropped
        (an emptied list goes)."""
        lst = cat.lists[h]
        k = 0
        while k < len(lst) and not self._live_rec(lst[k], h):
            k += 1
        if k:
            del lst[:k]
            cat.n_rec -= k
            self.stale += k
        if not lst:
            del cat.lists[h]
            return None
        return lst[0]

    def _list_after(self, cat: _Category, h: int, x: float) -> tuple | None:
        """The first live record of ``lists[h]`` inserted after ``x``."""
        lst = cat.lists.get(h, ())
        for k in range(bisect_right(lst, (x, np.inf)), len(lst)):
            if self._live_rec(lst[k], h):
                return lst[k]
        return None

    # ------------------------------------------------------------ buckets
    def _heads(self, cat: _Category) -> list[tuple[int, tuple]]:
        """(hits, oldest live record) per non-empty bucket."""
        heads = {h: self._list_first(cat, h) for h in sorted(cat.lists)}
        pos = self._ring_from(cat, cat.ring.lo, drop=True)
        if pos is not None:
            ring = cat.ring
            rec = (float(ring.ins[pos]), int(ring.slot[pos]),
                   int(ring.doc[pos]))
            if heads.get(0) is None or rec[:2] < heads[0][:2]:
                heads[0] = rec
        return [(h, rec) for h, rec in sorted(heads.items())
                if rec is not None]

    def _after(self, cat: _Category, h: int, x: float) -> tuple | None:
        """Oldest live record of bucket ``h`` inserted after ``x``."""
        best = self._list_after(cat, h, x)
        if h == 0:
            ring = cat.ring
            p = ring.lo + int(np.searchsorted(ring.ins[ring.lo:ring.hi],
                                              np.float32(x), side="right"))
            pos = self._ring_from(cat, p, drop=False)
            if pos is not None:
                rec = (float(ring.ins[pos]), int(ring.slot[pos]),
                       int(ring.doc[pos]))
                if best is None or rec[:2] < best[:2]:
                    best = rec
        return best

    def _bucket_slots(self, cat: _Category, h: int) -> np.ndarray:
        """Every live slot of bucket ``h``."""
        out = [int(rec[1]) for rec in cat.lists.get(h, ())
               if self._live_rec(rec, h)]
        if h == 0:
            ring = cat.ring
            live = self._live_ring(ring, ring.lo, ring.hi)
            out.extend(ring.slot[ring.lo:ring.hi][live].tolist())
        return np.asarray(out, np.int64)

    # --------------------------------------------------------------- pick
    def pick(self, cid: int | None, score) -> tuple[int, float]:
        """Lowest-scored live entry of ``cid`` (of all categories when
        None) under ``score(slots)``; ties go to the lowest slot.
        (INVALID, inf) when there is none."""
        if cid is None:
            cats = list(self._cats.values())
        else:
            cats = [self._cats[cid]] if cid in self._cats else []
        buckets = [(cat, h, rec) for cat in cats
                   for h, rec in self._heads(cat)]
        if not buckets:
            return INVALID, np.inf
        heads = np.asarray([rec[1] for _, _, rec in buckets], np.int64)
        s = np.asarray(score(heads), np.float64)
        self.scored += len(heads)
        m = float(s.min())
        tied = [buckets[j] for j in np.flatnonzero(s == m)]
        best = min(rec[1] for _, _, rec in tied)
        # A later entry of a tied bucket ties too only when scores cannot
        # tell them apart (both under the scorer's age clamp): check the
        # bucket's next insertion time, and when it ties, the whole bucket.
        nxt = [(cat, h, self._after(cat, h, rec[0])) for cat, h, rec in tied]
        nxt = [(cat, h, rec) for cat, h, rec in nxt if rec is not None]
        if nxt:
            s2 = np.asarray(score(np.asarray([rec[1] for *_, rec in nxt],
                                             np.int64)), np.float64)
            self.scored += len(nxt)
            for (cat, h, _), v in zip(nxt, s2):
                if v != m:
                    continue
                slots = self._bucket_slots(cat, h)
                s3 = np.asarray(score(slots), np.float64)
                self.scored += len(slots)
                best = min(best, int(slots[s3 == m].min()))
        return int(best), m
