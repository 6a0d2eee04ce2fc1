"""The plain reference of the cache's semantics (paper §5, Algorithm 1).

It imports nothing of the program. It keeps its own record of what the
benchmark inserted (``Mirror``): every entry's fp32 embedding, category,
response text and insertion time, which slot the cache put it in, and
every hit the cache reported. A slot that receives a new entry has lost
its old one: that is how an eviction shows in the cache's outputs. A
lookup's answer is then decided exactly, on the host in float64 over the
entries resident when it ran: the best entry of the query's own
category, a hit when its cosine reaches the category's τ and its age is
within the category's TTL. The evictions are replayed under the quota
rule (``evictions``).
"""

from __future__ import annotations

import heapq
import zlib
from collections import deque
from dataclasses import dataclass

import numpy as np


class Mirror:
    """Entries in insertion order; ``slot_entry`` is the entry each slot
    holds now. Entry e is resident for operations with sequence number
    s in (born[e], died[e])."""

    def __init__(self, capacity: int, dim: int, reserve: int):
        self.dim = dim
        self.slot_entry = np.full(capacity, -1, np.int64)
        self.emb = np.empty((reserve, dim), np.float32)
        self.cat = np.empty(reserve, np.int32)
        self.born = np.empty(reserve, np.int64)
        self.died = np.empty(reserve, np.int64)
        self.t_lo = np.empty(reserve, np.float64)     # insert call began
        self.t_hi = np.empty(reserve, np.float64)     # insert call ended
        self.n = 0
        # ("i", seq, t_lo, t_hi, ids, stored) per insert call and
        # ("h", seq, entries hit) per lookup call, in order
        self.log: list[tuple] = []

    def reserve_ids(self, k: int) -> np.ndarray:
        """Entry ids for the next k items offered to ``insert_batch``."""
        if self.n + k > len(self.cat):
            grow = max(k, len(self.cat) // 2)
            for f in ("emb", "cat", "born", "died", "t_lo", "t_hi"):
                a = getattr(self, f)
                b = np.empty((len(a) + grow,) + a.shape[1:], a.dtype)
                b[:len(a)] = a
                setattr(self, f, b)
        return np.arange(self.n, self.n + k)

    def record(self, ids: np.ndarray, slots: np.ndarray, emb: np.ndarray,
               cat: np.ndarray, seq: int, t_lo: float, t_hi: float) -> None:
        """The cache answered ``insert_batch`` with ``slots`` (-1: not
        stored). Stored items become entries; each slot's previous
        entry dies at ``seq``."""
        assert len(ids) and ids[0] == self.n
        self.n = int(ids[-1]) + 1
        self.emb[ids] = emb
        self.cat[ids] = cat
        self.born[ids] = seq
        self.died[ids] = np.iinfo(np.int64).max
        self.t_lo[ids] = t_lo
        self.t_hi[ids] = t_hi
        ok = slots >= 0
        stored, at = ids[ok], slots[ok]
        old = self.slot_entry[at]
        self.died[old[old >= 0]] = seq
        self.slot_entry[at] = stored
        # an item never stored dies at once
        self.died[ids[~ok]] = seq
        self.log.append(("i", seq, t_lo, t_hi, ids, ok))
        return int(np.sum(old >= 0))

    def hit(self, seq: int, entries: list[int]) -> None:
        """The cache reported a hit on each of ``entries`` at ``seq``."""
        if entries:
            self.log.append(("h", seq, np.asarray(entries, np.int64)))

    def reconcile(self, valid: np.ndarray, seq: int) -> None:
        """Entries whose slots the cache no longer holds (``valid``, its
        occupancy by slot) died at ``seq``, although no new entry took
        their slots."""
        live = np.flatnonzero(self.slot_entry >= 0)
        gone = live[~np.asarray(valid, bool)[live]]
        self.died[self.slot_entry[gone]] = seq
        self.slot_entry[gone] = -1


@dataclass
class Lookup:
    """One checked lookup: what was asked and what the cache answered."""
    seq: int
    t_lo: float                  # lookup call began (host clock)
    t_hi: float                  # lookup call ended
    query: np.ndarray            # (d,) fp32
    cat: int
    tau: float
    ttl: float
    hit: bool
    entry: int                   # entry in the hit's slot (-1 on a miss)
    score: float                 # the cache's score (-inf: no candidate)


def decide(mirror: Mirror, lookups: list[Lookup], score_tol: float,
           block: int = 256) -> dict:
    """Exact decisions for ``lookups``; compares each with the cache's.

    Returns the largest and the root-mean-square |score - exact| over
    the cache's scores, the count of decisions that differ without an
    excuse, and the counts excused. A decision may differ only where exactness cannot tell:
    the best exact score within ``score_tol`` of τ, a runner-up within
    ``score_tol`` of the best, or an age within the two calls' durations
    of the TTL."""
    n = mirror.n
    emb = mirror.emb[:n]
    errs: list[float] = []
    mismatches = excused = 0
    by_cat: dict[int, list[Lookup]] = {}
    for lk in lookups:
        by_cat.setdefault(lk.cat, []).append(lk)
    for c, group in by_cat.items():
        rows = np.nonzero(mirror.cat[:n] == c)[0]
        born, died = mirror.born[rows], mirror.died[rows]
        e64 = emb[rows].astype(np.float64)
        for s in range(0, len(group), block):
            part = group[s:s + block]
            q = np.stack([lk.query for lk in part]).astype(np.float64)
            sims = q @ e64.T
            for j, lk in enumerate(part):
                alive = (born < lk.seq) & (died > lk.seq)
                sc = np.where(alive, sims[j], -np.inf)
                best = int(np.argmax(sc)) if sc.size else -1
                s_best = float(sc[best]) if best >= 0 else -np.inf
                if sc.size > 1:
                    top2 = np.partition(sc, -2)[-2:]
                    runner = float(top2[0])
                else:
                    runner = -np.inf
                ent = rows[best] if np.isfinite(s_best) else -1
                # age window of the best entry at this lookup
                if ent >= 0:
                    age_lo = lk.t_lo - mirror.t_hi[ent]
                    age_hi = lk.t_hi - mirror.t_lo[ent]
                    expired = age_lo > lk.ttl
                    ttl_band = age_lo <= lk.ttl < age_hi
                else:
                    expired = ttl_band = False
                ref_hit = ent >= 0 and s_best >= lk.tau and not expired
                near_tau = abs(s_best - lk.tau) <= score_tol
                tie = s_best - runner <= score_tol
                if lk.hit:
                    k = int(np.searchsorted(rows, lk.entry))
                    mine = (float(sims[j, k]) if k < len(rows)
                            and rows[k] == lk.entry and alive[k]
                            else -np.inf)
                    errs.append(abs(lk.score - mine))
                    same = lk.entry == ent or (
                        np.isfinite(mine) and s_best - mine <= score_tol)
                    if ref_hit and same:
                        continue
                    if np.isfinite(mine) and (near_tau or ttl_band) and \
                            (same or tie):
                        excused += 1
                        continue
                    mismatches += 1
                else:
                    if np.isfinite(lk.score) or np.isfinite(s_best):
                        errs.append(abs(lk.score - s_best))
                    if not ref_hit:
                        continue
                    if near_tau or ttl_band:
                        excused += 1
                        continue
                    mismatches += 1
    e = np.asarray(errs, np.float64)
    return {"score_err_max": float(e.max()) if e.size else 0.0,
            "score_err_rms": float(np.sqrt(np.mean(e * e))) if e.size
            else 0.0,
            "decision_mismatches": mismatches,
            "decisions_excused": excused, "decisions_checked": len(lookups)}


# ------------------------------------------------------------ evictions

def evictions(m: Mirror, quota: np.ndarray, cacheable: np.ndarray) -> dict:
    """Replay every insert and hit in order under the quota rule and
    compare the cache's evictions with it.

    A category at its quota (``quota[c]`` entries, at least 1) evicts one
    entry for each item it stores beyond it: its entry of lowest score
    (hits + 1) / age (§5.4's priority × 1/age × (hits + 1); a category
    has one priority, so it does not order its own entries). The cache's
    victims are the entries it gave up. An age is known only to within
    the host-clock windows of the two insert calls, so a victim is wrong
    only where its lowest possible score lies above the highest possible
    score of an entry the category kept: no reading of the clock explains
    the choice. Also compared: the number of victims of each category at
    each insert (the quota, exactly), and that each item of a cacheable
    category was stored (no admission gate is configured)."""
    n = m.n
    cat, born, died = m.cat[:n], m.born[:n], m.died[:n]
    t_lo, t_hi = m.t_lo[:n], m.t_hi[:n]
    n_cat = len(quota)
    victims_at: dict[int, list[int]] = {}
    for e in np.nonzero(died[:n] > born[:n])[0]:
        if died[e] != np.iinfo(np.int64).max:
            victims_at.setdefault(int(died[e]), []).append(int(e))
    hits = np.zeros(n, np.int64)
    live = np.zeros(n_cat, np.int64)
    unhit = [deque() for _ in range(n_cat)]        # hits == 0, oldest first
    held: list[dict[int, list]] = [{} for _ in range(n_cat)]  # hits -> heap
    t_first = float(t_hi.min()) if n else 0.0
    out = {"wrong_victims": 0, "wrong_counts": 0, "unstored": 0,
           "victims": 0}

    def age(a: float) -> float:
        return max(a, 1e-3)

    def kept_score_hi(c: int, s: int, now_lo: float) -> float:
        """Highest possible score of category c's lowest kept entry."""
        best = np.inf
        q = unhit[c]
        while q and (died[q[0]] <= s or hits[q[0]] > 0):
            q.popleft()
        if q:
            best = 1.0 / age(now_lo - t_hi[q[0]])
        h, top = 1, max(held[c], default=0)
        while h <= top:
            if (h + 1) / age(now_lo - t_first) >= best:
                break
            hp = held[c].get(h)
            while hp and (died[hp[0][1]] <= s or hits[hp[0][1]] != h):
                heapq.heappop(hp)
            if hp:
                best = min(best, (h + 1) / age(now_lo - hp[0][0]))
            h += 1
        return best

    for ev in m.log:
        if ev[0] == "h":
            for e in ev[2]:
                hits[e] += 1
                heapq.heappush(held[cat[e]].setdefault(int(hits[e]), []),
                               (t_hi[e], int(e)))
            continue
        _, s, now_lo, now_hi, ids, stored = ev
        out["unstored"] += int(np.sum(~stored & cacheable[cat[ids]]))
        new = np.bincount(cat[ids[stored]], minlength=n_cat)
        by_c: dict[int, list[int]] = {}
        for v in victims_at.get(s, []):
            by_c.setdefault(int(cat[v]), []).append(v)
        for c in set(by_c) | set(np.nonzero(new)[0].tolist()):
            cap = max(1, int(quota[c]))
            need = int(new[c] - np.clip(cap - live[c], 0, new[c]))
            got = by_c.get(c, [])
            out["wrong_counts"] += abs(len(got) - need)
            if got:
                out["victims"] += len(got)
                best = kept_score_hi(c, s, now_lo)
                out["wrong_victims"] += sum(
                    (hits[v] + 1) / age(now_hi - t_lo[v]) > best
                    for v in got)
            live[c] += new[c] - len(got)
        for e in ids[stored]:
            unhit[cat[e]].append(int(e))
    return {k: int(v) for k, v in out.items()}


# ----------------------------------------------------- feature-hash embedding

def _stable_hash(token: str) -> int:
    return zlib.crc32(("0\x00" + token).encode("utf-8")) & 0xFFFFFFFF


def embed_text(text: str, dim: int = 384) -> np.ndarray:
    """Signed word and character 3/4-gram feature hashing into ``dim``
    buckets, L2-normalized: the embedding the engine's requests are keyed
    by, written out plainly."""
    text = text.lower().strip()
    feats = [w for w in text.split() if w]
    padded = f" {text} "
    for n in (3, 4):
        feats.extend(padded[i:i + n]
                     for i in range(max(0, len(padded) - n + 1)))
    vec = np.zeros(dim, np.float64)
    for f in feats:
        h = _stable_hash(f)
        vec[h % dim] += 1.0 if (h >> 31) & 1 else -1.0
    norm = np.linalg.norm(vec)
    return vec / norm if norm > 0 else vec
