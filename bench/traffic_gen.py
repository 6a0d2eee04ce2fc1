"""The one traffic generator: every mix is a data file in ``bench/traffic/``.

A mix names its categories with their Table-1 parameters (traffic share,
intent pool, repetition, downstream latency) and the paraphrase geometry
of ``repro.core.embedding.SyntheticCategorySpace`` (sigma, center_spread,
loose_frac, loose_mult), copied here so the yardstick does not move when
the program does. What differs from the program's generator:

* Centers are derived lazily from (seed, category, intent id) with JAX's
  counter-based PRNG (``fold_in``), on the device, so pools of millions
  of intents cost no memory: only the intents a run touches are made.
* The work is fixed by the mix's ``work_seed``: the sequence of
  (category, popularity rank) pairs, the fill history and the multiset
  of inter-arrival gaps. The run's ``--seed`` permutes their order
  within blocks of the mix's ``order_block`` consecutive positions, maps
  ranks to intent ids through a per-category bijection, and draws the
  geometry, the paraphrase noise, the texts and the prompts. Two seeds
  therefore run the same amount of work of the same shape, with the same
  bursts and the same mix in every stretch of the window, in another
  order within each block and on other vectors.
* Arrivals are open loop: a fixed count ``rate_per_s * seconds`` over the
  window (a Poisson process conditioned on its count).
"""

from __future__ import annotations

import math
import zlib
from dataclasses import dataclass

import numpy as np

EMBED_DIM = 384
CHUNK = 16384            # rows per jitted embedding call (one compile)


def seed_words(seed: int, n: int = 4) -> np.ndarray:
    """``n`` uint32 words from any non-negative integer seed (seeds run
    past 2**31; JAX keys take 32-bit words)."""
    return np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(
        n, np.uint32)


def jax_key(seed: int, salt: str):
    """A threefry key from the run seed and a purpose name."""
    import jax
    w = seed_words(seed, 2)
    w[1] ^= np.uint32(zlib.crc32(salt.encode()))
    return jax.random.wrap_key_data(w, impl="threefry2x32")


@dataclass(frozen=True)
class Category:
    name: str
    share: float
    pool: int
    zipf_alpha: float | None       # None: uniform repetition
    t_llm_ms: float
    sigma: float
    center_spread: float
    loose_frac: float
    loose_mult: float

    @staticmethod
    def from_dict(d: dict) -> "Category":
        return Category(d["name"], float(d["share"]), int(d["pool"]),
                        None if d.get("zipf_alpha") is None
                        else float(d["zipf_alpha"]),
                        float(d.get("t_llm_ms", 0.0)), float(d["sigma"]),
                        float(d["center_spread"]),
                        float(d.get("loose_frac", 0.30)),
                        float(d.get("loose_mult", 2.0)))


class RankSampler:
    """Popularity ranks 0..pool-1 of one category: bounded Zipf
    (p(k) ∝ (k+1)^-α, the program's ``_zipf_probs``) by inverse CDF, or
    uniform."""

    def __init__(self, cat: Category):
        self.cat = cat
        self._cdf = None
        if cat.zipf_alpha is not None:
            w = np.arange(1, cat.pool + 1, dtype=np.float64) ** -cat.zipf_alpha
            self._cdf = np.cumsum(w / w.sum())

    def draw(self, n: int, rng: np.random.Generator) -> np.ndarray:
        if self._cdf is None:
            return rng.integers(0, self.cat.pool, n)
        r = np.searchsorted(self._cdf, rng.random(n), side="right")
        return np.minimum(r, self.cat.pool - 1)


class Mix:
    """One traffic file, bound to a run seed."""

    def __init__(self, spec: dict, seed: int, dim: int = EMBED_DIM):
        self.spec = spec
        self.seed = int(seed)
        self.dim = dim
        self.cats = [Category.from_dict(c) for c in spec["categories"]]
        shares = np.array([c.share for c in self.cats])
        self.shares = shares / shares.sum()
        self.samplers = [RankSampler(c) for c in self.cats]
        self.run_rng = np.random.default_rng(seed_words(seed, 4))
        # rank -> intent id: id = (a·rank + b) mod pool with gcd(a, pool)
        # = 1, one bijection per category, drawn from the run seed.
        self._affine = []
        for c in self.cats:
            while True:
                a = int(self.run_rng.integers(1, max(2, c.pool)))
                if math.gcd(a, c.pool) == 1:
                    break
            self._affine.append((a, int(self.run_rng.integers(0, c.pool))))
        self._embed = None

    # ------------------------------------------------------------------ work
    def work_rng(self, purpose: str) -> np.random.Generator:
        """The fixed work of this mix: same for every run seed."""
        return np.random.default_rng(
            [int(self.spec["work_seed"]), zlib.crc32(purpose.encode())])

    def draw_ranks(self, n: int, rng: np.random.Generator
                   ) -> tuple[np.ndarray, np.ndarray]:
        """n requests of the mix: (category index, popularity rank)."""
        cat = rng.choice(len(self.cats), size=n, p=self.shares)
        rank = np.empty(n, np.int64)
        for k, s in enumerate(self.samplers):
            rows = np.nonzero(cat == k)[0]
            rank[rows] = s.draw(rows.size, rng)
        return cat, rank

    def intent_of(self, cat: np.ndarray, rank: np.ndarray) -> np.ndarray:
        """Intent ids behind popularity ranks (the run seed's bijection)."""
        out = np.empty(len(rank), np.int64)
        for k, (a, b) in enumerate(self._affine):
            rows = cat == k
            out[rows] = (a * rank[rows] + b) % self.cats[k].pool
        return out

    def block_permutation(self, n: int) -> np.ndarray:
        """A permutation of range(n), drawn from the run seed, that moves
        each position only within its block of ``order_block``
        consecutive positions."""
        block = int(self.spec["order_block"])
        key = np.arange(n) // block + self.run_rng.random(n)
        return np.argsort(key, kind="stable")

    def requests(self, n: int
                 ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The window's n requests, (category index, intent id, rank), in
        the run seed's order."""
        cat, rank = self.draw_ranks(n, self.work_rng("requests"))
        perm = self.block_permutation(n)
        cat, rank = cat[perm], rank[perm]
        return cat, self.intent_of(cat, rank), rank

    def arrivals(self, n: int, seconds: float) -> np.ndarray:
        """Due times (s from the window's start) of n open-loop arrivals:
        the work seed's exponential gaps, scaled to fill the window and
        put in the run seed's order."""
        gaps = self.work_rng("arrivals").exponential(1.0, n + 1)
        gaps = gaps[self.block_permutation(n + 1)]
        return np.cumsum(gaps)[:n] * (seconds / gaps.sum())

    def history(self, n: int) -> tuple[np.ndarray, np.ndarray]:
        """The fill history's n requests (category index, rank), oldest
        first: fixed by the work seed."""
        return self.draw_ranks(n, self.work_rng("history"))

    # ------------------------------------------------------------ embeddings
    def _embedder(self):
        if self._embed is None:
            self._embed = _make_embedder(self)
        return self._embed

    def embeddings(self, cat: np.ndarray, intent: np.ndarray,
                   stream: str) -> np.ndarray:
        """(n, dim) fp32 unit paraphrases: row i is a fresh paraphrase of
        intent ``intent[i]`` of category ``cat[i]``; the noise comes from
        (seed, ``stream``, i), the center from (seed, category, intent)."""
        import jax
        fn = self._embedder()
        n = len(cat)
        out = np.empty((n, self.dim), np.float32)
        key = jax_key(self.seed, "noise:" + stream)
        for s in range(0, n, CHUNK):
            e = min(n, s + CHUNK)
            c = np.zeros(CHUNK, np.int32)
            i = np.zeros(CHUNK, np.int32)
            c[:e - s] = cat[s:e]
            i[:e - s] = intent[s:e]
            rows = fn(c, i, np.int32(s), key)
            out[s:e] = np.asarray(rows)[:e - s]
        return out


def _make_embedder(mix: Mix):
    """Jitted (category, intent, offset, noise key) -> paraphrase rows.

    Center of intent i of category c (``SyntheticCategorySpace``):
    unit(w·raw + (1 - w)·anchor·sqrt(d)) with raw ~ N(0, I),
    w = center_spread · U(0.85, 1.30), anchor a unit vector per category.
    A paraphrase is unit(center + s·noise), s = sigma, or sigma·loose_mult
    with probability loose_frac."""
    import jax
    import jax.numpy as jnp

    d = mix.dim
    base = jax_key(mix.seed, "geometry")
    n_cat = len(mix.cats)
    cat_keys = jax.vmap(lambda c: jax.random.fold_in(base, c))(
        jnp.arange(n_cat))
    anchors = jax.vmap(lambda k: jax.random.normal(
        jax.random.fold_in(k, 0x7FFFFFFF), (d,)))(cat_keys)
    anchors = anchors / jnp.linalg.norm(anchors, axis=1, keepdims=True)
    par = {f: jnp.asarray([getattr(c, f) for c in mix.cats], jnp.float32)
           for f in ("sigma", "center_spread", "loose_frac", "loose_mult")}

    def unit(v):
        return v / jnp.maximum(jnp.linalg.norm(v, axis=-1, keepdims=True),
                               1e-12)

    def center(c, i):
        k = jax.random.fold_in(cat_keys[c], i)
        kr, kw = jax.random.split(k)
        raw = jax.random.normal(kr, (d,))
        w = par["center_spread"][c] * jax.random.uniform(
            kw, (), minval=0.85, maxval=1.30)
        return unit(w * raw + (1.0 - w) * anchors[c] * jnp.sqrt(float(d)))

    def one(c, i, j, key):
        kn, kl = jax.random.split(jax.random.fold_in(key, j))
        loose = jax.random.uniform(kl, ()) < par["loose_frac"][c]
        sig = par["sigma"][c] * jnp.where(loose, par["loose_mult"][c], 1.0)
        return unit(center(c, i) + sig * jax.random.normal(kn, (d,)))

    def rows(c, i, offset, key):
        j = offset + jnp.arange(c.shape[0], dtype=jnp.int32)
        return jax.vmap(one, in_axes=(0, 0, 0, None))(c, i, j, key)

    return jax.jit(rows)


# ----------------------------------------------------------------- the fill

def fill_entries(mix: Mix, quota: dict[str, int]
                 ) -> tuple[np.ndarray, np.ndarray]:
    """The cache as it stands after the mix's seeded history: one entry per
    distinct intent the history touched, each category's most recently
    requested intents up to its quota, oldest first. Returns (category
    index, intent id) arrays."""
    cat, rank = mix.history(int(mix.spec["history_requests"]))
    out_c, out_r = [], []
    for k, c in enumerate(mix.cats):
        r = rank[cat == k]
        # last occurrence of each rank: unique over the reversed stream
        rev = r[::-1]
        uniq, first = np.unique(rev, return_index=True)
        order = np.argsort(-first)          # oldest last-occurrence first
        keep = uniq[order][-quota[c.name]:] if quota[c.name] > 0 else uniq[:0]
        out_c.append(np.full(keep.size, k, np.int64))
        out_r.append(keep)
    cat = np.concatenate(out_c)
    rank = np.concatenate(out_r)
    # interleave categories in a fixed order, as a history would
    perm = mix.work_rng("fill-order").permutation(cat.size)
    cat, rank = cat[perm], rank[perm]
    return cat, mix.intent_of(cat, rank)


# ------------------------------------------------------------ request texts

LETTERS = np.array(list("abcdefghijklmnopqrstuvwxyz"))


def intent_text(mix: Mix, cat: int, intent: int) -> str:
    """Seeded random words for one intent: distinct intents embed far apart
    under feature hashing, and a repeat is the same request."""
    rng = np.random.default_rng(
        [*seed_words(mix.seed, 2), zlib.crc32(mix.cats[cat].name.encode()),
         int(intent)])
    t = mix.spec["text"]
    return " ".join("".join(rng.choice(LETTERS, t["word_len"]))
                    for _ in range(t["words"]))


def intent_prompt(mix: Mix, cat: int, intent: int, rank: int,
                  vocab: int) -> np.ndarray:
    """Seeded prompt token ids of one intent. The length depends on the
    popularity rank alone (the fixed work): a bounded Pareto over
    [min, max]; the ids on the run seed."""
    p = mix.spec["prompt"]
    u = ((zlib.crc32(f"{mix.cats[cat].name}:{rank}".encode())
          % 1_000_003) + 0.5) / 1_000_003
    lo, hi, a = p["min"], p["max"], p["pareto_alpha"]
    # inverse CDF of the Pareto(a) bounded to [lo, hi]
    n = lo / (1.0 - u * (1.0 - (lo / hi) ** a)) ** (1.0 / a)
    n = int(min(hi, max(lo, round(n))))
    rng = np.random.default_rng(
        [*seed_words(mix.seed, 2), 0x9E37, zlib.crc32(
            mix.cats[cat].name.encode()), int(intent)])
    return rng.integers(1, vocab, n).astype(np.int32)
