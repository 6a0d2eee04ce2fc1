"""The plain references against the program at tiny sizes (CPU): the
granite-moe forward against ``repro.models`` through prefill and decode,
and the traffic copy against ``SyntheticCategorySpace``."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.configs import granite_moe_ref as ref
from bench.tests.tiny import TINY_ARCH
from bench.traffic_gen import Mix, fill_entries


def _program_logits(arch, params, seqs, prompt_len):
    """Prefill the prompt, then decode the rest of ``seqs`` through the
    program's cache; logits at each position from prompt_len - 1 on."""
    from repro.models.config import ArchConfig
    from repro.models.model import Model
    model = Model(ArchConfig(**arch))
    T = seqs.shape[1]
    logits, cache, kv = model.prefill(
        params, {"tokens": jnp.asarray(seqs[:, :prompt_len])}, T + 1)
    out = [logits]
    for t in range(prompt_len, T):
        logits, cache, kv = model.decode_step(
            params, cache, jnp.asarray(seqs[:, t]), kv)
        out.append(logits)
    V = arch["vocab_size"]
    return np.stack([np.asarray(o[:, :V], np.float32) for o in out], 1)


@pytest.mark.parametrize("n_layers,top_k", [(2, 8), (1, 2)],
                         ids=["all-experts", "routed"])
def test_reference_matches_program_prefill_and_decode(n_layers, top_k):
    """fp32 weights and activations on both sides. Two layers with every
    expert active (the router's weights are continuous, so a rounding
    cannot flip a route) check attention, rotary, norms and the expert
    combine through the cache; one layer with top-2 routing checks the
    routing itself."""
    arch = dict(TINY_ARCH, dtype="float32", n_layers=n_layers,
                moe_top_k=top_k)
    params = jax.jit(lambda k: ref.make_weights(arch, k))(jax.random.key(3))
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    rng = np.random.default_rng(0)
    P, new = 12, 6
    seqs = rng.integers(1, arch["vocab_size"], (3, P + new - 1)).astype(
        np.int32)
    with jax.default_matmul_precision("highest"):
        prog = _program_logits(arch, params, seqs, P)
        mine = np.asarray(ref.reference_logits(arch, params, seqs, P - 1))
    assert prog.shape == mine.shape
    err = np.max(np.abs(prog - mine))
    scale = np.max(np.abs(mine))
    # the program's prefill attention rounds its probabilities to bf16
    # (models/attention.py): ~4e-3 of the logits' scale
    assert err <= 1e-2 * scale, (err, scale)


def test_fp8_control_departs_from_the_reference():
    arch = dict(TINY_ARCH)
    params = jax.jit(lambda k: ref.make_weights(arch, k))(jax.random.key(4))
    seqs = np.random.default_rng(1).integers(
        1, arch["vocab_size"], (4, 24)).astype(np.int32)
    exact = np.asarray(ref.reference_logits(arch, params, seqs, 12))
    low = np.asarray(ref.reference_logits(arch, params, seqs, 12,
                                          low_precision=True))
    assert np.max(np.abs(low - exact)) > 1e-2 * np.max(np.abs(exact))


def _spec(pool=2000):
    t = json.loads((harness.BENCH_DIR / "traffic" / "table1.json")
                   .read_text())
    for c in t["categories"]:
        c["pool"] = pool
    return t


@pytest.mark.parametrize("name", ["code_generation", "conversational_chat"])
def test_traffic_copy_reproduces_the_program_geometry(name):
    """At a small pool, paraphrase cosines and the 10th-NN distance
    profile of the lazily derived centers match the program's
    ``SyntheticCategorySpace`` for the same category parameters."""
    from repro.core.embedding import SyntheticCategorySpace
    mix = Mix(_spec(), seed=2**31 + 99)
    k = [c.name for c in mix.cats].index(name)
    c = mix.cats[k]
    space = SyntheticCategorySpace(c.name, c.pool, c.sigma, c.center_spread,
                                   c.loose_frac, c.loose_mult, seed=5)
    rng = np.random.default_rng(5)
    n = 2048
    ids = rng.integers(0, c.pool, n)
    a, b = space.sample_batch(ids, rng), space.sample_batch(ids, rng)
    prog_cos = float(np.mean(np.sum(a * b, 1)))
    cat = np.full(n, k)
    x, y = mix.embeddings(cat, ids, "a"), mix.embeddings(cat, ids, "b")
    mine_cos = float(np.mean(np.sum(x * y, 1)))
    assert mine_cos == pytest.approx(prog_cos, abs=0.01)

    probe = np.random.default_rng(1234).integers(0, c.pool, 256)
    pts = mix.embeddings(np.full(256, k), probe, "nn")
    s = pts @ pts.T
    np.fill_diagonal(s, -np.inf)
    mine_nn = float(np.mean(1.0 - np.sort(s, 1)[:, -10]))
    assert mine_nn == pytest.approx(space.nn_distance_profile(), rel=0.1)


def test_traffic_is_seeded_and_its_work_fixed():
    spec = _spec()
    a, b = Mix(spec, seed=7), Mix(spec, seed=7)
    ca, ia, ra = a.requests(500)
    cb, ib, rb = b.requests(500)
    assert np.array_equal(ia, ib)
    assert np.array_equal(a.embeddings(ca, ia, "w"), b.embeddings(cb, ib, "w"))
    c = Mix(spec, seed=2**33 + 1)          # seeds past 32 bits
    cc, ic, rc = c.requests(500)
    # same work in another order: category and rank multisets agree
    assert sorted(zip(ca.tolist(), ra.tolist())) == \
        sorted(zip(cc.tolist(), rc.tolist()))
    assert not np.array_equal(ca, cc)
    t = a.arrivals(100, 10.0)
    assert np.all(np.diff(t) > 0) and 0.0 < t[0] and t[-1] < 10.0


def test_fill_keeps_recent_distinct_intents_up_to_quota():
    spec = _spec(pool=500)
    spec["history_requests"] = 20000
    mix = Mix(spec, seed=3)
    quota = {c.name: 100 for c in mix.cats}
    cat, intent = fill_entries(mix, quota)
    for k in range(len(mix.cats)):
        ids = intent[cat == k]
        assert len(ids) == len(set(ids.tolist())) <= 100
