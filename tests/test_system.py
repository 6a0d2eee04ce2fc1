"""End-to-end behaviour tests for the paper's system: real model behind
the category-aware cache."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.cache import SemanticCache
from repro.core.clock import SimClock
from repro.core.policy import AdaptiveController, PolicyEngine, \
    paper_policies
from repro.models import Model
from repro.serving.engine import ServingEngine


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("llama3_2_3b").reduced(n_layers=2, d_model=64,
                                            vocab_size=256)
    model = Model(cfg)
    params = model.init_params(jax.random.key(0))
    return cfg, model, params


def test_engine_serves_hits_without_model(small_model, rng):
    cfg, model, params = small_model
    policies = PolicyEngine(paper_policies())
    cache = SemanticCache(policies, capacity=1024, clock=SimClock(),
                          index_kind="flat")
    eng = ServingEngine(model, params, cache, max_batch=4, prompt_len=16,
                        max_new_tokens=4)
    toks = rng.integers(2, cfg.vocab_size, 16)
    eng.submit("how do I sort a list in python", "code_generation", toks)
    r1 = eng.drain()
    assert len(r1) == 1 and not r1[0].cached
    tokens_after_first = eng.stats.model_tokens
    # paraphrase-identical resubmission → cache hit, no new model tokens
    eng.submit("how do I sort a list in python", "code_generation", toks)
    r2 = eng.drain()
    assert r2[0].cached
    assert eng.stats.model_tokens == tokens_after_first
    assert r2[0].text == r1[0].text


def test_engine_compliance_always_model(small_model, rng):
    cfg, model, params = small_model
    policies = PolicyEngine(paper_policies())
    cache = SemanticCache(policies, capacity=128, clock=SimClock(),
                          index_kind="flat")
    eng = ServingEngine(model, params, cache, max_batch=2, prompt_len=16,
                        max_new_tokens=4)
    toks = rng.integers(2, cfg.vocab_size, 16)
    for _ in range(2):
        eng.submit("patient record 1234", "phi_medical_records", toks)
    res = eng.drain()
    assert all(not r.cached for r in res)
    assert len(cache) == 0


def test_engine_watchdog_counts_straggler_steps(small_model, rng):
    """The StepWatchdog rides every non-empty step(): fast steps build
    the median history, an artificially slowed step surfaces as
    ``stats.straggler_steps``."""
    import time as _time
    from repro.distributed.fault import StepWatchdog

    cfg, model, params = small_model
    policies = PolicyEngine(paper_policies())
    cache = SemanticCache(policies, capacity=128, clock=SimClock(),
                          index_kind="flat")
    wd = StepWatchdog(timeout_factor=20.0, min_history=5)
    eng = ServingEngine(model, params, cache, max_batch=1, prompt_len=16,
                        max_new_tokens=4, watchdog=wd)
    assert eng.step() == []                 # empty queue: never timed
    toks = rng.integers(2, cfg.vocab_size, 16)
    # one miss compiles + serves, then hits build a stable fast history
    for i in range(8):
        eng.submit("what is a closure", "code_generation", toks)
        eng.step()
    assert eng.stats.straggler_steps == 0
    # slow one step far past 20× the (hit-dominated, ~ms) median
    orig = eng._generate

    def slow_generate(p, t):
        _time.sleep(0.5)
        return orig(p, t)
    eng._generate = slow_generate
    eng.submit("a brand new uncached question", "code_generation", toks)
    eng.step()
    eng._generate = orig
    assert eng.stats.straggler_steps == 1
    assert wd.straggler_events == 1


def test_engine_miss_counts_share_one_generate_program(small_model, rng):
    """Every miss count 1..max_batch pads to one bucket, so the model's
    generate program compiles once, and each miss still gets its own
    response."""
    cfg, model, params = small_model
    policies = PolicyEngine(paper_policies())
    cache = SemanticCache(policies, capacity=256, clock=SimClock(),
                          index_kind="flat")
    eng = ServingEngine(model, params, cache, max_batch=8, prompt_len=16,
                        max_new_tokens=4)
    served = 0
    for n in range(1, eng.max_batch + 1):
        for i in range(n):
            eng.submit(f"unique question {n}-{i} about sorting",
                       "code_generation", rng.integers(2, cfg.vocab_size, 16))
        res = eng.step()
        assert len(res) == n and not any(r.cached for r in res)
        assert all(r.tokens.shape == (4,) for r in res)
        served += n
    assert eng.stats.served == served
    assert eng._generate._cache_size() == 1


def test_adaptive_integration_relaxes_threshold(small_model, rng):
    cfg, model, params = small_model
    ctl = AdaptiveController()
    ctl.register_model("default", latency_target_ms=1.0, queue_target=1)
    policies = PolicyEngine(paper_policies(), controller=ctl)
    policies.update("code_generation", model_name="default")
    base_tau = policies.effective("code_generation").threshold
    cache = SemanticCache(policies, capacity=512, clock=SimClock(),
                          index_kind="flat")
    eng = ServingEngine(model, params, cache, max_batch=4, prompt_len=16,
                        max_new_tokens=4, controller=ctl)
    for i in range(12):                    # misses → model calls → load obs
        toks = rng.integers(2, cfg.vocab_size, 16)
        eng.submit(f"query number {i} entirely unique", "code_generation",
                   toks)
    eng.drain()
    assert policies.effective("code_generation").threshold < base_tau


def test_engine_stamps_latency_when_the_step_returns(small_model, rng):
    """Each response carries the time to the end of the step that hands
    it back: a hit found beside misses returns from its lookup step,
    before the misses' generate; the misses from the next step, after
    it. The engine's latency total is their sum."""
    import time as _time

    cfg, model, params = small_model
    policies = PolicyEngine(paper_policies())
    cache = SemanticCache(policies, capacity=128, clock=SimClock(),
                          index_kind="flat")
    eng = ServingEngine(model, params, cache, max_batch=4, prompt_len=16,
                        max_new_tokens=4)
    toks = rng.integers(2, cfg.vocab_size, 16)
    eng.submit("what is a closure", "code_generation", toks)
    eng.drain()                                  # now cached
    total0 = eng.stats.total_latency_ms

    def slow_generate(p, t):
        _time.sleep(0.3)
        return np.zeros((t.shape[0], 4), np.int32)
    eng._generate = slow_generate
    t0 = _time.monotonic()
    eng.submit("what is a closure", "code_generation", toks)
    eng.submit("a brand new uncached question", "code_generation", toks)
    (hit,) = eng.step()
    hit_wall_ms = (_time.monotonic() - t0) * 1e3
    (miss,) = eng.step()
    wall_ms = (_time.monotonic() - t0) * 1e3
    assert hit.cached and not miss.cached
    assert hit.latency_ms <= hit_wall_ms < 300
    assert 300 <= miss.latency_ms <= wall_ms
    assert eng.stats.total_latency_ms - total0 == pytest.approx(
        hit.latency_ms + miss.latency_ms)


def test_generate_program_names_its_phases(small_model):
    """The generate program's ops carry the ``prefill`` and ``decode``
    scopes in their metadata: the decode scan's loop and body under
    ``decode``, the prompt's forward pass under ``prefill``."""
    import re
    cfg, model, params = small_model
    eng = ServingEngine(model, params, None, max_batch=8, prompt_len=16,
                        max_new_tokens=4)
    text = eng._generate.lower(
        params, jnp.zeros((8, 16), jnp.int32)).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    assert any(re.search(r"/decode/while", n) for n in names)
    assert any(re.search(r"/prefill/", n) for n in names)
    assert not any("/prefill/" in n and "/decode/" in n for n in names)
