"""The engine's real generate program behind the cache, for every
architecture that takes a text prompt: a miss is served with the tokens of
a plain greedy loop over ``Model.prefill``/``decode_step``, and the same
text sent again is a hit that runs no generate."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.cache import SemanticCache
from repro.core.clock import SimClock
from repro.core.hnsw import _bucket_batch
from repro.core.policy import PolicyEngine, paper_policies
from repro.models import Model
from repro.serving.engine import ServingEngine

TEXT_ARCHS = ["llama3_2_3b", "gemma2_2b", "granite_8b", "deepseek_67b",
              "granite_moe_3b_a800m", "kimi_k2_1t_a32b", "jamba_v0_1_52b",
              "falcon_mamba_7b", "granite_4_0_h_small"]

PROMPT_LEN, NEW = 16, 4

# granite's x12 embedding multiplier under a tied head makes randomly
# initialized weights echo the last input token whatever came before it;
# at 1.0 the tokens depend on the whole prompt.
OVERRIDES = {"granite_4_0_h_small": {"embedding_multiplier": 1.0}}


def _greedy(model, params, tokens, max_new):
    """Greedy decoding one step at a time, outside the engine."""
    vocab = model.cfg.vocab_size
    logits, cache, kv_len = jax.jit(model.prefill, static_argnums=2)(
        params, {"tokens": tokens}, tokens.shape[1] + max_new)
    tok = jnp.argmax(logits[:, :vocab], axis=-1).astype(jnp.int32)
    out = [tok]
    decode = jax.jit(model.decode_step)
    for _ in range(max_new - 1):
        logits, cache, kv_len = decode(params, cache, tok, kv_len)
        tok = jnp.argmax(logits[:, :vocab], axis=-1).astype(jnp.int32)
        out.append(tok)
    return np.asarray(jnp.stack(out, axis=1))


@pytest.mark.parametrize("arch", TEXT_ARCHS)
def test_engine_generates_then_hits(arch):
    cfg = get_config(arch).reduced(dtype="float32", **OVERRIDES.get(arch, {}))
    model = Model(cfg)
    params = model.init_params(jax.random.key(0))
    cache = SemanticCache(PolicyEngine(paper_policies()), capacity=64,
                          clock=SimClock(), index_kind="flat")
    eng = ServingEngine(model, params, cache, max_batch=4,
                        prompt_len=PROMPT_LEN, max_new_tokens=NEW)
    calls = []
    generate = eng._generate
    eng._generate = lambda *a: calls.append(1) or generate(*a)

    prompt = np.random.default_rng(1).integers(2, cfg.vocab_size, 11)
    text = f"how do I sort a list with {arch}"
    eng.submit(text, "code_generation", prompt)
    (miss,) = eng.drain()
    assert not miss.cached and len(calls) == 1

    padded = np.zeros((_bucket_batch(1), PROMPT_LEN), np.int32)
    padded[0, :len(prompt)] = prompt
    want = _greedy(model, params, jnp.asarray(padded), NEW)[0]
    assert len(set(want.tolist())) > 1      # not one token echoed
    np.testing.assert_array_equal(miss.tokens, want)

    eng.submit(text, "code_generation", prompt)
    (hit,) = eng.drain()
    assert hit.cached and hit.text == miss.text
    assert len(calls) == 1
