"""Compile the served path for a TPU v5e chip, without the chip.

The rest of the suite runs the Pallas kernels in interpret mode, which
never asks the TPU compiler anything: a block shape or a DMA the chip's
compiler refuses passes every interpret-mode test. These tests compile —
for a described (not attached) v5e chip, with the compiler libtpu ships —
each cache kernel of the served path at the paper's scale (N = 2^20 rows,
d = 384, fp32 and int8 residency), granite-moe-3b-a800m's generate
program at published width and depth, and granite-4.0-h-small's at
published width, cut to one chip's share. Nothing runs: they pin what the
compiler accepts, not results.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N, D, B = 1 << 20, 384, 8          # rows, embedding width, query batch
F, M, R = 32, 32, 64               # beam, level-0 neighbors, delta rows
HBM_BYTES = 16 * 10**9             # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or it cannot load
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Programs compiled for a described chip cannot be read back from the
    # persistent cache without one: keep it out of these compiles.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_call(name, dtype, s):
    """(function, abstract args) for one served-path kernel."""
    from repro.kernels import flat_topk, frontier_hop, gather_scores, \
        scatter_update
    emb = _sds(s, (N, D), dtype)
    scales = _sds(s, (N,), jnp.float32) if dtype == jnp.int8 else None
    q = _sds(s, (B, D), jnp.float32)
    qcat = _sds(s, (B,), jnp.int32)
    cand = _sds(s, (B, F), jnp.int32)
    if name == "flat_topk":
        return (lambda e, v, q, c, qc, sc: flat_topk.flat_topk(
            e, v, q, c, qc, sc),
            (emb, _sds(s, (N,), jnp.int8), q, _sds(s, (N,), jnp.int32),
             qcat, scales))
    if name == "frontier_hop":
        return (lambda e, nb, me, fr, q, qc, dn, sc: frontier_hop.frontier_hop(
            e, nb, me, fr, q, qc, dn, sc),
            (emb, _sds(s, (N, M), jnp.int32), _sds(s, (N,), jnp.int32), cand,
             q, qcat, qcat, scales))
    if name == "gather_scores":
        return (lambda e, i, q, sc: gather_scores.gather_scores(e, i, q, sc),
                (emb, cand, q, scales))
    if name == "gather_scores_masked":
        return (lambda e, i, q, c, qc, sc: gather_scores.gather_scores_masked(
            e, i, q, c, qc, sc),
            (emb, cand, q, _sds(s, (N,), jnp.int32), qcat, scales))
    assert name == "scatter_rows"
    return (lambda e, r, v: scatter_update.scatter_rows(e, r, v),
            (emb, _sds(s, (R,), jnp.int32), _sds(s, (R, D), dtype)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8],
                         ids=["fp32", "int8"])
@pytest.mark.parametrize("name", ["flat_topk", "frontier_hop",
                                  "gather_scores", "gather_scores_masked",
                                  "scatter_rows"])
def test_cache_kernel_compiles_for_v5e(one_chip, name, dtype):
    fn, args = _kernel_call(name, dtype, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_granite_moe_generate_compiles_for_v5e(one_chip):
    """The engine's generate program (prefill + greedy decode scan) for
    granite-moe-3b-a800m at published width and depth, with its params
    and working memory inside one chip's HBM."""
    from repro.configs import get_config
    from repro.models.model import Model
    from repro.serving.engine import ServingEngine
    cfg = get_config("granite_moe_3b_a800m")
    model = Model(cfg)
    engine = ServingEngine(model, None, None, max_batch=B, prompt_len=32,
                           max_new_tokens=8)
    params = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                          model.param_shapes())
    toks = _sds(one_chip, (B, 32), jnp.int32)
    compiled = engine._generate.lower(params, toks).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 6 * 10**9      # 6.77 GB of bf16
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_granite_4h_generate_compiles_for_v5e(one_chip):
    """The same for granite-4.0-h-small cut to one chip's share (one
    10-layer period: 9 Mamba-2 layers with their fp32 state, one NoPE
    attention layer; 18 of 72 experts and the shared expert on every
    layer; the tied 100,352-row vocabulary)."""
    from dataclasses import replace

    from repro.configs import get_config
    from repro.models.model import Model
    from repro.serving.engine import ServingEngine
    cfg = replace(get_config("granite_4_0_h_small"), n_layers=10,
                  experts_held=18)
    model = Model(cfg)
    engine = ServingEngine(model, None, None, max_batch=B, prompt_len=32,
                           max_new_tokens=8)
    params = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                          model.param_shapes())
    toks = _sds(one_chip, (B, 32), jnp.int32)
    compiled = engine._generate.lower(params, toks).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 6.5 * 10**9    # 6.53 GB of bf16
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
