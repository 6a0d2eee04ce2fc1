"""The served path's Pallas kernels carry explicit names: the instruction
a device trace shows for each (``%flat_topk.1 = ... custom-call``), which
the benchmark's roofline readers find them by. Lowered for TPU without a
chip: the name is the Mosaic call's ``kernel_name``."""

import re

import jax
import jax.numpy as jnp
import pytest

N, D, B, F, M, R = 1024, 128, 8, 8, 8, 16


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _call(name):
    from repro.kernels import flat_topk, frontier_hop, gather_scores, \
        scatter_update
    emb, q = _sds((N, D)), _sds((B, D))
    cats, qcat = _sds((N,), jnp.int32), _sds((B,), jnp.int32)
    cand = _sds((B, F), jnp.int32)
    if name == "flat_topk":
        return (lambda e, v, q, c, qc: flat_topk.flat_topk(e, v, q, c, qc),
                (emb, _sds((N,), jnp.int8), q, cats, qcat))
    if name == "frontier_hop":
        return (lambda e, nb, me, fr, q, qc, dn: frontier_hop.frontier_hop(
            e, nb, me, fr, q, qc, dn),
            (emb, _sds((N, M), jnp.int32), cats, cand, q, qcat, qcat))
    if name == "gather_scores":
        return (lambda e, i, q: gather_scores.gather_scores(e, i, q),
                (emb, cand, q))
    if name == "gather_scores_masked":
        return (lambda e, i, q, c, qc: gather_scores.gather_scores_masked(
            e, i, q, c, qc), (emb, cand, q, cats, qcat))
    return (lambda e, r, v: scatter_update.scatter_rows(e, r, v),
            (emb, _sds((R,), jnp.int32), _sds((R, D))))


@pytest.mark.parametrize("name", ["flat_topk", "frontier_hop",
                                  "gather_scores", "gather_scores_masked",
                                  "scatter_rows"])
def test_kernel_has_its_stable_name(name):
    fn, args = _call(name)
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    names = re.findall(r'kernel_name = "([^"]*)"', text)
    assert names == [name]
