"""Distribution: sharding plans, MoE EP equivalence, sharded loss
equivalence, mesh construction. Multi-device tests run in subprocesses so
the main process keeps its single-CPU jax runtime."""

import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.distributed.context import Dist
from repro.launch import sharding as shd
from repro.models.model import Model


def run_sub(code: str, devices: int = 8, timeout: int = 480) -> str:
    env = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
           "PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}
    import os
    env.update({k: v for k, v in os.environ.items()
                if k not in env and k != "XLA_FLAGS"})
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env, cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_plan_rules_single_pod(arch):
    """The serve plan gives exactly one PartitionSpec per parameter leaf,
    none longer than its leaf's rank (a rule written for a 2-D leaf that
    meets a 1-D one, as Mamba-2's ``A_log`` did, fails here), and every
    leaf has a rule of its own."""
    cfg = get_config(arch)
    shapes = jax.eval_shape(Model(cfg).init_params, jax.random.key(0))
    plan = shd.param_plan(cfg, Dist.single())
    is_spec = lambda x: isinstance(x, P)
    assert (jax.tree.structure(plan.params, is_leaf=is_spec)
            == jax.tree.structure(shapes))
    specs = jax.tree.leaves(plan.params, is_leaf=is_spec)
    leaves = jax.tree.leaves(shapes)
    assert all(is_spec(s) for s in specs)
    for spec, leaf in zip(specs, leaves):
        assert len(spec) <= leaf.ndim, (spec, leaf.shape)
    assert not [n for n in plan.notes if "no rule" in n], plan.notes


def test_param_plan_on_real_mesh():
    out = run_sub("""
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_config
        from repro.distributed.context import Dist
        from repro.launch import sharding as shd
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        dist = Dist.from_mesh(mesh)
        cfg = get_config("deepseek_67b")
        plan = shd.param_plan(cfg, dist)
        s = plan.params["stack"]["sub0"]
        # weights are tensor-parallel over model, replicated over data
        assert s["mix"]["wq"] == P(None, None, "model", None), s["mix"]["wq"]
        assert s["mix"]["wo"] == P(None, "model", None, None)
        assert s["mlp"]["w_gate"] == P(None, None, "model")
        assert s["mlp"]["w_down"] == P(None, "model", None)
        assert plan.params["embed"] == P("model", None)
        # MoE experts are stored over data
        moe = shd.param_plan(get_config("granite_moe_3b_a800m"), dist)
        w = moe.params["stack"]["sub0"]["mlp"]
        assert w["w_gate"] == P(None, ("data",), None, "model"), w["w_gate"]
        assert w["w_down"] == P(None, ("data",), "model", None)
        print("OK")
    """)
    assert "OK" in out


def test_moe_ep_matches_dense_on_mesh():
    out = run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.distributed.context import Dist
        from repro.models import moe as moe_mod
        from repro.models.config import ArchConfig
        from repro.models.layers import init_moe
        cfg = ArchConfig(name="t", family="moe", n_layers=2, d_model=64,
                         n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=512,
                         n_experts=10, moe_top_k=3, d_ff_expert=32,
                         capacity_factor=4.0)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        dist = Dist.from_mesh(mesh)
        p = init_moe(jax.random.key(0), cfg)
        x = jnp.asarray(np.random.default_rng(3).standard_normal((64, 64)),
                        jnp.float32)
        y_ep, aux_ep = jax.jit(
            lambda x, p: moe_mod.moe_ffn_ep(x, p, cfg, dist))(x, p)
        y_ref, aux_ref = moe_mod.moe_ffn_dense_exact(x, p, cfg)
        err = float(jnp.max(jnp.abs(y_ep - y_ref)))
        assert err < 1e-4, err
        assert abs(float(aux_ep) - float(aux_ref)) < 1e-5
        print("OK", err)
    """)
    assert "OK" in out


def test_production_mesh_shapes():
    out = run_sub("""
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh()
        assert m1.shape == {"data": 16, "model": 16}, m1.shape
        m2 = make_production_mesh(multi_pod=True)
        assert m2.shape == {"pod": 2, "data": 16, "model": 16}
        print("OK")
    """, devices=512)
    assert "OK" in out


def test_sharded_loss_equals_single_device():
    """End-to-end numerical equivalence: the mesh run (EP MoE + sequence-
    sharded attention + all sharding constraints) must produce the same
    loss as the single-device run up to bf16 reduction noise."""
    out = run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.distributed.context import Dist
        from repro.launch import sharding as shd
        from repro.models.model import Model

        cfg = get_config("granite_moe_3b_a800m").reduced(
            n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
            vocab_size=256, n_experts=8, moe_top_k=2, d_ff_expert=32,
            capacity_factor=4.0)
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(1, 256, (8, 64))),
                 "labels": jnp.asarray(rng.integers(0, 256, (8, 64)))}

        m_single = Model(cfg, None)
        params = m_single.init_params(jax.random.key(0))
        loss_single, _ = jax.jit(m_single.loss_fn)(params, batch)

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        dist = Dist.from_mesh(mesh)
        m_mesh = Model(cfg, dist)
        pshard = shd.param_plan(cfg, dist).shardings(mesh)
        params_sharded = jax.device_put(params, pshard)
        loss_mesh, _ = jax.jit(m_mesh.loss_fn)(params_sharded, batch)

        d = abs(float(loss_single) - float(loss_mesh))
        assert d < 5e-3, (float(loss_single), float(loss_mesh))
        print("OK", float(loss_single), float(loss_mesh))
    """)
    assert "OK" in out
