"""chip_smoke.py's phases, rehearsed on the CPU at tiny sizes.

On the chip the script drives the served path at full size; here the same
phase functions run with small tables and a reduced model (kernels in
interpret mode, the reference hop), so a change that breaks the smoke's
entry points or checks fails in the tier-1 suite and not first on the
chip. The script itself refuses every backend but a TPU.
"""

import importlib.util
import json
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_phases_on_cpu(smoke, capsys):
    from repro.configs import get_config
    from repro.core.policy import PolicyEngine, paper_policies
    from repro.core.workload import TABLE1_WORKLOAD, WorkloadGenerator
    dev = jax.devices()[0]
    policies = PolicyEngine(paper_policies())
    gen = WorkloadGenerator(TABLE1_WORKLOAD, seed=0)
    with smoke.Census() as census:
        with smoke.Phase("flat", census, dev, "-", on_chip=False) as ph:
            cache = smoke.phase_flat(ph, policies, gen, rows=4096,
                                     n_queries=32)
        with smoke.Phase("hnsw", census, dev, "-", on_chip=False) as ph:
            smoke.phase_hnsw(ph, policies, gen, rows=2048, n_queries=32,
                             on_chip=False, seed=0)
        with smoke.Phase("served", census, dev, "-", on_chip=False) as ph:
            smoke.phase_served(
                ph, census, policies, cache,
                get_config("granite_moe_3b_a800m").reduced(), seed=0,
                shard_capacity=256, on_chip=False)
    lines = capsys.readouterr().out.splitlines()
    phases = {ln.split()[0]: json.loads(ln.split(" ", 1)[1]) for ln in lines}
    assert set(phases) == {"[flat]", "[hnsw]", "[served]"}
    assert phases["[flat]"]["ids_equal"] + \
        phases["[flat]"]["ids_excused_tie_or_tau"] == 32
    assert phases["[hnsw]"]["fused_ids_equal_reference"]
    served = phases["[served]"]
    assert served["single_cache"] == {"served": 48, "hits": 16,
                                      "misses": 32,
                                      "pallas_delta_flushes": 0,
                                      "delta_updates": 4}
    assert served["sharded"]["hits"] == 8
    from jax.experimental import pallas as pl
    assert pl.pallas_call.__module__.startswith("jax")   # hooks removed


def test_smoke_refuses_a_cpu_backend():
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, timeout=120,
                         env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert out.returncode != 0
    assert "'cpu'" in out.stderr
    assert '"ok"' not in out.stdout
