"""Static Pallas footprint estimator: BlockSpec/grid walking, no device.

A Pallas kernel that overflows VMEM fails at *compile* time on real
hardware — but this repo's CI runs the kernels in interpret mode on CPU,
where any block shape "works". A BlockSpec edit that pushes a tile past
the ~16 MB/core VMEM budget (or a scalar-prefetch operand past SMEM)
would therefore sail through every dynamic test and die on first TPU
contact. This module closes that gap statically: it intercepts
``pl.pallas_call`` under ``jax.eval_shape`` (abstract evaluation — no
kernel body ever runs), records each call's grid, BlockSpecs, scratch
shapes and operand avals, and charges every block to the memory space
its spec declares:

* VMEM: block bytes x 2 for grid-blocked operands/outputs (the pipeline
  double-buffers blocks to overlap DMA with compute), x 1 for scratch;
* SMEM: scalar-prefetch operands (they are materialized in scalar
  memory before the grid runs) plus explicit SMEM blocks;
* ANY: HBM-resident — zero on-chip charge (the kernel DMAs rows out of
  it manually, paying VMEM only for its scratch destination);
* semaphores: counted as objects, not bytes.

``check_kernels`` sweeps every production kernel (``flat_topk``,
``gather_scores[_masked]``, ``frontier_hop``, ``scatter_update``)
across the supported shape families — capacity sweep to 1M rows,
d = 384, fp32 and int8+scale operands — and returns a
:class:`~repro.analysis.contracts.Violation` per kernel config whose
estimated footprint exceeds budget. Pure shape arithmetic: safe for CI,
deterministic, and independent of the host's backend.
"""

from __future__ import annotations

import contextlib
import functools
import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import Violation

# Per-core budgets. VMEM is ~16 MB on current TPU generations; SMEM is
# "small" — 1 MiB is the conservative figure we gate scalar-prefetch
# operands against (a frontier table or delta-row list far past that is
# a design bug regardless of the exact hardware limit).
VMEM_BYTES = 16 * 2**20
SMEM_BYTES = 1 * 2**20


def _space(obj) -> str:
    """Normalize a BlockSpec/MemoryRef memory space to one of
    'vmem' | 'smem' | 'any' | 'semaphore'."""
    ms = getattr(obj, "memory_space", None)
    if ms is None:
        return "vmem"
    s = str(ms).lower()
    for key in ("semaphore", "smem", "any", "vmem"):
        if key in s:
            return key
    return "vmem"


def _block_bytes(spec, aval) -> int:
    shape = getattr(spec, "block_shape", None)
    if shape is None:
        shape = aval.shape
    n = 1
    for dim in shape:
        n *= int(dim) if dim is not None else 1
    return n * np.dtype(aval.dtype).itemsize


@dataclass
class KernelFootprint:
    """One captured ``pallas_call``: its static shape facts and the
    VMEM/SMEM bytes the blocks imply."""
    name: str
    grid: tuple
    vmem_bytes: int = 0
    smem_bytes: int = 0
    semaphores: int = 0
    detail: list = field(default_factory=list)

    def _charge(self, label: str, space: str, nbytes: int) -> None:
        if space == "vmem":
            self.vmem_bytes += nbytes
        elif space == "smem":
            self.smem_bytes += nbytes
        self.detail.append((label, space, nbytes))

    def violations(self, target: str, *, vmem_budget: int = VMEM_BYTES,
                   smem_budget: int = SMEM_BYTES) -> list[Violation]:
        out = []
        for space, used, budget in (("VMEM", self.vmem_bytes, vmem_budget),
                                    ("SMEM", self.smem_bytes, smem_budget)):
            if used > budget:
                top = sorted(self.detail, key=lambda t: -t[2])[:3]
                out.append(Violation(
                    "VmemBudget", target,
                    f"kernel '{self.name}' needs {used / 2**20:.2f} MiB "
                    f"{space} (budget {budget / 2**20:.0f} MiB) for grid "
                    f"{self.grid}",
                    "largest blocks: " + ", ".join(
                        f"{l} [{s}] {b / 2**20:.2f} MiB" for l, s, b in top)))
        return out


def _kernel_name(fn) -> str:
    while isinstance(fn, functools.partial):
        fn = fn.func
    return getattr(fn, "__name__", repr(fn))


@contextlib.contextmanager
def capture_pallas_calls():
    """Swap ``pallas_call`` for a recorder while tracing. The fake
    returns zeros of ``out_shape``, so the wrapped computation stays
    traceable under ``jax.eval_shape`` without lowering any kernel —
    the kernel modules resolve ``pl.pallas_call`` at call time, which
    is what makes the module-attribute patch sufficient."""
    import jax.experimental.pallas as pl_mod
    captured: list[KernelFootprint] = []
    real = pl_mod.pallas_call

    def fake_pallas_call(kernel, *, grid_spec=None, grid=None,
                         in_specs=None, out_specs=None, out_shape=None,
                         scratch_shapes=(), **kw):
        n_prefetch = 0
        if grid_spec is not None:
            n_prefetch = getattr(grid_spec, "num_scalar_prefetch", 0)
            grid = grid_spec.grid
            in_specs = grid_spec.in_specs
            out_specs = grid_spec.out_specs
            scratch_shapes = getattr(grid_spec, "scratch_shapes", ())

        def runner(*operands):
            fp = KernelFootprint(name=_kernel_name(kernel),
                                 grid=tuple(grid or ()))
            avals = [jax.ShapeDtypeStruct(jnp.shape(x),
                                          jnp.result_type(x))
                     for x in operands]
            # Scalar-prefetch operands are materialized whole in SMEM
            # before step 0.
            for i, a in enumerate(avals[:n_prefetch]):
                fp._charge(f"prefetch{i}{list(a.shape)}", "smem",
                           math.prod(a.shape)
                           * np.dtype(a.dtype).itemsize)
            specs = jax.tree_util.tree_leaves(
                in_specs, is_leaf=lambda s: hasattr(s, "block_shape"))
            grid_blocked = bool(grid)
            for i, (spec, a) in enumerate(zip(specs, avals[n_prefetch:])):
                space = _space(spec)
                if space == "any":
                    fp.detail.append((f"in{i}[hbm]", "any", 0))
                    continue
                mult = 2 if grid_blocked and space == "vmem" else 1
                fp._charge(f"in{i}{list(a.shape)}", space,
                           mult * _block_bytes(spec, a))
            outs = jax.tree_util.tree_leaves(
                out_shape,
                is_leaf=lambda s: isinstance(s, jax.ShapeDtypeStruct))
            ospecs = jax.tree_util.tree_leaves(
                out_specs, is_leaf=lambda s: hasattr(s, "block_shape"))
            if len(ospecs) < len(outs):
                ospecs = ospecs + [None] * (len(outs) - len(ospecs))
            for i, (spec, a) in enumerate(zip(ospecs, outs)):
                space = _space(spec) if spec is not None else "vmem"
                if space == "any":
                    fp.detail.append((f"out{i}[hbm]", "any", 0))
                    continue
                mult = 2 if grid_blocked and space == "vmem" else 1
                nbytes = (_block_bytes(spec, a) if spec is not None
                          else math.prod(a.shape)
                          * np.dtype(a.dtype).itemsize)
                fp._charge(f"out{i}{list(a.shape)}", space, mult * nbytes)
            for i, sc in enumerate(scratch_shapes or ()):
                space = _space(sc)
                if space == "semaphore":
                    fp.semaphores += 1
                    continue
                shape = getattr(sc, "shape", ())
                dt = getattr(sc, "dtype", jnp.float32)
                fp._charge(f"scratch{i}{list(shape)}", space,
                           math.prod(shape) * np.dtype(dt).itemsize)
            captured.append(fp)
            return [jnp.zeros(s.shape, s.dtype) for s in outs] \
                if isinstance(out_shape, (list, tuple)) else \
                jnp.zeros(out_shape.shape, out_shape.dtype)

        return runner

    # Jitted kernel wrappers keep their traces: drop them on the way in
    # (so the fake is called) and out (so no later caller compiles zeros).
    jax.clear_caches()
    pl_mod.pallas_call = fake_pallas_call
    try:
        yield captured
    finally:
        pl_mod.pallas_call = real
        jax.clear_caches()


def estimate(fn, *args, **kwargs) -> list[KernelFootprint]:
    """Abstractly evaluate ``fn(*args, **kwargs)`` and return the
    footprint of every ``pallas_call`` it issues. ``args`` may be
    arrays or ``ShapeDtypeStruct``s — nothing is computed."""
    with capture_pallas_calls() as captured:
        jax.eval_shape(functools.partial(fn, **kwargs), *args)
    return captured


# ---------------------------------------------------------------------------
# The production sweep.
# ---------------------------------------------------------------------------

def _sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, dtype)


def default_kernel_configs(*, d: int = 384):
    """(name, thunk) per supported kernel shape family. The capacity
    sweep tops out at 1M rows (the paper's flat-scan scale) and covers
    both residency dtypes; thunks call the *real* kernel entry points,
    so BlockSpec edits are picked up automatically."""
    from repro.kernels import flat_topk as FT
    from repro.kernels import frontier_hop as FH
    from repro.kernels import gather_scores as GS
    from repro.kernels import scatter_update as SU

    def _table(N, dtype):
        emb = _sds((N, d), dtype)
        scales = _sds((N,), jnp.float32) if dtype == jnp.int8 else None
        return emb, scales

    configs = []
    for dtype in (jnp.float32, jnp.int8):
        tag = "int8" if dtype == jnp.int8 else "fp32"
        for N in (4096, 65536, 1 << 20):
            for B in (8, 128):
                emb, scales = _table(N, dtype)
                configs.append((
                    f"flat_topk[{tag}] N={N} B={B}",
                    functools.partial(
                        FT.flat_topk, emb, _sds((N,), jnp.int8),
                        _sds((B, d), jnp.float32), _sds((N,), jnp.int32),
                        _sds((B,), jnp.int32), scales)))
        for B, K in ((8, 256), (128, 1024)):
            emb, scales = _table(65536, dtype)
            configs.append((
                f"gather_scores[{tag}] B={B} K={K}",
                functools.partial(
                    GS.gather_scores, emb, _sds((B, K), jnp.int32),
                    _sds((B, d), jnp.float32), scales)))
            configs.append((
                f"gather_scores_masked[{tag}] B={B} K={K}",
                functools.partial(
                    GS.gather_scores_masked, emb, _sds((B, K), jnp.int32),
                    _sds((B, d), jnp.float32), _sds((65536,), jnp.int32),
                    _sds((B,), jnp.int32), scales)))
        for B, F, M in ((8, 32, 32), (128, 32, 32)):
            N = 65536
            emb, scales = _table(N, dtype)
            configs.append((
                f"frontier_hop[{tag}] B={B} F={F} M={M}",
                functools.partial(
                    FH.frontier_hop, emb, _sds((N, M), jnp.int32),
                    _sds((N,), jnp.int32), _sds((B, F), jnp.int32),
                    _sds((B, d), jnp.float32), _sds((B,), jnp.int32),
                    _sds((B,), jnp.int32), scales)))
        for R in (8, 1024, 8192):
            configs.append((
                f"scatter_rows[{tag}] R={R}",
                functools.partial(
                    SU.scatter_rows, _sds((65536, d), dtype),
                    _sds((R,), jnp.int32), _sds((R, d), dtype))))
    return configs


def check_kernels(configs=None, *, vmem_budget: int = VMEM_BYTES,
                  smem_budget: int = SMEM_BYTES
                  ) -> tuple[list[Violation], list[tuple]]:
    """Run the footprint estimator over ``configs`` (default: the full
    production sweep). Returns (violations, report) with report one
    ``(config_name, KernelFootprint)`` per captured kernel launch."""
    configs = default_kernel_configs() if configs is None else configs
    violations: list[Violation] = []
    report: list[tuple] = []
    for name, thunk in configs:
        for fp in estimate(thunk):
            report.append((name, fp))
            violations.extend(fp.violations(
                name, vmem_budget=vmem_budget, smem_budget=smem_budget))
    return violations, report
