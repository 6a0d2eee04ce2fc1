"""Pallas TPU kernels for the framework's compute hot-spots.

The paper's data plane (cache similarity search) and the serving substrate
(attention, SSM scan) each get a TPU kernel with explicit BlockSpec VMEM
tiling, a jit'd wrapper in ``ops.py``, and a pure-jnp oracle in ``ref.py``:

    flat_topk        — tiled cosine top-1 + threshold over the cache table,
                       category-masked in-kernel (the hybrid cache's 2 ms
                       local search, §5.2/§5.3)
    frontier_hop     — FUSED beam expansion: scalar-prefetched frontier ids
                       → in-kernel neighbor-row fetch → per-candidate
                       embedding DMAs → masked scores; done queries issue
                       no DMAs (the lookup hot loop, §5.3)
    gather_scores    — scalar-prefetch gather + dot (entry-set scoring);
                       ``gather_scores_masked`` adds the per-query category
                       mask (§5.3)
    scatter_update   — in-place row scatter, the device tables' delta flush
    flash_attention  — tiled prefill attention (causal / sliding-window /
                       logit softcap / GQA)
    decode_attention — single-token decode against a long KV cache
    mamba_scan       — chunked selective-scan recurrence (Mamba1)

Kernels target TPU (MXU-aligned tiles, VMEM budgets, tile-aligned DMAs);
on the CPU they run with ``interpret=True`` against the oracles, and
tests/test_chip_compile.py compiles the cache kernels for a described v5e.
The models attend and scan in pure jnp; no served path calls the three
model kernels yet (they are reached through ``ops`` by tests and
``benchmarks/bench_kernels.py``).
"""
