"""repro: category-aware semantic caching for heterogeneous LLM workloads.

Production-grade JAX framework implementing Wang et al., "Category-Aware
Semantic Caching for Heterogeneous LLM Workloads" (CS.DB 2025):

- ``repro.core``     — the paper's contribution: category policy engine,
                       hybrid HNSW-in-memory / external-document cache,
                       break-even economics, adaptive load-based policies.
- ``repro.models``   — LLM substrate (dense / MoE / SSM / hybrid / enc-dec).
- ``repro.kernels``  — Pallas TPU kernels for the cache + attention hot spots.
- ``repro.serving``  — batched serving engine, multi-model router, simulator.
- ``repro.launch``   — meshes, sharding rules, the serving driver and the
                       data plane's mesh dry-run.
"""

__version__ = "1.0.0"
