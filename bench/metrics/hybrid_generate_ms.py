"""Mean ``model_generate`` span of the hybrid model (prefill and greedy
decode of one miss batch, Mamba-2 state carried), ms per batch."""

from bench.metrics import generate_ms


def read(ctx):
    return generate_ms.read(ctx)
