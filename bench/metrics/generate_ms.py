"""Mean ``model_generate`` span (prefill and greedy decode of one miss
batch), ms per batch."""


def read(ctx):
    v = ctx.get("spans", {}).get("model_generate")
    return sum(v) / len(v) if v else None
