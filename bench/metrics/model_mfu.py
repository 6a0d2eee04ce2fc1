"""The model's share of the chip's bf16 peak, %: the FLOPs the window's
generates required (real miss rows and prompt tokens, routed experts
only) over the summed ``model_generate`` spans times the peak."""


def read(ctx):
    spans = ctx.get("spans", {}).get("model_generate")
    fl = ctx.get("model_flops")
    if not spans or not fl:
        return None
    return 100.0 * fl / (sum(spans) / 1e3 * ctx["peaks"]["bf16_flops_per_s"])
