"""The hybrid model's share of the chip's bf16 peak, %: the FLOPs the
window's generates required (``request_flops`` of the cell's reference:
real miss rows and prompt tokens, Mamba-2 projections, conv, state update
and read-out, causal attention, the shared expert and the routed experts
held here, the tied head) over the summed ``model_generate`` spans times
the peak. The whole generate step's share in this cell."""

from bench.metrics import model_mfu


def read(ctx):
    return model_mfu.read(ctx)
