"""The decode step's share of the chip's bf16 peak: 2 x the matrix
parameters per token, times the tokens that went through the step (the
slots stepped, prompt and output tokens alike) per second of the window,
over the peak."""
from chipbench import counts


def read(bench, outcome):
    lay = outcome.layer
    if not lay.get("slot_ticks"):
        return None
    flops = counts.decode_flops_per_token(lay["config"]) * lay["slot_ticks"]
    return 100.0 * flops / bench.window_s / (
        len(bench.devices) * bench.peaks.bf16_flops)
