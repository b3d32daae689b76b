"""The whole step's share of the chip's peak for a trunk configuration: the
trunk's FLOPs on the real tokens of the traced window's finished ticks
(``harness/work_trunk.py``) over window seconds times chips times peak FLOP/s."""

from benchmarks.harness import work_trunk


def reduce(context):
    if context.peaks is None or not context.ticks:
        return None
    flops = sum(
        work_trunk.forward_flops(context.config, tokens)
        for tick in context.ticks
        for tokens in tick["encoder_tokens"]
    )
    peak = context.peaks["flops_per_s"] * context.chips
    return 100.0 * flops / (context.seconds * peak)
