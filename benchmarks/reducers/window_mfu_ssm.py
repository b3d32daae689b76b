"""The whole step's share of the chip's peak for a hybrid state-space trunk
configuration: the trunk's FLOPs at this chip's share on the real tokens of
the traced window's finished ticks, batch and probe: projections, convolution,
the scan in its dual form, the full layer's allowed pairs, router, shared and
held routed experts (``harness/work_ssm.py``), over window seconds times
chips times peak FLOP/s."""

from benchmarks.harness import work_ssm


def reduce(context):
    if context.peaks is None or not context.ticks:
        return None
    flops = sum(
        work_ssm.forward_flops(context.config, tokens)
        for tick in context.ticks
        for tokens in tick["encoder_tokens"]
    )
    peak = context.peaks["flops_per_s"] * context.chips
    return 100.0 * flops / (context.seconds * peak)
