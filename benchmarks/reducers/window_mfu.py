"""The whole step's share of the chip's peak: model FLOPs of the traced
window's finished ticks (encoder on real tokens, top-k on real queries) over
window seconds times chips times peak FLOP/s."""

from benchmarks.harness import work


def reduce(context, parts):
    if context.peaks is None or not context.ticks:
        return None
    dim = int(context.config["hidden_size"])
    depth = int(context.config["num_hidden_layers"])
    flops = 0.0
    for tick in context.ticks:
        if "encoder" in parts:
            flops += sum(work.encoder_flops(t, dim, depth) for t in tick["encoder_tokens"])
        if "topk" in parts:
            flops += sum(work.topk_flops(b, n, d) for b, n, d, _k in tick["topk"])
    peak = context.peaks["flops_per_s"] * context.chips
    return 100.0 * flops / (context.seconds * peak)
