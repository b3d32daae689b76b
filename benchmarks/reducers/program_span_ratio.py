"""One count over another, both carried as attributes by the named program
span, summed over the traced ticks: the useful share of what was done."""

from benchmarks.harness import program_spans


def reduce(context, span, useful, attempted):
    if context.peaks is None:  # a rehearsal's toy texts say nothing of a deployment's padding
        return None
    recorded = program_spans.read(context)
    if recorded is None:
        return None
    ticks = program_spans.per_tick(recorded, [span], len(context.ticks))
    done = sum(s.attributes[useful] for tick in ticks for s in tick)
    tried = sum(s.attributes[attempted] for tick in ticks for s in tick)
    return 100.0 * done / tried, {useful: done, attempted: tried}
