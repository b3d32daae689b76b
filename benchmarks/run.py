"""The one command: one run of one cell.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets up (build, load, warm every shape the traffic uses), measures a window of
``--seconds`` seconds, checks what the window produced against the plain
reference and prints one JSON line last. ``--rehearse`` walks the same control
flow on the CPU at toy sizes and reports no device metric; without it the
command refuses to run unless JAX finds the TPU chips the cell asks for.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # set-up is counted from here

import argparse
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse(argv):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true")
    return parser.parse_args(argv)


def main(argv=None, t0: float | None = None) -> dict:
    args = parse(argv)
    t0 = _T0 if t0 is None else t0
    from benchmarks.harness import check, runtime
    from benchmarks.harness.traffic import repeated_share

    cell, device = runtime.prepare(args.workload, args.rehearse)
    run = runtime.Run(
        cell=cell,
        seed=args.seed,
        seconds=args.seconds,
        traced=bool(args.trace),
        rehearse=args.rehearse,
        device=device,
        _phase_start=t0,
    )
    driver = cell.driver
    run.phase("reach_device")
    compiles = runtime.CompileCounter()

    state = driver.setup(run)
    compiled_before = compiles.count
    run.open_window()
    setup_s = run.window_start - t0
    records = driver.window(run, state)
    run.close_window()
    compiled_inside = compiles.count - compiled_before

    peak_bytes = runtime.memory_peak_bytes(cell.chips)
    if args.trace:
        context = run.reduce_context()
        metrics = runtime.per_layer_metrics(run, context)
    else:
        context = None
        values = dict(driver.end_to_end(run, records), setup_s=setup_s)
        metrics = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end
        }

    # the reference runs last: the peak is read and the program's state freed
    check_start = time.perf_counter()
    numbers = driver.check(run, state, records)
    print(f"reference and comparison: {time.perf_counter() - check_start:.3f} s", file=sys.stderr)
    numbers["compiles_in_window"] = compiled_inside
    compared = check.with_limits(numbers, cell.limits)
    attempted, failed = driver.counts(records)
    result = {
        "correct": all(c["value"] <= c["limit"] for c in compared.values()),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": runtime.device_section(run, context, peak_bytes),
    }
    if context is not None:
        result["breakdown"] = runtime.breakdown(context)
    tick_ms = [(r.t1 - r.t0) * 1e3 for r in records]
    result["window"] = {
        "seconds": run.window_s,
        "ticks": len(run.ticks),
        "repeated_texts_share": repeated_share([t for r in records for t in r.texts]),
        "tick_ms_quartiles": statistics.quantiles(tick_ms, n=4) if len(tick_ms) > 1 else tick_ms,
        "slowest_tick": slowest_tick(run, records),
    }
    result["setup_phases"] = dict(run.phases)
    runtime.print_result(result, compared)
    return result


def slowest_tick(run, records) -> dict:
    """Which tick took longest and in which call: a stall shows here."""
    slow = max(records, key=lambda r: r.t1 - r.t0)
    spans_ms: dict[str, float] = {}
    for name, tick, t0, t1 in run.spans.records:
        if tick == slow.tick:
            spans_ms[name] = spans_ms.get(name, 0.0) + (t1 - t0) * 1e3
    return {"tick": slow.tick, "ms": (slow.t1 - slow.t0) * 1e3, "spans_ms": spans_ms}


if __name__ == "__main__":
    main()
