"""``study.py`` for a cell whose driver knows more than one control.

    python3 benchmarks/study_controls.py --workload <cell> --seeds 11,12 --controls fp8,no_window [--seconds 5] [--rehearse]

For each seed, in one process: the program's readings as ``study.py`` takes
them, then each named control in the program's place on the same sample
(``driver.check_numbers(..., control=<name>)``). Prints one JSON line a seed
and exits with code 1 if the program came out over a limit on any seed or a
control under all of them on any. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> list[dict]:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--controls", required=True)
    parser.add_argument("--seconds", type=float, default=5.0)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)
    from benchmarks.harness import check, runtime
    from benchmarks.study import over

    cell, device = runtime.prepare(args.workload, args.rehearse)
    driver = cell.driver
    lines = []
    for seed in (int(s) for s in args.seeds.split(",")):
        run = runtime.Run(
            cell=cell, seed=seed, seconds=args.seconds, traced=False, rehearse=args.rehearse, device=device
        )
        state = driver.setup(run)
        run.open_window()
        records = driver.window(run, state)
        run.close_window()
        driver.release(state)
        line = {
            "seed": seed,
            "ticks": len(records),
            "docs_per_s": sum(len(r.texts) for r in records) / run.window_s,
            "program": driver.check_numbers(run, state, records),
        }
        line["program_over"] = over(check.with_limits(line["program"], cell.limits))
        for name in args.controls.split(","):
            line[name] = driver.check_numbers(run, state, records, control=name)
            line[name + "_over"] = over(check.with_limits(line[name], cell.limits))
        print(json.dumps(line), flush=True)
        lines.append(line)
        del state, records, run
        gc.collect()
    return lines


def verdict(lines: list[dict], controls: list[str]) -> int:
    """1 if a sound run failed a limit or a control passed them all."""
    bad = 0
    for line in lines:
        if line["program_over"]:
            print(f"seed {line['seed']}: the program is over {line['program_over']}", file=sys.stderr)
            bad = 1
        for name in controls:
            if not line[name + "_over"]:
                print(f"seed {line['seed']}: the control {name} passed every limit", file=sys.stderr)
                bad = 1
    return bad


if __name__ == "__main__":
    controls = sys.argv[sys.argv.index("--controls") + 1].split(",")
    sys.exit(verdict(main(), controls))
