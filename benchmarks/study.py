"""The readings a cell's limits are set from: program and control, seed by seed.

    python3 benchmarks/study.py --workload <cell> --seeds 11,12,13 --seconds 5 [--control] [--rehearse]

For each seed, in one process: set up the cell as ``run.py`` does, drive a
short window at the cell's own load, and compare what it produced with the
plain reference: the *lower* readings. With ``--control`` also put the
reference at fp8 in the program's place on the same sample: the *upper*
readings. Both go through the cell's limits as a run's numbers do. Prints one
JSON line a seed, and exits with code 1 if the program came out over a limit
on any seed or the control under all of them on any. The benchmark's own runs
never run this.
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
    parser.add_argument("--seconds", type=float, default=5.0)
    parser.add_argument("--control", action="store_true")
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)
    from benchmarks.harness import check, runtime

    cell, device = runtime.prepare(args.workload, args.rehearse)
    driver = cell.driver
    lines = []
    for seed in (int(s) for s in args.seeds.split(",")):
        run = runtime.Run(
            cell=cell,
            seed=seed,
            seconds=args.seconds,
            traced=False,
            rehearse=args.rehearse,
            device=device,
        )
        state = driver.setup(run)
        run.open_window()
        records = driver.window(run, state)
        run.close_window()
        driver.release(state)
        line = {
            "seed": seed,
            "ticks": len(records),
            "program": driver.check_numbers(run, state, records),
        }
        line["program_over"] = over(check.with_limits(line["program"], cell.limits))
        if args.control:
            line["control"] = driver.check_numbers(run, state, records, control=True)
            line["control_over"] = over(check.with_limits(line["control"], cell.limits))
        print(json.dumps(line), flush=True)
        lines.append(line)
        del state, records, run
        gc.collect()
    return lines


def over(compared: dict) -> list[str]:
    return [name for name, c in compared.items() if c["value"] > c["limit"]]


def verdict(lines: list[dict]) -> int:
    """1 if a sound run failed a limit or a control passed them all."""
    bad = 0
    for line in lines:
        if line["program_over"]:
            print(f"seed {line['seed']}: the program is over {line['program_over']}", file=sys.stderr)
            bad = 1
        if "control" in line and not line["control_over"]:
            print(f"seed {line['seed']}: the control passed every limit", file=sys.stderr)
            bad = 1
    return bad


if __name__ == "__main__":
    sys.exit(verdict(main()))
