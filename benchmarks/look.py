"""A traced run of one cell with the trace described for reading by hand.

    python3 benchmarks/look.py --workload <cell> --seed <n> --seconds <s> [--rehearse]

Runs ``run.py --trace 1`` and writes what the profiler's trace holds (planes,
lines, the first events of each, and how the device's operations sit inside
the harness's spans) to ``chiprun_out/trace_<cell>.txt``. For the session that
has to check or repair ``harness/trace.py`` against a new JAX or chip.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def describe(directory: str, out, read) -> None:
    import glob

    import jax

    from benchmarks.harness import trace as tracing

    path = sorted(glob.glob(os.path.join(directory, "plugins/profile/*/*.xplane.pb")))[-1]
    print(f"{path}: {os.path.getsize(path)} bytes", file=out)
    if os.path.getsize(path) <= 48 * 2**20:  # small enough to bring back and read here
        import shutil

        shutil.copy(path, os.path.join(os.path.dirname(out.name), os.path.basename(out.name)[:-4] + ".xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name}", file=out)
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            first = min(e.start_ns for e in events)
            last = max(e.start_ns + e.duration_ns for e in events)
            print(
                f"  LINE {line.name!r}: {len(events)} events, {first:.0f} .. {last:.0f} ns",
                file=out,
            )
            for e in events[:8]:
                print(f"      {e.name[:90]!r} start {e.start_ns:.0f} dur {e.duration_ns:.0f}", file=out)
    trace = read(directory)
    print(f"window {trace.window}", file=out)
    for device, ops in trace.device_ops.items():
        merged = tracing.union([(a, b) for a, b, _ in ops])
        print(f"{device}: {len(ops)} ops, busy {tracing.total(merged):.6f} s", file=out)
        for a, b, name in [s for s in trace.spans if s[2] != "bench.window"][:12]:
            inside = [o for o in ops if o[0] >= a and o[1] <= b]
            straddle = [o for o in ops if o[0] < b and o[1] > a and o not in inside]
            print(
                f"  {name} {a:.6f}..{b:.6f} ({(b - a) * 1e3:.3f} ms): {len(inside)} ops inside "
                f"({sum(o[1] - o[0] for o in inside) * 1e3:.3f} ms), {len(straddle)} straddle; "
                f"first inside: {[o[2][:40] for o in inside[:3]]}",
                file=out,
            )


def main() -> None:
    from benchmarks import run
    from benchmarks.harness import runtime
    from benchmarks.harness import trace as tracing

    workload = sys.argv[sys.argv.index("--workload") + 1]
    os.makedirs("chiprun_out", exist_ok=True)
    read = tracing.read

    def read_and_describe(directory):
        with open(os.path.join("chiprun_out", f"trace_{workload}.txt"), "w") as out:
            describe(directory, out, read)
        return read(directory)

    runtime.tracing.read = read_and_describe
    run.main(sys.argv[1:] + ["--trace", "1"])


if __name__ == "__main__":
    main()
