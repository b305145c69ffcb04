"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 ...

For each seed, in one process: the cell's set-up, a window of
``--seconds`` at the cell's own load, then the cell's check with the
control beside it.  The control is the plain reference put in the
program's place and broken as a later change might be tempted to break
it: for the halo cells computed in bfloat16 instead of float32, for the
pack cells an unpack that writes whole pitch rows.  Prints one line per
seed and, last, a JSON object with the largest reading of the program
(the lower end of each limit) and the smallest of the control (its
upper end).  The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    try:
        cell = run.load_cell(args.workload)
        devices = run.open_chips(cell)
    except run.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    program, control = {}, {}
    for seed in args.seeds:
        readings, calls = readings_of(cell, seed, args.seconds, devices)
        print(f"seed {seed} ({calls} calls): {json.dumps(readings)}",
              flush=True)
        for name, value in readings.items():
            if name.startswith("control."):
                key = name[len("control."):]
                control[key] = min(control.get(key, value), value)
            else:
                program[name] = max(program.get(name, value), value)
    print(json.dumps({"workload": cell.name, "seeds": args.seeds,
                      "program_max": program, "control_min": control}))
    return 0


def readings_of(cell, seed: int, seconds: float, devices):
    """One seed's numbers compared, the program's and the control's, and
    the number of calls its window made."""
    driver = run.load_module(run.BENCH / "drivers" /
                             f"{cell.traffic['driver']}.py")
    store = run.STATE / "store"
    store.mkdir(parents=True, exist_ok=True)
    session = driver.Session(cell.config, cell.traffic, seed, devices,
                             store, seconds)
    latencies, _, _ = run.drive(session, seconds)
    return session.release_and_check(control=True), len(latencies)


if __name__ == "__main__":
    sys.exit(main())
