"""A traced run of one cell, with the device time split by the program's
named scopes.

    python3 bench/scope_breakdown.py --workload <cell> --seed <n> --seconds <s> [--hlo DIR]
    python3 bench/scope_breakdown.py --compare-hlo DIR_A DIR_B

The first form is ``bench/run.py --trace 1`` with one addition: the
reduced trace also carries ``scope_s`` and ``unscoped_s``
(:func:`scopes.scope_times`), so the cell's per-layer metrics also hold
the scope metrics that ``scope_metrics.json`` lists for it.  It prints
the split (``scopes:``, with the number of the trace's operations
that no compiled program holds) before the result.  ``--hlo DIR`` also
writes the optimized HLO text of each program the session compiled
(``<name>.hlo.txt``).

The second form compares two such directories with metadata, source
locations and names stripped (:func:`scopes.strip_hlo`): two programs
that differ only in their named scopes and kernel names compare equal.
It exits 1 where they differ.
"""

from __future__ import annotations

import argparse
import dataclasses
import difflib
import json
import sys
from pathlib import Path

import run
import scopes
import tracereduce

def scope_metrics(cell_name: str) -> list:
    raw = json.loads((run.BENCH / "scope_metrics.json").read_text())
    return [m for m in raw["per_layer"] if cell_name in m["workloads"]]


def compiled_programs(session) -> dict:
    """The session's compiled programs by attribute name."""
    return {name: getattr(session, name)
            for name in ("step", "pack", "unpack")
            if hasattr(getattr(session, name, None), "as_text")}


def with_scopes(layer_metrics, hlo_dir):
    """``run.layer_metrics`` whose reduced trace also carries the scope
    times of the same trace file."""

    def wrapped(cell, session, trace_dir, calls, devices):
        texts = {k: p.as_text() for k, p in compiled_programs(session).items()}
        if hlo_dir is not None:
            hlo_dir.mkdir(parents=True, exist_ok=True)
            for name, text in texts.items():
                (hlo_dir / f"{name}.hlo.txt").write_text(text)
        classes = tracereduce.OpClasses.load()
        scoped, unmatched = scopes.load(trace_dir, classes, texts.values())
        times = scopes.scope_times(scoped)
        run.emit("scopes:", json.dumps(dict(times, calls=calls,
                                            unmatched_ops=unmatched)))
        reduce = tracereduce.reduce

        def reduce_with_scopes(*args, **kwargs):
            out = reduce(*args, **kwargs)
            out["scope_s"] = times["scope_s"]
            out["unscoped_s"] = times["unscoped_s"]
            return out

        tracereduce.reduce = reduce_with_scopes
        try:
            return layer_metrics(cell, session, trace_dir, calls, devices)
        finally:
            tracereduce.reduce = reduce

    return wrapped


def compare_hlo(a: Path, b: Path) -> int:
    names = sorted({p.name for p in a.glob("*.hlo.txt")}
                   | {p.name for p in b.glob("*.hlo.txt")})
    differ = 0
    for name in names:
        if not (a / name).is_file() or not (b / name).is_file():
            print(f"{name}: only in one of the two")
            differ += 1
            continue
        x, y = (scopes.strip_hlo((d / name).read_text()) for d in (a, b))
        if x == y:
            print(f"{name}: identical after stripping ({len(x)} chars)")
            continue
        differ += 1
        diff = list(difflib.unified_diff(x.splitlines(), y.splitlines(),
                                         str(a / name), str(b / name),
                                         lineterm="", n=1))
        print(f"{name}: {len(diff)} diff lines; first:")
        print("\n".join(line[:300] for line in diff[:40]))
    return 1 if differ else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--hlo", type=Path, default=None)
    ap.add_argument("--compare-hlo", nargs=2, type=Path, default=None)
    args = ap.parse_args(argv)
    if args.compare_hlo:
        return compare_hlo(*args.compare_hlo)
    if args.workload is None or args.seed is None or args.seconds is None:
        ap.error("--workload, --seed and --seconds are required")
    try:
        cell = run.load_cell(args.workload)
        cell = dataclasses.replace(
            cell, per_layer=cell.per_layer + scope_metrics(cell.name))
        devices = run.open_chips(cell)
        run.layer_metrics = with_scopes(run.layer_metrics, args.hlo)
        result = run.run_cell(cell, args.seed, args.seconds, True, devices)
    except (run.BenchError, FileNotFoundError, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    run.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
