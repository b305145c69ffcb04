"""Chip benchmark of the TEMPI datatype engine: one cell, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout, on a machine that holds the chips the
cell asks for.  It needs a TPU: with none, or with fewer chips than the
cell asks for, or with a device kind that ``bench/peaks.json`` does not
list, it exits non-zero and prints no result.

A run builds the cell's session (set-up: communicator, plan, data made
on the device from ``--seed``, ahead-of-time compiles, one warm-up
call), then calls it back to back for ``--seconds`` seconds, blocking
each call (a closed loop with one caller), then frees the program's
state and compares what the window produced with the plain reference.
Plan facts go to earlier lines of standard output, the numbers compared
and their limits to the last lines of standard error, and one JSON
object to the last line of standard output::

    {"correct": .., "attempted": .., "failed": .., "metrics": {..},
     "device": {..}, "breakdown": {..}, "checks": {..}}

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window (``breakdown`` then lists the device operations that took most
time and the longest idle gaps, named by the host span they fell in).

Everything is found by name from ``BENCHMARK.json``; adding to the
benchmark means adding files and entries, never editing these:

- a configuration: ``bench/configs/<config>.json`` (its sizes,
  ``source``, ``assumed``, ``reduced``) and an entry under ``configs``;
- a traffic mix: ``bench/traffic/<traffic>.json``, whose ``driver`` key
  names a traffic kind and whose other keys are that kind's parameters;
- a traffic kind: ``bench/drivers/<kind>.py`` defining ``Session(config,
  traffic, seed, devices, store, seconds, params=None)`` (``seconds``:
  the length of the window it will be driven for) with ``facts``,
  ``call()`` (returning the seconds of it that the check's own work
  took, which the window leaves out), ``work_units(calls)``,
  ``metrics(latencies, window_s)``, ``layer_context()`` and
  ``release_and_check()`` (see
  ``drivers/halo_program.py``); it refuses traffic keys it does not
  read;
- a cell: an entry under ``workloads`` naming a configuration and a
  traffic mix, plus ``bench/limits/<cell>.json``, the limit of each
  number its check compares;
- a per-layer metric: ``bench/layer_metrics/<metric>.py`` defining
  ``read(ctx)``, which returns the value or None where the trace holds
  nothing to read, and an entry under ``per_layer``.  ``ctx`` holds the
  reduced trace (``window_s``, ``busy_s``, ``class_s``, ``devices``,
  ``device_ops``, ``idle_gaps``), ``calls`` and ``work_units`` traced,
  the chip's ``peaks``, the cell's ``config`` and ``traffic``, the
  session's plan ``facts`` and whatever its ``layer_context()`` adds;
- an end-to-end metric: a key that a driver's ``metrics`` returns, and
  an entry under ``end_to_end``.

State lives at fixed paths inside the checkout: JAX's persistent
compilation cache in ``.cache/jax`` (so only a checkout's first run
compiles), and the communicator's store and the trace in
``.cache/bench``, emptied at the start of every run.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = ROOT / ".cache"
STATE = CACHE / "bench"
#: how much of a traced run's window the profiler records: enough calls
#: for the layer metrics, and a trace that reads in well under a minute
#: on four chips
TRACE_SECONDS = 5.0

sys.path.insert(0, str(BENCH))


class BenchError(RuntimeError):
    """A run that cannot give a result: no chip, an unknown device, a
    cell or file that is not there."""


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    limits: Dict[str, float]


def load_cell(name: str, bench: Optional[dict] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files."""
    if bench is None:
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no cell {name!r} in BENCHMARK.json; "
                         f"cells: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", ())
             or ("workloads" not in m and m["moves"] in reported)]
    limits = json.loads((BENCH / "limits" / f"{name}.json").read_text())
    return Cell(name, w["chips"], config, traffic, e2e, layer,
                {k: float(v) for k, v in limits["limits"].items()})


def peaks_for(kind: str) -> dict:
    """The published peaks of one chip of ``kind``."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json (it lists {sorted(table)})")
    return table[kind]


def chips(devices, wanted: int) -> list:
    """The first ``wanted`` TPU devices; an error without them."""
    if not devices or devices[0].platform != "tpu":
        plat = devices[0].platform if devices else "no"
        raise BenchError(f"no TPU: JAX sees {plat} devices; nothing was run")
    if len(devices) < wanted:
        raise BenchError(f"the cell needs {wanted} chips, JAX sees "
                         f"{len(devices)}")
    peaks_for(devices[0].device_kind)
    return list(devices[:wanted])


def load_module(path: Path):
    """Import one file by path (drivers and layer metrics are files named
    after cells' entries, not modules of a package)."""
    if not path.is_file():
        raise BenchError(f"{path.relative_to(ROOT)} is not there")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class CompileCounter:
    """Counts XLA compilations and jaxpr traces while ``active``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax.monitoring

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if self.active and event in self.EVENTS:
            self.count += 1

    def close(self) -> None:
        import jax.monitoring

        self.active = False
        jax.monitoring.unregister_event_duration_listener(self._on)


def emit(*parts) -> None:
    print(*parts, flush=True)


def drive(session, seconds: float, trace_dir: Optional[Path] = None
          ) -> Tuple[List[float], float, int]:
    """Call ``session`` back to back until ``seconds`` have passed;
    returns the latency of each call, the length of the window and the
    number of calls traced.  The seconds a call reports as the check's
    own work (copying a sampled answer to the host) count in neither.
    With ``trace_dir`` the profiler records the calls of the first
    ``TRACE_SECONDS`` of the window, inside the host span
    ``bench.window``."""
    import jax

    latencies: List[float] = []
    traced = 0
    span = None
    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
        span = jax.profiler.TraceAnnotation("bench.window")
        span.__enter__()
    t0 = time.perf_counter()
    untimed = 0.0
    while True:
        c0 = time.perf_counter()
        check_s = session.call()
        c1 = time.perf_counter()
        untimed += check_s
        latencies.append(c1 - c0 - check_s)
        window_s = c1 - t0 - untimed
        if span is not None:
            traced += 1
            if c1 - t0 >= TRACE_SECONDS or window_s >= seconds:
                span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                span = None
        if window_s >= seconds:
            return latencies, window_s, traced


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             *, params=None, t_start: float = T_START,
             state: Path = STATE) -> dict:
    """One run of ``cell`` on ``devices``; returns the result object.
    ``state`` is emptied first and holds the communicator's store and
    the trace."""
    import jax

    if state.exists():
        shutil.rmtree(state)
    store = state / "store"
    trace_dir = state / "trace"
    store.mkdir(parents=True)
    driver = load_module(BENCH / "drivers" / f"{cell.traffic['driver']}.py")
    counter = CompileCounter()
    with jax.profiler.TraceAnnotation("bench.setup"):
        session = driver.Session(cell.config, cell.traffic, seed, devices,
                                 store, seconds, params=params)
    # Python's full collections walk every object JAX keeps alive
    # (~0.1 s each); freezing what set-up made keeps them out of the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    emit("plan:", json.dumps(session.facts, sort_keys=True))

    counter.active = True
    try:
        latencies, window_s, traced = drive(
            session, seconds, trace_dir if trace else None)
    finally:
        counter.close()
        gc.unfreeze()
    median = statistics.median(latencies)
    emit(f"window: {len(latencies)} calls in {window_s:.6f} s; "
         f"compilations inside the window: {counter.count}; call latency "
         f"median {median:.6f} s, largest {sorted(latencies)[-3:][::-1]}, "
         f"{sum(t > 1.5 * median for t in latencies)} over 1.5x the median")
    peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
    )
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": peak,
    }
    breakdown = None
    if trace:
        metrics, breakdown = layer_metrics(cell, session, trace_dir,
                                           traced, devices)
        device["busy_s"] = breakdown.pop("busy_s")
        device["window_s"] = breakdown.pop("window_s")
    else:
        values = dict(session.metrics(latencies, window_s), setup_s=setup_s)
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise BenchError(f"the {cell.traffic['driver']} driver "
                                 f"gives no {m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    emit("comm stats after the window:", json.dumps(
        {k: v for k, v in session.comm.stats().items() if isinstance(v, int)},
        sort_keys=True))
    t_check = time.perf_counter()
    numbers = session.release_and_check()
    emit(f"check took {time.perf_counter() - t_check:.3f} s")
    if set(numbers) != set(cell.limits):
        raise BenchError(f"the check compares {sorted(numbers)}, "
                         f"bench/limits/{cell.name}.json limits "
                         f"{sorted(cell.limits)}")
    checks = {name: {"value": value, "limit": cell.limits[name]}
              for name, value in numbers.items()}
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(latencies),
        "failed": 0,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def layer_metrics(cell: Cell, session, trace_dir: Path, calls: int,
                  devices) -> tuple:
    """The cell's per-layer metrics from the trace of the window."""
    import tracereduce

    t0 = time.perf_counter()
    classes = tracereduce.OpClasses.load()
    reduced = tracereduce.reduce(tracereduce.load(trace_dir, classes), classes)
    shutil.rmtree(trace_dir)
    emit(f"trace read and reduced in {time.perf_counter() - t0:.3f} s")
    ctx = dict(reduced, calls=calls, work_units=session.work_units(calls),
               peaks=peaks_for(devices[0].device_kind), config=cell.config,
               traffic=cell.traffic, facts=session.facts,
               **session.layer_context())
    emit("trace:", json.dumps({k: reduced[k] for k in
                               ("window_s", "busy_s", "class_s", "devices")}))
    metrics = {}
    for m in cell.per_layer:
        reader = load_module(BENCH / "layer_metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    breakdown = {k: reduced[k] for k in
                 ("device_ops", "idle_gaps", "busy_s", "window_s")}
    return metrics, breakdown


def report(result: dict) -> None:
    """The numbers compared on the last lines of standard error, then the
    result on the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def setup_environment() -> None:
    """Keep JAX's compilation cache at the checkout's fixed path and make
    the program importable; before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise BenchError(f"the program is not at {src}: run from a checkout")
    sys.path.insert(0, str(src))


def open_chips(cell: Cell) -> list:
    """Prepare the environment, start JAX and return the cell's chips."""
    setup_environment()
    import jax

    from repro.launch.compile_cache import use_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    use_compile_cache()
    return chips(jax.devices(), cell.chips)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        devices = open_chips(cell)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          devices)
    except (BenchError, FileNotFoundError, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
