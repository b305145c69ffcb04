"""Machine-readable perf snapshot: ``BENCH_10.json``.

The CSV suites report human-scannable tables; this suite records the
numbers a perf *trajectory* needs — one JSON file per run, stable keys,
diffable run over run.  Times are CPU-container proxies (see
``benchmarks/common.py``): the values that transfer to TPU are the
byte counts, the relative orderings, and the probe overhead ratios.

Schema (``"format": 3``)::

    {
      "format": 3,                      # bump on incompatible change
      "suite": "snapshot",
      "halo": {                         # the smoother's fused program
        "fingerprint": str,             # program decision key
        "strategy": "program/s=N",      # pinned decision row strategy
        "schedule": str,                # wire schedule the plan chose
        "wire_bytes": int,              # issued bytes per exchange
        "steps": int,                   # fused halo depth s
        "cycle_len": int,
        "pinned": bool                  # True: depth came from the
      },                                #   decisions file, not the model
      "program_iteration": {            # compiled-iteration wall time
        "mean_s": float,                # telemetry window mean
        "p95_s": float,
        "samples": int,
        "predicted_s": float            # model's per-iteration price
      },
      "overlap": {                      # region-split overlap (PR 8)
        "chosen_mode": str,             # what mode="auto" resolved to
        "predicted_s": {                # price_overlap, both modes
          "monolithic": float,
          "region": float
        },
        "iteration_mean_s": {           # wall time per compiled
          "off": float,                 #   iteration, per overlap mode
          "monolithic": float,          #   (all bit-identical; the
          "region": float               #   checksum gate asserts it)
        },
        "drift": {                      # measured-vs-pinned audit (PR 9):
          "observed_ratio": float,      #   chosen / best alternative mode
          "margin": float,              #   DEFAULT_OVERLAP_MARGIN
          "drifted": bool,              #   ratio > margin
          "demoted": [str]              #   pins demote_stale_modes pruned
        }
      },
      "scale": {                        # simulated-scale ladder (PR 9):
        "ranks_per_node": int,          #   ci_params + synthetic two-tier
        "flip_ranks": int,              # first rung planning tiered
        "ladder": [{                    # one row per simulated rank count
          "ranks": int, "nodes": int,
          "schedule": str,              # model-cheapest wire schedule
          "costs": {str: float},        # schedule -> predicted seconds
          "wire_bytes": int,
          "correction_bytes": int,      # tiered's extra fast-tier bytes
          "inter_messages": {str: int}  # slow-tier messages per rank
        }]
      },
      "compress": {                     # length-aware wire (PR 10):
        "strategy": str,                #   what the probe selected
        "schedule": str,                #   "varlen" when it truncates
        "capacity_bytes": int,          # stored-mode wire bound
        "stream_bytes": int,            # probed effective bytes moved
        "ratio": float,                 # stream / capacity
        "achieved_ratio_mean": float,   # per-exchange telemetry ring
        "samples": int,
        "exchanges": int,               # Communicator compress counters
        "codec": {str: [{               # measure_compress_table rows
          "log2_total": float,
          "compress_s": float,
          "decompress_s": float,
          "ratio_sample": float
        }]}
      },
      "probes": {                       # observability self-cost
        "telemetry_overhead": float,    # probe cost / iteration cost
        "trace_overhead": float,
        "budget": float                 # the <2% gate both live under
      }
    }

Run via ``python -m benchmarks.run snapshot`` (writes ``BENCH_10.json``
in the CWD) or ``python -m benchmarks.bench_snapshot --out PATH``.
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path

from benchmarks.bench_measure import (
    TELEMETRY_OVERHEAD_BUDGET,
    telemetry_overhead,
    trace_overhead,
)
from benchmarks.common import emit

SNAPSHOT_FORMAT = 3
SNAPSHOT_FILENAME = "BENCH_10.json"

#: the simulated-scale sweep: fixed ranks-per-node, rank counts up to
#: the paper's 3072-process regime (same sweep --assert-scale gates on)
SCALE_RANKS = (8, 16, 64, 256, 1024, 3072)
SCALE_RANKS_PER_NODE = 8


def snapshot(iters: int = 10) -> dict:
    """Collect the snapshot dict (schema in the module docstring)."""
    from repro.comm.api import Communicator
    from repro.fleet import ExchangeTelemetry
    from repro.launch.smoother import run_smoother
    from repro.measure import DecisionCache

    # two runs over one DecisionCache: the first records the program
    # decision, the second pins it — the snapshot reports the *pinned*
    # path, the steady state a production job lives in
    decisions = DecisionCache()
    tel = ExchangeTelemetry()
    comm = Communicator(
        axis_name="data", decisions=decisions, telemetry=tel
    )
    run_smoother(comm, iters=1, interior=(8, 8, 8), cycle="smooth",
                 halo_steps="auto")
    tel2 = ExchangeTelemetry()
    comm2 = Communicator(
        axis_name="data", decisions=decisions, telemetry=tel2
    )
    report = run_smoother(comm2, iters=iters, interior=(8, 8, 8),
                          cycle="smooth", halo_steps="auto")
    program = report.program
    agg = tel2.get(program.fingerprint)

    # region-split overlap rows: the model's pricing of both modes on
    # this program's exchange, what "auto" resolves to, and per-mode
    # compiled-iteration wall time on the SAME pinned program — the
    # modes are bit-identical, so any spread is pure scheduling
    from repro.halo import overlap_region_descriptors

    core_bytes, rims = overlap_region_descriptors(
        program.spec, program.ops, program.plan.wire
    )
    chosen, ests, _ = comm2.model.choose_overlap_mode(
        program.plan.wire, rims, core_bytes, program.ops[0].nneighbors
    )
    overlap_iter = {}
    checksums = set()
    for m in ("off", "monolithic", "region"):
        telm = ExchangeTelemetry()
        commm = Communicator(
            axis_name="data", decisions=decisions, telemetry=telm
        )
        rep = run_smoother(commm, iters=iters, interior=(8, 8, 8),
                           cycle="smooth", halo_steps="auto", overlap=m)
        aggm = telm.get(rep.program.fingerprint)
        overlap_iter[m] = aggm.mean if aggm else 0.0
        checksums.add(rep.checksum)
    assert len(checksums) == 1, (
        f"overlap modes disagree on the checksum: {checksums}"
    )

    # measured-vs-pinned overlap audit: the per-mode wall times just
    # collected are the ground truth the pinned overlap/mode= decision
    # claims to have won — feed them to the drift detector; an
    # out-of-band pin is demoted so the next run re-prices
    from repro.fleet.drift import (
        DEFAULT_OVERLAP_MARGIN,
        DriftDetector,
        demote_stale_modes,
    )

    overlap_rows = [
        d for d in decisions.log if d.strategy.startswith("overlap/mode=")
    ]
    audit = DriftDetector().audit(
        decisions, comm2.model.params, system="snapshot",
        overlap_timings={d.fingerprint: overlap_iter for d in overlap_rows},
    )
    overlap_findings = [
        f for f in audit.findings if f.strategy.startswith("overlap/mode=")
    ]
    demoted = demote_stale_modes(decisions, audit)
    overlap_drift = {
        "observed_ratio": (
            overlap_findings[0].observed_ratio if overlap_findings else 0.0
        ),
        "margin": DEFAULT_OVERLAP_MARGIN,
        "drifted": any(f.drifted for f in overlap_findings),
        "demoted": demoted,
    }

    # the simulated-scale ladder on the checked-in CI tables under a
    # synthetic two-tier topology — the trajectory record of where the
    # schedule flips to tier-coalesced (--assert-scale gates the shape)
    from repro.comm import PerfModel, scale_ladder, synthetic_two_tier
    from repro.measure import load_ci_params

    smodel = PerfModel(synthetic_two_tier(load_ci_params()))
    ladder = scale_ladder(
        smodel, SCALE_RANKS, SCALE_RANKS_PER_NODE, pin=False
    )
    flip = next(
        (e.ranks for e in ladder if e.schedule == "tiered"), 0
    )
    scale = {
        "ranks_per_node": SCALE_RANKS_PER_NODE,
        "flip_ranks": int(flip),
        "ladder": [
            {
                "ranks": e.ranks,
                "nodes": e.nodes,
                "schedule": e.schedule,
                "costs": {s: c for s, c in sorted(e.costs.items())},
                "wire_bytes": int(e.wire_bytes),
                "correction_bytes": int(e.correction_bytes),
                "inter_messages": dict(e.inter_messages),
            }
            for e in ladder
        ],
    }
    # the length-aware compressed wire on the canonical zero-heavy
    # probe: plan with the payload sample, run the varlen exchange a few
    # times eagerly so the compress counters and the achieved-ratio
    # telemetry ring carry real samples, then sweep the codec timings
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map
    from repro.core import FLOAT, Subarray
    from repro.measure.bench import measure_compress_table

    ctel = ExchangeTelemetry()
    ccomm = Communicator(axis_name="data", telemetry=ctel)
    cct = ccomm.commit(Subarray((32, 32), (16, 16), (4, 4), FLOAT))
    csrc = np.zeros((32, 32), np.float32)
    csrc[10:12, 6:8] = 3.0
    cperms = [[(0, 0)]]
    cstrats, cplan = ccomm.plan_neighbor(
        [cct], cperms, probe=jnp.asarray(csrc)
    )
    cfn = jax.jit(shard_map(
        lambda b: ccomm.neighbor_alltoallv(
            b, [cct], [cct], cperms, plan=cplan, strategies=cstrats
        ),
        mesh=Mesh(np.array(jax.devices()[:1]), ("data",)),
        in_specs=P(), out_specs=P(), check_vma=False,
    ))
    cx = jnp.asarray(csrc)
    for _ in range(iters):
        jax.block_until_ready(cfn(cx))
    cring = ctel.get(f"{cplan.fingerprint}/ratio")
    cstats = ccomm.stats()
    ctable = measure_compress_table(
        total_bytes=(1 << 12, 1 << 16), iters=3
    )
    compress = {
        "strategy": cstrats[0].name,
        "schedule": cplan.schedule,
        "capacity_bytes": int(cplan.wire_bytes),
        "stream_bytes": int(cplan.effective_wire_bytes),
        "ratio": float(cplan.stream_ratio),
        "achieved_ratio_mean": cring.mean if cring else 0.0,
        "samples": cring.count if cring else 0,
        "exchanges": int(cstats["compress_exchanges"]),
        "codec": {
            name: [
                {
                    "log2_total": r[0],
                    "compress_s": r[1],
                    "decompress_s": r[2],
                    "ratio_sample": r[3],
                }
                for r in rows
            ]
            for name, rows in sorted(ctable.items())
        },
    }
    return {
        "format": SNAPSHOT_FORMAT,
        "suite": "snapshot",
        "halo": {
            "fingerprint": program.fingerprint,
            "strategy": f"program/s={program.steps}",
            "schedule": program.plan.wire.schedule,
            "wire_bytes": int(program.plan.wire.issued_bytes),
            "steps": int(program.steps),
            "cycle_len": int(program.cycle_len),
            "pinned": bool(program.pinned),
        },
        "program_iteration": {
            "mean_s": agg.mean if agg else 0.0,
            "p95_s": agg.p95 if agg else 0.0,
            "samples": agg.count if agg else 0,
            "predicted_s": agg.predicted if agg else 0.0,
        },
        "overlap": {
            "chosen_mode": chosen,
            "predicted_s": {
                m: e.t_total for m, e in sorted(ests.items())
            },
            "iteration_mean_s": overlap_iter,
            "drift": overlap_drift,
        },
        "scale": scale,
        "compress": compress,
        "probes": {
            "telemetry_overhead": telemetry_overhead(iters=iters),
            "trace_overhead": trace_overhead(iters=iters),
            "budget": TELEMETRY_OVERHEAD_BUDGET,
        },
    }


def run(out: str = SNAPSHOT_FILENAME) -> Path:
    """The ``benchmarks.run snapshot`` entry: write the JSON, echo the
    headline numbers as CSV rows like every other suite."""
    snap = snapshot()
    path = Path(out)
    path.write_text(json.dumps(snap, indent=2, sort_keys=True) + "\n")
    emit("snapshot/halo-wire-bytes", float(snap["halo"]["wire_bytes"]),
         f"{snap['halo']['strategy']};{snap['halo']['schedule']}"
         f";pinned={snap['halo']['pinned']}")
    emit("snapshot/program-iter", snap["program_iteration"]["mean_s"] * 1e6,
         f"samples={snap['program_iteration']['samples']}")
    for m, v in snap["overlap"]["iteration_mean_s"].items():
        emit(f"snapshot/overlap-iter-{m}", v * 1e6,
             f"chosen={snap['overlap']['chosen_mode']}")
    od = snap["overlap"]["drift"]
    emit("snapshot/overlap-drift-ratio", od["observed_ratio"],
         f"margin={od['margin']};drifted={od['drifted']}"
         f";demoted={len(od['demoted'])}")
    emit("snapshot/scale-flip-ranks", float(snap["scale"]["flip_ranks"]),
         f"ranks_per_node={snap['scale']['ranks_per_node']}")
    for row in snap["scale"]["ladder"]:
        emit(f"snapshot/scale-{row['ranks']}",
             row["costs"][row["schedule"]] * 1e6,
             f"schedule={row['schedule']};nodes={row['nodes']}"
             f";inter={row['inter_messages'].get('tiered', 0)}")
    cm = snap["compress"]
    emit("snapshot/compress-stream-bytes", float(cm["stream_bytes"]),
         f"capacity={cm['capacity_bytes']};schedule={cm['schedule']}"
         f";strategy={cm['strategy']}")
    emit("snapshot/compress-ratio", cm["ratio"],
         f"achieved={cm['achieved_ratio_mean']:.4f}"
         f";samples={cm['samples']}")
    for name, rows in cm["codec"].items():
        emit(f"snapshot/compress-codec-{name}",
             rows[-1]["compress_s"] * 1e6,
             f"log2n={rows[-1]['log2_total']:.0f}"
             f";decode_us={rows[-1]['decompress_s'] * 1e6:.2f}"
             f";ratio={rows[-1]['ratio_sample']:.4f}")
    emit("snapshot/telemetry-overhead-pct",
         snap["probes"]["telemetry_overhead"] * 100.0,
         f"budget={snap['probes']['budget'] * 100:.0f}%")
    emit("snapshot/trace-overhead-pct",
         snap["probes"]["trace_overhead"] * 100.0,
         f"budget={snap['probes']['budget'] * 100:.0f}%")
    emit("snapshot/json", 0.0, str(path))
    return path


def main() -> None:
    ap = argparse.ArgumentParser(prog="benchmarks.bench_snapshot",
                                 description=__doc__)
    ap.add_argument("--out", default=SNAPSHOT_FILENAME, metavar="PATH",
                    help=f"where to write the JSON "
                         f"(default: ./{SNAPSHOT_FILENAME})")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(args.out)


if __name__ == "__main__":
    main()
