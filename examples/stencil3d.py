"""3D stencil with a deep-halo HaloProgram (paper §6.4, extended).

Reproduces the paper's case study on the devices present — a 2x2x2
grid of 8 emulated CPU devices, or an (n, 1, 1) grid of the n chips of
a TPU host — a 26-point stencil over a periodic domain, each halo
region described by an MPI-style subarray datatype, packed by the
TEMPI engine and exchanged
through the Communicator's fused neighborhood alltoallv — and runs it as
a communication-avoiding ``HaloProgram``: one exchange at halo depth
``s * r`` amortized over ``s`` local stencil applications on a shrinking
valid region.

``--halo-steps N`` fixes the fusion depth (``2`` keeps the paper's
radius-2 / two-applications-per-exchange setup; ``1`` is the
step-per-exchange reference, bit-exact on the interior against any other
depth).  ``--halo-steps auto`` lets ``PerfModel.price_program`` pick the
depth from the measured wire/copy tables; with ``--decisions FILE`` the
choice is recorded there and reruns pin it.

``--cycle predictor-corrector`` fuses a heterogeneous two-op cycle —
a far-reaching predictor (radii (2,1,1)) then a local corrector — into
the same single exchange per iteration: the halo depth becomes
``steps * cycle_radii`` (the per-op radii summed) and each application
shrinks the valid region by its own op's radii.

``--overlap`` switches the iteration to the request-based pipeline:
the fused collective is issued first and the steps-deep interior chain
— which reads no halo cells — runs while the wire is in flight.

Run:  python examples/stencil3d.py [--mode tempi|baseline] [--iters 5]
          [--halo-steps auto|N] [--decisions FILE] [--overlap]
"""

# the dry-run pattern: the CPU device count must be fixed before jax
# init (the flag only shapes the host platform; TPU chips are unaffected)
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.comm import Communicator, MODES, policy_for_mode
from repro.halo import (
    STENCIL26,
    build_halo_program,
    make_program_step,
    parse_halo_steps,
)
from repro.launch.smoother import smoother_cycle
from repro.measure import DecisionCache

#: the demo cycles: the paper's single op, or the same
#: predictor/corrector pair the in-launch smoother workload fuses
CYCLES = {
    "single": (STENCIL26,),
    "predictor-corrector": smoother_cycle("predictor-corrector"),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="tempi", choices=list(MODES))
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--interior", type=int, default=24)
    ap.add_argument("--halo-steps", default="2", metavar="auto|N",
                    help="cycle repeats fused per exchange; 'auto' prices "
                         "the depth with PerfModel.price_program")
    ap.add_argument("--cycle", default="single", choices=list(CYCLES),
                    help="op cycle fused per repeat (predictor-corrector "
                         "= a (2,1,1) predictor then a 26-point corrector "
                         "on one exchange)")
    ap.add_argument("--decisions", default=None, metavar="FILE",
                    help="decision-cache file: records the auto depth "
                         "choice (and every strategy selection); reruns "
                         "pin it")
    ap.add_argument("--overlap", action="store_true",
                    help="hide the exchange behind the interior chain")
    args = ap.parse_args()

    devices = jax.devices()
    grid = (2, 2, 2) if devices[0].platform == "cpu" else (len(devices), 1, 1)
    n = args.interior
    steps = parse_halo_steps(args.halo_steps)

    decisions = DecisionCache.load(args.decisions) if args.decisions else None
    comm = Communicator(axis_name="ranks", policy=policy_for_mode(args.mode),
                        decisions=decisions)
    program = build_halo_program(grid, (n, n, n), comm, steps=steps,
                                 ops=CYCLES[args.cycle])
    spec = program.spec
    R = spec.nranks
    az, ay, ax = spec.alloc
    mesh = Mesh(np.array(devices[:R]), ("ranks",))
    step = make_program_step(program, comm, mesh, "ranks",
                             overlap=args.overlap)

    # seed the INTERIORS only (depth-independent: the same physical field
    # regardless of --halo-steps; shells are filled by the first exchange)
    rng = np.random.default_rng(0)
    nz, ny, nx = spec.interior
    rz, ry, rx = spec.radii
    state_np = np.zeros((R, az, ay, ax), np.float32)
    state_np[:, rz:rz + nz, ry:ry + ny, rx:rx + nx] = rng.normal(
        size=(R, nz, ny, nx)
    ).astype(np.float32)
    state = jnp.asarray(state_np.reshape(R * az, ay, ax))

    jax.block_until_ready(step(state))  # compile (state not advanced)
    t0 = time.perf_counter()
    for _ in range(args.iters):
        state = step(state)
    jax.block_until_ready(state)
    dt = (time.perf_counter() - t0) / args.iters

    stats = comm.stats()
    est = program.estimate
    print(f"mode={args.mode} overlap={args.overlap} ranks={R} "
          f"interior={spec.interior} halo-radius={spec.radii}")
    print(f"program: cycle={args.cycle} ({program.cycle_len} op"
          f"{'s' if program.cycle_len > 1 else ''}) steps={program.steps} "
          f"({'pinned' if program.pinned else args.halo_steps}), "
          f"exchanges/step={program.exchanges_per_step:.3f}, "
          f"exchanges/cycle={program.exchanges_per_cycle:.3f}, "
          f"predicted per-step {est.per_step * 1e6:.2f} us "
          f"(exchange {est.t_exchange * 1e6:.2f} us, "
          f"redundant {est.t_redundant * 1e6:.2f} us)")
    print(f"committed datatypes: {stats['committed_types']} (52 send/recv regions)")
    print(f"wire schedule: {program.plan.wire.schedule} "
          f"({program.plan.wire.wire_ops} collectives per exchange, "
          f"{program.plan.wire_bytes} exact bytes, "
          f"padding {program.plan.wire.padding_bytes})")
    print(f"time per iteration (1 exchange + {program.applications} stencil "
          f"applications): {dt*1e3:.2f} ms")
    # interior checksum: comparable across fusion depths (same physical
    # state whenever iters * steps match — the halo shells and the alloc
    # itself are depth-dependent, the interior is bit-exact)
    interior = np.asarray(state).reshape(R, az, ay, ax)[
        :, rz:rz + nz, ry:ry + ny, rx:rx + nx
    ]
    print(f"stencil applications: {args.iters * program.applications}")
    print(f"interior checksum: {float(interior.sum()):.6e}")
    if decisions is not None:
        path = decisions.save(args.decisions)
        print(f"decisions ({len(decisions)} rows, "
              f"{decisions.pinned_hits} pinned hits) -> {path}")


if __name__ == "__main__":
    main()
