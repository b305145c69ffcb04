#!/usr/bin/env python3
"""Bring-up check of the datatype engine on a TPU, through its public
entry points, at deployment size.

    python chip_smoke.py               # one chip
    python chip_smoke.py --chips 4     # four chips: the halo program only

One chip runs three phases:

1. ``MPI_Pack``/``MPI_Unpack`` (``Communicator.pack``/``unpack``) of
   committed 2D ``Vector``/``Subarray`` objects of 1 MiB (8-512 B blocks
   at a 512 B pitch) and of one face of the halo below (a 3D
   ``Subarray``), with the ``rows`` and ``dma`` kernels and the
   calibrated model's choice.  Each result must equal the
   ``repro.kernels.ref`` gather bit for bit, and every Pallas strategy
   must show a compiled kernel (``tpu_custom_call``) in its program.  A
   strategy that does not apply to an object says so and is not run.
2. One self ``Communicator.sendrecv`` on a 1-rank mesh.
3. ``repro.launch.smoother.run_smoother``: the predictor-corrector cycle
   at a 512^3 fp32 interior per rank, ``--halo-steps auto``, planned on
   the byte-exact ladder (the native ragged schedule wherever the
   backend runs ``ragged_all_to_all``), checked against a global
   periodic oracle.

``--chips 4`` runs phase 3 only, on a (4, 1, 1) decomposition: the
native ragged program and the same program on the grouped schedule must
agree bit for bit, and both must match the oracle.

On one chip the communicator calibrates (reduced grid) into
``.cache/measure``; four chips price on the analytic v5e table.  The
compile cache follows ``JAX_COMPILATION_CACHE_DIR`` or else lives in
``.cache/jax`` (both inside the checkout, git-ignored).  With no TPU the
script exits non-zero before printing any result.  The last line of
standard output is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

#: interior cube side per rank of the halo program
HALO_SIDE = 512
#: the cycle the halo program fuses
CYCLE = "predictor-corrector"
AXIS = "data"


def pack_objects(halo_face):
    """(label, datatype, buffer shape, buffer dtype) of phase 1."""
    from repro.core import BYTE, FLOAT, Subarray, Vector

    return [
        ("vector 8B x131072", Vector(131072, 8, 512, BYTE), (131072 * 512,),
         np.uint8),
        ("vector 64B x16384", Vector(16384, 64, 512, BYTE), (16384 * 512,),
         np.uint8),
        ("vector 512B x2048", Vector(2048, 512, 512, BYTE), (2048 * 512,),
         np.uint8),
        ("subarray 32B x32768",
         Subarray((32768, 128), (32768, 8), (0, 40), FLOAT, order="C"),
         (32768, 128), np.float32),
        halo_face,
    ]


def timed(fn, *args):
    """Compile ``jax.jit(fn)`` for ``args``, then run it once; returns
    (compiled, result, compile seconds, run seconds)."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return compiled, out, t1 - t0, time.perf_counter() - t1


def phase_pack(comm, seed: int) -> int:
    """Phase 1; returns the number of (object, strategy) cases run."""
    import jax.numpy as jnp

    from repro.comm import FixedPolicy, ModelPolicy
    from repro.halo import HaloSpec
    from repro.halo.exchange import make_halo_types
    from repro.halo.stencil import cycle_halo_radii
    from repro.launch.smoother import smoother_cycle

    n = HALO_SIDE
    spec = HaloSpec(
        grid=(1, 1, 1), interior=(n, n, n),
        radius=cycle_halo_radii(smoother_cycle(CYCLE), 1),
    )
    face = make_halo_types(spec, comm)[(0, 0, 1)][0].datatype
    rng = np.random.default_rng(seed)
    ran = 0
    for label, dt, shape, dtype in pack_objects(
        ("halo x-face 3D", face, spec.alloc, np.float32)
    ):
        ct = comm.commit(dt)
        if dtype == np.uint8:
            host = rng.integers(0, 256, size=shape, dtype=np.uint8)
        else:
            host = rng.standard_normal(shape, dtype=np.float32)
        buf = jnp.asarray(host)
        dst_host = np.zeros(shape, dtype)
        dst = jnp.asarray(dst_host)
        # the oracle: ref.py's block offsets, gathered on the host
        idx = offsets_array_bytes(ct)
        want = host.reshape(-1).view(np.uint8)[idx]
        want_dst = dst_host.reshape(-1).view(np.uint8).copy()
        want_dst[idx] = want
        for name in ("rows", "dma", "auto"):
            if name == "auto":
                comm.policy = ModelPolicy()
                strat = comm.select(ct, 1, wire=False)
            else:
                strat = comm.strategies.get(name)
                if not strat.applicable(ct):
                    print(f"  {label:22s} {name:4s}  not applicable")
                    continue
                comm.policy = FixedPolicy(name)
            pallas = strat.name in ("rows", "dma")
            cp, got, c_p, r_p = timed(lambda b: comm.pack(b, ct), buf)
            cu, out, c_u, r_u = timed(
                lambda d, p: comm.unpack(d, p, ct), dst, got
            )
            kernel = all(
                "tpu_custom_call" in c.as_text() for c in (cp, cu)
            )
            exact = (
                np.array_equal(np.asarray(got), want)
                and np.array_equal(
                    np.asarray(out).reshape(-1).view(np.uint8), want_dst
                )
            )
            print(
                f"  {label:22s} {name:4s}  strategy={strat.name} "
                f"packed={ct.size}B buffer={host.nbytes}B "
                f"bit-exact={exact} compiled-kernel={kernel} "
                f"pack {r_p * 1e3:.3f} ms unpack {r_u * 1e3:.3f} ms "
                f"(compile {c_p + c_u:.1f} s)"
            )
            if not exact:
                raise SystemExit(f"pack/unpack {label} {name}: not bit-exact")
            if pallas and not kernel:
                raise SystemExit(
                    f"pack/unpack {label} {name}: no compiled kernel "
                    "(tpu_custom_call) in the program"
                )
            ran += 1
        del buf, dst
    comm.policy = ModelPolicy()
    return ran


def phase_sendrecv(comm, devices, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core import BYTE, Vector

    ct = comm.commit(Vector(131072, 8, 512, BYTE))
    host = np.random.default_rng(seed + 1).integers(
        0, 256, size=(ct.extent,), dtype=np.uint8
    )
    mesh = Mesh(np.array(devices[:1]), (AXIS,))
    fn = jax.shard_map(
        lambda b: comm.sendrecv(b, jnp.zeros_like(b), ct, [(0, 0)], AXIS),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
    )
    compiled, out, c, r = timed(fn, jnp.asarray(host))
    want = np.zeros_like(host)
    offs = offsets_array_bytes(ct)
    want[offs] = host[offs]
    exact = np.array_equal(np.asarray(out), want)
    print(
        f"sendrecv: 1-rank self exchange of {ct.size}B "
        f"(vector 8B x131072) bit-exact={exact} "
        f"compiled-kernel={'tpu_custom_call' in compiled.as_text()} "
        f"{r * 1e3:.3f} ms (compile {c:.1f} s)"
    )
    if not exact:
        raise SystemExit("sendrecv: not bit-exact")


def offsets_array_bytes(ct):
    """Byte positions ``ct`` covers, in packing order (the offsets the
    ``repro.kernels.ref`` oracle gathers)."""
    from repro.kernels.ref import offsets_array

    offs = offsets_array(ct.block)
    return (offs[:, None] + np.arange(ct.block.counts[0])).reshape(-1)


def oracle_field(program, interiors, iters: int, device):
    """The global periodic field after ``iters`` program iterations,
    computed straightforwardly on one device: every op application
    wraps the whole (ranks*nz, ny, nx) domain and sums the shifted
    windows in the op's offset order."""
    import jax
    import jax.numpy as jnp

    from repro.halo.stencil import op_sequence

    def apply(u, op):
        rz, ry, rx = op.radii
        pad = jnp.pad(u, ((rz, rz), (ry, ry), (rx, rx)), mode="wrap")
        acc = jnp.zeros_like(u)
        for dz, dy, dx in op.offsets:
            acc = acc + jax.lax.dynamic_slice(
                pad, (rz + dz, ry + dy, rx + dx), u.shape
            )
        w = jnp.asarray(op.weight, u.dtype)
        return (1 - w) * u + (w / len(op.offsets)) * acc

    steps = {op: jax.jit(lambda u, op=op: apply(u, op)) for op in program.ops}
    u = jax.device_put(interiors.reshape(-1, *interiors.shape[2:]), device)
    for _ in range(iters):
        for op in op_sequence(program.ops, program.steps):
            u = steps[op](u)
    return np.asarray(u)


def interiors_of(field, program) -> np.ndarray:
    R = program.spec.nranks
    nz, ny, nx = program.spec.interior
    rz, ry, rx = program.spec.radii
    az, ay, ax = program.spec.alloc
    return np.asarray(field).reshape(R, az, ay, ax)[
        :, rz:rz + nz, ry:ry + ny, rx:rx + nx
    ]


def check_oracle(label, got, want, program, iters, x0_max) -> None:
    """fp32 bound fixed from the dtype and the shapes before the run:
    each application adds at most (neighbors + 2) roundings of values no
    larger than the initial maximum."""
    apps = iters * program.applications
    most = max(len(op.offsets) for op in program.ops)
    tol = apps * (most + 2) * float(np.finfo(np.float32).eps) * x0_max
    err = float(np.max(np.abs(got - want)))
    ok = err <= tol
    print(f"{label}: oracle max |err| {err:.3e} (bound {tol:.3e}) "
          f"over {apps} applications -> {'ok' if ok else 'FAILED'}")
    if not ok:
        raise SystemExit(f"{label}: does not match the oracle")


def run_program(program, comm, devices, iters: int, seed: int):
    """``iters`` iterations of ``program``'s compiled step from the
    smoother's initial state; returns (field, compile s, s/iteration)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.halo import make_program_step
    from repro.launch.smoother import initial_state

    mesh = Mesh(np.array(devices), (AXIS,))
    x = jax.device_put(
        initial_state(program, seed), NamedSharding(mesh, P(AXIS))
    )
    t0 = time.perf_counter()
    step = make_program_step(program, comm, mesh, AXIS).lower(x).compile()
    t1 = time.perf_counter()
    for _ in range(iters):
        x = step(x)
    jax.block_until_ready(x)
    return x, t1 - t0, (time.perf_counter() - t1) / iters


def phase_halo(comm, devices, iters: int, seed: int, compare: bool) -> None:
    import jax

    from repro.comm.wireplan import plan_wire
    from repro.launch.smoother import initial_state, run_smoother

    R = len(devices)
    n = HALO_SIDE
    t0 = time.perf_counter()
    report = run_smoother(
        comm, iters=iters, interior=(n, n, n), cycle=CYCLE,
        halo_steps="auto", axis_name=AXIS, seed=seed, devices=devices,
        schedule_policy="exact",
    )
    jax.block_until_ready(report.field)
    wall = time.perf_counter() - t0
    program = report.program
    wire = program.plan.wire
    shards = {s.device for s in report.field.addressable_shards}
    # the same program again, compiled apart from its iterations
    again, c, per_iter = run_program(program, comm, devices, iters, seed)
    print(
        f"halo program: {R} rank(s) x {n}^3 fp32, {report.summary} "
        f"strategies={sorted({s.name for s in program.plan.strategies})} "
        f"shards on {len(shards)} device(s); run_smoother "
        f"{wall:.1f} s incl. planning and compile; rerun "
        f"{per_iter * 1e3:.3f} ms/iteration (compile {c:.1f} s)"
    )
    if wire.schedule != "ragged":
        raise SystemExit(
            f"halo program: planned {wire.schedule!r}, not the native "
            "ragged schedule"
        )
    if len(shards) != R:
        raise SystemExit(f"halo program: {R} shards on {len(shards)} devices")
    if not np.array_equal(np.asarray(again), np.asarray(report.field)):
        raise SystemExit("halo program: the rerun differs from run_smoother")
    got = interiors_of(report.field, program)
    x0 = interiors_of(initial_state(program, seed), program)
    want = oracle_field(program, x0, iters, devices[0])
    check_oracle("halo program (native ragged)", got.reshape(want.shape),
                 want, program, iters, float(np.max(np.abs(x0))))
    if not compare:
        return

    grouped = plan_wire(
        tuple(s.nbytes for s in wire.segments),
        program.plan.perms,
        fingerprints=tuple(s.fingerprint for s in wire.segments),
        native=False,
    )
    if grouped.schedule != "grouped" or grouped.segments != wire.segments:
        raise SystemExit(
            f"halo program: native=False planned {grouped.schedule!r}"
        )
    twin = dataclasses.replace(
        program, plan=dataclasses.replace(program.plan, wire=grouped)
    )
    x, c, per_iter = run_program(twin, comm, devices, iters, seed)
    same = np.array_equal(np.asarray(x), np.asarray(report.field))
    print(
        f"halo program, planned native=False: schedule={grouped.schedule} "
        f"wire={grouped.issued_bytes}B ops/exchange={grouped.wire_ops} "
        f"{per_iter * 1e3:.3f} ms/iteration (compile {c:.1f} s); "
        f"native ragged == grouped bit for bit: {same}"
    )
    if not same:
        raise SystemExit("halo program: ragged and grouped schedules differ")
    check_oracle("halo program (grouped)",
                 interiors_of(x, program).reshape(want.shape), want,
                 program, iters, float(np.max(np.abs(x0))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: the multi-chip halo program only")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iters", type=int, default=2,
                    help="halo program iterations")
    args = ap.parse_args(argv)

    try:
        from repro.launch.compile_cache import CHECKOUT_CACHE, use_compile_cache
    except ImportError:
        sys.exit("chip_smoke: the repro package is not under src/ beside "
                 "this script; run it from the root of a checkout")
    cache = use_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX sees "
                 f"{devices[0].platform} devices); nothing was run")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devices)} TPU device(s)")
    devices = devices[:args.chips]
    kind = devices[0].device_kind
    print(f"device: {kind} x{len(devices)}; compile cache: {cache}")

    from repro.measure.production import production_communicator

    # one chip calibrates (reduced grid); the four-chip form, which only
    # compares two schedules of one program, prices on the analytic v5e
    # table to spare four chips the calibration
    t0 = time.perf_counter()
    comm, _ = production_communicator(
        CHECKOUT_CACHE / "measure", axis_name=AXIS,
        calibrate=args.chips == 1, reduced=True,
    )
    print(f"params: {comm.model.params.name} "
          f"[{time.perf_counter() - t0:.1f} s]")

    t_all = time.perf_counter()
    if args.chips == 1:
        print("pack/unpack (MPI_Pack / MPI_Unpack):")
        ran = phase_pack(comm, args.seed)
        print(f"pack/unpack: {ran} (object, strategy) cases bit-exact")
        phase_sendrecv(comm, devices, args.seed)
    phase_halo(comm, devices, args.iters, args.seed, compare=args.chips > 1)
    print(f"total: {time.perf_counter() - t_all:.1f} s after calibration")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
