"""Traffic kind ``halo_program``: the compiled deep-halo program step.

Set-up plans the program with ``repro.halo.build_halo_program`` on the
production communicator (analytic v5e table, no calibration, the wire
schedule left to the program's default), makes the field on the device
from the seed, compiles ``repro.halo.make_program_step`` ahead of time
and runs one iteration.  One call is one iteration, blocked.

Checked after the window: one iteration drawn from the seed, its input
run through the plain reference of ``bench/reference/halo.py`` and
compared with the program's output on the interiors.  The sampled
iteration copies its input to the host before it is dispatched and its
output once it is done; the window leaves the copies out (``call``
returns their seconds), since they are the check's work and took
0.3-0.5 s on one v5e chip and 11 s on four.  No device buffer is held
past its iteration, so the check survives a step that donates its
input, and ``memory_peak_bytes`` counts the program's buffers only.
The iteration is drawn between a half and three quarters of the count
the warm-up iteration's time predicts for the window, so the part a
traced run records (its first ``TRACE_SECONDS``), where the copies
would read as idle device time, never holds it.  A window that closes
before the drawn iteration (a traced one does) runs on to it, untimed.

Traffic file keys: ``decomposition`` (the process grid; its product is
the number of chips) and ``about`` (text).
"""

from __future__ import annotations

import math
import time
from pathlib import Path
from typing import Dict, List

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from reference import halo as ref

AXIS = "data"
TRAFFIC_KEYS = {"driver", "about", "decomposition"}


def cycle_ops(config: dict):
    """The configuration's op cycle as ``repro.halo`` ops; it must equal
    the launch smoother's cycle of the same name."""
    from repro.halo.stencil import StencilOp
    from repro.launch.smoother import smoother_cycle

    ops = tuple(
        StencilOp(tuple(o["radii"]), weight=float(o["weight"]))
        for o in config["cycle"]
    )
    if ops != smoother_cycle(config["cycle_name"]):
        raise ValueError(
            f"the configuration's cycle {ops} is not the smoother's "
            f"{config['cycle_name']!r} cycle"
        )
    return ops


class Session:
    """One cell's program, state and samples."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 store: Path, seconds: float, params=None):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from repro.core.datatypes import FLOAT
        from repro.halo import build_halo_program, make_program_step
        from repro.measure.production import production_communicator

        unknown = set(traffic) - TRAFFIC_KEYS
        if unknown:
            raise ValueError(
                f"halo_program reads no traffic keys {sorted(unknown)}")
        if config["element"] != "FLOAT":
            raise ValueError(f"element {config['element']!r} is not FLOAT")
        self.config = config
        self.grid = tuple(traffic["decomposition"])
        self.devices = list(devices)
        if math.prod(self.grid) != len(self.devices):
            raise ValueError(
                f"decomposition {self.grid} needs {math.prod(self.grid)} "
                f"devices, got {len(self.devices)}"
            )
        interior = tuple(config["interior"])
        t0 = time.perf_counter()
        self.comm, _ = production_communicator(
            store, axis_name=AXIS, calibrate=False, params=params,
        )
        t1 = time.perf_counter()
        self.program = build_halo_program(
            self.grid, interior, self.comm, ops=cycle_ops(config),
            steps=config["halo_steps"], element=FLOAT,
        )
        t2 = time.perf_counter()
        mesh = Mesh(np.array(self.devices), (AXIS,))
        sharding = NamedSharding(mesh, P(AXIS))
        nranks = len(self.devices)
        radii = self.program.spec.radii
        alloc = self.program.spec.alloc

        def make_field(key):
            z = jax.random.normal(key, (nranks, *interior), jnp.float32)
            z = jnp.pad(z, ((0, 0),) + tuple((r, r) for r in radii))
            return z.reshape(nranks * alloc[0], alloc[1], alloc[2])

        x = jax.jit(make_field, out_shardings=sharding)(jax.random.key(seed))
        self.step = make_program_step(
            self.program, self.comm, mesh, AXIS
        ).lower(x).compile()
        t3 = time.perf_counter()
        self.x = jax.block_until_ready(self.step(x))  # warm-up iteration
        t4 = time.perf_counter()
        expected = int(seconds / (t4 - t3))
        rng = np.random.default_rng(seed % 2**63)
        self.sample_at = int(rng.integers(
            expected // 2, max(expected // 2 + 1, 3 * expected // 4)))
        self.calls = 0
        self.sample = None
        self.copy_s = 0.0
        wire = self.program.plan.wire
        self.facts = {
            "steps": self.program.steps,
            "applications_per_iteration": self.program.applications,
            "halo_radii": list(radii),
            "wire_schedule": wire.schedule,
            "wire_issued_bytes_per_exchange": wire.issued_bytes,
            "wire_ops_per_exchange": wire.wire_ops,
            "strategies": sorted({s.name for s in self.program.plan.strategies}),
            "params": self.comm.model.params.name,
            "comm_stats_at_setup": {
                k: v for k, v in self.comm.stats().items()
                if isinstance(v, int)
            },
            "host_s": {
                "production_communicator": t1 - t0,
                "build_halo_program": t2 - t1,
                "field_and_compile": t3 - t2,
                "warmup_iteration": t4 - t3,
            },
            "sampled_iteration": self.sample_at,
        }

    def call(self) -> float:
        """One blocked iteration; returns the seconds spent copying the
        sampled iteration to the host."""
        copy_s = 0.0
        sampled = self.calls == self.sample_at
        if sampled:
            with TraceAnnotation("bench.sample_copy"):
                c0 = time.perf_counter()
                x_in = np.asarray(self.x)
                copy_s += time.perf_counter() - c0
        with TraceAnnotation("bench.dispatch"):
            self.x = self.step(self.x)
        with TraceAnnotation("bench.block"):
            self.x.block_until_ready()
        if sampled:
            with TraceAnnotation("bench.sample_copy"):
                c0 = time.perf_counter()
                self.sample = (x_in, np.asarray(self.x))
                copy_s += time.perf_counter() - c0
        self.copy_s += copy_s
        self.calls += 1
        return copy_s

    def work_units(self, calls: int) -> int:
        """Predictor-corrector cycles of the whole domain in ``calls``."""
        return calls * self.program.steps

    def metrics(self, latencies: List[float], window_s: float) -> Dict[str, float]:
        return {
            "halo_cycle_ms": window_s / self.work_units(len(latencies)) * 1e3,
        }

    def layer_context(self) -> dict:
        return {}

    def release_and_check(self, control: bool = False) -> Dict[str, float]:
        """Free the program's state and compare the sampled iteration
        with the reference; returns the numbers compared.  ``control``
        adds ``control.max_rel_err``: the same comparison with the
        reference computed in bfloat16 put in the program's place."""
        window_calls = self.calls
        while self.sample is None:
            self.call()
        print(f"sampled iteration {self.sample_at} of the window's "
              f"{window_calls}; its two host copies took {self.copy_s:.6f} s",
              flush=True)
        x_in, x_out = self.sample
        self.x = self.sample = self.step = None
        nranks = len(self.devices)
        interior = self.config["interior"]
        cycle, steps = self.config["cycle"], self.program.steps
        dev = self.devices[0]

        def global_field(x):
            return jax.device_put(
                ref.assemble(ref.interiors(x, nranks, interior), self.grid),
                dev,
            )

        out = {}
        u = global_field(x_in)
        want = ref.cycles(u, cycle, steps)
        if control:
            low = ref.cycles(u, cycle, steps, dtype=jnp.bfloat16)
            out["control.max_rel_err"] = ref.max_rel_err(low, want)
            del low
        del u
        out["max_rel_err"] = ref.max_rel_err(global_field(x_out), want)
        return out
