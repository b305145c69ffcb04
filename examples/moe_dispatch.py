"""The paper's technique applied to MoE expert dispatch (DESIGN.md §4.3).

Tokens routed to experts form *strided block* patterns of the grouped
token buffer — exactly TEMPI's domain.  This example runs an
expert-parallel all_to_all dispatch on an 8-device mesh where each
expert's token run is described by a derived datatype, packed by the
engine, shipped with one collective, and unpacked — vs the baseline
per-run copies.

Run:  PYTHONPATH=src python examples/moe_dispatch.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.comm import BaselinePolicy, Communicator
from repro.core import FLOAT, Subarray


def main():
    E = 8              # experts == devices
    cap = 64           # expert capacity per rank
    D = 128            # features (fp32)
    ndev = E
    assert len(jax.devices()) >= ndev
    mesh = Mesh(np.array(jax.devices()[:ndev]), ("expert",))

    # each rank holds a TOKEN-MAJOR (cap, E, D) dispatch buffer: slot c
    # of expert e lives at [c, e, :].  Expert e's payload is therefore a
    # *strided* block (cap runs of D floats at stride E*D) — the
    # canonical TEMPI case, vs. the expert-major layout where rows are
    # contiguous and packing is trivial.
    results = {}
    comms = {
        "baseline": Communicator(axis_name="expert", policy=BaselinePolicy()),
        "tempi": Communicator(axis_name="expert"),
    }
    for mode, comm in comms.items():
        # datatype for "the capacity block destined to expert e":
        # subarray of the (E, cap, D) fp32 buffer selecting row e
        cts = []
        for e in range(E):
            dt = Subarray(
                sizes=(D, E, cap),      # innermost-first: D, then E, then cap
                subsizes=(D, 1, cap),
                starts=(0, e, 0),
                oldtype=FLOAT,
            )
            cts.append(comm.commit(dt))
        strategies = {comm.select(c, wire=False).name for c in cts}

        def dispatch(buf):
            # pack every expert's block, all_to_all, receive (E, seg)
            return comm.all_to_all_packed(buf, cts)

        fn = jax.jit(
            shard_map(
                dispatch, mesh=mesh,
                in_specs=P("expert"), out_specs=P("expert"),
                check_vma=False,
            )
        )
        rng = np.random.default_rng(0)
        buf = jnp.asarray(
            rng.normal(size=(ndev * cap, E, D)).astype(np.float32)
        )
        out = fn(buf)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(3):
            out = fn(buf)
        jax.block_until_ready(out)
        dt_s = (time.perf_counter() - t0) / 3
        results[mode] = np.asarray(out)
        print(f"mode={mode:9s} committed={len(cts)} datatypes "
              f"strategies={sorted(strategies)} "
              f"dispatch time={dt_s*1e3:.1f}ms")

    np.testing.assert_array_equal(results["baseline"], results["tempi"])
    print("baseline == tempi dispatch bytes: OK")


if __name__ == "__main__":
    main()
