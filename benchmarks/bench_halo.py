"""Fig. 12: 3D stencil halo exchange, baseline vs TEMPI.

Runs the 26-neighbor exchange on an 8-rank emulated mesh in a
subprocess (device count must be set before jax init), reporting
per-iteration time for both interposer modes and the pack-only
latency (the paper's phase split), plus the exchange's wire-byte
accounting (exact ragged payload vs what the padded layout would move).

``--assert-ragged`` runs the wire-bytes regression gate instead (CI),
in two modes:

* **exact**: trace the fused halo step planned under
  ``schedule_policy="exact"`` and FAIL (exit 1) if the bytes its
  collectives move exceed the ragged optimum — the sum of per-peer
  packed extents;
* **padded allowance**: trace the step planned under the *default*
  (model-priced) policy and FAIL if the issued bytes exceed
  ``(1 + allowance) x`` the ragged optimum or the uniform row-equalized
  bound — the padding the model may legitimately buy is capped, so
  flipping the default to ``"model"`` stays byte-gated
  (``--padded-allowance X`` overrides the default 1.0).

``--assert-program`` runs the deep-halo HaloProgram gate (CI): for each
fusion depth ``s``, one traced program iteration must issue exactly ONE
exchange (exchanges-per-stencil-step <= 1/s), the deep-radius wire
layout must stay at the ragged optimum (the PR-3 wire-bytes gate, at the
new segment sizes), depths must agree bit-exactly on the interior, and
``price_program`` must never pick a depth whose predicted per-step cost
exceeds ``s=1``.  It also runs the heterogeneous-cycle gate: a fused
``[predictor, corrector]`` cycle with unequal per-dimension radii must
issue <= 1 exchange per cycle repeat, stay bit-exact against the
exchange-per-application reference, and price its auto depth no worse
per application than ``s=1``.

``--assert-overlap`` runs the region-split overlap gate (CI): region
mode must be bit-exact against the plain reference AND the monolithic
overlap path on the 2x2x2 grid, and ``choose_overlap_mode`` on the
checked-in ``ci_params.json`` tables must pick a mode priced no worse
than monolithic, record it as an ``overlap/mode=...`` decision, and pin
it on the rerun.

``--assert-compress`` runs the length-aware compressed-wire gate (CI):
a zero-heavy probed payload must select the lossless RLE wire and the
``varlen`` schedule, its traced collective bytes must equal
``plan.issued_bytes`` and land STRICTLY below the uncompressed ragged
optimum (the sum of packed extents — compressed bytes are the bytes on
the wire, not an accounting fiction), the exchange must stay bit-exact
against the capacity (grouped) transport, the model's probed choice on
the checked-in ``ci_params.json`` must never be priced worse than the
unprobed (uncompressed) choice of the same exchange, and the lossy
int8 wire must never be auto-picked.

``--assert-scale`` runs the simulated-scale gate (CI): sweep the
predicted schedule ladder (``PerfModel.at_scale``) over rank counts up
to the paper's 3072-process regime on the checked-in ``ci_params.json``
under a synthetic two-tier topology, and FAIL unless the model flips to
the ``tiered`` (inter-node coalesced) schedule at the large-rank end
with strictly fewer slow-tier messages than per-class grouped at equal
payload bytes, the best predicted cost is non-decreasing in rank count,
the flip is pinned as a topology-keyed decision that replays, and an
elastic remesh (``replan_on_remesh``) provably demotes the pin instead
of replaying it.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CODE = r"""
import time
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.comm import Communicator, policy_for_mode
from repro.halo import HaloSpec, halo_exchange, make_halo_plan

spec = HaloSpec(grid=(2, 2, 2), interior=(16, 16, 16), radius=2)
R = spec.nranks
az, ay, ax = spec.alloc
mesh = Mesh(np.array(jax.devices()[:R]), ("ranks",))
state0 = jnp.asarray(
    np.random.default_rng(0).normal(size=(R * az, ay, ax)).astype(np.float32))

for mode in ("baseline", "tempi"):
    comm = Communicator(axis_name="ranks", policy=policy_for_mode(mode))
    plan = make_halo_plan(spec, comm)
    fn = jax.jit(shard_map(
        lambda x: halo_exchange(x, spec, comm, "ranks", plan=plan),
        mesh=mesh, in_specs=P("ranks"), out_specs=P("ranks"),
        check_vma=False))
    print(f"fig12/wire-bytes/{mode},{plan.wire_bytes},"
          f"schedule={plan.wire.schedule};ops={plan.wire.wire_ops};"
          f"padded_layout_would_move={plan.wire.nranks * plan.wire.seg_bytes}")
    out = fn(state0); jax.block_until_ready(out)
    t0 = time.perf_counter()
    iters = 3
    for _ in range(iters):
        out = fn(out)
    jax.block_until_ready(out)
    us = (time.perf_counter() - t0) / iters * 1e6
    print(f"fig12/exchange/{mode},{us:.2f},"
          f"ranks=8;interior=16^3;r=2;wire_ops={comm.stats()['wire_ops']}")

    # pack-only phase (one face datatype, 26x per iteration in exchange)
    from repro.halo.exchange import _region_type
    ct = comm.commit(_region_type(spec, (0, 0, 1), "send"))
    local = jnp.zeros((az, ay, ax), jnp.float32)
    pfn = jax.jit(lambda b: comm.pack(b, ct))
    o = pfn(local); jax.block_until_ready(o)
    t0 = time.perf_counter()
    for _ in range(10):
        o = pfn(local)
    jax.block_until_ready(o)
    us = (time.perf_counter() - t0) / 10 * 1e6
    print(f"fig12/pack-face/{mode},{us:.2f},single-face")
"""


#: the CI regression gate: exact-policy bytes must equal the ragged
#: optimum, and the default (model-priced) policy may buy at most the
#: declared padding allowance — grows a diff the moment uncontrolled
#: padding creeps back in
_ASSERT_CODE = r"""
import os
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.comm import Communicator, FixedPolicy, collective_payload_bytes
from repro.halo import HaloSpec, halo_exchange, make_halo_plan

ALLOWANCE = float(os.environ.get("REPRO_PADDED_ALLOWANCE", "1.0"))

spec = HaloSpec(grid=(2, 2, 2), interior=(6, 5, 4), radius=2)
R = spec.nranks
az, ay, ax = spec.alloc
mesh = Mesh(np.array(jax.devices()[:R]), ("ranks",))
# forced pack strategy: the ragged optimum is exactly sum(ct.size)
comm = Communicator(axis_name="ranks", policy=FixedPolicy("rows"))
plan = make_halo_plan(spec, comm, schedule_policy="exact")
fn = jax.jit(shard_map(
    lambda x: halo_exchange(x, spec, comm, "ranks", plan=plan),
    mesh=mesh, in_specs=P("ranks"), out_specs=P("ranks"), check_vma=False))
x = jnp.zeros((R * az, ay, ax), jnp.float32)

ragged_optimum = sum(ct.packed_extent() for ct in plan.send_cts)
counts = collective_payload_bytes(fn, x)
print(f"wire-bytes-check: traced={counts['total']} "
      f"plan={plan.wire_bytes} optimum={ragged_optimum} "
      f"schedule={plan.wire.schedule} ops={counts['ops']}")
assert plan.wire_bytes == ragged_optimum, (plan.wire_bytes, ragged_optimum)
assert counts["total"] <= ragged_optimum, (
    f"exact-policy path moves {counts['total']} B > ragged optimum "
    f"{ragged_optimum} B — padding has crept back into the wire layout")
# the exchange must still be correct, in interpret mode, end to end
out = np.asarray(fn(jnp.asarray(
    np.random.default_rng(0).normal(size=(R * az, ay, ax)).astype(np.float32))))
assert np.isfinite(out).all()

# padded-allowance mode: the DEFAULT policy is model-priced and may buy
# uniform padding, but never more than the row-equalized bound nor the
# declared allowance over the ragged optimum
comm2 = Communicator(axis_name="ranks", policy=FixedPolicy("rows"))
plan2 = make_halo_plan(spec, comm2)
fn2 = jax.jit(shard_map(
    lambda x: halo_exchange(x, spec, comm2, "ranks", plan=plan2),
    mesh=mesh, in_specs=P("ranks"), out_specs=P("ranks"), check_vma=False))
counts2 = collective_payload_bytes(fn2, x)
uniform_bound = plan2.wire.nranks * plan2.wire.seg_bytes
print(f"padded-allowance-check: schedule={plan2.wire.schedule} "
      f"issued={plan2.wire.issued_bytes} traced={counts2['total']} "
      f"optimum={ragged_optimum} uniform_bound={uniform_bound} "
      f"allowance={ALLOWANCE}")
assert plan2.wire_bytes == ragged_optimum, (plan2.wire_bytes, ragged_optimum)
assert counts2["total"] == plan2.wire.issued_bytes, (counts2, plan2.wire.issued_bytes)
assert plan2.wire.issued_bytes <= uniform_bound, (
    "model policy issued more than the uniform row-equalized layout")
assert plan2.wire.issued_bytes <= (1.0 + ALLOWANCE) * ragged_optimum, (
    f"model policy buys {plan2.wire.padding_bytes} B padding — beyond the "
    f"{ALLOWANCE:.2f} allowance over the {ragged_optimum} B ragged optimum")
out2 = np.asarray(fn2(jnp.asarray(
    np.random.default_rng(0).normal(size=(R * az, ay, ax)).astype(np.float32))))
assert np.isfinite(out2).all()
print("WIRE_BYTES_OK")
"""


#: the deep-halo CI gate: a HaloProgram must actually avoid exchanges
#: (one per s stencil steps), keep the ragged-optimal wire layout at the
#: deep segment sizes, stay bit-exact across depths, and never let the
#: model pick a depth it predicts to be worse than step-per-exchange
_PROGRAM_ASSERT_CODE = r"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from repro.comm import Communicator, FixedPolicy, collective_payload_bytes
from repro.halo import build_halo_program, make_program_step
from repro.measure import DecisionCache

grid, interior = (2, 2, 2), (6, 5, 4)
nz, ny, nx = interior
R = 8
mesh = Mesh(np.array(jax.devices()[:R]), ("ranks",))
field = np.random.default_rng(0).normal(size=(R, nz, ny, nx)).astype(np.float32)

TOTAL_STEPS = 2
interiors = {}
for s in (1, 2):
    comm = Communicator(axis_name="ranks", policy=FixedPolicy("rows"))
    prog = build_halo_program(grid, interior, comm, steps=s,
                              schedule_policy="exact")
    fn = make_program_step(prog, comm, mesh)
    az, ay, ax = prog.spec.alloc
    rz, ry, rx = prog.spec.radii
    state = np.zeros((R, az, ay, ax), np.float32)
    state[:, rz:rz+nz, ry:ry+ny, rx:rx+nx] = field
    x = jnp.asarray(state.reshape(R * az, ay, ax))

    counts = collective_payload_bytes(fn, x)
    # one fused exchange (= plan.wire.wire_ops collectives) per s steps
    assert counts["ops"] == prog.plan.wire.wire_ops, (s, counts)
    exchanges_per_step = (counts["ops"] / prog.plan.wire.wire_ops) / s
    assert exchanges_per_step <= 1.0 / s + 1e-12, (s, exchanges_per_step)
    # wire-bytes gate (PR 3) at the deep radius: still the ragged optimum
    ragged_optimum = sum(ct.packed_extent() for ct in prog.plan.send_cts)
    assert prog.plan.wire_bytes == ragged_optimum, (s, prog.plan.wire_bytes)
    assert counts["total"] <= ragged_optimum, (s, counts, ragged_optimum)
    print(f"program/s={s}: ops={counts['ops']} "
          f"exchanges_per_step={exchanges_per_step:.3f} "
          f"wire_bytes={prog.plan.wire_bytes}")

    out = x
    for _ in range(TOTAL_STEPS // s):
        out = fn(out)
    interiors[s] = np.asarray(out).reshape(R, az, ay, ax)[
        :, rz:rz+nz, ry:ry+ny, rx:rx+nx]

np.testing.assert_array_equal(interiors[1], interiors[2])
print("program bit-exact across depths")

# the price_program oracle: auto never selects a depth predicted to be
# worse per stencil step than s=1 (and records the choice)
dc = DecisionCache()
comm = Communicator(axis_name="ranks", decisions=dc)
prog = build_halo_program(grid, interior, comm, steps="auto")
one = [e for e in prog.candidates if e.steps == 1]
assert one, prog.candidates
assert prog.estimate.per_step <= one[0].per_step, (
    prog.estimate, one[0])
assert any(d.strategy == f"program/s={prog.steps}" for d in dc.log)
print(f"auto depth s={prog.steps} per_step={prog.estimate.per_step:.3e} "
      f"(s=1 {one[0].per_step:.3e})")
print("PROGRAM_OK")
"""


#: the heterogeneous-cycle gate: a fused [predictor, corrector] cycle
#: with unequal per-dim radii must issue <= 1 exchange per cycle repeat,
#: keep the ragged-optimal deep wire layout (exact policy), stay
#: bit-exact against the exchange-per-application reference, and price
#: its auto depth no worse per application than s=1
_CYCLE_ASSERT_CODE = r"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from repro.comm import Communicator, FixedPolicy, collective_payload_bytes
from repro.halo import StencilOp, build_halo_program, make_program_step
from repro.measure import DecisionCache

ops = [StencilOp((2, 1, 1), weight=0.5), StencilOp((1, 1, 1), weight=0.25)]
grid, interior = (2, 2, 2), (8, 6, 6)   # cycle radii (3, 2, 2)
nz, ny, nx = interior
R = 8
mesh = Mesh(np.array(jax.devices()[:R]), ("ranks",))
field = np.random.default_rng(0).normal(size=(R, nz, ny, nx)).astype(np.float32)

def run_program(prog, comm, state_field, iters):
    fn = make_program_step(prog, comm, mesh)
    az, ay, ax = prog.spec.alloc
    rz, ry, rx = prog.spec.radii
    state = np.zeros((R, az, ay, ax), np.float32)
    state[:, rz:rz+nz, ry:ry+ny, rx:rx+nx] = state_field
    x = jnp.asarray(state.reshape(R * az, ay, ax))
    for _ in range(iters):
        x = fn(x)
    return np.asarray(x).reshape(R, az, ay, ax)[
        :, rz:rz+nz, ry:ry+ny, rx:rx+nx]

TOTAL = 2  # cycle repeats in every variant
interiors = {}
for s in (1, 2):
    comm = Communicator(axis_name="ranks", policy=FixedPolicy("rows"))
    prog = build_halo_program(grid, interior, comm, ops=ops, steps=s,
                              schedule_policy="exact")
    assert prog.spec.radii == (3 * s, 2 * s, 2 * s), prog.spec.radii
    assert prog.cycle_len == 2 and prog.applications == 2 * s
    fn = make_program_step(prog, comm, mesh)
    az, ay, ax = prog.spec.alloc
    x0 = jnp.zeros((R * az, ay, ax), jnp.float32)
    counts = collective_payload_bytes(fn, x0)
    assert counts["ops"] == prog.plan.wire.wire_ops, (s, counts)
    # wire-amortization measured over a FIXED amount of physical work:
    # TOTAL cycle repeats need TOTAL/s program iterations, so the
    # traced collective count must shrink to 1/s exchanges per repeat
    def total_work(x):
        for _ in range(TOTAL // s):
            x = fn(x)
        return x
    total_counts = collective_payload_bytes(total_work, x0)
    per_cycle = (total_counts["ops"] / prog.plan.wire.wire_ops) / TOTAL
    assert abs(per_cycle - 1.0 / s) < 1e-12, (s, per_cycle, total_counts)
    # exact-policy deep wire layout stays ragged-optimal
    ragged_optimum = sum(ct.packed_extent() for ct in prog.plan.send_cts)
    assert prog.plan.wire_bytes == ragged_optimum, (s, prog.plan.wire_bytes)
    assert counts["total"] <= ragged_optimum, (s, counts, ragged_optimum)
    print(f"cycle/s={s}: ops={counts['ops']} exchanges_per_cycle={per_cycle:.3f} "
          f"wire_bytes={prog.plan.wire_bytes}")
    interiors[s] = run_program(prog, comm, field, TOTAL // s)

np.testing.assert_array_equal(interiors[1], interiors[2])

# the per-application reference: exchange before EVERY op application
comm = Communicator(axis_name="ranks", policy=FixedPolicy("rows"))
ref_progs = [
    build_halo_program(grid, interior, comm, ops=[op], steps=1,
                       schedule_policy="exact")
    for op in ops
]
ref = field
for _ in range(TOTAL):
    for prog in ref_progs:
        ref = run_program(prog, comm, ref, 1)
np.testing.assert_array_equal(interiors[1], ref)
print("cycle bit-exact vs per-application reference")

# auto oracle + decision: never worse per application than s=1, and the
# cycle fingerprint lands in the decisions log
dc = DecisionCache()
comm = Communicator(axis_name="ranks", decisions=dc)
prog = build_halo_program(grid, interior, comm, ops=ops, steps="auto")
one = [e for e in prog.candidates if e.steps == 1]
assert one, prog.candidates
assert prog.estimate.per_step <= one[0].per_step, (prog.estimate, one[0])
rows = [d for d in dc.log if d.strategy == f"program/s={prog.steps}"]
assert rows and "cycle=[" in rows[0].signature, rows
print(f"cycle auto s={prog.steps} per_step={prog.estimate.per_step:.3e} "
      f"(s=1 {one[0].per_step:.3e})")
print("CYCLE_OK")
"""


#: the region-split overlap gate (CI): region mode must be bit-exact
#: against BOTH the plain exchange-then-cycle reference and the
#: monolithic overlap path, and the overlap/mode decision priced on the
#: checked-in ci_params.json must never choose a mode the model predicts
#: to be worse than monolithic (ties go to monolithic by construction)
_OVERLAP_ASSERT_CODE = r"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.comm import Communicator
from repro.halo import (HaloSpec, STENCIL26, halo_exchange, make_halo_plan,
                        make_halo_types, overlap_region_descriptors,
                        overlapped_stencil_iteration, stencil_steps)
from repro.measure import DecisionCache, load_ci_params

spec = HaloSpec(grid=(2, 2, 2), interior=(6, 5, 4), radius=2)
R = spec.nranks
az, ay, ax = spec.alloc
mesh = Mesh(np.array(jax.devices()[:R]), ("ranks",))
comm = Communicator(axis_name="ranks")
types = make_halo_types(spec, comm)
plan = make_halo_plan(spec, comm, types, schedule_policy="exact")
probe = {}

def plain(local):
    local = halo_exchange(local, spec, comm, "ranks", types, plan=plan)
    return stencil_steps(local, spec, steps=2)

def region(local):
    return overlapped_stencil_iteration(
        local, spec, comm, "ranks", types, steps=2, probe=probe,
        plan=plan, mode="region")

def mono(local):
    return overlapped_stencil_iteration(
        local, spec, comm, "ranks", types, steps=2, plan=plan,
        mode="monolithic")

kw = dict(mesh=mesh, in_specs=P("ranks"), out_specs=P("ranks"),
          check_vma=False)
jp = jax.jit(shard_map(plain, **kw))
jr = jax.jit(shard_map(region, **kw))
jm = jax.jit(shard_map(mono, **kw))
x = jnp.asarray(np.random.default_rng(0).normal(
    size=(R * az, ay, ax)).astype(np.float32))
ref = np.asarray(jp(x))
np.testing.assert_array_equal(ref, np.asarray(jr(x)))
np.testing.assert_array_equal(ref, np.asarray(jm(x)))
assert probe["overlap_mode"] == "region"
assert probe["rim_regions"] == 26, probe
assert sorted(probe["class_drain_order"]) == list(range(plan.wire.ngroups))
print(f"overlap-exact-check: rims={probe['rim_regions']} "
      f"classes={plan.wire.ngroups} bit-exact vs plain and monolithic")

# the decision gate on the pinned CI tables: whatever mode the model
# chooses must be priced no worse than monolithic, and the choice must
# land in (and pin from) the decisions cache
dc = DecisionCache()
comm_ci = Communicator(axis_name="ranks", params=load_ci_params(),
                       decisions=dc)
types_ci = make_halo_types(spec, comm_ci)
plan_ci = make_halo_plan(spec, comm_ci, types_ci)
core_bytes, rims = overlap_region_descriptors(spec, STENCIL26, plan_ci.wire)
mode, ests, pinned = comm_ci.model.choose_overlap_mode(
    plan_ci.wire, rims, core_bytes, STENCIL26.nneighbors)
assert not pinned
assert ests[mode].t_total <= ests["monolithic"].t_total, (mode, ests)
rows = [d for d in dc.log if d.strategy == f"overlap/mode={mode}"]
assert rows and "regions=" in rows[0].signature, rows
mode2, _, pinned2 = comm_ci.model.choose_overlap_mode(
    plan_ci.wire, rims, core_bytes, STENCIL26.nneighbors)
assert (mode2, pinned2) == (mode, True)
print(f"overlap-mode-check: schedule={plan_ci.wire.schedule} "
      f"classes={plan_ci.wire.ngroups} chose={mode} "
      + " ".join(f"{m}={e.t_total:.3e}s" for m, e in sorted(ests.items())))
print("OVERLAP_MODE_OK")
"""


#: the length-aware compressed-wire gate (CI): varlen RLE must move
#: strictly fewer traced bytes than the uncompressed ragged optimum,
#: bit-exact against the capacity transport; on the checked-in CI
#: tables the probed choice is never priced worse than the unprobed
#: one, and the lossy wire is never auto-picked
_COMPRESS_ASSERT_CODE = r"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.comm import Communicator, RleWire, collective_payload_bytes
from repro.comm.wireplan import reschedule
from repro.core import FLOAT, Subarray
from repro.measure import DecisionCache, load_ci_params

mesh = Mesh(np.array(jax.devices()[:1]), ("ranks",))
perms = [[(0, 0)]]
src = np.zeros((32, 32), np.float32)
src[10:12, 6:8] = 3.0  # zero-heavy halo shell: a compressible payload

comm = Communicator(axis_name="ranks")
ct = comm.commit(Subarray((32, 32), (16, 16), (4, 4), FLOAT))
strats, plan = comm.plan_neighbor([ct], perms, probe=jnp.asarray(src))
assert strats[0].name == RleWire.name, strats
assert plan.schedule == "varlen", plan.schedule
assert plan.stream_bytes and plan.effective_wire_bytes < plan.wire_bytes

def exchange(p):
    def body(buf):
        return comm.neighbor_alltoallv(buf, [ct], [ct], perms,
                                       plan=p, strategies=strats)
    return jax.jit(shard_map(body, mesh=mesh, in_specs=P(),
                             out_specs=P(), check_vma=False))

fn = exchange(plan)
x = jnp.asarray(src)
counts = collective_payload_bytes(fn, x)
ragged_optimum = ct.packed_extent()  # the uncompressed exact-byte floor
print(f"compress-bytes-check: traced={counts['total']} "
      f"issued={plan.issued_bytes} capacity={plan.wire_bytes} "
      f"uncompressed_optimum={ragged_optimum} "
      f"ratio={plan.stream_ratio:.4f}")
assert counts["total"] == plan.issued_bytes, (counts, plan.issued_bytes)
assert counts["total"] < ragged_optimum, (
    f"varlen moves {counts['total']} B >= the {ragged_optimum} B "
    f"uncompressed optimum — the compressed bytes are not the bytes "
    f"on the wire")

# bit-exact against the capacity (grouped, untruncated) transport
cap = reschedule(plan, "grouped")
assert cap.issued_bytes == cap.wire_bytes
out = np.asarray(fn(x))
out_cap = np.asarray(exchange(cap)(x))
np.testing.assert_array_equal(out, out_cap)
np.testing.assert_array_equal(out[10:12, 6:8], src[10:12, 6:8])
print("compress bit-exact vs capacity transport")

# model-choice gate on the pinned CI tables: planning WITH the probe
# must never be priced worse than planning without it (the probe only
# adds options), and the lossy int8 wire is never auto-picked
dc = DecisionCache()
comm_ci = Communicator(axis_name="ranks", params=load_ci_params(),
                       decisions=dc)
ct_ci = comm_ci.commit(Subarray((32, 32), (16, 16), (4, 4), FLOAT))
s_probed, p_probed = comm_ci.plan_neighbor([ct_ci], perms,
                                           probe=jnp.asarray(src))
s_plain, p_plain = comm_ci.plan_neighbor([ct_ci], perms)
wire_rows = {d.fingerprint: d for d in dc.log
             if d.strategy.startswith("wire/")}
probed_cost = wire_rows[p_probed.fingerprint].total
plain_cost = wire_rows[p_plain.fingerprint].total
print(f"compress-model-check: probed={p_probed.schedule} "
      f"({probed_cost:.3e}s) plain={p_plain.schedule} "
      f"({plain_cost:.3e}s)")
assert probed_cost <= plain_cost + 1e-15, (
    f"the probed plan ({p_probed.schedule}, {probed_cost:.3e}s) is "
    f"priced worse than the uncompressed plan ({p_plain.schedule}, "
    f"{plain_cost:.3e}s)")
for ss in (s_probed, s_plain, strats):
    assert all(s.name != "int8wire" for s in ss), (
        "the lossy int8 wire was auto-picked")
print("COMPRESS_OK")
"""


#: the simulated-scale gate (CI): the measured tables + a synthetic
#: two-tier topology must predict the paper-regime behavior — the wire
#: schedule flips to tier-coalesced as ranks grow, with strictly fewer
#: slow-tier messages than per-class grouped at equal payload, pinned
#: as a topology-keyed decision an elastic remesh provably demotes
_SCALE_ASSERT_CODE = r"""
from types import SimpleNamespace

from repro.comm import PerfModel, Topology, scale_ladder, synthetic_two_tier
from repro.measure import DecisionCache, load_ci_params
from repro.train.elastic import replan_on_remesh

RPN = 8
RANKS = (8, 16, 64, 256, 1024, 3072)
params = synthetic_two_tier(load_ci_params())
dc = DecisionCache()
model = PerfModel(params, decisions=dc)
ladder = scale_ladder(model, RANKS, RPN)
for e in ladder:
    print(f"scale/{e.ranks}: nodes={e.nodes} grid={e.grid} "
          f"best={e.schedule} wire_bytes={e.wire_bytes} "
          f"corr={e.correction_bytes} inter={e.inter_messages} "
          + " ".join(f"{s}={c:.3e}" for s, c in sorted(e.costs.items())))

# the ladder flips: single-node scales plan flat, the 3072-rank end is
# tier-coalesced and stays tier-coalesced above the flip point
top = ladder[-1]
assert top.ranks == 3072 and top.schedule == "tiered", top
assert ladder[0].schedule != "tiered", ladder[0]
flip = next(e.ranks for e in ladder if e.schedule == "tiered")
assert all(e.schedule == "tiered" for e in ladder if e.ranks >= flip)
print(f"scale/flip: tiered from {flip} ranks")

# above the flip: tiered never worse than per-class grouped, and it
# sends strictly fewer slow-tier messages at the same payload bytes
# (the costs dict prices every schedule on the same ScalePlan, so
# wire_bytes is equal by construction; the correction bytes tiered
# buys ride the fast tier and are accounted separately)
for e in ladder:
    if e.ranks < flip:
        continue
    assert e.costs["tiered"] <= e.costs["grouped"], (e.ranks, e.costs)
    assert e.inter_messages["tiered"] < e.inter_messages["grouped"], e
    assert e.correction_bytes > 0, e

# the predicted best exchange cost is non-decreasing in rank count
best = [min(e.costs.values()) for e in ladder]
assert all(b >= a - 1e-15 for a, b in zip(best, best[1:])), best

# the flip is pinned as a topology-keyed decision and replays
rows = [d for d in dc.log
        if d.strategy == "wire/tiered" and "topo=" in d.signature]
assert rows, dc.report()
again = model.at_scale(3072, ranks_per_node=RPN)
assert again.pinned and again.schedule == "tiered", again
print(f"scale/pin: {rows[0].strategy}@{rows[0].fingerprint} replayed")

# elastic remesh: rebinding to a reshaped topology demotes every
# topology-sensitive pin recorded under the old shapes — the next
# at_scale re-prices from scratch instead of replaying a stale pin
npins = len(dc)
rep = replan_on_remesh(SimpleNamespace(model=model),
                       Topology.blocked(2048, RPN))
assert rep.npruned == npins, (rep.npruned, npins)
assert len(dc) == 0, dc.report()
redo = model.at_scale(3072, ranks_per_node=RPN)
assert not redo.pinned and redo.schedule == "tiered", redo
print(f"scale/replan: pruned {rep.npruned} pins, re-priced fresh")
print("SCALE_OK")
"""


def run(assert_ragged: bool = False, assert_program: bool = False,
        assert_overlap: bool = False, assert_scale: bool = False,
        assert_compress: bool = False,
        padded_allowance: float = None) -> None:
    # CPU gates, not chip measurements: the children run on 8 virtual
    # CPU devices whatever the machine holds
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    if padded_allowance is not None:
        env["REPRO_PADDED_ALLOWANCE"] = str(padded_allowance)
    gate = (assert_ragged or assert_program or assert_overlap
            or assert_scale or assert_compress)
    # all requested gates run when several flags are given — combining
    # flags must never silently drop a regression check
    jobs = []
    if assert_ragged:
        jobs.append((_ASSERT_CODE, "WIRE_BYTES_OK"))
    if assert_program:
        jobs.append((_PROGRAM_ASSERT_CODE, "PROGRAM_OK"))
        jobs.append((_CYCLE_ASSERT_CODE, "CYCLE_OK"))
    if assert_overlap:
        jobs.append((_OVERLAP_ASSERT_CODE, "OVERLAP_MODE_OK"))
    if assert_scale:
        jobs.append((_SCALE_ASSERT_CODE, "SCALE_OK"))
    if assert_compress:
        jobs.append((_COMPRESS_ASSERT_CODE, "COMPRESS_OK"))
    if not jobs:
        jobs.append((_CODE, None))
    for code, ok_token in jobs:
        proc = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(code)],
            env=env, capture_output=True, text=True, timeout=1200,
        )
        if proc.returncode != 0:
            print(f"fig12/FAILED,0,{proc.stderr.splitlines()[-1] if proc.stderr else 'unknown'}")
            if gate:
                sys.stderr.write(proc.stderr)
                sys.exit(1)
            return
        sys.stdout.write(proc.stdout)
        if ok_token is not None and ok_token not in proc.stdout:
            sys.exit(1)


if __name__ == "__main__":
    argv = sys.argv[1:]
    allowance = None
    if "--padded-allowance" in argv:
        allowance = float(argv[argv.index("--padded-allowance") + 1])
    run(
        assert_ragged="--assert-ragged" in argv,
        assert_program="--assert-program" in argv,
        assert_overlap="--assert-overlap" in argv,
        assert_scale="--assert-scale" in argv,
        assert_compress="--assert-compress" in argv,
        padded_allowance=allowance,
    )
