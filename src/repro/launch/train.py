"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train \
        --arch qwen2-0.5b --steps 100 --scale smoke   # CPU-sized run
    PYTHONPATH=src python -m repro.launch.train --arch repro-100m --steps 300

Wires together: config -> model -> sharded params/opt -> data pipeline ->
jit'd train step (in/out shardings from the rule set) -> checkpoint
manager (restore-on-start, periodic atomic saves) -> straggler monitor.

Datatype communication goes through a *production* Communicator
(``repro.measure.production``): the first run on a machine calibrates
the system tables once (reduced grid off-TPU) and records every
strategy selection to a decisions file in the measure store; later runs
load both and pin the selections — the model is never consulted again
(``--no-comm-cache`` skips all of it).
"""

from __future__ import annotations

import argparse
import time
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.configs.registry import ARCHS, get_config, smoke_config
from repro.data.pipeline import DataConfig, synthetic_batch
from repro.distributed.sharding import (
    DEFAULT_RULES,
    tree_partition_specs,
    use_rules,
)
from repro.launch.mesh import make_test_mesh
from repro.models.model import build_model
from repro.train.checkpoint import CheckpointManager
from repro.train.elastic import StragglerMonitor
from repro.train.grad_wire import GRAD_WIRE_MODES, GradWire
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.train_step import make_grad_step, make_train_step

#: ~100M-parameter config for the end-to-end example (deliverable b)
REPRO_100M = ModelConfig(
    name="repro-100m", family="dense",
    num_layers=12, d_model=512, num_heads=8, num_kv_heads=4,
    d_ff=2048, vocab_size=32000, remat=False,
)


def resolve_config(arch: str, scale: str) -> ModelConfig:
    if arch == "repro-100m":
        cfg = REPRO_100M
    else:
        cfg = get_config(arch) if scale == "full" else smoke_config(arch)
    return cfg


def train(
    cfg: ModelConfig,
    steps: int,
    seq_len: int,
    global_batch: int,
    ckpt_dir: str,
    mesh=None,
    log_every: int = 10,
    ckpt_every: int = 100,
    comm=None,
    grad_wire: str = "off",
) -> dict:
    shape = ShapeConfig("train", seq_len, global_batch, "train")
    model = build_model(cfg)
    opt_cfg = AdamWConfig(
        moment_dtype=cfg.opt_moment_dtype, total_steps=max(steps, 10)
    )
    # "off" keeps the fused, donating train step; any other mode splits
    # it so the gradient exchange runs through the communicator's wire
    # stack between the jitted halves (model-priced, pinned, audited)
    wire = None
    if grad_wire != "off":
        if comm is None:
            raise ValueError(
                f"--grad-wire {grad_wire} needs a communicator "
                "(incompatible with --no-comm-cache)"
            )
        wire = GradWire(comm, mode=grad_wire)
        grad_fn, update_fn = make_grad_step(model, opt_cfg)
    else:
        step_fn = make_train_step(model, opt_cfg)
    mgr = CheckpointManager(ckpt_dir, every=ckpt_every)
    monitor = StragglerMonitor()

    if mesh is None and jax.device_count() >= 4:
        mesh = make_test_mesh(data=2, model=2)

    with use_rules(mesh, DEFAULT_RULES):
        if mesh is not None:
            p_specs = tree_partition_specs(
                jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))),
                DEFAULT_RULES, mesh,
            )
            p_shard = jax.tree.map(
                lambda s: NamedSharding(mesh, s), p_specs,
                is_leaf=lambda x: isinstance(x, P),
            )
            init = jax.jit(model.init, out_shardings=p_shard)
        else:
            init = jax.jit(model.init)

        def make_state():
            params = init(jax.random.PRNGKey(0))
            return {"params": params, "opt": init_opt_state(params, opt_cfg)}

        start, state = mgr.restore_or_init(make_state)
        if start:
            print(f"restored checkpoint at step {start}")
        params, opt_state = state["params"], state["opt"]

        if wire is not None:
            jit_grads = jax.jit(grad_fn)
            jit_update = jax.jit(update_fn, donate_argnums=(0, 1))
        else:
            jit_step = jax.jit(step_fn, donate_argnums=(0, 1))
        history = []
        for step in range(start, steps):
            t0 = time.perf_counter()
            batch = synthetic_batch(cfg, shape, step)
            if wire is not None:
                loss, metrics0, grads = jit_grads(params, batch)
                if not wire.planned:
                    # first concrete gradients are the calibration
                    # probe: the ratio is measured, never assumed
                    wire.plan_for(grads)
                    print(wire.describe())
                grads = wire.exchange(grads)
                params, opt_state, metrics = jit_update(
                    params, opt_state, grads, loss, metrics0
                )
            else:
                params, opt_state, metrics = jit_step(
                    params, opt_state, batch
                )
            metrics = jax.device_get(metrics)
            dt = time.perf_counter() - t0
            verdict = monitor.observe(step, dt)
            if verdict == "remesh":
                print(f"straggler policy escalation at step {step} "
                      f"(persistently slow steps) — checkpoint + remesh")
            history.append(float(metrics["loss"]))
            if step % log_every == 0 or step == steps - 1:
                print(
                    f"step {step:5d} loss {metrics['loss']:.4f} "
                    f"gnorm {metrics['grad_norm']:.3f} "
                    f"lr {metrics['lr']:.2e} {dt*1e3:.0f}ms [{verdict}]"
                )
            mgr.maybe_save(step, {"params": params, "opt": opt_state})

        mgr.maybe_save(steps, {"params": params, "opt": opt_state})
    out = {"losses": history, "params": params}
    if comm is not None:
        out["comm_stats"] = comm.stats()
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="repro-100m",
                    choices=["repro-100m", *ARCHS])
    ap.add_argument("--scale", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--comm-cache", default=None, metavar="DIR",
                    help="measure-store root for the production "
                         "communicator (default: $REPRO_MEASURE_DIR or "
                         "the user cache dir)")
    ap.add_argument("--no-comm-cache", action="store_true",
                    help="skip calibration/decision pinning entirely "
                         "(analytic model, nothing persisted)")
    ap.add_argument("--grad-wire", default="off", choices=GRAD_WIRE_MODES,
                    help="route the optimizer gradient exchange through "
                         "the production communicator as a committed "
                         "type: 'auto' is model-priced from a probe of "
                         "the first step's gradients (a compressible "
                         "payload rides the lossless varlen RLE wire), "
                         "'rle' forces it, 'int8' opts into the lossy "
                         "quantized wire (never auto-picked)")
    ap.add_argument("--halo-steps", default="auto", metavar="auto|N",
                    help="fusion depth for any deep-halo stencil program "
                         "the job builds (repro.halo.program); 'auto' is "
                         "model-priced and pinned through the decisions "
                         "file so reruns reuse the same depth")
    ap.add_argument("--smoother-iters", type=int, default=1,
                    help="iterations of the data-axis smoother workload "
                         "run before training (the in-launch HaloProgram "
                         "that exercises --halo-steps end to end; 0 "
                         "disables)")
    ap.add_argument("--smoother-cycle", default="predictor-corrector",
                    help="op cycle the smoother fuses (see "
                         "repro.launch.smoother.CYCLES)")
    ap.add_argument("--ranks-per-node", type=int, default=None,
                    metavar="N",
                    help="declare the two-level machine shape: ranks are "
                         "blocked N-per-node (repro.comm.topology), the "
                         "model prices intra- vs inter-node links "
                         "separately, and wire/program pins are keyed by "
                         "the topology fingerprint (default: flat)")
    ap.add_argument("--telemetry", action="store_true",
                    help="attach the runtime exchange probe "
                         "(repro.fleet): observed-vs-predicted wall time "
                         "per decision key, persisted to telemetry.json "
                         "in the measure store on save")
    ap.add_argument("--drift-report", default=None, metavar="FILE",
                    help="write a repro.fleet DriftReport JSON after the "
                         "run (implies --telemetry)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record hierarchical exchange spans (repro.obs) "
                         "and export a Chrome-trace JSON here (render "
                         "with `python -m repro.obs summary`)")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    from repro.halo.program import parse_halo_steps

    halo_steps = parse_halo_steps(args.halo_steps)

    cfg = resolve_config(args.arch, args.scale)
    n = cfg.param_count()
    print(f"training {cfg.name} ({n/1e6:.1f}M params, family={cfg.family}) "
          f"for {args.steps} steps @ seq={args.seq_len} batch={args.global_batch}")

    comm = save_decisions = None
    want_telemetry = bool(args.telemetry or args.drift_report)
    if not args.no_comm_cache:
        from repro.measure.production import production_communicator

        topology = None
        if args.ranks_per_node:
            from repro.comm.topology import Topology

            topology = Topology.blocked(
                jax.device_count(), args.ranks_per_node
            )
        comm, save_decisions = production_communicator(
            args.comm_cache, axis_name="data", halo_steps=halo_steps,
            telemetry=want_telemetry or None,
            tracer=bool(args.trace) or None,
            topology=topology,
        )
        dc = comm.model.decisions
        topo_note = (
            f" topo={topology.fingerprint}({topology.nnodes} nodes)"
            if topology is not None else ""
        )
        print(f"comm: params={comm.model.params.name} "
              f"pinned_decisions={len(dc)} halo_steps={halo_steps} "
              f"pinned_programs={len(dc.program_rows())}{topo_note}")
    else:
        from repro.halo.program import set_default_halo_steps

        set_default_halo_steps(halo_steps)

    if args.smoother_iters > 0 and comm is not None:
        # the in-launch deep-halo workload: smooth a data-axis field
        # before training so the fusion-depth seam (--halo-steps ->
        # production communicator -> build_halo_program -> decisions
        # file) runs end to end on every job
        from repro.launch.smoother import run_smoother

        report = run_smoother(comm, iters=args.smoother_iters,
                              cycle=args.smoother_cycle)
        print(report.summary)

    out = train(cfg, args.steps, args.seq_len, args.global_batch,
                args.ckpt_dir, comm=comm, grad_wire=args.grad_wire)
    losses = out["losses"]
    print(f"loss: first={losses[0]:.4f} last={losses[-1]:.4f} "
          f"(delta {losses[0]-losses[-1]:+.4f})")
    if save_decisions is not None:
        path = save_decisions()
        dc = comm.model.decisions
        print(f"comm: recorded {len(dc)} decisions "
              f"({dc.pinned_hits} pinned hits) -> {path}")
    if args.trace and comm is not None and comm.tracer is not None:
        from repro.obs.export import save_chrome_trace

        tpath = save_chrome_trace(comm.tracer, args.trace)
        print(f"trace ({len(comm.tracer)} spans) -> {tpath}")
    if comm is not None and want_telemetry:
        print(comm.telemetry.report())
        if args.drift_report:
            from repro.fleet.drift import DriftDetector

            drift = DriftDetector().audit(
                comm.model.decisions, comm.model.params,
                telemetry=comm.telemetry, system="train",
            )
            print(f"drift report -> {drift.save(args.drift_report)}")


if __name__ == "__main__":
    main()
