"""Batched serving driver: prefill + decode loop with a request queue.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
        --scale smoke --requests 8 --max-new 16

Implements the serving side of the framework: continuous batching
(slots are re-filled from the queue as sequences finish), family-aware
caches (KV ring buffer / SSM state / RWKV shift state), greedy sampling.

Like ``launch.train``, the server's datatype communication seam is a
*production* Communicator (``repro.measure.production``): calibrated
tables + a pinned decisions file mean the strategy model runs at most
once per deployment, not once per process.
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.configs.registry import ARCHS, get_config, smoke_config
from repro.models.model import build_model

__all__ = ["ServeLoop", "Request"]


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int
    out: List[int] = field(default_factory=list)
    done: bool = False


class ServeLoop:
    """Slot-based continuous batching over a fixed decode batch."""

    def __init__(self, cfg: ModelConfig, batch_size: int, max_len: int,
                 comm=None):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.params = self.model.init(jax.random.PRNGKey(0))
        self.B = batch_size
        self.max_len = max_len
        self.cache = self.model.init_cache(batch_size, max_len)
        self.slots: List[Optional[Request]] = [None] * batch_size
        self.slot_pos = np.zeros(batch_size, np.int32)
        self._decode = jax.jit(self.model.decode_step)
        #: datatype-communication seam (production Communicator); every
        #: cross-device exchange a deployment adds goes through it so
        #: calibrated params + pinned decisions apply uniformly
        self.comm = comm

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def admit(self, req: Request) -> bool:
        slot = self._free_slot()
        if slot is None:
            return False
        self.slots[slot] = req
        self.slot_pos[slot] = 0
        return True

    def step(self, t: int):
        """One global decode step: each active slot feeds its next
        prompt token (teacher-forced prefill-by-decode, family-agnostic)
        or its last generated token."""
        toks = np.zeros(self.B, np.int32)
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            p = self.slot_pos[i]
            if p < len(req.prompt):
                toks[i] = req.prompt[p]
            else:
                toks[i] = req.out[-1] if req.out else 0
        logits, self.cache = self._decode(
            self.params, self.cache, jnp.asarray(toks), jnp.int32(t)
        )
        nxt = np.asarray(jnp.argmax(logits, -1))
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            self.slot_pos[i] += 1
            if self.slot_pos[i] >= len(req.prompt):
                req.out.append(int(nxt[i]))
                if len(req.out) >= req.max_new:
                    req.done = True
                    self.slots[i] = None

    def run(self, queue: List[Request]) -> Dict[int, List[int]]:
        pending = list(queue)
        t = 0
        done: Dict[int, List[int]] = {}
        while pending or any(self.slots):
            while pending and self.admit(pending[0]):
                pending.pop(0)
            self.step(t)
            t += 1
            for r in queue:
                if r.done and r.rid not in done:
                    done[r.rid] = r.out
            if t >= self.max_len:
                break
        return done


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b", choices=list(ARCHS))
    ap.add_argument("--scale", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--comm-cache", default=None, metavar="DIR",
                    help="measure-store root for the production "
                         "communicator")
    ap.add_argument("--no-comm-cache", action="store_true",
                    help="skip calibration/decision pinning entirely")
    ap.add_argument("--halo-steps", default="auto", metavar="auto|N",
                    help="fusion depth for any deep-halo stencil program "
                         "the deployment builds; 'auto' is model-priced "
                         "and pinned through the decisions file")
    ap.add_argument("--smoother-iters", type=int, default=1,
                    help="iterations of the data-axis smoother workload "
                         "(the in-launch HaloProgram exercising "
                         "--halo-steps end to end; 0 disables)")
    ap.add_argument("--smoother-cycle", default="smooth",
                    help="op cycle the smoother fuses (see "
                         "repro.launch.smoother.CYCLES)")
    ap.add_argument("--ranks-per-node", type=int, default=None,
                    metavar="N",
                    help="declare the two-level machine shape: ranks "
                         "blocked N-per-node (repro.comm.topology); the "
                         "model prices intra- vs inter-node links "
                         "separately and keys wire/program pins by the "
                         "topology fingerprint (default: flat)")
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

    from repro.halo.program import parse_halo_steps, set_default_halo_steps

    halo_steps = parse_halo_steps(args.halo_steps)

    cfg = get_config(args.arch) if args.scale == "full" else smoke_config(args.arch)
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
            args.comm_cache, halo_steps=halo_steps,
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
        set_default_halo_steps(halo_steps)
    if args.smoother_iters > 0 and comm is not None:
        # the deployment's deep-halo workload: a state-smoothing pass
        # over the data axis through the same production communicator,
        # so the --halo-steps seam is exercised (and pinned) in serving
        # jobs too
        from repro.launch.smoother import run_smoother

        report = run_smoother(comm, iters=args.smoother_iters,
                              cycle=args.smoother_cycle, axis_name="data")
        print(report.summary)
    loop = ServeLoop(cfg, args.batch, args.max_len, comm=comm)
    rng = np.random.default_rng(0)
    reqs = [
        Request(
            rid=i,
            prompt=list(rng.integers(0, cfg.vocab_size, size=rng.integers(4, 12))),
            max_new=args.max_new,
        )
        for i in range(args.requests)
    ]
    t0 = time.perf_counter()
    done = loop.run(reqs)
    dt = time.perf_counter() - t0
    total_new = sum(len(v) for v in done.values())
    print(f"served {len(done)}/{args.requests} requests, "
          f"{total_new} tokens in {dt:.1f}s "
          f"({total_new/dt:.1f} tok/s, batch={args.batch}, {cfg.name})")
    for rid in sorted(done)[:3]:
        print(f"  req {rid}: {done[rid][:8]}...")
    if save_decisions is not None:
        path = save_decisions()
        print(f"comm: decisions -> {path}")
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
                telemetry=comm.telemetry, system="serve",
            )
            print(f"drift report -> {drift.save(args.drift_report)}")


if __name__ == "__main__":
    main()
