"""Traffic kind ``pack_unpack``: ``MPI_Pack`` then ``MPI_Unpack`` of one
byte ``Vector`` through ``repro.comm.Communicator``.

Set-up commits ``Vector(count, blocklen, pitch, BYTE)`` on the
production communicator (analytic v5e table, no calibration), makes
the source and the destination on the device from the seed (random
bytes, ``count * pitch`` each), compiles both calls and runs one
sequence.  One call is one sequence: ``pack`` of the source, then
``unpack`` of the packed bytes into the destination, which the next
sequence carries on; issue on the host through ``block_until_ready``.
Each of the two is a jitted call, and unpack donates the destination
(``MPI_Unpack`` writes the user's buffer).

Checked after the window: the packed bytes of the last sequence and of
one drawn from the seed, and the destination, against the plain
gather/scatter of ``bench/reference/pack.py``.

Traffic file keys: ``count``, ``blocklen`` and ``about`` (text).
"""

from __future__ import annotations

import time
from pathlib import Path
from typing import Dict, List

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import SingleDeviceSharding

from reference import pack as ref
from work import pack_unpack_bytes

TRAFFIC_KEYS = {"driver", "about", "count", "blocklen"}


class Session:
    """One cell's committed type, buffers and samples."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 store: Path, seconds: float, params=None):
        from repro.core import BYTE, Vector
        from repro.measure.production import production_communicator

        unknown = set(traffic) - TRAFFIC_KEYS
        if unknown:
            raise ValueError(
                f"pack_unpack reads no traffic keys {sorted(unknown)}")
        if config["element"] != "BYTE":
            raise ValueError(f"element {config['element']!r} is not BYTE")
        if len(devices) != 1:
            raise ValueError(f"pack_unpack runs on one device, got {len(devices)}")
        self.desc = (traffic["count"], traffic["blocklen"], config["pitch"])
        count, blocklen, pitch = self.desc
        t0 = time.perf_counter()
        self.comm, _ = production_communicator(
            store, calibrate=False, params=params,
        )
        ct = self.comm.commit(Vector(count, blocklen, pitch, BYTE))
        t1 = time.perf_counter()
        nbytes = count * pitch
        self.gen = jax.jit(
            lambda k: jax.random.bits(k, (nbytes,), jnp.uint8),
            out_shardings=SingleDeviceSharding(devices[0]),
        )
        key = jax.random.key(seed)
        self.src_key, self.dst_key = jax.random.split(key)
        self.src = self.gen(self.src_key)
        dst = self.gen(self.dst_key)
        comm = self.comm
        self.pack = jax.jit(lambda b: comm.pack(b, ct)).lower(
            self.src).compile()
        packed = self.pack(self.src)
        self.unpack = jax.jit(
            lambda d, p: comm.unpack(d, p, ct), donate_argnums=0
        ).lower(dst, packed).compile()
        t2 = time.perf_counter()
        self.dst = dst
        self.rng = np.random.default_rng(seed % 2**63)
        self.calls = 0
        self.call()  # warm-up sequence
        self.calls = 0
        self.sample = None
        self.facts = {
            "strategy": comm.select(ct, 1, wire=False).name,
            "packed_bytes": ct.size,
            "buffer_bytes": nbytes,
            "params": comm.model.params.name,
            "comm_stats_at_setup": {
                k: v for k, v in comm.stats().items() if isinstance(v, int)
            },
            "host_s": {
                "production_communicator_and_commit": t1 - t0,
                "buffers_and_compile": t2 - t1,
                "warmup_sequence": time.perf_counter() - t2,
            },
        }

    def call(self) -> float:
        with TraceAnnotation("bench.dispatch"):
            packed = self.pack(self.src)
            dst = self.unpack(self.dst, packed)
        with TraceAnnotation("bench.block"):
            dst.block_until_ready()
        self.dst = dst
        self.last = packed
        self.calls += 1
        if self.rng.random() * self.calls < 1.0:
            self.sample = packed
        return 0.0

    def work_units(self, calls: int) -> int:
        return calls

    def metrics(self, latencies: List[float], window_s: float) -> Dict[str, float]:
        lat = np.asarray(latencies)
        return {
            "call_us": window_s / len(lat) * 1e6,
            "call_p95_us": float(np.percentile(lat, 95)) * 1e6,
        }

    def layer_context(self) -> dict:
        count, blocklen, _ = self.desc
        return {"needed_bytes_per_call": pack_unpack_bytes(count, blocklen, 1)}

    def release_and_check(self, control: bool = False) -> Dict[str, float]:
        """Compare the sampled and last packed buffers and the
        destination with the reference; returns the numbers compared.
        ``control`` adds ``control.dest_bytes_wrong``: the destination
        as an unpack that writes whole pitch rows leaves it, which
        breaks the guarantee that bytes between the blocks stay
        unchanged."""
        count, blocklen, pitch = self.desc
        src = np.asarray(self.src)
        dst0 = np.asarray(self.gen(self.dst_key))
        want = ref.pack(src, count, blocklen, pitch)
        want_dst = ref.unpack(dst0, want, count, blocklen, pitch)
        out = {
            "packed_bytes_wrong": max(
                wrong_bytes(np.asarray(p), want)
                for p in (self.sample, self.last)
            ),
            "dest_bytes_wrong": wrong_bytes(np.asarray(self.dst), want_dst),
        }
        self.src = self.dst = self.sample = self.last = None
        if control:
            rows = dst0.copy()
            end = (count - 1) * pitch + blocklen
            rows[:end] = src[:end]
            out["control.dest_bytes_wrong"] = wrong_bytes(rows, want_dst)
        return out


def wrong_bytes(got: np.ndarray, want: np.ndarray) -> int:
    """Bytes of ``got`` that differ from ``want``; all of them when the
    sizes differ."""
    got = got.reshape(-1).view(np.uint8)
    if got.shape != want.shape:
        return int(max(got.size, want.size))
    return int(np.count_nonzero(got != want))
