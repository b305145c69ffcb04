"""Timed-sweep measurement harness (paper §6.3: "TEMPI provides a binary
that records system performance parameters to the file system.  This
binary should be run once before TEMPI is used in an application.").

The paper's model needs *every* term of T = T_pack + T_link + T_unpack
from empirical measurement, not analytic constants — strategy rankings
flip with block size and total size per system.  This module measures
all of them on the *running* backend:

* :func:`measure_pack_table` / :func:`measure_unpack_table` — per
  registered strategy, over a sparse (contiguous-block-size x
  total-object-size) grid, interpolated at query time;
* :func:`measure_wire_table` — one-hop collective (``ppermute`` ring
  over however many devices exist; 1-device self-permutes still price
  the dispatch overhead) over message sizes, with a least-squares
  (latency, bandwidth) fit;
* :func:`measure_wire_tables` — the same sweep run **per mesh axis**: a
  multi-axis mesh (fast ICI axis x slow DCN axis) has genuinely
  different link terms per axis, so each axis gets its own ring, table,
  and fit, and ``PerfModel.t_link(axis=...)`` prices the axis it is
  actually crossing;
* :func:`measure_copy_table` — contiguous device copy over sizes (the
  memcpy analogue every strategy's staging bottoms out in);
* :func:`measure_compress_table` — per wire compressor, the
  encode/decode transform cost over sizes plus an achieved-ratio
  sample (STORE_FORMAT 6) — what
  :meth:`~repro.comm.perfmodel.PerfModel.measured_compress`
  interpolates to price a compressed schedule's pack-side cost against
  its wire-byte savings;
* :func:`measure_stencil_table` — one stencil application
  (:func:`repro.kernels.ops.stencil_window_update`) over (neighbor
  count x window bytes): the redundant ghost-shell term of
  :meth:`~repro.comm.perfmodel.PerfModel.price_program` priced from a
  real sweep instead of the contiguous-copy proxy.

:func:`calibrate_params` assembles everything into a
:class:`~repro.comm.perfmodel.SystemParams`.  On a real TPU the
measurements are wall-clock; on CPU containers they still provide a
useful relative ordering.  ``reduced=True`` shrinks the grid for CI.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import BYTE, TypeRegistry, Vector
from repro.kernels import ops
from repro.comm.perfmodel import SystemParams, TPU_V5E

__all__ = [
    "BLOCK_BYTES",
    "TOTAL_BYTES",
    "REDUCED_BLOCK_BYTES",
    "REDUCED_TOTAL_BYTES",
    "PITCH",
    "time_fn",
    "measure_pack_table",
    "measure_unpack_table",
    "measure_wire_table",
    "measure_wire_tables",
    "measure_link_class_tables",
    "measure_copy_table",
    "measure_compress_table",
    "measure_stencil_table",
    "STENCIL_RADII",
    "REDUCED_STENCIL_RADII",
    "fit_latency_bandwidth",
    "calibrate_params",
]

# paper Fig. 10 sweeps 64 B - 4 MiB objects over block sizes; we use a
# coarser grid (interpolated at query time)
BLOCK_BYTES: Tuple[int, ...] = (8, 32, 128, 512)
TOTAL_BYTES: Tuple[int, ...] = (1 << 10, 1 << 14, 1 << 18, 1 << 22)
#: CI / smoke grid — small enough for interpret-mode kernels on CPU
REDUCED_BLOCK_BYTES: Tuple[int, ...] = (8, 128)
REDUCED_TOTAL_BYTES: Tuple[int, ...] = (1 << 10, 1 << 14)
PITCH = 512  # paper Fig. 7 uses 512 B pitch

#: stencil-sweep op shapes: per-dimension radii -> neighbor counts 26,
#: 44, and 124 — spanning the paper's 26-point op up to deep boxes
STENCIL_RADII: Tuple[Tuple[int, int, int], ...] = (
    (1, 1, 1), (2, 1, 1), (2, 2, 2),
)
REDUCED_STENCIL_RADII: Tuple[Tuple[int, int, int], ...] = ((1, 1, 1), (2, 1, 1))


def time_fn(fn, *args, iters: int = 5) -> float:
    """Mean wall-clock seconds per call of an async-dispatch ``fn``.

    The warm-up call (compile + caches) MUST be block_until_ready'd
    before ``t0`` is taken: JAX dispatch is asynchronous, so an
    unsynchronized warm-up would still be executing inside the timed
    region and bleed into every sample.
    """
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _resolve_strategies(strategies):
    from repro.comm.api import default_registry, resolve_strategy

    if strategies is None:
        return default_registry().measurable()
    return tuple(resolve_strategy(s) for s in strategies)


def _sweep(
    block_bytes: Sequence[int], total_bytes: Sequence[int]
) -> Iterable[Tuple[int, int, object, jax.Array]]:
    """Yield (blk, nblocks, committed vector type, source buffer) over
    the measurement grid — the same shapes for pack and unpack so their
    tables are directly comparable."""
    reg = TypeRegistry()
    for blk in block_bytes:
        pitch = max(PITCH, 2 * blk)
        for total in total_bytes:
            nblocks = max(total // blk, 1)
            ct = reg.commit(Vector(nblocks, blk, pitch, BYTE))
            buf = jnp.zeros((ct.extent + 64,), jnp.uint8)
            yield blk, nblocks, ct, buf


def _measure_table(
    make_timed, strategies, block_bytes, total_bytes, iters
) -> Dict[str, List[Tuple[float, float, float]]]:
    """Shared sweep scaffolding for the 2D kernel tables: ``make_timed``
    maps (strategy, ct, buf) -> (jitted fn, args).  One implementation
    so cap handling / grid shape / row format can never drift between
    the pack and unpack tables."""
    strats = _resolve_strategies(strategies)
    table: Dict[str, List[Tuple[float, float, float]]] = {
        s.name: [] for s in strats
    }
    for blk, nblocks, ct, buf in _sweep(block_bytes, total_bytes):
        for s in strats:
            cap = s.calibration_cap
            if cap is not None and nblocks > cap:
                continue  # per-block unrolled HLO blows up past the cap
            jfn, args = make_timed(s, ct, buf)
            sec = time_fn(jfn, *args, iters=iters)
            table[s.name].append(
                (math.log2(blk), math.log2(nblocks * blk), sec)
            )
    return table


def measure_pack_table(
    strategies=None,
    block_bytes: Sequence[int] = BLOCK_BYTES,
    total_bytes: Sequence[int] = TOTAL_BYTES,
    iters: int = 5,
) -> Dict[str, List[Tuple[float, float, float]]]:
    """Measure pack time for every calibratable registered strategy (or
    an explicit iterable of strategies/names) over the grid."""

    def timed(s, ct, buf):
        return jax.jit(
            lambda b, _ct=ct, _s=s: ops.pack(b, _ct, strategy=_s)
        ), (buf,)

    return _measure_table(timed, strategies, block_bytes, total_bytes, iters)


def measure_unpack_table(
    strategies=None,
    block_bytes: Sequence[int] = BLOCK_BYTES,
    total_bytes: Sequence[int] = TOTAL_BYTES,
    iters: int = 5,
) -> Dict[str, List[Tuple[float, float, float]]]:
    """Measure unpack (packed bytes -> strided destination) over the same
    grid as :func:`measure_pack_table` — the paper observes pack/unpack
    asymmetry, so the model must not derive one from the other."""

    def timed(s, ct, buf):
        packed = jnp.zeros((ct.size,), jnp.uint8)
        return jax.jit(
            lambda b, p, _ct=ct, _s=s: ops.unpack(b, p, _ct, strategy=_s)
        ), (buf, packed)

    return _measure_table(timed, strategies, block_bytes, total_bytes, iters)


def measure_copy_table(
    total_bytes: Sequence[int] = TOTAL_BYTES, iters: int = 5
) -> List[Tuple[float, float]]:
    """Contiguous device copy time over sizes (read + write of ``n``
    bytes — the staging floor every pack strategy competes with)."""
    rows = []
    for total in total_bytes:
        x = jnp.zeros((total,), jnp.uint8)
        jfn = jax.jit(lambda a: a + jnp.uint8(1))  # forced read+write
        rows.append((math.log2(total), time_fn(jfn, x, iters=iters)))
    return rows


def measure_compress_table(
    strategies=None,
    total_bytes: Sequence[int] = TOTAL_BYTES,
    iters: int = 5,
) -> Dict[str, List[Tuple[float, float, float, float]]]:
    """Compress / decompress throughput sweep per wire compressor
    (STORE_FORMAT 6): rows ``(log2_total, compress_sec, decompress_sec,
    achieved_ratio_sample)``.

    Times each compressor's ``encode_wire`` (packed member bytes ->
    wire) and ``decode_wire`` (wire -> member bytes) transforms in
    isolation — the *extra* cost a compressed wire adds on top of the
    base pack/unpack, which is exactly the term
    :meth:`~repro.comm.perfmodel.PerfModel.measured_compress`
    interpolates for ``model_pack`` / ``model_unpack``.  The sweep
    payload is zero-heavy (one nonzero byte per 256) so the RLE
    encoder's run machinery is exercised on its intended regime.

    The fourth column is an *informational* achieved-ratio sample for
    that payload: bytes the format would actually move (the probed
    stream length for varlen-capable formats, the capacity wire
    otherwise) per member byte.  Per-payload ratios always come from a
    live calibration probe of the actual payload
    (:meth:`~repro.comm.api.Strategy.probe_stream_bytes`), never from
    this table — the column only documents what the sweep saw.

    Default strategies: the registered wire compressors
    (``RLE_WIRE``, ``INT8_WIRE``).
    """
    from repro.comm.compress import INT8_WIRE, RLE_WIRE

    strats = (
        (RLE_WIRE, INT8_WIRE)
        if strategies is None
        else tuple(strategies)
    )
    reg = TypeRegistry()
    table: Dict[str, List[Tuple[float, float, float, float]]] = {}
    for s in strats:
        rows: List[Tuple[float, float, float, float]] = []
        for total in total_bytes:
            n = max(total - total % 4, 4)  # int8 views member bytes as f32
            member = np.zeros((n,), np.uint8)
            member[::256] = 1  # zero-heavy: short runs every 256 B
            buf = jnp.asarray(member)
            enc = jax.jit(s.encode_wire)
            wire = jax.block_until_ready(enc(buf))
            csec = time_fn(enc, buf, iters=iters)
            dec = jax.jit(lambda w, _n=n, _s=s: _s.decode_wire(w, _n))
            dsec = time_fn(dec, wire, iters=iters)
            ct = reg.commit(Vector(1, n, n, BYTE))  # contiguous: pack = id
            moved = min(s.probe_stream_bytes(ct, 1, buf), wire.shape[0])
            rows.append(
                (math.log2(n), csec, dsec, moved / float(n))
            )
        table[s.name] = rows
    return table


def measure_stencil_table(
    radii_set: Sequence[Tuple[int, int, int]] = STENCIL_RADII,
    total_bytes: Sequence[int] = TOTAL_BYTES,
    iters: int = 5,
) -> List[Tuple[float, float, float]]:
    """One weighted box-stencil application over (neighbor count x
    window bytes): rows ``(log2_neighbors, log2_window_bytes, sec)``.

    Times :func:`repro.kernels.ops.stencil_window_update` — the exact
    primitive every deep-halo application runs — on a float32 cube whose
    window holds ~``total`` bytes, for each op shape in ``radii_set``.
    ``PerfModel.price_program`` interpolates this table to price the
    redundant ghost-shell compute a fused program buys, instead of
    approximating a sweep with ``n_neighbors + 2`` contiguous-copy
    touches.
    """
    import itertools as _it

    from repro.kernels.ops import stencil_window_update

    rows: List[Tuple[float, float, float]] = []
    for radii in radii_set:
        rz, ry, rx = radii
        offsets = tuple(
            d
            for d in _it.product(
                range(-rz, rz + 1), range(-ry, ry + 1), range(-rx, rx + 1)
            )
            if d != (0, 0, 0)
        )
        for total in total_bytes:
            m = max(int(round((total / 4) ** (1.0 / 3.0))), 1)
            shape = (m, m, m)
            arr = jnp.zeros(
                tuple(s + 2 * r for s, r in zip(shape, radii)), jnp.float32
            )
            jfn = jax.jit(
                lambda a, _o=offsets, _r=radii, _s=shape: stencil_window_update(
                    a, _o, 0.4, _r, _s
                )
            )
            sec = time_fn(jfn, arr, iters=iters)
            rows.append(
                (math.log2(len(offsets)), math.log2(4 * m ** 3), sec)
            )
    return rows


def measure_wire_table(
    total_bytes: Sequence[int] = TOTAL_BYTES,
    iters: int = 5,
    axis_name: str = "wire",
) -> List[Tuple[float, float]]:
    """One-hop collective time over message sizes: a ``ppermute`` ring
    across every visible device (a 1-device mesh self-permutes, which
    still prices collective dispatch).  Rows are (log2_bytes, sec)."""
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(np.array(devs), (axis_name,))
    perm = [(i, (i + 1) % n) for i in range(n)]
    rows = []
    for total in total_bytes:
        def body(x):
            return jax.lax.ppermute(x, axis_name, perm)

        fn = jax.jit(
            shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                      check_vma=False)
        )
        x = jnp.zeros((total,), jnp.uint8)
        rows.append((math.log2(total), time_fn(fn, x, iters=iters)))
    return rows


def measure_wire_tables(
    axes: Optional[Dict[str, int]] = None,
    total_bytes: Sequence[int] = TOTAL_BYTES,
    iters: int = 5,
) -> Dict[str, List[Tuple[float, float]]]:
    """One-hop collective sweep per mesh axis.

    ``axes`` maps axis name -> size (ordered; the product must not
    exceed the visible device count — the first ``prod(sizes)`` devices
    are folded into the mesh).  Each axis is measured with a ``ppermute``
    ring *along that axis only*, inside a shard_map over the full mesh,
    so the timing reflects that axis's links.  Default: one flat
    ``wire`` axis over every device (the legacy single-table sweep).
    """
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    devs = jax.devices()
    if axes is None:
        axes = {"wire": len(devs)}
    names = tuple(axes)
    shape = tuple(axes[n] for n in names)
    ndev = int(np.prod(shape))
    if ndev > len(devs):
        raise ValueError(
            f"mesh {dict(axes)} needs {ndev} devices, have {len(devs)}"
        )
    mesh = Mesh(np.array(devs[:ndev]).reshape(shape), names)
    tables: Dict[str, List[Tuple[float, float]]] = {}
    for ai, name in enumerate(names):
        n = shape[ai]
        perm = [(i, (i + 1) % n) for i in range(n)]
        rows = []
        for total in total_bytes:
            def body(x, _name=name, _perm=perm):
                return jax.lax.ppermute(x, _name, _perm)

            fn = jax.jit(
                shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                          check_vma=False)
            )
            x = jnp.zeros((total,), jnp.uint8)
            rows.append((math.log2(total), time_fn(fn, x, iters=iters)))
        tables[name] = rows
    return tables


def measure_link_class_tables(
    topology,
    total_bytes: Sequence[int] = TOTAL_BYTES,
    iters: int = 5,
    axis_name: str = "wire",
) -> Dict[str, List[Tuple[float, float]]]:
    """Per-LINK-CLASS one-hop collective sweep (STORE_FORMAT 5).

    ``topology`` is a :class:`repro.comm.topology.Topology` whose rank
    count must not exceed the visible device count; rank ``r`` runs on
    device ``r``.  Two permutations isolate the two tiers of the
    hierarchy:

    * ``intra`` — a ring within each node's rank block (every edge
      stays on one node, so the timing is pure fast-tier);
    * ``inter`` — rank ``j`` of node ``i`` sends to rank ``j`` of node
      ``i + 1`` (mod nodes): every edge crosses nodes, and the
      bulk-synchronous collective completes at the slow tier.

    Rows are (log2_bytes, sec) per class; a single-node topology yields
    ``intra`` only.  On a single-host container both permutations ride
    the same physical links — the sweep is then a smoke-path (the two
    tables come out nearly equal), while a real multi-node mesh prices
    its DCN tier honestly.
    """
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    devs = jax.devices()
    n = topology.nranks
    if n > len(devs):
        raise ValueError(
            f"topology has {n} ranks, only {len(devs)} devices visible"
        )
    nodes = topology.nodes
    by_node: Dict[int, List[int]] = {}
    for r, nd in enumerate(nodes):
        by_node.setdefault(nd, []).append(r)

    # intra: ring within each node block (self-permute for 1-rank nodes)
    intra_perm: List[Tuple[int, int]] = []
    for members in by_node.values():
        k = len(members)
        intra_perm.extend(
            (members[i], members[(i + 1) % k]) for i in range(k)
        )
    perms = {"intra": intra_perm}
    node_ids = sorted(by_node)
    if len(node_ids) > 1:
        # inter: j-th rank of node i -> j-th rank of node i+1; ragged
        # node sizes wrap j modulo the destination block
        inter_perm: List[Tuple[int, int]] = []
        for i, nd in enumerate(node_ids):
            nxt = by_node[node_ids[(i + 1) % len(node_ids)]]
            for j, r in enumerate(by_node[nd]):
                inter_perm.append((r, nxt[j % len(nxt)]))
        if sorted(d for _, d in inter_perm) == list(range(n)):
            perms["inter"] = inter_perm

    mesh = Mesh(np.array(devs[:n]), (axis_name,))
    tables: Dict[str, List[Tuple[float, float]]] = {}
    for cls, perm in perms.items():
        rows = []
        for total in total_bytes:
            def body(x, _perm=tuple(perm)):
                return jax.lax.ppermute(x, axis_name, list(_perm))

            fn = jax.jit(
                shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                          check_vma=False)
            )
            x = jnp.zeros((total,), jnp.uint8)
            rows.append((math.log2(total), time_fn(fn, x, iters=iters)))
        tables[cls] = rows
    return tables


def fit_latency_bandwidth(
    rows: Sequence[Tuple[float, float]]
) -> Tuple[Optional[float], Optional[float]]:
    """Least-squares fit of t(n) = latency + n / bandwidth over
    (log2_bytes, sec) rows.  Either term is None when the sweep is too
    small or noisy to resolve it (a non-positive intercept or slope) —
    consumers treat None as "no fit" and fall back to analytic
    constants; a clamped 0.0 would instead price extra hops as free."""
    if len(rows) < 2:
        return None, None
    nbytes = np.asarray([2.0 ** r[0] for r in rows])
    secs = np.asarray([r[1] for r in rows])
    design = np.stack([np.ones_like(nbytes), nbytes], axis=1)
    (lat, inv_bw), *_ = np.linalg.lstsq(design, secs, rcond=None)
    return (
        float(lat) if lat > 0 else None,
        float(1.0 / inv_bw) if inv_bw > 0 else None,
    )


def calibrate_params(
    name: Optional[str] = None,
    reduced: bool = False,
    strategies=None,
    iters: Optional[int] = None,
    mesh_axes: Optional[Dict[str, int]] = None,
    topology=None,
) -> SystemParams:
    """Full-term calibration: pack + unpack + wire + contiguous copy +
    compress/decompress + stencil application.

    ``mesh_axes`` (axis name -> size, e.g. ``{"ici": 4, "dcn": 2}``)
    sweeps the wire term once per mesh axis and stores one table + fit
    per axis (``wire_tables`` / ``wire_fits``) so ``t_link`` can price
    multi-axis meshes honestly; the flat full-device ring remains the
    axis-agnostic ``wire_table`` fallback either way.

    ``topology`` (a :class:`repro.comm.topology.Topology`) additionally
    runs the per-link-class sweep (:func:`measure_link_class_tables`)
    and stores its tables + fits (``link_tables`` / ``link_fits``,
    STORE_FORMAT 5) so tier-aware pricing — and the simulated-scale mode
    built on it — reads measured numbers for both tiers.

    Returns a :class:`SystemParams` whose measured tables drive every
    term of the model's T = T_pack + T_link + T_unpack; the analytic
    constants remain as fallbacks for uncovered strategies.
    """
    blocks = REDUCED_BLOCK_BYTES if reduced else BLOCK_BYTES
    totals = REDUCED_TOTAL_BYTES if reduced else TOTAL_BYTES
    radii_set = REDUCED_STENCIL_RADII if reduced else STENCIL_RADII
    it = iters if iters is not None else (2 if reduced else 5)

    pack = measure_pack_table(strategies, blocks, totals, iters=it)
    unpack = measure_unpack_table(strategies, blocks, totals, iters=it)
    copy = measure_copy_table(totals, iters=it)
    compress = measure_compress_table(total_bytes=totals, iters=it)
    stencil = measure_stencil_table(radii_set, totals, iters=it)
    wire = measure_wire_table(totals, iters=it)
    wire_lat, wire_bw = fit_latency_bandwidth(wire)
    wire_tables = wire_fits = None
    if mesh_axes is not None:
        wire_tables = measure_wire_tables(mesh_axes, totals, iters=it)
        wire_fits = {
            ax: fit_latency_bandwidth(rows) for ax, rows in wire_tables.items()
        }
    link_tables = link_fits = None
    if topology is not None:
        link_tables = measure_link_class_tables(topology, totals, iters=it)
        link_fits = {
            cls: fit_latency_bandwidth(rows)
            for cls, rows in link_tables.items()
        }

    backend = jax.default_backend()
    base = TPU_V5E if backend == "tpu" else dataclasses.replace(
        TPU_V5E, name=f"{backend}_measured"
    )
    # the largest contiguous copy moves 2*total bytes (read + write):
    # use it as the measured memory-bandwidth fallback term
    hbm_bw = base.hbm_bw
    if copy and copy[-1][1] > 0:
        hbm_bw = 2.0 * (2.0 ** copy[-1][0]) / copy[-1][1]
    return dataclasses.replace(
        base,
        name=name or f"{backend}_calibrated",
        hbm_bw=hbm_bw,
        pack_table={k: tuple(v) for k, v in pack.items() if v},
        unpack_table={k: tuple(v) for k, v in unpack.items() if v},
        compress_table={k: tuple(v) for k, v in compress.items() if v},
        wire_table=tuple(wire),
        copy_table=tuple(copy),
        stencil_table=tuple(stencil),
        wire_tables=(
            {k: tuple(v) for k, v in wire_tables.items()} if wire_tables else None
        ),
        wire_fits=wire_fits,
        link_tables=(
            {k: tuple(v) for k, v in link_tables.items()} if link_tables else None
        ),
        link_fits=link_fits,
        wire_latency=wire_lat,
        wire_bw=wire_bw,
        ici_bw=wire_bw if wire_bw else base.ici_bw,
        ici_latency=wire_lat if wire_lat else base.ici_latency,
    )
