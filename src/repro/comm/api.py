"""The Communicator API: pluggable datatype strategies, request-based
nonblocking transfers, and fused neighborhood collectives.

This module is the *single* home of every strategy and mode name in the
system.  TEMPI's central claim is that an interposed layer can pick the
best datatype-handling implementation per call site; the seam that makes
that claim extensible is a registry of :class:`Strategy` plugins rather
than string comparisons scattered through the runtime:

* a :class:`Strategy` bundles the §5 cost model terms (``model_pack`` /
  ``model_unpack`` / ``wire_bytes`` -> :meth:`Strategy.plan`) with the
  execution paths (``pack`` / ``unpack`` / ``unpack_wire`` and the
  per-repetition ``pack_leaf`` / ``unpack_leaf`` kernels used by
  ``repro.kernels.ops``);
* a :class:`StrategyRegistry` holds the installed strategies; the
  :class:`~repro.comm.perfmodel.PerfModel` selects among *whatever is
  registered* — the paper's "one-shot" analogue (:class:`Bounding`) is
  an ordinary plugin, not a special case hardwired in ``sendrecv``;
* a :class:`Communicator` binds a mesh axis + :class:`SystemParams` and
  exposes MPI-shaped entry points: ``commit``, ``pack``/``unpack``,
  request-based ``isend``/``irecv`` (the wire op is issued eagerly so
  XLA can overlap independent exchanges; :meth:`Request.wait`
  materializes the unpack), and a fused
  :meth:`Communicator.neighbor_alltoallv` — the paper's actual
  ``MPI_Alltoallv`` halo transport — that packs every region at its
  **exact** wire extent into one flat buffer described by a
  :class:`~repro.comm.wireplan.WirePlan` and issues the cheapest wire
  schedule that can carry that ragged layout (a native ragged
  collective, a byte-exact uniform ``all_to_all``, or one ``ppermute``
  per delta class — see ``repro.comm.wireplan`` for the ladder).  The
  old padded-class layout is gone: the plan's ``wire_bytes`` is the sum
  of per-peer packed extents, and that same count is what the
  :class:`~repro.comm.perfmodel.PerfModel` prices and the
  ``DecisionCache`` records.

``repro.comm.interposer.Interposer`` remains as a thin deprecated shim
over :class:`Communicator` (mode strings map to :class:`Policy` objects
via :func:`policy_for_mode`).
"""

from __future__ import annotations

import math
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.commit import CommittedType, TypeRegistry, WireSegment
from repro.core.datatypes import Datatype
from repro.core.strided_block import StridedBlock
from repro.kernels import ops
from repro.kernels import ref as refk
from repro.kernels.geometry import (
    VMEM_BUDGET_BYTES,
    PackGeometry,
    plan_geometry,
)
from repro.kernels.pack import (
    pack_compress_ragged,
    pack_dma,
    pack_ragged,
    pack_rows,
)
from repro.kernels.unpack import (
    decode_unpack_ragged,
    unpack_dma,
    unpack_ragged,
    unpack_rows,
)
from repro.obs.trace import scope
from repro.comm.perfmodel import (
    PerfModel,
    StrategyEstimate,
    SystemParams,
    TPU_V5E,
)
from repro.comm.wireplan import (
    WireGroup,
    WirePlan,
    has_ragged_all_to_all,
    plan_wire,
)

__all__ = [
    "Strategy",
    "StrategyRegistry",
    "default_registry",
    "register_strategy",
    "resolve_strategy",
    "static_choice",
    "Policy",
    "ModelPolicy",
    "BaselinePolicy",
    "FixedPolicy",
    "policy_for_mode",
    "MODES",
    "Request",
    "SendRequest",
    "ClassRequest",
    "NeighborRequest",
    "Communicator",
    "as_communicator",
    "WirePlan",
    "WireGroup",
    "plan_neighbor_alltoallv",
    "DEFAULT_SCHEDULE_POLICY",
]

StrategyLike = Union[str, "Strategy", None]

#: baseline per-block copy emulation explodes HLO size past this many
#: blocks; beyond it the baseline degrades to the gather path (still a
#: fair stand-in: the real baselines issue that many cudaMemcpyAsyncs)
BASELINE_BLOCK_CAP = 1024


# ===========================================================================
# Strategy protocol
# ===========================================================================

class Strategy:
    """One way to move a committed datatype: cost model + execution.

    Subclass and :func:`register_strategy` (or register on a private
    :class:`StrategyRegistry`) to add a transfer strategy; the
    performance model then selects it whenever it wins.  Override points:

    ``applicable``    can this strategy handle the type at all?
    ``model_pack`` /  the §5 cost terms (seconds); ``plan`` assembles the
    ``model_unpack``  full T = T_pack + T_link + T_unpack estimate
    ``wire_bytes``    bytes this strategy puts on the wire
    ``pack``          produce the wire payload from the user buffer
    ``unpack``        scatter *packed member bytes* into the buffer
    ``unpack_wire``   consume the wire payload (differs from ``unpack``
                      only when the wire format isn't the packed bytes,
                      e.g. :class:`Bounding`'s contiguous window)
    ``pack_leaf`` /   per-repetition 2D/3D kernel dispatch used by
    ``unpack_leaf``   ``repro.kernels.ops`` once geometry is planned
    """

    name: str = "abstract"
    #: only meaningful when bytes cross the wire (no local pack/unpack)
    wire_only: bool = False
    #: participates in automatic PerfModel selection
    selectable: bool = True
    #: the wire format is length-aware: the live payload is a prefix of
    #: the capacity wire, truncatable at :meth:`probe_stream_bytes` —
    #: the "varlen" wire schedule only forms over such strategies
    supports_varlen: bool = False
    #: calibration sweep cap on block count (None = unbounded)
    calibration_cap: Optional[int] = None
    #: ``pack_leaf`` / ``unpack_leaf`` run the Pallas kernels on a
    #: row-group geometry, so they also take a buffer flat at its own
    #: element width under a geometry narrowed to that width (and
    #: ``unpack_leaf`` returns it so); see ``repro.kernels.ops``
    leaf_kernels: bool = False

    # -- applicability ----------------------------------------------------
    def applicable(self, ct: CommittedType) -> bool:
        return True

    # -- §5 cost model ----------------------------------------------------
    def model_pack(self, model: PerfModel, ct: CommittedType, incount: int) -> float:
        raise NotImplementedError

    def model_unpack(self, model: PerfModel, ct: CommittedType, incount: int) -> float:
        sb = ct.block
        if sb is not None and self._table_covers(sb, incount):
            m = model.measured_unpack(self.name, sb.counts[0], ct.size * incount)
            if m is not None:
                return m
        # no measured unpack table: strided writes are slower than pack
        # (paper §6.3 observes the same pack/unpack asymmetry)
        return 1.5 * self.model_pack(model, ct, incount)

    def _table_covers(self, sb: StridedBlock, incount: int) -> bool:
        """Whether this strategy's measured tables can legitimately
        answer for an object of this many blocks.  The calibration sweep
        never measures past ``calibration_cap``, so interpolating there
        would extrapolate a small-object time onto an object the cap
        exists to exclude (e.g. pricing 500k unrolled per-block copies
        at a 512-block measurement) — fall back to the analytic model."""
        cap = self.calibration_cap
        return cap is None or sb.num_blocks * incount <= cap

    def wire_bytes(self, ct: CommittedType, incount: int = 1) -> int:
        return ct.packed_extent(incount)

    def probe_stream_bytes(
        self, ct: CommittedType, incount: int, buf: jax.Array
    ) -> int:
        """Effective wire bytes for a *concrete* payload sample.  The
        default wire format is not length-aware, so the stream length
        is the capacity; ``supports_varlen`` strategies override this
        with an exact probe of the encoded stream."""
        return self.wire_bytes(ct, incount)

    def wire_segment(
        self, ct: CommittedType, incount: int = 1, offset: int = 0
    ) -> WireSegment:
        """The exact wire-segment descriptor this strategy's payload for
        ``ct`` occupies — the unit every :class:`WirePlan` is built
        from.  Strategies whose wire format differs from the packed
        member bytes (bounding windows, compressed payloads) inherit
        this and only override :meth:`wire_bytes`."""
        return ct.wire_segment(
            offset=offset, incount=incount, nbytes=self.wire_bytes(ct, incount)
        )

    def plan(
        self, model: PerfModel, ct: CommittedType, incount: int, hops: int = 1
    ) -> StrategyEstimate:
        """Full strategy estimate (paper Eqs. 1-3 analogue), priced on
        the exact wire-segment extent."""
        seg = self.wire_segment(ct, incount)
        return StrategyEstimate(
            self.name,
            self.model_pack(model, ct, incount),
            model.t_link(seg.nbytes, hops),
            self.model_unpack(model, ct, incount),
            wire_bytes=seg.nbytes,
        )

    # -- execution --------------------------------------------------------
    def pack(
        self,
        buf: jax.Array,
        ct: CommittedType,
        incount: int = 1,
        interpret: Optional[bool] = None,
    ) -> jax.Array:
        return ops.pack(buf, ct, incount=incount, strategy=self, interpret=interpret)

    def unpack(
        self,
        buf: jax.Array,
        packed: jax.Array,
        ct: CommittedType,
        incount: int = 1,
        interpret: Optional[bool] = None,
    ) -> jax.Array:
        return ops.unpack(
            buf, packed, ct, incount=incount, strategy=self, interpret=interpret
        )

    @scope("unpack")
    def unpack_wire(
        self,
        comm: "Communicator",
        dst: jax.Array,
        wire: jax.Array,
        recv_ct: CommittedType,
        send_ct: Optional[CommittedType] = None,
        incount: int = 1,
    ) -> jax.Array:
        """Consume received wire bytes.  Default: the wire carries packed
        member bytes; scatter them with the strategy the communicator
        selects for the receive type."""
        u = comm.select(recv_ct, incount, wire=False)
        return u.unpack(dst, wire, recv_ct, incount)

    # -- per-repetition kernel dispatch (called from repro.kernels.ops) ---
    def pack_leaf(
        self,
        b: jax.Array,
        sb: StridedBlock,
        geom: Optional[PackGeometry],
        interpret: bool,
    ) -> jax.Array:
        raise TypeError(f"strategy {self.name!r} has no local pack kernel")

    def unpack_leaf(
        self,
        b: jax.Array,
        packed: jax.Array,
        sb: StridedBlock,
        geom: Optional[PackGeometry],
        interpret: bool,
    ) -> jax.Array:
        raise TypeError(f"strategy {self.name!r} has no local unpack kernel")

    def pack_planes(
        self, view: jax.Array, geom: PackGeometry, interpret: bool
    ) -> Optional[jax.Array]:
        """Pack a 3D plane-block geometry straight from the buffer's own
        (planes, view_rows, pitch) word view (see ``repro.kernels.ops``);
        None: this strategy takes the byte path instead."""
        return None

    def unpack_planes(
        self, view: jax.Array, packed: jax.Array, geom: PackGeometry,
        interpret: bool,
    ) -> Optional[jax.Array]:
        """Inverse of :meth:`pack_planes`: the updated word view, given
        the (planes, rows, lanes) packed words; None: byte path."""
        return None

    # ---------------------------------------------------------------------
    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"<Strategy {self.name}>"


def _analytic_prologue(model, strategy, ct, incount):
    """Shared cost-model prologue: generic-type fallback and measured
    pack-table lookup (refused past the strategy's calibration cap —
    see :meth:`Strategy._table_covers`).  Returns (params, size, block,
    measured|None)."""
    p = model.params
    size = ct.size * incount
    sb = ct.block
    if sb is None:
        return p, size, None, p.kernel_launch + 2 * size / p.hbm_bw
    if not strategy._table_covers(sb, incount):
        return p, size, sb, None
    return p, size, sb, model.measured(strategy.name, sb.counts[0], size)


class Rows(Strategy):
    """Pitched row kernel, then one contiguous collective ≙ the paper's
    "device" method: Pallas double-buffers full-pitch row groups."""

    name = "rows"
    leaf_kernels = True

    def applicable(self, ct: CommittedType) -> bool:
        return ct.block is not None and plan_geometry(ct.block) is not None

    def model_pack(self, model, ct, incount):
        p, size, sb, m = _analytic_prologue(model, self, ct, incount)
        if sb is None or m is not None:
            return m
        geom = plan_geometry(sb)
        over = geom.overfetch if geom else 1.0
        touched = size * over + size  # pitched read + contiguous write
        return p.kernel_launch + touched / p.hbm_bw

    def pack_leaf(self, b, sb, geom, interpret):
        if geom is None:
            return refk.pack_ref(b, sb)
        return ops.run_pack_kernel(b, geom, pack_rows, interpret)

    def unpack_leaf(self, b, packed, sb, geom, interpret):
        if geom is None:
            return refk.unpack_ref(b, packed, sb)
        if geom.planes > 1 and geom.plane_rows < geom.rows:
            # interleaved planes: row read-modify-write would lose
            # updates; use the windowed DMA kernel instead
            kernel = _dma_unpack_kernel
        else:
            kernel = unpack_rows
        return ops.run_unpack_kernel(b, packed, geom, kernel, interpret)

    def pack_planes(self, view, geom, interpret):
        return ops.packed_bytes(pack_rows(view, geom, interpret=interpret))

    def unpack_planes(self, view, packed, geom, interpret):
        return unpack_rows(view, packed, geom, interpret=interpret)


def _dma_pack_kernel(src2d, geom, interpret=False):
    return pack_dma(src2d, geom, VMEM_BUDGET_BYTES, interpret=interpret)


def _dma_unpack_kernel(dst2d, pk3, geom, interpret=False):
    return unpack_dma(dst2d, pk3, geom, VMEM_BUDGET_BYTES, interpret)


class Dma(Strategy):
    """DMA kernel ≙ the paper's "staged" method: one manually issued DMA
    of whole pitch rows per 8-aligned row-chunk (TPU DMAs move whole
    tiles).  The analytic price below still assumes no pitch over-fetch;
    a calibrated table replaces it with what the chip measures."""

    name = "dma"
    leaf_kernels = True

    def applicable(self, ct: CommittedType) -> bool:
        if ct.block is None:
            return False
        geom = plan_geometry(ct.block)
        return geom is not None and not geom.plane_block

    def model_pack(self, model, ct, incount):
        p, size, sb, m = _analytic_prologue(model, self, ct, incount)
        if sb is None or m is not None:
            return m
        nblocks = sb.num_blocks * incount
        chunks = max(nblocks // 128, 1)  # descriptors per ~128-row chunk
        return p.kernel_launch + chunks * p.dma_setup + 2 * size / p.hbm_bw

    def pack_leaf(self, b, sb, geom, interpret):
        if geom is None or geom.plane_block:
            return refk.pack_ref(b, sb)
        return ops.run_pack_kernel(b, geom, _dma_pack_kernel, interpret)

    def unpack_leaf(self, b, packed, sb, geom, interpret):
        if geom is None or geom.plane_block:
            return refk.unpack_ref(b, packed, sb)
        return ops.run_unpack_kernel(b, packed, geom, _dma_unpack_kernel, interpret)


class XlaBlocks(Strategy):
    """Per-block XLA copies into a contiguous buffer — the naive
    CUDA-aware-MPI baseline every implementation shares."""

    name = "xla"
    calibration_cap = 512  # unrolled per-block HLO blows up past this

    def model_pack(self, model, ct, incount):
        p, size, sb, m = _analytic_prologue(model, self, ct, incount)
        if sb is None or m is not None:
            return m
        nblocks = sb.num_blocks * incount
        return nblocks * p.xla_copy_overhead + 2 * size / p.hbm_bw

    def pack_leaf(self, b, sb, geom, interpret):
        if geom is None:
            return refk.pack_ref(b, sb)
        return refk.pack_xla_blocks(b, sb)

    def unpack_leaf(self, b, packed, sb, geom, interpret):
        if geom is None:
            return refk.unpack_ref(b, packed, sb)
        return refk.unpack_xla_blocks(b, packed, sb)

    def pack_planes(self, view, geom, interpret):
        z0, y0 = divmod(geom.q, geom.view_rows)
        return ops.packed_bytes(jnp.stack([
            view[z0 + p, y0 + i, geom.r:geom.r + geom.lanes]
            for p in range(geom.planes) for i in range(geom.rows)
        ]))

    def unpack_planes(self, view, packed, geom, interpret):
        z0, y0 = divmod(geom.q, geom.view_rows)
        for p in range(geom.planes):
            for i in range(geom.rows):
                view = view.at[z0 + p, y0 + i, geom.r:geom.r + geom.lanes].set(
                    packed[p, i]
                )
        return view


@scope("wire")
def _ragged_exchange(
    wire: jax.Array, plan: WirePlan, sizes: Sequence[int], axis: str
) -> List[jax.Array]:
    """One native ``ragged_all_to_all`` moving ``sizes[g]`` bytes of each
    delta class ``g``; returns each class's received bytes.

    Per-peer metadata semantics: input_offsets/send_sizes and
    output_offsets are indexed by DESTINATION peer — the chunk this rank
    sends to peer d is operand[in_off[d]:+in_sz[d]] and lands at
    out_off[d] in d's OUTPUT buffer.  A group travels under the same
    global offset on both sides (the flat layout is rank-uniform), so
    out_off mirrors in_off.  recv_sizes is indexed by SOURCE peer: the
    bytes arriving from s are the group whose recv_rows entry names s.

    The operand goes over as rows of the widest power-of-two byte count
    (up to 512 B) that divides every offset, size and the buffer length:
    the TPU pads each operand row to a whole tile, so a flat byte operand
    costs ~512x its size in scratch.  The bytes sent stay exact.
    """
    ngroups = len(plan.groups)
    in_off = np.zeros((plan.nranks, plan.nranks), np.int64)
    in_sz = np.zeros_like(in_off)
    out_off = np.zeros_like(in_off)
    recv_sz = np.zeros_like(in_off)
    for r in range(plan.nranks):
        for d, g in enumerate(plan.send_rows[r]):
            if g < ngroups:
                in_off[r, d] = plan.group_offsets[g]
                in_sz[r, d] = sizes[g]
                out_off[r, d] = plan.group_offsets[g]
        for g, s in enumerate(plan.recv_rows[r]):
            recv_sz[r, s] = sizes[g]
    unit = math.gcd(
        int(wire.shape[0]),
        *(int(v) for t in (in_off, in_sz, recv_sz) for v in t.flat),
    )
    row = math.gcd(unit & -unit, 512) if unit else 1
    if row >= 4:
        operand = ops.as_words(wire, 4).reshape(-1, row // 4)
    else:
        operand = wire.reshape(-1, row)
    me = lax.axis_index(axis)
    got = lax.ragged_all_to_all(
        operand,
        jnp.zeros_like(operand),
        *(jnp.asarray(t // row, np.int32)[me]
          for t in (in_off, in_sz, out_off, recv_sz)),
        axis_name=axis,
    )
    got = ops.packed_bytes(got) if row >= 4 else got.reshape(-1)
    return [
        lax.dynamic_slice(got, (goff,), (sz,))
        for goff, sz in zip(plan.group_offsets, sizes)
    ]


class Gather(Strategy):
    """Oracle gather/scatter fallback (offset-list walk).  Correct for
    every type; never auto-selected."""

    name = "ref"
    selectable = False

    def model_pack(self, model, ct, incount):
        # modeled like the per-block baseline: a gather touches every
        # block individually
        p, size, sb, m = _analytic_prologue(model, self, ct, incount)
        if sb is None or m is not None:
            return m
        return sb.num_blocks * incount * p.xla_copy_overhead + 2 * size / p.hbm_bw

    def pack_leaf(self, b, sb, geom, interpret):
        return refk.pack_ref(b, sb)

    def unpack_leaf(self, b, packed, sb, geom, interpret):
        return refk.unpack_ref(b, packed, sb)


class Auto(Strategy):
    """Static geometry heuristic used when no calibrated model drives the
    choice: the pitched row kernel wins while its over-fetch stays
    moderate (automatic double-buffering); the strided-DMA kernel wins
    for small blocks at large pitches.  Not a modeled strategy — it
    defers to :func:`static_choice` per leaf."""

    name = "auto"
    leaf_kernels = True
    selectable = False

    def model_pack(self, model, ct, incount):
        geom = plan_geometry(ct.block) if ct.block is not None else None
        return static_choice(geom).model_pack(model, ct, incount)

    def pack_leaf(self, b, sb, geom, interpret):
        return static_choice(geom).pack_leaf(b, sb, geom, interpret)

    def unpack_leaf(self, b, packed, sb, geom, interpret):
        return static_choice(geom).unpack_leaf(b, packed, sb, geom, interpret)

    def pack_planes(self, view, geom, interpret):
        return static_choice(geom).pack_planes(view, geom, interpret)

    def unpack_planes(self, view, packed, geom, interpret):
        return static_choice(geom).unpack_planes(view, packed, geom, interpret)


class Bounding(Strategy):
    """The paper's "one-shot" analogue: ship the contiguous bounding
    window of the object with no sender-side pack at all; the receiver
    extracts the member bytes.  Wins when the object is dense in its
    extent — zero staging, pays over-transfer instead of pack cost."""

    name = "bounding"
    wire_only = True

    def applicable(self, ct: CommittedType) -> bool:
        return ct.block is not None

    def model_pack(self, model, ct, incount):
        return 0.0  # no pack at all

    def model_unpack(self, model, ct, incount):
        return 0.0  # extraction is priced in plan(), not here

    def wire_bytes(self, ct, incount=1):
        sb = ct.block
        if sb is None:
            return ct.extent * incount
        return sb.extent + (incount - 1) * ct.extent

    def plan(self, model, ct, incount, hops=1):
        sb = ct.block
        if sb is not None and sb.size == sb.extent:
            t_extract = 0.0  # fully dense: the wire bytes ARE the data
        else:
            # receiver must extract the member bytes from the bounding
            # window and splice them into the destination (two kernels)
            t_extract = ROWS.model_pack(model, ct, incount) + ROWS.model_unpack(
                model, ct, incount
            )
        nbytes = self.wire_bytes(ct, incount)
        return StrategyEstimate(
            self.name, 0.0, model.t_link(nbytes, hops), t_extract,
            wire_bytes=nbytes,
        )

    @scope("pack")
    def pack(self, buf, ct, incount=1, interpret=None):
        sb = ct.block
        if sb is None:
            raise ValueError(f"{self.name} needs a strided block")
        ext = self.wire_bytes(ct, incount)
        return lax.dynamic_slice(ops.byte_view(buf), (sb.start,), (ext,))

    @scope("unpack")
    def unpack_wire(self, comm, dst, wire, recv_ct, send_ct=None, incount=1):
        # extract member bytes from the received window: same geometry as
        # the send type, rebased to start 0
        send_ct = send_ct or recv_ct
        sb = send_ct.block
        rb = StridedBlock(0, sb.counts, sb.strides)
        if incount > 1:
            parts = [
                ops.pack_block(
                    lax.dynamic_slice(
                        wire, (r * send_ct.extent,), (sb.extent,)
                    ),
                    rb,
                )
                for r in range(incount)
            ]
            packed = jnp.concatenate(parts)
        else:
            packed = ops.pack_block(wire, rb)
        u = comm.select(recv_ct, incount, wire=False)
        return u.unpack(dst, packed, recv_ct, incount)

    def unpack(self, buf, packed, ct, incount=1, interpret=None):
        raise TypeError(
            f"{self.name} has no local unpack; use unpack_wire on the "
            "received window"
        )


# ===========================================================================
# registry
# ===========================================================================

class StrategyRegistry:
    """Installed strategies, by name.  The default registry carries the
    paper's menu; register plugins here (or on a copy, for isolated
    experiments) and the model immediately selects among them."""

    def __init__(self, strategies: Sequence[Strategy] = ()):
        self._by_name: Dict[str, Strategy] = {}
        self._version = 0  # bumped on mutation; invalidates model caches
        for s in strategies:
            self.register(s)

    @property
    def version(self) -> int:
        return self._version

    def register(self, strategy: Union[Strategy, type]) -> Strategy:
        if isinstance(strategy, type):
            strategy = strategy()
        if not strategy.name or strategy.name == Strategy.name:
            raise ValueError("strategy needs a distinct .name")
        if strategy.name in self._by_name:
            raise ValueError(f"strategy {strategy.name!r} already registered")
        self._by_name[strategy.name] = strategy
        self._version += 1
        return strategy

    def get(self, name: StrategyLike) -> Strategy:
        if isinstance(name, Strategy):
            return name
        if name is None:
            name = Auto.name
        s = self._by_name.get(name)
        if s is None:
            raise ValueError(
                f"unknown strategy {name!r}; registered: {self.names()}"
            )
        return s

    def names(self) -> Tuple[str, ...]:
        return tuple(self._by_name)

    def selectable(self) -> Tuple[Strategy, ...]:
        return tuple(s for s in self._by_name.values() if s.selectable)

    def measurable(self) -> Tuple[Strategy, ...]:
        """Strategies with a real pack kernel worth calibrating."""
        return tuple(
            s for s in self._by_name.values() if s.selectable and not s.wire_only
        )

    def copy(self) -> "StrategyRegistry":
        return StrategyRegistry(tuple(self._by_name.values()))

    def __iter__(self):
        return iter(self._by_name.values())

    def __contains__(self, name: object) -> bool:
        return name in self._by_name

    def __len__(self) -> int:
        return len(self._by_name)


ROWS = Rows()
DMA = Dma()
XLA = XlaBlocks()
REF = Gather()
AUTO = Auto()
BOUNDING = Bounding()

_DEFAULT_REGISTRY = StrategyRegistry((ROWS, DMA, XLA, REF, AUTO, BOUNDING))


def default_registry() -> StrategyRegistry:
    """The process-global strategy registry."""
    return _DEFAULT_REGISTRY


def register_strategy(strategy: Union[Strategy, type]) -> Strategy:
    """Install a strategy plugin into the default registry."""
    return _DEFAULT_REGISTRY.register(strategy)


def resolve_strategy(
    strategy: StrategyLike, registry: Optional[StrategyRegistry] = None
) -> Strategy:
    """Name -> Strategy (None resolves to the static-auto strategy)."""
    return (registry or _DEFAULT_REGISTRY).get(strategy)


def static_choice(geom: Optional[PackGeometry]) -> Strategy:
    """Geometry-only kernel choice used by :class:`Auto` (the calibrated
    model refines this crossover, as the paper's model picks one-shot vs
    device)."""
    if geom is None:
        return REF
    if geom.plane_block:
        return ROWS  # the DMA kernel needs 8-aligned row chunks
    return ROWS if geom.overfetch <= 4.0 else DMA


# ===========================================================================
# policies (strategy-selection behaviours; the old Interposer "modes")
# ===========================================================================

class Policy:
    """Decides the strategy per (committed type, incount, wire?) call."""

    def select(
        self, comm: "Communicator", ct: CommittedType, incount: int, wire: bool
    ) -> Strategy:
        raise NotImplementedError


class ModelPolicy(Policy):
    """Performance-model selection over the registered strategies (§5) —
    the paper's TEMPI behaviour."""

    def select(self, comm, ct, incount, wire):
        est = comm.model.select(
            ct, incount, allow_bounding=wire, registry=comm.strategies
        )
        return comm.strategies.get(est.strategy)


class BaselinePolicy(Policy):
    """Naive per-block copies (emulating the datatype handling every
    CUDA-aware MPI shares), degrading to the gather path past the HLO
    block cap."""

    def __init__(self, block_cap: int = BASELINE_BLOCK_CAP):
        self.block_cap = block_cap

    def select(self, comm, ct, incount, wire):
        if ct.block is not None and ct.block.num_blocks * incount > self.block_cap:
            return comm.strategies.get(REF.name)
        return comm.strategies.get(XLA.name)


class FixedPolicy(Policy):
    """Force one strategy for experiments.  Wire-only strategies (e.g.
    bounding) cannot serve local pack/unpack calls; those fall back to
    the static-auto heuristic so ``unpack``/``sendrecv`` keep working."""

    def __init__(self, strategy: StrategyLike):
        self.strategy = resolve_strategy(strategy)

    def select(self, comm, ct, incount, wire):
        s = comm.strategies.get(self.strategy)
        if s.wire_only and not wire:
            return comm.strategies.get(AUTO.name)
        return s


#: legacy Interposer mode names (kept for the shim + CLI flags)
MODES = ("baseline", "tempi", Rows.name, Dma.name, XlaBlocks.name, Gather.name)


def policy_for_mode(mode: str) -> Policy:
    """Map a legacy mode string to a Policy (ValueError on unknown)."""
    if mode == "baseline":
        return BaselinePolicy()
    if mode == "tempi":
        return ModelPolicy()
    if mode in MODES:
        return FixedPolicy(mode)
    raise ValueError(f"unknown mode {mode!r}; expected one of {MODES}")


# ===========================================================================
# requests (nonblocking semantics)
# ===========================================================================

_PENDING = object()


class Request:
    """Handle to a pending communication.  The wire transport is issued
    when the request is created (so XLA is free to overlap independent
    exchanges); :meth:`wait` materializes the receive-side unpack."""

    def __init__(self, thunk: Optional[Callable[[], jax.Array]] = None,
                 value: jax.Array = _PENDING):
        self._thunk = thunk
        self._value = value

    @property
    def completed(self) -> bool:
        return self._value is not _PENDING

    def wait(self) -> jax.Array:
        if self._value is _PENDING:
            self._value = self._thunk()
            self._thunk = None
        return self._value


class SendRequest(Request):
    """An issued wire transfer: holds the (traced) received payload plus
    the metadata ``irecv`` needs to unpack it.  ``segment`` is the exact
    :class:`~repro.core.commit.WireSegment` the payload occupied on the
    wire (what the communicator's byte accounting recorded)."""

    def __init__(self, wire: jax.Array, strategy: Strategy,
                 send_ct: CommittedType, incount: int,
                 segment: Optional[WireSegment] = None):
        super().__init__(value=wire)
        self.strategy = strategy
        self.send_ct = send_ct
        self.incount = incount
        self.segment = segment


class ClassRequest(Request):
    """One delta class of a fused neighborhood exchange: the class's
    received wire payload plus exactly the unpacks that consume it.
    Completable independently of its siblings — the recv regions of
    distinct transfers never overlap, so classes may be unpacked in any
    completion order and the buffer is bit-identical.

    ``transfers`` names the plan-level transfer indices riding in this
    class (for halo exchanges these map 1:1 onto ``DIRECTIONS``), which
    is what lets a region scheduler translate "this class landed" into
    "these rim regions are computable"."""

    def __init__(self, index: int, payload: jax.Array,
                 transfers: Sequence[int], nbytes: int,
                 unpack: Callable[[jax.Array, jax.Array], jax.Array]):
        super().__init__(value=payload)
        self.index = int(index)
        self.transfers = tuple(transfers)
        self.nbytes = int(nbytes)
        self._unpack = unpack
        #: set by :meth:`NeighborRequest.wait_any` once the class's
        #: unpacks have been applied to the exchange buffer
        self.applied = False

    def ready(self) -> bool:
        """Best-effort completion probe: True when the payload is known
        to be resident (``jax.Array.is_ready``).  Traced payloads have
        no runtime notion of readiness and report True, so a traced
        drain loop proceeds in deterministic plan order."""
        probe = getattr(self._value, "is_ready", None)
        if callable(probe):
            try:
                return bool(probe())
            except Exception:
                return True
        return True

    def unpack_into(self, buf: jax.Array) -> jax.Array:
        """Apply this class's unpacks to ``buf`` (returns the updated
        buffer).  Normally driven by :meth:`NeighborRequest.wait_any`."""
        self.applied = True
        return self._unpack(buf, self._value)


class NeighborRequest(Request):
    """The request :meth:`Communicator.ineighbor_alltoallv` returns:
    a fused exchange split into independently-completable per-class
    :class:`ClassRequest` handles.

    ``wait()`` keeps the historical monolithic contract — drain every
    class, return the fully-unpacked buffer.  Overlap-aware callers
    (the region-split stencil path) instead drive :meth:`wait_any` in a
    drain loop, reading :attr:`buffer` between drains: each drained
    class has written its recv regions, every other region of the
    buffer is untouched, so any consumer whose inputs are covered by
    the drained classes may run immediately."""

    def __init__(self, buf: jax.Array, classes: Sequence[ClassRequest],
                 plan: Optional[WirePlan] = None,
                 on_drain: Optional[Callable[["NeighborRequest",
                                              ClassRequest], None]] = None):
        super().__init__()
        self._buf = buf
        self.classes = tuple(classes)
        self.plan = plan
        #: class indices in the order they were drained
        self.drained: List[int] = []
        self._on_drain = on_drain
        if not self.classes:
            self._value = buf

    @property
    def buffer(self) -> jax.Array:
        """The exchange buffer with every *drained* class unpacked (and
        the send-side contents everywhere else)."""
        return self._buf

    @property
    def pending(self) -> Tuple[ClassRequest, ...]:
        return tuple(c for c in self.classes if not c.applied)

    def wait_any(self) -> ClassRequest:
        """Drain one class: prefer the first whose payload is already
        resident (out-of-order completion), fall back to plan order, and
        apply its unpacks to :attr:`buffer`.  Returns the drained class;
        raises ``ValueError`` once all classes are drained."""
        pend = [c for c in self.classes if not c.applied]
        if not pend:
            raise ValueError("wait_any() on a fully drained request")
        pick = next((c for c in pend if c.ready()), pend[0])
        self._buf = pick.unpack_into(self._buf)
        self.drained.append(pick.index)
        if self._on_drain is not None:
            self._on_drain(self, pick)
        if len(self.drained) == len(self.classes):
            self._value = self._buf
        return pick

    def wait(self) -> jax.Array:
        while self._value is _PENDING:
            self.wait_any()
        return self._value


# ===========================================================================
# fused neighborhood alltoallv planning (host-side, cached)
# ===========================================================================

#: how :meth:`Communicator.plan_neighbor` chooses a wire schedule when
#: the caller does not say: ``"model"`` prices grouped launches vs
#: uniform padding on the measured wire tables (ROADMAP: the flipped
#: default); ``"exact"`` restores the byte-exact ladder per call.
DEFAULT_SCHEDULE_POLICY = "model"


def plan_neighbor_alltoallv(
    sizes: Tuple[int, ...],
    perms: Tuple[Tuple[Tuple[int, int], ...], ...],
    fingerprints: Optional[Tuple[str, ...]] = None,
    uniform_waste_tolerance: float = 0.0,
) -> WirePlan:
    """Group ``len(sizes)`` transfers (one full permutation each) into
    an exact-byte :class:`WirePlan`.  Thin alias over
    :func:`repro.comm.wireplan.plan_wire` kept as the public planning
    entry point of this module."""
    return plan_wire(
        tuple(sizes),
        tuple(tuple(map(tuple, p)) for p in perms),
        fingerprints=fingerprints,
        uniform_waste_tolerance=uniform_waste_tolerance,
    )


# ===========================================================================
# the Communicator
# ===========================================================================

class Communicator:
    """Datatype-aware communication endpoint bound to a mesh axis.

    Parameters
    ----------
    axis_name: default mesh axis for the collective entry points (each
        accepts a per-call override).
    params: system parameter table for the performance model.
    registry: datatype commit cache (``MPI_Type_commit`` analogue).
    strategies: strategy registry; defaults to the process-global one.
    policy: strategy-selection behaviour; defaults to model selection.
    decisions: optional :class:`repro.measure.DecisionCache` — persists
        strategy selections (fingerprint-keyed) and records the audit
        log.
    telemetry: optional :class:`repro.fleet.ExchangeTelemetry` — the
        runtime half of the feedback loop.  Planning entry points
        register the model's predicted seconds per decision key
        (host-side, safe under tracing); the *blocking* entry points
        (:meth:`sendrecv`, :meth:`neighbor_alltoallv`) additionally
        observe wall time — but only when running eagerly: inside a
        ``jit``/``shard_map`` trace a timer would measure tracing, so
        tracer arguments skip the probe and jitted workloads time their
        compiled step from the launch layer instead.
    tracer: optional :class:`repro.obs.Tracer` — structured per-phase
        spans on the same paths the telemetry probe times, under the
        same guard: eager blocking entry points record ``exchange`` →
        ``pack``/``wire``/``unpack`` spans with ``block_until_ready``
        at each phase boundary (the decision signature and the model's
        per-phase predictions ride as span attributes); inside a jax
        trace nothing records, and fused compiled iterations are
        timed whole by the launch layer and split by the ``tempi.*``
        named scopes in a profiler trace (:func:`repro.obs.trace.scope`).
    topology: optional :class:`repro.comm.topology.Topology` — the
        rank -> node map of a two-level machine.  Wire plans pick up
        link-class annotations, pricing charges the slow tier per
        crossing class, the ``tiered`` coalesced schedule joins the
        candidate set, and every wire/program decision signature gains
        the topology fingerprint (``train.elastic.replan_on_remesh``
        re-prices when it changes).
    """

    def __init__(
        self,
        axis_name: Optional[str] = None,
        params: SystemParams = TPU_V5E,
        registry: Optional[TypeRegistry] = None,
        strategies: Optional[StrategyRegistry] = None,
        policy: Optional[Policy] = None,
        decisions=None,
        telemetry=None,
        tracer=None,
        topology=None,
    ):
        self.axis_name = axis_name
        self.registry = registry or TypeRegistry()
        self.strategies = strategies or default_registry()
        #: optional repro.comm.topology.Topology (rank -> node): wire
        #: plans get link-class annotations, the model prices each delta
        #: class by the slowest tier it crosses, and the ``tiered``
        #: (per-peer-node coalesced) schedule becomes a candidate
        self.model = PerfModel(
            params, decisions=decisions, axis=axis_name, topology=topology
        )
        self.policy = policy or ModelPolicy()
        self.telemetry = telemetry
        self.tracer = tracer
        self.wire_ops = 0  # collectives issued through this communicator
        self.wire_payload_bytes = 0  # exact bytes those collectives carried
        # per-delta-class wire accounting, keyed "<plan fp>/c<class>":
        # issue counts and exact bytes per class, plus the 1-based drain
        # position wait_any() last observed for the class — the counters
        # `python -m repro.fleet stats` renders region completion from
        self.wire_class_ops: Dict[str, int] = {}
        self.wire_class_bytes: Dict[str, int] = {}
        self.wire_class_drains: Dict[str, int] = {}
        # compressed-wire (varlen schedule) accounting: exchanges that
        # rode a length-aware transport, their capacity bytes vs the
        # stream bytes actually issued — the honest ratio stats()
        # publishes as the ``comm.compress.ratio`` gauge
        self.compress_exchanges = 0
        self.compress_capacity_bytes = 0
        self.compress_stream_bytes = 0

    def _tracing_spans(self, *operands) -> bool:
        """Whether the blocking entry points should record spans for
        this call: a tracer is attached, no operand is a jax tracer, and
        execution is eager (the tracer guard — same rule as telemetry)."""
        return (
            self.tracer is not None
            and self.tracer.active
            and not any(isinstance(b, jax.core.Tracer) for b in operands)
        )

    # ------------------------------------------------------------------
    def _axis(self, axis_name: Optional[str]) -> str:
        axis = axis_name or self.axis_name
        if axis is None:
            raise ValueError(
                "no axis_name: bind one at construction or pass it per call"
            )
        return axis

    # ------------------------------------------------------------------
    # commit (MPI_Type_commit)
    # ------------------------------------------------------------------
    def commit(self, dt: Datatype) -> CommittedType:
        return self.registry.commit(dt)

    # ------------------------------------------------------------------
    # strategy selection
    # ------------------------------------------------------------------
    def select(
        self, ct: CommittedType, incount: int = 1, wire: bool = True
    ) -> Strategy:
        """The strategy the active policy picks for this call site."""
        return self.policy.select(self, ct, incount, wire)

    # ------------------------------------------------------------------
    # MPI_Pack / MPI_Unpack (paper §6.2)
    # ------------------------------------------------------------------
    def pack(self, buf: jax.Array, ct: CommittedType, incount: int = 1) -> jax.Array:
        return self.select(ct, incount, wire=False).pack(buf, ct, incount)

    def unpack(
        self, buf: jax.Array, packed: jax.Array, ct: CommittedType, incount: int = 1
    ) -> jax.Array:
        return self.select(ct, incount, wire=False).unpack(buf, packed, ct, incount)

    # ------------------------------------------------------------------
    # point-to-point (requests; paper §6.3)
    # ------------------------------------------------------------------
    def isend(
        self,
        buf: jax.Array,
        ct: CommittedType,
        perm: Sequence[Tuple[int, int]],
        axis_name: Optional[str] = None,
        incount: int = 1,
    ) -> SendRequest:
        """Pack ``ct`` out of ``buf`` and issue the wire transport NOW;
        the returned request carries the (traced) received payload."""
        axis = self._axis(axis_name)
        s = self.select(ct, incount, wire=True)
        seg = s.wire_segment(ct, incount)
        if self.telemetry is not None:
            # price through the chosen strategy directly (no decision
            # recording — a baseline/fixed policy must not grow decision
            # rows just because telemetry is attached)
            est = s.plan(self.model, ct, incount)
            self.telemetry.register(ct.fingerprint, est.total, s.name)
        payload = s.pack(buf, ct, incount)
        with scope("wire"):
            wire = lax.ppermute(payload, axis, list(perm))
        self.wire_ops += 1
        self.wire_payload_bytes += seg.nbytes
        return SendRequest(wire, s, ct, incount, segment=seg)

    def irecv(
        self,
        buf: jax.Array,
        ct: CommittedType,
        send_req: SendRequest,
        incount: Optional[int] = None,
    ) -> Request:
        """Bind a destination buffer + receive type to an issued send;
        ``wait()`` materializes the unpack."""
        inc = send_req.incount if incount is None else incount
        return Request(
            thunk=lambda: send_req.strategy.unpack_wire(
                self, buf, send_req.wait(), ct, send_req.send_ct, inc
            )
        )

    def sendrecv(
        self,
        src_buf: jax.Array,
        dst_buf: jax.Array,
        send_ct: CommittedType,
        perm: Sequence[Tuple[int, int]],
        axis_name: Optional[str] = None,
        recv_ct: Optional[CommittedType] = None,
        incount: int = 1,
    ) -> jax.Array:
        """Blocking pack -> permute -> unpack; returns the updated
        ``dst_buf``.  With telemetry attached and eager arguments, the
        whole blocking exchange is timed against the send type's
        fingerprint (tracers skip the probe — a timer inside a trace
        measures tracing, not transfer).  With a tracer attached the
        same eager path additionally records an ``exchange`` span with
        ``pack``/``wire``/``unpack`` children, blocking at each phase
        boundary so the split is a real observation, not attribution."""
        if self._tracing_spans(src_buf):
            return self._sendrecv_traced(
                src_buf, dst_buf, send_ct, perm, axis_name, recv_ct, incount
            )
        if self.telemetry is None or isinstance(src_buf, jax.core.Tracer):
            req = self.isend(src_buf, send_ct, perm, axis_name, incount)
            return self.irecv(dst_buf, recv_ct or send_ct, req).wait()
        t0 = time.perf_counter()
        req = self.isend(src_buf, send_ct, perm, axis_name, incount)
        out = self.irecv(dst_buf, recv_ct or send_ct, req).wait()
        jax.block_until_ready(out)  # async dispatch would under-report
        self.telemetry.observe(send_ct.fingerprint, time.perf_counter() - t0)
        return out

    def _sendrecv_traced(
        self, src_buf, dst_buf, send_ct, perm, axis_name, recv_ct, incount
    ) -> jax.Array:
        """Eager :meth:`sendrecv` with per-phase spans.  Same work as
        isend + irecv, laid out phase by phase so each span boundary can
        block — the paper's pack/wire/unpack decomposition observed
        directly."""
        axis = self._axis(axis_name)
        s = self.select(send_ct, incount, wire=True)
        seg = s.wire_segment(send_ct, incount)
        est = s.plan(self.model, send_ct, incount)
        if self.telemetry is not None:
            self.telemetry.register(send_ct.fingerprint, est.total, s.name)
        t0 = time.perf_counter()
        with self.tracer.span(
            "exchange", fingerprint=send_ct.fingerprint, strategy=s.name,
            wire_bytes=seg.nbytes, incount=incount, pred=est.total,
        ):
            with self.tracer.span("pack", pred=est.t_pack):
                payload = s.pack(src_buf, send_ct, incount)
                jax.block_until_ready(payload)
            with self.tracer.span("wire", pred=est.t_link,
                                  wire_bytes=seg.nbytes):
                with scope("wire"):
                    wire = lax.ppermute(payload, axis, list(perm))
                jax.block_until_ready(wire)
            self.wire_ops += 1
            self.wire_payload_bytes += seg.nbytes
            with self.tracer.span("unpack", pred=est.t_unpack):
                out = s.unpack_wire(
                    self, dst_buf, wire, recv_ct or send_ct, send_ct, incount
                )
                jax.block_until_ready(out)
        if self.telemetry is not None:
            self.telemetry.observe(
                send_ct.fingerprint, time.perf_counter() - t0
            )
        return out

    # ------------------------------------------------------------------
    # fused neighborhood alltoallv (the paper's MPI_Alltoallv halo path)
    # ------------------------------------------------------------------
    def plan_neighbor(
        self,
        send_cts: Sequence[CommittedType],
        perms: Sequence[Sequence[Tuple[int, int]]],
        strategies: Optional[Sequence[Strategy]] = None,
        uniform_waste_tolerance: float = 0.0,
        schedule_policy: Optional[str] = None,
        probe: Optional[jax.Array] = None,
    ) -> Tuple[Tuple[Strategy, ...], WirePlan]:
        """Select a strategy per transfer and lay the exchange out as an
        exact-byte :class:`WirePlan`.  Call once at setup time (e.g.
        ``make_halo_step``) and hand the result to
        :meth:`ineighbor_alltoallv` to keep the per-call host work at
        dictionary lookups.  The plan is priced through the performance
        model and recorded (``wire_bytes`` included) in the attached
        :class:`~repro.measure.decisions.DecisionCache`, if any.

        ``schedule_policy`` picks how the wire schedule is chosen
        (default: :data:`DEFAULT_SCHEDULE_POLICY` — ``"model"``):

        ``"model"``   :meth:`PerfModel.choose_wire_schedule` trades the
                      grouped schedule's per-class collective launches
                      against the uniform collective's padding bytes on
                      the measured (per-axis) wire tables; the chosen
                      schedule and the prices of the rejected
                      alternatives are recorded in the decision row.
                      The padding it may buy is bounded by the uniform
                      row-equalized layout and byte-gated in CI with a
                      padded allowance (``bench_halo --assert-ragged``).
        ``"exact"``   the byte-exact ladder (``uniform`` only within
                      ``uniform_waste_tolerance`` of zero padding) — the
                      strict wire-bytes regression gates assume this.

        ``probe`` (a *concrete* sample of the exchange buffer) turns on
        length-aware planning: strategy selection may pick a
        ``supports_varlen`` compressor priced at the payload's probed
        stream length, the plan is annotated with per-class
        ``stream_bytes`` (single-transfer classes only — a truncated
        multi-transfer class would cut its later segments), and the
        model-priced schedule choice can then pick the ``varlen``
        transport.  The ratio is taken from the probe, never assumed;
        a tracer probe is ignored.
        """
        if schedule_policy is None:
            schedule_policy = DEFAULT_SCHEDULE_POLICY
        if schedule_policy not in ("exact", "model"):
            raise ValueError(
                f"unknown schedule_policy {schedule_policy!r}; "
                "expected 'exact' or 'model'"
            )
        t_plan0 = (
            time.perf_counter()
            if self.tracer is not None and self.tracer.active else None
        )
        if probe is not None and isinstance(probe, jax.core.Tracer):
            probe = None  # tracers carry no data to probe
        if strategies is not None:
            strats = tuple(strategies)
        elif probe is not None and isinstance(self.policy, ModelPolicy):
            # probed selection: varlen-capable compressors are priced at
            # the payload's actual stream length, so a zero-heavy class
            # can pick rle where capacity pricing never would
            strats = tuple(
                self.strategies.get(
                    self.model.select(
                        ct, 1, allow_bounding=True,
                        registry=self.strategies, probe=probe,
                    ).strategy
                )
                for ct in send_cts
            )
        else:
            strats = tuple(self.select(ct, 1, wire=True) for ct in send_cts)
        segs = [strats[i].wire_segment(send_cts[i]) for i in range(len(strats))]
        plan = plan_wire(
            tuple(s.nbytes for s in segs),
            tuple(tuple(map(tuple, p)) for p in perms),
            fingerprints=tuple(s.fingerprint for s in segs),
            uniform_waste_tolerance=uniform_waste_tolerance,
            topology=self.model.topology,
        )
        if probe is not None and any(
            getattr(s, "supports_varlen", False) for s in strats
        ):
            # attach per-class stream lengths AFTER planning so the
            # plan_wire cache stays payload-independent; only
            # single-transfer classes may truncate
            per_transfer = [
                strats[i].probe_stream_bytes(send_cts[i], 1, probe)
                for i in range(len(strats))
            ]
            per_group = tuple(
                min(per_transfer[grp.transfers[0]], grp.nbytes)
                if len(grp.transfers) == 1
                else grp.nbytes
                for grp in plan.groups
            )
            if sum(per_group) < plan.wire_bytes:
                plan = plan.with_stream_bytes(per_group)
        note = ""
        if schedule_policy == "model":
            plan, costs = self.model.choose_wire_schedule(plan)
            note = " priced[" + " ".join(
                f"{k}={v:.3e}" for k, v in sorted(costs.items())
            ) + "]"
        est = self.model.price_exchange(plan, note=note)
        if self.telemetry is not None:
            # trace-time half of the probe: the prediction is on file
            # before the first observation arrives
            self.telemetry.register(plan.fingerprint, est.total, est.strategy)
            # per-delta-class completion predictions ride next to the
            # whole-exchange key so drift attribution can name the slow
            # direction, not just the slow exchange
            if plan.ngroups > 1:
                completions = self.model.price_class_completions(plan)
                for g, t_c in enumerate(completions):
                    self.telemetry.register(
                        f"{plan.fingerprint}/c{g}", t_c,
                        f"class/{plan.schedule}",
                    )
            if plan.stream_bytes:
                # achieved-ratio ring: predicted = the probed ratio this
                # plan was priced at; each exchange observes the ratio
                # it actually issued so drift can flag decay
                self.telemetry.register(
                    f"{plan.fingerprint}/ratio", plan.stream_ratio,
                    "compress/ratio",
                )
        if t_plan0 is not None:
            self.tracer.add_manual(
                "plan", t_plan0, time.perf_counter() - t_plan0,
                fingerprint=plan.fingerprint, strategy=est.strategy,
                schedule=plan.schedule, wire_bytes=plan.issued_bytes,
                nsegments=len(plan.segments), pred=est.total,
            )
        return strats, plan

    @scope("wire")
    def _issue_wire(
        self, wire: jax.Array, plan: WirePlan, axis: str
    ) -> List[jax.Array]:
        """Put the flat exact-byte wire buffer on the link with the
        plan's schedule; returns one received payload per group (exact
        ``nbytes`` for the ragged schedules, a padded row — harmless,
        segment slicing never reads the tail — for ``uniform``)."""
        if plan.schedule == "grouped":
            rows = []
            for goff, grp in zip(plan.group_offsets, plan.groups):
                payload = lax.dynamic_slice(wire, (goff,), (grp.nbytes,))
                rows.append(lax.ppermute(payload, axis, list(grp.perm)))
            return rows

        if plan.schedule == "varlen":
            # length-aware transport: each class ships only its probed
            # stream length — a strict PREFIX of its capacity slot (the
            # compressed formats interleave run records, so truncation
            # loses nothing the decoder needs).  Native ragged collective
            # with per-class stream sizes where the backend runs it;
            # truncated per-class ppermutes otherwise.  Bit-exact vs the
            # capacity path for payloads within the probed stream budget.
            if len(plan.stream_bytes) != plan.ngroups:
                raise ValueError("varlen schedule on a stream-unannotated plan")
            if has_ragged_all_to_all() and plan.fused:
                return _ragged_exchange(wire, plan, plan.stream_bytes, axis)
            rows = []
            for goff, sb, grp in zip(
                plan.group_offsets, plan.stream_bytes, plan.groups
            ):
                payload = lax.dynamic_slice(wire, (goff,), (sb,))
                rows.append(lax.ppermute(payload, axis, list(grp.perm)))
            return rows

        if plan.schedule == "tiered":
            # two-level transport: fast-tier classes go per-class like
            # grouped; every inter-tier bundle travels as ONE coalesced
            # collective along its representative's permutation (the
            # concatenated payload lands on the right peer NODE), then
            # each non-representative member is forwarded to its true
            # destination rank by an intra-node correction hop — the
            # edge (dst_g0(r), dst_g(r)) stays on-node by the bundle-key
            # invariant and composes two bijections, so it is itself a
            # valid permutation
            if plan.link_classes is None:
                raise ValueError("tiered schedule on an unannotated plan")
            out: List[Optional[jax.Array]] = [None] * len(plan.groups)
            bundled = {g for b in plan.tier_bundles for g in b}
            for g, (goff, grp) in enumerate(
                zip(plan.group_offsets, plan.groups)
            ):
                if g in bundled:
                    continue
                payload = lax.dynamic_slice(wire, (goff,), (grp.nbytes,))
                out[g] = lax.ppermute(payload, axis, list(grp.perm))
            for b in plan.tier_bundles:
                g0 = b[0]
                parts = [
                    lax.dynamic_slice(
                        wire,
                        (plan.group_offsets[g],),
                        (plan.groups[g].nbytes,),
                    )
                    for g in b
                ]
                payload = (
                    jnp.concatenate(parts) if len(parts) > 1 else parts[0]
                )
                got = lax.ppermute(
                    payload, axis, list(plan.groups[g0].perm)
                )
                d0 = dict(plan.groups[g0].perm)
                off = 0
                for g in b:
                    part = lax.dynamic_slice(
                        got, (off,), (plan.groups[g].nbytes,)
                    )
                    off += plan.groups[g].nbytes
                    if g == g0:
                        out[g] = part
                    else:
                        dg = dict(plan.groups[g].perm)
                        corr = [
                            (d0[r], dg[r]) for r in range(plan.nranks)
                        ]
                        out[g] = lax.ppermute(part, axis, corr)
            return out

        if plan.schedule == "uniform":
            parts = []
            for goff, grp in zip(plan.group_offsets, plan.groups):
                row = lax.dynamic_slice(wire, (goff,), (grp.nbytes,))
                if grp.nbytes < plan.seg_bytes:
                    row = jnp.concatenate(
                        [row, jnp.zeros((plan.seg_bytes - grp.nbytes,), jnp.uint8)]
                    )
                parts.append(row)
            stacked = jnp.stack(
                parts + [jnp.zeros((plan.seg_bytes,), jnp.uint8)]
            )
            me = lax.axis_index(axis)
            send = jnp.asarray(np.asarray(plan.send_rows, np.int32))[me]
            sendbuf = jnp.take(stacked, send, axis=0)
            got = lax.all_to_all(sendbuf, axis, split_axis=0, concat_axis=0)
            back = jnp.asarray(np.asarray(plan.recv_rows, np.int32))[me]
            by_group = jnp.take(got, back, axis=0)
            return [by_group[g] for g in range(len(plan.groups))]

        # "ragged": one native ragged collective — exact bytes, one op.
        # Requires a backend that runs lax.ragged_all_to_all (the planner
        # only selects this schedule when has_ragged_all_to_all says so).
        return _ragged_exchange(
            wire, plan, tuple(g.nbytes for g in plan.groups), axis
        )

    def _phase_predictions(
        self, send_cts, strategies, plan
    ) -> Tuple[float, float, float]:
        """Model-predicted (pack, wire, unpack) seconds for one fused
        exchange — the ``pred`` attributes the per-phase spans carry, so
        an exported trace joins observed against predicted without the
        model in hand.  Host-side, computed only on traced eager calls."""
        t_pack = t_unpack = 0.0
        for ct, strat in zip(send_cts, strategies):
            est = strat.plan(self.model, ct, 1)
            t_pack += est.t_pack
            t_unpack += est.t_unpack
        try:
            costs = self.model.price_wire_schedules(plan)
            t_wire = float(costs.get(plan.schedule, 0.0))
        except Exception:
            t_wire = self.model.t_link(plan.issued_bytes, 1)
        return t_pack, t_wire, t_unpack

    def ineighbor_alltoallv(
        self,
        buf: jax.Array,
        send_cts: Sequence[CommittedType],
        recv_cts: Sequence[CommittedType],
        perms: Sequence[Sequence[Tuple[int, int]]],
        axis_name: Optional[str] = None,
        plan: Optional[WirePlan] = None,
        strategies: Optional[Sequence[Strategy]] = None,
    ) -> Request:
        """Nonblocking fused neighborhood exchange: transfer ``i`` packs
        ``send_cts[i]`` out of ``buf``, ships it along ``perms[i]``, and
        unpacks into ``recv_cts[i]`` of the same buffer.  Every region
        is packed at its exact wire extent into one flat buffer
        (:func:`repro.kernels.pack.pack_ragged`) laid out by a
        :class:`WirePlan`, and the plan's schedule puts exactly those
        bytes on the wire — no class padding; ``wait()`` materializes
        the unpacks.  Pass a prebuilt ``plan``/``strategies`` pair (from
        :meth:`plan_neighbor`) to skip per-call planning.

        Returns a :class:`NeighborRequest`: one :class:`ClassRequest`
        per delta class, independently completable via ``wait_any()``
        (region-split overlap drains them in completion order), with
        ``wait()`` preserving the monolithic drain-everything
        contract."""
        if not (len(send_cts) == len(recv_cts) == len(perms)):
            raise ValueError("send_cts, recv_cts, perms must align")
        axis = self._axis(axis_name)
        n = len(send_cts)
        if n == 0:
            return Request(value=buf)
        if strategies is None:
            strategies = tuple(self.select(ct, 1, wire=True) for ct in send_cts)
        if plan is None:
            _, plan = self.plan_neighbor(send_cts, perms, strategies=strategies)
        elif len(plan.segments) != n:
            raise ValueError(
                f"wire plan describes {len(plan.segments)} transfers, "
                f"got {n} send types"
            )

        def leaf_packer(strat: Strategy, ct: CommittedType):
            # fused pack+compress: compressors expose their wire encoder
            # separately so the member gather and the encode ride ONE
            # traced expression (no extra materialized pass); plain
            # strategies' wire format IS their packed bytes
            enc = getattr(strat, "encode_wire", None)
            if enc is not None:
                return (lambda b: ops.pack(b, ct), enc)
            return (lambda b: strat.pack(b, ct), None)

        entries = [
            (plan.segments[i].offset, *leaf_packer(strategies[i], send_cts[i]))
            for i in range(n)
        ]
        if self._tracing_spans(buf):
            # eager + traced: the pack and wire phases block at their
            # span boundaries so each is observed separately (the
            # predicted terms come from the member estimates and the
            # model's wire-schedule pricing)
            t_pack, t_wire, _ = self._phase_predictions(
                send_cts, strategies, plan
            )
            with self.tracer.span("pack", pred=t_pack,
                                  nbytes=plan.wire_bytes):
                wire = pack_compress_ragged(buf, entries, plan.wire_bytes)
                jax.block_until_ready(wire)
            with self.tracer.span("wire", pred=t_wire,
                                  wire_bytes=plan.issued_bytes,
                                  schedule=plan.schedule):
                group_rows = self._issue_wire(wire, plan, axis)
                jax.block_until_ready(group_rows)
        else:
            wire = pack_compress_ragged(buf, entries, plan.wire_bytes)
            group_rows = self._issue_wire(wire, plan, axis)
        varlen = plan.schedule == "varlen"
        self.wire_ops += plan.wire_ops
        self.wire_payload_bytes += plan.issued_bytes
        fp = plan.fingerprint
        if varlen:
            # compressed-wire accounting: capacity vs what actually
            # moved, plus the achieved-ratio ring drift audits against
            self.compress_exchanges += 1
            self.compress_capacity_bytes += plan.wire_bytes
            self.compress_stream_bytes += plan.effective_wire_bytes
            if self.telemetry is not None:
                self.telemetry.observe(f"{fp}/ratio", plan.stream_ratio)
        for g, grp in enumerate(plan.groups):
            key = f"{fp}/c{g}"
            self.wire_class_ops[key] = self.wire_class_ops.get(key, 0) + 1
            self.wire_class_bytes[key] = (
                self.wire_class_bytes.get(key, 0)
                + (plan.stream_bytes[g] if varlen else grp.nbytes)
            )

        def leaf_decoder(strat, recv_ct):
            dec = getattr(strat, "decode_wire", None)
            if dec is None:
                return None
            return lambda part: dec(part, recv_ct.size)

        def leaf_unpacker(strat, recv_ct, send_ct):
            # fused decompress+unpack: when the strategy exposes its
            # wire decoder the leaf receives decoded MEMBER bytes and
            # only scatters; otherwise unpack_wire consumes the raw
            # wire payload as before
            if getattr(strat, "decode_wire", None) is not None:
                return lambda dst, member: self.select(
                    recv_ct, 1, wire=False
                ).unpack(dst, member, recv_ct, 1)
            return lambda dst, part: strat.unpack_wire(
                self, dst, part, recv_ct, send_ct, 1
            )

        def class_unpacker(grp: WireGroup, g: int):
            # under the varlen schedule a single-transfer class's
            # payload is the truncated stream — the leaf decodes it at
            # its received length (the decoder derives the run count
            # from the wire length)
            stream = plan.stream_bytes[g] if varlen else grp.nbytes
            leaves = [
                (
                    off,
                    stream if len(grp.transfers) == 1
                    else plan.segments[i].nbytes,
                    leaf_decoder(strategies[i], recv_cts[i]),
                    leaf_unpacker(strategies[i], recv_cts[i], send_cts[i]),
                )
                for i, off in zip(grp.transfers, grp.offsets)
            ]
            return lambda dst, payload: decode_unpack_ragged(
                dst, payload, leaves
            )

        classes = [
            ClassRequest(
                g, group_rows[g], grp.transfers,
                plan.stream_bytes[g] if varlen else grp.nbytes,
                class_unpacker(grp, g),
            )
            for g, grp in enumerate(plan.groups)
        ]
        # drain-side probe: gauge the completion order unconditionally
        # (host-side dict write), and on eager drains observe per-class
        # completion latency against the registered per-class prediction
        # and record a per-class wire span — the same guard discipline
        # as the whole-exchange probes
        eager = not isinstance(buf, jax.core.Tracer)
        observe = eager and self.telemetry is not None
        tracing = eager and self._tracing_spans(buf)
        issued_at = time.perf_counter()

        def on_drain(req: NeighborRequest, cls: ClassRequest) -> None:
            key = f"{fp}/c{cls.index}"
            self.wire_class_drains[key] = len(req.drained)
            if not (observe or tracing):
                return
            jax.block_until_ready(req.buffer)
            dt = time.perf_counter() - issued_at
            if observe:
                self.telemetry.observe(key, dt)
            if tracing:
                self.tracer.add_manual(
                    "wire_class", issued_at, dt, fingerprint=fp,
                    nbytes=cls.nbytes, transfers=len(cls.transfers),
                    drain_order=len(req.drained), **{"class": cls.index},
                )

        return NeighborRequest(buf, classes, plan=plan, on_drain=on_drain)

    def neighbor_alltoallv(
        self,
        buf: jax.Array,
        send_cts: Sequence[CommittedType],
        recv_cts: Sequence[CommittedType],
        perms: Sequence[Sequence[Tuple[int, int]]],
        axis_name: Optional[str] = None,
        plan: Optional[WirePlan] = None,
        strategies: Optional[Sequence[Strategy]] = None,
    ) -> jax.Array:
        """Blocking :meth:`ineighbor_alltoallv`.  With telemetry
        attached and eager arguments the fused exchange is timed against
        the wire plan's fingerprint (the same key the decision cache
        records the schedule choice under).  With a tracer attached the
        eager call records the full span hierarchy: ``exchange`` (the
        decision signature in its attributes) hosting ``plan`` (when
        planned here), ``pack``/``wire`` (inside
        :meth:`ineighbor_alltoallv`) and ``unpack``."""
        if len(send_cts) > 0 and self._tracing_spans(buf):
            return self._neighbor_alltoallv_traced(
                buf, send_cts, recv_cts, perms, axis_name, plan, strategies
            )
        if (
            self.telemetry is None
            or isinstance(buf, jax.core.Tracer)
            or len(send_cts) == 0
        ):
            return self.ineighbor_alltoallv(
                buf, send_cts, recv_cts, perms, axis_name, plan, strategies
            ).wait()
        if plan is None:
            strategies, plan = self.plan_neighbor(
                send_cts, perms, strategies=strategies
            )
        t0 = time.perf_counter()
        out = self.ineighbor_alltoallv(
            buf, send_cts, recv_cts, perms, axis_name, plan, strategies
        ).wait()
        jax.block_until_ready(out)
        self.telemetry.observe(plan.fingerprint, time.perf_counter() - t0)
        return out

    def _neighbor_alltoallv_traced(
        self, buf, send_cts, recv_cts, perms, axis_name, plan, strategies
    ) -> jax.Array:
        """Eager blocking fused exchange under the tracer: one
        ``exchange`` span whose children decompose the call."""
        t0 = time.perf_counter()
        with self.tracer.span("exchange") as sp:
            if strategies is None:
                strategies = tuple(
                    self.select(ct, 1, wire=True) for ct in send_cts
                )
            if plan is None:
                strategies, plan = self.plan_neighbor(
                    send_cts, perms, strategies=strategies
                )
            t_pack, t_wire, t_unpack = self._phase_predictions(
                send_cts, strategies, plan
            )
            if sp is not None:
                sp.attrs.update(
                    fingerprint=plan.fingerprint,
                    strategy=f"wire/{plan.schedule}",
                    schedule=plan.schedule,
                    wire_bytes=plan.issued_bytes,
                    ngroups=len(plan.groups),
                    pred=t_pack + t_wire + t_unpack,
                )
            req = self.ineighbor_alltoallv(
                buf, send_cts, recv_cts, perms, axis_name, plan, strategies
            )
            with self.tracer.span("unpack", pred=t_unpack):
                out = req.wait()
                jax.block_until_ready(out)
        if self.telemetry is not None:
            self.telemetry.observe(
                plan.fingerprint, time.perf_counter() - t0
            )
        return out

    # ------------------------------------------------------------------
    # collectives on datatypes
    # ------------------------------------------------------------------
    def all_gather_packed(
        self,
        buf: jax.Array,
        ct: CommittedType,
        axis_name: Optional[str] = None,
        incount: int = 1,
    ) -> jax.Array:
        """Pack the datatype then all-gather the contiguous payloads.
        Returns (axis_size, size*incount) bytes."""
        axis = self._axis(axis_name)
        packed = self.pack(buf, ct, incount)
        self.wire_ops += 1
        with scope("wire"):
            return lax.all_gather(packed, axis)

    def all_to_all_packed(
        self,
        buf: jax.Array,
        cts: Sequence[CommittedType],
        axis_name: Optional[str] = None,
    ) -> jax.Array:
        """MPI_Alltoallv over equal-size segments: pack one datatype per
        peer into a single contiguous buffer, then all_to_all.  All
        ``cts`` must have equal packed size (pad types to match);
        returns (npeers, segment) received bytes."""
        axis = self._axis(axis_name)
        sizes = {ct.size for ct in cts}
        if len(sizes) != 1:
            raise ValueError("all_to_all_packed needs equal-size segments")
        parts = [self.pack(buf, ct) for ct in cts]
        self.wire_ops += 1
        with scope("wire"):
            sendbuf = jnp.stack(parts)  # (npeers, seg)
            return lax.all_to_all(sendbuf, axis, split_axis=0,
                                  concat_axis=0)

    # ------------------------------------------------------------------
    def stats(self) -> Dict[str, int]:
        """Cumulative counters for this communicator.  Every call also
        publishes them into the process metrics registry
        (:func:`repro.obs.metrics.publish_comm_stats`), so
        ``default_metrics().snapshot()`` — and the ``metrics.json`` the
        production ``save()`` persists — always reflects the latest
        totals."""
        out = {
            "committed_types": len(self.registry),
            "commit_hits": self.registry.hits,
            "model_lookups": self.model.lookups,
            "model_hits": self.model.hits,
            "strategies": len(self.strategies),
            "wire_ops": self.wire_ops,
            "wire_payload_bytes": self.wire_payload_bytes,
            "wire_classes": len(self.wire_class_bytes),
            "wire_class_ops": dict(self.wire_class_ops),
            "wire_class_bytes": dict(self.wire_class_bytes),
            "wire_class_drains": dict(self.wire_class_drains),
            "compress_exchanges": self.compress_exchanges,
            "compress_capacity_bytes": self.compress_capacity_bytes,
            "compress_stream_bytes": self.compress_stream_bytes,
            "compress_ratio": (
                self.compress_stream_bytes / self.compress_capacity_bytes
                if self.compress_capacity_bytes
                else 1.0
            ),
            "telemetry_keys": (
                len(self.telemetry) if self.telemetry is not None else 0
            ),
        }
        from repro.obs.metrics import publish_comm_stats

        publish_comm_stats(out, self.telemetry)
        return out


def as_communicator(obj) -> Communicator:
    """Accept a Communicator or anything wrapping one (the Interposer
    shim exposes ``.comm``)."""
    if isinstance(obj, Communicator):
        return obj
    comm = getattr(obj, "comm", None)
    if isinstance(comm, Communicator):
        return comm
    raise TypeError(f"expected a Communicator (or shim), got {type(obj)!r}")
