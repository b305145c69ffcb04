"""Runtime performance model for datatype transfer strategies (paper §5).

The paper models three ways to move a non-contiguous GPU object between
ranks — "device" (Eq. 1), "one-shot" (Eq. 2), "staged" (Eq. 3) — from
once-measured system parameters, then picks the cheapest per call site
(§6.3: the model query is pure, interpolated, and cached; measured
selection overhead 277 ns).

TPU adaptation (DESIGN.md §2): there is no host-mapped zero-copy path,
so the strategy menu becomes

    rows      pack with the pitched row kernel, then one contiguous
              collective                                ≙ "device"
    dma       pack with the strided-descriptor kernel, then collective
                                                        ≙ "staged"
    xla       per-block XLA copies into a contiguous buffer (the naive
              CUDA-aware-MPI baseline all impls share)  ≙ baseline
    bounding  send the *contiguous bounding extent* of the object with
              no pack at all; receiver slices.  Wins when the object is
              dense in its extent                       ≙ "one-shot"
              (zero explicit staging, pays over-transfer instead of
              pack cost — the same trade the paper's one-shot makes)

Each strategy time decomposes as  T = T_pack + T_link(bytes) + T_unpack,
mirroring Eqs. 1–3, with terms read from a :class:`SystemParams` table —
either analytic TPU v5e constants or the measured full-term tables
produced by ``repro.measure`` (the paper's "binary that records system
performance parameters"); see ``docs/measure.md``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

from repro.core.commit import CommittedType
from repro.comm.topology import Topology

__all__ = [
    "SystemParams",
    "StrategyEstimate",
    "ProgramEstimate",
    "OverlapEstimate",
    "PerfModel",
    "TPU_V5E",
    "synthetic_two_tier",
]


#: 2D measured table rows: (log2_contig_block_bytes, log2_total_bytes, sec)
Table2D = Tuple[Tuple[float, float, float], ...]
#: 1D measured table rows: (log2_total_bytes, sec)
Table1D = Tuple[Tuple[float, float], ...]


def _freeze2d(v) -> Optional[Dict[str, Table2D]]:
    if not v:
        return None
    return {k: tuple(tuple(row) for row in rows) for k, rows in v.items()}


def _freeze1d(v) -> Optional[Table1D]:
    if not v:
        return None
    return tuple(tuple(row) for row in v)


def _freeze_axis_tables(v) -> Optional[Dict[str, Table1D]]:
    if not v:
        return None
    return {k: tuple(tuple(row) for row in rows) for k, rows in v.items()}


def _freeze_axis_fits(v) -> Optional[Dict[str, Tuple]]:
    if not v:
        return None
    return {k: tuple(fit) for k, fit in v.items()}


@dataclass(frozen=True)
class SystemParams:
    """Measured or analytic system parameters (paper Fig. 9/10 tables).

    The analytic constants are the fallback; a full-term calibration
    (``repro.measure``) fills the optional measured tables and the model
    then consults them for *every* term of T = T_pack + T_link +
    T_unpack, as the paper's once-recorded filesystem measurements do.
    """

    name: str
    hbm_bw: float = 819e9          # bytes/s per chip
    ici_bw: float = 45e9           # effective bytes/s per link (50 GB/s raw)
    ici_latency: float = 1.0e-6    # per-hop collective latency floor
    kernel_launch: float = 1.5e-6  # pallas_call fixed cost
    dma_setup: float = 4.0e-7      # per strided-DMA-descriptor cost
    xla_copy_overhead: float = 8.0e-7  # per dynamic-slice copy op
    # measured tables ({strategy: rows} / rows) — sparse grids in log2
    # space, interpolated at query time (nearest-neighbor off-grid)
    pack_table: Optional[Dict[str, Table2D]] = None
    unpack_table: Optional[Dict[str, Table2D]] = None
    wire_table: Optional[Table1D] = None   # one-hop collective time
    copy_table: Optional[Table1D] = None   # contiguous device copy time
    # least-squares (latency, bandwidth) fit of wire_table; used for the
    # per-extra-hop latency term when the table drives t_link
    wire_latency: Optional[float] = None
    wire_bw: Optional[float] = None
    # per-mesh-axis wire measurements: a multi-axis mesh (e.g. a fast
    # ICI axis and a slow DCN axis) has genuinely different link terms
    # per axis, so the calibration sweeps each axis's ring separately
    # and t_link(axis=...) consults the matching table; the flat
    # wire_table remains the axis-agnostic fallback
    wire_tables: Optional[Dict[str, Table1D]] = None
    wire_fits: Optional[Dict[str, Tuple]] = None  # axis -> (latency, bw)
    # per-LINK-CLASS wire measurements (STORE_FORMAT 5): a two-level
    # machine has a fast intra-node tier and a slow inter-node tier, and
    # t_link(link_class=...) consults these before the per-axis/flat
    # tables.  Keys are "<class>" or "<axis>/<class>" for
    # class in repro.comm.topology.LINK_CLASSES; pre-format-5 envelopes
    # load with these None — the flat table then prices every class,
    # i.e. everything is treated as ``intra``
    link_tables: Optional[Dict[str, Table1D]] = None
    link_fits: Optional[Dict[str, Tuple]] = None  # key -> (latency, bw)
    # measured stencil-application sweep: rows (log2_neighbors,
    # log2_window_bytes, sec) — prices the deep-halo redundant-compute
    # term from a real sweep instead of the contiguous-copy proxy
    stencil_table: Optional[Table2D] = None
    # measured compress/decompress sweep (STORE_FORMAT 6): per wire
    # compressor, rows (log2_total_bytes, compress_sec, decompress_sec,
    # achieved_ratio_sample) — prices the pack-side cost of a compressed
    # schedule from a real sweep instead of the 2x-HBM-sweep analytic
    # proxy.  The ratio column is a *sample* on the sweep's synthetic
    # payload, recorded for reference; the ratio the model prices a
    # schedule at always comes from a probe of the actual payload.
    compress_table: Optional[Dict[str, Table2D]] = None

    def __post_init__(self):
        # normalize list-of-lists (JSON) into hashable tuple tables
        object.__setattr__(self, "pack_table", _freeze2d(self.pack_table))
        object.__setattr__(self, "unpack_table", _freeze2d(self.unpack_table))
        object.__setattr__(self, "wire_table", _freeze1d(self.wire_table))
        object.__setattr__(self, "copy_table", _freeze1d(self.copy_table))
        object.__setattr__(
            self, "wire_tables", _freeze_axis_tables(self.wire_tables)
        )
        object.__setattr__(self, "wire_fits", _freeze_axis_fits(self.wire_fits))
        object.__setattr__(
            self, "link_tables", _freeze_axis_tables(self.link_tables)
        )
        object.__setattr__(self, "link_fits", _freeze_axis_fits(self.link_fits))
        object.__setattr__(self, "stencil_table", _freeze1d(self.stencil_table))
        object.__setattr__(
            self, "compress_table", _freeze2d(self.compress_table)
        )

    def to_json(self) -> str:
        d = dataclasses.asdict(self)
        return json.dumps(d, indent=2)

    @staticmethod
    def from_json(s: str) -> "SystemParams":
        d = json.loads(s)
        known = {f.name for f in dataclasses.fields(SystemParams)}
        d = {k: v for k, v in d.items() if k in known}
        return SystemParams(**d)


#: Analytic TPU v5e table (197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link
#: ICI) — shipped for dry-run containers with no TPU to calibrate on.
TPU_V5E = SystemParams(name="tpu_v5e_analytic")


def synthetic_two_tier(
    params: SystemParams,
    latency_factor: float = 20.0,
    bandwidth_factor: float = 4.0,
) -> SystemParams:
    """Derive a two-tier parameter set from single-tier measurements.

    CI has no multi-node hardware, but the simulated-scale gate still
    needs an ``inter`` tier to price.  This takes the params' flat (or
    axis-default) wire sweep as the ``intra`` table and synthesizes the
    ``inter`` table by degrading it — each row's time becomes
    ``t * bandwidth_factor + (latency_factor - 1) * lat0`` with ``lat0``
    the fitted (or analytic) one-hop latency, i.e. a link that is
    ``bandwidth_factor`` x thinner and ``latency_factor`` x laggier, the
    usual DCN-vs-ICI shape.  ``latency_factor = bandwidth_factor = 1``
    gives ``inter == intra`` exactly — the oracle configuration under
    which tier-aware pricing must reproduce flat pricing bit-for-bit.
    """
    table = params.wire_table
    lat0 = params.wire_latency
    bw0 = params.wire_bw
    if not table:
        # no sweep calibrated: build a two-point analytic table so the
        # tiers are still priceable (dry-run containers)
        lat0 = params.ici_latency
        bw0 = params.ici_bw
        table = tuple(
            (float(x), lat0 + (2.0 ** x) / bw0) for x in (10.0, 22.0)
        )
    if lat0 is None:
        lat0 = params.ici_latency
    extra_lat = (latency_factor - 1.0) * lat0
    inter = tuple(
        (x, t * bandwidth_factor + extra_lat) for x, t in table
    )
    link_fits = {}
    if lat0 is not None and bw0 is not None:
        link_fits["intra"] = (lat0, bw0)
        link_fits["inter"] = (lat0 * latency_factor, bw0 / bandwidth_factor)
    return dataclasses.replace(
        params,
        link_tables={"intra": table, "inter": inter},
        link_fits=link_fits or None,
    )


@dataclass(frozen=True)
class StrategyEstimate:
    strategy: str
    t_pack: float
    t_link: float
    t_unpack: float
    #: exact bytes this strategy puts on the wire (0 when the estimate
    #: predates wire accounting, e.g. hand-built test fixtures)
    wire_bytes: int = 0

    @property
    def total(self) -> float:
        return self.t_pack + self.t_link + self.t_unpack


@dataclass(frozen=True)
class ProgramEstimate:
    """Predicted cost of one deep-halo iteration: a single exchange at
    halo depth ``steps * cycle_radii`` amortized over ``steps`` repeats
    of a (possibly heterogeneous) op cycle, plus the redundant
    ghost-shell re-evaluation the shrinking-region schedule pays instead
    of the saved exchanges.

    ``steps`` counts cycle repeats; :attr:`applications` counts the
    individual stencil applications (``steps * cycle_len``; equal to
    ``steps`` for the single-op cycle).  :attr:`op_redundant` splits
    :attr:`t_redundant` per op *position in the cycle* (summed over the
    repeats), so the audit shows which op of a predictor/corrector pair
    is buying the ghost shells.

    The figure of merit is :attr:`per_step` — seconds per stencil
    application — which is what :func:`PerfModel.price_program`
    minimizes when ``--halo-steps auto`` picks the fusion depth.
    """

    steps: int
    t_exchange: float   # one deep exchange: member pack/unpack + wire
    t_redundant: float  # ghost-region re-evaluation across the fused steps
    wire_bytes: int     # bytes that one exchange puts on the wire
    cycle_len: int = 1  # ops per cycle pass (1 = the single-op program)
    #: redundant seconds per cycle position, summed over the repeats
    #: (empty for estimates built before cycles existed)
    op_redundant: Tuple[float, ...] = ()

    @property
    def applications(self) -> int:
        """Stencil applications one iteration performs."""
        return self.steps * max(self.cycle_len, 1)

    @property
    def total(self) -> float:
        return self.t_exchange + self.t_redundant

    @property
    def per_step(self) -> float:
        """Seconds per stencil application (the argmin of the auto
        chooser)."""
        return self.total / max(self.applications, 1)

    @property
    def per_cycle(self) -> float:
        """Seconds per cycle repeat."""
        return self.total / max(self.steps, 1)


@dataclass(frozen=True)
class OverlapEstimate:
    """Predicted cost of hiding one halo exchange behind compute, for
    one overlap mode.

    ``monolithic`` waits for the fused collective then applies every
    rim region: ``max(wire, core) + sum(rims)``.  ``region`` drains
    delta classes as they complete and computes each rim region as soon
    as its dependency classes have landed, on a single compute
    resource: the core runs first, then rims in ready order, each
    starting at ``max(busy, ready)``.  ``class_completions`` is the
    per-class wire completion profile the region simulation consumed
    (:meth:`PerfModel.price_class_completions`)."""

    mode: str
    t_total: float
    t_core: float
    t_wire: float
    t_rims: Tuple[float, ...] = ()
    class_completions: Tuple[float, ...] = ()


class _Interp2D:
    """Bilinear interpolation on a sparse (log2 block, log2 total) grid.

    The paper interpolates pack cost from the stride and block length of
    the datatype (§6.3); we key on (contiguous block bytes, total bytes).
    The axis vectors, the dense grid (NaN holes), and the raw point list
    are built ONCE per table; queries are a couple of searchsorteds.
    Cells with missing corners — and degenerate single-row/column grids —
    fall back to the nearest measured point rather than "no answer".
    """

    def __init__(self, table: Table2D):
        import numpy as np

        self._np = np
        pts = np.asarray(table, dtype=float)
        self.pts = pts
        self.xs = np.unique(pts[:, 0])
        self.ys = np.unique(pts[:, 1])
        grid = np.full((len(self.xs), len(self.ys)), np.nan)
        xi = np.searchsorted(self.xs, pts[:, 0])
        yi = np.searchsorted(self.ys, pts[:, 1])
        grid[xi, yi] = pts[:, 2]
        self.grid = grid

    def _nearest(self, x: float, y: float) -> float:
        np = self._np
        d = (self.pts[:, 0] - x) ** 2 + (self.pts[:, 1] - y) ** 2
        return float(self.pts[int(np.argmin(d)), 2])

    def __call__(self, x: float, y: float) -> float:
        np = self._np
        xs, ys = self.xs, self.ys
        if len(xs) < 2 or len(ys) < 2:
            return self._nearest(x, y)
        x = min(max(x, xs[0]), xs[-1])
        y = min(max(y, ys[0]), ys[-1])
        i = min(int(np.searchsorted(xs, x, side="right") - 1), len(xs) - 2)
        j = min(int(np.searchsorted(ys, y, side="right") - 1), len(ys) - 2)
        q = self.grid[i : i + 2, j : j + 2]
        if np.isnan(q).any():
            return self._nearest(x, y)
        tx = (x - xs[i]) / (xs[i + 1] - xs[i])
        ty = (y - ys[j]) / (ys[j + 1] - ys[j])
        return float(
            q[0, 0] * (1 - tx) * (1 - ty)
            + q[1, 0] * tx * (1 - ty)
            + q[0, 1] * (1 - tx) * ty
            + q[1, 1] * tx * ty
        )


class _Interp1D:
    """Piecewise-linear interpolation on a (log2 total) -> seconds table,
    clamped at the ends (same precompute-once contract as _Interp2D)."""

    def __init__(self, table: Table1D):
        import numpy as np

        self._np = np
        pts = np.asarray(sorted(table), dtype=float)
        self.xs = pts[:, 0]
        self.vs = pts[:, 1]

    def __call__(self, x: float) -> float:
        return float(self._np.interp(x, self.xs, self.vs))


def _interp2d(table, x, y) -> Optional[float]:
    """Interpolated lookup on a measured 2D table (None iff empty).
    Builds the interpolator fresh — model queries go through the
    per-:class:`PerfModel` cache instead."""
    if not table:
        return None
    return _Interp2D(tuple(tuple(r) for r in table))(x, y)


class PerfModel:
    """Strategy selection per (committed type, incount, hop count).

    The per-strategy cost formulas live on the
    :class:`~repro.comm.api.Strategy` plugins themselves; this model
    supplies the shared terms (link time, measured pack tables, system
    parameters) and picks the cheapest among whatever strategies are
    registered.  Queries are pure functions of their arguments, so
    results are cached (paper §4/§6.3) — after the first call for a
    given type the decision is a dict lookup.
    """

    def __init__(self, params: SystemParams = TPU_V5E, decisions=None,
                 axis: Optional[str] = None,
                 topology: Optional[Topology] = None):
        self.params = params
        #: optional repro.measure.decisions.DecisionCache — pins choices
        #: across processes and records the audit log
        self.decisions = decisions
        #: default mesh axis whose wire table prices t_link (a model
        #: bound to a multi-axis mesh's DCN axis must not price its
        #: links with the ICI sweep); per-call override on t_link
        self.axis = axis
        #: optional rank->node map: annotated plans price each delta
        #: class by the slowest link tier it crosses and the planner may
        #: coalesce inter-tier classes (``tiered``); rebound by
        #: ``train.elastic.replan_on_remesh`` when the machine reshapes
        self.topology = topology
        self._cache: Dict[Tuple, StrategyEstimate] = {}
        # interpolators precomputed once per measured table, keyed by the
        # (frozen, hashable) table itself so their lifetime is tied to
        # this model — a process-global cache would pin every table ever
        # queried (tests, re-calibrations) for the life of the process
        self._interp: Dict[Tuple, object] = {}
        self.lookups = 0
        self.hits = 0

    @staticmethod
    def _resolve(strategy, registry=None):
        from repro.comm.api import resolve_strategy

        return resolve_strategy(strategy, registry)

    # -- measured tables ------------------------------------------------
    def _interp_for(self, table, cls):
        it = self._interp.get(table)
        if it is None:
            it = cls(table)
            self._interp[table] = it
        return it

    def _lookup2d(
        self,
        tables: Optional[Dict[str, Table2D]],
        strategy: str,
        contig: int,
        total: int,
    ) -> Optional[float]:
        if not tables or strategy not in tables or not tables[strategy]:
            return None
        return self._interp_for(tables[strategy], _Interp2D)(
            math.log2(max(contig, 1)), math.log2(max(total, 1))
        )

    def measured(self, strategy: str, contig: int, total: int) -> Optional[float]:
        """Interpolated measured pack time for a named strategy, or None
        when no calibration table covers it."""
        return self._lookup2d(self.params.pack_table, strategy, contig, total)

    def measured_unpack(
        self, strategy: str, contig: int, total: int
    ) -> Optional[float]:
        """Interpolated measured unpack time, or None when uncovered."""
        return self._lookup2d(self.params.unpack_table, strategy, contig, total)

    def measured_copy(self, nbytes: int) -> Optional[float]:
        """Interpolated measured contiguous-copy time, or None."""
        t = self.params.copy_table
        if not t:
            return None
        return self._interp_for(t, _Interp1D)(math.log2(max(nbytes, 1)))

    def measured_compress(
        self, strategy: str, nbytes: int
    ) -> Optional[Tuple[float, float]]:
        """Interpolated measured ``(compress_sec, decompress_sec)`` for
        ``nbytes`` of payload under the named wire compressor, or None
        when no compress sweep was calibrated (the compressors then
        price their codec sweep with the 2x-HBM analytic proxy).  Rows
        are (log2_total, compress_sec, decompress_sec, ratio_sample);
        the ratio column is informational — pricing ratios always come
        from a payload probe."""
        tables = self.params.compress_table
        if not tables or strategy not in tables or not tables[strategy]:
            return None
        rows = tables[strategy]
        x = math.log2(max(nbytes, 1))
        comp = self._interp_for(
            tuple((r[0], r[1]) for r in rows), _Interp1D
        )(x)
        decomp = self._interp_for(
            tuple((r[0], r[2]) for r in rows), _Interp1D
        )(x)
        return comp, decomp

    def measured_stencil(self, n_neighbors: int, nbytes: int) -> Optional[float]:
        """Interpolated measured time of one stencil application with
        ``n_neighbors`` neighbor reads over a window of ``nbytes``, or
        None when no stencil sweep was calibrated (the redundant-compute
        term then falls back to the contiguous-copy proxy)."""
        t = self.params.stencil_table
        if not t:
            return None
        return self._interp_for(t, _Interp2D)(
            math.log2(max(n_neighbors, 1)), math.log2(max(nbytes, 1))
        )

    # -- per-strategy terms (delegate to the registered plugin) ---------
    def t_pack(self, ct: CommittedType, incount: int, strategy) -> float:
        return self._resolve(strategy).model_pack(self, ct, incount)

    def t_unpack(self, ct: CommittedType, incount: int, strategy) -> float:
        return self._resolve(strategy).model_unpack(self, ct, incount)

    # -- link term ------------------------------------------------------
    def _axis_wire(self, axis: Optional[str]):
        """(table, fitted latency, fitted bw) pricing one link on
        ``axis`` (default: the model's bound axis): the per-axis sweep
        when one covers the axis, else the flat axis-agnostic table."""
        p = self.params
        axis = axis if axis is not None else self.axis
        if axis is not None and p.wire_tables and axis in p.wire_tables:
            fit = (p.wire_fits or {}).get(axis) or (None, None)
            return p.wire_tables[axis], fit[0], fit[1]
        return p.wire_table, p.wire_latency, p.wire_bw

    def _class_wire(self, axis: Optional[str], link_class: Optional[str]):
        """(table, fitted latency, fitted bw) for one link CLASS of the
        two-level hierarchy: the ``"<axis>/<class>"`` sweep when one
        covers it, else the class-wide ``"<class>"`` sweep, else the
        per-axis/flat fallback — so a flat calibration prices every
        class as ``intra`` and ``link_class=None`` is bit-identical to
        the pre-hierarchy model."""
        p = self.params
        if link_class is not None and p.link_tables:
            a = axis if axis is not None else self.axis
            keys = ((f"{a}/{link_class}",) if a is not None else ())
            for key in keys + (link_class,):
                if p.link_tables.get(key):
                    fit = (p.link_fits or {}).get(key) or (None, None)
                    return p.link_tables[key], fit[0], fit[1]
        return self._axis_wire(axis)

    def _hop_latency(self, axis: Optional[str] = None) -> float:
        _, lat, _ = self._axis_wire(axis)
        return lat if lat is not None else self.params.ici_latency

    def t_link(self, nbytes: int, hops: int = 1,
               axis: Optional[str] = None,
               link_class: Optional[str] = None) -> float:
        p = self.params
        table, wire_lat, wire_bw = self._class_wire(axis, link_class)
        if table:
            # measured one-hop collective time; extra hops add the fitted
            # (or analytic) latency floor, not another bandwidth term
            interp = self._interp_for(table, _Interp1D)
            x = math.log2(max(nbytes, 1))
            t = interp(x)
            end = float(interp.xs[-1])
            if x > end:
                # past the measured grid: charge the fitted (or analytic)
                # bandwidth for the excess bytes instead of flat-clamping
                # — a 64 MiB transfer must not price like the 4 MiB grid
                # ceiling (it would hand every large object to bounding)
                bw = wire_bw if wire_bw else p.ici_bw
                t += (nbytes - 2.0 ** end) / bw
            lat = wire_lat if wire_lat is not None else p.ici_latency
            return t + (hops - 1) * lat
        return hops * p.ici_latency + nbytes / p.ici_bw

    # -- exchange pricing (exact-byte wire plans) -----------------------
    def _tier_surcharge(self, nbytes: int, axis: Optional[str]) -> float:
        """Extra seconds ``nbytes`` cost for crossing the slow tier
        instead of the fast one — exactly 0.0 when the tiers price
        equally (the inter == intra oracle), clamped at 0 so a noisy
        calibration never pays agents to cross nodes."""
        return max(
            0.0,
            self.t_link(nbytes, 1, axis, link_class="inter")
            - self.t_link(nbytes, 1, axis, link_class="intra"),
        )

    def _price_schedule(self, plan, schedule: str,
                        axis: Optional[str] = None) -> float:
        """Predicted seconds of ``plan``'s layout under ``schedule``.

        Flat plans (no ``link_classes`` annotation) price exactly as the
        pre-hierarchy model: the link term on the bytes the schedule
        issues plus one launch latency per extra collective.  Annotated
        plans price each delta class by the slowest tier it crosses —
        the base stays on the fast (``intra``) tier and every
        inter-crossing class (grouped), coalesced bundle (tiered), or
        whole fused collective touching any inter edge (uniform/ragged)
        adds the tier *surcharge* for its bytes.  The formulation makes
        the oracle exact: with ``inter == intra`` tables every surcharge
        is 0.0 and the annotated prices equal the flat ones bit-for-bit.
        """
        lat = self._hop_latency(axis)
        lc = getattr(plan, "link_classes", None)
        base_class = "intra" if lc else None
        if schedule == "grouped":
            t = self.t_link(plan.wire_bytes, 1, axis, link_class=base_class)
            t += (plan.ngroups - 1) * lat
            if lc:
                for g, c in enumerate(lc):
                    if c == "inter":
                        t += self._tier_surcharge(plan.groups[g].nbytes, axis)
            return t
        if schedule == "tiered":
            if not lc:
                raise ValueError(
                    "schedule 'tiered' needs a topology-annotated plan"
                )
            # grouped-relative: swap the per-class slow-tier surcharges
            # for per-BUNDLE ones (one slow message per peer node — the
            # coalescing win is one slow latency per merged class), and
            # pay the fast tier for the correction bytes every
            # non-representative bundle member re-transmits on-node
            t = self._price_schedule(plan, "grouped", axis)
            for g, c in enumerate(lc):
                if c == "inter":
                    t -= self._tier_surcharge(plan.groups[g].nbytes, axis)
            for b in plan.tier_bundles:
                t += self._tier_surcharge(
                    sum(plan.groups[g].nbytes for g in b), axis
                )
            t += max(
                0.0,
                self.t_link(plan.wire_bytes + plan.correction_bytes, 1,
                            axis, link_class="intra")
                - self.t_link(plan.wire_bytes, 1, axis, link_class="intra"),
            )
            return t
        if schedule == "varlen":
            # the grouped transport with each class truncated at its
            # probed stream length: the link term runs on the EFFECTIVE
            # bytes (the compressed wire-byte saving), the per-class
            # launch latencies stay — the pack-side compress cost rides
            # the strategy estimates (PerfModel.select with a probe),
            # not the schedule, exactly as pack costs do for every
            # other schedule
            stream = getattr(plan, "stream_bytes", ())
            if len(stream) != plan.ngroups:
                raise ValueError(
                    "schedule 'varlen' needs a stream-annotated plan"
                )
            t = self.t_link(sum(stream), 1, axis, link_class=base_class)
            t += (plan.ngroups - 1) * lat
            if lc:
                for g, c in enumerate(lc):
                    if c == "inter":
                        t += self._tier_surcharge(stream[g], axis)
            return t
        if schedule == "uniform":
            issued = plan.nranks * plan.seg_bytes
        elif schedule == "ragged":
            issued = plan.wire_bytes
        else:
            raise ValueError(f"unknown wire schedule {schedule!r}")
        t = self.t_link(issued, 1, axis, link_class=base_class)
        if lc and any(c == "inter" for c in lc):
            # one fused collective: its slowest edge crosses nodes, so
            # the whole issued payload pays the slow tier
            t += self._tier_surcharge(issued, axis)
        return t

    def price_exchange(self, plan, axis: Optional[str] = None,
                       note: str = "") -> StrategyEstimate:
        """Price a :class:`~repro.comm.wireplan.WirePlan`: the link term
        for the bytes its schedule actually issues, plus the per-extra-
        collective latency of the grouped schedule (plus the slow-tier
        surcharges when the plan carries a topology annotation).  The
        estimate (byte count included) is recorded once per plan
        fingerprint in the attached decision cache, so audits show the
        true transfer size of every fused exchange; ``note`` is appended
        to the audit signature (the schedule chooser records the prices
        of the alternatives it rejected)."""
        t = self._price_schedule(plan, plan.schedule, axis)
        est = StrategyEstimate(
            f"wire/{plan.schedule}", 0.0, t, 0.0, wire_bytes=plan.issued_bytes
        )
        if self.decisions is not None:
            key = (plan.fingerprint, plan.ngroups, plan.wire_ops, True)
            if self.decisions.lookup(*key) is None:
                topo = getattr(plan, "topology", None)
                topo_tag = (
                    f" topo={topo.fingerprint}" if topo is not None else ""
                )
                stream_tag = ""
                if plan.schedule == "varlen":
                    # pin the probed compression alongside the topology:
                    # the drift audit re-reads this ratio from the
                    # signature and compares it to the achieved-ratio
                    # telemetry ring
                    stream_tag = (
                        f" stream_bytes={plan.effective_wire_bytes}"
                        f" ratio={plan.stream_ratio:.4f}"
                    )
                self.decisions.record(
                    *key,
                    est,
                    signature=(
                        f"exchange schedule={plan.schedule}"
                        f" groups={plan.ngroups} ranks={plan.nranks}"
                        f" ragged_bytes={plan.wire_bytes}"
                        f"{stream_tag}{topo_tag}{note}"
                    ),
                )
        return est

    def price_wire_schedules(
        self, plan, axis: Optional[str] = None, native: Optional[bool] = None
    ) -> Dict[str, float]:
        """Predicted seconds for every wire schedule that could carry the
        plan's layout (ROADMAP: model-priced ``uniform`` vs ``grouped``).

        ``grouped`` pays one collective launch per delta class on the
        exact ragged bytes; ``uniform`` pays a single launch on the
        row-equalized (padded) bytes; ``ragged`` — when the running JAX
        has the native collective — pays one launch on the exact bytes.
        The byte terms come from the measured per-axis wire tables when
        calibration filled them, so the trade is priced on the system
        actually running, not on a byte-exactness rule.

        The large-grid threshold still applies: past
        ``GROUPED_FALLBACK_RANK_FACTOR x ngroups`` ranks the fused
        layouts are mostly zero rows / dead per-peer metadata — a cost
        the per-byte link model cannot see — so only ``grouped`` (and,
        on a topology-annotated plan, ``tiered``) is a candidate there,
        exactly as in the exact ladder.

        Topology-annotated plans with at least one inter-crossing class
        additionally price ``tiered`` — the per-peer-node coalesced
        schedule.  Candidate order puts ``grouped`` first so exact price
        ties resolve to it (coalescing must *win*, not draw, to buy its
        correction hops), which is also what keeps the inter == intra
        oracle bit-for-bit.
        """
        from repro.comm.wireplan import (
            GROUPED_FALLBACK_RANK_FACTOR,
            has_ragged_all_to_all,
        )

        if native is None:
            native = has_ragged_all_to_all()

        costs = {"grouped": self._price_schedule(plan, "grouped", axis)}
        stream = getattr(plan, "stream_bytes", ())
        if len(stream) == plan.ngroups and sum(stream) < plan.wire_bytes:
            # the length-aware grouped transport: available whenever a
            # payload probe annotated the plan with a genuinely shorter
            # stream (it is per-class sends, so the large-grid fallback
            # does not exclude it); grouped stays first so a zero-saving
            # tie resolves to the plain transport
            costs["varlen"] = self._price_schedule(plan, "varlen", axis)
        lc = getattr(plan, "link_classes", None)
        if lc and plan.tier_bundles:
            costs["tiered"] = self._price_schedule(plan, "tiered", axis)
        oversize = (
            plan.ngroups
            and plan.nranks > GROUPED_FALLBACK_RANK_FACTOR * plan.ngroups
        )
        if plan.fused and not oversize:
            costs["uniform"] = self._price_schedule(plan, "uniform", axis)
            if native:
                costs["ragged"] = self._price_schedule(plan, "ragged", axis)
        return costs

    def choose_wire_schedule(
        self, plan, axis: Optional[str] = None, native: Optional[bool] = None
    ):
        """Re-schedule a plan onto the model-cheapest feasible wire
        schedule.  Returns ``(plan, costs)`` — the (possibly rescheduled)
        plan plus the per-schedule price table that justified it."""
        from repro.comm.wireplan import reschedule

        costs = self.price_wire_schedules(plan, axis, native)
        best = min(costs, key=costs.get)
        return reschedule(plan, best), costs

    # -- simulated-scale pricing (the 3072-process regime, no hardware) -
    def at_scale(
        self,
        ranks: int,
        nodes: Optional[int] = None,
        *,
        ranks_per_node: Optional[int] = None,
        interior: Tuple[int, int, int] = (8, 8, 8),
        radius: int = 1,
        element_bytes: int = 4,
        axis: Optional[str] = None,
        native: Optional[bool] = None,
        pin: bool = True,
    ):
        """Price the halo exchange the paper's scaling study runs — a 3D
        periodic stencil on a ``ranks``-process grid — *from the
        measured tables alone*, no devices.  ``nodes`` (or
        ``ranks_per_node``) shapes the two-level topology; the process
        grid is the pencil decomposition ``(nodes, fy, fx)`` with one
        leading-axis slab per node, so leading-axis classes cross the
        slow tier and everything else stays on-node (see
        ``repro.comm.scale``).  Sweeping ``ranks`` gives the predicted
        schedule *ladder* per scale — the CI artifact that lets a
        single-host container assert "at 3072 ranks the model flips to
        tier-coalesced".

        The winning schedule is pinned as a ``wire/<schedule>`` decision
        keyed by a fingerprint that includes the topology fingerprint —
        an existing pin short-circuits the choice (``pinned=True``), so
        a reshape-then-replay is detectable and an elastic replan
        (``train.elastic.replan_on_remesh``) provably re-prices.
        Returns a :class:`repro.comm.scale.ScaleEstimate`.
        """
        from repro.comm.scale import ScaleEstimate, build_scale_plan

        ranks = int(ranks)
        if ranks_per_node is None:
            nodes = int(nodes) if nodes else 1
            if ranks % nodes:
                raise ValueError(
                    f"ranks={ranks} does not split over nodes={nodes}"
                )
            ranks_per_node = ranks // nodes
        plan = build_scale_plan(
            ranks,
            ranks_per_node,
            interior=interior,
            radius=radius,
            element_bytes=element_bytes,
        )
        costs = self.price_wire_schedules(plan, axis, native)
        best = min(costs, key=costs.get)
        key_src = (
            "atscale.v1", ranks, plan.topology.nnodes, plan.grid,
            tuple(interior), int(radius), int(element_bytes),
            plan.topology.fingerprint,
        )
        fp = hashlib.sha256(repr(key_src).encode()).hexdigest()[:16]
        pinned = False
        if pin and self.decisions is not None:
            row = self.decisions.lookup(fp, 0, 1, True)
            if row is not None and row.strategy.startswith("wire/"):
                sched = row.strategy.split("/", 1)[1]
                if sched in costs:
                    best, pinned = sched, True
            if not pinned:
                self.decisions.record(
                    fp, 0, 1, True,
                    StrategyEstimate(
                        f"wire/{best}", 0.0, costs[best], 0.0,
                        wire_bytes=plan.wire_bytes,
                    ),
                    signature=(
                        f"atscale ranks={ranks} nodes={plan.topology.nnodes}"
                        f" grid={plan.grid} classes={plan.ngroups}"
                        f" topo={plan.topology.fingerprint} "
                        + " ".join(
                            f"{s}:{c:.3e}" for s, c in sorted(costs.items())
                        )
                    ),
                )
        n_inter = sum(1 for c in plan.link_classes if c == "inter")
        return ScaleEstimate(
            ranks=ranks,
            nodes=plan.topology.nnodes,
            grid=plan.grid,
            schedule=best,
            costs=dict(costs),
            wire_bytes=plan.wire_bytes,
            correction_bytes=plan.correction_bytes,
            inter_messages={
                "grouped": n_inter,
                "tiered": len(plan.tier_bundles),
            },
            fingerprint=fp,
            pinned=pinned,
        )

    # -- region-split overlap pricing -----------------------------------
    def _stencil_seconds(self, n_neighbors: int, nbytes: int) -> float:
        """Seconds of one ``n_neighbors``-point stencil application over
        a window of ``nbytes`` — the measured stencil sweep when
        calibrated, else the same contiguous-copy / HBM proxy the
        redundant-compute term falls back to."""
        if nbytes <= 0:
            return 0.0
        t_app = self.measured_stencil(n_neighbors, nbytes)
        if t_app is not None:
            return t_app
        touches = n_neighbors + 2
        copy = self.measured_copy(nbytes)
        per_touch = (
            copy / 2.0 if copy is not None else nbytes / self.params.hbm_bw
        )
        return touches * per_touch

    def price_class_completions(
        self, plan, axis: Optional[str] = None
    ) -> Tuple[float, ...]:
        """Predicted completion time of each delta class of ``plan``,
        measured from issue.  Under the grouped schedule class ``k``
        rides the ``k``-th per-class collective: it cannot complete
        before every earlier class's bytes are on the link
        (``class_cum_bytes``) plus one launch latency per earlier
        collective — the profile that makes region-split overlap
        worthwhile.  The fused schedules (uniform/ragged) complete every
        class together at the whole-collective time."""
        lat = self._hop_latency(axis)
        if plan.schedule == "grouped":
            return tuple(
                self.t_link(cum, 1, axis) + k * lat
                for k, cum in enumerate(plan.class_cum_bytes)
            )
        t = self._price_schedule(plan, plan.schedule, axis)
        return (t,) * plan.ngroups

    def price_overlap(
        self,
        plan,
        regions: Sequence[Tuple[int, Sequence[int]]],
        core_bytes: int,
        n_neighbors: int,
        axis: Optional[str] = None,
    ) -> Dict[str, OverlapEstimate]:
        """Price both overlap modes for one exchange-hiding stencil
        application.  ``regions`` describes the rim regions as
        ``(window_bytes, dep_class_ids)`` pairs — geometry stays in the
        halo layer; the model only sees bytes and dependencies.
        ``core_bytes`` is the core window (computable with no halo) and
        ``n_neighbors`` the stencil's neighbor count.

        Both modes run compute on a single resource.  ``monolithic``
        blocks on the fused wire: ``max(wire, core) + sum(rims)``.
        ``region`` starts the core at issue and each rim at
        ``max(resource free, its classes' completion)`` — the win is
        bounded by the spread of the per-class completion profile.
        """
        completions = self.price_class_completions(plan, axis)
        t_wire = max(completions) if completions else 0.0
        t_core = self._stencil_seconds(n_neighbors, core_bytes)
        rims = tuple(
            self._stencil_seconds(n_neighbors, rb) for rb, _ in regions
        )

        def ready(i: int) -> float:
            deps = regions[i][1]
            return max((completions[c] for c in deps), default=0.0)

        mono = max(t_wire, t_core) + sum(rims)
        busy = t_core
        for i in sorted(range(len(regions)), key=ready):
            busy = max(busy, ready(i)) + rims[i]
        return {
            "monolithic": OverlapEstimate(
                "monolithic", mono, t_core, t_wire, rims, completions
            ),
            "region": OverlapEstimate(
                "region", max(busy, t_wire), t_core, t_wire, rims,
                completions
            ),
        }

    def choose_overlap_mode(
        self,
        plan,
        regions: Sequence[Tuple[int, Sequence[int]]],
        core_bytes: int,
        n_neighbors: int,
        axis: Optional[str] = None,
    ) -> Tuple[str, Dict[str, OverlapEstimate], bool]:
        """Pick monolithic vs region-split overlap for one exchange,
        pinned as an ``overlap/mode=...`` decision exactly like the
        ``program/s=N`` depth choice: a cache hit with that strategy
        prefix short-circuits pricing (returns ``pinned=True``); a miss
        prices both modes on the system tables, records the choice with
        the rejected price in the signature, and returns it.  Ties go to
        ``monolithic`` — region-split must *win*, not draw, to buy its
        extra scheduling machinery."""
        regions = tuple(
            (int(rb), tuple(sorted(int(c) for c in deps)))
            for rb, deps in regions
        )
        key_src = (
            "overlap.v1", plan.fingerprint, int(core_bytes),
            int(n_neighbors), regions,
        )
        fp = hashlib.sha256(repr(key_src).encode()).hexdigest()[:16]
        ests = self.price_overlap(
            plan, regions, core_bytes, n_neighbors, axis
        )
        if self.decisions is not None:
            pin = self.decisions.lookup(fp, 0, 1, True)
            if pin is not None and pin.strategy.startswith("overlap/mode="):
                mode = pin.strategy.split("=", 1)[1]
                if mode in ests:
                    return mode, ests, True
        mode = (
            "region"
            if ests["region"].t_total < ests["monolithic"].t_total
            else "monolithic"
        )
        if self.decisions is not None:
            best = ests[mode]
            self.decisions.record(
                fp, 0, 1, True,
                StrategyEstimate(
                    f"overlap/mode={mode}",
                    t_pack=best.t_core + sum(best.t_rims),
                    t_link=best.t_wire,
                    t_unpack=0.0,
                    wire_bytes=plan.issued_bytes,
                ),
                signature=(
                    f"overlap plan={plan.fingerprint}"
                    f" classes={plan.ngroups} regions={len(regions)}"
                    f" core_B={int(core_bytes)} "
                    + " ".join(
                        f"{m}:{e.t_total:.3e}"
                        for m, e in sorted(ests.items())
                    )
                ),
            )
        return mode, ests, False

    # -- deep-halo program pricing (exchange vs redundant compute) ------
    def _redundant_time(
        self, n_neighbors: int, window_bytes: int, red_bytes: int
    ) -> float:
        """Seconds of redundant ghost-shell work inside one application
        whose full window is ``window_bytes`` of which ``red_bytes`` are
        shell cells some neighbor also computes.

        Preferred source: the measured stencil-application sweep
        (``SystemParams.stencil_table``) — the per-byte rate of a real
        ``n_neighbors``-point application at this window size, times the
        redundant bytes.  Fallback (no sweep calibrated): the
        contiguous-copy proxy — ``n_neighbors + 2`` touches per cell, a
        touch being half a measured copy (read + write), else analytic
        HBM bandwidth.
        """
        t_app = self.measured_stencil(n_neighbors, window_bytes)
        if t_app is not None and window_bytes > 0:
            return t_app * (red_bytes / window_bytes)
        touches = n_neighbors + 2
        copy = self.measured_copy(red_bytes)
        per_touch = (
            copy / 2.0 if copy is not None else red_bytes / self.params.hbm_bw
        )
        return touches * per_touch

    def price_program(
        self,
        plan,
        interior: Tuple[int, int, int],
        op_radii,
        n_neighbors,
        steps: int,
        element_bytes: int = 4,
        t_members: float = 0.0,
        axis: Optional[str] = None,
    ) -> ProgramEstimate:
        """Price one deep-halo iteration: ONE exchange at halo depth
        ``steps * cycle_radii`` (wire plan ``plan``, member pack/unpack
        time ``t_members``) amortized over ``steps`` repeats of an op
        cycle, against the redundant ghost-shell re-evaluation the
        shrinking valid region pays.

        ``op_radii`` is one per-dimension radii tuple (the single-op
        program) or a *sequence* of them — the cycle ``[op_1..op_k]`` in
        application order — with ``n_neighbors`` an int or matching
        sequence.  Application ``j`` of the flattened ``steps * k``
        schedule writes interior plus a shell of ``total - cum_j`` per
        dimension (``total`` the full halo depth, ``cum_j`` the radii of
        applications ``1..j`` summed) — every shell cell is a cell some
        neighbor also computes, i.e. pure redundancy bought to skip the
        other exchanges.  Redundant time is priced from the measured
        stencil sweep when calibration filled it, else the contiguous-
        copy proxy (see :meth:`_redundant_time`); per-op splits land in
        :attr:`ProgramEstimate.op_redundant`.  Compare ``per_step``
        across candidate depths to pick ``s`` — ``price_program`` never
        guesses, it prices the same tables every other selection uses.
        """
        if op_radii and isinstance(op_radii[0], (tuple, list)):
            cycle = [tuple(r) for r in op_radii]
        else:
            cycle = [tuple(op_radii)]
        if isinstance(n_neighbors, (tuple, list)):
            neighbors = [int(n) for n in n_neighbors]
        else:
            neighbors = [int(n_neighbors)] * len(cycle)
        if len(neighbors) != len(cycle):
            raise ValueError(
                f"n_neighbors ({len(neighbors)}) must match the cycle "
                f"length ({len(cycle)})"
            )
        wire = self._price_schedule(plan, plan.schedule, axis)
        t_exchange = t_members + wire
        interior_cells = math.prod(interior)
        total = tuple(steps * sum(r[d] for r in cycle) for d in range(3))
        op_red = [0.0] * len(cycle)
        cum = (0, 0, 0)
        for j in range(steps * len(cycle)):
            pos = j % len(cycle)
            cum = tuple(c + r for c, r in zip(cum, cycle[pos]))
            shell = tuple(t - c for t, c in zip(total, cum))
            cells = math.prod(n + 2 * s for n, s in zip(interior, shell))
            red_bytes = (cells - interior_cells) * element_bytes
            if red_bytes <= 0:
                continue
            op_red[pos] += self._redundant_time(
                neighbors[pos], cells * element_bytes, red_bytes
            )
        return ProgramEstimate(
            steps=steps,
            t_exchange=t_exchange,
            t_redundant=sum(op_red),
            wire_bytes=plan.issued_bytes,
            cycle_len=len(cycle),
            op_redundant=tuple(op_red),
        )

    # -- full strategy estimates (Eqs. 1-3 analogue) ----------------------
    def estimate(
        self, ct: CommittedType, incount: int, strategy, hops: int = 1
    ) -> StrategyEstimate:
        return self._resolve(strategy).plan(self, ct, incount, hops)

    def select(
        self,
        ct: CommittedType,
        incount: int = 1,
        hops: int = 1,
        allow_bounding: bool = True,
        registry=None,
        probe=None,
    ) -> StrategyEstimate:
        """Pick the cheapest applicable registered strategy (cached per
        call signature).  ``allow_bounding`` admits wire-only strategies
        (data actually crosses a link, so shipping the bounding window
        is meaningful).

        ``probe`` (a *concrete* payload sample) turns on length-aware
        pricing: every ``supports_varlen`` candidate's link term is
        priced at its probed stream length instead of its capacity —
        the only way a lossless compressor (whose capacity is strictly
        larger than the packed bytes) can ever win a selection.  The
        probed stream lengths key the selection cache, and a probed win
        records its stream bytes + ratio in the decision signature."""
        if registry is None:
            from repro.comm.api import default_registry

            registry = default_registry()
        # keyed on the type's CONTENT fingerprint (not id(ct): equal
        # structures share decisions across registries and processes) and
        # the strategy registry's mutation counter so a newly registered
        # plugin invalidates prior selections
        sig = ct.fingerprint
        streams = {}
        if probe is not None:
            for s in registry.selectable():
                if getattr(s, "supports_varlen", False) and s.applicable(ct):
                    stream = int(s.probe_stream_bytes(ct, incount, probe))
                    if stream < s.wire_bytes(ct, incount):
                        streams[s.name] = stream
        key = (sig, incount, hops, allow_bounding, id(registry),
               registry.version, tuple(sorted(streams.items())))
        self.lookups += 1
        hit = self._cache.get(key)
        if hit is not None:
            self.hits += 1
            return hit
        def plan_est(s):
            e = s.plan(self, ct, incount, hops)
            stream = streams.get(s.name)
            if stream is None:
                return e
            # re-price the link term at the probed stream length:
            # pack-side compress cost stays in t_pack, the wire-byte
            # saving lands in t_link — the honest pack-vs-wire trade
            return StrategyEstimate(
                e.strategy, e.t_pack, self.t_link(stream, hops),
                e.t_unpack, wire_bytes=stream,
            )

        pinned = None
        if self.decisions is not None:
            pinned = self.decisions.lookup(sig, incount, hops, allow_bounding)
        if pinned is not None and pinned.strategy in registry:
            best = plan_est(registry.get(pinned.strategy))
        else:
            cands = [
                s
                for s in registry.selectable()
                if (allow_bounding or not s.wire_only) and s.applicable(ct)
            ]
            if not cands:
                raise ValueError(f"no applicable strategy registered for {ct!r}")
            best = min((plan_est(s) for s in cands), key=lambda e: e.total)
            if self.decisions is not None:
                signature = None
                if best.strategy in streams:
                    from repro.measure.decisions import describe_type

                    ratio = streams[best.strategy] / max(
                        registry.get(best.strategy).wire_bytes(ct, incount), 1
                    )
                    signature = (
                        f"{describe_type(ct)}"
                        f" stream_bytes={streams[best.strategy]}"
                        f" ratio={ratio:.4f}"
                    )
                self.decisions.record(
                    sig, incount, hops, allow_bounding, best, ct=ct,
                    signature=signature,
                )
        self._cache[key] = best
        return best
