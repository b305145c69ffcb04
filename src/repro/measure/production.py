"""Production entry-point wiring: measured params + pinned decisions.

The §6.3 lifecycle for a long-running job in one call: the first run
calibrates (or loads a prior calibration for this system fingerprint)
and records every strategy selection it makes; the decisions file is
saved next to the params store, so every later run of the same job
**pins** those selections and never consults the model again.  The
``launch.train`` / ``launch.serve`` drivers construct their communicator
through this module.

    comm, save = production_communicator(axis_name="data")
    ... run the job; every datatype exchange goes through `comm` ...
    save()          # persist the (possibly grown) decision file

With ``telemetry=True`` the communicator also carries an
:class:`~repro.fleet.telemetry.ExchangeTelemetry` probe whose
aggregates persist to ``telemetry.json`` next to the decisions file on
``save()`` — the observation side of the fleet feedback loop
(``python -m repro.fleet report`` renders it; ``repro.fleet.drift``
audits it).
"""

from __future__ import annotations

from pathlib import Path
from typing import Callable, Optional, Tuple, Union

from repro.comm.api import Communicator
from repro.comm.perfmodel import SystemParams, TPU_V5E
from repro.measure.decisions import DecisionCache
from repro.measure.store import ParamsStore

__all__ = ["DECISIONS_FILENAME", "production_communicator"]

#: the decisions file lives next to the params envelopes in the store
DECISIONS_FILENAME = "decisions.json"


#: ``device_kind`` of the chip the analytic ``TPU_V5E`` table describes
V5E_DEVICE_KIND = "TPU v5 lite"


def _analytic_params() -> SystemParams:
    """``TPU_V5E`` when the devices are v5e chips; an error elsewhere,
    where the analytic table would price a device it does not describe."""
    import jax

    kind = jax.devices()[0].device_kind
    if kind != V5E_DEVICE_KIND:
        raise RuntimeError(
            f"no stored calibration for this system and the analytic "
            f"table describes a {V5E_DEVICE_KIND!r}, not {kind!r}: "
            "calibrate (calibrate=True) or pass params="
        )
    return TPU_V5E


def production_communicator(
    cache_dir: Optional[Union[str, Path]] = None,
    axis_name: Optional[str] = None,
    *,
    calibrate: bool = True,
    reduced: Optional[bool] = None,
    params: Optional[SystemParams] = None,
    halo_steps: Optional[Union[int, str]] = None,
    telemetry: Union[bool, "object", None] = None,
    tracer: Union[bool, "object", None] = None,
    topology: Optional["object"] = None,
) -> Tuple[Communicator, Callable[[], Path]]:
    """A :class:`Communicator` wired for production reuse.

    Parameters
    ----------
    cache_dir: params-store root (default: ``$REPRO_MEASURE_DIR`` or the
        user cache dir — the same store ``load_or_calibrate`` uses).
    axis_name: mesh axis the communicator (and its per-axis wire
        pricing) binds to.
    calibrate: when True (default), a missing calibration for this
        system fingerprint is measured once and persisted
        (``load_or_calibrate``); when False, a missing calibration falls
        back to the analytic ``TPU_V5E`` table on a v5e and raises
        anywhere else — nothing slow happens.
    reduced: grid size for a fresh calibration; defaults to reduced
        everywhere but on a real TPU backend.
    params: explicit SystemParams override (skips the store entirely).
    halo_steps: when given (``"auto"`` or an int), installs the
        process-wide deep-halo fusion-depth default
        (:func:`repro.halo.program.set_default_halo_steps`) alongside
        the decisions cache that pins ``"auto"`` — so any
        :func:`~repro.halo.program.build_halo_program` the job runs
        resolves its depth through this seam and the choice lands in
        the same persisted decisions file.
    telemetry: ``True`` loads (or starts) the store's runtime telemetry
        (``telemetry.json``, persisted by ``save()`` alongside the
        decisions); an explicit
        :class:`~repro.fleet.telemetry.ExchangeTelemetry` instance is
        attached as-is (the caller owns persistence); ``None``/``False``
        attaches no probe.
    tracer: ``True`` attaches a fresh :class:`repro.obs.Tracer`
        (hierarchical exchange spans — export with
        :func:`repro.obs.export.save_chrome_trace`, the launch drivers'
        ``--trace PATH``); an explicit Tracer instance is attached
        as-is; ``None``/``False`` attaches none.
    topology: a :class:`repro.comm.topology.Topology` rank->node map
        (the launch drivers build one from ``--ranks-per-node``).  The
        model then prices per link class, may pick the tier-coalesced
        wire schedule, and stamps every wire/program decision with the
        topology fingerprint so pins never replay across a reshape.
        ``None`` plans flat (every hop priced equal).

    Returns ``(comm, save)``: call ``save()`` after the job to persist
    the decision cache — the file that lets the next run skip the model
    — plus the telemetry (when store-owned) and a ``metrics.json``
    snapshot of the communicator's counters
    (:func:`repro.obs.metrics.publish_comm_stats`; inspect with
    ``python -m repro.fleet stats``).
    """
    if halo_steps is not None:
        from repro.halo.program import set_default_halo_steps

        set_default_halo_steps(halo_steps)
    store = ParamsStore(cache_dir)
    if params is None:
        if calibrate:
            if reduced is None:
                import jax

                reduced = jax.default_backend() != "tpu"
            params = store.load_or_calibrate(reduced=reduced)
        else:
            params = store.load() or _analytic_params()
    decisions_path = store.root / DECISIONS_FILENAME
    decisions = DecisionCache.load(decisions_path)
    tel = None
    tel_path = None
    if telemetry is True:
        from repro.fleet.telemetry import TELEMETRY_FILENAME, ExchangeTelemetry

        tel_path = store.root / TELEMETRY_FILENAME
        tel = ExchangeTelemetry.load(tel_path)
    elif telemetry:  # an ExchangeTelemetry (or compatible) instance
        tel = telemetry
    tr = None
    if tracer is True:
        from repro.obs.trace import Tracer

        tr = Tracer()
    elif tracer:  # a Tracer (or compatible) instance
        tr = tracer
    comm = Communicator(
        axis_name=axis_name, params=params, decisions=decisions,
        telemetry=tel, tracer=tr, topology=topology,
    )

    def save() -> Path:
        if tel_path is not None:
            tel.save(tel_path)
        from repro.obs.metrics import METRICS_FILENAME, default_metrics

        comm.stats()  # publish the latest counters into the registry
        default_metrics().save(store.root / METRICS_FILENAME)
        return decisions.save(decisions_path)

    return comm, save
