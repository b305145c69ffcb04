"""Tests: the in-launch data-axis smoother workload (ISSUE 5).

The smoother is the first in-tree launch workload that builds a
:class:`~repro.halo.program.HaloProgram`, so these tests cover the whole
``--halo-steps`` seam end to end: production communicator ->
process-default fusion depth -> cycle program -> ``program/s=N``
Decision row -> pinned, checksum-identical rerun.
"""

import numpy as np
import pytest

from repro.comm import TPU_V5E
from repro.halo import STENCIL26, get_default_halo_steps, set_default_halo_steps
from repro.launch.smoother import CYCLES, run_smoother, smoother_cycle
from repro.measure.production import production_communicator


class TestSmootherCycle:
    def test_named_cycles(self):
        assert smoother_cycle("smooth") == (STENCIL26,)
        pc = smoother_cycle("predictor-corrector")
        assert len(pc) == 2
        assert pc[0].radii == (2, 1, 1) and pc[1].radii == (1, 1, 1)
        assert set(CYCLES) == {"smooth", "predictor-corrector"}
        with pytest.raises(ValueError, match="unknown smoother cycle"):
            smoother_cycle("laplacian")


class TestRunSmoother:
    def test_records_program_decision_and_pins_rerun(self, tmp_path):
        before = get_default_halo_steps()
        try:
            comm, save = production_communicator(
                tmp_path, axis_name="data", params=TPU_V5E, halo_steps="auto"
            )
            report = run_smoother(comm, iters=1, interior=(8, 8, 8),
                                  cycle="predictor-corrector")
            assert report.decision_recorded
            assert not report.program.pinned  # first run prices, not pins
            assert report.program.cycle_len == 2
            assert np.isfinite(report.checksum)
            rows = comm.model.decisions.program_rows()
            assert len(rows) == 1
            assert rows[0].strategy == f"program/s={report.program.steps}"
            save()

            # "the rerun": a fresh production communicator over the same
            # store pins the depth and reproduces the field bit-exactly
            comm2, _ = production_communicator(
                tmp_path, axis_name="data", params=TPU_V5E, halo_steps="auto"
            )
            report2 = run_smoother(comm2, iters=1, interior=(8, 8, 8),
                                   cycle="predictor-corrector")
            assert report2.program.pinned
            assert report2.program.steps == report.program.steps
            assert report2.checksum == report.checksum
            assert report2.decision_recorded
        finally:
            set_default_halo_steps(before)

    def test_fixed_depth_and_summary(self, tmp_path):
        before = get_default_halo_steps()
        try:
            comm, _ = production_communicator(
                tmp_path, axis_name="data", params=TPU_V5E, halo_steps=1
            )
            report = run_smoother(comm, iters=2, interior=(6, 6, 6),
                                  cycle="smooth", schedule_policy="exact")
            assert report.program.steps == 1
            assert report.iterations == 2
            assert "smoother:" in report.summary
            assert "exchanges/cycle=1.00" in report.summary
            # the exact ladder on CPU devices: never the native schedule
            assert report.program.plan.wire.schedule != "ragged"
            # the returned field is the state the checksum summed
            spec = report.program.spec
            (rz, ry, rx), (nz, ny, nx) = spec.radii, spec.interior
            field = np.asarray(report.field)
            assert field.shape == (spec.nranks * spec.alloc[0],) + spec.alloc[1:]
            assert float(
                field[rz:rz + nz, ry:ry + ny, rx:rx + nx].sum()
            ) == report.checksum
        finally:
            set_default_halo_steps(before)


def test_compile_cache_placement(monkeypatch):
    """The entry points' compile cache follows JAX_COMPILATION_CACHE_DIR
    and is otherwise one fixed directory inside the checkout."""
    from pathlib import Path

    import jax

    from repro.launch import compile_cache

    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax")
        assert compile_cache.use_compile_cache() == "/elsewhere/jax"
        assert jax.config.jax_compilation_cache_dir == old  # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.use_compile_cache()
        repo = Path(__file__).resolve().parents[1]
        assert path == str(repo / ".cache" / "jax")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
