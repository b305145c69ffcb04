"""A halo cell's run, end to end at a tiny size on the CPU: the program
agrees with the plain reference, and each fault the cell can have,
planted in the timed path, turns ``correct`` false.  The control (the
reference in bfloat16) fails the limit too."""

import dataclasses
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

SEED = 2**31 + 3


def tiny(name, **traffic):
    cell = run.load_cell(name)
    return dataclasses.replace(
        cell, config=dict(cell.config, interior=[8, 8, 8]),
        traffic=dict(cell.traffic, **traffic))


def run_tiny(tmp_path, cell=None):
    import jax

    from repro.comm.perfmodel import TPU_V5E

    cell = cell or tiny("halo3d-512.r1")
    return run.run_cell(cell, SEED, 0.3, False, jax.devices()[:1],
                        params=TPU_V5E, state=tmp_path / "state")


def test_sound_run_is_correct(tmp_path):
    res = run_tiny(tmp_path)
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "halo_cycle_ms"}
    assert list(res)[-1] == "checks"


def test_check_survives_a_step_that_donates_its_input(tmp_path,
                                                     monkeypatch):
    """The sampled iteration is copied to the host around its call, so a
    step that donates the field leaves the check whole."""
    import jax

    import repro.halo as halo

    make = halo.make_program_step
    monkeypatch.setattr(halo, "make_program_step", lambda *a, **k: jax.jit(
        make(*a, **k), donate_argnums=0))
    res = run_tiny(tmp_path)
    assert res["correct"] is True
    assert res["checks"]["max_rel_err"]["value"] == 0.0


def test_window_closing_before_the_drawn_iteration_runs_on_to_it(tmp_path):
    import jax

    from repro.comm.perfmodel import TPU_V5E

    cell = tiny("halo3d-512.r1")
    driver = run.load_module(BENCH / "drivers" / "halo_program.py")
    session = driver.Session(cell.config, cell.traffic, SEED,
                             jax.devices()[:1], tmp_path, 0.5, params=TPU_V5E)
    latencies, _, _ = run.drive(session, 0.0)
    assert len(latencies) == 1 and session.sample_at >= 1
    got = session.release_and_check()
    assert session.calls == session.sample_at + 1
    assert got["max_rel_err"] <= cell.limits["max_rel_err"]


def test_state_left_unchanged_is_caught(tmp_path, monkeypatch):
    from repro.halo.program import HaloProgram

    monkeypatch.setattr(HaloProgram, "iteration",
                        lambda self, local, *a, **k: local)
    assert run_tiny(tmp_path)["correct"] is False


def test_exchange_left_out_is_caught(tmp_path, monkeypatch):
    import repro.halo.program as program

    monkeypatch.setattr(program, "halo_exchange", lambda local, *a, **k: local)
    assert run_tiny(tmp_path)["correct"] is False


def test_answer_altered_where_produced_is_caught(tmp_path, monkeypatch):
    import repro.halo.program as program

    cycle = program.stencil_cycle

    def altered(local, *a, **k):
        out = cycle(local, *a, **k)
        return out.at[tuple(n // 2 for n in out.shape)].add(1e-3)

    monkeypatch.setattr(program, "stencil_cycle", altered)
    res = run_tiny(tmp_path)
    assert res["correct"] is False
    assert res["checks"]["max_rel_err"]["value"] > 1e-4


def test_control_fails_the_limit(tmp_path):
    import jax

    from repro.comm.perfmodel import TPU_V5E

    cell = tiny("halo3d-512.r1")
    driver = run.load_module(BENCH / "drivers" / "halo_program.py")
    store = tmp_path / "store"
    store.mkdir()
    session = driver.Session(cell.config, cell.traffic, SEED,
                             jax.devices()[:1], store, 0.2, params=TPU_V5E)
    run.drive(session, 0.2)
    got = session.release_and_check(control=True)
    limit = cell.limits["max_rel_err"]
    assert got["max_rel_err"] <= limit < got["control.max_rel_err"]
