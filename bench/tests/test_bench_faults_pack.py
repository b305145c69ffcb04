"""The pack cell's run, end to end at a tiny size on the CPU: the program
agrees with the plain reference byte for byte, and each fault the cell
can have, planted in the timed path, turns ``correct`` false.  The
control (an unpack that writes whole pitch rows) fails its limit."""

import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
from reference import pack as ref  # noqa: E402

SEED = 2**31 + 5


def tiny():
    cell = run.load_cell("pack2d.1MiB-b8")
    return dataclasses.replace(cell, traffic=dict(cell.traffic, count=64))


def run_tiny(tmp_path):
    import jax

    from repro.comm.perfmodel import TPU_V5E

    return run.run_cell(tiny(), SEED, 0.3, False, jax.devices()[:1],
                        params=TPU_V5E, state=tmp_path / "state")


def test_sound_run_is_correct(tmp_path):
    res = run_tiny(tmp_path)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"setup_s", "call_us", "call_p95_us"}
    assert {k: c["value"] for k, c in res["checks"].items()} == {
        "packed_bytes_wrong": 0, "dest_bytes_wrong": 0}


def test_unpack_leaving_the_destination_unchanged_is_caught(tmp_path,
                                                            monkeypatch):
    from repro.comm.api import Communicator

    monkeypatch.setattr(Communicator, "unpack",
                        lambda self, buf, packed, ct, incount=1: buf)
    res = run_tiny(tmp_path)
    assert res["correct"] is False
    assert res["checks"]["dest_bytes_wrong"]["value"] > 0


def test_packed_byte_altered_where_produced_is_caught(tmp_path, monkeypatch):
    from repro.comm.api import Communicator

    pack = Communicator.pack

    def altered(self, buf, ct, incount=1):
        out = pack(self, buf, ct, incount)
        return out.at[3].add(np.uint8(1))

    monkeypatch.setattr(Communicator, "pack", altered)
    res = run_tiny(tmp_path)
    assert res["correct"] is False
    assert res["checks"]["packed_bytes_wrong"]["value"] == 1


def test_control_fails_the_limit(tmp_path):
    import jax

    from repro.comm.perfmodel import TPU_V5E

    cell = tiny()
    driver = run.load_module(BENCH / "drivers" / "pack_unpack.py")
    store = tmp_path / "store"
    store.mkdir()
    session = driver.Session(cell.config, cell.traffic, SEED,
                             jax.devices()[:1], store, 0.1, params=TPU_V5E)
    run.drive(session, 0.1)
    got = session.release_and_check(control=True)
    assert got["dest_bytes_wrong"] <= cell.limits["dest_bytes_wrong"]
    assert got["control.dest_bytes_wrong"] > cell.limits["dest_bytes_wrong"]


def test_reference_matches_the_program_oracle():
    """The plain gather/scatter equals ``repro.kernels.ref`` on the
    committed type's own offsets."""
    import jax.numpy as jnp

    from repro.comm.api import Communicator
    from repro.core import BYTE, Vector
    from repro.kernels.ref import pack_ref, unpack_ref

    count, blocklen, pitch = 37, 8, 512
    ct = Communicator().commit(Vector(count, blocklen, pitch, BYTE))
    rng = np.random.default_rng(0)
    src = rng.integers(0, 256, count * pitch, dtype=np.uint8)
    dst = rng.integers(0, 256, count * pitch, dtype=np.uint8)
    packed = ref.pack(src, count, blocklen, pitch)
    np.testing.assert_array_equal(
        packed, np.asarray(pack_ref(jnp.asarray(src), ct.block)))
    np.testing.assert_array_equal(
        ref.unpack(dst, packed, count, blocklen, pitch),
        np.asarray(unpack_ref(jnp.asarray(dst), jnp.asarray(packed), ct.block)))
    assert ref.member_index(2, 3, 10).tolist() == [0, 1, 2, 10, 11, 12]
