"""The plain halo reference against a loop over every gridpoint, and its
layout helpers against the program's rank layout, at tiny sizes."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from reference import halo as ref  # noqa: E402

CONFIG = json.loads((BENCH / "configs" / "halo3d-512.json").read_text())


def loop_apply(u, radii, weight):
    """One op, gridpoint by gridpoint, with periodic indices."""
    nz, ny, nx = u.shape
    offs = ref.box_offsets(radii)
    out = np.empty_like(u)
    for z in range(nz):
        for y in range(ny):
            for x in range(nx):
                acc = sum(u[(z + dz) % nz, (y + dy) % ny, (x + dx) % nx]
                          for dz, dy, dx in offs)
                out[z, y, x] = (1 - weight) * u[z, y, x] + weight / len(offs) * acc
    return out


def test_box_offsets():
    assert len(ref.box_offsets((1, 1, 1))) == 26
    assert len(ref.box_offsets((2, 1, 1))) == 44
    assert ref.box_offsets((1, 1, 1))[0] == (-1, -1, -1)
    assert (0, 0, 0) not in ref.box_offsets((2, 1, 1))


def test_cycles_match_a_loop_over_gridpoints():
    rng = np.random.default_rng(0)
    u = rng.standard_normal((6, 5, 4)).astype(np.float64)
    want = u
    for _ in range(2):
        for op in CONFIG["cycle"]:
            want = loop_apply(want, op["radii"], op["weight"])
    got = np.asarray(ref.cycles(u.astype(np.float32), CONFIG["cycle"], 2))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    low = ref.cycles(u.astype(np.float32), CONFIG["cycle"], 2,
                     dtype="bfloat16")
    assert ref.max_rel_err(low, np.asarray(want, np.float32)) > 1e-4


@pytest.mark.parametrize("grid", [(1, 1, 1), (4, 1, 1), (2, 2, 1)])
def test_layout_matches_the_programs_rank_coordinates(grid):
    from repro.halo import HaloSpec

    spec = HaloSpec(grid=grid, interior=(3, 4, 5), radius=(2, 1, 1))
    nz, ny, nx = spec.interior
    rz, ry, rx = spec.radii
    az, ay, ax = spec.alloc
    glob = np.arange(np.prod(grid) * nz * ny * nx, dtype=np.float32).reshape(
        grid[0] * nz, grid[1] * ny, grid[2] * nx)
    field = np.full((spec.nranks, az, ay, ax), -1.0, np.float32)
    for r in range(spec.nranks):
        cz, cy, cx = spec.coords(r)
        field[r, rz:rz + nz, ry:ry + ny, rx:rx + nx] = glob[
            cz * nz:(cz + 1) * nz, cy * ny:(cy + 1) * ny, cx * nx:(cx + 1) * nx]
    parts = ref.interiors(field.reshape(-1, ay, ax), spec.nranks, spec.interior)
    np.testing.assert_array_equal(ref.assemble(parts, grid), glob)
