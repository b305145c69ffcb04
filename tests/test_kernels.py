"""Per-kernel allclose tests against the pure-jnp oracle (ref.py).

Sweeps shapes/dtypes per the deliverable: every Pallas kernel variant
(rows, dma) plus the XLA-blocks baseline is compared bit-exactly with the
gather oracle across 2D/3D strided blocks, word widths, offsets, and
incounts.  Kernels run in interpret mode on CPU.
"""

import numpy as np
import pytest

import jax
import jax.extend.core as jax_core
import jax.numpy as jnp

from repro.core import (
    BYTE,
    FLOAT,
    FLOAT16,
    INT16,
    INT32,
    Contiguous,
    Hvector,
    Subarray,
    TypeRegistry,
    Vector,
)
from repro.kernels import pack, plan_geometry, unpack
from repro.kernels.geometry import VMEM_BUDGET_BYTES
from repro.kernels.ops import byte_view, pack_block
from repro.kernels.ref import pack_ref, unpack_ref

REG = TypeRegistry()
RNG = np.random.default_rng(1234)

KERNEL_STRATEGIES = ("rows", "dma")
ALL_STRATEGIES = ("rows", "dma", "xla", "auto")


def rand_bytes(n):
    return jnp.asarray(RNG.integers(0, 255, size=(n,), dtype=np.uint8))


def check_roundtrip(dt, strategies=ALL_STRATEGIES, incount=1):
    ct = REG.commit(dt)
    need = ct.extent * incount
    buf = rand_bytes(need + 37)  # ragged tail on purpose
    want = np.asarray(pack_ref(buf, ct.block, incount, ct.extent))
    dst0 = rand_bytes(need + 37)
    want_dst = np.asarray(unpack_ref(dst0, jnp.asarray(want), ct.block, incount, ct.extent))
    for strat in strategies:
        got = pack(buf, ct, incount=incount, strategy=strat)
        assert got.shape == (ct.size * incount,)
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=f"pack:{strat}")
        out = unpack(dst0, got, ct, incount=incount, strategy=strat)
        np.testing.assert_array_equal(
            np.asarray(out), want_dst, err_msg=f"unpack:{strat}"
        )


# ---------------------------------------------------------------------------
# 2D sweeps (paper Fig. 7: vector/subarray objects, 512B pitch)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("blocklen_bytes", [8, 32, 100, 128, 512])
@pytest.mark.parametrize("count", [1, 2, 13, 64])
def test_pack_2d_vector_sweep(blocklen_bytes, count):
    pitch = max(512, blocklen_bytes)
    if blocklen_bytes == pitch:
        pytest.skip("fully contiguous: covered by contig test")
    check_roundtrip(Vector(count, blocklen_bytes, pitch, BYTE))


@pytest.mark.parametrize("named", [BYTE, INT16, FLOAT, FLOAT16, INT32])
def test_pack_2d_dtype_sweep(named):
    w = named.extent
    check_roundtrip(Vector(24, 96 // w, 640 // w, named))


@pytest.mark.parametrize("start", [0, 1, 3, 64, 129])
def test_pack_2d_offsets(start):
    # offsets come from subarray starts; misaligned starts force W=1
    check_roundtrip(Subarray((256, 40), (100, 24), (start, 7), BYTE))


def test_planner_rejects_straddle_and_bad_plane_stride():
    from repro.core.strided_block import StridedBlock

    # block straddles a pitch row: r + lanes > pitch
    assert plan_geometry(StridedBlock(200, (100, 5), (1, 256))) is None
    # plane stride not a whole number of pitches
    assert plan_geometry(StridedBlock(0, (8, 4, 2), (1, 32, 100))) is None
    # well-formed constructors can never produce a straddle: subarray
    # guarantees start0 + sub0 <= size0 and hvector guarantees
    # stride >= blocklength, so the aligned planner covers the whole
    # constructor subset (checked exhaustively by the property test).


# ---------------------------------------------------------------------------
# 3D sweeps (paper Fig. 1 cuboids)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "alloc,ext,starts",
    [
        ((64, 32, 16), (40, 13, 7), (8, 3, 2)),
        ((256, 8, 4), (100, 8, 4), (0, 0, 0)),   # full inner dims fold
        ((128, 16, 8), (128, 5, 3), (0, 2, 1)),  # dense rows fold to 2D
        ((512, 4, 4), (12, 3, 2), (64, 1, 1)),
        ((32, 32, 32), (4, 32, 32), (28, 0, 0)),
    ],
)
def test_pack_3d_subarray_sweep(alloc, ext, starts):
    check_roundtrip(Subarray(alloc, ext, starts, BYTE))


@pytest.mark.parametrize("named", [BYTE, FLOAT])
def test_pack_3d_halo_faces(named):
    """The 26-neighbor halo regions of the §6.4 stencil are subarrays of
    these shapes (radius-2 faces/edges/corners of a 32^3 block)."""
    n, r = 32, 2
    e = named.extent
    alloc = (n * e, n, n) if named is BYTE else (n, n, n)
    face = Subarray(alloc, (r if named is BYTE else r, n, n), (0, 0, 0), named)
    edge = Subarray(alloc, (r, r, n), (4, 4, 0), named)
    corner = Subarray(alloc, (r, r, r), (n - r, n - r, n - r), named)
    for dt in (face, edge, corner):
        check_roundtrip(dt)


@pytest.mark.parametrize("strat", ALL_STRATEGIES)
def test_pack_3d_regions_of_own_shape_buffer(strat):
    """Halo regions of a 3D float array: plane-block geometries whose
    planes are the array's own trailing dims take the no-flatten path
    (the buffer's own shape as the word view); both paths must equal the
    byte oracle."""
    alloc = (10, 9, 12)  # (z, y, x): 9-row planes admit no 8-row group
    buf = jnp.asarray(RNG.normal(size=alloc).astype(np.float32))
    dst0 = jnp.asarray(RNG.normal(size=alloc).astype(np.float32))
    for sub, start in (((2, 5, 3), (0, 2, 9)), ((3, 2, 12), (7, 0, 0))):
        ct = REG.commit(Subarray(alloc, sub, start, FLOAT, order="C"))
        want = np.asarray(pack_ref(byte_view(buf), ct.block))
        got = pack(buf, ct, strategy=strat)
        np.testing.assert_array_equal(np.asarray(got), want)
        out = unpack(dst0, got, ct, strategy=strat)
        want_dst = unpack_ref(byte_view(dst0), jnp.asarray(want), ct.block)
        np.testing.assert_array_equal(
            np.asarray(byte_view(out)), np.asarray(want_dst)
        )


@pytest.mark.parametrize("incount", [1, 2, 3])
def test_incount(incount):
    check_roundtrip(Vector(6, 20, 50, BYTE), incount=incount)
    check_roundtrip(
        Subarray((64, 8, 4), (16, 4, 2), (4, 1, 1), BYTE),
        strategies=("rows", "dma", "auto"),
        incount=incount,
    )


def test_contig_and_1d():
    check_roundtrip(Contiguous(1000, FLOAT), strategies=("auto",))
    check_roundtrip(Subarray((4096,), (100,), (30,), BYTE), strategies=("auto",))


def test_user_dtype_buffers():
    """pack accepts arbitrarily-shaped/typed user arrays (byte view)."""
    ct = REG.commit(Vector(8, 16, 48, FLOAT))
    buf = jnp.asarray(RNG.normal(size=(64, 64)).astype(np.float32))
    got = pack(buf, ct)
    want = np.asarray(pack_ref(byte_view(buf), ct.block))
    np.testing.assert_array_equal(np.asarray(got), want)
    out = unpack(jnp.zeros((64, 64), jnp.float32), got, ct)
    assert out.shape == (64, 64) and out.dtype == jnp.float32


# ---------------------------------------------------------------------------
# kernels at the buffer's own element width
# ---------------------------------------------------------------------------

#: (label, datatype, incount): byte objects whose geometry's word is 4
#: bytes; the row group (64, 32, 16 or 8 rows) decides whether a uint8
#: or 16-bit buffer runs the kernels at its own width or keeps the
#: 32-bit word path
NARROW_OBJECTS = [
    ("b4 p256", Vector(64, 4, 256, BYTE), 1),
    ("b8 p512", Vector(64, 8, 512, BYTE), 1),
    ("b64 p1536", Vector(32, 64, 1536, BYTE), 1),
    ("b8 p512 start", Subarray((128, 512), (64, 8), (32, 40), BYTE), 1),
    ("b8 p512 start incount2",
     Subarray((96, 512), (64, 8), (32, 40), BYTE), 2),
    ("b8 p512 group16", Vector(48, 8, 512, BYTE), 1),
    ("b8 p512 group8", Vector(40, 8, 512, BYTE), 1),
]


def _buffer_of(raw: np.ndarray, dtype) -> jnp.ndarray:
    """The bytes ``raw`` as a flat buffer of ``dtype``."""
    uint = {1: np.uint8, 2: np.uint16, 4: np.uint32}[jnp.dtype(dtype).itemsize]
    return jax.lax.bitcast_convert_type(jnp.asarray(raw.view(uint)), dtype)


@pytest.mark.parametrize("strat", KERNEL_STRATEGIES)
@pytest.mark.parametrize(
    "dtype", [jnp.uint8, jnp.uint16, jnp.bfloat16, jnp.float32],
    ids=["u8", "u16", "bf16", "f32"],
)
@pytest.mark.parametrize(
    "label, dt, incount", NARROW_OBJECTS, ids=[o[0] for o in NARROW_OBJECTS]
)
def test_flat_buffer_roundtrip_at_element_width(label, dt, incount, dtype,
                                                strat):
    """Pack and unpack of flat buffers of 1-, 2- and 4-byte elements
    equal the byte oracle exactly: the packed bytes, and the destination
    inside the blocks and between them (and in its uncovered tail)."""
    ct = REG.commit(dt)
    width = jnp.dtype(dtype).itemsize
    nbytes = -(-(ct.extent * incount + 37) // width) * width
    src = _buffer_of(RNG.integers(0, 256, nbytes, dtype=np.uint8), dtype)
    dst0 = _buffer_of(RNG.integers(0, 256, nbytes, dtype=np.uint8), dtype)
    want = pack_ref(byte_view(src), ct.block, incount, ct.extent)
    want_dst = unpack_ref(byte_view(dst0), want, ct.block, incount, ct.extent)
    got = pack(src, ct, incount=incount, strategy=strat)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if incount == 1:
        np.testing.assert_array_equal(
            np.asarray(pack_block(src, ct.block, strategy=strat)),
            np.asarray(want),
        )
    out = unpack(dst0, got, ct, incount=incount, strategy=strat)
    assert out.shape == dst0.shape and out.dtype == dst0.dtype
    np.testing.assert_array_equal(
        np.asarray(byte_view(out)), np.asarray(want_dst)
    )


def test_narrowed_geometry_follows_the_sublane_tile():
    """The kernel word narrows to the buffer's width only where the
    narrowed row group sits on that width's sublane tile (32 rows of
    bytes, 16 of 16-bit elements)."""
    from repro.kernels.ops import _narrow_geometry

    def narrowed(dt, k):
        sb = REG.commit(dt).block
        return _narrow_geometry(sb, plan_geometry(sb), k)

    g = narrowed(Vector(64, 8, 512, BYTE), 1)
    assert (g.word_bytes, g.lanes, g.pitch, g.group) == (1, 8, 512, 64)
    assert narrowed(Vector(64, 8, 512, BYTE), 4) is None  # not narrower
    assert narrowed(Vector(48, 8, 512, BYTE), 1) is None  # 16-row group
    assert narrowed(Vector(48, 8, 512, BYTE), 2).group == 16
    assert narrowed(Vector(40, 8, 512, BYTE), 2) is None  # 8-row group
    assert narrowed(Vector(13, 8, 512, BYTE), 1) is None  # plane blocks


@pytest.mark.parametrize("strat", KERNEL_STRATEGIES)
def test_byte_buffer_is_never_viewed_as_words(strat):
    """The pack cell's traced pack and unpack of a flat uint8 buffer hold
    no bitcast-convert of the whole buffer (the TPU would relayout all
    of it through a lane-padded [n, 4] shape)."""
    ct = REG.commit(Vector(131072, 8, 512, BYTE))
    n = 131072 * 512
    buf = jax.ShapeDtypeStruct((n,), jnp.uint8)
    packed = jax.ShapeDtypeStruct((ct.size,), jnp.uint8)

    def whole_buffer_bitcasts(jaxpr):
        found = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "bitcast_convert_type":
                aval = eqn.invars[0].aval
                found += aval.size * aval.dtype.itemsize >= n
            for param in eqn.params.values():
                for sub in param if isinstance(param, (list, tuple)) else [param]:
                    if isinstance(sub, jax_core.ClosedJaxpr):
                        sub = sub.jaxpr
                    if isinstance(sub, jax_core.Jaxpr):
                        found += whole_buffer_bitcasts(sub)
        return found

    for fn, args in (
        (lambda b: pack(b, ct, strategy=strat, interpret=False), (buf,)),
        (lambda b, p: unpack(b, p, ct, strategy=strat, interpret=False),
         (buf, packed)),
    ):
        assert whole_buffer_bitcasts(jax.make_jaxpr(fn)(*args).jaxpr) == 0


def test_geometry_planner_properties():
    ct = REG.commit(Vector(13, 25, 128, FLOAT))
    g = plan_geometry(ct.block)
    assert g.word_bytes == 4
    assert g.lanes == 25 and g.pitch == 128
    assert g.rows == 13 and g.planes == 1
    # 13 rows admit no 8-aligned row group (the TPU sublane tile): the
    # kernels move the whole 2D view as one plane block
    assert g.plane_block and g.view_rows == 13
    assert g.view_rows * g.pitch * g.word_bytes <= VMEM_BUDGET_BYTES
    assert g.overfetch == pytest.approx(128 / 25)
    g = plan_geometry(REG.commit(Vector(64, 25, 128, FLOAT)).block)
    assert not g.plane_block
    assert g.group % 8 == 0 and g.rows % g.group == 0
    assert g.group * g.pitch * g.word_bytes <= VMEM_BUDGET_BYTES
    assert g.overfetch == pytest.approx(128 / 25)


# ---------------------------------------------------------------------------
# hypothesis: random strided geometry, kernels == oracle
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAS_HYPOTHESIS = True
except ImportError:  # keep the deterministic tests above collectable
    _HAS_HYPOTHESIS = False


if _HAS_HYPOTHESIS:

    @settings(max_examples=60, deadline=None)
    @given(
        st.integers(1, 3),  # ndims - but at least 2D via min sizes below
        st.data(),
    )
    def test_property_random_subarray_roundtrip(nd, data):
        sizes, subsizes, starts = [], [], []
        for d in range(nd):
            hi = 48 if d == 0 else 8
            size = data.draw(st.integers(2, hi), label=f"size{d}")
            sub = data.draw(st.integers(1, size), label=f"sub{d}")
            start = data.draw(st.integers(0, size - sub), label=f"start{d}")
            sizes.append(size)
            subsizes.append(sub)
            starts.append(start)
        dt = Subarray(tuple(sizes), tuple(subsizes), tuple(starts), BYTE)
        check_roundtrip(dt, strategies=("auto",))

else:

    def test_property_random_subarray_roundtrip():
        pytest.importorskip("hypothesis")
