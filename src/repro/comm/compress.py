"""Compressed-wire strategy plugin: int8 on the wire, upcast on unpack.

The point of the exact-byte :class:`~repro.comm.wireplan.WirePlan`
accounting is that a strategy's wire extent need not equal the packed
member bytes — a bounding window is *larger*, a compressed payload is
*smaller*.  This plugin exercises the smaller side: float32 member
bytes are symmetric-quantized to int8 for the link and dequantized on
the receive side before the scatter.

Quantization is **per 256-element block** by default: each block of the
packed payload carries its own float32 scale (the header grows by 4 B
per block), so one large-magnitude region no longer destroys the
resolution of every other region in the payload — the lossy wire is
usable on far more datatypes than the old per-payload scale allowed.
``Int8Wire(block_elems=None)`` still *produces* the legacy one-scale
format, and the decoder reads both (the scale count is recoverable from
the wire length and the receive type, so a per-payload payload
dequantizes correctly through the default per-block instance).

Quantization is lossy, so the strategy registers with
``selectable = False``: the model never auto-picks it; opt in per
communicator with ``FixedPolicy(Int8Wire.name)`` (lossy halo exchange
is a deliberate accuracy/bandwidth trade, e.g. on a DCN axis).  It is
``wire_only``: local ``pack``/``unpack`` calls fall back to the normal
kernels — only the wire format is compressed.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

import jax.numpy as jnp
from jax import lax

from repro.comm.api import Strategy
from repro.core.commit import CommittedType
from repro.kernels import ops
from repro.obs.trace import scope

__all__ = [
    "Int8Wire",
    "INT8_WIRE",
    "BLOCK_ELEMS",
    "RleWire",
    "RLE_WIRE",
    "RLE_HEADER_BYTES",
    "RLE_RUN_BYTES",
]

#: bytes per float32 dequantization scale in the wire header
_SCALE_BYTES = 4

#: default quantization granularity (elements per scale)
BLOCK_ELEMS = 256


class Int8Wire(Strategy):
    """Ship float32 member bytes as int8 + per-block float32 scales."""

    name = "int8wire"
    wire_only = True       # the compressed format only exists on the wire
    selectable = False     # lossy: never auto-selected, opt in explicitly

    def __init__(self, block_elems: Optional[int] = BLOCK_ELEMS):
        #: elements per quantization block; None = one scale for the
        #: whole payload (the legacy wire format)
        self.block_elems = block_elems

    def applicable(self, ct: CommittedType) -> bool:
        # the member bytes must re-view as float32 words; the type system
        # tracks bytes, not element dtypes, so the caller opting in (via
        # FixedPolicy) asserts the buffer really holds float32 data
        return ct.size % 4 == 0 and ct.word_bytes >= 4

    def _nblocks(self, nfloats: int) -> int:
        if self.block_elems is None or nfloats == 0:
            return 1
        return -(-nfloats // self.block_elems)

    # -- §5 cost model ----------------------------------------------------
    def model_pack(self, model, ct, incount):
        # pack the members (priced like rows) + quantize: the measured
        # compress sweep when calibrated, else one extra read+write
        # sweep of the packed bytes
        size = ct.size * incount
        from repro.comm.api import ROWS

        base = ROWS.model_pack(model, ct, incount)
        m = model.measured_compress(self.name, size)
        if m is not None:
            return base + m[0]
        return base + 2 * size / model.params.hbm_bw

    def model_unpack(self, model, ct, incount):
        size = ct.size * incount
        from repro.comm.api import ROWS

        base = ROWS.model_unpack(model, ct, incount)
        m = model.measured_compress(self.name, size)
        if m is not None:
            return base + m[1]
        return base + 2 * size / model.params.hbm_bw

    def wire_bytes(self, ct: CommittedType, incount: int = 1) -> int:
        # one int8 per float32 member + one scale per quantization block
        nfloats = (ct.size * incount) // 4
        return _SCALE_BYTES * self._nblocks(nfloats) + nfloats

    # -- execution --------------------------------------------------------
    def encode_wire(self, member):
        """Packed member bytes -> quantized wire (per-block scales header
        + int8 body).  Split out from :meth:`pack` so the fused
        pack+compress entry and the compress-throughput sweep
        (:func:`repro.measure.bench.measure_compress_table`) can time the
        quantize transform on its own."""
        f = lax.bitcast_convert_type(
            member.reshape(-1, 4), jnp.float32
        ).reshape(-1)
        n = f.shape[0]
        nb = self._nblocks(n)
        block = self.block_elems if (self.block_elems and nb > 1) else n
        pad = nb * block - n
        blocks = jnp.pad(f, (0, pad)).reshape(nb, block)
        scales = (
            jnp.maximum(jnp.max(jnp.abs(blocks), axis=1), jnp.float32(1e-30))
            / 127.0
        )
        q = jnp.clip(jnp.round(blocks / scales[:, None]), -127, 127)
        q = q.astype(jnp.int8).reshape(-1)[:n]
        header = lax.bitcast_convert_type(
            scales.astype(jnp.float32), jnp.uint8
        ).reshape(-1)
        return jnp.concatenate([header, ops.byte_view(q)])

    @scope("pack")
    def pack(self, buf, ct, incount: int = 1, interpret: Optional[bool] = None):
        return self.encode_wire(
            ops.pack(buf, ct, incount=incount, interpret=interpret)
        )

    def decode_wire(self, wire, n: int):
        """Wire bytes -> the ``n`` dequantized member bytes (lossy)."""
        nfloats = n // 4
        nscales = (wire.shape[0] - nfloats) // _SCALE_BYTES
        scales = lax.bitcast_convert_type(
            wire[: _SCALE_BYTES * nscales].reshape(nscales, _SCALE_BYTES),
            jnp.float32,
        ).reshape(-1)
        q = lax.bitcast_convert_type(wire[_SCALE_BYTES * nscales :], jnp.int8)
        if nscales == 1:
            f = q.astype(jnp.float32) * scales[0]  # legacy per-payload scale
        else:
            if self.block_elems is None or nscales != self._nblocks(nfloats):
                raise ValueError(
                    f"wire carries {nscales} scales for {nfloats} floats; "
                    f"expected {self._nblocks(nfloats)} "
                    f"(block_elems={self.block_elems})"
                )
            expand = jnp.repeat(scales, self.block_elems)[:nfloats]
            f = q.astype(jnp.float32) * expand
        return lax.bitcast_convert_type(f.reshape(-1, 1), jnp.uint8).reshape(-1)

    @scope("unpack")
    def unpack_wire(self, comm, dst, wire, recv_ct, send_ct=None, incount=1):
        member = self.decode_wire(wire, recv_ct.size * incount)
        u = comm.select(recv_ct, incount, wire=False)
        return u.unpack(dst, member, recv_ct, incount)

    def unpack(self, buf, packed, ct, incount=1, interpret=None):
        raise TypeError(
            f"{self.name} is wire-only; use unpack_wire on the received "
            "payload"
        )


INT8_WIRE = Int8Wire()


# ===========================================================================
# lossless zero-run / RLE wire format
# ===========================================================================

#: wire header: uint32 mode (0 = stored, 1 = rle) + uint32 run count
RLE_HEADER_BYTES = 8

#: bytes one RLE run occupies on the wire (uint8 value + uint32 length)
RLE_RUN_BYTES = 5
_RUN_BYTES = RLE_RUN_BYTES


class RleWire(Strategy):
    """Lossless run-length wire format with a stored-mode fallback.

    The *exact-byte* counterpart of :class:`Int8Wire`: where int8
    quantization trades accuracy for bytes, this plugin is bit-exact —
    the member bytes are run-length encoded (one ``(value, length)``
    pair per run, the classic zero-run case collapsing whole halo shells
    of zeros into one 5-byte run) and decoded exactly on the receive
    side before the scatter.

    XLA arrays have static shapes, so a wire payload cannot change size
    with its data; the format is therefore **capacity-allocated**: the
    wire always spans ``member_bytes + 8`` bytes (`wire_bytes`), the
    8-byte header records the live mode and run count, and the tail
    beyond the encoded stream is zero.  A payload whose RLE stream would
    not fit the capacity ships verbatim under ``mode = stored`` — the
    DEFLATE stored-block discipline — so the round trip is exact for
    *every* input.

    The body is laid out as **interleaved 5-byte run records** (run
    ``i`` at body offset ``5*i`` carries ``value:u8 ++ length:u32le``),
    so the live encoded stream is literally a *prefix* of the capacity
    wire: ``wire[:8 + 5*nruns]``.  That is what makes the format
    transport-truncatable — the ``varlen`` wire schedule
    (:meth:`Communicator._issue_wire`) ships only
    :meth:`probe_stream_bytes` bytes per class, and
    :meth:`unpack_wire` decodes either a full capacity wire *or* a
    header-prefixed stream whose run count it derives from the wire
    length.  A stream budget comes from a calibration probe of the
    actual payload (never assumed); a stored-mode payload never
    truncates (its stream length *is* the capacity).

    Registered ``selectable = True``: byte-exactness holds in both
    modes, and the strategy is priced honestly — at *capacity* bytes
    (header included, always >= the packed member bytes) unless the
    selection carries a probed stream length, so the model only ever
    picks it when a length-aware transport makes the compressed bytes
    the bytes actually moved.  ``wire_only``: local pack/unpack fall
    back to the normal kernels, and the strategy stays out of the
    measured pack/unpack sweeps (``StrategyRegistry.measurable``).
    """

    name = "rlewire"
    wire_only = True        # the RLE format only exists on the wire
    selectable = True       # lossless; priced at capacity unless probed
    supports_varlen = True  # live stream is a prefix of the capacity wire

    def applicable(self, ct: CommittedType) -> bool:
        return ct.size > 0

    @staticmethod
    def _run_capacity(nbytes: int) -> int:
        """Run slots the fixed layout can hold (5 B each, inside the
        member-byte capacity)."""
        return nbytes // _RUN_BYTES

    # -- §5 cost model ----------------------------------------------------
    def model_pack(self, model, ct, incount):
        from repro.comm.api import ROWS

        # pack the members + the encode sweep: measured compress table
        # when calibrated, else one extra read + write of the bytes
        size = ct.size * incount
        base = ROWS.model_pack(model, ct, incount)
        m = model.measured_compress(self.name, size)
        if m is not None:
            return base + m[0]
        return base + 2 * size / model.params.hbm_bw

    def model_unpack(self, model, ct, incount):
        from repro.comm.api import ROWS

        size = ct.size * incount
        base = ROWS.model_unpack(model, ct, incount)
        m = model.measured_compress(self.name, size)
        if m is not None:
            return base + m[1]
        return base + 2 * size / model.params.hbm_bw

    def wire_bytes(self, ct: CommittedType, incount: int = 1) -> int:
        # capacity layout: header + the member bytes (stored-mode bound)
        return RLE_HEADER_BYTES + ct.size * incount

    # -- length-aware transport -------------------------------------------
    def probe_stream_bytes(self, ct: CommittedType, incount, buf) -> int:
        """Exact stream length (header + live run records) for a
        *concrete* payload sample — the calibration probe the varlen
        transport truncates at.  Falls back to capacity for tracers
        (no data to probe) and for stored-mode payloads (their stream
        *is* the capacity)."""
        import jax

        cap = self.wire_bytes(ct, incount)
        if isinstance(buf, jax.core.Tracer):
            return cap  # tracer: nothing to probe
        member = np.asarray(ops.pack(jnp.asarray(buf), ct, incount=incount))
        n = member.size
        if n == 0:
            return cap
        runs = int(np.count_nonzero(member[1:] != member[:-1])) + 1
        if runs > self._run_capacity(n):
            return cap  # would ship stored: no truncation possible
        return min(RLE_HEADER_BYTES + _RUN_BYTES * runs, cap)

    # -- execution --------------------------------------------------------
    def encode_wire(self, member):
        """Member bytes -> capacity wire (header + interleaved run
        records + zero tail, or header + stored body).  The fused
        pack+compress entry (:func:`repro.kernels.pack.pack_compress_ragged`)
        composes this with the member gather in one traced pass."""
        b = member
        n = b.shape[0]
        R = self._run_capacity(n)
        if R == 0:
            header = lax.bitcast_convert_type(
                jnp.array([0, 0], jnp.uint32), jnp.uint8
            ).reshape(-1)
            return jnp.concatenate([header, b])
        # run starts: position 0 plus every byte differing from its
        # predecessor; run i spans [pos_i, pos_{i+1})
        starts = jnp.concatenate(
            [jnp.ones((1,), bool), b[1:] != b[:-1]]
        )
        nruns = starts.sum().astype(jnp.uint32)
        (pos,) = jnp.where(starts, size=n, fill_value=n)
        counts = jnp.diff(jnp.append(pos, n))  # 0 past the live runs
        values = jnp.where(counts > 0, b[jnp.clip(pos, 0, n - 1)], 0)
        fits = nruns <= jnp.uint32(R)
        mode = jnp.where(fits, jnp.uint32(1), jnp.uint32(0))
        count_bytes = lax.bitcast_convert_type(
            counts[:R].astype(jnp.uint32), jnp.uint8
        )  # (R, 4)
        records = jnp.concatenate(
            [values[:R].astype(jnp.uint8)[:, None], count_bytes], axis=1
        ).reshape(_RUN_BYTES * R)  # run i at body offset 5*i
        rle_body = jnp.concatenate(
            [records, jnp.zeros((n - _RUN_BYTES * R,), jnp.uint8)]
        )
        body = jnp.where(fits, rle_body, b)
        header = lax.bitcast_convert_type(
            jnp.stack([mode, nruns]), jnp.uint8
        ).reshape(-1)
        return jnp.concatenate([header, body])

    @scope("pack")
    def pack(self, buf, ct, incount: int = 1, interpret: Optional[bool] = None):
        return self.encode_wire(
            ops.pack(buf, ct, incount=incount, interpret=interpret)
        )

    def decode_wire(self, wire, n: int):
        """Wire bytes -> the ``n`` member bytes.  Accepts either the
        full capacity wire (``8 + n`` bytes, mode-dependent stored/rle
        body) or a truncated varlen stream (``8 + 5*S`` bytes, always
        rle mode; ``S`` derived from the wire length)."""
        total = wire.shape[0]
        body = wire[RLE_HEADER_BYTES:]
        if total == RLE_HEADER_BYTES + n:
            R = self._run_capacity(n)
            stream_only = False
        else:
            rec = total - RLE_HEADER_BYTES
            if rec < 0 or rec % _RUN_BYTES or rec > _RUN_BYTES * self._run_capacity(n):
                raise ValueError(
                    f"rle wire carries {total} bytes; expected "
                    f"{RLE_HEADER_BYTES + n} (capacity) for a {n}-byte "
                    f"member payload, or header + whole 5-byte run records"
                )
            R = rec // _RUN_BYTES
            stream_only = True
        if R == 0:
            return body
        records = body[: _RUN_BYTES * R].reshape(R, _RUN_BYTES)
        values = records[:, 0]
        counts = lax.bitcast_convert_type(records[:, 1:], jnp.uint32)
        # live counts sum to n exactly; dead slots are 0
        decoded = jnp.repeat(values, counts, total_repeat_length=n)
        if stream_only:
            return decoded  # a truncated stream is always rle mode
        header = lax.bitcast_convert_type(
            wire[:RLE_HEADER_BYTES].reshape(2, 4), jnp.uint32
        )
        return jnp.where(header[0] == 1, decoded, body)

    @scope("unpack")
    def unpack_wire(self, comm, dst, wire, recv_ct, send_ct=None, incount=1):
        member = self.decode_wire(wire, recv_ct.size * incount)
        u = comm.select(recv_ct, incount, wire=False)
        return u.unpack(dst, member, recv_ct, incount)

    def unpack(self, buf, packed, ct, incount=1, interpret=None):
        raise TypeError(
            f"{self.name} is wire-only; use unpack_wire on the received "
            "payload"
        )


RLE_WIRE = RleWire()
