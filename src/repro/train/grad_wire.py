"""Model-priced gradient wire: the optimizer gradient exchange routed
through a :class:`~repro.comm.api.Communicator` as a committed datatype.

The training driver's gradients are a pytree the launcher jits end to
end; this module pulls the *exchange* half out of that jit and runs it
through the same wire stack every halo exchange uses.  The gradients
are flattened to one contiguous byte vector, committed once as a
``Vector(1, n, n, BYTE)`` :class:`~repro.core.commit.CommittedType`,
and planned with :meth:`Communicator.plan_neighbor` using a **probe**
of the concrete first-step gradient bytes — so a compressible payload
(e.g. a sparsely-updated embedding's zero-heavy gradient) can select
the lossless RLE wire and the ``varlen`` length-aware transport, priced
at the probed stream length, while a dense payload honestly stays on
the plain wire.  The decision rows this records (``wire/varlen`` with
``stream_bytes=``/``ratio=`` and the topology tag in the signature) are
pinned through the decisions file and drift-audited like any other.

The exchange pattern is a **there-and-back ring rotation** along the
communicator's axis: every rank ships its gradient bytes to the next
rank and receives them back on the return hop.  For lossless wire
formats the composition is the identity on the gradients (bit-exact),
while the bytes still traverse the planned — possibly compressed —
schedule twice, so the wire is load-bearing: a decode bug or a wrong
truncation length corrupts training, not just a counter.  On a 1-rank
axis (CI) both hops are self-permutes through the same code path.

Modes (:data:`GRAD_WIRE_MODES`):

``off``    no wire; the caller keeps the fused train step.
``auto``   model-priced selection with the gradient probe — the varlen
           RLE transport wins only when the probed ratio beats the
           plain wire end to end.
``rle``    force the lossless RLE wire (still probe-annotated, so the
           varlen schedule applies when the payload compresses).
``int8``   opt-in lossy quantized wire (never auto-picked): the DCN
           bandwidth trade, explicit because it changes numerics.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import BYTE, Vector
from repro.kernels.ops import byte_view, unbyte_view

__all__ = ["GRAD_WIRE_MODES", "GradWire"]

GRAD_WIRE_MODES: Tuple[str, ...] = ("off", "auto", "rle", "int8")

#: mode -> forced strategy name (None = model-priced selection)
_MODE_STRATEGY = {"auto": None, "rle": "rlewire", "int8": "int8wire"}


class GradWire:
    """Plan once from a concrete gradient sample, exchange every step.

    ``nranks`` is the ring size along the communicator's axis; the
    instance builds its own mesh over the first ``nranks`` visible
    devices (1 on CI — a self-permute ring, same code path).
    """

    def __init__(self, comm, mode: str = "auto", nranks: int = 1):
        if mode not in GRAD_WIRE_MODES:
            raise ValueError(
                f"unknown grad-wire mode {mode!r}; expected one of "
                f"{GRAD_WIRE_MODES}"
            )
        self.comm = comm
        self.mode = mode
        self.nranks = int(nranks)
        self._ct = None
        self._strats = None
        self._plan_fwd = None
        self._plan_back = None
        self._exchange_fn = None
        n = self.nranks
        self._fwd_perm = [[(i, (i + 1) % n) for i in range(n)]]
        self._back_perm = [[((i + 1) % n, i) for i in range(n)]]

    # -- planning --------------------------------------------------------
    @property
    def planned(self) -> bool:
        return self._plan_fwd is not None

    def plan_for(self, grads) -> None:
        """Host-side planning from a *concrete* gradient pytree (the
        first step's output): commit the flat byte type, probe the
        actual payload, and record/pin both hops' wire decisions."""
        if self.mode == "off":
            return
        leaves = jax.tree.leaves(grads)
        probe = np.concatenate(
            [np.asarray(jax.device_get(l)).reshape(-1).view(np.uint8)
             for l in leaves]
        )
        n = int(probe.size)
        self._ct = self.comm.commit(Vector(1, n, n, BYTE))
        name = _MODE_STRATEGY[self.mode]
        strategies = (
            None if name is None else [self.comm.strategies.get(name)]
        )
        # the int8 wire is lossy: never annotate it with a stream probe
        # (it has none), and never let "auto" reach it — only the
        # explicit mode opts in
        use_probe = jnp.asarray(probe) if self.mode != "int8" else None
        self._strats, self._plan_fwd = self.comm.plan_neighbor(
            [self._ct], self._fwd_perm,
            strategies=strategies, probe=use_probe,
        )
        _, self._plan_back = self.comm.plan_neighbor(
            [self._ct], self._back_perm,
            strategies=list(self._strats), probe=use_probe,
        )
        self._exchange_fn = None  # re-trace against the fresh plans

    # -- the per-step exchange ------------------------------------------
    def _roundtrip(self, flat):
        ct = self._ct
        out = self.comm.neighbor_alltoallv(
            flat, [ct], [ct], self._fwd_perm,
            plan=self._plan_fwd, strategies=self._strats,
        )
        return self.comm.neighbor_alltoallv(
            out, [ct], [ct], self._back_perm,
            plan=self._plan_back, strategies=self._strats,
        )

    def _build(self, grads):
        from jax.sharding import Mesh, PartitionSpec as P

        from jax import shard_map

        axis = self.comm.axis_name or "data"
        devs = jax.devices()
        if self.nranks > len(devs):
            raise ValueError(
                f"grad wire ring needs {self.nranks} devices, "
                f"have {len(devs)}"
            )
        mesh = Mesh(np.array(devs[: self.nranks]), (axis,))
        leaves = jax.tree.leaves(grads)
        treedef = jax.tree.structure(grads)
        metas = [(l.dtype, l.shape, l.size * l.dtype.itemsize)
                 for l in leaves]

        def body(*flat_leaves):
            flat = jnp.concatenate([byte_view(l) for l in flat_leaves])
            out = self._roundtrip(flat)
            parts, off = [], 0
            for dtype, shape, nb in metas:
                part = lax.dynamic_slice(out, (off,), (nb,))
                parts.append(unbyte_view(part, dtype, shape))
                off += nb
            return tuple(parts)

        fn = jax.jit(
            shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                      check_vma=False)
        )

        def exchange(g):
            return jax.tree.unflatten(treedef, fn(*jax.tree.leaves(g)))

        return exchange

    def exchange(self, grads):
        """Round-trip the gradient bytes through the planned wire;
        lossless modes return the pytree bit-exact, ``int8`` returns the
        quantize/dequantize round trip (twice — once per hop)."""
        if self.mode == "off":
            return grads
        if not self.planned:
            self.plan_for(grads)
        if self._exchange_fn is None:
            self._exchange_fn = self._build(grads)
        return self._exchange_fn(grads)

    # -- reporting -------------------------------------------------------
    def describe(self) -> str:
        if not self.planned:
            return f"grad-wire mode={self.mode} (unplanned)"
        p = self._plan_fwd
        return (
            f"grad-wire mode={self.mode} strategy={self._strats[0].name} "
            f"schedule={p.schedule} wire_bytes={p.wire_bytes} "
            f"issued={p.issued_bytes} ratio={p.stream_ratio:.4f} "
            f"ring={self.nranks}"
        )
