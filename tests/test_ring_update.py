"""ring_update / ring_update_stacked on a real multi-device mesh."""

import pytest

from tests._subproc import run_with_devices

CODE = r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_test_mesh
from repro.distributed.sharding import DEFAULT_RULES, use_rules
from repro.models.layers import ring_update, ring_update_stacked

mesh = make_test_mesh(data=2, model=4)
B, S, KV, HD = 4, 16, 2, 8
L = 3

with use_rules(mesh, DEFAULT_RULES):
    cache = jnp.zeros((B, S, KV, HD), jnp.bfloat16)
    cache = jax.device_put(cache, NamedSharding(mesh, P("data", "model")))
    new = jnp.ones((B, 1, KV, HD), jnp.bfloat16) * 7

    fn = jax.jit(lambda c, n, s: ring_update(c, n, s))
    for slot in (0, 5, 15):
        out = np.asarray(fn(cache, new, jnp.int32(slot)))
        want = np.zeros((B, S, KV, HD), np.float32)
        want[:, slot] = 7
        np.testing.assert_array_equal(out.astype(np.float32), want)

    # stacked variant
    c2 = jnp.zeros((L, B, S, KV, HD), jnp.bfloat16)
    c2 = jax.device_put(c2, NamedSharding(mesh, P(None, "data", "model")))
    n2 = jnp.arange(L, dtype=jnp.bfloat16)[:, None, None, None, None] * jnp.ones(
        (L, B, 1, KV, HD), jnp.bfloat16)
    out2 = np.asarray(jax.jit(ring_update_stacked)(c2, n2, jnp.int32(9)))
    for l in range(L):
        np.testing.assert_array_equal(
            out2[l, :, 9].astype(np.float32),
            np.full((B, KV, HD), float(l), np.float32))
        assert (out2[l, :, :9] == 0).all() and (out2[l, :, 10:] == 0).all()
print("RING_OK")
"""


@pytest.mark.slow
def test_ring_update_multidevice():
    out = run_with_devices(CODE, ndev=8)
    assert "RING_OK" in out
