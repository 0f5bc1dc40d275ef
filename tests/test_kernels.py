"""The MBR scan kernel vs its pure-jnp oracle: shape sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops


@pytest.mark.parametrize("n", [17, 256, 1000])
@pytest.mark.parametrize("q", [1, 5])
def test_mbr_scan_sweep(n, q):
    key = jax.random.PRNGKey(n * 31 + q)
    lo = jax.random.uniform(key, (n, 2)) * 100
    mbrs = jnp.concatenate([lo, lo + jax.random.uniform(key, (n, 2)) * 10], axis=1)
    qs = jnp.concatenate(
        [jax.random.uniform(jax.random.fold_in(key, 1), (q, 2)) * 100] * 2, axis=1
    ) + jnp.array([0.0, 0.0, 20.0, 20.0])
    got = ops.mbr_scan(mbrs, qs)
    want = ops.mbr_scan_ref(mbrs, qs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

