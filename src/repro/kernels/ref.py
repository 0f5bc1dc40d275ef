"""Pure-jnp oracle of the MBR scan kernel (the ground truth in tests)."""

from __future__ import annotations

import jax.numpy as jnp


def mbr_scan_ref(mbrs: jnp.ndarray, queries: jnp.ndarray) -> jnp.ndarray:
    """mbrs: (N, 4); queries: (Q, 4) -> (Q, N) bool overlap mask."""
    a = mbrs[None, :, :]
    b = queries[:, None, :]
    return (
        (a[..., 0] <= b[..., 2])
        & (b[..., 0] <= a[..., 2])
        & (a[..., 1] <= b[..., 3])
        & (b[..., 1] <= a[..., 3])
    )
