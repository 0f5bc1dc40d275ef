"""Host↔device copies of the served path, counted (DESIGN.md §13).

Every launch stages its host inputs with :func:`to_device` and brings
its outputs back with :func:`fetch`, so the always-on stage counters see
each copy: ``h2d_bytes`` and ``d2h_bytes``, and the ``engine.wait`` /
``engine.fetch`` stage seconds.  :func:`count_confirm` adds the fetched
sums of a pyramid's object test to ``confirm_candidates`` and
``confirm_hits``.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.obs import trace as _obs_trace


def to_device(x, dtype=None) -> jax.Array:
    """``jnp.asarray(x, dtype)``, adding the bytes it copies to the
    device to ``h2d_bytes``; an array already on the device counts 0."""
    if not isinstance(x, jax.Array):
        x = np.asarray(x, dtype)
        itemsize = jax.dtypes.canonicalize_dtype(x.dtype).itemsize
        _obs_trace.add("h2d_bytes", x.size * itemsize)
    return jnp.asarray(x, dtype)


def fetch(*arrays):
    """Copy device ``arrays`` to the host: blocked until they are ready
    (``engine.wait``), then the copies alone (``engine.fetch``), their
    bytes added to ``d2h_bytes``.  Returns numpy arrays."""
    with _obs_trace.stage("engine.wait", "wait_s"):
        jax.block_until_ready(arrays)
    with _obs_trace.stage("engine.fetch", "fetch_s"):
        out = tuple(np.asarray(a) for a in arrays)
    _obs_trace.add("d2h_bytes", sum(a.nbytes for a in out))
    return out


def count_confirm(confirm) -> None:
    """Add a launch's fetched (Q, 2) object-test sums (candidates before
    the test, hits after it; :func:`repro.core.flat.confirm_shared`) to
    the ``confirm_candidates`` / ``confirm_hits`` counters."""
    cand, hits = np.asarray(confirm, np.int64).reshape(-1, 2).sum(axis=0)
    _obs_trace.add("confirm_candidates", int(cand))
    _obs_trace.add("confirm_hits", int(hits))
