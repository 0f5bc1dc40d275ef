"""Every Pallas kernel of the served path compiles for a TPU v5e.

Interpret mode cannot see what the chip's compiler (Mosaic) refuses:
block shapes off the (8, 128) tiling, unaligned DMA slices, selects
between boolean vectors, casts it has no lowering for, more VMEM than a
kernel may use.  These tests compile each kernel at a real width for a
described v5e chip — nothing runs, so they cost no chip time — and check
that the program really holds a Mosaic kernel (``interpret=False`` is
passed explicitly; off-TPU it would default to interpret mode).

The topology is described inside a module-scoped fixture and nowhere
else: only one process at a time may load the TPU compiler library, so
describing it while a module is imported would make the test workers
collect different tests.
"""
import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.flat import NEVER_MBR
from repro.kernels import ops

Q = 16            # the serving query block
W = 20_480        # a resident-sweep width (mqr tree of ~70k objects)
STREAM_L = 13     # pyramid depth at n = 1e7 (bulk.default_levels)
STREAM_W = 10_000_000
BUILD_N = 4096    # the build kernel's largest n (PALLAS_BUILD_MAX_N)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it.
    log_dir = os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu / no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    if log_dir == "disabled":
        os.environ.pop("TPU_LOG_DIR", None)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _resident(s, tile_dtype, query_dtype):
    return ops.level_sweep.lower(
        _spec(s, (Q, 4), query_dtype), _spec(s, (8, 4, W), tile_dtype),
        _spec(s, (8, W), tile_dtype if tile_dtype == jnp.uint16 else jnp.int32),
        block_w=128, interpret=False,
    )


def _stream(s):
    n_tiles = STREAM_W // 128
    return ops.level_sweep.lower(
        _spec(s, (Q, 4), jnp.float32),
        _spec(s, (STREAM_L, 4, STREAM_W), jnp.float32),
        _spec(s, (STREAM_L, STREAM_W), jnp.int32),
        block_w=128, interpret=False, stream=True,
        win_off=_spec(s, (STREAM_L, n_tiles), jnp.int32), win_w=256,
    )


def _stream_padded(s):
    """The pallas adapter's launch of a 3-request deadline batch at the
    map configuration's size: padded to the query block (Mosaic refuses
    the streamed sweep for Q = 3), with the object test of a pyramid
    whose deepest groups hold most objects."""
    n, levels, block_w = 4_000_000, 11, 512
    padded = np.concatenate([np.zeros((3, 4), np.float32),
                             np.broadcast_to(NEVER_MBR, (Q - 3, 4))])
    run = functools.partial(
        ops.fused_search, n_objects=n, block_w=block_w,
        root_unconditional=False, test_object_mbr=False,
        n_shared=3_900_000, interpret=False, stream=True, win_w=1024,
    )
    return jax.jit(run).lower(
        _spec(s, padded.shape, jnp.float32),
        _spec(s, (levels, 4, n), jnp.float32),
        _spec(s, (levels, n), jnp.int32),
        _spec(s, (n, 4), jnp.float32),
        *(_spec(s, (n,), jnp.int32) for _ in range(3)),
        win_off=_spec(s, (levels, -(-n // block_w)), jnp.int32),
    )


def _stream_ids(s):
    """The pallas adapter's launch of a full block over the bit layer's
    pyramid, returning hit ids where they fit (DESIGN.md §12): the
    streamed sweep, the fit counts, the id epilogue's compactions with
    the object test, and the dense epilogue it falls back to."""
    n, levels, block_w = 4_000_000, 11, 512
    members = (_spec(s, (n + 1,), jnp.int32), _spec(s, (n,), jnp.int32),
               *(_spec(s, (n,), jnp.float32) for _ in range(4)))
    return ops.fused_search_ids.lower(
        _spec(s, (Q, 4), jnp.float32), members,
        _spec(s, (levels, 4, n), jnp.float32),
        _spec(s, (levels, n), jnp.int32),
        _spec(s, (n, 4), jnp.float32),
        *(_spec(s, (n,), jnp.int32) for _ in range(3)),
        _spec(s, (), jnp.int32),
        n_objects=n, block_w=block_w, root_unconditional=False,
        confirm_w=3_932_160, interpret=False, caps=(256, 8192, 16, 32768),
        stream=True, win_off=_spec(s, (levels, -(-n // block_w)), jnp.int32),
        win_w=1024,
    )


def _hier(s):
    return ops.level_sweep_hier.lower(
        _spec(s, (Q, 4), jnp.int32), _spec(s, (Q, 4), jnp.int32),
        _spec(s, (7, 4, W), jnp.uint8), _spec(s, (1, 4, W), jnp.uint16),
        _spec(s, (8, W), jnp.uint16), split=7, block_w=128, interpret=False,
    )


def _pair(s):
    def sweep(a, pa, b, pb):
        return ops.pair_sweep(a, pa, b, pb, interpret=False)

    side = (_spec(s, (6, 4, 2048), jnp.float32), _spec(s, (6, 2048), jnp.int32))
    return jax.jit(sweep).lower(*side, *side)


def _quantize(s):
    return ops.quantize_cm_pallas.lower(
        _spec(s, (8, 4, W), jnp.float32), _spec(s, (4,), jnp.float32),
        _spec(s, (4,), jnp.float32), interpret=False,
    )


def _build(s):
    return ops.build_levels_pallas.lower(
        _spec(s, (BUILD_N, 4), jnp.float32), levels=7,
        interpret=False,
    )


KERNELS = {
    "resident_f32": lambda s: _resident(s, jnp.float32, jnp.float32),
    "resident_u16": lambda s: _resident(s, jnp.uint16, jnp.int32),
    "stream_1e7": _stream,
    "stream_padded_q3_4e6": _stream_padded,
    "stream_ids_4e6": _stream_ids,
    "hier_u8_u16": _hier,
    "pair_2048x2048": _pair,
    "quantize": _quantize,
    "build_4096": _build,
}


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel):
    compiled = KERNELS[kernel](one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()
