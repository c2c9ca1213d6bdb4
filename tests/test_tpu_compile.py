"""Ahead-of-time compiles of the serving path's Pallas kernels for TPU v5e,
and of the pipelined update's seed-weight resolution.

No chip is needed: the TPU compiler compiles for a described `v5e:2x2`
topology, and refuses what the chip's compiler would refuse (block shapes
off the (8, 128) tiling, primitives Mosaic cannot lower, more memory than
one chip has). Interpret-mode parity tests cannot see any of that.

Shapes are the deployment's (`configs/batchhl.py`): |V| = 2^20, R = 32,
query batches of 1024. The sweep's tiling is what `block_edges_topology`
produces by default for the serve loop's BA graph at that size (n = 2^20,
attachment degree 4, block_v = 512): rows capped at the mean per-block
edge count, 4096 slots, and 2880 rows once the hub blocks are chunked,
rounded up to 2944.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and a test worker that describes it
keeps it. Keep every such compile in this one file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.graphs import coo
from repro.kernels.edge_relax import kernel as er_kernel
from repro.kernels.edge_relax import ops as er_ops
from repro.kernels.minplus import kernel as mp_kernel

V = 1 << 20          # deployment vertex count
R = 32               # landmarks
BLOCK_V = 512        # serve loop's default destination block
ROWS, WIDTH = 2944, 4096   # default tiling of the BA(2^20, 4) graph
QUERY_BATCH = 1024
CHIP_HBM = 16 * 2 ** 30    # one v5e chip
UPDATES = 1024             # a churn tick's batch (configs/batchhl.py)
EDGE_SLOTS = 2 * 3_211_264  # youtube-stale1's directed slots (3·2^20 + 2^16)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back without one, so
    # the persistent cache stays off around these compiles.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, *dims):
    return jax.ShapeDtypeStruct(dims, jnp.int32, sharding=sharding)


def _assert_tpu_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < CHIP_HBM, used


@pytest.mark.parametrize("planes", [None, R], ids=["one_plane", "vmap_R"])
@pytest.mark.parametrize("hub", [False, True], ids=["plain", "hub_clear"])
def test_relax_sweep_compiles_for_v5e(one_chip, planes, hub):
    """The sweep behind every construction, search, repair and BiBFS wave
    at deployment width: one plane, and vmapped over R planes as the
    fixpoints run it; with and without the hub bit-clear stream (one
    flag per tile slot and plane, as a phase's streams carry it)."""
    tile = _shape(one_chip, 1, ROWS, WIDTH)
    bg = er_ops.BlockedGraph(tile, tile, tile, tile, tile,
                             _shape(one_chip, 1, ROWS), V, BLOCK_V,
                             V // BLOCK_V, True)
    lead = () if planes is None else (planes,)
    keys = _shape(one_chip, *lead, V)
    streams = er_ops.SweepStreams(
        tile, tile, _shape(one_chip, *lead, 1, ROWS, WIDTH) if hub else None)

    def sweep(keys, streams, bg):
        return er_ops.relax_sweep_pallas(keys, bg, streams, 2, 1 << 29, 1,
                                         interpret=False)

    fn = sweep
    if planes is not None:
        fn = jax.vmap(sweep, in_axes=(0, streams.plane_axes(), None))
    compiled = jax.jit(fn).lower(keys, streams, bg).compile()
    _assert_tpu_kernel(compiled)


@pytest.mark.parametrize("batch", [32, QUERY_BATCH],
                         ids=["microbatch", "query_batch"])
def test_frontier_or_compiles_for_v5e(one_chip, batch):
    """The bit-packed BiBFS's OR sweep at deployment width: one packed
    word per 32 queries, for the serve loop's 32-query microbatch and a
    1024-query batch, over the chunked BA tiling."""
    nb = V // BLOCK_V

    def sweep(words, src, dst, rowblk):
        return er_kernel.frontier_or_pallas(
            words, src, dst, rowblk, n=V, block_v=BLOCK_V, nb=nb,
            interpret=False)

    tile = _shape(one_chip, 1, ROWS, WIDTH)
    words = jax.ShapeDtypeStruct((batch // 32, V), jnp.uint32,
                                 sharding=one_chip)
    compiled = jax.jit(sweep).lower(words, tile, tile,
                                    _shape(one_chip, 1, ROWS)).compile()
    _assert_tpu_kernel(compiled)


@pytest.mark.parametrize("p", [R, R // 2], ids=["full", "model_shard"])
def test_minplus_compiles_for_v5e(one_chip, p):
    """The Eq.-3 bound at B = 1024, R = 32: the full contraction and the
    shard-local [R/2, R] slice a model=2 mesh contracts."""
    def bound(s, h, t):
        return mp_kernel.minplus_pallas(s, h, t, interpret=False)

    compiled = jax.jit(bound).lower(
        _shape(one_chip, QUERY_BATCH, p), _shape(one_chip, p, R),
        _shape(one_chip, QUERY_BATCH, R)).compile()
    _assert_tpu_kernel(compiled)


def test_seed_weight_resolution_fuses_for_v5e(one_chip):
    """The pipelined update's seed-weight resolution, compiled as it is
    called (`coo.resolve_seed_weights`, a program of its own) at a churn
    tick's 1024 updates over the Youtube-scale graph's edge slots: its
    [U, E2] endpoint match fuses into the reduction, so the compiled
    temporaries stay under 1 GiB, where each unfused [U, E2] boolean
    array is 6 GiB."""
    def arr(n, dtype=jnp.int32):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    g = coo.Graph(arr(EDGE_SLOTS), arr(EDGE_SLOTS), arr(EDGE_SLOTS, bool),
                  arr(EDGE_SLOTS), V)
    b = coo.BatchUpdate(arr(UPDATES), arr(UPDATES), arr(UPDATES, bool),
                        arr(UPDATES, bool), arr(UPDATES),
                        arr(UPDATES, bool))
    compiled = coo.resolve_seed_weights.lower(g, b).compile()
    assert UPDATES * EDGE_SLOTS > 6 * 2 ** 30
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30
