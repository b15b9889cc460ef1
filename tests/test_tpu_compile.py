"""Compile the main mining path's kernels for a described TPU v5e.

No chip is attached here: the TPU compiler builds for ``v5e:2x2`` device 0
from shapes alone, which refuses what the Pallas interpreter accepts (block
tiles Mosaic cannot lay out, SMEM overflow).  Every such compile of the repo
lives in this one file, and the topology, shardings and shapes are built in
fixtures only — only the worker that runs this file may load the TPU
library.

Shapes are the datasets' published widths: T10I4D100K's 100,000
transactions pack into 3,125 words, chess's 3,196 into 100.  Pair counts are
the smallest rung the engine issues and the per-call cap
(``MAX_PAIRS_PER_CALL``); block widths are the ones the engine resolves on a
TPU (``resolve_block_w(None, ..., "tpu")``).
"""
import importlib
import os

import pytest

ROWS = 1024                                   # frontier rows of the compile
WIDTHS = {"T10I4D100K": 3125, "chess": 100}   # packed words per row
KERNELS = ("pairs", "partial", "compact")
MODES = (0, 1, 2)

fi = importlib.import_module("repro.kernels.fused_intersect.fused_intersect")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a TPU executable written to the persistent cache cannot be read back
    # without a chip: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache

    def restore_cache():
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        restore_cache()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    restore_cache()


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)`` -> a ShapeDtypeStruct on v5e device 0."""
    import jax
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.fixture(scope="module")
def rungs():
    from repro.core.engine import pair_bucket
    return {"smallest": pair_bucket(1, 128), "largest": fi.MAX_PAIRS_PER_CALL}


def _compile(spec, kernel, mode, w, q, block_w=None):
    import jax.numpy as jnp
    from repro.kernels.fused_intersect import resolve_block_w
    bw = resolve_block_w(block_w, q, w, mode, "tpu")
    bm = spec((ROWS, w), jnp.uint32)
    pair = spec((q,), jnp.int32)
    scalar = spec((), jnp.int32)
    if kernel == "partial":
        lowered = fi.fused_intersect_partial_pairs.lower(
            bm, pair, pair, mode=mode, block_w=bw)
    elif kernel == "pairs":
        lowered = fi.fused_intersect_pairs.lower(
            bm, pair, pair, pair, scalar, mode=mode, block_w=bw)
    else:
        lowered = fi.fused_intersect_compact_pairs.lower(
            bm, pair, pair, pair, scalar, scalar, mode=mode, block_w=bw)
    return lowered.compile()


@pytest.mark.parametrize("rung", ["smallest", "largest"])
@pytest.mark.parametrize("dataset", sorted(WIDTHS))
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("kernel", KERNELS)
def test_fused_kernel_compiles_for_v5e(spec, rungs, kernel, mode, dataset,
                                       rung):
    compiled = _compile(spec, kernel, mode, WIDTHS[dataset], rungs[rung])
    assert "tpu_custom_call" in compiled.as_text()


def test_pair_cap_is_the_largest_that_fits_smem(spec, monkeypatch):
    """Twice the cap overflows SMEM: the cap is the largest power of two
    whose scalar-prefetched pair indices the compiler accepts."""
    cap = fi.MAX_PAIRS_PER_CALL
    monkeypatch.setattr(fi, "MAX_PAIRS_PER_CALL", 2 * cap)
    with pytest.raises(Exception, match="(?i)smem"):
        _compile(spec, "partial", 0, WIDTHS["chess"], 2 * cap, block_w=128)


def test_cooc_block_compiles_for_v5e(spec):
    """The level-2 co-occurrence pass at T10I4D100K width: one XLA program
    over every 64-row block, with no temporary beyond twice one block's
    (64, rows) counts, so neither a (64, rows, words) intermediate nor a
    second (rows, rows) matrix is materialised."""
    import jax.numpy as jnp
    from repro.core.triangular import _cooc_block
    compiled = _cooc_block.lower(spec((ROWS, WIDTHS["T10I4D100K"]), jnp.uint32),
                                 64).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * ROWS * 4 * 2


@pytest.fixture(scope="module")
def data_mesh(topo):
    """The described v5e:2x2 as a one-axis ``("data",)`` mesh of 4."""
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.array(topo.devices).reshape(4), ("data",))


def test_word_sharded_executor_compiles_for_a_2x2_v5e_at_t40i10d4m(
        data_mesh, monkeypatch):
    """The ``tidsharded`` executable at T40I10D4M's width (32,768 words a
    chip, 4,000,000 transactions over four) and its peak pair rung
    (24,576): the partial kernel, an all-reduce of the counts, under the
    jitted name the trace readers look up, within one chip's memory with
    a frontier of as many rows."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import engine as eng

    # a described device reports no memory; the budget is not compiled
    monkeypatch.setattr(eng, "block_budget", lambda device: 4 * 10**9)
    e = eng.TidShardedEngine(data_mesh, inner="pallas", interpret=False)

    def spec(shape, dtype, p):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(data_mesh, p))

    q = 24576
    pair = spec((q,), jnp.int32, P())
    scalar = spec((), jnp.int32, P())
    compiled = e._sharded[eng.MODE_TIDSET].lower(
        spec((q, 4 * 32768), jnp.uint32, P(None, "data")), pair, pair, pair,
        scalar, scalar).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    assert "jit_fused_intersect_partial_compact" in text
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9
