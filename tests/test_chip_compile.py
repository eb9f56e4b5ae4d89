"""The main path's programs compile for a TPU v5e that is described, not
attached, at the sizes ``chip_smoke.py`` runs them.

Nothing runs here, so these tests say nothing about results or times; they
catch what the chip's compiler would refuse (block shapes, memory) before
any chip time is spent. Shapes come from ``jax.eval_shape`` of the real
builders. The topology is described inside a module fixture, never at
import, so every test worker collects the same tests and only the worker
given this file loads the TPU compiler.

Kernel routing does not depend on the backend: no Pallas kernel compiles
for the v5e yet, so every builder takes its XLA path by default, and the
default programs below must hold no kernel (``tpu_custom_call``).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

SHARD = 1 << 16
VOCAB = 1 << 17
QUERIES = 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                                    # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _on(sharding, tree, lead=()):
    """ShapeDtypeStructs of ``tree`` with a leading ``lead`` axis, placed
    on ``sharding``."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(lead + tuple(s.shape), s.dtype,
                                       sharding=sharding), tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    return compiled


def _shard_shapes(sigma, n=SHARD):
    from repro.core.wavelet_matrix import build_wavelet_matrix
    return jax.eval_shape(lambda s: build_wavelet_matrix(s, sigma),
                          jax.ShapeDtypeStruct((n,), jnp.uint32))


def test_wavelet_matrix_build_compiles(one_chip):
    from repro.core.wavelet_matrix import build_wavelet_matrix
    seq = jax.ShapeDtypeStruct((SHARD,), jnp.uint32, sharding=one_chip)
    compiled = _compile(lambda s: build_wavelet_matrix(s, VOCAB), seq)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("op", ["quantile", "count"])
def test_sharded_vocab_queries_compile(one_chip, op):
    from repro.analytics.engine import (sharded_range_count,
                                        sharded_range_quantile)
    shards = _on(one_chip, _shard_shapes(VOCAB), lead=(1024,))
    q = jax.ShapeDtypeStruct((QUERIES,), jnp.int32, sharding=one_chip)
    n = 1024 * SHARD
    if op == "quantile":
        fn = lambda w, lo, hi, k: sharded_range_quantile(w, 16, n, lo, hi, k)
        compiled = _compile(fn, shards, q, q, q)
    else:
        fn = lambda w, lo, hi, a, b: sharded_range_count(w, 16, n, lo, hi,
                                                         a, b)
        compiled = _compile(fn, shards, q, q, q, q)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


@pytest.mark.parametrize("op", ["count", "locate"])
def test_sharded_fm_search_compiles(one_chip, op):
    from repro.index.fm_index import build_fm_index
    from repro.index.sharded import ShardedTextIndex

    # the per-shard build build_sharded_index runs: raw σ=256 + pad symbol
    fm = jax.eval_shape(lambda s: build_fm_index(s, 257),
                        jax.ShapeDtypeStruct((SHARD,), jnp.int32))
    idx = ShardedTextIndex(
        shards=_on(one_chip, fm, lead=(256,)),
        seam_windows=jax.ShapeDtypeStruct((255, 30), jnp.int32,
                                          sharding=one_chip),
        n=256 * SHARD, sigma=256, shard_bits=16, seam_overlap=15)
    pats = jax.ShapeDtypeStruct((QUERIES, 8), jnp.int32, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((QUERIES,), jnp.int32, sharding=one_chip)
    if op == "count":
        compiled = _compile(lambda ix, p, l: ix.count(p, l), idx, pats, lens)
    else:
        compiled = _compile(lambda ix, p, l: ix.locate(p, l, 4), idx, pats,
                            lens)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


def test_suffix_array_round_compiles(one_chip):
    """One prefix-doubling round of a 2^16-token shard: the counting sort
    with 256 buckets takes its XLA blocked path, not the radix kernel."""
    from repro.index.suffix_array import _rank_bits, doubling_round
    rank = jax.ShapeDtypeStruct((SHARD + 1,), jnp.int32, sharding=one_chip)
    off = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    kb = _rank_bits(SHARD + 1)
    _compile(lambda r, o: doubling_round(r, o, kb), rank, off)
