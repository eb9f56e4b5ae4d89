"""Error-feedback bitplane gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.optim.grad_compress import (compression_ratio,
                                       dequantize_bitplanes,
                                       ef_compress_tree, quantize_bitplanes,
                                       zero_residuals)


@given(st.integers(1, 5000), st.sampled_from([2, 4, 8, 12]),
       st.integers(0, 2**32 - 1))
@settings(max_examples=10)
def test_quantization_error_bound(n, bits, seed):
    x = jnp.asarray(np.random.default_rng(seed).normal(size=n)
                    .astype(np.float32))
    words, scale = quantize_bitplanes(x, bits)
    dq = dequantize_bitplanes(words, scale, bits, x.shape)
    # round-to-nearest: |err| <= scale/2 elementwise
    assert float(jnp.max(jnp.abs(dq - x))) <= float(scale) * 0.5 + 1e-7


def test_wire_format_size():
    x = jnp.ones((1000,), jnp.float32)
    for bits in (4, 8):
        words, _ = quantize_bitplanes(x, bits)
        assert words.shape == (bits, (1000 + 31) // 32)
        assert compression_ratio(bits) == bits / 32


def test_plane_truncation_degrades_gracefully():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=4096).astype(np.float32))
    words, scale = quantize_bitplanes(x, 8)
    errs = []
    for keep in (8, 6, 4, 2):
        dq = dequantize_bitplanes(words, scale, 8, x.shape,
                                  keep_planes=keep)
        errs.append(float(jnp.mean(jnp.abs(dq - x))))
    assert errs == sorted(errs)          # fewer planes → larger error


def test_error_feedback_unbiased_over_time():
    """With EF, the *sum* of compressed grads tracks the sum of true grads
    (residual is bounded), even at 2-bit sign-ish quantization."""
    rng = np.random.default_rng(1)
    g_true = [jnp.asarray(rng.normal(size=256).astype(np.float32))
              for _ in range(30)]
    residual = jnp.zeros((256,), jnp.float32)
    total_sent = jnp.zeros((256,), jnp.float32)
    for g in g_true:
        (sent,), (residual,) = ef_compress_tree((g,), (residual,), bits=3)
        total_sent = total_sent + sent
    total_true = sum(g_true)
    # EF guarantee: |Σ sent − Σ true| = |final residual| ≤ max per-step scale
    drift = np.abs(np.asarray(total_sent - total_true))
    assert drift.max() <= float(jnp.abs(residual).max()) + 1e-5
    # and the relative tracking error is small
    assert drift.max() / (np.abs(np.asarray(total_true)).max() + 1e-9) < 0.5


def test_tree_structure_preserved():
    params = {"a": jnp.ones((8, 8)), "b": {"c": jnp.ones((3,))}}
    res = zero_residuals(params)
    grads = jax.tree.map(lambda p: p * 0.5, params)
    q, new_res = ef_compress_tree(grads, res, bits=8)
    assert jax.tree_util.tree_structure(q) == \
        jax.tree_util.tree_structure(params)
    assert jax.tree_util.tree_structure(new_res) == \
        jax.tree_util.tree_structure(params)


def test_compressed_allreduce_under_shard_map():
    """Numerical check of the wire collective on a multi-device host mesh.

    Runs in a subprocess because it needs forced host devices and the test
    session must keep the single-device default."""
    import subprocess
    import sys
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim.grad_compress import compressed_allreduce_mean
mesh = jax.make_mesh((4,), ("pod",),
                     axis_types=(jax.sharding.AxisType.Auto,))
x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 64))
                .astype(np.float32))
f = jax.shard_map(lambda g: compressed_allreduce_mean(g, "pod", bits=8),
              mesh=mesh, in_specs=P("pod", None),
              out_specs=P("pod", None))
out = np.asarray(f(x))
want = np.mean(np.asarray(x), axis=0)
err = np.abs(out - want).max() / (np.abs(want).max() + 1e-9)
assert err < 0.02, err
print("OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-2000:]
