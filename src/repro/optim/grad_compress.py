"""Error-feedback bitplane gradient compression.

The DP/pod-axis all-reduce is the slowest link at multi-pod scale (DCN).
Each gradient tensor is quantized to ``bits`` levels (sign + magnitude) and
the bit-planes are packed into uint32 words with the same ``core.bitops``
machinery the rank/select structures use — wire volume drops to
``bits/32`` of f32 (e.g. 4 bits → 8×). Quantization error is carried in an
error-feedback residual (Seide et al. 2014; Karimireddy et al. 2019), so
the *accumulated* update is unbiased and convergence matches uncompressed
SGD/Adam to first order.

Planes are MSB-first: truncating trailing planes degrades precision
gracefully (an elastic-bandwidth knob: a congested pod link can drop
planes without renegotiation).

``compressed_allreduce_mean`` is the shard_map collective: quantize local →
all_gather packed planes (the compressed wire format) → dequantize → mean.
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from repro.core import bitops


def quantize_bitplanes(x: jax.Array, bits: int
                       ) -> Tuple[jax.Array, jax.Array]:
    """x (any shape) → (planes (bits, ceil(n/32)) uint32, scale () f32).

    Plane 0 = sign; planes 1.. = magnitude bits, MSB first.
    """
    assert bits >= 2
    flat = x.reshape(-1).astype(jnp.float32)
    m = jnp.int32((1 << (bits - 1)) - 1)
    amax = jnp.max(jnp.abs(flat))
    scale = jnp.where(amax > 0, amax / m, 1.0)
    q = jnp.clip(jnp.round(flat / scale), -m, m).astype(jnp.int32)
    sign = (q < 0).astype(jnp.uint8)
    mag = jnp.abs(q).astype(jnp.uint32)
    planes = [sign]
    for i in range(bits - 1):
        planes.append(((mag >> jnp.uint32(bits - 2 - i)) & 1).astype(jnp.uint8))
    words = jnp.stack([bitops.pack_bits(bitops.pad_bits(p)) for p in planes])
    return words, scale


def dequantize_bitplanes(words: jax.Array, scale: jax.Array, bits: int,
                         shape: tuple, keep_planes: int | None = None
                         ) -> jax.Array:
    """Inverse of :func:`quantize_bitplanes`.

    ``keep_planes`` < bits emulates dropping trailing magnitude planes
    (coarser quantization at lower wire cost)."""
    n = 1
    for d in shape:
        n *= d
    kp = bits if keep_planes is None else keep_planes
    sign = bitops.unpack_bits(words[0], n).astype(jnp.bool_)
    mag = jnp.zeros((n,), jnp.uint32)
    for i in range(kp - 1):
        mag = mag | (bitops.unpack_bits(words[1 + i], n).astype(jnp.uint32)
                     << jnp.uint32(bits - 2 - i))
    val = jnp.where(sign, -(mag.astype(jnp.float32)), mag.astype(jnp.float32))
    return (val * scale).reshape(shape)


def ef_compress_tree(grads: Any, residuals: Any, bits: int
                     ) -> Tuple[Any, Any]:
    """Error-feedback round trip on a gradient pytree.

    Returns (decompressed grads as seen after the wire, new residuals).
    The caller feeds the output grads to the optimizer; residuals persist
    in the train state."""
    def one(g, r):
        corrected = g.astype(jnp.float32) + r
        words, scale = quantize_bitplanes(corrected, bits)
        dq = dequantize_bitplanes(words, scale, bits, g.shape)
        return dq.astype(g.dtype), corrected - dq

    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_r = jax.tree_util.tree_leaves(residuals)
    out = [one(g, r) for g, r in zip(flat_g, flat_r)]
    return (jax.tree_util.tree_unflatten(treedef, [o[0] for o in out]),
            jax.tree_util.tree_unflatten(treedef, [o[1] for o in out]))


def zero_residuals(params: Any) -> Any:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def compressed_allreduce_mean(tree: Any, axis_name: str, bits: int) -> Any:
    """Mean-reduce a pytree across ``axis_name`` with compressed wire format
    (use under ``shard_map``). Each member ships packed planes + scale."""
    size = jax.lax.axis_size(axis_name)

    def one(g):
        words, scale = quantize_bitplanes(g, bits)
        all_words = jax.lax.all_gather(words, axis_name)     # (P, bits, W)
        all_scale = jax.lax.all_gather(scale, axis_name)     # (P,)
        dq = jax.vmap(
            lambda w, s: dequantize_bitplanes(w, s, bits, g.shape))(
                all_words, all_scale)
        return (jnp.sum(dq, axis=0) / size).astype(g.dtype)

    return jax.tree.map(one, tree)


def compression_ratio(bits: int) -> float:
    """Wire bytes vs f32 (ignoring the per-tensor scale scalar)."""
    return bits / 32.0
