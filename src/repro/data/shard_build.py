"""Parallel per-shard structure builds (compressed store + text index).

Shard builds are embarrassingly parallel: every shard runs the same
static-shape construction pipeline on its own slice. This helper turns the
historical host-side Python loop into a traced build:

* multi-device (``jax.local_device_count() > 1``): ``pmap`` over a device
  axis with an inner ``vmap`` over the shards each device owns — the mesh
  builds all shards at once and the result is already stacked leaf-wise;
* single device with ``parallel=True``: one ``vmap`` — a single XLA
  program builds every shard (no per-shard dispatch overhead);
* ``parallel=False`` or single device on "auto": the sequential loop —
  per-shard host dispatch, but each shard's build can early-exit on
  concrete values (e.g. the suffix-array doubling loop), which wins on one
  CPU device.

Any traced path requires ``build_one`` to be trace-safe (no host syncs on
data values) — the wavelet-matrix and FM-index builders both are when
their alphabet size is pinned.

Host spans: ``store.build.shards`` (the upload and the per-shard dispatch
loop, or the vmap or pmap call; JAX's trace, lower and compile phases
land under it) and ``store.build.stack`` (stacking the loop's per-shard
trees, reshaping the pmap's device axis away; nothing on the vmap path).
Neither waits for the device.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro import obs


def build_mode(num_shards: int, parallel: str | bool = "auto") -> str:
    """"loop", "vmap" or "pmap": how ``build_shards_stacked`` builds
    ``num_shards`` shards on this process's devices (module doc)."""
    ndev = jax.local_device_count()
    if parallel == "auto":
        mode = "pmap" if (ndev > 1 and num_shards > 1) else "loop"
    elif parallel is True:
        mode = "pmap" if (ndev > 1 and num_shards > 1) else "vmap"
    elif parallel is False:
        mode = "loop"
    else:
        raise ValueError(f"parallel must be 'auto'/True/False, "
                         f"got {parallel!r}")
    if mode == "pmap" and num_shards % ndev != 0:
        mode = "vmap"                  # ragged over devices → one program
    return "loop" if num_shards == 1 else mode


def build_shards_stacked(build_one: Callable, shards: jax.Array, *,
                         parallel: str | bool = "auto",
                         jit_loop: bool = False,
                         retries: int = 0, backoff_s: float = 0.05):
    """Build one pytree per shard row and stack them leaf-wise.

    ``shards``: (num_shards, shard_size) array (any integer dtype).
    ``parallel``: "auto" | True | False as described in the module doc.
    pmap requires ``num_shards`` divisible by the device count; otherwise
    the traced path falls back to a single vmap.

    ``jit_loop=True`` jits ``build_one`` once on the sequential-loop path,
    so every shard reuses one compiled whole-builder executable instead of
    dispatching op-by-op (all shards share one static shape). Leave it off
    for builders that exploit concrete values in loop mode (e.g. the
    suffix-array doubling early exit).

    ``retries > 0`` wraps the whole build in bounded retry with
    exponential backoff (``robust.faults.with_retry``) — the
    rebuild-from-source escalation path uses this so a transiently failing
    device doesn't turn a repairable incident into an outage.
    """
    if retries > 0:
        from repro.robust.faults import with_retry
        return with_retry(
            lambda: build_shards_stacked(build_one, shards,
                                         parallel=parallel,
                                         jit_loop=jit_loop, retries=0),
            retries=retries, backoff_s=backoff_s)
    num_shards = len(shards)
    mode = build_mode(num_shards, parallel)
    with obs.span("store.build.shards"):
        shards = jnp.asarray(shards)
        if mode == "loop":
            fn = jax.jit(build_one) if jit_loop else build_one
            built = [fn(shards[s]) for s in range(num_shards)]
        elif mode == "vmap":
            built = jax.vmap(build_one)(shards)
        else:
            ndev = jax.local_device_count()
            built = jax.pmap(jax.vmap(build_one))(
                shards.reshape(ndev, num_shards // ndev, shards.shape[1]))
    with obs.span("store.build.stack"):
        if mode == "loop":
            return jax.tree.map(lambda *xs: jnp.stack(xs), *built)
        if mode == "vmap":
            return built
        return jax.tree.map(
            lambda l: l.reshape((num_shards,) + l.shape[2:]), built)
