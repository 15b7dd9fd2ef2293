"""Paged-KV block gather/scatter (the reference CUDA kernel's TPU analog).

The reference ships one CUDA kernel — a dimension-aware strided block copy
used for KV transfer and (de)fragmentation (ref: lib/llm/src/kernels/
block_copy.cu:40-758). On TPU the same jobs are XLA dynamic gathers/scatters
over the flat paged cache: XLA already emits single-pass DMA programs for
these, so the kernels below are thin, jit-friendly contracts used by the
KVBM offload path (device→host staging) and disagg KV transfer:

  gather_blocks:  cache [L, slots, KV, hd] + ids [n] → [L, n, bs, KV, hd]
  scatter_blocks: writes such a bundle back into (possibly different) slots

A layout transpose between prefill-TP and decode-TP shardings is the
``reshard`` helper: gather → logical reshape → device_put under the target
sharding (XLA inserts the all-to-all).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.ops.kv_pages import (
    dequantize_kv, is_quant_cache, pack_kv_blocks, quantize_kv,
    unpack_kv_blocks,
)


def _pad_pow2_ids(block_ids: np.ndarray) -> np.ndarray:
    """Pad an id list to the next power of two by repeating the last id —
    duplicate gathers/scatters of the same block are idempotent, and the
    bounded shape set keeps the XLA compile cache from growing per prompt
    length (the engine pads all other shapes the same way)."""
    n = len(block_ids)
    p = 1
    while p < n:
        p *= 2
    if p == n:
        return block_ids
    return np.concatenate([block_ids, np.repeat(block_ids[-1:], p - n)])


def gather_blocks(cache, block_ids, *, block_size: int) -> jax.Array:
    """Pull whole blocks out of the flat paged cache.

    cache: [L, num_slots, KV, hd] array → bundle [L, P, block_size, KV, hd];
    int8 {"q","s"} cache → PACKED uint8 bundle [L, P, bs·KV·(hd+4)] (native
    (q, s) bytes — ops/kv_pages.pack_kv_blocks). P = next pow2 ≥ n (trailing
    entries repeat the last block; slice axis 1 host-side for exact n).

    Packed bundles keep KVBM tiers and the disagg wire at ~1 byte/element
    (4x smaller than an f32 bundle, 2x smaller than bf16) and make the
    offload→onboard roundtrip bit-exact by construction — the packing
    happens on device, so the device→host copy shrinks identically."""
    if is_quant_cache(cache):
        L, slots, KV, hd = cache["q"].shape
        ids = jnp.asarray(_pad_pow2_ids(np.asarray(block_ids, np.int32)))
        qp = cache["q"].reshape(L, slots // block_size, block_size, KV, hd)
        sp = cache["s"].reshape(L, slots // block_size, block_size, KV)
        return pack_kv_blocks(jnp.take(qp, ids, axis=1),
                              jnp.take(sp, ids, axis=1))
    L, slots, KV, hd = cache.shape
    ids = _pad_pow2_ids(np.asarray(block_ids, np.int32))
    paged = cache.reshape(L, slots // block_size, block_size, KV, hd)
    return jnp.take(paged, jnp.asarray(ids), axis=1)


@functools.partial(jax.jit, static_argnames=("block_size",), donate_argnums=(0,))
def _scatter(cache, block_ids, bundle, *, block_size):
    L, slots, KV, hd = cache.shape
    paged = cache.reshape(L, slots // block_size, block_size, KV, hd)
    return paged.at[:, block_ids].set(bundle).reshape(L, slots, KV, hd)


@functools.partial(jax.jit, static_argnames=("block_size",), donate_argnums=(0,))
def _scatter_quant(cache, block_ids, bundle, *, block_size):
    """Quantize the f32 bundle in-trace and write both cache leaves."""
    L, slots, KV, hd = cache["q"].shape
    qb, sb = quantize_kv(bundle)  # [L, n, bs, KV, hd] / [L, n, bs, KV]
    qp = cache["q"].reshape(L, slots // block_size, block_size, KV, hd)
    sp = cache["s"].reshape(L, slots // block_size, block_size, KV)
    return {
        "q": qp.at[:, block_ids].set(qb).reshape(L, slots, KV, hd),
        "s": sp.at[:, block_ids].set(sb).reshape(L, slots, KV),
    }


@functools.partial(jax.jit, static_argnames=("block_size",), donate_argnums=(0,))
def _scatter_packed(cache, block_ids, bundle, *, block_size):
    """Write a packed uint8 bundle's (q, s) bytes straight into the cache
    leaves — no requant, bit-exact by construction."""
    L, slots, KV, hd = cache["q"].shape
    qb, sb = unpack_kv_blocks(bundle, block_size, KV, hd)
    qp = cache["q"].reshape(L, slots // block_size, block_size, KV, hd)
    sp = cache["s"].reshape(L, slots // block_size, block_size, KV)
    return {
        "q": qp.at[:, block_ids].set(qb).reshape(L, slots, KV, hd),
        "s": sp.at[:, block_ids].set(sb).reshape(L, slots, KV),
    }


@functools.partial(jax.jit, static_argnames=("block_size", "start_layer"),
                   donate_argnums=(0,))
def _scatter_layers(cache, block_ids, bundle, *, block_size, start_layer):
    """Write a LAYER SLICE [nL, n, bs, KV, hd] of a bundle into layers
    [start_layer, start_layer+nL) of the cache. start_layer is static: the
    prefill side splits into a fixed group count, so the signature set is
    bounded by groups × widths (same discipline as the pow2 id padding)."""
    L, slots, KV, hd = cache.shape
    nL = bundle.shape[0]
    paged = cache.reshape(L, slots // block_size, block_size, KV, hd)
    return (paged.at[start_layer:start_layer + nL, block_ids]
            .set(bundle).reshape(L, slots, KV, hd))


@functools.partial(jax.jit, static_argnames=("block_size", "start_layer"),
                   donate_argnums=(0,))
def _scatter_packed_layers(cache, block_ids, bundle, *, block_size,
                           start_layer):
    """Layer-sliced write of a packed uint8 [nL, n, X] quant bundle."""
    L, slots, KV, hd = cache["q"].shape
    nL = bundle.shape[0]
    qb, sb = unpack_kv_blocks(bundle, block_size, KV, hd)
    qp = cache["q"].reshape(L, slots // block_size, block_size, KV, hd)
    sp = cache["s"].reshape(L, slots // block_size, block_size, KV)
    return {
        "q": (qp.at[start_layer:start_layer + nL, block_ids]
              .set(qb).reshape(L, slots, KV, hd)),
        "s": (sp.at[start_layer:start_layer + nL, block_ids]
              .set(sb).reshape(L, slots, KV)),
    }


@functools.partial(jax.jit, static_argnames=("block_size", "start_layer"),
                   donate_argnums=(0,))
def _scatter_quant_layers(cache, block_ids, bundle, *, block_size,
                          start_layer):
    """Layer-sliced write of a VALUE bundle into an int8 cache (quantize
    in-trace — the cross-layout pair of _scatter_quant)."""
    L, slots, KV, hd = cache["q"].shape
    nL = bundle.shape[0]
    qb, sb = quantize_kv(bundle)
    qp = cache["q"].reshape(L, slots // block_size, block_size, KV, hd)
    sp = cache["s"].reshape(L, slots // block_size, block_size, KV)
    return {
        "q": (qp.at[start_layer:start_layer + nL, block_ids]
              .set(qb).reshape(L, slots, KV, hd)),
        "s": (sp.at[start_layer:start_layer + nL, block_ids]
              .set(sb).reshape(L, slots, KV)),
    }


def _is_packed(bundle) -> bool:
    # attribute check, not np.asarray: device bundles must not round-trip
    # through host memory just to inspect dtype
    return (getattr(bundle, "dtype", None) == np.uint8
            and getattr(bundle, "ndim", 0) == 3)


def scatter_blocks(cache, block_ids, bundle, *, block_size: int,
                   start_layer=None):
    """Write a gathered bundle into blocks of the cache; returns new cache.

    bundle: [L, n, bs, KV, hd] values (np or jax), or a packed uint8
    [L, n, X] quant bundle (gather_blocks' native int8-cache format). The
    flat cache is donated at the jit boundary (reshapes live inside it), so
    the write is in-place in HBM — no transient second cache. ids/bundle
    are pow2-padded (idempotent duplicate writes) to bound the compile
    cache.

    Cross-layout pairs both work: a packed bundle into a plain cache
    dequantizes on the way in (mixed prefill/decode deployments); a value
    bundle into an int8 cache re-quantizes in-trace (bit-exact for bundles
    that started as quantized pages — ops/kv_pages.py's numerics contract).

    ``start_layer`` (int) means the bundle is a LAYER SLICE: its leading
    axis covers only layers [start_layer, start_layer + nL) of the cache —
    the layer-interleaved disagg transfer path (docs/disagg.md). None =
    full depth.
    """
    ids = np.asarray(block_ids, np.int32)
    pids = _pad_pow2_ids(ids)
    packed = _is_packed(bundle)
    # direct-transfer bundles arrive ALREADY pow2-padded (gather width kept
    # across the wire), so the pad delta is vs the bundle's actual width,
    # not len(ids)
    missing = len(pids) - bundle.shape[1]
    if missing > 0:
        if isinstance(bundle, jax.Array):
            # device bundles pad on device — a numpy round-trip would stage
            # every page through host RAM
            pad = jnp.repeat(bundle[:, -1:], missing, axis=1)
            bundle = jnp.concatenate([bundle, pad], axis=1)
        else:
            pad = np.repeat(np.asarray(bundle[:, -1:]), missing, axis=1)
            bundle = np.concatenate([np.asarray(bundle), pad], axis=1)
    elif missing < 0:
        raise ValueError(
            f"bundle width {bundle.shape[1]} exceeds padded id count "
            f"{len(pids)} — ids and bundle disagree")
    if is_quant_cache(cache):
        if packed:
            if start_layer is not None:
                return _scatter_packed_layers(cache, jnp.asarray(pids),
                                              jnp.asarray(bundle),
                                              block_size=block_size,
                                              start_layer=int(start_layer))
            return _scatter_packed(cache, jnp.asarray(pids),
                                   jnp.asarray(bundle),
                                   block_size=block_size)
        if start_layer is not None:
            return _scatter_quant_layers(cache, jnp.asarray(pids),
                                         jnp.asarray(bundle, jnp.float32),
                                         block_size=block_size,
                                         start_layer=int(start_layer))
        return _scatter_quant(cache, jnp.asarray(pids),
                              jnp.asarray(bundle, jnp.float32),
                              block_size=block_size)
    if packed:  # quantized prefill → full-precision decode cache
        KV, hd = cache.shape[2], cache.shape[3]
        qb, sb = unpack_kv_blocks(jnp.asarray(bundle), block_size, KV, hd)
        bundle = dequantize_kv(qb, sb)
    if start_layer is not None:
        return _scatter_layers(cache, jnp.asarray(pids),
                               jnp.asarray(bundle).astype(cache.dtype),
                               block_size=block_size,
                               start_layer=int(start_layer))
    return _scatter(cache, jnp.asarray(pids),
                    jnp.asarray(bundle).astype(cache.dtype),
                    block_size=block_size)


def reshard_bundle(bundle: jax.Array, sharding) -> jax.Array:
    """Re-lay a KV bundle onto a different sharding (prefill-TP ≠ decode-TP).

    XLA lowers the device_put to the needed collective (all-to-all /
    all-gather over ICI) — the TPU counterpart of the reference's
    layout-transpose copy between prefill and decode workers
    (ref: docs/architecture/disagg_serving.md:103).
    """
    return jax.device_put(bundle, sharding)
