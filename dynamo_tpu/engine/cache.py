"""Paged KV cache: device arrays + host-side block pool with prefix cache.

Device side: per cache GROUP (``ModelConfig.kv_cache_spec``: the layers
that share a page shape) a K array [L_g, num_slots, KV_g, kd] and a V array
[L_g, num_slots, KV_g, vd] (num_slots = num_blocks * block_size), flat slot
addressing. Every group has the same slots and ONE block table indexes them
all: page i of a sequence exists in each group. A model with one group —
all but those with layer kinds — holds the two arrays themselves, one with
more holds a tuple of arrays a stream (``is_multi_group``). Block 0 is the
reserved NULL block — padding slot-maps and block-tables point at it and
its contents are garbage by design (attention masks it out).

Host side: ``BlockPool`` mirrors the reference's block lifecycle (ref:
lib/llm/src/block_manager/pool/managed.rs — active refcounted registry +
inactive LRU reuse pool keyed by SequenceHash; and the mocker's KvManager +
LRU evictor — lib/llm/src/mocker/{kv_manager,evictor}.rs): blocks are
refcounted while sequences use them; on release, hash-identified full blocks
park in an LRU prefix cache for reuse; eviction emits the KV-removed events
the router's radix index relies on (ref: kv_router/indexer.rs).
"""

from __future__ import annotations

import logging
import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np

from dynamo_tpu.tokens import SequenceHash

logger = logging.getLogger("dynamo.engine.cache")

NULL_BLOCK = 0


# ---------------------------------------------------------------- int8 cache
#
# A quantized paged cache is a pytree {"q": int8 [L, slots, KV, hd],
# "s": f32 [L, slots, KV]} — symmetric per-(slot, kv-head) scales. On 16 GB
# v5e chips KV capacity is the wall right after weights (r3 verdict weak #3);
# int8 pages ~halve both the footprint and the decode kernel's HBM page
# traffic (the KV-capacity role of the reference's G1 tier,
# lib/llm/src/block_manager/). Scale overhead: 4/hd ≈ 3% at hd=128.
#
# Numerics contract: dequant is exact in f32 (int8 × f32 scale), and
# re-quantizing a dequantized block reproduces the identical (q, s) pair —
# the max |element| of a dequantized block is 127·s, so s survives the
# roundtrip bit-for-bit. KVBM offload/onboard and disagg transfer ride
# f32 bundles and therefore stay deterministic across tiers.

def is_quant_cache(cache) -> bool:
    return isinstance(cache, dict) and "q" in cache and "s" in cache


def is_multi_group(cache) -> bool:
    """A stream of more than one cache group (a tuple of arrays). The
    block movers — KVBM tiers, disagg bundles, swap, the KV audit — know
    one group; the engine refuses them at build for such a cache."""
    return isinstance(cache, tuple)


def cache_shape(cache) -> tuple:
    """[L, slots, KV, hd] shape for plain or quantized caches."""
    return cache["q"].shape if is_quant_cache(cache) else cache.shape


def quantize_kv(x):
    """[..., KV, hd] values → (int8 [..., KV, hd], f32 scales [..., KV]).

    Symmetric, per-(token, head): s = amax/127 over hd, TRUNCATED to bf16
    precision (stored f32). The truncation is what makes the roundtrip
    exact: with an 8-bit-mantissa s, 127·s is exactly representable, so a
    re-quantize computes amax' = 127·s and recovers the identical s — a
    full-mantissa scale loses the contract to one ulp of rounding in
    fl(fl(127·s)/127). Cost: ≤0.2% scale error, noise under int8's 0.4%
    step. jnp in / jnp out, np in / np out (the host requant path must
    match the traced one bit-for-bit)."""
    import jax.numpy as jnp
    import ml_dtypes

    is_np = isinstance(x, np.ndarray)
    xp = np if is_np else jnp
    bf16 = ml_dtypes.bfloat16 if is_np else jnp.bfloat16
    xf = x.astype(xp.float32)
    amax = xp.max(xp.abs(xf), axis=-1)
    s = (xp.maximum(amax, 1e-8) / 127.0).astype(bf16).astype(xp.float32)
    q = xp.clip(xp.round(xf / s[..., None]), -127, 127).astype(xp.int8)
    return q, s


def gather_pages(cache, lidx, slot_idx):
    """Gather [B, T, KV, hd] pages at layer ``lidx`` from a plain OR int8
    cache (used by every XLA-level attention read path: paged, flash
    prefill, ring). Quantized pages dequantize in the gather's consumer —
    XLA fuses the int8 read + scale multiply, so HBM sees 1 byte/element
    either way."""
    if is_quant_cache(cache):
        return dequantize_kv(cache["q"][lidx, slot_idx],
                             cache["s"][lidx, slot_idx])
    return cache[lidx, slot_idx]


def dequantize_kv(q, s, dtype=None):
    """Exact inverse in f32; optional final cast."""
    import jax.numpy as jnp

    xp = jnp if not isinstance(q, np.ndarray) else np
    out = q.astype(xp.float32) * s[..., None]
    return out if dtype is None else out.astype(dtype)


def pack_kv_blocks(q, s):
    """(int8 [..., bs, KV, hd], f32 [..., bs, KV]) → uint8 [..., X] with
    X = bs·KV·(hd+4): q bytes then scale bytes, per leading index.

    The NATIVE bundle format for quantized caches: offload tiers and the
    disagg wire carry ~1.03 bytes/element instead of the 4 an f32 bundle
    costs (and the device→host copy shrinks the same way). Byte order is
    the host's native layout — every TPU-VM in a fleet is little-endian,
    and bundles never persist across architectures."""
    import jax
    import jax.numpy as jnp

    bs, KV, hd = q.shape[-3:]
    lead = q.shape[:-3]
    qb = jax.lax.bitcast_convert_type(q, jnp.uint8).reshape(
        *lead, bs * KV * hd)
    sb = jax.lax.bitcast_convert_type(s, jnp.uint8).reshape(
        *lead, bs * KV * 4)
    return jnp.concatenate([qb, sb], axis=-1)


def unpack_kv_blocks(buf, block_size: int, KV: int, hd: int):
    """Inverse of :func:`pack_kv_blocks`: uint8 [..., X] →
    (int8 [..., bs, KV, hd], f32 [..., bs, KV])."""
    import jax
    import jax.numpy as jnp

    bs = block_size
    lead = buf.shape[:-1]
    nq = bs * KV * hd
    buf = jnp.asarray(buf)
    q = jax.lax.bitcast_convert_type(
        buf[..., :nq], jnp.int8).reshape(*lead, bs, KV, hd)
    s = jax.lax.bitcast_convert_type(
        buf[..., nq:].reshape(*lead, bs, KV, 4), jnp.float32)
    return q, s


def packed_block_width(block_size: int, KV: int, hd: int) -> int:
    """Trailing byte width of a packed quant-bundle row."""
    return block_size * KV * (hd + 4)


class SwapStore:
    """Byte-budgeted accounting for sequences' KV swapped out to host DRAM.

    Preempt-to-swap stages a victim's device pages in host memory (as the
    same value/packed quant bundles the KVBM G2 tier and the disagg wire
    carry) instead of throwing the KV away and re-prefilling. This class
    owns ONLY the budget arithmetic — buffers live on the engine's per-
    sequence swap entries; the scheduler asks reserve() before a swap-out
    and falls back to recompute preemption when the answer is no.

    ``external_used`` shares the budget with the KVBM host tier: when the
    engine runs G2 offload and swap against one DRAM allowance, available
    swap bytes = budget − swap-reserved − G2-resident (and the G2 tier's
    puts symmetrically evict down to budget − swap-reserved — HostTier's
    own ``external_used`` hook, wired by the engine). Thread-safe: the
    reserve happens on the event loop, the release can come from the
    offload worker threads' completion callbacks.
    """

    def __init__(self, budget_bytes: int,
                 external_used: Optional[Callable[[], int]] = None,
                 make_room: Optional[Callable[[int], None]] = None):
        self.budget = max(0, int(budget_bytes))
        self.external_used = external_used
        #: fn(target_bytes): ask the external consumer to shrink to
        #: ``target_bytes`` — without it, a G2 prefix cache that has
        #: naturally filled the shared allowance (LRU caches always do)
        #: would turn every reserve() into a permanent miss and silently
        #: disable swap in exactly the flagship KVBM deployment. G2's
        #: redundant cache copies yield to live-sequence KV.
        self.make_room = make_room
        self.used = 0  # bytes reserved by live swap entries
        self._lock = threading.Lock()

    def _external(self) -> int:
        # a lock-free attribute read on the G2 tier (never a lock
        # acquisition): safe under our lock, and the residual race with a
        # concurrent G2 put is bounded by one block because the tier
        # enforces the shared budget from its side too
        if self.external_used is None:
            return 0
        try:
            return int(self.external_used())
        except Exception:  # a broken G2 probe must not wedge swap
            logger.exception("swap external_used probe failed")
            return 0

    def reserve(self, nbytes: int) -> bool:
        with self._lock:
            ext = self._external()
            avail = self.budget - self.used - ext
            if avail < nbytes and self.make_room is not None and ext > 0:
                # evict the external LRU down far enough that this
                # reservation fits (kvbm takes its own lock; it never
                # takes ours, so the ordering is acyclic)
                try:
                    self.make_room(max(0, ext - (nbytes - avail)))
                except Exception:
                    logger.exception("swap make_room failed")
                avail = self.budget - self.used - self._external()
            if avail < nbytes:
                return False
            self.used += nbytes
            return True

    def release(self, nbytes: int) -> None:
        with self._lock:
            self.used = max(0, self.used - nbytes)


@dataclass
class BlockMeta:
    block_id: int
    ref_count: int = 0
    #: chained sequence hash once the block is full + registered (None = partial)
    seq_hash: Optional[SequenceHash] = None
    #: local tokens-only hash (the router's radix edge key)
    tokens_hash: Optional[int] = None
    parent_hash: Optional[SequenceHash] = None


class BlockPool:
    """Refcounted block allocator with an inactive LRU prefix cache.

    Events: ``on_removed(seq_hashes)`` fires when cached blocks are evicted
    (reused for new data), matching the reference's KV-removed events.
    """

    def __init__(self, num_blocks: int, enable_prefix_caching: bool = True,
                 on_removed: Optional[Callable[[list[int]], None]] = None,
                 ledger=None):
        self.num_blocks = num_blocks
        self.enable_prefix_caching = enable_prefix_caching
        self.on_removed = on_removed
        #: optional WorkerKvLedger (observability/kvaudit.py): the audit
        #: plane's device-tier (g1) residency digest, folded inline at
        #: register/evict/clear — membership mirrors _by_hash exactly
        self.ledger = ledger
        #: fn() called whenever release() returns capacity to the pool —
        #: the engine loop parks on it instead of polling when it is
        #: memory-starved (a freed block is exactly what unblocks plan())
        self.on_freed: Optional[Callable[[], None]] = None
        # block 0 reserved as NULL
        self._free: list[int] = list(range(num_blocks - 1, 0, -1))
        self._meta: dict[int, BlockMeta] = {}
        #: seq_hash -> block_id for *all* registered full blocks (active+inactive)
        self._by_hash: dict[SequenceHash, int] = {}
        #: inactive (refcount 0) cached blocks, LRU order (oldest first)
        self._lru: "OrderedDict[SequenceHash, int]" = OrderedDict()
        #: blocks' worth of KV currently parked on HOST by preempt-to-swap —
        #: accounting DISTINCT from the LRU prefix cache above: these blocks
        #: are NOT device-resident (their device ids were released) but their
        #: sequences are live and will re-allocate on swap-in
        self.swapped_blocks = 0

    # -- swap accounting ---------------------------------------------------

    def note_swapped_out(self, n: int) -> None:
        self.swapped_blocks += n

    def note_swapped_in(self, n: int) -> None:
        self.swapped_blocks = max(0, self.swapped_blocks - n)

    # -- capacity ----------------------------------------------------------

    @property
    def num_free_blocks(self) -> int:
        """Blocks allocatable right now (free list + evictable LRU)."""
        return len(self._free) + len(self._lru)

    @property
    def num_active_blocks(self) -> int:
        return len(self._meta) - len(self._lru)

    def usage(self) -> float:
        usable = self.num_blocks - 1
        return (usable - self.num_free_blocks) / max(1, usable)

    # -- allocation --------------------------------------------------------

    def allocate(self, n: int) -> Optional[list[int]]:
        """Allocate n blocks, evicting LRU-cached blocks if needed.

        Returns None (allocating nothing) if capacity is insufficient.
        """
        if self.num_free_blocks < n:
            return None
        out = []
        evicted: list[int] = []
        for _ in range(n):
            if self._free:
                bid = self._free.pop()
            else:
                h, bid = self._lru.popitem(last=False)
                meta = self._meta.pop(bid)
                self._by_hash.pop(h, None)
                if self.ledger is not None:
                    self.ledger.remove("g1", h)
                evicted.append(meta.seq_hash)
            self._meta[bid] = BlockMeta(block_id=bid, ref_count=1)
            out.append(bid)
        if evicted and self.on_removed:
            self.on_removed(evicted)
        return out

    # -- prefix cache ------------------------------------------------------

    def match_prefix(self, seq_hashes: list[SequenceHash]) -> list[int]:
        """Longest cached prefix: block ids for leading seq hashes, increffed."""
        if not self.enable_prefix_caching:
            return []
        out = []
        for h in seq_hashes:
            bid = self._by_hash.get(h)
            if bid is None:
                break
            meta = self._meta[bid]
            if meta.ref_count == 0:
                self._lru.pop(h, None)
            meta.ref_count += 1
            out.append(bid)
        return out

    def lookup(self, seq_hash: SequenceHash) -> Optional[int]:
        """Block id currently holding ``seq_hash``'s KV (active or LRU-
        cached), or None. Read-only — no incref, no LRU touch; callers
        that gather asynchronously must pin via acquire()/release()."""
        if not self.enable_prefix_caching:
            return None
        return self._by_hash.get(seq_hash)

    def register(self, block_id: int, seq_hash: SequenceHash, tokens_hash: int,
                 parent_hash: Optional[SequenceHash]) -> bool:
        """Mark a full block as identified by its hashes (→ reusable).

        Returns False if an identical block is already registered (duplicate
        content on this worker — caller may dedup, we keep both refs valid).
        """
        meta = self._meta[block_id]
        meta.seq_hash, meta.tokens_hash, meta.parent_hash = seq_hash, tokens_hash, parent_hash
        if not self.enable_prefix_caching:
            return True
        if seq_hash in self._by_hash and self._by_hash[seq_hash] != block_id:
            return False
        if self.ledger is not None and seq_hash not in self._by_hash:
            self.ledger.add("g1", seq_hash)
        self._by_hash[seq_hash] = block_id
        return True

    def acquire(self, block_ids: list[int]) -> None:
        """Incref blocks (e.g. pin for an async offload gather); pairs with
        release(). Cached refcount-0 blocks are pulled out of the LRU."""
        for bid in block_ids:
            meta = self._meta.get(bid)
            if meta is None:
                continue
            if (meta.ref_count == 0 and meta.seq_hash is not None):
                self._lru.pop(meta.seq_hash, None)
            meta.ref_count += 1

    def release(self, block_ids: list[int]) -> None:
        """Decref; refcount-0 blocks go to the LRU cache (if hashed) or free.

        No removed-event fires here: unhashed/duplicate blocks were never
        announced as stored, and a hash's home block parks in the LRU (its
        event fires on eviction in allocate()).
        """
        freed = False
        for bid in block_ids:
            if bid == NULL_BLOCK:
                continue
            meta = self._meta.get(bid)
            if meta is None:
                continue
            meta.ref_count -= 1
            if meta.ref_count > 0:
                continue
            freed = True  # LRU-parked blocks count as allocatable too
            if (meta.seq_hash is not None and self.enable_prefix_caching
                    and self._by_hash.get(meta.seq_hash) == bid):
                self._lru[meta.seq_hash] = bid
                self._lru.move_to_end(meta.seq_hash)
            else:
                self._meta.pop(bid)
                self._free.append(bid)
        if freed and self.on_freed:
            self.on_freed()

    def clear(self) -> None:
        """Drop the entire prefix cache (admin clear_kv_blocks analog)."""
        for h, bid in list(self._lru.items()):
            self._meta.pop(bid, None)
            self._by_hash.pop(h, None)
            if self.ledger is not None:
                self.ledger.remove("g1", h)
            self._free.append(bid)
        self._lru.clear()
        if self.on_removed:
            self.on_removed(None)  # None = cleared-all sentinel
        if self.on_freed:
            self.on_freed()


def allocate_device_cache(cfg, num_blocks: int, block_size: int, mesh=None,
                          dtype=None):
    """Allocate the k/v cache arrays (zeros): per group of
    ``cfg.kv_cache_spec`` a [L_g, num_slots, KV_g, width] array a stream;
    one group returns the two arrays, more return two tuples of them.

    ``dtype="int8"`` returns quantized caches ({"q": int8, "s": f32 scales}
    pytrees — see module int8 notes); any other dtype (or None = model
    dtype) returns plain arrays.

    Under a mesh the zeros come out of a jitted creation already sharded:
    the whole pool never sits on the first device (it is sized for all of
    them), and shards land on another host's devices too.
    """
    import jax.numpy as jnp

    from dynamo_tpu.engine.model import cache_shardings

    quant = dtype == "int8" or (dtype is not None
                                and jnp.dtype(dtype) == jnp.int8)
    dtype = jnp.dtype(cfg.dtype) if (dtype is None or quant) else dtype
    groups = cfg.kv_cache_spec
    if len(groups) > 1 and (mesh is not None or quant):
        raise NotImplementedError(
            f"a cache of {len(groups)} groups is neither sharded over a "
            "mesh nor quantized yet (one chip, model-dtype pages)")
    slots = num_blocks * block_size

    def alloc(shape, dt, sh):
        if sh is None:
            return jnp.zeros(shape, dt)
        from dynamo_tpu.parallel.multihost import global_zeros

        return global_zeros(shape, dt, sh)

    sh = cache_shardings(mesh, cfg, quant=quant) if mesh is not None else None

    def one(shape):
        if not quant:
            return alloc(shape, dtype, sh)
        return {"q": alloc(shape, jnp.int8, sh["q"] if sh else None),
                "s": alloc(shape[:-1], jnp.float32, sh["s"] if sh else None)}

    ks = tuple(one((len(g.layers), slots, *g.k_shape)) for g in groups)
    vs = tuple(one((len(g.layers), slots, g.kv_heads, g.v_dim))
               for g in groups)
    return (ks[0], vs[0]) if len(groups) == 1 else (ks, vs)


def allocate_state(cfg, slots: int):
    """The recurrent-state arrays of a model with state layers (zeros), one
    slot a running sequence and the dump slot padding rows write to, last:
    the convolution's tail ``[L, slots + 1, (taps - 1) · C]`` and, for
    Mamba-2 layers, the SSM state ``[L, slots + 1, H // pack, N, pack · P]``
    beside it — a tuple of one array (the short convolution: its tail is
    its whole state) or two. None for a model without state layers. The
    third kind of cache beside the pages: allocate it BEFORE the pool is
    sized, so that :func:`hbm_sized_num_blocks` sees what it left."""
    import jax.numpy as jnp

    spec = cfg.state_spec
    if spec is None:
        return None
    n = len(spec.layers)
    taps, width = spec.conv_shape
    conv = jnp.zeros((n, slots + 1, taps * width), spec.conv_dtype)
    if spec.ssm_shape is None:
        return (conv,)
    return (conv,
            jnp.zeros((n, slots + 1, *spec.ssm_shape), spec.ssm_dtype))


def tree_nbytes(params) -> int:
    """Resident bytes of a params pytree (int4 packs two weights/byte on
    TPU HBM — itemsize reports 1)."""
    import jax

    total = 0
    for x in jax.tree_util.tree_leaves(params):
        n = x.size // 2 if x.dtype.name == "int4" else x.size * x.dtype.itemsize
        total += n
    return total


def hbm_sized_num_blocks(cfg, block_size: int, fraction: float,
                         tp_size: int = 1, default: int = 512,
                         kv_cache_dtype: Optional[str] = None,
                         min_tokens: int = 0) -> int:
    """Size the block count from the device's free memory.

    ``min_tokens``: what one sequence may need (``max_model_len``, asked by
    a model whose state slots were allocated first): a pool that cannot
    hold it raises with the arithmetic instead of starting a worker no
    prompt fits.

    The device says what it has: ``memory_stats()`` is called plainly, and
    a device that cannot answer is an error — a pool sized from a guess
    either wastes the chip or thrashes it in preemptions. Only the CPU
    backend (tests), which reports no stats at all, gets ``default``.

    ``kv_cache_dtype="int8"``: 1 byte/element + 4-byte f32 scale per
    (slot, head) — block capacity roughly doubles vs bf16."""
    import jax

    dev = jax.devices()[0]
    stats = dev.memory_stats()
    if stats is None:
        if dev.platform != "cpu":
            raise RuntimeError(
                f"{dev.platform} device {dev.device_kind!r} reports no "
                "memory_stats(): cannot size the KV pool (pass num_blocks)")
        return default
    free = stats["bytes_limit"] - stats["bytes_in_use"]
    bytes_per_block = block_size * sum(
        slot_bytes(cfg, g, tp_size, kv_cache_dtype)
        for g in cfg.kv_cache_spec)
    n = int(free * fraction / max(1, bytes_per_block))
    if n * block_size < min_tokens:
        raise RuntimeError(
            f"the KV pool would hold {n} blocks = {n * block_size} tokens, "
            f"fewer than the {min_tokens} one sequence may need: "
            f"{stats['bytes_limit']} B on the device - "
            f"{stats['bytes_in_use']} B in use (weights and, allocated "
            f"before the pool, the state slots) = {free} B free, x "
            f"{fraction} for the pool, / {bytes_per_block} B a block of "
            f"{block_size} tokens; lower --max-num-seqs (state slots) or "
            "--max-model-len")
    return max(16, n)


def slot_bytes(cfg, group, tp_size: int = 1,
               kv_cache_dtype: Optional[str] = None) -> int:
    """Bytes one slot (one token) takes in one cache group, on one device:
    K and V rows of every layer of the group."""
    tp = max(1, tp_size)
    # MLA's single-latent-head cache is not TP-shardable (replicated)
    heads = group.kv_heads // tp if group.kv_heads % tp == 0 \
        else group.kv_heads
    if kv_cache_dtype == "int8":
        return len(group.layers) * heads * (group.k_dim + 4
                                            + group.v_dim + 4)
    return group.bytes_per_slot(2 if cfg.dtype == "bfloat16" else 4) \
        // group.kv_heads * heads
