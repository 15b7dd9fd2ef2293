"""Paged KV cache: device arrays + host-side block pool with prefix cache.

Device side: per cache GROUP (``ModelConfig.kv_cache_spec``: the layers
that share a page shape) a K array [L_g, num_slots, KV_g, kd] and a V array
[L_g, num_slots, KV_g, vd] (num_slots = num_blocks * block_size), flat slot
addressing. Every group has the same slots and ONE block table indexes them
all: page i of a sequence exists in each group. A model with one group —
all but those with layer kinds — holds the two arrays themselves, one with
more holds a tuple of arrays a stream. Block 0 is the reserved NULL block —
padding slot-maps and block-tables point at it and its contents are garbage
by design (attention masks it out). What a page IS (plain array or int8
{"q", "s"} pair, how a block is packed for the host) is ``ops/kv_pages.py``'s;
``KvPages`` below holds a worker's arrays and is the only code outside
``ops/`` that knows their format. This module stays importable without JAX
(the mocker imports the scheduler, which imports ``BlockPool``), so what
needs JAX or ``ops/`` imports it where it runs.

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


#: what a block mover needs of the cache it moves blocks of (the engine's
#: refusal table asks for them by these names)
ONE_GROUP, NO_STATE = "one group", "no state"


def movers_lack(cfg) -> dict:
    """What ``cfg``'s cache lacks of what a block mover needs → how a
    refusal names that cache. A mover knows one cache group, and nothing can
    resume from a boundary whose recurrent state nobody kept; empty where
    blocks move. The next layer kind that moves nothing adds an entry."""
    out, spec, groups = {}, cfg.state_spec, cfg.kv_cache_spec
    if spec is not None:
        out[NO_STATE] = (f"a model with recurrent state ({len(spec.layers)} "
                         f"{spec.mixer} layers)")
    if len(groups) > 1:
        out[ONE_GROUP] = (f"a cache of {len(groups)} groups (one per layer "
                          "kind)")
    return out


class KvPages:
    """The one holder of a worker's KV pages and recurrent-state slots.

    ``k`` and ``v`` are what :func:`allocate_device_cache` returned for
    ``cfg``, ``state`` what :func:`allocate_state` did (None for a model
    without state layers). The step programs take and return the arrays
    themselves, and their caller assigns what comes back to these fields;
    everything else that touches a page — a block leaving for the host, a
    tier, a peer, or coming back — goes through the methods below, so that a
    new kind of cache is taught to ONE place. A cache of several groups or
    with state beside it (``lacks``, :func:`movers_lack`) is held like any
    other and moves nothing: :meth:`gather` and :meth:`scatter` raise.
    """

    def __init__(self, cfg, k, v, block_size: int, num_blocks: int,
                 state=None):
        from dynamo_tpu.ops.kv_pages import cache_shape, is_quant_cache

        self.k, self.v, self.state = k, v, state
        self.block_size, self.num_blocks = block_size, num_blocks
        self.groups = len(cfg.kv_cache_spec)
        self.lacks = movers_lack(cfg)
        self.nbytes = tree_nbytes((k, v))
        self.state_nbytes = tree_nbytes(state)
        #: device bytes a block takes (both streams, quant scales included)
        self.device_block_nbytes = self.nbytes // max(1, num_blocks)
        self.quant = self.groups == 1 and is_quant_cache(k)
        #: [L, slots, KV, hd] of the K stream (None where nothing moves)
        self.dims: Optional[tuple] = (None if self.lacks
                                      else tuple(cache_shape(k)))
        #: host bytes of one block as :meth:`to_host` returns it: k + v, the
        #: pow2 gather padding cut off (what a swap or a tier budgets)
        self.host_block_nbytes = 0 if self.lacks else sum(
            int(np.prod(self._block_shape(cache_shape(c))))
            * (1 if self.quant else c.dtype.itemsize) for c in (k, v))

    def _movable(self, what: str) -> None:
        if self.lacks:
            raise NotImplementedError(
                f"{' and '.join(self.lacks.values())} does not support: "
                f"{what} (no block of it moves)")

    def _block_shape(self, dims, packed: Optional[bool] = None,
                     layers: Optional[int] = None) -> tuple:
        from dynamo_tpu.ops.kv_pages import packed_block_width

        L, _slots, KV, hd = dims
        n = L if layers is None else layers
        if self.quant if packed is None else packed:
            return (n, packed_block_width(self.block_size, KV, hd))
        return (n, self.block_size, KV, hd)

    def host_block_shape(self, packed: Optional[bool] = None,
                         layers: Optional[int] = None) -> tuple:
        """One block of a host-side k, the block axis left out: [layers, X]
        packed bytes or [layers, bs, KV, hd] values — as these pages gather
        it, unless ``packed`` says which; whole depth unless ``layers`` says
        how many."""
        self._movable("a block's host shape")
        return self._block_shape(self.dims, packed, layers)

    def gather(self, ids):
        """Blocks ``ids`` of both streams, on the device: value bundles
        [L, P, bs, KV, hd], or packed uint8 [L, P, X] from int8 pages
        (P = next power of two: ``ops/block_copy.gather_blocks``). The
        gathers are dispatched before this returns and read the arrays as
        they are now, whatever a later step writes."""
        from dynamo_tpu.ops.block_copy import gather_blocks

        self._movable("gathering blocks")
        bs = self.block_size
        return (gather_blocks(self.k, ids, block_size=bs),
                gather_blocks(self.v, ids, block_size=bs))

    @staticmethod
    def to_host(kb, vb, n: int):
        """A gathered pair on the host, cut back to its ``n`` real blocks:
        contiguous copies, not views — a view would pin the whole
        pow2-padded gather buffer past a tier's byte budget. Blocks on the
        device→host copy: call it off the event loop."""
        return (np.ascontiguousarray(np.asarray(kb)[:, :n]),
                np.ascontiguousarray(np.asarray(vb)[:, :n]))

    @staticmethod
    def to_host_blocks(kb, vb, n: int) -> list:
        """:meth:`to_host`, one (k, v) pair a block: what a tier keeps and a
        peer pulls, each a copy of its own."""
        kbh, vbh = np.asarray(kb), np.asarray(vb)
        return [(np.ascontiguousarray(kbh[:, i]),
                 np.ascontiguousarray(vbh[:, i])) for i in range(n)]

    def scatter(self, ids, k, v, start_layer=None) -> None:
        """Write bundles ``k``, ``v`` (as :meth:`to_host` made them, here or
        on a peer; [L, n, ...]) into blocks ``ids``. ``start_layer`` set
        means they are a layer slice covering [start_layer, start_layer +
        k.shape[0]) only. The arrays are donated and replaced: a later step
        reads the new pages by data dependency, no host sync."""
        from dynamo_tpu.ops.block_copy import scatter_blocks

        self._movable("scattering blocks")
        bs = self.block_size
        self.k = scatter_blocks(self.k, ids, k, block_size=bs,
                                start_layer=start_layer)
        self.v = scatter_blocks(self.v, ids, v, block_size=bs,
                                start_layer=start_layer)

    def accepts(self, bundle) -> bool:
        """Whether a peer's ``KvBundle`` can be scattered here: same block
        size, same depth, same heads and widths. Either layout is taken
        (:func:`ops.block_copy.scatter_blocks` converts); pages that move
        nothing take nothing."""
        if self.lacks or bundle.block_size != self.block_size:
            return False
        L, k = self.dims[0], bundle.k
        # layer slices (docs/disagg.md): the bundle covers layers
        # [start_layer, start_layer + k.shape[0]) of a total_layers-deep
        # cache — depth must match OUR cache and the slice must fit
        tl = getattr(bundle, "total_layers", None)
        layers = None
        if tl is not None:
            sl = getattr(bundle, "start_layer", 0) or 0
            if tl != L or sl < 0 or sl + k.shape[0] > L:
                return False
            layers = k.shape[0]
        if k.ndim == 3:  # packed quant bundle [nL, n, X]
            return (k.dtype == np.uint8 and (k.shape[0], k.shape[2])
                    == self.host_block_shape(True, layers))
        want = self.host_block_shape(False, layers)
        return k.shape[0] == want[0] and k.shape[3:] == want[2:]

    def layer_ranges(self, g: int) -> Optional[list]:
        """The depth cut into ``g`` contiguous (start, end) ranges, the
        earlier ones a layer longer where it does not divide; None where
        there is nothing to split."""
        L = self.dims[0]
        g = min(g, L)
        if g <= 1:
            return None
        base, rem = divmod(L, g)
        out, s = [], 0
        for i in range(g):
            e = s + base + (1 if i < rem else 0)
            out.append((s, e))
            s = e
        return out


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
