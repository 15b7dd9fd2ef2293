"""AsyncJaxEngine: the native TPU token-generation engine.

The engine loop executes Scheduler plans as jitted steps:

    plan() → [prefill chunk jit call] + [decode batch jit call] → sample →
    commit bookkeeping → emit LLMEngineOutput per sequence → KV events

Static-shape discipline (XLA semantics — one trace per bucket): chunk
lengths, decode batch sizes, and block-table widths are padded to
EngineArgs buckets, so steady-state serving touches a handful of compiled
programs. Caches are donated through every call (no HBM copies).

This module is the TPU-native replacement for the reference's delegated
engine (ref: components/backends/vllm/src/dynamo/vllm/{main,handlers}.py);
its generate() contract matches the pipeline's EngineFn so it slots behind
Backend/Migration/Router operators unchanged.
"""

from __future__ import annotations

import asyncio
import collections
import functools
import itertools
import json
import logging
import time
from typing import AsyncIterator, Callable, Optional

import numpy as np

from dynamo_tpu.engine.cache import (
    NO_STATE, NULL_BLOCK, ONE_GROUP, BlockPool, KvPages, SwapStore,
    allocate_device_cache, allocate_state, hbm_sized_num_blocks, movers_lack,
    slot_bytes, tree_nbytes,
)
from dynamo_tpu.engine.config import (
    RAGGED_MAX_CHUNKS, EngineArgs, ModelConfig,
)
from dynamo_tpu.engine.scheduler import Scheduler, SeqState, StepPlan
from dynamo_tpu.protocols import FinishReason, LLMEngineOutput, PreprocessedRequest
from dynamo_tpu.runtime.chaos import get_chaos as _get_chaos
from dynamo_tpu.runtime.context import StreamError
from dynamo_tpu.router.protocols import (
    ForwardPassMetrics, KvCacheEvent, KvStats, SpecDecodeStats, StoredBlock,
    WorkerStats,
)

logger = logging.getLogger("dynamo.engine")

#: standalone preempt-to-swap host budget when no G2 tier is configured
DEFAULT_SWAP_HOST_BYTES = 1 << 30

#: What moves, shares or re-reads PART of a sequence's cache, and what each
#: needs of the cache to do so (``engine/cache.py:movers_lack`` says what a
#: model's cache lacks): a block mover knows one cache group, and nothing
#: can resume from a boundary whose recurrent state nobody kept. Rows are
#: (what a refusal calls it, its needs, is it on — None for an entry point,
#: which refuses when called). A later PR that teaches the movers a group
#: or a state takes that need out of the rows it taught; the next layer
#: kind that moves nothing adds a need, not a list.
_NEEDS_OF_THE_CACHE = (
    ("--kvbm-host-gb / KVBM tiers", (ONE_GROUP, NO_STATE),
     lambda a, mesh: a.kvbm_host_bytes > 0),
    ("preempt-to-swap (pass --no-preempt-swap: a preempted sequence is "
     "then recomputed)", (ONE_GROUP, NO_STATE),
     lambda a, mesh: a.preempt_swap),
    ("int8 KV pages", (ONE_GROUP, NO_STATE),
     lambda a, mesh: a.kv_cache_dtype == "int8"),
    ("a device mesh or pipeline stages", (ONE_GROUP, NO_STATE),
     lambda a, mesh: mesh is not None),
    ("multi-step decode", (NO_STATE,),
     lambda a, mesh: a.multi_step_decode > 1),
    ("speculative decoding", (NO_STATE,),
     lambda a, mesh: a.speculative_tokens > 0),
) + tuple((name, (NO_STATE,), None) for name in (
    "embed", "prefill_extract", "prefill_extract_stream", "alloc_inject",
    "generate_prefilled", "generate_injected", "restore_probe",
    "export_blocks"))


class _SwapEntry:
    """One swapped-out sequence's host-side KV bundle + budget reservation.

    Lifecycle: created (gather dispatched, budget reserved) → ready (host
    copy landed) → freed (swap-in consumed it, or teardown). ``dropped``
    marks a teardown that raced the in-flight copy — the copy task frees
    the reservation when it completes."""

    __slots__ = ("n", "nbytes", "k", "v", "ready", "failed", "freed",
                 "dropped")

    def __init__(self, n: int, nbytes: int):
        self.n = n              # device blocks captured
        self.nbytes = nbytes    # reserved against the SwapStore budget
        self.k = None           # host bundle [L, n, bs, KV, hd] or packed
        self.v = None
        self.ready = False
        self.failed = False
        self.freed = False
        self.dropped = False


def _layers_by_kind(cfg: ModelConfig) -> dict:
    """Layer counts for the ``engine built:`` line: by attention kind
    (full / window), state layers where the model has them, and dense /
    experts."""
    kinds = [cfg.layer_kind(i) for i in range(cfg.num_layers)]
    attn = [k for k in kinds if k.mixer == "attention"]
    n_moe = cfg.num_layers - cfg.num_dense_prefix_layers if cfg.is_moe else 0
    out = {"full": sum(not k.window for k in attn),
           "window": sum(bool(k.window) for k in attn),
           "dense": cfg.num_layers - n_moe, "experts": n_moe}
    if len(attn) < len(kinds):  # one state mixer a model
        out[cfg.state_spec.mixer] = len(kinds) - len(attn)
    return out


def _has_penalties(s) -> bool:
    """True when the seq requests any sampling penalty (OpenAI presence/
    frequency over generated text, nvext/HF repetition over prompt+generated
    — ref: lib/llm/src/protocols/common.rs sampling options). Penalties need
    the per-step token history, so these rows are excluded from the fused
    burst and speculative paths."""
    so = s.req.sampling_options
    return bool(so.presence_penalty or so.frequency_penalty
                or (so.repetition_penalty not in (None, 1.0)))


def _guided_fsm(s):
    """The seq's device-compiled FSM cursor (structured/runtime.FsmCursor),
    or None for unconstrained rows AND host-oracle fallbacks. Device rows
    mask + advance inside the sampling dispatch, so they ride every fast
    path (ragged, pipelined, fused burst, spec verify)."""
    gs = s.guided_state
    return gs if gs is not None and getattr(gs, "device", False) else None


def _guided_host_only(s) -> bool:
    """True when the seq's constraint runs on the HOST oracle (table over
    budget, min_tokens EOS gating, multi-host, or --no-structured-device):
    it needs host-visible logits and a Python FSM advance per token, so it
    is excluded from the pipelined/burst/spec paths — the pre-structured
    behavior, now the exception instead of the rule."""
    gs = s.guided_state
    return gs is not None and not getattr(gs, "device", False)


class AsyncJaxEngine:
    """Continuously-batched paged-KV inference engine on JAX.

    Args:
      cfg/args: model + engine config.
      params: model params pytree (None → random init, tests/benches).
      mesh: optional jax Mesh with ("dp","tp") axes for sharded serving.
      event_cb: fn(KvCacheEvent) — KV events toward the router.
      metrics_cb: fn(ForwardPassMetrics) — per-step load metrics.
    """

    def __init__(self, cfg: ModelConfig, args: EngineArgs, params=None,
                 mesh=None, event_cb: Optional[Callable] = None,
                 metrics_cb: Optional[Callable] = None,
                 guided_vocab: Optional[list] = None):
        import jax
        from dynamo_tpu.engine import model as M

        self.cfg, self.args, self.mesh = cfg, args, mesh
        self.event_cb = event_cb
        self.metrics_cb = metrics_cb
        self._event_id = itertools.count()

        #: mesh spans processes? then arrays must be created as GLOBAL
        #: arrays (device_put cannot reach another host's devices) and every
        #: rank replays the same step order (parallel/multihost.py)
        self._multihost = False
        if mesh is not None:
            from dynamo_tpu.parallel.multihost import is_multihost
            self._multihost = is_multihost(mesh)
        #: leader hook: called with (kind, host_arrays) right before each
        #: jitted dispatch so follower ranks stay in SPMD lockstep
        self.broadcast_cb: Optional[Callable] = None

        # the one refusal table, asked what the holder of the pages will
        # hold: the config says that before a weight or a page is allocated
        lacks = movers_lack(cfg)
        for need, who in lacks.items():
            unmet = [what for what, needs, on in _NEEDS_OF_THE_CACHE
                     if need in needs and on is not None and on(args, mesh)]
            if unmet:
                raise ValueError(f"{who} does not support: "
                                 + "; ".join(unmet))
        # ... and its entry points refuse when called (engine/main.py
        # refuses the disagg roles at start-up)
        for what, needs, on in _NEEDS_OF_THE_CACHE:
            if on is None and any(need in lacks for need in needs):
                setattr(self, what,
                        functools.partial(self._refuse_state, what))

        dev = jax.devices()[0]
        mem_before = dev.memory_stats()
        if params is None:
            # weightless serving: every leaf is generated on its own
            # devices in its final (quantized, sharded) form — see
            # init_params; nothing below has anything left to do
            params = M.init_params(
                cfg, jax.random.key(args.seed), mesh=mesh,
                quantization=args.quantization)
        else:
            params = self._place_params(params)
        self.params = params

        self._pp = mesh.shape.get("pp", 1) if mesh is not None else 1
        if self._pp > 1:
            from dynamo_tpu.parallel.pipeline import pp_compatible
            reason = pp_compatible(cfg, self._pp)
            if reason is not None:
                # a pp fleet silently serving un-pipelined would run at a
                # fraction of its planned capacity — fail loudly
                raise ValueError(f"pp_size={self._pp}: {reason}")

        self._kv_quant = args.kv_cache_dtype == "int8"
        # capability gaps fail loudly at construction: a fleet silently
        # running a degraded configuration would serve at a fraction of its
        # planned capacity with nothing but a log line to show for it
        if self._pp > 1:
            if self._kv_quant:
                raise ValueError(
                    "kv_cache_dtype='int8' is not supported under pipeline "
                    "parallelism (pp_size=%d); use the model dtype or pp=1"
                    % self._pp)
            if args.multi_step_decode > 1:
                raise ValueError(
                    "multi_step_decode=%d is not supported under pipeline "
                    "parallelism (pp_size=%d); set multi_step_decode=1"
                    % (args.multi_step_decode, self._pp))
            if args.speculative_tokens > 0:
                raise ValueError(
                    "speculative_tokens=%d is not supported under pipeline "
                    "parallelism (pp_size=%d); set speculative_tokens=0"
                    % (args.speculative_tokens, self._pp))
        groups = cfg.kv_cache_spec
        spec = cfg.state_spec
        self._row_cols = 3  # columns of a step's per-row operand
        if spec is not None:
            if args.enable_prefix_caching:
                logger.warning(
                    "prefix reuse switched off: a prefix hit would need the "
                    "recurrent state AT the hit boundary, which nobody kept "
                    "(state snapshots are not implemented)")
                self.args = args = args.replace(enable_prefix_caching=False)
            self._row_cols = 4  # + the row's state slot
        #: recurrent state (a model with Mamba-2 or short-convolution
        #: layers): one slot a running sequence, allocated BEFORE the pool
        #: is sized; None for every other model, and nothing below then
        #: looks at state
        state = allocate_state(cfg, args.max_num_seqs)
        nb = args.num_blocks or hbm_sized_num_blocks(
            cfg, args.block_size, args.kv_cache_memory_fraction, args.tp_size,
            kv_cache_dtype="int8" if self._kv_quant else None,
            **({"min_tokens": args.max_model_len} if spec else {}))
        self.num_blocks = nb
        #: the KV pages and the state slots: the one holder of both
        self.kv = KvPages(
            cfg, *allocate_device_cache(
                cfg, nb, args.block_size, mesh,
                dtype="int8" if self._kv_quant else None),
            args.block_size, nb, state)

        #: silent-fallback visibility (docs/performance.md "Quantized
        #: serving"): static reason the ragged step degrades to the XLA
        #: attention path (None = Pallas ragged kernel on the path, or
        #: never requested). A degraded launch is a silent TTFT/HBM
        #: regression — log it ONCE here, count every degraded step into
        #: dynamo_ragged_fallback_total{reason}, and tag the flight record.
        self.ragged_fallback_reason = M.ragged_fallback_reason(
            cfg, mesh, args.use_pallas_attention, self._kv_quant,
            nb * args.block_size, args.block_size)
        self.ragged_fallback_total: dict = {}
        #: rows dispatched with q_len above the kernel's small query tile
        #: (prompt chunks, long verify rows): how often its wide tile
        #: engages — dynamo_ragged_wide_tile_rows_total, and per step in
        #: the flight record
        self.wide_tile_rows_total = 0
        #: the chunked Mamba-2 scan (ops/mamba2.py): (block, chunk row)
        #: pairs its kernel walked, and the blocks x RAGGED_MAX_CHUNKS a
        #: walk of every pair would take, summed over the Mamba-2 layers of
        #: every chunk-holding step — counted on the host from the plan;
        #: dynamo_ssd_block_rows_total{kind}, and per step in the flight
        #: record
        self.ssd_block_rows_total = {"walked": 0, "max": 0}
        if self.ragged_fallback_reason is not None:
            logger.warning(
                "ragged Pallas kernel unavailable (reason=%s): steps take "
                "the XLA attention path — counted in "
                "dynamo_ragged_fallback_total", self.ragged_fallback_reason)
        # every step kind (mixed, decode-only, multi-step, verify) reaches
        # attention through the same gate in model.forward, so one line
        # says which path all of them take, and why
        if cfg.is_mla:
            attention = "xla: MLA latent walk"
        elif not args.use_pallas_attention:
            attention = "xla: use_pallas_attention not set"
        elif self.ragged_fallback_reason is not None:
            attention = f"xla: fallback {self.ragged_fallback_reason}"
        else:
            from dynamo_tpu.ops.paged_attention import kernel_interpret_mode
            if dev.platform == "tpu" and kernel_interpret_mode():
                raise RuntimeError(
                    "use_pallas_attention on a TPU host, but the default "
                    f"backend is {jax.default_backend()!r}: the kernel "
                    "would run interpreted, not on the chip")
            attention = ("pallas: ragged kernel (interpreted)"
                         if kernel_interpret_mode()
                         else "pallas: ragged kernel (Mosaic)")
        #: held-experts layer counters (model.moe_stats_width), read with
        #: the step's other outputs: dynamo_moe_assignments_total{to},
        #: dynamo_moe_expert_tokens_total{expert}, dynamo_moe_row_tiles_total,
        #: dynamo_moe_combine_rows_total{rows}
        self._moe_held = cfg.is_moe and cfg.experts_held is not None
        if (cfg.is_moe and not self._moe_held and mesh is None
                and cfg.num_experts > 8):
            logger.warning(
                "%d experts and experts_held is None: on one chip every "
                "token runs through EVERY expert (the one-hot layer, %.0f x "
                "the work of its %d chosen); set experts_held=(0, %d) to "
                "take the dropless held-experts layer", cfg.num_experts,
                cfg.num_experts / cfg.num_experts_per_tok,
                cfg.num_experts_per_tok, cfg.num_experts)
        self.moe_assignments_total = (
            {"all": 0, "held": 0} if self._moe_held else {})
        self.moe_expert_tokens_total = np.zeros(
            (cfg.num_experts_held if self._moe_held else 0,), np.int64)
        self.moe_row_tiles_total = 0
        #: buffer rows the layer's read-back fetched, beside the rows a
        #: read-back of every pair of every padded token would fetch
        self.moe_combine_rows_total = (
            {"read": 0, "worst_case": 0} if self._moe_held else {})
        self._moe_head = M.MOE_STATS_HEAD  # the experts' counts follow
        self._moe_pending: collections.deque = collections.deque()
        #: (pairs, experts touched, row tiles, rows read back, their worst
        #: case) a cache group: the next record's
        self._moe_step = np.zeros((len(groups), 5), np.int64)
        mem_after = dev.memory_stats()
        state = (self.params, self.kv.k, self.kv.v, self.kv.state)
        #: what was built, as one line an operator (or chip_smoke.py) reads
        self.build_facts = {
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": jax.device_count()},
            "weights_bytes": tree_nbytes(self.params),
            "kv_blocks": nb,
            "kv_bytes": self.kv.nbytes,
            "attention": attention,
            "layers": _layers_by_kind(cfg),
            "experts_held": list(cfg.experts_held or ()) or None,
            "experts_routed": cfg.num_experts,
            "experts_per_tok": cfg.num_experts_per_tok,
            "expert_ffn": cfg.moe_ffn_size if cfg.is_moe else None,
            "hidden_size": cfg.hidden_size,
            "kv_lane_pad_share": cfg.kv_lane_pad_share,
            "cache_groups": [
                {"layers": len(g.layers), "kv_heads": g.kv_heads,
                 "k_dim": g.k_dim, "v_dim": g.v_dim, "window": g.window,
                 "page_bytes": args.block_size * slot_bytes(
                     cfg, g, args.tp_size,
                     "int8" if self._kv_quant else None)}
                for g in groups],
            **({"state_slots": args.max_num_seqs,
                "state_bytes": self.kv.state_nbytes,
                "state_layers": len(spec.layers),
                "state_mixer": spec.mixer} if spec else {}),
            **({"mamba": {"heads": cfg.mamba_n_heads,
                          "d_head": cfg.mamba_d_head,
                          "d_state": cfg.mamba_d_state}}
               if spec and spec.mixer == "mamba2" else {}),
            "bytes_in_use_before": mem_before and mem_before["bytes_in_use"],
            "bytes_in_use_after": mem_after and mem_after["bytes_in_use"],
            "bytes_limit": mem_after and mem_after["bytes_limit"],
            # a leaf on fewer devices than the mesh has is a leaf that was
            # built, or left, on the first chip
            "min_devices_per_leaf": min(
                len(x.sharding.device_set) for x in jax.tree.leaves(state)),
            "bytes_in_use_per_device": [
                (d.memory_stats() or {}).get("bytes_in_use")
                for d in jax.local_devices()],
        }
        logger.info("engine built: %s", json.dumps(self.build_facts))

        #: per-tier residency ledger (observability/kvaudit.py): the
        #: worker-side ground truth the KV audit plane compares the
        #: router's radix view against — rolling xor/count digests folded
        #: inline at register/evict/tier-change, served via the
        #: ``kv_digest`` wire op (engine/main.py)
        from dynamo_tpu.observability.kvaudit import WorkerKvLedger
        self.kv_ledger = WorkerKvLedger()
        self.kvbm = None
        if args.kvbm_host_bytes > 0 and args.enable_prefix_caching:
            from dynamo_tpu.kvbm import KvbmManager
            self.kvbm = KvbmManager(args.kvbm_host_bytes,
                                    disk_dir=args.kvbm_disk_dir,
                                    disk_bytes=args.kvbm_disk_bytes,
                                    # router-facing removed events fire
                                    # only when the LAST tier copy dies
                                    # (KvbmWorkerService chains onto this)
                                    on_change=self._on_kvbm_change,
                                    ledger=self.kv_ledger)
        #: set by engine/main.py when a distributed KVBM fleet is configured
        #: (RemoteKvbm — leader lookup + peer fetch)
        self.kvbm_remote = None
        self._offload_tasks: set = set()
        #: G4 prefix flow-up (docs/performance.md): prefix-cache hit
        #: counts per sequence hash; a block crossing the threshold is
        #: pushed to the fleet-global object store so cold workers can
        #: warm from it. 0 disables the flow-up (G4 then fills only via
        #: the eviction cascade, as before).
        import os as _os

        raw_hits = _os.environ.get("DYN_G4_PUBLISH_HITS")
        if raw_hits in (None, ""):
            self._g4_publish_hits = 2
        elif raw_hits in ("0", "off", "false"):
            self._g4_publish_hits = 0
        else:
            try:
                self._g4_publish_hits = int(raw_hits)
            except ValueError:
                # same startup-clarity contract as the DYN_ONBOARD_* /
                # DYN_RESTORE_* knobs (transfer._env_caster)
                raise ValueError(
                    f"bad DYN_G4_PUBLISH_HITS={raw_hits!r}") from None
        self._prefix_hits: dict = {}
        self._g4_publishing: set = set()

        self.pool = BlockPool(nb, args.enable_prefix_caching,
                              on_removed=self._on_removed,
                              ledger=self.kv_ledger)
        #: preempt-to-swap: host staging for preempted sequences' KV
        #: (scheduler-driven swap-out/swap-in replacing recompute). Budget
        #: shares the G2 tier's allowance when one is configured. Disabled
        #: under multi-host step replication: the gather/scatter dispatches
        #: are leader-local and would desync the follower replay.
        self._swap: Optional[SwapStore] = None
        if args.preempt_swap and not self._multihost:
            budget = args.swap_host_bytes
            shared = room = None
            if budget is None:
                if self.kvbm is not None:
                    budget = args.kvbm_host_bytes
                    shared = lambda: self.kvbm.host.used  # noqa: E731
                    # a full G2 LRU yields DRAM to swap reservations —
                    # without this, steady-state offload traffic would
                    # permanently starve swap of the shared allowance
                    room = self.kvbm.make_host_room
                else:
                    budget = DEFAULT_SWAP_HOST_BYTES
            self._swap = SwapStore(budget, external_used=shared,
                                   make_room=room)
            if shared is not None:
                # both directions of the shared allowance: G2 puts evict
                # down to (budget − swap reservations), so combined host
                # residency stays inside the ONE configured budget
                self.kvbm.host.external_used = lambda: self._swap.used
        self.swap_out_blocks = 0
        self.swap_in_blocks = 0
        #: ragged step (docs/performance.md): mixed prefill+decode in ONE
        #: packed launch, the ONLY step path — compiled signatures collapse
        #: to the token buckets, the scheduler plans a token budget per
        #: step, and padded dispatch between buckets is gone. Every mode
        #: (spec verify, MLA/TPLA, pp, multi-host, multi-step) rides the
        #: same packed layout.
        self.scheduler = Scheduler(
            args, self.pool,
            # a state model publishes no KV events: its blocks hold one
            # layer kind's part of a prefix, which nobody can resume from
            on_stored=self._on_stored if spec is None else None,
            state_slots=args.max_num_seqs if spec else 0,
            onboard_cb=self._onboard if self.kvbm is not None else None,
            swapper=self if self._swap is not None else None,
            hot_cb=self._note_hot_prefix if self.kvbm is not None else None)
        self.pp_fn = None
        self.ragged_fn = None
        self.ragged_dec_fn = None
        self._ragged_mm_fn = None  # compiled lazily on first mm request
        self.multi_fn = None
        self.verify_fn = None
        self.draft_fn = None
        if self._pp > 1:
            from dynamo_tpu.parallel.pipeline import make_pp_step_fn
            # pp takes packed ragged microbatches: each microbatch is one
            # ragged bin with the same (T, R, C, W) shape, so the compiled
            # signature is (T, M) — no bucketed lattice per stage
            self.pp_fn = make_pp_step_fn(
                cfg, args.block_size, mesh,
                replicate_logits=self._multihost)
        else:
            self.ragged_fn = M.make_ragged_step_fn(
                cfg, args.block_size, mesh,
                use_pallas=args.use_pallas_attention,
                replicate_logits=self._multihost,
                kv_quant=self._kv_quant)
            # decode-only variant (no chunk grid): what decode-only plans
            # and the pipelined decode loop dispatch
            self.ragged_dec_fn = M.make_ragged_step_fn(
                cfg, args.block_size, mesh,
                use_pallas=args.use_pallas_attention,
                replicate_logits=self._multihost,
                kv_quant=self._kv_quant, chunks=False)
            if self.kv.state is not None:
                self.ragged_fn = self._keep_state(self.ragged_fn)
                self.ragged_dec_fn = self._keep_state(self.ragged_dec_fn)
            if self._moe_held:
                self.ragged_fn = self._keep_moe_stats(self.ragged_fn)
                self.ragged_dec_fn = self._keep_moe_stats(self.ragged_dec_fn)
            if args.multi_step_decode > 1:
                self.multi_fn = M.make_multi_decode_fn(
                    cfg, args.block_size, args.multi_step_decode, mesh,
                    use_pallas=args.use_pallas_attention,
                    replicate_outputs=self._multihost,
                    kv_quant=self._kv_quant)
            if args.speculative_tokens > 0:
                # verify is a ragged row with q_len = draft+1
                self.verify_fn = M.make_ragged_verify_fn(
                    cfg, args.block_size, mesh,
                    replicate_outputs=self._multihost,
                    kv_quant=self._kv_quant)
                if args.speculative_method == "draft_layers":
                    self.draft_fn = M.make_draft_fn(
                        cfg, args.block_size, args.speculative_draft_layers,
                        args.speculative_tokens, mesh,
                        use_pallas=args.use_pallas_attention,
                        replicate_outputs=self._multihost,
                        kv_quant=self._kv_quant)
        self.spec_stats = SpecDecodeStats()
        #: speculative-decode auto-disable governor: rolling emitted-tokens
        #: window; when the measured gain stays < 1 the engine falls back to
        #: plain decode and re-probes after spec_reprobe_steps
        self._spec_window: "collections.deque" = collections.deque(
            maxlen=max(1, args.spec_gain_window))
        self._spec_resume_step = 0
        self.spec_disabled_total = 0
        self.spec_measured_gain: Optional[float] = None
        #: measured dispatch walls (EWMA, ms): one spec round (draft +
        #: verify + host round trip) vs one plain decode step — the
        #: governor's ragged cost re-baseline (_spec_dispatch_cost)
        self._spec_round_ms: Optional[float] = None
        self._decode_step_ms: Optional[float] = None
        from dynamo_tpu.engine import sampling as S
        self._sampling = S

        #: id → token text, for guided decoding's token-level DFA walks
        #: (engine/main.py decodes it from the served tokenizer); None =
        #: guided requests are refused
        self.guided_vocab = guided_vocab
        #: structured decoding (docs/structured.md): the device FSM arena
        #: constraints compile into. None = every constraint runs on the
        #: host oracle (no vocab, --no-structured-device, DYN_STRUCTURED=0,
        #: multi-host step replication — the arena uploads are leader-local
        #: and would desync follower replay, or a byte budget too small for
        #: this vocab width).
        self.structured = None
        if (args.structured_device and guided_vocab is not None
                and not self._multihost):
            from dynamo_tpu.structured import (
                StructuredRuntime, arena_states, env_enabled,
                table_budget_bytes,
            )
            if env_enabled():
                cap = arena_states(cfg.vocab_size,
                                   table_budget_bytes(args.structured_table_mb))
                if cap:
                    self.structured = StructuredRuntime(cfg.vocab_size, cap)
                else:
                    logger.info(
                        "structured device tables disabled: budget buys "
                        "too few states at vocab %d (DYN_STRUCTURED_TABLE_MB)",
                        cfg.vocab_size)
        #: lazily-compiled structured variants of the fused paths (first
        #: constrained request on each path pays one trace)
        self._multi_fsm_fn = None
        self._verify_masked_fn = None
        self._seq_counter = itertools.count()
        self._wake = asyncio.Event()
        # memory-starved plan(): park on _wake instead of hot-polling; a
        # BlockPool release (seq finish, offload unpin, abort) is the event
        # that can make the next plan() non-empty
        self.pool.on_freed = self._wake.set
        self._task: Optional[asyncio.Task] = None
        self._loop_ref = None  # captured by _ensure_loop (thread bridges)
        self._closed = False
        self.steps = 0
        #: decode steps executed by the depth-2 pipelined loop (telemetry:
        #: nonzero means the e2e path is actually overlapping copy/commit
        #: with device compute)
        self.pipelined_steps = 0
        #: jitted full-model forward passes (each reads every weight once
        #: from HBM) — the denominator for roofline/MFU accounting
        self.param_reads = 0
        #: padded-dispatch waste: tokens (and decode batch rows) dispatched
        #: beyond the plan's REAL work because static shapes bucket up —
        #: the cost the ragged step eliminates. Exported as
        #: dynamo_step_padded_tokens_total (engine/main.py); per-step
        #: values ride the step trace.
        self.padded_tokens_total = 0
        #: distinct jitted step signatures dispatched so far (kind + static
        #: shape tuple) — len() is dynamo_step_compiled_signatures, the
        #: bucket-lattice-vs-ragged contrast on /metrics
        self.compiled_signatures: set = set()
        #: AOT warmup bookkeeping: ``warmup_skipped`` marks a worker whose
        #: requested warmup could not run (multi-host step replication) —
        #: surfaced via WorkerStats.warmed_up so the autoscale readiness
        #: gate does not count a cold worker as warm (docs/autoscaling.md)
        self.warmup_requested = args.warmup_buckets
        self.warmup_skipped = False
        #: step flight recorder (observability/flight.py): one structured,
        #: anomaly-tagged record per executed step — the fleet-queryable
        #: "why was this step slow" layer; step_trace_summary() reads it
        from dynamo_tpu.observability.flight import (
            FlightRecorder, register_recorder,
        )
        self.flight = FlightRecorder(service="engine")
        self._flight_name = register_recorder("engine", self.flight)
        #: the engine loop's phase clock (observability/flight.py
        #: PhaseClock), made when the loop starts; None with DYN_FLIGHT=0
        #: and DYN_JAX_PROFILER unset, and a mark is then one test
        self._clock = None
        #: anomaly-triggered bounded jax.profiler capture (None unless
        #: DYN_PROFILE_ON_ANOMALY names a directory): a slow-step /
        #: compile-steady flight tag arms one device-trace capture whose
        #: artifact path lands on the triggering StepRecord
        #: (observability/profiler.py AnomalyProfiler)
        from dynamo_tpu.observability.profiler import AnomalyProfiler
        self.anomaly_profiler = AnomalyProfiler.from_env()
        #: last-seen cumulative totals, differenced into per-step flight
        #: record deltas (preemptions, swap block movement)
        self._flight_last: dict = {}
        #: post-warmup jit traces observed at the serving dispatch sites:
        #: kind → count / total seconds (→ dynamo_compile_total{kind} /
        #: dynamo_compile_seconds_total{kind} in engine/main.py; the
        #: unlabeled dynamo_compile_seconds histogram rides the tracer's
        #: SLO registry). A compile after FLIGHT steady_after steps logs a
        #: WARNING with the offending signature — a mid-traffic compile
        #: used to be silent except as a latency cliff.
        self.compile_events: dict[str, int] = {}
        self.compile_seconds: dict[str, float] = {}
        self._last_compile: Optional[tuple] = None  # (kind, sig, seconds)
        #: tier snapshot throttle for the flight record hot path (the
        #: pipelined decode loop records per step): occupancy moves at
        #: block-allocation cadence, so a 50 ms-old snapshot is current
        self._flight_tiers: dict = {}
        self._flight_tiers_t = 0.0
        self._last_empty_rec = 0.0  # empty-bubble record rate limit
        #: multi-process DP fleet rank (None = single-rank); reported in
        #: worker stats (ref: kv_router/protocols.rs:57 data_parallel_rank)
        self.dp_rank: Optional[int] = None
        #: direct device-to-device KV transfer for disagg (NIXL analog);
        #: None = host-staged bundles only
        self.direct_transfer = None
        if args.kv_transfer_direct:
            from dynamo_tpu.disagg.transfer import DirectTransferManager
            self.direct_transfer = DirectTransferManager()
        #: chaos ``worker.kill`` (runtime/chaos.py): True once this engine
        #: hard-died mid-step. The loop stops WITHOUT failing in-flight
        #: sinks (a SIGKILLed process completes nothing) — consumers hang
        #: until lease expiry breaks their streams, which is exactly the
        #: path stateful migration must survive (docs/robustness.md).
        self.killed = False
        #: fired (sync, best-effort) when worker.kill trips: mains use it
        #: to os._exit(137); in-process fleets to ServeHandle.kill() and
        #: to stop the worker's lease keepalive
        self.on_kill: list = []

    # The harness's, and nobody else's: chipbench/check_reference*.py assign
    # None to these three names to free the chip for the float32 reference.
    # They go when the next ``benchmark`` issue changes those lines
    # (ROADMAP D5a); everything else reads ``self.kv``.
    k_cache = property(lambda self: self.kv.k,
                       lambda self, x: setattr(self.kv, "k", x))
    v_cache = property(lambda self: self.kv.v,
                       lambda self, x: setattr(self.kv, "v", x))
    state = property(lambda self: self.kv.state,
                     lambda self, x: setattr(self.kv, "state", x))

    def direct_capability(self) -> Optional[str]:
        """Annotation a decode worker sends so prefill can offer direct
        device-to-device KV pulls (disagg/transfer.py)."""
        if self.direct_transfer is None:
            return None
        return self.direct_transfer.capability()

    # ------------------------------------------------------------------ api

    async def _new_seq(self, req: PreprocessedRequest, ctx, sink,
                       **kw) -> SeqState:
        """Build a SeqState — the ONE place request-scoped engine state
        (like the guided-decoding cursor) attaches, so every entry path
        (generate, disagg prefill_extract, generate_prefilled/injected)
        honors it."""
        if req.mm_embeds and self._pp > 1:
            # admission-time refusal: raising mid-step (inside _run_ragged)
            # would fail every in-flight sequence in the batch, not just
            # this request
            raise ValueError("multimodal requests are not supported under "
                             "pipeline parallelism yet")
        seq = SeqState(request_id=f"seq-{next(self._seq_counter)}",
                       req=req, ctx=ctx or _NullCtx(), sink=sink, **kw)
        if req.sampling_options.guided:
            from dynamo_tpu.structured import build_guided_state
            if self.guided_vocab is None:
                raise ValueError(
                    "guided decoding requested but this worker has no "
                    "tokenizer vocabulary (engine started without "
                    "guided_vocab)")
            # off the event loop: a cold constraint compiles the char NFA,
            # walks the vocab per visited DFA state, AND packs the device
            # tables; everything is cached so session turn 2+ is a dict hit.
            # min_tokens rows stay on the host oracle — its EOS suppression
            # depends on per-step generated counts the static tables can't
            # express (docs/structured.md fallback rules).
            seq.guided_state = await asyncio.to_thread(
                build_guided_state, req.sampling_options.guided,
                self.guided_vocab, req.eos_token_ids or [],
                self.structured,
                not (req.stop_conditions.min_tokens or 0) > 0)
        return seq

    async def generate(self, req: PreprocessedRequest, ctx=None
                       ) -> AsyncIterator[LLMEngineOutput]:
        """EngineFn-compatible async stream of per-token outputs."""
        from dynamo_tpu.observability import get_tracer

        from dynamo_tpu.observability.flight import flight_instance

        self._ensure_loop()
        sink: asyncio.Queue = asyncio.Queue()
        seq = await self._new_seq(req, ctx, sink)
        self.scheduler.add(seq)
        self._wake.set()
        # phase timing: queue+prefill until the first token (engine-side
        # TTFT), then the decode loop until finish — recorded as spans on
        # the request's trace (no-op for trace-less contexts). The spans
        # carry this worker's flight identity + the step-seq interval so
        # the attribution join (observability/attribution.py) can select
        # exactly the StepRecords that overlapped this request's life.
        tracer = get_tracer()
        t0 = time.time()
        seq0 = self.flight.seq_now
        seq_first = None
        t_first = None
        n_tokens = 0
        try:
            while True:
                out: Optional[LLMEngineOutput] = await sink.get()
                if out is None:
                    return
                if isinstance(out, Exception):
                    raise out  # chaos/step failure: surfaces as StreamError
                if t_first is None and out.token_ids:
                    t_first = time.time()
                    seq_first = self.flight.seq_now
                    tracer.record("engine.ttft", ctx, start=t0, end=t_first,
                                  service="engine",
                                  prompt_tokens=len(req.token_ids),
                                  flight_instance=flight_instance(),
                                  flight_name=self._flight_name,
                                  seq0=seq0, seq1=seq_first)
                    # first-frame flight identity: Migration reads it so a
                    # later re-send's restore hint can name THIS worker as
                    # the predecessor leg (prev_worker/prev_seq)
                    out.flight = {"worker": flight_instance(),
                                  "recorder": self._flight_name,
                                  "seq": seq_first}
                n_tokens += len(out.token_ids)
                yield out
                if out.finish_reason is not None:
                    return
        finally:
            if t_first is not None:
                tracer.record("engine.decode", ctx, start=t_first,
                              end=time.time(), service="engine",
                              tokens=n_tokens,
                              flight_instance=flight_instance(),
                              flight_name=self._flight_name,
                              seq0=seq_first, seq1=self.flight.seq_now)

    # ---------------------------------------------------------- embeddings

    async def embed(self, token_id_lists: list[list[int]]) -> list[list[float]]:
        """Mean-pooled L2-normalized embeddings for a batch of token lists
        (ref surface: /v1/embeddings, openai.rs:714). Runs the SERVING
        forward over a scratch paged cache, so every family the engine
        generates with (MLA, gpt-oss, MoE, …) embeds too. Shapes bucket to
        powers of two so steady traffic reuses a handful of programs."""
        if not token_id_lists:
            return []
        # bound inputs by the serving context the same way generate does
        # (an unbounded S — or an unbounded batch of near-limit inputs —
        # would OOM the worker)
        limit = self.args.max_model_len
        too_long = max(len(t) for t in token_id_lists)
        if too_long > limit:
            raise ValueError(
                f"embedding input of {too_long} tokens exceeds "
                f"max_model_len {limit}")
        total = len(token_id_lists) * too_long  # padded batch footprint
        budget = max(4096, 8 * limit)
        if total > budget:
            raise ValueError(
                f"embedding batch of {len(token_id_lists)}×{too_long} tokens "
                f"exceeds the per-request budget {budget}; split the batch")
        bs = self.args.block_size
        B = 1 << (len(token_id_lists) - 1).bit_length()
        S = max(bs, 1 << (too_long - 1).bit_length())
        if self._multihost:
            # the batch axis shards over "dp" under a global mesh; a bucket
            # narrower than the dp extent cannot be laid out
            B = max(B, self.mesh.shape.get("dp", 1))
        tokens = np.zeros((B, S), np.int32)
        lengths = np.zeros((B,), np.int32)
        for i, ids in enumerate(token_id_lists):
            tokens[i, :len(ids)] = ids
            lengths[i] = len(ids)

        if self._multihost:
            # broadcast + dispatch ON the event-loop thread: follower replay
            # order must match the leader's device dispatch order, and every
            # other step kind dispatches from this thread (a to_thread embed
            # could interleave differently on leader vs followers and wedge
            # the fleet in mismatched collectives)
            self._broadcast("embed", tokens=tokens, lengths=lengths)
            out = self._embed_forward(tokens, lengths)
            host = await asyncio.to_thread(np.asarray, out)
        else:
            def run():  # compile/dispatch + host copy off the event loop
                return np.asarray(self._embed_forward(tokens, lengths))

            host = await asyncio.to_thread(run)
        return [host[i].tolist() for i in range(len(token_id_lists))]

    def _embed_forward(self, tokens: np.ndarray, lengths: np.ndarray):
        """Setup (jitted fn + scratch caches) and dispatch of one embed
        forward — shared verbatim by the leader path and the follower's
        step replay so both ranks compile the identical program."""
        from dynamo_tpu.engine import model as M
        from dynamo_tpu.engine.cache import allocate_device_cache

        if getattr(self, "_embed_fn", None) is None:
            # one jitted callable (jax.jit re-specializes per (B,S) bucket)
            # + per-bucket scratch caches, reused across calls
            self._embed_fn = M.make_embed_fn(
                self.cfg, self.args.block_size, self.mesh,
                use_pallas=self.args.use_pallas_attention,
                replicate_outputs=self._multihost)
            self._embed_caches: dict = {}
        bs = self.args.block_size
        B, S = tokens.shape
        caches = self._embed_caches.get((B, S))
        if caches is None:
            # keep ONE scratch cache: mixed-shape embed traffic must not
            # accumulate per-bucket HBM the serving pool never budgeted
            # for (re-allocating on a shape change beats an OOM)
            self._embed_caches.clear()
            caches = allocate_device_cache(
                self.cfg, B * (S // bs) + 1, bs, self.mesh)
            self._embed_caches[(B, S)] = caches
        return self._embed_fn(self.params, self._put_batch("tokens", tokens),
                              self._put_batch("lengths", lengths), *caches)

    async def embed_handler(self, request: dict, ctx=None):
        """Endpoint handler: {"token_ids": [[...]]} → one embeddings frame."""
        try:
            vecs = await self.embed(request.get("token_ids") or [])
        except ValueError as e:  # input too long: client error, not a crash
            yield {"error": str(e)}
            return
        yield {"embeddings": vecs}

    # ------------------------------------------------------- disagg support

    async def prefill_extract(self, req: PreprocessedRequest, ctx=None):
        """Run prefill only and hand back (first token, logprob, KvBundle).

        The disagg prefill-worker path (ref: vllm/handlers.py:211-245 —
        max_tokens=1 generation returning kv_transfer_params); here the
        "transfer params" ARE the gathered pages.
        """
        import dataclasses

        from dynamo_tpu.disagg.protocols import KvBundle, PrefillResponse

        self._ensure_loop()
        t0 = time.time()
        sc = dataclasses.replace(req.stop_conditions, max_tokens=1,
                                 min_tokens=1, ignore_eos=True)
        preq = dataclasses.replace(req, stop_conditions=sc)
        sink: asyncio.Queue = asyncio.Queue()
        seq = await self._new_seq(preq, ctx, sink, hold_blocks=True)
        self.scheduler.add(seq)
        self._wake.set()
        token, logp = None, None
        try:
            while True:
                out = await sink.get()
                if out is None or isinstance(out, Exception):
                    break  # step failure: graceful token_id=-1 fallback below
                if out.token_ids:
                    token, logp = out.token_ids[0], (out.log_probs or [None])[0]
                if out.finish_reason is not None:
                    break
            if token is None:
                return PrefillResponse(token_id=-1, logprob=None, bundle=None)
            bs = self.args.block_size
            n = (seq.prompt_len + bs - 1) // bs
            # gather pads the id list to a power of two (compile-cache
            # friendliness); to_host slices back to the real block count
            k, v = self.kv.to_host(*self.kv.gather(seq.block_table[:n]), n)
            bundle = KvBundle(k=k, v=v, num_tokens=seq.prompt_len,
                              block_size=bs)
            return PrefillResponse(token_id=token, logprob=logp, bundle=bundle)
        finally:
            # covers cancellation at any point: pending/running seqs are
            # reaped with their blocks; finished ones release the held blocks
            self.scheduler.abort(seq)
            self._wake.set()
            from dynamo_tpu.observability import get_tracer

            get_tracer().record("prefill.extract", ctx, start=t0,
                                end=time.time(), service="engine",
                                prompt_tokens=len(req.token_ids),
                                streamed=False)

    async def prefill_extract_stream(self, req: PreprocessedRequest, ctx=None):
        """Pipelined prefill: yields KvChunkFrame wires for blocks whose KV is
        final WHILE later chunks are still computing, then the final
        PrefillResponse with the unshipped tail.

        The TPU analog of NIXL's compute-overlapped block transfer (ref:
        docs/architecture/disagg_serving.md:92-103): by the time the last
        chunk finishes, most pages are already on the decode worker.
        """
        import dataclasses

        from dynamo_tpu.disagg.protocols import (
            KvBundle, KvChunkFrame, KvLayerFrame, PrefillResponse,
        )
        from dynamo_tpu.disagg.transfer import KvDirectFrame

        self._ensure_loop()
        # direct device-to-device mode when the decode worker's capability
        # annotation says the pull can succeed (disagg/transfer.py); pages
        # then never touch the host on this side — only descriptors ship
        mode = (self.direct_transfer.choose_mode(req.annotations)
                if self.direct_transfer is not None else None)
        # layer-interleaved tail (docs/disagg.md): when negotiated, the
        # FINAL chunk's blocks are not shipped as one full-depth frame at
        # its commit — they ride the tail path below, split on the layer
        # axis so early layers' wire/scatter overlaps later layers' staging
        layer_groups = self._kv_layer_groups(req.annotations)
        bs = self.args.block_size
        sc = dataclasses.replace(req.stop_conditions, max_tokens=1,
                                 min_tokens=1, ignore_eos=True)
        preq = dataclasses.replace(req, stop_conditions=sc)
        sink: asyncio.Queue = asyncio.Queue()
        events: asyncio.Queue = asyncio.Queue()
        state = {"shipped": 0}  # full blocks whose gather is dispatched

        # The device gather MUST be dispatched inside the progress callback
        # (engine-loop context, right after the chunk commits): the block
        # table is valid at that instant, and the dispatched gather captures
        # the current immutable cache array — a later preemption only
        # releases host-side bookkeeping, the captured data stays correct.
        # Shipping is monotonic; a preemption recompute re-fires progress
        # with smaller ends, which are skipped (identical content anyway).
        def on_progress(end: int) -> None:
            if layer_groups is not None and end >= seq.prompt_len:
                return  # final commit: the whole last chunk is the tail
            full = end // bs
            if full <= state["shipped"]:
                return
            # backpressure: if the consumer (response plane) is behind, skip
            # this ship — unshipped blocks ride the next progress event or
            # the tail bundle, instead of piling duplicate KV copies in HBM
            if events.qsize() >= 4:
                return
            ids = seq.block_table[state["shipped"]:full]
            kb, vb = self.kv.gather(ids)
            events.put_nowait(("chunk", (state["shipped"], len(ids), kb, vb)))
            state["shipped"] = full

        seq = await self._new_seq(preq, ctx, sink, hold_blocks=True,
                            progress_cb=on_progress)

        async def drain_sink():
            while True:
                out = await sink.get()
                if isinstance(out, Exception):
                    out = None  # step failure: token_id=-1 fallback downstream
                events.put_nowait(("out", out))
                if out is None or out.finish_reason is not None:
                    return

        drainer = asyncio.get_running_loop().create_task(drain_sink())
        self.scheduler.add(seq)
        self._wake.set()
        t0 = time.time()
        token, logp = None, None

        try:
            done = False
            while not done:
                kind, val = await events.get()
                if kind == "chunk":
                    # FIFO ordering guarantees every chunk event lands before
                    # the finish output that follows it in the queue
                    start, n, kb, vb = val
                    if mode is not None:
                        # ship the pow2-padded gather output unchanged (the
                        # compile-cache contract of KvPages.gather); the
                        # true block count rides the descriptor
                        desc = self.direct_transfer.offer(
                            mode, [kb, vb],
                            {"num_tokens": (start + n) * bs, "n": n,
                             "block_size": bs, "start_block": start})
                        yield KvDirectFrame(desc).to_wire()
                        continue
                    k, v = await asyncio.to_thread(self.kv.to_host,
                                                   kb, vb, n)
                    b = KvBundle(k=k, v=v, num_tokens=(start + n) * bs,
                                 block_size=bs, start_block=start)
                    yield KvChunkFrame(bundle=b).to_wire()
                elif val is None:
                    done = True
                else:
                    if val.token_ids:
                        token = val.token_ids[0]
                        logp = (val.log_probs or [None])[0]
                    if val.finish_reason is not None:
                        done = True
            if token is None:
                yield PrefillResponse(token_id=-1, logprob=None,
                                      bundle=None).to_wire()
                return
            total = (seq.prompt_len + bs - 1) // bs
            shipped = state["shipped"]
            bundle = None
            if total > shipped:
                n = total - shipped
                groups = layer_groups
                if groups and mode is None:
                    # layer-interleaved tail (docs/disagg.md): ONE gather,
                    # then host-stage + ship a layer group at a time — the
                    # wire/scatter of group g overlaps the device→host copy
                    # of group g+1, instead of serializing the full-depth
                    # bundle after prefill completes
                    kb, vb = self.kv.gather(seq.block_table[shipped:total])
                    L = kb.shape[0]
                    for g0, g1 in groups:
                        k, v = await asyncio.to_thread(
                            self.kv.to_host, kb[g0:g1], vb[g0:g1], n)
                        yield KvLayerFrame(KvBundle(
                            k=k, v=v, num_tokens=seq.prompt_len,
                            block_size=bs, start_block=shipped,
                            start_layer=g0, total_layers=L)).to_wire()
                elif groups and mode is not None:
                    # direct path: one offer per layer group — the decode
                    # side's pulls + layer scatters interleave the same way
                    kb, vb = self.kv.gather(seq.block_table[shipped:total])
                    L = kb.shape[0]
                    for g0, g1 in groups:
                        desc = self.direct_transfer.offer(
                            mode, [kb[g0:g1], vb[g0:g1]],
                            {"num_tokens": seq.prompt_len, "n": n,
                             "block_size": bs, "start_block": shipped,
                             "start_layer": g0, "total_layers": L})
                        yield KvDirectFrame(desc).to_wire()
                elif mode is not None:
                    kb, vb = self.kv.gather(seq.block_table[shipped:total])
                    desc = self.direct_transfer.offer(
                        mode, [kb, vb],
                        {"num_tokens": seq.prompt_len, "n": n,
                         "block_size": bs, "start_block": shipped})
                    yield KvDirectFrame(desc).to_wire()
                else:
                    bundle = await self._gather_bundle(
                        seq.block_table[shipped:total], seq.prompt_len,
                        shipped)
            yield PrefillResponse(token_id=token, logprob=logp,
                                  bundle=bundle).to_wire()
        finally:
            drainer.cancel()
            self.scheduler.abort(seq)
            self._wake.set()
            from dynamo_tpu.observability import get_tracer

            get_tracer().record("prefill.extract", ctx, start=t0,
                                end=time.time(), service="engine",
                                prompt_tokens=len(req.token_ids),
                                streamed=True, mode=mode or "host")

    def _kv_layer_groups(self, annotations):
        """Contiguous (start, end) layer ranges for the layer-interleaved
        tail transfer, or None for whole-bundle. Only when the decode peer
        advertised ``kv_layers`` (capability negotiation) AND this engine
        has splitting enabled AND the model is deep enough to split."""
        from dynamo_tpu.disagg.handlers import KV_LAYERS_ANNOTATION

        g = getattr(self.args, "kv_transfer_layer_groups", 0) or 0
        if g <= 1 or KV_LAYERS_ANNOTATION not in (annotations or []):
            return None
        return self.kv.layer_ranges(g)

    async def _gather_bundle(self, ids: list[int], num_tokens: int,
                             start_block: int):
        """Gather ``ids`` pages and bring them to host off the event loop."""
        from dynamo_tpu.disagg.protocols import KvBundle

        kb, vb = self.kv.gather(ids)
        k, v = await asyncio.to_thread(self.kv.to_host, kb, vb, len(ids))
        return KvBundle(k=k, v=v, num_tokens=num_tokens,
                        block_size=self.args.block_size,
                        start_block=start_block)

    # ------------------------------------------------- decode-side injection

    def alloc_inject(self, n_blocks: int):
        """Allocate blocks for externally-computed KV, respecting admission
        limits (injection bypasses the waiting queue). None = can't place."""
        free_frac = self.pool.num_free_blocks / max(1, self.pool.num_blocks)
        if (len(self.scheduler.running) >= self.args.max_num_seqs
                or free_frac < self.args.watermark):
            return None
        return self.pool.allocate(n_blocks)

    def release_inject(self, ids) -> None:
        self.pool.release(ids)

    def check_bundle_dims(self, bundle) -> bool:
        return self.kv.accepts(bundle)

    def scatter_chunk(self, ids, k: np.ndarray, v: np.ndarray,
                      start_layer=None) -> None:
        """Place received pages [L, n, bs, KV, hd] into device blocks
        ``ids``. ``start_layer`` set means k/v are a layer slice covering
        [start_layer, start_layer + k.shape[0]) only."""
        self.kv.scatter(ids, k, v, start_layer=start_layer)

    async def generate_prefilled(self, req: PreprocessedRequest, token_id: int,
                                 logprob, ids, ctx=None
                                 ) -> AsyncIterator[LLMEngineOutput]:
        """Decode a request whose prompt KV is already scattered into ``ids``.

        Ownership of ``ids`` transfers to the sequence (released on finish).
        """
        from dynamo_tpu.observability import get_tracer

        self._ensure_loop()
        tracer = get_tracer()
        t0 = time.time()
        sink: asyncio.Queue = asyncio.Queue()
        seq = await self._new_seq(req, ctx, sink)
        if seq.guided_state is not None:
            # the prefill worker sampled this token under the same mask
            # (it compiles the same options); re-advance the local cursor —
            # in a thread, since a new DFA state costs an O(vocab) walk
            await asyncio.to_thread(seq.guided_state.advance, token_id)
        self.scheduler.add_prefilled(seq, ids)

        # the prefill worker's token is the stream's first output;
        # engine-side "TTFT" here is just the injection admission time
        # (the real prefill cost lives in the prefill worker's
        # prefill.extract span)
        first = LLMEngineOutput(token_ids=[token_id],
                                log_probs=[logprob]
                                if logprob is not None else None)
        self.scheduler.append_token(seq, token_id)
        t_first = time.time()
        tracer.record("engine.ttft", ctx, start=t0, end=t_first,
                      service="engine", prompt_tokens=len(req.token_ids),
                      injected=True)
        reason = self.scheduler.check_finish(seq, token_id)
        if reason is not None:
            first.finish_reason = reason
            self.scheduler.finish(seq, reason)
            yield first
            return
        yield first

        self._wake.set()
        n_tokens = 1
        try:
            while True:
                out = await sink.get()
                if out is None:
                    return
                if isinstance(out, Exception):
                    raise out  # chaos/step failure: surfaces as StreamError
                n_tokens += len(out.token_ids)
                yield out
                if out.finish_reason is not None:
                    return
        finally:
            tracer.record("engine.decode", ctx, start=t_first,
                          end=time.time(), service="engine",
                          tokens=n_tokens)

    async def generate_injected(self, req: PreprocessedRequest, prefill,
                                ctx=None) -> AsyncIterator[LLMEngineOutput]:
        """Decode a request whose prompt KV arrives as one whole KvBundle
        (the unpipelined path; the handler's streamed path uses
        alloc_inject/scatter_chunk/generate_prefilled directly).

        Falls back to a full local generate when the bundle can't be placed
        (allocation failure or block-size mismatch).
        """
        bundle = prefill.bundle
        if (bundle is None or prefill.token_id < 0
                or not self.check_bundle_dims(bundle)
                or bundle.start_block != 0):
            if bundle is not None and not self.check_bundle_dims(bundle):
                logger.warning("KV bundle dims %s mismatch cache %s; local "
                               "prefill", bundle.k.shape, self.kv.dims)
            async for out in self.generate(req, ctx):
                yield out
            return

        self._ensure_loop()
        n = bundle.k.shape[1]
        ids = self.alloc_inject(n)
        if ids is None:  # memory pressure: recompute prefill locally
            async for out in self.generate(req, ctx):
                yield out
            return
        try:
            self.scatter_chunk(ids, bundle.k, bundle.v)
        except Exception:
            self.pool.release(ids)
            logger.exception("KV bundle scatter failed; local prefill")
            async for out in self.generate(req, ctx):
                yield out
            return
        async for out in self.generate_prefilled(req, prefill.token_id,
                                                 prefill.logprob, ids, ctx):
            yield out

    # ------------------------------------------------- KV-restore migration
    #
    # Stateful migration (docs/robustness.md): a migrated request's
    # recoverable prefix of (prompt ‖ emitted) is pulled from surviving
    # peers and attached HERE through the prefix cache — pool.register +
    # stored events, exactly like a KVBM onboard — so the subsequent
    # generate() prefix-matches it and recomputes only the tail. The
    # attach is charge-free by construction: prefix hits never advance
    # the QoS ledger (scheduler.commit_computed charges computed deltas
    # only), mirroring the disagg add_prefilled charge=False discipline.

    def restore_probe(self, req: PreprocessedRequest):
        """Salted TokenBlockSequence over the request's matchable full
        blocks — the hash chain restore pulls/attaches against. None when
        restore cannot apply (prefix caching off, or nothing matchable)."""
        from dynamo_tpu.tokens import TokenBlockSequence

        if not self.args.enable_prefix_caching:
            return None
        bs = self.args.block_size
        # never the whole prompt: at least one token must be computed
        # locally to produce logits (same rule as _prefix_match)
        matchable = (len(req.token_ids) - 1) // bs
        if matchable <= 0:
            return None
        return TokenBlockSequence.from_tokens(
            list(req.token_ids[: matchable * bs]), bs,
            Scheduler._salt_for(req))

    def resident_prefix_blocks(self, probe) -> int:
        """Leading blocks of ``probe`` recoverable here WITHOUT a peer
        pull: device prefix cache, or the G2 host tier that admission's
        synchronous onboard reads. G3/G4 do NOT count — disk only feeds a
        background promotion and G4 is a remote index, so treating them
        as resident would skip pulls the stream actually needed and then
        re-prefill anyway."""
        hashes = probe.sequence_hashes()
        in_host = (self.kvbm.host_resident(hashes)
                   if self.kvbm is not None else frozenset())
        n = 0
        for h in hashes:
            if self.pool.lookup(h) is None and h not in in_host:
                break
            n += 1
        return n

    def attach_restored(self, probe, start: int, blocks: list) -> int:
        """Scatter pulled peer blocks into fresh device blocks and REGISTER
        them (prefix cache + stored events), extending the contiguous
        restored prefix from block ``start``. ``blocks`` is an ordered
        [(seq_hash, k, v), ...] run; validation stops at the first torn
        entry (hash out of order or shape mismatch) — like PR 8's layer
        tears, a torn bundle is rejected, never half-scattered. Returns
        how many blocks were attached; 0 leaks nothing."""
        if not blocks:
            return 0
        hashes = probe.sequence_hashes()
        quant, want_kv = self.kv.quant, self.kv.host_block_shape()
        ks, vs = [], []
        for i, (h, k, v) in enumerate(blocks):
            pos = start + i
            if pos >= len(hashes) or h != hashes[pos]:
                logger.warning("restore bundle torn at block %d (hash "
                               "mismatch); keeping %d blocks", pos, len(ks))
                break
            ok = (tuple(k.shape) == want_kv and tuple(v.shape) == want_kv
                  and (k.dtype == np.uint8 if quant else True))
            if not ok:
                logger.warning("restore bundle block %d shape %s mismatches "
                               "cache %s; truncating", pos, k.shape, want_kv)
                break
            ks.append(k)
            vs.append(v)
        if not ks:
            return 0
        ids = self._scatter_register(probe, start, ks, vs)
        if ids is None:
            return 0  # memory pressure / torn scatter: recompute
        # park in the LRU (refcount 0): generate()'s prefix match re-
        # acquires them moments later; until then they are ordinary
        # evictable cache content, so a failed restore leaks nothing
        self.pool.release(ids)
        return len(ks)

    def _scatter_register(self, probe, start: int, ks: list, vs: list):
        """Shared attach protocol for externally-sourced block data
        (KVBM onboard + KV restore): allocate, scatter per-block k/v
        stacks into the cache, register each block's hashes, announce
        ONE chained stored event. Returns the allocated ids (refcount 1,
        caller decides ownership) or None with nothing leaked."""
        ids = self.pool.allocate(len(ks))
        if ids is None:
            return None
        try:
            self.kv.scatter(ids, np.stack(ks, 1), np.stack(vs, 1))
        except Exception:
            self.pool.release(ids)
            logger.exception("block attach scatter failed")
            return None
        stored = []
        parent = (probe.blocks[start].parent_sequence_hash
                  if start < len(probe.blocks) else None)
        for i, bid in enumerate(ids):
            blk = probe.blocks[start + i]
            if self.pool.register(bid, blk.sequence_hash, blk.block_hash,
                                  blk.parent_sequence_hash):
                stored.append(StoredBlock(block_hash=blk.sequence_hash,
                                          tokens_hash=blk.block_hash))
        if stored and self.event_cb:  # this worker now owns the blocks
            self.event_cb(KvCacheEvent.stored(
                next(self._event_id), parent, stored))
        return ids

    async def export_blocks(self, hashes: list[int],
                            max_blocks: Optional[int] = None):
        """Serve a peer's KV-restore pull: yield (seq_hash, k, v) host
        arrays for the longest LEADING run of ``hashes`` recoverable here
        — device prefix cache first (pinned gather, same discipline as
        the offload path), then own G2/G3 tiers (kvbm.get_local; G4 is
        never touched — a deadline-bounded pull must not block on the
        object store). Stops at the first unrecoverable hash: restore
        attaches contiguous prefixes only."""
        budget = max_blocks if max_blocks is not None else len(hashes)
        run: list[tuple[int, int]] = []  # (hash, block_id) device run

        async def flush_run():
            if not run:
                return
            ids = [bid for _, bid in run]
            self.pool.acquire(ids)  # pin across the async gather
            try:
                kb, vb = self.kv.gather(ids)
                pairs = await asyncio.to_thread(self.kv.to_host_blocks,
                                                kb, vb, len(ids))
            finally:
                self.pool.release(ids)
            for (h, _bid), (k, v) in zip(run, pairs):
                yield h, k, v
            run.clear()

        served = 0
        for h in hashes:
            if served >= budget:
                break
            bid = self.pool.lookup(h)
            if bid is not None:
                run.append((h, bid))
                served += 1
                continue
            async for item in flush_run():
                yield item
            e = None
            if self.kvbm is not None:
                e = await asyncio.to_thread(self.kvbm.get_local, h)
            if e is None:
                break  # contiguity ends here
            served += 1
            yield h, e[0], e[1]
        async for item in flush_run():
            yield item

    def _hard_kill(self) -> None:
        """Chaos worker.kill: die like a SIGKILL. No sink resolution, no
        drain — just stop and tell the owner hooks (which exit the
        process, or kill serve handles + lease keepalive in-process)."""
        logger.warning("chaos: worker.kill fired — hard-dying with %d "
                       "running seqs", len(self.scheduler.running))
        self.killed = True
        self._closed = True
        for cb in list(self.on_kill):
            try:
                cb()
            except Exception:
                logger.exception("on_kill hook failed")

    def _ensure_loop(self) -> None:
        self._loop_ref = asyncio.get_running_loop()
        if self._task is None or self._task.done():
            self._task = self._loop_ref.create_task(self._run())

    async def close(self) -> None:
        self._closed = True
        self._wake.set()
        if self._task:
            try:
                await self._task
            except asyncio.CancelledError:
                pass
        if self._offload_tasks:
            await asyncio.gather(*list(self._offload_tasks),
                                 return_exceptions=True)
        if self.anomaly_profiler is not None:
            self.anomaly_profiler.close()  # stop a capture left open
        if self._clock is not None:
            self._clock.close()
        from dynamo_tpu.observability.flight import unregister_recorder
        unregister_recorder(self._flight_name)

    # ------------------------------------------------------------ main loop

    def _mark(self, phase: str) -> None:
        """The serving thread enters ``phase`` (flight.PHASES): one list of
        call sites feeds the flight records' ``phases`` and, under
        DYN_JAX_PROFILER=1, the ``dynamo.<phase>`` trace annotations."""
        if self._clock is not None:
            self._clock.mark(phase)

    def _landed(self) -> float:
        """Last thing a worker thread does with a step's result on the
        host: the stamp that ends the serving thread's ``device_wait``."""
        return self._clock.landed() if self._clock is not None else 0.0

    def _resumed(self, stamp: float) -> None:
        """The loop runs again after an await on the device: the wait ended
        at the worker's ``stamp``, what lies between is ``lag``, and what
        follows every such await is ``commit``."""
        if self._clock is not None:
            self._clock.mark("lag", at=stamp)
            self._clock.mark("commit")

    async def _run(self) -> None:
        logger.info("engine loop starting: %d blocks × %d tokens, tp=%d",
                    self.num_blocks, self.args.block_size, self.args.tp_size)
        from dynamo_tpu.observability import profiler
        from dynamo_tpu.observability.flight import PhaseClock

        if self._clock is not None:
            self._clock.close()
        self._clock = (PhaseClock(annotate=profiler.enabled())
                       if self.flight.enabled or profiler.enabled() else None)
        while not self._closed:
            if not self.scheduler.has_work:
                self._mark("idle")
                self._wake.clear()
                await self._wake.wait()
                continue
            self._mark("plan")
            plan = self.scheduler.plan()
            chaos = _get_chaos()
            if (chaos is not None and not plan.empty
                    and chaos.should_error("worker.kill")):
                # seeded hard death mid-decode (SIGKILL-grade): stop the
                # loop NOW — no drain, no goodbye, in-flight sinks never
                # resolve. Streams break only when the lease TTL expires.
                self._hard_kill()
                return
            if (chaos is not None and not plan.empty
                    and chaos.should_error("engine.step")):
                # injected step crash: fail in-flight sequences with a
                # RETRYABLE stream error (a dead worker's streams migrate;
                # the chaos layer exercises exactly that path)
                logger.warning("chaos: engine.step error injected; failing "
                               "%d in-flight seqs",
                               len(self.scheduler.running))
                for s in list(self.scheduler.running):
                    self.scheduler.finish(s, FinishReason.ERROR)
                    s.sink.put_nowait(StreamError(
                        "chaos: injected engine step error"))
                continue
            if plan.empty:
                # memory-starved and nothing runnable: park until a BlockPool
                # release or a finishing sequence sets _wake (event-driven —
                # the old 5 ms poll burned a wakeup per tick under pressure).
                # The timeout is a safety net for edge signals that have no
                # hook (e.g. a context cancelled while we sleep).
                self._mark("blocked")
                self._wake.clear()
                t0 = time.perf_counter()
                try:
                    await asyncio.wait_for(self._wake.wait(), timeout=0.05)
                except asyncio.TimeoutError:
                    pass
                # empty-step bubble: work exists but nothing could run —
                # the flight record carries how long the engine sat idle.
                # Rate-limited (same 10 ms guard as the mocker): _wake is
                # set by every arrival/cancel/offload, so a stall under
                # heavy ingress would otherwise flood the ring with
                # identical bubbles and evict the records explaining it
                now = time.monotonic()
                if now - self._last_empty_rec >= 0.01:
                    self._last_empty_rec = now
                    self._flight_record(
                        "empty", (time.perf_counter() - t0) * 1000,
                        decode_rows=0, prefill_chunks=0, chunk_tokens=0)
                continue
            try:
                await self._execute(plan)
            except Exception:
                logger.exception("engine step failed; failing in-flight seqs")
                for s in list(self.scheduler.running):
                    self.scheduler.finish(s, FinishReason.ERROR)
                    s.sink.put_nowait(LLMEngineOutput(
                        finish_reason=FinishReason.ERROR, text="engine step failed"))
            self.steps += 1
            if self.metrics_cb:
                self._mark("record")
                self.metrics_cb(self._metrics())
            # let request ingress / cancellation run
            self._mark("lag")
            await asyncio.sleep(0)

    async def _execute(self, plan: StepPlan) -> None:
        if not plan.prefill and plan.decode and self._can_pipeline(plan.decode):
            if await self._run_decode_pipelined(plan.decode):
                return
        if plan.empty:
            return
        # decode-only plans may take the burst/spec fast paths (K tokens or
        # a draft+verify round per dispatch) before falling back to the one
        # packed launch below
        if not plan.prefill and plan.decode:
            if await self._run_decode_fast(plan.decode):
                return
        # one packed launch for the whole plan — prefill chunks and
        # decode rows together (docs/performance.md ragged step). ONE
        # flight record per plan: the record owns the plan's starvation
        # count, QoS mix, and padded-token accounting.
        t0 = time.perf_counter()
        padded = await self._run_ragged(plan)
        wall = (time.perf_counter() - t0) * 1000
        if not plan.prefill and plan.decode:
            # plain decode step wall: the spec governor's cost baseline
            self._decode_step_ms = (
                wall if self._decode_step_ms is None
                else 0.8 * self._decode_step_ms + 0.2 * wall)
        self._flight_record(
            "ragged", wall, decode_rows=len(plan.decode),
            prefill_chunks=len(plan.prefill),
            chunk_tokens=sum(w.chunk for w in plan.prefill),
            padded=padded, qos_mix=self._plan_qos_mix(plan),
            constrained=self._constrained_count(
                plan.decode + [w.seq for w in plan.prefill]),
            decode_seqs=plan.decode,
            prefill_seqs=[w.seq for w in plan.prefill])

    def step_trace_summary(self) -> dict:
        """The flight ring's newest steps by kind: steps / seqs / tokens /
        total+mean wall / padded tokens — the first thing to read when e2e
        throughput is far below the kernel ceiling. Empty with DYN_FLIGHT=0
        (the records are the only step timing the engine keeps)."""
        return self.flight.kind_summary()

    # --------------------------------------------------- flight recording

    def _note_compile(self, kind: str, sig: tuple, seconds: float) -> None:
        """A serving dispatch just traced a NEW jit signature: count it,
        time it, stage it for the step's flight record, and WARN when it
        happened in steady state (the silent latency cliff)."""
        self.compile_events[kind] = self.compile_events.get(kind, 0) + 1
        self.compile_seconds[kind] = (self.compile_seconds.get(kind, 0.0)
                                      + seconds)
        try:
            from dynamo_tpu.observability import get_tracer
            get_tracer().metrics.histogram(
                "compile_seconds",
                "seconds spent tracing/compiling post-warmup jit "
                "signatures").observe(seconds)
        except Exception:
            pass  # metrics must never fail a step
        self._last_compile = (kind, sig, seconds)
        # SAME steady signal as the record's compile-steady tag (the
        # recorder's count) so the WARNING and the tag never desync; with
        # recording disabled, executed steps are the fallback proxy
        steady = (self.flight.steady() if self.flight.enabled
                  else self.steps >= self.flight.steady_after)
        if steady:
            logger.warning(
                "steady-state compile: signature %s traced in %.2fs at "
                "step %d (warmup did not cover this shape)",
                (kind,) + tuple(sig), seconds, self.steps)

    def kv_tier_occupancy(self) -> dict:
        """G1–G4 occupancy for /metrics gauges, flight records, and
        ``dynctl top``: ``{tier: {"blocks": n, "bytes": n}}``. G1 is the
        device paged cache (active blocks); G2/G3/G4 come from the KVBM
        hierarchy when configured (zeros otherwise — the series exist
        either way, so dashboards can wire against an unconfigured tier)."""
        g1 = self.pool.num_active_blocks
        out = {"g1": {"blocks": g1,
                      "bytes": g1 * self.kv.device_block_nbytes}}
        if self.kvbm is not None:
            s = self.kvbm.stats()
            out["g2"] = {"blocks": s["host_blocks"],
                         "bytes": s["host_bytes"]}
            out["g3"] = {"blocks": s["disk_blocks"],
                         "bytes": s["disk_bytes"]}
            out["g4"] = {"blocks": s["remote_blocks"],
                         "bytes": s["remote_bytes"]}
        else:
            for tier in ("g2", "g3", "g4"):
                out[tier] = {"blocks": 0, "bytes": 0}
        return out

    def _refuse_state(self, what: str, *_a, **_k):
        raise NotImplementedError(
            f"{what}: a model with recurrent state cannot hand over, take "
            "in or re-read part of a sequence's cache (state snapshots; "
            "state through disaggregated transfer and KVBM are not "
            "implemented)")

    def _keep_state(self, fn):
        """``fn`` with the recurrent-state arrays passed last (donated) and
        its last output, the updated arrays, kept here: callers see the
        step of a model without state."""
        def step(*operands):
            *out, self.kv.state = fn(*operands, self.kv.state)
            return tuple(out)
        return step

    def _keep_moe_stats(self, fn):
        """``fn`` minus its fourth output (the held-experts layer's
        counters), which waits on the device until a later flight record
        finds it ready: no step blocks on its own counters."""
        def step(*operands):
            logits, k, v, stats = fn(*operands)
            self._moe_pending.append(stats)
            return logits, k, v
        return step

    def _drain_moe_stats(self) -> None:
        """Called once a flight record: takes the counters of the oldest
        step still pending — the one the record is for; its tokens are on
        the host already — and of any that piled up beyond the pipeline's
        depth."""
        first = True
        while self._moe_pending and (first or len(self._moe_pending) > 2):
            first = False
            stats = np.asarray(self._moe_pending.popleft())  # [groups, ·]
            self.moe_assignments_total["all"] += int(stats[:, 0].sum())
            self.moe_assignments_total["held"] += int(stats[:, 1].sum())
            self.moe_expert_tokens_total += stats[:, self._moe_head:].sum(
                0)
            self.moe_row_tiles_total += int(stats[:, 3].sum())
            self.moe_combine_rows_total["read"] += int(stats[:, 4].sum())
            self.moe_combine_rows_total["worst_case"] += int(
                stats[:, 5].sum())
            self._moe_step += stats[:, 1:self._moe_head]

    def _dead_window_pages(self) -> int:
        """Pages of window cache groups wholly behind their sequence's
        window, summed over groups: page p of a sequence of n tokens is
        dead when its last slot is at or before n − window. (A page shared
        through the prefix cache counts once per holder.)"""
        bs, dead = self.args.block_size, 0
        for g in self.cfg.kv_cache_spec:
            if g.window:
                dead += sum(max(0, (len(s.tokens) - g.window + 1) // bs)
                            for s in self.scheduler.running)
        return dead

    def _flight_record(self, kind: str, wall_ms: float, decode_rows: int,
                       prefill_chunks: int, chunk_tokens: int,
                       padded: int = 0,
                       qos_mix: Optional[dict] = None,
                       starved: Optional[int] = None,
                       constrained: int = 0,
                       decode_seqs=None, prefill_seqs=None) -> None:
        """Append one flight record for an executed step: snapshot queue
        depths + tier occupancy, difference the cumulative preempt/swap
        totals into per-step deltas, attach a compile staged by
        ``_note_compile`` during this step's dispatch, stamp the
        step↔request-id linkage the attribution join needs, cut the phase
        clock (the record carries the loop's time since the record before
        it, by phase: its own making up to here is ``record``) and feed
        the anomaly-triggered profiler."""
        self._mark("record")
        fb = self.ragged_fallback_reason
        if fb is not None:
            # every executed step on a degraded attention path counts —
            # the counter runs even with the flight recorder disabled
            self.ragged_fallback_total[fb] = (
                self.ragged_fallback_total.get(fb, 0) + 1)
        if self._moe_held:
            self._drain_moe_stats()
        moe_step = self._moe_step.tolist()
        self._moe_step[:] = 0
        if not self.flight.enabled:
            return
        sched = self.scheduler
        cur = {"ps": sched.preempt_swap_total,
               "pr": sched.preempt_recompute_total,
               "so": self.swap_out_blocks, "si": self.swap_in_blocks,
               "wt": self.wide_tile_rows_total,
               "sw": self.ssd_block_rows_total["walked"],
               "sm": self.ssd_block_rows_total["max"]}
        last = self._flight_last
        delta = {k: cur[k] - last.get(k, 0) for k in cur}
        self._flight_last = cur
        compile_s, compile_sig = 0.0, ""
        if self._last_compile is not None:
            ck, cs, csec = self._last_compile
            compile_s = csec
            compile_sig = ":".join(str(x) for x in (ck,) + tuple(cs))
            self._last_compile = None
        now = time.monotonic()
        if now - self._flight_tiers_t > 0.05:
            self._flight_tiers = {
                t: v["blocks"] for t, v in self.kv_tier_occupancy().items()}
            self._flight_tiers_t = now
        tiers = self._flight_tiers
        # what follows the cut (the fields below, the ring, the anomaly
        # profiler, metrics_cb) is the NEXT record's ``record``
        period_ms, phases = (self._clock.cut() if self._clock is not None
                             else (0.0, {}))
        rec = self.flight.record(
            kind, wall_ms,
            period_ms=period_ms, phases=phases,
            dispatch_ms=phases.get("dispatch", 0.0),
            decode_rows=decode_rows, prefill_chunks=prefill_chunks,
            chunk_tokens=chunk_tokens, padded_tokens=padded,
            compile_s=compile_s, compile_sig=compile_sig,
            preempt_swap=delta["ps"], preempt_recompute=delta["pr"],
            swap_out_blocks=delta["so"], swap_in_blocks=delta["si"],
            wide_tile_rows=delta["wt"],
            moe_pairs=sum(g[0] for g in moe_step),
            moe_experts_touched=sum(g[1] for g in moe_step),
            moe_tiles=sum(g[2] for g in moe_step),
            moe_by_group=([g[:3] for g in moe_step]
                          if self._moe_held else []),
            moe_combine_rows=sum(g[3] for g in moe_step),
            moe_combine_rows_max=sum(g[4] for g in moe_step),
            dead_window_pages=self._dead_window_pages(),
            **({} if self.kv.state is None else self._state_fields(
                kind, decode_rows, prefill_chunks, chunk_tokens, padded,
                decode_seqs, delta)),
            waiting=sched.num_waiting(), swapped=len(sched.swapped),
            running=len(sched.running),
            starved_decode=(sched.last_starved_decode
                            if starved is None else starved),
            # like starved_decode: the plan's own count, where the record
            # is of a plan (a pipelined step passes starved=0: it has none)
            prefill_blocked=(sched.last_prefill_blocked
                             if starved is None else 0),
            constrained_rows=constrained,
            kv_tiers=tiers, qos_mix=qos_mix or {},
            decode_ids=self._ctx_ids(decode_seqs),
            prefill_ids=self._ctx_ids(prefill_seqs),
            starved_ids=(list(sched.last_starved_ids)
                         if starved is None else []))
        if rec is not None and fb is not None:
            rec.tags.append("ragged_fallback:" + fb)
        if self.anomaly_profiler is not None:
            self.anomaly_profiler.on_record(rec)

    def _state_fields(self, kind, decode_rows, prefill_chunks, chunk_tokens,
                      padded, decode_seqs, delta) -> dict:
        """A state model's flight fields: slots held, the rows whose state
        the step moved (a pipelined step's: every row it was dispatched
        with, finished meanwhile or not), the step program that did and
        what the chunked scan walked of what it could have."""
        sched = self.scheduler
        if kind == "decode_pipe":
            decode_rows = len(decode_seqs)
            bucket = self.args.bucket_ragged_tokens(decode_rows)
        else:
            bucket = decode_rows + chunk_tokens + padded
        return {"state_slots_used": sched.state_slots - len(sched.state_free),
                "state_rows_prefill": prefill_chunks,
                "state_rows_decode": decode_rows,
                "state_program": ("m" if prefill_chunks else "d")
                + str(bucket),
                "ssd_block_rows": delta["sw"],
                "ssd_block_rows_max": delta["sm"]}

    def _count_ssd_blocks(self, rows3, T: int) -> None:
        """Called where a chunk-holding step's ``rows3`` is built: the
        blocks of the flat token axis each chunk row (q_len > 1) overlaps
        — the (block, row) pairs ``mamba2_chunk_scan`` walks — beside the
        blocks x RAGGED_MAX_CHUNKS of the bucket, a Mamba-2 layer."""
        spec = self.cfg.state_spec
        if spec is None or spec.mixer != "mamba2":
            return
        from dynamo_tpu.ops.mamba2 import SSD_BLOCK as Q

        q0, n = rows3[..., 0], rows3[..., 1]
        walked = np.where(n > 1, (q0 + n - 1) // Q - q0 // Q + 1, 0).sum()
        L = len(spec.layers)
        self.ssd_block_rows_total["walked"] += L * int(walked)
        self.ssd_block_rows_total["max"] += L * -(-T // Q) * RAGGED_MAX_CHUNKS

    def _count_wide_rows(self, rows3) -> None:
        """Called where a step's ``rows3`` is built: the rows whose q_len
        takes the ragged kernel's wide query tile."""
        from dynamo_tpu.ops.ragged_attention import NARROW_TILE

        self.wide_tile_rows_total += int(
            np.count_nonzero(rows3[..., 1] > NARROW_TILE))

    @staticmethod
    def _ctx_ids(seqs) -> list:
        """Request ids (Context ids — what traces and attribution key on)
        of the step's sequences; context-less seqs contribute nothing."""
        if not seqs:
            return []
        out = []
        for s in seqs:
            rid = getattr(s.ctx, "id", None)
            if rid:
                out.append(rid)
        return out

    @staticmethod
    def _qos_mix_of(seqs) -> dict:
        mix: dict[str, int] = {}
        for s in seqs:
            mix[s.priority] = mix.get(s.priority, 0) + 1
        return mix

    @staticmethod
    def _constrained_count(seqs) -> int:
        return sum(1 for s in seqs if s.guided_state is not None)

    def _plan_qos_mix(self, plan: StepPlan) -> dict:
        return self._qos_mix_of(
            plan.decode + [w.seq for w in plan.prefill])

    # ------------------------------------------------------- bucket warmup

    async def warmup(self) -> dict:
        """AOT precompile of the ragged token-bucket signatures, so the
        first REAL request never eats an XLA compile — first-compile is the
        TTFT p95-vs-p50 cliff this attacks.

        The ragged step's whole signature space IS the token-bucket list
        (R, W, and the chunk grid derive statically from T), so warmup is a
        handful of traces instead of the old (chunk × batch × width)
        bucketed lattice — the table width never enters a ragged
        signature. Dummy writes land in the reserved NULL
        block, whose contents are garbage by design. Must run BEFORE
        serving traffic (the dummy calls ride the same donated cache chain
        as real steps). Returns a report listing each compiled signature
        exactly once.
        """
        if self._multihost:
            # NOT silent: warmup_skipped feeds WorkerStats.warmed_up, so
            # the operator's readiness gate (deploy/operator.py) stops
            # counting this worker as warm until its first real step lands
            # — a cold multi-host worker must not absorb autoscale traffic
            # projections while it pays the compile cliff.
            logger.warning("bucket warmup skipped under multi-host (dummy "
                           "steps are not in the leader's broadcast "
                           "replay); worker reports warmed_up=false until "
                           "its first served step")
            self.warmup_skipped = True
            return {"skipped": "multihost"}
        if self.scheduler.has_work:
            # the dummy dispatches run in a worker thread and reassign the
            # donated cache chain; racing a live engine step would hand XLA
            # an already-donated buffer and fail every in-flight sequence
            raise RuntimeError(
                "bucket warmup must run before serving traffic (sequences "
                "are already scheduled)")
        args = self.args
        t_start = time.perf_counter()

        def run_ragged():
            import jax.numpy as jnp

            from dynamo_tpu.engine.model import ragged_grid_shape

            report: dict = {"ragged": [], "sample": []}
            sampled: set = set()
            for T in args.ragged_token_buckets:
                R = args.ragged_rows(T)
                W = args.max_blocks_per_seq
                C, _ = ragged_grid_shape(T)
                ints5 = np.zeros((5, T), np.int32)
                ints5[3] = C
                rows3 = np.zeros((R, self._row_cols), np.int32)
                rows3[0, :3] = (0, 1, 1)  # one real row attending a NULL slot
                if self.kv.state is not None:
                    rows3[:, 3] = args.max_num_seqs  # the dump slot
                bt = np.full((R, W), NULL_BLOCK, np.int32)
                gr = np.zeros((C,), np.int32)
                if self.pp_fn is not None:
                    # pp: one packed microbatch stack per token bucket —
                    # the signature is (T, M) with M fixed at pp_size
                    Mmb = self._pp
                    logits, self.kv.k, self.kv.v = self.pp_fn(
                        self.params,
                        jnp.asarray(np.broadcast_to(
                            ints5, (Mmb, 5, T)).copy()),
                        jnp.asarray(np.broadcast_to(
                            rows3, (Mmb, R, 3)).copy()),
                        jnp.asarray(np.broadcast_to(gr, (Mmb, C)).copy()),
                        jnp.asarray(np.broadcast_to(
                            bt, (Mmb, R, W)).copy()),
                        self.kv.k, self.kv.v)
                    logits = logits[0]
                    self.compiled_signatures.add(("pp", T, Mmb))
                    report["ragged"].append(("pp", T, R, W))
                else:
                    # both variants: the mixed step and the pipelined
                    # decode-only step
                    for kind, fn in (("ragged", self.ragged_fn),
                                     ("ragged_dec", self.ragged_dec_fn)):
                        logits, self.kv.k, self.kv.v = fn(
                            self.params, jnp.asarray(ints5),
                            jnp.asarray(rows3), jnp.asarray(gr),
                            jnp.asarray(bt), self.kv.k, self.kv.v)
                        self.compiled_signatures.add((kind, T))
                        report["ragged"].append((kind, T, R, W))
                if R not in sampled:
                    sampled.add(R)
                    toks, _ = self._sampling.sample_jit(
                        logits, np.zeros((R,), np.float32),
                        np.zeros((R,), np.int32), np.ones((R,), np.float32),
                        self._sampling.make_keys([0] * R, [0] * R))
                    np.asarray(toks)
                    report["sample"].append(R)
            return report

        report = await asyncio.to_thread(run_ragged)
        self._moe_pending.clear()  # warm-up routed nothing anyone sent
        report["seconds"] = round(time.perf_counter() - t_start, 2)
        logger.info("ragged warmup: %d token-bucket signatures in %.1fs",
                    len(report["ragged"]), report["seconds"])
        return report

    # ------------------------------------------------------------- prefill

    def _mm_arrays(self, seq, start: int, end: int, S: int):
        """(mm_vec [1,S,D] f32, mm_mask [1,S] bool) for the chunk, or None
        when no multimodal segment overlaps [start, end)."""
        segs = seq.req.mm_embeds or []
        D = self.cfg.hidden_size
        vec = None
        mask = None
        for seg in segs:
            s0 = int(seg.get("start", 0))
            rows = seg["embeds"]
            for j, row in enumerate(rows):
                p = s0 + j
                if start <= p < end:
                    if vec is None:
                        vec = np.zeros((1, S, D), np.float32)
                        mask = np.zeros((1, S), bool)
                    vec[0, p - start, :len(row)] = row
                    mask[0, p - start] = True
        return (vec, mask) if vec is not None else None

    # -------------------------------------------------------- ragged step

    def _get_ragged_mm_fn(self):
        if self._ragged_mm_fn is None:
            from dynamo_tpu.engine import model as M

            self._ragged_mm_fn = M.make_ragged_step_fn(
                self.cfg, self.args.block_size, self.mesh,
                use_pallas=self.args.use_pallas_attention,
                replicate_logits=self._multihost,
                kv_quant=self._kv_quant, mm=True)
        return self._ragged_mm_fn

    def _get_verify_masked_fn(self):
        if self._verify_masked_fn is None:
            from dynamo_tpu.engine import model as M

            self._verify_masked_fn = M.make_ragged_verify_fn(
                self.cfg, self.args.block_size, self.mesh,
                replicate_outputs=self._multihost,
                kv_quant=self._kv_quant, masked=True)
        return self._verify_masked_fn

    async def _run_ragged(self, plan: StepPlan) -> int:
        """Execute the WHOLE plan — decode rows and prefill chunks — as one
        packed ragged launch (ops/ragged_attention.py; docs/performance.md).

        Every row's tokens pack consecutively into a [T_bucket] batch with
        per-row (q_start, q_len, kv_len) metadata; nothing pads to a
        chunk/batch/width bucket, so the only waste is the tail of the one
        token bucket (returned, for the step trace / padded-tokens metric).
        Under pipeline parallelism the plan splits into M packed ragged
        microbatches instead (_run_ragged_pp).
        """
        if self.pp_fn is not None:
            return await self._run_ragged_pp(plan)
        import jax.numpy as jnp

        from dynamo_tpu.engine.model import ragged_grid_shape

        self._mark("build")
        args = self.args
        bs = args.block_size
        works = plan.prefill
        total = len(plan.decode) + sum(w.chunk for w in works)
        T = args.bucket_ragged_tokens(total)
        R = args.ragged_rows(T)
        W = args.max_blocks_per_seq
        C, S_C = ragged_grid_shape(T)
        self.param_reads += 1
        self.padded_tokens_total += T - total

        # ints5: tokens / positions / slot_map / grid_row / grid_col —
        # grid_row defaults to the dump tile C (decode + padding tokens)
        ints5 = np.zeros((5, T), np.int32)
        ints5[3] = C
        # q_start/q_len/kv_len (0 = pad) and, a state model, the state slot
        rows3 = np.zeros((R, self._row_cols), np.int32)
        grid_rows = np.zeros((C,), np.int32)
        bt = np.full((R, W), NULL_BLOCK, np.int32)
        mm_vec = mm_mask = None
        #: (seq, samples?) in row order — decode rows first, then chunks
        rows = [(s, True, None) for s in plan.decode]
        rows += [(w.seq, w.sample, w) for w in works]
        t = 0
        tile = 0
        for i, (seq, _sample, w) in enumerate(rows):
            if w is None:  # decode row: one token, the sequence's newest
                start, chunk = len(seq.tokens) - 1, 1
            else:
                start, chunk = w.start, w.chunk
            end = start + chunk
            ints5[0, t:t + chunk] = seq.tokens[start:end]
            ints5[1, t:t + chunk] = np.arange(start, end)
            for j, pos in enumerate(range(start, end)):
                ints5[2, t + j] = seq.block_table[pos // bs] * bs + pos % bs
            if chunk > 1:
                # chunk grid tiling: ceil(chunk / S_C) tiles of this row
                # (1-token chunks ride the decode sub-call instead)
                for off in range(0, chunk, S_C):
                    width = min(S_C, chunk - off)
                    grid_rows[tile] = i
                    ints5[3, t + off:t + off + width] = tile
                    ints5[4, t + off:t + off + width] = np.arange(width)
                    tile += 1
            rows3[i, :3] = (t, chunk, end)
            if self.kv.state is not None:
                rows3[i, 3] = seq.state_slot
            n = min(len(seq.block_table), W)
            bt[i, :n] = seq.block_table[:n]
            if w is not None:
                mm = self._mm_arrays(seq, start, end, chunk)
                if mm is not None:
                    if mm_vec is None:
                        mm_vec = np.zeros((T, self.cfg.hidden_size),
                                          np.float32)
                        mm_mask = np.zeros((T,), bool)
                    mm_vec[t:t + chunk] = mm[0][0]
                    mm_mask[t:t + chunk] = mm[1][0]
            t += chunk
        assert tile <= C, f"chunk grid overflow: {tile} > {C}"

        self._count_wide_rows(rows3)
        operands = {"ints5": ints5, "rows3": rows3, "grid_rows": grid_rows,
                    "block_tables": bt}
        if mm_vec is not None:
            operands["mm_vec"], operands["mm_mask"] = mm_vec, mm_mask
            kind, fn = "ragged_mm", self._get_ragged_mm_fn()
        elif works:
            kind, fn = "ragged", self.ragged_fn
        else:
            # decode-only plan that bypassed the pipelined loop (logprobs,
            # host-oracle guided fallbacks, penalties, swapped/waiting
            # work pending): the no-chunk-grid variant
            kind, fn = "ragged_dec", self.ragged_dec_fn
        if works:
            self._count_ssd_blocks(rows3, T)
        new_sig = (kind, T) not in self.compiled_signatures
        self.compiled_signatures.add((kind, T))
        self._mark("put")
        self._broadcast(kind, **operands)
        on_device = [self._put_batch(k, v) for k, v in operands.items()]
        self._mark("dispatch")
        t0c = time.perf_counter() if new_sig else 0.0
        logits, self.kv.k, self.kv.v = fn(
            self.params, *on_device, self.kv.k, self.kv.v)
        if new_sig:
            self._note_compile(kind, (T,), time.perf_counter() - t0c)

        # commit BEFORE sampling, exactly like the bucketed steps: chunk
        # progress (and disagg block shipping) must never wait on the
        # sampler's host round trip
        self._mark("commit")
        for w in works:
            seq, end = w.seq, w.start + w.chunk
            self.scheduler.commit_computed(seq, end)
            if seq.progress_cb is not None:
                try:
                    seq.progress_cb(end)
                except Exception:
                    logger.exception("prefill progress callback failed; "
                                     "disabling chunk shipping for %s",
                                     seq.request_id)
                    seq.progress_cb = None
        for s in plan.decode:
            self.scheduler.commit_computed(s, len(s.tokens))

        sample_rows = [(i, seq) for i, (seq, smp, _w) in enumerate(rows)
                       if smp]
        if not sample_rows:
            # every row was a mid-prompt chunk: logits unused, sync to pace
            await self._device_sync(logits)
            return T - total
        idx = [i for i, _ in sample_rows]
        if idx == list(range(len(rows))):
            # common case: every row samples — _sample tolerates the
            # padded R >= len(rows), no gather needed
            sel = logits
        else:
            self._mark("put")
            Bp = args.bucket_batch(len(idx))
            sel = logits[jnp.asarray(idx + [idx[0]] * (Bp - len(idx)),
                                     jnp.int32)]
        seqs = [s for _, s in sample_rows]
        toks, logps, tops = await self._sample(seqs, sel)
        for j, (_i, seq) in enumerate(sample_rows):
            self._deliver(seq, int(toks[j]), float(logps[j]), tops.get(j))
        return T - total

    async def _device_sync(self, logits) -> None:
        """Wait in a worker thread until ``logits`` are computed: a step
        that samples nothing still paces the loop by its device time."""
        self._mark("device_wait")
        self._resumed(await asyncio.to_thread(
            lambda: (logits.block_until_ready(), self._landed())[1]))

    async def _run_ragged_pp(self, plan: StepPlan) -> int:
        """The pipeline-parallel ragged step: the plan's rows split into
        M = pp_size packed ragged microbatches (longest-first greedy into
        the lightest bin, so the GPipe ticks stay balanced), each bin laid
        out exactly like the single-bin packed launch. The compiled
        signature is (T, M) — T covers the HEAVIEST bin, M is fixed — so
        pp serving warms the same token-bucket family as everything else.
        """
        import jax.numpy as jnp

        from dynamo_tpu.engine.model import ragged_grid_shape

        self._mark("build")
        args = self.args
        bs = args.block_size
        works = plan.prefill
        Mmb = self._pp
        rows_all = [(s, True, None) for s in plan.decode]
        rows_all += [(w.seq, w.sample, w) for w in works]

        def ntok(row):
            return 1 if row[2] is None else row[2].chunk

        bins: list[list] = [[] for _ in range(Mmb)]
        loads = [0] * Mmb
        for row in sorted(rows_all, key=ntok, reverse=True):
            m = loads.index(min(loads))
            bins[m].append(row)
            loads[m] += ntok(row)
        total = sum(loads)
        T = args.bucket_ragged_tokens(max(1, max(loads)))
        R = args.ragged_rows(T)
        W = args.max_blocks_per_seq
        C, S_C = ragged_grid_shape(T)
        self.param_reads += 1
        self.padded_tokens_total += Mmb * T - total

        ints5 = np.zeros((Mmb, 5, T), np.int32)
        ints5[:, 3] = C
        rows3 = np.zeros((Mmb, R, 3), np.int32)
        grid_rows = np.zeros((Mmb, C), np.int32)
        bt = np.full((Mmb, R, W), NULL_BLOCK, np.int32)
        #: (bin, row-in-bin, seq) for every sampling row, bin pack order
        sample_rows = []
        for m, rows in enumerate(bins):
            t = 0
            tile = 0
            for i, (seq, sample, w) in enumerate(rows):
                if w is None:
                    start, chunk = len(seq.tokens) - 1, 1
                else:
                    start, chunk = w.start, w.chunk
                    if seq.req.mm_embeds:
                        # backstop only — _new_seq refuses mm requests at
                        # admission under pp
                        raise RuntimeError(
                            "multimodal prefill is not supported under "
                            "pipeline parallelism")
                end = start + chunk
                ints5[m, 0, t:t + chunk] = seq.tokens[start:end]
                ints5[m, 1, t:t + chunk] = np.arange(start, end)
                for j, pos in enumerate(range(start, end)):
                    ints5[m, 2, t + j] = (seq.block_table[pos // bs] * bs
                                          + pos % bs)
                if chunk > 1:
                    for off in range(0, chunk, S_C):
                        width = min(S_C, chunk - off)
                        grid_rows[m, tile] = i
                        ints5[m, 3, t + off:t + off + width] = tile
                        ints5[m, 4, t + off:t + off + width] = (
                            np.arange(width))
                        tile += 1
                rows3[m, i] = (t, chunk, end)
                n = min(len(seq.block_table), W)
                bt[m, i, :n] = seq.block_table[:n]
                if sample:
                    sample_rows.append((m, i, seq))
                t += chunk
            assert tile <= C, f"chunk grid overflow: {tile} > {C}"

        self._count_wide_rows(rows3)
        operands = {"ints5": ints5, "rows3": rows3, "grid_rows": grid_rows,
                    "block_tables": bt}
        new_sig = ("pp", T, Mmb) not in self.compiled_signatures
        self.compiled_signatures.add(("pp", T, Mmb))
        self._mark("put")
        self._broadcast("pp", **operands)
        on_device = [self._put_batch(k, v) for k, v in operands.items()]
        self._mark("dispatch")
        t0c = time.perf_counter() if new_sig else 0.0
        logits, self.kv.k, self.kv.v = self.pp_fn(
            self.params, *on_device, self.kv.k, self.kv.v)
        if new_sig:
            self._note_compile("pp", (T, Mmb), time.perf_counter() - t0c)

        # commit BEFORE sampling, exactly like the single-bin launch
        self._mark("commit")
        for w in works:
            seq, end = w.seq, w.start + w.chunk
            self.scheduler.commit_computed(seq, end)
            if seq.progress_cb is not None:
                try:
                    seq.progress_cb(end)
                except Exception:
                    logger.exception("prefill progress callback failed; "
                                     "disabling chunk shipping for %s",
                                     seq.request_id)
                    seq.progress_cb = None
        for s in plan.decode:
            self.scheduler.commit_computed(s, len(s.tokens))

        if not sample_rows:
            await self._device_sync(logits)
            return Mmb * T - total
        # logits land [M, R, V]: flatten and gather the sampling rows,
        # padded to a batch bucket so the sampling jit sees bounded shapes
        self._mark("put")
        idx = [m * R + i for m, i, _ in sample_rows]
        Bp = args.bucket_batch(len(idx))
        flat = logits.reshape(Mmb * R, logits.shape[-1])
        sel = flat[jnp.asarray(idx + [idx[0]] * (Bp - len(idx)), jnp.int32)]
        seqs = [s for _m, _i, s in sample_rows]
        toks, logps, tops = await self._sample(seqs, sel)
        for j, (_m, _i, seq) in enumerate(sample_rows):
            self._deliver(seq, int(toks[j]), float(logps[j]), tops.get(j))
        return Mmb * T - total

    # -------------------------------------------------------------- decode

    # ---------------------------------------------- speculative decoding

    @staticmethod
    def _draft_tokens(s, k: int) -> list[int]:
        """Prompt-lookup draft: match the trailing 3- or 2-gram earlier in
        the sequence and propose the tokens that followed it.

        O(new tokens) per call: ``s.ngram_pos`` maps each n-gram to the END
        position of its newest occurrence, extended incrementally — a full
        backward history scan per decode step would be O(n²) Python work on
        the event loop over a long generation. The current trailing gram's
        own end is deliberately left unindexed until the sequence grows past
        it, so a lookup never matches itself.
        """
        tokens = s.tokens
        n_tok = len(tokens)
        idx = s.ngram_pos
        for e in range(max(s.ngram_indexed + 1, 2), n_tok):  # end-exclusive
            if e >= 2:
                idx[(tokens[e - 2], tokens[e - 1])] = e
            if e >= 3:
                idx[(tokens[e - 3], tokens[e - 2], tokens[e - 1])] = e
        s.ngram_indexed = max(s.ngram_indexed, n_tok - 1)
        for n in (3, 2):
            if n_tok <= n:
                continue
            e = idx.get(tuple(tokens[-n:]))
            if e is not None:
                cont = tokens[e:e + k]
                if cont:
                    return cont
        return []

    def _prealloc_blocks(self, seqs: list[SeqState], extra: int) -> bool:
        """All-or-nothing block preallocation for fused decode paths — a
        partial extension left behind would deepen the memory pressure that
        made it fail (shared by the burst and speculative paths)."""
        extended: list = []
        for s in seqs:
            before = len(s.block_table)
            if not self.scheduler._ensure_blocks(s, len(s.tokens) + extra):
                for s2, b2 in extended:
                    self.pool.release(s2.block_table[b2:])
                    del s2.block_table[b2:]
                return False
            if len(s.block_table) > before:
                extended.append((s, before))
        return True

    async def _run_draft_model(self, seqs: list[SeqState],
                               K: int) -> list[list[int]]:
        """Layer-skip draft dispatch: K greedy tokens per row from the
        first speculative_draft_layers layers (model.make_draft_fn). Draft
        KV lands in the tokens' real slots — blocks are already
        preallocated by the caller."""
        args = self.args
        # ragged-family signature, like the multi burst: row bucket from
        # the token bucket, static table width
        B = args.ragged_rows(args.bucket_ragged_tokens(len(seqs)))
        W = args.max_blocks_per_seq

        last_tokens = np.zeros((B,), np.int32)
        positions = np.zeros((B,), np.int32)
        bt = np.full((B, W), NULL_BLOCK, np.int32)
        kv_lens = np.zeros((B,), np.int32)
        for i, s in enumerate(seqs):
            last_tokens[i] = s.tokens[-1]
            positions[i] = len(s.tokens) - 1
            n = min(len(s.block_table), W)
            bt[i, :n] = s.block_table[:n]
            kv_lens[i] = len(s.tokens)

        ints = np.stack([last_tokens, positions, kv_lens], axis=1)
        self.compiled_signatures.add(("draft", B))
        self._broadcast("draft", ints=ints, block_tables=bt)
        toks, self.kv.k, self.kv.v = self.draft_fn(
            self.params, self._put_batch("ints", ints),
            self._put_batch("block_tables", bt),
            self.kv.k, self.kv.v)
        # draft forwards read draft_layers/num_layers of the weights
        self.param_reads += (K * args.speculative_draft_layers
                             / self.cfg.num_layers)
        toks = await asyncio.to_thread(lambda: np.asarray(toks))
        return [toks[:, i].tolist() for i in range(len(seqs))]

    async def _run_spec_decode(self, seqs: list[SeqState]) -> bool:
        """Draft-and-verify: one forward over [last_token, draft...] per seq
        accepts the longest greedy-matching draft prefix plus one corrected
        token — emitting 1..K+1 tokens per dispatch with EXACTLY the tokens
        plain greedy decode would produce. Returns False (fall back) when no
        seq drafts anything or block preallocation fails."""
        self._mark("other")  # no phase marks on the draft/verify path yet
        args = self.args
        K = args.speculative_tokens
        t0 = time.perf_counter()
        if self.draft_fn is not None:
            # the draft model writes KV into the draft slots, so blocks
            # must exist BEFORE drafting
            if not self._prealloc_blocks(seqs, K):
                return False
            drafts = await self._run_draft_model(seqs, K)
        else:
            drafts = [self._draft_tokens(s, K) for s in seqs]
            if not any(drafts):
                return False
            if not self._prealloc_blocks(seqs, K):
                return False
        ok = await self._verify_and_commit(seqs, drafts)
        if ok:
            # measured spec round (draft + verify + host round trip): the
            # governor's cost re-baseline (_spec_dispatch_cost)
            wall = (time.perf_counter() - t0) * 1000
            self._spec_round_ms = (
                wall if self._spec_round_ms is None
                else 0.8 * self._spec_round_ms + 0.2 * wall)
        return ok

    async def _verify_and_commit(self, seqs: list[SeqState],
                                 drafts: list[list[int]]) -> bool:
        """Verify ON the packed ragged layout: each seq is one ragged row
        with q_len = draft+1, so verify shares the serving step's
        token-bucket signature family instead of its own [B, S, W]
        lattice. Every verify row is a chunk (q_len > 1) occupying
        ceil(S / tile) chunk-grid tiles; the token bucket is chosen as the
        smallest that holds both the packed tokens AND the needed tiles,
        dispatching in groups when even the largest bucket cannot."""
        from dynamo_tpu.engine.model import ragged_grid_shape

        args = self.args
        K = args.speculative_tokens
        S = 1 + K
        bs = args.block_size

        def bucket_for(n: int):
            # smallest token bucket with n*S tokens AND n chunk rows' tiles
            for cand in args.ragged_token_buckets:
                C, S_C = ragged_grid_shape(cand)
                if cand >= n * S and n * -(-S // S_C) <= C:
                    return cand
            return None

        T_all = bucket_for(len(seqs))
        if T_all is not None:
            groups = [list(range(len(seqs)))]
        else:
            Tmax = args.ragged_token_buckets[-1]
            C, S_C = ragged_grid_shape(Tmax)
            cap = max(1, min(C // -(-S // S_C), Tmax // S))
            groups = [list(range(i, min(i + cap, len(seqs))))
                      for i in range(0, len(seqs), cap)]

        total_emitted = 0
        for grp in groups:
            n = len(grp)
            T = T_all if T_all is not None else bucket_for(n)
            R = args.ragged_rows(T)
            W = args.max_blocks_per_seq
            C, S_C = ragged_grid_shape(T)
            ints5 = np.zeros((5, T), np.int32)
            ints5[3] = C  # padding tokens: grid dump tile
            rows3 = np.zeros((R, 3), np.int32)
            grid_rows = np.zeros((C,), np.int32)
            bt = np.full((R, W), NULL_BLOCK, np.int32)
            t = 0
            tile = 0
            for i, gi in enumerate(grp):
                s = seqs[gi]
                d = drafts[gi]
                row = [s.tokens[-1]] + d + [0] * (K - len(d))
                base = len(s.tokens) - 1
                ints5[0, t:t + S] = row
                ints5[1, t:t + S] = base + np.arange(S)
                for j in range(S):
                    p = base + j
                    ints5[2, t + j] = s.block_table[p // bs] * bs + p % bs
                for off in range(0, S, S_C):
                    width = min(S_C, S - off)
                    grid_rows[tile] = i
                    ints5[3, t + off:t + off + width] = tile
                    ints5[4, t + off:t + off + width] = np.arange(width)
                    tile += 1
                rows3[i] = (t, S, len(s.tokens) + K)
                nblk = min(len(s.block_table), W)
                bt[i, :nblk] = s.block_table[:nblk]
                t += S
            assert tile <= C, f"verify grid overflow: {tile} > {C}"

            cursors = [_guided_fsm(seqs[gi]) for gi in grp]
            use_fsm = any(c is not None for c in cursors)
            self.compiled_signatures.add(
                ("verify_fsm" if use_fsm else "verify", T))
            self.padded_tokens_total += T - n * S
            self._count_wide_rows(rows3)
            operands = {"ints5": ints5, "rows3": rows3,
                        "grid_rows": grid_rows, "block_tables": bt}
            if use_fsm:
                # constrained rows verify under per-position FSM masks:
                # walk each cursor's compiled table along its draft
                # host-side (O(K) lookups, no device round trip) — a draft
                # token the mask forbids can never match the masked argmax,
                # so it is rejected at its position exactly as masked
                # single-step decode would reject it, and the bonus token
                # at the first mismatch is drawn from the correctly-
                # advanced state's mask.
                self._get_verify_masked_fn()
                W32 = self.structured.W32
                mw = np.empty((T, W32), np.uint32)
                mw[:] = np.uint32(0xFFFFFFFF)  # padding tokens: identity
                for i, c in enumerate(cursors):
                    if c is None:
                        continue
                    d = drafts[grp[i]]
                    fsm = c.seg.fsm
                    st = 0 if c.done else (c.state - c.seg.offset)
                    for j in range(S):
                        mw[i * S + j] = fsm.mask[st]
                        if j < len(d):
                            tok = d[j]
                            if tok in c._eos_set or not 0 <= tok < fsm.V:
                                st = 0
                            else:
                                st = int(fsm.next[st, tok])
                operands["mask_words"] = mw
                self._broadcast("verify_fsm", **operands)
                ids, lps, self.kv.k, self.kv.v = (
                    self._verify_masked_fn(
                        self.params,
                        *(self._put_batch(k, v)
                          for k, v in operands.items()),
                        self.kv.k, self.kv.v))
            else:
                self._broadcast("verify", **operands)
                ids, lps, self.kv.k, self.kv.v = self.verify_fn(
                    self.params,
                    *(self._put_batch(k, v) for k, v in operands.items()),
                    self.kv.k, self.kv.v)
            ids, lps = await asyncio.to_thread(
                lambda: (np.asarray(ids), np.asarray(lps)))

            for i, gi in enumerate(grp):
                s = seqs[gi]
                d = drafts[gi]
                q0 = i * S
                row_ids = ids[q0:q0 + S]
                row_lps = lps[q0:q0 + S]
                accepted = 0
                while (accepted < len(d)
                       and d[accepted] == int(row_ids[accepted])):
                    accepted += 1
                # emit accepted drafts + the corrected/bonus token as ONE
                # coalesced output; each commit marks the CURRENT tokens'
                # KV resident (the verify step computed it — accepted
                # drafts equal the real tokens) before the next append
                emitted = self._deliver_batch(s, row_ids[:accepted + 1],
                                              row_lps[:accepted + 1])
                # count what was actually DELIVERED — a seq finishing
                # mid-burst must not inflate acceptance telemetry
                self.spec_stats.num_drafts += 1
                self.spec_stats.num_draft_tokens += len(d)
                self.spec_stats.num_accepted_tokens += min(accepted, emitted)
                self.spec_stats.num_spec_tokens += emitted
                total_emitted += emitted
            self.param_reads += 1
        self._note_spec_result(total_emitted, len(seqs))
        return True

    # ------------------------------------------ spec auto-disable governor

    def _spec_active(self) -> bool:
        """False while the governor has speculative decode suspended (the
        rolling measured gain fell below 1 — drafting was a net slowdown).
        Re-probes automatically once ``spec_reprobe_steps`` steps pass."""
        return self.steps >= self._spec_resume_step

    def _spec_dispatch_cost(self) -> float:
        """Dispatch cost of one draft+verify round relative to a plain
        decode step. Re-baselined on MEASURED ragged dispatch walls: a
        verify row is just one more ragged chunk in the packed launch, so
        the static bucketed-dispatch constants below OVERESTIMATE its cost
        and made the governor suspend speculation too eagerly. When both
        EWMAs exist the measured ratio is used, floored at 1.01 (a round
        computes strictly more than a decode step) and capped at the
        static estimate (measurement only ever CHEAPENS spec — a noisy
        high sample must not suspend harder than the old model did)."""
        args = self.args
        if (args.speculative_method == "draft_layers"
                and args.speculative_draft_layers > 0):
            static = 1.0 + (args.speculative_tokens
                            * args.speculative_draft_layers
                            / max(1, self.cfg.num_layers))
        else:
            static = 1.05  # prompt lookup: free drafts, small overhead
        if (self._spec_round_ms is not None
                and self._decode_step_ms is not None
                and self._decode_step_ms > 0):
            return min(static,
                       max(1.01, self._spec_round_ms / self._decode_step_ms))
        return static

    def _note_spec_result(self, emitted: int, n_seqs: int) -> None:
        """Feed the governor one verify dispatch's outcome. When the mean
        tokens-per-dispatch over the window, discounted by the dispatch
        cost, stays under 1.0 (BENCH_r05: accept 0.019 → gain 0.729, a 27%
        slowdown with nothing turning it off), suspend speculation and
        re-probe after spec_reprobe_steps engine steps."""
        if self.args.spec_gain_window <= 0:
            return
        self._spec_window.append(emitted / max(1, n_seqs))
        if len(self._spec_window) < (self._spec_window.maxlen or 1):
            return
        gain = (sum(self._spec_window) / len(self._spec_window)
                / self._spec_dispatch_cost())
        self.spec_measured_gain = gain
        if gain < 1.0:
            self.spec_disabled_total += 1
            self._spec_resume_step = (self.steps
                                      + max(1, self.args.spec_reprobe_steps))
            self._spec_window.clear()
            logger.warning(
                "speculative decode suspended: measured gain %.3f < 1 over "
                "%d dispatches (accept rate %.3f); re-probing after %d "
                "steps", gain, self.args.spec_gain_window,
                self.spec_stats.num_accepted_tokens
                / max(1, self.spec_stats.num_draft_tokens),
                self.args.spec_reprobe_steps)

    async def _run_decode_fast(self, seqs: list[SeqState]) -> bool:
        # Burst/spec paths gate on the DECODE SUBSET only — not on a
        # globally-idle scheduler. The old `not waiting and all(running)`
        # gate meant any queued or mid-prefill request demoted every other
        # stream to one-token-per-dispatch; under continuous closed-loop
        # load that is the COMMON state, and each single step pays the full
        # dispatch+fetch round trip. A K-burst delays a pending prefill
        # chunk by one burst
        # (~bounded TTFT cost) and buys K× fewer host round trips.
        # (plan.decode already contains only remaining==1 seqs — the
        # scheduler guarantees it, no per-step re-check needed)
        # Returns True when a fast path consumed the plan (with its own
        # flight record); False → the caller's packed ragged launch runs.
        t0 = time.perf_counter()
        kind = None
        if (self.verify_fn is not None and seqs and self._spec_active()
                and all(s.sampling_tuple()[0] == 0.0 for s in seqs)
                and all(s.req.output_options.logprobs is None for s in seqs)
                and all(not s.req.sampling_options.logit_bias for s in seqs)
                and not any(_has_penalties(s) for s in seqs)
                # device-FSM constrained rows verify under per-position
                # masks (host oracle fallbacks still force single-step)
                and not any(_guided_host_only(s) for s in seqs)
                # a seq one token from its limit gains nothing from a draft
                and all((s.req.stop_conditions.max_tokens is None
                         or s.req.stop_conditions.max_tokens - s.generated >= 2)
                        for s in seqs)
                and await self._run_spec_decode(seqs)):
            kind = "spec"
        elif (self.multi_fn is not None and seqs
                # top-k capture and logit_bias need host-visible logits:
                # the burst keeps them on device, so those requests take
                # the single-step path
                and all(s.req.output_options.logprobs is None for s in seqs)
                and all(not s.req.sampling_options.logit_bias for s in seqs)
                and not any(_has_penalties(s) for s in seqs)
                # device-FSM rows mask + advance INSIDE the burst scan
                # (model.multi_decode fsm variant)
                and not any(_guided_host_only(s) for s in seqs)
                # NOTE a seq within K of max_tokens does NOT disqualify the
                # burst: its overshoot rows cost FLOPs on the batch dim, not
                # wall clock, while the old fallback cost EVERY stream K
                # single-step dispatch round trips whenever any one stream
                # was finishing — under continuous load, constantly
                and await self._run_multi_decode(seqs)):
            kind = "multi"
        if kind is None:
            return False
        wall = (time.perf_counter() - t0) * 1000
        self._flight_record(
            kind, wall, decode_rows=len(seqs),
            prefill_chunks=0, chunk_tokens=0,
            qos_mix=self._qos_mix_of(seqs),
            constrained=self._constrained_count(seqs),
            decode_seqs=seqs)
        return True

    # ------------------------------------------------- pipelined decode loop

    #: re-plan (admission, preemption, metrics) at least this often even
    #: when the pipeline could keep running — bounds how long a pipelined
    #: burst can defer scheduler housekeeping
    PIPELINE_REPLAN_STEPS = 64

    def _can_pipeline(self, seqs: list[SeqState]) -> bool:
        """True when the decode batch qualifies for the depth-2 pipelined
        loop: single-host, single-step decode, every running seq in the
        batch, and no request feature that forces a host round trip
        between sample and emit (logprob capture, logit edits, host-oracle
        guided fallbacks — device-FSM constrained rows ride the loop, the
        mask and state advance live inside the sampling dispatch)."""
        if not self.args.pipeline_decode or self._multihost or self._pp > 1:
            return False
        if self.multi_fn is not None or self.verify_fn is not None:
            return False
        # swapped seqs need plan() to run their swap-in admission promptly
        if (self.scheduler.waiting or self.scheduler.swapped
                or self.scheduler._aborted):
            return False
        # a running seq still mid-prefill needs plan() interleaving
        if len(seqs) != len(self.scheduler.running):
            return False
        for s in seqs:
            if (s.req.output_options.logprobs is not None
                    or s.req.sampling_options.logit_bias
                    or _has_penalties(s) or _guided_host_only(s)):
                return False
        return True

    def _dispatch_decode_step(self, seqs: list[SeqState], feed=None):
        """Dispatch ONE single-token decode step without any host sync.

        ``feed`` is the previous (uncommitted) step's handle: its sampled
        tokens are substituted into the token column ON DEVICE, so this
        dispatch never waits for the previous step's device→host copy.
        Positions/slots/tables only need token COUNTS, which the host knows
        before the token identities arrive. Returns a handle for
        _commit_decode_step, or None when block allocation fails (caller
        drains and falls back to plan(), which preempts).
        """
        import jax.numpy as jnp

        self._mark("build")
        args = self.args
        bs = args.block_size
        off = 1 if feed is not None else 0  # uncommitted in-flight tokens
        for s in seqs:
            # this step writes KV at position len(s.tokens)-1+off → the
            # table must cover len+off tokens
            if not self.scheduler._ensure_blocks(s, len(s.tokens) + off):
                return None
        # ragged layout: decode row i is the single packed token at
        # flat index i — the feed substitution lands on ints5[0, :n].
        # Token arrays size to the T bucket, row/sampling/table arrays
        # to the (statically derived, R <= T) row count — the hot loop
        # must not memset T-bucket-sized host buffers it never reads.
        B = args.bucket_ragged_tokens(len(seqs))
        R = args.ragged_rows(B)
        W = args.max_blocks_per_seq

        A = R  # per-row host array size
        tokens = np.zeros((A, 1), np.int32)
        positions = np.zeros((A, 1), np.int32)
        slot_map = np.zeros((A, 1), np.int32)
        bt = np.full((A, W), NULL_BLOCK, np.int32)
        kv_lens = np.zeros((A,), np.int32)
        temp = np.zeros((A,), np.float32)
        top_k = np.zeros((A,), np.int32)
        top_p = np.ones((A,), np.float32)
        seeds, steps = [], []
        for i, s in enumerate(seqs):
            pos = len(s.tokens) - 1 + off
            if feed is None:
                tokens[i, 0] = s.tokens[-1]
            positions[i, 0] = pos
            slot_map[i, 0] = s.block_table[pos // bs] * bs + pos % bs
            n = min(len(s.block_table), W)
            bt[i, :n] = s.block_table[:n]
            kv_lens[i] = pos + 1
            t, k, p, seed = s.sampling_tuple()
            temp[i], top_k[i], top_p[i] = t, k, p
            seeds.append(seed if seed is not None
                         else hash(s.request_id) & 0x7FFFFFFF)
            # step_idx increments at commit; an uncommitted in-flight token
            # shifts this step's PRNG index by one (identical to what the
            # serial loop would use)
            steps.append(s.step_idx + off)
        seeds += [0] * (A - len(seqs))
        steps += [0] * (A - len(seqs))
        keys = self._sampling.make_keys(seeds, steps)

        self.param_reads += 1
        from dynamo_tpu.engine.model import ragged_grid_shape

        C, _ = ragged_grid_shape(B)
        ints5 = np.zeros((5, B), np.int32)
        ints5[0, :R] = tokens[:, 0]
        ints5[1, :R] = positions[:, 0]
        ints5[2, :R] = slot_map[:, 0]
        ints5[3] = C  # every token is decode: grid dump tile
        rows3 = np.zeros((R, self._row_cols), np.int32)
        rows3[:len(seqs), 0] = np.arange(len(seqs))
        rows3[:len(seqs), 1] = 1
        rows3[:len(seqs), 2] = kv_lens[:len(seqs)]
        if self.kv.state is not None:
            rows3[:len(seqs), 3] = [s.state_slot for s in seqs]
        self._mark("put")
        ints5 = jnp.asarray(ints5)
        if feed is not None:
            ints5 = ints5.at[0, :len(seqs)].set(
                feed["toks"][:len(seqs)].astype(jnp.int32))
        new_sig = ("ragged_dec", B) not in self.compiled_signatures
        self.compiled_signatures.add(("ragged_dec", B))
        self.padded_tokens_total += B - len(seqs)
        t0 = time.perf_counter()  # where ``wall_ms`` has always begun
        on_device = (ints5, jnp.asarray(rows3), jnp.zeros((C,), jnp.int32),
                     jnp.asarray(bt))
        self._mark("dispatch")
        logits, self.kv.k, self.kv.v = self.ragged_dec_fn(
            self.params, *on_device, self.kv.k, self.kv.v)
        if new_sig:
            self._note_compile("ragged_dec", (B,),
                               time.perf_counter() - t0)
        self._mark("sample")
        states = None
        if any(_guided_fsm(s) is not None for s in seqs):
            # constrained rows: per-row FSM state is one more device-fed
            # column — step N+1 dispatches with step N's advanced states
            # exactly like the token column, so the constraint costs no
            # host sync anywhere in the loop
            if feed is not None:
                states = feed["states"]
            else:
                st = np.zeros((A,), np.int32)
                for i, s in enumerate(seqs):
                    c = _guided_fsm(s)
                    if c is not None:
                        st[i] = c.state
                states = jnp.asarray(st)
        if states is not None:
            mask_t, next_t = self.structured.device_tables()
            toks, logps, states = self._sampling.sample_masked_jit(
                logits, temp, top_k, top_p, keys, states, mask_t, next_t)
        else:
            toks, logps = self._sampling.sample_jit(logits, temp, top_k,
                                                    top_p, keys)
        # device→host copy in a worker thread: the loop dispatches step N+1
        # and only then awaits this
        copy = asyncio.get_running_loop().create_task(asyncio.to_thread(
            lambda: (np.asarray(toks), np.asarray(logps), self._landed())))
        return {"seqs": list(seqs), "toks": toks, "states": states,
                "copy": copy, "t0": t0}

    async def _commit_decode_step(self, handle) -> None:
        """Land one in-flight step: await its host copy, then commit + emit.
        Rows of sequences that finished at an earlier step are overshoot —
        their KV write targeted an unregistered block and is discarded."""
        self._mark("device_wait")
        toks, logps, stamp = await handle["copy"]
        self._resumed(stamp)
        n = 0
        constrained = 0
        for i, s in enumerate(handle["seqs"]):
            if s.finished is not None:
                continue
            self.scheduler.commit_computed(s, len(s.tokens))
            gs = _guided_fsm(s)
            if gs is not None:
                # host mirror of the on-device table advance (same table →
                # same state); lands before _deliver's check_finish reads
                # done/exhausted. O(1) numpy, never an oracle walk.
                gs.advance(int(toks[i]))
                constrained += 1
            self._deliver(s, int(toks[i]), float(logps[i]))
            n += 1
        self.pipelined_steps += 1
        wall = (time.perf_counter() - handle["t0"]) * 1000
        self._flight_record(
            "decode_pipe", wall, decode_rows=n, prefill_chunks=0,
            chunk_tokens=0, starved=0, constrained=constrained,
            decode_seqs=handle["seqs"])

    async def _run_decode_pipelined(self, seqs: list[SeqState]) -> bool:
        """Depth-2 software pipeline over single-step decode.

        Serial loop per token: dispatch → device compute → host copy →
        commit/emit. Pipelined: step N+1 is dispatched (token column fed
        device-to-device from step N's sampler output) BEFORE step N's host
        copy is awaited, so the copy + Python bookkeeping + sink emission of
        step N overlap step N+1's device time. Greedy-invariant: positions,
        PRNG step indices and commits are exactly the serial loop's.

        Drains (commits every in-flight step) and returns whenever the
        steady state breaks: a sequence finished or was cancelled, new work
        arrived, allocation failed, or PIPELINE_REPLAN_STEPS elapsed.
        Returns True when at least one step ran.
        """
        prev = None
        done = 0
        try:
            while True:
                handle = self._dispatch_decode_step(seqs, feed=prev)
                if handle is None:
                    break  # allocation failure: plan() handles preemption
                done += 1
                # swap BEFORE the await: if the commit raises, ``prev`` is
                # the still-in-flight dispatch the except path must reap
                committed, prev = prev, handle
                if committed is not None:
                    await self._commit_decode_step(committed)
                if (done >= self.PIPELINE_REPLAN_STEPS or self._closed
                        or self.scheduler.waiting or self.scheduler.swapped
                        or self.scheduler._aborted
                        or any(s.finished is not None for s in seqs)
                        or any(getattr(s.ctx, "cancelled", False)
                               for s in seqs)):
                    break
        except BaseException:
            # surface the step failure, but never abandon an in-flight host
            # copy task (its late exception would be unretrieved)
            if prev is not None:
                prev["copy"].cancel()
                try:
                    await prev["copy"]
                except (Exception, asyncio.CancelledError):
                    pass
            raise
        if prev is not None:
            await self._commit_decode_step(prev)
        # _run adds 1 per _execute; top up so self.steps counts every
        # committed pipelined step exactly once
        self.steps += max(0, done - 1)
        return done > 0

    async def _run_multi_decode(self, seqs: list[SeqState]) -> bool:
        """Burst path: K decode steps in one dispatch. Returns False when a
        precondition fails (block preallocation) so the caller falls back to
        single-step."""
        import jax.numpy as jnp

        self._mark("build")
        args = self.args
        K = args.multi_step_decode
        # the burst writes positions len-1 .. len+K-2 → len+K-1 slots
        if not self._prealloc_blocks(seqs, K - 1):
            return False

        # ragged-family signature: the row bucket derives from the token
        # bucket (one token per row) and the table width is static, so the
        # burst adds no (B × W) lattice of its own
        B = args.ragged_rows(args.bucket_ragged_tokens(len(seqs)))
        W = args.max_blocks_per_seq

        last_tokens = np.zeros((B,), np.int32)
        positions = np.zeros((B,), np.int32)
        bt = np.full((B, W), NULL_BLOCK, np.int32)
        kv_lens = np.zeros((B,), np.int32)
        temp = np.zeros((B,), np.float32)
        top_k = np.zeros((B,), np.int32)
        top_p = np.ones((B,), np.float32)
        seeds = np.zeros((B,), np.uint32)
        step0 = np.zeros((B,), np.uint32)
        for i, s in enumerate(seqs):
            last_tokens[i] = s.tokens[-1]
            positions[i] = len(s.tokens) - 1
            n = min(len(s.block_table), W)
            bt[i, :n] = s.block_table[:n]
            kv_lens[i] = len(s.tokens)
            t, k, p, seed = s.sampling_tuple()
            temp[i], top_k[i], top_p[i] = t, k, p
            seeds[i] = (seed if seed is not None
                        else hash(s.request_id) & 0x7FFFFFFF) & 0xFFFFFFFF
            step0[i] = s.step_idx & 0xFFFFFFFF

        # packed operands: 4 transfers per K-token burst instead of 9
        ints = np.stack([last_tokens, positions, kv_lens, top_k], axis=1)
        floats = np.stack([temp, top_p], axis=1)
        rand = np.stack([seeds, step0], axis=1)
        cursors = [_guided_fsm(s) for s in seqs]
        use_fsm = any(c is not None for c in cursors)
        kind = "multi_fsm" if use_fsm else "multi"
        new_sig = (kind, B) not in self.compiled_signatures
        self.compiled_signatures.add((kind, B))
        self.padded_tokens_total += (B - len(seqs)) * K
        self._mark("dispatch")
        self._broadcast("multi", ints=ints, floats=floats, rand=rand,
                        block_tables=bt)
        self.param_reads += K
        t0c = time.perf_counter() if new_sig else 0.0
        if use_fsm:
            # constrained rows: per-row FSM state rides the burst scan —
            # masked sampling + table advance on device each of the K
            # steps (free rows carry the arena's identity state 0)
            import jax.numpy as _jnp
            if self._multi_fsm_fn is None:
                from dynamo_tpu.engine import model as M
                self._multi_fsm_fn = M.make_multi_decode_fn(
                    self.cfg, args.block_size, K, self.mesh,
                    use_pallas=args.use_pallas_attention,
                    replicate_outputs=self._multihost,
                    kv_quant=self._kv_quant, fsm=True)
            states = np.zeros((B,), np.int32)
            for i, c in enumerate(cursors):
                if c is not None:
                    states[i] = c.state
            mask_t, next_t = self.structured.device_tables()
            toks, logps, self.kv.k, self.kv.v = self._multi_fsm_fn(
                self.params, self._put_batch("ints", ints),
                self._put_batch("floats", floats),
                self._put_batch("rand", rand),
                self._put_batch("block_tables", bt),
                _jnp.asarray(states), mask_t, next_t,
                self.kv.k, self.kv.v)
        else:
            toks, logps, self.kv.k, self.kv.v = self.multi_fn(
                self.params, self._put_batch("ints", ints),
                self._put_batch("floats", floats),
                self._put_batch("rand", rand),
                self._put_batch("block_tables", bt),
                self.kv.k, self.kv.v)
        if new_sig:
            self._note_compile(kind, (B,), time.perf_counter() - t0c)
        self._mark("device_wait")
        toks, logps, stamp = await asyncio.to_thread(
            lambda: (np.asarray(toks), np.asarray(logps), self._landed()))
        self._resumed(stamp)

        for i, s in enumerate(seqs):
            # one coalesced output per seq per burst (overshoot discarded)
            self._deliver_batch(s, toks[:, i], logps[:, i])
        return True

    # ------------------------------------------------------------ sampling


    def _put_batch(self, name: str, arr):
        """Host batch array → device array; under a multi-host mesh the
        array becomes a GLOBAL array (batch axis on "dp", replicated when
        dp=1) so every rank's jitted call sees identical operands."""
        import jax.numpy as jnp

        if not self._multihost:
            return jnp.asarray(arr)
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dynamo_tpu.parallel.multihost import global_put

        a = np.asarray(arr)
        spec = P(*(["dp"] + [None] * (a.ndim - 1)))
        return global_put(a, NamedSharding(self.mesh, spec))

    def _broadcast(self, kind: str, **arrays) -> None:
        if self.broadcast_cb is not None:
            self.broadcast_cb(kind, arrays)

    async def _sample(self, seqs: list[SeqState], logits, rows=None):
        """Sample one token per seq from padded logits [B>=len(seqs), V].

        ``rows`` (multi-host batched prefill): bucket-padded row indices to
        gather from ``logits`` host-side, inside the worker thread — the
        sync must stay off the event loop, and the gather must be local
        (never a device op on the replicated global array).

        Returns (tokens, logps, tops) — ``tops[i]`` is the row's top-k
        [token_id, logprob] alternatives when seq i requested logprobs
        (ref surface: perf/logprobs.rs TokenLogProbs), else absent.
        """
        self._mark("build")
        B = len(rows) if rows is not None else logits.shape[0]
        temp = np.zeros((B,), np.float32)
        top_k = np.zeros((B,), np.int32)
        top_p = np.ones((B,), np.float32)
        seeds, steps = [], []
        want_tops: dict[int, int] = {}
        for i, s in enumerate(seqs):
            t, k, p, seed = s.sampling_tuple()
            temp[i], top_k[i], top_p[i] = t, k, p
            seeds.append(seed if seed is not None else hash(s.request_id) & 0x7FFFFFFF)
            steps.append(s.step_idx)
            n = s.req.output_options.logprobs
            if n is not None:  # 0 still captures the selected token's entry
                want_tops[i] = max(1, min(int(n), 20))
        seeds += [0] * (B - len(seqs))
        steps += [0] * (B - len(seqs))
        keys = self._sampling.make_keys(seeds, steps)

        V = logits.shape[-1]

        def build_triples():
            # sparse logit edits — at most a few hundred entries per row,
            # never a dense [B, V] materialization. Built in the worker
            # thread: the per-seq history scans (Counter over generated
            # tokens, set over the full sequence) are O(context) and must
            # not run on the event loop. seqs are not mutated while a step
            # is in flight (the engine loop delivers only after _sample).
            b_rows, b_cols, b_vals = [], [], []  # additive: bias + penalties
            # repetition penalty is multiplicative read-modify-write (HF
            # semantics: logit>0 -> /p else *p, over prompt+generated), so
            # it gets its own triples, applied BEFORE the additive terms
            r_rows, r_cols, r_pens = [], [], []
            for i, s in enumerate(seqs):
                so = s.req.sampling_options
                for tid, v in (so.logit_bias or {}).items():
                    t = int(tid)
                    if 0 <= t < V:
                        b_rows.append(i)
                        b_cols.append(t)
                        b_vals.append(v)
                pres = so.presence_penalty or 0.0
                freq = so.frequency_penalty or 0.0
                rep = so.repetition_penalty
                rep_on = rep is not None and rep > 0 and rep != 1.0
                if pres or freq or rep_on:
                    # fold new history incrementally (ngram_pos pattern):
                    # O(new tokens) per step, not O(context)
                    for j in range(s.pen_indexed, len(s.tokens)):
                        t = s.tokens[j]
                        s.seen_tokens.add(t)
                        if j >= s.prompt_len:
                            s.gen_counts[t] = s.gen_counts.get(t, 0) + 1
                    s.pen_indexed = len(s.tokens)
                if pres or freq:
                    # OpenAI semantics: counted over the GENERATED text
                    # only — rides the same sparse scatter-add as logit_bias
                    for tid, cnt in s.gen_counts.items():
                        if 0 <= tid < V:
                            b_rows.append(i)
                            b_cols.append(int(tid))
                            b_vals.append(-(pres + freq * cnt))
                if rep_on:
                    for tid in s.seen_tokens:
                        if 0 <= tid < V:
                            r_rows.append(i)
                            r_cols.append(int(tid))
                            r_pens.append(float(rep))
            # guided decoding: rows whose logits are masked to the
            # constraint's allowed set (allowed() walks the vocab once per
            # NEW dfa state — here in the worker thread, cached after)
            def g_allowed(s):
                ids = s.guided_state.allowed_token_ids(V)
                if (s.req.stop_conditions.min_tokens or 0) > s.generated:
                    # min_tokens: suppress EOS from the allowed set (the
                    # unguided path gates EOS the same way) — unless EOS is
                    # all the constraint has left, where stopping beats an
                    # all-masked step
                    non_eos = [t for t in ids
                               if t not in s.guided_state.eos_ids]
                    if non_eos:
                        return non_eos
                return ids
            # host-oracle guided rows mask via sparse host logit edits;
            # device-FSM rows (FsmCursor) mask inside the fused sampling
            # dispatch below. Logprob capture is the exception: top-k must
            # read the SAME masked logits the sampler saw, so those rows
            # fall back to the host edit too.
            g_rows = [(i, g_allowed(s)) for i, s in enumerate(seqs)
                      if _guided_host_only(s)
                      or (want_tops and _guided_fsm(s) is not None)]
            fsm_rows = ([] if want_tops else
                        [(i, c) for i, s in enumerate(seqs)
                         if (c := _guided_fsm(s)) is not None])
            return (b_rows, b_cols, b_vals, r_rows, r_cols, r_pens, g_rows,
                    fsm_rows)

        def run_sampling():
            # runs in a worker thread: the host sync below must NEVER block
            # the event loop — under multi-host it waits on a collective the
            # FOLLOWER ranks can only join after the loop's broadcaster task
            # flushed the step (blocking the loop here deadlocked the fleet)
            (b_rows, b_cols, b_vals, r_rows, r_cols, r_pens,
             g_rows, fsm_rows) = build_triples()
            lg = logits
            if self._multihost or isinstance(lg, np.ndarray):
                # logits are fully replicated (replicate_logits): round-trip
                # through host so sampling is a LOCAL computation — a global
                # op here would have to be mirrored by every follower rank
                # (this includes the penalty/bias edits below: numpy, never
                # a device op on the global array). Device-FSM rows mask
                # host-side here too — bit-unpack of the table row, same
                # allowed set as the fused gather.
                lg = np.asarray(lg)
                if rows is not None:
                    lg = lg[np.asarray(rows)]  # fancy index: fresh, writable
                elif r_rows or b_rows or g_rows or fsm_rows:
                    lg = lg.copy()
                if r_rows:
                    v = lg[r_rows, r_cols]
                    rp = np.asarray(r_pens, lg.dtype)
                    lg[r_rows, r_cols] = np.where(v > 0, v / rp, v * rp)
                if b_rows:
                    np.add.at(lg, (b_rows, b_cols), b_vals)
                for i, allowed in (g_rows
                                   + [(i, c.allowed_token_ids(V))
                                      for i, c in fsm_rows]):
                    masked = np.full((lg.shape[-1],), -1e30, lg.dtype)
                    if allowed:
                        ai = np.asarray(allowed)
                        masked[ai] = lg[i, ai]
                    lg[i] = masked
                fsm_rows = []
            elif r_rows or b_rows or g_rows:
                # single-host: tiny device gather/scatter
                import jax.numpy as jnp

                if r_rows:
                    rr = jnp.asarray(r_rows)
                    rc = jnp.asarray(r_cols)
                    rp = jnp.asarray(r_pens, lg.dtype)
                    v = lg[rr, rc]
                    lg = lg.at[rr, rc].set(jnp.where(v > 0, v / rp, v * rp))
                if b_rows:
                    lg = lg.at[jnp.asarray(b_rows), jnp.asarray(b_cols)].add(
                        jnp.asarray(b_vals, lg.dtype))
                for i, allowed in g_rows:
                    masked = jnp.full((lg.shape[-1],), -1e30, lg.dtype)
                    if allowed:
                        ai = jnp.asarray(allowed)
                        masked = masked.at[ai].set(lg[i, ai])
                    lg = lg.at[i].set(masked)
            if fsm_rows:
                # fused constrained sampling: the FSM mask is a packed-
                # bitmask gather INSIDE the jitted dispatch — no host
                # materialization, no per-row Python (docs/structured.md)
                import jax.numpy as jnp

                states = np.zeros((B,), np.int32)
                for i, c in fsm_rows:
                    states[i] = c.state
                mask_t, next_t = self.structured.device_tables()
                toks, logps, _ = self._sampling.sample_masked_jit(
                    lg, temp, top_k, top_p, keys, jnp.asarray(states),
                    mask_t, next_t)
            else:
                toks, logps = self._sampling.sample_jit(lg, temp, top_k,
                                                        top_p, keys)
            top_res = None
            if want_tops:
                # device-side top-k: only O(B·k) crosses to host, and the
                # selected logprob comes from the same log_softmax as its
                # alternatives (an ulp disagreement would read as a fake
                # near-tie). Always the k=20 kernel — one XLA compile ever,
                # sliced per row below
                top_res = self._sampling.make_topk_logprobs_fn(20)(lg, toks)
            t, l = np.asarray(toks), np.asarray(logps)
            for gi, gs in enumerate(seqs):
                if gs.guided_state is not None:
                    # advance here, in the worker thread: a newly-visited
                    # DFA state triggers an O(vocab) walk that must stay
                    # off the event loop (_deliver does not advance)
                    gs.guided_state.advance(int(t[gi]))
            tops: dict[int, list[list]] = {}
            if top_res is not None:
                ids, vals, sel = (np.asarray(x) for x in top_res)
                l = l.copy()
                for i, n in want_tops.items():
                    tops[i] = [[int(j), float(v)]
                               for j, v in zip(ids[i, :n], vals[i, :n])]
                    l[i] = sel[i]
            return t, l, tops, self._landed()

        # everything run_sampling does (host logit edits, the sampler's
        # call, the top-k read) is the worker thread's time: the serving
        # thread is in ``device_wait`` up to the stamp it returns
        self._mark("device_wait")
        t, l, tops, stamp = await asyncio.to_thread(run_sampling)
        self._resumed(stamp)
        return t, l, tops

    def _deliver_batch(self, seq: SeqState, tokens, logps) -> int:
        """Coalesced per-step emission: commit/append each token of a fused
        burst, but put ONE LLMEngineOutput on the sink for the whole step —
        one queue item → one detokenizer iteration → one SSE write instead
        of K of each. Tokens past a finish are discarded (overshoot rows).
        Returns the number of tokens actually delivered."""
        ids: list[int] = []
        lps: list[float] = []
        reason = None
        gs = seq.guided_state
        for t, lp in zip(tokens, logps):
            self.scheduler.commit_computed(seq, len(seq.tokens))
            self.scheduler.append_token(seq, int(t))
            ids.append(int(t))
            lps.append(float(lp))
            if gs is not None:
                # device-FSM cursor: one numpy table lookup — must land
                # before check_finish reads done/exhausted (only device
                # rows reach the fused paths, so this is never an
                # O(vocab) oracle walk on the event loop)
                gs.advance(int(t))
            reason = self.scheduler.check_finish(seq, int(t))
            if reason is not None:
                break
        if not ids:
            return 0
        if reason is not None:
            self.scheduler.finish(seq, reason)
        seq.sink.put_nowait(LLMEngineOutput(token_ids=ids, log_probs=lps,
                                            finish_reason=reason))
        if reason is not None:
            seq.sink.put_nowait(None)
        return len(ids)

    def _deliver(self, seq: SeqState, token: int, logp: float,
                 top: Optional[list] = None) -> None:
        self.scheduler.append_token(seq, token)
        reason = self.scheduler.check_finish(seq, token)
        out = LLMEngineOutput(token_ids=[token], log_probs=[logp],
                              top_logprobs=[top] if top is not None else None,
                              finish_reason=reason)
        if reason is not None:
            self.scheduler.finish(seq, reason)
        seq.sink.put_nowait(out)
        if reason is not None:
            seq.sink.put_nowait(None)

    # ------------------------------------------------------------- events

    def _on_stored(self, parent_hash, blocks: list[StoredBlock],
                   block_ids: Optional[list[int]] = None) -> None:
        if self.event_cb:
            self.event_cb(KvCacheEvent.stored(next(self._event_id), parent_hash, blocks))
        if self.kvbm is not None and block_ids:
            hashes = [b.block_hash for b in blocks]
            fresh = [(h, bid) for h, bid in zip(hashes, block_ids)
                     if h not in self.kvbm]
            if fresh:
                self._spawn_offload([h for h, _ in fresh],
                                    [bid for _, bid in fresh])

    # ----------------------------------------------------- KVBM offload/onboard

    def _spawn_remote_fetch(self, hashes: list) -> None:
        """G4→G2: pull prefix blocks held by PEER workers into the local
        host tier (distributed KVBM — ref: block_manager/distributed/
        leader.rs cross-worker onboarding). Same discipline as the disk
        promotion: the admission path never blocks on the network; the next
        admission of the prefix onboards from host."""
        if getattr(self, "_remote_fetching", None) is None:
            self._remote_fetching = set()
        todo = [h for h in hashes if h not in self._remote_fetching]
        if not todo:
            return
        self._remote_fetching.update(todo)

        async def run():
            try:
                await self.kvbm_remote.fetch_into_host(todo)
            except Exception:
                logger.exception("KVBM remote fetch failed")
            finally:
                self._remote_fetching.difference_update(todo)

        task = asyncio.get_running_loop().create_task(run())
        self._offload_tasks.add(task)
        task.add_done_callback(self._offload_tasks.discard)

    def _spawn_promote(self, hashes: list) -> None:
        """G3→G2 in a worker thread (np.load off the event loop)."""
        if getattr(self, "_promoting", None) is None:
            self._promoting = set()
        todo = [h for h in hashes if h not in self._promoting]
        if not todo:
            return
        self._promoting.update(todo)

        async def run():
            try:
                # reverse order: if the host tier can't hold the whole run,
                # it must end up holding the EARLIEST blocks — a prefix is
                # only usable from its first block
                for h in reversed(todo):
                    await asyncio.to_thread(self.kvbm.get, h)  # get() promotes
            except Exception:
                logger.exception("KVBM disk promotion failed")
            finally:
                self._promoting.difference_update(todo)

        task = asyncio.get_running_loop().create_task(run())
        self._offload_tasks.add(task)
        task.add_done_callback(self._offload_tasks.discard)

    def _spawn_offload(self, seq_hashes: list, block_ids: list[int]) -> None:
        """G1→G2: pin the blocks, gather their pages once, park on host."""
        self.pool.acquire(block_ids)
        task = asyncio.get_running_loop().create_task(
            self._offload(seq_hashes, block_ids))
        self._offload_tasks.add(task)
        task.add_done_callback(self._offload_tasks.discard)

    async def _offload(self, seq_hashes: list, block_ids: list[int]) -> None:
        try:
            kb, vb = self.kv.gather(block_ids)

            def work():  # host transfer + tier writes off the event loop
                pairs = self.kv.to_host_blocks(kb, vb, len(seq_hashes))
                for h, (k, v) in zip(seq_hashes, pairs):
                    self.kvbm.put(h, k, v)

            await asyncio.to_thread(work)
        except Exception:
            logger.exception("KVBM offload failed")
        finally:
            self.pool.release(block_ids)

    def _note_hot_prefix(self, probe, n: int) -> None:
        """Scheduler prefix-HIT hook (G4 flow-up, docs/performance.md):
        count repeat hits per block; leading runs whose blocks cross
        DYN_G4_PUBLISH_HITS are pushed up to the G4 object store so the
        whole fleet — including cold-started workers — can warm from
        them. Hits arrive leading-run-shaped, so a block's ancestors
        always cross the threshold no later than it does and the G4
        radix chain stays root-anchored."""
        if (self._g4_publish_hits <= 0 or self.kvbm is None
                or self.kvbm.remote is None):
            return
        hashes = probe.sequence_hashes()[:n]
        if len(self._prefix_hits) > (1 << 16):
            # bounded popularity state: drop the oldest half (dict order =
            # insertion order; hot prefixes re-earn their counts quickly)
            for h in list(itertools.islice(self._prefix_hits, 1 << 15)):
                del self._prefix_hits[h]
        todo = []
        for h in hashes:
            c = self._prefix_hits.get(h, 0) + 1
            self._prefix_hits[h] = c
            if c >= self._g4_publish_hits and h not in self._g4_publishing:
                todo.append(h)
        if not todo:
            return
        self._g4_publishing.update(todo)

        async def run():
            try:
                # tier reads + object-store writes off the event loop, in
                # prefix order (parents first — the announcer's chain
                # rule). The thread only READS engine state; _prefix_hits
                # is mutated exclusively on the loop (below), so the trim
                # above can never race a cross-thread pop.
                def work():
                    already = self.kvbm.remote_resident(todo)
                    missed, queued = [], 0
                    for h in todo:
                        if h in already:
                            continue  # LRU-touched; no byte read needed
                        e = self.kvbm.get_local(h)
                        if e is None:
                            missed.append(h)
                            continue
                        if self.kvbm.publish_remote(h, e[0], e[1],
                                                    drain=False):
                            # drain every 16 queued writes: one drain
                            # cycle per batch, bounded payload residency
                            # in the op queue
                            queued += 1
                            if queued % 16 == 0:
                                self.kvbm.drain_remote()
                    if queued % 16:
                        self.kvbm.drain_remote()
                    return missed

                for h in await asyncio.to_thread(work):
                    # device-only so far (the G1→G2 offload is still in
                    # flight): forget the threshold crossing so the NEXT
                    # hit retries once the bytes reach a tier
                    self._prefix_hits.pop(h, None)
            except Exception:
                logger.exception("G4 prefix flow-up failed")
            finally:
                self._g4_publishing.difference_update(todo)

        task = asyncio.get_running_loop().create_task(run())
        self._offload_tasks.add(task)
        task.add_done_callback(self._offload_tasks.discard)

    async def onboard_remote(self, probe, start: int, end: int) -> int:
        """G4 → host → device warmup at admission (routine onboarding's
        cold-start path, docs/performance.md): fetch the leading run of
        ``probe``'s missing blocks [start, end) out of the fleet-global
        object store into the host tier (worker thread — blocking plane
        I/O), then scatter/register them like any KVBM onboard. The
        attached blocks park in the LRU (refcount 0) for the subsequent
        generate()'s prefix match, so a failure leaks nothing. Returns
        blocks attached."""
        if self.kvbm is None or self.kvbm.remote is None or end <= start:
            return 0
        hashes = probe.sequence_hashes()[start:end]
        landed = await asyncio.to_thread(self.kvbm.fetch_remote, hashes)
        if not landed:
            return 0
        ids = self._onboard(probe, start, start + landed)
        if not ids:
            return 0
        self.pool.release(ids)
        return len(ids)

    def _onboard(self, probe, start: int, end: int) -> list[int]:
        """G2→G1 at admission: missing prefix blocks found in the HOST tier
        are scattered into fresh device blocks (synchronous — it replaces a
        much more expensive recompute). Disk-resident blocks are NOT read
        here — np.load inside plan() would stall every in-flight decode —
        instead a background promotion pulls them G3→G2 so the next
        admission of the prefix hits host."""
        hashes = probe.sequence_hashes()[start:end]
        ks, vs = [], []
        for i, h in enumerate(hashes):
            e = self.kvbm.get_host(h)
            if e is None:
                if self.kvbm.in_lower_tier(h):  # G3 disk or G4 remote
                    self._spawn_promote(hashes[i:])
                elif self.kvbm_remote is not None:
                    self._spawn_remote_fetch(hashes[i:])
                break
            ks.append(e[0])
            vs.append(e[1])
        if not ks:
            return []
        ids = self._scatter_register(probe, start, ks, vs)
        if ids is None:
            return []
        self.kvbm.onboarded_blocks += len(ks)
        return ids

    # ------------------------------------------------------ preempt-to-swap
    #
    # The scheduler's swapper backend: under KV pressure a victim's device
    # pages move to host DRAM (swap_out) and return before its next planned
    # step (swap_in) instead of being recomputed from scratch. Bundles ride
    # the SAME formats the G2 tier and the disagg wire use — value arrays
    # for plain caches, packed (q, s) uint8 for int8 caches — so the
    # round-trip is bit-exact by construction for both.

    def swap_out(self, seq: SeqState) -> bool:
        """Stage ``seq``'s computed KV on host; True = the scheduler may
        release its device blocks and park it in the swapped queue.

        The gathers are dispatched HERE, synchronously, against the current
        immutable cache arrays — device program order guarantees they read
        the pages before any later step reuses the slots, so the blocks are
        free for reallocation the moment this returns (same capacity
        timing as recompute preemption). Only the device→host copy runs
        async, overlapped with the next steps exactly like _spawn_offload.
        """
        bs = self.args.block_size
        n = (seq.num_computed + bs - 1) // bs  # blocks holding computed KV
        if n <= 0 or n > len(seq.block_table):
            return False
        nbytes = n * self.kv.host_block_nbytes
        if not self._swap.reserve(nbytes):
            return False  # host budget exhausted → recompute fallback
        entry = _SwapEntry(n, nbytes)
        try:
            kb, vb = self.kv.gather(seq.block_table[:n])
        except Exception:
            logger.exception("swap-out gather dispatch failed for %s",
                             seq.request_id)
            self._swap.release(nbytes)
            return False
        seq.swap = entry
        self.swap_out_blocks += n
        self.pool.note_swapped_out(n)

        async def copy():
            try:
                entry.k, entry.v = await asyncio.to_thread(
                    self.kv.to_host, kb, vb, n)
                entry.ready = True
            except Exception:
                logger.exception("swap-out host copy failed for %s",
                                 seq.request_id)
                entry.failed = True
                self._swap_free(entry)
            finally:
                if entry.dropped:
                    self._swap_free(entry)
                self._wake.set()  # a ready bundle can unblock plan()

        task = asyncio.get_running_loop().create_task(copy())
        self._offload_tasks.add(task)
        task.add_done_callback(self._offload_tasks.discard)
        return True

    def swap_status(self, seq: SeqState) -> str:
        entry = seq.swap
        if entry is None or entry.failed or entry.freed:
            return "failed"
        return "ready" if entry.ready else "pending"

    def swap_in(self, seq: SeqState) -> bool:
        """Scatter the host bundle back into the freshly allocated block
        table. No host sync needed: the scatter produces the new cache
        arrays the next jitted step consumes, so device data dependencies
        order it before any read of those pages."""
        entry: _SwapEntry = seq.swap
        if (entry is None or not entry.ready or entry.failed or entry.freed
                or len(seq.block_table) < entry.n):
            return False
        try:
            self.kv.scatter(seq.block_table[:entry.n], entry.k, entry.v)
        except Exception:
            logger.exception("swap-in scatter failed for %s", seq.request_id)
            entry.failed = True
            self.pool.note_swapped_in(entry.n)
            self._swap_free(entry)
            seq.swap = None
            return False
        self.swap_in_blocks += entry.n
        self.pool.note_swapped_in(entry.n)
        self._swap_free(entry)
        seq.swap = None
        # re-register the returning full blocks so the prefix cache serves
        # them again; fresh registrations (hash no longer resident via the
        # LRU) are re-announced so the router's radix view heals
        stored: list[StoredBlock] = []
        stored_ids: list[int] = []
        parent = None
        for i in range(min(seq.num_registered_blocks, entry.n)):
            blk = seq.hashes.blocks[i]
            if self.pool.register(seq.block_table[i], blk.sequence_hash,
                                  blk.block_hash, blk.parent_sequence_hash):
                if not stored:
                    parent = blk.parent_sequence_hash
                stored.append(StoredBlock(block_hash=blk.sequence_hash,
                                          tokens_hash=blk.block_hash))
                stored_ids.append(seq.block_table[i])
        if stored:
            self._on_stored(parent, stored, stored_ids)
        return True

    def swap_drop(self, seq: SeqState) -> None:
        """Cancel-safe teardown: free the bundle + budget (or mark the
        in-flight copy to free itself on completion)."""
        entry: _SwapEntry = seq.swap
        if entry is None:
            return
        seq.swap = None
        entry.dropped = True
        self.pool.note_swapped_in(entry.n)
        if entry.ready or entry.failed:
            self._swap_free(entry)

    def _swap_free(self, entry: "_SwapEntry") -> None:
        if entry.freed:
            return
        entry.freed = True
        entry.k = entry.v = None
        self._swap.release(entry.nbytes)

    def swap_stats(self) -> dict:
        """Telemetry for /metrics (engine/main.py gauge/counter callbacks)."""
        sched = self.scheduler
        return {
            "swap_out_blocks": self.swap_out_blocks,
            "swap_in_blocks": self.swap_in_blocks,
            "preempt_swap": sched.preempt_swap_total,
            "preempt_recompute": sched.preempt_recompute_total,
            "swap_in_seqs": sched.swap_in_total,
            "recomputed_tokens": sched.recomputed_tokens_total,
            "swapped_seqs": len(sched.swapped),
            "swapped_blocks": self.pool.swapped_blocks,
            "swap_host_bytes": self._swap.used if self._swap else 0,
            "swap_host_budget": self._swap.budget if self._swap else 0,
            "swap_in_blocked": sched.swap_in_blocked_total,
        }

    def qos_stats(self) -> dict:
        """Per-(tenant, class) QoS telemetry: served tokens, queue wait,
        preemptions (→ dynamo_tenant_* metrics, engine/main.py)."""
        return self.scheduler.qos.snapshot()

    def _place_params(self, params):
        """Quantize (on the host) and shard a caller-supplied param tree."""
        import jax
        from dynamo_tpu.engine import model as M

        if self.args.quantization is not None:
            from dynamo_tpu.engine.quant import quantize_params
            # host-side quantization (numpy): the bf16 original never has
            # to coexist with the quantized copy in HBM. Idempotent —
            # leaves already quantized at load (MXFP4/GGUF) pass through
            params = quantize_params(
                jax.tree.map(np.asarray, params), self.args.quantization)
            if self.mesh is None:
                # the host-side walk left every leaf as numpy; put the tree
                # back on device or each jitted step re-uploads it
                params = jax.device_put(params)
        if self.mesh is not None:
            from dynamo_tpu.engine.quant import quant_shardings
            sh = M.param_shardings(self.cfg, self.mesh)
            # no-op on unquantized trees; mirrors weight shardings onto
            # QTensor subtrees (q like the weight, scales' group dim
            # replicated) for load-time-quantized checkpoints too
            sh = quant_shardings(sh, params)
            if self._multihost:
                from dynamo_tpu.parallel.multihost import global_put
                params = jax.tree.map(global_put, params, sh)
            else:
                params = jax.device_put(params, sh)
        return params

    def _on_removed(self, seq_hashes) -> None:
        if self.event_cb is None:
            return
        if seq_hashes is None:
            self.event_cb(KvCacheEvent.clear(next(self._event_id)))
            return
        # fleet-wide KV hierarchy (docs/robustness.md): a device eviction
        # whose block survives in this worker's G2/G3 tiers is NOT gone —
        # admission onboards it back and restore pulls serve it
        # (export_blocks reads exactly host+disk) — so it must stay in
        # the global radix index. The removed event fires only when the
        # last LOCALLY-SERVABLE copy dies (here, or via the KVBM bridge
        # below when the tiers finally evict it). A G4-only block does
        # NOT suppress the removal: the remote index is not servable by
        # kv_pull, and advertising it would burn peers' pull attempts.
        if self.kvbm is not None:
            seq_hashes = self.kvbm.filter_not_local(seq_hashes)
        if seq_hashes:
            self.event_cb(KvCacheEvent.removed(next(self._event_id), list(seq_hashes)))

    def _on_kvbm_change(self, stored, removed) -> None:
        """KvbmManager.on_change bridge: when a hash leaves the LAST KVBM
        tier and is not device-resident either, announce the removal to
        the router — without this the radix would keep advertising KV
        this worker can no longer serve (stale restore sources / inflated
        overlap). Stored hashes need no event: blocks enter the tiers
        from the device (offload), which already announced them.

        Known G4 edge: on_change reports removal only when a hash leaves
        EVERY tier, so a block cascading G3→G4 keeps its radix entry
        until the G4 copy dies even though kv_pull cannot serve it (the
        distributed-KVBM fetch endpoint can, which is why the manager's
        contract is all-tiers). Cost: a peer's restore wastes one pull
        attempt and fails over; bounded, and only with G4 armed.

        Fired under the manager lock, possibly from an offload worker
        thread — publishing hops onto the engine's loop when needed
        (the event task machinery is loop-affine)."""
        if self.event_cb is None or not removed:
            return

        def emit():
            gone = [h for h in removed if self.pool.lookup(h) is None]
            if gone and self.event_cb is not None:
                self.event_cb(KvCacheEvent.removed(next(self._event_id),
                                                   gone))

        try:
            asyncio.get_running_loop()
        except RuntimeError:
            loop = self._loop_ref
            if loop is not None and not loop.is_closed():
                loop.call_soon_threadsafe(emit)
            return
        emit()

    def _metrics(self) -> ForwardPassMetrics:
        from dynamo_tpu.engine.model import MOE_DROPS

        sched = self.scheduler
        active = self.pool.num_active_blocks
        return ForwardPassMetrics(
            worker_stats=WorkerStats(
                request_active_slots=len(sched.running),
                request_total_slots=self.args.max_num_seqs,
                # swapped seqs count as waiting load: they hold no device
                # blocks but WILL reclaim capacity before new admissions
                num_requests_waiting=sched.num_waiting() + len(sched.swapped),
                data_parallel_rank=self.dp_rank,
                moe_dropped_tokens=MOE_DROPS["total"],
                # cold = warmup was requested but skipped (multi-host) and
                # no real step has compiled yet; workers that never asked
                # for warmup report None (legacy semantics: counted warm)
                warmed_up=(None if not self.warmup_requested
                           else not self.warmup_skipped or self.steps > 0),
            ),
            kv_stats=KvStats(
                kv_active_blocks=active,
                kv_total_blocks=self.num_blocks - 1,
                gpu_cache_usage_perc=self.pool.usage(),
                gpu_prefix_cache_hit_rate=(
                    sched.prefix_hit_tokens / sched.prefix_query_tokens
                    if sched.prefix_query_tokens else 0.0),
            ),
            spec_decode_stats=(self.spec_stats
                               if self.spec_stats.num_drafts else None),
        )


class _NullCtx:
    cancelled = False
    id = "local"
