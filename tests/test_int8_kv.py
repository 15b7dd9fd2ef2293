"""int8 paged KV cache: quantization numerics, attention parity across every
read path (XLA gather, Pallas decode interpret, flash prefill paged), the
gather/scatter bit-determinism contract KVBM/disagg rely on, capacity
sizing, and e2e engine serving.

Mirrors the KV-capacity role of the reference's G1 tier (ref:
lib/llm/src/block_manager/) — the reference gets KV compression from
engine-side fp8 KV caches (vllm flags pass through); here int8 pages are a
first-class cache layout (ops/kv_pages.py).
"""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine.cache import allocate_device_cache, hbm_sized_num_blocks
from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.ops.kv_pages import (
    cache_shape, dequantize_kv, is_quant_cache, quantize_kv,
)

pytestmark = pytest.mark.anyio


# ------------------------------------------------------------------ numerics

def test_quantize_roundtrip_is_exact():
    """dequant → requant must reproduce identical (q, s): the contract that
    keeps KVBM offload→onboard and disagg transfer bit-deterministic."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((64, 4, 32)).astype(np.float32) * 3.0
    q, s = quantize_kv(x)
    assert q.dtype == np.int8 and s.dtype == np.float32
    deq = dequantize_kv(q, s)
    q2, s2 = quantize_kv(deq)
    np.testing.assert_array_equal(q, q2)
    np.testing.assert_array_equal(s, s2)


def test_quantize_error_bounded():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((16, 2, 64)).astype(np.float32)
    q, s = quantize_kv(x)
    err = np.abs(dequantize_kv(q, s) - x)
    # symmetric int8: error ≤ s/2 per element
    assert np.all(err <= s[..., None] / 2 + 1e-7)


def test_quantize_zero_block():
    q, s = quantize_kv(np.zeros((4, 2, 8), np.float32))
    assert np.all(q == 0)
    deq = dequantize_kv(q, s)
    assert np.all(deq == 0)


def test_jnp_and_np_quantize_agree():
    import jax.numpy as jnp

    rng = np.random.default_rng(2)
    x = rng.standard_normal((8, 2, 16)).astype(np.float32)
    qn, sn = quantize_kv(x)
    qj, sj = quantize_kv(jnp.asarray(x))
    np.testing.assert_array_equal(qn, np.asarray(qj))
    np.testing.assert_array_equal(sn, np.asarray(sj))


# ------------------------------------------------------- allocation / sizing

def test_allocate_int8_cache_shapes():
    cfg = ModelConfig.tiny()
    k, v = allocate_device_cache(cfg, 8, 4, dtype="int8")
    assert is_quant_cache(k) and is_quant_cache(v)
    L, slots, KV, hd = cache_shape(k)
    assert (L, slots) == (cfg.num_layers, 32)
    assert k["q"].dtype == np.int8
    assert k["s"].shape == (L, slots, KV)


def test_hbm_sizing_int8_roughly_doubles():
    cfg = ModelConfig.llama3_1b()
    # fake free memory via the math itself: compare per-block byte formulas
    group, = cfg.kv_cache_spec
    kh = vh = group.kv_heads
    kd, vd = group.k_dim, group.v_dim
    bf16 = cfg.num_layers * 16 * (kh * kd + vh * vd) * 2
    int8 = cfg.num_layers * 16 * (kh * (kd + 4) + vh * (vd + 4))
    assert 1.8 < bf16 / int8 < 2.0


# ------------------------------------------------------------ attention paths

def _paged_setup(seed=0, B=2, kv_len=48, bs=4, KV=2, H=4, hd=16):
    """Build a random quantized cache + matching bf16 cache and q batch."""
    rng = np.random.default_rng(seed)
    W = (kv_len + bs - 1) // bs
    num_blocks = B * W + 1
    slots = num_blocks * bs
    kf = rng.standard_normal((slots, KV, hd)).astype(np.float32)
    vf = rng.standard_normal((slots, KV, hd)).astype(np.float32)
    kq, ks = quantize_kv(kf)
    vq, vs = quantize_kv(vf)
    q = rng.standard_normal((B, H, hd)).astype(np.float32)
    bt = np.zeros((B, W), np.int32)
    for i in range(B):
        bt[i] = 1 + i * W + np.arange(W)
    kv_lens = np.full((B,), kv_len, np.int32)
    return q, kf, vf, kq, ks, vq, vs, bt, kv_lens


def test_decode_xla_int8_close_to_f32():
    import jax.numpy as jnp

    from dynamo_tpu.ops.paged_attention import paged_attention_decode_xla

    q, kf, vf, kq, ks, vq, vs, bt, lens = _paged_setup()
    ref = paged_attention_decode_xla(
        jnp.asarray(q), jnp.asarray(kf), jnp.asarray(vf),
        jnp.asarray(bt), jnp.asarray(lens), block_size=4)
    out = paged_attention_decode_xla(
        jnp.asarray(q), jnp.asarray(kq), jnp.asarray(vq),
        jnp.asarray(bt), jnp.asarray(lens), block_size=4,
        k_scales=jnp.asarray(ks), v_scales=jnp.asarray(vs))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0.05, atol=0.05)


def test_decode_pallas_interpret_matches_xla_int8():
    """The in-kernel dequant (scale DMA + segment-matmul) must agree with
    the XLA gather-dequant path on the same int8 pages."""
    import jax.numpy as jnp

    from dynamo_tpu.ops.paged_attention import (
        paged_attention_decode, paged_attention_decode_xla,
    )

    # KV·hd = 2·64 = 128 → lane-aligned, kernel path taken (interpret on CPU)
    q, kf, vf, kq, ks, vq, vs, bt, lens = _paged_setup(KV=2, hd=64, H=4)
    ref = paged_attention_decode_xla(
        jnp.asarray(q), jnp.asarray(kq), jnp.asarray(vq),
        jnp.asarray(bt), jnp.asarray(lens), block_size=4,
        k_scales=jnp.asarray(ks), v_scales=jnp.asarray(vs))
    out = paged_attention_decode(
        jnp.asarray(q), jnp.asarray(kq), jnp.asarray(vq),
        jnp.asarray(bt), jnp.asarray(lens), block_size=4, interpret=True,
        k_scales=jnp.asarray(ks), v_scales=jnp.asarray(vs))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_decode_pallas_int8_sliding_window_and_sinks():
    import jax.numpy as jnp

    from dynamo_tpu.ops.paged_attention import (
        paged_attention_decode, paged_attention_decode_xla,
    )

    q, kf, vf, kq, ks, vq, vs, bt, lens = _paged_setup(KV=2, hd=64, H=4)
    sinks = np.linspace(-1, 1, 4).astype(np.float32)
    for window in (None, 8):
        ref = paged_attention_decode_xla(
            jnp.asarray(q), jnp.asarray(kq), jnp.asarray(vq),
            jnp.asarray(bt), jnp.asarray(lens), block_size=4, window=window,
            sinks=jnp.asarray(sinks),
            k_scales=jnp.asarray(ks), v_scales=jnp.asarray(vs))
        out = paged_attention_decode(
            jnp.asarray(q), jnp.asarray(kq), jnp.asarray(vq),
            jnp.asarray(bt), jnp.asarray(lens), block_size=4, window=window,
            sinks=jnp.asarray(sinks), interpret=True,
            k_scales=jnp.asarray(ks), v_scales=jnp.asarray(vs))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)


def test_flash_prefill_paged_int8():
    import jax.numpy as jnp

    from dynamo_tpu.ops.flash_prefill import flash_prefill_paged

    rng = np.random.default_rng(3)
    B, S, H, KV, hd, bs = 1, 16, 4, 2, 16, 4
    W = S // bs
    slots = (B * W + 1) * bs
    kf = rng.standard_normal((2, slots, KV, hd)).astype(np.float32)
    vf = rng.standard_normal((2, slots, KV, hd)).astype(np.float32)
    kq, ks = quantize_kv(kf)
    vq, vs = quantize_kv(vf)
    q = rng.standard_normal((B, S, H, hd)).astype(np.float32)
    bt = np.arange(1, B * W + 1, dtype=np.int32).reshape(B, W)
    pos = np.broadcast_to(np.arange(S, dtype=np.int32), (B, S)).copy()
    lens = np.full((B,), S, np.int32)

    ref = flash_prefill_paged(
        jnp.asarray(q), jnp.asarray(kf), jnp.asarray(vf), 1,
        jnp.asarray(bt), jnp.asarray(pos), jnp.asarray(lens),
        block_size=bs, interpret=True)
    out = flash_prefill_paged(
        jnp.asarray(q), {"q": jnp.asarray(kq), "s": jnp.asarray(ks)},
        {"q": jnp.asarray(vq), "s": jnp.asarray(vs)}, 1,
        jnp.asarray(bt), jnp.asarray(pos), jnp.asarray(lens),
        block_size=bs, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0.05, atol=0.05)


# ------------------------------------------------------- gather/scatter paths

def test_pack_unpack_roundtrip():
    from dynamo_tpu.ops.kv_pages import (
        pack_kv_blocks, packed_block_width, unpack_kv_blocks,
    )

    rng = np.random.default_rng(9)
    x = rng.standard_normal((2, 3, 4, 2, 16)).astype(np.float32)
    q, s = quantize_kv(x)
    import jax.numpy as jnp

    buf = pack_kv_blocks(jnp.asarray(q), jnp.asarray(s))
    assert buf.shape == (2, 3, packed_block_width(4, 2, 16))
    assert buf.dtype == np.uint8
    q2, s2 = unpack_kv_blocks(buf, 4, 2, 16)
    np.testing.assert_array_equal(np.asarray(q2), q)
    np.testing.assert_array_equal(np.asarray(s2), s)


def test_gather_scatter_roundtrip_bit_exact():
    """offload → onboard over an int8 cache must restore the identical
    quantized pages (the determinism KVBM promises across tiers). The
    native bundle is PACKED uint8 — ~1 byte/element on the wire/tiers."""
    from dynamo_tpu.ops.kv_pages import packed_block_width
    from dynamo_tpu.ops.block_copy import gather_blocks, scatter_blocks

    cfg = ModelConfig.tiny()
    k, v = allocate_device_cache(cfg, 8, 4, dtype="int8")
    rng = np.random.default_rng(4)
    L, slots, KV, hd = cache_shape(k)
    # fill with quantized random content
    kf = rng.standard_normal((L, slots, KV, hd)).astype(np.float32)
    kq, ks = quantize_kv(kf)
    import jax.numpy as jnp

    k = {"q": jnp.asarray(kq), "s": jnp.asarray(ks)}
    ids = [2, 5, 3]
    bundle = np.asarray(gather_blocks(k, ids, block_size=4))[:, :3]
    assert bundle.dtype == np.uint8
    assert bundle.shape == (L, 3, packed_block_width(4, KV, hd))
    # snapshot before scatter: the cache is DONATED at the jit boundary
    q_src = np.asarray(k["q"]).reshape(L, slots // 4, 4, KV, hd)[:, [2, 5, 3]]
    # scatter into DIFFERENT blocks, then gather back: bit-identical
    k2 = scatter_blocks(k, [6, 1, 7], bundle, block_size=4)
    back = np.asarray(gather_blocks(k2, [6, 1, 7], block_size=4))[:, :3]
    np.testing.assert_array_equal(back, bundle)
    # and the quantized representation round-tripped exactly
    q_dst = np.asarray(k2["q"]).reshape(L, slots // 4, 4, KV, hd)[:, [6, 1, 7]]
    np.testing.assert_array_equal(q_src, q_dst)


def test_packed_bundle_into_plain_cache_dequantizes():
    """Quantized prefill worker → full-precision decode worker: the packed
    bundle must land as dequantized values."""
    from dynamo_tpu.ops.block_copy import gather_blocks, scatter_blocks

    cfg = ModelConfig.tiny()
    kq_cache, _ = allocate_device_cache(cfg, 8, 4, dtype="int8")
    kp_cache, _ = allocate_device_cache(cfg, 8, 4, dtype="float32")
    rng = np.random.default_rng(5)
    L, slots, KV, hd = cache_shape(kq_cache)
    kf = rng.standard_normal((L, slots, KV, hd)).astype(np.float32)
    kq, ks = quantize_kv(kf)
    import jax.numpy as jnp

    src = {"q": jnp.asarray(kq), "s": jnp.asarray(ks)}
    bundle = np.asarray(gather_blocks(src, [2, 5], block_size=4))[:, :2]
    out = scatter_blocks(kp_cache, [1, 3], bundle, block_size=4)
    got = np.asarray(gather_blocks(out, [1, 3], block_size=4))[:, :2]
    want = dequantize_kv(kq, ks).reshape(
        L, slots // 4, 4, KV, hd)[:, [2, 5]]
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------- engine e2e

def _engine(**kw):
    from dynamo_tpu.engine.engine import AsyncJaxEngine

    cfg = ModelConfig.tiny()
    defaults = dict(block_size=4, num_blocks=128, max_num_seqs=8,
                    max_num_batched_tokens=64, max_model_len=256,
                    prefill_buckets=(8, 16, 32, 64),
                    decode_batch_buckets=(1, 2, 4, 8))
    defaults.update(kw)
    return AsyncJaxEngine(cfg, EngineArgs(**defaults))


def _req(tokens, max_tokens=8):
    from dynamo_tpu.protocols import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )

    return PreprocessedRequest(
        model="tiny", token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens,
                                       ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0))


async def _collect(eng, r):
    toks = []
    async for out in eng.generate(r):
        toks.extend(out.token_ids)
    return toks


async def test_engine_int8_kv_serves_and_matches_bf16_greedy():
    """Same weights, same greedy prompt: the int8-cache engine must produce
    the same tokens as the full-precision cache on a short horizon (tiny
    f32 model — quantization noise far below the logit gaps)."""
    e_ref = _engine()
    e_q = _engine(kv_cache_dtype="int8")
    assert e_q._kv_quant and is_quant_cache(e_q.kv.k)
    prompt = list(range(1, 20))
    t_ref = await _collect(e_ref, _req(prompt))
    t_q = await _collect(e_q, _req(prompt))
    assert t_ref == t_q
    await e_ref.close()
    await e_q.close()


async def test_engine_int8_prefix_cache_reuse_deterministic():
    eng = _engine(kv_cache_dtype="int8")
    prompt = list(range(1, 30))
    t1 = await _collect(eng, _req(prompt))
    t2 = await _collect(eng, _req(prompt))  # prefix-cache hit path
    assert t1 == t2
    await eng.close()


async def test_engine_int8_with_kvbm_offload_onboard():
    """Offload to host (f32 bundles) → clear device → onboard → decode must
    be deterministic vs the never-offloaded run."""
    eng = _engine(kv_cache_dtype="int8", kvbm_host_bytes=1 << 24)
    prompt = list(range(1, 40))
    t1 = await _collect(eng, _req(prompt))
    # force everything off-device, then replay: onboard path re-quantizes
    for _ in range(50):
        if eng.kvbm.offloaded_blocks:
            break
        await asyncio.sleep(0.05)
    eng.pool.clear()
    t2 = await _collect(eng, _req(prompt))
    assert t1 == t2
    await eng.close()


async def test_engine_int8_multi_step_decode():
    e_q = _engine(kv_cache_dtype="int8", multi_step_decode=4)
    e_ref = _engine(kv_cache_dtype="int8")
    prompt = list(range(1, 16))
    assert await _collect(e_q, _req(prompt)) == \
        await _collect(e_ref, _req(prompt))
    await e_q.close()
    await e_ref.close()


@pytest.mark.slow
async def test_engine_int8_spec_decode():
    e_q = _engine(kv_cache_dtype="int8", speculative_tokens=3)
    e_ref = _engine(kv_cache_dtype="int8")
    prompt = [1, 2, 3, 4, 1, 2, 3, 4, 1, 2, 3]  # n-gram-friendly
    assert await _collect(e_q, _req(prompt)) == \
        await _collect(e_ref, _req(prompt))
    await e_q.close()
    await e_ref.close()


def test_decode_pallas_int8_both_scale_placements_match(monkeypatch):
    """The kernel has TWO int8 scale placements — VMEM-resident operands
    (small caches) and per-page scale DMAs (caches past the VMEM budget).
    Tests naturally exercise only the VMEM variant; force the DMA variant
    via DYN_KV_SCALE_VMEM_BYTES=0 so its unpacking/semaphore layout keeps
    coverage (it remains the production path for 100k+-slot caches)."""
    import jax.numpy as jnp

    from dynamo_tpu.ops.paged_attention import (
        paged_attention_decode, paged_attention_decode_xla,
    )

    q, kf, vf, kq, ks, vq, vs, bt, lens = _paged_setup(KV=2, hd=64, H=4)
    args = (jnp.asarray(q), jnp.asarray(kq), jnp.asarray(vq),
            jnp.asarray(bt), jnp.asarray(lens))
    kw = dict(block_size=4, k_scales=jnp.asarray(ks), v_scales=jnp.asarray(vs))
    ref = paged_attention_decode_xla(*args, **kw)

    monkeypatch.setenv("DYN_KV_SCALE_VMEM_BYTES", str(1 << 30))
    out_vmem = paged_attention_decode(*args, interpret=True, **kw)
    monkeypatch.setenv("DYN_KV_SCALE_VMEM_BYTES", "0")
    out_dma = paged_attention_decode(*args, interpret=True, **kw)

    np.testing.assert_allclose(np.asarray(out_vmem), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(out_dma), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def _mla_cfg():
    from dynamo_tpu.engine.config import ModelConfig

    return ModelConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96, num_layers=2,
        num_heads=4, num_kv_heads=4, dtype="float32",
        max_position_embeddings=256,
        kv_lora_rank=128, q_lora_rank=None, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16)


def test_mla_int8_cache_matches_bf16_paths():
    """MLA latent caches now quantize too: prefill (gather dequant), XLA
    decode, and the Pallas latent kernel (VMEM-resident per-slot scales)
    must all track the full-precision cache within int8 tolerance."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.cache import allocate_device_cache
    from dynamo_tpu.engine.model import forward, init_params
    from tests.test_mla import _paged_inputs

    cfg = _mla_cfg()
    params = init_params(cfg, jax.random.key(3), dtype=jnp.float32)
    row = [5, 9, 17, 23, 42, 77, 101, 3, 54]
    (tokens, positions, slot_map, bt, kv_lens, last_idx,
     num_blocks) = _paged_inputs(cfg, [row])

    outs, caches = {}, {}
    for name, dtype in (("f32", jnp.float32), ("int8", "int8")):
        kc, vc = allocate_device_cache(cfg, num_blocks, 4, dtype=dtype)
        logits, kc, vc = forward(params, tokens, positions, slot_map, bt,
                                 kv_lens, last_idx, kc, vc, cfg=cfg,
                                 block_size=4)
        outs[name] = np.asarray(logits)
        caches[name] = (kc, vc)
    # prefill logits: int8 cache only affects ATTENTION reads of cached
    # tokens; tolerance is the int8 quant noise floor
    np.testing.assert_allclose(outs["int8"], outs["f32"], atol=0.1, rtol=0.1)

    # one decode step: XLA gather path and Pallas latent kernel on the
    # SAME int8 cache must agree with each other tightly, and with f32
    # within quant noise
    tok = jnp.asarray([[61]], jnp.int32)
    pos = jnp.asarray([[9]], jnp.int32)
    slot = jnp.asarray([[int(bt[0, 2]) * 4 + 1]], jnp.int32)
    lens = jnp.asarray([10], jnp.int32)
    li = jnp.asarray([0], jnp.int32)

    dec = {}
    for name, up in (("xla", False), ("pallas", True)):
        kc, vc = jax.tree.map(jnp.copy, caches["int8"])
        logits, _, _ = forward(params, tok, pos, slot, bt, lens, li, kc, vc,
                               cfg=cfg, block_size=4, use_pallas=up)
        dec[name] = np.asarray(logits)
    np.testing.assert_allclose(dec["pallas"], dec["xla"], atol=2e-3, rtol=2e-3)

    kc, vc = caches["f32"]
    ref, _, _ = forward(params, tok, pos, slot, bt, lens, li, kc, vc,
                        cfg=cfg, block_size=4)
    np.testing.assert_allclose(dec["xla"], np.asarray(ref), atol=0.1, rtol=0.1)


@pytest.mark.anyio
async def test_mla_engine_serves_with_int8_kv():
    """End-to-end: the engine no longer falls back to bf16 for MLA — an
    int8-KV mla_tiny engine generates deterministically."""
    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.models import get_model_config
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.protocols import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )

    cfg = get_model_config("mla_tiny")
    args = EngineArgs(block_size=4, num_blocks=64, max_num_seqs=4,
                      max_num_batched_tokens=64, max_model_len=64,
                      kv_cache_dtype="int8")
    eng = AsyncJaxEngine(cfg, args)
    assert eng._kv_quant, "MLA int8 KV must not silently fall back"

    async def run():
        req = PreprocessedRequest(
            model="m", token_ids=[3, 1, 4, 1, 5, 9, 2, 6],
            sampling_options=SamplingOptions(temperature=0.0),
            stop_conditions=StopConditions(max_tokens=6, ignore_eos=True))
        toks = []
        async for out in eng.generate(req):
            toks.extend(out.token_ids or [])
            if out.finish_reason is not None:
                break
        return toks

    a = await run()
    b = await run()
    assert len(a) == 6 and a == b  # deterministic greedy under int8 KV
    await eng.close()


class _FakeDev:
    """A device as hbm_sized_num_blocks sees one."""

    def __init__(self, platform, kind, stats):
        self.platform, self.device_kind, self._stats = platform, kind, stats

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


def test_hbm_sizing_int8_capacity_and_estimate_fallback(monkeypatch):
    """The KV pool is sized from what the device itself reports: int8 KV
    roughly doubles the block count on a TPU that answers; a TPU whose
    memory_stats() raises, or a non-CPU device that reports nothing, is an
    error out of the sizing (never a guess); only the CPU backend, which
    has no stats at all, gets the default."""
    import jax

    from dynamo_tpu.engine import cache as C
    from dynamo_tpu.engine.config import ModelConfig

    cfg = ModelConfig.llama3_1b()
    v5e = _FakeDev("tpu", "TPU v5 lite",
                   {"bytes_limit": 16 << 30, "bytes_in_use": 3 << 30})
    monkeypatch.setattr(jax, "devices", lambda *a: [v5e])
    bf16 = C.hbm_sized_num_blocks(cfg, 16, 0.6)
    int8 = C.hbm_sized_num_blocks(cfg, 16, 0.6, kv_cache_dtype="int8")
    # 0.6 of the 13 GiB free at 1 MiB per bf16 block
    assert bf16 == int(13 * (1 << 30) * 0.6) // (16 * 16 * 2 * 8 * 64 * 2)
    # int8: 1 byte + 4-byte scale per (slot, head) vs 2-byte bf16 → the
    # per-slot ratio for hd=64 is (2*64*2)/(64+4+64+4) ≈ 1.88x
    assert 1.7 < int8 / bf16 < 2.0, (bf16, int8)

    failing = _FakeDev("tpu", "TPU v5 lite", RuntimeError("device lost"))
    monkeypatch.setattr(jax, "devices", lambda *a: [failing])
    with pytest.raises(RuntimeError, match="device lost"):
        C.hbm_sized_num_blocks(cfg, 16, 0.6)

    mute = _FakeDev("tpu", "TPU v9 unheard-of", None)
    monkeypatch.setattr(jax, "devices", lambda *a: [mute])
    with pytest.raises(RuntimeError, match="reports no memory_stats"):
        C.hbm_sized_num_blocks(cfg, 16, 0.6)

    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDev("cpu", "cpu", None)])
    assert C.hbm_sized_num_blocks(cfg, 16, 0.6) == 512


def test_decode_scale_slot_base_layer_slice_matches(monkeypatch):
    """scale_slot_base (r5): a layer-stacked flat cache passes ONE layer's
    scale slice + that layer's slot base, so VMEM residency is per-layer.
    Both placements must agree with full-table, base-0 results."""
    import jax.numpy as jnp

    from dynamo_tpu.ops.paged_attention import (
        paged_attention_decode, paged_attention_decode_xla,
    )

    q, kf, vf, kq, ks, vq, vs, bt, lens = _paged_setup(KV=2, hd=64, H=4)
    slots = kq.shape[0]
    # build a fake "layer 1 of 2" flat cache: layer 0 is garbage pages,
    # layer 1 is our real pages; block tables shift by nb like the engine's
    nb = slots // 4
    kq2 = np.concatenate([np.ones_like(kq) * 7, kq])
    vq2 = np.concatenate([np.ones_like(vq) * 7, vq])
    bt2 = bt + nb
    args = (jnp.asarray(q), jnp.asarray(kq2), jnp.asarray(vq2),
            jnp.asarray(bt2), jnp.asarray(lens))
    # scales: ONLY layer 1's slice, rebased by scale_slot_base=slots
    kw = dict(block_size=4, k_scales=jnp.asarray(ks),
              v_scales=jnp.asarray(vs), scale_slot_base=slots)
    ref = paged_attention_decode_xla(
        jnp.asarray(q), jnp.asarray(kq), jnp.asarray(vq),
        jnp.asarray(bt), jnp.asarray(lens), block_size=4,
        k_scales=jnp.asarray(ks), v_scales=jnp.asarray(vs))

    assert np.allclose(np.asarray(paged_attention_decode_xla(*args, **kw)),
                       np.asarray(ref), rtol=2e-3, atol=2e-3)
    monkeypatch.setenv("DYN_KV_SCALE_VMEM_BYTES", str(1 << 30))
    out_vmem = paged_attention_decode(*args, interpret=True, **kw)
    monkeypatch.setenv("DYN_KV_SCALE_VMEM_BYTES", "0")
    out_dma = paged_attention_decode(*args, interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(out_vmem), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(out_dma), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
