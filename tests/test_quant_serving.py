"""Quantized serving to the bandwidth floor (ISSUE 19).

Covers: the ragged Pallas kernel consuming int8 KV pages natively (vs the
XLA oracle — window, sinks, staggered mixed rows, per-layer
``scale_slot_base`` rebase), the explicit fallback taxonomy that replaced
the silent int8 degrade (``ragged_fallback_reason`` + the engine's
``dynamo_ragged_fallback_total`` counter and flight tag, and the
``DYN_RAGGED_ORACLE`` bench/test switch), quantized WEIGHTS riding every
ragged mode with bit-identical streams (base / spec verify / multi-step /
pipelined, greedy AND seeded), int8-KV streams identical to the bf16-KV
oracle arm, swap-preemption and KVBM offload→onboard holding the identity
with weights+KV both quantized, the signature census proving int8 KV adds
ZERO compiled signatures over bf16, the plan_70b quantized-placement exit
gate, and the AOT ``memory_analysis`` proof that the grouped dequant chain
never materializes a full-width weight copy (docs/performance.md).
"""

import asyncio
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import AsyncJaxEngine
from dynamo_tpu.ops.kv_pages import is_quant_cache, quantize_kv
from dynamo_tpu.ops.ragged_attention import (
    ragged_attention_xla, ragged_int8_kernel_supported,
    ragged_paged_attention,
)
from dynamo_tpu.protocols import (
    PreprocessedRequest, SamplingOptions, StopConditions,
)

pytestmark = pytest.mark.anyio


# ------------------------------------------- ops: int8-KV ragged vs oracle


def make_int8_case(key, rows, H=8, KV=2, hd=128, bs=8, num_blocks=24, W=6,
                   pad_rows=2, pad_tokens=3):
    """Mixed decode/prefill rows over an int8-quantized paged cache.
    hd = 128 keeps the Pallas lane alignment (the tiny serving config
    is hd = 16 and legitimately degrades — see the taxonomy tests)."""
    ks = jax.random.split(key, 3)
    kf = jax.random.normal(ks[0], (num_blocks * bs, KV, hd), jnp.float32)
    vf = jax.random.normal(ks[1], (num_blocks * bs, KV, hd), jnp.float32)
    kq, ksc = quantize_kv(np.asarray(kf))
    vq, vsc = quantize_kv(np.asarray(vf))
    rng = np.random.default_rng(int(jax.random.randint(key, (), 0, 1 << 30)))
    R = len(rows) + pad_rows
    rows3 = np.zeros((R, 3), np.int32)
    bt = np.zeros((R, W), np.int32)
    t = 0
    for i, (ql, kl) in enumerate(rows):
        rows3[i] = (t, ql, kl)
        used = (kl + bs - 1) // bs
        bt[i, :used] = rng.choice(np.arange(1, num_blocks), size=used,
                                  replace=False)
        t += ql
    q = jax.random.normal(ks[2], (t + pad_tokens, H, hd), jnp.float32)
    return (q, jnp.asarray(kq), jnp.asarray(vq), jnp.asarray(ksc),
            jnp.asarray(vsc), jnp.asarray(bt), jnp.asarray(rows3), t)


STAGGERED = [(1, 20), (6, 24), (1, 9), (11, 11)]


@pytest.mark.parametrize("window,sinks", [(None, False), (7, False),
                                          (None, True), (7, True)])
def test_ragged_int8_kernel_matches_oracle(window, sinks):
    """Interpret-mode kernel with VMEM-resident scales == the XLA gather
    oracle, on a staggered mixed batch with padding rows/tokens, across
    window × sink."""
    q, kq, vq, ksc, vsc, bt, rows3, t = make_int8_case(
        jax.random.key(0), STAGGERED)
    sk = (jax.random.normal(jax.random.key(5), (8,), jnp.float32)
          if sinks else None)
    kw = dict(block_size=8, window=window, sinks=sk,
              k_scales=ksc, v_scales=vsc)
    want = ragged_attention_xla(q, kq, vq, bt, rows3, **kw)
    got = ragged_paged_attention(q, kq, vq, bt, rows3, interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(got)[:t], np.asarray(want)[:t],
                               atol=2e-5, rtol=2e-5)


def test_ragged_int8_scale_slot_base_rebases_layer_slice():
    """The layer-stacked caller passes ONE layer's scale slice plus
    ``scale_slot_base = lidx·slots``: prepending a junk layer to the flat
    cache and shifting block tables + base must be bit-exact vs the
    unshifted call."""
    q, kq, vq, ksc, vsc, bt, rows3, t = make_int8_case(
        jax.random.key(1), STAGGERED)
    base = ragged_paged_attention(q, kq, vq, bt, rows3, block_size=8,
                                  interpret=True, k_scales=ksc,
                                  v_scales=vsc)
    slots = kq.shape[0]
    junk = jnp.full_like(kq, 7)  # a fake layer 0 that must never be read
    kq2 = jnp.concatenate([junk, kq])
    vq2 = jnp.concatenate([junk, vq])
    got = ragged_paged_attention(
        q, kq2, vq2, bt + slots // 8, rows3, block_size=8, interpret=True,
        k_scales=ksc, v_scales=vsc, scale_slot_base=slots)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


#: rows of every q_len class of the kernel's two query tiles (8 and 128
#: tokens), packed in one batch
TILE_ROWS = [(1, 20), (8, 30), (9, 9), (40, 75), (1, 300), (128, 200),
             (129, 140), (200, 260)]


@pytest.mark.parametrize("H,KV,window,sinks", [
    (8, 2, None, False), (8, 8, None, False), (14, 2, 50, True),
    (8, 1, 7, False), (8, 4, None, True)],
    ids=["g4_kv2", "g1_kv8", "g7_window_sinks", "g8_mqa_window", "g2_kv4"])
def test_ragged_int8_kernel_tiles_with_layer_base(H, KV, window, sinks):
    """int8 pages through both query tiles, as the layer scan calls the
    kernel: layer 1's scale slice, block tables and ``scale_slot_base``
    shifted past a junk layer 0 that must never be read. KV = 1, 2 (a word
    of int8 rows holds several slots of one head: keys scored in piece
    order), 4 and 8."""
    need = sum(-(-kl // 8) for _, kl in TILE_ROWS) + 2
    q, kq, vq, ksc, vsc, bt, rows3, t = make_int8_case(
        jax.random.key(4), TILE_ROWS, H=H, KV=KV, num_blocks=need,
        W=-(-300 // 8), pad_rows=3, pad_tokens=5)
    sk = (jax.random.normal(jax.random.key(5), (H,), jnp.float32)
          if sinks else None)
    kw = dict(block_size=8, window=window, sinks=sk, k_scales=ksc,
              v_scales=vsc)
    want = ragged_attention_xla(q, kq, vq, bt, rows3, **kw)
    slots = kq.shape[0]
    junk = jnp.full_like(kq, 7)
    got = ragged_paged_attention(
        q, jnp.concatenate([junk, kq]), jnp.concatenate([junk, vq]),
        bt + slots // 8, rows3, interpret=True, scale_slot_base=slots, **kw)
    np.testing.assert_allclose(np.asarray(got)[:t], np.asarray(want)[:t],
                               atol=2e-5, rtol=2e-5)


def test_ragged_int8_scale_budget_degrades_to_oracle(monkeypatch):
    """Scale tables past the VMEM budget degrade to the XLA oracle —
    bit-equal to calling the oracle directly (it IS the oracle), and the
    predicate the engine's fallback taxonomy reads flips."""
    q, kq, vq, ksc, vsc, bt, rows3, t = make_int8_case(
        jax.random.key(2), STAGGERED)
    monkeypatch.setenv("DYN_KV_SCALE_VMEM_BYTES", "0")
    assert not ragged_int8_kernel_supported(2, int(kq.shape[0]))
    kw = dict(block_size=8, k_scales=ksc, v_scales=vsc)
    got = ragged_paged_attention(q, kq, vq, bt, rows3, interpret=True, **kw)
    want = ragged_attention_xla(q, kq, vq, bt, rows3, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ragged_oracle_env_switch(monkeypatch):
    """DYN_RAGGED_ORACLE=1 routes the launch to the XLA oracle — the
    explicit A/B arm that replaced the deleted silent fallback."""
    q, kq, vq, ksc, vsc, bt, rows3, t = make_int8_case(
        jax.random.key(3), STAGGERED[:2])
    monkeypatch.setenv("DYN_RAGGED_ORACLE", "1")
    kw = dict(block_size=8, k_scales=ksc, v_scales=vsc)
    got = ragged_paged_attention(q, kq, vq, bt, rows3, **kw)
    want = ragged_attention_xla(q, kq, vq, bt, rows3, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------ fallback taxonomy


def test_ragged_fallback_reason_taxonomy(monkeypatch):
    import dataclasses

    tiny = ModelConfig.tiny()  # hd = 16: not lane-aligned
    assert M.ragged_fallback_reason(tiny, None, use_pallas=False) is None
    assert M.ragged_fallback_reason(tiny, None, use_pallas=True) == \
        "lane_align"
    capped = dataclasses.replace(tiny, attn_logit_softcap=30.0)
    assert M.ragged_fallback_reason(capped, None, use_pallas=True) == \
        "softcap"
    aligned = dataclasses.replace(tiny, head_dim=128)
    assert M.ragged_fallback_reason(aligned, None, use_pallas=True) is None
    monkeypatch.setenv("DYN_KV_SCALE_VMEM_BYTES", "0")
    assert M.ragged_fallback_reason(aligned, None, use_pallas=True,
                                    kv_quant=True,
                                    slots_per_layer=128) == "scale_budget"
    monkeypatch.delenv("DYN_KV_SCALE_VMEM_BYTES")
    assert M.ragged_fallback_reason(aligned, None, use_pallas=True,
                                    kv_quant=True,
                                    slots_per_layer=128) is None


def _req(tokens, osl=8, seed=None, temp=None):
    if seed is not None:
        sopt = SamplingOptions(temperature=temp or 0.8, top_p=0.9,
                               seed=seed)
    else:
        sopt = SamplingOptions(temperature=0.0)
    return PreprocessedRequest(
        model="m", token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=osl, ignore_eos=True),
        sampling_options=sopt)


def _engine(**kw) -> AsyncJaxEngine:
    cfg = kw.pop("cfg", None) or ModelConfig.tiny()
    defaults = dict(block_size=4, num_blocks=128, max_num_seqs=8,
                    max_num_batched_tokens=64, max_model_len=256)
    defaults.update(kw)
    return AsyncJaxEngine(cfg, EngineArgs(**defaults))


async def _collect(eng, r):
    toks = []
    async for out in eng.generate(r):
        toks.extend(out.token_ids)
    return toks


async def _run(eng, prompts, osl=8, seed0=None):
    return await asyncio.gather(
        *[_collect(eng, _req(p, osl,
                             seed=None if seed0 is None else seed0 + i))
          for i, p in enumerate(prompts)])


async def test_engine_counts_ragged_fallback_and_tags_flight():
    """A Pallas-requested engine whose geometry degrades (tiny KV·hd=32)
    must expose the reason, count every degraded step, and tag flight
    records; the default engine (Pallas never requested) counts nothing."""
    eng = _engine(use_pallas_attention=True)
    assert eng.ragged_fallback_reason == "lane_align"
    await _collect(eng, _req([1, 2, 3, 4, 5]))
    assert eng.ragged_fallback_total.get("lane_align", 0) > 0
    tagged = [d for d in eng.flight.snapshot()
              if "ragged_fallback:lane_align" in (d.get("tags") or [])]
    assert tagged, "flight records must carry the fallback tag"
    await eng.close()

    e2 = _engine()
    assert e2.ragged_fallback_reason is None
    await _collect(e2, _req([1, 2, 3]))
    assert e2.ragged_fallback_total == {}
    await e2.close()


# ------------------------------- engine: quantized weights on every mode


PROMPTS = [list(range(1, 20)), list(range(30, 45)), [7, 9, 11]]


async def test_quant_weights_identical_streams_across_ragged_modes():
    """int8 weights ride base / spec-verify / multi-step / serial-loop
    engines with BIT-IDENTICAL greedy and seeded streams: the ragged modes
    are dispatch-count optimizations and quantized weights must not leak
    into any of them differently."""
    modes = [{}, dict(speculative_tokens=3), dict(multi_step_decode=4),
             dict(pipeline_decode=False)]
    engines = [_engine(quantization="int8", **m) for m in modes]
    greedy = [await _run(e, PROMPTS) for e in engines]
    seeded = [await _run(e, PROMPTS, seed0=7) for e in engines]
    for e in engines:
        await e.close()
    assert all(g == greedy[0] for g in greedy[1:]), "greedy diverged"
    assert all(s == seeded[0] for s in seeded[1:]), "seeded diverged"


async def test_quant_int4_grouped_deterministic_and_served():
    """int4-g32 end-to-end: the engine quantizes at init, serves, and
    replays identically (int4 noise may move argmax vs bf16 — run-to-run
    identity is the contract)."""
    eng = _engine(quantization="int4-g32")
    a = await _run(eng, PROMPTS)
    b = await _run(eng, PROMPTS)
    s1 = await _run(eng, PROMPTS, seed0=11)
    s2 = await _run(eng, PROMPTS, seed0=11)
    await eng.close()
    assert a == b and s1 == s2
    assert all(len(t) == 8 for t in a)


async def test_quant_weights_with_int8_kv_match_bf16_kv_oracle():
    """Weights int8 + KV int8 vs the SAME quantized weights over a bf16
    cache (the oracle arm): greedy and seeded streams identical on the
    short tiny-f32 horizon — cache quantization noise stays below the
    sampler."""
    e_q = _engine(quantization="int8", kv_cache_dtype="int8")
    e_o = _engine(quantization="int8")
    assert is_quant_cache(e_q.kv.k)
    assert await _run(e_q, PROMPTS) == await _run(e_o, PROMPTS)
    assert await _run(e_q, PROMPTS, seed0=5) == \
        await _run(e_o, PROMPTS, seed0=5)
    await e_q.close()
    await e_o.close()


async def test_quant_swap_and_onboard_hold_stream_identity():
    """Weights AND KV quantized, pool sized to force preempt-to-swap: the
    oversubscribed run must match the big-pool run exactly, and a KVBM
    offload→clear→onboard replay must be deterministic (the packed (q, s)
    bundle roundtrip contract)."""
    N, ISL, OSL = 4, 32, 12
    prompts = [[(7 * i + j) % 200 + 1 for j in range(ISL)]
               for i in range(N)]
    working = N * ((ISL + OSL + 3) // 4)
    quant = dict(quantization="int8", kv_cache_dtype="int8",
                 enable_prefix_caching=False)
    e_small = _engine(num_blocks=working // 2 + 1, **quant)
    e_big = _engine(num_blocks=working + 8, **quant)
    a = await _run(e_small, prompts, osl=OSL)
    b = await _run(e_big, prompts, osl=OSL)
    assert a == b, "swap preemption changed a quantized stream"
    await e_small.close()
    await e_big.close()

    eng = _engine(quantization="int8", kv_cache_dtype="int8",
                  kvbm_host_bytes=1 << 24)
    t1 = await _collect(eng, _req(list(range(1, 40)), osl=OSL))
    for _ in range(50):
        if eng.kvbm.offloaded_blocks:
            break
        await asyncio.sleep(0.05)
    eng.pool.clear()
    t2 = await _collect(eng, _req(list(range(1, 40)), osl=OSL))
    assert t1 == t2, "onboard replay diverged under full quantization"
    await eng.close()


async def test_mla_latent_int8_streams_match_bf16_kv():
    """MLA latent pages quantized vs bf16 latent cache: identical greedy
    streams on the short horizon — the latent ragged walk keeps parity
    under int8 (the MLA leg of the oracle-identity contract)."""
    from dynamo_tpu.models import get_model_config

    cfg = get_model_config("mla_tiny")
    kw = dict(cfg=cfg, num_blocks=64, max_model_len=64)
    e_q = _engine(kv_cache_dtype="int8", **kw)
    e_o = _engine(**kw)
    assert e_q._kv_quant
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6], list(range(2, 14))]
    assert await _run(e_q, prompts, osl=6) == await _run(e_o, prompts,
                                                         osl=6)
    await e_q.close()
    await e_o.close()


async def test_int8_kv_adds_zero_compiled_signatures():
    """The census gate: the int8-KV engine's compiled-signature set over a
    mixed staggered workload equals the bf16 engine's — quantized KV rides
    the SAME packed ragged launch, no extra specializations."""
    async def census(**kw):
        eng = _engine(enable_prefix_caching=False, **kw)
        tasks = []
        for p in PROMPTS:
            tasks.append(asyncio.ensure_future(_collect(eng, _req(p))))
            for _ in range(2000):
                if any(s.generated > 0 for s in eng.scheduler.running):
                    break
                await asyncio.sleep(0.001)
        await asyncio.gather(*tasks)
        sigs = set(eng.compiled_signatures)
        await eng.close()
        return sigs

    base = await census()
    kv8 = await census(kv_cache_dtype="int8")
    assert kv8 == base, f"int8 KV changed the census: {kv8 ^ base}"


# -------------------------------------------- config validation + plan gate


def test_engine_args_quantization_validated():
    for bad in ("int4", "int9", "int8-g0", "fp8", "int8-gx"):
        with pytest.raises(ValueError, match="quantization"):
            EngineArgs(block_size=4, num_blocks=8, quantization=bad)
    for ok in ("int8", "int8-g64", "int4-g32"):
        EngineArgs(block_size=4, num_blocks=8, quantization=ok)


def test_plan_70b_quant_gate_holds():
    """The solver half of --assert-quant: the solved tp8_wint4_kvint8
    placement fits and its real-layout bandwidth demand stays under the
    ceiling."""
    from benchmarks.plan_70b import assert_quant

    res = assert_quant(run_compile=False)
    assert res["fits"] and res["quant_ok"]
    assert res["kernel_hbm_util_v5e"] <= 1.25


def test_quant_compile_proof_never_materializes_full_width():
    """AOT memory_analysis guard (ISSUE 19 §2 risk): the int4-g32+int8-KV
    sharded step must lower with temp bytes at or below the bf16 step's —
    a materialized full-width dequant copy would ADD gigabytes (w_down
    alone is 0.94 GB f32 at 2 layers). Quantized params must also carry
    under half the bf16 bytes, proving the abstract tree really is
    quantized. The absolute on-chip temp ceiling is a TPU-only contract
    (CPU AOT keeps more temp than the fused TPU ideal) — that half skips
    cleanly off-TPU."""
    from benchmarks.plan_70b import QUANT_TEMP_RATIO_CEILING, compile_proof

    pq = compile_proof(quantization="int4-g32", kv_int8=True)
    pb = compile_proof()
    assert pq["params_bytes"] < pb["params_bytes"] * 0.51
    assert pq["temp_gb"] <= pb["temp_gb"] * QUANT_TEMP_RATIO_CEILING
    if jax.default_backend() != "tpu":
        pytest.skip("absolute temp ceiling is a TPU-only contract")
    assert pq["temp_gb"] <= 0.05


@pytest.mark.skipif(not hasattr(jax, "shard_map"),
                    reason="pp ragged path needs jax.shard_map "
                           "(partial-manual over 'pp'); this jax build "
                           "predates it — same gate as the bf16 pp tests")
@pytest.mark.parametrize("spec", ["int8", "int4-g32"])
def test_pp_decode_step_quantized_matches_dense(spec):
    """Quantized weights through the GPipe-pipelined ragged step: the pp
    microbatch path runs the same qmm/dequant chain as the dense scan, so
    a decode step over stage-sliced QTensor stacks (q sharded on "pp",
    scales riding along) must match the single-path forward with the SAME
    quantized params — the "PP microbatches" leg of the every-ragged-mode
    contract at the kernel level (the engine legs are the stream tests
    above; pp engines forbid int8 KV by construction, weights-only here)."""
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.quant import quant_shardings, quantize_params
    from dynamo_tpu.parallel import MeshConfig, make_mesh
    from dynamo_tpu.parallel.pipeline import make_pp_step_fn

    cfg = ModelConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96, num_layers=4,
        num_heads=4, num_kv_heads=2, head_dim=16, dtype="float32")
    block_size, W, B = 4, 4, 4
    num_blocks = 1 + B * W
    mesh = make_mesh(MeshConfig(pp=2, dp=2, tp=2))

    raw = M.init_params(cfg, jax.random.key(1), dtype=jnp.float32)
    params = quantize_params(jax.tree.map(np.asarray, raw), spec)
    shape = (cfg.num_layers, num_blocks * block_size,
             cfg.num_kv_heads, cfg.head_dim)

    def pp_inputs(S, kv_len):
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, S)),
                             jnp.int32)
        positions = jnp.tile(
            jnp.arange(kv_len - S, kv_len, dtype=jnp.int32), (B, 1))
        bt = np.zeros((B, W), np.int32)
        for i in range(B):
            bt[i] = 1 + i * W + np.arange(W)
        flat = bt[:, :, None] * block_size + np.arange(block_size)[None]
        flat = flat.reshape(B, W * block_size)
        return (tokens, positions, jnp.asarray(flat[:, kv_len - S:kv_len]),
                jnp.asarray(bt), jnp.full((B,), kv_len, jnp.int32),
                jnp.full((B,), S - 1, jnp.int32))

    # prefill 7 tokens via the dense path with the QUANTIZED params, then
    # decode token 8 dense (reference) and pipelined (subject)
    pre = pp_inputs(7, kv_len=7)
    kc, vc = jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)
    _, kc, vc = M.forward(params, *pre, kc, vc, cfg=cfg,
                          block_size=block_size)
    dec = pp_inputs(1, kv_len=8)
    want, _, _ = M.forward(params, *dec, kc, vc, cfg=cfg,
                           block_size=block_size)

    sh = quant_shardings(M.param_shardings(cfg, mesh), params)
    csh = M.cache_shardings(mesh, cfg)
    p_pp = jax.device_put(params, sh)
    step = make_pp_step_fn(cfg, block_size, mesh)
    d_tok, d_pos, d_slot, d_bt, d_lens, _ = dec
    Mmb, R = 2, 2
    T = R
    C, _ = M.ragged_grid_shape(T)
    ints5 = np.zeros((Mmb, 5, T), np.int32)
    rows3 = np.zeros((Mmb, R, 3), np.int32)
    bt_mb = np.zeros((Mmb, R, W), np.int32)
    for m in range(Mmb):
        for j in range(R):
            i = m * R + j
            ints5[m, 0, j] = int(d_tok[i, 0])
            ints5[m, 1, j] = int(d_pos[i, 0])
            ints5[m, 2, j] = int(d_slot[i, 0])
            ints5[m, 3, j] = C
            rows3[m, j] = (j, 1, int(d_lens[i]))
            bt_mb[m, j] = np.asarray(d_bt[i])
    grid_rows = np.zeros((Mmb, C), np.int32)
    got, _, _ = step(p_pp, jnp.asarray(ints5), jnp.asarray(rows3),
                     jnp.asarray(grid_rows), jnp.asarray(bt_mb),
                     jax.device_put(kc, csh), jax.device_put(vc, csh))
    np.testing.assert_allclose(np.asarray(got).reshape(B, -1),
                               np.asarray(want), atol=1e-5, rtol=1e-5)
