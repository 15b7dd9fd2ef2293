"""MLA (DeepSeek V2/V3) numerics + engine tests.

Golden parity against HF transformers' DeepseekV3 implementation (the same
conformance discipline as tests/test_parity.py for llama), plus
paged-latent-cache consistency (prefill-vs-decode) and an end-to-end engine
generate on the mla_tiny preset.

ref capability: recipes/deepseek-r1/sglang-wideep — the reference's flagship
wide-EP recipe serves DeepSeek-R1; MLA is what makes its KV cache servable.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.anyio


def _tiny_hf_cfg():
    from transformers import DeepseekV3Config

    return DeepseekV3Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4,
        n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
        n_group=2, topk_group=1, norm_topk_prob=True,
        routed_scaling_factor=2.5, first_k_dense_replace=1,
        kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16,
        max_position_embeddings=256, rope_theta=10000.0,
        tie_word_embeddings=False, attention_bias=False,
    )


@pytest.fixture(scope="module")
def hf_checkpoint(tmp_path_factory):
    """A tiny random DeepseekV3 checkpoint saved in HF layout."""
    import torch
    from transformers import DeepseekV3ForCausalLM

    torch.manual_seed(0)
    hf_cfg = _tiny_hf_cfg()
    model = DeepseekV3ForCausalLM(hf_cfg).eval().to(torch.float32)
    # randomize the e_score_correction_bias buffers so expert CHOICE and
    # gate WEIGHTS diverge — a loader/router that confuses them fails here
    with torch.no_grad():
        for layer in model.model.layers[hf_cfg.first_k_dense_replace:]:
            layer.mlp.gate.e_score_correction_bias.copy_(
                torch.randn(hf_cfg.n_routed_experts) * 0.5)
    path = tmp_path_factory.mktemp("deepseek_tiny")
    model.save_pretrained(path, safe_serialization=True)
    return model, str(path)


def _paged_inputs(cfg, token_rows, block_size=4):
    """Contiguous block tables / slot maps for a batch of prompts (one
    prefill chunk per row, padded to the longest)."""
    import jax.numpy as jnp

    B = len(token_rows)
    S = max(len(r) for r in token_rows)
    W = (S + block_size - 1) // block_size
    tokens = np.zeros((B, S), np.int32)
    positions = np.zeros((B, S), np.int32)
    slot_map = np.zeros((B, S), np.int32)
    bt = np.zeros((B, W), np.int32)
    kv_lens = np.zeros((B,), np.int32)
    last_idx = np.zeros((B,), np.int32)
    nxt = 1  # block 0 is NULL
    for b, row in enumerate(token_rows):
        n = len(row)
        tokens[b, :n] = row
        positions[b, :n] = np.arange(n)
        blocks = list(range(nxt, nxt + W))
        nxt += W
        bt[b] = blocks
        for s in range(n):
            slot_map[b, s] = blocks[s // block_size] * block_size + s % block_size
        kv_lens[b] = n
        last_idx[b] = n - 1
    num_blocks = nxt + 1
    return (jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(slot_map),
            jnp.asarray(bt), jnp.asarray(kv_lens), jnp.asarray(last_idx),
            num_blocks)


def test_mla_logits_parity_vs_hf(hf_checkpoint):
    """Paged MLA forward matches HF DeepseekV3 logits on a real (tiny)
    checkpoint — catches rope-interleave, absorption, router, and shared-
    expert mistakes in one shot."""
    import torch
    import jax.numpy as jnp

    from dynamo_tpu.engine.cache import allocate_device_cache
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.loader import load_hf_params
    from dynamo_tpu.engine.model import forward

    model, path = hf_checkpoint
    cfg = ModelConfig.from_pretrained(path)
    assert cfg.is_mla and cfg.scoring_func == "sigmoid"
    assert cfg.first_k_dense_replace == 1 and cfg.n_shared_experts == 1
    params = load_hf_params(cfg, path, dtype=jnp.float32)

    rows = [[5, 9, 17, 23, 42, 77, 101, 3], [7, 11, 13]]
    (tokens, positions, slot_map, bt, kv_lens, last_idx,
     num_blocks) = _paged_inputs(cfg, rows)
    kc, vc = allocate_device_cache(cfg, num_blocks, 4, dtype=jnp.float32)
    assert kc.shape[-2:] == (1, cfg.kv_lora_rank)
    assert vc.shape[-2:] == (1, cfg.rope_cache_dim)  # rope lane-padded

    logits, kc, vc = forward(params, tokens, positions, slot_map, bt,
                             kv_lens, last_idx, kc, vc, cfg=cfg, block_size=4)

    with torch.no_grad():
        for b, row in enumerate(rows):
            hf = model(torch.tensor([row])).logits[0, -1].numpy()
            np.testing.assert_allclose(np.asarray(logits[b]), hf,
                                       atol=2e-4, rtol=2e-3)


@pytest.mark.slow
def test_mla_decode_matches_full_prefill(hf_checkpoint):
    """Token-by-token decode through the paged latent cache reproduces the
    one-shot prefill logits (cache round-trip correctness)."""
    import jax.numpy as jnp

    from dynamo_tpu.engine.cache import allocate_device_cache
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.loader import load_hf_params
    from dynamo_tpu.engine.model import forward

    _, path = hf_checkpoint
    cfg = ModelConfig.from_pretrained(path)
    params = load_hf_params(cfg, path, dtype=jnp.float32)

    row = [5, 9, 17, 23, 42, 77, 101, 3]
    (tokens, positions, slot_map, bt, kv_lens, last_idx,
     num_blocks) = _paged_inputs(cfg, [row])
    kc, vc = allocate_device_cache(cfg, num_blocks, 4, dtype=jnp.float32)
    want, _, _ = forward(params, tokens, positions, slot_map, bt, kv_lens,
                         last_idx, kc, vc, cfg=cfg, block_size=4)

    # same prompt: prefill the first 5, then decode the last 3 one at a time
    kc2, vc2 = allocate_device_cache(cfg, num_blocks, 4, dtype=jnp.float32)
    (t5, p5, s5, bt5, kv5, li5, _) = _paged_inputs(cfg, [row[:5]])
    got, kc2, vc2 = forward(params, t5, p5, s5, bt, kv5, li5, kc2, vc2,
                            cfg=cfg, block_size=4)
    for i in range(5, 8):
        tok = jnp.asarray([[row[i]]], jnp.int32)
        pos = jnp.asarray([[i]], jnp.int32)
        slot = jnp.asarray([[int(bt[0, i // 4]) * 4 + i % 4]], jnp.int32)
        got, kc2, vc2 = forward(params, tok, pos, slot, bt,
                                jnp.asarray([i + 1], jnp.int32),
                                jnp.asarray([0], jnp.int32),
                                kc2, vc2, cfg=cfg, block_size=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-3)


async def test_mla_engine_generate():
    """End-to-end engine generate on the mla_tiny preset: latent cache
    allocation, scheduler, prefix cache, and greedy determinism."""
    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.models import get_model_config
    from dynamo_tpu.protocols import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )

    cfg = get_model_config("mla_tiny")
    args = EngineArgs(block_size=4, num_blocks=64, max_num_seqs=4,
                      max_num_batched_tokens=32, max_model_len=128,
                      prefill_buckets=(8, 16, 32),
                      decode_batch_buckets=(1, 2, 4))
    eng = AsyncJaxEngine(cfg, args)

    async def run(prompt):
        r = PreprocessedRequest(
            model="mla", token_ids=prompt,
            stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0))
        toks = []
        async for out in eng.generate(r):
            toks.extend(out.token_ids)
        return toks

    t1 = await run(list(range(1, 12)))
    t2 = await run(list(range(1, 12)))  # second run hits the prefix cache
    assert t1 == t2 and len(t1) == 6


def test_deepseek_presets_resolve():
    from dynamo_tpu.models import get_model_config

    v3 = get_model_config("deepseek_v3")
    assert v3.is_mla and v3.num_experts == 256 and v3.first_k_dense_replace == 3
    lite = get_model_config("deepseek_v2_lite")
    assert lite.is_mla and lite.q_lora_rank is None
    assert lite.kv_cache_spec[0][1:4] == (1, 512, 128)  # rope 64 lane-padded


def test_mla_ragged_packed_matches_bucketed():
    """MLA rides the packed ragged launch (_mla_ragged_olat): a two-chunk
    prefill launch and a mixed decode+chunk launch reproduce the bucketed
    latent-attention logits row by row (disjoint pages per row, greedy
    argmax identical)."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.cache import allocate_device_cache
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.model import (
        forward, init_params, make_ragged_step_fn, ragged_grid_shape,
    )

    cfg = ModelConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96, num_layers=2,
        num_heads=4, num_kv_heads=4, dtype="float32",
        max_position_embeddings=256,
        kv_lora_rank=32, q_lora_rank=None, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16)
    params = init_params(cfg, jax.random.key(3), dtype=jnp.float32)
    bs, W = 4, 8
    rows = [[5, 9, 17, 23, 42, 77, 101, 3], [7, 11, 13]]
    B = len(rows)
    bt = np.zeros((B, W), np.int32)
    nxt = 1
    for b in range(B):
        bt[b] = np.arange(nxt, nxt + W)
        nxt += W
    num_blocks = nxt + 1

    def slots(b, positions):
        return [int(bt[b, p // bs]) * bs + p % bs for p in positions]

    # bucketed reference: per-row prefills, then one decode + one chunk
    kcb, vcb = allocate_device_cache(cfg, num_blocks, bs, dtype=jnp.float32)
    want = []
    for b, row in enumerate(rows):
        n = len(row)
        lg, kcb, vcb = forward(
            params, jnp.asarray([row], jnp.int32),
            jnp.asarray([np.arange(n)], jnp.int32),
            jnp.asarray([slots(b, range(n))], jnp.int32),
            jnp.asarray(bt[b:b + 1]), jnp.asarray([n], jnp.int32),
            jnp.asarray([n - 1], jnp.int32), kcb, vcb,
            cfg=cfg, block_size=bs)
        want.append(np.asarray(lg[0]))
    lg_dec, kcb, vcb = forward(
        params, jnp.asarray([[54]], jnp.int32), jnp.asarray([[8]], jnp.int32),
        jnp.asarray([slots(0, [8])], jnp.int32), jnp.asarray(bt[0:1]),
        jnp.asarray([9], jnp.int32), jnp.asarray([0], jnp.int32),
        kcb, vcb, cfg=cfg, block_size=bs)
    lg_ch, kcb, vcb = forward(
        params, jnp.asarray([[15, 16]], jnp.int32),
        jnp.asarray([[3, 4]], jnp.int32),
        jnp.asarray([slots(1, [3, 4])], jnp.int32), jnp.asarray(bt[1:2]),
        jnp.asarray([5], jnp.int32), jnp.asarray([1], jnp.int32),
        kcb, vcb, cfg=cfg, block_size=bs)

    # ragged: launch 1 packs both prompts as chunks of ONE launch;
    # launch 2 mixes a decode row (row 0) with a prefill chunk (row 1)
    step = make_ragged_step_fn(cfg, bs)
    kc, vc = allocate_device_cache(cfg, num_blocks, bs, dtype=jnp.float32)

    def pack(work):  # work: list of (cache_row, tokens, positions)
        T = sum(len(t) for _, t, _ in work)
        C, S_C = ragged_grid_shape(T)
        ints5 = np.zeros((5, T), np.int32)
        ints5[3] = C  # decode/padding tokens route to the dump tile
        rows3 = np.zeros((len(work), 3), np.int32)
        grid_rows = np.zeros((C,), np.int32)
        t = tile = 0
        for i, (b, toks, poss) in enumerate(work):
            q = len(toks)
            rows3[i] = (t, q, poss[-1] + 1)
            ints5[0, t:t + q] = toks
            ints5[1, t:t + q] = poss
            ints5[2, t:t + q] = slots(b, poss)
            if q > 1:
                for off in range(0, q, S_C):
                    w = min(S_C, q - off)
                    grid_rows[tile] = i
                    ints5[3, t + off:t + off + w] = tile
                    ints5[4, t + off:t + off + w] = np.arange(w)
                    tile += 1
            t += q
        return (jnp.asarray(ints5), jnp.asarray(rows3),
                jnp.asarray(grid_rows))

    i5, r3, gr = pack([(0, rows[0], list(range(8))),
                       (1, rows[1], list(range(3)))])
    lg1, kc, vc = step(params, i5, r3, gr, jnp.asarray(bt), kc, vc)
    for b in range(B):
        np.testing.assert_allclose(np.asarray(lg1[b]), want[b],
                                   atol=1e-4, rtol=1e-3)
        assert int(np.argmax(lg1[b])) == int(np.argmax(want[b]))

    i5, r3, gr = pack([(0, [54], [8]), (1, [15, 16], [3, 4])])
    lg2, kc, vc = step(params, i5, r3, gr, jnp.asarray(bt), kc, vc)
    np.testing.assert_allclose(np.asarray(lg2[0]), np.asarray(lg_dec[0]),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(lg2[1]), np.asarray(lg_ch[0]),
                               atol=1e-4, rtol=1e-3)


def test_mla_pallas_decode_matches_xla():
    """The Pallas latent-decode kernel (interpret mode on CPU) must equal
    the XLA gather path bit-for-bit-ish on a lane-aligned config."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.cache import allocate_device_cache
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.model import forward, init_params
    from dynamo_tpu.ops.paged_attention import mla_pallas_supported

    cfg = ModelConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96, num_layers=2,
        num_heads=4, num_kv_heads=4, dtype="float32",
        max_position_embeddings=256,
        kv_lora_rank=128, q_lora_rank=None, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16)
    assert mla_pallas_supported(cfg.kv_lora_rank, cfg.rope_cache_dim)
    params = init_params(cfg, jax.random.key(3), dtype=jnp.float32)

    # prefill 9 tokens (XLA path), then one decode step both ways
    row = [5, 9, 17, 23, 42, 77, 101, 3, 54]
    (tokens, positions, slot_map, bt, kv_lens, last_idx,
     num_blocks) = _paged_inputs(cfg, [row])
    caches = {}
    for name in ("xla", "pallas"):
        kc, vc = allocate_device_cache(cfg, num_blocks, 4, dtype=jnp.float32)
        _, kc, vc = forward(params, tokens, positions, slot_map, bt, kv_lens,
                            last_idx, kc, vc, cfg=cfg, block_size=4)
        caches[name] = (kc, vc)

    tok = jnp.asarray([[61]], jnp.int32)
    pos = jnp.asarray([[9]], jnp.int32)
    slot = jnp.asarray([[int(bt[0, 2]) * 4 + 1]], jnp.int32)
    lens = jnp.asarray([10], jnp.int32)
    li = jnp.asarray([0], jnp.int32)
    outs = {}
    for name, up in (("xla", False), ("pallas", True)):
        kc, vc = caches[name]
        logits, _, _ = forward(params, tok, pos, slot, bt, lens, li, kc, vc,
                               cfg=cfg, block_size=4, use_pallas=up)
        outs[name] = np.asarray(logits)
    np.testing.assert_allclose(outs["pallas"], outs["xla"],
                               atol=1e-4, rtol=1e-4)


def test_mla_pallas_decode_sharded():
    """Pallas latent decode through shard_map on a dp×tp mesh equals the
    unsharded XLA result (heads shard on tp, latent cache replicated)."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.cache import allocate_device_cache
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.model import forward, init_params, param_shardings
    from dynamo_tpu.parallel import MeshConfig, make_mesh

    cfg = ModelConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96, num_layers=2,
        num_heads=4, num_kv_heads=4, dtype="float32",
        max_position_embeddings=256,
        kv_lora_rank=128, q_lora_rank=None, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16)
    params = init_params(cfg, jax.random.key(3), dtype=jnp.float32)

    row = [5, 9, 17, 23, 42, 77, 101, 3]
    (tokens, positions, slot_map, bt, kv_lens, last_idx,
     num_blocks) = _paged_inputs(cfg, [row, [int(x) + 1 for x in row]])
    kc, vc = allocate_device_cache(cfg, num_blocks, 4, dtype=jnp.float32)
    want, _, _ = forward(params, tokens, positions, slot_map, bt, kv_lens,
                         last_idx, kc, vc, cfg=cfg, block_size=4)

    mesh = make_mesh(MeshConfig(dp=2, sp=1, tp=2))
    sparams = jax.device_put(params, param_shardings(cfg, mesh))
    kc2, vc2 = allocate_device_cache(cfg, num_blocks, 4, mesh=mesh,
                                     dtype=jnp.float32)
    got, _, _ = forward(sparams, tokens, positions, slot_map, bt, kv_lens,
                        last_idx, kc2, vc2, cfg=cfg, block_size=4,
                        use_pallas=True, mesh=mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.slow
def test_mla_flash_prefill_matches_xla():
    """The latent flash-prefill kernel (interpret mode on CPU) must equal
    the XLA score-materializing path — logits AND the written caches —
    including a SECOND chunk attending back over the first (pos_base > 0,
    the chunked-prefill case the online softmax must get right)."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.cache import allocate_device_cache
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.model import forward, init_params

    cfg = ModelConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96, num_layers=2,
        num_heads=4, num_kv_heads=4, dtype="float32",
        max_position_embeddings=256,
        kv_lora_rank=128, q_lora_rank=None, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16)
    params = init_params(cfg, jax.random.key(3), dtype=jnp.float32)

    rows = [[5, 9, 17, 23, 42, 77, 101, 3],
            [6, 10, 18, 24, 43, 78, 102, 4]]
    (tokens, positions, slot_map, bt, kv_lens, last_idx,
     num_blocks) = _paged_inputs(cfg, rows, block_size=4)
    outs = {}
    for flash in (False, True):
        kc, vc = allocate_device_cache(cfg, num_blocks, 4, dtype=jnp.float32)
        logits, kc, vc = forward(
            params, tokens, positions, slot_map, bt, kv_lens, last_idx,
            kc, vc, cfg=cfg, block_size=4, use_flash_prefill=flash)
        # second chunk: 4 more tokens per row at positions 8..11
        t2 = jnp.asarray([[11, 12, 13, 14], [15, 16, 17, 18]], jnp.int32)
        p2 = jnp.asarray([[8, 9, 10, 11]] * 2, jnp.int32)
        s2 = jnp.stack([bt[:, 2] * 4 + j for j in range(4)], axis=1)
        l2 = jnp.asarray([12, 12], jnp.int32)
        li2 = jnp.asarray([3, 3], jnp.int32)
        logits2, kc, vc = forward(
            params, t2, p2, s2.astype(jnp.int32), bt, l2, li2, kc, vc,
            cfg=cfg, block_size=4, use_flash_prefill=flash)
        outs[flash] = (np.asarray(logits), np.asarray(logits2),
                       np.asarray(kc), np.asarray(vc))
    for a, b in zip(outs[False], outs[True]):
        np.testing.assert_allclose(b, a, atol=1e-4, rtol=1e-4)


def test_mla_flash_prefill_sharded():
    """Latent flash prefill through shard_map on a dp×tp mesh equals the
    unsharded XLA result (heads shard on tp, latent stream replicated)."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.cache import allocate_device_cache
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.model import forward, init_params, param_shardings
    from dynamo_tpu.parallel import MeshConfig, make_mesh

    cfg = ModelConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96, num_layers=2,
        num_heads=4, num_kv_heads=4, dtype="float32",
        max_position_embeddings=256,
        kv_lora_rank=128, q_lora_rank=None, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16)
    params = init_params(cfg, jax.random.key(4), dtype=jnp.float32)

    row = [5, 9, 17, 23, 42, 77, 101, 3]
    (tokens, positions, slot_map, bt, kv_lens, last_idx,
     num_blocks) = _paged_inputs(cfg, [row, [int(x) + 1 for x in row]])
    kc, vc = allocate_device_cache(cfg, num_blocks, 4, dtype=jnp.float32)
    want, _, _ = forward(params, tokens, positions, slot_map, bt, kv_lens,
                         last_idx, kc, vc, cfg=cfg, block_size=4)

    mesh = make_mesh(MeshConfig(dp=2, sp=1, tp=2))
    sparams = jax.device_put(params, param_shardings(cfg, mesh))
    kc2, vc2 = allocate_device_cache(cfg, num_blocks, 4, mesh=mesh,
                                     dtype=jnp.float32)
    got, _, _ = forward(sparams, tokens, positions, slot_map, bt, kv_lens,
                        last_idx, kc2, vc2, cfg=cfg, block_size=4,
                        use_flash_prefill=True, mesh=mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)
