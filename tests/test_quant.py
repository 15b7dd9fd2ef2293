"""On-device quantized serving: weights resident int8/int4 in HBM with
dequant riding the matmul (engine/quant.py).

Ref capability: the reference's flagship recipes serve quantized
checkpoints (FP8 70B: recipes/llama-3-70b/vllm/disagg-single-node/
deploy.yaml:21-86; MXFP4 gpt-oss: recipes/gpt-oss-120b/trtllm/agg/).
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import model as M
from dynamo_tpu.engine import quant as Q
from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import AsyncJaxEngine
from dynamo_tpu.protocols import (
    PreprocessedRequest, SamplingOptions, StopConditions,
)

pytestmark = pytest.mark.anyio


def test_quantize_roundtrip_per_channel():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((64, 48)), jnp.float32)
    qt = Q.quantize(w, bits=8)
    assert qt["q"].dtype == jnp.int8
    assert qt["s"].shape == (1, 48)
    back = Q.dequantize(qt)
    # 8-bit symmetric round-trip: ~qstep/2 of the channel max
    err = np.abs(np.asarray(back) - np.asarray(w))
    ceil = np.max(np.abs(np.asarray(w)), axis=0) / 127
    assert (err <= ceil[None, :] * 0.51).all()


def test_quantize_grouped_and_int4():
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    g8 = Q.quantize(w, bits=8, group=16)
    assert g8["s"].shape == (4, 32)
    assert np.abs(np.asarray(Q.dequantize(g8)) - np.asarray(w)).max() < 0.05
    g4 = Q.quantize(w, bits=4, group=16)
    assert g4["q"].dtype == jnp.int4
    # 4-bit: coarse but bounded by group-max/7
    err = np.abs(np.asarray(Q.dequantize(g4)) - np.asarray(w))
    assert err.max() < np.abs(np.asarray(w)).max() / 7 * 0.51 + 1e-6


def test_qmm_matches_dequant():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((5, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 48)), jnp.float32)
    for kw in (dict(bits=8), dict(bits=8, group=16), dict(bits=4, group=16)):
        qt = Q.quantize(w, **kw)
        np.testing.assert_allclose(np.asarray(Q.qmm(x, qt)),
                                   np.asarray(x @ Q.dequantize(qt)),
                                   rtol=2e-5, atol=2e-5)
    # stacked-layer shape [n, I, O] (scan slices feed qmm per layer)
    ws = jnp.asarray(rng.standard_normal((3, 64, 48)), jnp.float32)
    qt = Q.quantize(ws, bits=8)
    assert qt["s"].shape == (3, 1, 48)
    np.testing.assert_allclose(
        np.asarray(Q.qmm(x, {"q": qt["q"][1], "s": qt["s"][1]})),
        np.asarray(x @ Q.dequantize(qt)[1]), rtol=2e-5, atol=2e-5)


SPECS = [dict(bits=8), dict(bits=8, group=16), dict(bits=4, group=16)]
SPEC_IDS = ["int8", "int8-g16", "int4-g16"]


@pytest.mark.parametrize("kw", SPECS, ids=SPEC_IDS)
def test_head_major_quantize_is_the_in_out_one_transposed(kw):
    """An attention projection stored [heads, width, D] and quantized over
    its last axis holds the numbers of the [D, heads·width] QTensor of the
    same matrix, transposed — bit for bit, scales over the same elements —
    and qmm_heads gives the projection that qmm gave, by heads."""
    rng = np.random.default_rng(4)
    H, hd, D = 4, 12, 64
    w_hm = jnp.asarray(rng.standard_normal((3, H, hd, D)), jnp.float32)
    w_io = w_hm.reshape(3, H * hd, D).swapaxes(-1, -2)  # [L, D, H·hd]
    x = jnp.asarray(rng.standard_normal((5, D)), jnp.float32)
    hm, io = Q.quantize(w_hm, axis=-1, **kw), Q.quantize(w_io, **kw)
    G = D // kw.get("group", D)
    assert hm["q"].shape == (3, H, hd, D) and hm["s"].shape == (3, H, hd, G)
    assert Q.contraction_axis("wq") == -1 and Q.contraction_axis("wo") == -2
    for k in ("q", "s"):
        np.testing.assert_array_equal(
            np.asarray(hm[k].astype(jnp.float32)),
            np.asarray(io[k].astype(jnp.float32)
                       .swapaxes(-1, -2).reshape(hm[k].shape)))
    np.testing.assert_array_equal(
        np.asarray(Q.dequantize(hm, axis=-1)),
        np.asarray(Q.dequantize(io).swapaxes(-1, -2).reshape(w_hm.shape)))
    layer = lambda qt: {k: v[1] for k, v in qt.items()}  # noqa: E731
    np.testing.assert_allclose(
        np.asarray(Q.qmm_heads(x, layer(hm))),
        np.asarray(Q.qmm(x, layer(io)).reshape(5, H, hd)),
        rtol=2e-5, atol=2e-5)
    # numpy (checkpoint loaders) and jax quantize alike
    host = Q.quantize(np.asarray(w_hm), axis=-1, **kw)
    for k in ("q", "s"):
        np.testing.assert_array_equal(np.asarray(host[k].astype(jnp.float32)),
                                      np.asarray(hm[k].astype(jnp.float32)))
    # an affine format's zero points lie with the scales, either way
    z = jnp.asarray(rng.uniform(0, 0.5, io["s"].shape), jnp.float32)
    io["z"], hm["z"] = z, z.swapaxes(-1, -2).reshape(hm["s"].shape)
    np.testing.assert_array_equal(
        np.asarray(Q.dequantize(hm, axis=-1)),
        np.asarray(Q.dequantize(io).swapaxes(-1, -2).reshape(w_hm.shape)))
    np.testing.assert_allclose(
        np.asarray(Q.qmm_heads(x, layer(hm))),
        np.asarray(Q.qmm(x, layer(io)).reshape(5, H, hd)),
        rtol=2e-5, atol=2e-5)


def test_qmm_heads_plain_weight_is_the_matmul_by_heads():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((2, 5, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((4, 12, 64)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(Q.qmm_heads(x, w)),
        np.asarray((x @ w.reshape(48, 64).T).reshape(2, 5, 4, 12)),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("spec", ["int8", "int8-g16", "int4-g16"])
def test_quantize_params_groups_each_weight_along_its_contraction(spec):
    """The walk quantizes wq/wk/wv along their last axis and every other
    matmul weight along its second-to-last; the abstract walk (AOT compile
    proofs) lays out the same shapes."""
    cfg = ModelConfig.tiny()
    params = jax.tree.map(np.asarray, M.init_params(cfg, jax.random.key(0)))
    L, H, KV, hd, D = (cfg.num_layers, cfg.num_heads, cfg.num_kv_heads,
                       cfg.head_dim, cfg.hidden_size)
    assert params["layers"]["wq"].shape == (L, H, hd, D)
    assert params["layers"]["wk"].shape == (L, KV, hd, D)
    assert params["layers"]["wv"].shape == (L, KV, hd, D)
    assert params["layers"]["wo"].shape == (L, H * hd, D)
    G = 1 if spec == "int8" else D // 16
    real = Q.quantize_params(params, spec)
    abstract = Q.quantize_params_abstract(
        jax.eval_shape(lambda: M.init_params(cfg, jax.random.key(0))), spec)
    for tree in (real, abstract):
        lay = tree["layers"]
        assert lay["wq"]["s"].shape == (L, H, hd, G)
        assert lay["wk"]["s"].shape == (L, KV, hd, G)
        assert lay["wo"]["s"].shape == (L, G, D)
        assert lay["w_down"]["q"].shape == (L, cfg.intermediate_size, D)
    shapes = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype), t)  # noqa: E731
    assert shapes(real) == shapes(abstract)
    # what init_params quantizes on the device is what the host walk gives
    built = M.init_params(cfg, jax.random.key(0), quantization=spec)
    for k in ("wq", "wv", "wo"):
        for f in ("q", "s"):
            np.testing.assert_array_equal(
                np.asarray(built["layers"][k][f].astype(jnp.float32)),
                np.asarray(real["layers"][k][f].astype(jnp.float32)))


@pytest.mark.parametrize("spec,element_bytes", [("int8", 1.0),
                                                 ("int4-g32", 0.5)])
def test_quantized_weights_cash_their_bytes(spec, element_bytes):
    """A decode step streams every weight once, so the stored bytes are its
    roofline: no matmul weight of the layer stack falls back to full width
    in silence. Each is held at the format's bytes an element plus its
    scales (f32: 4/in_dim an element per-channel, 4/32 grouped)."""
    from dynamo_tpu.engine.cache import tree_nbytes

    cfg = ModelConfig.tiny()
    params = jax.tree.map(np.asarray, M.init_params(cfg, jax.random.key(0)))
    quant = Q.quantize_params(params, spec)
    matmuls = {k: w for k, w in params["layers"].items() if w.ndim >= 3}
    assert set(matmuls) >= {"wq", "wk", "wv", "wo", "w_gate", "w_up",
                            "w_down"}
    for key, full in matmuls.items():
        assert (tree_nbytes(quant["layers"][key])
                <= full.size * (element_bytes + 0.126)), key
    assert tree_nbytes(quant) * 1.5 <= tree_nbytes(params)


def _small_preset(name):
    import dataclasses

    from dynamo_tpu import models

    if name == "qwen2_bias":
        return dataclasses.replace(ModelConfig.tiny(), qkv_bias=True)
    if name == "mla":  # DeepSeek-V2-Lite's form: a plain wq, no q_a/q_b
        return dataclasses.replace(models.mla_tiny(), q_lora_rank=None)
    return models.PRESETS[name]()


@pytest.mark.parametrize("preset,quantization", [
    ("tiny", None), ("tiny", "int8"), ("qwen2_bias", None),
    ("qwen2_bias", "int8"), ("mla", None), ("mla", "int8"),
    ("mimo_tiny", None)])  # MiMo's stacks are served in bf16
def test_init_gives_the_in_out_weights_turned(preset, quantization):
    """init_params draws the numbers it drew when wq/wk/wv lay [L, D,
    heads·width] — the same key, the same [L, D, O] draw, the same
    quantization along D — and hands them out head-major, element for
    element (the benchmark's probe is greedy over the nearly flat logits of
    random weights: other weights, or one rounding gone the other way, take
    it down another path). wo, beside them, is drawn as it was."""
    cfg = _small_preset(preset)
    dtype = jnp.bfloat16
    key = jax.random.key(7)
    params = M.init_params(cfg, key, dtype, quantization=quantization)
    D, H = cfg.hidden_size, cfg.num_heads
    k_layers = jax.random.split(key, 4)[1]
    if cfg.layer_kinds is not None:  # one stack a (kind, dense | experts)
        stacks = M.layer_stacks(cfg)
        cases = [(lay, k, len(st.layers), cfg.layer_kinds[st.kind].num_kv_heads)
                 for lay, k, st in zip(
                     params["stacks"],
                     jax.random.split(k_layers, len(stacks)), stacks)]
    else:
        cases = [(params["layers"], k_layers,
                  cfg.num_layers - cfg.num_dense_prefix_layers,
                  cfg.num_kv_heads)]

    def drawn(k, shape, fan_in):
        w = (jax.random.normal(k, shape, jnp.float32)
             / np.float32(np.sqrt(fan_in))).astype(dtype)
        return Q.quantize(w, bits=8) if quantization else w

    def same(a, b):
        fields = ("q", "s") if quantization else (None,)
        for f in fields:
            x, y = (a[f], b[f]) if f else (a, b)
            assert x.shape == y.shape and x.dtype == y.dtype
            np.testing.assert_array_equal(np.asarray(x.astype(jnp.float32)),
                                          np.asarray(y.astype(jnp.float32)))

    for got, k, n, KV in cases:
        ks = jax.random.split(k, 16)
        if cfg.is_mla:
            want = {"wq": (ks[0], H,
                           cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)}
        else:
            want = {"wq": (ks[0], H, cfg.head_dim),
                    "wk": (ks[1], KV, cfg.head_dim),
                    "wv": (ks[2], KV, cfg.v_dim)}
        assert Q.HEAD_MAJOR_KEYS & set(got) == set(want)
        for name, (kk, heads, width) in want.items():
            old = drawn(kk, (n, D, heads * width), D)  # s: [n, 1, O]
            turned = jax.tree.map(
                lambda a: a.swapaxes(1, 2).reshape(n, heads, width, -1), old)
            leaf = got[name]["q"] if quantization else got[name]
            assert leaf.shape == (n, heads, width, D)
            same(got[name], turned)
        same(got["wo"], drawn(ks[3], (n, H * cfg.v_dim, D), H * cfg.v_dim))


def test_head_major_projections_shard_whole_heads_under_tp4():
    """tp moves to the heads axis: q and its scales hold a quarter of the
    heads a device (the scales' grouped axis, now the last, replicated);
    tiny's 2 KV heads do not divide 4 ranks and stay whole."""
    from jax.sharding import PartitionSpec as P

    from dynamo_tpu.parallel import MeshConfig, make_mesh

    cfg = ModelConfig.tiny()
    mesh = make_mesh(MeshConfig(dp=1, tp=4), jax.devices()[:4])
    sh = M.param_shardings(cfg, mesh)["layers"]
    assert sh["wq"].spec == P(None, "tp", None, None)
    assert sh["wk"].spec == sh["wv"].spec == P(None, None, None, None)
    assert sh["wo"].spec == P(None, "tp", None)
    params = jax.eval_shape(lambda: M.init_params(
        cfg, jax.random.key(0), quantization="int8-g16"))
    qsh = Q.quant_shardings(M.param_shardings(cfg, mesh), params)["layers"]
    assert qsh["wq"]["q"].spec == qsh["wq"]["s"].spec == P(
        None, "tp", None, None)
    assert qsh["wo"]["q"].spec == P(None, "tp", None)
    assert qsh["wo"]["s"].spec == P(None, None, None)


def test_affine_zero_point():
    """GGUF K-quants are affine (w = s·q − z): the z path must dequantize
    exactly."""
    rng = np.random.default_rng(3)
    q = rng.integers(0, 15, (32, 8)).astype(np.float32)
    s = rng.uniform(0.01, 0.1, (2, 8)).astype(np.float32)
    z = rng.uniform(0, 0.5, (2, 8)).astype(np.float32)
    qt = {"q": jnp.asarray(q, jnp.int8), "s": jnp.asarray(s),
          "z": jnp.asarray(z)}
    want = q * np.repeat(s, 16, axis=0) - np.repeat(z, 16, axis=0)
    np.testing.assert_allclose(np.asarray(Q.dequantize(qt)), want, rtol=1e-6)
    x = jnp.asarray(rng.standard_normal((4, 32)), jnp.float32)
    np.testing.assert_allclose(np.asarray(Q.qmm(x, qt)),
                               np.asarray(x) @ want, rtol=1e-4, atol=1e-4)


def test_spec_parsing():
    assert Q.parse_spec("int8") == (8, None)
    assert Q.parse_spec("int8-g128") == (8, 128)
    assert Q.parse_spec("int4-g32") == (4, 32)
    with pytest.raises(ValueError):
        Q.parse_spec("int4")  # groups required at 4 bits
    with pytest.raises(ValueError):
        Q.parse_spec("fp8")


@pytest.mark.parametrize("spec", ["int8", "int8-g16"])
def test_forward_parity_quantized(spec):
    """Quantized forward ≈ forward against the host-dequantized weights —
    the dequant-in-matmul path must introduce NO error beyond quantization
    itself (compared exactly, not loosely)."""
    cfg = ModelConfig.tiny()
    params = M.init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    qparams = Q.quantize_params(jax.tree.map(np.asarray, params), spec)
    deq = {k: ({kk: (Q.dequantize(vv, jnp.float32, Q.contraction_axis(kk))
                     if Q.is_qtensor(vv) else vv)
                for kk, vv in v.items()} if isinstance(v, dict) else
               (Q.dequantize(v, jnp.float32) if Q.is_qtensor(v) else v))
           for k, v in qparams.items()}

    B, S = 2, 8
    block_size = 4
    W = 4
    nb = B * W + 1
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        1, cfg.vocab_size, (B, S)), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S)).astype(jnp.int32)
    bt = np.zeros((B, W), np.int32)
    for i in range(B):
        bt[i] = 1 + i * W + np.arange(W)
    slot = (jnp.asarray(bt)[:, :, None] * block_size
            + jnp.arange(block_size)[None, None, :]).reshape(B, W * block_size)
    slot_map = slot[:, :S]
    kv_lens = jnp.full((B,), S, jnp.int32)
    last_idx = jnp.full((B,), S - 1, jnp.int32)
    shape = (cfg.num_layers, nb * block_size, cfg.num_kv_heads, cfg.head_dim)

    def run(p):
        kc = jnp.zeros(shape, jnp.float32)
        vc = jnp.zeros(shape, jnp.float32)
        logits, _, _ = M.forward(
            p, tokens, positions, slot_map, jnp.asarray(bt), kv_lens,
            last_idx, kc, vc, cfg=cfg, block_size=block_size)
        return np.asarray(logits)

    np.testing.assert_allclose(run(qparams), run(deq), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("spec", ["int8", "int8-g16"])
async def test_engine_serves_quantized(spec):
    """Full engine e2e with int8 weights: deterministic generation, and the
    params tree really is int8-resident."""
    cfg = ModelConfig.tiny()
    args = EngineArgs(block_size=4, num_blocks=128, max_num_seqs=4,
                      max_num_batched_tokens=64, max_model_len=128,
                      quantization=spec)
    eng = AsyncJaxEngine(cfg, args)
    try:
        qleaves = [v for v in eng.params["layers"].values()
                   if Q.is_qtensor(v)]
        assert qleaves, "no quantized leaves in served params"
        assert all(v["q"].dtype == jnp.int8 for v in qleaves)
        r = PreprocessedRequest(
            model="tiny", token_ids=list(range(1, 17)),
            stop_conditions=StopConditions(max_tokens=8, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0))
        outs = []
        async for out in eng.generate(r):
            outs.extend(out.token_ids)
        assert len(outs) == 8
        outs2 = []
        async for out in eng.generate(PreprocessedRequest(
                model="tiny", token_ids=list(range(1, 17)),
                stop_conditions=StopConditions(max_tokens=8, ignore_eos=True),
                sampling_options=SamplingOptions(temperature=0.0))):
            outs2.extend(out.token_ids)
        assert outs == outs2
    finally:
        await eng.close()


async def test_engine_quantized_under_mesh():
    """Quantized params shard over a (dp, tp) mesh: quant_shardings mirrors
    the weight sharding onto q and replicates the group dim of s."""
    from dynamo_tpu.parallel import MeshConfig, make_mesh

    cfg = ModelConfig.tiny()
    args = EngineArgs(block_size=4, num_blocks=128, max_num_seqs=4,
                      max_num_batched_tokens=64, max_model_len=128,
                      quantization="int8")
    params = M.init_params(cfg, jax.random.key(0))
    prompt = list(range(1, 17))
    mk = lambda: PreprocessedRequest(  # noqa: E731
        model="t", token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=8, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0))

    async def run(mesh):
        eng = AsyncJaxEngine(cfg, args, params=jax.tree.map(np.copy, params),
                             mesh=mesh)
        got = []
        async for out in eng.generate(mk()):
            got.extend(out.token_ids)
        await eng.close()
        return got

    base = await run(None)
    tp = await run(make_mesh(MeshConfig(dp=1, tp=2)))
    assert tp == base
