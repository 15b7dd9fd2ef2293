"""GGUF parsing + model resolution (ref: lib/llm/src/gguf/*.rs, hub.rs).

A tiny GGUF file is written in-test from the public spec, then parsed,
mapped to ModelConfig, its tokenizer rebuilt, its tensors loaded, and the
whole thing served through the engine for a greedy generate."""

import asyncio
import os
import struct

import numpy as np
import pytest

from dynamo_tpu.llm.gguf import (
    GGUFFile, config_from_gguf, eos_ids_from_gguf, load_gguf_params,
    tokenizer_from_gguf,
)
from dynamo_tpu.llm.resolve import resolve_model

pytestmark = pytest.mark.anyio

_U32, _F32, _BOOL, _STR, _ARR, _U64 = 4, 6, 7, 8, 9, 10


def _s(x: str) -> bytes:
    b = x.encode()
    return struct.pack("<Q", len(b)) + b


def _kv(key: str, vtype: int, value) -> bytes:
    out = _s(key) + struct.pack("<I", vtype)
    if vtype == _U32:
        out += struct.pack("<I", value)
    elif vtype == _F32:
        out += struct.pack("<f", value)
    elif vtype == _STR:
        out += _s(value)
    elif vtype == _ARR:
        etype, items = value
        out += struct.pack("<IQ", etype, len(items))
        for it in items:
            if etype == _STR:
                out += _s(it)
            elif etype == _F32:
                out += struct.pack("<f", it)
            elif etype == _U32:
                out += struct.pack("<I", it)
    return out


# a byte-level BPE over a toy vocab: base bytes for "abch i" + merges
_TOKENS = ["<unk>", "<s>", "</s>", "a", "b", "c", "h", "i", "Ġ", "hi", "Ġhi",
           "ab", "abc"]
_MERGES = ["h i", "Ġ hi", "a b", "ab c"]


def write_tiny_gguf(path: str, seed: int = 0, D: int = 16) -> dict:
    """Valid GGUF v3 file: llama arch metadata + gpt2 tokenizer + f32
    weights in llama.cpp tensor naming. Returns the tensor dict. ``D``:
    the hidden size (a multiple of 32 lets q8_0 take the attention
    projections too)."""
    rng = np.random.default_rng(seed)
    F, L, H, KV, V = 32, 2, 4, 2, len(_TOKENS)
    hd = D // H

    tensors: dict[str, np.ndarray] = {
        "token_embd.weight": rng.standard_normal((V, D), np.float32) * 0.1,
        "output_norm.weight": np.ones((D,), np.float32),
        "output.weight": rng.standard_normal((V, D), np.float32) * 0.1,
    }
    for i in range(L):
        tensors[f"blk.{i}.attn_norm.weight"] = np.ones((D,), np.float32)
        tensors[f"blk.{i}.ffn_norm.weight"] = np.ones((D,), np.float32)
        tensors[f"blk.{i}.attn_q.weight"] = rng.standard_normal((H * hd, D), np.float32) * 0.1
        tensors[f"blk.{i}.attn_k.weight"] = rng.standard_normal((KV * hd, D), np.float32) * 0.1
        tensors[f"blk.{i}.attn_v.weight"] = rng.standard_normal((KV * hd, D), np.float32) * 0.1
        tensors[f"blk.{i}.attn_output.weight"] = rng.standard_normal((D, H * hd), np.float32) * 0.1
        tensors[f"blk.{i}.ffn_gate.weight"] = rng.standard_normal((F, D), np.float32) * 0.1
        tensors[f"blk.{i}.ffn_up.weight"] = rng.standard_normal((F, D), np.float32) * 0.1
        tensors[f"blk.{i}.ffn_down.weight"] = rng.standard_normal((D, F), np.float32) * 0.1

    meta = b"".join([
        _kv("general.architecture", _STR, "llama"),
        _kv("llama.embedding_length", _U32, D),
        _kv("llama.feed_forward_length", _U32, F),
        _kv("llama.block_count", _U32, L),
        _kv("llama.attention.head_count", _U32, H),
        _kv("llama.attention.head_count_kv", _U32, KV),
        _kv("llama.context_length", _U32, 128),
        _kv("llama.rope.freq_base", _F32, 10000.0),
        _kv("llama.attention.layer_norm_rms_epsilon", _F32, 1e-5),
        _kv("tokenizer.ggml.model", _STR, "gpt2"),
        _kv("tokenizer.ggml.tokens", _ARR, (_STR, _TOKENS)),
        _kv("tokenizer.ggml.merges", _ARR, (_STR, _MERGES)),
        _kv("tokenizer.ggml.bos_token_id", _U32, 1),
        _kv("tokenizer.ggml.eos_token_id", _U32, 2),
        _kv("tokenizer.chat_template", _STR,
            "{% for m in messages %}{{ m['content'] }}{% endfor %}"),
    ])

    align = 32
    infos, data = b"", b""
    for name, arr in tensors.items():
        pad = (-len(data)) % align
        data += b"\0" * pad
        infos += (_s(name) + struct.pack("<I", arr.ndim)
                  + struct.pack(f"<{arr.ndim}Q", *reversed(arr.shape))
                  + struct.pack("<IQ", 0, len(data)))  # type 0 = F32
        data += arr.tobytes()

    header = (b"GGUF" + struct.pack("<I", 3)
              + struct.pack("<QQ", len(tensors), 15))
    body = header + meta + infos
    pad = (-len(body)) % align
    with open(path, "wb") as f:
        f.write(body + b"\0" * pad + data)
    return tensors


@pytest.fixture(scope="module")
def gguf_path(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("gguf") / "tiny-llama.gguf")
    tensors = write_tiny_gguf(path)
    return path, tensors


def test_parse_metadata_and_tensors(gguf_path):
    path, tensors = gguf_path
    g = GGUFFile.parse(path)
    assert g.version == 3 and g.architecture == "llama"
    assert g.metadata["llama.embedding_length"] == 16
    assert len(g.tensors) == len(tensors)
    for name, arr in tensors.items():
        got = g.load_tensor(name)
        assert got.shape == arr.shape
        np.testing.assert_array_equal(got, arr)


def test_config_and_eos(gguf_path):
    path, _ = gguf_path
    g = GGUFFile.parse(path)
    cfg = config_from_gguf(g)
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads,
            cfg.num_kv_heads) == (16, 2, 4, 2)
    assert cfg.vocab_size == len(_TOKENS)
    assert eos_ids_from_gguf(g) == [2]


def test_tokenizer_roundtrip(gguf_path):
    path, _ = gguf_path
    tk = tokenizer_from_gguf(GGUFFile.parse(path))
    ids = tk.encode("abc hi").ids
    assert tk.decode(ids) == "abc hi"
    assert tk.token_to_id("abc") == _TOKENS.index("abc")

    # the TokenizerWrapper path used by the frontend pipeline
    from dynamo_tpu.llm.tokenizer import TokenizerWrapper

    w = TokenizerWrapper.from_dir(path)
    assert w.chat_template and "messages" in w.chat_template
    assert w.decode(w.encode("hi ab", add_special_tokens=False)) == "hi ab"


def test_resolution_kinds(gguf_path, tmp_path):
    path, _ = gguf_path
    r = resolve_model(path)
    assert r.kind == "gguf"
    cfg = r.config()
    params = r.load_params(cfg)
    assert params["embed"].shape == (len(_TOKENS), 16)
    assert r.eos_token_ids() == [2]

    # a dir containing only the gguf resolves to it
    assert resolve_model(os.path.dirname(path)).kind == "gguf"
    with pytest.raises(FileNotFoundError):
        resolve_model(str(tmp_path / "nope"))
    with pytest.raises(FileNotFoundError):  # hermetic: no network attempt
        resolve_model("no-such-org/no-such-model-xyz", allow_download=False)


def test_unsupported_quant_refuses(gguf_path, tmp_path):
    path, _ = gguf_path
    g = GGUFFile.parse(path)
    g.tensors["token_embd.weight"].ggml_type = 16  # iq2_xxs: unsupported
    with pytest.raises(NotImplementedError):
        g.load_tensor("token_embd.weight")


async def test_engine_serves_gguf(gguf_path):
    """Greedy generate through the engine on params loaded from GGUF."""
    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.protocols import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )

    path, _ = gguf_path
    r = resolve_model(path)
    cfg = r.config()
    cfg.dtype = "float32"
    params = r.load_params(cfg)
    eng = AsyncJaxEngine(cfg, EngineArgs(
        block_size=4, num_blocks=32, max_num_seqs=2,
        max_num_batched_tokens=16, max_model_len=64,
        prefill_buckets=(8, 16), decode_batch_buckets=(1, 2)), params=params)
    req = PreprocessedRequest(
        model="gguf", token_ids=[1, 3, 4, 5],
        stop_conditions=StopConditions(max_tokens=4, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0))
    toks = []
    async for out in eng.generate(req):
        toks.extend(out.token_ids)
    assert len(toks) == 4
    await eng.close()


# ------------------------------------------------------ quant dequantization

def _scalar_q6k(block: bytes) -> np.ndarray:
    """Independent straight-from-spec scalar q6_K dequant to cross-check
    the vectorized loader path."""
    ql, qh = block[:128], block[128:192]
    sc = np.frombuffer(block[192:208], np.int8)
    d = float(np.frombuffer(block[208:210], np.float16)[0])
    y = np.zeros(256, np.float32)
    for half in range(2):
        for l in range(32):
            is_ = l // 16
            b0, b1 = ql[64 * half + l], ql[64 * half + 32 + l]
            h = qh[32 * half + l]
            q1 = ((b0 & 0xF) | (((h >> 0) & 3) << 4)) - 32
            q2 = ((b1 & 0xF) | (((h >> 2) & 3) << 4)) - 32
            q3 = ((b0 >> 4) | (((h >> 4) & 3) << 4)) - 32
            q4 = ((b1 >> 4) | (((h >> 6) & 3) << 4)) - 32
            s = sc[8 * half:]
            y[128 * half + l + 0] = d * s[is_ + 0] * q1
            y[128 * half + l + 32] = d * s[is_ + 2] * q2
            y[128 * half + l + 64] = d * s[is_ + 4] * q3
            y[128 * half + l + 96] = d * s[is_ + 6] * q4
    return y


def _scalar_q4k(block: bytes) -> np.ndarray:
    d = float(np.frombuffer(block[0:2], np.float16)[0])
    dmin = float(np.frombuffer(block[2:4], np.float16)[0])
    scales = block[4:16]
    qs = block[16:]

    def sm(j):
        if j < 4:
            return scales[j] & 63, scales[j + 4] & 63
        return ((scales[j + 4] & 0xF) | ((scales[j - 4] >> 6) << 4),
                (scales[j + 4] >> 4) | ((scales[j] >> 6) << 4))

    y = np.zeros(256, np.float32)
    pos = 0
    for j in range(4):
        s1, m1 = sm(2 * j)
        s2, m2 = sm(2 * j + 1)
        chunk = qs[32 * j:32 * (j + 1)]
        for q in chunk:
            y[pos] = d * s1 * (q & 0xF) - dmin * m1
            pos += 1
        for q in chunk:
            y[pos] = d * s2 * (q >> 4) - dmin * m2
            pos += 1
    return y


def test_q8_0_q4_0_roundtrip():
    """Quantize synthetic rows in the documented formats; dequant must
    recover within the format's quantization error."""
    from dynamo_tpu.llm.gguf import GGML_QUANTS, GGML_Q4_0, GGML_Q8_0

    rng = np.random.default_rng(3)
    x = rng.standard_normal((4, 64)).astype(np.float32)

    # q8_0 encoder: per-32 block, d = max|x|/127, q = round(x/d)
    blocks = []
    for row in x.reshape(-1, 32):
        d = np.abs(row).max() / 127.0
        q = np.clip(np.round(row / d), -127, 127).astype(np.int8)
        blocks.append(np.float16(d).tobytes() + q.tobytes())
    _, _, deq = GGML_QUANTS[GGML_Q8_0]
    out = deq(np.frombuffer(b"".join(blocks), np.uint8).reshape(-1, 34))
    np.testing.assert_allclose(out.reshape(x.shape), x, atol=0.02)

    # q4_0 encoder: d = -max|x|/8 convention is ggml's; use d = max|x|/7
    # with the (q-8) decode — valid blocks even if not bit-identical to
    # llama.cpp's chosen scale
    blocks = []
    for row in x.reshape(-1, 32):
        d = np.abs(row).max() / 7.0
        q = np.clip(np.round(row / d) + 8, 0, 15).astype(np.uint8)
        packed = (q[:16] | (q[16:] << 4)).astype(np.uint8)  # low|high nibble
        blocks.append(np.float16(d).tobytes() + packed.tobytes())
    _, _, deq = GGML_QUANTS[GGML_Q4_0]
    out = deq(np.frombuffer(b"".join(blocks), np.uint8).reshape(-1, 18))
    # error bound is d/2 = max|row|/14 — worst row here has max|x| ~3.3
    np.testing.assert_allclose(out.reshape(x.shape), x, atol=0.3)


def test_k_quants_match_scalar_reference():
    rng = np.random.default_rng(9)
    from dynamo_tpu.llm.gguf import GGML_QUANTS, GGML_Q4_K, GGML_Q6_K

    raw6 = rng.integers(0, 256, (3, 210), dtype=np.uint8)
    raw6[:, 208:210] = np.frombuffer(
        np.full(3, 0.02, np.float16).tobytes(), np.uint8).reshape(3, 2)
    _, _, deq6 = GGML_QUANTS[GGML_Q6_K]
    got = deq6(raw6.copy())
    for i in range(3):
        np.testing.assert_allclose(got[i], _scalar_q6k(raw6[i].tobytes()),
                                   rtol=1e-5, atol=1e-6)

    raw4 = rng.integers(0, 256, (3, 144), dtype=np.uint8)
    half = np.frombuffer(np.full(3, 0.01, np.float16).tobytes(),
                         np.uint8).reshape(3, 2)
    raw4[:, 0:2] = half
    raw4[:, 2:4] = half
    _, _, deq4 = GGML_QUANTS[GGML_Q4_K]
    got = deq4(raw4.copy())
    for i in range(3):
        np.testing.assert_allclose(got[i], _scalar_q4k(raw4[i].tobytes()),
                                   rtol=1e-5, atol=1e-6)


def write_q8_gguf(f32_path: str, qpath: str, tensors: dict) -> None:
    """Re-encode every (n, 32k)-shaped matrix of a written f32 GGUF as
    q8_0 (shared by the loader test and the e2e serve test)."""
    from dynamo_tpu.llm.gguf import GGML_Q8_0

    def q8(arr):
        rows = arr.reshape(-1, 32)
        d = np.abs(rows).max(axis=1, keepdims=True) / 127.0
        d = np.where(d == 0, 1e-8, d)
        q = np.clip(np.round(rows / d), -127, 127).astype(np.int8)
        blocks = np.concatenate(
            [d.astype(np.float16).view(np.uint8), q.view(np.uint8)], axis=1)
        return blocks.tobytes()

    with open(f32_path, "rb") as f:
        head = f.read()
    align, infos, data = 32, b"", b""
    for name, arr in tensors.items():
        pad = (-len(data)) % align
        data += b"\0" * pad
        quantize = arr.ndim == 2 and arr.shape[-1] % 32 == 0
        infos += (_s(name) + struct.pack("<I", arr.ndim)
                  + struct.pack(f"<{arr.ndim}Q", *reversed(arr.shape))
                  + struct.pack("<IQ", GGML_Q8_0 if quantize else 0,
                                len(data)))
        data += q8(arr) if quantize else arr.tobytes()
    # reuse the metadata bytes from the f32 file
    n_kv = struct.unpack("<Q", head[16:24])[0]
    meta = head[24:g0_meta_end(f32_path)]
    header = b"GGUF" + struct.pack("<I", 3) + struct.pack(
        "<QQ", len(tensors), n_kv)
    body = header + meta + infos
    pad = (-len(body)) % align
    with open(qpath, "wb") as f:
        f.write(body + b"\0" * pad + data)


def test_quantized_gguf_serves(tmp_path):
    """A GGUF whose big matrices are q8_0 must load and produce logits
    close to the f32 original through the real loader path."""
    import jax.numpy as jnp

    from dynamo_tpu.llm.gguf import (
        GGUFFile, config_from_gguf, load_gguf_params,
    )

    f32 = str(tmp_path / "f32.gguf")
    tensors = write_tiny_gguf(f32)
    qpath = str(tmp_path / "q8.gguf")
    write_q8_gguf(f32, qpath, tensors)

    g = GGUFFile.parse(qpath)
    cfg = config_from_gguf(g)
    cfg.dtype = "float32"
    params = load_gguf_params(g, cfg, dtype=jnp.float32)
    from dynamo_tpu.engine import quant as Q

    node = params["layers"]["w_down"]
    # Q8_0 weights stay QUANTIZED in HBM: grouped-int8 QTensor with the
    # ggml per-32 scales, never widened past 1 B/weight
    assert Q.is_qtensor(node)
    assert node["q"].dtype == jnp.int8
    assert node["s"].shape[-2] * 32 == node["q"].shape[-2]
    w = np.asarray(Q.dequantize(node, jnp.float32)[0])
    ref = tensors["blk.0.ffn_down.weight"].T  # [F=32, D] rows are aligned
    np.testing.assert_allclose(w, ref, atol=0.02)
    assert np.abs(w - ref).max() > 0  # the quantized path really ran
    # bit-identical to the legacy dequantize-at-load path
    import os

    os.environ["DYN_GGUF_DEQUANT"] = "1"
    try:
        legacy = load_gguf_params(GGUFFile.parse(qpath), cfg,
                                  dtype=jnp.float32)
    finally:
        del os.environ["DYN_GGUF_DEQUANT"]
    np.testing.assert_array_equal(w, np.asarray(legacy["layers"]["w_down"][0]))


@pytest.mark.parametrize("legacy", [False, True],
                         ids=["q8_native", "dequantized"])
def test_gguf_attention_projections_load_head_major(tmp_path, monkeypatch,
                                                    legacy):
    """wq/wk/wv keep the file's own [out, in] rows cut into heads, [L,
    heads, hd, D] — as resident Q8_0 QTensors (ggml's blocks of 32 along D:
    scales [L, heads, hd, D/32], the contraction last) and through the
    dequantize-at-load path — bit-identical to each other, and the logits
    of a prompt are the same either way."""
    import jax.numpy as jnp

    from dynamo_tpu.engine import quant as Q
    from dynamo_tpu.engine.model import forward
    from dynamo_tpu.llm.gguf import (
        GGUFFile, config_from_gguf, load_gguf_params,
    )

    f32 = str(tmp_path / "f32.gguf")
    tensors = write_tiny_gguf(f32, D=64)
    qpath = str(tmp_path / "q8.gguf")
    write_q8_gguf(f32, qpath, tensors)
    g = GGUFFile.parse(qpath)
    cfg = config_from_gguf(g)
    cfg.dtype = "float32"
    monkeypatch.delenv("DYN_GGUF_DEQUANT", raising=False)
    native = load_gguf_params(g, cfg, dtype=jnp.float32)
    monkeypatch.setenv("DYN_GGUF_DEQUANT", "1")
    dequant = load_gguf_params(g, cfg, dtype=jnp.float32)
    params = dequant if legacy else native
    L, H, KV, hd, D = 2, 4, 2, 16, 64
    for name, heads, gg in (("wq", H, "attn_q"), ("wk", KV, "attn_k"),
                            ("wv", KV, "attn_v")):
        node = params["layers"][name]
        if legacy:
            assert node.shape == (L, heads, hd, D)
        else:
            assert node["q"].dtype == jnp.int8
            assert node["q"].shape == (L, heads, hd, D)
            assert node["s"].shape == (L, heads, hd, D // 32)
        w = np.asarray(Q.dequantize(node, jnp.float32, axis=-1)
                       if Q.is_qtensor(node) else node)
        np.testing.assert_array_equal(
            w, np.asarray(dequant["layers"][name]))
        for i in range(L):
            ref = tensors[f"blk.{i}.{gg}.weight"].reshape(heads, hd, D)
            np.testing.assert_allclose(w[i], ref, atol=0.01)
    wo = native["layers"]["wo"]  # every other weight: [in, out] as before
    assert wo["q"].shape == (L, D, D) and wo["s"].shape == (L, D // 32, D)

    def logits(p):
        n, bs = 6, 4
        kc = jnp.zeros((L, 4 * bs, KV, hd), jnp.float32)
        bt = jnp.arange(1, 4)[None, :]
        pos = np.arange(n)
        out, _, _ = forward(
            p, jnp.asarray([[3, 5, 7, 9, 11, 13]]), jnp.asarray([pos]),
            (bt[0, pos // bs] * bs + pos % bs)[None, :], bt,
            jnp.asarray([n]), jnp.asarray([n - 1]), kc, kc, cfg=cfg,
            block_size=bs)
        return np.asarray(out)

    np.testing.assert_allclose(logits(native), logits(dequant),
                               rtol=1e-5, atol=1e-6)


def g0_meta_end(path):
    """Offset where the metadata block ends (= where tensor infos start):
    re-derive by re-reading kv pairs exactly as the parser does."""
    with open(path, "rb") as f:
        f.read(8)
        n_tensors, n_kv = struct.unpack("<QQ", f.read(16))
        for _ in range(n_kv):
            GGUFFile._read_str(f)
            (vtype,) = struct.unpack("<I", f.read(4))
            GGUFFile._read_value(f, vtype)
        return f.tell()


def test_q5_0_roundtrip_and_q5k_scalar():
    from dynamo_tpu.llm.gguf import GGML_QUANTS, GGML_Q5_0, GGML_Q5_K

    rng = np.random.default_rng(5)
    x = rng.standard_normal((2, 64)).astype(np.float32)
    blocks = []
    for row in x.reshape(-1, 32):
        d = np.abs(row).max() / 15.0
        q = np.clip(np.round(row / d) + 16, 0, 31).astype(np.uint8)
        qh = 0
        for j in range(32):
            qh |= int(q[j] >> 4) << j
        packed = ((q[:16] & 0xF) | ((q[16:] & 0xF) << 4)).astype(np.uint8)
        blocks.append(np.float16(d).tobytes()
                      + struct.pack("<I", qh) + packed.tobytes())
    _, _, deq = GGML_QUANTS[GGML_Q5_0]
    out = deq(np.frombuffer(b"".join(blocks), np.uint8).reshape(-1, 22))
    np.testing.assert_allclose(out.reshape(x.shape), x, atol=0.12)

    # q5_K vs straight-from-spec scalar
    raw = rng.integers(0, 256, (2, 176), dtype=np.uint8)
    half = np.frombuffer(np.full(2, 0.01, np.float16).tobytes(),
                         np.uint8).reshape(2, 2)
    raw[:, 0:2] = half
    raw[:, 2:4] = half

    def scalar_q5k(block):
        d = float(np.frombuffer(block[0:2], np.float16)[0])
        dmin = float(np.frombuffer(block[2:4], np.float16)[0])
        scales = block[4:16]
        qh, qs = block[16:48], block[48:]

        def sm(j):
            if j < 4:
                return scales[j] & 63, scales[j + 4] & 63
            return ((scales[j + 4] & 0xF) | ((scales[j - 4] >> 6) << 4),
                    (scales[j + 4] >> 4) | ((scales[j] >> 6) << 4))

        y = np.zeros(256, np.float32)
        pos, u1, u2 = 0, 1, 2
        for j in range(4):
            s1, m1 = sm(2 * j)
            s2, m2 = sm(2 * j + 1)
            chunk = qs[32 * j:32 * (j + 1)]
            for l, q in enumerate(chunk):
                y[pos] = d * s1 * ((q & 0xF) + (16 if qh[l] & u1 else 0)) \
                    - dmin * m1
                pos += 1
            for l, q in enumerate(chunk):
                y[pos] = d * s2 * ((q >> 4) + (16 if qh[l] & u2 else 0)) \
                    - dmin * m2
                pos += 1
            u1 <<= 2
            u2 <<= 2
        return y

    _, _, deq5k = GGML_QUANTS[GGML_Q5_K]
    got = deq5k(raw.copy())
    for i in range(2):
        np.testing.assert_allclose(got[i], scalar_q5k(raw[i].tobytes()),
                                   rtol=1e-5, atol=1e-6)


def test_quant_rows_must_be_block_aligned(gguf_path):
    """ggml blocks never span rows: a tensor whose row length is not a
    block multiple must refuse, not dequantize scrambled."""
    from dynamo_tpu.llm.gguf import GGML_Q8_0

    path, _ = gguf_path
    g = GGUFFile.parse(path)
    info = g.tensors["blk.0.attn_q.weight"]  # rows of 16 < 32-value block
    info.ggml_type = GGML_Q8_0
    with pytest.raises(ValueError, match="row length"):
        g.load_tensor("blk.0.attn_q.weight")


def test_q2k_q3k_match_scalar_reference():
    from dynamo_tpu.llm.gguf import GGML_QUANTS, GGML_Q2_K, GGML_Q3_K

    def scalar_q2k(block):
        sc = block[:16]
        qs = block[16:80]
        d = float(np.frombuffer(block[80:82], np.float16)[0])
        dmin = float(np.frombuffer(block[82:84], np.float16)[0])
        y = np.zeros(256, np.float32)
        pos = is_ = 0
        for n in range(2):
            q = qs[32 * n:32 * (n + 1)]
            for shift in (0, 2, 4, 6):
                for half in range(2):
                    s = sc[is_]
                    is_ += 1
                    dl, ml = d * (s & 0xF), dmin * (s >> 4)
                    for l in range(16):
                        y[pos] = dl * ((q[16 * half + l] >> shift) & 3) - ml
                        pos += 1
        return y

    def scalar_q3k(block):
        hm = block[:32]
        qs = block[32:96]
        import struct as st
        aux = list(st.unpack("<3I", block[96:108]))
        k1, k2 = 0x03030303, 0x0F0F0F0F
        tmp = aux[2]
        a = [(aux[0] & k2) | (((tmp >> 0) & k1) << 4),
             (aux[1] & k2) | (((tmp >> 2) & k1) << 4),
             ((aux[0] >> 4) & k2) | (((tmp >> 4) & k1) << 4),
             ((aux[1] >> 4) & k2) | (((tmp >> 6) & k1) << 4)]
        sc = np.frombuffer(st.pack("<4I", *a), np.int8).astype(np.float32) - 32
        d = float(np.frombuffer(block[108:110], np.float16)[0])
        y = np.zeros(256, np.float32)
        pos = is_ = 0
        m = 1
        for n in range(2):
            q = qs[32 * n:32 * (n + 1)]
            for shift in (0, 2, 4, 6):
                for half in range(2):
                    dl = d * sc[is_]
                    is_ += 1
                    for l in range(16):
                        col = 16 * half + l
                        qv = (q[col] >> shift) & 3
                        if not (hm[col] & m):
                            qv -= 4
                        y[pos] = dl * qv
                        pos += 1
                m <<= 1
        return y

    rng = np.random.default_rng(11)
    raw2 = rng.integers(0, 256, (3, 84), dtype=np.uint8)
    half = np.frombuffer(np.full(3, 0.05, np.float16).tobytes(),
                         np.uint8).reshape(3, 2)
    raw2[:, 80:82] = half
    raw2[:, 82:84] = half
    _, _, deq2 = GGML_QUANTS[GGML_Q2_K]
    got = deq2(raw2.copy())
    for i in range(3):
        np.testing.assert_allclose(got[i], scalar_q2k(raw2[i].tobytes()),
                                   rtol=1e-5, atol=1e-6)

    raw3 = rng.integers(0, 256, (3, 110), dtype=np.uint8)
    raw3[:, 108:110] = half
    _, _, deq3 = GGML_QUANTS[GGML_Q3_K]
    got = deq3(raw3.copy())
    for i in range(3):
        np.testing.assert_allclose(got[i], scalar_q3k(raw3[i].tobytes()),
                                   rtol=1e-5, atol=1e-6)


def test_rope_scaling_metadata():
    """rope.scaling.* must reach ModelConfig.rope_scaling (round-2 advisor:
    long-context scaled exports served plain RoPE silently)."""
    from types import SimpleNamespace

    def fake(extra):
        md = {"general.architecture": "qwen2",
              "qwen2.embedding_length": 16, "qwen2.block_count": 1,
              "qwen2.attention.head_count": 2, **extra}
        return SimpleNamespace(architecture="qwen2", metadata=md, tensors={})

    cfg = config_from_gguf(fake({
        "qwen2.rope.scaling.type": "yarn",
        "qwen2.rope.scaling.factor": 4.0,
        "qwen2.rope.scaling.original_context_length": 32768,
        "qwen2.rope.scaling.attn_factor": 1.2}))
    import math

    assert cfg.rope_scaling == {
        "rope_type": "yarn", "factor": 4.0,
        "original_max_position_embeddings": 32768,
        # ggml attn_factor multiplies the yarn mscale formula; HF
        # attention_factor replaces it — the loader pre-multiplies
        "attention_factor": 1.2 * (0.1 * math.log(4.0) + 1.0)}
    cfg = config_from_gguf(fake({"qwen2.rope.scaling.type": "linear",
                                 "qwen2.rope.scaling.factor": 2.0}))
    assert cfg.rope_scaling == {"rope_type": "linear", "factor": 2.0}
    assert config_from_gguf(fake({})).rope_scaling is None
    assert config_from_gguf(
        fake({"qwen2.rope.scaling.type": "none"})).rope_scaling is None
    with pytest.raises(NotImplementedError):
        config_from_gguf(fake({"qwen2.rope.scaling.type": "su"}))


async def test_q8_gguf_http_serve_native_matches_dequant(tmp_path):
    """E2E serve of a QUANTIZED artifact (r2 weak #6): the full HTTP stack
    serves a q8_0 GGUF with weights resident int8 (native QTensors), and
    greedy output is token-for-token identical to serving the same file
    through the legacy dequantize-at-load path."""
    import aiohttp

    from dynamo_tpu.disagg.handlers import DecodeWorkerHandler
    from dynamo_tpu.engine import quant as Q
    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.frontend.http import HttpService
    from dynamo_tpu.llm.discovery import ModelManager, ModelWatcher
    from dynamo_tpu.llm.model_card import ModelDeploymentCard, register_llm
    from dynamo_tpu.runtime import DistributedRuntime

    f32 = str(tmp_path / "f32.gguf")
    tensors = write_tiny_gguf(f32)
    qpath = str(tmp_path / "q8.gguf")
    write_q8_gguf(f32, qpath, tensors)

    rt = await DistributedRuntime.create()
    manager = ModelManager()
    watcher = await ModelWatcher(rt, manager, router_mode="rr").start()
    service = HttpService(manager, port=0)
    await service.start()
    engines, handles = [], []
    try:
        for name, env in (("g-native", None), ("g-dequant", "1")):
            # hermetic against a user-exported DYN_GGUF_DEQUANT: clear for
            # the native arm, restore whatever was set afterward
            saved = os.environ.pop("DYN_GGUF_DEQUANT", None)
            if env:
                os.environ["DYN_GGUF_DEQUANT"] = env
            try:
                r = resolve_model(qpath)
                cfg = r.config()
                cfg.dtype = "float32"
                params = r.load_params(cfg)
            finally:
                os.environ.pop("DYN_GGUF_DEQUANT", None)
                if saved is not None:
                    os.environ["DYN_GGUF_DEQUANT"] = saved
            qleaves = [v for v in params["layers"].values()
                       if Q.is_qtensor(v)]
            assert bool(qleaves) == (name == "g-native")
            eng = AsyncJaxEngine(cfg, EngineArgs(
                block_size=4, num_blocks=64, max_num_seqs=2,
                max_num_batched_tokens=32, max_model_len=64), params=params)
            engines.append(eng)
            ep = rt.namespace("dynamo").component(name).endpoint("generate")
            handles.append(await ep.serve_endpoint(
                DecodeWorkerHandler(eng).generate))
            card = ModelDeploymentCard(
                display_name=name, kv_cache_block_size=4,
                eos_token_ids=r.eos_token_ids(), tokenizer_ref=qpath,
                context_length=64)
            card.runtime_config.total_kv_blocks = eng.num_blocks
            await register_llm(rt, ep, card)
        for _ in range(100):
            if len(manager.list_models()) == 2:
                break
            await asyncio.sleep(0.05)
        outs = {}
        async with aiohttp.ClientSession() as http:
            for name in ("g-native", "g-dequant"):
                resp = await http.post(
                    f"http://127.0.0.1:{service.port}/v1/completions",
                    json={"model": name, "prompt": "abc hi ab",
                          "temperature": 0.0, "max_tokens": 8,
                          "ignore_eos": True})
                assert resp.status == 200, await resp.text()
                body = await resp.json()
                outs[name] = body["choices"][0]["text"]
        assert outs["g-native"] == outs["g-dequant"]
    finally:
        await service.stop()
        await watcher.stop()
        for h in handles:
            await h.stop(graceful=False)
        for e in engines:
            await e.close()
        await rt.shutdown()


def test_iq4_nl_and_xs_vs_scalar_spec():
    """IQ4_NL / IQ4_XS (nonlinear-codebook 4-bit, the importance-matrix
    export family) dequantize bit-identically to straight-from-spec scalar
    implementations over random blocks."""
    from dynamo_tpu.llm.gguf import (
        GGML_IQ4_NL, GGML_IQ4_XS, GGML_QUANTS, _IQ4_VALUES,
    )

    rng = np.random.default_rng(11)

    def scalar_iq4_nl(block: bytes) -> np.ndarray:
        d = np.frombuffer(block[:2], np.float16)[0].astype(np.float32)
        qs = np.frombuffer(block[2:], np.uint8)
        out = np.empty(32, np.float32)
        for j in range(16):
            out[j] = d * _IQ4_VALUES[qs[j] & 0xF]
            out[j + 16] = d * _IQ4_VALUES[qs[j] >> 4]
        return out

    def scalar_iq4_xs(block: bytes) -> np.ndarray:
        d = np.frombuffer(block[:2], np.float16)[0].astype(np.float32)
        sh = np.frombuffer(block[2:4], np.uint16)[0]
        sl = np.frombuffer(block[4:8], np.uint8)
        qs = np.frombuffer(block[8:], np.uint8)
        out = np.empty(256, np.float32)
        for ib in range(8):
            ls = ((sl[ib // 2] >> (4 * (ib % 2))) & 0xF) | (
                ((sh >> (2 * ib)) & 3) << 4)
            dl = d * (float(ls) - 32.0)
            for j in range(16):
                q = qs[16 * ib + j]
                out[32 * ib + j] = dl * _IQ4_VALUES[q & 0xF]
                out[32 * ib + j + 16] = dl * _IQ4_VALUES[q >> 4]
        return out

    for gtype, scalar, bpb, vpb in ((GGML_IQ4_NL, scalar_iq4_nl, 18, 32),
                                    (GGML_IQ4_XS, scalar_iq4_xs, 136, 256)):
        raw = rng.integers(0, 256, (4, bpb), dtype=np.uint8)
        # keep the f16 scale finite
        half = np.frombuffer(
            np.full(4, 0.02, np.float16).tobytes(), np.uint8).reshape(4, 2)
        raw[:, 0:2] = half
        _, _, deq = GGML_QUANTS[gtype]
        got = deq(raw)
        for i in range(4):
            np.testing.assert_array_equal(
                got[i], scalar(raw[i].tobytes()), err_msg=str(gtype))
