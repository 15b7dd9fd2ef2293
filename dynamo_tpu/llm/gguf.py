"""GGUF file parser: metadata, tensor index, tokenizer extraction.

Rebuild of the reference's GGUF support (ref: lib/llm/src/gguf/*.rs — it
parses metadata + tokenizer out of llama.cpp model files to build the
ModelDeploymentCard and preprocessor; actual quantized inference is the
llama.cpp engine's job there). Here the same surface:

- ``GGUFFile.parse`` reads the header, all metadata KV pairs, and the
  tensor index (name/shape/type/offset) without touching tensor data.
- ``config_from_gguf`` maps ``llama.*``/``qwen2.*`` metadata keys onto
  :class:`ModelConfig`.
- ``tokenizer_from_gguf`` rebuilds a HF ``tokenizers`` BPE from the
  embedded ``tokenizer.ggml.*`` arrays.
- ``load_tensor`` materializes F32/F16/BF16 tensors directly and
  DEQUANTIZES the common ggml quant formats (Q4_0/Q4_1/Q5_0/Q5_1/Q8_0 and
  the Q2_K..Q6_K superblocks) to float at load — real llama.cpp
  checkpoints ship quantized. Unsupported formats (IQ*) refuse loudly
  rather than dequantizing silently wrong.

Format per the public GGUF spec (ggml project): little-endian, magic
"GGUF", version 3; strings are u64-length-prefixed UTF-8; arrays carry an
element type + u64 count.
"""

from __future__ import annotations

import os
import struct
from dataclasses import dataclass, field
from typing import Any, BinaryIO, Optional

import numpy as np

GGUF_MAGIC = b"GGUF"

# metadata value types
_U8, _I8, _U16, _I16, _U32, _I32, _F32, _BOOL, _STR, _ARR, _U64, _I64, _F64 = range(13)

_SCALAR_FMT = {
    _U8: "<B", _I8: "<b", _U16: "<H", _I16: "<h", _U32: "<I", _I32: "<i",
    _F32: "<f", _U64: "<Q", _I64: "<q", _F64: "<d",
}

#: ggml tensor dtypes we can materialize (id → numpy dtype factory)
GGML_F32, GGML_F16 = 0, 1
GGML_BF16 = 30
GGML_Q4_0, GGML_Q4_1, GGML_Q5_0, GGML_Q5_1, GGML_Q8_0 = 2, 3, 6, 7, 8
GGML_Q2_K, GGML_Q3_K, GGML_Q4_K, GGML_Q5_K, GGML_Q6_K = 10, 11, 12, 13, 14
GGML_IQ4_NL, GGML_IQ4_XS = 20, 23


def _np_dtype(ggml_type: int):
    if ggml_type == GGML_F32:
        return np.dtype(np.float32)
    if ggml_type == GGML_F16:
        return np.dtype(np.float16)
    if ggml_type == GGML_BF16:
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return None


# ------------------------------------------------------- quant dequantizers
#
# Vectorized numpy dequantization of the ggml block formats (public GGUF
# spec / ggml-quants layout; ref behavior: the llamacpp engine serves these
# natively — here they materialize to float at load). Each entry:
# (bytes_per_block, values_per_block, fn(raw_u8[nb, bytes]) -> f32[nb, vals]).

def _deq_q8_0(b):
    d = b[:, :2].copy().view(np.float16).astype(np.float32)  # [nb, 1]
    q = b[:, 2:].view(np.int8).astype(np.float32)
    return d * q


def _nibbles(qs):
    """[nb, n] uint8 → [nb, 2n] with all LOW nibbles first, then HIGH —
    the ggml 4-bit in-block ordering."""
    return np.concatenate([qs & 0xF, qs >> 4], axis=1)


def _deq_q4_0(b):
    d = b[:, :2].copy().view(np.float16).astype(np.float32)
    return d * (_nibbles(b[:, 2:]).astype(np.float32) - 8.0)


def _deq_q4_1(b):
    d = b[:, :2].copy().view(np.float16).astype(np.float32)
    m = b[:, 2:4].copy().view(np.float16).astype(np.float32)
    return d * _nibbles(b[:, 4:]).astype(np.float32) + m


def _q5_high_bits(qh_bytes):
    """[nb, 4] packed u32 → [nb, 32] the per-value 5th bits."""
    qh = qh_bytes.copy().view(np.uint32)  # [nb, 1]
    return ((qh >> np.arange(32, dtype=np.uint32)[None, :]) & 1).astype(np.uint8)


def _deq_q5_0(b):
    d = b[:, :2].copy().view(np.float16).astype(np.float32)
    q = _nibbles(b[:, 6:]) | (_q5_high_bits(b[:, 2:6]) << 4)
    return d * (q.astype(np.float32) - 16.0)


def _deq_q5_1(b):
    d = b[:, :2].copy().view(np.float16).astype(np.float32)
    m = b[:, 2:4].copy().view(np.float16).astype(np.float32)
    q = _nibbles(b[:, 8:]) | (_q5_high_bits(b[:, 4:8]) << 4)
    return d * q.astype(np.float32) + m


def _k_scale_min(scales):
    """q4_K/q5_K 12-byte packed 6-bit scales/mins → (sc[nb,8], m[nb,8])."""
    sc = np.empty(scales.shape[:1] + (8,), np.float32)
    mn = np.empty_like(sc)
    for j in range(8):
        if j < 4:
            sc[:, j] = (scales[:, j] & 63).astype(np.float32)
            mn[:, j] = (scales[:, j + 4] & 63).astype(np.float32)
        else:
            sc[:, j] = ((scales[:, j + 4] & 0xF)
                        | ((scales[:, j - 4] >> 6) << 4)).astype(np.float32)
            mn[:, j] = ((scales[:, j + 4] >> 4)
                        | ((scales[:, j] >> 6) << 4)).astype(np.float32)
    return sc, mn


def _deq_q4_k(b):
    d = b[:, :2].copy().view(np.float16).astype(np.float32)
    dmin = b[:, 2:4].copy().view(np.float16).astype(np.float32)
    sc, mn = _k_scale_min(b[:, 4:16])
    qs = b[:, 16:]  # [nb, 128]
    out = np.empty((b.shape[0], 256), np.float32)
    for j in range(4):  # 64 values per chunk: 32 low nibbles, 32 high
        q = qs[:, 32 * j:32 * (j + 1)]
        lo, hi = 2 * j, 2 * j + 1
        out[:, 64 * j:64 * j + 32] = (
            d * sc[:, lo:lo + 1] * (q & 0xF) - dmin * mn[:, lo:lo + 1])
        out[:, 64 * j + 32:64 * (j + 1)] = (
            d * sc[:, hi:hi + 1] * (q >> 4) - dmin * mn[:, hi:hi + 1])
    return out


def _deq_q5_k(b):
    d = b[:, :2].copy().view(np.float16).astype(np.float32)
    dmin = b[:, 2:4].copy().view(np.float16).astype(np.float32)
    sc, mn = _k_scale_min(b[:, 4:16])
    qh, qs = b[:, 16:48], b[:, 48:]  # [nb,32], [nb,128]
    out = np.empty((b.shape[0], 256), np.float32)
    u = 1
    for j in range(4):
        q = qs[:, 32 * j:32 * (j + 1)]
        lo, hi = 2 * j, 2 * j + 1
        out[:, 64 * j:64 * j + 32] = (
            d * sc[:, lo:lo + 1]
            * ((q & 0xF) + np.where(qh & u, 16, 0))
            - dmin * mn[:, lo:lo + 1])
        u <<= 1
        out[:, 64 * j + 32:64 * (j + 1)] = (
            d * sc[:, hi:hi + 1]
            * ((q >> 4) + np.where(qh & u, 16, 0))
            - dmin * mn[:, hi:hi + 1])
        u <<= 1
    return out


def _deq_q2_k(b):
    # 84B: scales 16×(lo4=scale, hi4=min), qs 64B of 2-bit quants, d, dmin
    sc_raw = b[:, :16]
    qs = b[:, 16:80]
    d = b[:, 80:82].copy().view(np.float16).astype(np.float32)
    dmin = b[:, 82:84].copy().view(np.float16).astype(np.float32)
    out = np.empty((b.shape[0], 256), np.float32)
    pos, is_ = 0, 0
    for n in range(2):  # 128 values per 32-byte q chunk
        q = qs[:, 32 * n:32 * (n + 1)]
        for shift in (0, 2, 4, 6):
            for half in range(2):  # two 16-value sub-groups
                sc = sc_raw[:, is_:is_ + 1]
                is_ += 1
                dl = d * (sc & 0xF)
                ml = dmin * (sc >> 4).astype(np.float32)
                qv = (q[:, 16 * half:16 * (half + 1)] >> shift) & 3
                out[:, pos:pos + 16] = dl * qv - ml
                pos += 16
    return out


def _q3k_scales(scales):
    """q3_K 12-byte packing → 16 signed 6-bit scales (value - 32)."""
    a = scales.copy().view(np.uint32)  # [nb, 3]
    k1, k2 = np.uint32(0x03030303), np.uint32(0x0F0F0F0F)
    tmp = a[:, 2]
    aux = np.empty((scales.shape[0], 4), np.uint32)
    aux[:, 0] = (a[:, 0] & k2) | (((tmp >> 0) & k1) << 4)
    aux[:, 1] = (a[:, 1] & k2) | (((tmp >> 2) & k1) << 4)
    aux[:, 2] = ((a[:, 0] >> 4) & k2) | (((tmp >> 4) & k1) << 4)
    aux[:, 3] = ((a[:, 1] >> 4) & k2) | (((tmp >> 6) & k1) << 4)
    return aux.view(np.int8).astype(np.float32) - 32.0  # [nb, 16]


def _deq_q3_k(b):
    # 110B: hmask 32B (high bits), qs 64B (2-bit), scales 12B, d fp16
    hm = b[:, :32]
    qs = b[:, 32:96]
    sc = _q3k_scales(b[:, 96:108])
    d = b[:, 108:110].copy().view(np.float16).astype(np.float32)
    out = np.empty((b.shape[0], 256), np.float32)
    pos, is_, m = 0, 0, 1
    for n in range(2):
        q = qs[:, 32 * n:32 * (n + 1)]
        for shift in (0, 2, 4, 6):
            for half in range(2):
                dl = d * sc[:, is_:is_ + 1]
                is_ += 1
                cols = slice(16 * half, 16 * (half + 1))
                qv = ((q[:, cols] >> shift) & 3).astype(np.int8)
                # hmask bit SET means the value is NOT shifted down by 4
                qv = qv - np.where(hm[:, cols] & m, 0, 4).astype(np.int8)
                out[:, pos:pos + 16] = dl * qv
                pos += 16
            m <<= 1
    return out


def _deq_q6_k(b):
    ql, qh = b[:, :128], b[:, 128:192]
    sc = b[:, 192:208].view(np.int8).astype(np.float32)  # [nb, 16]
    d = b[:, 208:210].copy().view(np.float16).astype(np.float32)
    out = np.empty((b.shape[0], 256), np.float32)
    for half in range(2):  # 128 values per half
        qlh = ql[:, 64 * half:64 * (half + 1)]
        qhh = qh[:, 32 * half:32 * (half + 1)]
        s = sc[:, 8 * half:8 * (half + 1)]
        base = 128 * half
        # scale per 16 values → expand each of the 2 idx per 32-lane row
        sl = np.repeat(s, 16, axis=1)  # [nb, 128]
        q1 = ((qlh[:, :32] & 0xF) | (((qhh >> 0) & 3) << 4)).astype(np.int16) - 32
        q2 = ((qlh[:, 32:] & 0xF) | (((qhh >> 2) & 3) << 4)).astype(np.int16) - 32
        q3 = ((qlh[:, :32] >> 4) | (((qhh >> 4) & 3) << 4)).astype(np.int16) - 32
        q4 = ((qlh[:, 32:] >> 4) | (((qhh >> 6) & 3) << 4)).astype(np.int16) - 32
        out[:, base + 0:base + 32] = d * sl[:, 0:32] * q1
        out[:, base + 32:base + 64] = d * sl[:, 32:64] * q2
        out[:, base + 64:base + 96] = d * sl[:, 64:96] * q3
        out[:, base + 96:base + 128] = d * sl[:, 96:128] * q4
    return out


#: iq4 nonlinear 4-bit codebook (ggml kvalues_iq4nl): importance-matrix
#: exports map nibbles through this table instead of a linear grid
_IQ4_VALUES = np.array([-127, -104, -83, -65, -49, -35, -22, -10,
                        1, 13, 25, 38, 53, 69, 89, 113], np.float32)


def _deq_iq4_nl(b):
    """IQ4_NL: f16 scale + 16 nibble bytes per 32 values; low nibbles are
    values 0..15, high nibbles 16..31, through the nonlinear codebook."""
    d = b[:, :2].copy().view(np.float16).astype(np.float32)  # [nb, 1]
    return d * _IQ4_VALUES[_nibbles(b[:, 2:])]


def _deq_iq4_xs(b):
    """IQ4_XS superblock (256 values, 136 B): f16 d + u16 scales_h +
    4 B scales_l + 128 B nibbles; per-32 sub-scale ls = low-nibble |
    (2 bits of scales_h << 4), value = d·(ls−32)·codebook[nibble]."""
    d = b[:, :2].copy().view(np.float16).astype(np.float32)      # [nb, 1]
    sh = b[:, 2:4].copy().view(np.uint16).astype(np.uint32)      # [nb, 1]
    sl = b[:, 4:8]                                               # [nb, 4]
    qs = b[:, 8:].reshape(len(b), 8, 16)                         # [nb, 8, 16]
    ib = np.arange(8)
    ls = (((sl[:, ib // 2] >> (4 * (ib % 2))) & 0xF)
          | (((sh >> (2 * ib)) & 3) << 4)).astype(np.float32)    # [nb, 8]
    dl = d * (ls - 32.0)
    vals = np.concatenate([_IQ4_VALUES[qs & 0xF],
                           _IQ4_VALUES[qs >> 4]], axis=2)        # [nb, 8, 32]
    return (dl[:, :, None] * vals).reshape(len(b), 256)


#: ggml_type → (bytes_per_block, values_per_block, dequant)
GGML_QUANTS = {
    GGML_Q2_K: (84, 256, _deq_q2_k),
    GGML_Q3_K: (110, 256, _deq_q3_k),
    GGML_Q4_0: (18, 32, _deq_q4_0),
    GGML_Q4_1: (20, 32, _deq_q4_1),
    GGML_Q5_0: (22, 32, _deq_q5_0),
    GGML_Q5_1: (24, 32, _deq_q5_1),
    GGML_Q8_0: (34, 32, _deq_q8_0),
    GGML_Q4_K: (144, 256, _deq_q4_k),
    GGML_Q5_K: (176, 256, _deq_q5_k),
    GGML_Q6_K: (210, 256, _deq_q6_k),
    GGML_IQ4_NL: (18, 32, _deq_iq4_nl),
    GGML_IQ4_XS: (136, 256, _deq_iq4_xs),
}


@dataclass
class GGUFTensorInfo:
    name: str
    shape: tuple[int, ...]  # numpy/row-major order (GGUF stores reversed)
    ggml_type: int
    offset: int  # relative to data_start


@dataclass
class GGUFFile:
    path: str
    version: int
    metadata: dict[str, Any]
    tensors: dict[str, GGUFTensorInfo]
    data_start: int
    alignment: int = 32

    # -- parsing -----------------------------------------------------------

    @staticmethod
    def _read_str(f: BinaryIO) -> str:
        (n,) = struct.unpack("<Q", f.read(8))
        return f.read(n).decode("utf-8", "replace")

    @classmethod
    def _read_value(cls, f: BinaryIO, vtype: int):
        if vtype in _SCALAR_FMT:
            fmt = _SCALAR_FMT[vtype]
            (v,) = struct.unpack(fmt, f.read(struct.calcsize(fmt)))
            return v
        if vtype == _BOOL:
            return f.read(1)[0] != 0
        if vtype == _STR:
            return cls._read_str(f)
        if vtype == _ARR:
            (etype,) = struct.unpack("<I", f.read(4))
            (count,) = struct.unpack("<Q", f.read(8))
            if etype in _SCALAR_FMT:
                # bulk-read scalar arrays (token scores etc. can be 100k+)
                fmt = _SCALAR_FMT[etype]
                size = struct.calcsize(fmt)
                buf = f.read(size * count)
                return list(np.frombuffer(buf, dtype=fmt[1]).tolist())
            return [cls._read_value(f, etype) for _ in range(count)]
        raise ValueError(f"unknown GGUF value type {vtype}")

    @classmethod
    def parse(cls, path: str) -> "GGUFFile":
        with open(path, "rb") as f:
            if f.read(4) != GGUF_MAGIC:
                raise ValueError(f"{path}: not a GGUF file")
            (version,) = struct.unpack("<I", f.read(4))
            if version < 2:
                raise ValueError(f"{path}: GGUF v{version} unsupported (< 2)")
            n_tensors, n_kv = struct.unpack("<QQ", f.read(16))

            metadata: dict[str, Any] = {}
            for _ in range(n_kv):
                key = cls._read_str(f)
                (vtype,) = struct.unpack("<I", f.read(4))
                metadata[key] = cls._read_value(f, vtype)

            tensors: dict[str, GGUFTensorInfo] = {}
            for _ in range(n_tensors):
                name = cls._read_str(f)
                (nd,) = struct.unpack("<I", f.read(4))
                dims = struct.unpack(f"<{nd}Q", f.read(8 * nd))
                gtype, offset = struct.unpack("<IQ", f.read(12))
                # GGUF dims are innermost-first; numpy wants outermost-first
                tensors[name] = GGUFTensorInfo(
                    name=name, shape=tuple(reversed(dims)),
                    ggml_type=gtype, offset=offset)

            alignment = int(metadata.get("general.alignment", 32))
            pos = f.tell()
            data_start = (pos + alignment - 1) // alignment * alignment
        return cls(path=path, version=version, metadata=metadata,
                   tensors=tensors, data_start=data_start, alignment=alignment)

    # -- tensor data -------------------------------------------------------

    def load_tensor(self, name: str, f: Optional[BinaryIO] = None) -> np.ndarray:
        """Materialize one tensor; pass an open file to batch many reads
        through a single handle (load_gguf_params does)."""
        info = self.tensors[name]
        count = int(np.prod(info.shape)) if info.shape else 1
        dtype = _np_dtype(info.ggml_type)
        if dtype is None:
            quant = GGML_QUANTS.get(info.ggml_type)
            if quant is None:
                raise NotImplementedError(
                    f"tensor {name}: ggml type {info.ggml_type} is not "
                    "supported (F32/F16/BF16 and "
                    "Q4_0/Q4_1/Q5_0/Q5_1/Q8_0/Q2_K..Q6_K/IQ4_NL/IQ4_XS "
                    "are)")
            bpb, vpb, deq = quant
            # ggml blocks never span rows: the ROW length (ne[0], our last
            # dim) must be block-aligned — a total-count check would let a
            # malformed file dequantize scrambled across row boundaries
            row = info.shape[-1] if info.shape else count
            if row % vpb:
                raise ValueError(
                    f"tensor {name}: row length {row} not a multiple of "
                    f"the {vpb}-value quant block")
            nbytes = count // vpb * bpb
            buf = self._read(f, info.offset, nbytes)
            raw = np.frombuffer(buf, np.uint8).reshape(-1, bpb)
            return deq(raw).reshape(info.shape)
        buf = self._read(f, info.offset, count * dtype.itemsize)
        return np.frombuffer(buf, dtype=dtype).reshape(info.shape)

    def load_tensor_q8_native(self, name: str, f: Optional[BinaryIO] = None,
                              transpose: bool = True) -> Optional[dict]:
        """Q8_0 tensor as a grouped-int8 QTensor (engine/quant.py layout) —
        the weights NEVER widen past 1 B each: ggml's per-32 blocks map
        exactly onto {"q": int8 [in, out], "s": f32 [in/32, out]} (the
        stored layout is [out, in] row-major with blocks along the row, so
        one transpose lands groups on the contraction dim;
        ``transpose=False`` keeps [out, in] and [out, in/32], the
        contraction last). Returns None for any other ggml type — callers
        fall back to ``load_tensor``."""
        info = self.tensors[name]
        if info.ggml_type != GGML_Q8_0 or len(info.shape) != 2:
            return None
        R, C = info.shape  # [out, in]
        if C % 32:
            raise ValueError(f"tensor {name}: row length {C} not a multiple "
                             "of the 32-value quant block")
        raw = np.frombuffer(
            self._read(f, info.offset, R * C // 32 * 34),
            np.uint8).reshape(R * C // 32, 34)
        s = raw[:, :2].copy().view(np.float16).astype(np.float32)
        q = raw[:, 2:].view(np.int8)
        q, s = q.reshape(R, C), s.reshape(R, C // 32)
        if transpose:
            q, s = q.T, s.T
        return {"q": np.ascontiguousarray(q), "s": np.ascontiguousarray(s)}

    def _read(self, f: Optional[BinaryIO], offset: int, n: int) -> bytes:
        if f is None:
            with open(self.path, "rb") as fh:
                fh.seek(self.data_start + offset)
                return fh.read(n)
        f.seek(self.data_start + offset)
        return f.read(n)

    @property
    def architecture(self) -> str:
        return str(self.metadata.get("general.architecture", ""))


def config_from_gguf(g: GGUFFile):
    """Map ``<arch>.*`` metadata keys onto ModelConfig (ref: gguf.rs builds
    the same view for its ModelDeploymentCard)."""
    from dynamo_tpu.engine.config import ModelConfig

    arch = g.architecture
    if arch not in ("llama", "mistral", "qwen2"):
        raise NotImplementedError(
            f"GGUF architecture '{arch}' not supported (llama/mistral/qwen2)")
    md = g.metadata

    def key(name, default=None):
        return md.get(f"{arch}.{name}", default)

    n_heads = int(key("attention.head_count", 32))
    vocab = md.get("tokenizer.ggml.tokens")
    vocab_size = int(key("vocab_size", len(vocab) if vocab else 32000))
    # rope.scaling.* — long-context GGUF exports (scaled qwen2/llama) serve
    # garbage past the original context with plain RoPE, so map the ggml
    # keys onto HF rope_scaling semantics and fail loudly on unknown types
    # (same posture as model.rope_params)
    scaling = None
    sc_type = key("rope.scaling.type")
    if sc_type and sc_type != "none":
        if sc_type not in ("linear", "yarn"):
            raise NotImplementedError(
                f"GGUF rope scaling type '{sc_type}' not supported")
        scaling = {"rope_type": sc_type,
                   "factor": float(key("rope.scaling.factor", 1.0))}
        orig = key("rope.scaling.original_context_length")
        if orig is not None:
            scaling["original_max_position_embeddings"] = int(orig)
        attn = key("rope.scaling.attn_factor")
        if attn is not None and sc_type == "yarn":
            # ggml semantics: attn_factor MULTIPLIES the yarn mscale
            # (mscale = attn_factor·(1 + 0.1·ln(factor))); HF's
            # attention_factor REPLACES the formula, so pre-multiply here
            import math

            scaling["attention_factor"] = float(attn) * (
                0.1 * math.log(scaling["factor"]) + 1.0)
    return ModelConfig(
        # no output.weight tensor = tied embeddings (derived here, at the
        # config layer, so every consumer of config() agrees)
        tie_word_embeddings="output.weight" not in g.tensors,
        vocab_size=vocab_size,
        hidden_size=int(key("embedding_length", 4096)),
        intermediate_size=int(key("feed_forward_length", 11008)),
        num_layers=int(key("block_count", 32)),
        num_heads=n_heads,
        num_kv_heads=int(key("attention.head_count_kv", n_heads)),
        rope_theta=float(key("rope.freq_base", 10000.0)),
        rms_norm_eps=float(key("attention.layer_norm_rms_epsilon", 1e-5)),
        max_position_embeddings=int(key("context_length", 8192)),
        rope_scaling=scaling,
        qkv_bias=arch == "qwen2",
    )


def tokenizer_from_gguf(g: GGUFFile):
    """HF ``tokenizers.Tokenizer`` from the embedded ggml vocab.

    Supports the BPE ('gpt2') vocab model: tokens + merges come straight
    from ``tokenizer.ggml.*``. SentencePiece-style ('llama') vocabs carry
    scores instead of merges; those are rebuilt as a greedy Unigram over
    the token scores — byte-fallback tokens included.
    """
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers

    md = g.metadata
    tokens = md.get("tokenizer.ggml.tokens")
    if not tokens:
        raise ValueError("GGUF carries no tokenizer.ggml.tokens")
    model_kind = md.get("tokenizer.ggml.model", "gpt2")

    if model_kind == "gpt2":
        vocab = {t: i for i, t in enumerate(tokens)}
        merges = []
        for m in md.get("tokenizer.ggml.merges", []):
            a, _, b = m.partition(" ")
            merges.append((a, b))
        tk = Tokenizer(models.BPE(vocab=vocab, merges=merges,
                                  byte_fallback=False))
        tk.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
        tk.decoder = decoders.ByteLevel()
        return tk
    if model_kind == "llama":
        scores = md.get("tokenizer.ggml.scores") or [0.0] * len(tokens)
        tk = Tokenizer(models.Unigram(
            vocab=list(zip(tokens, [float(s) for s in scores])),
            unk_id=int(md.get("tokenizer.ggml.unknown_token_id", 0)),
            byte_fallback=True))
        tk.decoder = decoders.Sequence([
            decoders.Replace("▁", " "), decoders.ByteFallback(),
            decoders.Fuse()])
        return tk
    raise NotImplementedError(f"GGUF tokenizer model '{model_kind}'")


def eos_ids_from_gguf(g: GGUFFile) -> list[int]:
    eos = g.metadata.get("tokenizer.ggml.eos_token_id")
    return [int(eos)] if eos is not None else []


def load_gguf_params(g: GGUFFile, cfg, dtype=None) -> dict:
    """GGUF tensor names → the engine's stacked params pytree (unquantized
    exports only; see load_tensor). llama.cpp naming: ``blk.<i>.*``,
    ``token_embd``, ``output_norm``, ``output``."""
    import jax.numpy as jnp

    dtype = dtype or jnp.dtype(cfg.dtype)
    with open(g.path, "rb") as fh:  # one handle for the whole load

        def get(name):
            return jnp.asarray(g.load_tensor(name, fh), dtype=dtype)

        def proj(name):  # stored [out, in] like HF → transpose to [in, out]
            return get(name).T

        def proj_w(name, heads=None):
            """Matmul weight: Q8_0 tensors stay QUANTIZED in HBM (grouped-
            int8 QTensor, bit-identical numerics via the f32 dequant chain
            in engine/quant.materialize); everything else dequantizes as
            before. DYN_GGUF_DEQUANT=1 forces the legacy bf16 load.
            ``heads``: an attention projection, which keeps the file's own
            [out, in] cut into heads, [heads, width, in] (model.py's pytree
            comment), the scales with it."""
            def cut(a):
                return a.reshape(heads, -1, a.shape[-1]) if heads else a

            if not os.environ.get("DYN_GGUF_DEQUANT"):
                qt = g.load_tensor_q8_native(name, fh, transpose=not heads)
                if qt is not None:
                    return {k: cut(jnp.asarray(v)) for k, v in qt.items()}
            return cut(get(name) if heads else proj(name))

        L = cfg.num_layers
        from dynamo_tpu.engine.quant import stack_layers as stack

        layers = {
            "attn_norm": stack([get(f"blk.{i}.attn_norm.weight") for i in range(L)]),
            "mlp_norm": stack([get(f"blk.{i}.ffn_norm.weight") for i in range(L)]),
            "wq": stack([proj_w(f"blk.{i}.attn_q.weight", cfg.num_heads)
                         for i in range(L)]),
            "wk": stack([proj_w(f"blk.{i}.attn_k.weight", cfg.num_kv_heads)
                         for i in range(L)]),
            "wv": stack([proj_w(f"blk.{i}.attn_v.weight", cfg.num_kv_heads)
                         for i in range(L)]),
            "wo": stack([proj_w(f"blk.{i}.attn_output.weight") for i in range(L)]),
            "w_gate": stack([proj_w(f"blk.{i}.ffn_gate.weight") for i in range(L)]),
            "w_up": stack([proj_w(f"blk.{i}.ffn_up.weight") for i in range(L)]),
            "w_down": stack([proj_w(f"blk.{i}.ffn_down.weight") for i in range(L)]),
        }
        if cfg.qkv_bias:
            layers["bq"] = stack([get(f"blk.{i}.attn_q.bias") for i in range(L)])
            layers["bk"] = stack([get(f"blk.{i}.attn_k.bias") for i in range(L)])
            layers["bv"] = stack([get(f"blk.{i}.attn_v.bias") for i in range(L)])
        params = {
            "embed": get("token_embd.weight"),
            "layers": layers,
            "final_norm": get("output_norm.weight"),
        }
        if "output.weight" in g.tensors:
            params["lm_head"] = proj_w("output.weight")
    return params
