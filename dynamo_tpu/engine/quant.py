"""On-device quantized weights: int8/int4 resident in HBM, dequantized in
the matmul path.

The reference's flagship recipes serve quantized checkpoints — FP8 70B
disagg (ref: recipes/llama-3-70b/vllm/disagg-single-node/deploy.yaml:21-86)
and gpt-oss-120b MXFP4 (ref: recipes/gpt-oss-120b/trtllm/agg/deploy.yaml).
Dequantizing to bf16 at load can never fit 70B-class weights in v5e HBM
(16 GB/chip), so here weights STAY quantized on device and dequantization
rides the matmul:

- **per-out-channel scales** (``s.shape[-2] == 1``): computed as
  ``(x @ q) * s`` — the scale applies to the dot's *output*, so the weight
  is never materialized wider than its quantized storage, unconditionally;
- **grouped scales** (group size g over the contraction dim): the dequant
  chain ``q.astype(bf16) * repeat(s, g)`` feeds the dot as an elementwise
  producer XLA fuses into the operand read (tiles dequantize in VMEM), so
  HBM keeps only the quantized bytes. An optional zero-point ``z`` (same
  shape as ``s``) supports affine formats (GGUF K-quants).

TPU-fit: the MXU consumes bf16 — int8/int4 → bf16 conversion happens on
tile read, halving (or quartering) the HBM weight traffic that dominates
decode. ``jnp.int4`` packs two weights per byte in TPU HBM.

A quantized weight is a plain dict ``{"q": int, "s": float[, "z": float]}``
— a real pytree subtree, so shardings, device_put, and checkpointing all
treat it uniformly. Layout convention matches the model's weights: logical
``w[..., I, O]`` with ``q`` the same shape and ``s``/``z`` shaped
``[..., G, O]`` where ``G = I // group`` (``G == 1`` = per-out-channel).
"""

from __future__ import annotations

import logging
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

_logger = logging.getLogger("dynamo.engine.quant")

#: weight names eligible for quantization (matmul weights only — norms,
#: biases, sinks, router and embeddings stay at model dtype; embed doubles
#: as the tied head and feeds a gather, which wants full width)
QUANT_KEYS = frozenset({
    "wq", "wk", "wv", "wo", "q_a", "q_b", "kv_a",
    "w_gate", "w_up", "w_down", "ws_gate", "ws_up", "ws_down",
    "lm_head",
})


def is_qtensor(w) -> bool:
    return isinstance(w, dict) and "q" in w and "s" in w


def parse_spec(spec: str) -> tuple[int, Optional[int]]:
    """``"int8"`` → (8, None); ``"int8-g128"`` → (8, 128); ``"int4-g32"``
    → (4, 32). Grouping is required for int4 — per-channel 4-bit is too
    coarse to hold parity."""
    base, _, g = spec.partition("-g")
    if base not in ("int8", "int4"):
        raise ValueError(f"unsupported quantization spec '{spec}' "
                         "(int8[-gN] / int4-gN)")
    bits = int(base[3:])
    group = int(g) if g else None
    if group is not None and group <= 0:
        raise ValueError(f"unsupported quantization spec '{spec}' "
                         "(group size must be positive)")
    if bits == 4 and group is None:
        raise ValueError("int4 requires a group size (e.g. 'int4-g32')")
    return bits, group


def quantize(w, bits: int = 8, group: Optional[int] = None) -> dict:
    """Symmetric quantization of ``w[..., I, O]`` along the contraction dim.

    group=None → one scale per output channel; group=g → one scale per
    (g-chunk of I, output channel). A numpy ``w`` is quantized on the host
    (checkpoint loaders: the full-width original never reaches the
    device); a jax array or tracer is quantized where it lives, so the
    same math runs under ``jit`` for on-device random init."""
    xp = np if isinstance(w, np.ndarray) else jnp
    qmax = (1 << (bits - 1)) - 1  # 127 / 7
    wf = w.astype(xp.float32)
    I, O = wf.shape[-2], wf.shape[-1]
    if group is None:
        group = I
    if I % group:
        raise ValueError(f"contraction dim {I} not divisible by group {group}")
    G = I // group
    grp = wf.reshape(*wf.shape[:-2], G, group, O)
    # under jit the barrier keeps this a true division, bit-equal to the
    # host path (XLA would multiply by the inexact reciprocal of a constant)
    div = qmax if xp is np else jax.lax.optimization_barrier(
        jnp.float32(qmax))
    s = xp.max(xp.abs(grp), axis=-2, keepdims=True) / div  # [..., G, 1, O]
    s = xp.maximum(s, 1e-12)
    q = xp.clip(xp.rint(grp / s), -qmax, qmax)
    dt = jnp.int8 if bits == 8 else jnp.int4
    return {"q": jnp.asarray(q.reshape(wf.shape), dt),
            "s": jnp.asarray(s[..., 0, :], jnp.float32)}  # [..., G, O]


def dequantize(qt: dict, dtype=jnp.float32):
    """Full-width dequantized weight (tests / host-side checks)."""
    q, s = qt["q"], qt["s"]
    I = q.shape[-2]
    G = s.shape[-2]
    w = q.astype(jnp.float32) * jnp.repeat(s, I // G, axis=-2)
    if "z" in qt:
        w = w - jnp.repeat(qt["z"], I // G, axis=-2)
    return w.astype(dtype)


def materialize(w, dtype):
    """The weight as a matmul/einsum operand: a passthrough for plain
    arrays, the fusable dequant chain for QTensors. Use this at einsum
    sites (MoE experts); plain 2-D matmuls should prefer :func:`qmm`.

    Dequant math runs in f32 with ONE final cast so the result matches a
    dequantize-at-load weight bit-for-bit (f16 GGUF scales would lose
    mantissa bits if cast to bf16 first); the chain stays elementwise, so
    XLA still fuses it into the dot's operand read."""
    if not is_qtensor(w):
        return w
    q, s = w["q"], w["s"]
    g = q.shape[-2] // s.shape[-2]
    out = q.astype(jnp.float32) * jnp.repeat(s.astype(jnp.float32), g,
                                             axis=-2)
    if "z" in w:
        out = out - jnp.repeat(w["z"].astype(jnp.float32), g, axis=-2)
    return out.astype(dtype)


def qmm(x, w):
    """``x[..., I] @ w[I, O]`` with a maybe-quantized ``w``.

    Per-out-channel QTensors apply the scale to the dot OUTPUT (never a
    wide weight anywhere); grouped ones go through the fusable dequant
    chain."""
    if not is_qtensor(w):
        return x @ w
    q, s = w["q"], w["s"]
    if s.shape[-2] == 1 and "z" not in w:
        # Scale multiply in f32 with ONE final cast, matching materialize()'s
        # dequantize-at-load contract — a bf16 scale would shed ~8 mantissa
        # bits and diverge from the grouped path beyond quantization error.
        out = (x @ q.astype(x.dtype)).astype(jnp.float32)
        return (out * s[..., 0, :].astype(jnp.float32)).astype(x.dtype)
    return x @ materialize(w, x.dtype)


def stack_layers(xs: list):
    """Stack per-layer weights onto a leading layer axis — QTensor-aware
    (stacks each field), shared by the HF and GGUF loaders."""
    if isinstance(xs[0], dict):
        return {k: jnp.stack([x[k] for x in xs]) for k in xs[0]}
    return jnp.stack(xs)


def quant_walk(tree: dict, bits: int, group: Optional[int], leaf) -> dict:
    """Shared eligibility walk for the real and abstract quantizers:
    ``leaf(v, group)`` maps each eligible weight; narrow projections that
    do not divide the group fall back to per-channel (or stay full-width
    for int4, which needs groups)."""
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out[k] = quant_walk(v, bits, group, leaf)
        elif k in QUANT_KEYS:
            g = group
            if g is not None and v.shape[-2] % g:
                # narrow projections (e.g. MLA kv_a with small D) may
                # not divide; fall back to per-channel rather than fail
                g = None
                if bits == 4:
                    _logger.warning(
                        "quantize_params: %s dim %d not divisible by "
                        "group %d — kept at FULL width (int4 needs "
                        "groups)", k, v.shape[-2], group)
                    out[k] = v
                    continue
                _logger.warning(
                    "quantize_params: %s dim %d not divisible by group "
                    "%d — per-channel int8 instead", k, v.shape[-2],
                    group)
            out[k] = leaf(v, g)
        else:
            out[k] = v
    return out


def quantize_params(params: dict, spec: str) -> dict:
    """Quantize every eligible matmul weight in a loaded param tree.

    Stacked-layer arrays ([n_layers, I, O]) and MoE expert stacks
    ([n, E, I, O]) both quantize along their second-to-last dim. Runs on
    host (numpy) so the bf16 originals never need to be device-resident
    together with the quantized copies."""
    bits, group = parse_spec(spec)
    return quant_walk(params, bits, group,
                       lambda v, g: quantize(v, bits=bits, group=g))


def quantize_params_abstract(params: dict, spec: str) -> dict:
    """ShapeDtypeStruct analog of :func:`quantize_params` — same leaf
    eligibility and QTensor shapes without touching data. This is what
    AOT compile proofs (benchmarks/plan_70b.py) lower against: 70B-scale
    quantized layouts validated without 141 GB of arrays."""
    bits, group = parse_spec(spec)
    dt = jnp.int8 if bits == 8 else jnp.int4

    def leaf(v, g):
        G = v.shape[-2] // (g or v.shape[-2])
        return {"q": jax.ShapeDtypeStruct(v.shape, dt),
                "s": jax.ShapeDtypeStruct((*v.shape[:-2], G, v.shape[-1]),
                                          jnp.float32)}

    return quant_walk(params, bits, group, leaf)


def qtensor_shardings(sh, ndim: int) -> dict:
    """Shardings of one QTensor from its weight's: ``q`` like the weight,
    ``s`` like the weight with its contraction dim replicated (scales are
    [..., G, O] — G rarely divides meshes evenly, and they are tiny)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = list(sh.spec) + [None] * (ndim - len(sh.spec))
    s_spec = list(spec)
    s_spec[-2] = None  # scales: replicate the grouped dim
    return {"q": NamedSharding(sh.mesh, P(*spec)),
            "s": NamedSharding(sh.mesh, P(*s_spec))}


def quant_shardings(shardings: dict, params: dict) -> dict:
    """Mirror a param-sharding tree onto a (partially) quantized param
    tree (see :func:`qtensor_shardings`; ``z`` follows ``s``)."""

    def walk(sh, pt):
        if is_qtensor(pt):
            out = qtensor_shardings(sh, len(pt["q"].shape))
            if "z" in pt:
                out["z"] = out["s"]
            return out
        if isinstance(pt, dict):
            return {k: walk(sh[k] if isinstance(sh, dict) else sh, v)
                    for k, v in pt.items()}
        return sh

    return {k: walk(shardings[k], v) for k, v in params.items()}
