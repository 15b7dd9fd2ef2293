"""On-device quantized weights: int8/int4 resident in HBM, dequantized in
the matmul path.

The reference's flagship recipes serve quantized checkpoints — FP8 70B
disagg (ref: recipes/llama-3-70b/vllm/disagg-single-node/deploy.yaml:21-86)
and gpt-oss-120b MXFP4 (ref: recipes/gpt-oss-120b/trtllm/agg/deploy.yaml).
Dequantizing to bf16 at load can never fit 70B-class weights in v5e HBM
(16 GB/chip), so here weights STAY quantized on device and dequantization
rides the matmul:

- **per-out-channel scales** (one group: ``G == 1``): computed as
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
treat it uniformly. Layout convention matches the model's weights: ``q`` has
the weight's shape, and ``s``/``z`` have it too but for the contraction axis,
which holds ``G = I // group`` scales (``G == 1`` = per-out-channel). Every
matmul weight lies ``w[..., I, O]`` (scales ``[..., G, O]``) but the
attention projections ``wq``/``wk``/``wv`` (:data:`HEAD_MAJOR_KEYS`), which
lie ``w[..., heads, width, I]`` with the contraction LAST (scales
``[..., heads, width, G]``), the way their dot reads them
(:func:`qmm_heads`; model.py's pytree comment says why). Whatever handles a
QTensor takes that axis (:func:`contraction_axis` of the weight's key, -2
where none is given); either way a scale covers the same elements of the
same logical matrix.
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

#: the attention projections, stored ``[..., heads, width, D]`` and contracted
#: over their LAST axis (model.py's pytree comment says why); every other
#: matmul weight is ``[..., I, O]``
HEAD_MAJOR_KEYS = frozenset({"wq", "wk", "wv"})


def is_qtensor(w) -> bool:
    return isinstance(w, dict) and "q" in w and "s" in w


def contraction_axis(key: str) -> int:
    """The axis the weight named ``key`` is contracted over, which is the
    one its scales group along: the last for :data:`HEAD_MAJOR_KEYS`, the
    second-to-last for every other matmul weight."""
    return -1 if key in HEAD_MAJOR_KEYS else -2


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


def quantize(w, bits: int = 8, group: Optional[int] = None,
             axis: int = -2) -> dict:
    """Symmetric quantization of ``w`` along its contraction dim ``axis``
    (-2 for ``w[..., I, O]``, -1 for a head-major ``w[..., heads, width,
    I]``; the numbers are the same, transposed).

    group=None → one scale per output channel; group=g → one scale per
    (g-chunk of I, output channel). A numpy ``w`` is quantized on the host
    (checkpoint loaders: the full-width original never reaches the
    device); a jax array or tracer is quantized where it lives, so the
    same math runs under ``jit`` for on-device random init."""
    xp = np if isinstance(w, np.ndarray) else jnp
    qmax = (1 << (bits - 1)) - 1  # 127 / 7
    wf = w.astype(xp.float32)
    axis %= wf.ndim
    I = wf.shape[axis]
    if group is None:
        group = I
    if I % group:
        raise ValueError(f"contraction dim {I} not divisible by group {group}")
    G = I // group
    grp = wf.reshape(*wf.shape[:axis], G, group, *wf.shape[axis + 1:])
    # under jit the barrier keeps this a true division, bit-equal to the
    # host path (XLA would multiply by the inexact reciprocal of a constant)
    div = qmax if xp is np else jax.lax.optimization_barrier(
        jnp.float32(qmax))
    # one scale a group: [..., G, 1, O], or [..., G, 1] head-major
    s = xp.max(xp.abs(grp), axis=axis + 1, keepdims=True) / div
    s = xp.maximum(s, 1e-12)
    q = xp.clip(xp.rint(grp / s), -qmax, qmax)
    dt = jnp.int8 if bits == 8 else jnp.int4
    return {"q": jnp.asarray(q.reshape(wf.shape), dt),
            "s": jnp.asarray(s.squeeze(axis + 1), jnp.float32)}


def dequantize(qt: dict, dtype=jnp.float32, axis: int = -2):
    """Full-width dequantized weight (tests / host-side checks)."""
    return materialize(qt, dtype, axis)


def materialize(w, dtype, axis: int = -2):
    """The weight as a matmul/einsum operand: a passthrough for plain
    arrays, the fusable dequant chain for QTensors (``axis``: the
    contraction dim its scales group along). Use this at einsum sites (MoE
    experts); plain 2-D matmuls should prefer :func:`qmm`.

    Dequant math runs in f32 with ONE final cast so the result matches a
    dequantize-at-load weight bit-for-bit (f16 GGUF scales would lose
    mantissa bits if cast to bf16 first); the chain stays elementwise, so
    XLA still fuses it into the dot's operand read."""
    if not is_qtensor(w):
        return w
    q, s = w["q"], w["s"]
    g = q.shape[axis] // s.shape[axis]
    out = q.astype(jnp.float32) * jnp.repeat(s.astype(jnp.float32), g,
                                             axis=axis)
    if "z" in w:
        out = out - jnp.repeat(w["z"].astype(jnp.float32), g, axis=axis)
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


def qmm_heads(x, w):
    """``x[..., I]`` against a head-major, maybe-quantized
    ``w[heads, width, I]`` → ``[..., heads, width]``: the contraction runs
    over the weight's LAST axis, which is how the TPU's dot wants a
    projection that produces heads, so a layer's slice of the stack is an
    operand of the dot itself and nothing copies or transposes it first.
    Scales as in :func:`qmm`."""
    def dot(w):
        return jnp.einsum("...i,hki->...hk", x, w)

    if not is_qtensor(w):
        return dot(w)
    q, s = w["q"], w["s"]
    if s.shape[-1] == 1 and "z" not in w:
        out = dot(q.astype(x.dtype)).astype(jnp.float32)
        return (out * s[..., 0].astype(jnp.float32)).astype(x.dtype)
    return dot(materialize(w, x.dtype, axis=-1))


def stack_layers(xs: list):
    """Stack per-layer weights onto a leading layer axis — QTensor-aware
    (stacks each field), shared by the HF and GGUF loaders."""
    if isinstance(xs[0], dict):
        return {k: jnp.stack([x[k] for x in xs]) for k in xs[0]}
    return jnp.stack(xs)


def quant_walk(tree: dict, bits: int, group: Optional[int], leaf) -> dict:
    """Shared eligibility walk for the real and abstract quantizers:
    ``leaf(v, group, axis)`` maps each eligible weight (``axis``: its
    contraction dim, the last for :data:`HEAD_MAJOR_KEYS`); narrow
    projections that do not divide the group fall back to per-channel (or
    stay full-width for int4, which needs groups)."""
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out[k] = quant_walk(v, bits, group, leaf)
        elif k in QUANT_KEYS:
            g, axis = group, contraction_axis(k)
            if g is not None and v.shape[axis] % g:
                # narrow projections (e.g. MLA kv_a with small D) may
                # not divide; fall back to per-channel rather than fail
                g = None
                if bits == 4:
                    _logger.warning(
                        "quantize_params: %s dim %d not divisible by "
                        "group %d — kept at FULL width (int4 needs "
                        "groups)", k, v.shape[axis], group)
                    out[k] = v
                    continue
                _logger.warning(
                    "quantize_params: %s dim %d not divisible by group "
                    "%d — per-channel int8 instead", k, v.shape[axis],
                    group)
            out[k] = leaf(v, g, axis)
        else:
            out[k] = v
    return out


def quantize_params(params: dict, spec: str) -> dict:
    """Quantize every eligible matmul weight in a loaded param tree.

    Stacked-layer arrays ([n_layers, I, O]) and MoE expert stacks
    ([n, E, I, O]) both quantize along their second-to-last dim, the
    head-major attention projections ([n_layers, heads, width, I]) along
    their last. Runs on host (numpy) so the bf16 originals never need to be
    device-resident together with the quantized copies."""
    bits, group = parse_spec(spec)
    return quant_walk(
        params, bits, group,
        lambda v, g, axis: quantize(v, bits=bits, group=g, axis=axis))


def quantize_params_abstract(params: dict, spec: str) -> dict:
    """ShapeDtypeStruct analog of :func:`quantize_params` — same leaf
    eligibility and QTensor shapes without touching data. This is what
    AOT compile proofs (benchmarks/plan_70b.py) lower against: 70B-scale
    quantized layouts validated without 141 GB of arrays."""
    bits, group = parse_spec(spec)
    dt = jnp.int8 if bits == 8 else jnp.int4

    def leaf(v, g, axis):
        s_shape = list(v.shape)
        s_shape[axis] = v.shape[axis] // (g or v.shape[axis])
        return {"q": jax.ShapeDtypeStruct(v.shape, dt),
                "s": jax.ShapeDtypeStruct(tuple(s_shape), jnp.float32)}

    return quant_walk(params, bits, group, leaf)


def qtensor_shardings(sh, ndim: int, axis: int = -2) -> dict:
    """Shardings of one QTensor from its weight's: ``q`` like the weight,
    ``s`` like the weight with its contraction dim ``axis`` replicated (G
    scales lie there — G rarely divides meshes evenly, and they are
    tiny)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = list(sh.spec) + [None] * (ndim - len(sh.spec))
    s_spec = list(spec)
    s_spec[axis] = None  # scales: replicate the grouped dim
    return {"q": NamedSharding(sh.mesh, P(*spec)),
            "s": NamedSharding(sh.mesh, P(*s_spec))}


def quant_shardings(shardings: dict, params: dict) -> dict:
    """Mirror a param-sharding tree onto a (partially) quantized param
    tree (see :func:`qtensor_shardings`; ``z`` follows ``s``)."""

    def walk(key, sh, pt):
        if is_qtensor(pt):
            out = qtensor_shardings(sh, len(pt["q"].shape),
                                    contraction_axis(key))
            if "z" in pt:
                out["z"] = out["s"]
            return out
        if isinstance(pt, dict):
            return {k: walk(k, sh[k] if isinstance(sh, dict) else sh, v)
                    for k, v in pt.items()}
        return sh

    return {k: walk(k, shardings[k], v) for k, v in params.items()}
