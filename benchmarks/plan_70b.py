"""Paper-exercise Llama-3-70B on a v5e-64 slice (VERDICT r4 #9).

Two parts:

1. **Sharded compile proof**: AOT-compile the production decode step at
   70B LAYER SHAPES (hidden 8192, heads 64/8, ffn 28672) over a TP=8
   virtual mesh, depth-reduced to a few scan steps — ``lax.scan`` over
   layers means the compiled program is identical modulo the leading L
   dim, so this validates the 70B shardings without 141 GB of arrays.

2. **Budget + roofline solver**: exact per-chip HBM accounting (weights /
   KV split) and the KV-capacity-coupled decode roofline for every
   (tp, weight dtype, KV dtype) combo — decode throughput on v5e is
   bandwidth-bound, and at ISL 2000 the reachable batch is capped by KV
   residency, which feeds back into how well weight reads amortize.

Prints one JSON line; the markdown table for PERF_NOTES goes to stderr.

Usage: JAX_PLATFORMS=cpu python -m benchmarks.plan_70b [--compile]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

HBM_PER_CHIP = 16e9          # v5e
HBM_BW = 819e9               # bytes/s
RUNTIME_OVERHEAD = 1.5e9     # XLA prealloc, activations, framework slack
ISL, OSL = 2000, 256         # reference harness default workload
AVG_KV = ISL + OSL // 2      # mean resident context during decode


def model_bytes(cfg, dtype_bytes: float) -> int:
    """Exact parameter bytes for the llama3_70b preset."""
    D, F, V, L = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size, cfg.num_layers
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    per_layer = (D * H * hd + 2 * D * KV * hd + H * hd * D  # q k v o
                 + 3 * D * F                                # gate up down
                 + 2 * D)                                   # norms (f32-ish, ~0)
    total = L * per_layer + 2 * V * D + D                   # embed + head + norm
    return int(total * dtype_bytes)


def kv_bytes_per_token_per_chip(cfg, tp: int, kv_dtype_bytes: float) -> float:
    """K+V bytes one context token occupies on ONE chip (KV heads shard
    over tp; tp > num_kv_heads replicates heads, capping the win)."""
    heads_per_chip = max(cfg.num_kv_heads / tp, 1.0)
    scale = 4.0 / 16 if kv_dtype_bytes == 1 else 0.0  # int8: f32 scale per (slot, head)
    return 2 * cfg.num_layers * heads_per_chip * (cfg.head_dim * kv_dtype_bytes + scale)


def solve(cfg, tp: int, w_bytes: float, kv_b: float) -> dict:
    """Per-worker batch the HBM budget allows, and the decode roofline at
    that batch. Returns Nones when weights alone do not fit."""
    w_per_chip = model_bytes(cfg, w_bytes) / tp
    kv_room = HBM_PER_CHIP - RUNTIME_OVERHEAD - w_per_chip
    if kv_room <= 0:
        return {"fits": False, "weights_gb_chip": round(w_per_chip / 1e9, 1)}
    kvpt = kv_bytes_per_token_per_chip(cfg, tp, kv_b)
    max_tokens = int(kv_room / kvpt)
    batch = max_tokens // (ISL + OSL)  # each seq holds its full context
    if batch == 0:
        return {"fits": False, "weights_gb_chip": round(w_per_chip / 1e9, 1),
                "note": "KV room < one sequence"}
    # bandwidth-bound step: weights once + every seq's context once
    step_bytes = w_per_chip + batch * AVG_KV * kvpt
    step_s = step_bytes / HBM_BW
    tok_s_worker = batch / step_s
    return {
        "fits": True,
        "weights_gb_chip": round(w_per_chip / 1e9, 1),
        "kv_room_gb_chip": round(kv_room / 1e9, 1),
        "kv_bytes_per_tok_chip": int(kvpt),
        "max_batch_per_worker": batch,
        "step_ms_roofline": round(step_s * 1e3, 1),
        "tok_s_per_chip_roofline": int(tok_s_worker / tp),
        "tok_s_per_chip_at_60pct": int(0.6 * tok_s_worker / tp),
    }


#: the north-star topology on a v5e-64 slice (docs/PERF_NOTES.md "Hub
#: ceiling vs the 70B fleet"): 2 prefill workers + 6 decode workers, TP=8
#: each — 64 chips total. The combo is the solver's best-fitting config
#: (int4-g32 weights + int8 KV: the only pair with real batch headroom).
PLACEMENT_PREFILL_WORKERS = 2
PLACEMENT_DECODE_WORKERS = 6
PLACEMENT_TP = 8
PLACEMENT_COMBO = "tp8_wint4_kvint8"

#: measured hub ceilings the placement is checked against (PERF_NOTES):
#: ~11.7k rpc/s for non-stream hub ops, 119.5k stored blocks/s on the
#: per-request-batched event path, vs the fleet's ~53k blocks/s demand
HUB_RPC_CEILING_PER_S = 11_700
HUB_BLOCKS_CEILING_PER_S = 119_500
HUB_BLOCKS_REQUIRED_PER_S = 53_000


def placement(combo: str = PLACEMENT_COMBO) -> dict:
    """The solved north-star placement as one machine-readable document.

    This is what ``--emit-placement`` prints and what
    ``benchmarks/flagship_drive.py`` instantiates as a mocker fleet —
    the drive consumes the plan instead of re-deriving worker counts,
    step timings, and batch bounds by hand."""
    from dynamo_tpu.engine.config import ModelConfig

    cfg = ModelConfig.llama3_70b()
    w_bytes = {"bf16": 2.0, "int8": 1.0, "int4": 0.5}
    kv_bytes = {"bf16": 2.0, "int8": 1.0}
    # combo key grammar: tp{N}_w{dtype}_kv{dtype}
    tp_s, w_s, kv_s = combo.split("_")
    tp = int(tp_s[2:])
    solved = solve(cfg, tp, w_bytes[w_s[1:]], kv_bytes[kv_s[2:]])
    if not solved.get("fits"):
        raise ValueError(f"placement combo {combo} does not fit on v5e")
    # per-request stored-block math at the reference workload (PERF_NOTES):
    # prefill mints ceil(ISL/16) blocks per request; decode one block per
    # 16 generated tokens
    block = 16
    decode_tok_s = solved["tok_s_per_chip_roofline"] * tp \
        * PLACEMENT_DECODE_WORKERS
    req_s = decode_tok_s / OSL
    stored_blocks_s = int(req_s * math.ceil(ISL / block)
                          + decode_tok_s / block)
    return {
        "model": "llama3-70b",
        "slice": "v5e-64",
        "workload": {"isl": ISL, "osl": OSL},
        "combo": combo,
        "prefill": {"workers": PLACEMENT_PREFILL_WORKERS, "tp": tp,
                    **solved},
        "decode": {"workers": PLACEMENT_DECODE_WORKERS, "tp": tp,
                   **solved},
        "fleet": {
            "workers": PLACEMENT_PREFILL_WORKERS + PLACEMENT_DECODE_WORKERS,
            "chips": (PLACEMENT_PREFILL_WORKERS
                      + PLACEMENT_DECODE_WORKERS) * tp,
            "decode_tok_s": int(decode_tok_s),
            "request_rate_per_s": round(req_s, 1),
            "stored_blocks_per_s": stored_blocks_s,
        },
        "hub": {
            "rpc_ceiling_per_s": HUB_RPC_CEILING_PER_S,
            "blocks_ceiling_per_s": HUB_BLOCKS_CEILING_PER_S,
            "blocks_required_per_s": HUB_BLOCKS_REQUIRED_PER_S,
        },
    }


#: ceiling on the quantized combo's REAL bandwidth demand relative to the
#: solver's analytic roofline (quant_metrics): f32 group scales on int4-g32
#: weights cost 4/32 = 0.125 B/element over the 0.5 B/element payload, so
#: ~1.15× is the honest layout tax; past 1.25 the layout has regressed
#: (scales stored wide, a leaf fallen back to full width, ...)
QUANT_HBM_UTIL_CEILING = 1.25

#: the materialization guard (held by tests/test_quant_serving.py::
#: test_quant_compile_proof_never_materializes_full_width): a
#: grouped dequant chain that materializes full-width weight copies would
#: ADD gigabytes of temp to the 2-layer TP8 step (w_down alone is 0.94 GB
#: f32) — so the quantized program's temp bytes must stay BELOW the bf16
#: program's, never above. Measured on CPU AOT: 0.526 GB quant vs
#: 0.975 GB bf16.
QUANT_TEMP_RATIO_CEILING = 1.05


def compile_proof(tp: int = 8, layers: int = 2, quantization=None,
                  kv_int8: bool = False) -> dict:
    """AOT-compile the decode step at 70B layer shapes over a TP mesh.

    ``quantization``/``kv_int8`` lower the step against the ABSTRACT
    quantized param tree (engine/quant.quantize_params_abstract) and the
    int8 paged-KV pytree — the solved ``tp8_wint4_kvint8`` placement
    proven to lower, shard, and stay under the no-materialization temp
    ceiling without 141 GB of arrays."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={tp}").strip()
    import functools

    import jax
    import jax.numpy as jnp

    jax.config.update("jax_platforms", "cpu")
    from dynamo_tpu.engine import model as M
    from dynamo_tpu.engine.cache import tree_nbytes
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.parallel import MeshConfig, make_mesh

    full = ModelConfig.llama3_70b()
    cfg = ModelConfig(**{**full.__dict__, "num_layers": layers})
    mesh = make_mesh(MeshConfig(dp=1, sp=1, tp=tp))
    block_size, num_blocks, B, W = 16, 64, 8, 16

    params = jax.eval_shape(functools.partial(M.init_params, cfg),
                            jax.random.key(0))
    sh_params = M.param_shardings(cfg, mesh)
    if quantization is not None:
        from dynamo_tpu.engine.quant import (
            quant_shardings, quantize_params_abstract,
        )
        params = quantize_params_abstract(params, quantization)
        sh_params = quant_shardings(sh_params, params)
    slots = num_blocks * block_size
    if kv_int8:
        kc = {"q": jax.ShapeDtypeStruct(
                  (cfg.num_layers, slots, cfg.num_kv_heads, cfg.head_dim),
                  jnp.int8),
              "s": jax.ShapeDtypeStruct(
                  (cfg.num_layers, slots, cfg.num_kv_heads), jnp.float32)}
        sh_cache = M.cache_shardings(mesh, cfg, quant=True)
    else:
        kc = jax.ShapeDtypeStruct((cfg.num_layers, slots,
                                   cfg.num_kv_heads, cfg.head_dim),
                                  jnp.dtype(cfg.dtype))
        sh_cache = M.cache_shardings(mesh, cfg)
    args = (
        params,
        jax.ShapeDtypeStruct((B, 1), jnp.int32),      # tokens
        jax.ShapeDtypeStruct((B, 1), jnp.int32),      # positions
        jax.ShapeDtypeStruct((B, 1), jnp.int32),      # slot_map
        jax.ShapeDtypeStruct((B, W), jnp.int32),      # block_tables
        jax.ShapeDtypeStruct((B,), jnp.int32),        # kv_lens
        jax.ShapeDtypeStruct((B,), jnp.int32),        # last_idx
        kc, kc,
    )
    fn = functools.partial(M.forward, cfg=cfg, block_size=block_size,
                           mesh=mesh)
    bs = M.batch_shardings(mesh)
    in_sh = (sh_params, bs["tokens"], bs["positions"], bs["slot_map"],
             bs["block_tables"], bs["kv_lens"], bs["last_idx"],
             sh_cache, sh_cache)
    with mesh:
        lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
        compiled = lowered.compile()
    ma = compiled.memory_analysis()
    return {
        "tp": tp, "layers": layers,
        "quantization": quantization, "kv_int8": kv_int8,
        "params_bytes": int(tree_nbytes(params)),
        "argument_gb": round(ma.argument_size_in_bytes / 1e9, 2),
        "temp_gb": round(ma.temp_size_in_bytes / 1e9, 3),
        "output_gb": round(ma.output_size_in_bytes / 1e9, 3),
    }


def quant_metrics(combo: str = PLACEMENT_COMBO) -> dict:
    """Ground-truth HBM accounting for a quantized combo from the REAL
    quantized param tree (abstract — shapes only, full 80-layer depth),
    against the solver's analytic estimate.

    ``kernel_hbm_util_v5e`` is the fraction of v5e peak bandwidth the
    placement needs to hit its solved roofline tok/s once the real layout
    tax (f32 group scales, non-divisible leaves kept wide) is counted:
    1.0 = the analytic plan was exact, > QUANT_HBM_UTIL_CEILING = the
    quantized layout regressed and the plan is infeasible."""
    import functools

    import jax

    from dynamo_tpu.engine import model as M
    from dynamo_tpu.engine.cache import tree_nbytes
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.quant import quantize_params_abstract

    cfg = ModelConfig.llama3_70b()
    tp_s, w_s, kv_s = combo.split("_")
    tp = int(tp_s[2:])
    wname, kvname = w_s[1:], kv_s[2:]
    w_bytes = {"bf16": 2.0, "int8": 1.0, "int4": 0.5}[wname]
    kv_b = {"bf16": 2.0, "int8": 1.0}[kvname]
    solved = solve(cfg, tp, w_bytes, kv_b)
    params = jax.eval_shape(functools.partial(M.init_params, cfg),
                            jax.random.key(0))
    spec = {"int8": "int8", "int4": "int4-g32"}.get(wname)
    if spec is not None:
        params = quantize_params_abstract(params, spec)
    pb = int(tree_nbytes(params))
    out = {"combo": combo, "quant_spec": spec, "params_bytes": pb,
           "weights_gb_chip_actual": round(pb / tp / 1e9, 2),
           "fits": bool(solved.get("fits"))}
    if not solved.get("fits"):
        return out
    # the step the solver planned, re-costed with the real weight bytes
    kvpt = kv_bytes_per_token_per_chip(cfg, tp, kv_b)
    batch = solved["max_batch_per_worker"]
    step_bytes = pb / tp + batch * AVG_KV * kvpt
    planned_step_s = solved["step_ms_roofline"] / 1e3
    out["kernel_hbm_util_v5e"] = round(
        step_bytes / (planned_step_s * HBM_BW), 3)
    out["tok_s_per_chip_roofline_actual"] = int(
        batch / (step_bytes / HBM_BW) / tp)
    return out


def assert_quant(run_compile: bool = False) -> dict:
    """The ``--assert-quant`` exit gate: the solved quantized placement
    must fit, its real-layout bandwidth demand must stay under
    QUANT_HBM_UTIL_CEILING, and (with ``run_compile``) the quantized step
    must AOT-lower with temp bytes under the no-materialization ceiling.
    The bench quant phase runs the solver half of this; the compile half
    also runs as a test (tests/test_quant_serving.py)."""
    proofs = None
    if run_compile:
        # BEFORE any other jax use: compile_proof sets the host-device
        # XLA flag, which only takes effect if jax is uninitialized
        proofs = (compile_proof(quantization="int4-g32", kv_int8=True),
                  compile_proof())
    qm = quant_metrics(PLACEMENT_COMBO)
    ok = qm["fits"] and qm.get(
        "kernel_hbm_util_v5e", 99.0) <= QUANT_HBM_UTIL_CEILING
    out = dict(qm)
    if proofs is not None:
        proof_q, proof_bf16 = proofs
        out["compile_proof"] = proof_q
        out["compile_proof_bf16"] = proof_bf16
        # materialization guard: wide dequant copies would push quant temp
        # past bf16 temp (see QUANT_TEMP_RATIO_CEILING note)
        ok = (ok and proof_q["temp_gb"]
              <= proof_bf16["temp_gb"] * QUANT_TEMP_RATIO_CEILING)
    out["quant_ok"] = bool(ok)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--compile", action="store_true",
                    help="also AOT-compile the sharded step (slow on 1 core)")
    ap.add_argument("--emit-placement", action="store_true",
                    help="print ONLY the solved north-star placement "
                         "(2xTP8 prefill + 6xTP8 decode) as JSON and exit")
    ap.add_argument("--combo", default=PLACEMENT_COMBO,
                    help=f"placement combo key (default {PLACEMENT_COMBO})")
    ap.add_argument("--assert-quant", action="store_true",
                    help="exit 1 unless the solved quantized placement "
                         "(tp8_wint4_kvint8) fits with real-layout bytes "
                         "under the bandwidth ceiling; add --compile to "
                         "also AOT-lower the quantized step and gate its "
                         "temp bytes (no-materialization proof)")
    cli = ap.parse_args()

    if cli.emit_placement:
        print(json.dumps(placement(cli.combo)), flush=True)
        return

    if cli.assert_quant:
        res = assert_quant(run_compile=cli.compile)
        print(json.dumps(res), flush=True)
        sys.exit(0 if res["quant_ok"] else 1)

    from dynamo_tpu.engine.config import ModelConfig
    cfg = ModelConfig.llama3_70b()

    combos = {}
    for tp in (8, 16):
        for wname, wb in (("bf16", 2.0), ("int8", 1.0), ("int4", 0.5)):
            for kname, kb in (("bf16", 2.0), ("int8", 1.0)):
                combos[f"tp{tp}_w{wname}_kv{kname}"] = solve(cfg, tp, wb, kb)

    out = {
        "model": "llama3-70b",
        "workload": f"ISL={ISL} OSL={OSL} (benchmarking.md:33)",
        "params_b": round(model_bytes(cfg, 1.0) / 1e9, 1),
        "combos": combos,
    }
    if cli.compile:
        out["compile_proof"] = compile_proof()

    # human table to stderr
    print("| config | w GB/chip | KV room | max B/worker | roofline tok/s/chip | @60% |",
          file=sys.stderr)
    print("|---|---|---|---|---|---|", file=sys.stderr)
    for k, v in combos.items():
        if not v.get("fits"):
            print(f"| {k} | {v['weights_gb_chip']} | DOES NOT FIT | - | - | - |",
                  file=sys.stderr)
        else:
            print(f"| {k} | {v['weights_gb_chip']} | {v['kv_room_gb_chip']} | "
                  f"{v['max_batch_per_worker']} | {v['tok_s_per_chip_roofline']} | "
                  f"{v['tok_s_per_chip_at_60pct']} |", file=sys.stderr)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
