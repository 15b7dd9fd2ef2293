"""TPU cross-lowering guard for the kernels OFF the serving path.

``jax.export(platforms=["tpu"])`` runs Pallas→Mosaic MLIR generation and
the Mosaic dialect verifier — it catches malformed BlockSpecs and illegal
ops, and nothing the Mosaic COMPILER decides (tile alignment of dynamic
slices, fast-memory limits: the ragged kernel passed here for two rounds
and was refused by the compiler at every width). The real check is
tests/test_chip_compile.py, which compiles for a described v5e chip and
covers every kernel the serving path can select. These export cases stay
for the retired decode / MLA kernels and the bucketed oracle step that
only ``forward(ragged=None)`` reaches, until ROADMAP D2 deletes both.
"""

from unittest import mock

import jax
import jax.numpy as jnp


def _export_tpu(fn, *args):
    # paged_attention picks interpret mode off the default backend; fake
    # a TPU host so the REAL kernel path lowers (the export target is
    # what matters, not the local backend)
    with mock.patch.object(jax, "default_backend", return_value="tpu"):
        exp = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    assert b"tpu_custom_call" in exp.mlir_module_serialized, \
        "no Mosaic kernel in the exported module (interpret path lowered?)"
    return exp


def test_gqa_decode_kernel_exports_for_tpu():
    from dynamo_tpu.ops.paged_attention import paged_attention_decode

    B, KV, hd, H, bs, nb = 4, 8, 128, 32, 16, 32
    slots = nb * bs
    q = jnp.zeros((B, H, hd), jnp.bfloat16)
    kc = jnp.zeros((slots, KV, hd), jnp.bfloat16)
    bt = jnp.zeros((B, nb), jnp.int32)
    lens = jnp.full((B,), 64, jnp.int32)

    _export_tpu(lambda *a: paged_attention_decode(*a, block_size=bs),
                q, kc, kc, bt, lens)


def test_gqa_decode_int8_scale_placements_export_for_tpu(monkeypatch):
    """Both int8 scale placements: VMEM-resident transposed [KV, slots]
    (incl. the scale_slot_base rebase + dynamic lane slice) and the
    per-page scale-DMA fallback."""
    from dynamo_tpu.ops.paged_attention import paged_attention_decode

    B, KV, hd, H, bs, nb = 4, 8, 128, 32, 16, 32
    slots = nb * bs
    q = jnp.zeros((B, H, hd), jnp.bfloat16)
    kc = jnp.zeros((slots, KV, hd), jnp.int8)
    bt = jnp.zeros((B, nb), jnp.int32)
    lens = jnp.full((B,), 64, jnp.int32)
    ks = jnp.ones((slots, KV), jnp.float32)

    def make_fn():
        # a FRESH function object per export: the env var is read at trace
        # time, and jax's trace cache is keyed on (callable, avals) — the
        # same object would silently reuse the first placement's jaxpr
        def fn(*a):
            q, kc, vc, bt, lens, ks, vs = a
            return paged_attention_decode(q, kc, vc, bt, lens,
                                          block_size=bs, k_scales=ks,
                                          v_scales=vs,
                                          scale_slot_base=slots)
        return fn

    monkeypatch.setenv("DYN_KV_SCALE_VMEM_BYTES", str(1 << 30))
    _export_tpu(make_fn(), q, kc, kc, bt, lens, ks, ks)
    monkeypatch.setenv("DYN_KV_SCALE_VMEM_BYTES", "0")
    _export_tpu(make_fn(), q, kc, kc, bt, lens, ks, ks)


def test_mla_decode_kernels_export_for_tpu():
    from dynamo_tpu.ops.paged_attention import mla_paged_decode

    B, H, R, PR, bs, nb = 4, 16, 512, 128, 16, 32
    slots = nb * bs
    qe = jnp.zeros((B, H, R), jnp.bfloat16)
    qr = jnp.zeros((B, H, PR), jnp.bfloat16)
    bt = jnp.zeros((B, nb), jnp.int32)
    lens = jnp.full((B,), 64, jnp.int32)

    _export_tpu(lambda *a: mla_paged_decode(
        *a, block_size=bs, scale=0.1),
        qe, qr, jnp.zeros((slots, R), jnp.bfloat16),
        jnp.zeros((slots, PR), jnp.bfloat16), bt, lens)

    # int8 latent pages with lane-packed scales + slot-base rebase
    _export_tpu(lambda qe, qr, cc, rc, bt, lens, cs, rs: mla_paged_decode(
        qe, qr, cc, rc, bt, lens, block_size=bs, scale=0.1,
        c_scales=cs, r_scales=rs, scale_slot_base=slots),
        qe, qr, jnp.zeros((slots, R), jnp.int8),
        jnp.zeros((slots, PR), jnp.int8), bt, lens,
        jnp.ones((slots,), jnp.float32), jnp.ones((slots,), jnp.float32))


def test_flash_prefill_kernel_exports_for_tpu():
    from dynamo_tpu.ops.flash_prefill import flash_prefill_paged

    L, KV, hd, H, bs, nb, B, S = 2, 8, 128, 32, 16, 16, 2, 64
    slots = nb * bs
    q = jnp.zeros((B, S, H, hd), jnp.bfloat16)
    kc = jnp.zeros((L, slots, KV, hd), jnp.bfloat16)
    lidx = jnp.int32(0)
    bt = jnp.zeros((B, nb), jnp.int32)
    pos = jnp.tile(jnp.arange(S, dtype=jnp.int32), (B, 1))
    lens = jnp.full((B,), S, jnp.int32)

    _export_tpu(lambda *a: flash_prefill_paged(*a, block_size=bs),
                q, kc, kc, lidx, bt, pos, lens)


def test_full_serving_step_exports_for_tpu():
    """The COMPOSED serving step — scan over layers, Pallas decode
    attention, int8 resident weights, int8 KV with layer-sliced scales —
    at llama3-1b production widths (depth-reduced: scan makes the
    program identical modulo the leading L dim)."""
    import functools

    import numpy as np

    from dynamo_tpu.engine import model as M
    from dynamo_tpu.engine.cache import allocate_device_cache
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.quant import quantize_params

    cfg = ModelConfig(
        vocab_size=128256, hidden_size=2048, intermediate_size=8192,
        num_layers=2, num_heads=32, num_kv_heads=8, head_dim=64,
        rope_theta=500000.0, max_position_embeddings=8192,
        tie_word_embeddings=True)
    bs, nb, B, W = 16, 64, 8, 16
    params = quantize_params(
        jax.tree.map(np.asarray, M.init_params(cfg, jax.random.key(0))),
        "int8")
    kc, vc = allocate_device_cache(cfg, nb, bs, None, dtype="int8")
    args = (params,
            jnp.zeros((B, 1), jnp.int32), jnp.zeros((B, 1), jnp.int32),
            jnp.zeros((B, 1), jnp.int32),
            jnp.zeros((B, W), jnp.int32), jnp.full((B,), 64, jnp.int32),
            jnp.zeros((B,), jnp.int32), kc, vc)
    fn = functools.partial(M.forward, cfg=cfg, block_size=bs,
                           use_pallas=True)
    _export_tpu(fn, *args)


def test_flash_prefill_int8_cache_exports_for_tpu():
    """Quant-cache flash prefill ({"q","s"} pytree caches, dequant fused
    into the page gather) must also cross-lower for TPU."""
    from dynamo_tpu.ops.flash_prefill import flash_prefill_paged

    L, KV, hd, H, bs, nb, B, S = 2, 8, 128, 32, 16, 16, 2, 64
    slots = nb * bs
    q = jnp.zeros((B, S, H, hd), jnp.bfloat16)
    kq = {"q": jnp.zeros((L, slots, KV, hd), jnp.int8),
          "s": jnp.ones((L, slots, KV), jnp.float32)}
    lidx = jnp.int32(0)
    bt = jnp.zeros((B, nb), jnp.int32)
    pos = jnp.tile(jnp.arange(S, dtype=jnp.int32), (B, 1))
    lens = jnp.full((B,), S, jnp.int32)

    _export_tpu(lambda *a: flash_prefill_paged(*a, block_size=bs),
                q, kq, kq, lidx, bt, pos, lens)
