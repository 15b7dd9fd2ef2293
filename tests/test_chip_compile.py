"""The chip's own compiler, asked without the chip.

libtpu is installed wherever these tests run, and it compiles for a TPU that
is DESCRIBED, not attached (``jax.experimental.topologies``). That is the
only check short of a chip run that sees what Mosaic refuses — interpret
mode and ``jax.export`` (tests/test_tpu_export.py stops at the dialect
verifier) passed kernels the compiler then rejected for a DMA offset it
could not prove tile-aligned and for lane-dim slices off a multiple of 128.
Every kernel the serving path can select is compiled here at Mistral-7B and
Qwen2-7B widths, and the compiled text must contain the Mosaic call.

Rules this file keeps (on-chip-measurement guide §2): the topology, the
sharding and every shape built from it live in module-scoped fixtures — only
one process may hold libtpu, so nothing here runs at import, in conftest, in
a child process or through an autouse fixture, and all of it stays in this
one file. The persistent compilation cache is off around these compiles (an
entry written for a described chip cannot be read back without one).
"""

import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

#: (H, KV, hd) of the models the dense cells will use
MISTRAL_7B = (32, 8, 128)
QWEN2_7B = (28, 4, 128)
#: MiMo-V2.5's two layer kinds: (H, KV, hd of q, lane rows of a stored K
#: head, V width)
MIMO_WINDOW = (64, 8, 192, 2, 128)
MIMO_FULL = (64, 4, 192, 2, 128)
#: (T, R, W) of a decode-heavy step and of a mixed prefill+decode step at
#: the engine's defaults (max_num_seqs 64, 2048 tokens, 4096 context)
DECODE_HEAVY = (64, 64, 256)
MIXED = (2048, 64, 256)
BS = 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def compile_for_chip(one_chip, no_compile_cache):
    """compile_for_chip(fn, *specs) -> compiled text (``text=False``: the
    executable), with every spec placed on the described chip and every
    kernel wrapper taking its Mosaic path (the local backend is the CPU,
    where they would interpret)."""

    def place(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def run(fn, *specs, text=True):
        specs = jax.tree.map(place, specs)
        with mock.patch("dynamo_tpu.ops.paged_attention.kernel_interpret_mode",
                        return_value=False), \
             mock.patch("dynamo_tpu.ops.ragged_attention."
                        "kernel_interpret_mode", return_value=False), \
             mock.patch("dynamo_tpu.ops.flash_prefill.kernel_interpret_mode",
                        return_value=False), \
             mock.patch("dynamo_tpu.ops.moe_combine.kernel_interpret_mode",
                        return_value=False):
            # an already-jitted step keeps its own donation of the caches
            jitted = (fn if hasattr(fn, "lower")
                      else jax.jit(fn, out_shardings=one_chip))
            compiled = jitted.lower(*specs).compile()
            return compiled.as_text() if text else compiled

    return run


@pytest.fixture
def step_options_of_the_chip():
    """While this is in use ``model.step_compiler_options`` answers what it
    answers where the default backend is the TPU (here it is the CPU, whose
    compiler refuses the names), so a ``make_*_fn`` builds the jit the
    engine builds on the chip."""
    from dynamo_tpu.engine import model as M

    with mock.patch.object(jax, "default_backend", return_value="tpu"):
        options = M.step_compiler_options()
    assert options
    with mock.patch.object(M, "step_compiler_options",
                           return_value=options):
        yield options


def spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def rematerialised_ops(text):
    """Names and shapes of the ops XLA's rematerialisation pass added to a
    compiled program: it names a recomputed op ``<op>.remat<n>``."""
    return sorted(set(re.findall(r"%([\w.\-]*remat[\w.\-]*) = (\w+\[[\d,]*\])",
                                 text)))


def dots_producing(text, shape):
    """The matrix products of a compiled program (the TPU compiler writes a
    dot as a ``convolution``) whose result is ``shape``, e.g.
    ``bf16[2048,16768]``, fused or not."""
    return re.findall(r"%([\w.\-]+) = " + re.escape(shape)
                      + r"\S* (?:convolution|dot)\(", text)


def lone_projection_weight_ops(text, dtype, projections):
    """The ops of a compiled step that are launched on their own (they lie
    outside every fused computation) and do nothing but move one layer's
    attention projection weight: a ``copy`` or a ``fusion`` whose result is
    a ``[1, ...]`` slice of a stack with the dims of one of ``projections``
    (heads, width, D) in any order, heads and width merged or apart. Stored
    ``[L, D, heads·width]`` every layer of every step paid a slice and a
    transposing copy of each before its dot (PERF.md, PR 32); stored as the
    dot reads them there is none, the slice being an operand of the dot."""
    want = set()
    for heads, width, d in projections:
        want |= {tuple(sorted((heads, width, d))),
                 tuple(sorted((heads * width, d)))}
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    found, inside = [], False
    for ln in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", ln)
        if head:
            inside = head.group(1) in fused
            continue
        op = re.match(r"\s+(?:ROOT )?%[\w.\-]+ = (\w+)\[1,([\d,]+)\]\S* "
                      r"(?:copy|fusion)\(", ln)
        if (op and not inside and op.group(1) == dtype
                and tuple(sorted(map(int, op.group(2).split(",")))) in want):
            found.append(ln.strip()[:120])
    return found


@pytest.mark.parametrize("trw", [DECODE_HEAVY, MIXED],
                         ids=["decode_heavy", "mixed"])
@pytest.mark.parametrize("widths", [MISTRAL_7B, QWEN2_7B],
                         ids=["mistral_7b", "qwen2_7b"])
@pytest.mark.parametrize("pages", ["bf16", "int8"])
def test_ragged_kernel_compiles_for_v5e(compile_for_chip, pages, widths, trw):
    """The one kernel on the serving path, both page dtypes, with the
    sliding window traced as the layer scan traces it."""
    from dynamo_tpu.ops.ragged_attention import ragged_paged_attention

    (H, KV, hd), (T, R, W) = widths, trw
    slots = 4096 * BS
    q = spec((T, H, hd), jnp.bfloat16)
    bt, rows3 = spec((R, W), jnp.int32), spec((R, 3), jnp.int32)
    win = spec((), jnp.int32)
    if pages == "bf16":
        kc = spec((slots, KV, hd), jnp.bfloat16)
        text = compile_for_chip(
            lambda q, k, v, bt, r3, w: ragged_paged_attention(
                q, k, v, bt, r3, block_size=BS, window=w),
            q, kc, kc, bt, rows3, win)
    else:
        # one layer's scale slice of a 2-layer stacked cache, rebased
        kc = spec((2 * slots, KV, hd), jnp.int8)
        sc = spec((slots, KV), jnp.float32)
        text = compile_for_chip(
            lambda q, k, v, bt, r3, w, ks, vs, base: ragged_paged_attention(
                q, k, v, bt, r3, block_size=BS, window=w, k_scales=ks,
                v_scales=vs, scale_slot_base=base),
            q, kc, kc, bt, rows3, win, sc, sc, spec((), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("trw", [(64, 64, 2176), (2048, 64, 2176)],
                         ids=["decode_heavy", "mixed"])
@pytest.mark.parametrize("widths", [MIMO_WINDOW, MIMO_FULL],
                         ids=["mimo_window", "mimo_full"])
def test_ragged_kernel_compiles_for_v5e_k_wider_than_v(compile_for_chip,
                                                       widths, trw):
    """Both of MiMo-V2.5's layer kinds (G = 8 and 16), 192-wide q against
    K heads stored as two 128-lane rows and 128-wide V rows, at the cell's
    step shapes (64 rows, 34,816-token tables: 128 rows of that width
    do not fit the 1 MB of scalar memory the block table is prefetched
    into), sink on."""
    from dynamo_tpu.ops.ragged_attention import ragged_paged_attention

    (H, KV, hd, k_rows, vd), (T, R, W) = widths, trw
    slots = 4096 * BS
    text = compile_for_chip(
        lambda q, k, v, bt, r3, w, s: ragged_paged_attention(
            q, k, v, bt, r3, block_size=BS, window=w, sinks=s),
        spec((T, H, hd), jnp.bfloat16),
        spec((slots, KV * k_rows, 128), jnp.bfloat16),
        spec((slots, KV, vd), jnp.bfloat16), spec((R, W), jnp.int32),
        spec((R, 3), jnp.int32), spec((), jnp.int32),
        spec((H,), jnp.bfloat16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("tokens", [2048, 8], ids=["chunk", "decode"])
@pytest.mark.parametrize("shape", [
    (4096, 2048, 16, 8), (2048, 4096, 16, 8),
    (4096, 768, 36, 10), (768, 4096, 36, 10)],
    ids=["mimo_gate_up", "mimo_down", "granite_gate_up", "granite_down"])
def test_grouped_matmul_compiles_for_v5e(compile_for_chip, shape, tokens):
    """The held-experts layer's product at MiMo-V2.5's widths (16 experts,
    8 a token) and Granite-4.0-H-Small's (36 held, 10 a token): the
    dropless buffer of a 2,048-token step (a traced count of tiles under
    the grid's column blocks, whole-contraction weight blocks beyond the
    default VMEM limit) and of an 8-row decode step (square blocks)."""
    from dynamo_tpu.ops.grouped_matmul import ROW_TILE, grouped_matmul

    k, n, E, K = shape
    tiles = -(-tokens * K // ROW_TILE) + E
    with mock.patch("dynamo_tpu.ops.grouped_matmul.kernel_interpret_mode",
                    return_value=False):
        text = compile_for_chip(
            grouped_matmul, spec((tiles * ROW_TILE, k), jnp.bfloat16),
            spec((5, E, k, n), jnp.bfloat16), spec((tiles,), jnp.int32),
            spec((), jnp.int32), spec((), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("tokens", [2048, 8], ids=["chunk", "decode"])
@pytest.mark.parametrize("shape", [(16, 8, 4096, 2048), (36, 10, 4096, 768),
                                   (64, 4, 2048, 1536)],
                         ids=["mimo", "granite", "lfm2"])
def test_moe_combine_compiles_for_v5e(compile_for_chip, shape, tokens):
    """The held-experts layer's read-back at MiMo-V2.5's, Granite-4.0-H-
    Small's and LFM2-24B-A2B's widths (held experts, choices a token, D,
    F), a 2,048-token step and an 8-row decode step, with the down launch
    that writes the rows apart for it (whole-contraction blocks and square
    ones): Mosaic accepts both, and the combine asks for no more than the
    compiler's default VMEM (what a launch reserves beyond it, XLA loses for
    a decode step's buffers: ops/grouped_matmul.py)."""
    from dynamo_tpu.ops.grouped_matmul import ROW_TILE, grouped_matmul
    from dynamo_tpu.ops.moe_combine import moe_combine

    E, K, D, F = shape
    tiles = -(-tokens * K // ROW_TILE) + E

    def read_back(inter, w, tile_group, num_tiles, row, gates):
        return moe_combine(
            grouped_matmul(inter, w, tile_group, num_tiles, rows_apart=True),
            row, gates, tag="_m2048")

    with mock.patch("dynamo_tpu.ops.grouped_matmul.kernel_interpret_mode",
                    return_value=False):
        text = compile_for_chip(
            read_back, spec((tiles * ROW_TILE, F), jnp.bfloat16),
            spec((E, F, D), jnp.bfloat16), spec((tiles,), jnp.int32),
            spec((), jnp.int32), spec((tokens * K,), jnp.int32),
            spec((tokens, K), jnp.float32))
    call, = [ln for ln in text.splitlines()
             if "custom-call(" in ln and "%moe_combine_m2048" in ln]
    assert "vmem_limit_bytes" not in call.replace(
        '"vmem_limit_bytes":null', ""), call
    assert f"bf16[{tiles * ROW_TILE},{D // 128},128]" in call  # rows apart


@pytest.mark.parametrize("pages", ["bf16", "int8"])
def test_flash_prefill_compiles_for_v5e(compile_for_chip, pages):
    from dynamo_tpu.ops.flash_prefill import flash_prefill_paged

    (H, KV, hd), L, B, S, nb = MISTRAL_7B, 2, 2, 256, 64
    slots = nb * BS
    q = spec((B, S, H, hd), jnp.bfloat16)
    if pages == "bf16":
        kc = spec((L, slots, KV, hd), jnp.bfloat16)
    else:
        kc = {"q": spec((L, slots, KV, hd), jnp.int8),
              "s": spec((L, slots, KV), jnp.float32)}
    text = compile_for_chip(
        lambda *a: flash_prefill_paged(*a, block_size=BS),
        q, kc, kc, spec((), jnp.int32), spec((B, nb), jnp.int32),
        spec((B, S), jnp.int32), spec((B,), jnp.int32))
    assert "tpu_custom_call" in text


def test_gqa_decode_kernel_compiles_for_v5e(compile_for_chip):
    """The retired bf16 decode kernel, while forward(ragged=None) can still
    reach it (the make_step_fn / make_verify_fn oracles)."""
    from dynamo_tpu.ops.paged_attention import paged_attention_decode

    (H, KV, hd), B, nb = MISTRAL_7B, 8, 64
    kc = spec((nb * BS, KV, hd), jnp.bfloat16)
    text = compile_for_chip(
        lambda *a: paged_attention_decode(*a, block_size=BS),
        spec((B, H, hd), jnp.bfloat16), kc, kc, spec((B, nb), jnp.int32),
        spec((B,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kv,T,layers,nb", [
    ("bf16", 8, 2, 2048), ("bf16", 256, 2, 2048),
    ("int8", 8, 2, 2048), ("int8", 256, 2, 2048),
    ("bf16", 1024, 32, 2723)],
    ids=["bf16-decode_only", "bf16-mixed", "int8-decode_only", "int8-mixed",
         "bf16-cell_m1024"])
def test_serving_step_compiles_with_kernel_for_v5e(
        compile_for_chip, step_options_of_the_chip, kv, T, layers, nb):
    """The whole jitted ragged step — layer scan, int8 weights, the kernel
    inside — at Mistral-7B widths (depth cut to 2: the scan makes the
    program the same modulo the leading L; and the 1,024-token program of
    ``mistral7b-w8.chat-steady`` at full depth with the cell's pool, 13.09
    GB of arguments): the cache must pass into the kernel without a
    relayout copy of the pool, no op but its own dot reads a layer's q, k
    or v projection weight, and nothing is computed twice."""
    import dataclasses

    from dynamo_tpu.engine import model as M
    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.models import mistral_7b

    cfg = dataclasses.replace(mistral_7b(), num_layers=layers)
    args = EngineArgs()
    R, W = args.ragged_rows(T), args.max_blocks_per_seq
    C, _ = M.ragged_grid_shape(T)
    params = jax.eval_shape(lambda: M.init_params(
        cfg, jax.random.key(0), quantization="int8"))
    shape = (cfg.num_layers, nb * BS, cfg.num_kv_heads, cfg.head_dim)
    cache = (spec(shape, jnp.bfloat16) if kv == "bf16" else
             {"q": spec(shape, jnp.int8), "s": spec(shape[:-1], jnp.float32)})
    step = M.make_ragged_step_fn(cfg, BS, None, use_pallas=True,
                                 kv_quant=kv == "int8", chunks=T > 8)
    text = compile_for_chip(
        step, params, spec((5, T), jnp.int32), spec((R, 3), jnp.int32),
        spec((C,), jnp.int32), spec((R, W), jnp.int32), cache, cache)
    assert "tpu_custom_call" in text
    # the pool is 2 x layers x slots x 8 x 128: no op but the in-place
    # page write may produce an array of that size
    pool = f"[{cfg.num_layers},{nb * BS},{cfg.num_kv_heads},{cfg.head_dim}]"
    copies = [ln for ln in text.splitlines()
              if " copy(" in ln and pool in ln.split(" copy(")[0]]
    assert not copies, copies[:2]
    H, KV, hd = MISTRAL_7B
    moved = lone_projection_weight_ops(
        text, "s8", [(H, hd, cfg.hidden_size), (KV, hd, cfg.hidden_size)])
    assert not moved, moved
    assert not rematerialised_ops(text)
    assert "moe_combine" not in text  # no expert layer: no read-back


@pytest.mark.parametrize("T", [64, 2048], ids=["decode_heavy", "mixed"])
def test_mimo_step_compiles_with_both_kernels_for_v5e(
        compile_for_chip, step_options_of_the_chip, T):
    """The whole jitted ragged step of the MiMo-V2.5 share the benchmark
    runs (models.mimo_v25_ep16: all 7 layers, published widths, both cache
    groups at the cell's 10,088 blocks — 12.81 GB of arguments — the
    held-experts layer): the ragged kernel for both layer kinds and the
    grouped matmul are Mosaic calls, neither pool is copied, no op but its
    own dot reads a layer's q, k or v projection weight (the two one-layer
    stacks' included), and nothing is computed twice."""
    from dynamo_tpu.engine import model as M
    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.models import mimo_v25_ep16

    cfg = mimo_v25_ep16()
    args = EngineArgs(max_num_seqs=64, max_num_batched_tokens=2048,
                      max_model_len=34816)
    nb = 10088
    R, W = args.ragged_rows(T), args.max_blocks_per_seq
    C, _ = M.ragged_grid_shape(T)
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.key(0)))
    groups = cfg.kv_cache_spec
    kc = tuple(spec((len(g.layers), nb * BS, *g.k_shape), jnp.bfloat16)
               for g in groups)
    vc = tuple(spec((len(g.layers), nb * BS, g.kv_heads, g.v_dim),
                    jnp.bfloat16) for g in groups)
    step = M.make_ragged_step_fn(cfg, BS, None, use_pallas=True,
                                 chunks=T > 64)
    with mock.patch("dynamo_tpu.ops.grouped_matmul.kernel_interpret_mode",
                    return_value=False):
        text = compile_for_chip(
            step, params, spec((5, T), jnp.int32), spec((R, 3), jnp.int32),
            spec((C,), jnp.int32), spec((R, W), jnp.int32), kc, vc)
    assert text.count("ragged_paged_attention") >= 2
    assert "moe_grouped_matmul" in text
    # no layer's experts are sliced out of their stack (16 x 4096 x 2048 =
    # 268 MB a matrix, copied before every launch if they were)
    sliced = [ln for ln in text.splitlines()
              if " = bf16[16,4096,2048]" in ln or " = bf16[16,2048,4096]" in ln]
    assert not sliced, sliced[:2]
    for g in groups:
        pool = f"[{len(g.layers)},{nb * BS},{g.k_shape[0]},128]"
        copies = [ln for ln in text.splitlines()
                  if " copy(" in ln and pool in ln.split(" copy(")[0]]
        assert not copies, copies[:2]
    D = cfg.hidden_size
    moved = lone_projection_weight_ops(
        text, "bf16",
        [(cfg.num_heads, cfg.head_dim, D)]
        + [(k.num_kv_heads, w, D) for k in cfg.layer_kinds
           for w in (cfg.head_dim, cfg.v_dim)])
    assert not moved, moved
    assert not rematerialised_ops(text)


#: the KV pool of ``granite4-h-small-ep2.chat-steady`` (PERF.md section 4):
#: with it a step program's arguments are 14.93 GB of the chip's 16.9
GRANITE_CELL_BLOCKS = 44740


def granite_step_text(compile_for_chip, T):
    """(compiled text, cfg, state slots) of the T-token ragged step (T = 64:
    decode-only) of the Granite-4.0-H-Small share the benchmark runs, 64
    state slots and the cell's KV blocks, built as
    ``model.step_compiler_options`` says at the moment of the call."""
    from dynamo_tpu.engine import model as M
    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.models import granite4_h_small_ep2

    cfg = granite4_h_small_ep2()
    args = EngineArgs(max_num_seqs=64, max_num_batched_tokens=2048,
                      max_model_len=8192)
    nb, slots = GRANITE_CELL_BLOCKS, args.max_num_seqs + 1
    R, W = args.ragged_rows(T), args.max_blocks_per_seq
    C, _ = M.ragged_grid_shape(T)
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.key(0)))
    (g,), st = cfg.kv_cache_spec, cfg.state_spec
    kc = spec((1, nb * BS, *g.k_shape), jnp.bfloat16)
    vc = spec((1, nb * BS, g.kv_heads, g.v_dim), jnp.bfloat16)
    n = len(st.layers)
    state = (spec((n, slots, st.conv_shape[0] * st.conv_shape[1]),
                  jnp.bfloat16),
             spec((n, slots, *st.ssm_shape), jnp.float32))
    step = M.make_ragged_step_fn(cfg, BS, None, use_pallas=True,
                                 chunks=T > 64)
    with mock.patch("dynamo_tpu.ops.grouped_matmul.kernel_interpret_mode",
                    return_value=False), \
         mock.patch("dynamo_tpu.ops.mamba2.kernel_interpret_mode",
                    return_value=False):
        text = compile_for_chip(
            step, params, spec((5, T), jnp.int32), spec((R, 4), jnp.int32),
            spec((C,), jnp.int32), spec((R, W), jnp.int32), kc, vc, state)
    return text, cfg, slots


@pytest.mark.parametrize("T", [64, 256, 512, 1024, 2048],
                         ids=["decode_only", "m256", "m512", "m1024", "mixed"])
def test_granite_step_compiles_with_its_kernels_and_no_copies_for_v5e(
        compile_for_chip, step_options_of_the_chip, T):
    """The whole jitted ragged step of the Granite-4.0-H-Small share the
    benchmark runs (models.granite4_h_small_ep2: ten layers, published
    widths, 64 state slots, THE CELL'S POOL: at 8,192 blocks the compiler
    rematerialises nothing whatever it is told, so tier 1 never saw what
    the cell ran): the update kernel, the ragged kernel and the
    grouped matmul are Mosaic calls; the SSM state stack (2.45 GB) and the
    page pool are updated in place — no op copies either — and no op
    launched on its own slices or copies a layer's ``in_proj``,
    ``out_proj`` or experts out of their stacks (a run of layers that is
    part of its stack reads it by index, where a sliced stack was copied
    whole: 1.2 GB of ``in_proj`` a step). Nothing is computed twice:
    ``in_proj``'s product is made once a run of layers."""
    text, cfg, slots = granite_step_text(compile_for_chip, T)
    st, nb = cfg.state_spec, GRANITE_CELL_BLOCKS
    n = len(st.layers)
    program = ("m" if T > 64 else "d") + str(T)
    nC = T // 128
    for run in ("l0x5", "l5x4"):  # one launch a run of Mamba-2 layers
        assert f"mamba2_decode_update_{run}_{program}" in text
        # the chunked scan: a kernel where a step can hold a chunk, whose
        # running states never leave VMEM — no array of the block states'
        # shape (a state a block a chunk row) nor of the within-block
        # decay's (a [Q, Q] matrix a head a block) is left in the text
        assert (f"mamba2_chunk_scan_{run}_{program}" in text) == (T > 64)
    if T <= 64:
        assert "mamba2_chunk_scan" not in text
    H, P, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    assert not [ln.strip()[:120] for ln in text.splitlines() if re.search(
        rf"(?:f32|bf16)\[(?:{nC},4,{H},{P},{N}|{nC},128,128,{H})\]", ln)]
    assert "ragged_paged_attention" in text
    assert "moe_grouped_matmul" in text
    lines = text.splitlines()
    ssm = "f32[" + ",".join(map(str, (n, slots, *st.ssm_shape))) + "]"
    pool = f"[1,{nb * BS},8,128]"
    copies = [ln.strip()[:120] for ln in lines
              if (" copy(" in ln or " copy-start(" in ln)
              and (ssm in ln.split("(")[0] or pool in ln.split(" copy")[0])]
    assert not copies, copies[:2]
    # a layer's matrix leaves its stack only as an operand of its own dot
    D, di = cfg.hidden_size, cfg.mamba_d_inner
    wide = di + st.conv_shape[1] + cfg.mamba_n_heads
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    moved, inside = [], False
    for ln in lines:
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", ln)
        if head:
            inside = head.group(1) in fused
            continue
        op = re.match(r"\s+(?:ROOT )?%[\w.\-]+ = bf16\[(?:\d+,)?(\d+),(\d+)\]"
                      r"\S* (?:copy|fusion|slice|dynamic-slice)\(", ln)
        if op and not inside and (int(op.group(1)), int(op.group(2))) in (
                (D, wide), (di, D)):
            moved.append(ln.strip()[:120])
    assert not moved, moved[:2]
    F, Eh = cfg.moe_ffn_size, cfg.num_experts_held
    sliced = [ln for ln in lines
              if f" = bf16[{Eh},{D},{F}]" in ln or f" = bf16[{Eh},{F},{D}]" in ln]
    assert not sliced, sliced[:2]
    assert not rematerialised_ops(text)
    if T >= 512:  # one scan a run of Mamba-2 layers: two in the program
        assert len(dots_producing(text, f"bf16[{T},{wide}]")) == 2
    # the experts' read-back: the kernel that fetches the held pairs' rows,
    # and nothing of the worst case's size (every pair's row gathered, then
    # re-laid [T, K, D] for the sum)
    K = cfg.num_experts_per_tok
    assert "moe_combine" in text
    assert not [ln.strip()[:120] for ln in lines if re.search(
        rf" = bf16\[(?:{T * K},{D}|{T},{K},{D})\]", ln)]


def test_granite_step_is_rematerialised_without_the_options(compile_for_chip):
    """Why ``model.step_compiler_options`` exists: at the cell's pool, left
    to its defaults, the compiler makes ``in_proj``'s 68 MB product more
    than once a run of layers (three times since the chunked scan is a
    kernel; twice before) to save temp that fits. The day this fails the
    compiler no longer needs telling."""
    with mock.patch("dynamo_tpu.engine.model.step_compiler_options",
                    return_value={}):
        text, _, _ = granite_step_text(compile_for_chip, 2048)
    again = rematerialised_ops(text)
    assert [op for op, shape in again
            if ".remat" in op and shape == "bf16[2048,16768]"], again
    assert len(dots_producing(text, "bf16[2048,16768]")) > 2


#: a KV pool near the one ``lfm2-24b-a2b-pp4.chat-steady`` sizes (8,192 B a
#: token: 0.6 of what 10.53 GB of weights and the slots leave a 16.9 GB
#: chip): with it a step program's arguments are about 14.2 GB
LFM2_CELL_BLOCKS = 28000


@pytest.fixture(scope="module")
def lfm2_step_text(compile_for_chip):
    """lfm2_step_text(T, chunks) -> (compiled text, cfg, slots) of a ragged
    step of the LFM2-24B-A2B stage the benchmark runs, at the cell's 128
    state slots, pool and ``LIBTPU_INIT_ARGS``, each compiled once."""
    import json
    import os

    from dynamo_tpu.engine import model as M
    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.models import lfm2_24b_a2b_pp4

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))),
            "chipbench/configs/lfm2-24b-a2b-pp4.json")) as f:
        env = json.load(f)["worker_env"]
    cfg = lfm2_24b_a2b_pp4()
    args = EngineArgs(max_num_seqs=128, max_num_batched_tokens=2048,
                      max_model_len=8192)
    nb, slots = LFM2_CELL_BLOCKS, args.max_num_seqs + 1
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.key(0)))
    (g,), st = cfg.kv_cache_spec, cfg.state_spec
    kc = spec((len(g.layers), nb * BS, *g.k_shape), jnp.bfloat16)
    vc = spec((len(g.layers), nb * BS, g.kv_heads, g.v_dim), jnp.bfloat16)
    state = (spec((len(st.layers), slots,
                   st.conv_shape[0] * st.conv_shape[1]), jnp.bfloat16),)
    done = {}

    def text_of(T, chunks):
        if (T, chunks) in done:
            return done[T, chunks], cfg, slots
        R, W = args.ragged_rows(T), args.max_blocks_per_seq
        C, _ = M.ragged_grid_shape(T)
        with mock.patch.object(jax, "default_backend", return_value="tpu"):
            options = M.step_compiler_options()
        # libtpu reads LIBTPU_INIT_ARGS once a process, and this process's
        # is loaded: the cell's flags ride as options of this compile
        options |= dict(flag.lstrip("-").split("=")
                        for flag in env["LIBTPU_INIT_ARGS"].split())
        with mock.patch.object(M, "step_compiler_options",
                               return_value=options), \
             mock.patch("dynamo_tpu.ops.grouped_matmul.kernel_interpret_mode",
                        return_value=False):
            step = M.make_ragged_step_fn(cfg, BS, None, use_pallas=True,
                                         chunks=chunks)
            done[T, chunks] = compile_for_chip(
                step, params, spec((5, T), jnp.int32),
                spec((R, 4), jnp.int32), spec((C,), jnp.int32),
                spec((R, W), jnp.int32), kc, vc, state)
        return done[T, chunks], cfg, slots

    return text_of


@pytest.mark.parametrize("program", ["d64", "d128", "m1024", "m2048"])
def test_lfm2_step_compiles_with_both_kernels_and_no_copies_for_v5e(
        lfm2_step_text, program):
    """The whole jitted ragged step of the LFM2-24B-A2B stage the benchmark
    runs (models.lfm2_24b_a2b_pp4: ten layers, published widths, all 64
    experts of each, 128 state slots, a pool of the cell's size): the
    ragged kernel — 64-wide heads stored as whole lane rows — and the
    grouped matmul at 1,536-wide experts (every launch: the blocks of 768
    under 2,048 tokens, the whole matrices at 2,048) are Mosaic calls; the
    page pool and the convolutions' tails are updated in place; no layer's
    experts or ``in_proj`` leave their stacks but as operands of their own
    products; nothing is computed twice."""
    T = int(program[1:])
    text, cfg, slots = lfm2_step_text(T, program[0] == "m")
    assert text.count("ragged_paged_attention") >= 2   # two runs of one
    for name in ("gate", "up", "down"):
        assert f"moe_grouped_matmul_g0_{program}_{name}" in text  # attention
        assert f"moe_grouped_matmul_g1_{program}_{name}" in text  # conv
    lines = text.splitlines()
    st, (g,) = cfg.state_spec, cfg.kv_cache_spec
    tails = (f"bf16[{len(st.layers)},{slots},"
             f"{st.conv_shape[0] * st.conv_shape[1]}]")
    pool = f"[{len(g.layers)},{LFM2_CELL_BLOCKS * BS},8,128]"
    copies = [ln.strip()[:120] for ln in lines
              if (" copy(" in ln or " copy-start(" in ln)
              and (tails in ln.split("(")[0] or pool in ln.split(" copy")[0])]
    assert not copies, copies[:2]
    D, F, E = cfg.hidden_size, cfg.moe_ffn_size, cfg.num_experts_held
    sliced = [ln for ln in lines
              if f" = bf16[{E},{D},{F}]" in ln or f" = bf16[{E},{F},{D}]" in ln]
    assert not sliced, sliced[:2]
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    moved, inside = [], False
    for ln in lines:
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", ln)
        if head:
            inside = head.group(1) in fused
            continue
        op = re.match(r"\s+(?:ROOT )?%[\w.\-]+ = bf16\[(?:\d+,)?(\d+),(\d+)\]"
                      r"\S* (?:copy|fusion|slice|dynamic-slice)\(", ln)
        # (at 2,048 tokens in_proj's PRODUCT has its shape: not judged)
        if op and not inside and T != D and (
                int(op.group(1)), int(op.group(2))) == (D, 3 * D):
            moved.append(ln.strip()[:120])
    assert not moved, moved[:2]
    assert not rematerialised_ops(text)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_init_leaf_never_holds_a_float32_copy_on_v5e(compile_for_chip,
                                                     quantized):
    """The program that builds a stacked leaf casts (and quantizes) as it
    generates: output plus temporaries stay below the leaf's float32 size
    (all 32 layers of Mistral-7B's gate stack are 7.5 GB in f32 — half the
    chip). Only the TPU compiler shows it: the CPU backend does not fuse
    the generator."""
    import dataclasses

    from dynamo_tpu.engine import model as M
    from dynamo_tpu.models import mistral_7b

    cfg = dataclasses.replace(mistral_7b(), num_layers=4)
    leaf = M._init_layer_stack(cfg, jax.random.key(0), cfg.num_layers,
                               False, jnp.bfloat16)["w_gate"]
    program = M._leaf_program(leaf.shape, leaf.dtype, True,
                              (8, None) if quantized else None, None)
    mem = compile_for_chip(
        program, jax.eval_shape(lambda: jax.random.key(0)),
        spec((), jnp.float32), text=False).memory_analysis()
    f32_bytes = 4 * 4 * cfg.hidden_size * cfg.intermediate_size
    assert mem.output_size_in_bytes + mem.temp_size_in_bytes < f32_bytes, (
        mem.output_size_in_bytes, mem.temp_size_in_bytes)
