"""Granite 4.0-H through the engine's step programs against the plain
reference (dynamo_tpu/models/reference/granite4_h.py), at a tiny size
(models.granite4_tiny): Mamba-2 layers whose state lives in slots beside a
paged KV cache that one NoPE attention layer uses — prefill in one pass and
in chunks, decode through the slots, a mixed step; what fails when a piece
of the mathematics is left out; the shares of the expert layer adding up;
slots that change hands; what a state model refuses."""

import dataclasses
import filecmp
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.cache import allocate_device_cache, allocate_state
from dynamo_tpu.engine.config import RAGGED_MAX_CHUNKS
from dynamo_tpu.models import granite4_tiny
from dynamo_tpu.models.reference import granite4_h, granite4_h_inputs

BS, NB, T, R, W, SLOTS = 4, 64, 32, 4, 16, 3
#: float32 against float32 on one backend: what is left is the order of the
#: sums (the chunked scan against the token-by-token one, online softmax,
#: the experts' rows regrouped); measured 2e-5 here on logits of sd about 1
TOL_F32 = 3e-4
#: bf16 weights and activations against the float32 reference of the same
#: weights; measured 0.03 here
TOL_BF16 = 0.15
PIECES = ["embedding_multiplier", "residual_multiplier", "logits_scaling",
          "attention_multiplier", "D", "dt_bias", "conv_bias",
          "gate_before_norm", "shared_expert", "nope"]


def unpack_state(a, pack: int):
    """The SSM state as ``ops/mamba2.py`` lays it out, ``[..., H // pack, N,
    pack * P]``, turned to a head's state by itself: ``[..., H, P, N]``."""
    *lead, G, N, W = a.shape
    a = a.reshape(*lead, G, N, pack, W // pack)
    return np.moveaxis(a, -3, -1).reshape(*lead, G * pack, W // pack, N)


def _operands(rows, seqs, tables, slots, T=T, W=W):
    """The ragged step's operands for ``rows`` = [(seq, start, chunk)], as
    engine._run_ragged lays them out for a state model (rows3 [R, 4])."""
    C, S_C = M.ragged_grid_shape(T)
    ints5 = np.zeros((5, T), np.int32)
    ints5[3] = C
    rows4 = np.zeros((R, 4), np.int32)
    rows4[:, 3] = SLOTS  # the dump slot
    grid_rows = np.zeros((C,), np.int32)
    bt = np.zeros((R, W), np.int32)
    t = tile = 0
    for i, (s, start, chunk) in enumerate(rows):
        end = start + chunk
        ints5[0, t:t + chunk] = seqs[s][start:end]
        ints5[1, t:t + chunk] = np.arange(start, end)
        ints5[2, t:t + chunk] = [tables[s][p // BS] * BS + p % BS
                                 for p in range(start, end)]
        if chunk > 1:
            for off in range(0, chunk, S_C):
                width = min(S_C, chunk - off)
                grid_rows[tile] = i
                ints5[3, t + off:t + off + width] = tile
                ints5[4, t + off:t + off + width] = np.arange(width)
                tile += 1
        rows4[i] = (t, chunk, end, slots[s])
        bt[i, :len(tables[s])] = tables[s]
        t += chunk
    assert tile <= C and RAGGED_MAX_CHUNKS >= sum(c > 1 for *_, c in rows)
    return tuple(jnp.asarray(a) for a in (ints5, rows4, grid_rows, bt))


#: (stage, mixed program?, [(seq, start, chunk)]): a one-pass prefill (B),
#: a prefill in three chunks (A), one of them beside another prompt's start,
#: a mixed step with two decode rows and one chunk, then decode-only steps
PLAN = [
    ("fresh chunk", True, [("A", 0, 11)]),
    ("continuation + one pass", True, [("A", 11, 9), ("B", 0, 13)]),
    ("continuation + decode", True, [("B", 13, 1), ("A", 20, 8)]),
    ("mixed: two decode rows, one chunk", True,
     [("A", 28, 1), ("B", 14, 1), ("C", 0, 17)]),
] + [("decode", False, [("A", 29 + i, 1), ("B", 15 + i, 1), ("C", 17 + i, 1)])
     for i in range(24)]
SLOT_OF = {"A": 2, "B": 0, "C": 1}
TABLES = {"A": list(range(1, 15)), "B": list(range(20, 31)),
          "C": list(range(40, 52))}


def run_engine_steps(cfg, params, seqs, plan=PLAN, *, routing=False,
                     state=None, slots=SLOT_OF):
    """Every step of ``plan`` through the jitted ragged step programs, the
    paged cache and the state slots: ([(stage, seq, start, chunk, logits
    [V], ids | None)], the state arrays after the last step)."""
    kc, vc = allocate_device_cache(cfg, NB, BS)
    state = allocate_state(cfg, SLOTS) if state is None else state
    fns = {c: M.make_ragged_step_fn(cfg, BS, chunks=c, moe_routing=routing)
           for c in (True, False)}
    out = []
    for stage, chunks, rows in plan:
        ops = _operands(rows, seqs, TABLES, slots)
        logits, kc, vc, stats, *rest = fns[chunks](params, *ops, kc, vc,
                                                   state)
        state = rest[-1]
        stats = np.asarray(stats).sum(0)
        n_tok = sum(c for *_, c in rows)
        # every real token's K choices in every layer, and no pad's
        assert stats[0] == n_tok * cfg.num_experts_per_tok * cfg.num_layers
        t = 0
        for i, (s, start, chunk) in enumerate(rows):
            got = (np.asarray(rest[0])[:, t:t + chunk] if routing else None)
            out.append((stage, s, start, chunk, np.asarray(logits[i]), got))
            t += chunk
    return out, state


def _seqs(seed=0):
    rng = np.random.default_rng(seed)
    return {s: rng.integers(1, 256, 60) for s in "ABC"}


@pytest.fixture(scope="module")
def tiny_f32():
    cfg = granite4_tiny()
    params = M.init_params(cfg, jax.random.key(0))
    seqs = _seqs()
    steps, state = run_engine_steps(cfg, params, seqs)
    # on the host: a step donates the state arrays it is given
    return cfg, params, seqs, steps, tuple(np.asarray(a) for a in state)


def _reference(cfg, params, seqs, **kw):
    weights, hp = granite4_h_inputs(cfg, params)
    return {s: granite4_h.forward(weights, hp, toks, **kw)
            for s, toks in seqs.items()}


def _max_err(steps, ref, stage=None):
    return max(float(np.abs(lg - np.asarray(ref[s][0])[start + chunk - 1]
                            ).max())
               for st, s, start, chunk, lg, _ in steps
               if stage is None or st.startswith(stage))


@pytest.mark.parametrize("stage", ["fresh chunk", "continuation", "mixed",
                                   "decode"])
def test_engine_logits_match_the_reference_f32(tiny_f32, stage):
    cfg, params, seqs, steps, _ = tiny_f32
    ref = _reference(cfg, params, seqs)
    assert any(st.startswith(stage) for st, *_ in steps)
    assert _max_err(steps, ref, stage) < TOL_F32


@pytest.mark.parametrize("piece", PIECES)
def test_comparison_fails_when_a_piece_is_left_out(tiny_f32, piece):
    """The tolerance is tight enough to see each piece: the reference with
    it dropped (``nope``: with rotary added) is further from the engine
    than any tolerance used here."""
    cfg, params, seqs, steps, _ = tiny_f32
    ref = _reference(cfg, params, seqs, leave_out=(piece,))
    assert _max_err(steps, ref) > TOL_BF16


def test_state_after_a_chunked_prefill_is_the_state_after_one_pass(tiny_f32):
    """A's prompt went in as 11 + 9 + 8 tokens beside other rows, then 25
    single tokens; the reference made one pass over the same 53 tokens."""
    cfg, params, seqs, _steps, state = tiny_f32
    n = {"A": 29 + 24, "B": 15 + 24, "C": 17 + 24}
    ref = _reference(cfg, params, {s: seqs[s][:n[s]] for s in n})
    ssm = unpack_state(np.asarray(state[1]), cfg.mamba_head_pack)
    for s, slot in SLOT_OF.items():
        for j in range(len(cfg.state_spec.layers)):
            np.testing.assert_allclose(ssm[j, slot], ref[s][1]["ssm"][j],
                                       atol=1e-4, rtol=1e-4)
            np.testing.assert_allclose(
                np.asarray(state[0])[j, slot].reshape(3, -1),
                ref[s][1]["conv"][j], atol=1e-5)


def test_one_pass_and_three_chunks_leave_the_same_state():
    cfg = granite4_tiny()
    params = M.init_params(cfg, jax.random.key(1))
    seqs = _seqs(3)
    _, once = run_engine_steps(cfg, params, seqs, [("x", True,
                                                    [("A", 0, 30)])])
    _, thrice = run_engine_steps(cfg, params, seqs, [
        ("x", True, [("A", 0, 7)]), ("x", True, [("A", 7, 2), ("B", 0, 5)]),
        ("x", True, [("A", 9, 21)])])
    for a, b in zip(once, thrice):
        np.testing.assert_allclose(np.asarray(a)[:, SLOT_OF["A"]],
                                   np.asarray(b)[:, SLOT_OF["A"]],
                                   atol=1e-4, rtol=1e-4)


def test_a_slot_reused_by_a_new_sequence_starts_from_zeros(tiny_f32):
    """C takes A's slot, full of A's state, and its logits are those of a
    sequence alone: a row that starts at position 0 reads nothing."""
    cfg, params, seqs, _steps, state = tiny_f32
    assert np.abs(np.asarray(state[1])[:, SLOT_OF["A"]]).max() > 0
    plan = [("fresh", True, [("C", 0, 9)]), ("fresh", True, [("C", 9, 6)]),
            ("decode", False, [("C", 15, 1)])]
    steps, after = run_engine_steps(
        cfg, params, seqs, plan, state=tuple(jnp.asarray(a) for a in state),
        slots={"C": SLOT_OF["A"]})
    ref = _reference(cfg, params, {"C": seqs["C"][:16]})
    assert _max_err(steps, ref) < TOL_F32
    # and the rows' padding wrote to the dump slot only
    np.testing.assert_array_equal(np.asarray(after[1])[:, SLOT_OF["B"]],
                                  np.asarray(state[1])[:, SLOT_OF["B"]])


def test_engine_logits_match_the_reference_bf16(tiny_f32):
    """bf16 weights and activations, float32 state. The reference is told
    the engine's expert choices (a bf16 router picks other experts than a
    float32 one behind a small gap)."""
    cfg = dataclasses.replace(granite4_tiny(), dtype="bfloat16")
    keep = {"dt_bias", "A_log", "D", "router_bias"}
    params = jax.tree_util.tree_map_with_path(
        lambda p, a: a if p[-1].key in keep else a.astype(jnp.bfloat16),
        tiny_f32[1])
    seqs = _seqs(1)
    steps, _ = run_engine_steps(cfg, params, seqs, PLAN[:8], routing=True)
    ids = {s: np.zeros((cfg.num_layers, len(t), cfg.num_experts_per_tok),
                       np.int32) for s, t in seqs.items()}
    seen = {s: 0 for s in seqs}
    for _st, s, start, chunk, _lg, got in steps:
        ids[s][:, start:start + chunk] = got
        seen[s] = max(seen[s], start + chunk)
    weights, hp = granite4_h_inputs(cfg, params)
    for s, toks in seqs.items():
        n = seen[s]
        ref = np.asarray(granite4_h.forward(
            weights, hp, toks[:n], expert_ids=list(ids[s][:, :n]))[0])
        for _st, s2, start, chunk, lg, _ in steps:
            if s2 == s:
                err = float(np.abs(lg - ref[start + chunk - 1]).max())
                assert err < TOL_BF16, (s, start, err)


def test_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """Two ranks of 4 held experts, each computed by the program's layer,
    and the shared expert counted ONCE, add up to what the uncut reference
    layer gives."""
    whole = granite4_tiny(experts_held=None)
    E, D, F = whole.num_experts, whole.hidden_size, whole.moe_ffn_size
    ks = jax.random.split(jax.random.key(3), 9)
    lp = {"router": jax.random.normal(ks[0], (D, E)) / 8,
          "router_bias": jnp.zeros((E,)),
          "w_gate": jax.random.normal(ks[2], (E, D, F)) / 8,
          "w_up": jax.random.normal(ks[3], (E, D, F)) / 8,
          "w_down": jax.random.normal(ks[4], (E, F, D)) / 6,
          "ws_gate": jax.random.normal(ks[6], (D, 2 * F)) / 8,
          "ws_up": jax.random.normal(ks[7], (D, 2 * F)) / 8,
          "ws_down": jax.random.normal(ks[8], (2 * F, D)) / 8}
    x = jax.random.normal(ks[5], (24, D))
    valid = jnp.ones((24,), bool)
    total, pairs = 0.0, 0
    for first in range(0, E, 4):
        share = granite4_tiny(experts_held=(first, 4))
        part = {k: (v[first:first + 4] if k.startswith("w_") else v)
                for k, v in lp.items()}
        y, stats, _ids = M._mlp_moe_held(x, part, share, valid)
        total, pairs = total + y, pairs + int(stats[1])
    assert pairs == 24 * whole.num_experts_per_tok  # every pair, once
    with jax.default_matmul_precision("highest"):
        total = total + granite4_h.swiglu(x, lp["ws_gate"], lp["ws_up"],
                                          lp["ws_down"])
        hp = {"num_experts_per_tok": whole.num_experts_per_tok,
              "experts_held": [0, E]}
        ref, _, _ = granite4_h.experts(x, lp, hp)
    np.testing.assert_allclose(np.asarray(total), np.asarray(ref), atol=3e-5)


def test_the_two_copies_of_the_reference_are_byte_identical():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert filecmp.cmp(
        os.path.join(root, "dynamo_tpu/models/reference/granite4_h.py"),
        os.path.join(root, "chipbench/references/granite4_h.py"),
        shallow=False)


def test_preset_keeps_the_published_widths_and_the_cut():
    import json

    from dynamo_tpu.models import granite4_h_small_ep2

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "chipbench/configs/granite4-h-small-ep2.json")) as f:
        doc = json.load(f)
    cfg = granite4_h_small_ep2()
    assert [("attention", "mamba")[k] for k in cfg.layer_pattern] == \
        doc["layer_types"]
    for key, got in (
            ("hidden_size", cfg.hidden_size),
            ("num_attention_heads", cfg.num_heads),
            ("num_key_value_heads", cfg.layer_kinds[0].num_kv_heads),
            ("intermediate_size", cfg.moe_ffn_size),
            ("shared_intermediate_size",
             cfg.n_shared_experts * cfg.moe_ffn_size),
            ("mamba_n_heads", cfg.mamba_n_heads),
            ("mamba_d_head", cfg.mamba_d_head),
            ("mamba_d_state", cfg.mamba_d_state),
            ("mamba_d_conv", cfg.mamba_d_conv),
            ("num_experts_per_tok", cfg.num_experts_per_tok),
            ("vocab_size", cfg.vocab_size),
            ("num_hidden_layers", cfg.num_layers),
            ("embedding_multiplier", cfg.embedding_multiplier),
            ("residual_multiplier", cfg.residual_multiplier),
            ("logits_scaling", cfg.logits_scaling),
            ("attention_multiplier", cfg.query_pre_attn_scalar ** -0.5),
            ("rms_norm_eps", cfg.rms_norm_eps),
            ("tie_word_embeddings", cfg.tie_word_embeddings)):
        assert doc[key] == got, key
    assert doc["mamba_expand"] * cfg.hidden_size == cfg.mamba_d_inner
    assert doc["published"]["num_local_experts"] == cfg.num_experts
    assert tuple(doc["experts_held"]) == cfg.experts_held
    assert doc["num_local_experts"] == cfg.num_experts_held
    assert doc["position_embedding_type"] == cfg.position_embedding
    spec = cfg.state_spec
    assert spec.ssm_shape == (64, 128, 128) and spec.conv_shape == (3, 8448)
    assert spec.bytes_per_slot() == 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
    # the exact bytes of the weights, from the shapes alone
    shapes = jax.eval_shape(lambda: M.init_params(cfg, jax.random.key(0)))
    nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                 for a in jax.tree.leaves(shapes))
    assert nbytes == doc["expect"]["weights_bytes"] == \
        doc["sizing"]["weights_bytes"]


@pytest.mark.parametrize("what,kw", [
    ("preempt-to-swap", dict(preempt_swap=True)),
    ("KVBM tiers", dict(kvbm_host_bytes=1 << 20)),
    ("speculative decoding", dict(speculative_tokens=2)),
    ("multi-step decode", dict(multi_step_decode=4)),
    ("int8 KV pages", dict(kv_cache_dtype="int8")),
])
def test_a_state_model_refuses_what_would_move_part_of_a_cache(what, kw):
    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.engine.engine import AsyncJaxEngine

    args = dict(block_size=4, num_blocks=64, max_num_seqs=4,
                max_num_batched_tokens=32, max_model_len=64,
                preempt_swap=False) | kw
    with pytest.raises(ValueError, match="recurrent state.*" + what):
        AsyncJaxEngine(granite4_tiny(), EngineArgs(**args))


def test_state_that_leaves_no_pool_for_one_sequence_raises_the_arithmetic(
        monkeypatch):
    """The state slots are allocated before the pool is sized: where they
    leave fewer pages than ``max_model_len`` tokens need, sizing raises with
    its arithmetic rather than starting a worker no prompt fits."""
    from dynamo_tpu.engine import cache as C

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

        def __init__(self, in_use):
            self.in_use = in_use

        def memory_stats(self):
            return {"bytes_limit": 16 << 30, "bytes_in_use": self.in_use}

    cfg = granite4_tiny()
    per_block = 4 * sum(C.slot_bytes(cfg, g) for g in cfg.kv_cache_spec)
    room = (16 << 30) - 100 * per_block   # 100 blocks free: 200 at 0.5
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev(room)])
    assert C.hbm_sized_num_blocks(cfg, 4, 0.5, min_tokens=200) == 50
    with pytest.raises(RuntimeError, match=r"50 blocks = 200 tokens, fewer "
                                           r"than the 201 .* state slots"):
        C.hbm_sized_num_blocks(cfg, 4, 0.5, min_tokens=201)


def test_a_padded_long_chunk_leaves_a_state_that_decodes_right():
    """A prompt that runs PADDED in a program long enough for the dropless
    buffer to have two tiles an expert (150 tokens in the 256-token program;
    the scan's blocks are 128), every row of every expert launch that no
    pair names NaN, then decode steps from the state the chunk left: the
    logits are the reference's within the limit every other step is held
    to, and nothing in the state arrays is NaN. The shape that failed on
    the chip (PERF.md section 6, PRs 44 and 46), not a full unpadded chunk;
    what only the chip can show is chipbench/check_padded_chunk.py's."""
    from dynamo_tpu.ops.grouped_matmul import ROW_TILE, _blocks
    from tests.poisoned_launches import padded_chunk_then_decode

    cfg, long, n, steps = granite4_tiny(), 256, 150, 4
    (_, Eh), K = cfg.experts_held, cfg.num_experts_per_tok
    assert _blocks(-(-long * K // ROW_TILE) + Eh, Eh, 64, 64, 4)[2]
    assert not _blocks(-(-T * K // ROW_TILE) + Eh, Eh, 64, 64, 4)[2]
    params = M.init_params(cfg, jax.random.key(0))
    seq = {"A": np.random.default_rng(3).integers(1, 250, n + steps)}
    got, state = padded_chunk_then_decode(
        cfg, params, lambda row, width: _operands(
            [row], seq, {"A": list(range(1, 64))}, {"A": 1}, T=width, W=64),
        allocate_device_cache(cfg, NB, BS), allocate_state(cfg, SLOTS),
        [(long, True, ("A", 0, n))] + [
            (T, False, ("A", n + i, 1)) for i in range(steps)])
    for a in state:  # the sequence's slot, the others, the dump slot
        assert np.isfinite(np.asarray(a, np.float32)).all()
    ref = np.asarray(_reference(cfg, params, seq)["A"][0])
    for i, lg in enumerate(got):
        assert float(np.abs(lg - ref[n - 1 + i]).max()) < TOL_F32, i


def test_what_a_padding_tokens_row_holds_reaches_no_state():
    """The scan over a step's flat token axis with the padding tokens' rows
    NaN on the way in (what a kernel further down may leave there on the
    chip): the state and the valid tokens' y are bit for bit what zeros
    there give. The padding is selected away, not multiplied: 0 x NaN is
    NaN in the state of every chunk row of the step."""
    from dynamo_tpu.ops.mamba2 import mamba2_ragged

    cfg, long, n = granite4_tiny(), 256, 150
    lp = jax.tree.map(lambda a: a[0], M.init_params(
        cfg, jax.random.key(0))["stacks"][0])
    C = cfg.mamba_d_inner + 2 * cfg.mamba_d_state
    ks = jax.random.split(jax.random.key(1), 2)
    xbc = jax.random.normal(ks[0], (long, C))
    dt = jax.random.normal(ks[1], (long, cfg.mamba_n_heads))
    rows = jnp.zeros((R, 4), jnp.int32).at[:, 3].set(SLOTS).at[0].set(
        jnp.array([0, n, n, 1]))
    pos = jnp.where(jnp.arange(long) < n, jnp.arange(long), 0)
    pad = (jnp.arange(long) >= n)[:, None]

    def run(fill):
        conv, ssm = allocate_state(cfg, SLOTS)
        return mamba2_ragged(
            jnp.where(pad, fill, xbc), jnp.where(pad, fill, dt), lp, conv,
            ssm, 0, rows, pos, cfg=cfg, chunks=True)

    (y_n, conv_n, ssm_n), (y_z, conv_z, ssm_z) = run(jnp.nan), run(0.0)
    assert np.isfinite(np.asarray(ssm_n)).all()
    assert np.isfinite(np.asarray(conv_n)).all()
    assert np.asarray(ssm_n == ssm_z).all() and np.asarray(
        conv_n == conv_z).all()
    assert np.asarray(y_n[:n] == y_z[:n]).all()


@pytest.mark.anyio
async def test_a_padded_chunk_through_the_engine_counts_what_it_read_back():
    """The padded prompt through the engine: the first pick is the
    reference's and the flight records hold the layer's own counts
    (tests/poisoned_launches.py has the assertions)."""
    from tests.poisoned_launches import padded_prompt_through_the_engine

    cfg = granite4_tiny()
    params = M.init_params(cfg, jax.random.key(0))
    prompt = np.random.default_rng(3).integers(1, 250, 150)
    await padded_prompt_through_the_engine(
        cfg, params, "granite4_tiny", prompt, 256,
        np.asarray(_reference(cfg, params, {"A": prompt})["A"][0])[-1], cfg.num_layers)


@pytest.mark.anyio
async def test_engine_serves_granite_through_slots_and_says_what_it_did():
    """The normal path: scheduler (admission by slot), BlockPool, ragged
    step, pipelined decode, with the counters and flight-record fields the
    benchmark's readers read; prefix reuse switched off and still counted;
    the disaggregated entry points refused with their reason."""
    import asyncio

    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.protocols import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )

    cfg = granite4_tiny()
    eng = AsyncJaxEngine(cfg, EngineArgs(
        block_size=4, num_blocks=128, max_num_seqs=2,
        max_num_batched_tokens=32, max_model_len=96, preempt_swap=False))
    facts = eng.build_facts
    assert facts["state_slots"] == 2 and facts["state_layers"] == 5
    assert facts["state_bytes"] == 3 * cfg.state_spec.bytes_per_slot()
    assert eng.args.enable_prefix_caching is False
    assert eng.ragged_fallback_reason is None
    for name in ("prefill_extract", "generate_prefilled", "export_blocks",
                 "restore_probe", "embed"):
        with pytest.raises(NotImplementedError, match="recurrent state"):
            getattr(eng, name)(None)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 250, n).tolist() for n in (70, 20, 33)]

    async def one(ids):
        req = PreprocessedRequest(
            model="granite4_tiny", token_ids=ids,
            stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0))
        return [t async for o in eng.generate(req) for t in o.token_ids]

    # three requests over two slots: the third waits for one, then takes a
    # slot that holds a finished sequence's state
    outs = await asyncio.gather(*(one(p) for p in prompts))
    assert [len(o) for o in outs] == [6, 6, 6]
    assert eng.scheduler.state_slot_wait_total > 0
    assert sorted(eng.scheduler.state_free) == [0, 1]
    assert eng.scheduler.prefix_query_tokens == 70 + 20 + 33
    assert eng.scheduler.prefix_hit_tokens == 0
    recs = eng.flight.snapshot()
    assert any(r["kind"] == "decode_pipe" for r in recs)
    assert max(r.get("state_slots_used", 0) for r in recs) == 2
    assert sum(r.get("state_rows_prefill", 0) for r in recs) >= 5  # 70 = 3
    assert sum(r.get("state_rows_decode", 0) for r in recs) >= 15
    assert eng.moe_assignments_total["held"] > 0
    assert sum(r.get("moe_tiles", 0) for r in recs) == \
        eng.moe_row_tiles_total > 0
    # the chunked scan's counter: every step that held a chunk says what
    # its kernel walked (a chunk row a block here: the buckets are under
    # one block) of the four rows a block it could have, a Mamba-2 layer,
    # and /metrics adds them up
    from dynamo_tpu.engine.main import register_state_metrics
    from dynamo_tpu.runtime.metrics import MetricsRegistry

    held = [r for r in recs if r.get("state_rows_prefill")]
    assert held and all(
        0 < r["ssd_block_rows"] <= r["ssd_block_rows_max"] == 5 * 4
        and r["ssd_block_rows"] % 5 == 0 for r in held)
    assert not any(r.get("ssd_block_rows_max") for r in recs
                   if not r.get("state_rows_prefill"))
    registry = MetricsRegistry()
    register_state_metrics(registry, eng)
    text = registry.render()
    for kind, total in eng.ssd_block_rows_total.items():
        assert total >= sum(r["ssd_block_rows" + "_max" * (kind == "max")]
                            for r in held) > 0
        assert f'dynamo_ssd_block_rows_total{{kind="{kind}"}} {total}' in text
    assert "dynamo_state_slots_in_use" in text
    # greedy tokens are the reference's: each request alone, one pass
    weights, hp = granite4_h_inputs(cfg, eng.params)
    for ids, out in zip(prompts, outs):
        lg = np.asarray(granite4_h.forward(weights, hp, ids + out[:-1])[0])
        for i, t in enumerate(out):
            row = lg[len(ids) - 1 + i]
            top2 = np.sort(row)[-2:]
            if top2[1] - top2[0] > 1e-3:  # not a tie the sums could flip
                assert int(row.argmax()) == t
    await eng.close()
