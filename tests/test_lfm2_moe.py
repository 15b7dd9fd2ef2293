"""LFM2-MoE through the engine's step programs and the engine itself
against the plain reference (dynamo_tpu/models/reference/lfm2_moe.py), at a
tiny size (models.lfm2_tiny): gated short-convolution layers whose whole
state is the last two inputs a sequence, in slots beside a paged KV cache
that the attention layers use (heads narrower than a lane row, stored
padded to one) — a prompt in one pass and in three budgets, a mixed step,
decode through the slots at several row counts, a slot that changes hands,
recompute preemption; what fails when a piece of the mathematics is left
out or the tail forgets an input; the shares of the expert layer adding up
under this router."""

import dataclasses
import filecmp
import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.cache import allocate_device_cache, allocate_state
from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.models import lfm2_24b_a2b_pp4, lfm2_tiny
from dynamo_tpu.models.reference import lfm2_moe, lfm2_moe_inputs
from tests.test_granite4_h import (
    BS, NB, PLAN, SLOT_OF, SLOTS, TABLES, _operands, _seqs,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: float32 against float32 on one backend: what is left is the order of the
#: sums (online softmax, the experts' rows regrouped); measured 7e-6 here on
#: logits of sd about 1
TOL_F32 = 1e-4
#: bf16 weights and activations against the float32 reference of the same
#: weights; measured 0.16 here, and the smallest left-out piece reads 1.3
TOL_BF16 = 0.3
PIECES = ["in_gate", "out_gate", "oldest_tap", "thirds_order",
          "conv_activation", "qk_norm", "rope", "expert_bias",
          "norm_topk_prob", "embedding_norm"]
#: decode-only steps of 3, 2 and 1 rows after PLAN's prefills: sequences
#: that stop at different tokens
ROW_COUNTS = PLAN[:4] + [
    ("decode 3 rows", False, [("A", 29, 1), ("B", 15, 1), ("C", 17, 1)]),
    ("decode 2 rows", False, [("A", 30, 1), ("C", 18, 1)]),
    ("decode 2 rows", False, [("A", 31, 1), ("C", 19, 1)]),
    ("decode 1 row", False, [("C", 20, 1)]),
    ("decode 1 row", False, [("C", 21, 1)])]


def run_engine_steps(cfg, params, seqs, plan=PLAN, *, routing=False,
                     state=None, slots=SLOT_OF, use_pallas=False):
    """Every step of ``plan`` through the jitted ragged step programs, the
    paged cache and the state slots: ([(stage, seq, start, chunk, logits
    [V], ids | None)], the state arrays after the last step)."""
    kc, vc = allocate_device_cache(cfg, NB, BS)
    state = allocate_state(cfg, SLOTS) if state is None else state
    fns = {c: M.make_ragged_step_fn(cfg, BS, chunks=c, moe_routing=routing,
                                    use_pallas=use_pallas)
           for c in (True, False)}
    n_moe = cfg.num_layers - cfg.first_k_dense_replace
    out = []
    for stage, chunks, rows in plan:
        ops = _operands(rows, seqs, TABLES, slots)
        logits, kc, vc, stats, *rest = fns[chunks](params, *ops, kc, vc,
                                                   state)
        state = rest[-1]
        stats = np.asarray(stats).sum(0)
        n_tok = sum(c for *_, c in rows)
        # every real token's K choices in every expert layer, no pad's,
        # and every one of them held here
        assert stats[0] == stats[1] == \
            n_tok * cfg.num_experts_per_tok * n_moe
        t = 0
        for i, (s, start, chunk) in enumerate(rows):
            got = (np.asarray(rest[0])[:, t:t + chunk] if routing else None)
            out.append((stage, s, start, chunk, np.asarray(logits[i]), got))
            t += chunk
    return out, state


@pytest.fixture(scope="module")
def tiny_f32():
    cfg = lfm2_tiny()
    params = M.init_params(cfg, jax.random.key(0))
    seqs = _seqs()
    steps, state = run_engine_steps(cfg, params, seqs)
    # on the host: a step donates the state arrays it is given
    return cfg, params, seqs, steps, tuple(np.asarray(a) for a in state)


def _reference(cfg, params, seqs, **kw):
    weights, hp = lfm2_moe_inputs(cfg, params)
    return {s: lfm2_moe.forward(weights, hp, toks, **kw)
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
    # random weights that say something: logits of sd about 1
    assert 0.5 < float(np.std(np.asarray(ref["A"][0]))) < 2.0


@pytest.mark.parametrize("stage", ["decode 3 rows", "decode 2 rows",
                                   "decode 1 row"])
def test_decode_through_the_slots_at_several_row_counts(tiny_f32, stage):
    cfg, params, seqs, _steps, _ = tiny_f32
    steps, _ = run_engine_steps(cfg, params, seqs, ROW_COUNTS)
    assert _max_err(steps, _reference(cfg, params, seqs), stage) < TOL_F32


def test_the_interpreted_kernels_give_the_same_logits(tiny_f32):
    """The attention layers through the ragged Pallas kernel (interpreted
    here): heads of 16 dims stored as whole lane rows, K and V alike."""
    cfg, params, seqs, *_ = tiny_f32
    assert M.ragged_fallback_reason(cfg, None, True) is None
    assert cfg.kv_cache_spec[0].k_shape == (2, 128)
    assert cfg.kv_cache_spec[0].v_dim == 128
    steps, _ = run_engine_steps(cfg, params, seqs, PLAN[:6], use_pallas=True)
    assert _max_err(steps, _reference(cfg, params, seqs)) < TOL_F32


def test_a_padded_long_chunk_leaves_a_tail_that_decodes_right():
    """A prompt that runs PADDED (150 tokens in the 256-token program), the
    attention layers through the ragged kernel (interpreted here; it writes
    no row of a padding token, and the step selects those rows away), every
    row of every expert launch that no pair names NaN, then decode steps
    from the tails the chunk left: the logits are the reference's within the
    limit every other step is held to, and no tail holds a NaN — the twin of
    tests/test_granite4_h.py's case, the shape that failed on the chip."""
    from tests.poisoned_launches import padded_chunk_then_decode
    from tests.test_granite4_h import T

    cfg, long, n, steps = lfm2_tiny(), 256, 150, 4
    params = M.init_params(cfg, jax.random.key(0))
    seq = {"A": np.random.default_rng(3).integers(1, 250, n + steps)}
    got, state = padded_chunk_then_decode(
        cfg, params, lambda row, width: _operands(
            [row], seq, {"A": list(range(1, 64))}, {"A": 1}, T=width, W=64),
        allocate_device_cache(cfg, NB, BS), allocate_state(cfg, SLOTS),
        [(long, True, ("A", 0, n))] + [
            (T, False, ("A", n + i, 1)) for i in range(steps)],
        use_pallas=True)
    for a in state:
        assert np.isfinite(np.asarray(a, np.float32)).all()
    ref = np.asarray(_reference(cfg, params, seq)["A"][0])
    for i, lg in enumerate(got):
        assert float(np.abs(lg - ref[n - 1 + i]).max()) < TOL_F32, i


@pytest.mark.parametrize("piece", PIECES)
def test_comparison_fails_when_a_piece_is_left_out(tiny_f32, piece):
    """The tolerance is tight enough to see each piece: the reference with
    it dropped or swapped is further from the engine than any tolerance
    used here."""
    cfg, params, seqs, steps, _ = tiny_f32
    ref = _reference(cfg, params, seqs, leave_out=(piece,))
    assert _max_err(steps, ref) > TOL_BF16


def test_comparison_fails_when_the_engine_forgets_the_oldest_input(tiny_f32):
    """chipbench/check_reference_lfm2.py's second control at test size: the
    engine's copy of the first tap ``w[:, 0]`` zeroed (a tail that forgets
    ``z_{t-2}``), the reference's weights as they were."""
    cfg, params, seqs, *_ = tiny_f32
    broken = {**params, "stacks": tuple(
        {k: (v.at[:, 0].set(0) if k == "conv_w" else v)
         for k, v in st.items()} for st in params["stacks"])}
    steps, _ = run_engine_steps(cfg, broken, seqs)
    ref = _reference(cfg, params, seqs)
    for stage in ("fresh chunk", "continuation", "mixed", "decode"):
        assert _max_err(steps, ref, stage) > TOL_BF16, stage


def test_tail_after_a_chunked_prefill_is_the_tail_after_one_pass(tiny_f32):
    """A's prompt went in as 11 + 9 + 8 tokens beside other rows, then 25
    single tokens; the reference made one pass over the same 53 tokens."""
    cfg, params, seqs, _steps, state = tiny_f32
    n = {"A": 29 + 24, "B": 15 + 24, "C": 17 + 24}
    ref = _reference(cfg, params, {s: seqs[s][:n[s]] for s in n})
    assert len(state) == 1   # the tail is the whole state
    for s, slot in SLOT_OF.items():
        for j in range(len(cfg.state_spec.layers)):
            np.testing.assert_allclose(
                state[0][j, slot].reshape(cfg.shortconv_taps - 1, -1),
                ref[s][1]["conv"][j], atol=1e-5)


def test_one_pass_and_three_budgets_leave_the_same_tail_and_logits():
    cfg = lfm2_tiny()
    params = M.init_params(cfg, jax.random.key(1))
    seqs = _seqs(3)
    once, tail1 = run_engine_steps(cfg, params, seqs,
                                   [("x", True, [("A", 0, 30)])])
    thrice, tail3 = run_engine_steps(cfg, params, seqs, [
        ("x", True, [("A", 0, 7)]), ("x", True, [("A", 7, 2), ("B", 0, 5)]),
        ("x", True, [("A", 9, 21)])])
    np.testing.assert_allclose(np.asarray(tail1[0])[:, SLOT_OF["A"]],
                               np.asarray(tail3[0])[:, SLOT_OF["A"]],
                               atol=1e-5)
    last = [lg for _st, s, start, chunk, lg, _ in thrice
            if s == "A" and start + chunk == 30][0]
    np.testing.assert_allclose(once[0][4], last, atol=TOL_F32)


def test_a_slot_reused_by_a_new_sequence_starts_from_zeros(tiny_f32):
    """C takes A's slot, full of A's inputs, and its logits are those of a
    sequence alone: a row that starts at position 0 reads nothing."""
    cfg, params, seqs, _steps, state = tiny_f32
    assert np.abs(state[0][:, SLOT_OF["A"]]).max() > 0
    plan = [("fresh", True, [("C", 0, 9)]), ("fresh", True, [("C", 9, 6)]),
            ("decode", False, [("C", 15, 1)])]
    steps, after = run_engine_steps(
        cfg, params, seqs, plan, state=tuple(jnp.asarray(a) for a in state),
        slots={"C": SLOT_OF["A"]})
    ref = _reference(cfg, params, {"C": seqs["C"][:16]})
    assert _max_err(steps, ref) < TOL_F32
    # and the rows' padding wrote to the dump slot only
    np.testing.assert_array_equal(np.asarray(after[0])[:, SLOT_OF["B"]],
                                  state[0][:, SLOT_OF["B"]])


def test_engine_logits_match_the_reference_bf16(tiny_f32):
    """bf16 weights, activations and tails. The reference is told the
    engine's expert choices (a bf16 router picks other experts than a
    float32 one behind a small gap)."""
    cfg = dataclasses.replace(lfm2_tiny(), dtype="bfloat16")
    params = jax.tree_util.tree_map_with_path(
        lambda p, a: a if p[-1].key == "router_bias"
        else a.astype(jnp.bfloat16), tiny_f32[1])
    seqs = _seqs(1)
    steps, state = run_engine_steps(cfg, params, seqs, PLAN[:8],
                                    routing=True)
    assert state[0].dtype == jnp.bfloat16
    n_moe = cfg.num_layers - cfg.first_k_dense_replace
    ids = {s: np.zeros((n_moe, len(t), cfg.num_experts_per_tok), np.int32)
           for s, t in seqs.items()}
    seen = {s: 0 for s in seqs}
    for _st, s, start, chunk, _lg, got in steps:
        ids[s][:, start:start + chunk] = got
        seen[s] = max(seen[s], start + chunk)
    weights, hp = lfm2_moe_inputs(cfg, params)
    for s, toks in seqs.items():
        n = seen[s]
        ref = np.asarray(lfm2_moe.forward(
            weights, hp, toks[:n], expert_ids=list(ids[s][:, :n]))[0])
        for _st, s2, start, chunk, lg, _ in steps:
            if s2 == s:
                err = float(np.abs(lg - ref[start + chunk - 1]).max())
                assert err < TOL_BF16, (s, start, err)


def test_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """Two ranks of 4 held experts, each computed by the program's layer,
    add up to what the uncut reference layer gives under this router:
    sigmoid scores, a bias in the choice only, the chosen scores over
    (their sum + 1e-6)."""
    whole = lfm2_tiny()
    assert whole.experts_held == (0, 8) and whole.router_norm_eps == 1e-6
    E, D, F = whole.num_experts, whole.hidden_size, whole.moe_ffn_size
    ks = jax.random.split(jax.random.key(3), 6)
    lp = {"router": jax.random.normal(ks[0], (D, E)) / 8,
          # large beside the scores' spread: the choice is not the scores'
          "router_bias": jax.random.normal(ks[1], (E,)) / 2,
          "w_gate": jax.random.normal(ks[2], (E, D, F)) / 8,
          "w_up": jax.random.normal(ks[3], (E, D, F)) / 8,
          "w_down": jax.random.normal(ks[4], (E, F, D)) / 6}
    x = jax.random.normal(ks[5], (24, D))
    valid = jnp.ones((24,), bool)
    total, pairs = 0.0, 0
    for first in range(0, E, 4):
        share = lfm2_tiny(experts_held=(first, 4))
        part = {k: (v[first:first + 4] if k.startswith("w_") else v)
                for k, v in lp.items()}
        y, stats, _ids = M._mlp_moe_held(x, part, share, valid)
        total, pairs = total + y, pairs + int(stats[1])
    assert pairs == 24 * whole.num_experts_per_tok  # every pair, once
    hp = {"num_experts_per_tok": whole.num_experts_per_tok,
          "experts_held": [0, E], "norm_topk_prob": True,
          "routed_scaling_factor": 1.0}
    with jax.default_matmul_precision("highest"):
        ref, ids, choice = lfm2_moe.experts(x, lp, hp)
        unbiased, *_ = lfm2_moe.experts(x, lp, hp,
                                        leave_out=("expert_bias",))
    np.testing.assert_allclose(np.asarray(total), np.asarray(ref), atol=3e-5)
    # the bias did steer the choice, and the whole layer IS the held layer
    assert float(np.abs(np.asarray(ref - unbiased)).max()) > 0.1
    y, stats, ids2 = M._mlp_moe_held(x, lp, whole, valid)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=3e-5)
    assert int(stats[0]) == int(stats[1])
    np.testing.assert_array_equal(np.sort(np.asarray(ids2), 1),
                                  np.sort(np.asarray(ids), 1))


def test_the_two_copies_of_the_reference_are_byte_identical():
    assert filecmp.cmp(
        os.path.join(ROOT, "dynamo_tpu/models/reference/lfm2_moe.py"),
        os.path.join(ROOT, "chipbench/references/lfm2_moe.py"),
        shallow=False)


def _config_file() -> dict:
    with open(os.path.join(
            ROOT, "chipbench/configs/lfm2-24b-a2b-pp4.json")) as f:
        return json.load(f)


def test_preset_keeps_the_published_widths_and_the_cut():
    doc = _config_file()
    cfg = lfm2_24b_a2b_pp4()
    assert [("full_attention", "conv")[k] for k in cfg.layer_pattern] == \
        doc["layer_types"] == doc["published"]["layer_types"][:10]
    for key, got in (
            ("hidden_size", cfg.hidden_size),
            ("intermediate_size", cfg.intermediate_size),
            ("moe_intermediate_size", cfg.moe_ffn_size),
            ("num_attention_heads", cfg.num_heads),
            ("num_key_value_heads", cfg.layer_kinds[0].num_kv_heads),
            ("num_experts", cfg.num_experts),
            ("num_experts_per_tok", cfg.num_experts_per_tok),
            ("num_dense_layers", cfg.first_k_dense_replace),
            ("num_hidden_layers", cfg.num_layers),
            ("conv_L_cache", cfg.shortconv_taps),
            ("norm_eps", cfg.rms_norm_eps),
            ("norm_topk_prob", cfg.norm_topk_prob),
            ("routed_scaling_factor", cfg.routed_scaling_factor),
            ("vocab_size", cfg.vocab_size),
            ("max_position_embeddings", cfg.max_position_embeddings)):
        assert doc[key] == got, key
    assert doc["rope_parameters"]["rope_theta"] == \
        cfg.layer_kinds[0].rope_theta
    assert doc["conv_bias"] is False and doc["use_expert_bias"] is True
    assert cfg.head_dim == 64 and cfg.experts_held == (0, 64)
    spec = cfg.state_spec
    assert spec.ssm_shape is None and spec.conv_shape == (2, 2048)
    assert spec.bytes_per_slot() == 8 * 2 * 2048 * 2 == \
        doc["sizing"]["state_bytes_per_slot"]
    # a 64-wide head is stored as a whole lane row, K and V: half a page
    group, = cfg.kv_cache_spec
    assert (group.k_shape, group.v_dim) == ((8, 128), 128)
    assert group.bytes_per_slot(2) == doc["sizing"]["kv_bytes_per_token"]
    assert cfg.kv_lane_pad_share == 0.5
    # the exact bytes of the weights, from the shapes alone
    shapes = jax.eval_shape(lambda: M.init_params(cfg, jax.random.key(0)))
    leaves = jax.tree.leaves(shapes)
    assert sum(int(np.prod(a.shape)) for a in leaves) == \
        doc["sizing"]["params"]
    nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves)
    assert nbytes == doc["expect"]["weights_bytes"] == \
        doc["sizing"]["weights_bytes"]


def test_the_published_config_maps_to_the_preset():
    """``from_hf_config`` reads ``model_type: lfm2_moe``: the configuration
    file's own keys (the cut) give the preset's architecture."""
    doc = _config_file()
    got = ModelConfig.from_hf_config(doc)
    want = lfm2_24b_a2b_pp4()
    for f in dataclasses.fields(ModelConfig):
        if not f.name.startswith("init_"):
            assert getattr(got, f.name) == getattr(want, f.name), f.name
    with pytest.raises(NotImplementedError, match="conv_bias"):
        ModelConfig.from_hf_config({**doc, "conv_bias": True})


@pytest.mark.parametrize("what,kw", [
    ("preempt-to-swap", dict(preempt_swap=True)),
    ("KVBM tiers", dict(kvbm_host_bytes=1 << 20)),
    ("multi-step decode", dict(multi_step_decode=4)),
])
def test_a_shortconv_model_refuses_what_would_move_part_of_a_cache(what, kw):
    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.engine.engine import AsyncJaxEngine

    args = dict(block_size=4, num_blocks=64, max_num_seqs=4,
                max_num_batched_tokens=32, max_model_len=64,
                preempt_swap=False) | kw
    with pytest.raises(ValueError, match="recurrent state.*shortconv.*"
                                         + what):
        AsyncJaxEngine(lfm2_tiny(), EngineArgs(**args))


def test_layer_kinds_take_one_state_mixer_after_the_attention_kinds():
    attn, conv = (2, 1e6, 0, False), (0, 0.0, 0, False, "shortconv")
    mamba = (0, 0.0, 0, False, "mamba2")
    for kinds in ((conv, attn), (attn, conv, mamba), (attn, (0, 0.0, 0,
                                                            False, "lstm"))):
        with pytest.raises(ValueError, match="ONE state mixer"):
            dataclasses.replace(lfm2_tiny(), layer_kinds=kinds)


def _request(ids, n_out):
    from dynamo_tpu.protocols import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )

    return PreprocessedRequest(
        model="lfm2_tiny", token_ids=ids,
        stop_conditions=StopConditions(max_tokens=n_out, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0))


def _assert_greedy_is_the_references(cfg, params, prompts, outs):
    """Each request alone, one pass: the engine's tokens are the
    reference's picks wherever those are not a tie the sums could flip."""
    weights, hp = lfm2_moe_inputs(cfg, params)
    for ids, out in zip(prompts, outs):
        lg = np.asarray(lfm2_moe.forward(weights, hp, ids + out[:-1])[0])
        for i, t in enumerate(out):
            row = lg[len(ids) - 1 + i]
            top2 = np.sort(row)[-2:]
            if top2[1] - top2[0] > 1e-3:
                assert int(row.argmax()) == t


@pytest.mark.anyio
async def test_engine_serves_lfm2_through_slots_and_says_what_it_did():
    """The normal path: scheduler (admission by slot), BlockPool, ragged
    step, pipelined decode, with the counters and flight-record fields the
    benchmark's readers read."""
    import asyncio

    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.engine.engine import AsyncJaxEngine

    cfg = lfm2_tiny()
    eng = AsyncJaxEngine(cfg, EngineArgs(
        block_size=4, num_blocks=128, max_num_seqs=2,
        max_num_batched_tokens=32, max_model_len=96, preempt_swap=False))
    facts = eng.build_facts
    assert facts["state_slots"] == 2 and facts["state_layers"] == 6
    assert facts["state_mixer"] == "shortconv" and "mamba" not in facts
    assert facts["state_bytes"] == 3 * cfg.state_spec.bytes_per_slot()
    assert facts["layers"] == {"full": 2, "window": 0, "dense": 2,
                               "experts": 6, "shortconv": 6}
    assert facts["kv_lane_pad_share"] == 0.875  # 16 of 128 lanes
    assert facts["experts_held"] == [0, 8]
    assert eng.args.enable_prefix_caching is False
    for name in ("prefill_extract", "generate_prefilled", "export_blocks"):
        with pytest.raises(NotImplementedError, match="recurrent state"):
            getattr(eng, name)(None)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 250, n).tolist() for n in (70, 20, 33)]

    async def one(ids):
        return [t async for o in eng.generate(_request(ids, 6))
                for t in o.token_ids]

    # three requests over two slots: the third waits for one, then takes a
    # slot that holds a finished sequence's inputs
    outs = await asyncio.gather(*(one(p) for p in prompts))
    assert [len(o) for o in outs] == [6, 6, 6]
    assert eng.scheduler.state_slot_wait_total > 0
    assert sorted(eng.scheduler.state_free) == [0, 1]
    recs = eng.flight.snapshot()
    assert any(r["kind"] == "decode_pipe" for r in recs)
    assert max(r.get("state_slots_used", 0) for r in recs) == 2
    assert sum(r.get("state_rows_prefill", 0) for r in recs) >= 5  # 70 = 3
    assert sum(r.get("state_rows_decode", 0) for r in recs) >= 15
    assert {r["state_program"][0] for r in recs
            if r.get("state_program")} == {"m", "d"}
    # every assignment is to a held expert
    assert eng.moe_assignments_total["held"] == \
        eng.moe_assignments_total["all"] > 0
    assert sum(r.get("moe_tiles", 0) for r in recs) == \
        eng.moe_row_tiles_total > 0
    assert max(r.get("moe_experts_touched", 0) for r in recs) <= 6 * 8
    _assert_greedy_is_the_references(cfg, eng.params, prompts, outs)
    await eng.close()


@pytest.mark.anyio
async def test_a_preempted_sequence_is_recomputed_from_zeros():
    """A pool too small for four sequences: one is preempted, gives up its
    slot and pages, and is prefilled again from position 0 — its tokens are
    those of a sequence that was never disturbed."""
    import asyncio

    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.engine.engine import AsyncJaxEngine

    cfg = lfm2_tiny()
    eng = AsyncJaxEngine(cfg, EngineArgs(
        block_size=4, num_blocks=24, max_num_seqs=4,
        max_num_batched_tokens=16, max_model_len=64, preempt_swap=False))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 250, 22).tolist() for _ in range(4)]

    async def one(ids):
        return [t async for o in eng.generate(_request(ids, 10))
                for t in o.token_ids]

    outs = await asyncio.gather(*(one(p) for p in prompts))
    assert [len(o) for o in outs] == [10] * 4
    assert eng.scheduler.preempt_recompute_total > 0
    assert sorted(eng.scheduler.state_free) == [0, 1, 2, 3]
    _assert_greedy_is_the_references(cfg, eng.params, prompts, outs)
    await eng.close()


def test_a_one_chip_moe_that_holds_nothing_is_warned_at_build(caplog):
    """More than 8 experts and ``experts_held`` None take the one-hot
    layer, every expert's product for every token: the build says so."""
    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.engine.engine import AsyncJaxEngine

    cfg = ModelConfig(
        vocab_size=64, hidden_size=32, intermediate_size=32, num_layers=1,
        num_heads=2, num_kv_heads=2, dtype="float32", num_experts=16,
        num_experts_per_tok=2, max_position_embeddings=64)
    with caplog.at_level(logging.WARNING, logger="dynamo.engine"):
        AsyncJaxEngine(cfg, EngineArgs(
            block_size=4, num_blocks=16, max_num_seqs=2,
            max_num_batched_tokens=16, max_model_len=32))
    assert any("experts_held" in r.getMessage() for r in caplog.records)
