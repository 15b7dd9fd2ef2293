"""MiMo-V2 through the engine's step programs against the plain reference
(dynamo_tpu/models/reference/mimo_v2.py), at a tiny size that keeps every
published ratio (models.mimo_tiny): prefill in chunks, decode through both
cache groups, a mixed step; what fails when a piece of the mathematics is
left out; the shares of the expert layer adding up to the uncut layer."""

import dataclasses
import filecmp
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import model as M
from dynamo_tpu.engine.cache import allocate_device_cache
from dynamo_tpu.engine.config import RAGGED_MAX_CHUNKS, ModelConfig
from dynamo_tpu.models import mimo_tiny
from dynamo_tpu.models.reference import mimo_v2, mimo_v2_inputs

BS, NB, T, R, W = 4, 64, 32, 4, 16
#: float32 against float32 on one backend: what is left is the order of the
#: sums (online softmax over key segments, the experts' rows regrouped),
#: a few ulp of values of order 1 through 13 layers
TOL_F32 = 2e-4
#: bf16 weights, activations and pages against the float32 reference of the
#: same bf16 weights: 2^-8 relative a rounding, on logits of sd about 1,
#: through 13 residual layers; measured 0.02-0.05 here, and every negative
#: case below moves the logits by more than 0.3
TOL_BF16 = 0.12


def _rows_operands(rows, seqs, tables, T=T, W=W):
    """The ragged step's operands for ``rows`` = [(seq, start, chunk)], as
    engine._run_ragged lays them out."""
    C, S_C = M.ragged_grid_shape(T)
    ints5 = np.zeros((5, T), np.int32)
    ints5[3] = C
    rows3 = np.zeros((R, 3), np.int32)
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
        rows3[i] = (t, chunk, end)
        bt[i, :len(tables[s])] = tables[s]
        t += chunk
    assert tile <= C and RAGGED_MAX_CHUNKS >= sum(c > 1 for *_, c in rows)
    return tuple(jnp.asarray(a) for a in (ints5, rows3, grid_rows, bt))


#: the steps every comparison walks: two prompts prefilled in chunks longer
#: than the window (8), one continuing while the other starts; a MIXED
#: step (A decodes while B's chunk continues); then decode-only steps
#: through the no-chunk-grid program. (seq, start, chunk) a row
PLAN = [
    ("fresh chunk", True, [("A", 0, 16)]),
    ("continuation + fresh", True, [("A", 16, 15), ("B", 0, 12)]),
    ("mixed", True, [("A", 31, 1), ("B", 12, 10)]),
    ("decode", False, [("A", 32, 1), ("B", 22, 1)]),
    ("decode", False, [("A", 33, 1), ("B", 23, 1)]),
]


def run_engine_steps(cfg, params, seqs, *, routing=False):
    """Every PLAN step through the jitted ragged step programs and the
    paged cache: [(stage, seq, position, logits [V], ids | None)]."""
    tables = {"A": list(range(1, 11)), "B": list(range(20, 27))}
    kc, vc = allocate_device_cache(cfg, NB, BS)
    fns = {c: M.make_ragged_step_fn(cfg, BS, chunks=c, moe_routing=routing)
           for c in (True, False)}
    out = []
    for stage, chunks, rows in PLAN:
        ops = _rows_operands(rows, seqs, tables)
        logits, kc, vc, stats, *ids = fns[chunks](params, *ops, kc, vc)
        stats = np.asarray(stats).sum(0)  # over the cache groups
        n_tok = sum(c for *_, c in rows)
        n_moe = sum(st.moe * len(st.layers) for st in M.layer_stacks(cfg))
        # every real token's K choices, in every expert layer, and no pad's
        assert stats[0] == n_tok * cfg.num_experts_per_tok * n_moe
        assert stats[1] == stats[M.MOE_STATS_HEAD:].sum() <= stats[0]
        # row tiles launched: one at least an expert touched
        assert stats[2] <= stats[3] <= stats[1]
        t = 0
        for i, (s, start, chunk) in enumerate(rows):
            got = (np.asarray(ids[0])[:, t:t + chunk] if routing else None)
            out.append((stage, s, start, chunk, np.asarray(logits[i]), got))
            t += chunk
    return out


def _seqs(seed=0):
    rng = np.random.default_rng(seed)
    return {"A": rng.integers(1, 256, 40), "B": rng.integers(1, 256, 30)}


@pytest.fixture(scope="module")
def tiny_f32():
    cfg = mimo_tiny()
    params = M.init_params(cfg, jax.random.key(0))
    seqs = _seqs()
    return cfg, params, seqs, run_engine_steps(cfg, params, seqs)


def _reference_logits(cfg, params, seqs, **kw):
    weights, hp = mimo_v2_inputs(cfg, params)
    return {s: np.asarray(mimo_v2.forward(weights, hp, toks, **kw)[0])
            for s, toks in seqs.items()}


def _max_err(steps, ref):
    return max(float(np.abs(lg - ref[s][start + chunk - 1]).max())
               for _st, s, start, chunk, lg, _ in steps)


def test_engine_logits_match_the_reference_f32(tiny_f32):
    cfg, params, seqs, steps = tiny_f32
    ref = _reference_logits(cfg, params, seqs)
    for stage, s, start, chunk, lg, _ in steps:
        err = float(np.abs(lg - ref[s][start + chunk - 1]).max())
        assert err < TOL_F32, (stage, s, start, err)


@pytest.mark.parametrize("piece", ["sink", "value_scale", "rope_base",
                                   "correction_bias", "normalisation"])
def test_comparison_fails_when_a_piece_is_left_out(tiny_f32, piece):
    """The tolerance is tight enough to see each piece: the reference with
    it dropped is further from the engine than any tolerance used here."""
    cfg, params, seqs, steps = tiny_f32
    ref = _reference_logits(cfg, params, seqs, leave_out=(piece,))
    assert _max_err(steps, ref) > 2 * TOL_BF16


def test_engine_logits_match_the_reference_bf16(tiny_f32):
    """bf16 weights, activations and pages. The reference is told the
    engine's expert choices: behind a small gap a bf16 router picks other
    experts than a float32 one, and the row then computes another function."""
    cfg = dataclasses.replace(mimo_tiny(), dtype="bfloat16")
    params = jax.tree.map(  # the correction bias stays float32, as built
        lambda a: a.astype(jnp.bfloat16) if a.ndim > 2 or a.shape[-1] != 32
        else a, tiny_f32[1])
    seqs = _seqs(1)
    steps = run_engine_steps(cfg, params, seqs, routing=True)
    n_moe = cfg.num_layers - 1
    ids = {s: np.zeros((n_moe, len(t), cfg.num_experts_per_tok), np.int32)
           for s, t in seqs.items()}
    seen = {s: 0 for s in seqs}
    for _st, s, start, chunk, _lg, got in steps:
        ids[s][:, start:start + chunk] = got
        seen[s] = max(seen[s], start + chunk)
    weights, hp = mimo_v2_inputs(cfg, params)
    for s, toks in seqs.items():
        n = seen[s]
        ref, routed = mimo_v2.forward(weights, hp, toks[:n],
                                      expert_ids=list(ids[s][:, :n]))
        ref = np.asarray(ref)
        for _st, s2, start, chunk, lg, _ in steps:
            if s2 == s:
                err = float(np.abs(lg - ref[start + chunk - 1]).max())
                assert err < TOL_BF16, (s, start, err)


def test_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """Four ranks of 8 held experts, each computed by the program's layer,
    add up to what the uncut reference layer gives (no shared expert: there
    is nothing every rank computes alike to count once)."""
    whole = mimo_tiny(experts_held=None)
    E, D, F = whole.num_experts, whole.hidden_size, whole.moe_ffn_size
    ks = jax.random.split(jax.random.key(3), 6)
    lp = {"router": jax.random.normal(ks[0], (D, E)) / 8,
          "router_bias": jax.random.normal(ks[1], (E,)) / 10,
          "w_gate": jax.random.normal(ks[2], (E, D, F)) / 8,
          "w_up": jax.random.normal(ks[3], (E, D, F)) / 8,
          "w_down": jax.random.normal(ks[4], (E, F, D)) / 6}
    x = jax.random.normal(ks[5], (24, D))
    valid = jnp.ones((24,), bool)
    total, pairs = 0.0, 0
    for first in range(0, E, 8):
        share = mimo_tiny(experts_held=(first, 8))
        part = {k: (v[first:first + 8] if k.startswith("w_") else v)
                for k, v in lp.items()}
        y, stats, _ids = M._mlp_moe_held(x, part, share, valid)
        total, pairs = total + y, pairs + int(stats[1])
    assert pairs == 24 * whole.num_experts_per_tok  # every pair, once
    _w, hp = mimo_v2_inputs(mimo_tiny(), M.init_params(
        mimo_tiny(), jax.random.key(0)))
    with jax.default_matmul_precision("highest"):
        ref, _, _ = mimo_v2.experts(x, lp, {**hp, "experts_held": [0, E]})
    np.testing.assert_allclose(np.asarray(total), np.asarray(ref),
                               atol=2e-5)


def test_padding_tokens_are_routed_nowhere():
    cfg = mimo_tiny()
    lp = jax.tree.map(lambda a: a[0], M.init_params(
        cfg, jax.random.key(0))["stacks"][1])
    x = jax.random.normal(jax.random.key(5), (16, cfg.hidden_size))
    valid = jnp.arange(16) < 5
    y, stats, _ = M._mlp_moe_held(x, lp, cfg, valid)
    assert int(stats[0]) == 5 * cfg.num_experts_per_tok
    assert not np.asarray(y[5:]).any()


def test_row_tiles_are_counted_as_the_kernel_launches_them():
    """An expert with more pairs than one tile holds takes two; the
    counters say so: tiles − experts touched found their weights there."""
    from dynamo_tpu.ops.grouped_matmul import ROW_TILE
    cfg = mimo_tiny()
    lp = jax.tree.map(lambda a: a[0], M.init_params(
        cfg, jax.random.key(0))["stacks"][1])
    n = ROW_TILE * 8
    x = jax.random.normal(jax.random.key(5), (n, cfg.hidden_size))
    _, stats, _ = M._mlp_moe_held(x, lp, cfg, jnp.ones((n,), bool))
    stats = np.asarray(stats)
    per_expert = stats[M.MOE_STATS_HEAD:]
    assert stats[2] == (per_expert > 0).sum()
    assert stats[3] == (-(-per_expert // ROW_TILE)).sum() > stats[2]


def test_a_padded_long_chunk_then_decode_match_the_reference():
    """A prompt that runs PADDED in a program long enough for the dropless
    buffer to have two tiles an expert (150 tokens in the 256-token
    program), every row of every expert launch that no pair names NaN, then
    decode steps over the pages the chunk wrote: the logits are the
    reference's within the limit every other step is held to (the twin of
    tests/test_granite4_h.py's case: no recurrent state here, so a padding
    token's row cannot outlive its step)."""
    from dynamo_tpu.ops.grouped_matmul import ROW_TILE, _blocks
    from tests.poisoned_launches import padded_chunk_then_decode

    cfg, long, n, steps = mimo_tiny(), 256, 150, 4
    (_, Eh), K = cfg.experts_held, cfg.num_experts_per_tok
    assert _blocks(-(-long * K // ROW_TILE) + Eh, Eh, 64, 64, 4)[2]
    assert not _blocks(-(-T * K // ROW_TILE) + Eh, Eh, 64, 64, 4)[2]
    params = M.init_params(cfg, jax.random.key(0))
    seq = {"A": np.random.default_rng(3).integers(1, 250, n + steps)}
    got, _ = padded_chunk_then_decode(
        cfg, params, lambda row, width: _rows_operands(
            [row], seq, {"A": list(range(1, 64))}, T=width, W=64),
        allocate_device_cache(cfg, NB, BS), None,
        [(long, True, ("A", 0, n))] + [
            (T, False, ("A", n + i, 1)) for i in range(steps)])
    ref = _reference_logits(cfg, params, seq)["A"]
    for i, lg in enumerate(got):
        assert float(np.abs(lg - ref[n - 1 + i]).max()) < TOL_F32, i


@pytest.mark.anyio
async def test_a_padded_chunk_through_the_engine_counts_what_it_read_back():
    """The padded prompt through the engine: the first pick is the
    reference's and the flight records hold the layer's own counts
    (tests/poisoned_launches.py has the assertions)."""
    from tests.poisoned_launches import padded_prompt_through_the_engine

    cfg = mimo_tiny()
    params = M.init_params(cfg, jax.random.key(0))
    prompt = np.random.default_rng(3).integers(1, 250, 150)
    await padded_prompt_through_the_engine(
        cfg, params, "mimo_tiny", prompt, 256,
        _reference_logits(cfg, params, {"A": prompt})["A"][-1], 12)


def test_k_rows_wider_than_a_lane_row_are_stored_padded():
    """A head wider than 128 lanes that is no lane multiple (the published
    192) is stored at the next one, zeros behind it; the model's logits are
    what they are with the head stored as it is."""
    assert ModelConfig(head_dim=192).k_cache_dim == 256
    assert ModelConfig(head_dim=128).k_cache_dim == 128
    assert ModelConfig(head_dim=64).k_cache_dim == 64
    cfg = dataclasses.replace(mimo_tiny(), head_dim=136, num_layers=2,
                              layer_pattern=(0, 1))
    assert [g.k_dim for g in cfg.kv_cache_spec] == [256, 256]
    params = M.init_params(cfg, jax.random.key(2))
    seqs = _seqs(2)
    steps = run_engine_steps(cfg, params, seqs)
    assert _max_err(steps, _reference_logits(cfg, params, seqs)) < TOL_F32


def test_the_two_copies_of_the_reference_are_byte_identical():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert filecmp.cmp(
        os.path.join(root, "dynamo_tpu/models/reference/mimo_v2.py"),
        os.path.join(root, "chipbench/references/mimo_v2.py"), shallow=False)


def test_published_config_maps_onto_model_config():
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root,
                           "chipbench/configs/mimo-v25-ep16.json")) as f:
        doc = json.load(f)
    cfg = ModelConfig.from_hf_config(doc)
    from dynamo_tpu.models import mimo_v25_ep16

    want = mimo_v25_ep16()
    for f_ in dataclasses.fields(ModelConfig):
        if f_.name != "rope_scaling":  # published as {"rope_type": default}
            assert getattr(cfg, f_.name) == getattr(want, f_.name), f_.name


@pytest.mark.anyio
async def test_engine_serves_mimo_and_counts_what_its_experts_did():
    """The normal path: scheduler, BlockPool, ragged step, pipelined decode,
    both cache groups, with the counters and flight-record fields the
    benchmark's readers read."""
    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.protocols import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )

    cfg = mimo_tiny()
    args = dict(block_size=4, num_blocks=128, max_num_seqs=4,
                max_num_batched_tokens=32, max_model_len=64,
                preempt_swap=False)
    with pytest.raises(ValueError, match="cache of 2 groups"):
        AsyncJaxEngine(cfg, EngineArgs(**{**args, "preempt_swap": True}))
    eng = AsyncJaxEngine(cfg, EngineArgs(**args))
    facts = eng.build_facts
    assert facts["layers"] == {"full": 3, "window": 10, "dense": 1,
                               "experts": 12}
    assert facts["experts_held"] == [0, 8]
    assert [g["page_bytes"] for g in facts["cache_groups"]] == [
        3 * 4 * 1 * (24 + 16) * 4, 10 * 4 * 2 * (24 + 16) * 4]
    rng = np.random.default_rng(0)

    async def one(n):
        req = PreprocessedRequest(
            model="mimo_tiny", token_ids=rng.integers(1, 250, n).tolist(),
            stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0))
        return [t async for o in eng.generate(req) for t in o.token_ids]

    import asyncio
    # 40 and 20 tokens at a budget of 32: both prompts sample in the second
    # step and decode in lockstep, so the pipelined loop computes no row
    # past a finished sequence and the device's counts below are exact
    outs = await asyncio.gather(one(40), one(20))
    assert [len(o) for o in outs] == [6, 6]
    recs = eng.flight.snapshot()
    assert any(r["kind"] == "decode_pipe" for r in recs)
    assert sum(r.get("moe_pairs", 0) for r in recs) == \
        eng.moe_assignments_total["held"] == \
        int(eng.moe_expert_tokens_total.sum()) > 0
    assert sum(r.get("moe_tiles", 0) for r in recs) == \
        eng.moe_row_tiles_total >= \
        sum(r.get("moe_experts_touched", 0) for r in recs) > 0
    assert all([sum(col) for col in zip(*r["moe_by_group"])] == [
        r["moe_pairs"], r["moe_experts_touched"], r["moe_tiles"]]
        for r in recs if r.get("moe_pairs"))
    n_tok = 40 + 20 + 2 * 5  # prompts, and every emitted token but the last
    assert eng.moe_assignments_total["all"] == n_tok * 4 * 12
    # the 40-token prompt outgrows the 8-token window: pages behind it
    assert max(r.get("dead_window_pages", 0) for r in recs) >= (40 - 8) // 4
    await eng.close()
