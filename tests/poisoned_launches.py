"""What the rows nobody wrote may hold: a wrapper of the grouped matmul for
the tests of the held-experts layer's read-back.

ops/grouped_matmul.py leaves the rows of tiles it did not launch unwritten
(on the chip: whatever that memory held, NaN included), and the padding rows
inside an expert's last tile are nobody's. 0 x NaN is NaN, so whatever reads
the result back has to SELECT the rows of pairs and never multiply the rest
away. Interpret mode on the CPU hands out zeroed memory and hides a reader
that does not; under this wrapper every row that no pair names is poison.
"""

import contextlib
from unittest import mock

import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine import model as M
from dynamo_tpu.ops import grouped_matmul as gm


@contextlib.contextmanager
def unnamed_rows_poisoned(poison=np.nan):
    """While open, every ``grouped_matmul`` the model launches returns
    ``poison`` in each row that no pair names: the rows of tiles past
    ``num_tiles`` AND the padding rows inside a launched tile (a row whose
    input row is the dispatch's zero row or, in the down launch, the poison
    the launches before it left there). Yields the launches' tags, as
    traced."""
    launch, tags = gm.grouped_matmul, []

    def poisoned(a, w, tile_group, num_tiles, *args, **kw):
        out = launch(a, w, tile_group, num_tiles, *args, **kw)
        tags.append(kw.get("tag", ""))
        af = a.astype(jnp.float32)
        unnamed = ~(jnp.isfinite(af).all(1) & (af != 0).any(1)) | (
            jnp.arange(out.shape[0]) >= num_tiles * gm.ROW_TILE)
        return jnp.where(unnamed.reshape(-1, *[1] * (out.ndim - 1)), poison,
                         out).astype(out.dtype)

    with mock.patch("dynamo_tpu.ops.grouped_matmul.grouped_matmul", poisoned):
        yield tags


def padded_chunk_then_decode(cfg, params, operands, caches, state, plan,
                             block_size=4, **step_kw):
    """Every step of ``plan`` = [(program's tokens, mixed program?, row)]
    through the jitted ragged step programs with every unnamed row of every
    expert launch NaN — a PADDED chunk first, then decode steps from what it
    left. ``operands(row, tokens)`` lays a step out; ``state`` is None for a
    model without state slots. Returns (each step's first row's logits, the
    state arrays after the last step)."""
    kc, vc = caches
    fns = {c: M.make_ragged_step_fn(cfg, block_size, chunks=c, **step_kw)
           for c in (True, False)}
    got = []
    with unnamed_rows_poisoned() as tags:
        for tokens, chunks, row in plan:
            carry = (kc, vc) if state is None else (kc, vc, state)
            logits, kc, vc, _, *rest = fns[chunks](
                params, *operands(row, tokens), *carry)
            state = None if state is None else rest[-1]
            got.append(np.asarray(logits[0]))
    assert any(t.endswith("_down") for t in tags)
    return got, state


async def padded_prompt_through_the_engine(cfg, params, model, prompt, long,
                                           ref_last, expert_layers):
    """``prompt`` (shorter than ``long``, so its one chunk runs PADDED in the
    ``long``-token program) through an engine, four tokens out: the first
    pick is ``ref_last``'s (the reference's logits at the prompt's end), and
    every step's flight record holds what the layer counted on the device —
    the read-back fetched the held pairs' rows, of the padded tokens' every
    pair in each of the ``expert_layers``."""
    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.protocols import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )

    n = len(prompt)
    eng = AsyncJaxEngine(cfg, EngineArgs(
        block_size=4, num_blocks=128, max_num_seqs=2,
        max_num_batched_tokens=long, max_model_len=320, preempt_swap=False),
        params=params)
    req = PreprocessedRequest(
        model=model, token_ids=list(map(int, prompt)),
        stop_conditions=StopConditions(max_tokens=4, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0))
    out = [t async for o in eng.generate(req) for t in o.token_ids]
    assert len(out) == 4
    if np.diff(np.sort(ref_last)[-2:])[0] > 1e-3:  # no tie a sum could flip
        assert out[0] == int(ref_last.argmax())
    recs = [r for r in eng.flight.snapshot() if r.get("moe_pairs")]
    chunk, = [r for r in recs if r.get("chunk_tokens") == n]
    pairs_a_token = cfg.num_experts_per_tok * expert_layers
    assert chunk["moe_combine_rows"] == chunk["moe_pairs"] \
        < n * pairs_a_token < chunk["moe_combine_rows_max"] \
        == long * pairs_a_token
    assert all(r["moe_combine_rows"] == r["moe_pairs"]
               < r["moe_combine_rows_max"] for r in recs)
    assert eng.moe_combine_rows_total == {
        "read": sum(r["moe_combine_rows"] for r in recs),
        "worst_case": sum(r["moe_combine_rows_max"] for r in recs)}
    await eng.close()
