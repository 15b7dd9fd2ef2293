#!/usr/bin/env python3
"""The reference comparison of ``granite4-h-small-ep2`` at its published
widths, on the chip, of what the serving path itself produces under the
cell's flags (``check_reference.py`` is written for ``mimo_v2``; this
command stands beside it and shares what it can by import):

    python3 chipbench/check_reference_granite4.py --seed 0
    python3 chipbench/check_reference_granite4.py --seed 0 --control fp8-weights
    python3 chipbench/check_reference_granite4.py --seed 0 --control bf16-state

It builds the engine as the cell's worker does, sends requests through
``engine.generate`` — scheduler (admission by state slot), ragged step,
pipelined decode, the state slots and the one attention layer's pages —
taps every step's logits and expert choices, frees the engine, and holds the
logits against ``chipbench/references/granite4_h.py`` on the same chip:
float32 at ``highest`` precision, the recurrence a plain scan over tokens.

Stages, by what a step held (``check_reference.stage_of``): ``fresh_chunk``
(a whole 2,048-token budget of a 6,144-token prompt, from a state of
zeros), ``continuation`` (that prompt's later budgets: the chunked scan
starts from the slot's state and the convolution's stored tail), ``chunks``
(other steps of chunks only), ``mixed`` (chunks beside decode rows),
``decode_few`` and ``decode_batch`` (decode-only steps, of few rows and of
``--batch`` rows at contexts 200-6,000, through the pipelined decode
program and the update kernel).

Judged as there: a stage on its largest row's largest |logit - reference|;
the reference is told the engine's expert choices, and the choices are
judged apart — where the engine's ten experts are not the float32 router's,
the float32 logits of its tenth and eleventh choice lie closer than
``CHOICE_GAP``. Two controls, both of which have to come out as not correct:
``fp8-weights`` rounds every projection, expert and shared-expert matrix of
the ENGINE's copy to float8 (e4m3, scaled per output channel), the nearest
precision below the configuration's bf16; ``bf16-state`` keeps the ENGINE's
SSM state in bf16 between steps where the configuration says float32.
``TOLERANCES`` and ``CHOICE_GAP``: PERF.md section 6 (PR 36) has the
readings they were set from.

One JSON line on stdout, every row compared in
``chiprun_out/check_reference/<config>_seed<n>_<control>.json``, exit 0 only
if every stage and the choices are inside.
"""

import argparse
import asyncio
import dataclasses
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "references"))

from check_reference import drive, engine_args, stage_of  # noqa: E402

#: the largest |logit - reference logit| of a stage's rows; the logits have
#: a standard deviation near 1. Each lies between the largest reading of two
#: seeds and the float8 control's (my chip runs, PR 36; PERF.md section 6):
#: fresh_chunk 0.075 | 0.630, continuation 0.077 | 0.560, chunks 0.087 |
#: 0.692, mixed 0.137 | 1.752, decode_few 0.239 | 2.126, decode_batch 0.330 |
#: 3.185. The bf16-state control fails the two decode stages alone (0.641,
#: 2.353): a chunk reads a stored state once, a decode row at every token.
TOLERANCES = {"fresh_chunk": 0.2, "continuation": 0.2, "chunks": 0.2,
              "mixed": 0.4, "decode_few": 0.5, "decode_batch": 0.8}
#: the engine may choose another expert than the float32 router only behind
#: a gap of the router's logits (of order 1) smaller than this (readings:
#: 0.160 | 0.105 over two seeds, the float8 control 0.468, bf16 state 0.268)
CHOICE_GAP = 0.3

class StateTap:
    """``check_reference.Tap`` for a model with state: stands where the
    engine's two step programs stand, passes the state arrays through the
    engine's own keeper, and keeps per step what it takes to say which
    (request, position) every row was."""

    def __init__(self, engine, M, np):
        self.engine, self.steps, self.np = engine, [], np
        for name, chunks in (("ragged_fn", True), ("ragged_dec_fn", False)):
            fn = M.make_ragged_step_fn(
                engine.cfg, engine.args.block_size, None,
                use_pallas=engine.args.use_pallas_attention,
                chunks=chunks, moe_routing=True)
            setattr(engine, name, self._wrap(engine._keep_state(fn)))

    def _wrap(self, fn):
        def step(params, ints5, rows4, grid_rows, bt, kc, vc):
            logits, kc, vc, _stats, ids = fn(params, ints5, rows4,
                                             grid_rows, bt, kc, vc)
            # a sequence is known by its state slot while it runs
            owner = {s.state_slot: s.request_id
                     for s in self.engine.scheduler.running}
            rows4 = self.np.asarray(rows4)
            got = self.np.asarray(logits)
            self.steps.append((
                [(owner.get(int(r[3])), int(r[0]), int(r[1]), int(r[2]),
                  got[i]) for i, r in enumerate(rows4) if r[1] > 0],
                self.np.asarray(ids)))
            return logits, kc, vc
        return step


def round_weights_to_fp8(params, jnp):
    """The first control: every matrix of the layers (in/out projections,
    attention projections, experts, shared expert) rounded to float8 e4m3
    and widened back, scaled per output channel. Routers, norms, the
    convolution and the per-head vectors stay."""
    import functools

    import jax

    from dynamo_tpu.engine.quant import HEAD_MAJOR_KEYS

    # a program a step, so that a 2 GB expert stack is never widened to
    # float32 whole beside the other 9.5 GB — and TWO programs, the float8
    # array between them: in one, the compiler may keep the excess
    # precision and drop the rounding (it did: the control's first chip run
    # read the uncontrolled run's numbers to the digit)
    @functools.partial(jax.jit, static_argnums=1)
    def narrow(w, axis):
        s = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis,
                    keepdims=True) / 448.0
        return (w.astype(jnp.float32) / s).astype(jnp.float8_e4m3fn), s

    @functools.partial(jax.jit, static_argnums=2)
    def widen(w8, s, dtype):
        return (w8.astype(jnp.float32) * s).astype(dtype)

    def q(w, axis):
        dtype = w.dtype
        w8, s = narrow(w, axis)
        w.delete()   # given up at once: 9.5 GB twice do not fit
        return widen(w8, s, dtype)

    def leaf(k, v):
        if k in HEAD_MAJOR_KEYS:
            return q(v, -1)          # [L, heads, width, D]: D contracts
        if k in ("in_proj", "out_proj", "wo") or k.startswith(("w_", "ws_")):
            return q(v, -2)          # [..., in, out]
        return v

    return {**params, "stacks": tuple(
        {k: leaf(k, v) for k, v in st.items()} for st in params["stacks"])}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="granite4-h-small-ep2")
    ap.add_argument("--config-file", default=None,
                    help="a configuration file elsewhere (CPU rehearsal)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch", type=int, default=40)
    ap.add_argument("--control", default="none",
                    choices=("none", "fp8-weights", "bf16-state"))
    cli = ap.parse_args()
    with open(cli.config_file or os.path.join(
            HERE, "configs", cli.config + ".json")) as f:
        config = json.load(f)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import granite4_h as ref
    from dynamo_tpu.engine import engine as E
    from dynamo_tpu.engine import model as M
    from dynamo_tpu.models import get_model_config
    from dynamo_tpu.models.reference import granite4_h_inputs
    from dynamo_tpu.runtime.config import place_compile_cache

    place_compile_cache()
    say = lambda *a: print(*a, file=sys.stderr, flush=True)  # noqa: E731
    cfg = get_model_config(config["arch"])
    args = engine_args(config["worker_flags"], cli.seed).replace(
        warmup_buckets=False)
    budget = args.max_num_batched_tokens
    sizes = {"long_prompt": 6144, "mixed_prompts": [600, 3000],
             "contexts": [200, 6000], "batch_out": 96,
             **config.get("check_reference", {})}
    t0 = time.perf_counter()
    params = M.init_params(cfg, jax.random.key(cli.seed))
    run_cfg = cfg
    if cli.control == "fp8-weights":
        params = round_weights_to_fp8(params, jnp)
    elif cli.control == "bf16-state":
        run_cfg = dataclasses.replace(cfg, mamba_state_dtype="bfloat16")
    engine = E.AsyncJaxEngine(run_cfg, args, params=params)
    del params
    tap = StateTap(engine, M, np)
    built_s = time.perf_counter() - t0
    say("built", round(built_s, 1), json.dumps(engine.build_facts))
    rng = np.random.default_rng(cli.seed)
    vocab_hi = min(30000, cfg.vocab_size)

    async def run():
        try:
            return await drive(engine, vocab_hi, cli.batch, rng, np, sizes)
        finally:
            await engine.close()

    done = asyncio.run(run())
    ran_s = time.perf_counter() - t0 - built_s
    say("engine ran", round(ran_s, 1), "steps tapped", len(tap.steps))
    steps = tap.steps
    name_of = {rid: name for rid, (name, _p, _o) in done.items()}
    prompt_len = {rid: len(p) for rid, (_n, p, _o) in done.items()}

    # free the pool, the slots and the engine's weights: the reference
    # needs the room, and its weights are the configuration's own
    facts = engine.build_facts
    engine.k_cache = engine.v_cache = engine.params = engine.state = None
    del engine, tap
    gc.collect()
    jax.clear_caches()
    say("freed: bytes in use",
        (jax.devices()[0].memory_stats() or {}).get("bytes_in_use"))
    true_params = M.init_params(cfg, jax.random.key(cli.seed))
    K, L = cfg.num_experts_per_tok, cfg.num_layers
    seqs = {rid: np.asarray(p + o, np.int32)
            for rid, (_n, p, o) in done.items()}
    chosen = {rid: np.full((L, len(t), K), -1, np.int32)
              for rid, t in seqs.items()}
    wanted = {rid: set() for rid in seqs}
    for rows, ids in steps:
        for rid, q_start, q_len, kv_len, _lg in rows:
            if rid in seqs and kv_len <= len(seqs[rid]):
                chosen[rid][:, kv_len - q_len:kv_len] = \
                    ids[:, q_start:q_start + q_len]
                wanted[rid].add(kv_len - 1)
    weights, hp = granite4_h_inputs(cfg, true_params, consume=True)
    del true_params
    want, gaps, differ = {}, {}, {}
    fwd = jax.jit(lambda w, toks, ids, rows: ref.forward(
        w, hp, toks, expert_ids=list(ids), rows=rows))
    n_rows = max(len(w) for w in wanted.values())
    for rid, toks in seqs.items():
        n = max(wanted[rid]) + 1 if wanted[rid] else 0
        if not n:
            continue
        # a causal model's answers do not see what follows: pad to a power
        # of two, so the reference compiles a handful of times
        size = 1 << (n - 1).bit_length()
        rows = np.asarray(sorted(wanted[rid]), np.int32)
        lg, did = fwd(
            weights, np.pad(toks[:n], (0, size - n)),
            np.pad(chosen[rid][:, :n], ((0, 0), (0, size - n), (0, 0))),
            np.pad(rows, (0, n_rows - len(rows)), mode="edge"))
        want[rid] = dict(zip(rows.tolist(), np.asarray(lg)))
        worst, n_differ = 0.0, 0
        for li, choice in enumerate(did["choice"]):
            choice = np.asarray(choice[:n])
            top = np.sort(choice, axis=1)[:, ::-1]
            gap = top[:, K - 1] - top[:, K]
            mine = np.sort(np.argsort(-choice, axis=1)[:, :K], axis=1)
            theirs = np.sort(chosen[rid][li, :n], axis=1)
            other = (mine != theirs).any(axis=1)
            n_differ += int(other.sum())
            if other.any():
                worst = max(worst, float(gap[other].max()))
        gaps[rid], differ[rid] = worst, n_differ
        say("reference", name_of[rid], n, "tokens",
            round(time.perf_counter() - t0, 1))
    ref_s = time.perf_counter() - t0 - built_s - ran_s

    records = []
    for step, (rows, _ids) in enumerate(steps):
        known = [r for r in rows if r[0] in want]
        if not known:
            continue
        stage = stage_of(known, prompt_len, budget, cli.batch)
        records += [{"stage": stage, "step": step, "request": name_of[rid],
                     "position": kv - 1,
                     "diff": float(np.abs(lg - want[rid][kv - 1]).max())}
                    for rid, _qs, _ql, kv, lg in known
                    if kv - 1 in want[rid]]
    stages = {}
    for rec in records:
        stages.setdefault(rec["stage"], []).append(rec["diff"])
    loosest = max(TOLERANCES.values())
    out = {s: {"max_abs_diff": max(d),
               "median_abs_diff": sorted(d)[len(d) // 2], "rows": len(d),
               "tolerance": TOLERANCES.get(s, loosest),
               "ok": max(d) <= TOLERANCES.get(s, loosest)}
           for s, d in stages.items()}
    missing = sorted(set(TOLERANCES) - set(out))
    tokens = sum(len(c[0]) for c in chosen.values()) * L
    choices = {"assignments": tokens * K,
               "tokens_with_another_set": sum(differ.values()),
               "widest_gap_where_they_differ": max(gaps.values(), default=0),
               "gap_allowed": CHOICE_GAP}
    choices["ok"] = choices["widest_gap_where_they_differ"] < CHOICE_GAP
    ok = (not missing and choices["ok"]
          and all(s["ok"] for s in out.values()))
    where = os.path.join(ROOT, "chiprun_out", "check_reference")
    os.makedirs(where, exist_ok=True)
    with open(os.path.join(where, f"{cli.config}_seed{cli.seed}_"
                                  f"{cli.control}.json"), "w") as f:
        json.dump(records, f)
    dev = jax.devices()[0]
    some = next(iter(want.values()))
    print(json.dumps({
        "config": cli.config, "seed": cli.seed, "control": cli.control,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "ok": ok, "stages": out, "stages_missing": missing,
        "choices": choices,
        "logit_std": float(np.std(next(iter(some.values())))),
        "weights_bytes": facts["weights_bytes"],
        "state_bytes": facts["state_bytes"],
        "attention": facts["attention"],
        "seconds": {"build": built_s, "engine": ran_s, "reference": ref_s},
    }), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
