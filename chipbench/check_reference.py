#!/usr/bin/env python3
"""The reference comparison of a configuration at its published widths, on
the chip, of what the serving path itself produces at the cell's sizes:

    python3 chipbench/check_reference.py --config mimo-v25-ep16 --seed 0

It builds the engine as the cell's worker does (the configuration's ``arch``
and ``worker_flags``, random weights from ``--seed``), sends requests through
``engine.generate`` — so the scheduler, the ragged step, the pipelined decode
step and both cache groups run as they do in the window — taps every step's
logits and expert choices, and holds the logits against
``chipbench/references/mimo_v2.py`` computed on the same chip afterwards
(the pool freed), float32 at ``highest`` matmul precision, one layer's
weights widened at a time, queries in blocks.

Stages, by what a step held:

- ``fresh_chunk``: a whole 2,048-token budget of one prompt, from nothing;
- ``continuation``: a later chunk of a 12k prompt — the window layers have
  slid past their first pages, the full layers read them all;
- ``mixed``: prompt chunks beside decode rows;
- ``decode_batch``: decode-only steps of ``--batch`` (40) rows at contexts
  from 200 to 16k, through the pipelined decode program.

What is judged. A row's difference is the largest |logit − reference logit|
over the vocabulary slice; a stage is judged on its LARGEST row. The
reference is told the engine's expert choices (a bf16 router picks other
experts than a float32 one behind a small gap, and the row then computes
another function), and the choices are judged on their own: wherever the
engine's set of experts differs from the float32 router's, the reference's
scores of the last expert it chose and of the first it passed over must lie
closer than ``CHOICE_GAP``.

Tolerances (``TOLERANCES``, ``CHOICE_GAP``): PERF.md §6 (PR 30) has the
readings they were set from — the engine's largest over seeds, and the
control's. ``--control fp8-weights`` rounds the layers' matrices (attention
projections, MLP, experts) to float8 (e4m3, scaled per output channel) in
the ENGINE only: the nearest precision
below the configuration's bf16 (int8 per channel carries as many bits as
bf16 does and is no lower); it has to come out as not correct.

One JSON line on stdout, every row compared in
``chiprun_out/check_reference/<config>_seed<n>_<control>.json``, exit 0
only if every stage and the choices are inside.
"""

import argparse
import asyncio
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(HERE, "references"))

#: the largest |logit − reference logit| of a stage's rows. The logits have
#: a standard deviation near 1 (random weights, final norm, head of std
#: 1/sqrt(hidden)); bf16 weights, activations and pages against float32
#: carry 2^-8 relative a rounding through 7 layers, more where a row sums
#: over 16k keys. Readings: PERF.md §6, PR 30.
TOLERANCES = {"fresh_chunk": 0.12, "continuation": 0.12, "mixed": 0.12,
              "decode_batch": 0.12}
#: the engine may choose another expert than the float32 router only
#: behind a gap of the choice scores (σ + bias, of order 0.5) smaller than
#: this: a bf16 rounding of the hidden state moves σ by about 2^-8 · |logit|
CHOICE_GAP = 0.02


def engine_args(flags: list, seed: int):
    """EngineArgs from the configuration's ``worker_flags``, as
    ``dynamo_tpu.engine.main`` reads them."""
    from dynamo_tpu.engine.config import EngineArgs

    names = {"--max-num-seqs": ("max_num_seqs", int),
             "--max-num-batched-tokens": ("max_num_batched_tokens", int),
             "--max-model-len": ("max_model_len", int),
             "--use-pallas-attention": ("use_pallas_attention", True),
             "--warmup-buckets": ("warmup_buckets", True),
             "--no-preempt-swap": ("preempt_swap", False)}
    kw, it = {"seed": seed}, iter(flags)
    for flag in it:
        field, cast = names[flag]  # an unknown flag is an error
        kw[field] = cast if isinstance(cast, bool) else cast(next(it))
    return EngineArgs(**kw)


class Tap:
    """Stands where the engine's two step programs stand, runs the variant
    that also returns the routers' choices, and keeps per step what it
    takes to say which (request, position) every row and token was."""

    def __init__(self, engine, M, np):
        #: per step: [(request, q_start, q_len, kv_len, logits row)] and
        #: the routers' choices [L_moe, T, K], on the host
        self.engine, self.steps, self.np = engine, [], np
        for name, chunks in (("ragged_fn", True), ("ragged_dec_fn", False)):
            fn = M.make_ragged_step_fn(
                engine.cfg, engine.args.block_size, None,
                use_pallas=engine.args.use_pallas_attention,
                chunks=chunks, moe_routing=True)
            setattr(engine, name, self._wrap(fn, chunks))

    def _wrap(self, fn, chunks):
        def step(params, ints5, rows3, grid_rows, bt, kc, vc):
            logits, kc, vc, stats, ids = fn(params, ints5, rows3, grid_rows,
                                            bt, kc, vc)
            del stats  # the engine's counters are not what is checked here
            # a sequence is known by its first page while it lives
            owner = {s.block_table[0]: s.request_id
                     for s in self.engine.scheduler.running if s.block_table}
            # to the host at once (a step's logits are 5 MB): the check
            # is not timed, and the programs are the ones that are
            rows3, first = self.np.asarray(rows3), self.np.asarray(bt[:, 0])
            live = [i for i in range(len(rows3)) if rows3[i][1] > 0]
            got = self.np.asarray(logits)
            self.steps.append((
                [(owner.get(int(first[i])), *map(int, rows3[i]), got[i])
                 for i in live], self.np.asarray(ids)))
            return logits, kc, vc
        return step


def stage_of(rows: list, prompt_len: dict, budget: int, batch: int) -> str:
    chunk = [r for r in rows if r[2] > 1 or r[3] <= prompt_len[r[0]]]
    decode = [r for r in rows if r not in chunk]
    if chunk and decode:
        return "mixed"
    if decode:
        return "decode_batch" if len(decode) >= batch // 2 else "decode_few"
    if len(chunk) == 1 and chunk[0][2] == budget:
        return "fresh_chunk" if chunk[0][3] == budget else "continuation"
    return "chunks"


async def drive(engine, vocab_hi: int, batch: int, rng, np, sizes: dict):
    """The requests, and what each produced: {engine request id: (name,
    prompt, generated)}. The engine names its sequences seq-<n> in the order
    ``generate`` is entered, which is the order ``one`` is started in."""
    from dynamo_tpu.protocols import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )

    done, started = {}, {}

    async def one(name, n_prompt, n_out):
        rid = f"seq-{len(started)}"
        started[name] = asyncio.Event()
        prompt = rng.integers(10, vocab_hi, n_prompt).tolist()
        req = PreprocessedRequest(
            model="bench", token_ids=prompt,
            stop_conditions=StopConditions(max_tokens=n_out,
                                           ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0))
        out = []
        async for item in engine.generate(req):
            out += item.token_ids
            started[name].set()
        done[rid] = (name, prompt, out)

    # the long prompt alone: a fresh whole budget, then continuations
    await one("long", sizes["long_prompt"], 4)
    # one request decodes while another prefills: mixed steps
    a = asyncio.ensure_future(one("m0", sizes["mixed_prompts"][0], 48))
    while "m0" not in started or not started["m0"].is_set():
        await asyncio.sleep(0.002)
    await asyncio.gather(a, one("m1", sizes["mixed_prompts"][1], 4))
    # the batch: contexts from 200 to 16k, log-spaced, then decode-only steps
    lens = np.geomspace(*sizes["contexts"], batch).astype(int)
    # outputs long enough that the first to finish its prompt still
    # decodes when the last has: then the steps are decode-only
    await asyncio.gather(*[one(f"d{i}", int(n), sizes["batch_out"])
                           for i, n in enumerate(lens)])
    return done


def round_weights_to_fp8(params, jnp):
    """The control: every projection, MLP and expert matrix of the layers
    rounded to float8 (e4m3, scaled per output channel) and widened back,
    in the engine's copy only. Routers, norms and sinks stay."""
    def q(w):
        s = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2,
                    keepdims=True) / 448.0
        return ((w.astype(jnp.float32) / s).astype(jnp.float8_e4m3fn)
                .astype(jnp.float32) * s).astype(w.dtype)
    stacks = tuple(
        {k: (q(v) if k.startswith("w") else v) for k, v in st.items()}
        for st in params["stacks"])
    return {**params, "stacks": stacks}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="mimo-v25-ep16")
    ap.add_argument("--config-file", default=None,
                    help="a configuration file elsewhere (CPU rehearsal)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch", type=int, default=40,
                    help="rows of the decode-batch stage (40 contexts "
                         "log-spaced from 200 to 16k hold 144k tokens of a "
                         "163k-token pool; 64 would not fit)")
    ap.add_argument("--control", default="none",
                    choices=("none", "fp8-weights"))
    cli = ap.parse_args()
    with open(cli.config_file or os.path.join(
            HERE, "configs", cli.config + ".json")) as f:
        config = json.load(f)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import mimo_v2 as ref
    from dynamo_tpu.engine import engine as E
    from dynamo_tpu.engine import model as M
    from dynamo_tpu.models import get_model_config
    from dynamo_tpu.models.reference import mimo_v2_inputs
    from dynamo_tpu.runtime.config import place_compile_cache

    place_compile_cache()
    say = lambda *a: print(*a, file=sys.stderr, flush=True)  # noqa: E731
    cfg = get_model_config(config["arch"])
    args = engine_args(config["worker_flags"], cli.seed)
    args = args.replace(warmup_buckets=False)
    budget = args.max_num_batched_tokens
    #: the stages' sizes; a rehearsal configuration states smaller ones
    sizes = {"long_prompt": 12288, "mixed_prompts": [600, 3000],
             "contexts": [200, 16000], "batch_out": 96,
             **config.get("check_reference", {})}
    t0 = time.perf_counter()
    params = M.init_params(cfg, jax.random.key(cli.seed))
    if cli.control == "fp8-weights":
        params = round_weights_to_fp8(params, jnp)
    engine = E.AsyncJaxEngine(cfg, args, params=params)
    del params
    tap = Tap(engine, M, np)
    built_s = time.perf_counter() - t0
    say("built", round(built_s, 1), json.dumps(engine.build_facts))
    rng = np.random.default_rng(cli.seed)
    vocab_hi = min(19000, cfg.vocab_size)

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

    # free the pool and the engine's weights: the reference needs the room,
    # and its weights are the configuration's own (the control's are not)
    facts = engine.build_facts
    engine.k_cache = engine.v_cache = engine.params = None
    del engine, tap
    import gc
    gc.collect()
    jax.clear_caches()  # the step programs' executables live there too
    say("freed: bytes in use",
        (jax.devices()[0].memory_stats() or {}).get("bytes_in_use"))
    true_params = M.init_params(cfg, jax.random.key(cli.seed))
    K, n_moe = cfg.num_experts_per_tok, cfg.num_layers - 1
    seqs = {rid: np.asarray(p + o, np.int32)
            for rid, (_n, p, o) in done.items()}
    chosen = {rid: np.full((n_moe, len(t), K), -1, np.int32)
              for rid, t in seqs.items()}
    wanted = {rid: set() for rid in seqs}
    for rows, ids in steps:
        for rid, q_start, q_len, kv_len, _lg in rows:
            if rid in seqs and kv_len <= len(seqs[rid]):
                chosen[rid][:, kv_len - q_len:kv_len] = \
                    ids[:, q_start:q_start + q_len]
                wanted[rid].add(kv_len - 1)
    weights, hp = mimo_v2_inputs(cfg, true_params)
    del true_params  # the per-layer slices are copies: drop the stacks
    want, gaps, differ = {}, {}, {}
    # the weights are an operand: closed over, every compile would carry
    # 6.9 GB of constants through the host
    fwd = jax.jit(lambda w, toks, ids, rows: ref.forward(
        w, hp, toks, expert_ids=list(ids), rows=rows))
    n_rows = max(len(w) for w in wanted.values())
    for rid, toks in seqs.items():
        n = max(wanted[rid]) + 1 if wanted[rid] else 0
        if not n:
            continue
        # a causal model's answers do not see what follows: the sequence is
        # padded to a power of two (and the row list to one length), so the
        # reference compiles a handful of times, not once a request
        size = 1 << (n - 1).bit_length()
        rows = np.asarray(sorted(wanted[rid]), np.int32)
        lg, routed = fwd(
            weights, np.pad(toks[:n], (0, size - n)),
            np.pad(chosen[rid][:, :n], ((0, 0), (0, size - n), (0, 0))),
            np.pad(rows, (0, n_rows - len(rows)), mode="edge"))
        routed = {"choice": [c[:n] for c in routed["choice"]]}
        want[rid] = dict(zip(rows.tolist(), np.asarray(lg)))
        # the choices on their own: where the sets differ, how wide was the
        # float32 router's gap between its last pick and its first pass?
        worst, n_differ = 0.0, 0
        for li, choice in enumerate(routed["choice"]):
            choice = np.asarray(choice)
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
    out = {s: {"max_abs_diff": max(d), "median_abs_diff": sorted(d)[
        len(d) // 2], "rows": len(d),
        "tolerance": TOLERANCES.get(s, max(TOLERANCES.values())),
        "ok": max(d) <= TOLERANCES.get(s, max(TOLERANCES.values()))}
        for s, d in stages.items()}
    missing = sorted(set(TOLERANCES) - set(out))
    tokens = sum(len(c[0]) for c in chosen.values()) * n_moe
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
        "attention": facts["attention"],
        "seconds": {"build": built_s, "engine": ran_s, "reference": ref_s},
    }), flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
