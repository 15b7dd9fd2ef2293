"""Can what the device's memory held BEFORE a step reach the state a padded
prompt chunk leaves? A chip-side check beside ``check_reference_granite4.py``.

The held-experts layer's launches leave rows unwritten (the tiles a launch
skipped, ops/grouped_matmul.py), the ragged attention kernel writes no row
of a padding token, and 0 x NaN is NaN: ONE non-finite value in a padding
token's row of the stream, multiplied by a zero mask where it should have
been selected away, poisons the state of every chunk row of the step, and
every step after it. Interpret mode on the CPU hands out zeroed memory and
cannot show it (tests/poisoned_launches.py imitates it), and the benchmark's
``correct`` probes 200 tokens. PR 44's first build of the combine kernel
failed exactly here (PERF.md section 6): 600 tokens in the 1,024-token
program.

What runs: Granite-4.0-H-Small's widths at a reduced depth (``--pattern``,
"m" a Mamba-2 layer, "a" an attention layer; both layer groups by default),
random weights from ``--seed``, the engine's own jitted ragged step programs:
for each of ``--cases`` (``<program's tokens>:<valid tokens>``) ONE chunk of
the valid tokens in that program, then ``--decode`` decode steps from the
state it left — twice: once after the free device memory was filled with
NaN, once after it was filled with zeros. Passes (exit 0) when every logit
and every state element is finite and the two runs agree bit for bit, in
every case. One JSON line last.

  chiprun -- python3 chipbench/check_padded_chunk.py            # on the chip
  JAX_PLATFORMS=cpu python3 chipbench/check_padded_chunk.py --tiny   # rehearsal

``--tiny``: test-size widths on the CPU, where memory cannot be filled: the
rehearsal checks the plumbing, not the property.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

BS, NB, SLOTS, SLOT = 16, 2048, 8, 1


def fill_free_memory(value) -> int:
    """Fill most of the device's free memory with ``value`` and free it
    again: what a step finds in a buffer it does not write. Returns the
    bytes filled (0 where the backend does not say what is free)."""
    import jax
    import jax.numpy as jnp

    stats = jax.devices()[0].memory_stats()
    if not stats or "bytes_limit" not in stats:
        return 0
    free = stats["bytes_limit"] - stats["bytes_in_use"]
    piece, bufs = 2 ** 28, []
    try:
        for _ in range(int(free * 0.9) // (2 * piece)):
            bufs.append(jnp.full((piece,), value, jnp.bfloat16))
        jax.block_until_ready(bufs)
    except Exception as e:  # the allocator said enough
        print("fill stopped:", type(e).__name__, file=sys.stderr)
    filled = 2 * piece * len(bufs)
    del bufs
    return filled


def operands(M, args, cfg, T, row, tokens):
    """The ragged step's operands for one row ``(start, length)`` of the
    sequence ``tokens``, as engine._run_ragged lays them out for a state
    model (tests/test_granite4_h.py ``_operands``)."""
    import numpy as np
    import jax.numpy as jnp

    start, n = row
    R, W = args.ragged_rows(T), args.max_blocks_per_seq
    C, S_C = M.ragged_grid_shape(T)
    ints5 = np.zeros((5, T), np.int32)
    ints5[3] = C
    rows4 = np.zeros((R, 4), np.int32)
    rows4[:, 3] = SLOTS  # the dump slot
    grid_rows = np.zeros((C,), np.int32)
    bt = np.zeros((R, W), np.int32)
    table = list(range(1, 2 + (start + n) // BS))
    ints5[0, :n] = tokens[start:start + n]
    ints5[1, :n] = np.arange(start, start + n)
    ints5[2, :n] = [table[p // BS] * BS + p % BS
                    for p in range(start, start + n)]
    if n > 1:
        for tile, off in enumerate(range(0, n, S_C)):
            width = min(S_C, n - off)
            ints5[3, off:off + width] = tile
            ints5[4, off:off + width] = np.arange(width)
    rows4[0] = (0, n, start + n, SLOT)
    bt[0, :len(table)] = table
    return tuple(jnp.asarray(a) for a in (ints5, rows4, grid_rows, bt))


def run_once(M, args, cfg, params, fns, ns, T, valid, tokens, fill):
    """A chunk of ``valid`` tokens in the ``T``-token program and the decode
    steps, each after ``fill`` was spread over the free memory: (logits a
    step [steps, V], (conv, ssm) on the host, bytes filled)."""
    import numpy as np
    from dynamo_tpu.engine.cache import allocate_device_cache, allocate_state

    kc, vc = allocate_device_cache(cfg, NB, BS)
    state = allocate_state(cfg, SLOTS)
    plan = [(T, True, (0, valid))] + [
        (8, False, (valid + i, 1)) for i in range(ns.decode)]
    got = []
    for width, chunks, row in plan:
        ops = operands(M, args, cfg, width, row, tokens)
        filled = fill_free_memory(fill)
        logits, kc, vc, _, *rest = fns[chunks](params, *ops, kc, vc, state)
        state = rest[-1]
        got.append(np.asarray(logits[0], np.float32))
    return np.stack(got), tuple(np.asarray(a, np.float32) for a in state), (
        filled)


def check_case(M, args, cfg, params, fns, ns, T, valid):
    """One case's record: ``finite`` for the logits a step and for the conv
    and ssm state by layer, ``nan_fill_equals_zero_fill``, ``ok``."""
    import numpy as np

    assert 1 < valid <= T, (valid, T)
    tokens = np.random.default_rng(ns.seed).integers(
        10, min(30000, cfg.vocab_size), valid + ns.decode)
    (lg_n, st_n, filled), (lg_z, st_z, _) = (
        run_once(M, args, cfg, params, fns, ns, T, valid, tokens, fill)
        for fill in (float("nan"), 0.0))
    finite = {
        "logits": [bool(np.isfinite(a).all()) for a in lg_n],
        "conv_by_layer": np.isfinite(st_n[0]).all(axis=(1, 2)).tolist(),
        "ssm_by_layer": np.isfinite(st_n[1]).reshape(
            st_n[1].shape[0], -1).all(1).tolist()}
    same = bool((lg_n == lg_z).all()) and all(
        bool((a == b).all()) for a, b in zip(st_n, st_z))
    return {"tokens": T, "valid": valid, "filled_bytes": filled,
            "finite": finite, "nan_fill_equals_zero_fill": same,
            "logit_sums": [float(a.sum()) for a in lg_z],
            "ok": same and all(all(v) for v in finite.values())}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--pattern", default="mmam")
    ap.add_argument("--cases", default="1024:600,2048:1500",
                    help="<the step program's token bucket>:<tokens of the "
                         "chunk; the rest is padding>, comma-separated")
    ap.add_argument("--decode", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true")
    ns = ap.parse_args()
    t0 = time.perf_counter()
    if not ns.tiny:
        with open(os.path.join(ROOT, "chipbench", "configs",
                               "granite4-h-small-ep2.json")) as f:
            env = json.load(f)["worker_env"]
        os.environ.setdefault("LIBTPU_INIT_ARGS", env["LIBTPU_INIT_ARGS"])

    import jax
    from dynamo_tpu import models
    from dynamo_tpu.engine import model as M
    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.runtime.config import place_compile_cache

    place_compile_cache()
    if ns.tiny:
        cfg = models.granite4_tiny(pattern=ns.pattern)
    else:
        cfg = models._granite4_h(vocab_size=50176, pattern=ns.pattern,
                                 experts_held=(0, 36), init_out_gain=64.0)
    args = EngineArgs(block_size=BS, num_blocks=NB, max_num_seqs=64,
                      max_num_batched_tokens=2048, max_model_len=8192)
    params = M.init_params(cfg, jax.random.key(ns.seed % (2 ** 31)))
    fns = {c: M.make_ragged_step_fn(cfg, BS, None, use_pallas=not ns.tiny,
                                    chunks=c) for c in (True, False)}
    cases = [check_case(M, args, cfg, params, fns, ns, *map(int, c.split(":")))
             for c in ns.cases.split(",")]
    ok = all(c["ok"] for c in cases)
    print(json.dumps({
        "check": "padded_chunk", "ok": ok,
        "device": jax.devices()[0].device_kind, "pattern": ns.pattern,
        "decode": ns.decode, "seed": ns.seed,
        "finite": all(all(v) for c in cases for v in c["finite"].values()),
        "nan_fill_equals_zero_fill": all(
            c["nan_fill_equals_zero_fill"] for c in cases),
        "cases": cases, "seconds": round(time.perf_counter() - t0, 1)}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
