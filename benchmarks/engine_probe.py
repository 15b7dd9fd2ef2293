"""Direct-engine serving probe: AsyncJaxEngine without HTTP/frontend.

The r4 tool that located the serving-vs-kernel gap on real hardware:
reports engine init time and auto block sizing, runs a warmup (compile
set) then a concurrent closed-loop batch, and prints decode tok/s, TTFT
p50, and the engine's per-kind step-trace summary — the numbers to
compare against bench.py's kernel phase.

Usage: python -m benchmarks.engine_probe [--conc 32] [--isl 1024]
       [--osl 64] [--multi-step 16]
Runs on the device JAX gives it (JAX_PLATFORMS=cpu for a CPU run).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import time

import numpy as np


async def amain():
    ap = argparse.ArgumentParser(description="direct engine serving probe")
    ap.add_argument("--arch", default="llama3_1b")
    ap.add_argument("--conc", type=int, default=32)
    ap.add_argument("--isl", type=int, default=1024)
    ap.add_argument("--osl", type=int, default=64)
    ap.add_argument("--multi-step", type=int, default=16)
    ap.add_argument("--kv-cache-dtype", default=None)
    ap.add_argument("--quantization", default=None)
    cli = ap.parse_args()

    from dynamo_tpu.engine.config import EngineArgs
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.models import get_model_config
    from dynamo_tpu.protocols import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )

    cfg = get_model_config(cli.arch)
    args = EngineArgs(
        block_size=16, max_num_seqs=max(64, cli.conc),
        max_num_batched_tokens=2048, max_model_len=cli.isl + cli.osl + 64,
        multi_step_decode=cli.multi_step, use_pallas_attention=True,
        quantization=cli.quantization, kv_cache_dtype=cli.kv_cache_dtype,
        prefill_buckets=(1024, 2048), decode_batch_buckets=(32, 64))
    t0 = time.perf_counter()
    eng = AsyncJaxEngine(cfg, args)
    out = {"init_s": round(time.perf_counter() - t0, 1),
           "num_blocks": eng.num_blocks,
           "kv_capacity_tokens": eng.num_blocks * args.block_size}
    print(json.dumps(out), flush=True)

    rng = np.random.default_rng(0)

    async def run_one(isl, osl, timings):
        req = PreprocessedRequest(
            model="probe",
            token_ids=rng.integers(1, cfg.vocab_size, isl).tolist(),
            sampling_options=SamplingOptions(temperature=0.0),
            stop_conditions=StopConditions(max_tokens=osl, ignore_eos=True))
        t0 = time.perf_counter()
        first, n = None, 0
        async for o in eng.generate(req):
            if first is None:
                first = time.perf_counter() - t0
            n += len(o.token_ids or [])
            if o.finish_reason is not None:
                break
        timings.append((first, n))

    tm = []
    t0 = time.perf_counter()
    await asyncio.gather(*[run_one(cli.isl, 16, tm)
                           for _ in range(cli.conc)])
    print(json.dumps({"warmup_s": round(time.perf_counter() - t0, 1)}),
          flush=True)

    tm = []
    t0 = time.perf_counter()
    await asyncio.gather(*[run_one(cli.isl, cli.osl, tm)
                           for _ in range(cli.conc)])
    wall = time.perf_counter() - t0
    ttfts = sorted(f for f, _ in tm if f is not None)
    out = {
        "decode_tok_s": round(sum(n for _, n in tm) / wall, 1),
        "ttft_p50_ms": round(1000 * ttfts[len(ttfts) // 2], 1),
        "wall_s": round(wall, 1),
        "workload": f"ISL={cli.isl},OSL={cli.osl},conc={cli.conc}",
        "step_trace": eng.step_trace_summary(),
    }
    await eng.close()
    print(json.dumps(out))


if __name__ == "__main__":
    asyncio.run(amain())
