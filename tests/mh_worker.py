"""Rank worker for the multi-host lockstep test (tests/test_multihost.py).

Usage: python tests/mh_worker.py <rank> <coordinator> <plane_addr> [world]

``world`` (default 2) JAX processes × 2 virtual CPU devices form one GLOBAL
tp=2·world mesh. Rank 0 runs the real engine (greedy generate) broadcasting
each step's host inputs over DIRECT TCP to every follower; ranks >= 1
replay them through identical jitted functions. All ranks finish by
computing a jitted GLOBAL checksum of their k_cache — bit-identical inputs
must leave bit-identical global cache state on every rank.
"""

import asyncio
import json
import os
import sys


def _script_env():
    """ONLY for subprocess execution — mutating XLA_FLAGS inside a pytest
    process would poison any later jax backend re-initialization."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax

    jax.config.update("jax_platforms", "cpu")


def mh_model_cfg(world: int = 2):
    """Shared by worker and test: heads divisible by tp=2·world."""
    from dynamo_tpu.engine.config import ModelConfig

    tp = 2 * world
    # vocab must shard over tp (lm-head partition); 256 kept for world=2
    # so the single-process reference tokens stay comparable
    return ModelConfig(
        vocab_size=256 if tp == 4 else 48 * tp,
        hidden_size=16 * tp, intermediate_size=32 * tp,
        num_layers=2, num_heads=tp, num_kv_heads=tp, dtype="float32",
        head_dim=16, max_position_embeddings=512)


def mh_engine_args():
    from dynamo_tpu.engine.config import EngineArgs

    return EngineArgs(block_size=4, num_blocks=64, max_num_seqs=2,
                      max_num_batched_tokens=32, max_model_len=64,
                      prefill_buckets=(16,), decode_batch_buckets=(1,))


async def wait_kv(plane, key, timeout=240.0):
    for _ in range(int(timeout / 0.05)):
        v = await plane.kv_get(key)
        if v is not None:
            return v
        await asyncio.sleep(0.05)
    raise TimeoutError(key)


async def main():
    import jax

    rank, coord, plane_addr = int(sys.argv[1]), sys.argv[2], sys.argv[3]
    world = int(sys.argv[4]) if len(sys.argv) > 4 else 2

    from dynamo_tpu.parallel import MeshConfig
    from dynamo_tpu.parallel.multihost import (
        StepBroadcaster, StepFollower, init_multihost, make_global_mesh,
    )

    r, w = init_multihost(coord, world, rank)
    assert (r, w) == (rank, world)
    mesh = make_global_mesh(MeshConfig(dp=1, sp=1, tp=2 * world))

    from dynamo_tpu.engine.config import EngineArgs, ModelConfig
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.protocols import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )
    from dynamo_tpu.runtime.control_plane import RemoteControlPlane

    cfg = mh_model_cfg(world)
    args = mh_engine_args()
    plane = await RemoteControlPlane(plane_addr).connect()
    eng = AsyncJaxEngine(cfg, args, mesh=mesh)
    assert eng._multihost, "mesh must span both processes"

    if rank == 0:
        bcast = StepBroadcaster(plane)
        eng.broadcast_cb = bcast
        for fr in range(1, world):
            await wait_kv(plane, f"mh/ready{fr}")
        # direct one-to-MANY streams, one per follower
        await bcast.connect(expect=world - 1)

        req = PreprocessedRequest(
            model="t", token_ids=list(range(1, 13)),
            stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0))
        toks = []
        async for out in eng.generate(req):
            toks.extend(out.token_ids)
        print("TOKENS " + json.dumps(toks), flush=True)
        # /v1/embeddings on a multi-host fleet: the embed forward contains
        # global-mesh collectives, so without broadcast+replay (the r3
        # advisor's medium finding) this call wedges rank 0 forever
        vecs = await eng.embed([[1, 2, 3, 4], [5, 6]])
        print(f"EMBDIM {len(vecs[0])}", flush=True)
        await bcast.stop()
        await plane.kv_put("mh/nsteps", str(bcast.steps_sent).encode())
        for fr in range(1, world):
            await wait_kv(plane, f"mh/replayed{fr}")
    else:
        follower = await StepFollower(eng, plane).start()
        await plane.kv_put(f"mh/ready{rank}", b"1")
        nsteps = int(await wait_kv(plane, "mh/nsteps"))
        for _ in range(4800):  # 240s — 3 jax procs contend on a 1-core host
            if follower.steps_replayed >= nsteps:
                break
            await asyncio.sleep(0.05)
        assert follower.steps_replayed == nsteps, \
            f"replayed {follower.steps_replayed}/{nsteps}"
        print(f"REPLAYED {follower.steps_replayed}", flush=True)
        await plane.kv_put(f"mh/replayed{rank}", b"1")
        await follower.stop()

    # BOTH ranks issue the same global reduction — program order aligned
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    cks = jax.jit(lambda a: jnp.sum(jnp.abs(a.astype(jnp.float32))),
                  out_shardings=NamedSharding(mesh, P()))(eng.kv.k)
    print(f"CKSUM {float(cks):.6f}", flush=True)
    await eng.close()
    await plane.close()


if __name__ == "__main__":
    _script_env()
    asyncio.run(main())
