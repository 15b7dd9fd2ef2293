"""KVBM — multi-tier KV block manager.

Rebuild of the reference's block manager (ref: lib/llm/src/block_manager.rs:
62-75 — CacheLevel G1 device / G2 host / G3 disk / G4 remote; offload on
registration, onboard on cache miss, ref: block_manager/offload.rs:4-34).

TPU mapping: G1 is the engine's paged HBM cache (engine/cache.py BlockPool);
G2 is TPU-VM host DRAM (generous on TPU-VMs — it doubles as the disagg
staging buffer); G3 is local NVMe. Transfers ride engine/cache.py's KvPages
(ops/block_copy gather/scatter, one DMA per bundle) instead of CUDA copy
streams; there is no NIXL — cross-host movement goes through the response plane (disagg) or
the object store.
"""

from dynamo_tpu.kvbm.tiers import DiskTier, HostTier
from dynamo_tpu.kvbm.manager import KvbmManager

__all__ = ["DiskTier", "HostTier", "KvbmManager"]
