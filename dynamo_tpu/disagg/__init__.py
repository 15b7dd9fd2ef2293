"""Disaggregated prefill/decode serving.

The reference's core feature (ref: docs/architecture/disagg_serving.md:11-120,
components/backends/vllm/src/dynamo/vllm/handlers.py:89-250): decode workers
conditionally delegate prefill to a dedicated prefill fleet, and the computed
KV blocks move prefill→decode.

TPU-native transfer: no RDMA exists on TPU-VMs, so blocks ship host-staged —
prefill gathers its pages (engine/cache.py KvPages.gather, one device→host
DMA), the bundle rides the existing TCP response plane back to the decode
worker, which scatters it into its own paged cache (host→device). Intra-pod
(same process/mesh) hand-off skips the host round-trip via device-to-device
scatter. The reference's pull-based NIXL metadata handshake becomes a
push-with-the-response — same observable contract (decode-first flow,
max_tokens=1 prefill request, kv_transfer_params in the response).
"""

from dynamo_tpu.disagg.protocols import DisaggConfig, KvBundle
from dynamo_tpu.disagg.handlers import DecodeWorkerHandler, PrefillWorkerHandler

__all__ = ["DisaggConfig", "KvBundle", "DecodeWorkerHandler", "PrefillWorkerHandler"]
