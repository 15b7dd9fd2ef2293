"""The native TPU engine: JAX/XLA/Pallas token generation.

This is the TPU build's equivalent of the reference's delegated GPU engines
(vLLM/SGLang/TRT-LLM — ref: components/backends/*): a paged-KV, continuously
batched, pjit-sharded inference engine that plugs into the distributed runtime
exactly like the reference's Python backends plug into theirs (register_llm +
serve_endpoint + KV events + ForwardPassMetrics).

Layout:
- config.py    — ModelConfig / EngineArgs
- model.py     — llama-family forward pass over a paged KV cache (scan layers)
- sampling.py  — on-device sampling (greedy / temperature / top-k / top-p)
- cache.py     — device cache allocation and its one holder (KvPages) +
                host-side block pool & prefix cache
- scheduler.py — continuous batching: admission, chunked prefill, decode batch
- engine.py    — AsyncJaxEngine: the async generate() loop + KV events
- loader.py    — HF checkpoint loading / random init
"""

from dynamo_tpu.engine.config import EngineArgs, ModelConfig  # noqa: F401
