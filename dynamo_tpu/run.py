"""``python -m dynamo_tpu.run in=http out=engine --model ...`` — one-command
serving, the reference's ``dynamo-run`` CLI analog (ref: launch/dynamo-run/
src/main.rs:30, opt.rs:7).

``in=``  http | text | batch | grpc (OpenAI server, REPL, JSONL batch, or
                                     KServe gRPC)
``out=`` engine | mocker | echo     (native JAX engine, simulator, or echo)

Everything runs in ONE process over the in-process control plane unless
DYN_CONTROL_PLANE points at a dynctl/etcd-style endpoint — handy for local
smoke tests and demos; production uses the separate frontend/worker mains.
"""

from __future__ import annotations

import argparse
import asyncio
import signal
import sys

from dynamo_tpu.runtime import DistributedRuntime
from dynamo_tpu.runtime.config import setup_logging


def parse_inout(argv):
    inp, out, rest = "http", "engine", []
    for a in argv:
        if a.startswith("in="):
            inp = a[3:]
        elif a.startswith("out="):
            out = a[4:]
        else:
            rest.append(a)
    if inp not in ("http", "text", "batch", "grpc"):
        raise SystemExit(f"unknown in={inp} (http|text|batch|grpc)")
    if out not in ("engine", "mocker", "echo"):
        raise SystemExit(f"unknown out={out} (engine|mocker|echo)")
    return inp, out, rest


async def start_worker(runtime, out: str, cli):
    if out == "mocker":
        from dynamo_tpu.mocker.engine import MockEngineArgs
        from dynamo_tpu.mocker.main import run_mocker

        margs = MockEngineArgs()
        if cli.vocab_size:
            if cli.vocab_size < 16:  # mocker samples ids in [10, vocab)
                raise SystemExit("--vocab-size must be >= 16")
            margs.vocab_size = cli.vocab_size
        (engine, *_), (handle, *_) = await run_mocker(runtime, cli.model, margs)
        return [handle]

    if out == "echo":
        from dynamo_tpu.llm.model_card import ModelDeploymentCard, register_llm
        from dynamo_tpu.protocols import FinishReason, LLMEngineOutput, PreprocessedRequest

        async def echo(request, ctx):
            req = PreprocessedRequest.from_wire(request)
            for t in req.token_ids:
                yield LLMEngineOutput(token_ids=[t]).to_wire()
            yield LLMEngineOutput(
                token_ids=[], finish_reason=FinishReason.STOP).to_wire()

        ep = runtime.namespace("dynamo").component("echo").endpoint("generate")
        handle = await ep.serve_endpoint(echo)
        card = ModelDeploymentCard(
            display_name=cli.model, kv_cache_block_size=16,
            eos_token_ids=[], tokenizer_ref=cli.model_path or "test")
        await register_llm(runtime, ep, card)
        return [handle]

    # native JAX engine (aggregated role)
    from dynamo_tpu.runtime.config import place_compile_cache
    place_compile_cache()
    from dynamo_tpu.engine.config import EngineArgs, ModelConfig
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.disagg.handlers import DecodeWorkerHandler
    from dynamo_tpu.llm.model_card import ModelDeploymentCard, register_llm

    # resolve EOS before the heavy param load so a bad checkpoint dir fails
    # in milliseconds (same fail-fast property as engine/main.py).
    # --model-path accepts a HF dir, a .gguf file, or an org/name hub id
    # (ref: hub.rs resolution order)
    tokenizer_ref = None
    if cli.model_path:
        from dynamo_tpu.llm.resolve import resolve_model
        try:
            resolved = resolve_model(cli.model_path)
            eos = resolved.eos_token_ids()
        except (FileNotFoundError, ValueError) as e:
            raise SystemExit(str(e))
        if not eos:  # a GGUF without an eos id would never stop generating
            raise SystemExit(
                f"{cli.model_path}: no EOS token id in the model metadata")
        cfg = resolved.config()
        params = resolved.load_params(cfg)
        tokenizer_ref = resolved.tokenizer_ref
    else:
        # random weights — a demo by construction; still make the toy
        # metadata impossible to mistake for a real deployment
        import logging
        logging.getLogger("dynamo.run").warning(
            "no --model-path: serving RANDOM weights with the toy test "
            "tokenizer and eos=[2] — demo/smoke only")
        eos = [2]
        from dynamo_tpu.models import get_model_config
        cfg = get_model_config(cli.arch)
        params = None
    if cli.quantization:  # validate the spec BEFORE the heavy load
        from dynamo_tpu.engine.quant import parse_spec
        parse_spec(cli.quantization)
    eargs = EngineArgs(multi_step_decode=cli.multi_step_decode,
                       speculative_tokens=cli.speculative_tokens,
                       use_pallas_attention=cli.use_pallas_attention,
                       quantization=cli.quantization,
                       kv_cache_dtype=cli.kv_cache_dtype)
    guided_vocab = None
    if tokenizer_ref:
        from dynamo_tpu.llm.tokenizer import load_guided_vocab
        guided_vocab = load_guided_vocab(tokenizer_ref)
    engine = AsyncJaxEngine(cfg, eargs, params=params,
                            guided_vocab=guided_vocab)
    mm_client = None
    mm_worker = None
    if cli.mm_encode:
        from dynamo_tpu.multimodal import EncodeWorker
        from dynamo_tpu.multimodal.encoder import ENCODE_COMPONENT
        mm_worker = await EncodeWorker(runtime).start()
        mm_ep = runtime.namespace("dynamo").component(
            ENCODE_COMPONENT).endpoint("encode")
        mm_client = await mm_ep.client().start()
    handler = DecodeWorkerHandler(engine, mm_client=mm_client)
    backend = runtime.namespace("dynamo").component("backend")
    ep = backend.endpoint("generate")
    handle = await ep.serve_endpoint(handler.generate)
    embed_handle = await backend.endpoint("embed").serve_endpoint(
        engine.embed_handler)

    async def clear_kv_handler(request, ctx):
        """Admin flush (ref: clear_kv_blocks.rs): device prefix cache +
        every KVBM tier."""
        engine.pool.clear()
        if engine.kvbm is not None:
            await asyncio.to_thread(engine.kvbm.clear)
        yield {"ok": True, "message": "KV cache cleared"}

    clear_handle = await backend.endpoint("clear_kv_blocks").serve_endpoint(
        clear_kv_handler)
    # session KV parking/restore endpoint (docs/sessions.md)
    from dynamo_tpu.sessions import SESSION_ENDPOINT, SessionKvHandler
    session_handle = await backend.endpoint(SESSION_ENDPOINT).serve_endpoint(
        SessionKvHandler(engine).generate)
    card = ModelDeploymentCard(
        display_name=cli.model, kv_cache_block_size=eargs.block_size,
        eos_token_ids=eos, tokenizer_ref=tokenizer_ref or "test")
    card.runtime_config.total_kv_blocks = engine.num_blocks
    await register_llm(runtime, ep, card)
    handles = [handle, embed_handle, clear_handle, session_handle]
    if mm_worker is not None:  # duck-typed: _stop_worker calls .stop()
        handles.append(mm_worker)
    return handles


async def run_text_repl(manager):
    """Interactive REPL (in=text): reads prompts, streams completions."""
    from dynamo_tpu.protocols.openai import parse_chat_request
    from dynamo_tpu.runtime.context import Context
    from dynamo_tpu.protocols import Annotated

    print("interactive chat — empty line or Ctrl-D to exit", flush=True)
    loop = asyncio.get_running_loop()
    while True:
        line = await loop.run_in_executor(None, _read_prompt)
        if not line:
            return
        model = manager.list_models()[0]
        req = parse_chat_request({
            "model": model, "stream": True,
            "messages": [{"role": "user", "content": line}],
        })
        served = manager.get(model)
        async for wire in served.pipeline.generate(req, Context()):
            ann = Annotated.from_wire(wire)
            if ann.event is not None or ann.data is None:
                continue
            for ch in ann.data.get("choices", []):
                delta = (ch.get("delta") or {}).get("content")
                if delta:
                    print(delta, end="", flush=True)
        print(flush=True)


async def _stop_worker(handles):
    for h in reversed(handles[1:]):  # auxiliary endpoints first, hard stop
        await h.stop(graceful=False)
    await handles[0].stop()


def _read_prompt():
    try:
        return input("> ").strip()
    except EOFError:
        return ""


async def run_batch(manager, cli):
    """``in=batch``: process a JSONL file of requests with bounded
    concurrency, writing one JSON response per line (ref:
    lib/llm/src/entrypoint/input.rs:32 batch mode).

    Each input line is either {"prompt": "..."} or {"messages": [...]},
    plus optional sampling fields (max_tokens, temperature, ...).
    """
    import json

    from dynamo_tpu.llm.pipeline import (aggregate_chat_stream,
                                         aggregate_completion_stream)
    from dynamo_tpu.protocols.openai import (parse_chat_request,
                                             parse_completion_request)
    from dynamo_tpu.runtime.context import Context

    if not cli.input_file:
        raise SystemExit("in=batch requires --input-file <requests.jsonl>")
    models = manager.list_models()
    if not models:
        raise SystemExit("no model registered (worker failed to start?)")
    model = models[0]
    lines: list = []
    with open(cli.input_file) as f:
        for ln, line in enumerate(f, 1):
            if not line.strip():
                continue
            try:
                lines.append(json.loads(line))
            except json.JSONDecodeError as e:
                # one bad line becomes one error entry, not a dead batch
                lines.append({"_parse_error": f"line {ln}: {e}"})

    sem = asyncio.Semaphore(cli.batch_concurrency)
    results: list = [None] * len(lines)

    async def one(i: int, body: dict):
        async with sem:
            if "_parse_error" in body:
                results[i] = {"error": {"message": body["_parse_error"]}}
                return
            body.setdefault("model", model)
            body["stream"] = True
            try:
                if "messages" in body:
                    req = parse_chat_request(body)
                    agg = aggregate_chat_stream
                else:
                    req = parse_completion_request(body)
                    agg = aggregate_completion_stream
                served = manager.get(req.model)
                results[i] = await agg(served.pipeline.generate(req, Context()))
            except Exception as e:
                results[i] = {"error": {"message": str(e)}}

    await asyncio.gather(*[one(i, body) for i, body in enumerate(lines)])

    out = open(cli.output_file, "w") if cli.output_file else sys.stdout
    try:
        for r in results:
            out.write(json.dumps(r) + "\n")
    finally:
        if cli.output_file:
            out.close()
    ok = sum(1 for r in results if r and "error" not in r)
    print(f"BATCH_DONE {ok}/{len(results)} ok", file=sys.stderr, flush=True)


async def amain():
    inp, out, rest = parse_inout(sys.argv[1:])
    ap = argparse.ArgumentParser(description="dynamo-tpu run")
    ap.add_argument("--model", default="dynamo-model")
    ap.add_argument("--model-path", default=None)
    ap.add_argument("--arch", default="tiny")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--router-mode", default="kv",
                    choices=["kv", "round_robin", "random"])
    ap.add_argument("--multi-step-decode", type=int, default=1)
    ap.add_argument("--speculative-tokens", type=int, default=0)
    ap.add_argument("--mm-encode", action="store_true",
                    help="start a stub multimodal encode worker and resolve "
                         "image_url content parts against it")
    ap.add_argument("--use-pallas-attention", action="store_true")
    ap.add_argument("--quantization", default=None,
                    help="on-device weight quantization: int8 | int8-gN | "
                         "int4-gN (weights stay quantized in HBM)")
    ap.add_argument("--kv-cache-dtype", default=None,
                    help="int8 = quantized paged KV cache (per-(slot,head) "
                         "scales, dequant in the attention kernels; GQA "
                         "and MLA latent caches both supported)")
    ap.add_argument("--vocab-size", type=int, default=0,
                    help="mocker vocab size (out=mocker only)")
    ap.add_argument("--input-file", default=None,
                    help="in=batch: JSONL file of requests")
    ap.add_argument("--output-file", default=None,
                    help="in=batch: JSONL output (default stdout)")
    ap.add_argument("--batch-concurrency", type=int, default=8)
    cli = ap.parse_args(rest)

    runtime = await DistributedRuntime.create()
    handles = await start_worker(runtime, out, cli)

    from dynamo_tpu.frontend.http import HttpService
    from dynamo_tpu.llm.discovery import ModelManager, ModelWatcher

    manager = ModelManager()
    watcher = await ModelWatcher(runtime, manager,
                                 router_mode=cli.router_mode).start()
    # wait for the model registration to flow through discovery
    for _ in range(100):
        if manager.list_models():
            break
        await asyncio.sleep(0.05)

    if inp in ("text", "batch"):
        try:
            if inp == "text":
                await run_text_repl(manager)
            else:
                await run_batch(manager, cli)
        finally:
            await watcher.stop()
            await _stop_worker(handles)
            await runtime.shutdown()
        return

    if inp == "grpc":
        from dynamo_tpu.frontend.grpc import KserveGrpcService

        service = KserveGrpcService(manager, port=cli.port)
        await service.start()
        print(f"READY grpc://localhost:{service.port}  model={cli.model}",
              flush=True)
    else:
        service = HttpService(manager, port=cli.port)
        await service.start()
        print(f"READY http://localhost:{service.port}/v1  model={cli.model}",
              flush=True)
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    await service.stop()
    await watcher.stop()
    await _stop_worker(handles)
    await runtime.shutdown()


def main():
    setup_logging()
    asyncio.run(amain())


if __name__ == "__main__":
    main()
