"""The one holder of a worker's KV pages and state slots
(``engine/cache.py:KvPages``), and the arrows around it: ``ops/`` knows
arrays, ``engine/cache.py`` knows ``ops/``, ``engine/engine.py`` knows
``engine/cache.py``."""

import ast
import dataclasses
import pathlib
import re

import jax
import numpy as np
import pytest

from dynamo_tpu.disagg.protocols import KvBundle
from dynamo_tpu.engine.cache import (
    KvPages, allocate_device_cache, allocate_state,
)
from dynamo_tpu.engine.config import EngineArgs, ModelConfig
from dynamo_tpu.engine.engine import _NEEDS_OF_THE_CACHE, AsyncJaxEngine

BS, NB = 4, 8
L, KV, HD = 2, 2, 16  # ModelConfig.tiny()'s depth, KV heads and head width
PACKED = BS * KV * (HD + 4)


def _holder(dtype: str, fill: bool) -> KvPages:
    """Pages of ``ModelConfig.tiny()``: bf16 values or int8 (q, s) pairs,
    zeros or — ``fill`` — random in every slot."""
    cfg = dataclasses.replace(ModelConfig.tiny(), dtype="bfloat16")
    k, v = allocate_device_cache(cfg, NB, BS,
                                 dtype="int8" if dtype == "int8" else None)
    if fill:
        rng = np.random.default_rng(3)

        def rand(a):
            if a.dtype == np.int8:
                return rng.integers(-127, 128, a.shape).astype(np.int8)
            return rng.standard_normal(a.shape).astype(a.dtype)

        k, v = jax.tree.map(lambda a: jax.numpy.asarray(rand(a)), (k, v))
    return KvPages(cfg, k, v, BS, NB)


def _leaves(pages: KvPages) -> list:
    return [np.asarray(a) for a in jax.tree.leaves((pages.k, pages.v))]


@pytest.mark.parametrize("sliced", [False, True],
                         ids=["whole depth", "a layer slice at a time"])
@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_gather_to_host_scatter_is_the_identity(dtype, sliced):
    """Blocks leave one worker's pages for the host and enter another's:
    what arrives is bit for bit what left, whole or a layer range at a time
    (``start_layer``), and nothing beside the named blocks is written."""
    src, dst = _holder(dtype, fill=True), _holder(dtype, fill=False)
    ids, new_ids = [2, 5, 6], [1, 3, 7]
    kb, vb = src.gather(ids)
    assert kb.shape[1] == vb.shape[1] == 4  # padded to a power of two
    k, v = src.to_host(kb, vb, len(ids))
    assert k.shape == (L, 3) + src.host_block_shape()[1:]
    assert k.flags.c_contiguous and v.flags.c_contiguous
    assert (k.dtype == np.uint8) == (dtype == "int8")
    if sliced:
        assert dst.layer_ranges(2) == [(0, 1), (1, 2)]
        for g0, g1 in dst.layer_ranges(2):
            dst.scatter(new_ids, k[g0:g1], v[g0:g1], start_layer=g0)
    else:
        dst.scatter(new_ids, k, v)
    k2, v2 = dst.to_host(*dst.gather(new_ids), len(new_ids))
    np.testing.assert_array_equal(k2, k)
    np.testing.assert_array_equal(v2, v)
    # block b of every leaf: [L, b * BS:(b + 1) * BS, ...]
    for got, want in zip(_leaves(dst), _leaves(src)):
        for b in range(NB):
            rows = got[:, b * BS:(b + 1) * BS]
            if b in new_ids:
                np.testing.assert_array_equal(
                    rows, want[:, ids[new_ids.index(b)] * BS:][:, :BS])
            else:
                assert not rows.any()
    # and back where it came from: the source is what it was
    before = _leaves(src)
    src.scatter(ids, k, v)
    for got, want in zip(_leaves(src), before):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_a_block_costs_the_host_what_to_host_returns(dtype):
    """The budget a swap reserves a block is the bytes the host pair of one
    block holds; a one-group cache takes the same on the device."""
    pages = _holder(dtype, fill=False)
    k, v = pages.to_host(*pages.gather([3]), 1)
    assert pages.host_block_nbytes == k.nbytes + v.nbytes
    assert pages.device_block_nbytes == pages.host_block_nbytes
    assert pages.nbytes == NB * pages.device_block_nbytes
    per = PACKED if dtype == "int8" else BS * KV * HD * 2
    assert pages.host_block_nbytes == 2 * L * per
    one = pages.to_host_blocks(*pages.gather([3, 4, 6]), 3)
    assert len(one) == 3 and all(
        a.shape == pages.host_block_shape() and a.flags.c_contiguous
        for pair in one for a in pair)


def _bundle(shape, dtype=np.float32, **kw):
    a = np.zeros(shape, dtype)
    return KvBundle(k=a, v=a, num_tokens=0, **{"block_size": BS, **kw})


@pytest.mark.parametrize("bundle,ok", [
    (_bundle((L, 3, BS, KV, HD)), True),
    (_bundle((L, 3, PACKED), np.uint8), True),
    (_bundle((1, 3, BS, KV, HD), start_layer=1, total_layers=L), True),
    (_bundle((1, 3, PACKED), np.uint8, start_layer=0, total_layers=L), True),
    (_bundle((L, 3, 8, KV, HD), block_size=8), False),
    (_bundle((L + 1, 3, BS, KV, HD)), False),
    (_bundle((1, 3, BS, KV, HD), start_layer=0, total_layers=L + 1), False),
    (_bundle((2, 3, BS, KV, HD), start_layer=1, total_layers=L), False),
    (_bundle((L, 3, BS, KV, HD // 2)), False),
    (_bundle((L, 3, PACKED + 1), np.uint8), False),
    (_bundle((L, 3, PACKED), np.int8), False),
], ids=["values", "packed bytes", "a layer slice", "a packed layer slice",
        "another block size", "another depth", "a slice of a deeper cache",
        "a slice past the end", "another head width",
        "a packed width off by one", "packed, and not bytes"])
def test_accepts_what_scatter_can_place(bundle, ok):
    """Either layout into either kind of pages (scatter converts); never
    another block size, depth, head count or width."""
    for dtype in ("bf16", "int8"):
        assert _holder(dtype, fill=False).accepts(bundle) is ok


def _tiny(kind: str):
    from dynamo_tpu.models import granite4_tiny, mimo_tiny

    return {"two groups": mimo_tiny, "state": granite4_tiny}[kind]()


@pytest.mark.parametrize("kind,who", [
    ("two groups", r"a cache of 2 groups \(one per layer kind\)"),
    ("state", r"a model with recurrent state \(5 mamba2 layers\)"),
])
def test_a_cache_that_moves_nothing_is_held_and_says_so(kind, who):
    """Two groups of pages (a tuple a stream) and pages with state slots
    beside them are held like any other — arrays, bytes, the state — and
    every way a block could leave or enter refuses in the table's words."""
    cfg = _tiny(kind)
    state = allocate_state(cfg, 3)
    pages = KvPages(cfg, *allocate_device_cache(cfg, NB, BS), BS, NB, state)
    assert pages.groups == len(cfg.kv_cache_spec) == (
        2 if kind == "two groups" else 1)
    assert (pages.state is None) == (kind == "two groups")
    assert pages.state_nbytes == sum(a.nbytes for a in state or ())
    assert pages.nbytes == NB * pages.device_block_nbytes > 0
    assert pages.host_block_nbytes == 0 and pages.dims is None
    assert not pages.quant and len(pages.lacks) == 1
    for move in (lambda: pages.gather([1, 2]),
                 lambda: pages.scatter([1], None, None),
                 lambda: pages.host_block_shape()):
        with pytest.raises(NotImplementedError,
                           match=who + " does not support: "):
            move()
    assert not pages.accepts(_bundle((L, 3, BS, KV, HD)))


_ARGS = dict(block_size=4, num_blocks=64, max_num_seqs=3,
             max_num_batched_tokens=32, max_model_len=64, preempt_swap=False)


#: the arguments that turn each feature of the table on (None: a mesh)
_TURNS_ON = {
    "--kvbm-host-gb / KVBM tiers": dict(kvbm_host_bytes=1 << 20),
    "preempt-to-swap (pass --no-preempt-swap: a preempted sequence is then "
    "recomputed)": dict(preempt_swap=True),
    "int8 KV pages": dict(kv_cache_dtype="int8"),
    "a device mesh or pipeline stages": None,
    "multi-step decode": dict(multi_step_decode=4),
    "speculative decoding": dict(speculative_tokens=2),
}
_FEATURES = [(what, needs) for what, needs, on in _NEEDS_OF_THE_CACHE
             if on is not None]
_ENTRY_POINTS = [what for what, _needs, on in _NEEDS_OF_THE_CACHE
                 if on is None]
_LACKS = {"two groups": "one group", "state": "no state"}


def test_every_row_of_the_table_has_a_case_below():
    assert [what for what, _needs in _FEATURES] == list(_TURNS_ON)
    assert len(_ENTRY_POINTS) == 8
    assert all(needs == ("no state",) for what, needs, on
               in _NEEDS_OF_THE_CACHE if on is None)


@pytest.mark.parametrize("kind,what", [
    (kind, what) for kind, need in _LACKS.items()
    for what, needs in _FEATURES if need in needs])
def test_the_build_refuses_each_feature_whose_needs_the_cache_lacks(
        kind, what):
    """One table, asked once at build: a feature that is on and needs what
    the cache lacks is refused by name."""
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    kw = _TURNS_ON[what]
    mesh = make_mesh(MeshConfig(tp=2)) if kw is None else None
    args = EngineArgs(**{**_ARGS, **(kw or {"tp_size": 2})})
    with pytest.raises(ValueError, match=".* does not support: .*"
                       + re.escape(what)):
        AsyncJaxEngine(_tiny(kind), args, mesh=mesh)


@pytest.fixture(scope="module")
def state_engine():
    return AsyncJaxEngine(_tiny("state"), EngineArgs(**_ARGS))


@pytest.mark.parametrize("name", _ENTRY_POINTS)
def test_a_state_model_refuses_each_entry_point_when_called(
        name, state_engine):
    with pytest.raises(NotImplementedError,
                       match=name + ": a model with recurrent state cannot"):
        getattr(state_engine, name)(None)


def test_a_state_model_is_held_with_its_state(state_engine):
    eng, cfg = state_engine, _tiny("state")
    want = allocate_state(cfg, 3)
    assert [a.shape for a in eng.kv.state] == [a.shape for a in want]
    assert eng.kv.state_nbytes == eng.build_facts["state_bytes"] > 0
    assert eng.kv.nbytes == eng.build_facts["kv_bytes"]
    assert eng.kv.num_blocks == eng.num_blocks == 64


def test_the_harness_frees_the_chip_through_three_names_of_the_engine():
    """``chipbench/check_reference*.py`` assign None to ``k_cache``,
    ``v_cache`` and ``state``: they read and write the holder."""
    eng = AsyncJaxEngine(ModelConfig.tiny(), EngineArgs(**_ARGS))
    assert eng.k_cache is eng.kv.k and eng.v_cache is eng.kv.v
    assert eng.state is eng.kv.state is None
    eng.k_cache = eng.v_cache = eng.state = None
    assert eng.kv.k is None and eng.kv.v is None


def _imports(path: pathlib.Path) -> set:
    """Every module a file imports, at any depth of nesting."""
    out = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            out.add(node.module)
            out.update(f"{node.module}.{a.name}" for a in node.names)
    return out


_PKG = pathlib.Path(__file__).resolve().parent.parent / "dynamo_tpu"


@pytest.mark.parametrize("files,banned,allowed", [
    (sorted(_PKG.glob("ops/*.py")), "dynamo_tpu.engine",
     ("dynamo_tpu.engine.config",)),
    ([_PKG / "engine/model.py", *sorted(_PKG.glob("parallel/*.py"))],
     "dynamo_tpu.engine.cache", ()),
    ([_PKG / "engine/engine.py"], "dynamo_tpu.ops.block_copy", ()),
    ([_PKG / "engine/engine.py"], "dynamo_tpu.ops.kv_pages", ()),
], ids=["ops knows arrays", "readers of pages ask ops",
        "the engine copies no block", "the engine knows no page format"])
def test_the_arrows_point_down(files, banned, allowed):
    assert len(files) >= 1
    for path in files:
        bad = sorted(m for m in _imports(path)
                     if (m == banned or m.startswith(banned + "."))
                     and not any(m == a or m.startswith(a + ".")
                                 for a in allowed))
        assert not bad, f"{path.name} imports {bad}"
