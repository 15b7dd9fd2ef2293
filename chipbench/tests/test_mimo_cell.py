"""The MiMo-V2.5 configuration file, the cost functions of its new kernel
and the readers of its per-layer metrics (synthetic sources)."""

import importlib.util
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def load(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"),
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "mimo-v25-ep16.json")) as f:
        return json.load(f)


def test_configuration_states_its_cut(config):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == "mimo-v25-ep16"][0]
    assert config["reduced"] == entry["reduced"]
    assert config["source"] == entry["source"]
    assert config["published"]["num_hidden_layers"] == 48
    assert config["published"]["n_routed_experts"] == 256
    assert config["published"]["vocab_size"] == 152576
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (7, 16, 19072)
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert "16 chips share each layer" in config["deployment"]
    for key in ("weights", "kv_cache", "k_page_width", "rope_pairing"):
        assert config["assumed"][key]
    # floors of the model-configs guide: a whole period and >= 4 layers
    # after the dense one, >= 8 experts, >= 1/8 of the vocabulary
    assert config["hybrid_layer_pattern"][1:] == [1, 1, 1, 1, 1, 0]


def test_every_published_number_is_as_published_or_listed(config):
    rows = os.path.join("/opt/skills/guides/model-configs",
                        "architectures.jsonl")
    if not os.path.exists(rows):
        pytest.skip("no catalog here")
    with open(rows) as f:
        row = [json.loads(ln) for ln in f if '"MiMo-V2.5"' in ln][0]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value, key
        else:
            assert config[key] == value, key


def test_weights_bytes_is_what_the_preset_builds(config):
    """Computed from shapes on the CPU, nothing allocated."""
    jax = pytest.importorskip("jax")
    import numpy as np

    import sys
    sys.path.insert(0, ROOT)
    from dynamo_tpu.engine import model as M
    from dynamo_tpu.models import get_model_config

    cfg = get_model_config(config["arch"])
    shapes = jax.eval_shape(lambda: M.init_params(cfg, jax.random.key(0)))
    nbytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                 for x in jax.tree.leaves(shapes))
    assert nbytes == config["expect"]["weights_bytes"] == \
        config["sizing"]["weights_bytes"]
    assert 0.25 * 16e9 < nbytes < 0.5 * 16e9


def test_grouped_matmul_costs_on_a_hand_counted_case():
    import moe_costs as C

    # 3 pairs, 2 experts touched, hidden 4, ffn 2, 2-byte elements: one
    # projection is 3 rows x (4 x 2) multiply-adds x 2 = 48
    assert C.launch_ops(3, 4, 2) == 48
    # gate / up read 2 matrices of 8 elements + 3 rows of 4 = 28 elements;
    # down reads the same matrices' worth + 3 rows of 2 = 22 elements
    assert C.launch_bytes(3, 2, 4, 2, "gate") == 56
    assert C.launch_bytes(3, 2, 4, 2, "down") == 44
    assert C.roofline_seconds(48, 56, 24.0, 56.0) == 2.0   # compute
    assert C.roofline_seconds(48, 56, 480.0, 28.0) == 2.0  # memory


class Src:
    """What a reader is handed, as far as these readers look."""

    def __init__(self, deltas=None, flight=(), facts=None, trace=None):
        self.deltas, self.flight = deltas or {}, list(flight)
        self.facts, self.trace = facts, trace

    def delta(self, which, name):
        return self.deltas.get(name, {})

    def device(self):
        return self.trace and self.trace["devices"]["d0"]


FACTS = {"device": {"kind": "TPU v5 lite"}, "hidden_size": 4096,
         "expert_ffn": 2048, "kv_bytes": 1000,
         "cache_groups": [{"window": 0, "page_bytes": 7},
                          {"window": 128, "page_bytes": 50}]}


def trace(ops, busy=2.0, kernel=0.5):
    return {"devices": {"d0": {"busy_s": busy, "kernel_s": kernel}},
            "kernel_on_device": kernel > 0,
            "breakdown": {"device_ops": ops},
            "asked": {"on_epoch": 10.0, "stop_epoch": 14.0}}


def test_counter_readers():
    src = Src({"dynamo_moe_assignments_total": {'{to="held"}': 50.0,
                                                '{to="all"}': 800.0},
               "dynamo_moe_expert_tokens_total": {
                   '{expert="0"}': 30.0, '{expert="1"}': 10.0,
                   '{expert="2"}': 10.0, '{expert="3"}': 10.0}})
    assert load("moe.held_share").compute(src) == 0.0625
    assert load("moe.expert_load_skew").compute(src) == 2.0
    for name in ("moe.held_share", "moe.expert_load_skew"):
        assert load(name).compute(Src()) is None  # the parent: no counter


def test_trace_readers():
    src = Src(facts=FACTS, trace=trace([["%fusion.9 = bf16[1] fusion()", 1.0]]))
    assert load("attn.ragged_dev_share").compute(src) == pytest.approx(25.0)
    # the parent's facts, or a slice in which the kernel never ran: nothing
    assert load("attn.ragged_dev_share").compute(Src(facts={})) is None


def test_dead_window_share():
    flight = [{"dead_window_pages": 2}, {"dead_window_pages": 4}, {}]
    src = Src(flight=flight, facts=FACTS)
    assert load("cache.dead_window_share").compute(src) == pytest.approx(
        100.0 * 2 * 50 / 1000)
    assert load("cache.dead_window_share").compute(
        Src(flight=flight, facts={"kv_bytes": 5})) is None
