"""Plain references of the architectures the benchmark runs (model-configs
guide, section 3.2), and the glue that hands one the system's weights.

A reference file imports nothing from this package: ``chipbench/references/``
holds a byte-identical copy of each, so that the yardstick cannot drift with
the program (tests/test_mimo_v2.py says so).
"""

from __future__ import annotations


def mimo_v2_inputs(cfg, params) -> tuple[dict, dict]:
    """(weights, hp) for ``reference.mimo_v2.forward`` from a ModelConfig
    with layer kinds and its ``init_params`` pytree: the parameter stacks
    cut back into one dict a layer, and the configuration spelled with the
    published keys."""
    import jax

    from dynamo_tpu.engine.model import layer_stacks
    from dynamo_tpu.engine.quant import HEAD_MAJOR_KEYS

    layers: list = [None] * cfg.num_layers
    for stack, lps in zip(layer_stacks(cfg), params["stacks"]):
        for j, i in enumerate(stack.layers):
            layers[i] = jax.tree.map(lambda a: a[j], lps)
            for k in HEAD_MAJOR_KEYS:
                # the engine keeps [heads, width, D]; the reference reads
                # the published x @ W orientation, [D, heads·width]
                w = layers[i][k]
                layers[i][k] = w.reshape(-1, w.shape[-1]).T
    full, swa = cfg.layer_kinds
    hp = {
        "hidden_size": cfg.hidden_size,
        "num_attention_heads": cfg.num_heads,
        "head_dim": cfg.head_dim, "v_head_dim": cfg.v_dim,
        # any factor in [rot, rot + 1) / head_dim rounds down to rot
        "partial_rotary_factor": (cfg.rotary_dim + 0.5) / cfg.head_dim,
        "attention_value_scale": cfg.value_scale,
        "num_key_value_heads": full.num_kv_heads,
        "rope_theta": full.rope_theta,
        "add_full_attention_sink_bias": full.sink,
        "swa_num_key_value_heads": swa.num_kv_heads,
        "swa_rope_theta": swa.rope_theta, "sliding_window": swa.window,
        "add_swa_attention_sink_bias": swa.sink,
        "hybrid_layer_pattern": list(cfg.layer_pattern),
        "moe_layer_freq": [int(i >= cfg.first_k_dense_replace)
                           for i in range(cfg.num_layers)],
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "layernorm_epsilon": cfg.rms_norm_eps,
        "experts_held": list(cfg.experts_held or (0, cfg.num_experts)),
    }
    weights = {"embed": params["embed"], "layers": layers,
               "final_norm": params["final_norm"],
               "lm_head": params["lm_head"]}
    return weights, hp
