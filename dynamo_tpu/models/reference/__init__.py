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


def _cut_stacks(cfg, params, consume: bool) -> list:
    """The parameter stacks of a model with layer kinds cut into one dict a
    layer, in model order. ``consume``: take each stacked leaf OUT of
    ``params`` as it is cut into its layers, so that the stacks and their
    cuts are never both whole on the device (9.5 GB each at Granite's
    published widths)."""
    from dynamo_tpu.engine.model import layer_stacks
    from dynamo_tpu.engine.quant import HEAD_MAJOR_KEYS

    layers: list = [{} for _ in range(cfg.num_layers)]
    for stack, lps in zip(layer_stacks(cfg), params["stacks"]):
        for k in list(lps):
            a = lps.pop(k) if consume else lps[k]
            for j, i in enumerate(stack.layers):
                w = a[j]
                if k in HEAD_MAJOR_KEYS:
                    # the engine keeps [heads, width, D]; the reference
                    # reads the published x @ W orientation
                    w = w.reshape(-1, w.shape[-1]).T
                layers[i][k] = w
            del a
    return layers


def granite4_h_inputs(cfg, params, consume: bool = False
                      ) -> tuple[dict, dict]:
    """(weights, hp) for ``reference.granite4_h.forward`` from a
    ModelConfig with a Mamba-2 kind and its ``init_params`` pytree.
    ``consume``: see :func:`_cut_stacks`."""
    layers = _cut_stacks(cfg, params, consume)
    hp = {
        "hidden_size": cfg.hidden_size,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.layer_kinds[0].num_kv_heads,
        "attention_multiplier": cfg.query_pre_attn_scalar ** -0.5,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "logits_scaling": cfg.logits_scaling,
        "layer_types": [("attention", "mamba")[k]
                        for k in cfg.layer_pattern],
        "mamba_n_heads": cfg.mamba_n_heads,
        "mamba_d_head": cfg.mamba_d_head,
        "mamba_d_state": cfg.mamba_d_state,
        "mamba_d_conv": cfg.mamba_d_conv,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "rms_norm_eps": cfg.rms_norm_eps,
        "experts_held": list(cfg.experts_held or (0, cfg.num_experts)),
    }
    weights = {"embed": params["embed"], "layers": layers,
               "final_norm": params["final_norm"]}
    return weights, hp


def lfm2_moe_inputs(cfg, params, consume: bool = False
                    ) -> tuple[dict, dict]:
    """(weights, hp) for ``reference.lfm2_moe.forward`` from a ModelConfig
    with a short-convolution kind and its ``init_params`` pytree.
    ``consume``: see :func:`_cut_stacks`."""
    hp = {
        "hidden_size": cfg.hidden_size,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.layer_kinds[0].num_kv_heads,
        "rope_parameters": {"rope_theta": cfg.layer_kinds[0].rope_theta,
                            "rope_type": "default"},
        "norm_eps": cfg.rms_norm_eps,
        "layer_types": [("full_attention", "conv")[k]
                        for k in cfg.layer_pattern],
        "conv_L_cache": cfg.shortconv_taps,
        "num_dense_layers": cfg.first_k_dense_replace,
        "num_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "experts_held": list(cfg.experts_held or (0, cfg.num_experts)),
    }
    weights = {"embed": params["embed"],
               "layers": _cut_stacks(cfg, params, consume),
               "final_norm": params["final_norm"]}
    return weights, hp
