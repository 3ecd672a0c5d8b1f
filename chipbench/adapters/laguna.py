"""How the harness drives the program's decoder at the ``laguna-xs.2-ep8``
configuration: the model configuration the program builds, every size of
it checked against the configuration file, and the next-token loss the
trainer steps on."""

from __future__ import annotations

from typing import Any, Dict

# The rows are one list column of token ids and a label, as BERT's.
from chipbench.adapters.bert import loader_spec  # noqa: F401
# The loss is the decoder's, whichever configuration it was built from.
from chipbench.adapters.mellum import make_loss  # noqa: F401


def check_sizes(model_cfg, sizes: Dict[str, Any]) -> None:
    import jax.numpy as jnp
    layers = range(model_cfg.num_layers)
    got = {"vocab_size": model_cfg.vocab_size,
           "hidden_size": model_cfg.hidden_size,
           "intermediate_size": model_cfg.intermediate_size,
           "num_hidden_layers": model_cfg.num_layers,
           "layer_types": list(model_cfg.layer_types),
           "mlp_layer_types": [model_cfg.mlp_type(i) for i in layers],
           "num_attention_heads": model_cfg.num_heads,
           "num_attention_heads_per_layer": [model_cfg.heads(i)
                                             for i in layers],
           "num_key_value_heads": model_cfg.num_kv_heads,
           "head_dim": model_cfg.head_dim,
           "gating": model_cfg.attention_gate,
           "sliding_window": model_cfg.sliding_window,
           "num_experts_routed": model_cfg.num_experts,
           "experts_held_first": model_cfg.experts_held[0],
           "num_experts": model_cfg.experts_held[1],
           "num_experts_per_tok": model_cfg.top_k,
           "moe_intermediate_size": model_cfg.expert_width,
           "shared_expert_intermediate_size": model_cfg.shared_expert_width,
           "moe_routed_scaling_factor": model_cfg.routed_scale,
           "partial_rotary_factor": model_cfg.full_rotary_factor,
           "rms_norm_eps": model_cfg.rms_norm_eps,
           "compute_dtype": jnp.dtype(model_cfg.compute_dtype).name}
    for key, value in got.items():
        if sizes[key] != value:
            raise ValueError(f"the program builds {key}={value!r}, the "
                             f"configuration file says {sizes[key]!r}")
    full = sizes["rope_parameters"]["full_attention"]
    sliding = sizes["rope_parameters"]["sliding_attention"]
    sliding_theta = (model_cfg.rope_theta
                     if model_cfg.sliding_rope_theta is None
                     else model_cfg.sliding_rope_theta)
    rope = {"rope_type": "yarn", "rope_theta": model_cfg.rope_theta,
            "partial_rotary_factor": model_cfg.full_rotary_factor,
            **{k: getattr(model_cfg.yarn, k) for k in (
                "factor", "original_max_position_embeddings", "beta_fast",
                "beta_slow", "attention_factor")}}
    for where, stated, built in (
            ("full_attention", full, rope),
            ("sliding_attention", sliding,
             {"rope_type": "default", "rope_theta": sliding_theta,
              "partial_rotary_factor": 1})):
        for key, value in built.items():
            if stated[key] != value:
                raise ValueError(
                    f"the program builds {where} rope {key}={value!r}, the "
                    f"configuration file says {stated[key]!r}")
