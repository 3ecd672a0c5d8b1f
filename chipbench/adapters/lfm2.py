"""How the harness drives the program's decoder at the
``lfm2-24b-a2b-ep8`` configuration: the model configuration the program
builds, every size and switch of it checked against the configuration
file, and the next-token loss the trainer steps on."""

from __future__ import annotations

from typing import Any, Dict

# The rows are one list column of token ids and a label, as BERT's.
from chipbench.adapters.bert import loader_spec  # noqa: F401
# The loss is the decoder's, whichever configuration it was built from.
from chipbench.adapters.mellum import make_loss  # noqa: F401


def check_sizes(model_cfg, sizes: Dict[str, Any]) -> None:
    import jax.numpy as jnp
    layers = range(model_cfg.num_layers)
    dense = [model_cfg.mlp_type(i) == "dense" for i in layers]
    got = {"vocab_size": model_cfg.vocab_size,
           "hidden_size": model_cfg.hidden_size,
           "intermediate_size": model_cfg.intermediate_size,
           "num_hidden_layers": model_cfg.num_layers,
           "layer_types": list(model_cfg.layer_types),
           # the leading layers dense, every later one sparse
           "num_dense_layers": (sum(dense) if dense == sorted(
               dense, reverse=True) else None),
           "num_attention_heads": model_cfg.num_heads,
           "num_key_value_heads": model_cfg.num_kv_heads,
           "head_dim": model_cfg.head_dim,
           "conv_L_cache": model_cfg.conv_taps,
           "conv_bias": False,              # the operator has no biases
           "num_experts_routed": model_cfg.num_experts,
           "experts_held_first": model_cfg.experts_held[0],
           "num_experts": model_cfg.experts_held[1],
           "num_experts_per_tok": model_cfg.top_k,
           "moe_intermediate_size": model_cfg.expert_width,
           "routed_scaling_factor": model_cfg.routed_scale,
           "norm_topk_prob": True,          # ops/moe.py:route renormalises
           "use_expert_bias": model_cfg.expert_bias,
           "expert_bias_update_speed": model_cfg.expert_bias_speed,
           "router_trains": model_cfg.router_trains,
           "qk_layernorm": model_cfg.qk_norm,
           "tie_word_embeddings": model_cfg.tie_embeddings,
           "norm_eps": model_cfg.rms_norm_eps,
           "compute_dtype": jnp.dtype(model_cfg.compute_dtype).name}
    for key, value in got.items():
        if sizes[key] != value:
            raise ValueError(f"the program builds {key}={value!r}, the "
                             f"configuration file says {sizes[key]!r}")
    rope = {"rope_type": ("default" if model_cfg.rotary
                          and model_cfg.yarn is None else None),
            "rope_theta": model_cfg.rope_theta}
    for key, value in rope.items():
        if sizes["rope_parameters"][key] != value:
            raise ValueError(
                f"the program builds rope {key}={value!r}, the configuration "
                f"file says {sizes['rope_parameters'][key]!r}")
    if model_cfg.heads_per_layer is not None or model_cfg.attention_gate \
            or model_cfg.shared_expert_width or model_cfg.differential \
            or model_cfg.full_rotary_factor != 1.0:
        raise ValueError("the program builds what the configuration file "
                         "has no key for: heads by layer, a head gate, a "
                         "shared expert, differential attention or a part "
                         "of a head rotated")
