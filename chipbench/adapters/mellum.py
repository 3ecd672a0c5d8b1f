"""How the harness drives the program's sparse-expert decoder: the model
configuration the program builds, checked against the configuration file,
and the next-token loss the trainer steps on."""

from __future__ import annotations

from typing import Any, Dict

# The rows are one list column of token ids and a label, as BERT's.
from chipbench.adapters.bert import loader_spec  # noqa: F401


def check_sizes(model_cfg, sizes: Dict[str, Any]) -> None:
    import jax.numpy as jnp
    yarn = sizes["rope_parameters"]["full_attention"]
    got = {"vocab_size": model_cfg.vocab_size,
           "hidden_size": model_cfg.hidden_size,
           "num_hidden_layers": model_cfg.num_layers,
           "layer_types": list(model_cfg.layer_types),
           "num_attention_heads": model_cfg.num_heads,
           "num_key_value_heads": model_cfg.num_kv_heads,
           "head_dim": model_cfg.head_dim,
           "sliding_window": model_cfg.sliding_window,
           "num_experts_routed": model_cfg.num_experts,
           "experts_held_first": model_cfg.experts_held[0],
           "num_experts": model_cfg.experts_held[1],
           "num_experts_per_tok": model_cfg.top_k,
           "moe_intermediate_size": model_cfg.expert_width,
           "rms_norm_eps": model_cfg.rms_norm_eps,
           "compute_dtype": jnp.dtype(model_cfg.compute_dtype).name}
    for key, value in got.items():
        if sizes[key] != value:
            raise ValueError(f"the program builds {key}={value!r}, the "
                             f"configuration file says {sizes[key]!r}")
    rope = {"rope_theta": model_cfg.rope_theta,
            **{k: getattr(model_cfg.yarn, k) for k in (
                "factor", "original_max_position_embeddings", "beta_fast",
                "beta_slow", "attention_factor")}}
    for key, value in rope.items():
        if yarn[key] != value:
            raise ValueError(f"the program builds rope {key}={value!r}, the "
                             f"configuration file says {yarn[key]!r}")
    if sizes["rope_parameters"]["sliding_attention"]["rope_theta"] \
            != model_cfg.rope_theta:
        raise ValueError("the window layers' rope_theta differs")


def make_loss(model_cfg, sizes: Dict[str, Any], mesh):
    """``loss(params, features, label, step, seed_key)`` for
    ``SpmdTrainer``: the rows are the step's whole input; nothing is
    drawn on the device, so step and key go unused."""
    from ray_shuffling_data_loader_tpu.models import mellum

    def loss(params, features, label, step, seed_key):
        return mellum.loss_fn(model_cfg, params, features[0], mesh)

    return loss
