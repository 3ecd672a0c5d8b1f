"""How the harness drives the program's decoder at the
``sdar-30b-a3b-ep8`` configuration: the model configuration the program
builds, every size and switch of it checked against the configuration
file, the objective's three among them, and the block-diffusion loss the
trainer steps on, its noise drawn on the device from the step's own
key."""

from __future__ import annotations

from typing import Any, Dict

# The rows are one list column of token ids and a label, as BERT's.
from chipbench.adapters.bert import loader_spec  # noqa: F401


def check_sizes(model_cfg, sizes: Dict[str, Any]) -> None:
    import jax.numpy as jnp
    got = {"vocab_size": model_cfg.vocab_size,
           "hidden_size": model_cfg.hidden_size,
           "num_hidden_layers": model_cfg.num_layers,
           # every layer full attention over a sparse MLP
           "layer_kinds": sorted(set(model_cfg.layer_types)),
           "decoder_sparse_step": 1 if all(
               model_cfg.mlp_type(i) == "sparse"
               for i in range(model_cfg.num_layers)) else None,
           "num_attention_heads": model_cfg.num_heads,
           "num_key_value_heads": model_cfg.num_kv_heads,
           "head_dim": model_cfg.head_dim,
           "use_sliding_window": False,     # no layer_types entry has one
           "num_experts_routed": model_cfg.num_experts,
           "experts_held_first": model_cfg.experts_held[0],
           "num_experts": model_cfg.experts_held[1],
           "num_experts_per_tok": model_cfg.top_k,
           "moe_intermediate_size": model_cfg.expert_width,
           "norm_topk_prob": True,          # ops/moe.py:route renormalises
           "router_trains": model_cfg.router_trains,
           "qk_norm": model_cfg.qk_norm,
           "tie_word_embeddings": model_cfg.tie_embeddings,
           "rms_norm_eps": model_cfg.rms_norm_eps,
           "rope_theta": model_cfg.rope_theta,
           "rope_scaling": (None if model_cfg.rotary
                            and model_cfg.yarn is None else "other"),
           "block_length": model_cfg.diffusion_block,
           "mask_token_id": model_cfg.mask_token_id,
           "noise_eps": model_cfg.diffusion_eps,
           "compute_dtype": jnp.dtype(model_cfg.compute_dtype).name}
    wanted = dict(sizes, layer_kinds=["full_attention"])
    for key, value in got.items():
        if wanted[key] != value:
            raise ValueError(f"the program builds {key}={value!r}, the "
                             f"configuration file says {wanted[key]!r}")
    if sizes["seq_len"] % model_cfg.diffusion_block:
        raise ValueError("seq_len is not whole blocks of block_length")
    if model_cfg.heads_per_layer is not None or model_cfg.attention_gate \
            or model_cfg.shared_expert_width or model_cfg.differential \
            or model_cfg.expert_bias or model_cfg.routed_scale != 1.0 \
            or model_cfg.full_rotary_factor != 1.0 \
            or model_cfg.attention_multiplier is not None \
            or (model_cfg.embedding_multiplier, model_cfg.logits_scaling,
                model_cfg.residual_multiplier) != (1.0, 1.0, 1.0):
        raise ValueError("the program builds what the configuration file "
                         "has no key for: heads by layer, a head gate, a "
                         "shared expert, differential attention, a "
                         "selection bias, a scale on the routed sum, the "
                         "softmax, the embedding, the logits or the "
                         "residuals, or a part of a head rotated")


def make_loss(model_cfg, sizes: Dict[str, Any], mesh):
    """``loss(params, features, label, step, seed_key)`` for
    ``SpmdTrainer``. The noise's key is folded from the seed's and the
    step's number on the device: an argument, not a constant of the
    program (a constant would make every seed a new program to
    compile)."""
    import jax

    from ray_shuffling_data_loader_tpu.models import mellum

    def loss(params, features, label, step, seed_key):
        return mellum.loss_fn(model_cfg, params, features[0], mesh,
                              jax.random.fold_in(seed_key, step))

    return loss
