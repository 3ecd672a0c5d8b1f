"""How the harness drives the program's decoder at the
``granite-4.0-h-micro-p1`` configuration: the model configuration the
program builds, every size and switch of it checked against the
configuration file, and the next-token loss the trainer steps on."""

from __future__ import annotations

from typing import Any, Dict

# The rows are one list column of token ids and a label, as BERT's.
from chipbench.adapters.bert import loader_spec  # noqa: F401
# The loss is the decoder's, whichever configuration it was built from.
from chipbench.adapters.mellum import make_loss  # noqa: F401

#: The file's words for the program's layer types.
_LAYER_WORDS = {"mamba": "mamba", "full_attention": "attention"}


def check_sizes(model_cfg, sizes: Dict[str, Any]) -> None:
    import jax.numpy as jnp
    layers = range(model_cfg.num_layers)
    dense = all(model_cfg.mlp_type(i) == "dense" for i in layers)
    got = {"vocab_size": model_cfg.vocab_size,
           "hidden_size": model_cfg.hidden_size,
           "intermediate_size": model_cfg.intermediate_size,
           "shared_intermediate_size": model_cfg.intermediate_size,
           "num_hidden_layers": model_cfg.num_layers,
           "layer_types": [_LAYER_WORDS.get(kind, kind)
                           for kind in model_cfg.layer_types],
           "num_attention_heads": model_cfg.num_heads,
           "num_key_value_heads": model_cfg.num_kv_heads,
           "head_dim": model_cfg.head_dim,
           "attention_bias": False,         # the decoder has no biases
           "attention_multiplier": model_cfg.attention_multiplier,
           "embedding_multiplier": model_cfg.embedding_multiplier,
           "residual_multiplier": model_cfg.residual_multiplier,
           "logits_scaling": model_cfg.logits_scaling,
           "position_embedding_type": ("rope" if model_cfg.rotary
                                       else "nope"),
           "tie_word_embeddings": model_cfg.tie_embeddings,
           "rms_norm_eps": model_cfg.rms_norm_eps,
           "rope_theta": model_cfg.rope_theta,
           "hidden_act": "silu", "normalization_function": "rmsnorm",
           "mamba_n_heads": model_cfg.mamba_heads,
           "mamba_d_head": model_cfg.mamba_head_dim,
           "mamba_d_state": model_cfg.mamba_state,
           "mamba_d_conv": model_cfg.mamba_conv,
           "mamba_chunk_size": model_cfg.mamba_chunk,
           "mamba_n_groups": 1,             # B and C in one group
           "mamba_expand": model_cfg.mamba_width / model_cfg.hidden_size,
           "mamba_conv_bias": True, "mamba_proj_bias": False,
           # every MLP the dense SwiGLU: no routed experts
           "num_local_experts": 0 if dense else None,
           "num_experts_per_tok": 0 if dense else None,
           "compute_dtype": jnp.dtype(model_cfg.compute_dtype).name}
    for key, value in got.items():
        if sizes[key] != value:
            raise ValueError(f"the program builds {key}={value!r}, the "
                             f"configuration file says {sizes[key]!r}")
    if sizes["seq_len"] % model_cfg.mamba_chunk:
        raise ValueError(f"rows of {sizes['seq_len']} tokens are not whole "
                         f"chunks of {model_cfg.mamba_chunk}")
    if sizes["hidden_size"] != sizes["num_attention_heads"] \
            * sizes["head_dim"]:
        raise ValueError("head_dim is not hidden_size / num_attention_heads")
