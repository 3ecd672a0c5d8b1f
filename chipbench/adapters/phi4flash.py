"""How the harness drives the program's decoder at the
``phi-4-mini-flash-j6`` configuration: the model configuration the program
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
    got = {"vocab_size": model_cfg.vocab_size,
           "hidden_size": model_cfg.hidden_size,
           "intermediate_size": model_cfg.intermediate_size,
           "num_hidden_layers": model_cfg.num_layers,
           "layer_types": list(model_cfg.layer_types),
           "published_layer_indices": list(model_cfg.published_indices or ()),
           "num_attention_heads": model_cfg.num_heads,
           "num_key_value_heads": model_cfg.num_kv_heads,
           "head_dim": model_cfg.head_dim,
           "sliding_window": model_cfg.sliding_window,
           "layer_norm_eps": model_cfg.rms_norm_eps,
           "tie_word_embeddings": model_cfg.tie_embeddings,
           "hidden_act": "silu",
           # no biases but LayerNorm's and the convolution's, no dropout
           "mlp_bias": False, "lm_head_bias": False,
           "embd_pdrop": 0, "resid_pdrop": 0,
           "mamba_d_state": model_cfg.mamba1_state,
           "mamba_d_conv": model_cfg.mamba_conv,
           "mamba_dt_rank": model_cfg.mamba1_dt_rank,
           "mamba_expand": model_cfg.mamba1_width / model_cfg.hidden_size,
           "scan_chunk": model_cfg.mamba_chunk,
           "compute_dtype": jnp.dtype(model_cfg.compute_dtype).name}
    for key, value in got.items():
        if sizes[key] != value:
            raise ValueError(f"the program builds {key}={value!r}, the "
                             f"configuration file says {sizes[key]!r}")
    if sizes["published"]["num_hidden_layers"] != model_cfg.published_layers:
        raise ValueError("the published depth differs")
    # what the file has no key for: SambaY's decoder is differential,
    # under LayerNorm, and has none of the other decoders' switches
    plain = (all(model_cfg.mlp_type(i) == "dense"
                 for i in range(model_cfg.num_layers))
             and not model_cfg.rotary and not model_cfg.attention_gate
             and model_cfg.attention_multiplier is None
             and (model_cfg.embedding_multiplier,
                  model_cfg.residual_multiplier,
                  model_cfg.logits_scaling) == (1.0, 1.0, 1.0))
    if not (plain and model_cfg.differential and model_cfg.norm == "layer"):
        raise ValueError("the program builds another decoder than the "
                         "configuration file's: differential attention "
                         "under LayerNorm, dense MLPs, no positions, gate "
                         "or multipliers")
    if sizes["seq_len"] % model_cfg.mamba_chunk:
        raise ValueError(f"rows of {sizes['seq_len']} tokens are not whole "
                         f"chunks of {model_cfg.mamba_chunk}")
    if sizes["hidden_size"] != sizes["num_attention_heads"] \
            * sizes["head_dim"]:
        raise ValueError("head_dim is not hidden_size / num_attention_heads")
