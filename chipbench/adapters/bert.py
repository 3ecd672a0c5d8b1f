"""How the harness drives the program's BERT masked-LM step: the model
configuration the program builds, checked against the configuration file,
and the loss (dynamic masking on the device, then the model's loss) the
trainer steps on."""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def check_sizes(model_cfg, sizes: Dict[str, Any]) -> None:
    import jax.numpy as jnp
    got = {"vocab_size": model_cfg.vocab_size,
           "hidden_dim": model_cfg.hidden_dim,
           "num_layers": model_cfg.num_layers,
           "num_heads": model_cfg.num_heads,
           "ffn_dim": model_cfg.ffn_dim,
           "max_seq_len": model_cfg.max_seq_len,
           "compute_dtype": jnp.dtype(model_cfg.compute_dtype).name}
    for key, value in got.items():
        if sizes[key] != value:
            raise ValueError(f"the program builds {key}={value!r}, the "
                             f"configuration file says {sizes[key]!r}")
    if sizes["seq_len"] > model_cfg.max_seq_len:
        raise ValueError("seq_len exceeds the model's max_seq_len")


def make_loss(model_cfg, sizes: Dict[str, Any], mesh):
    """``loss(params, features, label, step, seed_key)`` for
    ``SpmdTrainer``. The key is an argument, not a constant of the
    program: a constant would make every seed a new program to compile."""
    import jax

    from ray_shuffling_data_loader_tpu.models import bert
    from ray_shuffling_data_loader_tpu.workloads import bert_mlm

    def loss(params, features, label, step, seed_key):
        inputs, targets = bert_mlm.mlm_mask(
            features[0], jax.random.fold_in(seed_key, step),
            sizes["vocab_size"], mask_prob=sizes["mask_prob"])
        return bert.loss_fn(model_cfg, params, inputs, targets)

    return loss


def loader_spec(data: Dict[str, Any]) -> Dict[str, Any]:
    features = [c for c in data["columns"] if c.get("role") == "feature"]
    label = next(c for c in data["columns"] if c.get("role") == "label")
    return {"feature_columns": [c["name"] for c in features],
            "feature_shapes": [(c["width"],) for c in features],
            "feature_types": [np.dtype(c["deliver_as"]) for c in features],
            "label_column": label["name"],
            "label_type": np.dtype(label["deliver_as"])}
