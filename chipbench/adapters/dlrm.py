"""How the harness drives the program's DLRM: the model configuration the
program builds, checked against the configuration file, and the loss the
trainer steps on. Everything here calls the program; the reference
(``chipbench/references/dlrm.py``) does not."""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def tiny_config():
    """bench.py's ``tiny`` DLRM, for the CPU rehearsal only."""
    import jax.numpy as jnp

    from ray_shuffling_data_loader_tpu.models import dlrm
    return dlrm.DLRMConfig(
        vocab_sizes=tuple(min(v, 1000) for v in dlrm.DATA_SPEC_VOCAB_SIZES),
        embed_dim=8, top_hidden=(64, 32), compute_dtype=jnp.float32)


def check_sizes(model_cfg, sizes: Dict[str, Any]) -> None:
    """The file holds the configuration as it is run."""
    import jax.numpy as jnp
    got = {"vocab_sizes": list(model_cfg.vocab_sizes),
           "embed_dim": model_cfg.embed_dim,
           "top_hidden": list(model_cfg.top_hidden),
           "dense_dim": model_cfg.dense_dim,
           "compute_dtype": jnp.dtype(model_cfg.compute_dtype).name,
           "lookup_mode": model_cfg.lookup_mode}
    for key, value in got.items():
        if sizes[key] != value:
            raise ValueError(f"the program builds {key}={value!r}, the "
                             f"configuration file says {sizes[key]!r}")


def make_loss(model_cfg, sizes: Dict[str, Any], mesh):
    """``loss(params, features, label, step, seed_key)`` for
    ``SpmdTrainer`` (this model draws nothing per step)."""
    from ray_shuffling_data_loader_tpu.models import dlrm
    multi = mesh is not None and mesh.devices.size > 1

    def loss(params, features, label, step, seed_key):
        return dlrm.loss_fn(model_cfg, params, None, features, label,
                            mesh if multi else None)

    return loss


def loader_spec(data: Dict[str, Any]) -> Dict[str, Any]:
    """``JaxShufflingDataset`` keyword arguments for the data's columns."""
    features = [c for c in data["columns"] if c.get("role") == "feature"]
    label = next(c for c in data["columns"] if c.get("role") == "label")
    return {"feature_columns": [c["name"] for c in features],
            "feature_types": [np.dtype(c["deliver_as"]) for c in features],
            "label_column": label["name"],
            "label_type": np.dtype(label["deliver_as"])}
