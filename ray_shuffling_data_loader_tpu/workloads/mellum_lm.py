"""Causal-LM workload: packed pre-tokenized rows -> next-token training of
the decoder (``models/mellum.py``), at any of its configurations, or its
training by block diffusion (``--model sdar``).

Rows are fixed-length packed token sequences stored as
``FixedSizeList<int32>`` columns, 32 KB a row at 8,192 tokens: the shuffle
moves them untouched (the fused reduce falls back to Arrow concat+take for
list columns) and ``JaxShufflingDataset`` delivers ``(batch, seq_len)``
int32 arrays. The next-token targets are the row itself shifted by one,
made on the device inside the loss: nothing but the rows travels. Under
block diffusion the row's noised copy is made there too, from a key folded
from the run's and the step's number.

The entry point trains one chip's share of a deployment: of an
expert-parallel one the chip holds ``experts_held`` of the router's experts,
of the hybrid state-space model one pipeline stage; either way a slice of
the vocabulary, and the ids are drawn from the slice.
"""

from __future__ import annotations

from ray_shuffling_data_loader_tpu.workloads import bert_mlm

# The files are BERT's: a ``FixedSizeList<int32>`` column of ids from 4 up
# between a first id of 1 and a last of 2, a label column and the key. What
# differs is on the device: no masking, the row is its own target.
generate_packed_parquet = bert_mlm.generate_tokenized_parquet
mellum_lm_spec = bert_mlm.bert_mlm_spec


def make_loss(config):
    """``loss(params, features, label, step, seed_key)`` for
    ``SpmdTrainer``: the decoder's loss over the batch's rows. The
    next-token objective draws nothing and needs neither ``step`` nor
    ``seed_key``; block diffusion draws its noise from the two (arguments
    of the step: a key that was a constant of the program would compile a
    new one every run)."""
    import jax

    from ray_shuffling_data_loader_tpu.models import mellum

    def loss(params, features, label, step=None, seed_key=None):
        key = (jax.random.fold_in(seed_key, step)
               if config.diffusion_block else None)
        return mellum.loss_fn(config, params, features[0], key=key)

    return loss


if __name__ == "__main__":
    # Smoke driver: packed shards -> shuffle -> device feed -> SpmdTrainer
    # over one device. The defaults are a tiny preset; --full trains one
    # chip's share of the model at its published widths (a TPU).
    import argparse
    import tempfile
    import timeit

    parser = argparse.ArgumentParser(description="causal-LM workload smoke")
    parser.add_argument("--num-sequences", type=int, default=64)
    parser.add_argument("--num-files", type=int, default=4)
    parser.add_argument("--num-epochs", type=int, default=2)
    parser.add_argument("--batch-size", type=int, default=4)
    parser.add_argument("--seq-len", type=int, default=64)
    parser.add_argument("--model", choices=("mellum", "laguna", "granite",
                                            "phi4flash", "lfm2", "sdar"),
                        default="mellum",
                        help="the decoder's configuration: Mellum2-12B-A2.5B, "
                        "Laguna-XS.2 (its step fills the chip at "
                        "--batch-size 2), granite-4.0-h-micro or "
                        "Phi-4-mini-flash-reasoning's junction (both "
                        "--batch-size 1; --seq-len in whole chunks of 8) or "
                        "LFM2-24B-A2B (--batch-size 2: 7.5 GB of state and "
                        "4.9 GB of a step's temporaries; 4 would pass 15 GB) "
                        "or SDAR-30B-A3B-Chat by block diffusion "
                        "(--batch-size 1: a row is 16,384 positions; "
                        "--seq-len in whole blocks of 4)")
    parser.add_argument("--full", action="store_true",
                        help="the published widths (models.mellum."
                        "mellum2_ep4_share / laguna_xs2_ep8_share / "
                        "granite4_h_micro_period / phi4_mini_flash_junction "
                        "/ lfm2_24b_a2b_ep8_share / sdar_30b_a3b_ep8_share) "
                        "at 8,192-token rows")
    args = parser.parse_args()

    import jax
    import numpy as np
    import optax

    from ray_shuffling_data_loader_tpu.jax_dataset import JaxShufflingDataset
    from ray_shuffling_data_loader_tpu.models import mellum
    from ray_shuffling_data_loader_tpu.parallel import mesh as mesh_mod
    from ray_shuffling_data_loader_tpu.parallel.trainer import SpmdTrainer
    from ray_shuffling_data_loader_tpu.plan import ir as plan_ir

    tiny, full = {"mellum": (mellum.mellum_tiny, mellum.mellum2_ep4_share),
                  "laguna": (mellum.laguna_tiny,
                             mellum.laguna_xs2_ep8_share),
                  "granite": (mellum.granite_tiny,
                              mellum.granite4_h_micro_period),
                  "phi4flash": (mellum.phi4flash_tiny,
                                mellum.phi4_mini_flash_junction),
                  "lfm2": (mellum.lfm2_tiny,
                           mellum.lfm2_24b_a2b_ep8_share),
                  "sdar": (mellum.sdar_tiny,
                           mellum.sdar_30b_a3b_ep8_share)}[args.model]
    cfg = full() if args.full else tiny()
    seq_len = 8192 if args.full else args.seq_len
    with tempfile.TemporaryDirectory() as tmpdir:
        filenames, _ = generate_packed_parquet(
            args.num_sequences, args.num_files, tmpdir, seq_len=seq_len,
            vocab_size=cfg.vocab_size)
        ds = JaxShufflingDataset(
            filenames, num_epochs=args.num_epochs, num_trainers=1,
            batch_size=args.batch_size, rank=0, drop_last=True,
            **mellum_lm_spec(seq_len))
        trainer = SpmdTrainer(
            mesh_mod.make_mesh(num_devices=1), make_loss(cfg),
            mellum.init(cfg, jax.random.key(0)), optax.adam(1e-4))
        noise_key = jax.random.key(1)
        start = timeit.default_timer()
        rows = steps = 0
        for epoch in plan_ir.epoch_range(0, args.num_epochs):
            ds.set_epoch(epoch)
            for features, label in ds:
                loss = trainer.train_step(features, label, np.int32(steps),
                                          noise_key)
                rows += label.shape[0]
                steps += 1
        jax.block_until_ready(loss)
        duration = timeit.default_timer() - start
        print(f"{rows} rows / {steps} steps in {duration:.2f}s "
              f"({rows * seq_len / duration:,.0f} tokens/s), final loss "
              f"{float(loss):.4f}, stall "
              f"{ds.batch_wait_stats.summary()['total']:.2f}s")
