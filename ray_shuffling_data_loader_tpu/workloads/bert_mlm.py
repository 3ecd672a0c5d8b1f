"""BERT-MLM workload: pre-tokenized sequence Parquet -> sequence batches.

BASELINE config 4: "BERT-base MLM on pre-tokenized Wikipedia Parquet
(sequence batching)". Rows are fixed-length token sequences stored as
``FixedSizeList<int32>`` columns; the shuffle moves them untouched (the
fused reduce falls back to Arrow concat+take for list columns,
shuffle.py:339-347) and ``JaxShufflingDataset`` reshapes each batch to
``(batch, seq_len)``.

MLM masking is **dynamic and on-device**: :func:`mlm_mask` is a jittable
function of (tokens, PRNG key) applying the BERT 80/10/10 rule. The
reference's pipeline could only ship statically pre-masked rows; keyed JAX
PRNG gives every epoch fresh masks for free, with zero host-side cost and
fully replayable (seed, epoch, step) streams.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from ray_shuffling_data_loader_tpu import workloads
from ray_shuffling_data_loader_tpu.models.bert import IGNORE_ID
from ray_shuffling_data_loader_tpu.runtime import telemetry
from ray_shuffling_data_loader_tpu.utils.logger import setup_custom_logger

logger = setup_custom_logger(__name__)

TOKENS_COLUMN = "input_ids"
LABEL_COLUMN = "label"
KEY_COLUMN = "key"

# Conventional special-token ids for the synthetic vocab: [PAD]=0, [CLS]=1,
# [SEP]=2, [MASK]=3; real corpora pass their own ids to mlm_mask.
PAD_ID = 0
CLS_ID = 1
SEP_ID = 2
MASK_ID = 3
NUM_SPECIAL_TOKENS = 4
#: The name a device trace shows the dynamic masking's draws and selects
#: under (:func:`mlm_mask`, inside the jitted step).
MASK_SCOPE = telemetry.step_scope("rsdl.bert.mask")


def generate_file(file_index: int, global_row_index: int, num_rows: int,
                  data_dir: str, seq_len: int, vocab_size: int,
                  seed: int) -> Tuple[str, int]:
    """One Parquet shard of [CLS] body... [SEP] token rows; (path, nbytes)."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, file_index]))
    tokens = rng.integers(NUM_SPECIAL_TOKENS, vocab_size,
                          size=(num_rows, seq_len), dtype=np.int32)
    tokens[:, 0] = CLS_ID
    tokens[:, -1] = SEP_ID
    table = pa.table({
        TOKENS_COLUMN: pa.FixedSizeListArray.from_arrays(
            pa.array(tokens.reshape(-1)), seq_len),
        LABEL_COLUMN: np.zeros(num_rows, dtype=np.int64),
        KEY_COLUMN: np.arange(global_row_index, global_row_index + num_rows,
                              dtype=np.int64),
    })
    filename = os.path.join(data_dir,
                            f"tokenized_shard_{file_index}.parquet.snappy")
    pq.write_table(table, filename, compression="snappy")
    return filename, table.nbytes


def generate_tokenized_parquet(num_sequences: int,
                               num_files: int,
                               data_dir: str,
                               seq_len: int = 128,
                               vocab_size: int = 30522,
                               seed: int = 0,
                               num_workers: Optional[int] = None
                               ) -> Tuple[List[str], int]:
    """Parallel synthetic pre-tokenized shards (seeded)."""
    os.makedirs(data_dir, exist_ok=True)

    def write_file(file_index: int, start: int, n: int) -> Tuple[str, int]:
        return generate_file(file_index, start, n, data_dir, seq_len,
                             vocab_size, seed)

    filenames, total_bytes = workloads.generate_shards(
        write_file, num_sequences, num_files, num_workers=num_workers,
        thread_name_prefix="rsdl-bertgen")
    logger.info("generated %d tokenized shards, %d sequences, %.1f MB",
                len(filenames), num_sequences, total_bytes / 1e6)
    return filenames, total_bytes


def mlm_mask(tokens,
             key,
             vocab_size: int,
             mask_prob: float = 0.15,
             mask_token_id: int = MASK_ID,
             num_special_tokens: int = NUM_SPECIAL_TOKENS):
    """Jittable dynamic MLM masking: (tokens, PRNG key) -> (inputs, targets).

    BERT recipe: select ``mask_prob`` of non-special positions; of those,
    80% become [MASK], 10% a uniform random token, 10% keep the original.
    ``targets`` holds the original token at selected positions and
    ``IGNORE_ID`` elsewhere — exactly what models/bert.py ``loss_fn`` eats.
    Runs under jit on device: masking costs no host time and the stream is
    replayable from (seed, epoch, step).
    """
    import jax
    import jax.numpy as jnp

    with jax.named_scope(MASK_SCOPE):
        select_key, action_key, random_key = jax.random.split(key, 3)
        maskable = tokens >= num_special_tokens
        selected = (jax.random.uniform(select_key, tokens.shape)
                    < mask_prob) & maskable
        action = jax.random.uniform(action_key, tokens.shape)
        random_tokens = jax.random.randint(
            random_key, tokens.shape, num_special_tokens, vocab_size,
            dtype=tokens.dtype)
        inputs = jnp.where(
            selected & (action < 0.8), mask_token_id,
            jnp.where(selected & (action >= 0.9), random_tokens, tokens))
        targets = jnp.where(selected, tokens, IGNORE_ID)
    return inputs, targets


def bert_mlm_spec(seq_len: int) -> Dict[str, Any]:
    """``JaxShufflingDataset`` kwargs for the tokenized-sequence layout."""
    return {
        "feature_columns": [TOKENS_COLUMN],
        "feature_shapes": [(seq_len,)],
        "feature_types": [np.int32],
        "label_column": LABEL_COLUMN,
        "label_type": np.int32,
    }


if __name__ == "__main__":
    # Smoke driver (reference pattern: dataset.py:233-276): tokenized
    # shards -> shuffle -> on-device dynamic masking -> BERT train loop.
    import argparse
    import tempfile
    import timeit

    parser = argparse.ArgumentParser(description="BERT-MLM workload smoke")
    parser.add_argument("--num-sequences", type=int, default=4096)
    parser.add_argument("--num-files", type=int, default=4)
    parser.add_argument("--num-epochs", type=int, default=2)
    parser.add_argument("--batch-size", type=int, default=64)
    parser.add_argument("--seq-len", type=int, default=128)
    parser.add_argument("--vocab-size", type=int, default=8192)
    parser.add_argument("--hidden-dim", type=int, default=128)
    parser.add_argument("--num-layers", type=int, default=2)
    parser.add_argument("--num-heads", type=int, default=4)
    parser.add_argument("--ffn-dim", type=int, default=256)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import optax

    from ray_shuffling_data_loader_tpu.jax_dataset import JaxShufflingDataset
    from ray_shuffling_data_loader_tpu.models import bert

    with tempfile.TemporaryDirectory() as tmpdir:
        filenames, _ = generate_tokenized_parquet(
            args.num_sequences, args.num_files, tmpdir,
            seq_len=args.seq_len, vocab_size=args.vocab_size)
        ds = JaxShufflingDataset(
            filenames, num_epochs=args.num_epochs, num_trainers=1,
            batch_size=args.batch_size, rank=0, drop_last=True,
            **bert_mlm_spec(args.seq_len))
        cfg = bert.BertConfig(vocab_size=args.vocab_size,
                              hidden_dim=args.hidden_dim,
                              num_layers=args.num_layers,
                              num_heads=args.num_heads,
                              ffn_dim=args.ffn_dim,
                              max_seq_len=args.seq_len)
        params = bert.init(cfg, jax.random.key(0))
        opt = optax.adam(1e-4)
        opt_state = opt.init(params)

        # No attention_fn: the model chooses (the Pallas flash kernels on
        # the chip from flash_attention.FLASH_MIN_SEQ_LEN up, XLA's inline
        # attention below it and on the CPU). The step is jitted on one
        # device, so it has no mesh to tell ``loss_fn`` of.
        @jax.jit
        def step(params, opt_state, tokens, key):
            inputs, targets = mlm_mask(tokens, key, args.vocab_size)
            loss, grads = jax.value_and_grad(
                lambda p: bert.loss_fn(cfg, p, inputs, targets))(params)
            updates, opt_state = opt.update(grads, opt_state)
            return optax.apply_updates(params, updates), opt_state, loss

        start = timeit.default_timer()
        rows = steps = 0
        from ray_shuffling_data_loader_tpu.plan import ir as plan_ir
        for epoch in plan_ir.epoch_range(0, args.num_epochs):
            ds.set_epoch(epoch)
            for (tokens,), _ in ds:
                params, opt_state, loss = step(params, opt_state, tokens,
                                               jax.random.key(steps))
                rows += tokens.shape[0]
                steps += 1
        jax.block_until_ready(loss)
        duration = timeit.default_timer() - start
        print(f"{rows} sequences / {steps} steps in {duration:.2f}s "
              f"({rows / duration:,.0f} seq/s), final loss "
              f"{float(loss):.4f}, stall "
              f"{ds.batch_wait_stats.summary()['total']:.2f}s")
