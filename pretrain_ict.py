#!/usr/bin/env python
"""Pretrain the ICT biencoder (ref: /root/reference/pretrain_ict.py).

  python pretrain_ict.py --num_layers 12 ... \\
      --data_path blocks_sentence_document \\
      --titles_data_path titles_document \\
      --tokenizer_type BertWordPieceLowerCase --vocab_file vocab.txt \\
      --train_iters 1000

Inverse-cloze retrieval loss: each pseudo-query's positive is its own
evidence block, in-batch negatives everywhere else.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from megatron_llm_tpu.arguments import args_to_configs, build_base_parser
from megatron_llm_tpu.models.biencoder import BiEncoderModel
from megatron_llm_tpu.parallel import initialize_parallel
from megatron_llm_tpu.tokenizer import build_tokenizer

ICT_KEYS = ["query_tokens", "query_pad_mask", "context_tokens",
            "context_pad_mask"]


def get_batch(raw: dict) -> dict:
    """Loader dict -> BiEncoderModel.loss kwargs
    (ref: pretrain_ict.py:42-66)."""
    return {
        "query_tokens": jnp.asarray(raw["query_tokens"]),
        "query_mask": jnp.asarray(raw["query_pad_mask"]),
        "context_tokens": jnp.asarray(raw["context_tokens"]),
        "context_mask": jnp.asarray(raw["context_pad_mask"]),
    }


def main(argv=None):
    from megatron_llm_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from megatron_llm_tpu.data.data_samplers import (
        build_pretraining_data_loader,
    )
    from megatron_llm_tpu.data.ict_dataset import ICTDataset
    from megatron_llm_tpu.data.indexed_dataset import make_dataset
    from megatron_llm_tpu.training.trainer import Trainer

    p = build_base_parser()
    p.add_argument("--titles_data_path", type=str, required=True)
    p.add_argument("--query_in_block_prob", type=float, default=0.1)
    p.add_argument("--use_one_sent_docs", action="store_true")
    p.add_argument("--biencoder_projection_dim", type=int, default=0)
    p.add_argument("--biencoder_shared_query_context_model",
                   action="store_true")
    args = p.parse_args(argv)
    if args.train_data_path or args.valid_data_path or args.test_data_path:
        raise SystemExit(
            "--train_data_path/--valid_data_path/--test_data_path are "
            "GPT-family knobs; this entry point uses --data_path + --split"
        )

    from megatron_llm_tpu.parallel.mesh import (
        maybe_initialize_distributed,
    )

    maybe_initialize_distributed()  # before any jax.devices() use
    tokenizer = build_tokenizer(
        args.tokenizer_type or "BertWordPieceLowerCase",
        vocab_file=args.vocab_file,
        make_vocab_size_divisible_by=args.make_vocab_size_divisible_by,
        tensor_parallel_size=args.tensor_model_parallel_size,
    )
    # BERT-family towers; args_to_configs applies every CLI override
    args.model_name = "bert"
    mcfg, pcfg, tcfg, dargs = args_to_configs(args, tokenizer.vocab_size)
    import dataclasses

    mcfg = dataclasses.replace(mcfg, add_binary_head=False)
    if args.use_checkpoint_args and args.load:
        from megatron_llm_tpu.training.checkpointing import (
            load_model_config_from_checkpoint,
        )

        mcfg = load_model_config_from_checkpoint(args.load, mcfg)
    assert pcfg.pipeline_parallel_size == 1

    assert pcfg.context_parallel_size == 1, (
        "--context_parallel_size: ring attention is causal-only; "
        "encoder pretraining doesn't support cp"
    )
    initialize_parallel(
        dp=pcfg.data_parallel_size, pp=1, tp=pcfg.tensor_parallel_size,
        sequence_parallel=pcfg.sequence_parallel,
    )
    model = BiEncoderModel(
        mcfg, projection_dim=args.biencoder_projection_dim,
        shared_query_context_model=args.biencoder_shared_query_context_model,
    )

    block_ds = make_dataset(dargs.data_path if isinstance(dargs.data_path, str)
                            else dargs.data_path[0], "mmap")
    titles_ds = make_dataset(args.titles_data_path, "mmap")
    train_ds = ICTDataset(
        name="train", block_dataset=block_ds, title_dataset=titles_ds,
        data_prefix=dargs.data_path if isinstance(dargs.data_path, str)
        else dargs.data_path[0],
        num_epochs=None,
        max_num_samples=(tcfg.train_iters or 0) * tcfg.global_batch_size,
        max_seq_length=mcfg.seq_length,
        query_in_block_prob=args.query_in_block_prob, seed=tcfg.seed,
        tokenizer=tokenizer, use_one_sent_docs=args.use_one_sent_docs,
    )
    trainer = Trainer(model, tcfg, pcfg, batch_builder=get_batch)
    state = trainer.setup()
    # multi-host: each process loads only its data-axis rows
    row_range = None
    if trainer.ctx is not None and jax.process_count() > 1:
        from megatron_llm_tpu.parallel.multihost import process_row_range

        row_range = process_row_range(
            trainer.ctx, tcfg.micro_batch_size * pcfg.data_parallel_size
        )
    trainer.train_data_iterator = build_pretraining_data_loader(
        train_ds, state.consumed_train_samples, tcfg.micro_batch_size,
        pcfg.data_parallel_size, trainer.num_microbatches_calc.get,
        keys=ICT_KEYS,
        row_range=row_range,
    )
    state = trainer.train(state)
    if tcfg.save:
        trainer._save(state)


if __name__ == "__main__":
    main()
