#!/usr/bin/env python
"""Pretrain BERT (ref: /root/reference/pretrain_bert.py).

  python pretrain_bert.py --model_name bert --num_layers 12 ... \\
      --data_path corpus_sentence_document \\
      --tokenizer_type BertWordPieceLowerCase --vocab_file vocab.txt \\
      --train_iters 1000

Masked-LM + sentence-order (binary) loss through the shared Trainer; the
BERT batch fields ride the generic dict data loader.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from megatron_llm_tpu.arguments import args_to_configs, build_base_parser
from megatron_llm_tpu.models import BertModel
from megatron_llm_tpu.parallel import initialize_parallel
from megatron_llm_tpu.tokenizer import build_tokenizer

BERT_KEYS = ["text", "types", "labels", "is_random", "loss_mask",
             "padding_mask"]


def get_batch(raw: dict) -> dict:
    """Loader dict -> BertModel.loss kwargs (ref: pretrain_bert.py:42-68)."""
    labels = np.asarray(raw["labels"])
    return {
        "tokens": jnp.asarray(raw["text"]),
        "labels": jnp.asarray(np.maximum(labels, 0)),  # -1 filler -> 0, masked out
        "loss_mask": jnp.asarray(raw["loss_mask"], jnp.float32),
        "attention_mask": jnp.asarray(raw["padding_mask"]),
        "tokentype_ids": jnp.asarray(raw["types"]),
        "sop_labels": jnp.asarray(raw["is_random"]),
    }


def main(argv=None):
    from megatron_llm_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from megatron_llm_tpu.data.data_samplers import (
        build_pretraining_data_loader,
    )
    from megatron_llm_tpu.data.dataset_utils import (
        build_train_valid_test_datasets,
    )
    from megatron_llm_tpu.training.trainer import Trainer

    p = build_base_parser()
    # --mask_prob is the reference spelling (arguments.py:885)
    p.add_argument("--masked_lm_prob", "--mask_prob", type=float,
                   default=0.15)
    p.add_argument("--short_seq_prob", type=float, default=0.1)
    p.add_argument("--no_binary_head", action="store_true")
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
    # args_to_configs dispatches the bert preset for --model_name bert and
    # applies every CLI override (dtype, dropout, recompute, flash, ...)
    args.model_name = "bert"
    mcfg, pcfg, tcfg, dargs = args_to_configs(args, tokenizer.vocab_size)
    import dataclasses

    binary_head = not args.no_binary_head
    mcfg = dataclasses.replace(mcfg, add_binary_head=binary_head)
    if args.use_checkpoint_args and args.load:
        from megatron_llm_tpu.training.checkpointing import (
            load_model_config_from_checkpoint,
        )

        mcfg = load_model_config_from_checkpoint(args.load, mcfg)
    assert pcfg.pipeline_parallel_size == 1, \
        "encoder pretraining: pp>1 not supported (GPT-only pipeline)"

    assert pcfg.context_parallel_size == 1, (
        "--context_parallel_size: ring attention is causal-only; "
        "encoder pretraining doesn't support cp"
    )
    initialize_parallel(
        dp=pcfg.data_parallel_size, pp=1, tp=pcfg.tensor_parallel_size,
        sequence_parallel=pcfg.sequence_parallel,
    )
    model = BertModel(mcfg)

    train_iters = tcfg.train_iters or 0
    num_samples = train_iters * tcfg.global_batch_size
    train_ds, valid_ds, _ = build_train_valid_test_datasets(
        dargs.data_path, dargs.split,
        [num_samples, tcfg.eval_iters * tcfg.global_batch_size, 0],
        mcfg.seq_length, args.masked_lm_prob, args.short_seq_prob,
        tcfg.seed, tokenizer, dataset_type="standard_bert",
        binary_head=binary_head,
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
        keys=BERT_KEYS,
        row_range=row_range,
    )
    trainer.valid_data_iterator = build_pretraining_data_loader(
        valid_ds, 0, tcfg.micro_batch_size, pcfg.data_parallel_size, 1,
        keys=BERT_KEYS,
        row_range=row_range,
    )
    state = trainer.train(state)
    if tcfg.save:
        trainer._save(state)


if __name__ == "__main__":
    main()
