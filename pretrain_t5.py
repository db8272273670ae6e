#!/usr/bin/env python
"""Pretrain T5 (ref: /root/reference/pretrain_t5.py).

  python pretrain_t5.py --num_layers 12 ... \\
      --data_path corpus_sentence_document --decoder_seq_length 128 \\
      --tokenizer_type BertWordPieceLowerCase --vocab_file vocab.txt \\
      --vocab_extra_ids 100 --train_iters 1000

Span-corruption seq2seq loss through the shared Trainer.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from megatron_llm_tpu.arguments import args_to_configs, build_base_parser
from megatron_llm_tpu.models import T5Model
from megatron_llm_tpu.parallel import initialize_parallel
from megatron_llm_tpu.tokenizer import build_tokenizer

T5_KEYS = ["text_enc", "text_dec", "labels", "loss_mask", "enc_mask",
           "dec_mask"]


def get_batch(raw: dict) -> dict:
    """Loader dict -> T5Model.loss kwargs (ref: pretrain_t5.py:41-64)."""
    labels = np.asarray(raw["labels"])
    return {
        "encoder_input_ids": jnp.asarray(raw["text_enc"]),
        "decoder_input_ids": jnp.asarray(raw["text_dec"]),
        "lm_labels": jnp.asarray(np.maximum(labels, 0)),
        "loss_mask": jnp.asarray(raw["loss_mask"], jnp.float32),
        "encoder_attn_mask": jnp.asarray(raw["enc_mask"]),
        "decoder_attn_mask": jnp.asarray(raw["dec_mask"]),
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
    p.add_argument("--decoder_seq_length", type=int, default=128)
    # --vocab_extra_ids now lives in the base parser (default 0); T5 span
    # corruption needs sentinel tokens, so default the T5 run to 100
    p.set_defaults(vocab_extra_ids=100)
    args = p.parse_args(argv)
    if args.train_data_path or args.valid_data_path or args.test_data_path:
        raise SystemExit(
            "--train_data_path/--valid_data_path/--test_data_path are "
            "GPT-family knobs; T5 pretraining uses --data_path + --split"
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
        vocab_extra_ids=args.vocab_extra_ids,
    )
    # args_to_configs dispatches the t5 preset for --model_name t5 and
    # applies every CLI override (dtype, dropout, recompute, ...)
    args.model_name = "t5"
    mcfg, pcfg, tcfg, dargs = args_to_configs(args, tokenizer.vocab_size)
    import dataclasses

    mcfg = dataclasses.replace(
        mcfg,
        max_position_embeddings=max(mcfg.seq_length,
                                    args.decoder_seq_length),
    )
    if args.use_checkpoint_args and args.load:
        from megatron_llm_tpu.training.checkpointing import (
            load_model_config_from_checkpoint,
        )

        mcfg = load_model_config_from_checkpoint(args.load, mcfg)
    assert pcfg.pipeline_parallel_size == 1, \
        "encoder-decoder pretraining: pp>1 not supported"

    assert pcfg.context_parallel_size == 1, (
        "--context_parallel_size: ring attention is causal-only; "
        "encoder-decoder pretraining doesn't support cp"
    )
    initialize_parallel(
        dp=pcfg.data_parallel_size, pp=1, tp=pcfg.tensor_parallel_size,
        sequence_parallel=pcfg.sequence_parallel,
    )
    model = T5Model(mcfg)

    train_iters = tcfg.train_iters or 0
    num_samples = train_iters * tcfg.global_batch_size
    train_ds, valid_ds, _ = build_train_valid_test_datasets(
        dargs.data_path, dargs.split,
        [num_samples, tcfg.eval_iters * tcfg.global_batch_size, 0],
        mcfg.seq_length, args.masked_lm_prob, args.short_seq_prob,
        tcfg.seed, tokenizer, dataset_type="t5",
        max_seq_length_dec=args.decoder_seq_length,
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
        keys=T5_KEYS,
        row_range=row_range,
    )
    trainer.valid_data_iterator = build_pretraining_data_loader(
        valid_ds, 0, tcfg.micro_batch_size, pcfg.data_parallel_size, 1,
        keys=T5_KEYS,
        row_range=row_range,
    )
    state = trainer.train(state)
    if tcfg.save:
        trainer._save(state)


if __name__ == "__main__":
    main()
