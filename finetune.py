#!/usr/bin/env python
"""Train/fine-tune GPT, Llama or Falcon (ref: /root/reference/finetune.py).

Same job as the reference entry point, one process driving the whole TPU
mesh instead of one process per GPU:

  python finetune.py --model_name llama2 --model_size 7 \\
      --data_path corpus_text_document --tokenizer_type SentencePieceTokenizer \\
      --tokenizer_model tokenizer.model --train_iters 1000 \\
      --tensor_model_parallel_size 8 --sequence_parallel --bf16
"""

from __future__ import annotations

import jax

from megatron_llm_tpu.arguments import args_to_configs, build_base_parser
from megatron_llm_tpu.models import FalconModel, GPTModel, LlamaModel
from megatron_llm_tpu.parallel import initialize_parallel
from megatron_llm_tpu.tokenizer import build_tokenizer
from megatron_llm_tpu.training.trainer import pretrain


def model_provider(args, mcfg):
    """ref: model_provider (finetune.py:33-63)."""
    if args.model_name in ("llama", "llama2", "codellama"):
        return LlamaModel(mcfg)
    if args.model_name == "falcon":
        return FalconModel(mcfg)
    if args.model_name in ("bert", "t5"):
        # The shared Trainer path here feeds GPT-style batches
        # (tokens/labels/position_ids/causal mask) which the encoder
        # models' loss signatures don't accept, and dataset_provider
        # builds GPT token streams, not masked-LM corpora.
        raise SystemExit(
            f"--model_name {args.model_name}: to PRETRAIN use "
            f"pretrain_{args.model_name}.py (masked-LM/span-corruption data "
            "+ matching batch builder); to FINETUNE a pretrained encoder on "
            "GLUE/RACE use tasks/main.py"
        )
    return GPTModel(mcfg)


def main(argv=None):
    from megatron_llm_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    parser = build_base_parser()
    args = parser.parse_args(argv)

    tokenizer = None
    vocab_size = 0
    if args.tokenizer_type:
        tokenizer = build_tokenizer(
            args.tokenizer_type,
            vocab_file=args.vocab_file,
            merges_file=args.merges_file,
            tokenizer_model=args.tokenizer_model,
            make_vocab_size_divisible_by=args.make_vocab_size_divisible_by,
            tensor_parallel_size=args.tensor_model_parallel_size,
            vocab_extra_ids=args.vocab_extra_ids,
            vocab_extra_ids_list=args.vocab_extra_ids_list,
            new_tokens=args.new_tokens,
            null_vocab_size=args.null_vocab_size,
        )
        vocab_size = tokenizer.vocab_size

    from megatron_llm_tpu.parallel.mesh import maybe_initialize_distributed

    maybe_initialize_distributed()
    mcfg, pcfg, tcfg, dargs = args_to_configs(args, vocab_size)
    if args.use_checkpoint_args and args.load:
        from megatron_llm_tpu.training.checkpointing import (
            load_model_config_from_checkpoint,
        )

        mcfg = load_model_config_from_checkpoint(args.load, mcfg)

    print(f"devices: {len(jax.devices())} ({jax.default_backend()}); "
          f"mesh dp={pcfg.data_parallel_size} pp={pcfg.pipeline_parallel_size} "
          f"cp={pcfg.context_parallel_size} tp={pcfg.tensor_parallel_size} "
          f"sp={pcfg.sequence_parallel}")
    initialize_parallel(
        dp=pcfg.data_parallel_size,
        pp=pcfg.pipeline_parallel_size,
        tp=pcfg.tensor_parallel_size,
        cp=pcfg.context_parallel_size,
        sequence_parallel=pcfg.sequence_parallel,
    )

    model = model_provider(args, mcfg)

    def dataset_provider(train_val_test_num_samples):
        """ref: train_valid_test_datasets_provider (finetune.py:104-126)."""
        from megatron_llm_tpu.data import build_train_valid_test_datasets

        assert dargs.data_path or dargs.train_data_path, (
            "--data_path (or --train_data_path/--valid_data_path/"
            "--test_data_path) is required"
        )
        return build_train_valid_test_datasets(
            data_prefix=dargs.data_path,
            splits_string=dargs.split,
            train_valid_test_num_samples=train_val_test_num_samples,
            seq_length=mcfg.seq_length,
            seed=tcfg.seed,
            train_data_prefix=dargs.train_data_path,
            valid_data_prefix=dargs.valid_data_path,
            test_data_prefix=dargs.test_data_path,
        )

    pretrain(
        model, tcfg, pcfg, dataset_provider,
        eod_token=tokenizer.eod if tokenizer else None,
        reset_position_ids=dargs.reset_position_ids,
        reset_attention_mask=dargs.reset_attention_mask,
        eod_mask_loss=dargs.eod_mask_loss,
    )


if __name__ == "__main__":
    main()
