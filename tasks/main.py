#!/usr/bin/env python
"""Downstream-task entry point (ref: /root/reference/tasks/main.py).

  python tasks/main.py --task WIKITEXT103 --model_name llama2 \\
      --valid_data wiki.test.tokens --tokenizer_type SentencePieceTokenizer \\
      --tokenizer_model tokenizer.model --load <checkpoint_dir>

  python tasks/main.py --task LAMBADA --valid_data lambada.jsonl ...

Classification finetuning (BERT encoder + task head, epoch loop with
per-epoch validation accuracy):

  python tasks/main.py --task MNLI --train_data train.tsv \\
      --valid_data dev_matched.tsv --pretrained_checkpoint ckpts/bert \\
      --epochs 3 --lr 5e-5 ...   (QQP and RACE likewise)

Without --load / --pretrained_checkpoint the model runs at random init
(useful for smoke runs only). The REALM/retriever finetune family is not
implemented.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                os.pardir)))

import jax


def get_tasks_args(parser):
    """ref: get_tasks_args (tasks/main.py:14-72), minus the retriever/faiss
    group that belongs to the REALM stack."""
    g = parser.add_argument_group("tasks")
    g.add_argument("--task", type=str, required=True,
                   choices=["WIKITEXT103", "LAMBADA", "MNLI", "QQP", "RACE",
                            "MSDP-PROMPT", "MSDP-EVAL-F1",
                            "RETRIEVER-EVAL", "ICT-ZEROSHOT-NQ",
                            "RET-FINETUNE-NQ"])
    g.add_argument("--train_data", nargs="+", default=None)
    g.add_argument("--valid_data", nargs="*", default=None)
    g.add_argument("--overlapping_eval", type=int, default=32)
    g.add_argument("--strict_lambada", action="store_true")
    g.add_argument("--eval_micro_batch_size", type=int, default=None)
    g.add_argument("--epochs", type=int, default=3)
    g.add_argument("--pretrained_checkpoint", type=str, default=None)
    # MSDP (ref: tasks/msdp/main.py get_tasks_args)
    g.add_argument("--sample_input_file", type=str, default=None)
    g.add_argument("--sample_output_file", type=str, default=None)
    g.add_argument("--prompt_file", type=str, default=None)
    g.add_argument("--prompt_type", type=str, default=None,
                   choices=[None, "knowledge", "response"])
    g.add_argument("--num_prompt_examples", type=int, default=10)
    g.add_argument("--guess_file", type=str, default=None)
    g.add_argument("--answer_file", type=str, default=None)
    g.add_argument("--out_seq_length", type=int, default=100)
    # ORQA retriever eval (ref: tasks/main.py:56-72 + orqa args)
    g.add_argument("--qa_data_dev", type=str, default=None)
    g.add_argument("--qa_data_test", type=str, default=None)
    g.add_argument("--evidence_data_path", type=str, default=None)
    # prebuilt evidence index (tools/build_retrieval_index.py output);
    # omitted -> embed the evidence on the fly
    g.add_argument("--embedding_path", type=str, default=None)
    g.add_argument("--retriever_seq_length", type=int, default=256)
    g.add_argument("--retriever_topk", type=int, default=20)
    g.add_argument("--match", type=str, default="string",
                   choices=["string", "regex"])
    g.add_argument("--biencoder_shared_query_context_model",
                   action="store_true")
    g.add_argument("--biencoder_projection_dim", type=int, default=0)
    g.add_argument("--use_hard_negatives", action="store_true")
    return parser


def _finetune_main(args):
    """Classification finetuning dispatch (ref: tasks/glue/finetune.py +
    tasks/race/finetune.py through finetune_utils.finetune)."""
    import dataclasses

    from megatron_llm_tpu.arguments import args_to_configs
    from megatron_llm_tpu.parallel import initialize_parallel
    from megatron_llm_tpu.tokenizer import build_tokenizer
    from megatron_llm_tpu.training.checkpointing import load_checkpoint

    from megatron_llm_tpu.models.classification import (
        Classification,
        MultipleChoice,
    )
    from tasks.finetune_utils import accuracy, finetune

    tokenizer = build_tokenizer(
        args.tokenizer_type or "BertWordPieceLowerCase",
        vocab_file=args.vocab_file,
        make_vocab_size_divisible_by=args.make_vocab_size_divisible_by,
        tensor_parallel_size=args.tensor_model_parallel_size,
    )
    args.model_name = "bert"
    mcfg, pcfg, tcfg, _ = args_to_configs(args, tokenizer.vocab_size)
    mcfg = dataclasses.replace(mcfg, add_binary_head=False)
    assert pcfg.context_parallel_size == 1, (
        "--context_parallel_size: ring attention is causal-only; "
        "encoder finetuning tasks don't support cp"
    )
    initialize_parallel(dp=pcfg.data_parallel_size, pp=1,
                        tp=pcfg.tensor_parallel_size,
                        sequence_parallel=pcfg.sequence_parallel)

    if args.task == "MNLI":
        from tasks.glue.mnli import MNLIDataset as DS

        model = Classification(mcfg, num_classes=3)
    elif args.task == "QQP":
        from tasks.glue.qqp import QQPDataset as DS

        model = Classification(mcfg, num_classes=2)
    else:  # RACE
        from tasks.race.data import RaceDataset as DS

        model = MultipleChoice(mcfg)

    params = model.init(jax.random.key(tcfg.seed))
    # --load is the generic flag the LM-eval path uses; accept it as an
    # alias for --pretrained_checkpoint here
    if not args.pretrained_checkpoint and args.load:
        args.pretrained_checkpoint = args.load
    if args.pretrained_checkpoint:
        # Load ENCODER weights from a BERT pretraining checkpoint; heads
        # stay freshly initialized (the reference's strict=False load,
        # finetune_utils.py:291-312). Orbax restores against the exact
        # saved tree, so restore into a pretraining-shaped template and
        # merge the overlapping subtrees.
        from megatron_llm_tpu.models import BertModel as _Bert

        loaded, errors = None, []
        for binary in (True, False):
            tmpl_cfg = dataclasses.replace(mcfg, add_binary_head=binary)
            tmpl = jax.eval_shape(
                _Bert(tmpl_cfg).init, jax.random.key(0)
            )
            try:
                restored = load_checkpoint(
                    args.pretrained_checkpoint, tmpl, no_load_optim=True,
                    finetune=True,
                )
            except Exception as e:
                errors.append(f"binary_head={binary}: {e!r}")
                continue
            if restored is not None:
                loaded = restored[0]
                break
        assert loaded is not None, (
            f"could not restore encoder weights from "
            f"{args.pretrained_checkpoint}; attempts: {errors}"
        )
        for key in params:
            if key in loaded:
                params[key] = loaded[key]
        print(" > loaded pretrained encoder weights "
              f"({sorted(set(params) & set(loaded))})", flush=True)

    assert args.train_data, f"--train_data is required for {args.task}"
    train_ds = DS("training", args.train_data, tokenizer, mcfg.seq_length)
    valid_ds = (DS("validation", args.valid_data, tokenizer,
                   mcfg.seq_length) if args.valid_data else None)
    params, best = finetune(
        model, params, train_ds, valid_ds, epochs=args.epochs,
        batch_size=args.micro_batch_size, lr=tcfg.lr,
        weight_decay=tcfg.weight_decay, seed=tcfg.seed,
        warmup_fraction=(args.lr_warmup_fraction
                         if args.lr_warmup_fraction is not None else 0.065),
        tcfg=tcfg, log_interval=args.log_interval,
    )
    if valid_ds is not None:
        final = accuracy(model, params, valid_ds, args.micro_batch_size)
        print(f"final validation accuracy: {final:.4f} (best {best:.4f})",
              flush=True)
    if args.save:
        from megatron_llm_tpu.training.checkpointing import save_checkpoint

        save_checkpoint(args.save, 0, params, None, mcfg)
        print(f"saved finetuned weights to {args.save}", flush=True)


def _retriever_eval_main(args):
    """Biencoder retriever accuracy on NQ (ref: tasks/orqa/evaluate_orqa.py
    + evaluate_utils.py): embed the evidence TSV with the context tower,
    embed the questions with the query tower, MIPS on-device, report
    top-k answer-containment accuracy."""
    import dataclasses

    from megatron_llm_tpu.arguments import args_to_configs
    from megatron_llm_tpu.models.biencoder import BiEncoderModel
    from megatron_llm_tpu.parallel import initialize_parallel
    from megatron_llm_tpu.tokenizer import build_tokenizer
    from megatron_llm_tpu.training.checkpointing import load_checkpoint

    from tasks.orqa.evaluate import ORQAEvaluator, read_evidence_tsv

    assert args.evidence_data_path, "--evidence_data_path is required"
    assert args.qa_data_dev or args.qa_data_test, (
        "--qa_data_dev and/or --qa_data_test is required"
    )
    tokenizer = build_tokenizer(
        args.tokenizer_type or "BertWordPieceLowerCase",
        vocab_file=args.vocab_file,
        make_vocab_size_divisible_by=args.make_vocab_size_divisible_by,
        tensor_parallel_size=args.tensor_model_parallel_size,
    )
    args.model_name = "bert"
    mcfg, pcfg, tcfg, _ = args_to_configs(args, tokenizer.vocab_size)
    mcfg = dataclasses.replace(mcfg, add_binary_head=False)
    initialize_parallel(dp=pcfg.data_parallel_size, pp=1,
                        tp=pcfg.tensor_parallel_size)

    model = BiEncoderModel(
        mcfg,
        projection_dim=args.biencoder_projection_dim,
        shared_query_context_model=args.biencoder_shared_query_context_model,
    )
    params = model.init(jax.random.key(tcfg.seed))
    if args.load:
        restored = load_checkpoint(args.load, params, no_load_optim=True,
                                   finetune=True)
        assert restored is not None, f"no checkpoint found in {args.load}"
        params = restored[0]

    evaluator = ORQAEvaluator(
        model, params, tokenizer,
        seq_length=args.retriever_seq_length,
        batch_size=args.micro_batch_size,
    )
    docs = read_evidence_tsv(args.evidence_data_path)
    if args.embedding_path:
        print(f" > loading prebuilt index {args.embedding_path} ...",
              flush=True)
        evaluator.load_index(docs, args.embedding_path)
    else:
        print(f" > embedding {len(docs)} evidence blocks ...", flush=True)
        evaluator.build_index(docs)
    if args.qa_data_dev:
        evaluator.evaluate(args.qa_data_dev, "DEV",
                           topk=args.retriever_topk,
                           match_type=args.match)
    if args.qa_data_test:
        evaluator.evaluate(args.qa_data_test, "TEST",
                           topk=args.retriever_topk,
                           match_type=args.match)


def _retriever_finetune_main(args):
    """Supervised biencoder finetuning on DPR-format NQ
    (ref: tasks/orqa/supervised/finetune.py, RET-FINETUNE-NQ)."""
    import dataclasses

    from megatron_llm_tpu.arguments import args_to_configs
    from megatron_llm_tpu.models.biencoder import BiEncoderModel
    from megatron_llm_tpu.parallel import initialize_parallel
    from megatron_llm_tpu.tokenizer import build_tokenizer
    from megatron_llm_tpu.training.checkpointing import load_checkpoint

    from tasks.orqa.supervised import (
        OpenRetrievalDataset,
        finetune_retriever,
    )

    assert args.train_data, "--train_data (DPR-format json) is required"
    tokenizer = build_tokenizer(
        args.tokenizer_type or "BertWordPieceLowerCase",
        vocab_file=args.vocab_file,
        make_vocab_size_divisible_by=args.make_vocab_size_divisible_by,
        tensor_parallel_size=args.tensor_model_parallel_size,
    )
    args.model_name = "bert"
    mcfg, pcfg, tcfg, _ = args_to_configs(args, tokenizer.vocab_size)
    mcfg = dataclasses.replace(mcfg, add_binary_head=False)
    initialize_parallel(dp=pcfg.data_parallel_size, pp=1,
                        tp=pcfg.tensor_parallel_size)

    model = BiEncoderModel(
        mcfg,
        projection_dim=args.biencoder_projection_dim,
        shared_query_context_model=args.biencoder_shared_query_context_model,
    )
    params = model.init(jax.random.key(tcfg.seed))
    if not args.pretrained_checkpoint and args.load:
        args.pretrained_checkpoint = args.load
    if args.pretrained_checkpoint:
        restored = load_checkpoint(args.pretrained_checkpoint, params,
                                   no_load_optim=True, finetune=True)
        assert restored is not None, (
            f"no checkpoint in {args.pretrained_checkpoint}"
        )
        params = restored[0]

    train_ds = OpenRetrievalDataset(
        args.train_data[0], tokenizer,
        max_seq_length=args.retriever_seq_length,
        use_hard_negatives=args.use_hard_negatives, seed=tcfg.seed,
    )
    valid_ds = (OpenRetrievalDataset(
        args.valid_data[0], tokenizer,
        max_seq_length=args.retriever_seq_length, seed=tcfg.seed)
        if args.valid_data else None)
    params = finetune_retriever(
        model, params, train_ds, valid_ds, epochs=args.epochs,
        batch_size=args.micro_batch_size, lr=tcfg.lr,
        use_hard_negatives=args.use_hard_negatives, seed=tcfg.seed,
        log_interval=args.log_interval,
    )
    if args.save:
        from megatron_llm_tpu.training.checkpointing import save_checkpoint

        save_checkpoint(args.save, 0, params, None, mcfg)
        print(f"saved finetuned retriever to {args.save}", flush=True)


def main(argv=None):
    from megatron_llm_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from megatron_llm_tpu.arguments import args_to_configs, build_base_parser
    from megatron_llm_tpu.parallel import initialize_parallel
    from megatron_llm_tpu.tokenizer import build_tokenizer
    from megatron_llm_tpu.training.checkpointing import load_checkpoint

    from finetune import model_provider
    from tasks.zeroshot.datasets import build_dataset
    from tasks.zeroshot.evaluate import evaluate_and_print_results

    parser = get_tasks_args(build_base_parser())
    args = parser.parse_args(argv)
    if args.task in ("MNLI", "QQP", "RACE"):
        _finetune_main(args)
        print("done :-)")
        return
    if args.task == "MSDP-EVAL-F1":
        # pure file-vs-file metric, no model (ref: tasks/msdp/evaluate.py)
        assert args.guess_file and args.answer_file, (
            "MSDP-EVAL-F1 needs --guess_file and --answer_file"
        )
        from tasks.msdp.evaluate import main as msdp_eval_main

        msdp_eval_main(args)
        print("done :-)")
        return
    if args.task in ("RETRIEVER-EVAL", "ICT-ZEROSHOT-NQ"):
        _retriever_eval_main(args)
        print("done :-)")
        return
    if args.task == "RET-FINETUNE-NQ":
        _retriever_finetune_main(args)
        print("done :-)")
        return
    if args.task == "MSDP-PROMPT":
        assert args.sample_input_file and args.sample_output_file \
            and args.prompt_file and args.prompt_type, (
                "MSDP-PROMPT needs --sample_input_file, "
                "--sample_output_file, --prompt_file, --prompt_type"
            )
    else:
        assert args.valid_data and len(args.valid_data) == 1, \
            "--valid_data takes exactly one path"

    tokenizer = build_tokenizer(
        args.tokenizer_type or "NullTokenizer",
        vocab_file=args.vocab_file,
        merges_file=args.merges_file,
        tokenizer_model=args.tokenizer_model,
        make_vocab_size_divisible_by=args.make_vocab_size_divisible_by,
        tensor_parallel_size=args.tensor_model_parallel_size,
        null_vocab_size=args.null_vocab_size,
    )
    mcfg, pcfg, tcfg, _ = args_to_configs(args, tokenizer.vocab_size)

    initialize_parallel(
        dp=pcfg.data_parallel_size,
        pp=pcfg.pipeline_parallel_size,
        tp=pcfg.tensor_parallel_size,
        cp=pcfg.context_parallel_size,
        sequence_parallel=pcfg.sequence_parallel,
    )

    model = model_provider(args, mcfg)
    params = model.init(jax.random.key(tcfg.seed))
    if args.load:
        restored = load_checkpoint(args.load, params, model_cfg=mcfg,
                                   no_load_optim=True)
        assert restored is not None, f"no checkpoint found in {args.load}"
        params = restored[0]

    if args.task == "MSDP-PROMPT":
        from tasks.msdp.prompt import main as msdp_prompt_main

        msdp_prompt_main(args, model=model, params=params,
                         tokenizer=tokenizer)
        print("done :-)")
        return

    data = build_dataset(
        args.task, args.valid_data[0], tokenizer, mcfg.seq_length,
        overlapping_eval=args.overlapping_eval,
        strict_lambada=args.strict_lambada,
    )
    print(f" > found {len(data)} samples.")
    evaluate_and_print_results(
        args.task, model, params, data,
        micro_batch_size=args.eval_micro_batch_size or args.micro_batch_size,
        log_interval=args.log_interval,
    )
    print("done :-)")


if __name__ == "__main__":
    main()
