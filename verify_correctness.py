#!/usr/bin/env python
"""Golden-logit correctness gate: native model vs side-by-side HuggingFace.

The rebuild of ref verify_correctness.py:107-122 — runs both
implementations on the same batches and prints per-iteration max/avg
absolute logit error and the loss delta. Gate: avg max-abs logit error
<= --tolerance (1e-3 fp32, the reference's own test gate,
ref: tests/test_llama_weights.py:104-106; docs allow 0.01 fp32 / 0.1 fp16,
docs/guide/getting_started.md:152).

With --hf_dir it verifies a real checkpoint; without, it builds a randomly
initialized small HF model (same code path transformers uses for the real
one) so the gate runs hermetically in CI.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", choices=["llama", "falcon"], default="llama")
    p.add_argument("--hf_dir", default=None,
                   help="HF checkpoint dir; omit for a random hermetic model")
    p.add_argument("--iters", type=int, default=4)
    p.add_argument("--batch_size", type=int, default=2)
    p.add_argument("--seq_length", type=int, default=64)
    p.add_argument("--tolerance", type=float, default=1e-3)
    # hermetic-model architecture knobs
    p.add_argument("--num_layers", type=int, default=4)
    p.add_argument("--hidden_size", type=int, default=128)
    p.add_argument("--num_heads", type=int, default=8)
    p.add_argument("--num_kv_heads", type=int, default=4)
    p.add_argument("--vocab_size", type=int, default=512)
    p.add_argument("--dump_layer_errors", action="store_true",
                   help="per-layer hidden-state max-abs error vs HF on the "
                        "first batch — localizes drift to the layer that "
                        "introduces it (release-gate debugging aid)")
    args = p.parse_args()

    from megatron_llm_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    import torch
    from transformers import AutoModelForCausalLM, LlamaConfig, LlamaForCausalLM

    import jax
    import jax.numpy as jnp

    # Correctness gates compare against torch's true-fp32 matmuls. JAX's
    # default matmul precision lowers fp32 matmul inputs (bf16-class passes;
    # ~1e-3 relative error per matmul on both CPU and TPU), which compounds
    # with depth — a 4-layer/h128 model drifts to ~6e-3 max-abs logit error.
    # Pin the highest precision so an fp32 run is actually fp32; this is the
    # analogue of the reference running its gate in full torch fp32
    # (ref: tests/test_llama_weights.py:104-106).
    jax.config.update("jax_default_matmul_precision", "highest")

    from megatron_llm_tpu.convert import hf_falcon_to_native, hf_llama_to_native
    from megatron_llm_tpu.models import FalconModel, LlamaModel
    from tools.convert_weights import _model_cfg_from_hf

    if args.hf_dir:
        hf = AutoModelForCausalLM.from_pretrained(
            args.hf_dir, torch_dtype=torch.float32
        ).eval()
    elif args.model == "llama":
        hf = LlamaForCausalLM(LlamaConfig(
            vocab_size=args.vocab_size, hidden_size=args.hidden_size,
            intermediate_size=int(args.hidden_size * 8 / 3 // 16 * 16),
            num_hidden_layers=args.num_layers,
            num_attention_heads=args.num_heads,
            num_key_value_heads=args.num_kv_heads,
            max_position_embeddings=max(2048, args.seq_length),
            tie_word_embeddings=False, attention_bias=False, mlp_bias=False,
        )).float().eval()
    else:
        # hermetic falcon: --num_kv_heads 1 builds the 7b MQA style,
        # >1 the 40b grouped (new_decoder_architecture) style — both
        # converter layouts get exercised
        from transformers import FalconConfig, FalconForCausalLM

        mqa = args.num_kv_heads == 1
        hf = FalconForCausalLM(FalconConfig(
            vocab_size=args.vocab_size, hidden_size=args.hidden_size,
            num_hidden_layers=args.num_layers,
            num_attention_heads=args.num_heads,
            num_kv_heads=args.num_kv_heads,
            multi_query=mqa, new_decoder_architecture=not mqa,
            parallel_attn=True, bias=False, alibi=False,
        )).float().eval()

    cfg = _model_cfg_from_hf(args.model, hf.config, "float32")
    import dataclasses
    cfg = dataclasses.replace(cfg, compute_dtype=jnp.float32)
    sd = {k: v.detach().numpy() for k, v in hf.state_dict().items()}
    convert = hf_llama_to_native if args.model == "llama" else hf_falcon_to_native
    params = jax.tree.map(jnp.asarray, convert(sd, cfg))
    model = (LlamaModel if args.model == "llama" else FalconModel)(cfg)

    def dump_layer_errors(tokens):
        """Per-layer hidden-state drift vs HF (embedding + each block),
        running the native stack layer by layer."""
        from megatron_llm_tpu.models.language_model import embed_tokens
        from megatron_llm_tpu.models.rope import precompute_rope
        from megatron_llm_tpu.models.transformer import transformer_layer

        with torch.no_grad():
            hf_states = hf(torch.tensor(tokens),
                           output_hidden_states=True).hidden_states
        rope = None
        if cfg.position_embedding_type == "rotary":
            rope = precompute_rope(cfg.head_dim, cfg.max_position_embeddings,
                                   cfg.rope_theta, cfg.rope_scaling_factor)
        from megatron_llm_tpu.models.norms import apply_norm

        h = embed_tokens(params, cfg, jnp.asarray(tokens))
        for i in range(cfg.num_layers + 1):
            if i > 0:
                layer_p = jax.tree.map(lambda x: x[i - 1], params["layers"])
                h, _ = transformer_layer(layer_p, cfg, h, rope, None, None)
            # transformers' LAST hidden state is post-final-norm
            h_cmp = (apply_norm(h, params["final_norm"], cfg)
                     if i == cfg.num_layers else h)
            err = float(np.abs(
                np.asarray(h_cmp, np.float32) - hf_states[i].numpy()
            ).max())
            name = "embedding" if i == 0 else f"layer {i - 1}"
            if i == cfg.num_layers:
                name += " (+final norm)"
            print(f"  {name:>22s}: max abs hidden error {err:.3e}",
                  flush=True)

    fwd = jax.jit(lambda p, t: model.forward(p, t)[0])
    rs = np.random.RandomState(0)
    max_errs, ok = [], True
    for it in range(args.iters):
        data = rs.randint(
            0, min(cfg.padded_vocab_size, hf.config.vocab_size),
            (args.batch_size, args.seq_length + 1),
        )
        tokens, labels = data[:, :-1], data[:, 1:]
        with torch.no_grad():
            out = hf(torch.tensor(tokens)).logits
            ref_loss = torch.nn.functional.cross_entropy(
                out.reshape(-1, out.shape[-1]),
                torch.tensor(labels).reshape(-1),
            ).item()
        ref_logits = out.numpy()
        ours_logits = np.asarray(fwd(params, jnp.asarray(tokens)))[
            ..., : ref_logits.shape[-1]
        ]
        our_loss = float(model.loss(
            params, jnp.asarray(tokens), jnp.asarray(labels)
        ))
        abs_err = np.abs(ours_logits - ref_logits)
        max_err, avg_err = float(abs_err.max()), float(abs_err.mean())
        max_errs.append(max_err)
        if args.dump_layer_errors and it == 0:
            dump_layer_errors(tokens)
        # ref verify_correctness.py prints this exact breakdown per iter
        print(
            f"iteration {it}: max abs logit error {max_err:.3e} | "
            f"avg abs logit error {avg_err:.3e} | "
            f"our loss {our_loss:.6f} | hf loss {ref_loss:.6f} | "
            f"loss delta {abs(our_loss - ref_loss):.3e}",
            flush=True,
        )

    avg_max = float(np.mean(max_errs))
    ok = avg_max <= args.tolerance
    print(f"avg max-abs logit error over {args.iters} iters: {avg_max:.3e} "
          f"({'OK' if ok else 'FAIL'}, tolerance {args.tolerance})", flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
