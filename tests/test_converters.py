"""Weight-converter correctness: round-trips + golden-logit parity vs HF.

This is the rebuild of the reference's correctness gate
(ref: verify_correctness.py:107-122 compares per-token logits vs a
side-by-side HF model, tolerance <= 1e-3 per
tests/test_llama_weights.py:104-106). Real Llama weights aren't in the
image, so the gate runs against randomly-initialized transformers models in
fp32 — which exercises every layout/permutation decision identically.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.config import ModelConfig, falcon_config, llama_config
from megatron_llm_tpu.convert import (
    hf_falcon_to_native,
    hf_llama_to_native,
    native_to_hf_falcon,
    native_to_hf_llama,
)
from megatron_llm_tpu.models import FalconModel, LlamaModel

pytestmark = pytest.mark.slow

torch = pytest.importorskip("torch")


def _tiny_llama_cfg(n_kv=4):
    return llama_config(
        7,
        num_layers=2,
        hidden_size=64,
        num_attention_heads=8,
        num_attention_heads_kv=n_kv,
        ffn_hidden_size=112,
        seq_length=48,
        vocab_size=128,
        max_position_embeddings=48,
        padded_vocab_size=128,
        compute_dtype=jnp.float32,
    )


def _hf_llama(cfg):
    from transformers import LlamaConfig, LlamaForCausalLM

    hf_cfg = LlamaConfig(
        vocab_size=cfg.padded_vocab_size,
        hidden_size=cfg.hidden_size,
        intermediate_size=cfg.ffn_hidden_size,
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_attention_heads_kv,
        max_position_embeddings=cfg.max_position_embeddings,
        rms_norm_eps=cfg.layernorm_epsilon,
        rope_theta=cfg.rope_theta,
        tie_word_embeddings=False,
        attention_bias=False,
        mlp_bias=False,
    )
    torch.manual_seed(0)
    model = LlamaForCausalLM(hf_cfg).float().eval()
    return model


def _sd_numpy(model):
    return {k: v.detach().numpy() for k, v in model.state_dict().items()}


def _max_err(a, b):
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b))))


class TestLlamaConverter:
    @pytest.mark.parametrize("n_kv", [8, 4, 1])  # MHA, GQA, MQA
    def test_logit_parity_vs_hf(self, n_kv):
        """The golden gate: converted weights reproduce HF logits <= 1e-3
        (ref gate: tests/test_llama_weights.py:104-106)."""
        cfg = _tiny_llama_cfg(n_kv)
        hf = _hf_llama(cfg)
        params = hf_llama_to_native(_sd_numpy(hf), cfg)
        params = jax.tree.map(jnp.asarray, params)

        rs = np.random.RandomState(0)
        tokens = rs.randint(0, cfg.padded_vocab_size, (2, 32))
        with torch.no_grad():
            ref_logits = hf(torch.tensor(tokens)).logits.numpy()

        model = LlamaModel(cfg)
        logits, _ = model.forward(params, jnp.asarray(tokens))
        err = _max_err(logits, ref_logits)
        assert err <= 1e-3, f"max |logit diff| = {err}"

    def test_roundtrip_bit_exact(self):
        """native -> HF -> native must be bit-exact
        (VERDICT r1 missing #1 acceptance criterion)."""
        cfg = _tiny_llama_cfg(4)
        model = LlamaModel(cfg)
        params = model.init(jax.random.key(0))
        sd = native_to_hf_llama(params, cfg)
        back = hf_llama_to_native(sd, cfg)

        flat_a, _ = jax.tree_util.tree_flatten_with_path(params)
        flat_b = jax.tree.leaves(back)
        for (path, a), b in zip(flat_a, flat_b):
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b), err_msg=str(path)
            )

    def test_hf_roundtrip_exact(self):
        """HF -> native -> HF preserves every tensor exactly."""
        cfg = _tiny_llama_cfg(4)
        hf = _hf_llama(cfg)
        sd = _sd_numpy(hf)
        back = native_to_hf_llama(hf_llama_to_native(sd, cfg), cfg)
        for k, v in back.items():
            np.testing.assert_array_equal(v, sd[k], err_msg=k)

    def test_loss_parity_vs_hf(self):
        """CE loss through our vocab-parallel CE matches torch CE
        (ref: verify_correctness.py prints loss delta alongside logits)."""
        cfg = _tiny_llama_cfg(4)
        hf = _hf_llama(cfg)
        params = jax.tree.map(jnp.asarray, hf_llama_to_native(_sd_numpy(hf), cfg))

        rs = np.random.RandomState(1)
        data = rs.randint(0, cfg.padded_vocab_size, (2, 33))
        tokens, labels = data[:, :-1], data[:, 1:]
        with torch.no_grad():
            out = hf(torch.tensor(tokens)).logits
            ref_loss = torch.nn.functional.cross_entropy(
                out.reshape(-1, out.shape[-1]), torch.tensor(labels).reshape(-1)
            ).item()
        ours = float(LlamaModel(cfg).loss(
            params, jnp.asarray(tokens), jnp.asarray(labels)
        ))
        assert abs(ours - ref_loss) <= 1e-4, (ours, ref_loss)


class TestConverterCLI:
    def test_hf2native2hf_roundtrip(self, tmp_path):
        """tools/convert_weights.py end-to-end: HF dir -> native release
        checkpoint -> HF dir; weights identical (ref chain:
        tests/test_llama_weights.py:129-180)."""
        import subprocess
        import sys

        cfg = _tiny_llama_cfg(4)
        hf = _hf_llama(cfg)
        hf_dir = tmp_path / "hf_in"
        hf.save_pretrained(hf_dir, safe_serialization=True)

        import os

        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        native = tmp_path / "native"
        out = tmp_path / "hf_out"
        for cmd in (
            ["--model", "llama", "--direction", "hf2native",
             "--input", str(hf_dir), "--output", str(native)],
            ["--model", "llama", "--direction", "native2hf",
             "--input", str(native), "--output", str(out)],
        ):
            r = subprocess.run(
                [sys.executable, os.path.join(repo, "tools/convert_weights.py")]
                + cmd,
                env=env, capture_output=True, text=True,
            )
            assert r.returncode == 0, r.stderr[-2000:]

        from transformers import LlamaForCausalLM

        back = LlamaForCausalLM.from_pretrained(out)
        orig_sd = hf.state_dict()
        for k, v in back.state_dict().items():
            np.testing.assert_array_equal(
                v.float().numpy(), orig_sd[k].float().numpy(), err_msg=k
            )


class TestReleaseCheckpoint:
    def test_release_load_skips_optimizer(self, tmp_path):
        """A converter-written release checkpoint (weights only) must load
        like --finetune: no optimizer restore, iteration 0 (ref: release
        semantics checkpointing.py:93, :583-625)."""
        from megatron_llm_tpu.config import TrainConfig
        from megatron_llm_tpu.optimizer.optimizer import init_optimizer_state
        from megatron_llm_tpu.training.checkpointing import (
            load_checkpoint,
            save_checkpoint,
        )

        cfg = _tiny_llama_cfg(4)
        model = LlamaModel(cfg)
        params = model.init(jax.random.key(3))
        save_checkpoint(str(tmp_path), 0, params, model_cfg=cfg, release=True)

        opt_state = init_optimizer_state(params, TrainConfig(train_iters=1))
        loaded = load_checkpoint(str(tmp_path), params, opt_state, cfg)
        assert loaded is not None
        lparams, lopt, meta, iteration = loaded
        assert lopt is None
        assert iteration == 0
        np.testing.assert_array_equal(
            np.asarray(lparams["lm_head"]), np.asarray(params["lm_head"])
        )


class TestFalconConverter:
    @pytest.mark.parametrize("new_arch", [True, False])
    def test_logit_parity_vs_hf(self, new_arch):
        """Falcon-7b-style (multi_query) and 40b-style (grouped + parallel
        layernorm) both match HF (ref: falcon_to_megatron w2m.py:23-79)."""
        from transformers import FalconConfig, FalconForCausalLM

        n_kv = 2 if new_arch else 1
        cfg = falcon_config(
            7,
            num_layers=2,
            hidden_size=64,
            num_attention_heads=8,
            num_attention_heads_kv=n_kv,
            ffn_hidden_size=256,
            seq_length=48,
            vocab_size=128,
            max_position_embeddings=48,
            padded_vocab_size=128,
            parallel_layernorm=new_arch,
            compute_dtype=jnp.float32,
        )
        hf_cfg = FalconConfig(
            vocab_size=128,
            hidden_size=64,
            num_hidden_layers=2,
            num_attention_heads=8,
            num_kv_heads=n_kv,
            new_decoder_architecture=new_arch,
            multi_query=not new_arch,
            parallel_attn=True,
            bias=False,
            alibi=False,
            rope_theta=cfg.rope_theta,
        )
        torch.manual_seed(1)
        hf = FalconForCausalLM(hf_cfg).float().eval()
        params = jax.tree.map(jnp.asarray, hf_falcon_to_native(_sd_numpy(hf), cfg))

        rs = np.random.RandomState(2)
        tokens = rs.randint(0, 128, (2, 24))
        with torch.no_grad():
            ref_logits = hf(torch.tensor(tokens)).logits.numpy()
        logits, _ = FalconModel(cfg).forward(params, jnp.asarray(tokens))
        err = _max_err(logits, ref_logits)
        assert err <= 1e-3, f"max |logit diff| = {err}"

    def test_roundtrip_exact(self):
        from transformers import FalconConfig, FalconForCausalLM

        cfg = falcon_config(
            7,
            num_layers=2,
            hidden_size=64,
            num_attention_heads=8,
            num_attention_heads_kv=1,
            ffn_hidden_size=256,
            seq_length=48,
            vocab_size=128,
            max_position_embeddings=48,
            padded_vocab_size=128,
            compute_dtype=jnp.float32,
        )
        hf_cfg = FalconConfig(
            vocab_size=128, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=8, new_decoder_architecture=False,
            multi_query=True, parallel_attn=True, bias=False, alibi=False,
        )
        torch.manual_seed(2)
        hf = FalconForCausalLM(hf_cfg).float().eval()
        sd = _sd_numpy(hf)
        back = native_to_hf_falcon(hf_falcon_to_native(sd, cfg), cfg)
        for k in back:
            np.testing.assert_array_equal(back[k], sd[k], err_msg=k)
