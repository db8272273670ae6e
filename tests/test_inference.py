"""Inference runtime correctness: decode loop, sampling, beam, server.

The reference gates its generation stack through server-level tests
(ref: tests/test_llama_weights.py:129-180 drives the full stack;
text_generation/generation.py:89-286 is the loop under test here). These
tests pin the jitted while-loop decode against oracle implementations:
greedy decode == step-by-step argmax of full teacher-forced forwards,
log_probs == score_tokens on the generated sequence, top-k/top-p filters
== numpy re-derivations, beam search == exhaustive search on a tiny vocab,
and the REST server's validation + round-trip contract.
"""

import json
import threading
from http.client import HTTPConnection

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.config import tiny_config
from megatron_llm_tpu.models import LlamaModel
from megatron_llm_tpu.inference.generation import (
    beam_search,
    generate_tokens,
    score_tokens,
)
from megatron_llm_tpu.inference.sampling import (
    NEG_INF,
    modify_logits_for_top_k,
    modify_logits_for_top_p,
    sample,
)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tiny_config(compute_dtype=jnp.float32)
    model = LlamaModel(cfg)
    params = model.init(jax.random.key(7))
    return model, params


class ByteTokenizer:
    """Char-level tokenizer for round-trip tests (vocab = 256 bytes)."""

    vocab_size = 256
    eod = 0
    bos = 1

    def tokenize(self, text):
        return [b % 256 for b in text.encode()]

    def detokenize(self, ids):
        return bytes(int(i) % 256 for i in ids).decode(errors="replace")


# ---------------------------------------------------------------------------
# Decode loop
# ---------------------------------------------------------------------------


def _oracle_greedy(model, params, tokens, lengths, steps):
    """Step-by-step argmax with FULL (uncached) forwards — the oracle the
    KV-cached while-loop must match."""
    toks = np.asarray(tokens).copy()
    b, max_len = toks.shape
    for t in range(1, max_len):
        logits, _ = model.forward(params, jnp.asarray(toks[:, :t]))
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        for i in range(b):
            if t >= lengths[i]:  # past this row's prompt: generate
                toks[i, t] = nxt[i]
    return toks


def test_greedy_decode_matches_uncached_argmax(tiny_model):
    model, params = tiny_model
    rs = np.random.RandomState(0)
    max_len = 24
    tokens = rs.randint(2, 256, (3, max_len)).astype(np.int32)
    lengths = np.asarray([4, 7, 5], np.int32)

    out = generate_tokens(
        model, params, jnp.asarray(tokens), jnp.asarray(lengths),
        prefill_len=4, rng=None, top_k=1, termination_id=None,
        use_eod_for_early_termination=False,
    )
    oracle = _oracle_greedy(model, params, tokens, lengths, max_len)
    np.testing.assert_array_equal(np.asarray(out.tokens), oracle)
    # prompt regions are preserved (teacher forcing)
    for i, n in enumerate(lengths):
        np.testing.assert_array_equal(
            np.asarray(out.tokens)[i, :n], tokens[i, :n]
        )


def test_log_probs_align_with_score_tokens(tiny_model):
    model, params = tiny_model
    rs = np.random.RandomState(1)
    tokens = rs.randint(2, 256, (2, 16)).astype(np.int32)
    lengths = np.asarray([3, 3], np.int32)
    out = generate_tokens(
        model, params, jnp.asarray(tokens), jnp.asarray(lengths),
        prefill_len=3, rng=None, top_k=1, termination_id=None,
        use_eod_for_early_termination=False, return_log_probs=True,
    )
    # score the final sequences: lp[:, i] = log P(tok[i+1] | tok[:i+1])
    ref_lp = np.asarray(score_tokens(model, params, out.tokens))
    np.testing.assert_allclose(
        np.asarray(out.log_probs), ref_lp, rtol=1e-4, atol=1e-4
    )


def test_eod_early_termination_lengths(tiny_model):
    model, params = tiny_model
    rs = np.random.RandomState(2)
    tokens = rs.randint(2, 256, (2, 24)).astype(np.int32)
    lengths = np.asarray([4, 4], np.int32)
    # first run without early stop to learn what greedy emits
    free = generate_tokens(
        model, params, jnp.asarray(tokens), jnp.asarray(lengths),
        prefill_len=4, rng=None, top_k=1, termination_id=None,
        use_eod_for_early_termination=False,
    )
    free_toks = np.asarray(free.tokens)
    # pick the token generated at position 8 of row 0 as the "eod"
    eod = int(free_toks[0, 8])
    out = generate_tokens(
        model, params, jnp.asarray(tokens), jnp.asarray(lengths),
        prefill_len=4, rng=None, top_k=1, termination_id=eod,
        use_eod_for_early_termination=True,
    )
    out_lens = np.asarray(out.lengths)
    # row 0 must be marked done exactly where that token first appears
    gen_region = free_toks[0, 4:]
    first = 4 + int(np.argmax(gen_region == eod))
    assert out_lens[0] == first + 1
    # tokens up to the stop point match the unconstrained run
    np.testing.assert_array_equal(
        np.asarray(out.tokens)[0, : first + 1], free_toks[0, : first + 1]
    )


def test_sampled_decode_respects_vocab_clamp(tiny_model):
    model, params = tiny_model
    rs = np.random.RandomState(3)
    tokens = rs.randint(2, 200, (2, 16)).astype(np.int32)
    lengths = np.asarray([3, 3], np.int32)
    out = generate_tokens(
        model, params, jnp.asarray(tokens), jnp.asarray(lengths),
        prefill_len=3, rng=jax.random.key(0), top_k=0, top_p=0.9,
        temperature=0.8, vocab_size=200, termination_id=None,
        use_eod_for_early_termination=False,
    )
    assert int(np.asarray(out.tokens).max()) < 200


# ---------------------------------------------------------------------------
# Sampling filters vs numpy oracles (ref: sampling.py:14-93)
# ---------------------------------------------------------------------------


def test_top_k_filter_vs_numpy():
    rs = np.random.RandomState(0)
    logits = rs.randn(4, 64).astype(np.float32)
    got = np.asarray(modify_logits_for_top_k(jnp.asarray(logits), 5))
    for row_in, row_out in zip(logits, got):
        keep = np.argsort(row_in)[-5:]
        mask = np.zeros(64, bool)
        mask[keep] = True
        np.testing.assert_array_equal(row_out[mask], row_in[mask])
        assert (row_out[~mask] == NEG_INF).all()


def test_top_p_filter_shift_by_one_vs_numpy():
    rs = np.random.RandomState(1)
    logits = rs.randn(4, 64).astype(np.float32)
    top_p = 0.6
    got = np.asarray(modify_logits_for_top_p(jnp.asarray(logits), top_p))
    for row_in, row_out in zip(logits, got):
        order = np.argsort(-row_in)
        probs = np.exp(row_in - row_in.max())
        probs /= probs.sum()
        cum = np.cumsum(probs[order])
        # keep every token up to and INCLUDING the first that crosses top_p
        # (the reference's shift-by-1, sampling.py:30-38)
        crossed = cum > top_p
        kill_sorted = np.concatenate([[False], crossed[:-1]])
        kill = np.zeros(64, bool)
        kill[order] = kill_sorted
        np.testing.assert_array_equal(row_out[~kill], row_in[~kill])
        assert (row_out[kill] == NEG_INF).all()


def test_sample_greedy_and_padded_vocab():
    rs = np.random.RandomState(2)
    logits = rs.randn(8, 32).astype(np.float32)
    # greedy = argmax
    got = np.asarray(sample(jnp.asarray(logits), rng=None, top_k=1))
    np.testing.assert_array_equal(got, logits.argmax(-1))
    # padded vocab never sampled even with hot logits in the pad region
    logits[:, 30:] = 50.0
    for seed in range(20):
        got = np.asarray(sample(
            jnp.asarray(logits), rng=jax.random.key(seed), top_k=5,
            vocab_size=30,
        ))
        assert got.max() < 30


def test_temperature_flattens_distribution():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]] * 2000, jnp.float32)
    draws_hot = np.asarray(
        jax.vmap(lambda i: sample(
            logits[:1], rng=jax.random.fold_in(jax.random.key(0), i),
            top_k=4, temperature=10.0,
        ))(jnp.arange(300))
    )
    draws_cold = np.asarray(
        jax.vmap(lambda i: sample(
            logits[:1], rng=jax.random.fold_in(jax.random.key(1), i),
            top_k=4, temperature=0.1,
        ))(jnp.arange(300))
    )
    # cold temperature concentrates on argmax; hot spreads out
    assert (draws_cold == 0).mean() > 0.95
    assert (draws_hot == 0).mean() < 0.6


# ---------------------------------------------------------------------------
# Beam search vs exhaustive (tiny vocab)
# ---------------------------------------------------------------------------


@pytest.mark.slow  # 71 s on the sandbox: over tier-1's 60 s a test
def test_beam_search_finds_exhaustive_best(tiny_model):
    model, params = tiny_model
    vocab = 16  # restrict scoring to a tiny effective vocab
    stop = 15
    rs = np.random.RandomState(4)
    prompt_len, steps = 3, 2
    max_len = prompt_len + steps
    prompt = rs.randint(2, vocab, (1, prompt_len)).astype(np.int32)
    buf = np.full((1, max_len), 0, np.int32)
    buf[:, :prompt_len] = prompt

    out_toks, out_scores = beam_search(
        model, params, jnp.asarray(buf), prompt_length=prompt_len,
        beam_size=vocab, stop_token=stop, num_return_gen=1,
        length_penalty=1.0, vocab_size=vocab, max_new_tokens=steps,
    )

    # exhaustive: all (vocab-1)^2 two-token continuations avoiding `stop`
    def seq_logprob(seq):
        # the beam log_softmaxes over the FULL padded vocab and only then
        # excludes pad ids as candidates (generation.py _beam_step); the
        # oracle must normalize identically
        full = np.concatenate([prompt[0], seq])[None]
        lp = np.asarray(score_tokens(model, params, jnp.asarray(full)))
        return float(lp[0, prompt_len - 1:].sum())

    best_score, best_seq = -np.inf, None
    for a in range(2, vocab):  # skip eod-ish ids 0/1 and stop
        if a == stop:
            continue
        for b in range(2, vocab):
            if b == stop:
                continue
            sc = seq_logprob(np.asarray([a, b]))
            if sc > best_score:
                best_score, best_seq = sc, (a, b)

    got = tuple(int(x) for x in np.asarray(out_toks)[0, prompt_len:prompt_len + steps])
    # beam may legitimately prefer a sequence routed through ids 0/1 or an
    # early stop; only compare when it returned a plain 2-token sequence
    got_score = float(np.asarray(out_scores)[0]) * steps  # undo len penalty
    assert got_score >= best_score - 1e-4, (got, got_score, best_seq, best_score)


def test_beam_respects_token_budget(tiny_model):
    model, params = tiny_model
    prompt_len, budget = 3, 4
    buf = np.full((1, 64), 0, np.int32)  # padded way past the budget
    buf[:, :prompt_len] = [[5, 6, 7]]
    out_toks, _ = beam_search(
        model, params, jnp.asarray(buf), prompt_length=prompt_len,
        beam_size=2, stop_token=255, num_return_gen=1,
        vocab_size=256, max_new_tokens=budget,
    )
    assert out_toks.shape[1] <= prompt_len + budget


# ---------------------------------------------------------------------------
# API + server round-trip
# ---------------------------------------------------------------------------


def test_generate_and_post_process_roundtrip(tiny_model):
    from megatron_llm_tpu.inference.api import generate_and_post_process

    model, params = tiny_model
    tok = ByteTokenizer()
    texts, segments, lp, out_tokens = generate_and_post_process(
        model, params, tok, ["hello", "hi"], tokens_to_generate=4,
        top_k_sampling=1, return_output_log_probs=True,
    )
    assert len(texts) == 2 and len(segments) == 2
    assert texts[0].startswith("hello") and texts[1].startswith("hi")
    assert lp is not None


def test_server_validation_and_generate(tiny_model):
    from megatron_llm_tpu.inference.server import MegatronGenerate, MegatronServer

    model, params = tiny_model
    tok = ByteTokenizer()
    srv = MegatronServer(model, params, tok)
    # bind to an ephemeral port; block=False only creates the socket
    srv.run("127.0.0.1", 0, block=False)
    httpd = srv._httpd
    port = httpd.server_address[1]
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    try:
        def put(payload):
            conn = HTTPConnection("127.0.0.1", port, timeout=120)
            conn.request("PUT", "/api", json.dumps(payload),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = json.loads(resp.read().decode())
            conn.close()
            return resp.status, body

        # validation errors: byte-parity messages (ref :39-99)
        status, body = put({})
        assert status == 400 and body == "prompts argument required"
        status, body = put({"prompts": ["a"], "max_len": 4})
        assert status == 400
        assert body == (
            "max_len is no longer used.  Replace with tokens_to_generate"
        )
        status, body = put({"prompts": ["a"], "top_k": 2, "top_p": 0.5})
        assert status == 400
        assert body == "cannot set both top-k and top-p samplings."
        # greedy generation round-trip
        status, body = put({
            "prompts": ["ab"], "tokens_to_generate": 3, "top_k": 1,
        })
        assert status == 200
        assert isinstance(body["text"], list)
        assert body["text"][0].startswith("ab")
        # static generation UI at / (ref: megatron/static/index.html)
        conn = HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", "/")
        resp = conn.getresponse()
        page = resp.read().decode()
        conn.close()
        assert resp.status == 200 and "<textarea" in page
    finally:
        httpd.shutdown()
