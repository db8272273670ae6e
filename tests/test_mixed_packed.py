"""The packed mixed prefill+decode round (ISSUE 27).

A mixed round is ONE row axis of `width + slots` tokens: the admitting
slot's chunk at the chunk's width, then one decode row a slot
(inference/engine.py `_make_mixed_step_fn`, models/attention.py
"packed_chunk"). Pinned here, tier-1 on the CPU:

- **Parity.** A request served through packed mixed rounds gives the
  tokens and logprobs (prompt logprobs included, prompts that cross
  chunk boundaries, decode rows riding beside another slot's chunk) of
  the decode scan after a whole-prompt prefill — with fp KV, int8 KV, a
  prefix-cache hit whose suffix chunk starts mid-prompt, a sampled
  neighbour slot (the non-greedy specialization) and the tp2 serving
  mesh. A sliding window has no whole-prompt engine (the dense prefill
  cannot window), so its oracle is the dense forward under the windowed
  causal mask.
- **The layout cannot come back.** The lowered mixed step of a small
  engine has no dot at `slots * width` token rows, and every weight
  matmul has `width + slots`.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.config import tiny_config
from megatron_llm_tpu.inference.engine import DecodeEngine
from megatron_llm_tpu.models import LlamaModel

BASE = dict(compute_dtype=jnp.float32, use_decode_attn=False)
VOCAB = 256
# fp32 ulps: chunking changes matmul shapes, the tp all-reduce reorders
# the row-parallel sums (tests/test_tp_serving.py pins the same bound)
ULPS = 5e-6
# int8 KV: the whole-prompt prefill attends its own fp K/V, a chunk
# reads back what it just quantized (tests/test_quantization.py
# observes ~7e-4 and pins 0.05; this traffic reads 6.8e-4)
INT8_DRIFT = 0.01


@pytest.fixture(scope="module")
def params():
    return LlamaModel(tiny_config(**BASE)).init(jax.random.key(7))


def _engine(params, window=None, **over):
    model = LlamaModel(tiny_config(**BASE, attention_window_size=window))
    kw = dict(slots=3, page_size=16, max_context=96, max_queue=16,
              prefill_chunk_tokens=8, termination_id=None,
              vocab_size=VOCAB)
    kw.update(over)
    return DecodeEngine(model, params, **kw)


def _traffic():
    """Four requests over three slots: prompts of 21 (chunks 8 + 8 + 5),
    5, 13 and 9 tokens, so chunks resume mid-prompt, the narrow last
    chunk takes another width bucket, decode rows ride beside every
    later chunk and the fourth request admits mid-flight."""
    rs = np.random.RandomState(11)
    return [(list(rs.randint(2, VOCAB, n)), g, {})
            for n, g in ((21, 6), (5, 10), (13, 5), (9, 7))]


def _run(eng, traffic, log_probs=True):
    reqs = [eng.submit(p, g, return_log_probs=log_probs,
                       **(kw or {"top_k": 1})) for p, g, kw in traffic]
    eng.drain()
    return [r.result(60) for r in reqs]


def _rode_beside_a_chunk(eng):
    """Mixed rounds in which a decode row rode beside a chunk."""
    return sum(1 for r in eng._round_log
               if r["prefill_tokens"] and r["decode_slots"])


def _dense_windowed(params, prompt, gen, window, pad_to=64):
    """The plain oracle for the windowed case: the dense forward over
    the whole sequence so far, causal and `window` wide, one greedy
    token at a time. Returns (tokens, logprobs) in the engine's layout."""
    model = LlamaModel(tiny_config(**BASE))
    rows = jnp.arange(pad_to)[:, None]
    cols = jnp.arange(pad_to)[None, :]
    mask = (cols > rows) | (cols < rows - (window - 1))
    fwd = jax.jit(lambda t: jax.nn.log_softmax(model.forward(
        params, t, attention_mask=mask)[0][0, :, :VOCAB], axis=-1))
    toks, lps = list(prompt), []
    for _ in range(gen):
        buf = np.zeros((1, pad_to), np.int32)
        buf[0, :len(toks)] = toks
        lp = np.asarray(fwd(jnp.asarray(buf)))
        if not lps:
            lps = [float(lp[i, toks[i + 1]])
                   for i in range(len(toks) - 1)]
        nxt = int(np.argmax(lp[len(toks) - 1]))
        lps.append(float(lp[len(toks) - 1, nxt]))
        toks.append(nxt)
    return toks, lps


def _assert_same(got, want, atol):
    assert len(got) == len(want)
    for i, ((t0, l0), (t1, l1)) in enumerate(zip(got, want)):
        assert t0 == t1, f"request {i}: token stream diverged"
        if l0 is not None:
            assert len(l0) == len(t0) - 1  # prompt logprobs included
            np.testing.assert_allclose(l0, l1, rtol=0, atol=atol,
                                       err_msg=f"request {i}")


def _prefix_traffic():
    """A system prompt served once, then (a second drain) a request
    that shares 24 of its tokens — a full page and a mid-page
    divergence, so the suffix chunk starts at lengths[slot] = 24 on the
    copy-on-write page — one that shares all 40, and a stranger whose
    decode rows ride beside their chunks."""
    rs = np.random.RandomState(5)
    system = list(rs.randint(2, VOCAB, 40))
    return [[(system + [7, 8, 9], 6, {})],
            [(system[:24] + list(rs.randint(2, VOCAB, 12)), 8, {}),
             (system + list(rs.randint(2, VOCAB, 5)), 6, {}),
             (list(rs.randint(2, VOCAB, 11)), 9, {})]]


def _sampled_traffic():
    """A non-greedy slot beside the greedy ones selects the sampled
    specialization of both step flavours; its stream is its seed's,
    whichever flavour served it."""
    traffic = _traffic()
    traffic[1] = (*traffic[1][:2], dict(top_k=4, temperature=0.9, seed=123))
    return [traffic]


def _window_traffic():
    traffic = _traffic()
    traffic[0] = (traffic[0][0], 14, {})  # 35 positions: page 0 dies
    return [traffic]


# case -> the packed engine's options, the reference's (None: the dense
# windowed oracle), the drains of traffic, whether logprobs are asked
# for, the logprob tolerance, and what else the packed engine must show
CASES = {
    "fp_kv": dict(packed={}, whole={}, atol=ULPS),
    "int8_kv": dict(packed=dict(kv_dtype="int8", page_size=32),
                    whole=dict(kv_dtype="int8", page_size=32),
                    atol=INT8_DRIFT),
    # window 12 binds inside the 21-token prompt and inside every
    # decode; no whole-prompt engine can window
    "sliding_window": dict(
        packed=dict(window=12), whole=None, traffic=_window_traffic,
        atol=ULPS, shows=lambda e: e._window_reclaimed > 0),
    # requests that ask for logprobs bypass prefix matching
    "prefix_hit_mid_prompt": dict(
        packed=dict(prefix_cache=True), whole={}, traffic=_prefix_traffic,
        log_probs=False, atol=0,
        shows=lambda e: e.counters()["serve_prefix_hits"] >= 2),
    "sampled_neighbour": dict(
        packed={}, whole={}, traffic=_sampled_traffic, atol=ULPS,
        shows=lambda e: any(not greedy for _, greedy in e._mixed_fns)),
    "tp2_mesh": dict(packed=dict(serving_tp=2), whole={}, atol=ULPS),
}


@pytest.mark.parametrize("case", list(CASES))
def test_packed_rounds_match_scan_after_whole_prompt_prefill(params, case):
    spec = CASES[case]
    drains = spec.get("traffic", lambda: [_traffic()])()
    log_probs = spec.get("log_probs", True)
    packed = _engine(params, **spec["packed"])
    got = [o for t in drains for o in _run(packed, t, log_probs)]
    if spec["whole"] is None:
        want = [_dense_windowed(params, p, g, spec["packed"]["window"])
                for t in drains for p, g, _ in t]
    else:
        whole = _engine(params, prefill_chunk_tokens=0, **spec["whole"])
        want = [o for t in drains for o in _run(whole, t, log_probs)]
        assert whole.counters()["serve_rounds_mixed"] == 0
    _assert_same(got, want, spec["atol"])
    assert packed.counters()["serve_rounds_mixed"] >= 7
    assert _rode_beside_a_chunk(packed) >= 2
    assert spec.get("shows", lambda e: True)(packed)


# ---------------------------------------------------------------------------
# the (slots, width) layout cannot come back
# ---------------------------------------------------------------------------

_DOT = re.compile(
    r"stablehlo\.dot_general.*?: \(tensor<([0-9x]+)x\w+>, "
    r"tensor<([0-9x]+)x\w+>\) -> tensor<([0-9x]+)x\w+>")


def _token_rows(text, heads):
    """(token rows, is a weight matmul) of every dot in a lowered
    program: a weight matmul has one row a token against a 2-D right
    operand, or against the decode tree's head-major (heads, head_dim,
    hidden) `wqkv`; an attention dot folds the heads into its rows."""
    out = []
    for lhs, rhs, res in _DOT.findall(text):
        lhs, rhs, res = ([int(x) for x in t.split("x")]
                         for t in (lhs, rhs, res))
        weight = len(rhs) == 2 or (
            len(rhs) == 3 and len(res) == len(lhs) + 1)
        rows = int(np.prod(res[:len(lhs) - 1]))
        out.append((rows if weight else rows // heads, weight))
    return out


def test_mixed_step_lowers_to_width_plus_slots_rows(params):
    slots, width = 4, 16
    eng = _engine(params, slots=slots, prefill_chunk_tokens=width,
                  max_context=64)
    cfg = eng.model.cfg
    text = eng._mixed_fn(width, True).lower(
        *eng._null_mixed_args(width)).as_text()
    dots = _token_rows(text, cfg.num_attention_heads)
    weight_rows = [r for r, w in dots if w]
    # qkv, out, up, down a layer, the head, and the embedding's rows as
    # a one-hot product (the toy's table lies vocab-minor on a TPU)
    assert len(weight_rows) == 4 * cfg.num_layers + 2
    assert set(weight_rows) == {width + slots}
    assert slots * width not in [r for r, _ in dots]
    # attention: the chunk at (1, width), the decode rows at (slots, 1)
    assert {r for r, w in dots if not w} == {width, slots}
