"""Continuous-batching decode engine on a paged KV cache (ISSUE 3).

The whole-batch path (`generate_tokens`) is synchronous: every request
in a call starts together, the batch runs until the SLOWEST row
finishes, and each row owns a dense (b, g, max_len, d) cache sized to
the worst case. Mixed-length traffic wastes both HBM and decode steps.
This engine is the serving-side alternative, after Ragged Paged
Attention (arxiv 2604.15464) and the slot-level-admission result of the
Gemma-on-TPU serving study (arxiv 2605.25645):

- the cache is a GLOBAL page pool per layer, held lane-packed
  (num_pages, page_size, g * d): a token's K/V heads side by side, the
  order a round writes and reads (models/gpt.py init_paged_kv_caches
  states the shape, ops/prefill_attention.py which head widths take
  its kernel), plus one (slots, max_pages) page table and per-slot
  lengths; HBM holds `page_budget` tokens
  of KV total, not slots * max_len;
- a fixed number of SLOTS decode in lockstep through a jitted
  lax.scan of up to `step_horizon` single-token steps per host
  round-trip (dispatch amortizer; the horizon is clamped to the
  nearest slot completion and pow2-bucketed, so at most
  log2(H)+1 scan lengths x {greedy, mixed} are ever traced) —
  admission, retirement and ragged lengths never recompile anything;
- finished slots retire their pages to a free list and queued requests
  are admitted mid-flight into the free slots;
- admission is CHUNKED by default (ISSUE 4, after Ragged Paged
  Attention's mixed-step result): each scheduler round carries a token
  budget (`prefill_chunk_tokens`) split between ONE resumable prefill
  chunk — a ragged span of the oldest admitting prompt, at a saved
  offset — and a single-token decode row for every other live slot,
  all through one jitted MIXED step (models/attention.py chunked paged
  branch -> ops/prefill_attention.py). The step lays the round out as
  ONE packed row axis of `width + slots` tokens — the chunk at its
  width bucket, then one decode row a slot — so the weights are read
  once, for that many rows, and only the admitting slot is laid out at
  the chunk's width (ISSUE 27; attention takes the chunk as (1, width)
  and the decode rows as (slots, 1), the decode scan's shape). A long
  prompt therefore delays each in-flight decode token by at most one
  budget-bounded chunk forward instead of its whole prefill, prompts
  are never pow2-padded, and only one mixed-step trace exists per pow2
  width bucket (vs one whole-prompt prefill executable per prompt
  bucket).
  `prefill_chunk_tokens=0` restores whole-prompt admission: a bucketed
  prefill (`bucket_prefill_len` compile shapes, LRU-bounded executable
  cache) writes the prompt's K/V into the slot's pages between decode
  rounds — still the right call for single-tenant short-prompt traffic
  (docs/GUIDE.md "Chunked prefill");
- per-request knobs (tokens_to_generate, greedy/top-k/top-p/
  temperature/seed, logprobs) ride per-slot ARRAYS through the step
  function — they are data, not compile-time statics.

Greedy decode is exact-match with `generate_tokens` for the same
prompt (tests/test_engine.py) in BOTH admission modes and regardless of
where chunk boundaries fall: every position's compute is
row-independent (per-position matmul rows, per-row softmax over the
same masked columns), so chunking the prompt changes op shapes but not
values — the token stream is bitwise identical, and logprobs are
bitwise at matched shapes / within one fp32 ulp when the backend's
matmul thread-blocking differs across chunk widths (the CPU test
harness's virtual-device split does this); the paged XLA fallback
gathers pages into the same dense view the dense path reads.

Scheduling is host-driven (one device scan per loop iteration) because
admission IS a host decision; the dense engine's while_loop stays the
right tool for single-shot batch eval (docs/GUIDE.md, "when the dense
kernel still wins").

ISSUE 6 adds three compounding serving features on the same pool:

- **Prefix sharing** (`prefix_cache=True`, inference/prefix_cache.py):
  admission looks the prompt up in a refcounted page-aligned prefix
  index and maps cache-hit pages into the slot's page table instead of
  re-prefilling them — chunked prefill resumes at the first uncached
  token (mid-page divergence rides a copy-on-write page copy). Pages
  free-list only at refcount zero; unreferenced cached prefixes evict
  LRU under pool pressure. Requires chunked admission (the suffix
  prefill must attend to pooled context, which the whole-prompt dense
  prefill cannot).
- **Token streaming** (`submit(..., stream=True)`): every generated
  token is pushed to a per-request queue as it is booked, closed with a
  None sentinel at completion/failure — the HTTP layer's SSE feed
  (inference/server.py). `cancel()` retires an abandoned request's slot
  mid-flight and reclaims its pages (refcounts intact).
- **Speculative decoding** (`spec_decode_k>0`): a prompt-lookup n-gram
  drafter proposes up to k tokens per greedy slot; one width-(k+1)
  ragged chunk per slot verifies them (the prefill kernel's
  arbitrary-start chunks ARE the verification shape). Accepted runs
  keep bitwise greedy parity — every emitted token is the same
  `_greedy_pick` the decode scan would have made; rejection rolls the
  slot's host-authoritative length back, so stale K/V past the accepted
  position is overwritten by the next round's writes and never read
  (the kernels mask by length).

ISSUE 9 quantizes the serving hot path, both bandwidth levers at once:
`kv_dtype="int8"` stores the page pools as int8 with per-(token, group)
fp32 scale pools riding every jitted step beside the data (quantize at
scatter, dequantize in-register — ops/quantization.py is the ONE
convention; COW page copies and null-page routing carry scales with
their pages, and the host-side refcount/eviction accounting never sees
a dtype), and `quantize_weights=True` swaps the decode GEMV weights for
one-shot weight-only int8. Both default OFF: the fp path keeps its
bitwise generate_tokens parity; the int8 path's accuracy is a measured
drift bound (tests/test_quantization.py, docs/GUIDE.md "Quantized serving").

ISSUE 14 grows the engine a mesh axis and a fleet: `serving_tp > 1`
shards the page pools (and scale pools) over the head/group axis and
runs every jitted step — decode scan, mixed step, spec verify, prefill
buckets, COW page copy — under pjit on a tp mesh via GSPMD constraints
(kv_pool_spec / decode_param_specs, parallel/sharding.py), with page
tables, lengths and the per-slot sampling arrays replicated; the Pallas
paged kernels already read per-(group) blocks, so each shard runs them
over its own groups with the XLA twins as the CPU oracle. N such
engines (each tagged `replica_id`, optionally pinned to a `devices`
subset) sit behind the prefix-affinity router (inference/router.py),
which dispatches shared-prefix traffic to the replica whose PrefixCache
already holds the pages and falls back least-loaded.
"""

from __future__ import annotations

import collections
import contextlib
import logging
import queue as queue_mod
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from megatron_llm_tpu.analysis.contracts import (
    compile_contract,
    release_variant,
)
from megatron_llm_tpu.config import CapabilityError
from megatron_llm_tpu.inference.generation import bucket_prefill_len
from megatron_llm_tpu.inference.prefix_cache import PrefixCache
from megatron_llm_tpu.models.moe import N_STATS
from megatron_llm_tpu.inference.sampling import (
    NEG_INF,
    modify_logits_for_top_p,
)
from megatron_llm_tpu.telemetry import (
    FlightRecorder,
    Histogram,
    SpanTracer,
    render_prometheus,
)

_logger = logging.getLogger(__name__)

SERVE_THREAD = "engine-serve"
# a round's kind by the program it dispatched, and the phases of the
# serve loop each timed by one span (`engine.<phase>`; `wait` is
# `engine.wait_for_work`): the keys of the serve_rounds_* /
# serve_round_ms_* and serve_host_ms_* counters, in their order
ROUND_KINDS = ("mixed", "decode", "spec")
# kind -> (the program's compile-contract name, which the cost registry
# keys its record by; the flight recorder's event kind)
ROUND_NAMES = {"mixed": ("engine.mixed_step", "round.mixed"),
               "decode": ("engine.decode_scan", "round.decode_scan"),
               "spec": ("engine.spec_verify", "round.spec_verify")}
# `counters()` of a model that routes, in `models/moe.py`'s `stats` order,
# summed over rounds and routed layers
MOE_COUNTERS = ("serve_moe_pairs", "serve_moe_experts_touched",
                "serve_moe_expert_slots", "serve_moe_hottest_pairs")
HOST_PHASES = ("schedule", "build_inputs", "dispatch", "fetch", "book",
               "wait")


def _name_os_thread(name: str) -> None:
    """Give the calling thread its OS name: the profiler names a host
    thread's line by it (Python before 3.14 names only its own Thread
    object), and a trace reducer finds the serve loop's spans by that
    line. Linux only; elsewhere the line keeps the process's name."""
    try:
        import ctypes

        ctypes.CDLL(None).prctl(15, name.encode()[:15], 0, 0, 0)
    except (OSError, AttributeError):
        pass


def horizon_buckets(step_horizon: int) -> list:
    """The pow2 decode-scan horizons an engine with this step_horizon
    can ever dispatch: {1, 2, 4, ..., pow2floor(step_horizon)}. ONE
    definition shared by warmup(), the contract budget, and the audit —
    the claim 'at most log2(H)+1 scan lengths trace' is enforced, not
    asserted in prose."""
    top = 1 << (max(step_horizon, 1).bit_length() - 1)
    out, h = [], 1
    while h <= top:
        out.append(h)
        h *= 2
    return out


def mixed_width_buckets(prefill_chunk_tokens: int) -> list:
    """The mixed-step chunk widths _chunk_width can ever return: every
    pow2 below the budget plus the budget itself — log2(C)+1 buckets.
    Shared by warmup(), the contract budget, and the audit."""
    c = prefill_chunk_tokens
    if c <= 0:
        return []
    widths = {c}
    w = 1
    while w < c:
        widths.add(w)
        w *= 2
    return sorted(widths)


class QueueFull(RuntimeError):
    """Raised by submit() when the admission queue is at capacity; the
    HTTP layer maps it to 503 + Retry-After."""


@jax.named_scope("sample")
def _greedy_pick(last_logits, vocab_size):
    """The greedy-specialized token decision — argmax on the
    vocab-clamped logits, no per-row sort machinery. ONE definition
    shared by the decode-scan and mixed-step builders: the engine's
    tokens must be independent of which step flavor served them, so the
    two paths may never drift numerically."""
    l = last_logits.astype(jnp.float32)
    if vocab_size is not None and vocab_size < l.shape[-1]:
        pad = jnp.arange(l.shape[-1]) >= vocab_size
        l = jnp.where(pad[None, :], NEG_INF, l)
    return jnp.argmax(l, axis=-1).astype(jnp.int32)


@jax.named_scope("sample")
def _per_slot_sample(logits, greedy, temperature, top_k, top_p, seeds,
                     steps, vocab_size):
    """One sampling decision per SLOT with per-slot knobs as traced
    arrays (the whole-batch `sample` takes them as jit statics — a
    continuous batch mixes them freely, so they must be data here).
    top-k/top-p reproduce inference/sampling.py semantics, including the
    top-p shift-by-1, via one shared descending sort; greedy rows ignore
    the sampled value. RNG: per-request seed folded with the request's
    own sampling-step count, so a request's stream is independent of
    which slot it landed in and of its neighbours."""
    V = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    if vocab_size is not None and vocab_size < V:
        pad = jnp.arange(V) >= vocab_size
        logits = jnp.where(pad[None, :], NEG_INF, logits)
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    l = logits / jnp.maximum(temperature, 1e-6)[:, None]
    # per-row top-k: the kth DESCENDING-sorted value is the row's
    # threshold (modify_logits_for_top_k needs a static k; the threshold
    # form is its per-row generalization)
    sorted_l = jnp.sort(l, axis=-1)[:, ::-1]
    kth_idx = jnp.clip(top_k - 1, 0, V - 1)
    kth = jnp.take_along_axis(sorted_l, kth_idx[:, None], axis=-1)
    l = jnp.where((top_k > 1)[:, None] & (l < kth), NEG_INF, l)
    # per-row top-p through the ONE reference implementation
    # (sampling.modify_logits_for_top_p broadcasts a (rows, 1)
    # threshold); rows with top_p == 0 keep their logits untouched
    filt = modify_logits_for_top_p(l, top_p[:, None])
    l = jnp.where((top_p > 0.0)[:, None], filt, l)

    keys = jax.vmap(
        lambda s, t: jax.random.fold_in(jax.random.key(s), t)
    )(seeds, steps)
    sampled = jax.vmap(
        lambda k, row: jax.random.categorical(k, row)
    )(keys, l).astype(jnp.int32)
    return jnp.where(greedy, greedy_tok, sampled)


@dataclass
class EngineRequest:
    """One queued/running generation. `tokens` grows to prompt +
    generated; `log_probs[i]` (when requested) is
    log P(tokens[i+1] | tokens[:i+1]) — the generate_tokens layout."""

    rid: int
    prompt: List[int]
    tokens_to_generate: int
    # which replica's engine owns this request (ISSUE 14): None on a
    # standalone engine; the router routes cancel() by it and the SSE
    # `id:` field carries it so N replicas' rids stay distinguishable
    replica_id: Optional[int] = None
    greedy: bool = True
    top_k: int = 0
    top_p: float = 0.0
    temperature: float = 1.0
    seed: int = 0
    return_log_probs: bool = False
    use_eod_for_early_termination: bool = True
    # per-request wall-clock budget from submit(); None = no deadline.
    # Enforced by the scheduler each round: an expired request fails its
    # waiter with TimeoutError and RETIRES its slot — the pages go back
    # to the pool instead of being held by a client that gave up.
    deadline_s: Optional[float] = None

    # streaming: when submit(stream=True), every GENERATED token id is
    # put here as it is booked; a None sentinel closes the stream at
    # completion, failure, timeout, or cancel (the SSE layer's feed)
    stream_q: Optional["queue_mod.SimpleQueue"] = None
    # set by DecodeEngine.cancel() (e.g. the HTTP client disconnected
    # mid-stream); the scheduler reaps it next round — queued requests
    # fail immediately, running slots retire and reclaim their pages
    cancelled: bool = False

    tokens: List[int] = field(default_factory=list)
    log_probs: List[float] = field(default_factory=list)
    error: Optional[str] = None
    timed_out: bool = False
    done: threading.Event = field(default_factory=threading.Event)
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first: float = 0.0  # first GENERATED token (TTFT = t_first - t_submit)
    t_done: float = 0.0

    def expired(self, now: float) -> bool:
        return (self.deadline_s is not None
                and now - self.t_submit > self.deadline_s)

    def result(self, timeout: Optional[float] = None):
        """Block until the request finishes; returns (tokens, log_probs).
        A request that blew its `deadline_s` raises TimeoutError (the
        engine already reclaimed its slot/pages); other engine failures
        raise RuntimeError."""
        if not self.done.wait(timeout):
            raise TimeoutError(f"request {self.rid} still running")
        if self.error is not None:
            if self.timed_out:
                raise TimeoutError(self.error)
            raise RuntimeError(self.error)
        return self.tokens, (self.log_probs if self.return_log_probs
                             else None)


@dataclass
class _Slot:
    req: Optional[EngineRequest] = None
    pages: List[int] = field(default_factory=list)
    forced: collections.deque = field(default_factory=collections.deque)
    generated: int = 0
    sample_step: int = 0
    # chunked admission: next prompt position to prefill (the resumable
    # saved offset); == len(req.prompt) once prefill is complete.
    # Prefix sharing starts it at the matched-token count: cache-hit
    # positions never prefill.
    prefill_pos: int = 0
    # prefix cache: how many full prompt pages of this slot are already
    # registered (or were mapped shared at admission); registration
    # advances as prefill passes each page boundary
    registered: int = 0
    # speculative drafting: bigram -> up to the 8 most recent start
    # indices in req.tokens, maintained INCREMENTALLY (amortized O(1)
    # per booked token — a per-round rescan of a long history would
    # erode the latency spec decoding buys). Multiple occurrences are
    # kept because on short-period repetition the NEWEST one sits at
    # the sequence tail with an empty continuation — an older one is
    # what actually drafts. `bigram_next` is the next start index to
    # fold in; the FINAL bigram stays unindexed so a lookup never
    # matches the occurrence it is extending.
    bigram: dict = field(default_factory=dict)
    bigram_next: int = 0
    # per-request device-cost accounting (ISSUE 15, cost_registry on):
    # the scheduler round this slot admitted at, the prompt offset
    # prefill started from (cache-hit positions never compute), the
    # prompt tokens actually prefilled on device, and the draft tokens
    # spec-decode booked for this request — the retire event's cost
    # record is assembled from exactly these host counters
    admit_round: int = 0
    prefill_start: int = 0
    prefilled: int = 0
    spec_accepted: int = 0
    # sliding-window serving (ISSUE 19): logical page frontier counters.
    # `mapped` — logical pages [reclaimed, mapped) hold physical pages
    # (windowed slots allocate lazily and top up just before each round
    # writes past the frontier); `reclaimed` — logical pages [0,
    # reclaimed) fell wholly out of every live window and were released
    # (table entries parked on null page 0). pages[k] is the physical
    # page at logical index reclaimed + k.
    mapped: int = 0
    reclaimed: int = 0

    @property
    def prefilling(self) -> bool:
        return self.req is not None and self.prefill_pos < len(
            self.req.prompt)


# a hand-off payload's names for the cache tree's page pools (the wire
# format of export_prefix / import_prefix)
_PAYLOAD_NAMES = {"k_pages_layers": "k", "v_pages_layers": "v",
                  "k_scales_layers": "ks", "v_scales_layers": "vs"}


class _CacheStep:
    """A jitted step over `(dec_params, cache, *operands)`. Called, it is
    the jitted function. `lower` also takes the pools the way the steps
    took them before the cache was one tree, `(dec_params, pools_k,
    pools_v, pools_ks, pools_vs, page_table, ...)`: what
    `benchmark/sizing.py` still hands it. Those pools name a geometry
    (pages, page size, type, placement); the tree that is lowered is the
    model's own for it, so a model that keeps per-slot state is sized
    with its state and with pools for its attention layers alone."""

    def __init__(self, fn, model):
        self._fn, self._model = fn, model

    def __call__(self, *args):
        return self._fn(*args)

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def lower(self, dec_params, *args):
        if not isinstance(args[0], dict):
            pools_k, page_table = args[0], args[4]
            like = jax.eval_shape(
                lambda: self._model.init_paged_kv_caches(
                    page_table.shape[0], *pools_k[0].shape[:2],
                    page_table.shape[1], kv_dtype=pools_k[0].dtype))
            cache = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype,
                    sharding=getattr(pools_k[0], "sharding", None)),
                {k: v for k, v in like.items()
                 if k not in ("page_table", "lengths")})
            args = (cache,) + args[4:]
        return self._fn.lower(dec_params, *args)


@compile_contract(
    "engine.decode_scan",
    max_variants=16,  # 2 specializations x (log2(horizon)+1) pow2 buckets
    collectives={"single": frozenset(),
                 # tp2 (ISSUE 14): all-reduce = the row-parallel wo/w2
                 # partial sums and the vocab-sharded embedding/head/
                 # argmax reductions; all-gather = the carried
                 # last_logits re-replicating each scan step (the
                 # carry is a REPLICATED per-slot operand by design —
                 # the host reads tokens from it and sampling sorts
                 # it whole). reduce-scatter would be a resharding
                 # leak and fails the audit.
                 "tp2": frozenset({"all-reduce", "all-gather"})},
    tmp_bytes_budget=1 << 20,
    notes="pow2-bucketed scan horizons x {greedy, mixed}; the engine "
          "passes the config-derived budget "
          "2*len(horizon_buckets(step_horizon)) at mint time; kv_dtype "
          "is an engine-level choice, never a new variant key; so is "
          "attention_window_size (ISSUE 19) — the window bakes into "
          "the model config at trace time and page reclamation is host "
          "bookkeeping, zero new executables")
def _make_step_fn(model, vocab_size, horizon, all_greedy):
    """The jitted continuous-batching step, traced once per (engine,
    horizon bucket): a lax.scan of `horizon` single-token steps — each
    samples/teacher-forces one token per slot from the carried logits
    and runs it through the paged stack (scatter K/V into each slot's
    current page, paged attention over owned pages). Batching HORIZON
    steps per host round-trip amortizes dispatch latency; the host
    clamps the horizon to the nearest slot completion, so no request
    ever overruns its budget inside a horizon. `cache` is the engine's
    ONE tree of device state (`GPTModel.init_paged_kv_caches` less the
    page table and the lengths: the page pools, an int8 engine's fp32
    scale pools, the per-slot state of layers that keep one), donated —
    the update is in place — and handed back whole, so a further kind
    of state is an entry of the tree and not an argument. A model that
    routes also returns the round's `moe_stats` (models/moe.py)."""
    routed = model.cfg.num_experts > 0

    def step(dec_params, cache, page_table, lengths, last_logits, active,
             forced, use_forced, greedy, temperature, top_k, top_p, seeds,
             sample_steps):
        # forced/use_forced: (slots, horizon) — the remaining prompt
        # tokens are known in advance, so teacher forcing rides the scan

        def body(carry, xs):
            cache, lengths, last_logits, steps_c, stats = carry
            forced_t, use_forced_t = xs
            lp_full = jax.nn.log_softmax(
                last_logits.astype(jnp.float32), axis=-1)
            if all_greedy:
                # every live request is greedy (the serving-bench hot
                # path): the per-row sort/cumsum machinery of the
                # sampled branch would cost a full (slots, V) sort per
                # token for nothing
                sampled = _greedy_pick(last_logits, vocab_size)
            else:
                sampled = _per_slot_sample(
                    last_logits, greedy, temperature, top_k, top_p,
                    seeds, steps_c, vocab_size)
            chosen = jnp.where(use_forced_t, forced_t, sampled)
            chosen = jnp.where(active, chosen, 0)
            chosen_lp = jnp.take_along_axis(
                lp_full, chosen[:, None].astype(jnp.int32), axis=-1)[:, 0]
            caches = {**cache, "page_table": page_table,
                      "lengths": lengths, "active": active}
            logits, new_caches = model.forward(
                dec_params, chosen[:, None], kv_caches=caches,
                position_ids=lengths[:, None],
            )
            steps_c = steps_c + (active & ~use_forced_t)
            if routed:
                stats = stats + new_caches["moe_stats"]
            # carry the logits at last_logits' dtype (fp32): a bf16-
            # compute model would otherwise flip the scan carry dtype
            # on the first step and fail trace (no-op for fp32 models,
            # so the bitwise-parity engines are untouched)
            return (({k: new_caches[k] for k in cache},
                     new_caches["lengths"],
                     logits[:, 0].astype(last_logits.dtype), steps_c,
                     stats),
                    (chosen, chosen_lp))

        carry = (cache, lengths, last_logits, sample_steps,
                 jnp.zeros((N_STATS,), jnp.int32) if routed else None)
        carry, (chosen_h, lp_h) = jax.lax.scan(
            body, carry, (forced.T, use_forced.T))
        cache, _, last_logits, _, stats = carry
        # (horizon, slots) -> (slots, horizon)
        return chosen_h.T, lp_h.T, last_logits, cache, stats

    return _CacheStep(jax.jit(step, donate_argnums=1), model)


@compile_contract(
    "engine.mixed_step",
    max_variants=24,  # 2 specializations x (log2(chunk budget)+1) widths
    collectives={"single": frozenset(),
                 "tp2": frozenset({"all-reduce"})},  # see decode_scan
    # the audit's fullest engine (int8 KV + int8 weights) reads 348,240
    tmp_bytes_budget=352_000,
    notes="pow2 chunk-width buckets x {greedy, mixed}; the engine "
          "passes 2*len(mixed_width_buckets(prefill_chunk_tokens)) "
          "at mint time; a round is one packed row axis of width + "
          "slots tokens (the admitting slot's chunk, then a decode row "
          "a slot), so a width bucket never multiplies by slots; "
          "attention_window_size is engine-static like kv_dtype (see "
          "decode_scan) — windowed engines mint the same width "
          "buckets, never a window-keyed variant")
def _make_mixed_step_fn(model, vocab_size, width, all_greedy):
    """The jitted MIXED prefill+decode step (chunked admission), traced
    once per (engine, pow2 width bucket, greedy specialization). The
    round is ONE packed row axis of `width + slots` tokens: rows
    0..width-1 are the admitting slot's prefill chunk (up to `width`
    prompt tokens at its saved offset, chunk_lens[chunk_idx] of them
    valid), rows width + i one sampled/greedy decode token per slot
    (valid where the slot decodes; the admitting slot's and idle slots'
    rows are masked). Embedding, projections, MLP, norms and head run
    on those width + slots rows in one pass over the weights — only the
    admitting slot is laid out at the chunk's width — and the paged
    branch (models/attention.py, "packed_chunk") calls THE ragged paged
    kernel on the chunk as (1, width) and on the decode rows as (slots,
    1), the decode scan's own shape. Decode rows sample from the carried
    last_logits BEFORE the forward, exactly like the decode scan body,
    so tokens and logprobs are independent of which step flavor served
    them. `cache` (the decode scan's: the engine's one tree of device
    state) is donated — the update is in place.

    Returns per-slot (first token, its logprob under last_logits),
    the CHUNK slot's in-chunk logprobs [lp of chunk token p+1 at p],
    the new last logits, the cache and (a model that routes) the
    round's `moe_stats`. last_logits is PRESERVED for idle slots."""

    def step(dec_params, cache, page_table, lengths, last_logits,
             chunk_tokens, chunk_lens, is_prefill, chunk_idx, greedy,
             temperature, top_k, top_p, seeds, sample_steps):
        # chunk_tokens: (width,) — the admitting slot's span; every
        # other operand is per-slot, as in the decode scan
        active = chunk_lens > 0
        lp_full = jax.nn.log_softmax(
            last_logits.astype(jnp.float32), axis=-1)
        if all_greedy:
            sampled = _greedy_pick(last_logits, vocab_size)
        else:
            sampled = _per_slot_sample(
                last_logits, greedy, temperature, top_k, top_p, seeds,
                sample_steps, vocab_size)
        first = jnp.where(is_prefill, chunk_tokens[0], sampled)
        first = jnp.where(active, first, 0)
        first_lp = jnp.take_along_axis(
            lp_full, first[:, None].astype(jnp.int32), axis=-1)[:, 0]
        caches = {**cache, "page_table": page_table, "lengths": lengths,
                  "chunk_lens": chunk_lens, "packed_chunk": chunk_idx}
        chunk_pos = lengths[chunk_idx] + jnp.arange(width)
        logits, new_caches = model.forward(
            dec_params, jnp.concatenate([chunk_tokens, first])[None],
            kv_caches=caches,
            position_ids=jnp.concatenate([chunk_pos, lengths])[None],
        )
        logits = logits[0]  # (width + slots, V)
        if width > 1:
            # lp of chunk token p+1 under the logits at p — the prompt-
            # logprob stream of a prefill chunk (position p's target is
            # the NEXT prompt token; the chunk's last target arrives
            # next round via first_lp, the decode scan's layout)
            lp_in = jax.nn.log_softmax(
                logits[:width - 1].astype(jnp.float32), axis=-1)
            chunk_lps = jnp.take_along_axis(
                lp_in, chunk_tokens[1:, None].astype(jnp.int32),
                axis=-1)[:, 0]
        else:
            chunk_lps = jnp.zeros((0,), jnp.float32)
        # the admitting slot carries the logits at its chunk's last
        # valid row, a decoding slot those of its own row
        chunk_last = jax.lax.dynamic_index_in_dim(
            logits, jnp.clip(chunk_lens[chunk_idx] - 1, 0, width - 1), 0,
            keepdims=False)
        new_last = jnp.where(is_prefill[:, None], chunk_last[None],
                             logits[width:])
        # keep last_logits' dtype (fp32; bf16-compute models upcast
        # here — no-op for fp32 models)
        new_last = jnp.where(active[:, None],
                             new_last.astype(last_logits.dtype),
                             last_logits)
        return (first, first_lp, chunk_lps, new_last,
                {k: new_caches[k] for k in cache},
                new_caches.get("moe_stats"))

    return _CacheStep(jax.jit(step, donate_argnums=1), model)


@compile_contract(
    "engine.prefill_bucket",
    max_variants=8,  # == DecodeEngine._PREFILL_CACHE_CAP: the LRU
    # eviction path release_variant()s, so the live count IS the cache
    collectives={"single": frozenset(),
                 "tp2": frozenset({"all-reduce"})},  # see decode_scan
    tmp_bytes_budget=8 << 20,
    notes="whole-prompt mode only; one executable per prefill bucket, "
          "LRU-bounded — eviction releases the variant")
def _make_prefill_fn(model, prefill_len, page_size):
    """Bucketed prefill, traced once per bucket: one causal forward over
    the prompt's bucket prefix through dense per-layer caches, whose
    K/V rows are scattered STRAIGHT into the slot's pool pages inside
    the same jitted program, a token's heads side by side as the
    lane-packed pool holds them. Int8 pools quantize each (token,
    group) row at the same scatter (the dense prefill math itself
    stays fp — quantization is a storage decision,
    ops/quantization.py). Returns updated pools, the
    slot's next-token logits, and the prompt logprobs of the prefix."""

    def prefill(dec_params, cache, tokens, pt_row):
        pools_k, pools_v = cache["k_pages_layers"], cache["v_pages_layers"]
        quant = "k_scales_layers" in cache
        caches = model.init_kv_caches(1, prefill_len, layout="layers")
        logits, caches = model.forward(dec_params, tokens,
                                       kv_caches=caches)
        lp = jax.nn.log_softmax(logits[0].astype(jnp.float32), axis=-1)
        prompt_lp = jnp.take_along_axis(
            lp[:-1], tokens[0, 1:, None].astype(jnp.int32), axis=-1)[:, 0]
        pos = jnp.arange(prefill_len)
        pages = pt_row[pos // page_size]
        offs = pos % page_size
        if quant:
            # quantize-at-write through the ONE shared definition —
            # the same rounding/scale convention as the chunked and
            # decode scatter paths (ops/quantization.py)
            from megatron_llm_tpu.ops.quantization import (
                scatter_quantized_rows,
            )

            new_k, new_v, new_ks, new_vs = [], [], [], []
            for pk, pv, pks, pvs, kl, vl in zip(
                    pools_k, pools_v, cache["k_scales_layers"],
                    cache["v_scales_layers"],
                    caches["k_layers"], caches["v_layers"]):
                pk, pks = scatter_quantized_rows(
                    pk, pks, pages, offs, kl[0].transpose(1, 0, 2))
                pv, pvs = scatter_quantized_rows(
                    pv, pvs, pages, offs, vl[0].transpose(1, 0, 2))
                new_k.append(pk)
                new_v.append(pv)
                new_ks.append(pks)
                new_vs.append(pvs)
            return ({"k_pages_layers": tuple(new_k),
                     "v_pages_layers": tuple(new_v),
                     "k_scales_layers": tuple(new_ks),
                     "v_scales_layers": tuple(new_vs)},
                    logits[0, -1], prompt_lp)
        def rows(x):  # (1, g, T, d) dense cache -> (T, g * d) pool rows
            return x[0].transpose(1, 0, 2).reshape(prefill_len, -1)

        pools_k = tuple(
            pk.at[pages, offs].set(rows(kl))
            for pk, kl in zip(pools_k, caches["k_layers"]))
        pools_v = tuple(
            pv.at[pages, offs].set(rows(vl))
            for pv, vl in zip(pools_v, caches["v_layers"]))
        return ({"k_pages_layers": pools_k, "v_pages_layers": pools_v},
                logits[0, -1], prompt_lp)

    return jax.jit(prefill, donate_argnums=1)


@compile_contract(
    "engine.spec_verify",
    max_variants=2,  # ONE width (spec_decode_k+1) x {greedy, mixed}
    collectives={"single": frozenset(),
                 # all-gather: the replicated last_logits carry +
                 # per-position greedy targets the host books — see
                 # decode_scan
                 "tp2": frozenset({"all-reduce", "all-gather"})},
    tmp_bytes_budget=4 << 20,
    notes="all spec traffic verifies through width spec_decode_k+1; "
          "shorter drafts pad via chunk_lens — per-draft-length buckets "
          "are a contract violation (tests/test_spec_decode.py)")
def _make_spec_step_fn(model, vocab_size, width, all_greedy):
    """The jitted SPECULATIVE verification step, traced once per
    (engine, width = spec_decode_k + 1, greedy specialization): every
    live slot contributes one ragged chunk through the chunked paged
    stack — a spec slot's chunk is [its next token (decided from the
    carried last_logits exactly like a decode row), then its draft
    tokens], a non-spec slot a plain width-1 decode row. The forward
    writes K/V for every chunk position and returns logits per
    position; verification is ON DEVICE: the greedy target at chunk
    position j (`_greedy_pick`, the ONE token-decision definition) is
    compared with the draft at position j+1, and the accepted count is
    the leading run of matches. The carried logits come from the
    ACCEPTED position — so a rejection "rolls back" by simply not
    advancing past it; the host mirrors lengths to first+accepted and
    the next round's writes overwrite the stale K/V (never read: the
    kernels mask by length). Every emitted token is bitwise the token
    the decode scan would have produced, because both paths share
    `_greedy_pick` and per-position compute is row-independent.

    Returns per-slot (first token, its logprob), the per-position
    greedy targets + their logprobs (the accepted tokens' stream
    values), the accepted counts, the new last logits (preserved for
    idle slots), the donated cache tree and (a model that routes) the
    round's `moe_stats`."""

    def step(dec_params, cache, page_table, lengths, last_logits,
             chunk_tokens, chunk_lens, is_spec, greedy, temperature, top_k,
             top_p, seeds, sample_steps):
        active = chunk_lens > 0
        lp_full = jax.nn.log_softmax(
            last_logits.astype(jnp.float32), axis=-1)
        if all_greedy:
            sampled = _greedy_pick(last_logits, vocab_size)
        else:
            sampled = _per_slot_sample(
                last_logits, greedy, temperature, top_k, top_p, seeds,
                sample_steps, vocab_size)
        first = jnp.where(active, sampled, 0)
        first_lp = jnp.take_along_axis(
            lp_full, first[:, None].astype(jnp.int32), axis=-1)[:, 0]
        toks = chunk_tokens.at[:, 0].set(first)
        caches = {**cache, "page_table": page_table, "lengths": lengths,
                  "chunk_lens": chunk_lens}
        logits, new_caches = model.forward(
            dec_params, toks, kv_caches=caches,
            position_ids=lengths[:, None] + jnp.arange(width)[None, :],
        )
        n = logits.shape[0]
        V = logits.shape[-1]
        gt = _greedy_pick(logits.reshape(n * width, V),
                          vocab_size).reshape(n, width)
        glp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        gt_lp = jnp.take_along_axis(
            glp, gt[..., None].astype(jnp.int32), axis=-1)[..., 0]
        # accepted run: draft at position j+1 matches the greedy target
        # of position j, leading matches only, within the chunk's valid
        # length
        pos = jnp.arange(1, width)[None, :]
        matches = (toks[:, 1:] == gt[:, :-1]) & (pos < chunk_lens[:, None])
        acc = jnp.sum(jnp.cumprod(matches.astype(jnp.int32), axis=1),
                      axis=1)
        acc = jnp.where(is_spec, acc, 0)
        last_idx = jnp.where(
            is_spec, acc, jnp.clip(chunk_lens - 1, 0, width - 1))
        new_last = jnp.take_along_axis(
            logits, last_idx[:, None, None], axis=1)[:, 0]
        new_last = jnp.where(active[:, None],
                             new_last.astype(last_logits.dtype),
                             last_logits)
        return (first, first_lp, gt, gt_lp, acc, new_last,
                {k: new_caches[k] for k in cache},
                new_caches.get("moe_stats"))

    return jax.jit(step, donate_argnums=1)


@compile_contract(
    "engine.page_copy",
    max_variants=1,  # src/dst are traced scalars: ONE executable ever
    collectives={"single": frozenset(),
                 # tp2: copies are shard-local (the pages axis is
                 # unsharded; each chip copies its own group slice) —
                 # ZERO collectives, pinned
                 "tp2": frozenset()},
    tmp_bytes_budget=1 << 20,
    notes="the prefix cache's COW copy; a second variant would mean "
          "src/dst leaked into the static signature")
def _make_page_copy_fn():
    """One jitted whole-page pool copy (the prefix cache's
    copy-on-write): page `dst` becomes a private replica of shared page
    `src` across every layer's K and V pool — AND, on an int8 engine,
    across every layer's scale pool: a quantized page's KV is the
    (data, scale) pair, and copying one without the other would
    dequantize the replica against a foreign scale. src/dst are traced
    scalars — one executable serves every COW. The read-before-write
    data dependency orders it against any later scatter into `dst`."""

    def copy(cache, src, dst):
        # every leaf is a page pool: a model that keeps per-slot state
        # is refused the features that copy pages (_refuse_for_slot_state)
        return jax.tree.map(lambda pool: pool.at[dst].set(pool[src]), cache)

    return jax.jit(copy, donate_argnums=0)


@compile_contract(
    "engine.page_export",
    max_variants=1,  # ids is a traced fixed-width vector: ONE executable
    collectives={"single": frozenset(),
                 # tp2: the pages axis is unsharded, so each chip
                 # gathers its own group slice of every requested page —
                 # ZERO collectives, pinned (a collective here would
                 # mean the export resharded the pool)
                 "tp2": frozenset()},
    tmp_bytes_budget=1 << 20,
    notes="disaggregated serving's donor-side page gather (ISSUE 17): "
          "ids is padded to max_pages_per_slot with the null page, so "
          "prefix length can never leak into the static signature")
def _make_page_export_fn():
    """One jitted batched whole-page gather (the donor half of the
    cross-replica KV hand-off): rows `ids` of every layer's K and V
    pool — AND, on an int8 engine, of every layer's scale pool — are
    pulled into dense (max_pages_per_slot, ...) row blocks the host can
    device_get and ship. `ids` is a fixed-width int32 vector padded
    with the null page 0, so one executable serves every prefix length;
    pad rows gather the dead page and are sliced off on the host.
    Pools are NOT donated: an export is a read, and the donor keeps
    serving from the same buffers."""

    def export(cache, ids):
        return jax.tree.map(lambda pool: pool[ids], cache)

    return jax.jit(export)


@compile_contract(
    "engine.page_import",
    max_variants=1,  # same fixed-width ids idiom as the export
    collectives={"single": frozenset(),
                 # tp2: the replicated payload rows scatter into each
                 # chip's own group slice of the page pools — ZERO
                 # collectives, pinned, same argument as page_copy
                 "tp2": frozenset()},
    tmp_bytes_budget=1 << 20,
    notes="disaggregated serving's receiver-side page scatter "
          "(ISSUE 17): fixed-width ids padded with the null page; pad "
          "rows scatter zeros into dead page 0, which is dead by the "
          "null-page invariant")
def _make_page_import_fn():
    """One jitted batched whole-page scatter (the receiver half of the
    cross-replica KV hand-off): payload row blocks land at rows `ids`
    of every layer's K/V pool — and of every layer's scale pool on an
    int8 engine, because a quantized page's KV is the (data, scale)
    pair and splitting them would dequantize against a foreign scale.
    `ids` is the same fixed-width null-padded vector the export uses;
    pad rows carry zeros into the dead null page 0, which no page-table
    row maps for reads. Pools are donated — the splice is in place,
    exactly like page_copy."""

    def imp(cache, ids, rows):
        return jax.tree.map(lambda pool, r: pool.at[ids].set(r), cache, rows)

    return jax.jit(imp, donate_argnums=0)


class DecodeEngine:
    """Fixed-slot continuous-batching decode engine over a paged pool.

    Knobs (docs/GUIDE.md "Continuous-batching serving engine"):
    - `slots`: concurrent requests decoding per step; the step batch.
    - `page_size`: tokens per KV page (>= 16 to keep the Pallas kernel
      eligible; 64 default balances fragmentation vs table size).
    - `page_budget`: total KV positions in the pool across all slots
      (+1 null page is added internally). Defaults to the full
      reservation slots * max_context — set it lower to oversubscribe
      HBM against observed context lengths; admission then blocks on
      free pages, never preempts.
    - `max_context`: per-slot prompt + generation cap; fixes the page
      table width (static for the step trace).
    - `max_queue`: admission queue depth; submit() past it raises
      QueueFull (the HTTP layer's 503).
    - `step_horizon`: decode steps per host round-trip (one jitted
      scan) — amortizes dispatch latency at the price of quantizing
      admission/retirement latency; clamped per call to the nearest
      slot completion so no budget is overrun mid-scan.
    - `prefill_chunk_tokens`: per-round prompt-token budget of chunked
      admission (the mixed prefill+decode step). While any slot is
      admitting, each round prefills at most this many tokens of the
      OLDEST admitting prompt and advances every other live slot by one
      decode token in the same jitted dispatch — the decode-latency
      interference of a long prompt is bounded by one budget-sized
      chunk forward per token. 0 disables chunking: whole-prompt
      bucketed prefill at admission (the pre-ISSUE-4 behavior; wins for
      single-tenant short-prompt traffic, docs/GUIDE.md).
    - `warmup_compile`: pre-trace the mixed-step/decode-scan
      executables for the configured buckets at `start()` so the first
      request doesn't eat the compile stall (opt-in; warmup rounds run
      every slot idle, so they only scribble the dead null page).
    - `prefix_cache`: share prompt-prefix K/V pages across requests
      (inference/prefix_cache.py; page-aligned hash index, COW on
      mid-page divergence, refcounted free-list returns, LRU eviction
      under pool pressure). Requires chunked admission
      (prefill_chunk_tokens > 0): the suffix prefill must attend to
      pooled context. Requests with return_log_probs bypass matching
      (their PROMPT logprobs require the full forward) but still
      register their pages for others.
    - `spec_decode_k`: speculative decoding — a prompt-lookup n-gram
      drafter proposes up to k tokens per greedy slot per round,
      verified in one width-(k+1) ragged chunk (ONE executable per
      greedy specialization). Greedy token streams stay bitwise;
      sampled slots ride the same round as plain decode rows. 0
      disables.
    - `kv_dtype` ("bf16" default | "int8", ISSUE 9): page-pool storage
      dtype. int8 stores K/V as int8 with per-(token, group) fp32
      scale pools (quantized at write time in the scatter paths,
      dequantized in-register by the paged kernels / on the gathered
      view by the XLA twins) — roughly half the pool bytes/token and
      half the decode kernels' cache traffic, at a bounded
      (tests/test_quantization.py) greedy logprob drift. bf16 keeps the bitwise
      generate_tokens parity contract.
    - `quantize_weights` (default False): one-shot weight-only int8 of
      the decode GEMV weights (per-output-channel scales,
      prepare_decode_params(quantize_int8=True)); decode matvecs read
      half the weight bytes. Decode-only — the fp tree is untouched.
    - `serving_tp` (default 1, ISSUE 14): tensor-parallel degree of
      the serving mesh. The K/V page pools (and int8 scale pools)
      shard over the head/group axis (parallel/sharding.kv_pool_spec
      — the zero1_axis one-rule idiom), decode params shard by
      decode_param_specs, and every jitted step runs under pjit on a
      (1,1,1,tp) mesh via GSPMD constraints; only the paged attention
      call sits in a shard_map (models/attention.py: Mosaic kernels
      cannot be GSPMD-partitioned). Page tables / lengths / per-slot sampling
      arrays stay replicated host-trivial operands. Must divide
      num_query_groups. Greedy TOKEN streams match the single-chip
      engine bitwise; logprobs carry the same last-ulps latitude the
      backend's matmul blocking already has across chunk widths (the
      tp all-reduce reorders the row-parallel reduction) — pinned in
      tests/test_tp_serving.py. Incompatible with quantize_weights
      (flattened-GLU layout); docs/GUIDE.md "Serving on a tp mesh &
      replica routing".
    - `devices` (default None = jax.devices() prefix): pin the engine
      to a device subset — N emulated replicas on one host each own a
      device (inference/router.py, bench scaleout).
    - `replica_id` (default None): tag this engine as replica i behind
      a router: counters() grows `serve_replica_id`, flight-recorder
      events and trace spans carry `replica`, and the SSE `id:` field
      becomes "i-rid", so N replicas' aggregated metrics and dumps
      stay distinguishable. None keeps every schema byte-compatible
      with the standalone engine.
    - `trace_dir` (ISSUE 13): enable the host span tracer; the Chrome
      trace-event JSON exports here at stop(). `record_dir`: where the
      flight recorder dumps its crash artifact (defaults to trace_dir;
      None = in-memory + log-summary only). `flight_recorder_size`:
      the event ring bound. Telemetry never touches jitted code —
      telemetry-on steps are bitwise telemetry-off
      (docs/GUIDE.md "Observability").
    - `cost_registry` (default False, ISSUE 15): capture each minted
      executable's compiled cost (cost_analysis FLOPs/bytes +
      memory_analysis temp/args bytes) at MINT time into a
      telemetry/costs.CostRegistry — never in the per-round path.
      Unlocks the per-request device-cost record stamped into retire
      events (prefill/decode/spec-accepted tokens, page-rounds held,
      modeled FLOPs), the `serve_modeled_gflops`/`serve_page_rounds`
      aggregates, and (with a known chip) the
      `serve_dispatch_overhead_pct` gauge — modeled roofline device
      time vs measured round wall. Opt-in because capture pays one
      extra AOT compile per minted executable (docs/GUIDE.md "Goodput
      & device-cost accounting"); all gauges it adds are absent when
      off, keeping the /metrics JSON byte-compatible.
    - `chip_spec` (default None = detect from the engine's devices):
      chipspec table override ("v5e"/"v5p"/"v4") for the roofline
      denominators — the only way to get deterministic overhead
      gauges on the CPU harness.
    - `perf_sentinel_ksigma` (default 0.0 = off, ISSUE 15): arm the
      perf-regression sentinel on the DECODE-SCAN per-token-advance
      round latency — the one homogeneous series. Mixed rounds are
      excluded (their wall carries a prefill chunk: long-prompt
      admission would read as a false regression) and so are spec
      rounds (their per-advance moves with the ACCEPT RATE: a prompt
      mix dropping acceptance is not a hardware regression);
      interference and acceptance stay the serve_decode_round_ms
      histogram's and serve_spec_accept_rate's jobs. `patience`
      consecutive rounds above median + ksigma * 1.4826*MAD of the
      recent window trips it — flight-recorder event trail, a
      `serve_perf_regressions` counter, and an auto-dump of the ring
      into record_dir through the same postmortem path as poison.
      `perf_sentinel_window`/`perf_sentinel_patience` tune it
      (docs/GUIDE.md sentinel tuning table).

    Pages are reserved UP FRONT at admission for the request's whole
    prompt + tokens_to_generate reach, so a running request can never
    be starved mid-flight (no preemption path to get wrong); the
    trade is documented in the guide.
    """

    def __init__(self, model, params, *, slots: int = 4,
                 page_size: int = 64, max_context: int = 1024,
                 page_budget: Optional[int] = None, max_queue: int = 64,
                 step_horizon: int = 8,
                 prefill_chunk_tokens: int = 256,
                 warmup_compile: bool = False,
                 prefix_cache: bool = False,
                 spec_decode_k: int = 0,
                 window_reclaim: bool = True,
                 kv_dtype: str = "bf16",
                 quantize_weights: bool = False,
                 serving_tp: int = 1,
                 devices=None,
                 replica_id: Optional[int] = None,
                 termination_id: Optional[int] = None,
                 vocab_size: Optional[int] = None, timers=None,
                 trace_dir: Optional[str] = None,
                 record_dir: Optional[str] = None,
                 flight_recorder_size: int = 4096,
                 cost_registry: bool = False,
                 chip_spec: Optional[str] = None,
                 perf_sentinel_ksigma: float = 0.0,
                 perf_sentinel_window: int = 64,
                 perf_sentinel_patience: int = 8):
        assert max_context % page_size == 0, \
            "max_context must be a multiple of page_size"
        if kv_dtype not in ("bf16", "int8"):
            raise ValueError(
                f"kv_dtype must be 'bf16' (the model compute dtype — "
                f"the bitwise-parity default) or 'int8' (quantized "
                f"pages, docs/GUIDE.md 'Quantized serving'), got "
                f"{kv_dtype!r}")
        self.model = model
        self.cfg = model.cfg
        self._refuse_for_slot_state(
            prefix_cache=prefix_cache, spec_decode_k=spec_decode_k,
            prefill_chunk_tokens=prefill_chunk_tokens,
            serving_tp=serving_tp, quantize_weights=quantize_weights)
        # -- tp mesh (ISSUE 14) -------------------------------------------
        # serving_tp > 1: the pools shard over their heads (the lanes
        # of a lane-packed pool: kv_pool_spec, the zero1_axis one-rule
        # idiom) and every
        # jitted step runs under pjit on a (1, 1, 1, tp) mesh via GSPMD
        # constraints; the paged attention call alone runs per shard
        # (parallel/mesh.shard_kernel). `devices` pins
        # the engine to a device subset even at tp=1 (N emulated
        # replicas on one host each own a device — bench scaleout /
        # inference/router.py). Page tables, lengths, and the per-slot
        # sampling arrays stay REPLICATED: they are host-trivial
        # scalar-prefetch operands every chip must agree on.
        self.serving_tp = max(1, serving_tp)
        self.replica_id = replica_id
        if self.serving_tp > 1 or devices is not None:
            from megatron_llm_tpu.parallel.mesh import (
                ParallelContext,
                build_mesh,
            )

            if self.cfg.num_query_groups % self.serving_tp != 0:
                raise ValueError(
                    f"serving_tp={self.serving_tp} must divide the KV "
                    f"group count ({self.cfg.num_query_groups}): the "
                    f"page pools shard over the group axis "
                    f"(parallel/sharding.kv_pool_spec) — use a tp that "
                    f"divides num_query_groups, or replicate the "
                    f"engine behind the router instead (docs/GUIDE.md "
                    f"'Serving on a tp mesh & replica routing')")
            if quantize_weights and self.serving_tp > 1:
                raise ValueError(
                    "quantize_weights is single-chip-layout only (the "
                    "weight-only int8 decode tree bakes the flattened "
                    "(h, 2f) GLU view, whose gate|up concat crosses "
                    "the tp shard boundary); serve the fp decode tree "
                    "on a tp mesh, or quantize at tp=1 (docs/GUIDE.md "
                    "'Serving on a tp mesh & replica routing')")
            self._ctx = ParallelContext(
                build_mesh(tp=self.serving_tp, devices=devices))
            self._rep = self._ctx.sharding()  # replicated operands
        else:
            self._ctx = None
            self._rep = None
        self.slots = slots
        self.page_size = page_size
        self.max_pages_per_slot = max_context // page_size
        self.max_context = max_context
        if page_budget is None:
            page_budget = slots * max_context
        assert page_budget % page_size == 0
        self.num_pages = 1 + page_budget // page_size  # +1: null page 0
        self.max_queue = max_queue
        # decode steps per host round-trip: dispatch latency amortizer
        # (admission/retirement latency is quantized by it; the host
        # clamps each call to the nearest slot completion so no budget
        # is overrun, and buckets the clamp to powers of two so at most
        # log2(step_horizon)+1 scan lengths are ever traced)
        self.step_horizon = max(1, step_horizon)
        assert prefill_chunk_tokens >= 0
        if prefill_chunk_tokens > max_context:
            prefill_chunk_tokens = max_context
        self.prefill_chunk_tokens = prefill_chunk_tokens
        self.warmup_compile = warmup_compile
        if prefix_cache and not prefill_chunk_tokens:
            raise ValueError(
                "prefix_cache requires chunked admission "
                "(prefill_chunk_tokens > 0): a cache-hit suffix prefill "
                "must attend to pooled prefix K/V, which the whole-prompt "
                "dense prefill cannot — enable chunking or disable the "
                "prefix cache")
        self._prefix = PrefixCache(page_size) if prefix_cache else None
        assert spec_decode_k >= 0
        self.spec_decode_k = spec_decode_k
        # sliding-window serving (ISSUE 19): static per-model — every
        # serving trace of a window-enabled model bakes the O(window)
        # kernel clamp in, and the host reclaims pages wholly out of
        # every live window back to the free pool mid-flight (see
        # _reclaim_window_pages). Windowed slots also ALLOCATE lazily:
        # admission reserves only the window-bound page count and
        # _ensure_pages tops the frontier up just before each round
        # (see _window_slot_pages) — pool capacity prices O(window) per
        # long slot, not O(prompt + budget).
        w = getattr(self.cfg, "attention_window_size", None)
        self.window = int(w) if w else None
        # window_reclaim=False keeps the window MASK but never frees a
        # page mid-flight — the A/B control the bitwise reclamation pin
        # runs against (outputs must be identical by construction:
        # reclaimed pages are exactly the ones no kernel reads again)
        self.window_reclaim = bool(window_reclaim)
        if self.window is not None and not prefill_chunk_tokens:
            raise ValueError(
                "attention_window_size requires chunked admission "
                "(prefill_chunk_tokens > 0): whole-prompt admission "
                "prefills through the DENSE path, which carries no "
                "window mask, so its cache would disagree with every "
                "windowed chunked/decode step — enable chunking or "
                "clear the window")
        self._window_reclaimed = 0
        self.kv_dtype = kv_dtype
        self.quantize_weights = quantize_weights
        self.termination_id = termination_id
        self.vocab_size = vocab_size
        self.timers = timers

        if self._ctx is not None:
            # onto this engine's devices BEFORE the decode tree is cut
            # from it: prepare_decode_params copies every layer, and
            # done where the caller's tree lives (replica 0's chip for
            # a router fleet) those copies pile up there — measured on
            # four v5e chips, PR 21: 7.3 GB peak on chip 0 against 3.2
            # on the rest. (A tp=1 tree already on this engine's
            # chip stays as it is: device_put onto the equivalent
            # one-device mesh sharding would copy it whole.)
            from megatron_llm_tpu.parallel.sharding import param_shardings

            here = set(self._ctx.mesh.devices.flat)
            if self.serving_tp > 1:
                params = jax.device_put(
                    params, param_shardings(self._ctx, self.cfg, params))
            elif any(not isinstance(x, jax.Array) or x.devices() != here
                     for x in jax.tree.leaves(params)):
                params = jax.device_put(params, self._rep)
        if quantize_weights:
            if not hasattr(model, "prepare_decode_params"):
                raise ValueError(
                    "quantize_weights=True needs the model's "
                    "prepare_decode_params(quantize_int8=...) decode "
                    "layout (weight-only int8 is a decode-tree "
                    "transform)")
            dec = model.prepare_decode_params(params, quantize_int8=True)
        elif hasattr(model, "prepare_decode_params"):
            # tp engines keep the UNFLATTENED (h, 2, f) GLU layout: the
            # single-chip (h, 2f) flatten concatenates gate|up along
            # the axis tp shards (parallel/sharding.decode_param_specs)
            dec = model.prepare_decode_params(
                params, flatten_glu=(self.serving_tp == 1))
        else:
            dec = params
        if self.serving_tp > 1:
            from megatron_llm_tpu.parallel.sharding import (
                decode_param_shardings,
            )

            dec = jax.device_put(
                dec, decode_param_shardings(self._ctx, self.cfg, dec))
        self._dec_params = dec
        caches = model.init_paged_kv_caches(
            slots, self.num_pages, page_size, self.max_pages_per_slot,
            kv_dtype=jnp.int8 if kv_dtype == "int8" else None,
            mesh_ctx=self._ctx)
        # ONE tree of device state that every step takes, donates and
        # hands back whole: the page pools, an int8 engine's fp32 scale
        # pools (ISSUE 9), the per-slot state of layers that keep one.
        # The page table and the lengths are the host's (mirrors below).
        self._cache = {k: v for k, v in caches.items()
                       if k not in ("page_table", "lengths")}
        if kv_dtype == "int8" and page_size % 32 != 0:
            # the int8 Pallas gate needs 32-sublane pages: with this
            # page_size every TPU step silently takes the dequantizing
            # XLA twin (full fp32 pool materialization per layer per
            # step) — worse bandwidth than the bf16 path the operator
            # opted out of. Legitimate off-TPU (the twin IS the CPU
            # path), so warn loudly instead of refusing.
            _logger.warning(
                "kv_dtype=int8 with page_size=%d: the int8 paged "
                "kernels need page_size %% 32 == 0 — on TPU this "
                "config serves every step through the dequantizing "
                "XLA fallback and forfeits the bandwidth win. Use "
                "page_size 32/64 (docs/GUIDE.md 'Quantized serving')",
                page_size)
        V = self.cfg.padded_vocab_size
        self._last_logits = self._dev(np.zeros((slots, V), np.float32))
        homes = {x.sharding for x in jax.tree.leaves(self._dec_params)
                 if getattr(x, "committed", False)}
        if self._ctx is None and len(homes) == 1:
            # one committed argument (a restored checkpoint's leaf, a
            # device_put one) commits every output of a jitted step,
            # and a committed argument is another program than an
            # uncommitted one: what rides from round to round starts
            # out the way it comes back, or warm-up compiles one
            # program and the first round another (seen on the v5e at
            # PR 34 with committed leaves: three 32-layer compiles
            # inside a 50 s window)
            self._cache, self._last_logits = jax.device_put(
                (self._cache, self._last_logits), homes.pop())
        # host-authoritative mirrors (tiny; shipped to device each step)
        self._pt = np.zeros((slots, self.max_pages_per_slot), np.int32)
        self._lengths = np.zeros((slots,), np.int32)
        self._free_pages = list(range(self.num_pages - 1, 0, -1))

        self._slots = [_Slot() for _ in range(slots)]
        self._queue: collections.deque = collections.deque()
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._next_rid = 0
        self._thread: Optional[threading.Thread] = None
        self._running = False
        self._broken: Optional[str] = None

        # -- compiled-cost registry + perf sentinel (ISSUE 15) ------------
        # Construction precedes the _copy_fn mint below so the first
        # executable this engine ever mints is already capturable.
        self.costs = None
        self.chip = None
        if cost_registry:
            from megatron_llm_tpu.telemetry.chipspec import detect_chip
            from megatron_llm_tpu.telemetry.costs import CostRegistry

            self.chip = detect_chip(
                devices=self._ctx.mesh.devices.flatten().tolist()
                if self._ctx is not None else None,
                override=chip_spec)
            # owner=self: the mint-listener inventory tracks THIS
            # engine's variants, not a sibling replica's
            self.costs = CostRegistry(chip=self.chip, owner=self).attach()
        # analytic per-token decode-FLOPs coefficients for the
        # per-request cost record (telemetry/chipspec.py model):
        # linear term 2*N over the decode tree, attention term
        # 4*L*h per cached position
        self._cost_fpt_linear = 0.0
        self._cost_attn_coeff = 0.0
        if self.costs is not None:
            n_dec = sum(
                int(np.prod(l.shape)) for l in jax.tree.leaves(dec)
                if hasattr(l, "shape"))
            self._cost_fpt_linear = 2.0 * n_dec
            self._cost_attn_coeff = (4.0 * self.cfg.num_layers
                                     * self.cfg.hidden_size)
        # modeled-vs-measured dispatch accounting (round granularity)
        self._modeled_device_ms = 0.0
        self._measured_round_ms = 0.0
        self._modeled_gflops = 0.0
        self._page_rounds = 0
        self._sentinel = None
        if perf_sentinel_ksigma > 0:
            from megatron_llm_tpu.telemetry.sentinel import PerfSentinel

            self._sentinel = PerfSentinel(
                k_sigma=perf_sentinel_ksigma,
                # clamped like the trainer's (arguments.py path): a
                # too-small CLI value degrades to the floor instead of
                # an unexplained AssertionError at server startup
                window=max(perf_sentinel_window, 4),
                patience=max(perf_sentinel_patience, 1),
                recorder=None,  # wired to self.recorder below (the
                # recorder is constructed in the telemetry block)
                name="decode_round_ms")

        self._step_fns: dict = {}  # horizon bucket -> jitted scan
        self._mixed_fns: dict = {}  # (width bucket, greedy) -> jitted
        # spec verification executables: ONE width (spec_decode_k + 1)
        # per greedy specialization — shorter drafts pad via chunk_lens,
        # so traffic can never mint per-draft-length buckets
        # (tests/test_spec_decode.py pins the count)
        self._spec_fns: dict = {}  # (width, greedy) -> jitted
        self._copy_fn = _make_page_copy_fn(
            contract_key=(), contract_owner=self, contract_budget=1)
        self._capture_cost("engine.page_copy", (), self._copy_fn,
                           self._null_copy_args)
        # cross-replica KV hand-off pair (ISSUE 17). Minted eagerly
        # (jax.jit is lazy — no trace happens until a transfer or the
        # audit calls them) so the contract inventory and the audit's
        # entry-point walk see the same surface on every engine.
        self._export_fn = _make_page_export_fn(
            contract_key=(), contract_owner=self, contract_budget=1)
        self._capture_cost("engine.page_export", (), self._export_fn,
                           self._null_export_args)
        self._import_fn = _make_page_import_fn(
            contract_key=(), contract_owner=self, contract_budget=1)
        self._capture_cost("engine.page_import", (), self._import_fn,
                           self._null_import_args)
        # transfer inbox: export/import ops funneled onto the serve
        # thread. The serve loop DONATES the page pools every round, so
        # a router-thread jit on self._pools_* would race a deleted
        # buffer; and the PrefixCache's documented thread contract puts
        # every mutating call on the serve thread. _step_inner drains
        # this deque at the top of each round; with no serve thread
        # (manual-step tests, bench setup) the op is applied inline.
        self._xfers: collections.deque = collections.deque()
        # hand-off accounting (gated: exported via counters() only
        # when a transfer has happened, keeping legacy JSON byte-
        # compatible per the PR 15 pin)
        self._transfers_out = 0
        self._transfer_pages_out = 0
        self._transfers_in = 0
        self._transfer_pages_in = 0
        # whole-prompt prefill executables, LRU-bounded like the pp
        # decode cache (api.py _pp_decode_fn): prompt buckets are an
        # unbounded key space across traffic
        self._prefill_fns: "collections.OrderedDict" = \
            collections.OrderedDict()

        # counters (exported through the timers-gauge path)
        self._admitted = 0
        self._retired = 0
        self._timed_out = 0  # deadline_s expiries (queued + running)
        self._steps = 0
        self._tokens_out = 0
        self._prefill_tokens = 0
        self._cancelled = 0  # cancel() reaps (disconnected streams)
        # speculative decoding accounting: proposed vs accepted draft
        # tokens (the acceptance-rate gauge) and spec rounds run
        self._spec_rounds = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._t0 = time.perf_counter()
        # recent-window latency gauges: submit -> first generated token
        # per request, and wall ms per decode-token advance per round
        # (a mixed round IS one decode step — its latency is exactly the
        # chunked-prefill interference the p95 gauge exists to expose)
        self._ttft_ms: collections.deque = collections.deque(maxlen=256)
        self._decode_ms: collections.deque = collections.deque(maxlen=256)
        # per-round accounting (prefill/decode token split + wall ms),
        # the auditable budget trail (tests pin the interference bound
        # on it; bench reads it for the decode-p95 row)
        self._round_log: collections.deque = collections.deque(
            maxlen=4096)

        # -- telemetry (ISSUE 13, ISSUE 26) -------------------------------
        # Span tracer: every span is a profiler annotation (it lands in
        # any jax.profiler capture, on the device's clock); with a
        # trace_dir the spans also fill the ring exported as Chrome
        # trace JSON at stop(). Flight recorder: ALWAYS on — a bounded ring
        # of per-round/lifecycle events auto-dumped on serve-loop
        # poison (record_dir; falls back to trace_dir) and served on
        # demand at GET /flight_record. Histograms: the distributional
        # SLO metrics behind the Prometheus text exposition on
        # GET /metrics. NONE of this touches jitted code: telemetry-on
        # steps are bitwise telemetry-off (tests/test_telemetry.py +
        # the graft-check audit pin it).
        self.trace_dir = trace_dir
        self.record_dir = record_dir if record_dir is not None else trace_dir
        self.tracer = SpanTracer(enabled=bool(trace_dir))
        if replica_id is not None:
            # replica correlation (ISSUE 14): every span and flight-
            # recorder event from this engine names its replica, so
            # aggregated dumps from N replicas behind the router stay
            # attributable (the SSE `id:` field and counters() carry
            # the same tag)
            self.tracer.set_context(replica=replica_id)
        self.recorder = FlightRecorder(
            flight_recorder_size,
            base=None if replica_id is None else {"replica": replica_id})
        if self._sentinel is not None:
            # the sentinel's bad/trip event trail lands in the same
            # flight ring its trip auto-dumps (ISSUE 15)
            self._sentinel.recorder = self.recorder
        self._hists = {
            "serve_ttft_ms": Histogram(
                "serve_ttft_ms", help_text="submit -> first generated "
                "token, per request"),
            "serve_decode_round_ms": Histogram(
                "serve_decode_round_ms", help_text="wall ms per decode-"
                "token advance per round (mixed rounds included: the "
                "chunked-prefill interference distribution)"),
            "serve_queue_wait_ms": Histogram(
                "serve_queue_wait_ms", help_text="submit -> slot "
                "admission, per request"),
        }
        self._rounds = 0  # did-work scheduler rounds (telemetry clock)
        # cumulative counts taken where the work is done (_emit_round):
        # token positions a round's program laid out against those that
        # carried a real token, rounds and summed wall ms by kind, and
        # the summed durations of the round's phase spans
        self._rows_computed = 0
        self._moe_stats = np.zeros(N_STATS, np.int64)
        self._rows_useful = 0
        self._kind_rounds = dict.fromkeys(ROUND_KINDS, 0)
        self._kind_ms = dict.fromkeys(ROUND_KINDS, 0.0)
        self._host_ms = dict.fromkeys(HOST_PHASES, 0.0)
        # fault-injection hook (ISSUE 20, inference/chaos.py): called at
        # the top of every scheduler round INSIDE the round's timed
        # window, so an injected stall rides the round wall the perf
        # sentinel measures (an honest trip, not a synthetic counter
        # bump) and an injected raise kills the serve loop through the
        # REAL poison path (flight-ring dump + _fail_all + _broken).
        # None (the default) is one attribute check per round — the
        # chaos-off hot path is unchanged.
        self._fault_hook = None
        # jax.profiler capture hook (POST /profile): armed request ->
        # started before the next round, stopped after N did-work
        # rounds; start/stop failures are LOGGED no-ops (capture is a
        # diagnostic, never a crash source)
        self._profile_pending: Optional[tuple] = None
        self._profile_active = False
        self._profile_left = 0
        self._profile_dir: Optional[str] = None

    # -- tp-mesh plumbing (ISSUE 14) ---------------------------------------

    def _dev(self, x, dtype=None):
        """Host operand -> device array. Single-chip engines keep the
        jnp.asarray fast path (bitwise-unchanged); mesh engines
        device_put REPLICATED onto the serving mesh — a committed
        single-device array mixed into a pjit over sharded pools would
        be an incompatible-devices error, and every small operand
        (page table, lengths, sampling knob arrays, scan inputs) is by
        contract replicated (host-trivial scalar prefetch)."""
        if dtype is not None:
            x = np.asarray(x, dtype)
        if self._ctx is None:
            return jnp.asarray(x)
        return jax.device_put(np.asarray(x), self._rep)

    def _capture_cost(self, name: str, key, fn, args_thunk) -> None:
        """Compiled-cost capture for one freshly MINTED executable
        (ISSUE 15): lowers `fn` against warmup-style example args (the
        thunk defers building them — and any device_put they need —
        until the registry is actually on) and records cost_analysis
        FLOPs/bytes + memory_analysis temp/args under (contract, key).
        Mint-time only by construction: every call site sits next to a
        builder invocation, never in the per-round path (the GR006
        contract); the capture itself pays one extra AOT compile per
        executable, which is why cost_registry is opt-in."""
        if self.costs is None:
            return
        with self.mesh_scope():
            self.costs.capture(name, key, fn, args_thunk())

    def _artifact_tag(self, base: str) -> str:
        """Filename tag for exported artifacts (span traces, flight-
        record dumps): N in-process replicas share a pid, so an
        untagged per-pid filename would let later replicas silently
        overwrite earlier ones' postmortems — the replica id joins the
        name whenever one is set."""
        if self.replica_id is None:
            return base
        return f"{base}-r{self.replica_id}"

    def mesh_scope(self):
        """Context manager installing the serving-mesh ParallelContext
        for the duration of a dispatch: the model's shard_activation
        constraints read the global context AT TRACE TIME, so every
        site that can trace a step executable (step()/warmup()/
        audit_entry_points()) runs under this scope. GSPMD then
        partitions the traced program over the tp mesh — pools sharded
        per kv_pool_spec, activations steered by the existing
        heads/groups/ffn constraint sites, collectives materialised by
        the partitioner (the pjit-TPUv4 playbook); the same context
        tells shard_kernel which mesh the paged kernel call is manual
        over. `use_mesh` installs a
        THREAD-LOCAL override (parallel/mesh.py), so N tp engines'
        serve threads each trace under their own mesh concurrently —
        no process-wide lock, no fleet serialization. tp=1 engines
        (including device-pinned replicas) return a null scope: a
        1-device mesh needs no constraints at all."""
        if self._ctx is None or self.serving_tp == 1:
            return contextlib.nullcontext()
        from megatron_llm_tpu.parallel.mesh import use_mesh

        return use_mesh(self._ctx)

    # -- admission ---------------------------------------------------------

    def submit(self, prompt: List[int], tokens_to_generate: int, *,
               top_k: int = 1, top_p: float = 0.0,
               temperature: float = 1.0, seed: int = 0,
               return_log_probs: bool = False,
               use_eod_for_early_termination: bool = True,
               deadline_s: Optional[float] = None,
               stream: bool = False,
               ) -> EngineRequest:
        """Queue one request. Raises ValueError when it cannot ever fit
        (prompt + generation past max_context) and QueueFull when the
        queue is at capacity — callers map the latter to 503.

        `deadline_s` is a wall-clock budget measured from submit: once
        exceeded, the request's waiter fails with TimeoutError and —
        when it was running — its slot retires and the pages return to
        the free list, so an abandoned request can never pin pool
        capacity or wedge the FIFO head forever.

        `stream=True` attaches a per-request token queue
        (`req.stream_q`): every generated token id is pushed as it is
        booked, and a None sentinel closes the stream on completion OR
        failure — consumers must treat the sentinel, not result(), as
        end-of-stream, then call result() for the final status."""
        total = len(prompt) + tokens_to_generate
        if len(prompt) < 1:
            raise ValueError("empty prompt")
        if tokens_to_generate < 1:
            raise ValueError("tokens_to_generate must be >= 1 (score-only "
                             "requests take the whole-batch path)")
        if total > self.max_context:
            raise ValueError(
                f"prompt ({len(prompt)}) + tokens_to_generate "
                f"({tokens_to_generate}) exceeds the engine max_context "
                f"({self.max_context})")
        # must also fit the POOL: under an oversubscribed page_budget a
        # request can satisfy max_context yet need more pages than the
        # pool holds — admitted, it would sit at the FIFO head forever
        # and starve everything behind it. Window-enabled engines
        # (ISSUE 19) price a request at the WINDOW bound, not its full
        # reach: out-of-window pages reclaim mid-flight, so a long slot
        # can never hold more than _window_slot_pages at once.
        need = -(-total // self.page_size)
        if self.window is not None and self.window_reclaim:
            need = min(need, self._window_slot_pages())
        if need > self.num_pages - 1:
            raise ValueError(
                f"request needs {need} pages but the pool holds only "
                f"{self.num_pages - 1} (page_budget "
                f"{(self.num_pages - 1) * self.page_size} tokens); raise "
                f"page_budget or shrink the request")
        if self._broken is not None:
            raise RuntimeError(f"engine is stopped: {self._broken}")
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s must be positive, got {deadline_s}")
        req = EngineRequest(
            rid=-1, prompt=list(prompt),
            tokens_to_generate=tokens_to_generate,
            replica_id=self.replica_id,
            greedy=(top_k == 1), top_k=top_k, top_p=top_p,
            temperature=temperature, seed=seed,
            return_log_probs=return_log_probs,
            use_eod_for_early_termination=use_eod_for_early_termination,
            deadline_s=deadline_s,
            stream_q=queue_mod.SimpleQueue() if stream else None,
        )
        req.t_submit = time.perf_counter()
        with self._lock:
            if len(self._queue) >= self.max_queue:
                raise QueueFull(
                    f"engine queue at capacity ({self.max_queue})")
            req.rid = self._next_rid
            self._next_rid += 1
            self._queue.append(req)
            self._work.notify()
        # per-request ID assigned above is THE correlation key: it rides
        # SSE `id:` fields, server error logs, trace spans and these
        # flight-recorder events (ISSUE 13)
        self.recorder.record(
            "submit", rid=req.rid, prompt_tokens=len(req.prompt),
            tokens_to_generate=tokens_to_generate, stream=stream)
        return req

    @staticmethod
    def _finish(req: EngineRequest):
        """The ONE completion point: wake the waiter and close the
        token stream (None sentinel) so an SSE consumer can never hang
        on a request that already failed/retired."""
        req.done.set()
        if req.stream_q is not None:
            req.stream_q.put(None)

    def cancel(self, req: EngineRequest):
        """Abandon a request (e.g. its streaming client disconnected):
        queued requests fail their waiter immediately; a running one is
        flagged and reaped by the scheduler's next round — the slot
        retires and its pages return/release exactly like a normal
        retirement, so shared-prefix refcounts stay intact. Idempotent;
        a no-op on requests that already finished."""
        with self._lock:
            if req.done.is_set():
                return
            req.cancelled = True
            try:
                self._queue.remove(req)
            except ValueError:
                # not queued: running (the serve loop reaps it) or
                # being admitted right now (ditto, next round)
                self._work.notify()
                return
            # inside the lock: the serve thread increments this counter
            # too (running-slot reap), and a racing unlocked += would
            # drop counts under concurrent disconnects
            self._cancelled += 1
        req.error = f"request {req.rid} cancelled"
        self._finish(req)

    _PREFILL_CACHE_CAP = 8

    def _prefill_fn(self, plen):
        """Whole-prompt prefill executable per bucket, LRU-bounded at
        _PREFILL_CACHE_CAP (requeue-on-hit, loud eviction) — the same
        contract as the pp decode cache (api.py _pp_decode_fn): prompt
        buckets are a small-but-unbounded key space across traffic, and
        an unbounded dict held every executable forever."""
        if plen in self._prefill_fns:
            fn = self._prefill_fns.pop(plen)
            self._prefill_fns[plen] = fn  # LRU requeue
            return fn
        while len(self._prefill_fns) >= self._PREFILL_CACHE_CAP:
            old, _ = self._prefill_fns.popitem(last=False)
            # the budget counts LIVE executables: eviction un-counts
            release_variant("engine.prefill_bucket", old, owner=self)
            _logger.warning(
                "prefill executable cache full (%d): evicting LRU bucket "
                "%d; the next prompt at that bucket recompiles its "
                "prefill (chunked admission — prefill_chunk_tokens > 0 — "
                "avoids per-prompt buckets entirely)",
                self._PREFILL_CACHE_CAP, old,
            )
        fn = _make_prefill_fn(self.model, plen, self.page_size,
                              contract_key=plen, contract_owner=self,
                              contract_budget=self._PREFILL_CACHE_CAP)
        self._prefill_fns[plen] = fn
        self._capture_cost("engine.prefill_bucket", plen, fn,
                           lambda: self._null_prefill_args(plen))
        return fn

    def _admit(self) -> int:
        """Move queued requests into free slots while pages allow.
        FIFO head-of-line: a request that does not fit blocks the ones
        behind it (predictable latency ordering, no starvation).
        Returns the prompt tokens PREFILLED ON DEVICE during this call
        (whole-prompt mode only; chunked admission does no device work
        here), so the caller's round accounting can attribute the
        in-round prefill stall honestly."""
        prefilled = 0
        for si, slot in enumerate(self._slots):
            if slot.req is not None:
                continue
            with self._lock:
                if not self._queue:
                    return prefilled
                req = self._queue[0]
                need = -(-(len(req.prompt) + req.tokens_to_generate)
                         // self.page_size)
                # prefix sharing: cache-hit pages map into the page
                # table instead of being allocated + prefilled.
                # return_log_probs requests bypass MATCHING (their
                # prompt logprobs need the full forward) but still
                # register their pages below for later requests.
                match = None
                if self._prefix is not None and not req.return_log_probs:
                    match = self._prefix.lookup(req.prompt)
                    if match.matched == 0:
                        match = None
                matched_pages = match.full_pages if match else 0
                # windowed engines (ISSUE 19) reserve only the window
                # bound up front — _ensure_pages tops the frontier up
                # before each round and _reclaim_window_pages returns
                # dead pages, so a long request never holds O(prompt +
                # budget) pages. Shared prefix pages are refcounts, not
                # allocations, so a hit larger than the bound still
                # maps whole (its out-of-window pages release back to
                # the cache on the first reclaim pass); a COW divergence
                # always gets its fresh private page.
                cap = need
                if self.window is not None and self.window_reclaim:
                    cap = max(min(need, self._window_slot_pages()),
                              matched_pages
                              + (1 if match is not None
                                 and match.cow_src is not None else 0))
                need_new = max(cap - matched_pages, 0)
                if match is not None:
                    # pin the hit (incl. the COW source) BEFORE any
                    # eviction below could free it out from under us
                    self._prefix.acquire(match)
                if len(self._free_pages) < need_new \
                        and self._prefix is not None:
                    # reclaim unreferenced cached prefixes (LRU) before
                    # blocking the FIFO head on pages
                    evicted = self._prefix.evict(
                        need_new - len(self._free_pages))
                    if evicted:
                        self.tracer.instant("prefix_evict", rid=req.rid,
                                            pages=len(evicted))
                        self.recorder.record("prefix_evict", rid=req.rid,
                                             pages=len(evicted))
                    self._free_pages.extend(evicted)
                if len(self._free_pages) < need_new:
                    if match is not None:
                        self._prefix.unacquire(match)
                    return prefilled
                self._queue.popleft()
                # claim the slot INSIDE the lock: stop(drain=True) polls
                # "queue empty and no slot busy" — a request must never
                # be invisible to that check between dequeue and prefill
                slot.req = req
            fresh = [self._free_pages.pop() for _ in range(need_new)]
            pages = (list(match.pages) if match is not None else []) + fresh
            self._pt[si] = 0
            self._pt[si, :len(pages)] = pages
            slot.pages = pages
            slot.mapped = len(pages)
            slot.reclaimed = 0
            slot.generated = 0
            slot.sample_step = 0
            slot.registered = match.full_pages if match is not None else 0
            slot.bigram = {}
            slot.bigram_next = 0
            # per-request cost accounting (ISSUE 15): admission round,
            # prefill origin, and counters the retire record reads
            slot.admit_round = self._rounds
            slot.prefill_start = 0
            slot.prefilled = 0
            slot.spec_accepted = 0
            req.tokens = list(req.prompt)
            if self.prefill_chunk_tokens:
                # chunked admission: no device work here beyond the COW
                # copy — the prompt suffix prefills incrementally
                # through the mixed rounds, resumable at
                # slot.prefill_pos (== the matched-token count: cache-
                # hit positions never prefill)
                matched = 0
                if match is not None:
                    matched = match.matched
                    if match.cow_src is not None:
                        # copy-on-write: the divergent page starts as a
                        # private replica of the shared page (data AND
                        # scale pools — a quantized page is the pair);
                        # prefill resumes at the divergence offset
                        # inside it, so the shared page never sees this
                        # request's writes
                        with self.tracer.span(
                                "cow_copy", rid=req.rid,
                                src=match.cow_src,
                                dst=pages[match.full_pages]):
                            self._cache = self._copy_fn(
                                self._cache,
                                self._dev(match.cow_src, np.int32),
                                self._dev(pages[match.full_pages],
                                          np.int32))
                        self._prefix.release_page(match.cow_src)
                        self._prefix.cow_copies += 1
                if self._prefix is not None:
                    self._prefix.note(len(req.prompt), matched)
                slot.prefill_pos = matched
                slot.prefill_start = matched
                slot.forced = collections.deque()
                self._lengths[si] = matched
            else:
                plen = bucket_prefill_len(len(req.prompt))
                with self.tracer.span("prefill_bucket", rid=req.rid,
                                      slot=si, tokens=plen):
                    self._cache, row_logits, plp = \
                        self._prefill_fn(plen)(
                            self._dec_params, self._cache,
                            self._dev(np.asarray(req.prompt[:plen],
                                                 np.int32)[None]),
                            self._dev(self._pt[si]),
                        )
                self._last_logits = \
                    self._last_logits.at[si].set(row_logits)
                self._lengths[si] = plen
                slot.prefill_pos = len(req.prompt)
                slot.forced = collections.deque(req.prompt[plen:])
                slot.prefilled = plen
                self._prefill_tokens += plen
                prefilled += plen
                if req.return_log_probs:
                    req.log_probs = [float(x) for x in np.asarray(plp)]
            req.t_admit = time.perf_counter()
            # queue-wait telemetry: a retroactive span from the
            # request's own stamps (submit -> admission), plus the
            # histogram behind the Prometheus exposition
            wait_ms = (req.t_admit - req.t_submit) * 1e3
            self.tracer.complete("queue_wait", req.t_submit, req.t_admit,
                                 rid=req.rid, slot=si)
            self._hists["serve_queue_wait_ms"].observe(wait_ms)
            self.recorder.record(
                "admit", rid=req.rid, slot=si,
                queue_wait_ms=round(wait_ms, 3),
                prefill_start=slot.prefill_pos, pages=need)
            self._admitted += 1
        return prefilled

    def _request_cost(self, si: int) -> Optional[dict]:
        """The per-request device-cost record stamped into the retire
        event (ISSUE 15; cost_registry on). GR006 HOT_PATHS: pure host
        arithmetic over the slot's own counters and the host-side
        length mirror — never a device value. modeled_mflops is the
        analytic decode model (telemetry/chipspec.decode_flops_per_token
        coefficients precomputed at construction): the linear term over
        every position this request computed past its cache-hit offset,
        plus the attention integral over its context growth. A MODELED
        number by contract — it prices the request for cost-per-token
        attribution (the Gemma fine-tune-and-serve framing), it is not
        a profiler measurement."""
        if self.costs is None:
            return None
        slot = self._slots[si]
        req = slot.req
        final_len = int(self._lengths[si])
        start = slot.prefill_start
        computed = max(final_len - start, 0)
        rounds_held = self._rounds - slot.admit_round + 1
        pages = len(slot.pages)
        modeled = (self._cost_fpt_linear * computed
                   + 0.5 * self._cost_attn_coeff
                   * (final_len * final_len - start * start))
        return {
            "prompt_tokens": len(req.prompt),
            "cached_tokens": start,
            "prefill_tokens": slot.prefilled,
            "decode_tokens": slot.generated,
            "spec_accepted": slot.spec_accepted,
            "rounds_held": rounds_held,
            "pages": pages,
            "page_rounds": pages * rounds_held,
            "modeled_mflops": round(modeled / 1e6, 3),
        }

    def _retire(self, si: int):
        slot = self._slots[si]
        # cost record FIRST: it reads pages/lengths/counters this
        # method is about to reset
        cost = self._request_cost(si)
        if cost is not None:
            self._modeled_gflops += cost["modeled_mflops"] / 1e3
            self._page_rounds += cost["page_rounds"]
        if self._prefix is None:
            self._free_pages.extend(slot.pages)
        else:
            # refcounted returns: registered/shared pages stay with the
            # cache (evictable once unreferenced); only untracked pages
            # (generated tokens, partial prompt tails, lost insert
            # races) go straight back to the free list
            for pg in slot.pages:
                if not self._prefix.release(pg):
                    self._free_pages.append(pg)
        slot.pages = []
        slot.registered = 0
        slot.mapped = 0
        slot.reclaimed = 0
        self._pt[si] = 0
        self._lengths[si] = 0
        req = slot.req
        slot.req = None
        req.t_done = time.perf_counter()
        self._retired += 1
        self.tracer.instant("retire", rid=req.rid, slot=si,
                            generated=slot.generated,
                            error=req.error is not None)
        # the retire event schema grows the cost record ONLY when the
        # registry is on (the pre-ISSUE-15 event stays byte-identical)
        self.recorder.record("retire", rid=req.rid, slot=si,
                             generated=slot.generated, error=req.error,
                             **({"cost": cost} if cost is not None
                                else {}))
        self._finish(req)

    # -- sliding-window page bookkeeping (ISSUE 19) ------------------------

    def _window_slot_pages(self) -> int:
        """Peak physical pages a window-enabled slot holds: pages
        overlapping [L - window + 1, L + round_width) at any length L —
        the window itself, the widest span one round can write past it
        (decode horizon / prefill chunk / spec verify chunk), plus one
        boundary page each side. THE windowed capacity unit: submit()
        prices requests with it, _admit reserves it, start() logs it."""
        width = max(self.step_horizon, self.prefill_chunk_tokens,
                    self.spec_decode_k + 1)
        return min(self.max_pages_per_slot,
                   -(-(self.window + width) // self.page_size) + 1)

    def _ensure_pages(self, si: int, upto: int) -> None:
        """Top the slot's physical page frontier up to cover positions
        [0, upto): windowed slots allocate lazily (admission reserved
        only the window bound), so every round calls this for exactly
        the span it is about to write — the jitted step scatters K/V
        across page boundaries and must find real pages in the table.
        No-op when the frontier already covers `upto` (always, for
        non-window engines: admission mapped the full reach)."""
        if self.window is None:
            return
        want = min(-(-upto // self.page_size), self.max_pages_per_slot)
        s = self._slots[si]
        while s.mapped < want:
            if not self._free_pages and self._prefix is not None:
                self._free_pages.extend(
                    self._prefix.evict(want - s.mapped))
            if not self._free_pages:
                # unreachable when submit()/_admit price the window
                # bound correctly — reclamation returns a page for
                # every page the frontier consumes past the window
                raise RuntimeError(
                    f"page pool exhausted topping slot {si} up to "
                    f"{want} pages — window admission accounting bug")
            pg = self._free_pages.pop()
            self._pt[si, s.mapped] = pg
            s.pages.append(pg)
            s.mapped += 1

    def _reclaim_window_pages(self) -> None:
        """Release pages wholly below every live window back to the
        pool (the engine-side half of the ISSUE 19 tentpole). At length
        L the next query attends no position below L - window + 1, and
        lengths are monotone, so logical pages [0, (L+1-window) //
        page_size) are dead forever: the kernel's double-ended DMA
        clamp never dereferences their table entries again and the XLA
        twin masks their columns to exact-0 probabilities — freeing
        (and reusing) them is bitwise-invisible to the stream, which
        tests pin (reclamation ON == OFF). Refcount discipline:
        registered/shared prefix pages RELEASE to the cache (still
        evictable, never free-listed while referenced — a concurrent
        slot may be reading them inside ITS window); only private
        refcount-1 pages return to the free list. Table entries park
        on null page 0 and slot.reclaimed advances so _retire never
        double-releases; unregistered reclaimed pages also advance
        slot.registered so _register_prefix can never insert a freed
        page."""
        W = self.window
        if W is None or not self.window_reclaim:
            return
        ps = self.page_size
        for si, s in enumerate(self._slots):
            if s.req is None:
                continue
            dead = min(max(0, int(self._lengths[si]) + 1 - W) // ps,
                       s.mapped)
            if dead <= s.reclaimed:
                continue
            for p in range(s.reclaimed, dead):
                pg = int(self._pt[si, p])
                self._pt[si, p] = 0
                if s.pages and s.pages[0] == pg:
                    s.pages.pop(0)
                if pg == 0:
                    continue
                if self._prefix is not None and self._prefix.release(pg):
                    pass  # shared/registered: the cache retains it
                else:
                    self._free_pages.append(pg)
                self._window_reclaimed += 1
            n = dead - s.reclaimed
            s.reclaimed = dead
            if s.registered < dead:
                s.registered = dead
            self.tracer.instant("window_reclaim", rid=s.req.rid,
                                slot=si, pages=n)

    # -- the decode loop ---------------------------------------------------

    def _step_fn(self, horizon, all_greedy):
        key = (horizon, all_greedy)
        if key not in self._step_fns:
            # the contract registry is the ONE executable counter: a
            # horizon outside the pow2 bucket set blows the budget and
            # fails HERE, at mint time (analysis/contracts.py)
            self._step_fns[key] = _make_step_fn(
                self.model, self.vocab_size, horizon, all_greedy,
                contract_key=key, contract_owner=self,
                contract_budget=2 * len(horizon_buckets(self.step_horizon)))
            self._capture_cost(
                "engine.decode_scan", key, self._step_fns[key],
                lambda: self._null_scan_args(horizon))
        return self._step_fns[key]

    def _mixed_fn(self, width, all_greedy):
        key = (width, all_greedy)
        if key not in self._mixed_fns:
            self._mixed_fns[key] = _make_mixed_step_fn(
                self.model, self.vocab_size, width, all_greedy,
                contract_key=key, contract_owner=self,
                contract_budget=2 * len(
                    mixed_width_buckets(self.prefill_chunk_tokens)))
            self._capture_cost(
                "engine.mixed_step", key, self._mixed_fns[key],
                lambda: self._null_mixed_args(width))
        return self._mixed_fns[key]

    def _chunk_width(self, remaining: int) -> int:
        """Pow2 width bucket for a chunk covering `remaining` prompt
        tokens, capped at the budget: the mixed step traces once per
        distinct width, so at most log2(prefill_chunk_tokens)+1
        executables exist regardless of prompt lengths."""
        c = self.prefill_chunk_tokens
        if remaining >= c:
            return c
        return min(1 << (max(remaining, 1) - 1).bit_length(), c)

    def _book_token(self, i: int, tok: int, now: Optional[float] = None
                    ) -> bool:
        """Record one GENERATED token for slot i (TTFT on the first);
        retires the slot on eod/budget. Returns True if it retired."""
        s = self._slots[i]
        r = s.req
        r.tokens.append(tok)
        if r.stream_q is not None:
            r.stream_q.put(tok)
        s.generated += 1
        s.sample_step += 1
        self._tokens_out += 1
        if s.generated == 1:
            r.t_first = now if now is not None else time.perf_counter()
            ttft = (r.t_first - r.t_submit) * 1e3
            with self._lock:  # counters() sorts this window concurrently
                self._ttft_ms.append(ttft)
            self._hists["serve_ttft_ms"].observe(ttft)
            self.tracer.instant("first_token", rid=r.rid,
                                ttft_ms=round(ttft, 3))
        hit_eod = (r.use_eod_for_early_termination
                   and self.termination_id is not None
                   and tok == self.termination_id)
        if hit_eod or s.generated >= r.tokens_to_generate:
            self._retire(i)
            return True
        return False

    def _expire_deadlines(self) -> None:
        """Fail every queued/running request past its wall-clock
        deadline (TimeoutError at the waiter) and reclaim running slots'
        pages — run once per scheduler round, so enforcement granularity
        is one round (≤ one horizon scan / one mixed chunk)."""
        now = time.perf_counter()
        expired_q: List[EngineRequest] = []
        with self._lock:
            if any(r.expired(now) for r in self._queue):
                keep = collections.deque()
                for r in self._queue:
                    if r.expired(now):
                        expired_q.append(r)
                    else:
                        keep.append(r)
                self._queue = keep
        for r in expired_q:
            r.error = (f"request {r.rid} exceeded deadline_s="
                       f"{r.deadline_s} while queued")
            r.timed_out = True
            self._timed_out += 1
            self.recorder.record("timeout_queued", rid=r.rid,
                                 deadline_s=r.deadline_s)
            self._finish(r)
        for i, s in enumerate(self._slots):
            r = s.req
            if r is None:
                continue
            if r.cancelled:
                # cancel() mid-flight (e.g. streaming client gone):
                # retire exactly like a completion — pages return or
                # release through the refcounted path, shared-prefix
                # refcounts stay intact
                r.error = (f"request {r.rid} cancelled after "
                           f"{len(r.tokens) - len(r.prompt)}"
                           f"/{r.tokens_to_generate} generated tokens; "
                           f"slot retired, pages reclaimed")
                with self._lock:  # cancel() (HTTP thread) bumps it too
                    self._cancelled += 1
                self._retire(i)
                continue
            if r.expired(now):
                r.error = (f"request {r.rid} exceeded deadline_s="
                           f"{r.deadline_s} after {len(r.tokens) - len(r.prompt)}"
                           f"/{r.tokens_to_generate} generated tokens; "
                           f"slot retired, pages reclaimed")
                r.timed_out = True
                self._timed_out += 1
                self._retire(i)

    def step(self) -> bool:
        """One scheduler iteration (see _step_inner for the scheduling
        contract). This wrapper owns the telemetry clock (ISSUE 13):
        the jax.profiler capture hook (POST /profile) starts before /
        stops after the requested number of did-work rounds, the
        did-work round counter feeds span correlation (`round`), and
        every 256 rounds the flight recorder takes a counters()
        snapshot. All of it is host bookkeeping — the jitted dispatches
        inside are telemetry-blind."""
        if self._profile_pending is not None:
            self._start_profile()
        with self.mesh_scope():
            # the serving-mesh context is read at TRACE time by the
            # model's shard_activation sites; any round can lazily
            # trace a new horizon/width bucket, so every dispatch runs
            # scoped (a no-op null scope on tp=1 engines)
            did = self._step_inner()
        if did:
            self._rounds += 1
            if self._rounds % 256 == 0:
                self.recorder.note_counters(self.counters())
        if self._profile_active:
            self._tick_profile(did)
        return did

    def request_profile(self, rounds: int,
                        trace_dir: Optional[str] = None) -> dict:
        """Arm a `jax.profiler` device capture of the next `rounds`
        did-work engine rounds (the POST /profile hook). The capture
        starts before the next round the serve loop runs and stops
        once `rounds` have completed; start/stop failures (no profiler
        on this runtime, a capture already running out-of-band) are
        LOGGED no-ops recorded in the flight ring — a diagnostic hook
        must never take the serve loop down. One capture at a time:
        a second request while one is armed/active is refused."""
        if rounds < 1:
            raise ValueError(f"rounds must be >= 1, got {rounds}")
        d = trace_dir or self.trace_dir or "./profile"
        with self._lock:
            if self._profile_active or self._profile_pending is not None:
                return {"ok": False,
                        "error": "a profiler capture is already in "
                                 "progress"}
            self._profile_pending = (int(rounds), d)
            self._work.notify()
        self.recorder.record("profile_armed", rounds=int(rounds), dir=d)
        return {"ok": True, "rounds": int(rounds), "trace_dir": d}

    def _start_profile(self) -> None:
        with self._lock:
            pending, self._profile_pending = self._profile_pending, None
            if pending is not None:
                # claim the one-capture slot BEFORE the unlocked
                # start_trace below: a request_profile racing in here
                # must see busy, not arm a second capture the profiler
                # will refuse
                rounds, d = pending
                self._profile_active = True
                self._profile_left = rounds
                self._profile_dir = d
        if pending is None:
            return
        try:
            jax.profiler.start_trace(d)
        except Exception as e:  # noqa: BLE001 — capture is best-effort
            with self._lock:
                self._profile_active = False
            _logger.warning(
                "jax.profiler capture unavailable (%r): the /profile "
                "request is a no-op on this runtime", e)
            self.recorder.record("profile_unsupported", error=repr(e))
            return
        self.recorder.record("profile_start", rounds=rounds, dir=d)

    def _tick_profile(self, did: bool) -> None:
        if did:
            self._profile_left -= 1
        if self._profile_left <= 0:
            self._stop_profile()

    def _stop_profile(self) -> None:
        if not self._profile_active:
            return
        try:
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001
            _logger.warning("jax.profiler stop_trace failed: %r", e)
        with self._lock:
            self._profile_active = False
        self.recorder.record("profile_done", dir=self._profile_dir)
        _logger.info("profiler capture complete: %s", self._profile_dir)

    def _step_inner(self) -> bool:
        """One scheduler iteration. Chunked admission (the default):
        while any slot is mid-prefill, run one MIXED round — a budget-
        bounded ragged chunk of the oldest admitting prompt plus one
        decode token for every other live slot, one jitted dispatch —
        otherwise one jitted scan of up to `step_horizon` decode steps.
        A round with anything to do runs under the `engine.round` span
        with one child span a phase (schedule / build_inputs / dispatch
        / fetch / book: docs/GUIDE.md "Observability"); what it did is
        emitted once, by `_emit_round`, after the span closed. Returns
        False when there was nothing to do (idle)."""
        if not (self._queue or self._xfers
                or any(s.req is not None for s in self._slots)):
            if self._fault_hook is not None:
                self._fault_hook(self)
            return False
        facts = None
        with self.tracer.span("engine.round", round=self._rounds) as rnd:
            if self._fault_hook is not None:
                self._fault_hook(self)
            with self.tracer.span("engine.schedule",
                                  queue_depth=len(self._queue)) as sched:
                self._expire_deadlines()
                did = self._apply_transfers()
                admitted_before = self._admitted
                admit_prefilled = self._admit()
                sched.note(admitted=self._admitted - admitted_before,
                           prefilled_tokens=admit_prefilled)
            if self.prefill_chunk_tokens and any(
                    s.prefilling for s in self._slots):
                facts = self._mixed_round()
            else:
                drafts = (self._collect_drafts() if self.spec_decode_k
                          else None)
                if drafts:
                    facts = self._spec_round(drafts, admit_prefilled)
                else:
                    facts = self._decode_round(admit_prefilled)
        if facts is None:
            return did
        facts["phases"]["schedule"] = sched
        self._emit_round(rnd, facts)
        return True

    def _emit_round(self, rnd, facts: dict) -> None:
        """The one emission point of a round that dispatched: the budget
        audit trail (`_round_log`), the decode-latency window and its
        histogram, the modeled-vs-measured note, the perf sentinel, the
        cumulative counters and the flight recorder all read the same
        facts and the spans' own clock reads (the ring and the profiler
        took the spans as they closed). GR006 HOT_PATHS: host floats and
        dict writes only."""
        kind = facts["kind"]
        cost_name, event = ROUND_NAMES[kind]
        dt_ms = rnd.seconds * 1e3
        # wall ms per decode-token advance; None for a round that
        # advanced no decoding slot (a mixed round of prefill alone)
        div = facts["advance_div"]
        advance_ms = dt_ms / div if div else None
        with self._lock:  # counters() reads these windows concurrently
            self._round_log.append({**facts["log"], "ms": dt_ms})
            if advance_ms is not None:
                self._decode_ms.append(advance_ms)
            self._rows_computed += facts["rows_computed"]
            self._rows_useful += facts["rows_useful"]
            if facts["moe_stats"] is not None:
                self._moe_stats += facts["moe_stats"]
            self._kind_rounds[kind] += 1
            self._kind_ms[kind] += dt_ms
            for phase, span in facts["phases"].items():
                self._host_ms[phase] += span.seconds * 1e3
        if advance_ms is not None:
            self._hists["serve_decode_round_ms"].observe(advance_ms)
        self._note_dispatch(cost_name, facts["cost_key"], dt_ms)
        self.recorder.record(event, round=self._rounds,
                             **facts["event_args"], ms=round(dt_ms, 3))
        if kind == "decode":
            # the sentinel eats decode-scan rounds only — the one
            # homogeneous per-token-advance series. A mixed round's wall
            # includes a prefill chunk (a long-prompt admission would
            # read as `patience` consecutive regressions; interference
            # is the serve_decode_round_ms HISTOGRAM's job) and a spec
            # round's per-advance latency moves with the ACCEPT RATE
            # (serve_spec_accept_rate's job), not with the hardware.
            self._sentinel_observe(advance_ms)

    def _note_dispatch(self, name: str, key, dt_ms: float) -> None:
        """Round-granularity modeled-vs-measured accounting behind the
        serve_dispatch_overhead_pct gauge (ISSUE 15): the registry's
        roofline device time for the executable this round dispatched
        vs the round's measured wall. GR006 HOT_PATHS: one dict lookup
        + float adds; rounds whose executable has no captured record
        (or no known chip) contribute measurement only and the gauge
        stays honest about its modeled denominator."""
        if self.costs is None:
            return
        self._measured_round_ms += dt_ms
        rec = self.costs.record(name, key)
        if rec is None:
            return
        modeled = rec.modeled_seconds(self.chip, n_chips=self.serving_tp)
        if modeled is not None:
            self._modeled_device_ms += modeled * 1e3

    def _sentinel_observe(self, ms_per_advance: float) -> None:
        """Feed the perf sentinel one DECODE-SCAN round's per-token-
        advance latency — the one homogeneous series (mixed and spec
        rounds are excluded at their call sites: prefill interference
        and accept-rate drift are not hardware regressions); a TRIP
        auto-dumps the flight ring through the same postmortem path as
        poison. GR006 HOT_PATHS: host floats; the dump runs only on
        the (rare) trip."""
        if self._sentinel is None:
            return
        if self._sentinel.observe(ms_per_advance, step=self._rounds):
            self.recorder.note_counters(self.counters())
            self.recorder.dump(
                self.record_dir,
                self._artifact_tag("perf-regression"),
                extra={"trip": self._sentinel.trips,
                       "threshold_ms": round(
                           self._sentinel.last_threshold, 3),
                       "round": self._rounds})

    def _decode_round(self, prefill_tokens: int = 0) -> Optional[dict]:
        """One jitted scan of up to `step_horizon` decode steps over
        every live slot (the decode-only round). The horizon is clamped
        to the nearest slot completion (so no request overruns its
        budget mid-scan) and bucketed to a power of two (bounded trace
        count). `prefill_tokens` is the device prefill _admit() ran
        inside this round (whole-prompt mode) — its stall is inside
        this round's wall time, so the audit entry must carry it.
        Returns the round's facts for `_emit_round`, None when no slot
        is live."""
        live = [i for i, s in enumerate(self._slots) if s.req is not None]
        if not live:
            return None
        with self.tracer.span("engine.build_inputs",
                              transfers=11) as sp_build:
            # nearest completion: forced tokens still owed + sampling
            # budget
            remaining = min(
                len(self._slots[i].forced) + self._slots[i].req
                .tokens_to_generate - self._slots[i].generated
                for i in live)
            hor = min(self.step_horizon, max(remaining, 1))
            hor = 1 << (hor.bit_length() - 1)  # pow2 bucket
            # windowed lazy allocation (ISSUE 19): the scan writes hor
            # tokens past each live length — the frontier must hold real
            # pages BEFORE dispatch (no-op for non-window engines)
            for i in live:
                self._ensure_pages(i, self._lengths[i] + hor)

            n = self.slots
            active = np.zeros(n, bool)
            forced = np.zeros((n, hor), np.int32)
            use_forced = np.zeros((n, hor), bool)
            greedy = np.ones(n, bool)
            temperature = np.ones(n, np.float32)
            top_k = np.zeros(n, np.int32)
            top_p = np.zeros(n, np.float32)
            seeds = np.zeros(n, np.uint32)
            sample_steps = np.zeros(n, np.int32)
            for i in live:
                s = self._slots[i]
                r = s.req
                active[i] = True
                nf = min(len(s.forced), hor)
                if nf:
                    forced[i, :nf] = [s.forced[t] for t in range(nf)]
                    use_forced[i, :nf] = True
                greedy[i] = r.greedy
                temperature[i] = r.temperature
                top_k[i] = r.top_k
                top_p[i] = r.top_p
                seeds[i] = np.uint32(r.seed & 0xFFFFFFFF)
                sample_steps[i] = s.sample_step
            all_greedy = all(self._slots[i].req.greedy for i in live)
            operands = (
                self._dev(self._pt), self._dev(self._lengths),
                self._last_logits, self._dev(active),
                self._dev(forced), self._dev(use_forced),
                self._dev(greedy), self._dev(temperature),
                self._dev(top_k), self._dev(top_p),
                self._dev(seeds), self._dev(sample_steps),
            )
        with self.tracer.span("engine.dispatch", fn="decode_scan",
                              kind="decode", width=hor,
                              greedy=all_greedy,
                              prefill_tokens=prefill_tokens,
                              decode_slots=len(live)) as sp_disp:
            chosen, chosen_lp, new_logits, self._cache, moe_stats = \
                self._step_fn(hor, all_greedy)(
                    self._dec_params, self._cache, *operands)
            self._last_logits = new_logits
        with self.tracer.span("engine.fetch") as sp_fetch:
            chosen = np.asarray(chosen)  # (slots, hor) — the scheduler's
            # own data dependency: the next round cannot be built
            # without it
            moe_stats = self._fetch_moe_stats(moe_stats)
            # P0 (graft-check GR006 dogfood): the logprob matrix is an
            # EXTRA per-round device->host transfer that most serving
            # traffic (return_log_probs=False) never reads — fetch it
            # only when some live request actually asked
            want_lp = any(self._slots[i].req.return_log_probs
                          for i in live)
            chosen_lp = np.asarray(chosen_lp) if want_lp else None
        self._steps += hor

        with self.tracer.span("engine.book") as sp_book:
            booked_before, retired_before = self._tokens_out, self._retired
            now = sp_book.t0
            for t in range(hor):
                for i in live:
                    s = self._slots[i]
                    r = s.req
                    if r is None:
                        continue  # retired earlier in this horizon (eod)
                    self._lengths[i] += 1
                    if r.return_log_probs:
                        r.log_probs.append(float(chosen_lp[i, t]))
                    if s.forced:
                        s.forced.popleft()  # prompt token, already in
                        continue            # tokens
                    self._book_token(i, int(chosen[i, t]), now)
            # out-of-window pages died as the round advanced lengths;
            # return them before the next round's admission/top-up
            # prices the pool (no-op for non-window engines)
            self._reclaim_window_pages()
            sp_book.note(booked=self._tokens_out - booked_before,
                         retired=self._retired - retired_before)
        return {
            "kind": "decode",
            "log": {"prefill_tokens": prefill_tokens, "decode_steps": hor,
                    "decode_slots": len(live)},
            # the scan amortizes hor steps (the whole-prompt admission
            # stall, when any, rides this round's wall time — that IS
            # the interference)
            "advance_div": hor,
            "moe_stats": moe_stats,
            "rows_computed": self.slots * hor,
            "rows_useful": len(live) * hor,
            "phases": {"build_inputs": sp_build, "dispatch": sp_disp,
                       "fetch": sp_fetch, "book": sp_book},
            "cost_key": (hor, all_greedy),
            "event_args": {"horizon": hor, "decode_slots": len(live),
                           "prefill_tokens": prefill_tokens},
        }

    def _mixed_round(self) -> dict:
        """One mixed prefill+decode round (chunked admission): the
        OLDEST admitting slot (FIFO by rid — bounds per-round prefill
        tokens to ONE chunk <= the budget) contributes a ragged prompt
        span resumed at its saved offset; every fully-prefilled live
        slot contributes one decode token; other admitting slots sit
        idle (chunk_lens 0). One jitted dispatch serves all of it, laid
        out as one packed row axis: `chunk_tokens` is the (width,) span
        of the admitting slot alone, every other operand is per slot
        (chunk_lens: the span's length for the admitting slot, 1 for a
        decoding slot, 0 for an idle one), so the round computes width
        + slots token rows.
        Returns the round's facts for `_emit_round`: decode slots
        advanced, prefill tokens consumed, the chunk request's rid (the
        round's correlation key: a streaming client's stalled `id:`
        greps straight to these rounds) and the (width, greedy)
        executable key the dispatch-overhead accounting reads."""
        with self.tracer.span("engine.build_inputs",
                              transfers=12) as sp_build:
            n = self.slots
            pref = [i for i, s in enumerate(self._slots) if s.prefilling]
            ci = min(pref, key=lambda i: self._slots[i].req.rid)
            s_c = self._slots[ci]
            remaining = len(s_c.req.prompt) - s_c.prefill_pos
            width = self._chunk_width(remaining)
            ln = min(remaining, width)
            dec = [i for i, s in enumerate(self._slots)
                   if s.req is not None and not s.prefilling]
            # windowed lazy allocation (ISSUE 19): this round scatters
            # the chunk's ln tokens (and one decode token per live slot)
            # past the frontiers — top them up before dispatch
            self._ensure_pages(ci, self._lengths[ci] + ln)
            for i in dec:
                self._ensure_pages(i, self._lengths[i] + 1)

            chunk_tokens = np.zeros((width,), np.int32)
            chunk_lens = np.zeros((n,), np.int32)
            is_prefill = np.zeros((n,), bool)
            greedy = np.ones(n, bool)
            temperature = np.ones(n, np.float32)
            top_k = np.zeros(n, np.int32)
            top_p = np.zeros(n, np.float32)
            seeds = np.zeros(n, np.uint32)
            sample_steps = np.zeros(n, np.int32)
            chunk_tokens[:ln] = s_c.req.prompt[
                s_c.prefill_pos:s_c.prefill_pos + ln]
            chunk_lens[ci] = ln
            is_prefill[ci] = True
            for i in dec:
                r = self._slots[i].req
                chunk_lens[i] = 1
                greedy[i] = r.greedy
                temperature[i] = r.temperature
                top_k[i] = r.top_k
                top_p[i] = r.top_p
                seeds[i] = np.uint32(r.seed & 0xFFFFFFFF)
                sample_steps[i] = self._slots[i].sample_step
            all_greedy = all(self._slots[i].req.greedy for i in dec)
            operands = (
                self._dev(self._pt), self._dev(self._lengths),
                self._last_logits, self._dev(chunk_tokens),
                self._dev(chunk_lens), self._dev(is_prefill),
                self._dev(ci, np.int32),
                self._dev(greedy), self._dev(temperature),
                self._dev(top_k), self._dev(top_p),
                self._dev(seeds), self._dev(sample_steps),
            )
        chunk_rid = s_c.req.rid
        with self.tracer.span("engine.dispatch", fn="mixed_step",
                              kind="mixed", width=width,
                              greedy=all_greedy, rid=chunk_rid,
                              prefill_tokens=ln,
                              decode_slots=len(dec)) as sp_disp:
            (first, first_lp, chunk_lps, new_last, self._cache,
             moe_stats) = self._mixed_fn(width, all_greedy)(
                    self._dec_params, self._cache, *operands)
            self._last_logits = new_last
        with self.tracer.span("engine.fetch") as sp_fetch:
            first = np.asarray(first)
            moe_stats = self._fetch_moe_stats(moe_stats)
            # P0 (graft-check GR006 dogfood): logprob outputs transfer
            # only when a live request asked for them — the mixed round
            # is the chunked-prefill interference path the decode-p95
            # gauge watches, so every needless per-round transfer counts
            want_lp = (s_c.req.return_log_probs
                       or any(self._slots[i].req.return_log_probs
                              for i in dec))
            first_lp = np.asarray(first_lp) if want_lp else None
            chunk_lps = (np.asarray(chunk_lps)
                         if s_c.req.return_log_probs else None)
        self._steps += 1
        self._prefill_tokens += ln

        with self.tracer.span("engine.book") as sp_book:
            booked_before, retired_before = self._tokens_out, self._retired
            # prefill slot: advance the saved offset, book prompt
            # logprobs (position p predicts prompt token p+1; the
            # chunk's first token was predicted by last round's final
            # logits = first_lp)
            r = s_c.req
            if r.return_log_probs:
                if s_c.prefill_pos > 0:
                    r.log_probs.append(float(first_lp[ci]))
                if ln > 1:
                    r.log_probs.extend(
                        float(x) for x in chunk_lps[:ln - 1])
            s_c.prefill_pos += ln
            s_c.prefilled += ln
            self._lengths[ci] += ln
            # every prompt page this chunk completed becomes a shareable
            # cache entry (no-op without the prefix cache)
            self._register_prefix(ci)

            # decode slots: one token each, the scan-path bookkeeping at
            # horizon 1
            now = sp_book.t0
            for i in dec:
                r = self._slots[i].req
                self._lengths[i] += 1
                if r.return_log_probs:
                    r.log_probs.append(float(first_lp[i]))
                self._book_token(i, int(first[i]), now)
            # out-of-window pages died as the round advanced lengths
            # (no-op for non-window engines)
            self._reclaim_window_pages()
            sp_book.note(booked=self._tokens_out - booked_before,
                         retired=self._retired - retired_before)
        return {
            "kind": "mixed",
            "log": {"prefill_tokens": ln, "decode_steps": 1,
                    "decode_slots": len(dec)},
            "advance_div": 1 if dec else None,
            "moe_stats": moe_stats,
            # the chunk at its width plus one row a slot; the chunk's
            # tokens and one token a decoding slot are real
            "rows_computed": width + n,
            "rows_useful": ln + len(dec),
            "phases": {"build_inputs": sp_build, "dispatch": sp_disp,
                       "fetch": sp_fetch, "book": sp_book},
            "cost_key": (width, all_greedy),
            "event_args": {"rid": chunk_rid, "prefill_tokens": ln,
                           "decode_slots": len(dec)},
        }

    # -- prefix sharing ----------------------------------------------------

    def _register_prefix(self, si: int) -> None:
        """Register every COMPLETED full prompt page of slot `si` in
        the prefix cache (called as chunked prefill passes each page
        boundary): a later request sharing the prefix hits these pages
        even while this one is still mid-flight. Only pages whose
        tokens are ENTIRELY prompt are registered — a page that also
        receives decode writes is request-specific. Shared pages mapped
        at admission arrive pre-counted in slot.registered; an insert
        lost to a concurrent identical prefill leaves the page
        untracked (free-listed at retirement), never double-indexed."""
        if self._prefix is None:
            return
        s = self._slots[si]
        r = s.req
        ps = self.page_size
        limit = min(s.prefill_pos, len(r.prompt))
        while (s.registered + 1) * ps <= limit:
            pg = int(self._pt[si, s.registered])
            self._prefix.insert(r.prompt[: (s.registered + 1) * ps], pg)
            s.registered += 1

    # -- speculative decoding ----------------------------------------------

    def _spec_fn(self, width, all_greedy):
        key = (width, all_greedy)
        if key not in self._spec_fns:
            self._spec_fns[key] = _make_spec_step_fn(
                self.model, self.vocab_size, width, all_greedy,
                contract_key=key, contract_owner=self,
                contract_budget=2)
            self._capture_cost(
                "engine.spec_verify", key, self._spec_fns[key],
                lambda: self._null_spec_args(width))
        return self._spec_fns[key]

    def _draft(self, si: int) -> List[int]:
        """Prompt-lookup (n-gram) drafter: find the most recent earlier
        occurrence of the request's trailing bigram in its own tokens
        (prompt + generated) and propose the continuation — free to
        compute, surprisingly effective on prompts the answer quotes
        (the Saxena prompt-lookup result). Greedy slots only: sampled
        verification would need rejection-sampling machinery for
        distribution parity. Drafts are capped so the verify chunk
        never writes a position past the request's reserved prompt +
        tokens_to_generate page reach."""
        s = self._slots[si]
        r = s.req
        if not r.greedy:
            return []
        cap = min(self.spec_decode_k,
                  r.tokens_to_generate - s.generated - 1)
        if self.window is not None:
            # window edge (ISSUE 19): keep the whole verify chunk
            # inside one window of its first position, so every chunk
            # row still attends the round's carried context
            cap = min(cap, self.window - 1)
        if cap <= 0:
            return []
        toks = r.tokens
        if len(toks) < 3:
            return []
        # fold newly-booked tokens into the bigram index; every start
        # j <= len-3 is interior (the trailing bigram at len-2 stays
        # out, or the lookup below would match itself)
        while s.bigram_next <= len(toks) - 3:
            j = s.bigram_next
            occ = s.bigram.setdefault((toks[j], toks[j + 1]), [])
            occ.append(j)
            if len(occ) > 8:
                del occ[0]
            s.bigram_next += 1
        # position len(toks) is decided by the carried logits inside
        # the round, so the continuation shifts by one: drafts cover
        # the positions after it. Prefer the newest occurrence whose
        # continuation fills the cap; on short-period repetition the
        # newest ones sit at the tail with truncated continuations, so
        # fall back to the longest available.
        occ = s.bigram.get((toks[-2], toks[-1]))
        if not occ:
            return []
        best_j, best_avail = None, 0
        for j in reversed(occ):
            avail = len(toks) - (j + 3)
            if avail >= cap:
                best_j, best_avail = j, avail
                break
            if avail > best_avail:
                best_j, best_avail = j, avail
        if best_j is None:
            return []
        return list(toks[best_j + 3: best_j + 3 + cap])

    def _collect_drafts(self) -> dict:
        """Drafts for every eligible live slot; empty dict means 'run a
        plain decode round'. No spec round while any slot still owes
        teacher-forced prompt tokens (whole-prompt mode's post-bucket
        tail): the spec step has no forcing machinery, and a sampled
        token where a forced one belongs would corrupt the stream."""
        if any(s.req is not None and s.forced for s in self._slots):
            return {}
        drafts = {}
        for i, s in enumerate(self._slots):
            if s.req is None:
                continue
            d = self._draft(i)
            if d:
                drafts[i] = d
        return drafts

    def _spec_round(self, drafts: dict, prefill_tokens: int = 0) -> dict:
        """One speculative round: every live slot contributes a ragged
        chunk — spec slots [next token + draft run], the rest plain
        width-1 decode rows — through ONE jitted width-(k+1) dispatch.
        The device verifies drafts against its own greedy targets
        (_make_spec_step_fn); the host books the first token plus the
        accepted run and rolls the slot's length mirror forward by
        exactly the booked count, which IS the rejection rollback (the
        next round's writes overwrite stale K/V past it). Returns the
        round's facts for `_emit_round`."""
        with self.tracer.span("engine.build_inputs",
                              transfers=11) as sp_build:
            width = self.spec_decode_k + 1
            n = self.slots
            live = [i for i, s in enumerate(self._slots)
                    if s.req is not None]
            # windowed lazy allocation (ISSUE 19): the verify chunk
            # writes up to 1 + len(draft) tokens past each live frontier
            for i in live:
                self._ensure_pages(
                    i, self._lengths[i] + 1 + len(drafts.get(i, [])))
            chunk_tokens = np.zeros((n, width), np.int32)
            chunk_lens = np.zeros((n,), np.int32)
            is_spec = np.zeros((n,), bool)
            greedy = np.ones(n, bool)
            temperature = np.ones(n, np.float32)
            top_k = np.zeros(n, np.int32)
            top_p = np.zeros(n, np.float32)
            seeds = np.zeros(n, np.uint32)
            sample_steps = np.zeros(n, np.int32)
            for i in live:
                s = self._slots[i]
                r = s.req
                d = drafts.get(i, [])
                if d:
                    chunk_tokens[i, 1:1 + len(d)] = d
                chunk_lens[i] = 1 + len(d)
                is_spec[i] = bool(d)
                greedy[i] = r.greedy
                temperature[i] = r.temperature
                top_k[i] = r.top_k
                top_p[i] = r.top_p
                seeds[i] = np.uint32(r.seed & 0xFFFFFFFF)
                sample_steps[i] = s.sample_step
            all_greedy = all(self._slots[i].req.greedy for i in live)
            operands = (
                self._dev(self._pt), self._dev(self._lengths),
                self._last_logits, self._dev(chunk_tokens),
                self._dev(chunk_lens), self._dev(is_spec),
                self._dev(greedy), self._dev(temperature),
                self._dev(top_k), self._dev(top_p),
                self._dev(seeds), self._dev(sample_steps),
            )
        with self.tracer.span("engine.dispatch", fn="spec_step",
                              kind="spec", width=width, greedy=all_greedy,
                              prefill_tokens=prefill_tokens,
                              decode_slots=len(live)) as sp_disp:
            (first, first_lp, gt, gt_lp, acc, new_last, self._cache,
             moe_stats) = self._spec_fn(width, all_greedy)(
                    self._dec_params, self._cache, *operands)
            self._last_logits = new_last
        with self.tracer.span("engine.fetch") as sp_fetch:
            first = np.asarray(first)
            moe_stats = self._fetch_moe_stats(moe_stats)
            gt = np.asarray(gt)
            acc = np.asarray(acc)
            # P0 (graft-check GR006 dogfood): the two logprob matrices
            # are EXTRA per-round device->host transfers that
            # logprob-less traffic (the common case) never reads — fetch
            # them only when some live request actually asked
            want_lp = any(self._slots[i].req.return_log_probs
                          for i in live)
            first_lp = np.asarray(first_lp) if want_lp else None
            gt_lp = np.asarray(gt_lp) if want_lp else None
        self._steps += 1
        self._spec_rounds += 1

        with self.tracer.span("engine.book") as sp_book:
            retired_before = self._retired
            now = sp_book.t0
            emitted_total = 0
            for i in live:
                s = self._slots[i]
                r = s.req
                d_n = int(chunk_lens[i]) - 1
                a = int(acc[i]) if d_n else 0
                self._spec_proposed += d_n
                # the round's first token (decided from the carried
                # logits, exactly a decode row), then the accepted draft
                # run — each accepted token IS the greedy target the
                # decode scan would have produced at that position
                emit = [(int(first[i]),
                         float(first_lp[i]) if want_lp else 0.0)]
                emit += [(int(gt[i, j]),
                          float(gt_lp[i, j]) if want_lp else 0.0)
                         for j in range(a)]
                booked = 0
                for j, (tok, lp) in enumerate(emit):
                    self._lengths[i] += 1
                    if r.return_log_probs:
                        r.log_probs.append(lp)
                    if j > 0:
                        # per-request spec accounting for the retire
                        # cost record: BEFORE _book_token, which may
                        # retire the slot (resetting its counters) on
                        # eod/budget
                        s.spec_accepted += 1
                    booked += 1
                    if self._book_token(i, tok, now):
                        break  # eod/budget: stale chunk tail never books
                emitted_total += booked
                # acceptance gauge counts only draft tokens actually
                # BOOKED (booked minus the first decode-row token):
                # eod/budget can retire the slot mid-run, and the
                # unbooked accepted tail must not inflate
                # serve_spec_accept_rate — operators read that gauge to
                # decide whether spec decode pays for itself
                self._spec_accepted += booked - 1
            # out-of-window pages died as the round advanced lengths
            # (no-op for non-window engines)
            self._reclaim_window_pages()
            sp_book.note(booked=emitted_total,
                         retired=self._retired - retired_before)
        return {
            "kind": "spec",
            # prefill_tokens: whole-prompt-mode _admit() ran its device
            # prefill inside this round's wall time (the _decode_round
            # contract) — the audit trail must carry it here too
            "log": {"prefill_tokens": prefill_tokens, "decode_steps": 1,
                    "decode_slots": len(live),
                    "spec_emitted": emitted_total},
            # per decode-token advance: one spec round advances
            # emitted/live tokens per slot
            "advance_div": max(emitted_total, 1) / len(live),
            "moe_stats": moe_stats,
            # every slot is laid out k+1 wide; the booked tokens (first
            # + accepted drafts) are what the round was for
            "rows_computed": n * width,
            "rows_useful": emitted_total,
            "phases": {"build_inputs": sp_build, "dispatch": sp_disp,
                       "fetch": sp_fetch, "book": sp_book},
            "cost_key": (width, all_greedy),
            "event_args": {"decode_slots": len(live),
                           "emitted": emitted_total,
                           "drafted": len(drafts)},
        }

    def drain(self):
        """Run until the queue and every slot are empty."""
        while self.step():
            pass

    def reset_prefix_cache(self):
        """Drop every cached prefix and return its pages to the free
        list. Only legal on an IDLE engine (no live slots): a live slot
        holding refcounted shared pages would double-free them at
        retirement once the owning cache is gone. Benchmarks use this
        to measure a cold cache on a compile-warmed engine."""
        if self._prefix is None:
            return
        busy = [i for i, s in enumerate(self._slots) if s.req is not None]
        if busy:
            raise RuntimeError(
                f"reset_prefix_cache on a busy engine (slots {busy} "
                f"live): drain() first")
        self._free_pages.extend(self._prefix.evict(self.num_pages))
        assert self._prefix.cached_pages == 0
        self._prefix = PrefixCache(self.page_size)

    # -- cross-replica KV page hand-off (ISSUE 17) -------------------------
    # Disaggregated serving's transfer pair: a prefill replica exports
    # the full-page prefix of a finished prompt as a self-contained
    # host payload; a decode replica imports it into freshly allocated
    # pages and registers the chain on its PrefixCache, so the next
    # submit() of that prompt admits as a prefix HIT and decodes
    # without prefilling. Both sides funnel through the transfer inbox
    # (`_xfers`): the serve loop donates the page pools every round and
    # the PrefixCache is serve-thread-only, so the actual pool work
    # always runs on the serve thread (or inline when no serve thread
    # exists — manual-step tests and bench setup).

    def export_prefix(self, prompt: List[int]):
        """Export the cached full-page prefix of `prompt` as a host
        payload dict, or None when this engine's PrefixCache holds no
        full page of it (never prefilled here, or already evicted).
        The donor's pages stay registered and unreferenced — shipping
        is a read, and LRU eviction reclaims them under pressure, so a
        hand-off that dies on the receiving side needs no donor-side
        cleanup at all."""
        self._refuse_page_transfer("page export (export_prefix)")
        if self._prefix is None:
            raise ValueError(
                "export_prefix needs prefix_cache=True: the transfer "
                "ships the cache's registered pages")
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("export_prefix: empty prompt")
        return self._run_transfer({"kind": "export", "prompt": prompt})

    def import_prefix(self, payload):
        """Splice an exported prefix payload into this engine's pool:
        allocate pages (evicting idle cache entries if short), scatter
        the payload rows in one jitted dispatch, and register the chain
        on the PrefixCache refcounted exactly like locally prefilled
        pages. Returns {'pages': shipped, 'registered': retained} on
        success; False when the pool stayed short after eviction (the
        caller falls back to prefilling locally). Geometry/dtype
        mismatches (page size, kv dtype, layer shapes) raise
        ValueError: splicing incompatible pages would poison decode."""
        self._refuse_page_transfer("page import (import_prefix)")
        if self._prefix is None:
            raise ValueError(
                "import_prefix needs prefix_cache=True: transferred "
                "pages land as cache entries")
        self._check_payload(payload)
        return self._run_transfer({"kind": "import", "payload": payload})

    def _check_payload(self, payload) -> None:
        """Receiver-side compatibility gate, on the CALLER's thread so
        a bad payload fails fast instead of poisoning the serve loop."""
        if not isinstance(payload, dict):
            raise ValueError("import_prefix: payload must be the dict "
                             "export_prefix produced")
        n = int(payload.get("pages", 0))
        ps = int(payload.get("page_size", 0))
        toks = payload.get("tokens") or []
        if n < 1 or n > self.max_pages_per_slot:
            raise ValueError(
                f"import_prefix: {n} pages outside [1, "
                f"{self.max_pages_per_slot}] for this engine")
        if ps != self.page_size:
            raise ValueError(
                f"import_prefix: payload page_size {ps} != engine "
                f"page_size {self.page_size}")
        if len(toks) != n * ps:
            raise ValueError(
                f"import_prefix: {len(toks)} prefix tokens for {n} "
                f"pages of {ps}")
        if str(payload.get("dtype")) != self.kv_pool_dtype():
            raise ValueError(
                f"import_prefix: payload kv dtype "
                f"{payload.get('dtype')} != pool "
                f"{self.kv_pool_dtype()} — a cross-dtype splice would "
                f"decode garbage")
        for key, name in _PAYLOAD_NAMES.items():
            pools = self._cache.get(key, ())
            rows = payload.get(name) or []
            if len(rows) != len(pools):
                raise ValueError(
                    f"import_prefix: {len(rows)} '{name}' layer blocks "
                    f"vs {len(pools)} pools (int8 (data, scale) pairs "
                    f"must travel together)")
            for i, (r, p) in enumerate(zip(rows, pools)):
                if tuple(r.shape[1:]) != tuple(p.shape[1:]):
                    raise ValueError(
                        f"import_prefix: '{name}' layer {i} page shape "
                        f"{tuple(r.shape[1:])} != pool "
                        f"{tuple(p.shape[1:])}")

    def _run_transfer(self, op: dict):
        """Apply `op` on the serve thread (inbox + wake + wait) or
        inline when no serve loop is running. Waiters poll the engine's
        liveness so a poisoned loop fails the hand-off instead of
        hanging the router's orchestration thread."""
        op["done"] = threading.Event()
        op["result"] = None
        op["error"] = None
        with self._lock:
            alive = (self._thread is not None and self._running
                     and self._broken is None)
            if alive:
                self._xfers.append(op)
                self._work.notify()
        if not alive:
            with self.mesh_scope():
                self._apply_transfer(op)
        else:
            while not op["done"].wait(timeout=0.05):
                if self._broken is not None or self._thread is None \
                        or not self._thread.is_alive():
                    # the loop died with the op possibly still queued;
                    # _fail_all also sweeps the inbox, so either way:
                    if not op["done"].is_set():
                        raise RuntimeError(
                            f"page transfer failed: engine "
                            f"{'broken: ' + self._broken if self._broken else 'stopped'}")
        if op["error"] is not None:
            raise op["error"]
        return op["result"]

    def _apply_transfers(self) -> bool:
        """Serve-thread inbox drain (top of every scheduler round)."""
        did = False
        while True:
            with self._lock:
                if not self._xfers:
                    return did
                op = self._xfers.popleft()
            self._apply_transfer(op)
            did = True

    def _fail_transfers(self, msg: str) -> None:
        while True:
            with self._lock:
                if not self._xfers:
                    return
                op = self._xfers.popleft()
            op["error"] = RuntimeError(msg)
            op["done"].set()

    def _apply_transfer(self, op: dict) -> None:
        try:
            if op["kind"] == "export":
                op["result"] = self._do_export(op["prompt"])
            else:
                op["result"] = self._do_import(op["payload"])
        except Exception as e:  # noqa: BLE001 — the waiter re-raises;
            # a transfer failure must never poison the serve loop
            op["error"] = e
        op["done"].set()

    def _do_export(self, prompt: List[int]):
        match = self._prefix.lookup(prompt)
        n = match.full_pages
        if n <= 0:
            return None
        # pin against eviction across the gather (serve-thread-local
        # today, but the pin is what makes that an implementation
        # detail rather than a liveness assumption)
        self._prefix.acquire(match)
        try:
            ids = np.zeros(self.max_pages_per_slot, np.int32)
            ids[:n] = match.pages[:n]
            rows = self._export_fn(self._cache, self._dev(ids))
            payload = {
                "tokens": list(prompt[: n * self.page_size]),
                "pages": n,
                "page_size": self.page_size,
                "dtype": self.kv_pool_dtype(),
                **{name: [np.asarray(r)[:n] for r in rows.get(key, ())]
                   for key, name in _PAYLOAD_NAMES.items()},
            }
        finally:
            self._prefix.unacquire(match)
        self._transfers_out += 1
        self._transfer_pages_out += n
        self.recorder.record("xfer.export", pages=n,
                             tokens=len(payload["tokens"]))
        return payload

    def _do_import(self, payload):
        n = int(payload["pages"])
        if n > len(self._free_pages):
            self._free_pages.extend(
                self._prefix.evict(n - len(self._free_pages)))
        if n > len(self._free_pages):
            return False  # pool full of LIVE pages: prefill locally
        pages = [self._free_pages.pop() for _ in range(n)]
        P = self.max_pages_per_slot
        ids = np.zeros(P, np.int32)
        ids[:n] = pages

        def pad(rows, pools):
            out = []
            for r, p in zip(rows, pools):
                block = np.zeros((P,) + tuple(p.shape[1:]),
                                 np.dtype(p.dtype))
                block[:n] = r
                out.append(self._dev(block))
            return tuple(out)

        self._cache = self._import_fn(
            self._cache, self._dev(ids),
            {key: pad(payload[_PAYLOAD_NAMES[key]], pools)
             for key, pools in self._cache.items()})
        rejected = self._prefix.insert_chain(
            [int(t) for t in payload["tokens"]], pages)
        self._free_pages.extend(rejected)
        registered = n - len(rejected)
        self._transfers_in += 1
        self._transfer_pages_in += registered
        self.recorder.record("xfer.import", pages=n,
                             registered=registered)
        return {"pages": n, "registered": registered}

    # -- modeled backlog / admission (ISSUE 17) ----------------------------

    def modeled_request_flops(self, prompt_tokens: int,
                              gen_tokens: int, start: int = 0):
        """Modeled device FLOPs to finish one request from cache length
        `start`: the same analytic integral the per-request cost record
        uses (linear 2N per computed token + attention 4*L*h per cached
        position, integrated over context growth). None when the cost
        registry is off — callers must fall back to occupancy signals,
        not model against zero coefficients."""
        if self.costs is None:
            return None
        final = prompt_tokens + gen_tokens
        start = min(max(int(start), 0), final)
        return (self._cost_fpt_linear * (final - start)
                + 0.5 * self._cost_attn_coeff
                * (float(final) ** 2 - float(start) ** 2))

    def modeled_backlog_flops(self):
        """Total modeled FLOPs queued on this engine: every queued
        request priced from zero, every live slot priced from its
        current cache length. The router's placement signal (ISSUE 17)
        — replaces raw queue_depth + slots_busy, which weighs a 4k-token
        prefill and a 12-token completion identically."""
        if self.costs is None:
            return None
        total = 0.0
        with self._lock:
            work = [(len(r.prompt), r.tokens_to_generate, 0)
                    for r in self._queue]
            for i, s in enumerate(self._slots):
                r = s.req
                if r is not None:
                    work.append((len(r.prompt), r.tokens_to_generate,
                                 int(self._lengths[i])))
        for plen, gen, start in work:
            total += self.modeled_request_flops(plen, gen, start)
        return total

    def modeled_backlog_seconds(self):
        """Modeled wall seconds to drain this engine's backlog at the
        chip's roofline: backlog FLOPs / (peak FLOP/s x serving_tp).
        None without a cost registry AND a credible chip spec — an SLO
        decision against a guessed peak would be dishonest, so callers
        degrade to the constant fallback instead."""
        fl = self.modeled_backlog_flops()
        if fl is None or self.chip is None:
            return None
        dtype = "int8" if self.quantize_weights else "bf16"
        rate = self.chip.peak_flops_for(dtype) * max(self.serving_tp, 1)
        return fl / max(rate, 1.0)

    def retry_after_s(self) -> float:
        """Honest Retry-After (ISSUE 17 satellite): the modeled drain
        time of the current backlog, clamped to [1, 60] s; constant 1 s
        when the cost registry is off (the pre-ISSUE-17 behaviour,
        pinned by tests/test_server.py)."""
        s = self.modeled_backlog_seconds()
        if s is None:
            return 1.0
        return float(min(max(s, 1.0), 60.0))

    # -- background serve loop --------------------------------------------

    def _fail_all(self, msg: str):
        """Fail every queued and in-flight request (fatal step error or
        non-drain stop) so no waiter hangs on a dead engine."""
        self._fail_transfers(msg)
        with self._lock:
            pending = list(self._queue)
            self._queue.clear()
        for req in pending:
            req.error = msg
            self._finish(req)
        for i, s in enumerate(self._slots):
            if s.req is not None:
                s.req.error = msg
                self._retire(i)

    # -- idle-round example args (ONE construction for warmup, the AOT
    # audit, and mint-time cost capture — three consumers of the same
    # shapes that previously each hand-built them, ISSUE 15 refactor).
    # All-zero page-table rows route every K/V write to the dead null
    # page; the live pools ride the args so what is traced/lowered is
    # exactly what traffic runs.

    def _null_scan_args(self, h: int) -> tuple:
        n = self.slots
        zeros_i = self._dev(np.zeros((n,), np.int32))
        return (self._dec_params, self._cache,
                self._dev(np.zeros_like(self._pt)), zeros_i,
                self._last_logits,
                self._dev(np.zeros(n, bool)),
                self._dev(np.zeros((n, h), np.int32)),
                self._dev(np.zeros((n, h), bool)),
                self._dev(np.ones(n, bool)),
                self._dev(np.ones(n, np.float32)),
                zeros_i,
                self._dev(np.zeros(n, np.float32)),
                self._dev(np.zeros(n, np.uint32)),
                zeros_i)

    def _null_mixed_args(self, w: int) -> tuple:
        n = self.slots
        zeros_i = self._dev(np.zeros((n,), np.int32))
        return (self._dec_params, self._cache,
                self._dev(np.zeros_like(self._pt)), zeros_i,
                self._last_logits,
                self._dev(np.zeros((w,), np.int32)),
                zeros_i,
                self._dev(np.zeros(n, bool)),
                self._dev(0, np.int32),
                self._dev(np.ones(n, bool)),
                self._dev(np.ones(n, np.float32)),
                zeros_i,
                self._dev(np.zeros(n, np.float32)),
                self._dev(np.zeros(n, np.uint32)),
                zeros_i)

    def _null_spec_args(self, w: int) -> tuple:
        n = self.slots
        zeros_i = self._dev(np.zeros((n,), np.int32))
        return (self._dec_params, self._cache,
                self._dev(np.zeros_like(self._pt)), zeros_i,
                self._last_logits,
                self._dev(np.zeros((n, w), np.int32)),
                zeros_i,
                self._dev(np.zeros(n, bool)),
                self._dev(np.ones(n, bool)),
                self._dev(np.ones(n, np.float32)),
                zeros_i,
                self._dev(np.zeros(n, np.float32)),
                self._dev(np.zeros(n, np.uint32)),
                zeros_i)

    def _null_prefill_args(self, plen: int) -> tuple:
        return (self._dec_params, self._cache,
                self._dev(np.zeros((1, plen), np.int32)),
                self._dev(self._pt[0]))

    def _null_copy_args(self) -> tuple:
        return (self._cache, self._dev(0, np.int32),
                self._dev(0, np.int32))

    def _null_xfer_ids(self):
        # all-null ids: every row gathers/scatters the dead page 0 —
        # the same idle-round idiom the other _null_*_args use
        return self._dev(
            np.zeros(self.max_pages_per_slot, np.int32))

    def _null_payload_rows(self) -> dict:
        """Zero payload row blocks shaped like a full-width import —
        one (max_pages_per_slot, ...) block per layer pool, pool
        dtypes, on the engine's devices."""
        P = self.max_pages_per_slot
        return jax.tree.map(
            lambda p: self._dev(np.zeros((P,) + tuple(p.shape[1:]),
                                         np.dtype(p.dtype))), self._cache)

    def _null_export_args(self) -> tuple:
        return (self._cache, self._null_xfer_ids())

    def _null_import_args(self) -> tuple:
        return (self._cache, self._null_xfer_ids(),
                self._null_payload_rows())

    def warmup(self):
        """Pre-trace every step executable the configured buckets can
        reach — the pow2 decode-scan horizons and (chunked mode) the
        pow2 mixed-step widths, greedy-specialized (the serving hot
        path) — so the first request never eats a compile stall.
        Warmup rounds run with every slot idle against the REAL pools:
        all K/V writes land on the dead null page (all-zero page-table
        rows), lengths are untouched on the host, and the returned
        last_logits is discarded, so warmup is invisible to traffic.
        Opt-in: `warmup_compile=True` runs it inside `start()`."""
        with self.mesh_scope():
            self._warmup_scoped()

    def _warmup_scoped(self):
        for h in horizon_buckets(self.step_horizon):
            self._cache = self._step_fn(h, True)(
                *self._null_scan_args(h))[-2]
        if self.prefill_chunk_tokens:
            for w in mixed_width_buckets(self.prefill_chunk_tokens):
                self._cache = self._mixed_fn(w, True)(
                    *self._null_mixed_args(w))[-2]
        if self.spec_decode_k:
            w = self.spec_decode_k + 1
            self._cache = self._spec_fn(w, True)(
                *self._null_spec_args(w))[-2]
        if self._prefix is not None:
            # hand-off pair (ISSUE 17): the first cross-replica
            # transfer must not eat a compile stall mid-burst. The
            # null import scatters zero rows into the dead null page
            # only (all-null ids), so like every other warmup dispatch
            # it is invisible to traffic; pools are reassigned from
            # the donated outputs.
            self._export_fn(*self._null_export_args())
            self._cache = self._import_fn(*self._null_import_args())

    def audit_entry_points(self):
        """(contract name, jitted fn, example args) for every jitted
        entry point this engine's configuration can dispatch — the AOT
        compile-contract audit (analysis/audit.py) lowers each one
        against the REAL pools/params, so what it audits is exactly
        what traffic runs. Args are the same idle-round construction
        warmup() and mint-time cost capture use (the _null_*_args
        helpers); nothing here executes — builders are invoked (minting
        variants within the engine's own budgets) but the returned fns
        are only lowered.

        On a tp mesh the caller must ALSO lower under `mesh_scope()`
        (analysis/audit.py does): the constraints bake at trace time,
        and the tp2 audit rows exist to pin exactly that program."""
        h = horizon_buckets(self.step_horizon)[-1]
        out = [("engine.decode_scan", self._step_fn(h, True),
                self._null_scan_args(h))]
        if self.prefill_chunk_tokens:
            w = mixed_width_buckets(self.prefill_chunk_tokens)[-1]
            out.append(("engine.mixed_step", self._mixed_fn(w, True),
                        self._null_mixed_args(w)))
        plen = bucket_prefill_len(min(8, self.max_context))
        out.append(("engine.prefill_bucket", self._prefill_fn(plen),
                    self._null_prefill_args(plen)))
        if self.spec_decode_k:
            w = self.spec_decode_k + 1
            out.append(("engine.spec_verify", self._spec_fn(w, True),
                        self._null_spec_args(w)))
        out.append(("engine.page_copy", self._copy_fn,
                    self._null_copy_args()))
        out.append(("engine.page_export", self._export_fn,
                    self._null_export_args()))
        out.append(("engine.page_import", self._import_fn,
                    self._null_import_args()))
        return out

    def start(self):
        assert self._thread is None, "engine already started"
        # startup capacity log (ISSUE 9): the kv_dtype decision and
        # what it buys, in the operator's units — mirrors the
        # serve_kv_* gauges on GET /metrics
        # capacity numbers are PER CHIP from live shardings (ISSUE 14
        # small fix): on a tp mesh the group-sharded pools cost 1/tp
        # per chip, and this log is what operators size against HBM
        _logger.info(
            "decode engine%s: %d slots, paged KV pool kv_dtype=%s%s — "
            "%d pages x %d tokens = %d KV positions, %.1f MiB/chip "
            "pool (%d bytes/token/chip)%s%s",
            "" if self.replica_id is None
            else f" [replica {self.replica_id}]",
            self.slots, self.kv_pool_dtype(),
            "" if self.serving_tp == 1
            else f" tp={self.serving_tp} (group-sharded)",
            self.num_pages - 1,
            self.page_size, (self.num_pages - 1) * self.page_size,
            self.kv_pool_bytes() / 2**20, self.kv_bytes_per_token(),
            ", weight-only int8 decode matmuls"
            if self.quantize_weights else "",
            "" if self.kv_dtype == "bf16" else
            " [fp default off: greedy parity is measured drift, not "
            "bitwise — see docs/GUIDE.md 'Quantized serving']",
        )
        if self.window is not None:
            # windowed capacity (ISSUE 19): what a long slot actually
            # costs — the operator sizes page_budget against THIS bound
            # per concurrent slot, not against max_context
            _logger.info(
                "sliding-window serving: window=%d tokens — peak "
                "%d pages/slot (vs %d at full max_context reach); "
                "out-of-window pages reclaim mid-flight "
                "(serve_window_reclaimed_pages on /metrics)",
                self.window, self._window_slot_pages(),
                self.max_pages_per_slot)
        if self.warmup_compile:
            self.warmup()
        self._running = True

        def loop():
            _name_os_thread(SERVE_THREAD)
            while self._running:
                try:
                    did = self.step()
                except Exception as e:  # noqa: BLE001 — a dead serve
                    # loop with hung waiters is strictly worse than any
                    # error it could swallow: fail every request LOUDLY
                    # and refuse new ones
                    self._broken = f"engine step failed: {e!r}"
                    _logger.exception("serve loop died; failing all "
                                      "in-flight requests")
                    # flight-recorder postmortem (ISSUE 13): the last-
                    # N-rounds record + live counters, BEFORE _fail_all
                    # clears the slots — the artifact must show what
                    # the engine was doing when it died, keyed by rid
                    self.recorder.record(
                        "poison", error=repr(e), round=self._rounds,
                        queue_depth=len(self._queue),
                        live_rids=[s.req.rid for s in self._slots
                                   if s.req is not None])
                    self.recorder.note_counters(self.counters())
                    self.recorder.dump(
                        self.record_dir,
                        self._artifact_tag("engine-poison"),
                        extra={"costs": self.costs.snapshot()}
                        if self.costs is not None else None)
                    self._stop_profile()
                    self._fail_all(self._broken)
                    self._running = False
                    return
                if not did:
                    with self._work:
                        if self._running:
                            # idle because no request, not because of
                            # the host: the device gap under this span
                            # is nobody's fault
                            with self.tracer.span(
                                    "engine.wait_for_work",
                                    live_slots=sum(
                                        s.req is not None
                                        for s in self._slots),
                                    queue_depth=len(self._queue)) as sp:
                                self._work.wait(timeout=0.05)
                            self._host_ms["wait"] += sp.seconds * 1e3

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name=SERVE_THREAD)
        self._thread.start()

    def stop(self, drain: bool = True):
        """Stop the serve loop; drain=True (graceful) finishes every
        admitted AND queued request first, drain=False fails queued
        requests and abandons running slots."""
        if self._thread is None:
            return
        if drain:
            while self._thread.is_alive() and self._broken is None:
                with self._lock:
                    busy = bool(self._queue) or any(
                        s.req is not None for s in self._slots)
                if not busy:
                    break
                time.sleep(0.005)
        self._running = False
        with self._work:
            self._work.notify_all()
        self._thread.join()
        self._thread = None
        # a transfer enqueued after the loop's last drain would hang
        # its waiter forever — sweep the inbox now the loop is gone
        self._fail_transfers("engine stopped")
        self._stop_profile()  # an interrupted capture still flushes
        if self.trace_dir:
            import os as _os

            path = self.tracer.export(_os.path.join(
                self.trace_dir,
                f"trace_{self._artifact_tag('engine')}_"
                f"{_os.getpid()}.json"))
            if path:
                _logger.info("engine span trace exported to %s "
                             "(Perfetto / chrome://tracing)", path)
        if not drain:
            self._fail_all("engine stopped")

    # -- observability -----------------------------------------------------

    # the page pools of the cache tree, under the names they had as four
    # tuples (read by tests, gauges and benchmark/program.py)
    _pools_k = property(lambda self: self._cache["k_pages_layers"])
    _pools_v = property(lambda self: self._cache["v_pages_layers"])
    _pools_ks = property(
        lambda self: self._cache.get("k_scales_layers", ()))
    _pools_vs = property(
        lambda self: self._cache.get("v_scales_layers", ()))

    def _refuse_for_slot_state(self, **asked) -> None:
        """THE capability check of a model that keeps per-slot state
        beside the paged K/V (a conv layer's last gated inputs):
        whatever copies, shares, exports or rolls back PAGES cannot yet
        do so for a slot's state, and what builds the decode tree or the
        mesh knows one kind of layer. Each is refused by name."""
        if not self.cfg.has_slot_state:
            return
        why = "a layer keeps per-slot state beside the paged K/V"
        refused = {
            "prefix_cache": asked["prefix_cache"],
            "spec_decode_k": asked["spec_decode_k"] > 0,
            "whole-prompt admission (prefill_chunk_tokens=0)":
                asked["prefill_chunk_tokens"] == 0,
            "serving_tp": asked["serving_tp"] > 1,
            "quantize_weights": asked["quantize_weights"],
        }
        for feature, on in refused.items():
            if on:
                raise CapabilityError(feature, why)

    def _refuse_page_transfer(self, feature: str) -> None:
        if self.cfg.has_slot_state:
            raise CapabilityError(
                feature, "a slot's state would have to travel with its "
                "pages")

    def _fetch_moe_stats(self, stats) -> Optional[np.ndarray]:
        """The round's four routing integers (models/moe.py), fetched
        beside the round's tokens; None for a model that does not route."""
        return None if stats is None else np.asarray(stats)

    def kv_pool_dtype(self) -> str:
        """The pool's ACTUAL storage dtype (e.g. 'int8', 'bfloat16',
        'float32') — what the gauges report. kv_dtype='bf16' means
        'the model compute dtype', so an fp32-compute deployment
        genuinely stores fp32 pages; reporting the constructor string
        there would contradict the bytes gauges next to it."""
        return str(self._pools_k[0].dtype)

    def kv_pool_bytes(self) -> int:
        """PER-CHIP HBM the paged KV pool holds — data pools plus
        (int8) scale pools, summed over layers, derived from the LIVE
        shardings of the actual allocated arrays (each leaf counts its
        shard shape, not its global shape). On a single chip the two
        are the same number this gauge always reported; on a tp mesh
        the group-sharded pools cost 1/tp per chip, and reporting the
        global bytes here would overstate per-chip capacity by tp×
        (ISSUE 14 small fix — operators size THIS against one chip's
        HBM). Pinned by tests/test_tp_serving.py."""
        total = 0
        for x in (*self._pools_k, *self._pools_v,
                  *self._pools_ks, *self._pools_vs):  # pages, not slot state
            shard = x.sharding.shard_shape(x.shape)
            total += int(np.prod(shard)) * x.dtype.itemsize
        return total

    def kv_bytes_per_token(self) -> int:
        """PER-CHIP KV bytes one cached token costs across all layers
        (K + V data + any scales) — the page-pool sizing number
        operators compare against one chip's HBM (docs/GUIDE.md sizing
        math: ~96 KiB/token bf16 at tp=1, /tp on a serving mesh, ~half
        for int8)."""
        return round(self.kv_pool_bytes()
                     / (self.num_pages * self.page_size))

    @staticmethod
    def _pct(window, p: float) -> float:
        xs = sorted(window)
        if not xs:
            return 0.0
        return xs[min(int(p * len(xs)), len(xs) - 1)]

    def health(self) -> dict:
        """Liveness snapshot for GET /health (inference/server.py): is
        the serve loop running, did it die poisoned (`_broken` carries
        the fatal step error), and how much work is pending. Cheap by
        design — a load balancer polls this."""
        alive = self._thread is not None and self._thread.is_alive()
        return {
            "alive": alive,
            "broken": self._broken,
            "queue_depth": len(self._queue),
            "slots_busy": sum(1 for s in self._slots if s.req is not None),
        }

    def counters(self) -> dict:
        """Live serving counters; exported via `export_gauges` through
        the existing timers-gauge path (training/timers.py) and served
        by the HTTP layer at GET /metrics (inference/server.py). The
        latency gauges are recent-window percentiles (last 256):
        `serve_ttft_*` = submit -> first GENERATED token per request,
        `serve_decode_p95_ms` = wall ms per decode-token advance per
        round — during chunked admission a mixed round IS one decode
        step, so this gauge is the chunked-prefill interference bound
        made visible."""
        occupied = sum(1 for s in self._slots if s.req is not None)
        dt = max(time.perf_counter() - self._t0, 1e-9)
        with self._lock:
            # snapshot the latency windows under the lock (the serve
            # loop appends to them under the same lock): sorting a
            # deque mid-append raises RuntimeError, and GET /metrics
            # must never die mid-traffic
            ttft = list(self._ttft_ms)
            decode_ms = list(self._decode_ms)
        out = {}
        if self.replica_id is not None:
            # replica tag first (ISSUE 14): aggregated /metrics from N
            # replicas stay attributable at the router. ABSENT on
            # standalone engines, so the pre-router JSON schema stays
            # byte-compatible (tests/test_telemetry.py pins it).
            out["serve_replica_id"] = self.replica_id
        out |= {
            # capacity gauges (ISSUE 9): which dtype the pool ACTUALLY
            # stores (kv_pool_dtype — consistent with the bytes gauges
            # by construction), what it costs, and what one token
            # costs — the int8 capacity doubling made visible to
            # operators (timers.gauge takes numbers or strings;
            # /metrics serves both)
            "serve_kv_dtype": self.kv_pool_dtype(),
            "serve_kv_pool_bytes": self.kv_pool_bytes(),
            "serve_kv_bytes_per_token": self.kv_bytes_per_token(),
            "serve_slot_occupancy": occupied / self.slots,
            "serve_queue_depth": len(self._queue),
            "serve_pages_in_use": self.num_pages - 1
            - len(self._free_pages),
            "serve_pages_free": len(self._free_pages),
            "serve_admitted": self._admitted,
            "serve_retired": self._retired,
            "serve_timed_out": self._timed_out,
            "serve_cancelled": self._cancelled,
            "serve_steps": self._steps,
            "serve_tok_s": round(self._tokens_out / dt, 2),
            "serve_prefill_tokens": self._prefill_tokens,
            "serve_ttft_p50_ms": round(self._pct(ttft, 0.50), 2),
            "serve_ttft_p95_ms": round(self._pct(ttft, 0.95), 2),
            "serve_decode_p95_ms": round(self._pct(decode_ms, 0.95), 2),
        }
        if self._prefix is not None:
            # hit-rate / shared-page / COW / eviction gauges
            # (prefix_cache.PrefixCache.stats), serve_-prefixed into the
            # one counters schema /metrics and the timers export share
            for k, v in self._prefix.stats().items():
                out["serve_" + k] = v
        if self.spec_decode_k:
            out["serve_spec_rounds"] = self._spec_rounds
            out["serve_spec_proposed"] = self._spec_proposed
            out["serve_spec_accepted"] = self._spec_accepted
            out["serve_spec_accept_rate"] = round(
                self._spec_accepted / max(self._spec_proposed, 1), 4)
        if self.costs is not None:
            # device-cost gauges (ISSUE 15; ABSENT when the registry is
            # off so the legacy JSON schema stays byte-compatible):
            # aggregated per-request modeled work + pool occupancy-time,
            # and — when the chip is known — modeled roofline device
            # time vs measured round wall (the dispatch-overhead gauge)
            out["serve_modeled_gflops"] = round(self._modeled_gflops, 3)
            out["serve_page_rounds"] = self._page_rounds
            out["serve_cost_records"] = self.costs.captures
            if self.chip is not None:
                out["serve_chip_spec"] = self.chip.label()
            if self._modeled_device_ms > 0 and self._measured_round_ms > 0:
                out["serve_dispatch_overhead_pct"] = round(
                    (self._measured_round_ms - self._modeled_device_ms)
                    / self._measured_round_ms * 100, 2)
        if self.window is not None:
            # sliding-window gauges (ISSUE 19; gated like every other
            # feature group so the window-off JSON stays byte-
            # compatible): the configured window and the pages returned
            # to the pool mid-flight
            out["serve_window_size"] = self.window
            out["serve_window_reclaimed_pages"] = self._window_reclaimed
        if self._sentinel is not None:
            # gated like the cost gauges: the sentinel-off schema is
            # the legacy one
            out["serve_perf_regressions"] = self._sentinel.trips
            out["serve_perf_bad_rounds"] = self._sentinel.bad_total
        if (self._transfers_out or self._transfers_in):
            # cross-replica hand-off gauges (ISSUE 17): present only
            # once this engine has actually shipped/received pages, so
            # every non-disaggregated deployment keeps the legacy JSON
            out["serve_transfers_out"] = self._transfers_out
            out["serve_transfer_pages_out"] = self._transfer_pages_out
            out["serve_transfers_in"] = self._transfers_in
            out["serve_transfer_pages_in"] = self._transfer_pages_in
        from megatron_llm_tpu.ops.dispatch import fallbacks

        gave_way = fallbacks()
        if gave_way:
            # process-wide and TPU-only (ops/dispatch.py): a requested
            # Pallas kernel that gave way to its XLA reference is named
            # here, so "served" can never quietly mean "fell back"
            out["serve_kernel_fallbacks"] = "; ".join(sorted(gave_way))
        # ISSUE 26, always on and LAST, so every schema pinned before
        # them stays a byte-compatible prefix: padding share = 1 -
        # useful / computed rows; rounds and their summed wall by kind
        # (chunk round against decode round); the summed durations of
        # the serve loop's phase spans
        with self._lock:
            out["serve_rows_computed"] = self._rows_computed
            out["serve_rows_useful"] = self._rows_useful
            for kind in ROUND_KINDS:
                out["serve_rounds_" + kind] = self._kind_rounds[kind]
            for kind in ROUND_KINDS:
                out["serve_round_ms_" + kind] = round(
                    self._kind_ms[kind], 3)
            for phase in HOST_PHASES:
                out["serve_host_ms_" + phase] = round(
                    self._host_ms[phase], 3)
            if self.cfg.num_experts:
                # a model that routes, after everything pinned before:
                # the rounds' own integers, booked by the serve loop
                for name, value in zip(MOE_COUNTERS, self._moe_stats):
                    out[name] = int(value)
        return out

    def export_gauges(self, timers=None):
        timers = timers if timers is not None else self.timers
        if timers is None:
            return
        for name, value in self.counters().items():
            timers.gauge(name, value)

    def histograms(self):
        """The engine's latency histograms (telemetry/prometheus.py):
        TTFT, per-decode-token-advance round ms, queue wait — the
        distributional SLO metrics the point-percentile gauges in
        counters() cannot express."""
        return list(self._hists.values())

    def prometheus_metrics(self) -> str:
        """The Prometheus text exposition GET /metrics serves under
        content negotiation: every numeric counter as a gauge, string
        facts as one info metric, plus the real histograms — and, with
        the cost registry on, the per-(contract, specialization)
        compiled-cost gauges as labeled samples (ISSUE 15). The JSON
        path (counters()) stays byte-compatible and untouched."""
        text = render_prometheus(self.counters(), self.histograms())
        if self.costs is not None:
            lines = self.costs.prometheus_lines()
            if lines:
                text += "\n".join(lines) + "\n"
        return text

    def flight_record(self) -> dict:
        """On-demand flight-recorder snapshot (GET /flight_record):
        the same artifact a dying engine dumps, with live counters —
        and, with the cost registry on, the full compiled-cost table —
        attached."""
        self.recorder.note_counters(self.counters())
        return self.recorder.snapshot(
            reason="on-demand",
            extra={"costs": self.costs.snapshot()}
            if self.costs is not None else None)
