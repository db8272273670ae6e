"""Typed configuration for megatron_llm_tpu.

Replaces the reference's 1075-line argparse tree (ref: arguments.py:14-345)
and its global-singleton access pattern (ref: global_vars.py:22-67) with
plain frozen dataclasses passed explicitly. The flag surface mirrors the
groups catalogued in SURVEY.md §2.5: network_size, regularization, training,
initialization, learning-rate, checkpointing, mixed precision, distributed,
validation, data.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Optional

import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Activation-recompute policy vocabulary (the registry's NAMES; the jax
# policy objects live in models/remat.py so this module stays import-light).
#
# The ladder, cheapest-memory first (FLOPs move the other way):
#   "full"      — jax.checkpoint with no policy: save only the layer
#                 boundary carry, recompute everything (+~1/3 FLOPs).
#   "offload"   — save the named matmul outputs like "selective" but park
#                 them in pinned HOST memory (save_and_offload_only_these_
#                 names): device HBM like "full", FLOPs like "selective",
#                 paid for in PCIe/DMA traffic — the long-sequence lever.
#   "selective" — save_only_these_names(...) over the named save points
#                 (models/remat.py CHECKPOINT_NAMES): keep the big matmul
#                 outputs, recompute only cheap elementwise ops. Megatron's
#                 "selective" granularity, generalized.
#   "save_dots" — jax.checkpoint_policies.checkpoint_dots: keep EVERY dot
#                 output (named or not); FLOP floor, more live HBM.
#   "none"      — no remat: AD saves whatever it wants (highest memory).
# ---------------------------------------------------------------------------

REMAT_POLICIES = ("full", "selective", "save_dots", "offload", "none")

# back-compat mapping from the reference's --recompute_granularity surface
_GRANULARITY_TO_POLICY = {None: "none", "selective": "selective",
                          "full": "full"}


# ---------------------------------------------------------------------------
# Model architecture
# ---------------------------------------------------------------------------


class CapabilityError(ValueError):
    """A feature was asked of a model that cannot give it yet.
    `feature` names it; the message says what stands in the way."""

    def __init__(self, feature: str, why: str):
        super().__init__(f"{feature}: not available for this model ({why})")
        self.feature = feature


@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters (ref: arguments.py:406-474 network_size group)."""

    num_layers: int = 2
    hidden_size: int = 128
    ffn_hidden_size: Optional[int] = None  # default 4*h, or derived for GLU presets
    num_attention_heads: int = 4
    # GQA/MQA: number of distinct KV heads (ref: arguments.py:420
    # --num_attention_heads_kv; MQA when 1, GQA when 1<kv<heads).
    num_attention_heads_kv: Optional[int] = None
    kv_channels: Optional[int] = None  # head_dim; default hidden/heads
    max_position_embeddings: int = 2048
    seq_length: int = 2048
    padded_vocab_size: int = 0  # set by tokenizer padding (see pad_vocab_size)
    make_vocab_size_divisible_by: int = 128

    # Norms (ref: arguments.py:434-445, fused_layer_norm.py:64-139)
    layernorm_epsilon: float = 1e-5
    use_rms_norm: bool = False
    use_post_ln: bool = False  # post-LN (BERT-style) vs default pre-LN

    # Projections / activations (ref: arguments.py:439-452)
    use_bias: bool = True
    glu_activation: Optional[str] = None  # liglu|geglu|reglu|swiglu
    hidden_act: str = "gelu"  # used when glu_activation is None

    # Position embeddings (ref: arguments.py:456-463, positional_embeddings.py)
    position_embedding_type: str = "absolute"  # absolute | rotary
    rope_scaling_factor: float = 1.0
    rope_theta: float = 10000.0

    # Falcon-style structure (ref: arguments.py:465-468, transformer.py:774-806)
    parallel_attn: bool = False  # attention and MLP read the same LN, summed
    parallel_layernorm: bool = False  # separate LN for MLP input (Falcon-40B)

    # Embedding/head tying (ref: arguments.py:470-473, gpt_model.py:56-78)
    tie_embed_logits: bool = True

    # Regularization (ref: arguments.py:544-574)
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    lima_dropout: bool = False  # layer-index-scaled dropout (ref: transformer.py:964-971)

    # Precision (ref: arguments.py:783-815)
    params_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    fp32_residual_connection: bool = False
    # NOTE deliberately absent: apply_query_key_layer_scaling and
    # attention_softmax_in_fp32 (ref arguments.py:632-650). Both exist to
    # keep fp16 softmax in range; this build ALWAYS computes attention
    # scores/softmax in fp32 (models/attention.py, ops/flash_attention.py),
    # which is the apply_query_key_layer_scaling=False +
    # attention_softmax_in_fp32=True behavior, so the knobs would be lies.

    # Init (ref: arguments.py:694-705, layers.py:79-125)
    init_method_std: float = 0.02
    use_scaled_init_method: bool = True  # output layers scaled by 1/sqrt(2L)

    # Recompute (ref: arguments.py:606-630). `recompute_granularity` keeps
    # the reference vocabulary; `remat_policy` is the first-class policy
    # name (REMAT_POLICIES above). Give ONE of them — when both are given
    # they must agree (full<->full, selective<->selective) or __post_init__
    # raises, so a script can never silently train with the wrong
    # memory/FLOP trade. `resolved_remat_policy` is what the model reads.
    recompute_granularity: Optional[str] = None  # None | "selective" | "full"
    remat_policy: Optional[str] = None  # None | one of REMAT_POLICIES
    recompute_method: str = "uniform"  # "uniform" | "block"
    recompute_num_layers: int = 1

    # Kernels
    use_flash_attn: bool = False  # Pallas flash-attention path
    use_fused_rmsnorm: bool = False  # Pallas fused RMSNorm path
    # Pallas decode-attention kernel (ops/decode_attention.py) on the
    # KV-cached single-token path. Default ON: off-TPU it falls back to
    # the XLA decode math unless decode_attn_interpret routes the real
    # kernel through the Pallas interpreter (the CPU test path).
    use_decode_attn: bool = True
    # below this allocated cache length the XLA matvecs win (kernel
    # launch overhead dominates a cache this small)
    decode_attn_min_cache: int = 128
    decode_attn_interpret: bool = False
    # Sliding-window attention on the PAGED serving path (ISSUE 19):
    # a token at position p attends [max(0, p - W + 1), p]. None = full
    # causal; W >= context is bitwise full-causal. Static — baked into
    # the serving traces, and the engine reclaims pages wholly out of
    # every live window mid-flight. Serving-side only for now: the
    # dense training paths ignore it (GUIDE "Long-context serving").
    attention_window_size: Optional[int] = None

    # BERT/T5 family (ref: --num_tokentypes language_model.py:160-170;
    # bert_binary_head bert_model.py:130)
    num_tokentypes: int = 0
    add_binary_head: bool = False

    # A layer's KIND is (operator, feed-forward), static per layer
    # (`layer_kind`). `layer_types[i]` names layer i's operator,
    # "full_attention" or "conv" (the gated short convolution of
    # models/short_conv.py, `conv_L_cache` taps); None = attention
    # everywhere. With `num_experts` > 0 every layer from
    # `num_dense_layers` on has the routed MLP of models/moe.py
    # (`num_experts_per_tok` experts a token, each `moe_intermediate_size`
    # wide) in place of the dense one. `qk_layernorm`: RMSNorm over each
    # q and k head's channels before RoPE.
    layer_types: Optional[tuple] = None
    conv_L_cache: int = 3
    qk_layernorm: bool = False
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: Optional[int] = None
    num_dense_layers: int = 0
    use_expert_bias: bool = False
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0

    def __post_init__(self):
        if self.layer_types is not None:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
            if len(self.layer_types) != self.num_layers or not set(
                    self.layer_types) <= {"full_attention", "conv"}:
                raise ValueError(
                    f"layer_types names {len(self.layer_types)} operators "
                    f"({sorted(set(self.layer_types))}) for "
                    f"{self.num_layers} layers of 'full_attention' | 'conv'")
        if self.num_experts and not (
                0 < self.num_experts_per_tok <= self.num_experts
                and self.moe_intermediate_size):
            raise ValueError(
                "num_experts > 0 needs num_experts_per_tok in "
                "[1, num_experts] and moe_intermediate_size")
        if self.kv_channels is None:
            object.__setattr__(
                self, "kv_channels", self.hidden_size // self.num_attention_heads
            )
        if self.num_attention_heads_kv is None:
            object.__setattr__(self, "num_attention_heads_kv", self.num_attention_heads)
        if self.ffn_hidden_size is None:
            object.__setattr__(self, "ffn_hidden_size", 4 * self.hidden_size)
        assert self.num_attention_heads % self.num_attention_heads_kv == 0
        if self.attention_window_size is not None \
                and self.attention_window_size < 1:
            raise ValueError(
                "attention_window_size must be >= 1 (or None for full "
                f"causal attention), got {self.attention_window_size}")
        # Recompute-policy validation: unknown strings raise HERE, at config
        # construction, never downstream as a silently-wrong memory/FLOP
        # trade (the pre-policy code mapped granularity="selective" to "no
        # remat at all" without a word).
        if self.recompute_granularity not in _GRANULARITY_TO_POLICY:
            raise ValueError(
                f"recompute_granularity={self.recompute_granularity!r}: "
                f"expected one of {sorted(k for k in _GRANULARITY_TO_POLICY if k)} "
                f"or None"
            )
        if self.remat_policy is not None \
                and self.remat_policy not in REMAT_POLICIES:
            raise ValueError(
                f"remat_policy={self.remat_policy!r}: expected one of "
                f"{REMAT_POLICIES} or None"
            )
        if self.recompute_method not in ("uniform", "block"):
            raise ValueError(
                f"recompute_method={self.recompute_method!r}: expected "
                f"'uniform' or 'block'"
            )
        if (self.remat_policy is not None
                and self.recompute_granularity is not None
                and _GRANULARITY_TO_POLICY[self.recompute_granularity]
                != self.remat_policy):
            raise ValueError(
                f"conflicting recompute flags: "
                f"recompute_granularity={self.recompute_granularity!r} "
                f"implies remat_policy="
                f"{_GRANULARITY_TO_POLICY[self.recompute_granularity]!r} "
                f"but remat_policy={self.remat_policy!r} was given; "
                f"specify one, or make them agree"
            )
        # method/num_layers only do anything under an active policy /
        # block splits — requesting them in a dead combination is the same
        # silent-misconfiguration class the checks above exist to reject
        if self.recompute_method == "block" \
                and self.resolved_remat_policy == "none":
            raise ValueError(
                "recompute_method='block' does nothing without an active "
                "remat policy: also pass remat_policy "
                "(full/selective/save_dots/offload) or "
                "recompute_granularity (full/selective)"
            )
        if self.recompute_num_layers != 1 and self.recompute_method != "block":
            raise ValueError(
                f"recompute_num_layers={self.recompute_num_layers} is only "
                f"read by recompute_method='block' (uniform remats every "
                f"layer); drop it or request block splits"
            )

    # -- derived ----------------------------------------------------------
    @property
    def resolved_remat_policy(self) -> str:
        """The active policy name (one of REMAT_POLICIES): `remat_policy`
        when given, else the reference-vocabulary mapping of
        `recompute_granularity` (None->none, selective->selective,
        full->full). __post_init__ guarantees the two agree."""
        if self.remat_policy is not None:
            return self.remat_policy
        return _GRANULARITY_TO_POLICY[self.recompute_granularity]

    @property
    def head_dim(self) -> int:
        return self.kv_channels

    @property
    def num_query_groups(self) -> int:
        return self.num_attention_heads_kv

    @property
    def q_per_kv(self) -> int:
        return self.num_attention_heads // self.num_attention_heads_kv

    @property
    def qkv_projection_size(self) -> int:
        # ref: transformer.py:316 — n*hd + 2*n_kv*hd, grouped layout.
        return self.kv_channels * (
            self.num_attention_heads + 2 * self.num_attention_heads_kv
        )

    @property
    def mlp_input_size(self) -> int:
        # GLU doubles the up-projection width (ref: transformer.py:92-102).
        mult = 2 if self.glu_activation else 1
        return mult * self.ffn_hidden_size

    def layer_kind(self, layer: int) -> tuple:
        """Layer `layer`'s (operator, feed-forward): ("attention" |
        "conv", "mlp" | "moe")."""
        conv = self.layer_types is not None \
            and self.layer_types[layer] == "conv"
        routed = self.num_experts > 0 and layer >= self.num_dense_layers
        return ("conv" if conv else "attention", "moe" if routed else "mlp")

    @property
    def layer_kinds(self) -> tuple:
        return tuple(self.layer_kind(i) for i in range(self.num_layers))

    @property
    def has_slot_state(self) -> bool:
        """Whether serving carries a per-slot state beside the paged K/V."""
        return any(op == "conv" for op, _ in self.layer_kinds)

    def pad_vocab_size(self, vocab_size: int, tp: int = 1) -> int:
        """Pad vocab so it divides evenly over TP ranks (ref: tokenizer.py:49-63)."""
        multiple = self.make_vocab_size_divisible_by * tp
        return ((vocab_size + multiple - 1) // multiple) * multiple


# ---------------------------------------------------------------------------
# Parallel layout
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParallelConfig:
    """Device-mesh layout (ref: parallel_state.py:51-214, arguments.py:820-866).

    The reference builds NCCL process groups for tp/pp/dp; here the same
    topology is a single `jax.sharding.Mesh` with axes (data, stage, model)
    and parallelism is expressed as sharding over those axes.
    """

    data_parallel_size: int = 1
    pipeline_parallel_size: int = 1
    tensor_parallel_size: int = 1
    # Context parallelism: the sequence axis sharded over the `context`
    # mesh axis, exact ring attention at every layer
    # (parallel/ring_attention.py). BEYOND-reference capability — the
    # reference's only long-sequence lever is SP + selective recompute
    # (ref: transformer.py:508-523); cp shards the N^2 attention itself.
    context_parallel_size: int = 1
    # NOTE deliberately absent: virtual/interleaved pipeline
    # (ref: --num_layers_per_virtual_pipeline_stage arguments.py:828).
    # vpp exists to shrink the pipeline bubble when 1F1B's memory
    # (∝ pp in-flight full-chunk stashes) forbids more microbatches. The
    # TPU schedule remats per tick, so per-stage live memory is one
    # boundary (b,s,h) per tick and raising num_microbatches is the
    # bubble lever (see parallel/pipeline.py module docstring).
    # Korthikanti sequence parallelism over the model axis
    # (ref: arguments.py:683; forced off at tp=1 per arguments.py:327-328).
    sequence_parallel: bool = False
    # ZeRO-1 optimizer-state sharding over data axis
    # (ref: --use_distributed_optimizer arguments.py:864). On pure-dp
    # meshes with a GPT-family model the gradient reduction runs the
    # EXPLICIT reduce-scatter/all-gather decomposition
    # (optimizer/zero1.py); mixed meshes keep the GSPMD-spec path.
    use_distributed_optimizer: bool = False
    # Size target (MB of fp32 gradient payload) for the explicit path's
    # reduce-scatter buckets — the analogue of the reference's
    # distributed.py grad-buffer packing. One collective per bucket per
    # microbatch; smaller buckets give the latency-hiding scheduler
    # more overlap slack, larger ones amortize collective launch.
    grad_rs_bucket_mb: float = 4.0
    # Opt-in EQuARX-style int8 gradient reduction (ops/quantization
    # conventions: symmetric RTN, per-chunk fp32 scales, fp32
    # accumulation of dequantized partials). Default OFF: the fp path
    # is bitwise-unchanged; drift is bounded by tests/test_zero1.py, not
    # assumed. Requires use_distributed_optimizer on a pure-dp mesh.
    quantized_grad_reduce: bool = False
    # Collective overlap scheduling (ISSUE 12). Both default OFF: the
    # eager explicit path stays the bitwise oracle.
    # --overlap_grad_reduce: the explicit path's backward runs in layer
    # GROUPS (sized by grad_rs_bucket_mb) and issues each group's
    # psum_scatter the moment its cotangents materialize — group N's
    # collective is consumed only after group N-1's backward is emitted
    # (double-buffered), so the latency-hiding scheduler can overlap
    # comm with the remaining backward compute. Requires the explicit
    # ZeRO-1 path (zero1 on a pure-dp mesh, GPT-family model); the m/v
    # layout follows the grads to a within-layer shard axis
    # (parallel/sharding.py zero1_axis skip_leading).
    overlap_grad_reduce: bool = False
    # --overlap_param_gather: the param reassembly after the sharded
    # Adam update becomes explicit per-bucket all-gathers issued
    # first-needed-first (embedding, then layer groups in forward
    # order), double-buffered like the reduce-scatters, instead of one
    # GSPMD whole-tree constraint. Same explicit-path requirements;
    # composes with either grad-reduce path and with
    # quantized_grad_reduce.
    overlap_param_gather: bool = False
    # --async_pipeline_dispatch (pp>1): decouple the stage-ring ppermute
    # from the lockstep tick — the boundary send for tick T is issued in
    # tick T+1's body, data-independent of that tick's stage compute
    # (double-buffered carry; each hop takes 2 ticks, fill/drain grows
    # to 2(pp-1) ticks). Moves toward the MPMD paper's async
    # point-to-point dispatch while keeping the scan-transpose backward
    # (parallel/pipeline.py).
    async_pipeline_dispatch: bool = False
    # Number of microbatches for pipelining / gradient accumulation.
    num_microbatches: int = 1
    # Pipeline backward rematerialization policy — the memory/FLOP trade
    # 1F1B exists to manage (ref: schedules.py:606-722 trains WITHOUT
    # recomputing stage internals). Speaks the SAME policy vocabulary as
    # ModelConfig.remat_policy (REMAT_POLICIES), applied to the per-tick
    # scan body, plus two legacy aliases:
    #   "tick" (legacy alias of "full", the default): jax.checkpoint every
    #     scan tick; backward keeps only the (b,s,h) boundary carry per
    #     tick and recomputes stage internals (~+1 forward of FLOPs — the
    #     memory-minimal choice);
    #   "selective": save_only_these_names over the named save points
    #     (models/remat.py) — matmul outputs kept, elementwise recomputed;
    #   "dots" (legacy alias of "save_dots"): checkpoint_dots policy; every
    #     matmul output is kept (1F1B-class FLOPs at intermediate memory);
    #   "offload": the selective save set parked in pinned host memory;
    #   "none":  no remat; AD stashes every tick's internals (1F1B-class
    #     FLOPs, highest memory — pick when per-stage HBM allows).
    # Measured FLOPs/memory per policy: docs/PIPELINE_MEMORY.md.
    pipeline_remat: str = "tick"

    def __post_init__(self):
        if self.tensor_parallel_size == 1 and self.sequence_parallel:
            object.__setattr__(self, "sequence_parallel", False)
        if self.pipeline_remat not in REMAT_POLICIES + ("tick", "dots"):
            raise ValueError(
                f"pipeline_remat={self.pipeline_remat!r}: expected one of "
                f"{REMAT_POLICIES + ('tick', 'dots')}"
            )
        if self.grad_rs_bucket_mb <= 0:
            raise ValueError(
                f"grad_rs_bucket_mb={self.grad_rs_bucket_mb}: the "
                f"reduce-scatter bucket size target must be positive"
            )
        if self.quantized_grad_reduce:
            # reject dead/misleading combinations at construction (the
            # recompute-flag pattern above): quantization lives inside
            # the explicit decomposition, which needs zero1 on a
            # pure-dp mesh — anywhere else the flag would silently
            # train full-precision.
            if not self.use_distributed_optimizer:
                raise ValueError(
                    "quantized_grad_reduce requires "
                    "use_distributed_optimizer: the int8 reduction is "
                    "the wire format of the ZeRO-1 reduce-scatter "
                    "(optimizer/zero1.py); without it there is no "
                    "decomposed dp reduction to quantize"
                )
            if (self.tensor_parallel_size > 1
                    or self.pipeline_parallel_size > 1
                    or self.context_parallel_size > 1):
                raise ValueError(
                    "quantized_grad_reduce is only available on pure-dp "
                    "meshes (tp=pp=cp=1): the explicit reduce-scatter "
                    "path runs the fwd/bwd inside a fully manual "
                    "shard_map, which the tp/pp/cp programs are not "
                    "written for (docs/GUIDE.md, 'ZeRO-1 distributed "
                    "optimizer')"
                )
            if self.data_parallel_size <= 1:
                raise ValueError(
                    "quantized_grad_reduce with data_parallel_size=1: "
                    "there is no dp gradient reduction to quantize"
                )
        for flag in ("overlap_grad_reduce", "overlap_param_gather"):
            if not getattr(self, flag):
                continue
            # same construction-time gate as quantized_grad_reduce: the
            # overlap scheduling lives inside the explicit decomposition
            # — anywhere else the flag would silently do nothing.
            if not self.use_distributed_optimizer:
                raise ValueError(
                    f"{flag} requires use_distributed_optimizer: the "
                    f"overlap scheduling reorders the ZeRO-1 explicit "
                    f"reduce-scatter/all-gather decomposition "
                    f"(optimizer/zero1.py); without it there is nothing "
                    f"to schedule")
            if (self.tensor_parallel_size > 1
                    or self.pipeline_parallel_size > 1
                    or self.context_parallel_size > 1):
                raise ValueError(
                    f"{flag} is only available on pure-dp meshes "
                    f"(tp=pp=cp=1): the explicit path runs the fwd/bwd "
                    f"inside a fully manual shard_map, which the "
                    f"tp/pp/cp programs are not written for "
                    f"(docs/GUIDE.md, 'Collective overlap scheduling')")
            if self.data_parallel_size <= 1:
                raise ValueError(
                    f"{flag} with data_parallel_size=1: there is no dp "
                    f"collective to overlap")
        if self.async_pipeline_dispatch and self.pipeline_parallel_size <= 1:
            raise ValueError(
                "async_pipeline_dispatch requires pipeline_parallel_size "
                "> 1: it reschedules the stage-ring ppermute "
                "(parallel/pipeline.py); there is no ring at pp=1")

    @property
    def resolved_pipeline_remat(self) -> str:
        """pipeline_remat with the legacy aliases normalized to the shared
        REMAT_POLICIES vocabulary (tick->full, dots->save_dots)."""
        return {"tick": "full", "dots": "save_dots"}.get(
            self.pipeline_remat, self.pipeline_remat
        )

    @property
    def world_size(self) -> int:
        return (
            self.data_parallel_size
            * self.pipeline_parallel_size
            * self.context_parallel_size
            * self.tensor_parallel_size
        )

    @property
    def mesh_shape(self):
        return (
            self.data_parallel_size,
            self.pipeline_parallel_size,
            self.context_parallel_size,
            self.tensor_parallel_size,
        )


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class TrainConfig:
    """Optimizer / schedule / run-control (ref: arguments.py:579-815)."""

    micro_batch_size: int = 1
    global_batch_size: int = 1
    rampup_batch_size: Optional[tuple] = None  # (start, increment, samples)

    train_iters: Optional[int] = None
    train_samples: Optional[int] = None
    exit_interval: Optional[int] = None
    exit_duration_in_mins: Optional[float] = None
    exit_signal_handler: bool = False
    # sentinel-file termination hook — the TPU analogue of ADLR autoresume
    # (ref: --adlr_autoresume arguments.py + utils.py:117-135): when the
    # file appears, every host checkpoints and exits together.
    autoresume_file: Optional[str] = None
    autoresume_interval: int = 50

    # Optimizer (ref: arguments.py:666, optimizer/__init__.py:64)
    optimizer: str = "adam"  # adam | sgd
    lr: float = 1e-4
    min_lr: float = 0.0
    lr_decay_style: str = "linear"  # constant|linear|cosine|inverse-square-root
    lr_decay_iters: Optional[int] = None
    lr_decay_samples: Optional[int] = None
    lr_warmup_iters: int = 0
    lr_warmup_samples: int = 0
    lr_warmup_fraction: Optional[float] = None
    use_checkpoint_opt_param_scheduler: bool = False
    override_opt_param_scheduler: bool = False

    weight_decay: float = 0.01
    start_weight_decay: Optional[float] = None
    end_weight_decay: Optional[float] = None
    weight_decay_incr_style: str = "constant"  # constant|linear|cosine
    clip_grad: float = 1.0
    adam_beta1: float = 0.9
    adam_beta2: float = 0.999
    adam_eps: float = 1e-8
    sgd_momentum: float = 0.9

    # Mixed precision (ref: arguments.py:783-815)
    fp16: bool = False
    bf16: bool = True
    loss_scale: Optional[float] = None  # constant scale; None => dynamic if fp16
    initial_loss_scale: float = 2.0**32
    min_loss_scale: float = 1.0
    loss_scale_window: int = 1000
    hysteresis: int = 2

    # Checkpointing (ref: arguments.py:751-779)
    save: Optional[str] = None
    load: Optional[str] = None
    save_interval: Optional[int] = None
    finetune: bool = False
    no_save_optim: bool = False
    no_load_optim: bool = False
    no_load_rng: bool = False
    # Fault tolerance (ISSUE 5, training/checkpointing.py +
    # training/watchdog.py):
    # async_save: interval saves go through the CheckpointManager's
    # orbax-async path — the train loop stalls only for the device→host
    # copy (the `ckpt_blocked_ms` gauge), commits finish on a background
    # thread, wait-at-exit only. --no_async_save restores blocking saves.
    async_save: bool = True
    # retention: keep the newest N COMPLETE checkpoints, GC the rest
    # (never the one being written or the one resume read). None = keep
    # everything.
    keep_latest_n: Optional[int] = None
    # loss watchdog: a step whose loss is non-finite or above
    # median + ksigma * robust-sigma of the recent-loss window is
    # SKIPPED in-step (the fp16 scaler's skip machinery, for bf16 too);
    # ksigma <= 0 disables spike detection (NaN/inf losses still skip).
    loss_watchdog_ksigma: float = 0.0
    loss_watchdog_window: int = 64
    # after this many CONSECUTIVE bad steps, reload the last complete
    # checkpoint and fast-forward the data iterator past the poison
    # window; 0 disables rollback (skip-only).
    spike_rollback_patience: int = 0

    # Logging / eval (ref: arguments.py:477-541, 870-877)
    log_interval: int = 100
    eval_interval: int = 1000
    eval_iters: int = 100
    tensorboard_dir: Optional[str] = None
    # ref: --tensorboard_log_interval/--tensorboard_queue_size and the
    # log_*_to_tensorboard toggles (arguments.py:477-529)
    tensorboard_log_interval: int = 1
    tensorboard_queue_size: int = 1000
    log_timers_to_tensorboard: bool = False
    log_validation_ppl_to_tensorboard: bool = False
    log_memory_to_tensorboard: bool = False
    log_world_size_to_tensorboard: bool = False
    # ref: --timing_log_level/--timing_log_option (arguments.py:493-508)
    timing_log_level: int = 0
    timing_log_option: str = "minmax"
    wandb_logger: bool = False
    wandb_project: Optional[str] = None
    wandb_entity: Optional[str] = None
    wandb_id: Optional[str] = None
    wandb_resume: bool = False
    wandb_api_key: Optional[str] = None
    # ref: --log-params-norm / --log-num-zeros-in-grad (arguments.py:481-487)
    log_params_norm: bool = False
    log_num_zeros_in_grad: bool = False
    # ref: --profile/--profile-step-start/--profile-step-end
    # (arguments.py:531-541, nsys there; jax.profiler trace here)
    profile: bool = False
    profile_step_start: int = 10
    profile_step_end: int = 12
    profile_dir: Optional[str] = None
    # flight-recorder telemetry (ISSUE 13, megatron_llm_tpu/telemetry/):
    # trace_dir enables the host span tracer (Chrome trace-event JSON,
    # exported at the end of train()); the flight recorder is ALWAYS on
    # (bounded event ring, auto-dumped on watchdog rollback + SIGTERM
    # emergency save), dumping into flight_record_dir (default: the
    # --save dir). Telemetry never touches jitted code — telemetry-on
    # steps are bitwise telemetry-off (tests/test_telemetry.py).
    trace_dir: Optional[str] = None
    flight_record_dir: Optional[str] = None
    flight_recorder_size: int = 4096
    # goodput & device-cost accounting (ISSUE 15, docs/GUIDE.md
    # "Goodput & device-cost accounting"): the goodput ledger is
    # ALWAYS on (pure host float adds); device_cost_registry opts into
    # mint-time compiled-cost capture (one extra AOT compile per step
    # specialization) which upgrades the live MFU gauge from analytic
    # to registry FLOPs and adds per-executable roofline gauges;
    # chip_spec overrides chipspec detection ("v5e"/"v5p"/"v4" — the
    # roofline denominators); perf_sentinel_ksigma > 0 arms the
    # step-latency regression sentinel (median+MAD, the watchdog's
    # machinery) with its flight-ring auto-dump.
    device_cost_registry: bool = False
    chip_spec: Optional[str] = None
    perf_sentinel_ksigma: float = 0.0
    perf_sentinel_window: int = 64
    perf_sentinel_patience: int = 8

    seed: int = 1234

    def __post_init__(self):
        assert not (self.fp16 and self.bf16)
        if self.train_iters is not None and self.train_samples is not None:
            raise ValueError("specify train_iters or train_samples, not both")
        # iteration- and sample-based schedules must not mix (ref:
        # validate_args arguments.py:98-130)
        if self.train_samples is not None:
            if self.lr_decay_iters is not None or self.lr_warmup_iters:
                raise ValueError(
                    "sample-based run (--train_samples): use "
                    "--lr_decay_samples/--lr_warmup_samples, not the "
                    "*_iters variants"
                )
        elif self.lr_decay_samples is not None or self.lr_warmup_samples:
            raise ValueError(
                "--lr_decay_samples/--lr_warmup_samples require "
                "--train_samples (iteration-based runs use the *_iters "
                "variants)"
            )


# ---------------------------------------------------------------------------
# Model family presets (ref: llama_model.py:22-30, falcon_model.py:18-29,
# examples/finetune.sh:62-109)
# ---------------------------------------------------------------------------

_LLAMA_SIZES = {
    # size -> (layers, hidden, heads, n_kv, ffn)
    7: (32, 4096, 32, 32, 11008),
    13: (40, 5120, 40, 40, 13824),
    30: (60, 6656, 52, 52, 17920),
    34: (48, 8192, 64, 8, 22016),  # CodeLlama-34B (GQA)
    65: (80, 8192, 64, 64, 22016),
    70: (80, 8192, 64, 8, 28672),  # Llama-2-70B (GQA)
}

_FALCON_SIZES = {
    # size -> (layers, hidden, heads, n_kv, parallel_layernorm)
    7: (32, 4544, 71, 1, False),
    40: (60, 8192, 128, 8, True),
}


def llama_config(
    size_b: int = 7,
    version: int = 2,
    seq_length: int = 4096,
    vocab_size: int = 32000,
    tp: int = 1,
    **overrides,
) -> ModelConfig:
    """Llama-1/2/CodeLlama preset (ref: llama_model.py:10-44).

    Asserts mirrored from the reference: rotary + swiglu + RMSNorm + no bias
    + untied embeddings (ref: llama_model.py:22-30).
    """
    layers, hidden, heads, n_kv, ffn = _LLAMA_SIZES[size_b]
    if version == 1:
        seq_length = min(seq_length, 2048)
    cfg = dict(
        num_layers=layers,
        hidden_size=hidden,
        num_attention_heads=heads,
        num_attention_heads_kv=n_kv,
        ffn_hidden_size=ffn,
        seq_length=seq_length,
        max_position_embeddings=seq_length,
        position_embedding_type="rotary",
        glu_activation="swiglu",
        use_rms_norm=True,
        use_bias=False,
        tie_embed_logits=False,
        layernorm_epsilon=1e-6 if version == 1 else 1e-5,
        hidden_dropout=0.0,
        attention_dropout=0.0,
        init_method_std=0.02,
        # Train through the Pallas flash kernel by default, like the
        # reference trains Llama through FlashAttention-2
        # (ref: transformer.py:508-523); proven to compile under Mosaic on
        # TPU and to beat the XLA path (tests/test_flash_attention.py;
        # PERF.md, PR 32, both training cells).
        use_flash_attn=True,
    )
    cfg.update(overrides)
    mc = ModelConfig(**cfg)
    if mc.padded_vocab_size == 0:
        mc = dataclasses.replace(mc, padded_vocab_size=mc.pad_vocab_size(vocab_size, tp))
    return mc


def codellama_config(size_b: int = 7, seq_length: int = 16384, **overrides) -> ModelConfig:
    """CodeLlama: Llama-2 + rope_theta=1e6 + 16k seq (ref: examples/finetune.sh:74-86)."""
    overrides.setdefault("rope_theta", 1e6)
    return llama_config(size_b, version=2, seq_length=seq_length,
                        vocab_size=overrides.pop("vocab_size", 32016), **overrides)


def falcon_config(
    size_b: int = 7,
    seq_length: int = 2048,
    vocab_size: int = 65024,
    tp: int = 1,
    **overrides,
) -> ModelConfig:
    """Falcon preset (ref: falcon_model.py:10-42): rotary + MQA/GQA +
    parallel attention; 40B adds parallel layernorm."""
    layers, hidden, heads, n_kv, pln = _FALCON_SIZES[size_b]
    cfg = dict(
        num_layers=layers,
        hidden_size=hidden,
        num_attention_heads=heads,
        num_attention_heads_kv=n_kv,
        ffn_hidden_size=4 * hidden,
        seq_length=seq_length,
        max_position_embeddings=seq_length,
        position_embedding_type="rotary",
        glu_activation=None,
        hidden_act="gelu",
        use_rms_norm=False,
        use_bias=False,
        parallel_attn=True,
        parallel_layernorm=pln,
        tie_embed_logits=True,
        hidden_dropout=0.0,
        attention_dropout=0.0,
    )
    cfg.update(overrides)
    mc = ModelConfig(**cfg)
    if mc.padded_vocab_size == 0:
        mc = dataclasses.replace(mc, padded_vocab_size=mc.pad_vocab_size(vocab_size, tp))
    return mc


def gpt_config(
    num_layers: int = 12,
    hidden_size: int = 768,
    num_attention_heads: int = 12,
    seq_length: int = 1024,
    vocab_size: int = 50257,
    tp: int = 1,
    **overrides,
) -> ModelConfig:
    """GPT-2/3-style preset (ref: gpt_model.py:45)."""
    cfg = dict(
        num_layers=num_layers,
        hidden_size=hidden_size,
        num_attention_heads=num_attention_heads,
        seq_length=seq_length,
        max_position_embeddings=seq_length,
        position_embedding_type="absolute",
        hidden_act="gelu",
        tie_embed_logits=True,
    )
    cfg.update(overrides)
    mc = ModelConfig(**cfg)
    if mc.padded_vocab_size == 0:
        mc = dataclasses.replace(mc, padded_vocab_size=mc.pad_vocab_size(vocab_size, tp))
    return mc


def bert_config(
    num_layers: int = 12,
    hidden_size: int = 768,
    num_attention_heads: int = 12,
    seq_length: int = 512,
    vocab_size: int = 30522,
    tp: int = 1,
    **overrides,
) -> ModelConfig:
    """BERT preset (ref: bert_model.py:125-176 through the standard
    pre-LN ParallelTransformer): learned positions, tokentypes, gelu,
    biases, binary (SOP) head, tied LM head."""
    cfg = dict(
        num_layers=num_layers,
        hidden_size=hidden_size,
        num_attention_heads=num_attention_heads,
        seq_length=seq_length,
        max_position_embeddings=seq_length,
        position_embedding_type="absolute",
        hidden_act="gelu",
        use_rms_norm=False,
        use_bias=True,
        tie_embed_logits=True,
        num_tokentypes=2,
        add_binary_head=True,
    )
    cfg.update(overrides)
    mc = ModelConfig(**cfg)
    if mc.padded_vocab_size == 0:
        mc = dataclasses.replace(mc, padded_vocab_size=mc.pad_vocab_size(vocab_size, tp))
    return mc


def t5_config(
    num_layers: int = 12,
    hidden_size: int = 768,
    num_attention_heads: int = 12,
    seq_length: int = 512,
    decoder_seq_length: int = 128,
    vocab_size: int = 30522,
    tp: int = 1,
    **overrides,
) -> ModelConfig:
    """T5 preset (ref: t5_model.py:70-120): shared embeddings, learned
    positions, gelu, biases. seq_length is the encoder side; the decoder
    length is a data-pipeline property (ref: --decoder_seq_length)."""
    cfg = dict(
        num_layers=num_layers,
        hidden_size=hidden_size,
        num_attention_heads=num_attention_heads,
        seq_length=seq_length,
        max_position_embeddings=max(seq_length, decoder_seq_length),
        position_embedding_type="absolute",
        hidden_act="gelu",
        use_rms_norm=False,
        use_bias=True,
        tie_embed_logits=True,
    )
    cfg.update(overrides)
    mc = ModelConfig(**cfg)
    if mc.padded_vocab_size == 0:
        mc = dataclasses.replace(mc, padded_vocab_size=mc.pad_vocab_size(vocab_size, tp))
    return mc


def tiny_config(**overrides) -> ModelConfig:
    """Small config for tests."""
    cfg = dict(
        num_layers=2,
        hidden_size=64,
        num_attention_heads=4,
        num_attention_heads_kv=2,
        ffn_hidden_size=128,
        seq_length=64,
        max_position_embeddings=64,
        padded_vocab_size=256,
        position_embedding_type="rotary",
        glu_activation="swiglu",
        use_rms_norm=True,
        use_bias=False,
        tie_embed_logits=False,
        hidden_dropout=0.0,
        attention_dropout=0.0,
    )
    cfg.update(overrides)
    return ModelConfig(**cfg)
