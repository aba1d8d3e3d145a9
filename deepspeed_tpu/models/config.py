"""Model configurations for the built-in model families.

The reference ships no model zoo of its own — it wraps user/HF torch modules
(SURVEY.md §2.1 "Module injection / TP": policies for BERT, GPT2, GPT-Neo/J/
NeoX, OPT, BLOOM, LLaMA, Megatron).  A TPU-native framework cannot wrap torch
modules, so we ship functional jax implementations of the same architecture
families instead; `ModelConfig` spans them with feature flags:

- Llama family  : RMSNorm + RoPE + SwiGLU + GQA   (``llama`` presets)
- GPT-2 family  : LayerNorm + learned positions + GELU (``gpt2`` presets)
- Mixtral family: Llama backbone + top-k MoE MLP  (``mixtral`` presets)
- OLMoE         : the same with QK-norm, 64 experts top-8, dropless and an
  unnormalised router (``qk_norm``, ``moe_drop_tokens=False``,
  ``moe_norm_topk_prob=False``; benchmarks/configs/olmoe-1b-7b-L8.json)
- EvaByte       : Llama backbone over bytes with EVA attention (an exact
  window joined in one softmax with learned per-chunk summaries of every
  earlier window), RMSNorm scaled by ``1 + weight``, a float32 residual
  stream and ``num_pred_heads`` output heads (``attention="eva"``,
  ``eva_window``, ``eva_chunk``, ``norm_add_unit_offset``,
  ``fp32_residual``; benchmarks/configs/evabyte-L6.json).  SERVED ONLY, on
  seeded weights: no checkpoint import, no training loss over the eight
  heads, no multi-byte self-speculative decoding, no image tokenizer
- AFMoE (Trinity): layers of more than one kind from a static pattern
  (``layer_types``: ``sliding_attention`` layers with RoPE and a
  ``sliding_window``, ``full_attention`` layers with no position encoding),
  ``num_dense_layers`` leading SwiGLU layers of ``dense_intermediate_size``
  then expert layers; per-head QK-norm, a sigmoid output gate on attention,
  a norm before AND after each sub-block, a sigmoid router over
  ``moe_router_experts`` whose selection bias picks and does not weigh, the
  kept weights normalised and scaled by ``moe_route_scale``, shared experts,
  and ONE CHIP'S SHARE of the routed experts (``num_experts`` held from
  ``moe_first_expert``; the other ranks' assignments are not computed and
  no exchange stands in for them), the embedding times ``embed_scale``
  (``models/afmoe.py``; benchmarks/configs/trinity-large-L5-ep8.json).
  SERVED ONLY, on seeded weights, one chip (tp = ep = sp = 1): no training
  loss, no checkpoint import
- Linear-attention layers beside latent-attention layers (Kimi-Linear): the
  same layer form (static pattern, leading dense layers, the held-share
  expert block) over two further attention kinds, plain pre-norm
  (``layer_types``: ``linear_attention``, a gated delta rule whose state is
  ``kda_num_heads`` matrices of ``kda_head_dim`` squared in float32 a slot,
  fed through a short causal convolution of ``kda_conv_kernel`` taps with a
  channel-wise decay and a gated output norm; ``latent_attention``, keys
  and values decompressed from ONE row of ``mla_kv_rank + mla_rot_dim``
  values a token shared by all heads, the ``mla_rot_dim`` values unrotated
  (Kimi-Linear: no position encoding) or rotated by position with YaRN
  frequencies (``mla_rope``), the query full-rank or through a normed
  low-rank bottleneck (``mla_q_rank``); a model may be latent layers only
  (A.X-K1, whose router also keeps ``moe_topk_group`` of ``moe_n_group``
  groups of experts before its top-k); ``models/kda_mla.py``;
  benchmarks/configs/kimi-linear-L5-ep8.json, axk1-L5-ep16.json).
  SERVED ONLY, one chip's share, seeded weights
- Latent layers of TWO kinds (dots3-note-prev): sizes, head count and base
  are a property of the layer kind (:meth:`ModelConfig.mla_kind`):
  ``latent_attention`` layers that attend the ``mla_index_topk`` keys a
  learned indexer selects (``mla_index_heads`` index heads of
  ``mla_index_dim`` over a cache of index keys), ``latent_sliding_attention``
  layers with sizes of their own (``mla_sliding``) over the last
  ``sliding_window`` positions, a sigmoid gate a head (``mla_head_gate``)
  and the low-rank rescale (``mla_lora_rescale``); the same module;
  benchmarks/configs/dots3-note-L5-ep16.json.  SERVED ONLY likewise
- Linear-attention layers beside per-head softmax layers (Solar-Open2):
  ``linear_attention`` beside ``full_attention`` (NO position encoding,
  ``num_heads`` query heads over ``num_kv_heads`` key-value heads of
  ``head_dim``, per-head K and V rows in pages, ``attn_output_gate`` the
  elementwise gate) in the same module, and a delta rule whose ``beta`` runs
  to 2 (``kda_neg_eigval``: ``I - beta k k^T`` has its moving eigenvalue in
  (-1, 1)); K/V pages in the full layers ONLY
  (:attr:`ModelConfig.cache_layers` counts them) beside the slot state;
  benchmarks/configs/solar-open2-L4-ep8.json.  SERVED ONLY likewise
- A looped stack (Ouro): the Llama backbone's ``num_layers`` layers run
  ``total_ut_steps`` times with the SAME weights, the final norm closing
  every pass and feeding the next, a norm on both sides of each sub-block
  (``sandwich_norm``), an exit gate on the normed stream
  (``loop_exit_gate``).  Pass ``t``, layer ``l`` reads and writes cache
  layer ``t * num_layers + l`` (:attr:`ModelConfig.cache_layers` of them
  under one page table), at the same rotary positions in every pass;
  benchmarks/configs/ouro-2.6b-L12.json.  SERVED ONLY, every token through
  every pass (``early_exit_threshold`` 1): no training loss, no early exit
- A layer that is ONE mixer (NVIDIA Nemotron-3-Nano, ``nemotron_h``): ``x = x
  + mixer(N(x))`` with ``layer_types`` kinds ``mamba2`` (a selective state
  space: ``ssm_num_heads`` heads of ``ssm_head_dim`` over a float32 state of
  ``ssm_state_size`` a slot, a scalar decay a head, B and C shared by the
  heads of one of ``ssm_groups`` groups, a short causal convolution of
  ``ssm_conv_kernel`` taps WITH bias, a gated norm over groups of channels),
  ``full_attention`` (per-head softmax, NO position encoding, K/V pages in
  THOSE layers only) and ``experts`` (the held-share expert block of
  ``models/afmoe.py`` with TWO matrices an expert and ``relu2``:
  ``activation="relu2"``, ``glu=False``, a shared expert of
  ``shared_intermediate_size``); no layer has both an attention and an MLP
  (``models/ssm_moe.py``; benchmarks/configs/nemotron3-nano-L9-ep2.json).
  SERVED ONLY likewise
- The same form with kinds ``mamba1`` and ``mlp`` (AI21 Jamba2-3B, ``jamba``:
  a Mamba-1 selective scan, ``ssm_inner_size`` channels with a decay for
  every (channel, state dim) pair, ``dt`` through a bottleneck of
  ``ssm_dt_rank``, RMSNorms on dt, B and C (``ssm_inner_norms``), no gated
  norm; a dense gated MLP as a layer of its own; multi-query attention; a
  tied head): a published layer is a PAIR of one-mixer layers ``[mamba1 |
  full_attention, mlp]``, and runs of ``[mamba1, mlp]`` pairs are rolled
  (``lax.scan``) over stacks both the chunk programs and the decode step
  read in place; benchmarks/configs/jamba2-3b.json.  SERVED ONLY likewise

All presets follow the public architecture descriptions of those model
families; sizes match the milestone configs in BASELINE.json.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass
class ModelConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: Optional[int] = None     # GQA; None -> == num_heads
    head_dim: Optional[int] = None         # None -> hidden_size // num_heads
    max_seq_len: int = 4096
    norm: str = "rmsnorm"                  # "rmsnorm" (llama) | "layernorm" (gpt2)
    norm_eps: float = 1e-5
    activation: str = "silu"               # "silu" (swiglu) | "gelu"
    glu: bool = True                       # gated MLP (llama) vs plain (gpt2)
    position: str = "rope"                 # "rope" | "learned" | "alibi"
    rope_theta: float = 10000.0
    tie_embeddings: bool = False
    use_bias: bool = False                 # attn/mlp projection biases (gpt2)
    qkv_bias: bool = False                 # biases on q/k/v only (qwen2)
    mlp_bias: bool = False                 # biases on the MLP only (gpt-j)
    lm_head_bias: bool = False             # bias on the LM head (gpt-j)
    embed_norm: bool = False               # layernorm after token embed (bloom)
    # RMSNorm over the WHOLE q and k projections (all heads at once), before
    # the head split and RoPE (olmoe)
    qk_norm: bool = False
    # "full": causal softmax over every earlier token.  "eva" (evabyte): a
    # query attends exactly to the tokens of its own ``eva_window`` and, in
    # the same softmax, to one learned summary per ``eva_chunk`` tokens of
    # every earlier window (parameters ``attn.eva_mu`` / ``attn.eva_phi``
    # [L, H, Dh]; models/eva.py)
    attention: str = "full"
    eva_window: int = 2048
    eva_chunk: int = 16
    # output heads: head p's ``vocab_size`` columns of ``lm_head``
    # [D, num_pred_heads * vocab_size] predict token i + 1 + p (evabyte);
    # serving samples from head 0
    num_pred_heads: int = 1
    # the layer stack runs this many times over the same weights (a looped
    # model, Ouro), each pass closed by the final norm; pass t, layer l
    # keeps its keys and values in cache layer ``t * num_layers + l``
    total_ut_steps: int = 1
    # a Linear with bias on each pass's normed stream, ``exit_gate`` {"w"
    # [D, 1], "b" [1]}: ``CausalLM.apply(exit_distribution=True)`` returns the
    # distribution over passes it defines.  The serve programs do not
    # compute it: at ``early_exit_threshold`` 1, the only value built, every
    # token leaves after the last pass and the gate cannot move a logit
    loop_exit_gate: bool = False
    early_exit_threshold: float = 1.0
    # -- the afmoe layer form (models/afmoe.py); ``layer_types`` turns it on
    # one entry a layer, "sliding_attention" (RoPE, keys j with
    # 0 <= i - j < sliding_window) or "full_attention" (NO position
    # encoding, every j <= i).  The first ``num_dense_layers`` layers carry
    # a dense MLP of ``dense_intermediate_size`` (parameters
    # ``dense_layers``), the rest the expert block (``layers``)
    layer_types: Optional[tuple] = None
    sliding_window: int = 0
    num_dense_layers: int = 0
    dense_intermediate_size: int = 0
    # RMSNorm of q and k PER HEAD (gains [head_dim], shared by the heads),
    # after the head split and before RoPE
    qk_norm_per_head: bool = False
    # attention output times sigmoid(h Wg) before the output projection
    attn_output_gate: bool = False
    # a norm AFTER each sub-block too: x + N_post(block(N_pre(x))).  Read by
    # the layer form and by the Llama backbone (parameters
    # ``layers.attn_post_norm`` / ``layers.mlp_post_norm``; afmoe.close)
    sandwich_norm: bool = False
    embed_scale: float = 1.0               # multiplies the token embedding
    # router scores: "softmax" over the experts, or independent "sigmoid"s
    moe_score_func: str = "softmax"
    moe_route_scale: float = 1.0           # multiplies the kept weights
    # a per-expert bias added to the scores for the top-k SELECTION only
    moe_select_bias: bool = False
    num_shared_experts: int = 0            # always-on experts beside the routed
    # the router's width where this chip holds a share of the experts:
    # ``num_experts`` are HELD here, [moe_first_expert, + num_experts) of
    # ``moe_router_experts`` (0 = all of them are held)
    moe_router_experts: int = 0
    moe_first_expert: int = 0
    # group-limited selection: the router's experts lie in ``moe_n_group``
    # groups of consecutive experts, a group scores the sum of its two best
    # selection scores, and the top-k is taken inside the ``moe_topk_group``
    # best groups (1 group = the plain top-k)
    moe_n_group: int = 1
    moe_topk_group: int = 1
    # -- ``layer_types`` kinds "linear_attention" and "latent_attention"
    # (models/kda_mla.py).  Linear: heads, the size of a head's key and
    # value (its state is [size, size] float32), taps of the short causal
    # convolution on q, k and v, rank of the decay gate's and the output
    # gate's two-matrix projections
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_kernel: int = 0
    kda_gate_rank: int = 0
    # the delta rule's ``beta = 2 sigmoid(.)`` in (0, 2), so that ``I - beta k
    # k^T`` may reflect (an eigenvalue in (-1, 1)); False: ``sigmoid(.)``
    kda_neg_eigval: bool = False
    # -- the one-mixer layer form (models/ssm_moe.py): ``layer_types`` kinds
    # "mamba2", "experts" and "full_attention", each layer ``x + mixer(N(x))``.
    # A Mamba-2 layer: heads, a head's width (its state is [width,
    # ssm_state_size] float32), groups of heads that share B and C (and
    # groups of channels the gated output norm runs over), taps of the short
    # causal convolution (with bias) on x, B and C, rows of a block of the
    # chunked (SSD) form
    ssm_num_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 0
    ssm_state_size: int = 0
    ssm_conv_kernel: int = 0
    ssm_chunk: int = 128
    # kind "mamba1" (Mamba-1's selective scan; Jamba): ``ssm_inner_size``
    # channels, each with a state of ``ssm_state_size`` float32 and a decay of
    # its own for every state dim; the step ``dt`` through a bottleneck of
    # ``ssm_dt_rank``, with bias; ``ssm_inner_norms``: an RMSNorm with its own
    # gain on each of dt's bottleneck, B and C.  Kind "mlp": a layer that is
    # the dense MLP of ``intermediate_size`` (``glu`` / ``activation``)
    ssm_inner_size: int = 0
    ssm_dt_rank: int = 0
    ssm_inner_norms: bool = False
    # the shared expert's width where it is a key of its own (0:
    # ``intermediate_size * num_shared_experts``)
    shared_intermediate_size: int = 0
    # latent: the cache row is ``mla_kv_rank`` normed values plus
    # ``mla_rot_dim`` shared key values; a query head is ``mla_nope_dim +
    # mla_rot_dim`` wide, a value head ``mla_v_dim``; ``num_heads`` query
    # heads
    mla_kv_rank: int = 0
    mla_nope_dim: int = 0
    mla_rot_dim: int = 0
    mla_v_dim: int = 0
    # the query through a bottleneck of this rank with a norm of its own,
    # ``N_q(h W_qa) W_qb`` (0: one full-rank ``W_q``)
    mla_q_rank: int = 0
    # the position encoding of the ``mla_rot_dim`` values of the query heads
    # and of the shared key.  None: they are used UNROTATED (no position
    # encoding at all).  A group ``{"theta", "factor",
    # "original_max_position_embeddings", "beta_fast", "beta_slow",
    # "mscale", "mscale_all_dim"}``: rotated at the token's position, pairs
    # (2i, 2i + 1), YaRN frequencies (``factor`` 1: plain RoPE), and the
    # softmax scale times YaRN's factor squared (kda_mla.yarn)
    mla_rope: Optional[dict] = None
    # a SECOND latent kind with sizes, head count and base of its own
    # (``layer_types`` kind "latent_sliding_attention": keys j with 0 <= t -
    # j < ``sliding_window``, its cache a per-slot RING of rows): a group
    # ``{"num_heads", "kv_rank", "nope_dim", "rot_dim", "v_dim", "q_rank",
    # "rope"}`` (``rope`` a group as ``mla_rope``, or None).  The fields
    # above are the "latent_attention" kind's (:meth:`mla_kind`)
    mla_sliding: Optional[dict] = None
    # a learned selection of the "latent_attention" layers' keys (DeepSeek
    # sparse attention): ``mla_index_heads`` index heads of ``mla_index_dim``
    # score every earlier key from a cache of index keys of its own, and the
    # layer attends the ``mla_index_topk`` best (all of them while there are
    # no more); needs ``mla_q_rank`` (the index queries come from the
    # query's bottleneck) and ``mla_rope``.  0: every earlier key
    mla_index_heads: int = 0
    mla_index_dim: int = 0
    mla_index_topk: int = 0
    # one sigmoid gate a HEAD on the head's output before ``W_o``, from the
    # normed input (``afmoe``'s ``attn_output_gate`` is the elementwise one)
    mla_head_gate: bool = False
    # the normed bottlenecks times sqrt(hidden_size / rank): ``c_q`` and the
    # latent ``c`` (the shared key values unscaled)
    mla_lora_rescale: bool = False
    # RMSNorm multiplies by (1 + scale): the stored gain starts at 0
    norm_add_unit_offset: bool = False
    # the residual stream (and the logits) stay float32 whatever dtype the
    # weights and matmul inputs are served in
    fp32_residual: bool = False
    # gpt-neox/pythia: x + attn(ln1(x)) + mlp(ln2(x)) — the MLP reads the
    # LAYER INPUT, not the post-attention stream
    parallel_residual: bool = False
    rotary_pct: float = 1.0                # fraction of head dims rotated (neox)
    dropout: float = 0.0                   # residual dropout (needs a dropout rng)
    # MoE (mixtral family); num_experts == 0 -> dense MLP
    num_experts: int = 0
    num_experts_per_tok: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_loss_coef: float = 0.01
    # True: GShard capacity, overflow dropped.  False: dropless (tokens
    # sorted by expert, grouped matmuls; moe/sharded_moe.py)
    moe_drop_tokens: bool = True
    # renormalise the kept top-k router weights to sum to 1 (mixtral);
    # False keeps the softmax probabilities as they are (olmoe)
    moe_norm_topk_prob: bool = True
    moe_use_rts: bool = False          # random token selection for capacity
    # "scatter": O(N·k·D) scatter/gather dispatch (default);
    # "einsum": GShard one-hot [N,E,C] einsums (O(N²·k/E), parity reference)
    moe_dispatch: str = "scatter"
    # quantized-collective transport (ds_config "comm_quantization" sets
    # these at engine init; comm/collectives_q.py): int8 codes cross the
    # ep dispatch boundary / the sp ring instead of dense activations
    moe_q_dispatch: bool = False
    seq_ring_q: bool = False
    comm_quant_block: int = 256
    # pipeline boundary transport (ds_config "comm_quantization.pipeline"
    # arms pp_boundary_q at engine init): int8 codes + block scales ride
    # the stage-boundary rings instead of the dense activation/cotangent
    pp_boundary_q: bool = False
    # trace-time boundary byte ledger (runtime/pipe/spmd.py).  The engine
    # sets this False and commits its analytic per-execution comm plan
    # instead — the two feeds must stay disjoint (double-count rule)
    pp_comm_record: bool = True
    # training-time knobs
    sp_mode: str = "auto"                  # "auto" | "ulysses" | "ring" (sp>1)
    pp_microbatches: int = 0               # pipeline microbatches (0 -> pp size)
    # "gpipe": fill-drain scan + autodiff (stashes M+pp-1 boundaries);
    # "1f1b": fused fwd+bwd scan, circular buffer of 2pp-1 boundaries —
    # the reference TrainSchedule's memory bound (training with labels only)
    pp_schedule: str = "gpipe"
    # Activation checkpointing (ds_config "activation_checkpointing" section
    # overrides these at engine init). None = off: recompute-in-backward costs
    # ~1/3 extra FLOPs, so it must be opted into when the model doesn't fit,
    # not paid by default. Large presets below turn it on.
    remat: Optional[bool] = None
    # "full" | "dots" | "mlp_only" | "mlp_dots" | "offload_dots" (saved
    # matmul outputs page to pinned host memory — cpu_checkpointing)
    remat_policy: str = "full"
    # ZeRO-Infinity parameter tiering (engine sets this from ds_config
    # offload_param): params live in host memory; the forward streams each
    # scanned layer's weights to the device on demand, so device-resident
    # param bytes are O(one layer), not O(model).
    param_offload: bool = False
    scan_layers: bool = True               # lax.scan over stacked layer params
    z_loss: float = 0.0
    # Cross-entropy chunking (tokens per block; the [chunk, V] logits block is
    # the only logits materialization). 0 = dense; None = auto (chunk when the
    # full [B*S, V] fp32 logits would exceed ~2^28 elements).
    ce_chunk: Optional[int] = None

    def __post_init__(self):
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_heads
        assert self.num_heads % self.num_kv_heads == 0
        if self.pp_schedule not in ("gpipe", "1f1b"):
            raise ValueError(f"pp_schedule must be 'gpipe' or '1f1b', got "
                             f"{self.pp_schedule!r}")
        if self.position not in ("rope", "learned", "alibi"):
            raise ValueError(f"position must be 'rope', 'learned' or "
                             f"'alibi', got {self.position!r}")
        if self.attention not in ("full", "eva"):
            raise ValueError(f"attention must be 'full' or 'eva', got "
                             f"{self.attention!r}")
        if self.is_eva:
            if self.eva_chunk < 1 or self.eva_window % self.eva_chunk:
                raise ValueError(
                    f"eva_window {self.eva_window} must be a multiple of "
                    f"eva_chunk {self.eva_chunk}")
            if self.num_kv_heads != self.num_heads or self.position != "rope":
                raise ValueError("attention='eva' is built for RoPE and one "
                                 "KV head a query head (evabyte)")
        if self.num_pred_heads < 1:
            raise ValueError("num_pred_heads must be >= 1")
        if self.moe_score_func not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_score_func must be 'softmax' or "
                             f"'sigmoid', got {self.moe_score_func!r}")
        if self.layer_types is not None:
            self._check_afmoe()
        elif self._moved(_AFMOE_ONLY):
            raise ValueError(
                f"{sorted(_AFMOE_ONLY)} belong to the layer form that "
                "``layer_types`` turns on (models/afmoe.py: sliding_attention "
                "and full_attention layers; models/kda_mla.py: "
                "linear_attention and latent_attention layers)")
        self._check_loop()

    def _check_loop(self):
        """What a looped stack and the Llama backbone's post-norms are
        written for."""
        if self.total_ut_steps < 1:
            raise ValueError("total_ut_steps must be >= 1")
        if self.early_exit_threshold != 1.0:
            raise NotImplementedError(
                f"early_exit_threshold={self.early_exit_threshold}: a row that "
                "leaves the pass loop early is not built (serving/scheduler.py "
                "and the page pool assume every row runs every cache layer); "
                "at 1 every token takes the last pass's logits")
        if self.loop_exit_gate and self.total_ut_steps == 1:
            raise ValueError("loop_exit_gate is the gate of a looped stack "
                             "(total_ut_steps > 1)")
        if self.total_ut_steps > 1 and (
                self.layer_types is not None or self.is_eva or self.is_moe
                or self.parallel_residual or self.dropout):
            raise NotImplementedError(
                "total_ut_steps > 1 is built for the dense Llama / GPT-2 "
                "backbone: a looped stack beside layer_types kinds "
                "(models/afmoe.py, models/kda_mla.py), attention='eva', "
                "experts (their stacked arrays are indexed by layer, their "
                "counts by step), parallel_residual or dropout is not")
        if self.sandwich_norm and self.layer_types is None and (
                self.norm != "rmsnorm" or self.parallel_residual
                or self.is_moe or self.norm_add_unit_offset):
            raise NotImplementedError(
                "sandwich_norm outside layer_types is built for the dense "
                "Llama backbone: RMSNorm post-norms (afmoe.close) on "
                "sequential sub-blocks, no experts, no unit-offset gains")

    def _check_afmoe(self):
        """The combinations the layer form is written for: sliding and
        global layers (models/afmoe.py); linear-attention and
        latent-attention layers (models/kda_mla.py); or linear-attention
        layers beside per-head ``full_attention`` layers (the same module:
        :data:`_HYBRID_KINDS`, both of them present).  No other mix of the
        two families: a sliding layer's ring beside a state, or latent rows
        beside per-head K and V pages, is not built."""
        self.layer_types = tuple(self.layer_types)
        got = set(self.layer_types)
        if len(self.layer_types) != self.num_layers or not (
                got <= _WINDOW_KINDS or got <= _STATE_KINDS
                or got == _HYBRID_KINDS
                or (got & _SSM_KINDS and got <= _MIXER_KINDS)):
            raise ValueError(
                f"layer_types must name, for each of the {self.num_layers} "
                f"layers, one of {sorted(_WINDOW_KINDS)} (models/afmoe.py), "
                f"one of {sorted(_STATE_KINDS)} (models/kda_mla.py), both "
                f"of {sorted(_HYBRID_KINDS)} (models/kda_mla.py: a state a "
                f"slot beside per-head K/V pages), or one of "
                f"{sorted(_MIXER_KINDS)} with a mamba2 or a mamba1 layer "
                f"among them (models/ssm_moe.py: a layer is one mixer), got "
                f"{self.layer_types!r}")
        if self.is_mixer:
            self._check_router()
            self._check_mixer()
            return
        if self._moved(_MIXER_ONLY):
            raise ValueError(
                f"{sorted(_MIXER_ONLY)} belong to the one-mixer layer form "
                "(models/ssm_moe.py: mamba2, mamba1, experts, mlp and "
                "full_attention layers)")
        self._check_kda_mla()
        if "sliding_attention" in self.layer_types and self.sliding_window < 1:
            raise ValueError("sliding_attention layers need sliding_window")
        if not 0 <= self.num_dense_layers <= self.num_layers:
            raise ValueError("num_dense_layers out of range")
        if self.num_dense_layers and self.dense_intermediate_size < 1:
            raise ValueError("dense layers need dense_intermediate_size")
        if self.num_dense_layers < self.num_layers and not self.is_moe:
            raise ValueError("the layers after num_dense_layers are expert "
                             "layers: num_experts must be > 0")
        self._check_router()
        if (self.norm, self.position, self.glu, self.attention) != (
                "rmsnorm", "rope", True, "full") or self._not_layer_form():
            raise ValueError(
                "layer_types (models/afmoe.py, models/kda_mla.py) is built "
                "for RMSNorm, RoPE on the sliding layers and no position "
                "encoding elsewhere, gated MLPs without biases, dropless "
                "experts (moe_drop_tokens=False), an untied head and a "
                "stream in the weights' dtype")

    def _moved(self, names):
        """Those of the fields ``names`` that are not at their default."""
        return sorted(f.name for f in dataclasses.fields(self)
                      if f.name in names
                      and getattr(self, f.name) != f.default)

    def _not_layer_form(self, tied: bool = False) -> bool:
        """A field no ``layer_types`` model is built with (``tied``: the
        form builds a tied head)."""
        return bool(
            self.moe_drop_tokens or self.use_bias or self.qkv_bias
            or self.mlp_bias or self.qk_norm or self.parallel_residual
            or self.fp32_residual or self.norm_add_unit_offset
            or (self.tie_embeddings and not tied)
            or self.num_pred_heads != 1
            or self.rotary_pct != 1.0 or self.dropout)

    def _check_router(self):
        """The held share and the group limit of a ``layer_types`` model's
        router."""
        if not self.moe_router_experts:
            self.moe_router_experts = self.num_experts
        if not (0 <= self.moe_first_expert and self.moe_first_expert
                + self.num_experts <= self.moe_router_experts):
            raise ValueError(
                f"held experts [{self.moe_first_expert}, "
                f"{self.moe_first_expert + self.num_experts}) do not lie in "
                f"the router's {self.moe_router_experts}")
        G, Gk = self.moe_n_group, self.moe_topk_group
        if not (1 <= Gk <= G and self.moe_router_experts % G == 0
                and (G == 1 or self.num_experts_per_tok
                     <= Gk * (self.moe_router_experts // G))):
            raise ValueError(
                f"moe_topk_group={Gk} of moe_n_group={G} equal groups of the "
                f"router's {self.moe_router_experts} experts must hold the "
                f"top-{self.num_experts_per_tok}")

    def _check_mixer(self):
        """The one-mixer form (models/ssm_moe.py): the sizes a mamba2 or a
        mamba1 layer needs and what the module does not build."""
        got = set(self.layer_types)
        if _SSM_KINDS <= got:
            raise NotImplementedError(
                "mamba1 and mamba2 layers in one model (models/ssm_moe.py): "
                "a slot's cache has ONE state shape")
        kind = "mamba1" if "mamba1" in got else "mamba2"
        wants = _SSM_SIZES[kind] + _SSM_SHARED
        need = [k for k in wants if getattr(self, k) < 1]
        other = self._moved(tuple(k for sizes in _SSM_SIZES.values()
                                  for k in sizes if k not in wants))
        if need or other:
            raise ValueError(
                f"{kind} layers (models/ssm_moe.py) need {need}, and "
                f"{other} belong to the other state-space kind")
        if kind == "mamba2" and self.ssm_num_heads % self.ssm_groups:
            raise ValueError(
                f"ssm_num_heads={self.ssm_num_heads} must be whole groups "
                f"of ssm_groups={self.ssm_groups}: the heads of a group "
                "share B and C")
        if "experts" in got and not self.is_moe:
            raise ValueError("experts layers need num_experts > 0")
        wrong = self._moved(_AFMOE_ONLY - _MIXER_READS)
        if wrong or (self.norm, self.attention) != ("rmsnorm", "full") \
                or (self.glu, self.activation) not in (
                    (False, "relu2"), (True, "silu")) \
                or ("experts" in got and self.glu) \
                or self._not_layer_form(tied=True) or self.sandwich_norm:
            raise ValueError(
                "the one-mixer form (models/ssm_moe.py) is built for "
                "RMSNorm before each mixer, attention without a position "
                "encoding, norm or gate, experts of two matrices and relu2 "
                "(activation='relu2', glu=False), mlp layers of that form "
                "or gated with silu (glu=True, activation='silu'), no "
                "biases, dropless (moe_drop_tokens=False) and a stream in "
                f"the weights' dtype; not for {wrong or 'these fields'}")

    def _check_kda_mla(self):
        """The sizes the two kinds of models/kda_mla.py need, and what that
        module does not build."""
        sizes = {k: getattr(self, k) for k in _KDA_MLA_ONLY}
        if not self.is_kda_mla:
            if any(sizes.values()) or any(
                    getattr(self, k) for k in _KDA_FORMS + _MLA_FORMS):
                raise ValueError(
                    f"{sorted(_KDA_MLA_ONLY + _KDA_FORMS + _MLA_FORMS)} "
                    "belong to linear_attention and latent_attention layers "
                    "(models/kda_mla.py)")
            return
        # (a full_attention layer's sizes are the top-level head counts)
        used = {"linear_attention": "kda_", "latent_attention": "mla_",
                "latent_sliding_attention": "mla_sliding"}
        need = [k for k in _KDA_MLA_ONLY if sizes[k] < 1 and any(
            k.startswith(used[t]) for t in used.keys() & set(self.layer_types))]
        if need:
            raise ValueError(
                f"layer_types {self.layer_types!r} (models/kda_mla.py) "
                f"needs {need}")
        if self.mla_rope is not None:
            self.mla_rope = dict(self.mla_rope)
            if set(self.mla_rope) != _MLA_ROPE_KEYS or self.mla_rot_dim % 2:
                raise ValueError(
                    f"mla_rope names {sorted(_MLA_ROPE_KEYS)} and rotates "
                    f"pairs of an even mla_rot_dim, got "
                    f"{sorted(self.mla_rope)}, mla_rot_dim={self.mla_rot_dim}")
        self._check_mla_kinds()
        hybrid = "full_attention" in self.layer_types
        if hybrid and (
                any(v for k, v in sizes.items() if k.startswith("mla_"))
                or any(getattr(self, k) for k in _MLA_FORMS)):
            raise ValueError(
                "full_attention beside linear_attention layers "
                "(models/kda_mla.py) is per-head K and V: the mla_* sizes "
                "and forms belong to latent layers, which are not built "
                "beside it (latent rows and per-head pages under one table)")
        if self.kda_neg_eigval and "linear_attention" not in self.layer_types:
            raise ValueError("kda_neg_eigval is the linear_attention "
                             "layers' beta = 2 sigmoid(.)")
        if self.sandwich_norm or self.qk_norm_per_head \
                or (self.attn_output_gate and not hybrid) \
                or self.embed_scale != 1.0 \
                or (self.sliding_window and self.mla_sliding is None):
            raise ValueError(
                "linear_attention and latent_attention layers "
                "(models/kda_mla.py) are plain pre-norm: sandwich_norm, "
                "qk_norm_per_head, embed_scale and sliding_window belong to "
                "models/afmoe.py's two kinds, and attn_output_gate to a "
                "model with full_attention layers (models/afmoe.py's, or "
                "those beside linear_attention layers)")

    def _check_mla_kinds(self):
        """The second latent kind's group and the indexer's sizes."""
        sliding = "latent_sliding_attention" in self.layer_types
        if sliding != (self.mla_sliding is not None):
            raise ValueError(
                "latent_sliding_attention layers and the group mla_sliding "
                f"({sorted(_MLA_SLIDING_KEYS)}) come together: a sliding "
                "latent layer has sizes of its own")
        if sliding:
            self.mla_sliding = dict(self.mla_sliding)
            extra = set(self.mla_sliding) - _MLA_SLIDING_KEYS
            if any(k.startswith("index") for k in extra):
                raise ValueError(
                    f"mla_sliding names {sorted(extra)}: an indexer on a "
                    "sliding layer is not built (a window attends every key "
                    "it holds; mla_index_* select among a latent_attention "
                    "layer's keys)")
            if set(self.mla_sliding) != _MLA_SLIDING_KEYS:
                raise ValueError(
                    f"mla_sliding names {sorted(_MLA_SLIDING_KEYS)}, got "
                    f"{sorted(self.mla_sliding)}")
            need = [k for k in ("num_heads", "kv_rank", "nope_dim",
                                "rot_dim", "v_dim")
                    if int(self.mla_sliding[k]) < 1]
            if need or self.sliding_window < 1:
                raise ValueError(
                    "latent_sliding_attention layers need sliding_window and "
                    f"mla_sliding's sizes, missing: "
                    f"{need or ['sliding_window']}")
            if "linear_attention" in self.layer_types:
                raise ValueError(
                    "latent_sliding_attention beside linear_attention layers "
                    "is not built (one per-slot array a model: a ring or a "
                    "state)")
        index = (self.mla_index_heads, self.mla_index_dim,
                 self.mla_index_topk)
        if any(index) and not (all(v > 0 for v in index) and self.mla_q_rank
                               and self.mla_rope is not None
                               and "latent_attention" in self.layer_types
                               and self.mla_index_dim >= self.mla_rot_dim):
            raise ValueError(
                "mla_index_heads, mla_index_dim and mla_index_topk come "
                "together, on latent_attention layers with mla_q_rank (the "
                "index queries are made from the query's bottleneck) and "
                "mla_rope (their first mla_rot_dim values are rotated), got "
                f"{index}")

    def mla_kind(self, layer_type: str) -> "MlaKind":
        """The sizes, head count, base and forms of one KIND of latent
        layer: what ``models/kda_mla.py``'s pieces read."""
        D, eps = self.hidden_size, self.norm_eps
        scale = lambda rank: (D / rank) ** 0.5 \
            if self.mla_lora_rescale and rank else 1.0
        if layer_type == "latent_attention":
            index = (self.mla_index_heads, self.mla_index_dim,
                     self.mla_index_topk) if self.mla_index_topk else None
            return MlaKind(
                "mla", self.num_heads, self.mla_kv_rank, self.mla_nope_dim,
                self.mla_rot_dim, self.mla_v_dim, self.mla_q_rank,
                self.mla_rope, 0, index, self.mla_head_gate,
                scale(self.mla_q_rank), scale(self.mla_kv_rank), eps)
        if layer_type == "latent_sliding_attention":
            g = self.mla_sliding
            return MlaKind(
                "mla_sw", int(g["num_heads"]), int(g["kv_rank"]),
                int(g["nope_dim"]), int(g["rot_dim"]), int(g["v_dim"]),
                int(g["q_rank"]), g["rope"], self.sliding_window, None,
                self.mla_head_gate, scale(int(g["q_rank"])),
                scale(int(g["kv_rank"])), eps)
        raise ValueError(f"{layer_type!r} is not a latent kind")

    @property
    def has_mlp_bias(self) -> bool:
        return self.use_bias or self.mlp_bias

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_eva(self) -> bool:
        return self.attention == "eva"

    @property
    def is_looped(self) -> bool:
        return self.total_ut_steps > 1

    @property
    def cache_layers(self) -> int:
        """Layers of a K/V cache: one a (pass, layer) pair; beside
        linear-attention layers (models/kda_mla.py) the per-head
        ``full_attention`` layers alone keep K and V rows."""
        if self.is_kda_mla or self.is_mixer:
            return self.layer_types.count("full_attention")
        return self.total_ut_steps * self.num_layers

    @property
    def is_afmoe(self) -> bool:
        return self.layer_types is not None

    @property
    def is_kda_mla(self) -> bool:
        """A ``layer_types`` model of linear-attention and latent-attention
        layers, or of linear-attention layers beside per-head
        ``full_attention`` ones (models/kda_mla.py)."""
        return self.layer_types is not None and \
            bool(set(self.layer_types) & _STATE_KINDS)

    @property
    def is_mixer(self) -> bool:
        """A ``layer_types`` model whose layers are ONE mixer each: mamba2
        or mamba1, experts, mlp and full_attention layers
        (models/ssm_moe.py)."""
        return self.layer_types is not None and \
            bool(_SSM_KINDS & set(self.layer_types))

    @property
    def num_expert_layers(self) -> int:
        """Layers that carry the expert block (all of a MoE model's but an
        afmoe model's leading dense ones; a one-mixer model's ``experts``
        layers)."""
        if self.is_mixer:
            return self.layer_types.count("experts")
        return (self.num_layers - self.num_dense_layers) if self.is_moe else 0


@dataclasses.dataclass(frozen=True)
class MlaKind:
    """One kind of latent layer (:meth:`ModelConfig.mla_kind`)."""
    stack: str               # its parameter stack: params[stack]
    heads: int
    kv: int                  # the latent's rank
    nope: int
    rot: int
    v: int
    q_rank: int              # 0: a full-rank query
    rope: Optional[dict]     # None: the rotary values pass unrotated
    window: int              # 0: every earlier key
    index: Optional[tuple]   # (index heads, their size, keys selected)
    gate: bool               # a sigmoid a head on the head's output
    q_scale: float           # on the normed query bottleneck
    kv_scale: float          # on the normed latent
    eps: float

    @property
    def row_width(self) -> int:
        """A cache row: ``kv + rot`` values padded to whole 128-lane
        tiles."""
        return -(-(self.kv + self.rot) // 128) * 128


_WINDOW_KINDS = frozenset({"sliding_attention", "full_attention"})
_STATE_KINDS = frozenset({"linear_attention", "latent_attention",
                          "latent_sliding_attention"})
# a state a slot beside per-head K/V pages: both kinds, nothing else
_HYBRID_KINDS = frozenset({"linear_attention", "full_attention"})
# a layer is ONE mixer (models/ssm_moe.py); a state-space kind turns the
# form on
_SSM_KINDS = frozenset({"mamba2", "mamba1"})
_MIXER_KINDS = _SSM_KINDS | {"experts", "mlp", "full_attention"}
# the sizes each state-space kind needs, and those both do
_SSM_SIZES = {"mamba2": ("ssm_num_heads", "ssm_head_dim", "ssm_groups"),
              "mamba1": ("ssm_inner_size", "ssm_dt_rank")}
_SSM_SHARED = ("ssm_state_size", "ssm_conv_kernel", "ssm_chunk")
# fields only that form reads
_MIXER_ONLY = (*_SSM_SIZES["mamba2"], *_SSM_SIZES["mamba1"], *_SSM_SHARED,
               "ssm_inner_norms", "shared_intermediate_size")
_MLA_SLIDING_KEYS = frozenset({"num_heads", "kv_rank", "nope_dim", "rot_dim",
                               "v_dim", "q_rank", "rope"})
# fields only models/kda_mla.py reads
_KDA_MLA_ONLY = ("kda_num_heads", "kda_head_dim", "kda_conv_kernel",
                 "kda_gate_rank", "mla_kv_rank", "mla_nope_dim",
                 "mla_rot_dim", "mla_v_dim")
# ... the forms of a linear layer and of a latent layer that a model may
# leave at their zero
_KDA_FORMS = ("kda_neg_eigval",)
_MLA_FORMS = ("mla_q_rank", "mla_rope", "mla_sliding", "mla_index_heads",
              "mla_index_dim", "mla_index_topk", "mla_head_gate",
              "mla_lora_rescale")
_MLA_ROPE_KEYS = frozenset({
    "theta", "factor", "original_max_position_embeddings", "beta_fast",
    "beta_slow", "mscale", "mscale_all_dim"})
# fields only the layer form (models/afmoe.py, models/kda_mla.py) reads.
# ``sandwich_norm`` is not among them: the Llama backbone reads it too
# (models/transformer.py, models/decoding.py, models/fused_decode.py), as it
# reads ``total_ut_steps`` and ``loop_exit_gate``, which the layer form refuses
_AFMOE_ONLY = frozenset({
    "sliding_window", "num_dense_layers", "dense_intermediate_size",
    "qk_norm_per_head", "attn_output_gate", "embed_scale",
    "moe_score_func", "moe_route_scale", "moe_select_bias",
    "num_shared_experts", "moe_router_experts", "moe_first_expert",
    "moe_n_group", "moe_topk_group", *_KDA_MLA_ONLY, *_KDA_FORMS,
    *_MLA_FORMS, *_MIXER_ONLY})
# ... of which the one-mixer form reads the router's and its own
_MIXER_READS = frozenset({
    "moe_score_func", "moe_route_scale", "moe_select_bias",
    "num_shared_experts", "moe_router_experts", "moe_first_expert",
    "moe_n_group", "moe_topk_group", *_MIXER_ONLY})


_PRESETS = {
    # GPT-2 family (BASELINE.json configs[1]: GPT-2 125M rung)
    "gpt2-small": dict(vocab_size=50257, hidden_size=768, intermediate_size=3072,
                       num_layers=12, num_heads=12, max_seq_len=1024,
                       norm="layernorm", activation="gelu", glu=False,
                       position="learned", tie_embeddings=True),
    "gpt2-medium": dict(vocab_size=50257, hidden_size=1024, intermediate_size=4096,
                        num_layers=24, num_heads=16, max_seq_len=1024,
                        norm="layernorm", activation="gelu", glu=False,
                        position="learned", tie_embeddings=True),
    "gpt2-xl": dict(vocab_size=50257, hidden_size=1600, intermediate_size=6400,
                    num_layers=48, num_heads=25, max_seq_len=1024,
                    norm="layernorm", activation="gelu", glu=False,
                    position="learned", tie_embeddings=True, remat=True),
    # Llama family (configs[2]/[4]: 8B on v5p-8, 70B on v5p-128; llama2-7b is
    # the BASELINE.json "7B" north-star size)
    "llama-tiny": dict(vocab_size=32000, hidden_size=256, intermediate_size=688,
                       num_layers=4, num_heads=8, num_kv_heads=4, max_seq_len=2048),
    # 1.34B dense rung (VERDICT r4 item 1: a >1B model that fits one 16GB
    # chip with int8 optimizer states + bf16 grad accum + remat).  Vocab
    # padded to a multiple of 128 for MXU tiling; head_dim 128 fills the
    # systolic array (D=64 heads halve it — see ops/pallas notes).
    "llama-1b4": dict(vocab_size=50304, hidden_size=2048, intermediate_size=5632,
                      num_layers=24, num_heads=16, num_kv_heads=16,
                      max_seq_len=2048, tie_embeddings=True, remat=True,
                      remat_policy="mlp_dots"),
    "llama2-7b": dict(vocab_size=32000, hidden_size=4096, intermediate_size=11008,
                      num_layers=32, num_heads=32, max_seq_len=4096, remat=True),
    "llama2-13b": dict(vocab_size=32000, hidden_size=5120, intermediate_size=13824,
                       num_layers=40, num_heads=40, max_seq_len=4096, remat=True),
    "llama3-8b": dict(vocab_size=128256, hidden_size=4096, intermediate_size=14336,
                      num_layers=32, num_heads=32, num_kv_heads=8, max_seq_len=8192,
                      rope_theta=500000.0, remat=True),
    "llama3-70b": dict(vocab_size=128256, hidden_size=8192, intermediate_size=28672,
                       num_layers=80, num_heads=64, num_kv_heads=8, max_seq_len=8192,
                       rope_theta=500000.0, remat=True),
    # Mixtral family (configs[3]: MoE expert-parallel rung)
    "mixtral-tiny": dict(vocab_size=32000, hidden_size=256, intermediate_size=512,
                         num_layers=4, num_heads=8, num_kv_heads=4, max_seq_len=2048,
                         num_experts=8, num_experts_per_tok=2),
    "mixtral-8x7b": dict(vocab_size=32000, hidden_size=4096, intermediate_size=14336,
                         num_layers=32, num_heads=32, num_kv_heads=8, max_seq_len=8192,
                         rope_theta=1000000.0, num_experts=8, num_experts_per_tok=2,
                         remat=True),
}


def get_model_config(name: str, **overrides) -> ModelConfig:
    if name not in _PRESETS:
        raise KeyError(f"unknown model preset {name!r}; available: {sorted(_PRESETS)}")
    kw = dict(_PRESETS[name])
    kw.update(overrides)
    return ModelConfig(**kw)
