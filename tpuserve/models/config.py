"""Model architecture configs and the model registry.

The reference serves models by name only — the architecture lives inside the
vLLM container it deploys (reference: llm-d-deploy.yaml:118 pins
``Qwen/Qwen3-0.6B``; kubernetes-single-node.yaml:15 names Phi-3-mini;
templates/opt-chat-template.yaml targets facebook/opt-1.3b).  Here the
architectures are first-class: one ``ModelConfig`` covers the whole
decoder-only family the framework serves (Qwen3/Qwen2/Llama/Phi-3/OPT), with
per-family presets plus loading from a HuggingFace ``config.json``.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
from typing import Optional

# ModelConfig.layer_mixer()'s answers
MIXER_ATTENTION = "attention"
MIXER_LINEAR = "linear_attention"
MIXER_BOTH = "attention+ssm"


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    max_position_embeddings: int = 32768
    # Architecture knobs spanning the supported families.
    norm: str = "rmsnorm"            # "rmsnorm" | "layernorm"
    norm_eps: float = 1e-6
    act: str = "silu"                # "silu" | "gelu" | "relu"
    mlp_style: str = "gated"         # "gated" (SwiGLU-style) | "mlp" (2-layer)
    pos: str = "rope"                # "rope" | "learned" | "none"
    rope_theta: float = 10000.0
    partial_rotary_factor: float = 1.0
    qk_norm: bool = False            # Qwen3 per-head RMSNorm on q/k
    attention_bias: bool = False     # Qwen2-style bias on q/k/v projections
    mlp_bias: bool = False
    # Gemma traits: RMSNorm computes (1 + weight) — checkpoints store the
    # residual around 0 — and embeddings scale by sqrt(hidden_size).
    norm_weight_offset: float = 0.0
    embed_scale_by_sqrt_dim: bool = False
    # Sliding-window attention (Mistral): each position attends to at most
    # the previous `sliding_window` tokens.  None = full context.  Besides
    # correctness for the family, decode skips whole KV pages outside the
    # window — at 32k context with a 4k window that is 8x fewer KV reads.
    sliding_window: Optional[int] = None
    # Qwen2-style mixed layers: the FIRST this-many layers use full
    # attention, the rest the sliding window (HF max_window_layers).
    # Non-zero disables the rolling-buffer block release — full-attention
    # layers need every position's KV forever.
    full_attention_first_layers: int = 0
    # "first_full" (Qwen2) or "alternate" (Gemma2: even layers sliding,
    # odd layers full) — see layer_window()
    window_pattern: str = "first_full"
    # Explicit per-layer windowed flags (True = sliding), from HF
    # layer_types (Gemma3's 5-local:1-global pattern); overrides
    # window_pattern when set.
    window_layers: Optional[tuple] = None
    # Per-layer rope (Gemma3): WINDOWED layers use this base frequency
    # unscaled; full layers use rope_theta with rope_scaling_factor
    # (linear: positions divided by the factor).
    rope_local_base_freq: Optional[float] = None
    rope_scaling_factor: float = 1.0
    # Llama-3.1 frequency transform: (factor, low_freq_factor,
    # high_freq_factor, original_max_position_embeddings) — see
    # ops/rope.py rope_freqs.
    rope_llama3_scaling: Optional[tuple] = None
    # YaRN long-context scaling (DeepSeek): (factor, beta_fast, beta_slow,
    # mscale, mscale_all_dim, original_max_position_embeddings) — see
    # ops/rope.py rope_freqs; mscale_all_dim also squares into attn_scale.
    rope_yarn: Optional[tuple] = None
    # YaRN on the FULL-attention layers alone (Mellum 2: layers of two
    # kinds with their own rotary tables, HF ``rope_parameters`` keyed by
    # layer type): (factor, beta_fast, beta_slow,
    # original_max_position_embeddings).  Windowed layers rotate by the
    # plain table; the attention factor 0.1 ln(factor) + 1 multiplies the
    # full layers' cos and sin, attn_scale is untouched — see layer_yarn().
    rope_full_yarn: Optional[tuple] = None
    # Rotary on the WINDOWED layers alone (K-EXAONE, after EXAONE 4.0's
    # hybrid attention): a layer that attends the whole context carries no
    # positional rotation at all — see layer_rotates().
    rope_windowed_only: bool = False
    # Gemma2 traits: tanh softcaps on attention scores / final logits,
    # attention scale from query_pre_attn_scalar instead of head_dim, and
    # sandwich norms (post-attention + pre/post-feedforward layernorms).
    attn_logit_softcapping: Optional[float] = None
    final_logit_softcapping: Optional[float] = None
    query_pre_attn_scalar: Optional[int] = None
    sandwich_norms: bool = False
    tie_word_embeddings: bool = True
    learned_pos_offset: int = 0      # OPT stores positions shifted by 2
    final_layernorm: bool = True
    bos_token_id: Optional[int] = None
    eos_token_id: Optional[int] = None
    dtype: str = "bfloat16"
    # Mixture-of-experts (Qwen3-MoE-style): 0 experts = dense MLP.  The
    # router picks num_experts_per_tok experts per token; expert MLPs use
    # moe_intermediate_size (falls back to intermediate_size).
    num_experts: int = 0
    num_experts_per_tok: int = 2
    moe_intermediate_size: Optional[int] = None
    norm_topk_prob: bool = True      # renormalise the top-k router weights
    # DeepSeek MoE extensions (deepseek_v2/v3; HF modeling_deepseek_v3):
    # sigmoid expert scoring with a selection-only correction bias
    # (e_score_correction_bias), grouped top-k (pick topk_group of n_group
    # expert groups, then top-k inside the surviving groups), a scaling
    # factor on the combine weights, always-on shared experts added to the
    # routed output, and the first k layers staying dense.
    moe_scoring: str = "softmax"     # "softmax" (Qwen3) | "sigmoid" (DSv3)
    moe_router_bias: bool = False    # e_score_correction_bias on selection
    moe_n_group: int = 1
    moe_topk_group: int = 1
    moe_routed_scaling: float = 1.0
    moe_shared_experts: int = 0      # shared-expert width multiplier
    moe_first_k_dense: int = 0       # first_k_dense_replace
    # One chip's share of a deployment whose chips share each expert layer
    # (expert parallelism): this process holds ``moe_experts_held``
    # contiguous experts from ``moe_first_expert`` on, of the
    # ``num_experts`` the router is wide.  The router, the top-k and the
    # combine weights are over all of them; the layer computes the held
    # experts' part for the rows routed to them and leaves the rest out
    # (models/transformer.py _moe_held_experts), the shared expert whole.
    # 0 = every expert is held, and the layer is the one it always was.
    moe_experts_held: int = 0
    moe_first_expert: int = 0
    # Multi-head latent attention (DeepSeek MLA): K/V are compressed to a
    # kv_lora_rank latent + one shared roped key per token, so the cache
    # stores ONE (kv_lora_rank + qk_rope_head_dim)-wide "head" per token
    # instead of num_heads full K/V pairs — ~10x less KV HBM traffic and
    # capacity, the TPU-first win for decode.  head_dim must equal
    # qk_nope + qk_rope (the q/k attention width); v_head_dim is separate.
    mla_kv_lora_rank: Optional[int] = None   # None = standard attention
    mla_q_lora_rank: Optional[int] = None    # None = direct q projection
    mla_qk_rope_head_dim: int = 64
    mla_v_head_dim: int = 128
    # DeepSeek checkpoints store rope-dim weights channel-INTERLEAVED
    # (GPT-J pairing).  The loader de-interleaves those output channels
    # once at load (models/weights.py _mla_deinterleave), so the forward
    # always runs the NeoX split-half rope — zero runtime cost.
    mla_rope_interleave: bool = True
    # Hybrid state-space layers (Falcon-H1): EVERY layer runs a Mamba-2
    # mixer beside its attention heads on the same normed input and sums
    # the two into the residual.  ``mamba_d_ssm`` 0 = no such branch.  The
    # mixer keeps a recurrent state (mamba_n_heads x mamba_d_head x
    # mamba_d_state, float32) and the last mamba_d_conv - 1 inputs of its
    # short convolution per SEQUENCE, not per token: the engine holds them
    # in a pool indexed by seat beside the paged KV cache
    # (runtime/kv_cache.create_ssm_state).  B and C are shared by the
    # heads of one of mamba_n_groups groups; prefill evaluates the
    # recurrence mamba_chunk_size rows at a time (ops/ssm.py).
    mamba_d_ssm: int = 0
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False     # bias on the mixer's in_proj
    mamba_out_bias: bool = False      # ... on its out_proj (HF projectors_bias)
    mamba_rms_norm: bool = True       # grouped RMSNorm on the gated output
    mamba_norm_before_gate: bool = False
    # Falcon-H1's fixed muP multipliers: plain scalars on the embedding,
    # the keys, the attention branch's input and output, the mixer's input
    # and output, the MLP's gate and down projections and the logits, and
    # five more on the slices [z | x | B | C | dt] of the mixer's input
    # projection.  1.0 everywhere else (and then not applied at all).
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    mlp_multipliers: tuple = (1.0, 1.0)               # (gate, down)
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)  # z, x, B, C, dt
    # Layers that differ in their KIND OF MIXER (Olmo-Hybrid; Ling-3.0-
    # flash, whose attention layers are LATENT ones: ``is_mla`` says what an
    # attention layer caches, this tuple which layers attend): True where
    # a layer is a gated delta-rule linear-attention layer, which holds a
    # matrix state a SEQUENCE (lin_num_value_heads x lin_key_head_dim x
    # lin_value_head_dim, float32) and the last lin_conv_kernel - 1 inputs
    # of its short convolution, and no K/V pages at all; False where it
    # is an attention layer, which holds pages and no state.  One entry a
    # PUBLISHED layer (a cut of the depth keeps the first num_layers, as
    # window_layers does); None: every layer attends -- see layer_mixer().
    # ``lin_allow_neg_eigval`` doubles the step size beta, so a state's
    # transition alpha (I - beta k k^T) may reflect (arXiv:2411.12537);
    # prefill evaluates the recurrence lin_chunk_size rows at a time
    # (ops/gated_delta.py).
    linear_layers: Optional[tuple] = None
    lin_num_key_heads: int = 0
    lin_num_value_heads: int = 0
    lin_key_head_dim: int = 0
    lin_value_head_dim: int = 0
    lin_conv_kernel: int = 4
    lin_allow_neg_eigval: bool = False
    lin_chunk_size: int = 64
    # The linear mixer's FORM, which the model's config answers: "scalar"
    # (Olmo-Hybrid: ONE decay a head, -exp(A_log) softplus(a + dt_bias), a
    # SiLU output gate) or "channel" (Kimi Delta Attention,
    # arXiv:2510.26692: a decay for every KEY CHANNEL of every head, so
    # the state's rows decay each at its own rate; the safe gate g =
    # lin_gate_lower_bound * sigmoid(exp(A_log) (W_f x + dt_bias)) lies in
    # (lin_gate_lower_bound, 0), which is what bounds the chunked scan's
    # exponents (ops/gated_delta.py kda_chunk_scan); a sigmoid output
    # gate).  The sizes are the lin_* fields' either way.
    lin_gate: str = "scalar"
    lin_gate_lower_bound: float = 0.0
    # A sigmoid gate a HEAD on an attention layer's output before o_proj
    # (bailing_hybrid ``gated_attention_proj_granularity_type`` head_wise:
    # one more column a head beside the query projection).
    attn_head_gate: bool = False
    # A latent-attention layer's q/k width (qk_nope + qk_rope) in a model
    # whose ``head_dim`` is ANOTHER mixer's head (bailing_hybrid: 128 is
    # the linear layers' head and the rotary's base, 192 the latent
    # layers' q/k); None: ``head_dim`` is that width, as in DeepSeek's
    # family -- see qk_head_dim.
    mla_qk_head_dim: Optional[int] = None
    # bailing_hybrid's per-layer clamp on the gated activation of the
    # routed and of the shared experts (``expert_swiglu_limit_list``,
    # ``share_expert_swiglu_limit_list``), one entry a PUBLISHED layer, 0 =
    # none.  The clamp is NOT built: require_built() refuses a running
    # layer whose entry is not 0.
    moe_swiglu_limits: Optional[tuple] = None
    moe_shared_swiglu_limits: Optional[tuple] = None
    # Where a layer's two norms stand: "pre" on each branch's INPUT (every
    # family above), "post" on its OUTPUT before the add to the residual
    # stream (the OLMo 2 / 3 family: x + norm(mixer(x)), x + norm(mlp(x))).
    norm_placement: str = "pre"
    # The q/k norm (``qk_norm``) over the WHOLE projection, all heads at
    # once, before the split into heads (OLMo 2 / 3) -- not a head at a
    # time (Qwen3).
    qk_norm_whole: bool = False

    def __post_init__(self):
        if self.moe_experts_held or self.moe_first_expert:
            held, first = self.moe_experts_held, self.moe_first_expert
            if not (0 < held and 0 <= first
                    and first + held <= self.num_experts):
                raise ValueError(
                    f"{self.name}: cannot hold experts {first} to "
                    f"{first + held - 1} of {self.num_experts}")
        if self.linear_layers is not None:
            if len(self.linear_layers) < self.num_layers \
                    or not self.lin_num_value_heads:
                raise ValueError(
                    f"{self.name}: linear_layers states "
                    f"{len(self.linear_layers)} layers of {self.num_layers} "
                    "(or no lin_* sizes)")
            if self.lin_num_key_heads != self.lin_num_value_heads:
                raise ValueError(
                    f"{self.name}: {self.lin_num_key_heads} key heads under "
                    f"{self.lin_num_value_heads} value heads in the linear "
                    "layers: one key head a value head is what runs")
            if self.mamba_d_ssm:
                raise ValueError(f"{self.name}: linear-attention layers "
                                 "and state-space heads in one model")
            if self.lin_gate not in ("scalar", "channel"):
                raise ValueError(f"{self.name}: lin_gate {self.lin_gate!r} "
                                 "(scalar and channel are built)")
            if self.lin_gate == "channel" and not self.lin_gate_lower_bound < 0:
                raise ValueError(
                    f"{self.name}: a channel gate needs its lower bound "
                    f"(lin_gate_lower_bound {self.lin_gate_lower_bound!r}): "
                    "the chunked scan's exponents are bounded by it")

    @property
    def has_ssm(self) -> bool:
        """True when every layer runs Mamba-2 heads BESIDE its attention
        (Falcon-H1): the weights' layout and names ask this.  Whether a
        layer holds a recurrent state is :meth:`layer_mixer`'s answer."""
        return self.mamba_d_ssm > 0

    def layer_mixer(self, layer_idx: int) -> str:
        """One layer's kind of mixer, beside layer_window() and
        layer_rotates() -- ONE function for every forward path, the cache
        and the seat pool: ``MIXER_ATTENTION`` (K/V pages, or latent pages
        where the model ``is_mla``, no state), ``MIXER_LINEAR`` (a gated
        delta-rule state in ``lin_gate``'s form, no pages) or
        ``MIXER_BOTH`` (Falcon-H1: state-space heads beside attention
        heads, pages and a state)."""
        if self.mamba_d_ssm:
            return MIXER_BOTH
        if self.linear_layers is not None and self.linear_layers[layer_idx]:
            return MIXER_LINEAR
        return MIXER_ATTENTION

    @property
    def kv_layers(self) -> tuple:
        """The running layers that hold K/V pages, in order: the paged
        cache has one entry each (runtime/kv_cache.create_kv_cache), and a
        layer's entry is at its place in this tuple."""
        return tuple(i for i in range(self.num_layers)
                     if self.layer_mixer(i) != MIXER_LINEAR)

    @property
    def state_layers(self) -> tuple:
        """The running layers that hold a recurrent state a sequence: the
        seat pool's entries (runtime/kv_cache.create_ssm_state)."""
        return tuple(i for i in range(self.num_layers)
                     if self.layer_mixer(i) != MIXER_ATTENTION)

    @property
    def has_state(self) -> bool:
        """True when ANY layer holds a recurrent state: what the engine
        observes to build the seat pool and to close the routes that
        would need a snapshot of it."""
        return bool(self.state_layers)

    @property
    def lin_conv_dim(self) -> int:
        """Channels of a linear layer's short convolution: q, k, then v."""
        return (2 * self.lin_num_key_heads * self.lin_key_head_dim
                + self.lin_num_value_heads * self.lin_value_head_dim)

    @property
    def layer_group_size(self) -> int:
        """bailing_hybrid's spelling of ``linear_layers``: the period ``p``
        with layer ``i`` an attention layer exactly where ``(i + 1) % p ==
        0`` (over the PUBLISHED layers); 0 where the kinds follow no such
        period or no layer is linear."""
        kinds = self.linear_layers or ()
        return next((p for p in range(2, len(kinds) + 1)
                     if all(lin == bool((i + 1) % p)
                            for i, lin in enumerate(kinds))), 0)

    @property
    def lin_safe_gate(self) -> bool:
        """bailing_hybrid ``kda_safe_gate``: the channel gate's bounded
        form, the only one built."""
        return self.lin_gate == "channel"

    @property
    def attn_gate_granularity(self) -> Optional[str]:
        """bailing_hybrid ``gated_attention_proj_granularity_type``."""
        return "head_wise" if self.attn_head_gate else None

    @property
    def expert_swiglu_limit_list(self) -> list:
        """The running layers' clamp on the routed experts' gated
        activation as config.json lists it, 0 = none (the only value
        :meth:`require_built` lets run)."""
        return list((self.moe_swiglu_limits
                     or (0,) * self.num_layers)[:self.num_layers])

    @property
    def share_expert_swiglu_limit_list(self) -> list:
        return list((self.moe_shared_swiglu_limits
                     or (0,) * self.num_layers)[:self.num_layers])

    @property
    def moe_shared_intermediate_size(self) -> int:
        return self.expert_intermediate_size * self.moe_shared_experts

    def require_built(self) -> None:
        """Refuse, by name, what this configuration states and no code
        computes -- asked where weights are made or loaded, so that a
        registered model whose LATER layers need it can still be cut to
        the layers that do not (a benchmark configuration's depth)."""
        for key in ("expert_swiglu_limit_list",
                    "share_expert_swiglu_limit_list"):
            clamped = [i for i, x in enumerate(getattr(self, key)) if x]
            if clamped:
                raise ValueError(
                    f"{self.name}: {key} clamps the gated activation of "
                    f"layers {clamped}: the clamped activation is not built "
                    "(only layers whose entry is 0 run)")

    @property
    def mamba_conv_dim(self) -> int:
        """Channels of the short convolution: x, then B and C a group."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def mamba_proj_widths(self) -> tuple:
        """Columns of the mixer's input projection, slice by slice, in
        the order ``ssm_multipliers`` scales them: [z | x | B | C | dt]."""
        gn = self.mamba_n_groups * self.mamba_d_state
        return (self.mamba_d_ssm, self.mamba_d_ssm, gn, gn,
                self.mamba_n_heads)

    @property
    def mamba_proj_size(self) -> int:
        return sum(self.mamba_proj_widths)

    @property
    def mlp_multiplier_list(self) -> list:
        """As config.json spells it (a JSON list never equals a tuple)."""
        return list(self.mlp_multipliers)

    @property
    def ssm_multiplier_list(self) -> list:
        return list(self.ssm_multipliers)

    def layer_window(self, layer_idx: int) -> Optional[int]:
        """Effective sliding window for one layer — ONE implementation for
        every forward path.  "first_full": the first
        ``full_attention_first_layers`` layers run full attention (Qwen2
        max_window_layers).  "alternate": even layers sliding, odd full
        (Gemma2 layer_types)."""
        if self.sliding_window is None:
            return None
        if self.window_layers is not None:
            return (self.sliding_window if self.window_layers[layer_idx]
                    else None)
        if self.window_pattern == "alternate":
            return self.sliding_window if layer_idx % 2 == 0 else None
        if layer_idx < self.full_attention_first_layers:
            return None
        return self.sliding_window

    def layer_rope(self, layer_idx: int) -> tuple[float, float]:
        """(theta, linear position scaling) for one layer.  Gemma3:
        windowed layers rotate at rope_local_base_freq unscaled; full
        layers at rope_theta with the linear factor.  Families without
        per-layer rope get (rope_theta, rope_scaling_factor) everywhere."""
        if (self.rope_local_base_freq is not None
                and self.layer_window(layer_idx) is not None):
            return self.rope_local_base_freq, 1.0
        return self.rope_theta, self.rope_scaling_factor

    def layer_yarn(self, layer_idx: int) -> Optional[tuple]:
        """``ops/rope.rope_freqs``'s ``yarn_scaling`` for one layer's
        table, beside layer_rope() — ONE function for every forward path
        (``_qkv``).  ``rope_full_yarn`` applies to the layers that attend
        the whole context; no mscale pair, so the attention factor is
        0.1 ln(factor) + 1 on cos and sin.  (``rope_yarn`` is DeepSeek's
        model-wide form and belongs to the MLA path.)"""
        if (self.rope_full_yarn is None
                or self.layer_window(layer_idx) is not None):
            return None
        factor, beta_fast, beta_slow, orig_max = self.rope_full_yarn
        return (factor, beta_fast, beta_slow, 0, 0, orig_max)

    def layer_rotates(self, layer_idx: int) -> bool:
        """Whether one layer's q and k are rotated at all, beside
        layer_rope() and layer_yarn() — read by ``_qkv`` alone.  False
        only on the full layers of a model whose windowed layers alone
        carry positions (``rope_windowed_only``)."""
        return not (self.rope_windowed_only
                    and self.layer_window(layer_idx) is None)

    def layer_like(self, layer_idx: int) -> int:
        """The lowest layer index that answers every per-layer question of
        this configuration as ``layer_idx`` does: layer_mixer(),
        layer_window(), layer_rotates(), layer_rope(), layer_yarn() and
        moe_layer_is_dense().  Layers of one KIND share it, and a trunk
        hands it to its layer's body in place of the layer's own index
        (models/transformer.py: one trace and one lowered function a
        kind, not a layer).  A per-layer question added to this class
        belongs in ``_layer_kind`` too."""
        return _layer_likes(self)[layer_idx]

    def _layer_kind(self, layer_idx: int) -> tuple:
        return (self.layer_mixer(layer_idx), self.layer_window(layer_idx),
                self.layer_rotates(layer_idx), self.layer_rope(layer_idx),
                self.layer_yarn(layer_idx),
                self.moe_layer_is_dense(layer_idx))

    # What a configuration file's keys are held to (benchmark/harness/
    # plan.py compares with ``!=``: a JSON list or dict never equals a
    # tuple), as config.json spells them.

    @property
    def layer_types(self) -> list:
        """The running layers' kinds, as HF ``layer_types`` names them."""
        return ["linear_attention" if self.layer_mixer(i) == MIXER_LINEAR
                else "sliding_attention" if self.layer_window(i) is not None
                else "full_attention" for i in range(self.num_layers)]

    @property
    def mlp_layer_types(self) -> list:
        return ["sparse" if self.num_experts
                and not self.moe_layer_is_dense(i) else "dense"
                for i in range(self.num_layers)]

    @property
    def max_window_layers(self) -> int:
        return self.full_attention_first_layers

    @property
    def rope_parameters(self) -> Optional[dict]:
        """HF ``rope_parameters``: keyed by layer type for a model whose
        rotary tables differ by layer kind, flat (one plain table) for
        one whose windowed layers alone rotate, a null base for one that
        rotates nothing (``pos`` "none"); None for any other."""
        if self.pos == "none":
            return {"rope_theta": None}
        if self.rope_windowed_only:
            return {"rope_theta": self.rope_theta, "rope_type": "default"}
        if self.rope_full_yarn is None:
            return None
        from tpuserve.ops.rope import yarn_mscale
        factor, beta_fast, beta_slow, orig_max = self.rope_full_yarn
        return {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": self.rope_theta,
                "factor": factor,
                "original_max_position_embeddings": orig_max,
                "beta_fast": beta_fast, "beta_slow": beta_slow,
                "attention_factor": yarn_mscale(factor)},
            "sliding_attention": {
                "rope_type": "default",
                "rope_theta": self.rope_local_base_freq or self.rope_theta},
        }

    @property
    def sliding_windows(self) -> list:
        """Each running layer's window, 0 where it attends the whole
        context (K-EXAONE's ``sliding_windows``)."""
        return [self.layer_window(i) or 0 for i in range(self.num_layers)]

    @property
    def sliding_window_pattern(self) -> str:
        """The shortest period of the running layers' kinds, ``L`` a
        windowed layer and ``G`` a full one (K-EXAONE's spelling)."""
        kinds = "".join("L" if w else "G" for w in self.sliding_windows)
        return next(kinds[:p] for p in range(1, len(kinds) + 1)
                    if kinds == (kinds[:p] * len(kinds))[:len(kinds)])

    @property
    def moe_local_experts(self) -> int:
        """Experts whose kernels this process holds."""
        return self.moe_experts_held or self.num_experts

    @property
    def uniform_window(self) -> bool:
        """True when EVERY layer is windowed — the rolling-buffer block
        release is only sound then (any full-attention layer needs every
        position's KV forever)."""
        return (self.sliding_window is not None
                and all(self.layer_window(i) is not None
                        for i in range(self.num_layers)))

    @property
    def qk_head_dim(self) -> int:
        """Width of an attention layer's queries and keys: ``head_dim``,
        but for a latent layer beside another mixer (mla_qk_head_dim)."""
        return self.mla_qk_head_dim or self.head_dim

    @property
    def attn_scale(self) -> float:
        """Attention score scale: Gemma2 uses query_pre_attn_scalar**-0.5
        instead of head_dim**-0.5; under YaRN with mscale_all_dim the
        DeepSeek magnitude correction squares in (HF DeepseekV3Attention)."""
        scale = (self.query_pre_attn_scalar or self.qk_head_dim) ** -0.5
        if self.rope_yarn is not None and self.rope_yarn[4]:
            from tpuserve.ops.rope import yarn_mscale
            m = yarn_mscale(self.rope_yarn[0], self.rope_yarn[4])
            scale *= m * m
        return scale

    @property
    def q_size(self) -> int:
        return self.num_heads * self.qk_head_dim

    @property
    def attn_out_size(self) -> int:
        """Width of the attention output fed to o_proj: MLA values are
        mla_v_head_dim wide, not head_dim."""
        return self.num_heads * (self.mla_v_head_dim if self.is_mla
                                 else self.head_dim)

    @property
    def kv_size(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def expert_intermediate_size(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def is_mla(self) -> bool:
        return self.mla_kv_lora_rank is not None

    @property
    def mla_qk_nope_head_dim(self) -> int:
        """q/k split: qk_head_dim covers nope + rope (matches HF
        qk_head_dim, so attn_scale = qk_head_dim**-0.5 is DeepSeek's
        scaling)."""
        return self.qk_head_dim - self.mla_qk_rope_head_dim

    @property
    def mla_latent_dim(self) -> int:
        """Width of the single cached vector per token: the compressed KV
        latent plus the shared roped key."""
        return self.mla_kv_lora_rank + self.mla_qk_rope_head_dim

    @property
    def cache_kv_heads(self) -> int:
        """KV-cache head count: MLA stores one latent "head".  More than
        one sublane tile of heads that is not whole tiles (30) is stored
        as whole tiles (32): the chip lays a page's ``(heads, head size)``
        rows out in tiles of 8 either way, and only whole tiles make a
        page the contiguous ``(page x heads, head size)`` slab the paged
        decode kernel lands in one copy (ops/pallas_paged_attention.py).
        The heads past ``num_kv_heads`` hold zeros (``_qkv`` pads q, k and
        v, ``_attn_residual`` drops their output)."""
        if self.is_mla:
            return 1
        hkv = self.num_kv_heads
        return hkv if hkv <= 8 else -(-hkv // 8) * 8

    @property
    def cache_q_heads(self) -> int:
        """Query heads as the attention kernels see them: each KV head's
        group, over :attr:`cache_kv_heads`."""
        if self.is_mla:
            return self.num_heads
        return self.cache_kv_heads * (self.num_heads // self.num_kv_heads)

    @property
    def cache_head_dim(self) -> int:
        """KV-cache per-head width: MLA stores the latent vector.  More
        than one lane tile of it that is not whole tiles (576) is stored
        as whole tiles (640), as :attr:`cache_kv_heads` stores heads: the
        chip lays a row out in 128-lane tiles either way (a 576-wide
        array IS 640 wide in HBM), and the paged kernels may copy whole
        tiles only.  The lanes past ``mla_latent_dim`` hold zeros
        (``write_mla_entry`` pads the latent, ``_mla_absorb_q`` the
        query), so they add nothing to a score and no value reads them."""
        if not self.is_mla:
            return self.head_dim
        d = self.mla_latent_dim
        return d if d <= 128 else -(-d // 128) * 128

    @property
    def routes_experts(self) -> bool:
        """True when some layer is an expert layer: its cache trunks then
        return a dispatch's routing counts beside its result."""
        return bool(self.num_experts) \
            and self.moe_first_k_dense < self.num_layers

    def moe_layer_is_dense(self, layer_idx: int) -> bool:
        """DeepSeek first_k_dense_replace: the first k layers keep a dense
        MLP even in MoE models."""
        return bool(self.num_experts) and layer_idx < self.moe_first_k_dense

    @property
    def num_params(self) -> int:
        """Approximate parameter count (embeddings counted once if tied)."""
        h, i, l, v = self.hidden_size, self.intermediate_size, self.num_layers, self.vocab_size
        attn = h * self.q_size + 2 * h * self.kv_size + self.q_size * h
        if self.is_mla:
            attn = ((h * self.mla_q_lora_rank
                     + self.mla_q_lora_rank * self.q_size)
                    if self.mla_q_lora_rank else h * self.q_size) \
                + h * self.mla_latent_dim + self.mla_kv_lora_rank * (
                    self.num_heads * (self.mla_qk_nope_head_dim
                                      + self.mla_v_head_dim)) \
                + self.attn_out_size * h
        dense_mlp = (3 if self.mlp_style == "gated" else 2) * h * i
        if self.num_experts:
            # the held experts, the router at its published width and the
            # shared expert; the leading dense layers (moe_first_k_dense)
            # are put right below
            mlp = ((self.moe_local_experts + self.moe_shared_experts) * 3 * h
                   * self.expert_intermediate_size + h * self.num_experts)
        else:
            mlp = dense_mlp
        embed = v * h * (1 if self.tie_word_embeddings else 2)
        ssm = 0
        if self.has_ssm:
            ssm = (h * self.mamba_proj_size + self.mamba_d_ssm * h
                   + self.mamba_conv_dim * (self.mamba_d_conv + 1)
                   + 3 * self.mamba_n_heads + self.mamba_d_ssm)
        linear = len(self.state_layers) if not self.has_ssm else 0
        dense = min(self.moe_first_k_dense, l) if self.num_experts else 0
        return ((l - linear) * attn + linear * self.lin_layer_params
                + l * (mlp + ssm) + dense * (dense_mlp - mlp) + embed)

    @property
    def lin_layer_params(self) -> int:
        """Parameters of one linear-attention mixer: q, k, v, the output
        gate and the output projection, the two scalars a head (step size
        and decay) with ``A_log`` and ``dt_bias``, the convolution."""
        h, heads = self.hidden_size, self.lin_num_value_heads
        d_v = heads * self.lin_value_head_dim
        # the decay's projection and its bias: a scalar a head, or (the
        # channel gate) a value a key channel; pre-norm layers carry the
        # mixer's and the MLP's input norms
        d_g = heads * self.lin_key_head_dim if self.lin_gate == "channel" \
            else heads
        norms = 0 if self.norm_placement == "post" else 2 * h
        return (h * (self.lin_conv_dim + d_v) + d_v * h + h * (heads + d_g)
                + heads + d_g + self.lin_conv_kernel * self.lin_conv_dim
                + self.lin_value_head_dim + norms)


_REGISTRY: dict[str, ModelConfig] = {}


@functools.lru_cache(maxsize=64)
def _layer_likes(cfg: ModelConfig) -> tuple:
    """``cfg.layer_like`` of every layer: asked once a layer of every
    program a trunk traces, so kept by configuration."""
    first: dict = {}
    return tuple(first.setdefault(cfg._layer_kind(i), i)
                 for i in range(cfg.num_layers))


def register_model_config(cfg: ModelConfig, *aliases: str) -> ModelConfig:
    for key in (cfg.name, *aliases):
        _REGISTRY[key.lower()] = cfg
    return cfg


def list_model_configs() -> list[str]:
    return sorted({c.name for c in _REGISTRY.values()})


def get_model_config(name_or_path: str) -> ModelConfig:
    """Resolve a model by registry name, or by a local HF checkpoint dir."""
    key = name_or_path.lower()
    if key in _REGISTRY:
        return _REGISTRY[key]
    cfg_path = os.path.join(name_or_path, "config.json")
    if os.path.isfile(cfg_path):
        return config_from_hf_json(name_or_path, json.load(open(cfg_path)))
    raise KeyError(
        f"Unknown model {name_or_path!r}; known: {list_model_configs()} "
        "or pass a local checkpoint directory containing config.json"
    )


def config_from_hf_json(name: str, hf: dict) -> ModelConfig:
    """Map a HuggingFace config.json onto ModelConfig for supported families."""
    arch = (hf.get("architectures") or [""])[0].lower()
    mt = hf.get("model_type", "").lower()
    family = mt or arch
    common = dict(
        name=name,
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        num_layers=hf.get("num_hidden_layers", hf.get("num_layers")),
        num_heads=hf.get("num_attention_heads"),
        max_position_embeddings=hf.get("max_position_embeddings", 32768),
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
        bos_token_id=hf.get("bos_token_id"),
        eos_token_id=_first(hf.get("eos_token_id")),
    )
    if family == "falcon_h1" or arch.startswith("falconh1"):
        return _falcon_h1_config(hf, common)
    if family == "mellum":
        return _mellum_config(hf, common)
    if family == "exaone_moe":
        return _exaone_moe_config(hf, common)
    if family == "olmo_hybrid":
        return _olmo_hybrid_config(hf, common)
    if family == "pangu_ultra_moe":
        return _pangu_ultra_moe_config(hf, common)
    if family == "bailing_hybrid":
        return _bailing_hybrid_config(hf, common)
    if "opt" in family:
        common["tie_word_embeddings"] = hf.get("tie_word_embeddings", True)
        return ModelConfig(
            intermediate_size=hf["ffn_dim"],
            num_kv_heads=hf["num_attention_heads"],
            head_dim=hf["hidden_size"] // hf["num_attention_heads"],
            norm="layernorm",
            norm_eps=1e-5,
            act="relu",
            mlp_style="mlp",
            pos="learned",
            learned_pos_offset=2,
            attention_bias=True,
            mlp_bias=True,
            **common,
        )
    if family.startswith("deepseek_v") or arch.startswith("deepseekv"):
        # DeepSeek V2/V3 (MLA + DeepSeek-MoE).  head_dim is the q/k
        # attention width (qk_nope + qk_rope = HF qk_head_dim); the cache
        # stores the kv_lora_rank+rope latent instead (cache_head_dim).
        rs = hf.get("rope_scaling") or {}
        yarn = None
        if rs.get("type", rs.get("rope_type")) == "yarn":
            yarn = (rs["factor"], rs.get("beta_fast", 32),
                    rs.get("beta_slow", 1), rs.get("mscale", 1.0),
                    rs.get("mscale_all_dim", 0),
                    rs.get("original_max_position_embeddings",
                           common["max_position_embeddings"]))
        moe = {}
        if hf.get("n_routed_experts"):
            moe = dict(
                num_experts=hf["n_routed_experts"],
                num_experts_per_tok=hf["num_experts_per_tok"],
                moe_intermediate_size=hf["moe_intermediate_size"],
                norm_topk_prob=hf.get("norm_topk_prob", True),
                # V3 checkpoints say scoring_func sigmoid / topk_method
                # noaux_tc; the integrated transformers DeepseekV3Config
                # hardcodes both, so default by generation
                moe_scoring=hf.get(
                    "scoring_func",
                    "sigmoid" if "v3" in family or "v3" in arch
                    else "softmax"),
                moe_router_bias=(hf.get("topk_method") == "noaux_tc"
                                 or ("topk_method" not in hf
                                     and ("v3" in family or "v3" in arch))),
                moe_n_group=hf.get("n_group") or 1,
                moe_topk_group=hf.get("topk_group") or 1,
                moe_routed_scaling=hf.get("routed_scaling_factor", 1.0),
                moe_shared_experts=hf.get("n_shared_experts") or 0,
                moe_first_k_dense=hf.get("first_k_dense_replace", 0),
            )
        return ModelConfig(
            intermediate_size=hf["intermediate_size"],
            num_kv_heads=hf["num_attention_heads"],
            head_dim=hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"],
            rope_theta=hf.get("rope_theta", 10000.0),
            rope_yarn=yarn,
            norm_eps=hf.get("rms_norm_eps", 1e-6),
            attention_bias=hf.get("attention_bias", False),
            mla_kv_lora_rank=hf["kv_lora_rank"],
            mla_q_lora_rank=hf.get("q_lora_rank"),
            mla_qk_rope_head_dim=hf["qk_rope_head_dim"],
            mla_v_head_dim=hf["v_head_dim"],
            mla_rope_interleave=hf.get("rope_interleave", True),
            **moe,
            **common,
        )
    # gemma generations by model_type OR architectures (some configs omit
    # model_type); gemma3 adds per-layer rope scaling etc. — falling
    # through to the llama path would load and SILENTLY mis-serve, so
    # unsupported generations reject loudly
    gemma1 = mt == "gemma" or arch.startswith("gemmafor")
    gemma2 = mt == "gemma2" or arch.startswith("gemma2for")
    # gemma3 TEXT only; the multimodal wrapper (model_type "gemma3", a
    # vision tower + text_config) is rejected loudly below
    gemma3 = mt == "gemma3_text" or arch.startswith("gemma3forcausallm")
    if "gemma" in family and not (gemma1 or gemma2 or gemma3):
        raise ValueError(f"model family {family!r} is not supported yet "
                         "(gemma, gemma2 and gemma3 text are)")
    if gemma3:
        nh = hf["num_attention_heads"]
        lt = hf.get("layer_types")
        if lt:
            window_layers = tuple(t == "sliding_attention" for t in lt)
        else:
            # original-release configs encode the pattern as
            # sliding_window_pattern=p: every p-th layer is global
            pat = hf.get("sliding_window_pattern")
            if not pat:
                raise ValueError("gemma3 configs must carry layer_types "
                                 "or sliding_window_pattern")
            window_layers = tuple(
                (i + 1) % int(pat) != 0
                for i in range(hf["num_hidden_layers"]))
        rs = hf.get("rope_scaling")
        factor = 1.0
        if rs:
            if rs.get("rope_type", rs.get("type", "linear")) != "linear":
                raise ValueError(f"unsupported rope_scaling {rs!r} "
                                 "(linear only)")
            factor = float(rs.get("factor", 1.0))
        common["tie_word_embeddings"] = hf.get("tie_word_embeddings", True)
        return ModelConfig(
            intermediate_size=hf["intermediate_size"],
            num_kv_heads=hf.get("num_key_value_heads", nh),
            head_dim=hf.get("head_dim") or hf["hidden_size"] // nh,
            norm="rmsnorm",
            norm_eps=hf.get("rms_norm_eps", 1e-6),
            norm_weight_offset=1.0,
            embed_scale_by_sqrt_dim=True,
            act=(hf.get("hidden_activation") or hf.get("hidden_act")
                 or "gelu_pytorch_tanh"),
            mlp_style="gated",
            pos="rope",
            rope_theta=hf.get("rope_theta", 1e6),
            rope_local_base_freq=hf.get("rope_local_base_freq", 10000.0),
            rope_scaling_factor=factor,
            qk_norm=True,
            sliding_window=hf.get("sliding_window"),
            window_layers=window_layers,
            query_pre_attn_scalar=hf.get("query_pre_attn_scalar"),
            sandwich_norms=True,
            **common,
        )
    if gemma2:
        nh = hf["num_attention_heads"]
        lt = hf.get("layer_types")
        if lt is not None and any(
                (t == "sliding_attention") != (i % 2 == 0)
                for i, t in enumerate(lt)):
            raise ValueError(
                "gemma2 checkpoints with a non-alternating layer_types "
                f"pattern are not supported yet (got {lt[:6]}...)")
        common["tie_word_embeddings"] = hf.get("tie_word_embeddings", True)
        return ModelConfig(
            intermediate_size=hf["intermediate_size"],
            num_kv_heads=hf.get("num_key_value_heads", nh),
            head_dim=hf.get("head_dim") or hf["hidden_size"] // nh,
            norm="rmsnorm",
            norm_eps=hf.get("rms_norm_eps", 1e-6),
            norm_weight_offset=1.0,
            embed_scale_by_sqrt_dim=True,
            act=(hf.get("hidden_activation") or hf.get("hidden_act")
                 or "gelu_pytorch_tanh"),
            mlp_style="gated",
            pos="rope",
            rope_theta=hf.get("rope_theta", 10000.0),
            sliding_window=hf.get("sliding_window"),
            window_pattern="alternate",
            attn_logit_softcapping=hf.get("attn_logit_softcapping"),
            final_logit_softcapping=hf.get("final_logit_softcapping"),
            query_pre_attn_scalar=hf.get("query_pre_attn_scalar"),
            sandwich_norms=True,
            **common,
        )
    if gemma1:
        # Gemma: llama-shaped weights, but RMSNorm(1 + w), sqrt(hidden)
        # embedding scale, tanh-GELU MLP, tied embeddings, head_dim from
        # config (not hidden/heads)
        nh = hf["num_attention_heads"]
        common["tie_word_embeddings"] = hf.get("tie_word_embeddings", True)
        return ModelConfig(
            intermediate_size=hf["intermediate_size"],
            num_kv_heads=hf.get("num_key_value_heads", nh),
            head_dim=hf.get("head_dim") or hf["hidden_size"] // nh,
            norm="rmsnorm",
            norm_eps=hf.get("rms_norm_eps", 1e-6),
            norm_weight_offset=1.0,
            embed_scale_by_sqrt_dim=True,
            # hidden_activation can be PRESENT as null (GemmaConfig's
            # nullable default) — `or` through to the real fallbacks
            act=(hf.get("hidden_activation") or hf.get("hidden_act")
                 or "gelu_pytorch_tanh"),
            mlp_style="gated",
            pos="rope",
            rope_theta=hf.get("rope_theta", 10000.0),
            **common,
        )
    # Llama / Qwen2 / Qwen3 / Phi-3 all share the rotary+gated-MLP skeleton;
    # the Qwen3-MoE variant swaps the MLP for routed experts.
    nh = hf["num_attention_heads"]
    moe = {}
    if hf.get("num_experts"):
        # We build every layer as MoE; a checkpoint with interleaved dense
        # layers (mlp_only_layers / decoder_sparse_step) would fail at weight
        # load with missing mlp.experts.* keys or, worse, mis-serve.  Reject
        # loudly until per-layer dense/MoE selection is supported.
        if hf.get("mlp_only_layers"):
            raise ValueError(
                "Qwen3-MoE checkpoints with non-empty mlp_only_layers "
                f"(got {hf['mlp_only_layers']}) interleave dense layers, "
                "which this loader does not support yet")
        if hf.get("decoder_sparse_step", 1) != 1:
            raise ValueError(
                "Qwen3-MoE checkpoints with decoder_sparse_step != 1 "
                f"(got {hf['decoder_sparse_step']}) interleave dense layers, "
                "which this loader does not support yet")
        moe = dict(num_experts=hf["num_experts"],
                   num_experts_per_tok=hf.get("num_experts_per_tok", 2),
                   moe_intermediate_size=hf.get("moe_intermediate_size"),
                   norm_topk_prob=hf.get("norm_topk_prob", True))
    return ModelConfig(
        intermediate_size=hf["intermediate_size"],
        num_kv_heads=hf.get("num_key_value_heads", nh),
        head_dim=hf.get("head_dim") or hf["hidden_size"] // nh,
        norm="rmsnorm",
        norm_eps=hf.get("rms_norm_eps", 1e-6),
        act=hf.get("hidden_act", "silu"),
        mlp_style="gated",
        pos="rope",
        rope_theta=hf.get("rope_theta", 10000.0),
        partial_rotary_factor=hf.get("partial_rotary_factor", 1.0),
        qk_norm="qwen3" in family,
        attention_bias="qwen2" in family or hf.get("attention_bias", False),
        rope_llama3_scaling=_rope_scaling(hf),
        **_sliding_window(hf, family),
        **moe,
        **common,
    )


def _mellum_config(hf: dict, common: dict) -> ModelConfig:
    """Mellum 2 (``model_type`` ``mellum``): a Llama-style GQA decoder whose
    layers are of two kinds (``layer_types``: a sliding window with the
    plain rotary table, or the whole context with a YaRN table;
    ``rope_parameters`` keyed by kind) and whose MLPs are all routed
    experts (softmax router, top-k renormalised, no shared expert).  What
    this code does not implement rejects loudly."""
    kinds = hf.get("layer_types")
    if not kinds or len(kinds) != hf["num_hidden_layers"] or set(kinds) - {
            "sliding_attention", "full_attention"}:
        raise ValueError("mellum configs must carry layer_types, one of "
                         "sliding_attention / full_attention a layer; got "
                         f"{kinds!r}")
    mlp_kinds = hf.get("mlp_layer_types") or ["sparse"] * len(kinds)
    if set(mlp_kinds) != {"sparse"} or len(mlp_kinds) != len(kinds):
        raise ValueError("mellum with dense MLP layers is not supported "
                         f"(mlp_layer_types {mlp_kinds!r})")
    if hf.get("max_window_layers"):
        raise ValueError("mellum with max_window_layers "
                         f"{hf['max_window_layers']!r}: layer_types decides "
                         "the kinds here")
    if not hf.get("use_sliding_window", True) or not hf.get("sliding_window"):
        raise ValueError("mellum without a sliding window is not supported")
    rp = hf.get("rope_parameters") or {}
    full, local = rp.get("full_attention"), rp.get("sliding_attention")
    if not full or not local or full.get("rope_type") != "yarn" \
            or local.get("rope_type", "default") != "default":
        raise ValueError("mellum rope_parameters must give full_attention "
                         "a yarn table and sliding_attention the default "
                         f"one; got {rp!r}")
    from tpuserve.ops.rope import yarn_mscale
    stated = full.get("attention_factor")
    if stated is not None and abs(stated - yarn_mscale(full["factor"])) > 1e-9:
        raise ValueError(f"mellum attention_factor {stated!r} is not "
                         "0.1 ln(factor) + 1")
    if full.get("mscale") or full.get("mscale_all_dim") \
            or not full.get("truncate", True):
        raise ValueError(f"unsupported yarn parameters {full!r}")
    nh = hf["num_attention_heads"]
    theta = float(full["rope_theta"])
    local_theta = float(local.get("rope_theta", theta))
    return ModelConfig(
        intermediate_size=hf["intermediate_size"],
        num_kv_heads=hf.get("num_key_value_heads", nh),
        head_dim=hf.get("head_dim") or hf["hidden_size"] // nh,
        norm_eps=hf.get("rms_norm_eps", 1e-6),
        act=hf.get("hidden_act", "silu"),
        attention_bias=hf.get("attention_bias", False),
        rope_theta=theta,
        rope_local_base_freq=None if local_theta == theta else local_theta,
        rope_full_yarn=(full["factor"], full.get("beta_fast", 32),
                        full.get("beta_slow", 1),
                        full.get("original_max_position_embeddings",
                                 common["max_position_embeddings"])),
        sliding_window=hf["sliding_window"],
        window_layers=tuple(t == "sliding_attention" for t in kinds),
        num_experts=hf["num_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        norm_topk_prob=hf.get("norm_topk_prob", True),
        **common,
    )


def _exaone_moe_config(hf: dict, common: dict) -> ModelConfig:
    """K-EXAONE (``model_type`` ``exaone_moe``): a GQA decoder with a
    per-head q/k norm whose layers are of two kinds (``layer_types``: a
    sliding window with the plain rotary table, or the whole context with
    NO rotation) and whose MLPs, after ``first_k_dense_replace`` dense
    ones, are routed experts beside a shared one behind DeepSeek-V3's
    router (sigmoid scores, a selection bias, the top-k renormalised and
    scaled).  ``config.json`` states neither the q/k norm, nor which
    layers rotate, nor the selection bias: they are the family's
    convention (benchmark/configs/k-exaone-236b-ep8-l8.json ``assumed``).
    The multi-token-prediction layers (``num_nextn_predict_layers``) are
    no part of the next-token forward pass and are not built.  What this
    code does not implement rejects loudly."""
    layers = hf["num_hidden_layers"]
    kinds = hf.get("layer_types")
    if not kinds or len(kinds) != layers or set(kinds) - {
            "sliding_attention", "full_attention"}:
        raise ValueError("exaone_moe configs must carry layer_types, one of "
                         "sliding_attention / full_attention a layer; got "
                         f"{kinds!r}")
    window = hf.get("sliding_window")
    if not window:
        raise ValueError("exaone_moe without a sliding window is not "
                         "supported")
    windows = [window if t == "sliding_attention" else 0 for t in kinds]
    if hf.get("sliding_windows", windows) != windows:
        raise ValueError(f"exaone_moe sliding_windows "
                         f"{hf['sliding_windows']!r} disagree with "
                         "layer_types and sliding_window")
    if (hf.get("n_group") or 1) != 1 or (hf.get("topk_group") or 1) != 1:
        raise ValueError("exaone_moe with grouped routing (n_group "
                         f"{hf.get('n_group')!r}, topk_group "
                         f"{hf.get('topk_group')!r}) is not supported")
    dense = hf.get("first_k_dense_replace", 0)
    mlp_kinds = ["dense"] * dense + ["sparse"] * (layers - dense)
    if hf.get("mlp_layer_types", mlp_kinds) != mlp_kinds:
        raise ValueError("exaone_moe mlp_layer_types must be "
                         "first_k_dense_replace dense layers and then sparse "
                         f"ones; got {hf['mlp_layer_types']!r}")
    rp = hf.get("rope_parameters") or {}
    if rp.get("rope_type", "default") != "default":
        raise ValueError(f"unsupported exaone_moe rope_parameters {rp!r}")
    nh = hf["num_attention_heads"]
    cfg = ModelConfig(
        intermediate_size=hf["intermediate_size"],
        num_kv_heads=hf.get("num_key_value_heads", nh),
        head_dim=hf.get("head_dim") or hf["hidden_size"] // nh,
        norm_eps=hf.get("rms_norm_eps", 1e-5),
        act=hf.get("hidden_act", "silu"),
        attention_bias=hf.get("attention_bias", False),
        rope_theta=float(rp.get("rope_theta", hf.get("rope_theta", 1e6))),
        rope_windowed_only=True, qk_norm=True,
        sliding_window=window,
        window_layers=tuple(t == "sliding_attention" for t in kinds),
        num_experts=hf["num_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        norm_topk_prob=hf.get("norm_topk_prob", True),
        moe_scoring=hf.get("scoring_func", "sigmoid"),
        moe_router_bias=True,
        moe_routed_scaling=hf.get("routed_scaling_factor", 1.0),
        moe_shared_experts=hf.get("num_shared_experts") or 0,
        moe_first_k_dense=dense,
        **common,
    )
    pattern = hf.get("sliding_window_pattern", cfg.sliding_window_pattern)
    if pattern != cfg.sliding_window_pattern:
        raise ValueError(f"exaone_moe sliding_window_pattern {pattern!r}: "
                         f"layer_types repeat {cfg.sliding_window_pattern!r}")
    return cfg


def _pangu_ultra_moe_config(hf: dict, common: dict) -> ModelConfig:
    """openPangu-Ultra-MoE (``model_type`` ``pangu_ultra_moe``): latent
    attention at DeepSeek-V3's sizes (a query latent, one cached
    ``kv_lora_rank + qk_rope_head_dim`` vector a token, the plain rotary
    table over the rope features) under SANDWICH norms (``sandwich_norm``:
    a norm on each branch's output before its add, beside the two
    pre-norms), ``first_k_dense_replace`` dense layers and then routed
    experts beside shared ones.  ``config.json`` names no scoring
    function, expert groups or selection bias: the router is the sigmoid
    recipe its ``norm_topk_prob`` and ``routed_scaling_factor`` belong to,
    over all experts at once, with NO selection bias
    (benchmark/configs/openpangu-ultra-718b-ep16-l7.json ``assumed``).
    The multi-token-prediction layers (``num_nextn_predict_layers``) are
    no part of the next-token forward pass and are not built.  What this
    code does not implement rejects loudly."""
    if hf.get("rope_scaling"):
        raise ValueError("unsupported pangu_ultra_moe rope_scaling "
                         f"{hf['rope_scaling']!r}")
    for key in ("n_group", "topk_group"):
        if (hf.get(key) or 1) != 1:
            raise ValueError(f"pangu_ultra_moe with grouped routing "
                             f"({key} {hf[key]!r}) is not supported")
    if hf.get("scoring_func", "sigmoid") != "sigmoid" or hf.get(
            "topk_method", "greedy") not in ("greedy", "noaux_tc"):
        raise ValueError("unsupported pangu_ultra_moe router: scoring_func "
                         f"{hf.get('scoring_func')!r}, topk_method "
                         f"{hf.get('topk_method')!r}")
    return ModelConfig(
        intermediate_size=hf["intermediate_size"],
        num_kv_heads=hf["num_attention_heads"],
        head_dim=hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"],
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        norm_eps=hf.get("rms_norm_eps", 1e-5),
        act=hf.get("hidden_act", "silu"),
        attention_bias=hf.get("attention_bias", False),
        sandwich_norms=bool(hf.get("sandwich_norm", False)),
        mla_kv_lora_rank=hf["kv_lora_rank"],
        mla_q_lora_rank=hf.get("q_lora_rank"),
        mla_qk_rope_head_dim=hf["qk_rope_head_dim"],
        mla_v_head_dim=hf["v_head_dim"],
        mla_rope_interleave=hf.get("rope_interleave", True),
        num_experts=hf["n_routed_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        norm_topk_prob=hf.get("norm_topk_prob", True),
        moe_scoring="sigmoid",
        moe_router_bias=hf.get("topk_method") == "noaux_tc",
        moe_routed_scaling=hf.get("routed_scaling_factor", 1.0),
        moe_shared_experts=hf.get("n_shared_experts") or 0,
        moe_first_k_dense=hf.get("first_k_dense_replace", 0),
        **common,
    )


def _bailing_hybrid_config(hf: dict, common: dict) -> ModelConfig:
    """Ling-3.0-flash (``model_type`` ``bailing_hybrid``): a pre-norm
    decoder whose layers differ in their kind of mixer by a PERIOD
    (``layer_group_size`` p: layer i is a latent-attention layer where
    ``(i + 1) % p == 0``, every other a Kimi-delta linear-attention layer,
    arXiv:2510.26692: the gated delta rule with a decay for every key
    channel, ``lin_gate`` "channel"), ``first_k_dense_replace`` dense MLPs
    and then sigmoid-routed experts behind DeepSeek-V3's group-limited
    router (``n_group`` / ``topk_group``, a selection bias) beside a shared
    expert.  The latent layers are DeepSeek's without a query latent, with
    a q/k norm (``use_qk_norm``) and a sigmoid gate a head on their output
    (``gated_attention_proj_granularity_type`` head_wise).  ``head_dim``
    is the linear layers' head and the base of ``partial_rotary_factor``;
    the latent layers' q/k are ``qk_nope_head_dim + qk_rope_head_dim``
    wide (``mla_qk_head_dim``).  What the keys leave open is listed with
    its reason in benchmark/configs/ling-3.0-flash-vl-ep8-l12.json
    (``assumed``).  The vision tower of the VL checkpoint (of which
    config.json holds four token ids and no size), the multi-token-
    prediction layer and the clamped gated activation of the last layers
    (``expert_swiglu_limit_list``) are not built; every switch for a
    variant this code does not compute rejects loudly."""
    layers = hf["num_hidden_layers"]
    period = hf.get("layer_group_size")
    if not isinstance(period, int) or period < 2:
        raise ValueError("bailing_hybrid configs must carry layer_group_size "
                         "(layer i attends where (i + 1) % layer_group_size "
                         f"== 0); got {period!r}")
    nh = hf["num_attention_heads"]
    d = hf.get("head_dim") or hf["hidden_size"] // nh
    only = {"kda_safe_gate": True, "linear_silu": True, "group_norm_size": 1,
            "use_mla_nope": False, "use_nGPT": False, "value_norm": False,
            "scale_router_input": False, "up_proj_norm": False,
            "use_kda_lora": False, "use_bias": False, "use_qkv_bias": False,
            "rope_scaling": None, "q_lora_rank": None}
    for key, built in only.items():
        if hf.get(key, built) != built:
            raise ValueError(f"bailing_hybrid with {key} {hf[key]!r} is not "
                             f"supported ({built!r} is what is built)")
    if not hf.get("no_kda_lora", True):
        raise ValueError("bailing_hybrid with a low-rank decay projection "
                         "(no_kda_lora false) is not supported")
    if hf.get("num_kv_heads_for_linear_attn") not in (None, 0, nh) \
            or hf.get("num_key_value_heads", nh) != nh:
        raise ValueError(
            "bailing_hybrid with fewer key heads than query heads "
            f"(num_kv_heads_for_linear_attn "
            f"{hf.get('num_kv_heads_for_linear_attn')!r}, "
            f"num_key_value_heads {hf.get('num_key_value_heads')!r}) is not "
            "supported")
    bound = hf.get("kda_lower_bound")
    if not isinstance(bound, (int, float)) or not bound < 0:
        raise ValueError(f"bailing_hybrid kda_lower_bound {bound!r}: the safe "
                         "gate needs a negative bound")
    rope = hf["qk_rope_head_dim"]
    factor = hf.get("partial_rotary_factor", rope / d)
    if int(d * factor) != rope or hf.get("rotary_dim", rope) != rope:
        raise ValueError(
            f"bailing_hybrid partial_rotary_factor {factor!r} of head_dim "
            f"{d} (rotary_dim {hf.get('rotary_dim')!r}) is not "
            f"qk_rope_head_dim {rope}")
    granularity = hf.get("gated_attention_proj_granularity_type")
    if granularity not in (None, "head_wise"):
        raise ValueError("bailing_hybrid "
                         "gated_attention_proj_granularity_type "
                         f"{granularity!r} is not supported (head_wise is)")
    score = hf.get("score_function", hf.get("scoring_func", "sigmoid"))
    if score != "sigmoid" or hf.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError(f"unsupported bailing_hybrid router: score_function "
                         f"{score!r}, topk_method {hf.get('topk_method')!r}")
    ei = hf["moe_intermediate_size"]
    si = hf.get("moe_shared_expert_intermediate_size", ei) \
        * hf.get("num_shared_experts", 1)
    if si % ei:
        raise ValueError(f"bailing_hybrid shared expert of width {si} beside "
                         f"experts of {ei}")

    def limits(key):
        got = hf.get(key)
        if got is not None and len(got) != layers:
            raise ValueError(f"bailing_hybrid {key} states {len(got)} "
                             f"layers of {layers}")
        return None if got is None else tuple(got)

    cfg = ModelConfig(
        intermediate_size=hf["intermediate_size"],
        num_kv_heads=nh, head_dim=d,
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        partial_rotary_factor=factor,
        norm_eps=hf.get("rms_norm_eps", 1e-6),
        act=hf.get("hidden_act", "silu"),
        qk_norm=bool(hf.get("use_qk_norm", False)),
        attn_head_gate=granularity == "head_wise",
        mla_kv_lora_rank=hf["kv_lora_rank"], mla_q_lora_rank=None,
        mla_qk_rope_head_dim=rope, mla_v_head_dim=hf["v_head_dim"],
        mla_qk_head_dim=hf["qk_nope_head_dim"] + rope,
        mla_rope_interleave=hf.get("rope_interleave", True),
        linear_layers=tuple(bool((i + 1) % period) for i in range(layers)),
        lin_num_key_heads=nh, lin_num_value_heads=nh,
        lin_key_head_dim=d, lin_value_head_dim=d,
        lin_conv_kernel=hf.get("short_conv_kernel_size", 4),
        lin_gate="channel", lin_gate_lower_bound=float(bound),
        num_experts=hf["num_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=ei,
        norm_topk_prob=hf.get("norm_topk_prob", True),
        moe_scoring="sigmoid",
        moe_router_bias=bool(hf.get("moe_router_enable_expert_bias", False)),
        moe_n_group=hf.get("n_group") or 1,
        moe_topk_group=hf.get("topk_group") or 1,
        moe_routed_scaling=hf.get("routed_scaling_factor", 1.0),
        moe_shared_experts=si // ei,
        moe_first_k_dense=hf.get("first_k_dense_replace", 0),
        moe_swiglu_limits=limits("expert_swiglu_limit_list"),
        moe_shared_swiglu_limits=limits("share_expert_swiglu_limit_list"),
        **common,
    )
    cfg.require_built()
    return cfg


def _olmo_hybrid_config(hf: dict, common: dict) -> ModelConfig:
    """Olmo-Hybrid (``model_type`` ``olmo_hybrid``): a dense decoder whose
    layers differ in their kind of mixer (``layer_types``): gated
    delta-rule linear attention (arXiv:2412.06464, the step size doubled
    under ``linear_allow_neg_eigval``, arXiv:2411.12537) or full causal
    attention.  ``config.json`` sizes both; what it leaves open is set
    HERE, one statement a point, and listed with its reason in
    benchmark/configs/olmo-hybrid-7b-l16.json (``assumed``): the OLMo 2 /
    3 family's norm on each branch's output and q/k norm over the whole
    projection, no rotation at all (``rope_parameters.rope_theta`` is
    null: the recurrent layers carry order), a head of hidden / heads.
    What this code does not implement rejects loudly."""
    kinds = hf.get("layer_types")
    if not kinds or len(kinds) != hf["num_hidden_layers"] or set(kinds) - {
            "linear_attention", "full_attention"}:
        raise ValueError("olmo_hybrid configs must carry layer_types, one "
                         "of linear_attention / full_attention a layer; got "
                         f"{kinds!r}")
    rp = hf.get("rope_parameters") or {}
    if rp.get("rope_theta") is not None or hf.get("rope_theta") is not None \
            or hf.get("rope_scaling"):
        raise ValueError("olmo_hybrid with a rotary base is not supported "
                         f"(rope_parameters {rp!r}): its attention layers "
                         "are built unrotated")
    if hf.get("sliding_window"):
        raise ValueError("olmo_hybrid with a sliding window is not "
                         "supported")
    nh = hf["num_attention_heads"]
    return ModelConfig(
        intermediate_size=hf["intermediate_size"],
        num_kv_heads=hf.get("num_key_value_heads", nh),
        head_dim=hf.get("head_dim") or hf["hidden_size"] // nh,
        norm_eps=hf.get("rms_norm_eps", 1e-6),
        act=hf.get("hidden_act", "silu"),
        attention_bias=hf.get("attention_bias", False),
        pos="none",
        norm_placement="post",
        qk_norm=True, qk_norm_whole=True,
        linear_layers=tuple(t == "linear_attention" for t in kinds),
        lin_num_key_heads=hf["linear_num_key_heads"],
        lin_num_value_heads=hf["linear_num_value_heads"],
        lin_key_head_dim=hf["linear_key_head_dim"],
        lin_value_head_dim=hf["linear_value_head_dim"],
        lin_conv_kernel=hf.get("linear_conv_kernel_dim", 4),
        lin_allow_neg_eigval=hf.get("linear_allow_neg_eigval", False),
        **common,
    )


def _falcon_h1_config(hf: dict, common: dict) -> ModelConfig:
    """Falcon-H1 (HF ``modeling_falcon_h1``): attention heads and a Mamba-2
    mixer side by side in every layer.  What this code does not implement
    rejects loudly: a subset of attention layers, rope scaling, a mixer
    without the MLP."""
    if hf.get("attn_layer_indices") is not None:
        raise ValueError("falcon_h1 with attn_layer_indices (attention in "
                         "some layers only) is not supported")
    if hf.get("rope_scaling"):
        raise ValueError(f"unsupported rope_scaling {hf['rope_scaling']!r} "
                         "for falcon_h1")
    if not hf.get("mamba_use_mlp", True):
        raise ValueError("falcon_h1 with mamba_use_mlp false is not "
                         "supported")
    nh = hf["num_attention_heads"]
    d_ssm = hf.get("mamba_d_ssm") or hf["mamba_expand"] * hf["hidden_size"]
    return ModelConfig(
        intermediate_size=hf["intermediate_size"],
        num_kv_heads=hf.get("num_key_value_heads", nh),
        head_dim=hf.get("head_dim") or hf["hidden_size"] // nh,
        norm_eps=hf.get("rms_norm_eps", 1e-5),
        act=hf.get("hidden_act", "silu"),
        rope_theta=hf.get("rope_theta", 100000.0),
        attention_bias=hf.get("attention_bias", False),
        mlp_bias=hf.get("mlp_bias", False),
        mamba_d_ssm=d_ssm,
        mamba_n_heads=hf["mamba_n_heads"],
        mamba_d_head=hf["mamba_d_head"],
        mamba_d_state=hf["mamba_d_state"],
        mamba_n_groups=hf.get("mamba_n_groups", 1),
        mamba_d_conv=hf.get("mamba_d_conv", 4),
        mamba_chunk_size=hf.get("mamba_chunk_size", 128),
        mamba_conv_bias=hf.get("mamba_conv_bias", True),
        mamba_proj_bias=hf.get("mamba_proj_bias", False),
        mamba_out_bias=hf.get("projectors_bias", False),
        mamba_rms_norm=hf.get("mamba_rms_norm", True),
        mamba_norm_before_gate=hf.get("mamba_norm_before_gate", False),
        embedding_multiplier=hf.get("embedding_multiplier", 1.0),
        lm_head_multiplier=hf.get("lm_head_multiplier", 1.0),
        key_multiplier=hf.get("key_multiplier", 1.0),
        attention_in_multiplier=hf.get("attention_in_multiplier", 1.0),
        attention_out_multiplier=hf.get("attention_out_multiplier", 1.0),
        ssm_in_multiplier=hf.get("ssm_in_multiplier", 1.0),
        ssm_out_multiplier=hf.get("ssm_out_multiplier", 1.0),
        mlp_multipliers=tuple(hf.get("mlp_multipliers") or (1.0, 1.0)),
        ssm_multipliers=tuple(hf.get("ssm_multipliers") or (1.0,) * 5),
        **common,
    )


def _rope_scaling(hf: dict):
    """Llama-3.1-style rope_scaling for the llama-family path.  Ignoring
    an unknown scheme would SILENTLY mis-rotate long contexts, so
    anything unrecognized rejects loudly."""
    rs = hf.get("rope_scaling")
    if not rs:
        return None
    rt = rs.get("rope_type", rs.get("type"))
    if rt == "llama3":
        return (float(rs["factor"]), float(rs["low_freq_factor"]),
                float(rs["high_freq_factor"]),
                float(rs["original_max_position_embeddings"]))
    if rt == "default":
        return None
    raise ValueError(f"unsupported rope_scaling {rs!r} for this family "
                     "(llama3 and default are)")


def _sliding_window(hf: dict, family: str) -> dict:
    """Mistral applies its sliding_window whenever set; Qwen2/Qwen3 carry
    the field but gate it behind use_sliding_window (default off) and
    max_window_layers.  Honoring a disabled window would corrupt long-
    context serving for every Qwen checkpoint.

    HF max_window_layers semantics: the FIRST that-many layers use full
    attention, the rest the window — mapped onto
    ``full_attention_first_layers``."""
    sw = hf.get("sliding_window")
    if sw is None:
        return {}
    if not hf.get("use_sliding_window", "mistral" in family):
        return {}
    mwl = hf.get("max_window_layers")
    nl = hf.get("num_hidden_layers", 0)
    if mwl is None:
        if "mistral" in family:
            mwl = 0                       # mistral windows every layer
        else:
            # HF Qwen2Config defaults max_window_layers=28 INDEPENDENT of
            # the layer count; guessing here risks silently windowing
            # layers transformers runs full — demand the field instead
            raise ValueError(
                "use_sliding_window is enabled but max_window_layers is "
                "missing; add it to the config (HF defaults it per-class, "
                "not per-model)")
    if nl and mwl >= nl:
        return {}                         # window never applies
    return {"sliding_window": int(sw),
            "full_attention_first_layers": int(mwl)}


def _first(x):
    if isinstance(x, (list, tuple)):
        return x[0] if x else None
    return x


# --- Presets for the tracked configs (BASELINE.json "configs") -------------

register_model_config(ModelConfig(
    name="Qwen/Qwen3-0.6B",
    vocab_size=151936, hidden_size=1024, intermediate_size=3072,
    num_layers=28, num_heads=16, num_kv_heads=8, head_dim=128,
    max_position_embeddings=40960, rope_theta=1e6, norm_eps=1e-6,
    qk_norm=True, tie_word_embeddings=True,
    bos_token_id=151643, eos_token_id=151645,
), "qwen3-0.6b")

register_model_config(ModelConfig(
    name="Qwen/Qwen2-72B-Instruct",
    vocab_size=152064, hidden_size=8192, intermediate_size=29568,
    num_layers=80, num_heads=64, num_kv_heads=8, head_dim=128,
    max_position_embeddings=32768, rope_theta=1e6, norm_eps=1e-6,
    attention_bias=True, tie_word_embeddings=False,
    bos_token_id=151643, eos_token_id=151645,
), "qwen2-72b")

register_model_config(ModelConfig(
    name="meta-llama/Meta-Llama-3-8B-Instruct",
    vocab_size=128256, hidden_size=4096, intermediate_size=14336,
    num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
    max_position_embeddings=8192, rope_theta=500000.0, norm_eps=1e-5,
    tie_word_embeddings=False,
    bos_token_id=128000, eos_token_id=128009,
), "llama3-8b")

register_model_config(ModelConfig(
    name="meta-llama/Llama-3.1-8B-Instruct",
    vocab_size=128256, hidden_size=4096, intermediate_size=14336,
    num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
    max_position_embeddings=131072, rope_theta=500000.0, norm_eps=1e-5,
    rope_llama3_scaling=(8.0, 1.0, 4.0, 8192.0),
    tie_word_embeddings=False,
    bos_token_id=128000, eos_token_id=128009,
), "llama31-8b")

register_model_config(ModelConfig(
    name="microsoft/Phi-3-mini-4k-instruct",
    vocab_size=32064, hidden_size=3072, intermediate_size=8192,
    num_layers=32, num_heads=32, num_kv_heads=32, head_dim=96,
    max_position_embeddings=4096, rope_theta=10000.0, norm_eps=1e-5,
    tie_word_embeddings=False,
    bos_token_id=1, eos_token_id=32000,
), "phi3-mini")

register_model_config(ModelConfig(
    name="facebook/opt-1.3b",
    vocab_size=50272, hidden_size=2048, intermediate_size=8192,
    num_layers=24, num_heads=32, num_kv_heads=32, head_dim=64,
    max_position_embeddings=2048, norm="layernorm", norm_eps=1e-5,
    act="relu", mlp_style="mlp", pos="learned", learned_pos_offset=2,
    attention_bias=True, mlp_bias=True, tie_word_embeddings=True,
    bos_token_id=2, eos_token_id=2,
), "opt-1.3b")

register_model_config(ModelConfig(
    name="mistralai/Mistral-7B-Instruct-v0.1",
    vocab_size=32000, hidden_size=4096, intermediate_size=14336,
    num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
    max_position_embeddings=32768, rope_theta=10000.0, norm_eps=1e-5,
    sliding_window=4096, tie_word_embeddings=False,
    bos_token_id=1, eos_token_id=2,
), "mistral-7b")

register_model_config(ModelConfig(
    name="google/gemma-3-4b-text",
    vocab_size=262208, hidden_size=2560, intermediate_size=10240,
    num_layers=34, num_heads=8, num_kv_heads=4, head_dim=256,
    max_position_embeddings=131072, rope_theta=1_000_000.0,
    rope_local_base_freq=10000.0, rope_scaling_factor=8.0,
    norm_eps=1e-6, norm_weight_offset=1.0, embed_scale_by_sqrt_dim=True,
    act="gelu_pytorch_tanh", tie_word_embeddings=True, qk_norm=True,
    sliding_window=1024,
    window_layers=tuple(i % 6 != 5 for i in range(34)),   # 5 local : 1 global
    query_pre_attn_scalar=256, sandwich_norms=True,
    bos_token_id=2, eos_token_id=1,
), "gemma3-4b")

register_model_config(ModelConfig(
    name="google/gemma-2-2b",
    vocab_size=256000, hidden_size=2304, intermediate_size=9216,
    num_layers=26, num_heads=8, num_kv_heads=4, head_dim=256,
    max_position_embeddings=8192, rope_theta=10000.0, norm_eps=1e-6,
    norm_weight_offset=1.0, embed_scale_by_sqrt_dim=True,
    act="gelu_pytorch_tanh", tie_word_embeddings=True,
    sliding_window=4096, window_pattern="alternate",
    attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
    query_pre_attn_scalar=256, sandwich_norms=True,
    bos_token_id=2, eos_token_id=1,
), "gemma2-2b")

register_model_config(ModelConfig(
    name="google/gemma-2b",
    vocab_size=256000, hidden_size=2048, intermediate_size=16384,
    num_layers=18, num_heads=8, num_kv_heads=1, head_dim=256,
    max_position_embeddings=8192, rope_theta=10000.0, norm_eps=1e-6,
    norm_weight_offset=1.0, embed_scale_by_sqrt_dim=True,
    act="gelu_pytorch_tanh", tie_word_embeddings=True,
    bos_token_id=2, eos_token_id=1,
), "gemma-2b")

# Mixture-of-experts family (Qwen3-MoE): routed experts replace the dense
# MLP; serves with expert-parallel sharding over the mesh 'ep' axis.
register_model_config(ModelConfig(
    name="Qwen/Qwen3-30B-A3B",
    vocab_size=151936, hidden_size=2048, intermediate_size=6144,
    num_layers=48, num_heads=32, num_kv_heads=4, head_dim=128,
    max_position_embeddings=40960, rope_theta=1e6, norm_eps=1e-6,
    qk_norm=True, tie_word_embeddings=False,
    num_experts=128, num_experts_per_tok=8, moe_intermediate_size=768,
    bos_token_id=151643, eos_token_id=151645,
), "qwen3-30b-a3b")

# DeepSeek family (MLA + DeepSeek-MoE).  MLA is the TPU-first long-context
# cache design: one 576-wide latent per token instead of per-head K/V.
register_model_config(ModelConfig(
    name="deepseek-ai/DeepSeek-V2-Lite",
    vocab_size=102400, hidden_size=2048, intermediate_size=10944,
    num_layers=27, num_heads=16, num_kv_heads=16, head_dim=192,
    max_position_embeddings=163840, rope_theta=10000.0,
    rope_yarn=(40.0, 32, 1, 0.707, 0.707, 4096),
    norm_eps=1e-6, tie_word_embeddings=False,
    mla_kv_lora_rank=512, mla_q_lora_rank=None,
    mla_qk_rope_head_dim=64, mla_v_head_dim=128,
    num_experts=64, num_experts_per_tok=6, moe_intermediate_size=1408,
    norm_topk_prob=False, moe_scoring="softmax", moe_routed_scaling=1.0,
    moe_shared_experts=2, moe_first_k_dense=1,
    bos_token_id=100000, eos_token_id=100001,
), "deepseek-v2-lite")

register_model_config(ModelConfig(
    name="deepseek-ai/DeepSeek-V3",
    vocab_size=129280, hidden_size=7168, intermediate_size=18432,
    num_layers=61, num_heads=128, num_kv_heads=128, head_dim=192,
    max_position_embeddings=163840, rope_theta=10000.0,
    rope_yarn=(40.0, 32, 1, 1.0, 1.0, 4096),
    norm_eps=1e-6, tie_word_embeddings=False,
    mla_kv_lora_rank=512, mla_q_lora_rank=1536,
    mla_qk_rope_head_dim=64, mla_v_head_dim=128,
    num_experts=256, num_experts_per_tok=8, moe_intermediate_size=2048,
    norm_topk_prob=True, moe_scoring="sigmoid", moe_router_bias=True,
    moe_n_group=8, moe_topk_group=4, moe_routed_scaling=2.5,
    moe_shared_experts=1, moe_first_k_dense=3,
    bos_token_id=0, eos_token_id=1,
), "deepseek-v3", "deepseek-r1")

# Falcon-H1 (hybrid): attention heads and Mamba-2 state-space heads side
# by side in every layer, under fixed muP multipliers.  The numbers are
# config.json's; 33.6 B parameters, so one chip serves a cut of the depth.
register_model_config(ModelConfig(
    name="tiiuae/Falcon-H1-34B-Instruct",
    vocab_size=261120, hidden_size=5120, intermediate_size=21504,
    num_layers=72, num_heads=20, num_kv_heads=4, head_dim=128,
    max_position_embeddings=262144, rope_theta=1e11, norm_eps=1e-5,
    tie_word_embeddings=False,
    mamba_d_ssm=4096, mamba_n_heads=32, mamba_d_head=128,
    mamba_d_state=256, mamba_n_groups=2, mamba_d_conv=4,
    mamba_chunk_size=128,
    embedding_multiplier=5.656854249492381,
    lm_head_multiplier=0.0078125,
    key_multiplier=0.011048543456039804,
    attention_in_multiplier=1.0,
    attention_out_multiplier=0.0375,
    ssm_in_multiplier=0.25,
    ssm_out_multiplier=0.08838834764831845,
    mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
    ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738),
    bos_token_id=1, eos_token_id=11,
), "falcon-h1-34b")

# Mellum 2 (JetBrains): a GQA decoder whose layers are of two kinds, three
# with a 1,024-token window and the plain rotary table to each one that
# attends the whole context under a YaRN table, and whose MLPs are all 64
# routed experts of width 896, eight a token.  The numbers are
# config.json's; 12.15 B parameters, so one chip serves a cut of the
# depth.  No q/k norm (no key states one); the MTP head is left out.
register_model_config(ModelConfig(
    name="JetBrains/Mellum2-12B-A2.5B-Instruct",
    vocab_size=98304, hidden_size=2304, intermediate_size=7168,
    num_layers=28, num_heads=32, num_kv_heads=4, head_dim=128,
    max_position_embeddings=131072, rope_theta=500000.0, norm_eps=1e-6,
    tie_word_embeddings=False,
    sliding_window=1024,
    window_layers=tuple(i % 4 != 3 for i in range(28)),   # S S S F
    rope_full_yarn=(16, 32, 1, 8192),
    num_experts=64, num_experts_per_tok=8, moe_intermediate_size=896,
    norm_topk_prob=True,
), "mellum2-12b")

# K-EXAONE-236B-A23B (LG AI Research): 48 layers of 64 query heads on 8 KV
# heads with a per-head q/k norm, three with a 128-token window and the
# plain rotary table to each one that attends the whole context unrotated;
# one dense layer of width 18,432, then 128 routed experts of width 2,048,
# eight a token by sigmoid scores, beside one shared expert.  The numbers
# are config.json's; what it leaves open is in
# benchmark/configs/k-exaone-236b-ep8-l8.json (``assumed``).  236 B
# parameters: a chip serves its share of a cut of the depth
# (``moe_experts_held``).  The multi-token-prediction layer is not built.
register_model_config(ModelConfig(
    name="LGAI-EXAONE/K-EXAONE-236B-A23B",
    vocab_size=153600, hidden_size=6144, intermediate_size=18432,
    num_layers=48, num_heads=64, num_kv_heads=8, head_dim=128,
    max_position_embeddings=262144, rope_theta=1e6, norm_eps=1e-5,
    tie_word_embeddings=False, qk_norm=True,
    sliding_window=128,
    window_layers=tuple(i % 4 != 3 for i in range(48)),   # L L L G
    rope_windowed_only=True,
    num_experts=128, num_experts_per_tok=8, moe_intermediate_size=2048,
    norm_topk_prob=True, moe_scoring="sigmoid", moe_router_bias=True,
    moe_routed_scaling=2.5, moe_shared_experts=1, moe_first_k_dense=1,
), "k-exaone-236b")

# openPangu-Ultra-MoE-718B: 61 layers of hidden 7,680 under sandwich norms;
# latent attention with 128 heads (a query latent of 1,536, one cached
# vector of 512 + 64 a token, 128 + 64 wide keys, values of 128, the plain
# rotary table at theta 25.6e6); three dense layers of width 18,432, then
# 256 routed experts of width 2,048, eight a token by sigmoid scores over
# all of them at once with no selection bias, renormalised and scaled 2.5,
# beside one shared expert.  The numbers are config.json's; what it leaves
# open is in benchmark/configs/openpangu-ultra-718b-ep16-l7.json
# (``assumed``).  718 B parameters: a chip serves its share of a cut of the
# depth (``moe_experts_held``).  The multi-token-prediction layer is not
# built.
register_model_config(ModelConfig(
    name="FreedomIntelligence/openPangu-Ultra-MoE-718B",
    vocab_size=153600, hidden_size=7680, intermediate_size=18432,
    num_layers=61, num_heads=128, num_kv_heads=128, head_dim=192,
    max_position_embeddings=131072, rope_theta=25600000.0, norm_eps=1e-5,
    tie_word_embeddings=False, sandwich_norms=True,
    mla_kv_lora_rank=512, mla_q_lora_rank=1536,
    mla_qk_rope_head_dim=64, mla_v_head_dim=128,
    num_experts=256, num_experts_per_tok=8, moe_intermediate_size=2048,
    norm_topk_prob=True, moe_scoring="sigmoid", moe_routed_scaling=2.5,
    moe_shared_experts=1, moe_first_k_dense=3,
), "openpangu-ultra-718b")

# Olmo-Hybrid-7B (Ai2): 32 layers of hidden 3,840, three gated delta-rule
# linear-attention layers (30 heads, keys of 96, values of 192, a causal
# convolution of 4 in front) to each full-attention layer (30 heads of
# 128, unrotated, q/k norm over the whole projection), norms on each
# branch's output, a gated SiLU MLP of 11,008, untied 100,352 vocabulary.
# The numbers are config.json's; what it leaves open is in
# benchmark/configs/olmo-hybrid-7b-l16.json (``assumed``).  7.43 B
# parameters: one chip serves a cut of the depth.
register_model_config(ModelConfig(
    name="allenai/Olmo-Hybrid-7B",
    vocab_size=100352, hidden_size=3840, intermediate_size=11008,
    num_layers=32, num_heads=30, num_kv_heads=30, head_dim=128,
    max_position_embeddings=65536, norm_eps=1e-6,
    tie_word_embeddings=False, pos="none", norm_placement="post",
    qk_norm=True, qk_norm_whole=True,
    linear_layers=tuple(i % 4 != 3 for i in range(32)),    # L L L F
    lin_num_key_heads=30, lin_num_value_heads=30, lin_key_head_dim=96,
    lin_value_head_dim=192, lin_conv_kernel=4, lin_allow_neg_eigval=True,
), "olmo-hybrid-7b")

# Ling-3.0-flash-VL's language model (inclusionAI; ``bailing_hybrid``): 42
# pre-norm layers of hidden 2,560 in periods of six, five Kimi-delta
# linear-attention layers (32 heads, keys and values of 128, a causal
# convolution of 4 in front, a decay for every key channel bounded below
# at -5) to each latent-attention layer (32 heads, one cached vector of
# 512 + 64 a token, 128 + 64 wide keys, values of 128, the plain rotary
# table at theta 6e6, a q/k norm, a sigmoid gate a head on the output);
# two dense layers of width 6,144, then 512 routed experts of width 768,
# eight a token by sigmoid scores with a selection bias inside the 4 best
# of 8 groups, renormalised and scaled 2.5, beside one shared expert.  The
# numbers are config.json's; what it leaves open is in
# benchmark/configs/ling-3.0-flash-vl-ep8-l12.json (``assumed``).  124 B
# parameters: a chip serves its share of a cut of the depth
# (``moe_experts_held``).  The vision tower, the multi-token-prediction
# layer and the last layers' clamped activation (require_built) are not
# built.
register_model_config(ModelConfig(
    name="inclusionAI/Ling-3.0-flash-VL",
    vocab_size=157184, hidden_size=2560, intermediate_size=6144,
    num_layers=42, num_heads=32, num_kv_heads=32, head_dim=128,
    max_position_embeddings=131072, rope_theta=6000000.0, norm_eps=1e-6,
    partial_rotary_factor=0.5, tie_word_embeddings=False,
    qk_norm=True, attn_head_gate=True,
    mla_kv_lora_rank=512, mla_qk_rope_head_dim=64, mla_v_head_dim=128,
    mla_qk_head_dim=192,
    linear_layers=tuple(bool((i + 1) % 6) for i in range(42)),
    lin_num_key_heads=32, lin_num_value_heads=32, lin_key_head_dim=128,
    lin_value_head_dim=128, lin_conv_kernel=4,
    lin_gate="channel", lin_gate_lower_bound=-5.0,
    num_experts=512, num_experts_per_tok=8, moe_intermediate_size=768,
    norm_topk_prob=True, moe_scoring="sigmoid", moe_router_bias=True,
    moe_n_group=8, moe_topk_group=4, moe_routed_scaling=2.5,
    moe_shared_experts=1, moe_first_k_dense=2,
    moe_swiglu_limits=(0,) * 35 + (4,) * 7,
    moe_shared_swiglu_limits=(0,) * 34 + (5,) * 6 + (7,) * 2,
), "ling-3.0-flash-vl")

# Tiny configs for tests / CPU smoke (one per architectural family).
register_model_config(ModelConfig(
    name="tiny-qwen3",
    vocab_size=512, hidden_size=64, intermediate_size=128,
    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
    max_position_embeddings=512, rope_theta=1e6,
    qk_norm=True, tie_word_embeddings=True, eos_token_id=1,
))

# Falcon-H1 in small: 10 query heads on 2 KV heads (the family's group of
# five), two B/C groups, a scan chunk of 8 and every multiplier off 1.
# float32 like tiny-mistral: its tests compare tokens across routes.
register_model_config(ModelConfig(
    name="tiny-falcon-h1",
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_layers=2, num_heads=10, num_kv_heads=2, head_dim=16,
    max_position_embeddings=512, rope_theta=1e6, norm_eps=1e-5,
    tie_word_embeddings=False, eos_token_id=1, dtype="float32",
    mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16,
    mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=8,
    embedding_multiplier=4.0, lm_head_multiplier=0.125,
    key_multiplier=0.25, attention_in_multiplier=0.8,
    attention_out_multiplier=0.3, ssm_in_multiplier=0.5,
    ssm_out_multiplier=0.4, mlp_multipliers=(0.6, 0.2),
    ssm_multipliers=(0.7, 0.5, 0.35, 0.9, 0.6),
))

# Mellum 2 in small: two periods of S S S F, a window of 16, YaRN factor 4
# over an original 32 on the full layers, 8 experts and 2 a token, 8
# query heads on 2 KV heads.  float32 like tiny-mistral.
register_model_config(ModelConfig(
    name="tiny-mellum2",
    vocab_size=256, hidden_size=64, intermediate_size=256,
    num_layers=8, num_heads=8, num_kv_heads=2, head_dim=16,
    max_position_embeddings=512, rope_theta=10000.0, norm_eps=1e-6,
    tie_word_embeddings=False, eos_token_id=1, dtype="float32",
    sliding_window=16, window_layers=tuple(i % 4 != 3 for i in range(8)),
    rope_full_yarn=(4, 32, 1, 32),
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
    norm_topk_prob=True,
))

# K-EXAONE in small: two periods of L L L G with a window of 8 and no
# rotation on the G layers, 16 query heads on 2 KV heads (the family's
# group of eight) under a q/k norm, a dense layer first, then 32 experts,
# 4 a token by sigmoid scores scaled 2.5, beside a shared one.  Every
# expert held; a test takes a share with dataclasses.replace.  float32
# like tiny-mistral.
register_model_config(ModelConfig(
    name="tiny-k-exaone",
    vocab_size=256, hidden_size=64, intermediate_size=192,
    num_layers=8, num_heads=16, num_kv_heads=2, head_dim=16,
    max_position_embeddings=512, rope_theta=1e6, norm_eps=1e-5,
    tie_word_embeddings=False, eos_token_id=1, dtype="float32",
    qk_norm=True, sliding_window=8,
    window_layers=tuple(i % 4 != 3 for i in range(8)),
    rope_windowed_only=True,
    num_experts=32, num_experts_per_tok=4, moe_intermediate_size=32,
    norm_topk_prob=True, moe_scoring="sigmoid", moe_router_bias=True,
    moe_routed_scaling=2.5, moe_shared_experts=1, moe_first_k_dense=1,
))

# openPangu-Ultra-MoE in small: latent attention with a query latent under
# sandwich norms, two dense layers, then 16 experts, 4 a token by sigmoid
# scores over all of them with no selection bias, scaled 2.5, beside a
# shared one.  The cached vector is 136 + 12 = 148 wide: NOT a multiple of
# a lane tile nor of a sublane tile, as 576 is not of 128, and stored as
# 256 (cache_head_dim), so a fault in the page's zero lanes shows on the
# CPU.  Every expert held; a test takes a share with dataclasses.replace.
# float32 like tiny-mistral.
register_model_config(ModelConfig(
    name="tiny-pangu",
    vocab_size=256, hidden_size=64, intermediate_size=160,
    num_layers=5, num_heads=8, num_kv_heads=8, head_dim=28,
    max_position_embeddings=512, rope_theta=25600000.0, norm_eps=1e-5,
    tie_word_embeddings=False, eos_token_id=1, dtype="float32",
    sandwich_norms=True,
    mla_kv_lora_rank=136, mla_q_lora_rank=40,
    mla_qk_rope_head_dim=12, mla_v_head_dim=16,
    num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
    norm_topk_prob=True, moe_scoring="sigmoid", moe_routed_scaling=2.5,
    moe_shared_experts=1, moe_first_k_dense=2,
))

# Olmo-Hybrid in small: two periods of L L L F, 6 linear heads with keys
# of 24 and values of 48 (NOT multiples of a lane tile or of each other's
# tile, so a padding fault shows on the CPU), a scan chunk of 8, 10
# attention heads of 16 (cached as 16: cache_kv_heads) unrotated under a
# whole-projection q/k norm, norms on each branch's output.  float32 like
# tiny-mistral.
register_model_config(ModelConfig(
    name="tiny-olmo-hybrid",
    vocab_size=256, hidden_size=96, intermediate_size=160,
    num_layers=8, num_heads=10, num_kv_heads=10, head_dim=16,
    max_position_embeddings=512, norm_eps=1e-6,
    tie_word_embeddings=False, eos_token_id=1, dtype="float32",
    pos="none", norm_placement="post", qk_norm=True, qk_norm_whole=True,
    linear_layers=tuple(i % 4 != 3 for i in range(8)),
    lin_num_key_heads=6, lin_num_value_heads=6, lin_key_head_dim=24,
    lin_value_head_dim=48, lin_conv_kernel=4, lin_allow_neg_eigval=True,
    lin_chunk_size=8,
))

# Ling-3.0-flash in small: two periods of K K A (Kimi-delta, Kimi-delta,
# latent attention), 4 heads of 16 (keys and values of the linear layers;
# NOT a lane tile, so the pool's slabs of two heads run), a scan chunk of
# 32 (two sub-blocks of 16), the cached vector 136 + 12 = 148 wide stored
# as 256 (tiny-pangu's, for the same reason), q/k 16 + 12 wide under a
# q/k norm and a gate a head; one dense layer, then 8 experts in 2 groups
# of which 1 survives, 2 a token by biased sigmoid scores, scaled 2.5,
# beside a shared one.  Every expert held; a test takes group 0 with
# dataclasses.replace.  float32 like tiny-mistral.
register_model_config(ModelConfig(
    name="tiny-ling-hybrid",
    vocab_size=256, hidden_size=64, intermediate_size=160,
    num_layers=6, num_heads=4, num_kv_heads=4, head_dim=16,
    max_position_embeddings=512, rope_theta=6000000.0, norm_eps=1e-6,
    partial_rotary_factor=0.75, tie_word_embeddings=False, eos_token_id=1,
    dtype="float32", qk_norm=True, attn_head_gate=True,
    mla_kv_lora_rank=136, mla_qk_rope_head_dim=12, mla_v_head_dim=16,
    mla_qk_head_dim=28,
    linear_layers=tuple(bool((i + 1) % 3) for i in range(6)),
    lin_num_key_heads=4, lin_num_value_heads=4, lin_key_head_dim=16,
    lin_value_head_dim=16, lin_conv_kernel=4, lin_chunk_size=32,
    lin_gate="channel", lin_gate_lower_bound=-5.0,
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
    norm_topk_prob=True, moe_scoring="sigmoid", moe_router_bias=True,
    moe_n_group=2, moe_topk_group=1, moe_routed_scaling=2.5,
    moe_shared_experts=1, moe_first_k_dense=1,
))

register_model_config(ModelConfig(
    name="tiny-moe",
    vocab_size=512, hidden_size=64, intermediate_size=128,
    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
    max_position_embeddings=512, rope_theta=1e6,
    qk_norm=True, tie_word_embeddings=True, eos_token_id=1,
    num_experts=4, num_experts_per_tok=2, moe_intermediate_size=32,
))

# MLA + V3-style MoE in one tiny config: q-lora, sigmoid+bias grouped
# routing, shared experts, first layer dense.
register_model_config(ModelConfig(
    name="tiny-deepseek",
    vocab_size=512, hidden_size=64, intermediate_size=128,
    num_layers=3, num_heads=4, num_kv_heads=4, head_dim=48,
    max_position_embeddings=512, rope_theta=10000.0,
    tie_word_embeddings=True, eos_token_id=1,
    mla_kv_lora_rank=32, mla_q_lora_rank=24,
    mla_qk_rope_head_dim=16, mla_v_head_dim=32,
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
    moe_scoring="sigmoid", moe_router_bias=True,
    moe_n_group=2, moe_topk_group=1, moe_routed_scaling=1.5,
    moe_shared_experts=1, moe_first_k_dense=1,
))

register_model_config(ModelConfig(
    name="tiny-mistral",
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
    max_position_embeddings=512, sliding_window=8,
    tie_word_embeddings=False, eos_token_id=1,
    # float32: the windowed tests assert token equality ACROSS impls
    # (reference/pallas/chunked/spec/disagg), and random-init logit gaps
    # (~4e-3) sit below bf16 rounding — bf16 argmax is path-sensitive
    dtype="float32",
))

register_model_config(ModelConfig(
    name="tiny-gemma3",
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_layers=6, num_heads=4, num_kv_heads=2, head_dim=24,
    max_position_embeddings=512, norm_weight_offset=1.0,
    embed_scale_by_sqrt_dim=True, act="gelu_pytorch_tanh",
    tie_word_embeddings=True, qk_norm=True, eos_token_id=1,
    sliding_window=8, window_layers=tuple(i % 6 != 5 for i in range(6)),
    rope_theta=1_000_000.0, rope_local_base_freq=10000.0,
    rope_scaling_factor=8.0, query_pre_attn_scalar=24,
    sandwich_norms=True,
    # float32 for cross-impl token-equality tests (see tiny-mistral)
    dtype="float32",
))

register_model_config(ModelConfig(
    name="tiny-gemma2",
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=24,
    max_position_embeddings=512, norm_weight_offset=1.0,
    embed_scale_by_sqrt_dim=True, act="gelu_pytorch_tanh",
    tie_word_embeddings=True, eos_token_id=1,
    sliding_window=8, window_pattern="alternate",
    attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
    query_pre_attn_scalar=24, sandwich_norms=True,
    # float32 for the cross-impl token-equality tests (see tiny-mistral)
    dtype="float32",
))

register_model_config(ModelConfig(
    name="tiny-gemma",
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_layers=2, num_heads=4, num_kv_heads=2, head_dim=24,
    max_position_embeddings=512, norm_weight_offset=1.0,
    embed_scale_by_sqrt_dim=True, act="gelu_pytorch_tanh",
    tie_word_embeddings=True, eos_token_id=1,
))

register_model_config(ModelConfig(
    name="tiny-llama",
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_layers=2, num_heads=4, num_kv_heads=4, head_dim=16,
    max_position_embeddings=512, tie_word_embeddings=False, eos_token_id=1,
))

register_model_config(ModelConfig(
    name="tiny-opt",
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_layers=2, num_heads=4, num_kv_heads=4, head_dim=16,
    max_position_embeddings=512, norm="layernorm", norm_eps=1e-5,
    act="relu", mlp_style="mlp", pos="learned", learned_pos_offset=2,
    attention_bias=True, mlp_bias=True, tie_word_embeddings=True, eos_token_id=1,
))
