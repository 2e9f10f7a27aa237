"""Llama-family decoder in functional JAX (covers Llama 2/3, Mistral,
Qwen2, Qwen3 and TinyLlama-style variants via config knobs: GQA, RoPE
theta, qkv bias, per-head qk-norm, tied embeddings, optional logit
softcap).

Params are a plain pytree (nested dict of jnp arrays) so sharding is a
matching pytree of NamedShardings (parallel/sharding.py) and jit donation
works without framework indirection.  Two entry points:
- `prefill(params, tokens, valid_len, kv_pages, page_ids)` — causal
  self-attention over the prompt, writes KV pages, returns last-token logits.
- `decode_step(params, tokens, pos, kv_pages, page_table, seq_lens, active)`
  — one token per sequence against the paged cache.

Role parity: the model zoo the reference reaches through vLLM/HF
(python/huggingfaceserver); rebuilt TPU-first rather than wrapped.
"""

from __future__ import annotations

import json
import math
import os
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.kv_write import (
    append_token_kv,
    slice_runs,
    write_chunk_kv_batch,
    write_prompt_kv_batch,
    write_ragged_kv,
)
from ..ops.attention import (
    causal_prefill_attention,
    chunked_prefill_attention,
    paged_attention,
    ragged_paged_attention,
)
from ..ops.norms import rms_norm, rms_norm_plus_one
from ..ops.rotary import apply_rope
from .lora import lora_delta
from .quant import (
    LINEAR_KEYS,
    dense,
    embed_lookup,
    quantize_array_np,
    tied_head_matmul,
)

Params = Dict[str, Any]


def _map_hidden_act(act) -> str:
    """HF activation name -> ours.  Loud on anything unimplemented: a
    silent silu substitution (e.g. for exact 'gelu') would produce wrong
    logits with no signal."""
    if act in (None, "silu", "swish"):
        return "silu"
    if act in ("gelu_pytorch_tanh", "gelu_tanh"):
        return "gelu_tanh"
    raise ValueError(f"unsupported hidden_act {act!r}")


#: what a layer's mixer can be, and what state it writes.  In a hybrid
#: table (models/hybrid.py) `attention`, `window_attention` and
#: `cross_attention` are the first hybrid family's differential attention
#: (`LlamaConfig.diff_attention`: a pair of heads a cache row) and
#: `gqa_attention` is plain grouped-query attention over the pool's pages,
#: `gqa_window_attention` the same over the last `sliding_window` tokens,
#: kept in a ring; `kda` is a Kimi-delta linear-attention mixer (the gated
#: delta rule with a decay per channel, ops/delta.py); `short_conv` is a
#: gated short convolution (the LFM2 family's: a product, a few taps over
#: the hidden columns, a product) whose only state is the convolution's tail
MIXER_KINDS = ("attention", "window_attention", "cross_attention", "mamba",
               "gmu", "latent_attention", "mamba2", "ffn", "gqa_attention",
               "gqa_window_attention", "kda", "short_conv")
_WRITES = {"attention": "paged_kv", "window_attention": "window_kv",
           "cross_attention": "none", "mamba": "recurrent", "gmu": "none",
           "latent_attention": "latent_kv", "mamba2": "recurrent",
           "ffn": "none", "gqa_attention": "paged_kv",
           "gqa_window_attention": "window_kv", "kda": "recurrent",
           "short_conv": "recurrent"}
#: a recurrent slot that is a convolution's tail alone: no float32 state
NO_SCAN_STATE = (0,)
#: what a layer that writes `recurrent` keeps a lane, by its kind: (the
#: float32 state's shape, NO_SCAN_STATE where the mixer has none, the
#: columns its convolution runs over, the convolution's taps).
#: engine/kvcache.StateLayout sizes its slots by it
_RECURRENT_SLOT = {
    "mamba": lambda c: ((c.mamba_d_inner, c.mamba_d_state),
                        c.mamba_d_inner, c.mamba_d_conv),
    "mamba2": lambda c: ((c.mamba_n_heads, c.mamba_head_dim, c.mamba_d_state),
                         c.mamba2_conv_dim, c.mamba_d_conv),
    "kda": lambda c: ((c.kda_n_heads, c.kda_head_dim, c.kda_head_dim),
                      c.kda_conv_dim, c.kda_d_conv),
    "short_conv": lambda c: (NO_SCAN_STATE, c.hidden_size, c.conv_taps),
}

#: model_type values LlamaConfig's family knobs describe
_LLAMA_MODEL_TYPES = (None, "llama", "mistral", "mixtral", "qwen2", "qwen3",
                      "gemma2", "ouro")
#: config.json keys of mixers those knobs cannot express
_FOREIGN_MIXER_KEYS = (
    "mb_per_layer", "ssm_cfg", "state_size", "conv_kernel", "mamba_d_state",
    "kv_lora_rank", "q_lora_rank", "layers_block_type", "attn_layer_indices",
    "full_attention_interval", "linear_num_value_heads", "n_routed_experts",
    "num_experts", "moe_intermediate_size",
    # a stack run several times a token: served as one pass it would give
    # wrong logits and no error
    "total_ut_steps")


@dataclass(frozen=True)
class LayerSpec:
    """One row of a model's per-layer table: the layer's mixer, the kind of
    per-lane state it writes (`none`, `paged_kv`: pages of the pool,
    `window_kv`: a ring bounded by the window, `recurrent`: a slot of
    convolution tail and scan state), and the layer whose state or hand-on
    it reads (itself where it reads its own).  The forward
    (models/hybrid.py), the cache manager (engine/kvcache.StateLayout),
    parallel/sharding.param_pspecs, the weight initialiser and the
    checkpoint loader all derive from these rows.  `ffn` is the layer's
    feed-forward: `dense` (one gated MLP), `experts` (a router over routed
    experts, models/moe.py) or `none`.  A model of ONE sublayer a layer
    (`LlamaConfig.one_sublayer`: `h += Mixer(norm(h))`, one norm, one
    residual) has rows whose feed-forward is `none` and rows of kind `ffn`,
    whose mixer IS the feed-forward."""

    kind: str
    writes: str
    reads: int
    ffn: str = "dense"


def phi4flash_mixer_kinds(n_layers: int, mb_per_layer: int) -> Tuple[str, ...]:
    """The SambaY decoder-hybrid-decoder (arXiv:2507.06607): a self-decoder
    of Mamba / window-attention pairs, one more Mamba layer whose scan
    output the cross-decoder's gated memory units reuse, one full-attention
    layer whose K/V its cross-attention layers reuse."""
    if n_layers % 4 or mb_per_layer != 2:
        raise ValueError(
            "phi4flash: num_hidden_layers must be a multiple of 4 and "
            f"mb_per_layer 2, got {n_layers} and {mb_per_layer}")
    half = n_layers // 2
    kinds = []
    for i in range(n_layers):
        if i < half:
            kinds.append("mamba" if i % 2 == 0 else "window_attention")
        elif i == half:
            kinds.append("mamba")
        elif i == half + 1:
            kinds.append("attention")
        else:
            kinds.append("gmu" if i % 2 == 0 else "cross_attention")
    return tuple(kinds)


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    head_dim: Optional[int] = None
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None  # HF rope_scaling (llama3/linear)
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 4096
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    # per-head RMSNorm on q/k before rope (Qwen3-family; a hybrid table's
    # `gqa_attention` rows likewise: the LFM2 family)
    qk_norm: bool = False
    # ---- Gemma-2 family knobs (all default to Llama behavior) ----
    hidden_act: str = "silu"  # or "gelu_tanh" (GeGLU)
    norm_plus_one: bool = False  # RMSNorm multiplies by (1 + w)
    embed_scale: bool = False  # inputs scaled by sqrt(hidden_size)
    sandwich_norms: bool = False  # post-attn + post-ffn norms per layer
    attn_logit_softcap: float = 0.0  # tanh cap on ATTENTION scores
    query_pre_attn_scalar: Optional[float] = None  # attn scale = qpas**-0.5
    sliding_window: int = 0  # >0: window on layers marked sliding
    # per-layer attention kind; None = all full attention.  Tuple of
    # "sliding_attention"|"full_attention" (hashable: configs close over
    # jitted programs)
    layer_types: Optional[Tuple[str, ...]] = None
    # final-logit tanh cap (pre-existing knob)
    logit_softcap: float = 0.0
    # Mixture-of-Experts (Mixtral-style): n_experts == 0 => dense MLP.
    # Experts shard over the `model` mesh axis (expert parallelism).
    n_experts: int = 0
    n_experts_per_tok: int = 2
    dtype: str = "bfloat16"
    # ---- hybrid families (models/hybrid.py); None / defaults = Llama ----
    # mixer kind per layer (MIXER_KINDS); None = every layer "attention"
    mixer_kinds: Optional[Tuple[str, ...]] = None
    norm_type: str = "rmsnorm"  # "layernorm": weight and bias, eps = rms_norm_eps
    norm_bias: bool = True  # False: a layernorm of a weight alone (Cohere)
    use_rope: bool = True
    # a hybrid table's plain grouped-query rows (models/hybrid.py): rotary
    # turns columns (2j, 2j+1) (`rope_gptj`), not (j, j + d/2); only the
    # window rows carry positions, the full rows none
    rope_interleaved: bool = False
    rope_window_rows_only: bool = False
    # the mixer and the feed-forward read ONE norm of h and meet in ONE
    # residual: h' = h + Mixer(u) + FFN(u), u = norm(h)
    parallel_block: bool = False
    attention_out_bias: bool = False
    # differential attention (arXiv:2410.05258): heads pair up (2j, 2j+1)
    diff_attention: bool = False
    # Mamba-1 sizes
    mamba_d_inner: int = 0
    mamba_d_state: int = 0
    mamba_d_conv: int = 0
    mamba_dt_rank: int = 0
    # Mamba-2 sizes (kind "mamba2"; d_inner = heads x head_dim, d_state and
    # d_conv as above): B and C are shared by groups of heads
    mamba_n_heads: int = 0
    mamba_head_dim: int = 0
    mamba_n_groups: int = 0
    # every layer is one sublayer (Nemotron-H): see LayerSpec
    one_sublayer: bool = False
    # Kimi-delta mixers (kind "kda", ops/delta.py): heads x head_dim columns
    # of q, k and v each, a convolution of kda_d_conv taps over all three, a
    # float32 state [heads, head_dim, head_dim] a lane; the decay's and the
    # output gate's projections pass through kda_rank columns
    kda_n_heads: int = 0
    kda_head_dim: int = 0
    kda_d_conv: int = 0
    kda_rank: int = 0
    kda_neg_eigval: bool = False  # beta in (0, 2): eigenvalues in (-1, 1)
    # `gqa_attention` rows multiply their attention's output by
    # sigmoid(u W_g) before the output projection (arXiv:2505.06708)
    attention_gate: bool = False
    # gated short convolutions (kind "short_conv"): taps of the depthwise
    # causal convolution over the hidden columns; the lane keeps its last
    # conv_taps - 1 rows and nothing else
    conv_taps: int = 0
    # ---- latent attention (models/latent.py): queries through a rank
    # q_lora_rank bottleneck, keys and values through ONE compressed row of
    # kv_lora_rank values plus qk_rope_head_dim roped ones a token, which is
    # all the cache holds; 0 = not a latent-attention model ----
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # ---- routed experts beside Mixtral's (models/moe.py) ----
    moe_intermediate_size: int = 0  # an expert's width; 0 = intermediate_size
    n_shared_experts: int = 0  # experts every token passes, beside the routed
    first_k_dense: int = 0  # leading layers whose feed-forward stays dense
    moe_router: str = "softmax"  # "sigmoid": scores sigmoid, a bias chooses
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    moe_form: str = "gated"  # "relu2": down(relu(up x)^2), no gate matrix
    moe_shared_intermediate_size: int = 0  # 0 = an expert's width
    # several shared experts: their outputs averaged (True) or summed
    moe_shared_average: bool = False
    moe_router_bias: bool = True  # sigmoid router: a choice-only bias
    # this chip's share of the n_experts the router scores: experts
    # first_expert .. first_expert + n_experts_held - 1; 0 held = all
    n_experts_held: int = 0
    first_expert: int = 0
    # ---- looped models (arXiv:2510.25741): the stack runs n_passes times
    # a token over ONE set of weights, the final norm closes every pass and
    # every (pass, layer) keeps K/V rows of its own ----
    n_passes: int = 1
    # the exit gate's threshold: at >= 1 the exit distribution reaches 1
    # only at the last pass, so every token runs every pass and the gate
    # (params "exit_gate_w" / "exit_gate_b") is loaded but not evaluated
    early_exit_threshold: float = 1.0

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.n_heads
        if self.n_passes < 1:
            raise ValueError(f"n_passes must be >= 1, got {self.n_passes}")
        if self.n_passes > 1 and self.mixer_kinds is not None:
            raise NotImplementedError(
                "n_passes > 1 over a hybrid per-layer table: per-lane state "
                "of the other mixer kinds has no rows per pass")
        if self.layer_types is not None:
            self.layer_types = tuple(self.layer_types)
        if self.mixer_kinds is not None:
            self.mixer_kinds = tuple(self.mixer_kinds)
            unknown = sorted(set(self.mixer_kinds) - set(MIXER_KINDS))
            if unknown or len(self.mixer_kinds) != self.n_layers:
                raise ValueError(
                    f"mixer_kinds: {self.n_layers} entries of {MIXER_KINDS} "
                    f"expected, got {len(self.mixer_kinds)} with {unknown}")
            if self.diff_attention and {
                    "gqa_attention", "gqa_window_attention"} & set(self.mixer_kinds):
                raise ValueError(
                    "gqa_attention rows in a model whose cache rows hold "
                    "differential pairs (diff_attention)")

    @property
    def is_hybrid(self) -> bool:
        return self.mixer_kinds is not None

    @property
    def is_latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def latent_width(self) -> int:
        """Values a token leaves in a latent layer's cache: the compressed
        row and the one roped key all heads share."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def ffn_kind(self, i: int) -> str:
        """Layer i's feed-forward: `dense`, `experts` or `none`."""
        if self.one_sublayer and self.mixer_kinds[i] != "ffn":
            return "none"
        return ("experts" if self.n_experts > 0 and i >= self.first_k_dense
                else "dense")

    @property
    def mamba2_conv_dim(self) -> int:
        """Columns a Mamba-2 mixer's convolution runs over: x, B and C."""
        return (self.mamba_n_heads * self.mamba_head_dim
                + 2 * self.mamba_n_groups * self.mamba_d_state)

    @property
    def kda_conv_dim(self) -> int:
        """Columns a Kimi-delta mixer's convolution runs over: q, k and v."""
        return 3 * self.kda_n_heads * self.kda_head_dim

    def recurrent_slot(self) -> Tuple[Tuple[int, ...], int, int]:
        """A lane's slot in a layer that writes `recurrent`, by the mixer
        (_RECURRENT_SLOT); a model without such a layer gets Mamba-1's at
        its sizes of 0.  The slots of one model have one shape."""
        kinds = {kind for kind in self.mixer_kinds or ()
                 if _WRITES[kind] == "recurrent"}
        if len(kinds) > 1:
            raise ValueError(
                f"recurrent mixers of {len(kinds)} kinds ({sorted(kinds)}) in "
                "one model: a lane's slots have one shape")
        return _RECURRENT_SLOT[kinds.pop() if kinds else "mamba"](self)

    @property
    def n_expert_layers(self) -> int:
        return sum(self.ffn_kind(i) == "experts" for i in range(self.n_layers))

    @property
    def has_expert_sums(self) -> bool:
        """Whether the model's state carries its expert layers' sums
        (models/hybrid._ffn adds to `state["stats"]`; the `mixed` program
        returns them with its tokens)."""
        return self.is_hybrid and self.n_expert_layers > 0

    @property
    def counts_routed_pairs(self) -> bool:
        """Whether the program itself counts the pairs its expert layers
        routed and multiplied (two more sums).  It need not where every
        expert is held and every expert layer sees every token, since the
        host then knows both as tokens x experts a token x layers.  It must
        where the chip holds a share of the experts (which pairs fall on it
        is the router's doing), or where an expert layer lies behind the
        last layer that writes state: the packed forward runs such a layer
        on the sampled rows only (models/hybrid.forward_ragged)."""
        if not self.has_expert_sums:
            return False
        table = self.layer_table()
        last_writer = max(
            (i for i, row in enumerate(table) if row.writes != "none"),
            default=-1)
        return self.n_experts_held > 0 or any(
            row.ffn == "experts" for row in table[last_writer + 1:])

    def layer_table(self) -> Tuple[LayerSpec, ...]:
        """The per-layer table.  A Llama-family model is n_layers equal
        rows: attention over its own paged K/V."""
        kinds = self.mixer_kinds or ("attention",) * self.n_layers
        table = []
        for i, kind in enumerate(kinds):
            reads = i
            if kind in ("cross_attention", "gmu"):
                wanted = "attention" if kind == "cross_attention" else "mamba"
                reads = max((j for j in range(i) if kinds[j] == wanted),
                            default=-1)
                if reads < 0:
                    raise ValueError(
                        f"layer {i} ({kind}) has no {wanted} layer before it")
            table.append(
                LayerSpec(kind, _WRITES[kind], reads, self.ffn_kind(i)))
        return tuple(table)

    @property
    def is_looped(self) -> bool:
        return self.n_passes > 1

    @property
    def pairs_kv_heads(self) -> bool:
        """Whether a cache row holds TWO adjacent K/V heads side by side, in
        one row of twice the head's width (models/hybrid.py's head note): a
        differential pair's, and those of a hybrid table's `gqa_attention`
        rows whose heads are 64 wide, half the 128 lanes the attention
        kernels and the page write tile by.  The same bytes a token either
        way.  A table with window rows of such heads (none exists) and the
        Llama path keep their 64-wide rows, and with them the gather."""
        kinds = set(self.mixer_kinds or ())
        return self.diff_attention or (
            self.head_dim == 64 and self.n_kv_heads % 2 == 0
            and "gqa_attention" in kinds
            and "gqa_window_attention" not in kinds)

    @property
    def cache_kv_heads(self) -> int:
        """K/V heads as the cache stores them (`pairs_kv_heads`)."""
        return self.n_kv_heads // 2 if self.pairs_kv_heads else self.n_kv_heads

    @property
    def cache_head_dim(self) -> int:
        return 2 * self.head_dim if self.pairs_kv_heads else self.head_dim

    def layer_ropes(self, i: int) -> bool:
        """Whether a hybrid table's plain grouped-query row i turns its
        queries and keys by position."""
        if not self.use_rope:
            return False
        return (not self.rope_window_rows_only
                or self.mixer_kinds[i] == "gqa_window_attention")

    def layer_window(self, i: int) -> int:
        """Sliding-window width for layer i (0 = full attention)."""
        if self.sliding_window <= 0:
            return 0
        if self.layer_types is None:
            return self.sliding_window
        return (self.sliding_window
                if self.layer_types[i] == "sliding_attention" else 0)

    @property
    def attn_scale(self) -> Optional[float]:
        """Attention score scale override (None = 1/sqrt(head_dim))."""
        if self.query_pre_attn_scalar is None:
            return None
        return float(self.query_pre_attn_scalar) ** -0.5

    @staticmethod
    def tiny(**overrides) -> "LlamaConfig":
        """Small config for tests/CI meshes."""
        base = dict(
            vocab_size=512,
            hidden_size=64,
            intermediate_size=128,
            n_layers=2,
            n_heads=4,
            n_kv_heads=2,
            max_position_embeddings=256,
        )
        base.update(overrides)
        return LlamaConfig(**base)

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=128256,
            hidden_size=4096,
            intermediate_size=14336,
            n_layers=32,
            n_heads=32,
            n_kv_heads=8,
            rope_theta=500000.0,
            max_position_embeddings=8192,
        )

    @staticmethod
    def llama3_1b() -> "LlamaConfig":
        """Llama-3.2-1B-shaped config (bench-friendly on one v5e chip)."""
        return LlamaConfig(
            vocab_size=128256,
            hidden_size=2048,
            intermediate_size=8192,
            n_layers=16,
            n_heads=32,
            n_kv_heads=8,
            head_dim=64,
            rope_theta=500000.0,
            max_position_embeddings=8192,
            tie_word_embeddings=True,
        )

    @staticmethod
    def qwen3_0_6b() -> "LlamaConfig":
        """Qwen3-0.6B shape (qk-norm family; MXU-native head_dim=128)."""
        return LlamaConfig(
            vocab_size=151936,
            hidden_size=1024,
            intermediate_size=3072,
            n_layers=28,
            n_heads=16,
            n_kv_heads=8,
            head_dim=128,
            rope_theta=1000000.0,
            max_position_embeddings=32768,
            tie_word_embeddings=True,
            qk_norm=True,
            rms_norm_eps=1e-6,
        )

    @staticmethod
    def gemma2_2b() -> "LlamaConfig":
        """Gemma-2-2B shape (sandwich norms, GeGLU, softcaps, alternating
        4096-token sliding windows on even layers)."""
        return LlamaConfig(
            vocab_size=256000,
            hidden_size=2304,
            intermediate_size=9216,
            n_layers=26,
            n_heads=8,
            n_kv_heads=4,
            head_dim=256,
            rope_theta=10000.0,
            max_position_embeddings=8192,
            tie_word_embeddings=True,
            hidden_act="gelu_tanh",
            norm_plus_one=True,
            embed_scale=True,
            sandwich_norms=True,
            attn_logit_softcap=50.0,
            logit_softcap=30.0,
            query_pre_attn_scalar=256,
            sliding_window=4096,
            layer_types=tuple(
                "sliding_attention" if i % 2 == 0 else "full_attention"
                for i in range(26)),
            rms_norm_eps=1e-6,
        )

    @staticmethod
    def from_hf_config(path_or_dict) -> "LlamaConfig":
        """Map a HuggingFace config.json (LlamaForCausalLM/MistralForCausalLM/
        Qwen2ForCausalLM) onto LlamaConfig."""
        if isinstance(path_or_dict, str):
            with open(path_or_dict) as f:
                cfg = json.load(f)
        else:
            cfg = dict(path_or_dict)
        model_type = cfg.get("model_type")
        if model_type == "phi4flash":
            return _phi4flash_config(cfg)
        if model_type == "glm4_moe_lite":
            return _glm4_moe_lite_config(cfg)
        if model_type == "nemotron_h":
            return _nemotron_h_config(cfg)
        if model_type == "cohere2_moe":
            return _cohere2_moe_config(cfg)
        if model_type == "solar_open2":
            return _solar_open2_config(cfg)
        if model_type == "lfm2_moe":
            return _lfm2_moe_config(cfg)
        if model_type not in _LLAMA_MODEL_TYPES:
            foreign = [k for k in _FOREIGN_MIXER_KEYS if k in cfg
                       and not (k == "total_ut_steps" and int(cfg[k]) <= 1)]
            if foreign:
                raise ValueError(
                    f"model_type {model_type!r} is not supported: its keys "
                    f"{foreign} describe mixers the per-layer table "
                    f"({', '.join(MIXER_KINDS)}) cannot express")
        rope_scaling = cfg.get("rope_scaling")
        if rope_scaling is not None:
            # Validate eagerly: Llama-3.1/3.2 checkpoints rely on rope_type
            # "llama3" at every position; silently dropping an unsupported
            # variant would load but produce wrong logits.
            from ..ops.rotary import rope_frequencies

            rope_frequencies(
                cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"],
                cfg.get("rope_theta", 10000.0),
                rope_scaling,
            )
        return LlamaConfig(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            intermediate_size=(
                cfg["intermediate_size"] if "intermediate_size" in cfg
                else cfg["ffn_dim"]  # loud KeyError on unsupported configs
            ),
            n_layers=cfg["num_hidden_layers"],
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg.get("num_key_value_heads", cfg["num_attention_heads"]),
            head_dim=cfg.get("head_dim"),
            rope_theta=cfg.get("rope_theta", 10000.0),
            rope_scaling=rope_scaling,
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_position_embeddings=cfg.get("max_position_embeddings", 4096),
            tie_word_embeddings=cfg.get("tie_word_embeddings", False),
            attention_bias=cfg.get("attention_bias", False),
            # Qwen3 carries q_norm/k_norm weights per layer; model_type is
            # always present in real config.json, architectures often not
            qk_norm=(
                cfg.get("model_type") == "qwen3"
                or any("Qwen3" in a
                       for a in (cfg.get("architectures") or []))),
            # Gemma-2 family (model_type "gemma2")
            hidden_act=_map_hidden_act(
                cfg.get("hidden_act", cfg.get("hidden_activation"))),
            norm_plus_one=cfg.get("model_type") == "gemma2",
            embed_scale=cfg.get("model_type") == "gemma2",
            # Ouro's layer is the same four-norm layer, with plain RMSNorm
            sandwich_norms=cfg.get("model_type") in ("gemma2", "ouro"),
            attn_logit_softcap=cfg.get("attn_logit_softcapping") or 0.0,
            logit_softcap=cfg.get("final_logit_softcapping") or 0.0,
            query_pre_attn_scalar=cfg.get("query_pre_attn_scalar"),
            sliding_window=(
                cfg.get("sliding_window") or 0
                if cfg.get("model_type") == "gemma2" else 0),
            # raw hub config.json for Gemma-2 predates the layer_types
            # key (the alternation lived in modeling code: even layers
            # sliding); synthesize it so full-attention layers are never
            # silently windowed
            layer_types=(
                tuple(cfg["layer_types"]) if cfg.get("layer_types")
                else tuple(
                    "sliding_attention" if i % 2 == 0 else "full_attention"
                    for i in range(cfg["num_hidden_layers"]))
                if cfg.get("model_type") == "gemma2"
                and (cfg.get("sliding_window") or 0) > 0
                else None),
            # MixtralForCausalLM fields
            n_experts=cfg.get("num_local_experts", 0),
            n_experts_per_tok=cfg.get("num_experts_per_tok", 2),
            # Ouro (model_type "ouro"): the stack runs total_ut_steps times
            n_passes=(int(cfg.get("total_ut_steps", 1))
                      if model_type == "ouro" else 1),
            early_exit_threshold=(float(cfg.get("early_exit_threshold", 1.0))
                                  if model_type == "ouro" else 1.0),
        )


def _phi4flash_config(cfg: dict) -> LlamaConfig:
    """config.json of `model_type: phi4flash`.  What the published file
    does not give is read from keys a deployment adds and otherwise set by
    the family's convention (benchmark/configs/phi4-mini-flash.json lists
    each under `assumed`)."""
    h = cfg["hidden_size"]
    d_inner = cfg.get("mamba_d_inner", 2 * h)
    if cfg.get("diff_attention_pairing", "adjacent") != "adjacent":
        raise ValueError(
            "phi4flash: only diff_attention_pairing 'adjacent' (heads 2j, "
            f"2j+1) is implemented, got {cfg['diff_attention_pairing']!r}")
    return LlamaConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=h,
        intermediate_size=cfg["intermediate_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg.get("num_key_value_heads", cfg["num_attention_heads"]),
        head_dim=cfg.get("head_dim"),
        rms_norm_eps=cfg.get("layer_norm_eps", 1e-5),
        max_position_embeddings=cfg.get("max_position_embeddings", 4096),
        tie_word_embeddings=cfg.get("tie_word_embeddings", True),
        attention_bias=cfg.get("attention_bias", True),
        attention_out_bias=cfg.get("attention_out_bias", True),
        hidden_act=_map_hidden_act(cfg.get("hidden_act")),
        sliding_window=cfg["sliding_window"],
        mixer_kinds=phi4flash_mixer_kinds(
            cfg["num_hidden_layers"], cfg.get("mb_per_layer", 2)),
        norm_type="layernorm",
        use_rope=False,
        diff_attention=True,
        mamba_d_inner=d_inner,
        mamba_d_state=cfg.get("mamba_d_state", 16),
        mamba_d_conv=cfg.get("mamba_d_conv", 4),
        mamba_dt_rank=cfg.get("mamba_dt_rank", -(-h // 16)),
    )


def _glm4_moe_lite_config(cfg: dict) -> LlamaConfig:
    """config.json of `model_type: glm4_moe_lite`: latent attention in every
    layer, `first_k_dense_replace` dense feed-forwards and routed experts
    (sigmoid router, a choice-only bias, a shared expert) behind them.
    What the published file leaves to the modeling file is listed under
    `assumed` in benchmark/configs/glm47-flash.json.  The next-token-
    prediction module (`num_nextn_predict_layers`) is neither built nor
    run: the published causal-LM forward does not evaluate it."""
    refused = []
    if int(cfg.get("n_group", 1)) != 1 or int(cfg.get("topk_group", 1)) != 1:
        refused.append(f"n_group={cfg.get('n_group')} / topk_group="
                       f"{cfg.get('topk_group')} (group-limited routing)")
    if cfg.get("topk_method", "noaux_tc") != "noaux_tc":
        refused.append(f"topk_method={cfg['topk_method']!r}")
    if cfg.get("rope_scaling") is not None:
        refused.append("rope_scaling (the softmax scale's extra factor is "
                       "not implemented)")
    if float(cfg.get("partial_rotary_factor", 1)) != 1:
        refused.append(f"partial_rotary_factor={cfg['partial_rotary_factor']}")
    if cfg.get("attention_bias"):
        refused.append("attention_bias")
    if not cfg.get("q_lora_rank"):
        refused.append("q_lora_rank null (queries without the bottleneck)")
    if int(cfg.get("n_shared_experts", 1)) > 1:
        # models/moe.py runs several shared experts as one fused MLP and a
        # factor (the Cohere family's four, averaged); this family's would
        # be their sum, which its reference does not compute yet
        refused.append(f"n_shared_experts={cfg['n_shared_experts']} (held "
                       "to no reference for this family: benchmark/"
                       "reference/glm4_moe_lite.py computes one)")
    if refused:
        raise ValueError(
            "glm4_moe_lite: not implemented: " + "; ".join(refused))
    n_layers = cfg["num_hidden_layers"]
    return LlamaConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        n_layers=n_layers,
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_attention_heads"],
        head_dim=cfg["qk_rope_head_dim"],
        rope_theta=cfg.get("rope_theta", 10000.0),
        rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
        max_position_embeddings=cfg.get("max_position_embeddings", 4096),
        tie_word_embeddings=cfg.get("tie_word_embeddings", False),
        hidden_act=_map_hidden_act(cfg.get("hidden_act")),
        mixer_kinds=("latent_attention",) * n_layers,
        q_lora_rank=cfg["q_lora_rank"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        n_experts=cfg["n_routed_experts"],
        n_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_shared_experts=int(cfg.get("n_shared_experts", 0)),
        first_k_dense=int(cfg.get("first_k_dense_replace", 0)),
        moe_router="sigmoid",
        routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
        norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
    )


#: `hybrid_override_pattern` letter -> the row's mixer kind
_NEMOTRON_H_LETTERS = {"M": "mamba2", "E": "ffn", "*": "gqa_attention"}


def _nemotron_h_config(cfg: dict) -> LlamaConfig:
    """config.json of `model_type: nemotron_h`: ONE sublayer a layer, by
    the letter of `hybrid_override_pattern`: `M` a Mamba-2 mixer, `E`
    routed experts (sigmoid router, a choice-only bias, ungated relu^2
    experts and a shared one), `*` plain grouped-query attention with no
    positional encoding.  What the published file leaves to the modeling
    file is listed under `assumed` in benchmark/configs/nemotron3-nano.json.
    A deployment that holds a chip's share of the experts says so with two
    keys of its own: `n_routed_experts` counts the experts HELD here,
    `router_n_experts` the experts the router scores (the published
    count), `first_expert` the first one held."""
    pattern = cfg["hybrid_override_pattern"]
    n_layers = cfg["num_hidden_layers"]
    refused = []
    unknown = sorted(set(pattern) - set(_NEMOTRON_H_LETTERS))
    if unknown:
        refused.append(
            f"hybrid_override_pattern letters {unknown} (built: M Mamba-2, "
            "E experts, * attention; '-' is the family's dense MLP)")
    if len(pattern) != n_layers:
        refused.append(f"hybrid_override_pattern of {len(pattern)} letters "
                       f"for num_hidden_layers={n_layers}")
    if int(cfg.get("n_group", 1)) != 1 or int(cfg.get("topk_group", 1)) != 1:
        refused.append(f"n_group={cfg.get('n_group')} / topk_group="
                       f"{cfg.get('topk_group')} (group-limited routing)")
    for key in ("mamba_proj_bias", "mlp_bias", "attention_bias", "use_bias"):
        if cfg.get(key):
            refused.append(f"{key} true")
    if cfg.get("sliding_window"):
        refused.append(f"sliding_window={cfg['sliding_window']}")
    if cfg.get("mlp_hidden_act", "relu2") != "relu2":
        refused.append(f"mlp_hidden_act={cfg['mlp_hidden_act']!r}")
    if cfg.get("mamba_hidden_act", "silu") != "silu":
        refused.append(f"mamba_hidden_act={cfg['mamba_hidden_act']!r}")
    if int(cfg.get("n_shared_experts", 1)) > 1:
        # as above: the family states ONE shared width
        # (moe_shared_expert_intermediate_size); how several would split or
        # combine it is not published
        refused.append(f"n_shared_experts={cfg['n_shared_experts']} (the "
                       "family states one shared width; how several combine "
                       "is not published)")
    if cfg.get("residual_in_fp32"):
        refused.append("residual_in_fp32")
    heads, groups = cfg["mamba_num_heads"], cfg["n_groups"]
    if heads % groups:
        refused.append(f"mamba_num_heads={heads} not a multiple of "
                       f"n_groups={groups}")
    if refused:
        raise ValueError("nemotron_h: not implemented: " + "; ".join(refused))
    held = cfg["n_routed_experts"]
    scored = int(cfg.get("router_n_experts", held))
    return LlamaConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        n_layers=n_layers,
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim"),
        rms_norm_eps=cfg.get("layer_norm_epsilon", 1e-5),
        max_position_embeddings=cfg.get("max_position_embeddings", 4096),
        tie_word_embeddings=cfg.get("tie_word_embeddings", False),
        mixer_kinds=tuple(_NEMOTRON_H_LETTERS[c] for c in pattern),
        one_sublayer=True,
        use_rope=False,
        mamba_d_state=cfg["ssm_state_size"],
        mamba_d_conv=cfg["conv_kernel"],
        mamba_n_heads=heads,
        mamba_head_dim=cfg["mamba_head_dim"],
        mamba_n_groups=groups,
        mamba_d_inner=heads * cfg["mamba_head_dim"],
        n_experts=scored,
        n_experts_held=0 if held == scored else held,
        first_expert=int(cfg.get("first_expert", 0)),
        n_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_shared_intermediate_size=(
            cfg.get("moe_shared_expert_intermediate_size", 0)
            if int(cfg.get("n_shared_experts", 0)) else 0),
        n_shared_experts=int(cfg.get("n_shared_experts", 0)),
        moe_router="sigmoid",
        moe_form="relu2",
        routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
        norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
    )


def _cohere2_moe_config(cfg: dict) -> LlamaConfig:
    """config.json of `model_type: cohere2_moe` (Command A+): every layer ONE
    bias-free LayerNorm read by the attention and by the experts alike, one
    residual (`use_parallel_block`); `layer_types` says which rows attend
    over the last `sliding_window` tokens with interleaved rotary
    (`rope_gptj`) and which over the whole context with no positions; the
    feed-forward is routed experts (sigmoid scores, no choice bias, weights
    normalised over the chosen) beside `num_shared_experts` shared ones
    whose outputs are averaged.  `intermediate_size` is one expert's width,
    routed and shared.  What the published file leaves to the modeling file
    is listed under `assumed` in benchmark/configs/command-a-plus.json.  A
    deployment that holds a chip's share of the experts says so as the
    Nemotron family does: `num_experts` counts the experts HELD here,
    `router_n_experts` the experts the router scores (the published count),
    `first_expert` the first one held."""
    n_layers = cfg["num_hidden_layers"]
    kinds = cfg.get("layer_types") or ()
    refused = []
    if len(kinds) != n_layers or set(kinds) - {
            "sliding_attention", "full_attention"}:
        refused.append(f"layer_types of {len(kinds)} entries "
                       f"{sorted(set(kinds))} for num_hidden_layers={n_layers}")
    if not cfg.get("use_parallel_block", True):
        refused.append("use_parallel_block false")
    if int(cfg.get("first_k_dense_replace", 0)):
        refused.append(f"first_k_dense_replace={cfg['first_k_dense_replace']} "
                       "(the prefix_dense_* layers)")
    if cfg.get("position_embedding_type", "rope_gptj") != "rope_gptj":
        refused.append(
            f"position_embedding_type={cfg['position_embedding_type']!r}")
    if float(cfg.get("rotary_pct", 1)) != 1:
        refused.append(f"rotary_pct={cfg['rotary_pct']}")
    rope = cfg.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default" or cfg.get("rope_scaling"):
        refused.append("rope scaling")
    if cfg.get("expert_selection_fn", "sigmoid") != "sigmoid":
        refused.append(f"expert_selection_fn={cfg['expert_selection_fn']!r}")
    if cfg.get("shared_expert_combination_strategy", "average") not in (
            "average", "sum"):
        refused.append("shared_expert_combination_strategy="
                       f"{cfg['shared_expert_combination_strategy']!r}")
    if not cfg.get("use_gated_activation", True):
        refused.append("use_gated_activation false")
    for key in ("attention_bias", "use_qk_norm", "use_parallel_embedding"):
        if cfg.get(key):
            refused.append(f"{key} true")
    if float(cfg.get("logit_scale", 1)) != 1:
        refused.append(f"logit_scale={cfg['logit_scale']}")
    if not cfg.get("tie_word_embeddings", True):
        refused.append("tie_word_embeddings false")
    if not cfg.get("sliding_window"):
        refused.append("sliding_window absent")
    if refused:
        raise ValueError("cohere2_moe: not implemented: " + "; ".join(refused))
    held = cfg["num_experts"]
    scored = int(cfg.get("router_n_experts", held))
    return LlamaConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        n_layers=n_layers,
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim"),
        rope_theta=float(rope.get("rope_theta", cfg.get("rope_theta", 10000.0))),
        rms_norm_eps=cfg.get("layer_norm_eps", 1e-5),
        max_position_embeddings=cfg.get("max_position_embeddings", 4096),
        tie_word_embeddings=True,
        hidden_act=_map_hidden_act(cfg.get("hidden_act")),
        sliding_window=cfg["sliding_window"],
        mixer_kinds=tuple(
            "gqa_window_attention" if kind == "sliding_attention"
            else "gqa_attention" for kind in kinds),
        norm_type="layernorm",
        norm_bias=False,
        rope_interleaved=True,
        rope_window_rows_only=True,
        parallel_block=True,
        n_experts=scored,
        n_experts_held=0 if held == scored else held,
        first_expert=int(cfg.get("first_expert", 0)),
        n_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=int(cfg.get("num_shared_experts", 0)),
        moe_shared_average=cfg.get(
            "shared_expert_combination_strategy", "average") == "average",
        moe_router="sigmoid",
        moe_router_bias=False,
        norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
    )


def _solar_open2_config(cfg: dict) -> LlamaConfig:
    """config.json of `model_type: solar_open2` (Solar-Open2): pre-norm
    layers of two residuals, `h += Mixer(RMSNorm(h)); h += Experts(
    RMSNorm(h))`, with no positional encoding anywhere.  The mixer is a
    Kimi-delta linear-attention mixer (`kda`, the `kda_*` keys and
    `linear_attn_config`: arXiv:2510.26692) but in `gqa_layers`, every
    `gqa_interval + 1`-th layer from 0, where it is plain grouped-query
    attention whose output an elementwise sigmoid gate multiplies
    (`use_gqa_gate`).  Every layer's feed-forward is routed experts (sigmoid
    scores, a choice-only bias, weights normalised over the chosen) beside
    one shared expert.  What the published file leaves to the modeling file
    is listed under `assumed` in benchmark/configs/solar-open2.json.  A
    deployment that holds a chip's share of the experts says so as the
    Nemotron family does: `n_routed_experts` counts the experts HELD here,
    `router_n_experts` the experts the router scores (the published count),
    `first_expert` the first one held."""
    n_layers = cfg["num_hidden_layers"]
    linear = cfg.get("linear_attn_config") or {}
    period = int(cfg.get("gqa_interval", 3)) + 1
    gqa = list(cfg.get("gqa_layers") or ())
    refused = []
    if int(cfg.get("first_k_dense_replace", 0)):
        refused.append(f"first_k_dense_replace={cfg['first_k_dense_replace']} "
                       "(leading dense feed-forwards)")
    if cfg.get("kda_use_full_proj"):
        refused.append("kda_use_full_proj true (the decay's and the gate's "
                       "projections at full rank)")
    if cfg.get("use_rope"):
        refused.append("use_rope true (the attention rows carry no positions)")
    if int(cfg.get("n_group", 1)) != 1 or int(cfg.get("topk_group", 1)) != 1:
        refused.append(f"n_group={cfg.get('n_group')} / topk_group="
                       f"{cfg.get('topk_group')} (group-limited routing)")
    if linear.get("num_kv_heads") is not None:
        refused.append(f"linear_attn_config.num_kv_heads="
                       f"{linear['num_kv_heads']} (keys shared by groups of "
                       "heads)")
    if gqa != list(range(0, n_layers, period)):
        refused.append(f"gqa_layers={gqa} (built: every {period}th layer "
                       f"from 0 of num_hidden_layers={n_layers})")
    if int(cfg.get("n_shared_experts", 1)) > 1:
        refused.append(f"n_shared_experts={cfg['n_shared_experts']} (held to "
                       "no reference for this family)")
    if cfg.get("tie_word_embeddings"):
        refused.append("tie_word_embeddings true")
    if cfg.get("attention_bias"):
        refused.append("attention_bias true")
    if refused:
        raise ValueError("solar_open2: not implemented: " + "; ".join(refused))
    held = cfg["n_routed_experts"]
    scored = int(cfg.get("router_n_experts", held))
    return LlamaConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        n_layers=n_layers,
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim"),
        rope_theta=cfg.get("rope_theta", 10000.0),
        rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
        max_position_embeddings=cfg.get("max_position_embeddings", 4096),
        tie_word_embeddings=False,
        mixer_kinds=tuple("gqa_attention" if i % period == 0 else "kda"
                          for i in range(n_layers)),
        use_rope=False,
        attention_gate=bool(cfg.get("use_gqa_gate", False)),
        kda_n_heads=linear["num_heads"],
        kda_head_dim=linear["head_dim"],
        kda_d_conv=linear["short_conv_kernel_size"],
        # the family's low-rank projections pass through head_dim columns
        kda_rank=linear["head_dim"],
        kda_neg_eigval=bool(cfg.get("kda_allow_neg_eigval", False)),
        n_experts=scored,
        n_experts_held=0 if held == scored else held,
        first_expert=int(cfg.get("first_expert", 0)),
        n_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_shared_experts=int(cfg.get("n_shared_experts", 0)),
        moe_router="sigmoid",
        routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
        norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
    )


#: `layer_types` entry of `model_type: lfm2_moe` -> the row's mixer kind
_LFM2_LAYER_TYPES = {"conv": "short_conv", "full_attention": "gqa_attention"}


def _lfm2_moe_config(cfg: dict) -> LlamaConfig:
    """config.json of `model_type: lfm2_moe` (LFM2-24B-A2B): pre-norm layers
    of two residuals, `h += Mixer(RMSNorm(h)); h += FFN(RMSNorm(h))`, the
    mixer by `layer_types`: `conv` a gated short convolution (`short_conv`:
    `[B | C | x] = u W_in`, a depthwise causal convolution of `conv_L_cache`
    taps over `B * x` with no bias and no activation, `(C * conv) W_out`),
    `full_attention` grouped-query attention with an RMSNorm a head on q and
    k before a half-split rotary.  The first `num_dense_layers` feed-forwards
    are one gated MLP of `intermediate_size`, the others `num_experts`
    routed experts of `moe_intermediate_size` (sigmoid scores, a choice-only
    bias where `use_expert_bias`, weights normalised over the chosen), no
    shared one.  The head is the embedding, transposed.  What the published
    file leaves to the modeling file is listed under `assumed` in
    benchmark/configs/lfm2-24b-a2b.json."""
    n_layers = cfg["num_hidden_layers"]
    kinds = list(cfg.get("layer_types") or ())
    rope = cfg.get("rope_parameters") or {}
    refused = []
    if cfg.get("conv_bias"):
        refused.append("conv_bias true")
    if int(cfg.get("conv_L_cache", 3)) < 2:
        refused.append(f"conv_L_cache={cfg['conv_L_cache']} (a convolution "
                       "of one tap keeps no tail)")
    unknown = sorted(set(kinds) - set(_LFM2_LAYER_TYPES))
    if unknown or len(kinds) != n_layers:
        refused.append(
            f"layer_types of {len(kinds)} entries for num_hidden_layers="
            f"{n_layers} with {unknown} (built: conv, full_attention)")
    if int(cfg.get("num_dense_layers", 0)) > n_layers:
        refused.append(f"num_dense_layers={cfg['num_dense_layers']} of "
                       f"num_hidden_layers={n_layers}")
    if rope.get("rope_type", "default") != "default" or cfg.get("rope_scaling"):
        refused.append(f"rope_type={rope.get('rope_type')!r} (built: default)")
    if not cfg.get("tie_word_embeddings", True):
        refused.append("tie_word_embeddings false")
    if refused:
        raise ValueError("lfm2_moe: not implemented: " + "; ".join(refused))
    return LlamaConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        n_layers=n_layers,
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim"),
        rope_theta=float(rope.get("rope_theta", cfg.get("rope_theta", 10000.0))),
        rms_norm_eps=cfg.get("norm_eps", 1e-5),
        max_position_embeddings=cfg.get("max_position_embeddings", 4096),
        tie_word_embeddings=True,
        qk_norm=True,
        mixer_kinds=tuple(_LFM2_LAYER_TYPES[kind] for kind in kinds),
        conv_taps=int(cfg.get("conv_L_cache", 3)),
        n_experts=cfg["num_experts"],
        n_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        first_k_dense=int(cfg.get("num_dense_layers", 0)),
        moe_router="sigmoid",
        moe_router_bias=bool(cfg.get("use_expert_bias", True)),
        routed_scaling_factor=float(cfg.get("routed_scaling_factor", 1.0)),
        norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
    )


def init_params(config: LlamaConfig, rng: jax.Array, scale: float = 0.02,
                weight_quant: str = "none", shardings=None) -> Params:
    """Random-initialized parameter pytree (bench/tests; real serving loads
    checkpoints via load_hf_weights).

    weight_quant="int8" emits quantized leaves DIRECTLY (random int8 +
    constant scales matching `scale`'s distribution) — an 8B random init
    must never stage the bf16 tree on a 16-GB chip just to quantize it.

    Every layer (and the embedding/head group) is generated under jit, and
    `shardings` — a pytree of jax shardings matching the result
    (parallel/sharding.init_params_on_mesh) — becomes that jit's
    out_shardings: leaves are created ON their devices, so no device ever
    holds more than its own shard plus one layer's f32 temporaries.  A
    model that only fits sharded (Llama-3-8B bf16 at tp=4) starts; None
    places everything on the default device."""
    if config.is_hybrid:
        from . import hybrid

        return hybrid.init_params(config, rng, scale, weight_quant, shardings)
    dtype = jnp.dtype(config.dtype)
    h, hd = config.hidden_size, config.head_dim
    nq, nkv = config.n_heads, config.n_kv_heads
    keys = jax.random.split(rng, config.n_layers + 2)

    def dense_f32(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)

    def dense_q(key, shape, channel_axis=-1):
        # uniform int8 has std ~73; s maps that back onto N(0, scale)
        q = jax.random.randint(key, shape, -127, 128, jnp.int8)
        s_shape = (shape[channel_axis],)
        return {"q": q, "s": jnp.full(s_shape, scale / 73.0, jnp.float32)}

    quant = weight_quant == "int8"
    if quant and config.n_experts > 0:
        raise NotImplementedError("weight_quant over MoE experts")
    dense = (lambda key, shape: dense_q(key, shape)) if quant else dense_f32
    norm_init = jnp.zeros if config.norm_plus_one else jnp.ones
    # A looped model's branches start at 1 / sqrt(2 L) (the residual
    # scaling of GPT-2's and DeepNet's inits: the 2 L branches of a pass
    # add the variance the stream entered it with).  At gain 1 a pass of
    # random layers multiplies a rounding error ~2.5 times, so four passes
    # in bf16 leave the float32 reference by as much as int8 weights do
    # (correlation of logits 0.955; 0.9999 after one pass: PERF.md section
    # 6, PR 35) and no tolerance tells a precision from a fault.
    post_norm_init = norm_init
    if config.is_looped:
        def post_norm_init(shape, dtype):
            return jnp.full(shape, (2 * config.n_layers) ** -0.5, dtype)

    def make_layer(window: int, key):
        k = jax.random.split(key, 8)
        layer = {
            "attn_norm": norm_init((h,), dtype),
            "wq": dense(k[0], (h, nq * hd)),
            "wk": dense(k[1], (h, nkv * hd)),
            "wv": dense(k[2], (h, nkv * hd)),
            "wo": dense(k[3], (nq * hd, h)),
            "mlp_norm": norm_init((h,), dtype),
        }
        if config.n_experts > 0:
            E, f = config.n_experts, config.intermediate_size
            layer["router"] = dense(k[7], (h, E))
            layer["w_gate"] = dense(k[4], (E, h, f))
            layer["w_up"] = dense(k[5], (E, h, f))
            layer["w_down"] = dense(k[6], (E, f, h))
        else:
            layer["w_gate"] = dense(k[4], (h, config.intermediate_size))
            layer["w_up"] = dense(k[5], (h, config.intermediate_size))
            layer["w_down"] = dense(k[6], (config.intermediate_size, h))
        if config.attention_bias:
            layer["bq"] = jnp.zeros((nq * hd,), dtype)
            layer["bk"] = jnp.zeros((nkv * hd,), dtype)
            layer["bv"] = jnp.zeros((nkv * hd,), dtype)
        if config.qk_norm:
            layer["q_norm"] = jnp.ones((hd,), dtype)
            layer["k_norm"] = jnp.ones((hd,), dtype)
        if config.sandwich_norms:
            # Gemma's init to ZERO ((1+w) multiplies by 1), plain ones to 1
            layer["post_attn_norm"] = post_norm_init((h,), dtype)
            layer["post_mlp_norm"] = post_norm_init((h,), dtype)
        if config.sliding_window > 0:
            layer["attn_window"] = jnp.asarray(window, jnp.int32)
        return layer

    def make_top(embed_key, head_key):
        top: Params = {
            # tied quantized embeddings carry per-ROW scales (they serve as
            # the transposed lm_head); untied embeddings stay bf16
            # (gather-only)
            "embed": (
                dense_q(embed_key, (config.vocab_size, h), channel_axis=0)
                if quant and config.tie_word_embeddings
                else dense_f32(embed_key, (config.vocab_size, h))
            ),
            "final_norm": norm_init((h,), dtype),
        }
        if not config.tie_word_embeddings:
            top["lm_head"] = dense(head_key, (h, config.vocab_size))
        if config.is_looped:
            # the exit gate, Linear(h -> 1): present so that a checkpoint
            # loads; not evaluated at early_exit_threshold >= 1
            top["exit_gate_w"] = dense_f32(
                jax.random.fold_in(head_key, 1), (h, 1))
            top["exit_gate_b"] = jnp.zeros((1,), dtype)
        return top

    # all layers share shapes and shardings: one compiled program per
    # distinct (static) window value, reused across the layers
    layer_fn = jax.jit(
        make_layer, static_argnums=0,
        out_shardings=None if shardings is None else shardings["layers"][0])
    layers = [layer_fn(config.layer_window(i), keys[i])
              for i in range(config.n_layers)]
    top_sharding = None if shardings is None else {
        k: v for k, v in shardings.items() if k != "layers"}
    params = jax.jit(make_top, out_shardings=top_sharding)(keys[-2], keys[-1])
    params["layers"] = layers
    return params


def _maybe_add(y: jnp.ndarray, delta) -> jnp.ndarray:
    # trace-time decision: the no-LoRA program is unchanged
    return y if delta is None else y + delta


def _qkv(layer: Params, x: jnp.ndarray, config: LlamaConfig, onehot=None):
    B, T, _ = x.shape
    lora = layer.get("lora")
    q = _maybe_add(dense(x, layer["wq"]), lora_delta(lora, "wq", x, onehot))
    k = _maybe_add(dense(x, layer["wk"]), lora_delta(lora, "wk", x, onehot))
    v = _maybe_add(dense(x, layer["wv"]), lora_delta(lora, "wv", x, onehot))
    if config.attention_bias:
        q = q + layer["bq"]
        k = k + layer["bk"]
        v = v + layer["bv"]
    q = q.reshape(B, T, config.n_heads, config.head_dim)
    k = k.reshape(B, T, config.n_kv_heads, config.head_dim)
    v = v.reshape(B, T, config.n_kv_heads, config.head_dim)
    if config.qk_norm:
        # Qwen3: per-head RMSNorm over head_dim before rope
        q = rms_norm(q, layer["q_norm"], config.rms_norm_eps)
        k = rms_norm(k, layer["k_norm"], config.rms_norm_eps)
    return q, k, v


@jax.named_scope("mlp")
def _mlp(layer: Params, x: jnp.ndarray, config: LlamaConfig, onehot=None) -> jnp.ndarray:
    if "router" in layer:  # this layer's feed-forward is routed experts
        from .moe import moe_config_of, moe_mlp

        return moe_mlp(layer, x, moe_config_of(config))
    lora = layer.get("lora")
    gate = _act(
        _maybe_add(dense(x, layer["w_gate"]), lora_delta(lora, "w_gate", x, onehot)),
        config,
    )
    up = _maybe_add(dense(x, layer["w_up"]), lora_delta(lora, "w_up", x, onehot))
    h = gate * up
    return _maybe_add(
        dense(h, layer["w_down"]), lora_delta(lora, "w_down", h, onehot)
    )


@jax.named_scope("lm_head")
def _logits(params: Params, x: jnp.ndarray, config: LlamaConfig) -> jnp.ndarray:
    if not config.is_looped:  # a looped model's passes each end in it
        x = _norm(x, params["final_norm"], config)
    head = params.get("lm_head")
    if head is None:
        logits = tied_head_matmul(x, params["embed"]).astype(jnp.float32)
    else:
        logits = dense(x, head).astype(jnp.float32)
    if config.logit_softcap > 0.0:
        logits = jnp.tanh(logits / config.logit_softcap) * config.logit_softcap
    return logits


def _norm(x: jnp.ndarray, weight: jnp.ndarray, config: LlamaConfig) -> jnp.ndarray:
    """Config-dispatched RMSNorm: Gemma's (1+w) variant or the default."""
    if config.norm_plus_one:
        return rms_norm_plus_one(x, weight, config.rms_norm_eps)
    return rms_norm(x, weight, config.rms_norm_eps)


@jax.named_scope("embedding")
def _embed(params: Params, tokens: jnp.ndarray, config: LlamaConfig) -> jnp.ndarray:
    x = embed_lookup(params["embed"], tokens, jnp.dtype(config.dtype))
    if config.embed_scale:
        # Gemma scales embeddings by sqrt(hidden); the normalizer is cast
        # to the activation dtype first (HF parity)
        x = x * jnp.asarray(config.hidden_size ** 0.5, x.dtype)
    return x


def _act(x: jnp.ndarray, config: LlamaConfig) -> jnp.ndarray:
    if config.hidden_act == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def _adapter_onehot(params: Params, adapter_ids, batch: int):
    """[B, n_adapters] one-hot from per-slot adapter ids (-1 -> all-zero row
    -> exact-zero delta -> base model); None when no adapters are loaded.
    Handles both layer layouts: the per-layer list and the pp-stacked dict
    (whose lora leaves carry a leading layer axis)."""
    layers = params["layers"]
    if isinstance(layers, dict):  # pp-stacked
        lora = layers.get("lora")
        if lora:
            n_a = next(iter(lora.values()))["A"].shape[1]  # [L, n, in, r]
            if adapter_ids is None:
                adapter_ids = jnp.full((batch,), -1, jnp.int32)
            return jax.nn.one_hot(adapter_ids, n_a, dtype=jnp.float32)
        return None
    for layer in layers:
        lora = layer.get("lora")
        if lora:
            n_a = next(iter(lora.values()))["A"].shape[0]
            if adapter_ids is None:
                adapter_ids = jnp.full((batch,), -1, jnp.int32)
            return jax.nn.one_hot(adapter_ids, n_a, dtype=jnp.float32)
    return None


def _run_passes(params: Params, config: LlamaConfig, x, kv_pages, table,
                stack):
    """Run the layer stack `config.n_passes` times over one set of weights.

    `stack(x, kv_pages, table) -> (x, kv_pages)` is one pass over the layers
    with `table` as the page table (or page ids) its K/V writes and reads go
    through.  One pass is that call and nothing else: the program a
    one-pass model lowers to does not change.

    A looped model runs a `lax.fori_loop` over the passes IN the program
    (one traced stack, not n_passes of them).  The cache of every layer
    holds n_passes x the pool's pages: pass u owns pages
    [u * pool, (u + 1) * pool), so the K/V row of (pass u, layer l) is
    layer l's array read through `table + u * pool` (the kernels address
    pages through the table: nothing is sliced or copied), written in
    place by the loop's carry.  A padded table entry (page 0) becomes pass
    u's own null page, which no sequence is ever given.  The final norm
    closes EVERY pass, the last included, so `_logits` applies none to a
    looped model.  The body is the same for every pass and reads nothing
    of the loop's index (the pass's table is carried): a body that chose
    by the index (`where(u > 0, norm(x), x)` at its top) computed other
    logits on the TPU than on the CPU and than the same passes unrolled
    (PERF.md section 6, PR 35).  The exit gate is not evaluated: the
    engine refuses early_exit_threshold < 1
    (engine/limits.resolve_serving)."""
    if not config.is_looped:
        return stack(x, kv_pages, table)
    first = kv_pages[0]
    pool = (first[0] if isinstance(first, tuple) else first).shape[0] \
        // config.n_passes

    def one_pass(_, carry):
        x, kv_pages, table = carry
        with jax.named_scope("loop_pass"):
            x, kv_pages = stack(x, kv_pages, table)
            return (_norm(x, params["final_norm"], config), kv_pages,
                    table + pool)

    x, kv_pages, _ = jax.lax.fori_loop(
        0, config.n_passes, one_pass, (x, kv_pages, table))
    return x, kv_pages


def transformer_block(
    layer: Params,
    x: jnp.ndarray,  # [B, T, h]
    positions: jnp.ndarray,  # [B, T]
    valid_len: jnp.ndarray,  # [B]
    config: LlamaConfig,
    onehot=None,  # LoRA adapter one-hot (or None = base weights)
    attention_fn=None,  # (q, k, v, valid_len, softcap) -> attn
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One transformer block (prefill form, pre-cache): returns
    (x_out, k, v) — the caller scatters K/V into its pages (prefill) or
    discards them (the pipeline-parallel layer_fn).  The single source of
    the block math: prefill and parallel/pipeline.py both call this, so
    rope/softcap/LoRA changes cannot drift between them."""
    B, T = x.shape[0], x.shape[1]
    residual = x
    h = _norm(x, layer["attn_norm"], config)
    with jax.named_scope("attention"):
        q, k, v = _qkv(layer, h, config, onehot)
        q = apply_rope(q, positions, config.rope_theta, config.rope_scaling)
        k = apply_rope(k, positions, config.rope_theta, config.rope_scaling)
        if attention_fn is None:
            attn = causal_prefill_attention(
                q, k, v, valid_len, config.attn_logit_softcap,
                scale=config.attn_scale, window=layer.get("attn_window"),
            )
        else:
            # pluggable path (SP ring attention); engines exclude it for
            # windowed/scaled configs at init
            attn = attention_fn(q, k, v, valid_len, config.attn_logit_softcap)
        attn_flat = attn.reshape(B, T, -1)
        attn = _maybe_add(
            dense(attn_flat, layer["wo"]),
            lora_delta(layer.get("lora"), "wo", attn_flat, onehot),
        )
    if config.sandwich_norms:
        attn = _norm(attn, layer["post_attn_norm"], config)
    x = residual + attn
    residual = x
    h = _norm(x, layer["mlp_norm"], config)
    out = _mlp(layer, h, config, onehot)
    if config.sandwich_norms:
        out = _norm(out, layer["post_mlp_norm"], config)
    return residual + out, k, v


def prefill(
    params: Params,
    config: LlamaConfig,
    tokens: jnp.ndarray,  # [B, T] padded prompt
    valid_len: jnp.ndarray,  # [B]
    kv_pages: List[jnp.ndarray],  # per layer [num_pages, 2, nkv, ps, d]
    page_ids: jnp.ndarray,  # [B, max_pages] pages owned by each sequence
    page_size: int,
    attention_fn=None,  # (q, k, v, valid_len, softcap) -> attn; SP engines
    # pass a shard_map-wrapped ring_attention here (parallel/ring_attention)
    adapter_ids: Optional[jnp.ndarray] = None,  # [B] LoRA ids (-1 = base)
) -> Tuple[jnp.ndarray, List[jnp.ndarray]]:
    """Process prompts, write their KV into the cache, return logits at the
    last valid token of each row: [B, vocab]."""
    # attention_fn=None flows through to transformer_block, whose default
    # branch passes scale= and window= — substituting the bare default here
    # would silently drop both (sliding-window layers would attend globally)
    B, T = tokens.shape
    onehot = _adapter_onehot(params, adapter_ids, B)
    positions = jnp.arange(T, dtype=jnp.int32)[None, :].repeat(B, axis=0)
    x = _embed(params, tokens, config)

    def stack(x, kv_pages, page_ids):
        new_pages = []
        for layer, pages in zip(params["layers"], kv_pages):
            x, k, v = transformer_block(
                layer, x, positions, valid_len, config,
                onehot=onehot, attention_fn=attention_fn,
            )
            # scatter the whole batch's K/V into its pages in one op
            pages = write_prompt_kv_batch(
                pages, k, v, page_ids, valid_len, page_size)
            new_pages.append(pages)
        return x, new_pages

    x, new_pages = _run_passes(params, config, x, kv_pages, page_ids, stack)
    last = jnp.maximum(valid_len - 1, 0)
    x_last = x[jnp.arange(B), last]  # [B, h]
    return _logits(params, x_last[:, None], config)[:, 0], new_pages


def chunk_transformer_block(
    layer: Params,
    pages,  # this layer's KV pages
    x: jnp.ndarray,  # [B, C, h]
    chunk_start: jnp.ndarray,  # [B]
    valid_len: jnp.ndarray,  # [B]
    page_ids: jnp.ndarray,  # [B, W]
    page_size: int,
    config: LlamaConfig,
    onehot=None,
) -> Tuple[jnp.ndarray, Any]:
    """One chunked-prefill transformer block: attend to the cached
    history + the chunk's causal prefix, then write the chunk's KV.  The
    SINGLE source of the chunk math — the sequential path
    (prefill_chunk) and the pipeline-parallel path (_pp_chunk_block)
    both call this, so their numerics cannot drift."""
    B, C = x.shape[0], x.shape[1]
    positions = chunk_start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    residual = x
    h = _norm(x, layer["attn_norm"], config)
    with jax.named_scope("attention"):
        q, k, v = _qkv(layer, h, config, onehot)
        q = apply_rope(q, positions, config.rope_theta, config.rope_scaling)
        k = apply_rope(k, positions, config.rope_theta, config.rope_scaling)
        attn = chunked_prefill_attention(
            q, k, v, pages, page_ids, chunk_start, valid_len,
            config.attn_logit_softcap,
            scale=config.attn_scale, window=layer.get("attn_window"),
        )
        attn_flat = attn.reshape(B, C, -1)
        attn = _maybe_add(
            dense(attn_flat, layer["wo"]),
            lora_delta(layer.get("lora"), "wo", attn_flat, onehot),
        )
    if config.sandwich_norms:
        attn = _norm(attn, layer["post_attn_norm"], config)
    x = residual + attn
    residual = x
    h = _norm(x, layer["mlp_norm"], config)
    out = _mlp(layer, h, config, onehot)
    if config.sandwich_norms:
        out = _norm(out, layer["post_mlp_norm"], config)
    x = residual + out
    pages = write_chunk_kv_batch(
        pages, k, v, page_ids, chunk_start, valid_len, page_size
    )
    return x, pages


def prefill_chunk(
    params: Params,
    config: LlamaConfig,
    tokens: jnp.ndarray,  # [B, C] one chunk of the prompt (padded)
    chunk_start: jnp.ndarray,  # [B] tokens already prefilled (history)
    valid_len: jnp.ndarray,  # [B] valid tokens within THIS chunk
    kv_pages: List[jnp.ndarray],
    page_ids: jnp.ndarray,  # [B, max_pages] the sequence's pages
    page_size: int,
    adapter_ids: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, List[jnp.ndarray]]:
    """One chunk of a chunked prefill: attends to the cached history plus
    the chunk's causal prefix, writes the chunk's KV into the cache, and
    returns logits at the chunk's last valid token.  history=0 makes this
    equivalent to (a window of) plain prefill; a prefix-cache hit just
    starts with chunk_start > 0 and the cached pages in page_ids."""
    B, C = tokens.shape
    onehot = _adapter_onehot(params, adapter_ids, B)
    x = _embed(params, tokens, config)

    def stack(x, kv_pages, page_ids):
        new_pages = []
        for layer, pages in zip(params["layers"], kv_pages):
            x, pages = chunk_transformer_block(
                layer, pages, x, chunk_start, valid_len, page_ids, page_size,
                config, onehot=onehot,
            )
            new_pages.append(pages)
        return x, new_pages

    x, new_pages = _run_passes(params, config, x, kv_pages, page_ids, stack)
    last = jnp.maximum(valid_len - 1, 0)
    x_last = x[jnp.arange(B), last]  # [B, h]
    return _logits(params, x_last[:, None], config)[:, 0], new_pages


def decode_step(
    params: Params,
    config: LlamaConfig,
    tokens: jnp.ndarray,  # [B] current tokens
    pos: jnp.ndarray,  # [B] their positions
    kv_pages: List[jnp.ndarray],
    page_table: jnp.ndarray,  # [B, max_pages]
    active: jnp.ndarray,  # [B] bool
    page_size: int,
    use_pallas: Optional[bool] = None,
    adapter_ids: Optional[jnp.ndarray] = None,  # [B] LoRA ids (-1 = base)
    attention_fn=None,  # fn(q,[B,nq,d], pages, page_table, seq_lens) —
    # e.g. ops.attention.make_sharded_paged_attention for tp>1
) -> Tuple[jnp.ndarray, List[jnp.ndarray]]:
    """One decode token per sequence; returns ([B, vocab] logits, new pages)."""
    if config.is_hybrid:
        # a table of several mixer kinds: `kv_pages` is the state pytree of
        # engine/kvcache.StateLayout, stepped by models/hybrid.py
        from . import hybrid

        return hybrid.decode_step(
            params, config, tokens, pos, kv_pages, page_table, active,
            page_size, use_pallas=use_pallas)
    B = tokens.shape[0]
    onehot = _adapter_onehot(params, adapter_ids, B)
    x = _embed(params, tokens, config)[:, None, :]  # [B,1,h]
    positions = pos[:, None]
    seq_lens = jnp.where(active, pos + 1, 0)
    # a sharded attention_fn means a cache sharded over the mesh: its write
    # stays the scatter GSPMD partitions (ops/attention.kv_write_path)
    page_kernel = None if attention_fn is None else False

    def stack(x, kv_pages, page_table):
        new_pages = []
        for layer, pages in zip(params["layers"], kv_pages):
            residual = x
            h = _norm(x, layer["attn_norm"], config)
            with jax.named_scope("attention"):
                q, k, v = _qkv(layer, h, config, onehot)
                q = apply_rope(q, positions, config.rope_theta, config.rope_scaling)
                k = apply_rope(k, positions, config.rope_theta, config.rope_scaling)
                pages = append_token_kv(
                    pages, k[:, 0], v[:, 0], page_table, pos, active,
                    page_size, page_kernel=page_kernel)
                window = layer.get("attn_window")
                if attention_fn is not None:
                    attn = attention_fn(q[:, 0], pages, page_table, seq_lens,
                                        window if window is not None
                                        else jnp.asarray(0, jnp.int32))
                else:
                    attn = paged_attention(
                        q[:, 0],
                        pages,
                        page_table,
                        seq_lens,
                        logit_softcap=config.attn_logit_softcap,
                        use_pallas=use_pallas,
                        scale=config.attn_scale,
                        window=window,
                    )
                attn_flat = attn.reshape(B, 1, -1)
                attn = _maybe_add(
                    dense(attn_flat, layer["wo"]),
                    lora_delta(layer.get("lora"), "wo", attn_flat, onehot),
                )
            if config.sandwich_norms:
                attn = _norm(attn, layer["post_attn_norm"], config)
            x = residual + attn
            residual = x
            h = _norm(x, layer["mlp_norm"], config)
            out = _mlp(layer, h, config, onehot)
            if config.sandwich_norms:
                out = _norm(out, layer["post_mlp_norm"], config)
            x = residual + out
            new_pages.append(pages)
        return x, new_pages

    x, new_pages = _run_passes(
        params, config, x, kv_pages, page_table, stack)
    return _logits(params, x, config)[:, 0], new_pages


def forward_ragged(
    params: Params,
    config: LlamaConfig,
    tokens: jnp.ndarray,  # [T] packed ragged token buffer
    token_seq: jnp.ndarray,  # [T] lane index per token (-1 = padding)
    token_pos: jnp.ndarray,  # [T] absolute position per token
    q_start: jnp.ndarray,  # [B] first packed index of each lane's slice
    q_len: jnp.ndarray,  # [B] slice length (0 = inactive lane)
    kv_start: jnp.ndarray,  # [B] tokens already cached before the slice
    kv_pages: List[jnp.ndarray],
    page_table: jnp.ndarray,  # [B, max_pages]
    page_size: int,
    last_idx: jnp.ndarray,  # [B] packed index of each lane's LAST token
    adapter_ids: Optional[jnp.ndarray] = None,  # [B] LoRA ids (-1 = base)
    attention_fn=None,  # sharded ragged attention for tp>1 (ops/attention)
    use_pallas: Optional[bool] = None,
    logits_at: Optional[jnp.ndarray] = None,  # [N] packed indices: return
    # logits at EVERY listed token instead of one per lane — the
    # speculative-verify surface (docs/kernels.md), where each position of
    # a K+1-token slice needs its own next-token distribution
    dense_stride: Optional[int] = None,  # static dense-packing stride for
    # the Pallas kernel (lanes share blocks; None = solo-block invariant)
    ragged_block: int = 1,  # static: the alignment slices are packed at
) -> Tuple[jnp.ndarray, List[jnp.ndarray]]:
    """The unified mixed-batch forward (docs/kernels.md): every lane
    contributes an arbitrary-length query slice — a whole prompt, a prompt
    chunk, or a single decode token — packed into one [T] buffer.  Each
    layer writes the slice's K/V into the paged cache, then runs ragged
    paged attention over the pages with the causal mask anchored at each
    lane's kv offset.  Returns ([B, vocab] logits at each lane's last
    token, new pages).

    The buffer runs through the stack as a [T, 1, h] token-batch (batch
    axis = packed tokens), which keeps every per-batch mechanism — LoRA
    one-hot selection, biases, qk-norm — per-TOKEN, so lanes with
    different adapters coexist in one mixed dispatch."""
    if config.is_hybrid:
        from . import hybrid

        return hybrid.forward_ragged(
            params, config, tokens, token_seq, token_pos, q_start, q_len,
            kv_start, kv_pages, page_table, page_size, last_idx,
            use_pallas=use_pallas, block=ragged_block)
    T = tokens.shape[0]
    valid = token_seq >= 0
    seq_ix = jnp.maximum(token_seq, 0)
    token_adapters = None
    if adapter_ids is not None:
        token_adapters = jnp.where(valid, adapter_ids[seq_ix], -1)
    onehot = _adapter_onehot(params, token_adapters, T)
    x = _embed(params, tokens, config)[:, None, :]  # [T, 1, h]
    positions = token_pos[:, None]
    runs = slice_runs(q_start, q_len, kv_start)
    page_kernel = None if attention_fn is None else False  # as decode_step

    def stack(x, kv_pages, page_table):
        new_pages = []
        for layer, pages in zip(params["layers"], kv_pages):
            residual = x
            h = _norm(x, layer["attn_norm"], config)
            with jax.named_scope("attention"):
                q, k, v = _qkv(layer, h, config, onehot)
                q = apply_rope(q, positions, config.rope_theta, config.rope_scaling)
                k = apply_rope(k, positions, config.rope_theta, config.rope_scaling)
                pages = write_ragged_kv(
                    pages, k[:, 0], v[:, 0], page_table, token_seq, token_pos,
                    page_size, runs=runs, page_kernel=page_kernel)
                window = layer.get("attn_window")
                if attention_fn is not None:
                    attn = attention_fn(
                        q[:, 0], pages, page_table, q_start, q_len, kv_start,
                        window if window is not None else jnp.asarray(0, jnp.int32))
                else:
                    attn = ragged_paged_attention(
                        q[:, 0], pages, page_table, q_start, q_len, kv_start,
                        logit_softcap=config.attn_logit_softcap,
                        use_pallas=use_pallas,
                        scale=config.attn_scale,
                        window=window,
                        dense_stride=dense_stride,
                    )
                attn_flat = attn.reshape(T, 1, -1)
                attn = _maybe_add(
                    dense(attn_flat, layer["wo"]),
                    lora_delta(layer.get("lora"), "wo", attn_flat, onehot),
                )
            if config.sandwich_norms:
                attn = _norm(attn, layer["post_attn_norm"], config)
            x = residual + attn
            residual = x
            h = _norm(x, layer["mlp_norm"], config)
            out = _mlp(layer, h, config, onehot)
            if config.sandwich_norms:
                out = _norm(out, layer["post_mlp_norm"], config)
            x = residual + out
            new_pages.append(pages)
        return x, new_pages

    x, new_pages = _run_passes(
        params, config, x, kv_pages, page_table, stack)
    if logits_at is not None:
        x_sel = x[logits_at, 0]  # [N, h]
        return _logits(params, x_sel[:, None], config)[:, 0], new_pages
    x_last = x[last_idx, 0]  # [B, h]
    return _logits(params, x_last[:, None], config)[:, 0], new_pages


# ---------------- pipeline-parallel execution (engine pp > 1) ----------------


def stack_layer_params(params: Params) -> Params:
    """Per-layer list -> stacked pytree with leading layer axis (sharded
    over the pipe mesh axis by parallel/sharding.stacked_layer_pspecs)."""
    out = dict(params)
    out["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *params["layers"])
    return out


def _pp_prefill_block(config: LlamaConfig, page_size: int):
    """One transformer block + prompt-KV scatter as a pipeline block_fn.
    Invalid (warm-up/drain) microbatches write to the null page (page 0)."""

    def block_fn(layer, pages_l, x, aux, valid):
        B, T = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
        valid_len = aux["valid_len"]
        x_out, k, v = transformer_block(
            layer, x, positions, valid_len, config,
            onehot=aux.get("onehot"))
        page_ids = jnp.where(valid, aux["page_ids"], 0)
        pages_l = write_prompt_kv_batch(
            pages_l, k, v, page_ids, valid_len, page_size)
        return x_out, pages_l

    return block_fn


def _pp_decode_block(config: LlamaConfig, page_size: int):
    """One decode step per sequence against this stage's paged cache.
    `live` folds in the pipeline validity mask, so warm-up/drain steps
    append to the null page and read zero-length sequences."""

    def block_fn(layer, pages_l, x, aux, valid):
        B = x.shape[0]
        pos, page_table = aux["pos"], aux["page_table"]
        onehot = aux.get("onehot")
        live = aux["live"] & valid
        positions = pos[:, None]
        residual = x
        h = _norm(x, layer["attn_norm"], config)
        with jax.named_scope("attention"):
            q, k, v = _qkv(layer, h, config, onehot)
            q = apply_rope(q, positions, config.rope_theta, config.rope_scaling)
            k = apply_rope(k, positions, config.rope_theta, config.rope_scaling)
            pages_l = append_token_kv(
                pages_l, k[:, 0], v[:, 0], page_table, pos, live, page_size,
                page_kernel=False)  # a stage's cache may be sharded over tp
            seq_lens = jnp.where(live, pos + 1, 0)
            attn = paged_attention(
                q[:, 0], pages_l, page_table, seq_lens,
                logit_softcap=config.attn_logit_softcap, use_pallas=False,
                scale=config.attn_scale, window=layer.get("attn_window"),
            )
            attn_flat = attn.reshape(B, 1, -1)
            attn_out = _maybe_add(
                dense(attn_flat, layer["wo"]),
                lora_delta(layer.get("lora"), "wo", attn_flat, onehot),
            )
        if config.sandwich_norms:
            attn_out = _norm(attn_out, layer["post_attn_norm"], config)
        x = residual + attn_out
        residual = x
        h = _norm(x, layer["mlp_norm"], config)
        out = _mlp(layer, h, config, onehot)
        if config.sandwich_norms:
            out = _norm(out, layer["post_mlp_norm"], config)
        return residual + out, pages_l

    return block_fn


def prefill_pp(
    params: Params,
    config: LlamaConfig,
    tokens: jnp.ndarray,  # [B, T]
    valid_len: jnp.ndarray,  # [B]
    kv_pages: jnp.ndarray,  # stacked [L, num_pages, 2, nkv, ps, d]
    page_ids: jnp.ndarray,  # [B, max_pages]
    page_size: int,
    mesh,
    n_microbatches: int,
    adapter_ids: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pipeline-parallel prefill: params["layers"] is the stacked pytree,
    stages stream microbatches GPipe-style (parallel/pipeline.py).
    Embedding and logits run pipe-replicated outside the staged stack."""
    from ..parallel.pipeline import pipeline_blocks

    B = tokens.shape[0]
    x = _embed(params, tokens, config)
    aux = {"valid_len": valid_len, "page_ids": page_ids}
    onehot = _adapter_onehot(params, adapter_ids, B)
    if onehot is not None:
        aux["onehot"] = onehot
    x, new_pages = pipeline_blocks(
        params["layers"], kv_pages, x, aux,
        _pp_prefill_block(config, page_size), mesh, n_microbatches,
    )
    last = jnp.maximum(valid_len - 1, 0)
    x_last = x[jnp.arange(B), last]
    return _logits(params, x_last[:, None], config)[:, 0], new_pages


def _pp_chunk_block(config: LlamaConfig, page_size: int):
    """One chunked-prefill transformer block as a pipeline block_fn: the
    chunk attends to this stage's cached history plus its own causal
    prefix, then writes its KV.  Warm-up/drain microbatches write to the
    null page and read zero history."""

    def block_fn(layer, pages_l, x, aux, valid):
        chunk_start = jnp.where(valid, aux["chunk_start"], 0)
        page_ids = jnp.where(valid, aux["page_ids"], 0)
        return chunk_transformer_block(
            layer, pages_l, x, chunk_start, aux["valid_len"], page_ids,
            page_size, config, onehot=aux.get("onehot"),
        )

    return block_fn


def prefill_chunk_pp(
    params: Params,
    config: LlamaConfig,
    tokens: jnp.ndarray,  # [B, C]
    chunk_start: jnp.ndarray,  # [B]
    valid_len: jnp.ndarray,  # [B]
    kv_pages: jnp.ndarray,  # stacked [L, ...]
    page_ids: jnp.ndarray,  # [B, W]
    page_size: int,
    mesh,
    n_microbatches: int,
    adapter_ids: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pipeline-parallel chunked prefill (engine pp>1): unlocks prompts
    beyond max_prefill_len AND prefix-cache hits under pp."""
    from ..parallel.pipeline import pipeline_blocks

    B = tokens.shape[0]
    x = _embed(params, tokens, config)
    aux = {"chunk_start": chunk_start, "valid_len": valid_len,
           "page_ids": page_ids}
    onehot = _adapter_onehot(params, adapter_ids, B)
    if onehot is not None:
        aux["onehot"] = onehot
    x, new_pages = pipeline_blocks(
        params["layers"], kv_pages, x, aux,
        _pp_chunk_block(config, page_size), mesh, n_microbatches,
    )
    last = jnp.maximum(valid_len - 1, 0)
    x_last = x[jnp.arange(B), last]
    return _logits(params, x_last[:, None], config)[:, 0], new_pages


def decode_step_pp(
    params: Params,
    config: LlamaConfig,
    tokens: jnp.ndarray,  # [B]
    pos: jnp.ndarray,  # [B]
    kv_pages: jnp.ndarray,  # stacked [L, ...]
    page_table: jnp.ndarray,  # [B, max_pages]
    active: jnp.ndarray,  # [B] bool
    page_size: int,
    mesh,
    n_microbatches: int,
    adapter_ids: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pipeline-parallel decode step (engine pp>1)."""
    from ..parallel.pipeline import pipeline_blocks

    x = _embed(params, tokens, config)[:, None, :]
    aux = {"pos": pos, "page_table": page_table, "live": active}
    onehot = _adapter_onehot(params, adapter_ids, tokens.shape[0])
    if onehot is not None:
        aux["onehot"] = onehot
    x, new_pages = pipeline_blocks(
        params["layers"], kv_pages, x, aux,
        _pp_decode_block(config, page_size), mesh, n_microbatches,
    )
    return _logits(params, x, config)[:, 0], new_pages


# ---------------- HF checkpoint loading ----------------

_HF_LAYER_MAP = {
    "input_layernorm.weight": "attn_norm",
    "self_attn.q_proj.weight": "wq",
    "self_attn.k_proj.weight": "wk",
    "self_attn.v_proj.weight": "wv",
    "self_attn.o_proj.weight": "wo",
    "self_attn.q_proj.bias": "bq",
    "self_attn.k_proj.bias": "bk",
    "self_attn.v_proj.bias": "bv",
    "self_attn.q_norm.weight": "q_norm",
    "self_attn.k_norm.weight": "k_norm",
    "post_attention_layernorm.weight": "mlp_norm",
    # Gemma-2 sandwich norms: HF's post_attention_layernorm is the
    # POST-attn norm and pre_feedforward_layernorm the pre-ffn norm; the
    # loader remaps below when the config is sandwich
    "pre_feedforward_layernorm.weight": "pre_ffn_norm_hf",
    "post_feedforward_layernorm.weight": "post_mlp_norm",
    # Ouro's sandwich norms (assumed names, benchmark/configs/ouro-2.6b.json):
    # input_layernorm_2 follows the mixer, post_attention_layernorm_2 the
    # feed-forward; post_attention_layernorm stays the pre-ffn norm
    "input_layernorm_2.weight": "post_attn_norm",
    "post_attention_layernorm_2.weight": "post_mlp_norm",
    "mlp.gate_proj.weight": "w_gate",
    "mlp.up_proj.weight": "w_up",
    "mlp.down_proj.weight": "w_down",
}

_TRANSPOSED = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}

#: a looped model's exit gate, Linear(hidden -> 1) with bias
_HF_EXIT_GATE = {"model.early_exit_gate.weight": "exit_gate_w",
                 "model.early_exit_gate.bias": "exit_gate_b"}


def load_hf_weights_streamed(model_dir: str, config: LlamaConfig,
                             weight_quant: str = "none",
                             stats: Optional[dict] = None) -> Params:
    """Streaming twin of :func:`load_hf_weights`: tensors are read from the
    safetensors shards ONE AT A TIME, transposed/quantized on the host and
    placed on device immediately, so peak host staging stays ~one tensor
    instead of the whole checkpoint (docs/coldstart.md).  With
    ``weight_quant="int8"`` the device only ever sees int8 + scales — an 8B
    load peaks near the QUANTIZED resident size plus one bf16 tensor,
    which is what makes cold start weight-I/O-bound on a warmed
    LocalModelCache volume instead of host-RAM-bound.

    `stats` (optional dict) is filled with the accounting the coldstart
    bench records: ``peak_host_bytes`` (largest simultaneous raw staging
    footprint), ``read_bytes`` (total checkpoint bytes streamed) and
    ``n_tensors``.

    MoE expert stacks are the one exception to strict streaming: a
    layer's experts buffer on the host until all E are seen (they must
    stack into one [E, in, out] tensor), then free."""
    from safetensors import safe_open

    if config.is_hybrid:
        from . import hybrid

        return hybrid.load_hf_weights_streamed(
            model_dir, config, weight_quant, stats)
    if weight_quant == "int8" and config.n_experts > 0:
        raise NotImplementedError("weight_quant over MoE experts")
    dtype = jnp.dtype(config.dtype)
    quant = weight_quant == "int8"
    files = sorted(
        os.path.join(model_dir, f)
        for f in os.listdir(model_dir)
        if f.endswith(".safetensors")
    )
    if not files:
        raise FileNotFoundError(f"no .safetensors files under {model_dir}")

    acct = {"peak_host_bytes": 0, "read_bytes": 0, "n_tensors": 0}
    held = {"bytes": 0}  # raw host staging currently alive (MoE buffers)

    def charge(nbytes: int) -> None:
        held["bytes"] += nbytes
        acct["peak_host_bytes"] = max(acct["peak_host_bytes"], held["bytes"])

    def to_jnp(arr: np.ndarray, transpose: bool) -> jnp.ndarray:
        if transpose:
            arr = arr.T
        return jnp.asarray(arr).astype(dtype)

    def to_jnp_q(arr: np.ndarray, transpose: bool, channel_axis: int = -1):
        if transpose:
            arr = arr.T
        axis = 1 - (channel_axis % 2)
        qd = quantize_array_np(arr, axis=axis)
        return {"q": jnp.asarray(qd["q"]), "s": jnp.asarray(qd["s"])}

    params: Params = {"layers": [dict() for _ in range(config.n_layers)]}
    # MoE staging: (layer, proj) -> {expert_index: raw np tensor}
    moe_pending: Dict[tuple, Dict[int, np.ndarray]] = {}
    layer_re = re.compile(r"^model\.layers\.(\d+)\.(.+)$")
    expert_re = re.compile(r"^block_sparse_moe\.experts\.(\d+)\.(w[123])\.weight$")
    _MOE_PROJ = {"w1": "w_gate", "w3": "w_up", "w2": "w_down"}

    def place(name: str, arr: np.ndarray) -> bool:
        """Route ONE checkpoint tensor to its pytree slot, on device.
        Returns True when the raw host tensor was RETAINED (an MoE expert
        buffered until its stack completes) — the caller keeps its bytes
        charged against the staging footprint."""
        if name == "model.embed_tokens.weight":
            params["embed"] = (
                to_jnp_q(arr, False, channel_axis=0)
                if quant and config.tie_word_embeddings
                else to_jnp(arr, False)
            )
            return False
        if name == "model.norm.weight":
            params["final_norm"] = to_jnp(arr, False)
            return False
        if name == "lm_head.weight":
            if not config.tie_word_embeddings:
                params["lm_head"] = (
                    to_jnp_q(arr, True) if quant else to_jnp(arr, True))
            return False
        if name in _HF_EXIT_GATE and config.is_looped:
            params[_HF_EXIT_GATE[name]] = to_jnp(arr, arr.ndim == 2)
            return False
        m = layer_re.match(name)
        if m is None:
            return False  # rotary inv_freq etc.: derived, never loaded
        i, suffix = int(m.group(1)), m.group(2)
        if i >= config.n_layers:
            return False
        layer = params["layers"][i]
        if config.n_experts > 0:
            if suffix == "block_sparse_moe.gate.weight":
                layer["router"] = to_jnp(arr, True)
                return False
            em = expert_re.match(suffix)
            if em is not None:
                e, proj = int(em.group(1)), _MOE_PROJ[em.group(2)]
                pending = moe_pending.setdefault((i, proj), {})
                pending[e] = arr
                if len(pending) == config.n_experts:
                    stacked = np.stack(
                        [pending[k].T for k in range(config.n_experts)])
                    layer[proj] = jnp.asarray(stacked).astype(dtype)
                    # release every buffered expert INCLUDING this one —
                    # hence retained=True so the caller doesn't re-release
                    held["bytes"] -= sum(t.nbytes for t in pending.values())
                    del moe_pending[(i, proj)]
                return True
        ours = _HF_LAYER_MAP.get(suffix)
        if ours is None:
            return False
        if quant and ours in LINEAR_KEYS:
            layer[ours] = to_jnp_q(arr, True)
        else:
            layer[ours] = to_jnp(arr, ours in _TRANSPOSED)
        return False

    for path in files:
        with safe_open(path, framework="numpy") as f:
            for name in f.keys():
                arr = f.get_tensor(name)
                acct["read_bytes"] += arr.nbytes
                acct["n_tensors"] += 1
                charge(arr.nbytes)
                retained = place(name, arr)
                if not retained:
                    held["bytes"] -= arr.nbytes
                del arr

    if moe_pending:
        missing = sorted(moe_pending)
        raise ValueError(
            f"checkpoint is missing MoE experts for (layer, proj): {missing[:4]}")
    for i, layer in enumerate(params["layers"]):
        if config.sandwich_norms and "pre_ffn_norm_hf" in layer:
            # Gemma-2's names (Ouro's map straight to ours)
            layer["post_attn_norm"] = layer.pop("mlp_norm")
            layer["mlp_norm"] = layer.pop("pre_ffn_norm_hf")
        elif not config.sandwich_norms:
            layer.pop("pre_ffn_norm_hf", None)
            layer.pop("post_mlp_norm", None)
            layer.pop("post_attn_norm", None)
        if config.sliding_window > 0:
            layer["attn_window"] = jnp.asarray(
                config.layer_window(i), jnp.int32)
    if stats is not None:
        stats.update(acct)
    return params


def load_hf_weights(model_dir: str, config: LlamaConfig,
                    weight_quant: str = "none") -> Params:
    """Load a local HuggingFace safetensors checkpoint (no torch needed:
    safetensors.numpy) into the functional param pytree.  HF Linear stores
    [out, in]; our layout is [in, out], hence the transposes.

    weight_quant="int8" quantizes tensor-by-tensor ON THE HOST before
    device placement, so an 8B load peaks at one bf16 tensor of host RAM
    extra — the device only ever sees int8 + scales."""
    from safetensors import safe_open

    if weight_quant == "int8" and config.n_experts > 0:
        raise NotImplementedError("weight_quant over MoE experts")
    dtype = jnp.dtype(config.dtype)
    files = sorted(
        os.path.join(model_dir, f)
        for f in os.listdir(model_dir)
        if f.endswith(".safetensors")
    )
    if not files:
        raise FileNotFoundError(f"no .safetensors files under {model_dir}")
    tensors: Dict[str, np.ndarray] = {}
    for path in files:
        with safe_open(path, framework="numpy") as f:
            for name in f.keys():
                tensors[name] = f.get_tensor(name)

    def to_jnp(arr: np.ndarray, transpose: bool) -> jnp.ndarray:
        if transpose:
            arr = arr.T
        return jnp.asarray(arr).astype(dtype)

    def to_jnp_q(arr: np.ndarray, transpose: bool, channel_axis: int = -1):
        """Host-quantize, then place: int8 + float32 scale on device."""
        if transpose:
            arr = arr.T
        axis = 1 - (channel_axis % 2)  # reduce over the non-channel axis
        qd = quantize_array_np(arr, axis=axis)
        return {"q": jnp.asarray(qd["q"]), "s": jnp.asarray(qd["s"])}

    quant = weight_quant == "int8"
    params: Params = {
        "embed": (
            to_jnp_q(tensors["model.embed_tokens.weight"], False, channel_axis=0)
            if quant and config.tie_word_embeddings
            else to_jnp(tensors["model.embed_tokens.weight"], False)
        ),
        "final_norm": to_jnp(tensors["model.norm.weight"], False),
        "layers": [],
    }
    if "lm_head.weight" in tensors and not config.tie_word_embeddings:
        params["lm_head"] = (
            to_jnp_q(tensors["lm_head.weight"], True) if quant
            else to_jnp(tensors["lm_head.weight"], True)
        )
    if config.is_looped:
        for hf_name, ours in _HF_EXIT_GATE.items():
            params[ours] = to_jnp(tensors[hf_name], tensors[hf_name].ndim == 2)
    for i in range(config.n_layers):
        prefix = f"model.layers.{i}."
        layer: Params = {}
        for hf_suffix, ours in _HF_LAYER_MAP.items():
            key = prefix + hf_suffix
            if key in tensors:
                if quant and ours in LINEAR_KEYS:
                    layer[ours] = to_jnp_q(tensors[key], True)
                else:
                    layer[ours] = to_jnp(tensors[key], ours in _TRANSPOSED)
        if config.sandwich_norms and "pre_ffn_norm_hf" in layer:
            # Gemma-2 norm remap: HF post_attention_layernorm is the
            # POST-attn norm (our "post_attn_norm"); pre_feedforward is
            # the pre-ffn norm (our "mlp_norm" slot).  Ouro's names map
            # straight to ours.
            layer["post_attn_norm"] = layer.pop("mlp_norm")
            layer["mlp_norm"] = layer.pop("pre_ffn_norm_hf")
        elif not config.sandwich_norms:
            layer.pop("pre_ffn_norm_hf", None)
            layer.pop("post_mlp_norm", None)
            layer.pop("post_attn_norm", None)
        if config.sliding_window > 0:
            layer["attn_window"] = jnp.asarray(
                config.layer_window(i), jnp.int32)
        if config.n_experts > 0:
            # MixtralForCausalLM: block_sparse_moe.gate + per-expert w1/w3/w2
            # (HF w1=gate, w3=up, w2=down; Linear stores [out, in] -> stack
            # experts then transpose to our [E, in, out] layout)
            moe_prefix = prefix + "block_sparse_moe."
            layer["router"] = to_jnp(tensors[moe_prefix + "gate.weight"], True)
            for hf_name, ours in (("w1", "w_gate"), ("w3", "w_up"), ("w2", "w_down")):
                stacked = np.stack(
                    [
                        tensors[f"{moe_prefix}experts.{e}.{hf_name}.weight"].T
                        for e in range(config.n_experts)
                    ]
                )
                layer[ours] = jnp.asarray(stacked).astype(dtype)
        params["layers"].append(layer)
    return params
