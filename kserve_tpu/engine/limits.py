"""What a model cannot be served with: the feature matrix, as ONE table.

A row is a setting that was asked for and, for each kind of model that
cannot have it yet, why (ROADMAP.md Queue R names the mechanisms).  The
kinds are read from the model's per-layer table (`model_kinds`): a new
family is refused what its layers' kinds are.  What was asked for explicitly
is refused at start-up, by name (`resolve_serving`); what was left at its
default is resolved to off, with a log line; request-time features that run
the legacy programs are refused at submit (`check_request`).
"""

from __future__ import annotations

from typing import Dict

from ..logging import logger
from ..ops.attention import dense_stride_for
from ..parallel import sharding as shd


def model_kinds(model_config) -> Dict[str, str]:
    """The kinds the rows apply to that this model is, each with its name
    in a refusal: `looped` (the stack runs several passes a token), `hybrid`
    (a per-layer table of mixers: the forward is models/hybrid.py, which
    runs `mixed` only, on one chip, in bf16), `lane_state` (a lane holds
    state that pages do not carry: recurrent slots, window rings), `experts`,
    `windows`."""
    table = model_config.layer_table()
    writes = {row.writes for row in table}
    family = ("a model with latent-attention layers and routed experts"
              if "latent_kv" in writes else
              "a model with Mamba-1 / Mamba-2 / delta-rule / "
              "short-convolution / window / shared-cache layers")
    kinds = {
        "looped": model_config.n_passes > 1 and (
            f"a looped model ({model_config.n_passes} passes over shared "
            "weights)"),
        "hybrid": model_config.is_hybrid and family,
        "lane_state": bool(writes & {"recurrent", "window_kv"}) and family,
        "experts": any(row.ffn == "experts" for row in table) and (
            "a model with expert layers"),
        "windows": (model_config.sliding_window > 0
                    or model_config.query_pre_attn_scalar is not None) and (
            "a model with sliding windows or an attention-scale override"),
    }
    return {kind: name for kind, name in kinds.items() if name}


def resolve_serving(model_config, engine_config, role: str = "both",
                    shapes=None, lora: bool = False) -> None:
    """Refuse what `model_config` cannot be served with under
    `engine_config` (NotImplementedError naming every refused setting, then
    ValueError for a size that does not divide), and resolve the prefix
    cache to off for a model whose lanes hold state beside their pages.
    `shapes`, the engine's DispatchShapes, says which regime the sizes
    admit; a server that has no sizes yet calls without, and the engine
    judges the rows over sizes when it is built."""
    model, cfg = model_config, engine_config
    mixed_ok = shapes.admits_mixed(cfg) if shapes is not None else None
    # the engine would step through the legacy programs
    legacy = cfg.use_ragged is False or (
        cfg.use_ragged is None and mixed_ok is False)
    spec = cfg.spec_decode_k is not None
    #: (the setting as asked for, named, or falsy), {kind of model that
    #: cannot have it: why}; `any`: every model
    table = (
        (model.early_exit_threshold < 1.0 and (
            f"early_exit_threshold={model.early_exit_threshold} < 1"),
         {"looped": "per-token early exit: the lanes of one dispatch would "
                    "run different numbers of passes"}),
        (cfg.tp > 1 and "tp>1",
         {"hybrid": "tp>1 over a hybrid model: its parameters, per-lane "
                    "state and latent pages have no sharding rules yet, nor "
                    "has a chip's share of the experts"}),
        (cfg.pp > 1 and "pp>1",
         {"looped": "a stage boundary inside the loop over passes",
          "hybrid": "staged layers assume one kind of layer"}),
        (cfg.sp > 1 and "sp>1",
         {"looped": "ring-attention prefill under the loop over passes is "
                    "untested",
          "hybrid": "ring-attention prefill is over K and V per head",
          "windows": "ring-attention prefill does not support sliding "
                     "windows or attention-scale overrides yet"}),
        (cfg.pp > 1 and cfg.sp > 1 and "pp>1 with sp>1",
         {"any": "they do not compose yet"}),
        (cfg.kv_quant != "none" and f"kv_quant={cfg.kv_quant}",
         {"hybrid": "rings, slots and latent rows have no scales"}),
        (cfg.weight_quant != "none" and f"weight_quant={cfg.weight_quant}",
         {"hybrid": "int8 over the mixers', the experts' and the latent "
                    "projections"}),
        (spec and "spec_decode_k",
         {"hybrid": "a rejected draft rewinds kv_len, recurrent state has "
                    "no rewind; the dense verify program has no attention "
                    "over latent pages",
          **({"any": "requires the unified ragged (mixed) path; it does "
                     "not compose with use_ragged=False, pp>1 or sp>1"}
             if legacy else {})}),
        ((cfg.kv_offload != "none" or cfg.kv_persist_dir) and (
            "kv_offload / kv_persist_dir"),
         {"hybrid": "tier offload, page-in and the persistent prefix store "
                    "move K/V pages only"}),
        (cfg.prefix_cache and "prefix_cache",
         {"lane_state": "a prefix's pages do not hold the recurrent state "
                        "or the rings at its boundary"}),
        (legacy and (
            "use_ragged=False" if cfg.use_ragged is False else
            f"max_batch_size x the {shapes.align}-token slice alignment "
            "past the largest prefill bucket"),
         {"hybrid": "a hybrid model runs the mixed program only: the "
                    "legacy programs assume one kind of layer"}),
        (cfg.use_ragged and mixed_ok is False and "use_ragged=True",
         {"any": "requires pp==1, sp==1 and max_batch_size (x the kernel's "
                 "block alignment) <= the largest prefill bucket; set "
                 "use_ragged=None/False for this topology"}),
        (role != "both" and f"role={role}",
         {"hybrid": "the P/D wire ships K/V pages only"}),
        (lora and "lora_adapters",
         {"hybrid": "LoRA adapters over a hybrid model",
          "experts": "LoRA over MoE layers is not supported yet"}),
    )
    kinds = {**model_kinds(model), "any": ""}
    refused, cited = [], []
    for named, whys in table:
        why = "; ".join(w for kind, w in whys.items() if kind in kinds)
        if named and why:
            refused.append(f"{named} ({why})")
            cited += [kinds[k] for k in whys if kinds.get(k)]
    if refused:
        whom = " for " + "; ".join(dict.fromkeys(cited)) if cited else ""
        raise NotImplementedError(
            f"not supported yet{whom}: " + "; ".join(refused))
    shd.validate_tp(model, cfg.tp)
    if cfg.pp > 1 and model.n_layers % cfg.pp != 0:
        raise ValueError(
            f"n_layers={model.n_layers} not divisible by pp={cfg.pp}")
    spec_k, lanes = cfg.spec_decode_k, cfg.max_batch_size
    if spec and spec_k < 0:
        raise ValueError(f"spec_decode_k must be >= 0, got {spec_k}")
    if spec and shapes is not None:
        stride = dense_stride_for(spec_k + 1, shapes.align)
        if shapes.align > 1 and (lanes * stride) % shapes.align:
            raise ValueError(
                "spec_decode_k on the Pallas kernel path needs "
                f"max_batch_size * padded-slice stride ({lanes}*{stride}) "
                f"to be a multiple of the {shapes.align}-token block")
        # the [B, V] draft table shards lane rows over the model axis
        # (sharding.draft_table_pspec): an indivisible batch would only
        # surface as a JAX sharding error at the first dense dispatch
        if lanes % cfg.tp:
            raise ValueError(
                f"spec_decode_k needs max_batch_size ({lanes}) divisible by "
                f"the tensor-parallel mesh axis ({cfg.tp}): the draft table "
                "shards lane rows over it")
    if "lane_state" in kinds and cfg.prefix_cache is None:
        cfg.prefix_cache = False
        logger.info(
            "hybrid model: prefix cache adoption resolved to OFF (snapshots "
            "of recurrent state and of rings are not implemented); "
            "speculative decoding, tier offload, the P/D wire of pages, "
            "logprobs and penalties lanes are refused by name")


def check_request(model_config, params, kv_wire: bool = False) -> None:
    """The request-time rows: logprobs and penalties lanes run the legacy
    programs, and the P/D wire ships pages: not a hybrid lane's whole state."""
    if not model_config.is_hybrid:
        return
    if kv_wire:
        raise ValueError(
            "the P/D wire of KV pages is not supported for a model with "
            "recurrent state or latent pages")
    if params is not None and (
            params.has_penalties or params.logprobs is not None):
        raise ValueError(
            "logprobs and sampling penalties run the legacy programs, "
            "which a model with recurrent state or latent pages does "
            "not have")
