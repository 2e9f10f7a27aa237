"""Device mesh + sharding rules for the generative engine.

Megatron-style tensor parallelism expressed as PartitionSpecs over a
("data", "model") mesh; XLA inserts the all-reduces (row-parallel wo/w_down
contractions) and all-gathers (vocab-sharded logits) over ICI.

Axes:
- data:  engine decode slots (DP) — batch dimension of decode/prefill
- model: attention heads / MLP hidden / vocab (TP); KV pages shard their
  head axis so paged attention never reshards.

The reference reaches TP/DP through vLLM flags wired by the controller
(SURVEY.md §2.3); here the mesh IS the backend — no NCCL/Ray analogue
needed inside a slice.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.llama import LlamaConfig

DATA_AXIS = "data"
SEQ_AXIS = "seq"
PIPE_AXIS = "pipe"
MODEL_AXIS = "model"


def create_mesh(
    tp: int = 1, dp: int = 1, sp: int = 1, pp: int = 1,
    devices: Optional[list] = None,
) -> Mesh:
    """(dp, sp, pp, tp) mesh. TP should map to ICI-adjacent devices: jax
    device order within a slice is topology-contiguous, so tp is the
    fastest-varying axis; pipe sits just outside it so each stage's tp
    group is contiguous and the stage->stage ppermute hop is one step (or
    crosses DCN exactly once between pods); the seq axis (ring-attention
    sequence parallelism) sits outside pipe."""
    devices = devices if devices is not None else jax.devices()
    need = tp * dp * sp * pp
    if need > len(devices):
        raise ValueError(
            f"mesh {dp}x{sp}x{pp}x{tp} needs {need} devices, have {len(devices)}"
        )
    grid = np.asarray(devices[:need]).reshape(dp, sp, pp, tp)
    return Mesh(grid, (DATA_AXIS, SEQ_AXIS, PIPE_AXIS, MODEL_AXIS))


def validate_tp(config: LlamaConfig, tp: int) -> None:
    """The sizes tp divides (a hybrid model is refused tp>1 before it comes
    here: engine/limits.py)."""
    if config.n_heads % tp != 0:
        raise ValueError(f"n_heads={config.n_heads} not divisible by tp={tp}")
    if config.n_kv_heads % tp != 0:
        raise ValueError(
            f"n_kv_heads={config.n_kv_heads} not divisible by tp={tp}; "
            "KV-head replication is not implemented yet"
        )
    if config.n_experts > 0:
        if config.n_experts % tp != 0:
            raise ValueError(
                f"n_experts={config.n_experts} not divisible by tp={tp} "
                "(experts shard over the model axis)"
            )
    elif config.intermediate_size % tp != 0:
        raise ValueError(f"intermediate_size not divisible by tp={tp}")


def param_pspecs(config: LlamaConfig) -> Dict[str, Any]:
    """PartitionSpec pytree matching models.llama param pytree."""
    if config.is_hybrid:
        # tp=1 only (engine/limits.py): every leaf of every row replicated
        from ..models import hybrid

        specs = {
            "embed": P(), "final_norm": P(),
            "layers": [
                {name: P() for name in hybrid.layer_param_shapes(config, spec)}
                for spec in config.layer_table()],
        }
        if config.norm_type == "layernorm":
            specs["final_norm_b"] = P()
        if not config.tie_word_embeddings:
            specs["lm_head"] = P()
        return specs
    layer = {
        "attn_norm": P(),
        "wq": P(None, MODEL_AXIS),  # column parallel (heads)
        "wk": P(None, MODEL_AXIS),
        "wv": P(None, MODEL_AXIS),
        "wo": P(MODEL_AXIS, None),  # row parallel -> psum by XLA
        "mlp_norm": P(),
        "w_gate": P(None, MODEL_AXIS),
        "w_up": P(None, MODEL_AXIS),
        "w_down": P(MODEL_AXIS, None),
    }
    if config.n_experts > 0:
        # expert parallelism: the expert dim shards over `model`; XLA
        # psums the masked combine across expert shards (specs owned by
        # the MoE op so engine sharding can't drift from its contract)
        from ..models.moe import moe_param_pspecs

        layer.update(moe_param_pspecs())
    if config.attention_bias:
        layer.update({"bq": P(MODEL_AXIS), "bk": P(MODEL_AXIS), "bv": P(MODEL_AXIS)})
    if config.qk_norm:
        # per-head norm weights are [head_dim] — tiny, replicated
        layer.update({"q_norm": P(), "k_norm": P()})
    if config.sandwich_norms:
        layer.update({"post_attn_norm": P(), "post_mlp_norm": P()})
    if config.sliding_window > 0:
        layer.update({"attn_window": P()})
    specs: Dict[str, Any] = {
        "embed": P(MODEL_AXIS, None),  # vocab-sharded
        "final_norm": P(),
        "layers": [dict(layer) for _ in range(config.n_layers)],
    }
    if not config.tie_word_embeddings:
        specs["lm_head"] = P(None, MODEL_AXIS)  # logits vocab-sharded -> gather
    if config.is_looped:
        # the exit gate, Linear(h -> 1): tiny, replicated
        specs.update({"exit_gate_w": P(), "exit_gate_b": P()})
    return specs


def kv_pages_pspec() -> P:
    """[num_pages, 2, n_kv, ps, d] — shard KV heads over model axis."""
    return P(None, None, MODEL_AXIS, None, None)


def draft_table_pspec() -> P:
    """[B, V] speculative-decoding bigram draft table — lane rows over
    the model axis.  This is the spelling GSPMD propagates onto the
    table from the embedding/lm_head it interacts with inside
    mixed_decode (a fully-replicated constraint is treated as
    UNconstrained and re-spelled); the engine commits the host-seeded
    table to the same spelling so refresh-built and dispatch-output
    tables share one jit signature (the donated-kv_pages settle lesson,
    tests/test_retrace_budget.py)."""
    return P(MODEL_AXIS, None)


def stacked_kv_pages_pspec() -> P:
    """[L, num_pages, 2, n_kv, ps, d] — pipeline mode: the layer axis
    shards over pipe (each stage holds its own layers' KV) and the KV-head
    axis over model, so pp composes with tp without resharding."""
    return P(PIPE_AXIS, None, None, MODEL_AXIS, None, None)


def stacked_layer_pspecs(config: LlamaConfig, stacked_layers=None,
                         layer_specs=None) -> dict:
    """Spec pytree for PP-stacked layer params: each leaf takes its
    megatron TP spec from param_pspecs with the pipe axis prepended on the
    new leading layer dim — so pp>1 composes with tp>1 (the pipeline
    shard_map is manual over `pipe` only; XLA inserts the TP collectives
    inside each stage as it does for pp==1).

    With `stacked_layers` (the actual stacked pytree), int8-quantized
    {"q","s"} leaves get matched specs: q keeps the weight's spec, s
    follows the output channel — both with pipe prepended (pp x
    weight_quant)."""
    from ..models.quant import is_quantized

    if layer_specs is None:
        layer_specs = param_pspecs(config)["layers"][0]
    out = {}
    for k, spec in layer_specs.items():
        leaf = None if stacked_layers is None else stacked_layers.get(k)
        if leaf is not None and is_quantized(leaf):
            # same rule as the flat path, with pipe prepended to each part
            flat = quant_leaf_specs(spec, k)
            out[k] = {name: P(PIPE_AXIS, *sub)
                      for name, sub in flat.items()}
        else:
            out[k] = P(PIPE_AXIS, *spec)
    return out


def quant_leaf_specs(weight_spec: P, key=None) -> dict:
    """THE rule for int8-quantized {"q","s"} leaves: q takes the plain
    weight's spec; s follows the output channel (per-output-channel
    scales shard with the output; per-row embed scales shard with the
    vocab).  Every spec builder — flat, stacked/pp — derives from here."""
    if key == "embed":
        s_spec = P(weight_spec[0]) if len(weight_spec) > 0 else P()
    else:
        s_spec = P(weight_spec[1]) if len(weight_spec) > 1 else P()
    return {"q": weight_spec, "s": s_spec}


def expand_quant_specs(p, s, key=None):
    """Match a spec pytree to a param pytree that may hold int8-quantized
    {"q","s"} leaves (quant_leaf_specs is the per-leaf rule)."""
    from ..models.quant import is_quantized

    if isinstance(s, P):
        if is_quantized(p):
            return quant_leaf_specs(s, key)
        return s
    if isinstance(p, dict):
        return {k: expand_quant_specs(p[k], s[k], k) for k in p}
    if isinstance(p, list):
        return [expand_quant_specs(pi, si) for pi, si in zip(p, s)]
    return s



def shard_params(params, config: LlamaConfig, mesh: Mesh):
    """Place a param pytree onto the mesh according to param_pspecs."""
    specs = expand_quant_specs(params, param_pspecs(config))
    return jax.tree.map(
        lambda arr, spec: jax.device_put(arr, NamedSharding(mesh, spec)),
        params,
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def init_params_on_mesh(config: LlamaConfig, rng: jax.Array, mesh: Mesh,
                        weight_quant: str = "none"):
    """Random-initialized params created SHARDED over `mesh` (never staged
    whole on one device and then moved — models/llama.init_params)."""
    from ..models import llama

    init = partial(llama.init_params, config, weight_quant=weight_quant)
    specs = expand_quant_specs(jax.eval_shape(init, rng), param_pspecs(config))
    shardings = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), specs,
        is_leaf=lambda x: isinstance(x, P))
    return init(rng, shardings=shardings)


def kv_pages_sharding(mesh: Mesh) -> NamedSharding:
    """The cache's canonical sharding (engine/kvcache.init_kv_pages creates
    the pages directly on it)."""
    return named_canonical(mesh, kv_pages_pspec())


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def canonical_pspec(mesh: Mesh, spec: P) -> P:
    """Spell `spec` the way GSPMD spells program-OUTPUT shardings: axis
    names with mesh extent 1 drop to None, trailing Nones trim (observed:
    P(None, None, 'model', None, None) comes back as P() on a tp=1 mesh
    and as P(None, None, 'model') on tp=2).

    Matters for long-lived DONATED buffers (the KV cache): they are fed
    back into the next dispatch, so the init-time sharding must be spelled
    exactly as the program outputs it or the second dispatch sees a "new"
    input signature and every cache-carrying program recompiles once (the
    "donated kv_pages layout settles" retrace, pinned away by
    tests/test_retrace_budget.py)."""

    def keep(ax):
        if ax is None:
            return None
        if isinstance(ax, tuple):
            kept = tuple(a for a in ax if mesh.shape[a] > 1)
            return kept if kept else None
        return ax if mesh.shape[ax] > 1 else None

    parts = [keep(ax) for ax in spec]
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def named_canonical(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, canonical_pspec(mesh, spec))
