"""Sparse mixture-of-experts feed-forward: a router over gated experts.

Two routers (`MoEConfig.router`):

- `softmax` (Mixtral): the top-k logits, softmaxed among themselves;
- `sigmoid` (the DeepSeek-V3 family's `noaux_tc`, one group): scores
  `s = sigmoid(x W_r)` in float32; a learned bias `b`
  (`e_score_correction_bias`) is added to CHOOSE the top-k and plays no part
  in the weights, which are the chosen scores, renormalised to sum 1
  (`norm_topk`) and multiplied by `scale` (`routed_scaling_factor`).

A `shared` expert, where the model has one, takes every token beside the
routed ones.

ONE compute path for every router, packed step and decode alike
(`routed_experts`): the (token, expert) pairs that were routed are sorted
by expert, each expert multiplies the contiguous rows that chose it
(`jax.lax.ragged_dot`, a grouped matmul: on the TPU a kernel whose FLOPs
and weight reads follow the rows, expert by expert; experts no row chose
are not read), and the rows go back to their tokens weighted.  Nothing of
size [tokens, experts, width] exists.  Rows of padding (`valid` False) are
given to no expert.

Experts live on stacked tensors [n_experts, ...].  `moe_param_pspecs`
shards that axis over the `model` mesh axis, which GSPMD partitions where
`ragged_dot` lowers to plain XLA (the CPU); a chip's share of the experts
on the TPU (an expert layer told which experts it holds) is ROADMAP work.

Role parity: vLLM's fused MoE path (SURVEY.md §2.3 Expert parallel row).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .quant import dense


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8
    top_k: int = 2
    hidden_size: int = 64
    intermediate_size: int = 128  # one expert's width
    router: str = "softmax"  # or "sigmoid"
    norm_topk: bool = True  # sigmoid router: weights renormalised to sum 1
    scale: float = 1.0  # sigmoid router: routed_scaling_factor
    shared: bool = False  # a shared expert beside the routed ones


def moe_config_of(config) -> MoEConfig:
    """The expert layers of a models/llama.LlamaConfig."""
    return MoEConfig(
        n_experts=config.n_experts,
        top_k=config.n_experts_per_tok,
        hidden_size=config.hidden_size,
        intermediate_size=(config.moe_intermediate_size
                           or config.intermediate_size),
        router=config.moe_router,
        norm_topk=config.norm_topk_prob,
        scale=config.routed_scaling_factor,
        shared=config.n_shared_experts > 0,
    )


def moe_param_shapes(config: MoEConfig) -> Dict[str, tuple]:
    """{name: shape} of one expert layer's feed-forward."""
    E, h, f = config.n_experts, config.hidden_size, config.intermediate_size
    shapes = {"router": (h, E), "w_gate": (E, h, f), "w_up": (E, h, f),
              "w_down": (E, f, h)}
    if config.router == "sigmoid":
        shapes["router_bias"] = (E,)
    if config.shared:
        shapes.update({"shared_gate": (h, f), "shared_up": (h, f),
                       "shared_down": (f, h)})
    return shapes


def init_moe_params(config: MoEConfig, rng: jax.Array, scale: float = 0.02,
                    dtype=jnp.float32) -> Dict[str, Any]:
    shapes = moe_param_shapes(config)
    keys = jax.random.split(rng, len(shapes))
    out = {}
    for (name, shape), key in zip(sorted(shapes.items()), keys):
        if name == "router_bias":  # chooses only; zero for random weights
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            out[name] = (jax.random.normal(key, shape, jnp.float32)
                         * scale).astype(dtype)
    return out


@jax.named_scope("router")
def route(params: Dict[str, Any], x: jnp.ndarray,
          config: MoEConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [N, h] -> (weights [N, k] float32, experts [N, k] int32)."""
    logits = jnp.dot(x, params["router"],
                     preferred_element_type=jnp.float32)  # [N, E]
    if config.router == "softmax":
        top, selected = jax.lax.top_k(logits, config.top_k)
        return jax.nn.softmax(top, axis=-1), selected
    if config.router != "sigmoid":
        raise ValueError(f"unknown router {config.router!r}")
    scores = jax.nn.sigmoid(logits)
    _, selected = jax.lax.top_k(
        scores + params["router_bias"].astype(jnp.float32), config.top_k)
    weights = jnp.take_along_axis(scores, selected, axis=-1)
    if config.norm_topk:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
    return weights * config.scale, selected


@jax.named_scope("experts")
def routed_experts(params: Dict[str, Any], x: jnp.ndarray,
                   weights: jnp.ndarray, selected: jnp.ndarray,
                   n_experts: int, valid: Optional[jnp.ndarray] = None,
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The routed pairs through their experts, and nothing else.

    x [N, h], weights / selected [N, k] -> (out [N, h] float32, rows
    [n_experts] int32: how many rows each expert multiplied).  Pairs are
    ordered by expert; `valid` False rows come behind every expert and
    belong to no group, so no expert multiplies them."""
    N, k = selected.shape
    E = n_experts
    flat = selected.reshape(-1).astype(jnp.int32)
    if valid is not None:
        flat = jnp.where(jnp.repeat(valid, k), flat, E)
    # a counting sort, not a sort: pair i goes to row `dest[i]`, behind the
    # pairs of lower experts and the earlier pairs of its own
    onehot = (flat[:, None] == jnp.arange(E + 1, dtype=jnp.int32)[None, :]
              ).astype(jnp.int32)  # [N k, E + 1]
    counts = onehot.sum(axis=0)
    starts = jnp.cumsum(counts) - counts
    earlier = jnp.cumsum(onehot, axis=0) - onehot
    dest = jnp.sum(onehot * (starts[None, :] + earlier), axis=-1)  # [N k]
    rows = counts[:E]
    order = jnp.zeros_like(dest).at[dest].set(
        jnp.arange(N * k, dtype=jnp.int32), unique_indices=True)
    xs = x[order // k]  # [N k, h]: each pair's token, in its expert's run
    gate = jax.lax.ragged_dot(xs, params["w_gate"], rows)
    up = jax.lax.ragged_dot(xs, params["w_up"], rows)
    ys = jax.lax.ragged_dot(
        (jax.nn.silu(gate) * up).astype(xs.dtype), params["w_down"], rows,
        preferred_element_type=jnp.float32)  # [N k, h]
    # back to (token, choice) order; rows of no group hold nothing defined
    y = ys[dest].reshape(N, k, -1)
    if valid is not None:
        y = jnp.where(valid[:, None, None], y, 0.0)
    return jnp.einsum("nkh,nk->nh", y, weights.astype(jnp.float32)), rows


@jax.named_scope("shared_expert")
def shared_expert(params: Dict[str, Any], x: jnp.ndarray) -> jnp.ndarray:
    gate = jax.nn.silu(dense(x, params["shared_gate"]))
    return dense(gate * dense(x, params["shared_up"]), params["shared_down"])


def moe_mlp(params: Dict[str, Any], x: jnp.ndarray, config: MoEConfig,
            valid: Optional[jnp.ndarray] = None, with_rows: bool = False):
    """x [..., h] -> [..., h]; `valid` [...] marks the rows that are tokens.
    `with_rows`: also the rows each expert multiplied ([n_experts] int32),
    from which the engine's expert counters are summed."""
    lead, h = x.shape[:-1], x.shape[-1]
    flat = x.reshape(-1, h)
    mask = None if valid is None else valid.reshape(-1)
    weights, selected = route(params, flat, config)
    out, rows = routed_experts(
        params, flat, weights, selected, config.n_experts, mask)
    if config.shared:
        out = out + shared_expert(params, flat).astype(jnp.float32)
    out = out.astype(x.dtype).reshape(lead + (h,))
    return (out, rows) if with_rows else out


def moe_param_pspecs():
    """Expert-parallel shardings: the expert axis over the `model` mesh axis
    (EP == TP axis on a single slice), the router replicated."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.sharding import MODEL_AXIS

    return {
        "router": P(),
        "w_gate": P(MODEL_AXIS, None, None),
        "w_up": P(MODEL_AXIS, None, None),
        "w_down": P(MODEL_AXIS, None, None),
    }
