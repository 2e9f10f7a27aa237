"""Sparse mixture-of-experts feed-forward: a router over experts, of which
this chip may hold a share.

Two routers (`MoEConfig.router`):

- `softmax` (Mixtral): the top-k logits, softmaxed among themselves;
- `sigmoid` (the DeepSeek-V3 family's `noaux_tc`, one group): scores
  `s = sigmoid(x W_r)` in float32; a learned bias `b`
  (`e_score_correction_bias`) is added to CHOOSE the top-k and plays no part
  in the weights, which are the chosen scores, renormalised to sum 1
  (`norm_topk`) and multiplied by `scale` (`routed_scaling_factor`).

Two forms of an expert (`MoEConfig.form`): `gated`, `down(silu(gate x) *
up x)`, three matrices; `relu2`, `down(relu(up x)^2)`, two (the
Nemotron-H family).  A `shared` expert of the same form, where the model
has one, takes every token beside the routed ones, at a width of its own
where `shared_intermediate_size` says so.  SEVERAL shared experts
(`n_shared`) are ONE fused MLP and a factor: expert s is columns [s w,
(s + 1) w) of `shared_gate` / `shared_up` and the same rows of
`shared_down`, so the one matmul's result is their sum, and their average
(`shared_average`, the Cohere family) is that sum over `n_shared`.

ONE compute path for every router, packed step and decode alike
(`routed_experts`): the (token, expert) pairs that were routed are sorted
by expert, each expert multiplies the contiguous rows that chose it
(`jax.lax.ragged_dot`, a grouped matmul: on the TPU a kernel whose FLOPs
and weight reads follow the rows, expert by expert; experts no row chose
are not read), and the rows go back to their tokens weighted.  Nothing of
size [tokens, experts, width] exists.  Rows of padding (`valid` False) are
given to no expert.

A chip's share of the experts (`first_expert`, `held`): the router keeps
its published width (`n_experts`) and its experts a token, and the weights
are normalised over all the experts chosen, held or not; the stacked
tensors hold experts `first_expert .. first_expert + held - 1` only, a pair
routed to another expert goes where padding goes, to no group, and adds
nothing: what the absent experts would have added is the other chips' part
of the sum.  On one chip the layer runs without the exchange that would
add the parts up; nothing here stands in for it.  `rows` counts what THIS
chip multiplied.

Experts live on stacked tensors [held, ...].  `moe_param_pspecs` shards
that axis over the `model` mesh axis, which GSPMD partitions where
`ragged_dot` lowers to plain XLA (the CPU): Mixtral's tp path, which holds
every expert on every mesh.

Two dimensions of those tensors meet the grouped matmul's tiles, and both
are laid out by shape alone: the routed width is STORED in whole 512-tiles
(`stored_width`, zeros behind it, in the parameters themselves), and a
`hidden` that is no multiple of 512 is SPLIT on the device into a body of
whole tiles and what is left over (`device_layout`, made by the engine from
the parameters as they were initialised or loaded; `routed_experts` takes
either).

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
    n_shared: int = 1  # how many: one fused MLP of n_shared x the width
    shared_average: bool = False  # their outputs averaged, not summed
    router_bias: bool = True  # sigmoid router: a choice-only bias
    form: str = "gated"  # or "relu2": down(relu(up x)^2), no gate matrix
    shared_intermediate_size: int = 0  # the shared expert's width; 0 = an expert's
    # this chip's share: experts first_expert .. first_expert + held - 1 of
    # the n_experts the router scores; held 0 = all of them
    first_expert: int = 0
    held: int = 0

    def __post_init__(self):
        if self.form not in ("gated", "relu2"):
            raise ValueError(f"unknown expert form {self.form!r}")
        if not (0 <= self.first_expert
                and self.first_expert + self.n_held <= self.n_experts):
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert + self.n_held - 1} "
                f"held of {self.n_experts}")

    @property
    def n_held(self) -> int:
        return self.held or self.n_experts

    @property
    def holds_all(self) -> bool:
        return self.n_held == self.n_experts


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
        n_shared=max(1, config.n_shared_experts),
        shared_average=config.moe_shared_average,
        router_bias=config.moe_router_bias,
        form=config.moe_form,
        shared_intermediate_size=config.moe_shared_intermediate_size,
        first_expert=config.first_expert,
        held=config.n_experts_held,
    )


#: columns the grouped matmul's widest tiles span (`stored_width`)
STORED_WIDTH_TILE = 512


def stored_width(width: int) -> int:
    """Columns a routed expert's `width` is STORED in: the next multiple of
    512, with zero columns (w_up, w_gate) and zero rows (w_down) behind the
    model's width, which change nothing that is computed.  A width that is
    no multiple of the TPU's 128 lanes (Nemotron's 1856) makes the device's
    default layout of [experts, hidden, width] put `hidden` innermost, and
    the grouped matmul, which wants the width there, then copies every
    layer's tensor on every call: 609 MB a layer, past the chip's memory
    (docs/kernels.md, "A chip's share of the experts").  In the layout the
    kernel wants the tiles hold padding to 128 anyway; at the next multiple
    of 512 (2048) the chip measured its tiles twice as fast again.  A width
    under one such tile (a test's) is stored as it is."""
    if width < STORED_WIDTH_TILE:
        return width
    return -(-width // STORED_WIDTH_TILE) * STORED_WIDTH_TILE


def hidden_body(hidden: int) -> int:
    """Rows of `hidden` that `device_layout` keeps in the BODY: the largest
    multiple of 512 in it; all of it where it is such a multiple, or lies
    under one tile (a test's).  The grouped matmul takes ONE tile size for
    a whole dimension, the largest of 512 / 256 / 128 that divides it: at
    Nemotron's 2688 = 21 x 128 every call ran one of k and n on 128-wide
    tiles (`ragged_dot_tiling` 32,128,512 and 32,512,128), where 2560
    compiles to 512 x 512 as every other configuration's tensors do
    (docs/kernels.md, "A chip's share of the experts"; pinned in
    tests/test_tpu_lowering.py)."""
    if hidden < STORED_WIDTH_TILE:
        return hidden
    return hidden // STORED_WIDTH_TILE * STORED_WIDTH_TILE


def device_layout(layer: Dict[str, Any]) -> Dict[str, Any]:
    """An expert layer's tensors as they lie on the device: where `hidden`
    is no whole number of 512-tiles, each routed tensor is a pair (body,
    rest) split along `hidden` at `hidden_body`: `w_up` (and `w_gate`)
    [held, h, f] -> ([held, body, f], [held, h - body, f]), `w_down` [held,
    f, h] -> ([held, f, body], [held, f, h - body]).  The same values,
    slice for slice; not a byte more is stored or read, nothing is padded.
    Everywhere else (`hidden` a multiple of 512 or under one tile, a layer
    without routed experts) the layer comes back AS IT IS, the same
    object, and traces the program it traced.

    Chosen by the tensors' shape alone.  tp shards the expert axis
    (`moe_param_pspecs`), never `hidden`, so each chip's tensors keep the
    whole `hidden` and the split is taken on it as on one chip; a sharding
    of `hidden` itself would have to take the rule on each chip's own
    share of the rows, and none exists."""
    w_up = layer.get("w_up")
    if getattr(w_up, "ndim", 0) != 3:  # a dense feed-forward, or none
        return layer
    body = hidden_body(w_up.shape[1])
    if body == w_up.shape[1]:
        return layer
    out = dict(layer)
    for name in ("w_up", "w_gate"):
        if name in out:
            out[name] = (out[name][:, :body], out[name][:, body:])
    out["w_down"] = (out["w_down"][:, :, :body], out["w_down"][:, :, body:])
    return out


def moe_param_shapes(config: MoEConfig) -> Dict[str, tuple]:
    """{name: shape} of one expert layer's feed-forward: the router over
    every expert, the stacked tensors over those held here, a routed
    expert's width as `stored_width` has it."""
    E, h = config.n_experts, config.hidden_size
    f = stored_width(config.intermediate_size)
    held = config.n_held
    shapes = {"router": (h, E), "w_up": (held, h, f), "w_down": (held, f, h)}
    if config.router == "sigmoid" and config.router_bias:
        shapes["router_bias"] = (E,)
    if config.shared:
        fs = config.n_shared * (
            config.shared_intermediate_size or config.intermediate_size)
        shapes.update({"shared_up": (h, fs), "shared_down": (fs, h)})
    if config.form == "gated":
        shapes["w_gate"] = (held, h, f)
        if config.shared:
            shapes["shared_gate"] = shapes["shared_up"]
    return shapes


def zero_stored_padding(params: Dict[str, Any], config: MoEConfig) -> Dict[str, Any]:
    """An expert layer's tensors with what lies behind the routed experts'
    width set to zero (`stored_width`); as they are where the width is
    stored as it is."""
    f = config.intermediate_size
    if stored_width(f) == f:
        return params
    out = dict(params)
    for name in ("w_up", "w_gate"):
        if name in out:
            out[name] = out[name].at[:, :, f:].set(0)
    out["w_down"] = out["w_down"].at[:, f:, :].set(0)
    return out


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
    return zero_stored_padding(out, config)


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
    chooser = scores
    if config.router_bias:
        chooser = scores + params["router_bias"].astype(jnp.float32)
    _, selected = jax.lax.top_k(chooser, config.top_k)
    weights = jnp.take_along_axis(scores, selected, axis=-1)
    if config.norm_topk:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
    return weights * config.scale, selected


def _grouped_in(xs: jnp.ndarray, w: Any, rows: jnp.ndarray) -> jnp.ndarray:
    """xs [M, h] through each row's expert of w [held, h, f] -> [M, f] in
    xs's dtype: float32 accumulation, rounded ONCE.  Over a `device_layout`
    pair the contraction split at a column is the sum of two, each asked in
    float32, summed in float32 and rounded where the whole call rounds."""
    if not isinstance(w, tuple):
        return jax.lax.ragged_dot(xs, w, rows)
    body, rest = w
    cut = body.shape[1]
    return (
        jax.lax.ragged_dot(xs[:, :cut], body, rows,
                           preferred_element_type=jnp.float32)
        + jax.lax.ragged_dot(xs[:, cut:], rest, rows,
                             preferred_element_type=jnp.float32)
    ).astype(xs.dtype)


def _grouped_out(act: jnp.ndarray, w: Any, rows: jnp.ndarray) -> jnp.ndarray:
    """act [M, f] through each row's expert of w [held, f, h] -> [M, h]
    float32; over a `device_layout` pair the output split at a column is
    the two calls' columns side by side."""
    parts = w if isinstance(w, tuple) else (w,)
    ys = [jax.lax.ragged_dot(act, part, rows,
                             preferred_element_type=jnp.float32)
          for part in parts]
    return ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=-1)


@jax.named_scope("experts")
def routed_experts(params: Dict[str, Any], x: jnp.ndarray,
                   weights: jnp.ndarray, selected: jnp.ndarray,
                   n_experts: int, valid: Optional[jnp.ndarray] = None,
                   share: Optional[Tuple[int, int]] = None,
                   form: str = "gated") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The routed pairs through the experts held here, and nothing else.

    x [N, h], weights / selected [N, k] over `n_experts` experts -> (out
    [N, h] float32, rows [held] int32: how many rows each held expert
    multiplied).  `share` (first expert, held) where the stacked tensors
    hold a part of the experts; None = all.  Pairs are ordered by expert;
    `valid` False rows and pairs routed to an expert that is not held come
    behind every expert and belong to no group, so no expert multiplies
    them and they add nothing.

    The routed tensors as `moe_param_shapes` names them or as
    `device_layout` lays them out: the same sums either way, `up` (and
    `gate`) accumulated in float32 and rounded to x's dtype once, BEFORE
    the activation, in both."""
    N, k = selected.shape
    first_expert, E = share or (0, n_experts)
    flat = selected.reshape(-1).astype(jnp.int32)
    here = None
    if share is not None:
        flat = flat - first_expert
        here = (flat >= 0) & (flat < E)
    if valid is not None:
        rows_valid = jnp.repeat(valid, k)
        here = rows_valid if here is None else here & rows_valid
    if here is not None:
        flat = jnp.where(here, flat, E)
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
    if form == "gated":
        gate = _grouped_in(xs, params["w_gate"], rows)
        act = jax.nn.silu(gate) * _grouped_in(xs, params["w_up"], rows)
    else:
        act = jnp.square(jax.nn.relu(_grouped_in(xs, params["w_up"], rows)))
    ys = _grouped_out(act.astype(xs.dtype), params["w_down"], rows)  # [N k, h]
    # back to (token, choice) order; rows of no group hold nothing defined
    y = ys[dest].reshape(N, k, -1)
    if share is not None:
        y = jnp.where(here.reshape(N, k, 1), y, 0.0)
    elif valid is not None:
        y = jnp.where(valid[:, None, None], y, 0.0)
    return jnp.einsum("nkh,nk->nh", y, weights.astype(jnp.float32)), rows


@jax.named_scope("shared_experts")
def shared_expert(params: Dict[str, Any], x: jnp.ndarray,
                  form: str = "gated") -> jnp.ndarray:
    """The shared experts' SUM over x [N, h]: one MLP over their fused
    columns (`moe_param_shapes`)."""
    if form == "gated":
        gate = jax.nn.silu(dense(x, params["shared_gate"]))
        act = gate * dense(x, params["shared_up"])
    else:
        act = jnp.square(jax.nn.relu(dense(x, params["shared_up"])))
    return dense(act, params["shared_down"])


def moe_mlp(params: Dict[str, Any], x: jnp.ndarray, config: MoEConfig,
            valid: Optional[jnp.ndarray] = None, with_rows: bool = False):
    """x [..., h] -> [..., h]; `valid` [...] marks the rows that are tokens.
    `with_rows`: also the rows each held expert multiplied ([held] int32),
    from which the engine's expert counters are summed."""
    lead, h = x.shape[:-1], x.shape[-1]
    flat = x.reshape(-1, h)
    mask = None if valid is None else valid.reshape(-1)
    weights, selected = route(params, flat, config)
    out, rows = routed_experts(
        params, flat, weights, selected, config.n_experts, mask,
        None if config.holds_all else (config.first_expert, config.n_held),
        config.form)
    if config.shared:
        shared = shared_expert(params, flat, config.form).astype(jnp.float32)
        if config.shared_average:
            shared = shared / config.n_shared
        out = out + shared
    out = out.astype(x.dtype).reshape(lead + (h,))
    return (out, rows) if with_rows else out


def moe_param_pspecs(config: Optional[MoEConfig] = None):
    """Expert-parallel shardings of `moe_param_shapes(config)`'s tensors
    (a Mixtral layer's without a config): the expert axis over the `model`
    mesh axis (EP == TP axis on a single slice), the router, its bias and
    the shared expert replicated."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.sharding import MODEL_AXIS

    names = moe_param_shapes(config or MoEConfig())
    return {name: (P(MODEL_AXIS, None, None) if name.startswith("w_") else P())
            for name in names}
