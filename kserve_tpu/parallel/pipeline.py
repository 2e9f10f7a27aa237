"""Pipeline parallelism: a stage mesh axis + collective-permute of
activations (GPipe-style microbatch schedule, SPMD formulation).

The reference turns `PipelineParallelSize` into multi-node worker math
(pkg/controller/v1beta1/inferenceservice/components/predictor.go:761) and
lets vLLM run the stages over NCCL.  The TPU-native equivalent is a
`pipe` mesh axis: the layer stack is sharded over it (each device holds
L/S contiguous layers), microbatches stream through the stages, and
activations move stage->stage via `lax.ppermute` over ICI/DCN — the
canonical use is spanning pods (DCN) where a single ppermute hop per
microbatch tolerates the higher latency, while TP stays inside the slice.

Within one slice, TP is strictly preferable at serving scales: the
pipeline adds (S-1) bubble steps per round and holds S in-flight
microbatch activations, while TP's all-reduces ride full ICI bandwidth.
PP exists for when the model does not fit a slice's HBM (see README
"Pipeline parallelism" for the measured framing).

Schedule (S stages, M microbatches, M+S-1 steps, all SPMD — every stage
computes every step; warm-up/drain emit garbage that is masked off):

    step t: stage s computes microbatch (t - s) if 0 <= t-s < M
            activations ppermute s -> s+1
            stage S-1's outputs for t >= S-1 are the pipeline outputs
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

PIPE_AXIS = "pipe"


def _psum_last_stage(outs: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Broadcast the last stage's outputs to every stage.  `outs` is zero
    everywhere except stage S-1, so the psum is exact in any dtype — but
    XLA's SPMD partitioner (CPU backend, jax 0.9) hits a fatal
    "Invalid binary instruction opcode copy" building a sub-f32 all-reduce
    inside a partial-auto shard_map over a multi-axis mesh.  Reducing in
    f32 sidesteps the crash and is bit-identical (x + 0.0 round-trips
    exactly through the widen/narrow).  CPU-only: on TPU the sub-f32
    all-reduce partitions fine and the upcast would double the
    stage-broadcast bytes on the hot path."""
    if outs.dtype == jnp.float32 or jax.default_backend() != "cpu":
        return jax.lax.psum(outs, axis_name)
    return jax.lax.psum(outs.astype(jnp.float32), axis_name).astype(outs.dtype)


def create_pp_mesh(pp: int, devices=None) -> Mesh:
    """A (pipe,) mesh.  Stages should map contiguously onto the device
    order so the ppermute hop is ICI-adjacent (or crosses DCN exactly once
    between pods)."""
    devices = devices if devices is not None else jax.devices()
    if pp > len(devices):
        raise ValueError(f"pp={pp} needs {pp} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:pp]), (PIPE_AXIS,))


def stack_stage_params(layer_params_list):
    """[L] list of per-layer pytrees -> one pytree with leading layer axis
    (sharded over PIPE_AXIS by pipeline_forward's in_specs)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layer_params_list)


def _pipeline_local(
    stacked_local,  # pytree, leading axis = L/S local layers
    microbatches: jnp.ndarray,  # [M, mb, ...] same on every stage
    layer_fn: Callable,  # (layer_params, x) -> x, one transformer block
    axis_name: str,
    S: int,  # static stage count (the ppermute ring needs a Python int)
):
    """The per-device program (inside shard_map)."""
    stage = jax.lax.axis_index(axis_name)
    M = microbatches.shape[0]

    def run_stage(x):
        def body(h, layer):
            return layer_fn(layer, h), None

        out, _ = jax.lax.scan(body, x, stacked_local)
        return out

    def step(carry, t):
        buf = carry  # activation received from the previous stage
        # stage 0 ingests microbatch t (clamped index; garbage past M is
        # masked by the output gather), later stages consume the buffer
        mb = microbatches[jnp.clip(t, 0, M - 1)]
        x_in = jnp.where(stage == 0, mb, buf)
        y = run_stage(x_in)
        # rotate activations one stage forward (the S-1 -> 0 wrap carries
        # garbage that stage 0 ignores)
        buf_next = jax.lax.ppermute(
            y, axis_name, [(i, (i + 1) % S) for i in range(S)]
        )
        # only the LAST stage's output is the pipeline output; zero
        # elsewhere so a psum over the axis broadcasts it
        out = jnp.where(stage == S - 1, y, jnp.zeros_like(y))
        return buf_next, out

    steps = M + S - 1
    _, outs = jax.lax.scan(
        step, jnp.zeros_like(microbatches[0]), jnp.arange(steps)
    )
    # outs[t] is microbatch t-(S-1); steps before the pipeline filled are
    # warm-up garbage
    outs = outs[S - 1:]
    # broadcast the last stage's outputs to every device (replicated out)
    return _psum_last_stage(outs, axis_name)


def _pipeline_local_stateful(
    stacked_local,  # pytree, leading axis = L/S local layers
    local_pages,  # [L/S, num_pages, 2, nkv, ps, d] this stage's KV
    mbs_x: jnp.ndarray,  # [M, mb, ...] microbatched activations
    mbs_aux,  # pytree of [M, mb, ...] per-row tensors riding with each mb
    block_fn,  # (layer, pages_l, x, aux, valid) -> (x_out, pages_l_new)
    axis_name: str,
    S: int,
):
    """GPipe schedule with per-stage KV state.  Unlike _pipeline_local,
    each microbatch's aux (positions, page tables, live masks) must TRAVEL
    with its activations through the ppermute ring — stage s at step t is
    processing microbatch t-s, so indexing aux by t would feed it a later
    microbatch's page tables.  `valid` (0 <= t-s < M) tells block_fn to
    mask KV writes (null page / live=False) during warm-up/drain."""
    stage = jax.lax.axis_index(axis_name)
    M = mbs_x.shape[0]

    def run_stage(x, pages, aux, valid):
        def body(h, inp):
            layer, pages_l = inp
            h, pages_l = block_fn(layer, pages_l, h, aux, valid)
            return h, pages_l

        out, new_pages = jax.lax.scan(body, x, (stacked_local, pages))
        return out, new_pages

    perm = [(i, (i + 1) % S) for i in range(S)]

    def step(carry, t):
        buf_x, buf_aux, pages = carry
        m = t - stage
        valid = (m >= 0) & (m < M)
        idx = jnp.clip(t, 0, M - 1)
        x_in = jnp.where(stage == 0, mbs_x[idx], buf_x)
        aux_in = jax.tree.map(
            lambda mb_a, buf_a: jnp.where(stage == 0, mb_a[idx], buf_a),
            mbs_aux, buf_aux,
        )
        y, pages = run_stage(x_in, pages, aux_in, valid)
        buf_x_next = jax.lax.ppermute(y, axis_name, perm)
        buf_aux_next = jax.tree.map(
            lambda a: jax.lax.ppermute(a, axis_name, perm), aux_in
        )
        out = jnp.where(stage == S - 1, y, jnp.zeros_like(y))
        return (buf_x_next, buf_aux_next, pages), out

    steps = M + S - 1
    carry0 = (
        jnp.zeros_like(mbs_x[0]),
        jax.tree.map(lambda a: jnp.zeros_like(a[0]), mbs_aux),
        local_pages,
    )
    (_, _, pages_final), outs = jax.lax.scan(step, carry0, jnp.arange(steps))
    outs = outs[S - 1:]
    return _psum_last_stage(outs, axis_name), pages_final


def pipeline_blocks(
    stacked_layers,  # pytree with leading axis L, sharded P(pipe)
    stacked_pages,  # [L, num_pages, 2, nkv, ps, d] P(pipe), or the
    # (int8 pages, scales) tuple for a quantized cache
    x: jnp.ndarray,  # [B, ...] activations after embedding (pipe-replicated)
    aux,  # pytree of [B, ...] tensors each microbatch carries
    block_fn,
    mesh: Mesh,
    n_microbatches: int,
    axis_name: str = PIPE_AXIS,
):
    """Stage-sharded transformer stack WITH paged-KV state: the serving
    engine's pipeline-parallel execution path (engine pp>1).  Returns
    ([B, ...] outputs replicated over pipe, updated stacked pages)."""
    B = x.shape[0]
    if B % n_microbatches != 0:
        raise ValueError(
            f"batch {B} not divisible by {n_microbatches} microbatches")
    S = mesh.shape[axis_name]
    mb = B // n_microbatches
    mbs_x = x.reshape((n_microbatches, mb) + x.shape[1:])
    mbs_aux = jax.tree.map(
        lambda a: a.reshape((n_microbatches, mb) + a.shape[1:]), aux
    )
    layer_spec = jax.tree.map(lambda _: P(axis_name), stacked_layers)
    # pages may be one stacked array OR an (int8 pages, scales) tuple
    # (kv_quant): spec the pytree leaf-wise
    pages_spec = jax.tree.map(lambda _: P(axis_name), stacked_pages)
    fn = jax.shard_map(
        partial(_pipeline_local_stateful, block_fn=block_fn,
                axis_name=axis_name, S=S),
        mesh=mesh,
        in_specs=(layer_spec, pages_spec, P(), jax.tree.map(
            lambda _: P(), mbs_aux)),
        out_specs=(P(), pages_spec),
        axis_names={axis_name},
        check_vma=False,
    )
    outs, new_pages = fn(stacked_layers, stacked_pages, mbs_x, mbs_aux)
    return outs.reshape((B,) + outs.shape[2:]), new_pages


def llama_block_layer_fn(config):
    """One full llama transformer block (prefill form, no KV cache) as a
    pipeline `layer_fn` — delegates to llama.transformer_block, the single
    source of the block math (no drift between prefill and the pipeline)."""
    from ..models.llama import transformer_block

    def layer_fn(layer, x):
        B, T, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        valid = jnp.full((B,), T, jnp.int32)
        x_out, _, _ = transformer_block(layer, x, positions, valid, config)
        return x_out

    return layer_fn


def pipeline_forward(
    stacked_params,  # pytree with leading axis L (= S * layers_per_stage)
    x: jnp.ndarray,  # [B, ...] full batch
    layer_fn: Callable,
    mesh: Mesh,
    n_microbatches: int,
    axis_name: str = PIPE_AXIS,
) -> jnp.ndarray:
    """Run a layer stack over the pipe axis of `mesh`.

    The batch is split into `n_microbatches` along dim 0 (must divide B);
    output is the full [B, ...] result, replicated over the pipe axis.
    """
    B = x.shape[0]
    if B % n_microbatches != 0:
        raise ValueError(f"batch {B} not divisible by {n_microbatches} microbatches")
    S = mesh.shape[axis_name]
    n_layers = jax.tree.leaves(stacked_params)[0].shape[0]
    if n_layers % S != 0:
        raise ValueError(
            f"n_layers={n_layers} not divisible by {S} pipeline stages")
    mb = B // n_microbatches
    microbatches = x.reshape((n_microbatches, mb) + x.shape[1:])

    stage_spec = jax.tree.map(lambda _: P(PIPE_AXIS), stacked_params)
    fn = jax.shard_map(
        partial(_pipeline_local, layer_fn=layer_fn, axis_name=axis_name,
                S=mesh.shape[axis_name]),
        mesh=mesh,
        in_specs=(stage_spec, P()),
        out_specs=P(),
        check_vma=False,
    )
    out = fn(stacked_params, microbatches)
    return out.reshape((B,) + out.shape[2:])
