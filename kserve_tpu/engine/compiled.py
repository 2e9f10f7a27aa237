"""The engine's compiled device programs (VERDICT r4 weak #8: split from
the scheduler/loop module).

`build_compiled(model_config, engine_config, mesh)` jits every program the
serving loop dispatches: batched + chunked prefill, multi-step decode (the
penalized and logprob-emitting variants compiled separately so ordinary
requests never pay their per-step cost), first-token sampling for chunked
admission, and the P/D KV injection scatters.  All sharding-aware pieces
(TP decode attention under shard_map, SP ring-attention prefill, PP staged
execution) are chosen here from the engine config.
"""

from __future__ import annotations

import hashlib
import logging
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from ..metrics import XLA_COMPILE_SECONDS, XLA_COMPILES
from ..models import llama
from ..observability.pauses import PROGRAM_COMPILE
from ..parallel import sharding as shd
from .kvcache import pages_of_passes
from .sampling import (
    COLUMNS as SAMPLER_COLUMNS,
    SamplingState,
    apply_penalties,
    compute_logprobs,
    sample_tokens,
    sampler_top_k,
    sampler_truncates,
)
from .shapes import PLAN_ROWS, TOKEN_ROWS, DispatchShapes, MixedLayout

_log = logging.getLogger(__name__)

#: per-program compile events, appended by _CompileCounting (jit path) and
#: AOTProgram._compile (AOT path).  Each event is a dict with the argument
#: signature that triggered the compile — the jit cache key's observable
#: spelling (shape/dtype/weak-type/sharding per leaf) — plus a short
#: digest of it, so a retrace-budget failure can name WHICH spelling
#: drifted between call N and call N+1 instead of just reporting a count.
_COMPILE_FINGERPRINTS: Dict[str, List[dict]] = {}


def _leaf_spelling(leaf) -> str:
    """One leaf's jit-cache-relevant spelling: dtype[shape]@spec, with a
    ``~w`` suffix for weak types (the classic invisible retrace source)."""
    aval = getattr(leaf, "aval", None)
    shape = getattr(aval, "shape", getattr(leaf, "shape", ()))
    dtype = getattr(aval, "dtype", getattr(leaf, "dtype", type(leaf).__name__))
    weak = bool(getattr(aval, "weak_type", getattr(leaf, "weak_type", False)))
    sharding = getattr(leaf, "sharding", None)
    spec = getattr(sharding, "spec", None)
    s = f"{dtype}[{','.join(str(d) for d in shape)}]"
    if spec is not None:
        s += f"@{spec}"
    if weak:
        s += "~w"
    return s


def _args_signature(args, kwargs) -> str:
    """Compact per-argument signature of a dispatch: big pytrees (params,
    kv caches) collapse to ``<N leaves:digest8>`` so the string stays
    log-line sized while still changing whenever any leaf's spelling does."""
    parts = []
    for arg in list(args) + [v for _, v in sorted(kwargs.items())]:
        leaves = jax.tree_util.tree_leaves(arg)
        spellings = [_leaf_spelling(leaf) for leaf in leaves]
        if len(spellings) > 4:
            digest = hashlib.sha256(
                "|".join(spellings).encode()).hexdigest()[:8]
            parts.append(f"<{len(spellings)} leaves:{digest}>")
        elif len(spellings) == 1:
            parts.append(spellings[0])
        else:
            parts.append("(" + ",".join(spellings) + ")")
    return ", ".join(parts)


def record_compile_fingerprint(program: str, signature: str,
                               hlo_hash: str = "") -> None:
    """Append one compile event for `program`.  `signature` is the arg
    spelling that keyed the compile; `hlo_hash` (optional) is a digest of
    the lowered module when the recorder has it (the AOT path does)."""
    _COMPILE_FINGERPRINTS.setdefault(program, []).append({
        "signature": signature,
        "fingerprint": hashlib.sha256(
            f"{program}:{signature}".encode()).hexdigest()[:12],
        "hlo_hash": hlo_hash,
    })


def compile_fingerprints(program: Optional[str] = None):
    """Recorded compile events: a list for one `program`, else the whole
    {program: [events]} map (live view — copy before mutating)."""
    if program is not None:
        return list(_COMPILE_FINGERPRINTS.get(program, ()))
    return {k: list(v) for k, v in _COMPILE_FINGERPRINTS.items()}


def reset_compile_fingerprints() -> None:
    _COMPILE_FINGERPRINTS.clear()


class _CompileCounting:
    """Wrap a jitted program and count its jit-cache misses (compiles AND
    retraces) into the engine_xla_compiles_total counter, labeled by the
    program's fixed name.  A growing count at steady state is the recompile
    alarm beside the HLO perf oracle (analysis/hlo_oracle): shape-bucket
    drift, weak-type wobble and donation mismatch all show up here before
    they show up as tail latency.  Each counted miss also records the
    dispatch's argument signature via record_compile_fingerprint, so the
    retrace-budget test can diff the spellings of compile N and N+1.  The
    signature is built from avals (which survive donation) AFTER the
    dispatch — cost is one tree-flatten per compile event, and one clock
    read per steady-state call: a call that missed is timed whole (trace,
    compile, first run) into engine_xla_compile_seconds_total, which is how
    long it kept the engine's loop from serving anything, /metrics
    included."""

    __slots__ = ("_name", "_fn", "_seen")

    def __init__(self, name: str, fn: Callable):
        self._name = name
        self._fn = fn
        self._seen = 0

    @property
    def compiles(self) -> int:
        """Cache misses so far; the engine reads it around a launch to
        mark the dispatch that compiled."""
        return self._seen

    def __call__(self, *args, **kwargs):
        t0 = time.perf_counter()
        with PROGRAM_COMPILE:  # a compile in here is the engine's own
            out = self._fn(*args, **kwargs)
        n = self._fn._cache_size()
        if n > self._seen:
            XLA_COMPILE_SECONDS.labels(program=self._name).inc(
                time.perf_counter() - t0)
            XLA_COMPILES.labels(program=self._name).inc(n - self._seen)
            self._seen = n
            try:
                record_compile_fingerprint(
                    self._name, _args_signature(args, kwargs))
            except Exception:  # diagnostics must never fail a dispatch
                _log.debug("compile fingerprint failed for %s",
                           self._name, exc_info=True)
        return out


def _counted(**named) -> dict:
    return {k: _CompileCounting(k, v) for k, v in named.items()}


@dataclass(frozen=True)
class CompiledPrograms:
    """The engine's device programs.  `mixed` is the unified ragged
    prefill+decode program (docs/kernels.md) the engine dispatches by
    default; the remaining entries are the legacy per-path programs, kept
    as the fallback behind EngineConfig.use_ragged=False (and for the
    feature corners mixed doesn't cover yet: per-step logprobs, penalties,
    P/D detached prefill, pp>1/sp>1).  jit is lazy, so unused legacy
    programs cost nothing at steady state."""

    prefill: Callable
    prefill_lp: Callable
    prefill_chunk: Callable
    sample_first: Callable
    sample_first_lp: Callable
    decode: Callable
    decode_lp: Callable
    decode_penalized: Callable
    decode_penalized_lp: Callable
    inject: Callable
    inject_q: Callable
    mixed: Callable = None  # None when the config can't build it (pp>1)
    # dense decode packing + self-drafting speculative verify
    # (docs/kernels.md): built only when spec_decode_k is configured —
    # the pure-decode fast path the engine chains depth-2
    mixed_decode: Callable = None


def program_defs(model_config, engine_config, mesh, spec_k=None) -> dict:
    """The engine's program-definition table: ``{name: (python_fn,
    donate_argnums)}`` for every program this config builds.  This is the
    single source of truth `build_compiled` jits (or AOT-compiles) from —
    and the seam the HLO perf oracle (analysis/hlo_oracle) re-enters to
    lower the SAME programs standalone, so its budgets audit exactly what
    the engine dispatches.  The aot-cache-key-drift lint audits the
    engine-config reads in here (same scope as build_compiled).

    `spec_k` (EngineConfig.spec_decode_k, passed EXPLICITLY so the
    aot-cache-key-drift lint stays honest: the field is deliberately NOT
    in the AOT key until hardware-validated, and the engine disables the
    AOT cache whenever it is set) builds the `mixed_decode` dense/
    speculative program: K draft tokens per decode lane verified as one
    ragged multi-token chunk per round."""
    cfg = engine_config
    mc = model_config

    from jax.sharding import PartitionSpec as _P

    _quantized = getattr(cfg, "kv_quant", None) == "int8"

    def _kv_pin(kv_pages):
        """Constrain returned kv_pages to the canonical cache sharding.

        Without this, XLA is free to return the donated cache with a
        differently-SPELLED (equivalent) sharding — observed on CPU: the
        init arrays carry PartitionSpec(None, None, 'model', None, None)
        but the program output comes back as PartitionSpec(), so the
        SECOND dispatch sees a new input signature and recompiles once
        per program ("the donated kv_pages layout settles", PR 6/7 note).
        Pinning the output spec makes call 2's signature identical to
        call 1's: every program compiles exactly once per shape bucket
        (pinned by tests/test_retrace_budget.py)."""
        if mc.is_hybrid:
            # a state pytree (kvcache.StateLayout), tp=1: every leaf pinned
            # to the replicated spelling its init carries
            rep = shd.named(mesh, _P())
            return jax.tree.map(
                lambda a: jax.lax.with_sharding_constraint(a, rep), kv_pages)
        if cfg.pp > 1:
            # no constraint under pp: the staged shard_map is manual over
            # `pipe`, and adding a GSPMD constraint to its output makes
            # this jax's partitioner reject the module (PartitionId under
            # SPMD).  pp keeps the benign one-time settle retrace instead.
            return kv_pages
        page_s = shd.named(mesh, shd.kv_pages_pspec())
        scale_s = shd.named(mesh, _P(None, None, shd.MODEL_AXIS, None))
        if _quantized:
            return [
                (jax.lax.with_sharding_constraint(p, page_s),
                 jax.lax.with_sharding_constraint(s, scale_s))
                for p, s in kv_pages
            ]
        return [
            jax.lax.with_sharding_constraint(p, page_s) for p in kv_pages
        ]

    # the pallas kernel has no GSPMD partitioning rule; under tp/sp>1
    # decode attention runs under shard_map over the model axis instead
    # (each device: its LOCAL heads — q and KV heads shard together so
    # GQA groups stay intact; no collectives) so the kernel's
    # auto-dispatch stays available on the multi-chip path
    decode_attention_fn = None
    if cfg.tp > 1 or cfg.sp > 1:
        from ..ops.attention import make_sharded_paged_attention

        decode_attention_fn = make_sharded_paged_attention(
            mesh,
            logit_softcap=mc.attn_logit_softcap,
            use_pallas=cfg.use_pallas,
            quantized=_quantized,
            scale=mc.attn_scale,
            # static: only windowed models thread the per-layer scalar
            # through (a traced window forces the gather path)
            windowed=mc.sliding_window > 0,
        )

    # same shard_map seam for the RAGGED attention in the mixed program:
    # q heads and KV heads shard together over the model axis, packing
    # metadata is replicated (ops/attention.make_sharded_ragged_attention)
    ragged_attention_fn = None
    if cfg.tp > 1 or cfg.sp > 1:
        from ..ops.attention import make_sharded_ragged_attention

        ragged_attention_fn = make_sharded_ragged_attention(
            mesh,
            logit_softcap=mc.attn_logit_softcap,
            use_pallas=cfg.use_pallas,
            quantized=_quantized,
            scale=mc.attn_scale,
        )

    attention_fn = None
    if cfg.sp > 1:
        # sequence-parallel prefill: the prompt dim shards over `seq`,
        # attention runs as ring attention under shard_map (KV chunks
        # rotate via ppermute, comms overlap compute); the KV-page
        # scatter's output sharding is seq-replicated, so XLA inserts
        # the K/V allgather automatically.  Decode stays seq-replicated
        # (single-token steps have nothing to shard over seq).
        from functools import partial as _partial

        from jax.sharding import PartitionSpec as _P

        from ..parallel.ring_attention import ring_attention

        qkv_spec = _P(None, shd.SEQ_AXIS, shd.MODEL_AXIS, None)
        ring_fn = jax.shard_map(
            _partial(
                ring_attention,
                axis_name=shd.SEQ_AXIS,
                logit_softcap=mc.attn_logit_softcap,
            ),
            mesh=mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec, _P(None)),
            out_specs=qkv_spec,
            check_vma=False,
        )
        attention_fn = lambda q, k, v, vl, softcap: ring_fn(q, k, v, vl)  # noqa: E731

    def _pp_microbatches(B: int) -> int:
        """Largest divisor of B not above the requested microbatch
        count (pp by default) — static per compiled shape."""
        m = min(cfg.pp_microbatches or cfg.pp, B)
        while B % m:
            m -= 1
        return max(m, 1)

    def _make_prefill(with_logprobs: bool):
        def fn(params, tokens, valid_len, kv_pages, page_ids, state, rng,
               adapter_ids):
            if cfg.sp > 1:
                tokens = jax.lax.with_sharding_constraint(
                    tokens, shd.named(mesh, jax.sharding.PartitionSpec(None, shd.SEQ_AXIS))
                )
            if cfg.pp > 1:
                logits, kv_pages = llama.prefill_pp(
                    params, mc, tokens, valid_len, kv_pages, page_ids,
                    cfg.page_size, mesh,
                    _pp_microbatches(tokens.shape[0]),
                    adapter_ids=adapter_ids,
                )
            else:
                logits, kv_pages = llama.prefill(
                    params, mc, tokens, valid_len, kv_pages, page_ids, cfg.page_size,
                    attention_fn=attention_fn, adapter_ids=adapter_ids,
                )
            # vLLM-parity: repetition_penalty counts prompt tokens as
            # "seen" for the very first sampled token.  Rows with default
            # penalties are bit-identical to the unpenalized math.
            Bp, V = logits.shape
            pos_valid = (
                jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
                < valid_len[:, None]
            )
            in_prompt = (
                jnp.zeros((Bp, V), bool)
                .at[jnp.arange(Bp)[:, None], tokens]
                .max(pos_valid)
            )
            logits = apply_penalties(
                logits,
                jnp.zeros((Bp, V), jnp.int32),
                state.repetition_penalty,
                state.frequency_penalty,
                state.presence_penalty,
                in_prompt,
            )
            first = sample_tokens(logits, state, rng)
            kv_pages = _kv_pin(kv_pages)
            if with_logprobs:
                lp, tv, ti = compute_logprobs(logits, first, cfg.max_logprobs)
                return first, (lp, tv, ti), kv_pages
            return first, kv_pages

        return fn

    def _make_decode(with_penalties: bool, with_logprobs: bool = False):
        """steps_per_sync decode steps on device; emits [steps, B] tokens.
        Lanes past their page capacity (or inactive) hold token/pos and
        write to the null page — a clamped page-table index would
        otherwise corrupt a neighbouring sequence's last page.

        The penalized variant additionally threads a [B, V] output-count
        carry (plus a static [B, V] prompt mask) through the scan and
        returns the updated counts; it is compiled separately so requests
        without penalties never pay the per-step [B, V] scatter/gather.
        The logprobs variant additionally emits per-step sampled-token
        logprobs and the top-k (cfg.max_logprobs) ids/values — compiled
        separately so ordinary requests never pay the per-step top_k."""

        def fn(params, tokens, pos, kv_pages, page_table, active,
               capacity, counters, state, rng, adapter_ids, *penalty_args):
            steps = cfg.steps_per_sync
            B = tokens.shape[0]
            truncates = sampler_truncates(state)  # once, not once a step
            top_k = sampler_top_k(state)  # likewise

            def body(carry, step_rng):
                if with_penalties:
                    tokens, pos, counters, kv_pages, counts = carry
                else:
                    tokens, pos, counters, kv_pages = carry
                live = active & (pos < capacity)
                if cfg.pp > 1:
                    logits, kv_pages = llama.decode_step_pp(
                        params, mc, tokens, pos, kv_pages, page_table,
                        live, cfg.page_size, mesh, _pp_microbatches(B),
                        adapter_ids=adapter_ids,
                    )
                else:
                    logits, kv_pages = llama.decode_step(
                        params, mc, tokens, pos, kv_pages, page_table, live,
                        cfg.page_size, use_pallas=cfg.use_pallas,
                        adapter_ids=adapter_ids,
                        attention_fn=decode_attention_fn,
                    )
                if with_penalties:
                    logits = apply_penalties(
                        logits, counts,
                        state.repetition_penalty,
                        state.frequency_penalty,
                        state.presence_penalty,
                        penalty_args[0],
                    )
                nxt = sample_tokens(
                    logits, state, step_rng, counters, truncates, top_k)
                nxt = jnp.where(live, nxt, tokens)
                if with_logprobs:
                    lp, tv, ti = compute_logprobs(logits, nxt, cfg.max_logprobs)
                    out_step = (nxt, lp, tv, ti)
                else:
                    out_step = nxt
                new_carry = (
                    nxt,
                    pos + live.astype(pos.dtype),
                    counters + live.astype(counters.dtype),
                    kv_pages,
                )
                if with_penalties:
                    counts = counts.at[jnp.arange(B), nxt].add(
                        live.astype(counts.dtype)
                    )
                    new_carry = new_carry + (counts,)
                return new_carry, out_step

            init = (tokens, pos, counters, kv_pages)
            if with_penalties:
                init = init + (penalty_args[1],)
            rngs = jax.random.split(rng, steps)
            carry, out = jax.lax.scan(body, init, rngs)
            if with_penalties:
                return out, _kv_pin(carry[3]), carry[4]
            return out, _kv_pin(carry[3])

        return fn

    def _rows_at(layer, i, data, ids):
        """Layer i's array with the wire rows `data` [cache_rows, P, ...]
        set at pages `ids`: a looped model's layer holds a row a pass, pass
        u's pages at + u * pool, its wire rows at u * n_layers + i."""
        if not mc.is_looped:
            return layer.at[ids].set(data[i].astype(layer.dtype))
        of_pass = pages_of_passes(
            ids, mc.n_passes, layer.shape[0] // mc.n_passes)
        rows = data.reshape((mc.n_passes, -1) + data.shape[1:])[:, i]
        return layer.at[of_pass].set(rows.astype(layer.dtype))

    def _inject(kv_pages, kv_data, ids):
        """Scatter transferred KV pages (P/D transfer or tier-store
        resume) into the cache.  Padded ids point at the null page (page
        0), whose contents are never read unmasked.  pp>1: the cache is
        one stacked [L, ...] array (layer axis on pipe) and the payload
        arrives in the same layout, so one scatter covers every stage."""
        if cfg.pp > 1:
            return _kv_pin(
                kv_pages.at[:, ids].set(kv_data.astype(kv_pages.dtype)))
        return _kv_pin([
            _rows_at(layer, i, kv_data, ids)
            for i, layer in enumerate(kv_pages)
        ])

    def _inject_q(kv_pages, q, s, ids):
        """Quantized-cache variant: scatter int8 pages AND their
        scales (tier-store resume over kv_quant=int8)."""
        if cfg.pp > 1:
            pages, scales = kv_pages
            return _kv_pin((pages.at[:, ids].set(q.astype(pages.dtype)),
                            scales.at[:, ids].set(s.astype(scales.dtype))))
        return _kv_pin([
            (_rows_at(pages, i, q, ids), _rows_at(scales, i, s, ids))
            for i, (pages, scales) in enumerate(kv_pages)
        ])

    def _prefill_chunk(params, tokens, chunk_start, valid_len, kv_pages,
                       page_ids, adapter_ids):
        if cfg.pp > 1:
            # staged chunked prefill: unlocks long prompts AND prefix-
            # cache hits under pipeline parallelism
            logits, kv_pages = llama.prefill_chunk_pp(
                params, mc, tokens, chunk_start, valid_len, kv_pages,
                page_ids, cfg.page_size, mesh,
                _pp_microbatches(tokens.shape[0]),
                adapter_ids=adapter_ids,
            )
        else:
            logits, kv_pages = llama.prefill_chunk(
                params, mc, tokens, chunk_start, valid_len, kv_pages,
                page_ids, cfg.page_size, adapter_ids=adapter_ids,
            )
        return logits, _kv_pin(kv_pages)

    # the alignment the engine packs slices at: a block of that many
    # tokens holds one lane, which a hybrid model's packed scan and window
    # attention build on
    ragged_block = DispatchShapes.of(mc, cfg, jax.default_backend()).align
    expert_stats = mc.has_expert_sums

    def _make_mixed():
        """THE unified ragged program (docs/kernels.md): one dispatch
        serves an arbitrary mix of prompt chunks and decode lanes.

        Step 0 runs llama.forward_ragged over the packed token buffer —
        prompt chunks write their KV and decode lanes advance in the SAME
        causal-masked attention — then samples one token per lane (a
        finishing prompt's first token; a decode lane's next token).  The
        remaining steps_per_sync-1 steps are a standard decode scan over
        every lane host-side planning marked `joins`: decode lanes AND
        lanes whose prompt just completed, so a short request can prefill
        and decode its whole budget in one dispatch.  Lanes mid-chunk sit
        the scan out (joins=False); resumes override the scan's first
        token with their last generated token (scan_tok0 >= 0) since the
        ragged sample at a re-prefill boundary is discarded.

        Emits [steps, B] tokens like the legacy decode program; the host
        consumes per-lane windows (engine._route_mixed).

        What the host built for the dispatch arrives in three int32 arrays
        (shapes.MixedLayout: the tokens' buffer, the lanes' buffer, the page
        table), not in one argument a column: `packed` is the program, and
        cuts them into the arguments of `fn`, its body (kept as the
        program's `body` for the test that holds the two to the same
        tokens).  The dispatch's key is folded here from the base key, which
        stays on the device, and the step's number in the lanes' buffer."""

        def fn(params, q_tokens, token_seq, token_pos, q_start, q_len,
               kv_start, last_idx, kv_pages, page_table, joins, scan_tok0,
               scan_pos0, step0_emits, capacity, counters, state, rng,
               adapters):
            steps = cfg.steps_per_sync
            rngs = jax.random.split(rng, steps)
            truncates = sampler_truncates(state)  # once, not once a step
            top_k = sampler_top_k(state)  # likewise
            if expert_stats:
                # this dispatch's sums start at zero (kvcache.StateLayout)
                kv_pages = dict(kv_pages, stats=[
                    jnp.zeros_like(a) for a in kv_pages["stats"]])
            logits, kv_pages = llama.forward_ragged(
                params, mc, q_tokens, token_seq, token_pos,
                q_start, q_len, kv_start, kv_pages, page_table,
                cfg.page_size, last_idx,
                adapter_ids=adapters,
                attention_fn=ragged_attention_fn,
                use_pallas=cfg.use_pallas,
                ragged_block=ragged_block,
            )
            sampled0 = sample_tokens(
                logits, state, rngs[0], counters, truncates, top_k)
            tokens0 = jnp.where(scan_tok0 >= 0, scan_tok0, sampled0)
            counters0 = counters + step0_emits

            def body(carry, step_rng):
                tokens, pos, counters, kv_pages = carry
                live = joins & (pos < capacity)
                logits, kv_pages = llama.decode_step(
                    params, mc, tokens, pos, kv_pages, page_table, live,
                    cfg.page_size, use_pallas=cfg.use_pallas,
                    adapter_ids=adapters,
                    attention_fn=decode_attention_fn,
                )
                nxt = sample_tokens(
                    logits, state, step_rng, counters, truncates, top_k)
                nxt = jnp.where(live, nxt, tokens)
                return (
                    nxt,
                    pos + live.astype(pos.dtype),
                    counters + live.astype(counters.dtype),
                    kv_pages,
                ), nxt

            if steps > 1:
                init = (tokens0, scan_pos0, counters0, kv_pages)
                carry, scan_out = jax.lax.scan(body, init, rngs[1:])
                out = jnp.concatenate([sampled0[None], scan_out], axis=0)
                kv_pages = carry[3]
            else:
                out = sampled0[None]
            if expert_stats:
                # a row behind the tokens' for each sum (two; four where the
                # program counts its pairs: LlamaConfig.counts_routed_pairs),
                # the sum in column 0: fetched with the tokens, no sync of
                # their own
                sums = kv_pages["stats"][0]
                out = jnp.concatenate([out, jnp.broadcast_to(
                    sums[:, None].astype(out.dtype),
                    (sums.shape[0], out.shape[1]))], axis=0)
            return out, _kv_pin(kv_pages)

        def packed(params, tokens_buf, lanes_buf, kv_pages, page_table,
                   base_rng):
            layout = MixedLayout(
                tokens_buf.shape[1], lanes_buf.shape[1], page_table.shape[1])
            cols = layout.unpack(tokens_buf, lanes_buf)
            state = SamplingState(
                **{name: cols[name] for name in SAMPLER_COLUMNS})
            rng = jax.random.fold_in(base_rng, cols["step"])
            return fn(params, kv_pages=kv_pages, page_table=page_table,
                      state=state, rng=rng,
                      **{name: cols[name] for name in TOKEN_ROWS + PLAN_ROWS})

        packed.body = fn
        return packed

    def _make_mixed_decode(k_drafts: int):
        """Dense decode packing + self-drafting speculative verify
        (docs/kernels.md): the decode-only companion of `mixed`, chained
        depth-2 by the engine on pure-decode steps.

        Every round, each live lane packs a (K+1)-token slice — its last
        accepted token plus K drafts walked out of a per-lane bigram
        `draft_table` — at a STATIC stride, writes the slice's KV, runs
        the ragged forward once, and samples a target token at every
        slice position.  Acceptance is the vectorized longest prefix of
        drafts matching the target samples; the lane emits acc+1 tokens
        (accepted drafts ARE the target's samples there, plus the bonus
        sample at the rejection/acceptance frontier) and advances kv_len
        by the same amount.  Rollback costs nothing: rejected-draft KV
        sits past every causal horizon (never read) and the lane's next
        slice overwrites it in place.  Emitted tokens are ALWAYS samples
        from the target distribution — greedy lanes are token-identical
        to sequential decode, and seeded lanes are too (the per-row rng
        folds the same (seed, generated-count) pairs sequential decode
        folds).  K=0 degenerates to dense-packed plain decode: one token
        per lane per round, no drafts, no table reads.

        Returns ([rounds, B, K+1] target samples, [rounds, B] emit
        counts, pinned kv_pages, updated draft_table, and the final
        (token, pos, counters) device carry the engine feeds a chained
        dispatch without a host round-trip)."""
        from ..ops.attention import dense_stride_for

        Kp = k_drafts + 1
        sp = dense_stride_for(Kp, ragged_block)  # padded slice stride
        # lanes share the kernel's blocks only below its alignment
        dense_stride = sp if sp < ragged_block else None
        dense_attention_fn = None
        if cfg.tp > 1 or cfg.sp > 1:
            from ..ops.attention import make_sharded_ragged_attention

            dense_attention_fn = make_sharded_ragged_attention(
                mesh,
                logit_softcap=mc.attn_logit_softcap,
                use_pallas=cfg.use_pallas,
                quantized=_quantized,
                scale=mc.attn_scale,
                dense_stride=dense_stride,
            )

        def fn(params, tokens, pos, kv_pages, page_table, live, capacity,
               counters, draft_table, state, rng, adapter_ids):
            B = tokens.shape[0]
            rounds = cfg.steps_per_sync
            T = B * sp
            lane_of = jnp.repeat(jnp.arange(B, dtype=jnp.int32), sp)  # [T]
            off = jnp.tile(jnp.arange(sp, dtype=jnp.int32), B)  # [T]
            in_slice = off < Kp  # rows beyond K+1 are stride padding
            q_start = jnp.arange(B, dtype=jnp.int32) * sp
            # packed indices of the real (non-padding) slice rows, in
            # (lane, offset) order — the verify logits gather
            logits_at = (
                jnp.arange(B, dtype=jnp.int32)[:, None] * sp
                + jnp.arange(Kp, dtype=jnp.int32)[None, :]
            ).reshape(-1)
            # per-ROW sampling state: lane i's params replicated over its
            # K+1 slice rows, so every verify position samples with the
            # lane's own temperature/top-k/top-p/seed
            row_state = jax.tree.map(lambda a: jnp.repeat(a, Kp), state)
            truncates = sampler_truncates(state)  # once, not once a round
            top_k = sampler_top_k(state)  # likewise
            rngs = jax.random.split(rng, rounds)
            lane_ix = jnp.arange(B)

            def body(carry, step_rng):
                tok, p, cnt, table, kv_pages = carry
                # a lane runs a round only when its pages cover the whole
                # K+1-token write window; starved lanes sit the round out
                # (the host grows pages between dispatches) — mirrors the
                # capacity freeze of the plain decode scan
                ok = live & (p + Kp <= capacity)
                drafts = []
                prev = tok
                for _ in range(k_drafts):
                    nxt = table[lane_ix, prev]
                    # unseen bigram: draft the token itself (repetition is
                    # the cheapest guess; wrong drafts only cost
                    # acceptance, never correctness)
                    nxt = jnp.where(nxt >= 0, nxt, prev)
                    drafts.append(nxt)
                    prev = nxt
                slice_toks = jnp.stack([tok] + drafts, axis=1)  # [B, Kp]
                pad = jnp.zeros((B, sp - Kp), jnp.int32)
                q_tokens = jnp.concatenate(
                    [slice_toks, pad], axis=1).reshape(T)
                token_seq = jnp.where(
                    ok[lane_of] & in_slice, lane_of, -1)
                token_pos = p[lane_of] + off
                q_len = jnp.where(ok, Kp, 0).astype(jnp.int32)
                logits, kv_pages = llama.forward_ragged(
                    params, mc, q_tokens, token_seq, token_pos,
                    q_start, q_len, p, kv_pages, page_table,
                    cfg.page_size, q_start,  # last_idx unused (logits_at)
                    adapter_ids=adapter_ids,
                    attention_fn=dense_attention_fn,
                    use_pallas=cfg.use_pallas,
                    logits_at=logits_at,
                    dense_stride=dense_stride,
                )  # [B*Kp, V]
                row_counters = (
                    cnt[:, None] + jnp.arange(Kp, dtype=cnt.dtype)[None, :]
                ).reshape(-1)
                sampled = sample_tokens(
                    logits, row_state, step_rng, row_counters, truncates, top_k
                ).reshape(B, Kp)
                if k_drafts > 0:
                    match = (slice_toks[:, 1:] == sampled[:, :-1])
                    acc = jnp.cumprod(
                        match.astype(jnp.int32), axis=1).sum(axis=1)
                else:
                    acc = jnp.zeros((B,), jnp.int32)
                n_emit = jnp.where(ok, acc + 1, 0)
                new_tok = jnp.where(ok, sampled[lane_ix, acc], tok)
                new_p = p + n_emit
                new_cnt = cnt + n_emit
                if k_drafts > 0:
                    # learn the ACCEPTED chain's bigrams on device:
                    # (chain[j] -> chain[j+1]) for the emitted prefix —
                    # masked pairs scatter to a dropped out-of-range lane
                    chain = jnp.concatenate(
                        [tok[:, None], sampled], axis=1)  # [B, Kp+1]
                    srcs = chain[:, :-1].reshape(-1)
                    dsts = chain[:, 1:].reshape(-1)
                    pair_off = jnp.tile(jnp.arange(Kp), B)
                    pair_ok = (
                        ok[jnp.repeat(lane_ix, Kp)]
                        & (pair_off <= jnp.repeat(acc, Kp))
                    )
                    pair_lane = jnp.where(
                        pair_ok, jnp.repeat(lane_ix, Kp), B)
                    table = table.at[pair_lane, srcs].set(
                        dsts, mode="drop")
                new_carry = (new_tok, new_p, new_cnt, table, kv_pages)
                return new_carry, (sampled, n_emit)

            init = (tokens, pos, counters, draft_table, kv_pages)
            (tok, p, cnt, table, kv_pages), (toks_out, n_out) = (
                jax.lax.scan(body, init, rngs))
            # pin the device carries to canonical spellings — the same
            # settle hazard _kv_pin exists for: the table carry (and
            # the chained tok/pos/cnt) would otherwise come back with a
            # differently-SPELLED sharding and buy one retrace on the
            # next dispatch (tests/test_retrace_budget.py pins the spec
            # steady state at {mixed: 1, mixed_decode: 1}).  The table
            # pins to draft_table_pspec — the spelling GSPMD propagates
            # from the embedding it gathers against (a replicated
            # constraint is treated as unconstrained and re-spelled);
            # the engine commits refresh-built tables to the same.
            rep = shd.named(mesh, _P())
            pin = lambda a: jax.lax.with_sharding_constraint(a, rep)  # noqa: E731
            table = jax.lax.with_sharding_constraint(
                table, shd.named(mesh, shd.draft_table_pspec()))
            return (toks_out, n_out, _kv_pin(kv_pages), table,
                    pin(tok), pin(p), pin(cnt))

        return fn

    def _make_sample_first(with_logprobs: bool):
        def fn(logits, state, rng, in_prompt):
            # same first-token penalty semantics as the batched prefill:
            # repetition penalty counts prompt tokens as seen
            logits = apply_penalties(
                logits,
                jnp.zeros(logits.shape, jnp.int32),
                state.repetition_penalty,
                state.frequency_penalty,
                state.presence_penalty,
                in_prompt,
            )
            first = sample_tokens(logits, state, rng)
            if with_logprobs:
                return first, compute_logprobs(logits, first, cfg.max_logprobs)
            return first

        return fn

    n_kv_args = 3  # kv_pages is arg index 3 in the prefill/decode sigs
    # program name -> (python fn, donated arg indices): the one
    # definition table every consumer (jit, AOT, hlo_oracle) builds from.
    defs = {
        "prefill": (_make_prefill(False), (n_kv_args,)),
        "prefill_lp": (_make_prefill(True), (n_kv_args,)),
        "prefill_chunk": (_prefill_chunk, (4,)),
        "sample_first": (_make_sample_first(False), ()),
        "sample_first_lp": (_make_sample_first(True), ()),
        "decode": (_make_decode(False), (n_kv_args,)),
        "decode_lp": (_make_decode(False, with_logprobs=True), (n_kv_args,)),
        # arg 11 = prompt mask (kept across chunks), arg 12 = counts (donated)
        "decode_penalized": (_make_decode(True), (n_kv_args, 12)),
        "decode_penalized_lp": (
            _make_decode(True, with_logprobs=True), (n_kv_args, 12)),
        "inject": (_inject, (0,)),
        "inject_q": (_inject_q, (0,)),
    }
    if cfg.pp == 1:
        # the mixed program runs the flat per-layer forward; pp>1 engines
        # keep the staged legacy programs (use_ragged forces off there)
        defs["mixed"] = (_make_mixed(), (3,))
        if spec_k is not None:
            # kv_pages (3) and the draft table (8) are the device-resident
            # carries the engine threads dispatch to dispatch — both
            # donated, both updated in place
            defs["mixed_decode"] = (_make_mixed_decode(int(spec_k)), (3, 8))
    return defs


def build_compiled(model_config, engine_config, mesh,
                   aot_cache=None, spec_k=None) -> CompiledPrograms:
    """`aot_cache` (an engine/aot_cache.AOTExecutableCache) switches the
    program set from lazy ``jax.jit`` to persistent per-signature AOT
    executables — same call surface, zero compiles on a warm start.  The
    program table itself comes from `program_defs` (one definition table
    serves both dispatch modes AND the hlo_oracle's standalone lowering,
    so a program cannot exist jitted but be missing from the AOT-cached
    build or the perf budgets)."""
    defs = program_defs(model_config, engine_config, mesh, spec_k=spec_k)
    if aot_cache is not None:
        # persistent AOT path (engine/aot_cache.py): per-signature
        # executables lowered once and serialized to disk, so a warm
        # replica start dispatches without a single trace or XLA compile
        from .aot_cache import AOTProgram

        return CompiledPrograms(**{
            name: AOTProgram(name, fn, aot_cache, donate_argnums=donate)
            for name, (fn, donate) in defs.items()
        })
    return CompiledPrograms(**_counted(**{
        name: jax.jit(fn, donate_argnums=donate)
        for name, (fn, donate) in defs.items()
    }))
