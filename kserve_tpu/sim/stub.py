"""Cycle-accurate stub device for the fleet simulator.

`build_stub_programs()` returns an object with the exact attribute
surface of `engine.compiled.CompiledPrograms`, so a real `LLMEngine`
runs its real admission, batching, chunked prefill, preemption, drain
and checkpoint logic against it — only the device math is replaced:

- tokens come from a deterministic chain (`stub_first_token` /
  `stub_next_token`) that is a pure function of prompt length and
  position, so the SAME stream continues token-exactly across
  preemption, checkpoint and cross-replica resume — which is what lets
  the goodput report prove zero lost / zero duplicated tokens without
  comparing against a second uninterrupted run;
- compute costs are configurable virtual durations (`StubCosts`)
  charged to a per-replica `StubDevice` timeline, paid when the engine
  fetches the result: the decode hot loop awaits them on the SimClock
  (fleet compute overlaps), sync prefill fetches jump the clock
  (conservative, one call per admission batch);
- a `clock_skew` FaultSpec targeting ``<replica>.compute`` (or a direct
  `device.skew` knob) multiplies costs — the deterministic slow-replica
  stand-in.

`SimFetcher` replaces the engine's daemon fetch worker with an
event-loop-thread implementation: thread handoff order is the one piece
of nondeterminism a byte-identical simulation cannot keep.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np

from ..engine.shapes import MixedLayout

# token ids the stub emits: a printable-ASCII band, clear of BOS/EOS/PAD
# (ByteTokenizer reserves 256..258) so streams never hit an accidental
# EOS and detokenize to readable text
SAFE_LO = 32
SAFE_BAND = 64


def stub_first_token(prompt_len: int) -> int:
    """First sampled token for a prompt of `prompt_len` tokens."""
    return SAFE_LO + (prompt_len * 31 + 17) % SAFE_BAND


def stub_next_token(prev: int, pos: int) -> int:
    """Decode chain: the token decoded at KV position `pos` given the
    previous token.  Depending only on (prev, pos) is what makes the
    chain resumable: a checkpointed stream re-seated anywhere continues
    with exactly the token the uninterrupted stream would have had."""
    return SAFE_LO + ((prev - SAFE_LO) * 7 + pos * 13 + 29) % SAFE_BAND


def stub_spec_accept(prev: int, pos: int, k_drafts: int) -> int:
    """Tokens emitted by one speculative verify round for a lane whose
    chain state is (prev token, kv position): 1..k_drafts+1, a pure
    function of the CHAIN STATE — not of wall time, lane index or batch
    composition — so the acceptance pattern is byte-identical per seed
    AND resumes token-exactly: a checkpointed stream re-seated anywhere
    replays the same accept/reject sequence the uninterrupted stream had
    (the `expected_stream` oracle keeps holding with speculation on)."""
    return 1 + (prev * 7 + pos * 11 + 3) % (k_drafts + 1)


def expected_stream(prompt_len: int, n_tokens: int) -> List[int]:
    """The exact token stream a request with `prompt_len` prompt tokens
    generates — the goodput report's token-accounting oracle."""
    if n_tokens <= 0:
        return []
    out = [stub_first_token(prompt_len)]
    for k in range(1, n_tokens):
        out.append(stub_next_token(out[-1], prompt_len + k - 1))
    return out


@dataclass
class StubCosts:
    """Virtual compute costs, per compiled-program dispatch."""

    prefill_base_s: float = 2e-3  # fixed launch cost per prefill call
    prefill_per_token_s: float = 2e-5  # per prompt token in the call
    decode_step_s: float = 2e-3  # per decode step (chunk = steps_per_sync)
    inject_s: float = 1e-3  # per KV-injection scatter
    # replica-start costs (the AOT-cache story, docs/coldstart.md): a COLD
    # build pays compile_s (XLA-compiling the program set before ready — on
    # a chip this is tens of seconds); a WARM build pays aot_load_s
    # (deserializing persisted executables — orders of magnitude cheaper).
    # Charged once at StubPrograms build, so the cold/warm ready-time delta
    # is assertable in tier-1.  Default 0 keeps pre-AOT scenarios unchanged.
    compile_s: float = 0.0
    aot_load_s: float = 0.0
    # speculative decoding (docs/kernels.md): each mixed_decode round
    # costs one decode step PLUS this much per draft token verified — the
    # ragged multi-token chunk is more compute than a single-token step,
    # but far less than K separate dispatches.  With the stub's seeded
    # acceptance pattern (avg (K+2)/2 tokens per round) the default makes
    # decode-heavy spec traffic >2x tok/s in virtual time at K=4.
    spec_verify_per_token_s: float = 2e-4
    # kernel block-granularity modeling (docs/kernels.md dense packing):
    # on the modeled TPU the ragged kernel walks this-many-token query
    # blocks, so a mixed dispatch pays (align-1) wasted token-slots of
    # step-0 compute PER DECODE LANE (each single-token lane burns a
    # whole block), which the dense mixed_decode packing avoids.  0 (the
    # default) disables the charge — every pre-dense scenario's virtual
    # timeline stays byte-identical; 8 (RAGGED_BQ)
    # prices the K=0 dense-packing win in sim terms.
    ragged_align_tokens: int = 0

    @classmethod
    def from_oracle(cls, budgets: dict, decode_step_s: float = 2e-3,
                    variant: str = "tp1", **overrides) -> "StubCosts":
        """Derive cost RATIOS from the HLO perf oracle's committed
        budgets (analysis/hlo_oracle, perf_budgets.json) instead of
        inventing them: anchor one wall-clock number — `decode_step_s`,
        a measured (or assumed) per-decode-step latency — and scale the
        other program costs by their oracle-extracted FLOP/byte ratios
        (ROADMAP 5b: sim SLO numbers become predictions, not fictions).

        - prefill_per_token_s: decode's seconds-per-flop times the
          largest prefill bucket's flops-per-token;
        - inject_s: decode_step_s scaled by the inject/decode-step
          bytes-accessed ratio (the scatter is bandwidth-, not
          flop-bound);
        - spec_verify_per_token_s: the extra flops a K-draft
          mixed_decode round carries over a plain decode step, divided
          by K, priced at decode's seconds-per-flop.

        Programs missing from the budgets keep the dataclass defaults;
        `overrides` pin any field explicitly.  Raises ValueError when
        the decode anchor itself is missing — a cost model silently
        built from nothing would be the old fiction with better
        branding."""
        programs = budgets.get("programs", budgets)

        def _norm(entry, field, default=1):
            return max(int(entry.get("norm", {}).get(field, default)), 1)

        decode = programs.get(f"{variant}/decode")
        if not decode or not decode.get("flops"):
            raise ValueError(
                f"from_oracle: no usable {variant}/decode entry in the "
                "budgets (run `python -m kserve_tpu.analysis.hlo_oracle "
                "update`)")
        steps = _norm(decode, "steps")
        flops_per_step = float(decode["flops"]) / steps
        bytes_per_step = float(decode.get("bytes_accessed", 0.0)) / steps
        s_per_flop = decode_step_s / flops_per_step
        fields: dict = {"decode_step_s": decode_step_s}

        prefills = sorted(
            (k, e) for k, e in programs.items()
            if k.startswith(f"{variant}/prefill/b") and e.get("flops"))
        if prefills:
            _, pf = prefills[-1]  # largest bucket: the steady-state shape
            fields["prefill_per_token_s"] = s_per_flop * (
                float(pf["flops"]) / _norm(pf, "tokens"))

        inject = programs.get(f"{variant}/inject")
        if inject and inject.get("bytes_accessed") and bytes_per_step:
            fields["inject_s"] = decode_step_s * (
                float(inject["bytes_accessed"]) / bytes_per_step)

        spec = [
            e for k, e in programs.items()
            if f"/mixed_decode/k" in k and k.startswith(variant)
            and e.get("norm", {}).get("k") and e.get("flops")
        ]
        if spec:
            e = spec[0]
            k = int(e["norm"]["k"])
            round_flops = float(e["flops"]) / _norm(e, "steps")
            extra = max(round_flops - flops_per_step, 0.0)
            fields["spec_verify_per_token_s"] = s_per_flop * extra / k
        fields.update(overrides)
        return cls(**fields)


class StubDevice:
    """One replica's device timeline: dispatches accumulate `busy_until`,
    fetches wait for it.  `skew` (set directly or via a clock_skew /
    slow_decode fault targeting ``<name>.compute``) multiplies every
    subsequent cost.

    Gray-failure knobs (docs/resilience.md — the replica stays alive and
    pollable through all of these; detection belongs to the engine
    watchdog and fleet health scoring, never to liveness):

    - ``wedge_fetch_until(t)`` parks the ASYNC fetch path until virtual
      time `t`: dispatches land, the fetch worker just never delivers —
      the stall shape the engine watchdog exists to confirm.  Sync
      fetches (batched-prefill admission) ignore it: a sync clock jump
      to the wedge horizon would drag the whole fleet's virtual time
      forward.
    - ``flap(period_s, skew)`` alternates compute between normal and
      ``skew``-slow in `period_s` windows — a flapping host that defeats
      consecutive-failure counting.
    """

    def __init__(self, name: str, costs: StubCosts, clock):
        self.name = name
        self.costs = costs
        self.clock = clock
        self.busy_until = 0.0
        self.skew = 1.0
        self.wedged_until = 0.0
        self.flap_period_s = 0.0
        self.flap_skew = 1.0
        # resilience.FaultPlan shared with the engine (SimReplica wires it)
        self.fault_plan = None
        self.dispatches = 0

    def wedge_fetch_until(self, until_s: float) -> None:
        self.wedged_until = max(self.wedged_until, until_s)

    def flap(self, period_s: float, skew: float) -> None:
        self.flap_period_s = period_s
        self.flap_skew = skew

    def heal_gray(self) -> None:
        """Clear every gray-failure knob (the heal_skew churn leg)."""
        self.skew = 1.0
        self.wedged_until = 0.0
        self.flap_period_s = 0.0
        self.flap_skew = 1.0

    def _effective_skew(self, now: float) -> float:
        s = self.skew
        if self.flap_period_s > 0 and int(now / self.flap_period_s) % 2:
            s *= self.flap_skew
        return s

    def dispatch(self, cost_s: float) -> None:
        now = self.clock.now()
        cost = cost_s * self._effective_skew(now)
        if self.fault_plan is not None:
            spec = self.fault_plan.decide(f"{self.name}.compute")
            if spec is not None and spec.kind in ("clock_skew", "slow_decode"):
                cost *= spec.skew
        self.dispatches += 1
        self.busy_until = max(self.busy_until, now) + cost

    def reset(self) -> None:
        """Fresh device for a restarted replica."""
        self.busy_until = 0.0
        self.heal_gray()


class SimFetcher:
    """Duck-type of engine.types._DeadlineFetcher that runs the fetch thunk
    on the event-loop thread and pays the stub device's accumulated compute
    time in virtual seconds: the async path parks on the SimClock (other
    replicas keep running — fleet overlap), the sync path jumps the clock
    (the engine's batched-prefill fetch is synchronous by design)."""

    def __init__(self, device: StubDevice, clock):
        self.device = device
        self.clock = clock

    def fetch(self, fn, timeout_s: float):
        # sync fetches deliberately ignore the gray wedge (see
        # StubDevice.wedge_fetch_until): jumping the shared clock to the
        # wedge horizon would fast-forward the whole fleet
        out = fn()
        self.clock.advance_to(self.device.busy_until)
        return out

    async def fetch_async(self, fn, timeout_s: float, meanwhile=None):
        out = fn()
        if meanwhile is not None:
            meanwhile()
        # a gray-wedged fetch worker: the result exists on the "device",
        # it just never gets delivered until the wedge lifts — liveness
        # stays green, the step deadline never fires (the sim fetcher
        # has no wedge deadline by design), and only the engine
        # watchdog's no-progress detection catches it
        await self.clock.sleep_until(
            max(self.device.busy_until, self.device.wedged_until))
        return out

    def close(self) -> None:
        pass


class StubPrograms:
    """CompiledPrograms-shaped set of host-math device programs.

    Every function matches the jitted signature it replaces (see
    engine/compiled.py) and returns plain numpy arrays — the engine's
    `_fetch`/`_fetch_async` np.asarray conversion is then a no-op and all
    cost accounting lives on the StubDevice timeline."""

    def __init__(self, engine_config, device: StubDevice,
                 vocab_size: int = 512, warm: bool = False):
        self._cfg = engine_config
        self._device = device
        self._vocab = vocab_size
        self._K = engine_config.max_logprobs
        # replica-start cost (mirrors engine.aot_warmup running BEFORE the
        # replica turns ready): a cold build XLA-compiles the program set,
        # a warm build deserializes it from the node's AOT cache
        self.warm = warm
        self.startup_cost_s = (
            device.costs.aot_load_s if warm else device.costs.compile_s)
        if self.startup_cost_s > 0:
            device.dispatch(self.startup_cost_s)
        self.prefill = self._make_prefill(False)
        self.prefill_lp = self._make_prefill(True)
        self.prefill_chunk = self._prefill_chunk
        self.sample_first = self._make_sample_first(False)
        self.sample_first_lp = self._make_sample_first(True)
        self.decode = self._make_decode(False, False)
        self.decode_lp = self._make_decode(False, True)
        self.decode_penalized = self._make_decode(True, False)
        self.decode_penalized_lp = self._make_decode(True, True)
        self.inject = self._inject
        self.inject_q = self._inject_q
        self.mixed = self._mixed
        # the dense/speculative decode program exists only when the
        # engine config asks for it — pre-spec scenarios keep their
        # byte-identical traces (the engine falls back to mixed-only
        # when the attribute is absent)
        if getattr(engine_config, "spec_decode_k", None) is not None:
            self.mixed_decode = self._mixed_decode

    # ---------------- prefill ----------------

    def _charge_prefill(self, valid: np.ndarray) -> None:
        c = self._device.costs
        self._device.dispatch(
            c.prefill_base_s + c.prefill_per_token_s * int(valid.sum()))

    def _lp_zeros(self, *lead):
        lp = np.zeros(lead, np.float32)
        tv = np.zeros(lead + (self._K,), np.float32)
        ti = np.zeros(lead + (self._K,), np.int32)
        return lp, tv, ti

    def _make_prefill(self, with_logprobs: bool):
        def fn(params, tokens, valid_len, kv_pages, page_ids, state, rng,
               adapters):
            valid = np.asarray(valid_len)
            self._charge_prefill(valid)
            # fused prefill carries the whole (uncached) sequence per row,
            # so the row's total length IS its valid count
            first = np.asarray(
                [stub_first_token(int(v)) for v in valid], np.int32)
            if with_logprobs:
                return first, self._lp_zeros(valid.shape[0]), kv_pages
            return first, kv_pages

        return fn

    def _prefill_chunk(self, params, tokens, chunk_start, valid_len,
                       kv_pages, page_ids, adapters):
        start = np.asarray(chunk_start)
        valid = np.asarray(valid_len)
        self._charge_prefill(valid)
        # "logits" carry each row's total prefilled length so sample_first
        # reproduces the fused path's first token exactly: chunk_start +
        # valid == full sequence length on the final chunk, whether the
        # prefix came from the cache, earlier chunks, or both
        return _StubLogits(start + valid), kv_pages

    def _make_sample_first(self, with_logprobs: bool):
        def fn(logits, state, rng, in_prompt):
            totals = logits.totals
            first = np.asarray(
                [stub_first_token(int(t)) for t in totals], np.int32)
            if with_logprobs:
                return first, self._lp_zeros(first.shape[0])
            return first

        return fn

    # ---------------- decode ----------------

    def _make_decode(self, with_penalties: bool, with_logprobs: bool):
        def fn(params, tokens, pos, kv_pages, page_table, active, capacity,
               counters, state, rng, adapters, *penalty_arrays):
            steps = self._cfg.steps_per_sync
            tok = np.asarray(tokens)
            pos_np = np.asarray(pos)
            act = np.asarray(active)
            cap = np.asarray(capacity)
            B = tok.shape[0]
            self._device.dispatch(self._device.costs.decode_step_s * steps)
            chunk = np.zeros((steps, B), np.int32)
            for i in range(B):
                if not act[i]:
                    continue
                prev = int(tok[i])
                p = int(pos_np[i])
                limit = int(cap[i])
                for s in range(steps):
                    if p + s < limit:
                        # capacity-capped lanes freeze at their last real
                        # token (mirrors the jitted program's mask), so a
                        # chained chunk's tokens_dev row is always the
                        # correct chain predecessor
                        prev = stub_next_token(prev, p + s)
                    chunk[s, i] = prev
            out = chunk
            if with_logprobs:
                out = (chunk,) + self._lp_zeros(steps, B)
            if with_penalties:
                # counts array rides through untouched (host penalty state
                # is refreshed from slot lists, never read back)
                return out, kv_pages, penalty_arrays[1]
            return out, kv_pages

        return fn

    # ---------------- unified ragged (mixed) program ----------------

    def _mixed(self, params, tokens_buf, lanes_buf, kv_pages, page_table,
               base_rng):
        """Host-math twin of engine/compiled.py's mixed program, emitting
        the SAME deterministic token chain as the legacy stub paths so
        checkpoint/resume stays token-exact across both program sets and
        `expected_stream()` remains the oracle.  It takes the program's
        three packed buffers and cuts them apart by the program's own
        layout (engine/shapes.MixedLayout), in numpy.

        Step-0 discrimination mirrors the engine's packing contract: a
        lane sampling its FIRST token has counters==0 (stub_first_token of
        its full sequence length); a decode lane has counters>=1 and
        continues the chain from its packed token; a resume boundary
        (step0_emits==0 with scan_tok0>=0) re-enters the chain at its
        checkpointed token."""
        steps = self._cfg.steps_per_sync
        tokens_buf, lanes_buf = np.asarray(tokens_buf), np.asarray(lanes_buf)
        cols = MixedLayout(
            tokens_buf.shape[1], lanes_buf.shape[1],
            np.shape(page_table)[1]).unpack(tokens_buf, lanes_buf)
        toks = cols["q_tokens"]
        qs = cols["q_start"]
        ql = cols["q_len"]
        ks = cols["kv_start"]
        jn = cols["joins"]
        st0 = cols["scan_tok0"]
        sp0 = cols["scan_pos0"]
        emits0 = cols["step0_emits"]
        cap = cols["capacity"]
        cnt = cols["counters"]
        B = qs.shape[0]
        # cost: the ragged step pays prefill for every packed prompt
        # token (non-decode lanes) + the scan pays the decode chunk
        c = self._device.costs
        n_prefill = int(sum(
            int(ql[i]) for i in range(B)
            if ql[i] > 0 and not (emits0[i] == 1 and cnt[i] >= 1)
        ))
        cost = c.decode_step_s * steps
        if n_prefill:
            cost += c.prefill_base_s + c.prefill_per_token_s * n_prefill
        if c.ragged_align_tokens > 1:
            # block-granularity waste: every decode lane's single-token
            # slice burns a whole align-token kernel block in step 0 —
            # the cost the dense mixed_decode packing exists to avoid
            n_decode = int(sum(
                1 for i in range(B)
                if ql[i] > 0 and emits0[i] == 1 and cnt[i] >= 1))
            cost += (n_decode * (c.ragged_align_tokens - 1)
                     * c.prefill_per_token_s)
        self._device.dispatch(cost)
        chunk = np.zeros((steps, B), np.int32)
        for i in range(B):
            if ql[i] <= 0:
                continue
            decode_lane = emits0[i] == 1 and cnt[i] >= 1
            if decode_lane:
                # packed token is generated[-1] at position kv_start
                s0 = stub_next_token(int(toks[qs[i]]), int(ks[i]))
            else:
                # a completed (or still-chunking: discarded) prompt slice
                s0 = stub_first_token(int(ks[i]) + int(ql[i]))
            chunk[0, i] = s0
            prev = int(st0[i]) if st0[i] >= 0 else s0
            p = int(sp0[i])
            limit = int(cap[i])
            for s in range(1, steps):
                if jn[i] and p < limit:
                    prev = stub_next_token(prev, p)
                    p += 1
                chunk[s, i] = prev
        return chunk, kv_pages

    # ---------------- dense / speculative decode (mixed_decode) ----------------

    def _mixed_decode(self, params, tokens, pos, kv_pages, page_table,
                      live, capacity, counters, draft_table, state, rng,
                      adapters):
        """Host-math twin of engine/compiled.py's mixed_decode: every
        round each live lane with page capacity for a full (K+1)-token
        slice emits `stub_spec_accept(prev, pos)` tokens of the SAME
        deterministic chain the other stub programs emit — acceptance
        varies, the token stream never does, so `expected_stream()` stays
        the oracle and the goodput report's zero-lost/zero-duplicated
        accounting covers speculative traffic.  Returns the engine
        contract: ([rounds, B, K+1] tokens, [rounds, B] emit counts,
        kv_pages, draft_table, and the final (token, pos, counters)
        carry for depth-2 chaining)."""
        cfg = self._cfg
        K = cfg.spec_decode_k or 0
        Kp = K + 1
        rounds = cfg.steps_per_sync
        tok = np.array(np.asarray(tokens), np.int64)
        p = np.array(np.asarray(pos), np.int64)
        cnt = np.array(np.asarray(counters), np.int64)
        lv = np.asarray(live)
        cap = np.asarray(capacity)
        B = tok.shape[0]
        c = self._device.costs
        self._device.dispatch(
            rounds * (c.decode_step_s + c.spec_verify_per_token_s * K))
        toks = np.zeros((rounds, B, Kp), np.int32)
        n = np.zeros((rounds, B), np.int32)
        for r in range(rounds):
            for i in range(B):
                if not lv[i] or p[i] + Kp > cap[i]:
                    continue  # capacity-starved lanes sit the round out
                acc = stub_spec_accept(int(tok[i]), int(p[i]), K)
                prev = int(tok[i])
                pp = int(p[i])
                for j in range(acc):
                    prev = stub_next_token(prev, pp)
                    pp += 1
                    toks[r, i, j] = prev
                n[r, i] = acc
                tok[i] = prev
                p[i] = pp
                cnt[i] += acc
        return (toks, n, kv_pages, draft_table, tok.astype(np.int32),
                p.astype(np.int32), cnt.astype(np.int32))

    # ---------------- KV injection (P/D, tier-store resume) ----------------

    def _inject(self, kv_pages, kv_data, ids):
        self._device.dispatch(self._device.costs.inject_s)
        return kv_pages

    def _inject_q(self, kv_pages, q, s, ids):
        self._device.dispatch(self._device.costs.inject_s)
        return kv_pages


class _StubLogits:
    """Per-row total prefilled length, standing in for the [B, V] logits
    the real chunked prefill hands to sample_first."""

    __slots__ = ("totals",)

    def __init__(self, totals: np.ndarray):
        self.totals = np.asarray(totals, np.int64)


def build_stub_programs(engine_config, device: StubDevice,
                        vocab_size: int = 512,
                        warm: bool = False) -> StubPrograms:
    return StubPrograms(engine_config, device, vocab_size=vocab_size,
                        warm=warm)
