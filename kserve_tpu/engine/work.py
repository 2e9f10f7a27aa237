"""What a dispatch runs, counted: the host's arithmetic over a launch's plan
behind the benchmark's roofline readers.  The engine calls its one
`DispatchWork` once a launch (`packed` for `mixed`, `forward` for every other
program) and once a fetched result (`fetched`); the counters are functions
of the plan alone.  A new layer kind adds its counters here, not to the
scheduler.
"""

from __future__ import annotations

from typing import Callable, Dict

import numpy as np

from ..metrics import (
    ENGINE_CONV_PACKED_TOKENS,
    ENGINE_CONV_UPDATE_LANE_STEPS,
    ENGINE_KDA_CHUNK_TOKENS,
    ENGINE_KDA_UPDATE_LANE_STEPS,
    ENGINE_KV_CONTEXT_TOKENS,
    ENGINE_KV_DECODE_PAGES,
    ENGINE_KV_WRITE_CALLS,
    ENGINE_LAYER_PASSES,
    ENGINE_MOE_ASSIGNMENTS,
    ENGINE_MOE_EXPERT_HITS,
    ENGINE_MOE_EXPERTS_HELD,
    ENGINE_MOE_PAIRS_ELSEWHERE,
    ENGINE_MOE_PEAK_LOAD,
    ENGINE_PACKED_LANES,
    ENGINE_SSD_SCAN_TOKENS,
    ENGINE_SSD_UPDATE_CALLS,
    ENGINE_SSD_UPDATE_LANE_STEPS,
    ENGINE_WINDOW_LANE_STEPS,
    ENGINE_WINDOW_RAGGED_WORK,
    KV_DECODE_REACHES,
    PACKED_LANE_PATHS,
)
from ..observability import KV_WRITE_PATHS
from .kvcache import StateLayout, pages_needed


#: a recurrent mixer's two forms, by its kind: the counter of the packed
#: tokens its chunked form is handed and the counter of the live lanes x
#: decode steps its one-step form is (each x the layers of that kind)
_FORM_COUNTERS = {
    "mamba2": (ENGINE_SSD_SCAN_TOKENS, ENGINE_SSD_UPDATE_LANE_STEPS),
    "kda": (ENGINE_KDA_CHUNK_TOKENS, ENGINE_KDA_UPDATE_LANE_STEPS),
    "short_conv": (ENGINE_CONV_PACKED_TOKENS, ENGINE_CONV_UPDATE_LANE_STEPS),
}


def _page_reach(pages) -> Dict[str, int]:
    """`pages` [calls, lanes], the pages a lane holds of its context at each
    call of the decode kernel, summed by metrics.KV_DECODE_REACHES: `own`
    sums the pages the lanes hold; `block` sums, over the kernel's blocks
    (`_pick_sb(lanes)` lanes each, dealt in order of length:
    ops/pallas_paged_attention.length_order), the lanes of a block x the
    pages of its longest lane: the iterations of the kernel's loop x the
    ring slots an iteration has."""
    from ..ops.pallas_paged_attention import _pick_sb

    sb = _pick_sb(pages.shape[1])
    longest = np.sort(pages, axis=1).reshape(len(pages), -1, sb).max(axis=2)
    return {"own": int(pages.sum()), "block": int(longest.sum()) * sb}


class DispatchWork:
    """The engine_*_total work counters' labelled children and the per-kind
    layer counts they multiply by.  `attention` is the engine's
    `dispatch_report["attention"]`; `wrote(path, n)` hears every K/V write
    beside its counter (DispatchPhases.wrote: the row's `kv_*` columns)."""

    def __init__(self, model_config, layout: StateLayout, attention: dict,
                 metrics_label: str, wrote: Callable[[str, int], None]):
        def child(metric, **labels):
            return metric.labels(model_name=metrics_label, **labels)

        table = model_config.layer_table()
        self._attention = attention
        self._page_size = layout.page_size
        self._wrote = wrote
        self._n_passes = model_config.n_passes
        self._layer_passes = child(ENGINE_LAYER_PASSES)
        self._kv_context_tokens = child(ENGINE_KV_CONTEXT_TOKENS)
        self._kv_decode_pages = {
            reach: child(ENGINE_KV_DECODE_PAGES, reach=reach)
            for reach in KV_DECODE_REACHES}
        self._packed_lanes = {
            path: child(ENGINE_PACKED_LANES, attention_path=path)
            for path in PACKED_LANE_PATHS}
        # layers a pass writes by each K/V write path: the report's path of
        # each kind of cache x the layers of that kind
        self._kv_write_calls = {
            path: child(ENGINE_KV_WRITE_CALLS, write_path=path)
            for path in KV_WRITE_PATHS}
        self._kv_write_layers = dict.fromkeys(KV_WRITE_PATHS, 0)
        for kind, path in attention["kv_write"].items():
            self._kv_write_layers[path] += len(getattr(layout, kind + "_layers"))
        # engine_moe_*_total: pairs counted at launch (tokens x experts a
        # token x expert layers) unless the program counts its own; hits and
        # peak load summed in the program (the `mixed` program's last rows)
        self._moe_assignments = child(ENGINE_MOE_ASSIGNMENTS)
        self._moe_hits = child(ENGINE_MOE_EXPERT_HITS)
        self._moe_peak = child(ENGINE_MOE_PEAK_LOAD)
        self._moe_elsewhere = child(ENGINE_MOE_PAIRS_ELSEWHERE)
        self._expert_sums = (
            layout.expert_sums if model_config.has_expert_sums else 0)
        self._host_pairs = (
            model_config.n_experts_per_tok * model_config.n_expert_layers
            if model_config.n_experts > 0
            and not model_config.counts_routed_pairs else 0)
        if model_config.has_expert_sums:
            child(ENGINE_MOE_EXPERTS_HELD, of=str(model_config.n_experts)).set(
                model_config.n_experts_held or model_config.n_experts)
        # engine_ssd_*_total, engine_kda_*_total, engine_conv_*_total: what
        # the recurrent mixers' two forms are asked (layers of the kind, its
        # two counters)
        self._forms = [
            (sum(row.kind == kind for row in table), child(chunked), child(stepped))
            for kind, (chunked, stepped) in _FORM_COUNTERS.items()]
        self._ssd_layers = sum(row.kind == "mamba2" for row in table)
        self._ssd_update_calls = child(ENGINE_SSD_UPDATE_CALLS)
        # engine_window_*_total: layers that keep a ring a lane, and their
        # window (0 where there is none: nothing is counted)
        self._ring_layers = len(layout.window_layers)
        self._ring_window = layout.window
        self._window_lane_steps = {
            bound: child(ENGINE_WINDOW_LANE_STEPS, bound=bound)
            for bound in ("yes", "no")}
        self._window_ragged_work = {
            unit: child(ENGINE_WINDOW_RAGGED_WORK, unit=unit)
            for unit in ("queries", "pairs", "keys")}

    def packed(self, plan: dict, width: int, steps: int) -> None:
        """A `mixed` launch of `plan`, run at a page table of `width`: the
        packed step, then steps - 1 decode steps over the joining lanes."""
        self.forward(
            steps, plan["scan_pos0"], plan["joins"], plan["capacity"],
            decode_steps=steps - 1,
            packed_tokens=plan["prefill_tokens"] + plan["decode_tokens"])
        self.packed_lanes(plan["q_len"], plan["kv_start"], width)
        if self._ring_window:
            self.window_ragged(plan["q_len"], plan["kv_start"])

    def forward(self, steps: int, pos=None, live=None, capacity=None,
                decode_steps: int = 0, packed_tokens: int = 0,
                legacy_prefill: bool = False) -> None:
        """Count what a launch runs: `steps` forward steps (each all the
        model's passes, each pass one K/V write a writing layer: by the path
        the program was built with, and by the row scatter in a
        `legacy_prefill` program whatever the others take) and, over its
        `decode_steps` decode steps, the cached tokens the live lanes attend
        to.  Lane b, live at position pos[b], attends to pos[b] + s + 1
        tokens at decode step s while it stays under its page capacity: the
        device's own rule (compiled._make_decode / _make_mixed), evaluated
        on the host; the pages of those tokens are counted beside them, as
        the lanes hold them and as the decode kernel's blocks of lanes walk
        them (_page_reach).  The tokens that pass the model
        (`packed_tokens` in the packed step, one a live lane and decode
        step) are each routed to `n_experts_per_tok` experts in every expert
        layer, where every expert layer sees them all and every expert is
        held; else the program counts its pairs
        (LlamaConfig.counts_routed_pairs)."""
        self._layer_passes.inc(steps * self._n_passes)
        for path, layers in self._kv_write_layers.items():
            if layers:
                path = "row_scatter" if legacy_prefill else path
                self._kv_write_calls[path].inc(steps * self._n_passes * layers)
                self._wrote(path, steps * self._n_passes * layers)
        tokens = packed_tokens
        if decode_steps and pos is not None:
            pos = np.asarray(pos, np.int64)
            n = np.where(np.asarray(live),
                         np.clip(np.asarray(capacity) - pos, 0, decode_steps), 0)
            self._kv_context_tokens.inc(int(np.sum(n * pos + n * (n + 1) // 2)))
            step = np.arange(decode_steps)[:, None]  # none past n: seq_lens
            for reach, pages in _page_reach(np.where(
                    step < n, pages_needed(pos + step + 1, self._page_size),
                    0)).items():
                self._kv_decode_pages[reach].inc(pages)
            tokens += int(np.sum(n))
            if self._ring_window:
                # step s of a lane at pos attends to pos + s + 1 tokens: past
                # the window from s = window - pos on
                free = np.clip(self._ring_window - pos, 0, n)
                self._window_lane_steps["no"].inc(int(np.sum(free)))
                self._window_lane_steps["yes"].inc(int(np.sum(n - free)))
        for layers, chunked, stepped in self._forms:
            if layers:
                chunked.inc(packed_tokens * layers)
                stepped.inc((tokens - packed_tokens) * layers)
        self._ssd_update_calls.inc(decode_steps * self._ssd_layers)
        if self._host_pairs and tokens:
            # every expert is held and every expert layer sees every token:
            # what is routed is multiplied.  Else the counts are the
            # program's own and come back with the dispatch's tokens
            self._moe_assignments.inc(tokens * self._host_pairs)

    def packed_lanes(self, q_len, kv_start, width: int) -> None:
        """engine_packed_lanes_total for one packed step run at a table of
        `width` pages and, where the program hands its single-token lanes
        to the decode kernel from that width on (the report's
        `packed_single_token_min_pages`), that call's work on the decode
        attention's counters: one more step for those lanes, each at
        `kv_start + 1` tokens of context, every other lane at 0 (the
        kernel's `seq_lens` as
        ops/pallas_paged_attention.ragged_single_token_split_pallas hands
        them over)."""
        q_len = np.asarray(q_len)
        single = q_len == 1
        min_pages = self._attention["packed_single_token_min_pages"]
        split = min_pages is not None and width >= min_pages
        self._packed_lanes["decode_kernel" if split else "ragged"].inc(
            int(single.sum()))
        self._packed_lanes["ragged"].inc(int((q_len > 1).sum()))
        if split:
            context = np.where(single, np.asarray(kv_start, np.int64) + 1, 0)
            self._kv_context_tokens.inc(int(context.sum()))
            for reach, pages in _page_reach(pages_needed(
                    context, self._page_size)[None, :]).items():
                self._kv_decode_pages[reach].inc(pages)

    def window_ragged(self, q_len, kv_start) -> None:
        """engine_window_ragged_work_total for one packed step: the query at
        offset j of a slice that starts at `kv_start` sees min(kv_start + j
        + 1, window) keys; the slice must read the ring tokens its first
        query sees and its own."""
        R, layers = self._ring_window, self._ring_layers
        n, s = np.asarray(q_len, np.int64), np.asarray(kv_start, np.int64)
        under = np.clip(R - s, 0, n)  # queries whose context is <= window
        pairs = under * s + under * (under + 1) // 2 + (n - under) * R
        keys = np.where(n > 0, np.minimum(s, R - 1) + n, 0)
        work = self._window_ragged_work
        work["queries"].inc(int(n.sum()) * layers)
        work["pairs"].inc(int(pairs.sum()) * layers)
        work["keys"].inc(int(keys.sum()) * layers)

    def fetched(self, chunk_np):
        """A `mixed` dispatch's fetched rows without the expert layers' sums
        behind its tokens (models/hybrid._ffn): hits and peak load, then
        where the program counts them the pairs it multiplied and the pairs
        its layers routed; the rest went to experts held elsewhere."""
        n = self._expert_sums
        if not n:
            return chunk_np
        chunk_np, sums = chunk_np[:-n], chunk_np[-n:, 0]
        self._moe_hits.inc(int(sums[0]))
        self._moe_peak.inc(int(sums[1]))
        if n > 2:
            self._moe_assignments.inc(int(sums[2]))
            self._moe_elsewhere.inc(int(sums[3] - sums[2]))
        return chunk_np
