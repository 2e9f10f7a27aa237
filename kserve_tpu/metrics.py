"""Prometheus metrics for the request path.

Parity: reference python/kserve/kserve/metrics.py (per-stage latency
histograms labeled by model name); extended with engine-level counters used
by the JAX generative engine (tokens generated, batch occupancy) so KPA-style
tokens/sec autoscaling has a native signal.
"""

from __future__ import annotations

from prometheus_client import Counter, Gauge, Histogram, Summary

PRE_HIST_TIME = Histogram(
    "request_preprocess_seconds", "pre-process request latency", ["model_name"]
)
POST_HIST_TIME = Histogram(
    "request_postprocess_seconds", "post-process request latency", ["model_name"]
)
PREDICT_HIST_TIME = Histogram(
    "request_predict_seconds", "predict request latency", ["model_name"]
)
EXPLAIN_HIST_TIME = Histogram(
    "request_explain_seconds", "explain request latency", ["model_name"]
)

# Generative engine metrics (no reference analogue; vLLM keeps these internal).
GENERATED_TOKENS = Counter(
    "engine_generated_tokens_total", "decode tokens generated", ["model_name"]
)
PROMPT_TOKENS = Counter(
    "engine_prompt_tokens_total", "prompt tokens prefill-processed", ["model_name"]
)
ENGINE_BATCH_OCCUPANCY = Gauge(
    "engine_batch_occupancy", "active sequences in the decode batch", ["model_name"]
)
ENGINE_QUEUE_DEPTH = Gauge(
    "engine_queue_depth", "requests waiting for admission", ["model_name"]
)
ENGINE_KV_PAGES_FREE = Gauge(
    "engine_kv_pages_free", "free KV cache pages", ["model_name"]
)
# The pool and what a token holds of it (engine/kvcache.py): a looped model
# keeps a K/V row for every (pass, layer), so one token's bytes, and with
# them the tokens a pool seats, follow passes x layers.
ENGINE_KV_PAGES_TOTAL = Gauge(
    "engine_kv_pages_total",
    "pages of the pool a sequence can be given (the null page left out)",
    ["model_name"],
)
ENGINE_KV_TOKEN_BYTES = Gauge(
    "engine_kv_token_bytes",
    "bytes of the pool one token of context holds, all its cache rows: K "
    "and V of every (pass, layer), or a latent layer's one row as stored "
    "(its padding to 128 lanes included)",
    ["model_name"],
)
# Per-lane state by kind (engine/kvcache.StateLayout): what the seated lanes
# hold now.  `kind`: shared_kv (K/V pages of the pool), latent_kv (latent
# attention's rows in pages of the pool), window_kv (window layers' rings),
# ssm and conv (recurrent layers' slots).
ENGINE_STATE_BYTES = Gauge(
    "engine_state_bytes", "bytes of per-lane state in use, by kind",
    ["model_name", "kind"],
)
ENGINE_STATE_SLOTS_IN_USE = Gauge(
    "engine_state_slots_in_use",
    "lanes seated: each holds its ring and recurrent-state slot",
    ["model_name"],
)
ENGINE_STATE_RESETS = Counter(
    "engine_state_resets_total",
    "lanes started from zero state (a request admitted at position 0)",
    ["model_name"],
)
ENGINE_WEDGED = Gauge(
    "engine_wedged", "1 once a device fetch blew the step deadline "
    "(liveness fails; pod restart expected)", ["model_name"]
)
ENGINE_PREEMPTIONS = Counter(
    "engine_preemptions_total",
    "sequences preempted back to the queue on KV pressure", ["model_name"],
)
ENGINE_KV_OFFLOAD_BYTES = Gauge(
    "engine_kv_offload_bytes",
    "KV bytes currently parked in the host-RAM tier", ["model_name"],
)
ENGINE_KV_DISK_BYTES = Gauge(
    "engine_kv_disk_bytes",
    "KV bytes currently parked in the disk tier", ["model_name"],
)

# Hierarchical KV store (kserve_tpu/kvstore — docs/kv_hierarchy.md).
# `tier` is the closed tier set (host | disk | persist — HBM never emits
# tier events: its eviction IS the host demote); `event` the closed
# movement enum.  No digest/request labels — per-digest detail lives in
# the /state prefix_store block.
KV_TIER_EVENTS = Counter(
    "kv_tier_events_total",
    "hierarchical KV store page movements (demote | pagein | drop | "
    "store | corrupt), by tier",
    ["tier", "event"],
)
# `tier` is the closed source set: hbm counts admission hits served from
# the device-resident prefix cache; host/disk/persist count tokens paged
# in from that tier (and therefore served as hits instead of prefilled);
# peer counts tokens paged in over the network from another replica's
# persistent store (kvstore/peer.py)
KV_PREFIX_HIT_TOKENS = Counter(
    "kv_prefix_hit_tokens_total",
    "prompt tokens served from cached prefix pages instead of being "
    "prefilled, by the tier that held them "
    "(hbm | host | disk | persist | peer)",
    ["model_name", "tier"],
)
KV_PAGEIN_SECONDS = Histogram(
    "kv_pagein_seconds",
    "wall time of one async prefix page-in: tier read scheduled -> pages "
    "uploaded and adopted into the HBM prefix cache",
    ["model_name"],
    buckets=(
        0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
        0.5, 1.0, 2.5, 5.0, float("inf"),
    ),
)

# Cross-replica KV page fabric (kvstore/peer.py — docs/kv_hierarchy.md
# "Cross-replica page serving").  `outcome` is the closed fetch-result
# enum; peer identity is a pod ip:port (unbounded under churn — the
# cardinality policy below) and lives in the scheduler_state() peer
# block and the EPP snapshots, never in a label.
KV_PEER_FETCH_TOTAL = Counter(
    "kv_peer_fetch_total",
    "cross-replica KV page fetch attempts by outcome: hit = verified and "
    "adopted, miss = peer answered 404, corrupt = payload failed digest "
    "verification (lying peer — also health evidence), timeout = "
    "transport failure / deadline / retries exhausted, breaker_open = "
    "skipped because the peer's circuit was open",
    ["outcome"],
)
KV_PEER_FETCH_SECONDS = Histogram(
    "kv_peer_fetch_seconds",
    "wall time of one peer page fetch: request issued -> payload "
    "digest-verified (successful fetches only)",
    buckets=(
        0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
        0.5, 1.0, 2.5, 5.0, float("inf"),
    ),
)
KV_PEER_PAGES_SERVED = Counter(
    "kv_peer_pages_served_total",
    "persisted px- pages this replica served to peers over "
    "GET /v1/internal/kv/pages/{digest}",
)

# Resilience layer (kserve_tpu/resilience — docs/resilience.md).
# Labeled by state only: backend identity is a pod ip:port, an unbounded
# label cardinality under replica churn (prometheus label children are
# never freed); per-backend state lives in the picker/router snapshots.
BREAKER_TRANSITIONS = Counter(
    "resilience_breaker_transitions_total",
    "circuit breaker state transitions",
    ["state"],
)
SHED_REQUESTS = Counter(
    "resilience_shed_requests_total",
    "requests bounced with 429 + Retry-After at admission",
    ["component"],
)
DEADLINE_REJECTED = Counter(
    "resilience_deadline_rejected_total",
    "requests rejected because their propagated deadline had expired",
    ["component"],
)
# Retry amplification: every retry a client loop issues BEYOND the first
# attempt.  rate(request_retry_attempts_total) / rate(first attempts)
# is the fleet's amplification factor; the simulator asserts it stays
# bounded (<= 2x) under churn, and production dashboards alarm on the
# same series.  Components are the literal set of in-repo retry loops:
# rest (inference_client REST), grpc (inference_client gRPC), graph
# (graph router steps), cluster (api.http_transport flow control), sim
# (the fleet simulator's client loop).
RETRY_ATTEMPTS = Counter(
    "request_retry_attempts_total",
    "retry attempts issued beyond a request's first try, per client loop",
    ["component"],
)

# Lifecycle layer (kserve_tpu/lifecycle — docs/lifecycle.md): graceful
# drain + preemption-safe resumable generation.
LIFECYCLE_STATE = Gauge(
    "replica_lifecycle_state",
    "1 for the replica's current lifecycle state "
    "(STARTING/READY/DRAINING/TERMINATING), 0 otherwise",
    ["state"],
)
DRAIN_DURATION = Histogram(
    "lifecycle_drain_duration_seconds",
    "wall time from drain start (SIGTERM / POST /admin/drain) until every "
    "in-flight generation finished or was checkpointed",
)
GENERATION_CHECKPOINTS = Counter(
    "generation_checkpoints_total",
    "live generations snapshotted into portable checkpoints",
    ["model_name", "reason"],
)
GENERATION_RESUMES = Counter(
    "generation_resumes_total",
    "generations resumed from a checkpoint on this replica",
    ["model_name"],
)
TOKENS_SALVAGED = Counter(
    "generation_tokens_salvaged_total",
    "decoded tokens carried across a drain/preemption via checkpoint "
    "instead of being re-decoded from scratch",
    ["model_name"],
)

# Gray-failure immune system (engine/watchdog.py + scheduler/health.py —
# docs/resilience.md).  `stat` and `transition` are closed enums; replica
# identity is deliberately NOT a label (unbounded under churn — the
# cardinality policy above): per-replica scores/status ride the picker
# snapshot and EPP /state.  `reason` comes from the closed checkpoint
# reason set ("stall" = watchdog self-drain rescued the stream, "hedge" =
# the client's inter-token hedge migrated it off a slow replica).
REPLICA_HEALTH_SCORE = Gauge(
    "replica_health_score",
    "fleet health-score distribution at the latest poll (min | median | "
    "max over replicas; per-replica scores live in the EPP /state)",
    ["stat"],
)
QUARANTINE_TRANSITIONS = Counter(
    "replica_quarantine_transitions_total",
    "gray-failure health state transitions "
    "(quarantine | reintroduce | degrade | restore)",
    ["transition"],
)
GENERATION_MIGRATIONS = Counter(
    "generation_migrations_total",
    "live generations migrated off a sick replica and resumed elsewhere, "
    "by trigger (stall = watchdog self-drain checkpoint, hedge = "
    "client-side inter-token-gap hedge)",
    ["reason"],
)


def record_quarantine_transition(transition: str) -> None:
    """FleetHealth transition hook; replica identity stays in /state."""
    QUARANTINE_TRANSITIONS.labels(transition=transition).inc()


def record_generation_migration(reason: str) -> None:
    GENERATION_MIGRATIONS.labels(reason=reason).inc()

# Request-lifecycle telemetry (kserve_tpu/observability — the serving
# metrics that matter per the vLLM/TGI comparative study, arXiv:2511.17593).
# Sub-millisecond buckets on ITL because decode steps on-chip are ~1-10ms;
# TTFT/e2e reach minutes because long-prompt prefill + queueing legitimately
# do.  All observations come from the engine's injectable Clock.
_TTFT_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, float("inf"),
)
_ITL_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
    0.5, 1.0, 2.5, float("inf"),
)
REQUEST_TTFT = Histogram(
    "request_ttft_seconds",
    "time to first token: request received by the engine -> first token "
    "emitted (queue wait included — the client experiences it)",
    ["model_name"], buckets=_TTFT_BUCKETS,
)
REQUEST_ITL = Histogram(
    "request_inter_token_seconds",
    "inter-token latency: gap between consecutive emitted tokens",
    ["model_name"], buckets=_ITL_BUCKETS,
)
REQUEST_QUEUE_WAIT = Histogram(
    "request_queue_wait_seconds",
    "received -> admitted into a decode slot (first admission)",
    ["model_name"], buckets=_TTFT_BUCKETS,
)
REQUEST_E2E = Histogram(
    "request_e2e_seconds",
    "received -> finished (full generation wall time)",
    ["model_name"], buckets=_TTFT_BUCKETS,
)
ENGINE_STEP_DURATION = Histogram(
    "engine_decode_step_seconds",
    "wall time of one decode step: a steps_per_sync-token chunk dispatched "
    "and its tokens fetched",
    ["model_name"], buckets=_ITL_BUCKETS,
)
ENGINE_PREFILL_CHUNK_DURATION = Histogram(
    "engine_prefill_chunk_seconds",
    "wall time of one compiled prefill call (batched admission or one "
    "long-prompt chunk)",
    ["model_name"], buckets=_TTFT_BUCKETS,
)
# `program` is the fixed compiled-program name set (engine/compiled.py),
# bounded by construction — NOT a shape signature (unbounded under bucket
# drift) nor a request attribute
XLA_COMPILES = Counter(
    "engine_xla_compiles_total",
    "XLA compilations observed (jit cache misses incl. retraces), by "
    "compiled engine program",
    ["program"],
)
XLA_COMPILE_SECONDS = Counter(
    "engine_xla_compile_seconds_total",
    "wall seconds of the calls that missed the jit cache (trace + compile "
    "+ the first run), by compiled engine program: what a compile cost the "
    "loop it blocked",
    ["program"],
)
# `phase` is the closed set observability/timeline.PHASES plus `wait_lag`;
# the six tile one iteration of the engine's loop, so their sum over
# engine_dispatches_total is the dispatch period
ENGINE_DISPATCH_PHASE_SECONDS = Counter(
    "engine_dispatch_phase_seconds_total",
    "seconds of the engine's loop by phase of a dispatch: admit | plan | "
    "launch | wait | route | yield, and wait_lag (the part of wait in "
    "which the result was on the host and the loop had not resumed)",
    ["model_name", "phase"],
)
ENGINE_DISPATCHES = Counter(
    "engine_dispatches_total",
    "device dispatches committed by the engine's loop, by program",
    ["model_name", "program"],
)
# Handing a dispatch's tokens to their streams (detokenise, the output, the
# queue put) is split from what the tokens change in the engine's state: on
# the `mixed` path it runs after the NEXT dispatch is launched, inside that
# one's `wait` (docs/observability.md "Dispatch phases").  `when` is the
# closed set observability.timeline.DELIVERIES.
ENGINE_DISPATCH_DELIVERIES = Counter(
    "engine_dispatch_deliveries_total",
    "tokens handed to their streams, by when: overlapped (after the NEXT "
    "dispatch was launched, while it ran) | inline (in place, before the "
    "next launch: a lane with stop strings, the legacy and dense paths, "
    "nothing to launch next); fed from the committed dispatch rows, as the "
    "phases are",
    ["model_name", "when"],
)
# `part` is the closed set observability.timeline.PARTS: what the host's
# phases are made of, each timed inside its phase by DispatchPhases.span
# (docs/observability.md "Dispatch phases")
ENGINE_DISPATCH_PART_SECONDS = Counter(
    "engine_dispatch_part_seconds_total",
    "seconds of the engine's loop by part of a phase: prepare | sampling | "
    "pack (of plan), upload | call | account (of launch), deliver (handing "
    "deferred tokens to their streams, inside wait or in place), register "
    "(finishes, page frees and prefix registration, of route); a part never "
    "counts what a part opened inside it counts; fed from the committed "
    "dispatch rows, as the phases are",
    ["model_name", "part"],
)
# Host-to-device transfers of the launches' inputs: every one goes through
# the engine's one helper (LLMEngine._upload), which counts it on the row of
# the dispatch under way.  A transfer costs ~0.25 ms of the loop whatever it
# carries, so `mixed` hands its inputs over in three (shapes.MixedLayout);
# over engine_dispatches_total this reads 3 there
ENGINE_DISPATCH_UPLOADS = Counter(
    "engine_dispatch_uploads_total",
    "host-to-device transfers of the inputs the engine's loop built for its "
    "launches (a `mixed` dispatch: the tokens' buffer, the lanes' buffer, "
    "the page table); fed from the committed dispatch rows, as the phases "
    "are",
    ["model_name"],
)
# CPU seconds of the loop's THREAD (time.thread_time), booked to the phase
# each stamp closes: in `wait` they are the work the device's step hides
# (delivery, the SSE writes, the handlers); over the phases' wall seconds
# they say how near the host is to being the bottleneck
ENGINE_DISPATCH_PHASE_CPU_SECONDS = Counter(
    "engine_dispatch_phase_cpu_seconds_total",
    "CPU seconds of the thread that runs the engine's loop, by phase of a "
    "dispatch (the phases of engine_dispatch_phase_seconds_total); fed from "
    "the committed dispatch rows",
    ["model_name", "phase"],
)
# XLA compiles of anything in the process that is NONE of the engine's own
# programs (a helper jitted on a new shape, an `.at[].set`,
# jax.random.fold_in), from jax.monitoring's backend-compile event
# (observability/pauses.py): what engine_xla_compile_seconds_total cannot
# see, and stalls the loop as long.  The engine's own compiles are filtered
# out (pauses.PROGRAM_COMPILE) and stay with engine_xla_compiles_total.  Of
# the process, so no model_name.
OTHER_COMPILE_SECONDS = Counter(
    "engine_other_compile_seconds_total",
    "seconds of XLA backend compiles anywhere in the process outside the "
    "engine's own compiled programs (those are "
    "engine_xla_compile_seconds_total); above zero after warm-up, "
    "something compiled under traffic: the dispatch row's other_compile "
    "says which iteration it hit, the log's warning which function",
)
# `generation` is "1" or "2": a collection of generation 0 is not timed
GC_PAUSE_SECONDS = Counter(
    "engine_gc_pause_seconds_total",
    "seconds the Python collector held the process, by the generation "
    "collected (1 | 2; generation 0 is not timed)",
    ["generation"],
)
# `sampler_path` is the closed set engine/sampling.SAMPLER_PATHS: what the
# dispatch's batch asked of the sampler, decided on the device by the same
# predicate (docs/observability.md "Sampler paths"); not named `path`,
# which the cardinality gate keeps for URL paths
ENGINE_SAMPLER_DISPATCHES = Counter(
    "engine_sampler_dispatches_total",
    "device dispatches by the path their batch takes through the sampler: "
    "truncate (a sampled row carries top-k, top-p or min-p: threshold "
    "searches over the vocabulary each step, no sort) | plain (none does: "
    "no pass)",
    ["model_name", "sampler_path"],
)
# `fit` is the closed set engine/shapes.FITS: how the (T, W) pair a `mixed`
# dispatch ran in was found among the pairs the program is loaded in
ENGINE_DISPATCH_SHAPE = Counter(
    "engine_dispatch_shape_total",
    "mixed dispatches by how their (T, W) pair was found: exact (the "
    "needed pair was loaded) | padded (it was not: the smallest loaded "
    "pair that holds it) | compiled (no loaded pair holds it: the needed "
    "pair, new to this engine)",
    ["model_name", "fit"],
)
# What the forward did, counted at launch from the dispatch's plan
# (docs/observability.md "Passes and context").  A forward step is one run
# of the model over a dispatch's tokens: the packed step and each decode
# step of a dispatch.
ENGINE_LAYER_PASSES = Counter(
    "engine_layer_passes_total",
    "passes of the layer stack run: forward steps x the model's passes "
    "(1 a step for a model that is not looped)",
    ["model_name"],
)
ENGINE_KV_WRITE_CALLS = Counter(
    "engine_kv_write_calls_total",
    "K/V writes of one layer in one forward step (a layer-step), by the "
    "path the program was built with: page_kernel (the Pallas page write) | "
    "row_scatter (XLA's scatter); from static shapes: forward steps x "
    "passes x the layers that write a cache of that path",
    ["model_name", "write_path"],
)
ENGINE_KV_CONTEXT_TOKENS = Counter(
    "engine_kv_context_tokens_total",
    "sum over a dispatch's decode steps of the cached tokens its live "
    "lanes attend to: the work of decode attention, in tokens a cache row; "
    "where the packed step hands its single-token lanes to the decode "
    "kernel (engine_packed_lanes_total{attention_path=\"decode_kernel\"}) those "
    "lanes' tokens too, as one more step",
    ["model_name"],
)
#: what is summed of a decode step's pages of context: the pages the lanes
#: `own`; the walk of the decode kernel's `block`s (each out to its longest
#: lane) as it forms them, from lanes in order of length
#: (ops/pallas_paged_attention.length_order)
KV_DECODE_REACHES = ("own", "block")
ENGINE_KV_DECODE_PAGES = Counter(
    "engine_kv_decode_pages_total",
    "sum over a dispatch's decode steps of pages of context, by reach: own = "
    "the pages its live lanes hold; block = over the decode kernel's blocks "
    "of lanes, lanes a block x the pages of the block's longest lane (what "
    "it fetches and folds), the lanes dealt to blocks in order of length; "
    "the packed step's call of the kernel over its single-token lanes "
    "counts as one more step, where the program makes it",
    ["model_name", "reach"],
)
#: which kernel attends for a lane of the packed step (`attention_path`):
#: the decode kernel in one call over the lanes that bring ONE token, where
#: the program was built with that split
#: (ops/attention.ragged_attention_path); the ragged kernel, or its XLA
#: reference, for every other slice
PACKED_LANE_PATHS = ("decode_kernel", "ragged")
ENGINE_PACKED_LANES = Counter(
    "engine_packed_lanes_total",
    "lanes with a slice in a `mixed` dispatch's packed step, by the kernel "
    "that attends for them over the pool's pages: decode_kernel (a slice of "
    "one token, in a program whose packed step hands such lanes to the "
    "decode kernel) | ragged (every other slice); counted at planning from "
    "the plan's slice lengths and how the program was built",
    ["model_name", "attention_path"],
)
# Expert layers (models/moe.py).  Assignments are counted at launch from the
# dispatch's tokens; hits and peak load are summed IN the program over its
# forward steps and expert layers and come back with the dispatch's tokens.
# Where the chip holds a share of the experts all three count what THIS
# chip multiplied (the assignments then come back with the tokens too, as
# do the pairs routed), and a fourth counts the pairs routed to experts
# held elsewhere.
ENGINE_MOE_ASSIGNMENTS = Counter(
    "engine_moe_assignments_total",
    "(token, expert) pairs the routed experts held here multiplied: forward "
    "tokens x experts a token x expert layers where every expert is held",
    ["model_name"],
)
ENGINE_MOE_PAIRS_ELSEWHERE = Counter(
    "engine_moe_pairs_elsewhere_total",
    "(token, expert) pairs routed to experts this chip does not hold: the "
    "pairs the program's expert layers routed over the rows each saw, less "
    "those it multiplied; given to no group, multiplied by nobody here; 0 "
    "where every expert is held",
    ["model_name"],
)
ENGINE_MOE_EXPERTS_HELD = Gauge(
    "engine_moe_experts_held",
    "experts of an expert layer held on this chip, of the `scored` the "
    "router chooses among",
    ["model_name", "of"],
)
# Mamba-2 mixers (ops/ssm.ssd_*): what the two forms were asked to do,
# counted at launch from the dispatch's plan.
ENGINE_SSD_SCAN_TOKENS = Counter(
    "engine_ssd_scan_tokens_total",
    "tokens the packed step's chunked scan took (real tokens of the packed "
    "buffer x Mamba-2 layers)",
    ["model_name"],
)
ENGINE_SSD_UPDATE_CALLS = Counter(
    "engine_ssd_update_calls_total",
    "one-step state updates launched: decode steps x Mamba-2 layers",
    ["model_name"],
)
ENGINE_SSD_UPDATE_LANE_STEPS = Counter(
    "engine_ssd_update_lane_steps_total",
    "live lanes summed over those updates: lane-steps whose state moved on",
    ["model_name"],
)
# Kimi-delta mixers (ops/delta.kda_*): likewise.
ENGINE_KDA_CHUNK_TOKENS = Counter(
    "engine_kda_chunk_tokens_total",
    "tokens the packed step's chunked delta rule took (real tokens of the "
    "packed buffer x Kimi-delta layers)",
    ["model_name"],
)
ENGINE_KDA_UPDATE_LANE_STEPS = Counter(
    "engine_kda_update_lane_steps_total",
    "live lanes summed over the decode steps' one-step delta-rule updates "
    "and Kimi-delta layers: lane-steps whose state moved on",
    ["model_name"],
)
# Gated short convolutions (models/hybrid.py `short_conv`): likewise.
ENGINE_CONV_PACKED_TOKENS = Counter(
    "engine_conv_packed_tokens_total",
    "tokens the packed step's gated short convolutions took (real tokens "
    "of the packed buffer x short-convolution layers)",
    ["model_name"],
)
ENGINE_CONV_UPDATE_LANE_STEPS = Counter(
    "engine_conv_update_lane_steps_total",
    "live lanes summed over the decode steps' one-token convolutions and "
    "short-convolution layers: lane-steps whose tail moved on",
    ["model_name"],
)
# Window layers that keep a ring a lane (models/hybrid.py): whether the
# window BOUND a decode step's attention, counted at launch from the plan.
ENGINE_WINDOW_LANE_STEPS = Counter(
    "engine_window_lane_steps_total",
    "decode lane-steps of a model with window rings, by whether the lane's "
    "context at that step was past the window (bound=yes: the ring is full "
    "and the step reads `sliding_window` tokens whatever the context) or "
    "not (bound=no: it reads the context); the rule of "
    "engine_kv_context_tokens_total, evaluated on the host",
    ["model_name", "bound"],
)
ENGINE_WINDOW_RAGGED_WORK = Counter(
    "engine_window_ragged_work_total",
    "what the packed step's window attention is asked to do, summed over "
    "lanes' slices and window layers at launch: unit=queries (tokens of "
    "the slices), unit=pairs ((query, key) pairs inside the window: its "
    "operations), unit=keys (ring tokens a slice's first query sees plus "
    "the slice's own: K/V rows it must read at least once)",
    ["model_name", "unit"],
)
ENGINE_MOE_EXPERT_HITS = Counter(
    "engine_moe_expert_hits_total",
    "sum over forward steps and expert layers of the experts that got at "
    "least one token: what was read of the experts' weights, in experts",
    ["model_name"],
)
ENGINE_MOE_PEAK_LOAD = Counter(
    "engine_moe_peak_load_total",
    "sum over forward steps and expert layers of the fullest expert's rows",
    ["model_name"],
)
ENGINE_FIRST_TOKEN_DISPATCHES = Summary(
    "engine_first_token_dispatches",
    "dispatches from a request's admission to its first token, both "
    "included, observed once per request at the first token",
    ["model_name"],
)
# `role` is a closed enum (decoding/prefilling/free): batch composition per
# engine step without per-request labels
ENGINE_STEP_BATCH_COMPOSITION = Gauge(
    "engine_step_batch_composition",
    "decode-batch slots by role at the latest engine step "
    "(decoding | prefilling | free); under the unified ragged program the "
    "roles are token counts (prefill_tokens | decode_tokens), and with "
    "speculative decoding additionally spec_accepted_tokens — the latest "
    "dispatch's accepted-draft length",
    ["model_name", "role"],
)
# Speculative decoding (docs/kernels.md): `outcome` is the closed
# drafted | accepted | rejected set.  accepted/drafted is the fleet's
# live acceptance rate; every ACCEPTED token is also counted in
# engine_generated_tokens_total (these series classify drafts, they do
# not double-count output).
SPEC_TOKENS = Counter(
    "engine_spec_tokens_total",
    "speculative-decoding draft tokens by outcome (drafted | accepted | "
    "rejected); bonus target samples are ordinary generated tokens and "
    "are not counted here",
    ["model_name", "outcome"],
)

# Replica startup phases (kserve_tpu/engine/aot_cache.py — docs/coldstart.md).
# `phase` is the closed STARTUP_PHASES enum; buckets reach minutes because a
# cold 8B compile + weight load legitimately does.
STARTUP_PHASES = ("trace", "compile", "aot_load", "weights", "ready")
_STARTUP_BUCKETS = (
    0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
    60.0, 120.0, 300.0, float("inf"),
)
ENGINE_STARTUP = Histogram(
    "engine_startup_seconds",
    "replica startup wall time by phase: trace (jaxpr+lowering), compile "
    "(XLA), aot_load (executable deserialization from the AOT cache), "
    "weights (checkpoint read + device placement), ready (total "
    "construct->serving)",
    ["model_name", "phase"], buckets=_STARTUP_BUCKETS,
)
# `program` is the fixed compiled-program name set (same bound as
# engine_xla_compiles_total); `event` is a closed enum
AOT_CACHE_EVENTS = Counter(
    "engine_aot_cache_events_total",
    "persistent AOT executable cache events (hit | miss | store | invalid), "
    "by compiled engine program",
    ["program", "event"],
)


# Autoscaler (kserve_tpu/autoscale — docs/autoscaling.md).  `action` and
# `reason` come from the closed ACTIONS/REASONS sets in autoscale/policy.py
# (every decision is explained in the same vocabulary dashboards see);
# `signal` is the fixed FleetSignals field enum; `outcome` the closed
# hold-queue terminal set.  No per-replica/backend labels — per-replica
# detail lives in the EPP /state snapshot.
AUTOSCALER_DECISIONS = Counter(
    "autoscaler_decisions_total",
    "scaling decisions taken by the EPP-signal autoscaler loop, by action "
    "and policy reason",
    ["action", "reason"],
)
AUTOSCALER_TARGET_REPLICAS = Gauge(
    "autoscaler_target_replicas",
    "replica count the autoscaler currently wants (post-clamp)",
)
AUTOSCALER_SIGNAL = Gauge(
    "autoscaler_signal",
    "fleet-wide autoscaling signals at the latest decision tick "
    "(ready_replicas | queue_depth | inflight | shed_rate_per_s | "
    "arrival_rate_per_s | held_requests | ttft_p99_s)",
    ["signal"],
)
GATEWAY_HOLDS = Counter(
    "gateway_hold_outcomes_total",
    "zero-window hold-and-replay outcomes at the gateway "
    "(replayed | expired | overflow | failed)",
    ["outcome"],
)


def observe_startup_phase(model_name: str, phase: str, seconds: float) -> None:
    """Record one engine_startup_seconds observation (phase must be in
    STARTUP_PHASES; anything else is a programming error worth raising)."""
    if phase not in STARTUP_PHASES:
        raise ValueError(f"unknown startup phase {phase!r}")
    ENGINE_STARTUP.labels(model_name=model_name, phase=phase).observe(seconds)


def observe_request_timeline(model_name: str, timeline) -> None:
    """Export one finished RequestTimeline to the Prometheus histograms
    (observability/timeline.py keeps the ring-buffer/percentile view)."""
    if timeline.queue_wait_s is not None:
        REQUEST_QUEUE_WAIT.labels(model_name=model_name).observe(
            timeline.queue_wait_s)
    if timeline.ttft_s is not None:
        REQUEST_TTFT.labels(model_name=model_name).observe(timeline.ttft_s)
    if timeline.e2e_s is not None:
        REQUEST_E2E.labels(model_name=model_name).observe(timeline.e2e_s)
    itl = REQUEST_ITL.labels(model_name=model_name)
    for gap in timeline.itls:
        itl.observe(gap)


_LIFECYCLE_STATES = ("STARTING", "READY", "DRAINING", "TERMINATING")


def set_lifecycle_state(state: str) -> None:
    """One-hot the lifecycle gauge (the PromQL-friendly enum idiom)."""
    for s in _LIFECYCLE_STATES:
        LIFECYCLE_STATE.labels(state=s).set(1.0 if s == state else 0.0)


def record_breaker_transition(backend: str, state: str) -> None:
    """The BreakerRegistry on_transition hook (resilience/breaker.py);
    `backend` is part of the hook signature but deliberately not a label."""
    BREAKER_TRANSITIONS.labels(state=state).inc()


def get_labels(model_name: str) -> dict:
    return {"model_name": model_name}
