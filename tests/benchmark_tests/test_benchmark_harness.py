"""Pieces of the harness that need no server: the warm-up grid, the
/metrics reader, the model directory, the per-layer readers on a recorded
run, and the model arithmetic."""

import json
import os

import pytest
from bench_paths import BENCH, ROOT

from kbench import manifest, model_math, server, stats, warmup
from kbench.loadgen import body_for, parse_token_id
from kbench.schedule import Request


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def cell_grid(cell, rehearsal=False):
    """(grid, server flags) of a cell file, resolved as run.py's Plan does:
    the configuration's `engine_policy` cut to the cell's server sizes."""
    pair = load("cells", cell)
    config = load("configs", cell.split(".")[0])
    dep = config["deployment"]
    flags = {**dep["server_flags"], **pair.get("server_flags", {})}
    policy, warm = dep["engine_policy"], pair["warm"]
    if rehearsal:
        tiny = config["rehearsal"]
        flags.update(tiny["server_flags"])
        policy, warm = {**policy, **tiny["engine_policy"]}, tiny["warm"]
    return warmup.grid_of(policy, flags, warm), flags


CELL_FILES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "cells")))


def bucket(buckets, n):
    return next(b for b in buckets if n <= b)


def test_grid_ladders_follow_the_engine_policy_and_the_cells_sizes():
    """The token ladder ends at max_prefill_len, the width ladder doubles
    up to max_model_len's pages (the program's EngineConfig does the same),
    and a cell file carries no copy of either."""
    grid, _ = cell_grid("qwen3-4b.chat")
    assert grid["token_buckets"] == [32, 64, 128, 256, 512, 1024]
    assert grid["width_buckets"] == [8, 16, 32, 64, 128]
    grid, _ = cell_grid("qwen3-4b.decode-sat")
    assert grid["token_buckets"] == [32, 64, 128, 256, 512]
    assert grid["width_buckets"] == [8, 16, 32, 40]
    grid, _ = cell_grid("qwen3-4b.chat", rehearsal=True)
    assert (grid["lane_tokens"], grid["lanes"], grid["pacers"]) == (1, 8, 3)
    assert grid["warm_tokens"] == [32, 64, 128] and grid["warm_widths"] == [8, 16]
    for cell in CELL_FILES:
        assert not {"token_buckets", "width_buckets", "lane_tokens",
                    "tokens_per_dispatch", "warmup_grid"} & set(load("cells", cell))


def test_the_policy_copy_matches_the_program():
    """`engine_policy` is a copy: this is where a change of the program's
    packing policy is noticed on the CPU, before a chip run misses shapes."""
    from kserve_tpu.engine.types import EngineConfig
    from kserve_tpu.ops.pallas_paged_attention import RAGGED_BQ

    policy = load("configs", "qwen3-4b")["deployment"]["engine_policy"]
    engine = EngineConfig(max_batch_size=48, num_pages=2300, page_size=16,
                          max_pages_per_seq=128)
    assert policy["lane_tokens"] == RAGGED_BQ
    assert policy["tokens_per_dispatch"] == engine.steps_per_sync
    assert tuple(policy["token_buckets"]) == engine.prefill_buckets
    assert policy["min_width"] == engine.page_bucket(1)
    grid, _ = cell_grid("qwen3-4b.chat")
    for pages in (1, 9, 33, 65, 128):
        assert bucket(grid["width_buckets"], pages) == engine.page_bucket(pages)


@pytest.mark.parametrize("rehearsal", (False, True), ids=("chip", "rehearsal"))
@pytest.mark.parametrize("cell", CELL_FILES)
def test_grid_reaches_every_warmed_pair_of_buckets(cell, rehearsal):
    """Per stage: anchor and pacers stay inside the width bucket W for
    their whole life and pack into the smallest warmed token bucket; each
    wave packs, beside them, into its token bucket and no smaller one, and
    its requests stay inside W pages."""
    grid, flags = cell_grid(cell, rehearsal)
    page, lane = grid["page_size"], grid["lane_tokens"]
    tokens, widths = grid["token_buckets"], grid["width_buckets"]
    assert tokens[-1] == flags["max_prefill_len"]
    assert widths[-1] == flags["max_model_len"] // page
    warm_t, warm_w = grid["warm_tokens"], grid["warm_widths"]
    assert set(warm_t) <= set(tokens) and set(warm_w) <= set(widths)
    base = lane * (grid["pacers"] + 1)
    plan = warmup.grid_plan(grid)
    assert [w for w, *_ in plan] == warm_w
    for w, a_prompt, life, waves in plan:
        assert bucket(widths, -(-(a_prompt + 1) // page)) == w
        assert bucket(widths, -(-(a_prompt + life) // page)) == w
        assert a_prompt <= tokens[-1]  # one chunk: no stray tail dispatch
        reached = {bucket(tokens, base)}
        for t, k, p in waves:
            assert bucket(tokens, base + k * p) == t
            assert -(-(p + 1) // page) <= w
            assert k + grid["pacers"] + 1 <= grid["lanes"]
            reached.add(t)
        assert reached == set(warm_t)
        # the stage's background outlives its waves: two dispatches a wave
        assert life >= grid["tokens_per_dispatch"] * 2 * len(waves)


def test_grid_plan_refuses_what_it_cannot_reach():
    grid, _ = cell_grid("qwen3-4b.chat")
    with pytest.raises(ValueError, match="pacers already pack past"):
        warmup.grid_plan(dict(grid, warm_tokens=[64]))
    with pytest.raises(ValueError, match="outgrows"):
        warmup.grid_plan(dict(grid, warm_widths=[8], pacers=1,
                              tokens_per_dispatch=64))


@pytest.mark.parametrize("mix", ("chat", "decode-sat"))
def test_ramp_primer_is_a_warmed_shape(mix):
    """The primer is alone in the ramp's first dispatch: its prompt's token
    bucket and its pages' width bucket are a pair the cell's grid warms."""
    primer = load("traffic", mix)["ramp_primer"]
    grid, _ = cell_grid("qwen3-4b." + mix)
    lane, page = grid["lane_tokens"], grid["page_size"]
    aligned = -(-primer["prompt_len"] // lane) * lane
    assert bucket(grid["token_buckets"], aligned) in grid["warm_tokens"]
    pages = -(-(primer["prompt_len"] + 1) // page)
    assert bucket(grid["width_buckets"], pages) in grid["warm_widths"]


def test_knee_is_the_highest_rate_that_holds():
    import run as bench_run

    def row(rate, slo, tok, first, last, failed=0):
        return {"rate": rate, "slo_share": slo, "output_tok_s": tok,
                "ttft_first_third_ms": first, "ttft_last_third_ms": last,
                "failed": failed}

    rows = [row(4, 99, 324, 700, 750), row(5, 96, 405, 800, 900),
            row(6, 91, 480, 900, 1500), row(7, 60, 520, 1500, 6000)]
    assert bench_run.knee_of(rows) == 6
    assert bench_run.knee_of(rows[:1] + [row(5, 96, 405, 800, 2300)]) == 4
    assert bench_run.knee_of([row(4, 80, 324, 700, 750)]) is None
    assert bench_run.knee_of(rows[:2] + [row(6, 95, 420, 900, 1000)]) == 5


def test_parse_metrics_and_deltas():
    page = (
        '# HELP engine_xla_compiles_total x\n'
        'engine_xla_compiles_total{program="mixed"} 36.0\n'
        'engine_xla_compiles_total{program="inject"} 1.0\n'
        'engine_startup_seconds_sum{model_name="bench",phase="ready"} 18.5\n'
        'engine_generated_tokens_total{model_name="bench"} 1.5e+04\n'
        'plain_gauge 3\n')
    snap = server.parse_metrics(page)
    assert server.metric_sum(snap, "engine_xla_compiles_total") == 37.0
    assert server.metric_sum(snap, "engine_xla_compiles_total", program="mixed") == 36.0
    assert server.metric_sum(snap, "engine_startup_seconds_sum", phase="ready") == 18.5
    assert server.metric_sum(snap, "plain_gauge") == 3.0
    later = server.parse_metrics(page.replace("1.5e+04", "1.6e+04"))
    assert server.metric_delta(snap, later, "engine_generated_tokens_total") == 1000.0


def test_model_dir_holds_the_config_as_run_and_a_one_to_one_tokenizer(tmp_path):
    from tokenizers import Tokenizer

    cfg = {"vocab_size": 300, "hidden_size": 8}
    path = str(tmp_path / "m")
    server.write_model_dir(path, cfg)
    with open(os.path.join(path, "config.json")) as f:
        assert json.load(f) == cfg
    tok = Tokenizer.from_file(os.path.join(path, "tokenizer.json"))
    assert tok.get_vocab_size() == 300
    ids = [0, 7, 255, 256, 299]
    text = tok.decode(ids, skip_special_tokens=True)
    assert [parse_token_id(w) for w in text.split()] == ids
    mtime = os.path.getmtime(os.path.join(path, "tokenizer.json"))
    server.write_model_dir(path, cfg)  # a second run writes nothing
    assert os.path.getmtime(os.path.join(path, "tokenizer.json")) == mtime


def test_request_body_keeps_exact_lengths_and_the_mix_sampling():
    r = Request(index=0, due_s=0.0, prompt_len=3, output_len=17,
                prompt=[5, 6, 7], sampling_seed=99)
    body = body_for("bench", r, {"temperature": 0.7, "top_p": 0.9, "seeded": True})
    assert body["prompt"] == [5, 6, 7] and body["max_tokens"] == 17
    assert body["ignore_eos"] is True and body["stream"] is True
    assert (body["temperature"], body["top_p"], body["seed"]) == (0.7, 0.9, 99)
    greedy = body_for("bench", r, {"temperature": 0.0})
    assert greedy["temperature"] == 0.0 and "seed" not in greedy


def test_flags_to_argv():
    assert server.flags_to_argv(
        {"random_weights": True, "tp": 4, "x": False, "y": None}) == \
        ["--random_weights", "--tp=4"]


def test_child_env_keeps_a_given_compile_cache_and_else_fixes_one(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    env = server.child_env("tpu", "/c")
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/c/jax" and env["JAX_PLATFORMS"] == "tpu"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/given")
    assert server.child_env("tpu", "/c")["JAX_COMPILATION_CACHE_DIR"] == "/given"
    assert server.cache_root().startswith(ROOT)


@pytest.mark.parametrize("config,params", [
    ("qwen3-4b", 4.02e9), ("mistral-7b-tp4", 7.11e9)])
def test_matmul_params(config, params):
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        cfg = json.load(f)
    # Mistral's 7.25 B include a 0.13 B embedding table that multiplies nothing
    assert model_math.matmul_params(cfg) == pytest.approx(params, rel=0.01)
    assert model_math.forward_flops_per_token(cfg) == 2 * model_math.matmul_params(cfg)


def recorded_run():
    """A run as the readers see it, with made-up but consistent numbers."""
    records = []
    for i in range(40):
        times = [1.0 + 0.5 * i + 0.06 * j for j in range(30)]
        records.append(stats.Record(
            index=i, phase="window", prompt_len=100, output_len=30,
            due_s=0.5 * i, sent_s=0.5 * i + 0.002, token_times=times,
            token_ids=[1] * 30, finish_reason="length", done=True))

    def snap(steps, step_sum, prompt, gen, hit, compiles):
        return server.parse_metrics(
            f'engine_decode_step_seconds_count{{model_name="bench"}} {steps}\n'
            f'engine_decode_step_seconds_sum{{model_name="bench"}} {step_sum}\n'
            f'engine_prompt_tokens_total{{model_name="bench"}} {prompt}\n'
            f'engine_generated_tokens_total{{model_name="bench"}} {gen}\n'
            f'kv_prefix_hit_tokens_total{{model_name="bench",tier="hbm"}} {hit}\n'
            f'engine_xla_compiles_total{{program="mixed"}} {compiles}\n'
            'engine_startup_seconds_count{model_name="bench",phase="trace"} 2\n'
            'engine_startup_seconds_sum{model_name="bench",phase="trace"} 7.5\n')

    with open(os.path.join(BENCH, "configs", "qwen3-4b.json")) as f:
        cfg = json.load(f)
    before = snap(100, 40.0, 1000, 2000, 0, 36)
    return {
        "cell": "qwen3-4b.chat", "chips": 1, "seconds": 30.0,
        "records": records, "client": stats.end_to_end(records, 30.0, 1),
        "limits": {"ttft_ms": 2000.0, "tpot_ms": 100.0},
        "before": before, "after": snap(150, 61.5, 9000, 5000, 1000, 36),
        "startup_metrics": before,
        "telemetry": {"queue_wait_s": {"p50": 0.2, "n": 40}},
        "state": {}, "hf_config": cfg,
        "flags": cfg["deployment"]["server_flags"],
        "peaks": manifest.load_peaks("TPU v5 lite"),
        "device": {"memory_peak_bytes": 12.8e9},
        "timings": {"ready_s": 18.0, "grid_s": 120.0},
        "trace": {"busy_s": 3.2, "window_s": 4.0, "opcode_s": {
            "sort": 1.2, "custom-call": 0.4, "fusion": 1.0,
            "all-reduce": 0.2, "all-gather-start": 0.2, "while": 0.2},
            # decode-sat's own labels (my chip run, PR 24): the gather of 48
            # lanes x 40 pages and its copies count, the Pallas kernel's
            # result, the whole cache (2300 pages) and the logits do not
            "op_s": {"fusion_bf16_48_40_1_8_16_128_": 0.4,
                     "copy_bf16_48_40_1_8_16_128_": 0.3,
                     "fusion_bf16_1920_2_8_16_128_": 0.1,
                     "fusion_bf16_2300_2_8_16_128_": 0.5,
                     "closed_call_bf16_48_32_128_": 0.4,
                     "sort_f32_48_151936_": 1.2, "while": 0.3}},
    }


EXPECTED = {
    "loadgen.late_p99_ms": 2.0,
    "service.ttft_p50_ms": 1000.0,
    "service.ttft_p95_ms": 1000.0,
    "service.tpot_p95_ms": 60.0,
    "service.slo_share": 100.0,
    "engine.queue_wait_ms": 200.0,
    "engine.tokens_per_dispatch": 220.0,  # (8000 + 3000) / 50
    "engine.prefix_hit_share": 100.0 * 1000 / 9000,
    "dispatch.step_ms": 430.0,  # 21.5 s / 50
    "dispatch.compiles_in_window": 0.0,
    # 2 x 4.02e9 x 11000 tokens / 30 s over 197e12
    "model.mfu": 100.0 * 2 * 4.0225e9 * 11000 / 30.0 / 197e12,
    "sampler.sort_share": 37.5,
    "kernel.attention_share": 12.5,
    "attention.xla_gather_share": 25.0,  # (0.4 + 0.3 + 0.1) / 3.2
    "collective.share": 12.5,
    "device.idle_share": 20.0,
    "device.peak_hbm_share": 100.0 * 12.8e9 / 17179869184,
    "startup.ready_s": 18.0,
    "startup.warmup_s": 120.0,
    "startup.trace_s": 7.5,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_recorded_run(name):
    value = manifest.load_reader(name).read(recorded_run())
    assert value == pytest.approx(EXPECTED[name], rel=2e-3)


def test_a_reader_with_nothing_to_read_returns_nothing():
    run = recorded_run()
    run["trace"] = None
    run["telemetry"] = {}
    for name in ("sampler.sort_share", "kernel.attention_share",
                 "attention.xla_gather_share",
                 "collective.share", "device.idle_share", "engine.queue_wait_ms",
                 "startup.compile_s"):
        assert manifest.load_reader(name).read(run) is None
