#!/usr/bin/env python3
"""Hold served tokens of chosen prompt lengths to a cell's plain reference.

The benchmark's own probes are 48 + 8 tokens (benchmark/kbench/
correctness.py): shorter than a prefill chunk, a window or anything a
cache has to carry far.  This starts the server exactly as a cell's run
does (the same configuration file, flags and model directory), sends one
greedy request per `--prompts` length with `--served` tokens each, stops
the server, and hands prompts and served tokens to the unedited
`benchmark/reference/check.py`: every served token's reference logit
against the reference's maximum at its position.  On the chip (a CPU run
with `--cpu` uses the cell's rehearsal size and says nothing about it):

    python3 scripts/reference_probe_check.py --workload phi4-mini-flash.reason-sat \\
        --prompts 48,600,1000 --served 24

`--together` sends the probes at once, so that lanes of different lengths
share dispatches (packed prefill chunks beside decode lanes).  `--weights
int8` computes the reference a second time with its projection weights
rounded to int8 per output channel (the nearest precision below bf16
weights that the program has: models/quant.py's rule) and reports that
reading beside the first: a configuration's `logit_tolerance` lies between
the two as the harness's own probes read them (`--seed 24 --prompts
48,48,48,48 --served 8` sends exactly those); `--tolerance` holds longer
probes to a limit of their own.

Prints one JSON line: per probe its largest gap, the largest of all, the
share of served tokens that are the reference's argmax, the tolerance of
the configuration; exits 1 where the largest gap exceeds it.
"""

import argparse
import concurrent.futures
import json
import os
import random
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import run as bench_run  # noqa: E402  (benchmark/run.py: never imports JAX)
from kbench import correctness, manifest  # noqa: E402
from kbench.server import ServerFailure, cache_root  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--prompts", default="48,600,1000",
                    type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--served", type=int, default=24)
    ap.add_argument("--seed", type=int, default=29)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--together", action="store_true",
                    help="send the probes at once: lanes share dispatches")
    ap.add_argument("--weights", choices=("bf16", "int8"), default="bf16",
                    help="int8: also read the reference with int8 weights")
    ap.add_argument("--tolerance", type=float, default=None,
                    help="hold the reading to this limit and not to the "
                    "configuration's logit_tolerance, which is set at the "
                    "harness's own 48 + 8 probes: thousands of tokens of "
                    "near-flat random logits have a scale of their own")
    ap.add_argument("--experts", action="store_true",
                    help="also count, on the host CPU, the (position, expert "
                    "layer) decisions at which the program's router and the "
                    "reference's chose different experts")
    args = ap.parse_args(argv)
    plan = bench_run.Plan(manifest.resolve_cell(args.workload), rehearse=args.cpu)
    platform = "cpu" if args.cpu else "tpu"
    cache = cache_root()
    rng = random.Random(args.seed)
    prompts = [[rng.randrange(plan.vocab) for _ in range(n)] for n in args.prompts]
    server = bench_run.start_server(plan, platform, cache, plan.cell.name + ".probes")
    try:
        bench_run.log(f"server ready after {server.wait_ready():.1f} s")
        if args.together:
            with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
                served = [one[0] for one in pool.map(
                    lambda p: correctness.run_probes(server, [p], args.served),
                    prompts)]
        else:
            served = correctness.run_probes(server, prompts, args.served)
    except (ServerFailure, RuntimeError) as e:
        bench_run.log(f"FAILED: {e}\n{server.log_tail()}")
        return 1
    finally:
        server.stop()
    out_dir = os.path.join(cache, "reference")
    os.makedirs(out_dir, exist_ok=True)
    probes_path = os.path.join(out_dir, "probe_check.probes.json")
    out_path = os.path.join(out_dir, "probe_check.out.json")
    with open(probes_path, "w") as f:
        json.dump([{"prompt": p, "served": s} for p, s in zip(prompts, served)], f)
    env = dict(os.environ, JAX_PLATFORMS="cpu", TF_CPP_MIN_LOG_LEVEL="3")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "reference", "check.py"),
         "--config", os.path.join(bench_run.model_dir_of(plan, cache), "config.json"),
         "--family", plan.cell.deployment["family"],
         "--probes", probes_path, "--out", out_path], env=env, check=True)
    with open(out_path) as f:
        result = json.load(f)
    tolerance = (plan.cell.deployment["logit_tolerance"]
                 if args.tolerance is None else args.tolerance)
    line = {
        "workload": args.workload, "platform": platform,
        "prompt_lens": args.prompts, "served": args.served,
        "together": args.together,
        "max_gap_per_probe": [max(g) for g in result["gaps"]],
        "max_gap": result["max_gap"],
        "argmax_match_share": result["argmax_match_share"],
        "tolerance": tolerance, "reference_s": result["total_s"]}
    if args.weights == "int8":
        env["REFERENCE_WEIGHTS"] = "int8"
        subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--int8-child",
             os.path.join(bench_run.model_dir_of(plan, cache), "config.json"),
             plan.cell.deployment["family"], probes_path, out_path + ".int8"],
            env=env, check=True)
        with open(out_path + ".int8") as f:
            line["int8_weights"] = json.load(f)
    if args.experts:
        subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--experts-child",
             os.path.join(bench_run.model_dir_of(plan, cache), "config.json"),
             plan.cell.deployment["family"], probes_path, out_path + ".experts"],
            env=env, check=True)
        with open(out_path + ".experts") as f:
            line["expert_choices"] = json.load(f)
    print(json.dumps(line))
    return 0 if result["max_gap"] <= tolerance else 1


def int8_child(config_path, family_name, probes_path, out_path) -> int:
    """benchmark/reference/check.py's measure with the reference's
    projection weights rounded to int8 per output channel and back: what a
    program serving int8 weights would be held against.  The served tokens
    are the bf16 program's, so the gap reads how far int8 rounding alone
    moves the reference's own logits under them."""
    sys.path.insert(0, os.path.join(ROOT, "benchmark", "reference"))
    import check  # noqa: E402  (the unedited child: weights and families)
    import jax
    import numpy as np

    with open(config_path) as f:
        cfg = json.load(f)
    with open(probes_path) as f:
        probes = json.load(f)
    family = check.load_family(family_name)
    params = check.program_weights(config_path)

    def rounded(w):
        # per output channel: the contraction axis is the last but one
        # ([in, out], and [experts, in, out] of stacked experts)
        w = np.asarray(w, np.float32)
        scale = np.maximum(np.abs(w).max(axis=-2, keepdims=True) / 127.0, 1e-8)
        return jax.numpy.asarray(np.round(w / scale).clip(-127, 127) * scale)

    linear = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
              # latent attention's projections and the shared expert
              "wq_a", "wq_b", "wkv_a", "wkv_b",
              "shared_gate", "shared_up", "shared_down")
    if family_name == "nemotron_h":
        # a Mamba-2 mixer's two projections (the Mamba-1 family's readings
        # of PR 29 were taken with its own left in bf16, and stay so)
        linear += ("in_proj", "out_proj")
    if family_name == "solar_open2":
        # a Kimi-delta mixer's projections and the attention row's gate
        linear += ("wqkv", "wf_a", "wf_b", "w_beta", "wg_a", "wg_b", "wg")
    if family_name == "lfm2_moe":
        # a gated short convolution's two projections
        linear += ("in_proj", "out_proj")
    for i, layer in enumerate(params["layers"]):
        # in place: a layer's bf16 tensors go as its float32 ones come
        params["layers"][i] = {k: rounded(v) if k in linear else v
                               for k, v in layer.items()}
    if "lm_head" in params:
        params["lm_head"] = rounded(params["lm_head"])
    elif family_name == "lfm2_moe":
        # the tied head: a scale a vocabulary row, as the program's int8
        # embedding carries (the earlier tied families' readings were taken
        # with theirs in bf16, and stay so)
        params["embed"] = rounded(np.asarray(params["embed"], np.float32).T).T
    gaps, matches, total = [], 0, 0
    for probe in probes:
        prompt, served = probe["prompt"], probe["served"]
        rows = np.asarray(
            family.forward(params, cfg, prompt + served[:-1]))[len(prompt) - 1:]
        gaps.append([float(row.max() - row[t]) for row, t in zip(rows, served)])
        matches += sum(int(row.argmax()) == t for row, t in zip(rows, served))
        total += len(served)
    with open(out_path, "w") as f:
        json.dump({"max_gap_per_probe": [max(g) for g in gaps],
                   "max_gap": max(max(g) for g in gaps),
                   "argmax_match_share": matches / max(1, total)}, f)
    return 0


def experts_child(config_path, family_name, probes_path, out_path) -> int:
    """How often a top-k router, which is discontinuous, chooses other
    experts in the program's precision than in the reference's float32.
    The program's own packed forward (kserve_tpu, bf16 weights and
    activations, the XLA attention path) runs teacher-forced over each
    probe's prompt + served tokens ON THE HOST CPU, as the reference does;
    both routers' choices are recorded and compared as sets, per (expert
    layer, position).  A count, not a device metric: the chip's bf16 rounds
    like the CPU's but not bit for bit, so the chip's own count differs by
    a few decisions."""
    sys.path.insert(0, os.path.join(ROOT, "benchmark", "reference"))
    import check  # noqa: E402
    import jax.numpy as jnp
    import numpy as np

    with open(config_path) as f:
        cfg = json.load(f)
    with open(probes_path) as f:
        probes = json.load(f)
    family = check.load_family(family_name)
    params = check.program_weights(config_path)
    from kserve_tpu.engine.kvcache import StateLayout
    from kserve_tpu.models import llama, moe

    config = llama.LlamaConfig.from_hf_config(config_path)
    chosen = {"program": [], "reference": []}

    def recording(route, into):
        def wrapped(*a, **k):
            weights, selected = route(*a, **k)
            chosen[into].append(np.sort(np.asarray(selected), axis=-1))
            return weights, selected
        return wrapped

    moe.route = recording(moe.route, "program")
    family.route = recording(family.route, "reference")
    page = 64
    out = {"decisions": 0, "differ": 0, "positions": 0,
           "positions_with_a_difference": 0}
    for probe in probes:
        tokens = probe["prompt"] + probe["served"][:-1]
        n, first = len(tokens), len(probe["prompt"]) - 1
        width = -(-n // page)
        layout = StateLayout.of(config, page, width + 1, 1, config.dtype)
        for rows in chosen.values():
            rows.clear()
        llama.forward_ragged(
            params, config, jnp.asarray(tokens, jnp.int32),
            jnp.zeros(n, jnp.int32), jnp.arange(n, dtype=jnp.int32),
            jnp.zeros(1, jnp.int32), jnp.full((1,), n, jnp.int32),
            jnp.zeros(1, jnp.int32), layout.init_state(),
            1 + jnp.arange(width, dtype=jnp.int32)[None, :], page,
            jnp.full((1,), n - 1, jnp.int32), use_pallas=False)
        family.forward(params, cfg, tokens)
        differ = np.stack([
            (a[first:] != b[first:]).any(axis=-1)
            for a, b in zip(chosen["program"], chosen["reference"])])
        out["decisions"] += int(differ.size)
        out["differ"] += int(differ.sum())
        out["positions"] += int(differ.shape[1])
        out["positions_with_a_difference"] += int(differ.any(axis=0).sum())
    with open(out_path, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--int8-child":
        sys.exit(int8_child(*sys.argv[2:6]))
    if len(sys.argv) > 1 and sys.argv[1] == "--experts-child":
        sys.exit(experts_child(*sys.argv[2:6]))
    sys.exit(main())
