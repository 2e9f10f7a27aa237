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
the two.

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
    tolerance = plan.cell.deployment["logit_tolerance"]
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
        w = np.asarray(w, np.float32)
        scale = np.maximum(np.abs(w).max(axis=0, keepdims=True) / 127.0, 1e-8)
        return jax.numpy.asarray(np.round(w / scale).clip(-127, 127) * scale)

    linear = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
    params = dict(params, layers=[
        {k: rounded(v) if k in linear else v for k, v in layer.items()}
        for layer in params["layers"]])
    if "lm_head" in params:
        params["lm_head"] = rounded(params["lm_head"])
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


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--int8-child":
        sys.exit(int8_child(*sys.argv[2:6]))
    sys.exit(main())
