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

Prints one JSON line: per probe its largest gap, the largest of all, the
share of served tokens that are the reference's argmax, the tolerance of
the configuration; exits 1 where the largest gap exceeds it.
"""

import argparse
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
    args = ap.parse_args(argv)
    plan = bench_run.Plan(manifest.resolve_cell(args.workload), rehearse=args.cpu)
    platform = "cpu" if args.cpu else "tpu"
    cache = cache_root()
    rng = random.Random(args.seed)
    prompts = [[rng.randrange(plan.vocab) for _ in range(n)] for n in args.prompts]
    server = bench_run.start_server(plan, platform, cache, plan.cell.name + ".probes")
    try:
        bench_run.log(f"server ready after {server.wait_ready():.1f} s")
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
    print(json.dumps({
        "workload": args.workload, "platform": platform,
        "prompt_lens": args.prompts, "served": args.served,
        "max_gap_per_probe": [max(g) for g in result["gaps"]],
        "max_gap": result["max_gap"],
        "argmax_match_share": result["argmax_match_share"],
        "tolerance": tolerance, "reference_s": result["total_s"]}))
    return 0 if result["max_gap"] <= tolerance else 1


if __name__ == "__main__":
    sys.exit(main())
