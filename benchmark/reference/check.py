"""Child process: hold served tokens to the plain float32 reference.

    JAX_PLATFORMS=cpu python benchmark/reference/check.py \
        --config <dir>/config.json --family llama --probes probes.json --out out.json

`probes.json` is a list of {"prompt": [ids], "served": [ids]}.  For each
probe the reference runs teacher-forced over prompt + served tokens, and for
every served token reports how far its reference logit lies under the
reference's maximum at that position (0 where the served token IS the
reference's argmax).  With random weights the largest logit flips on
rounding, so agreement is judged on this gap, not on token identity.

Weights are data: they are made with the program's own
`parallel/sharding.init_params_on_mesh(config, PRNGKey(1), mesh)` on a
one-device CPU mesh — the same call the engine makes for `--random_weights`
— while the forward itself (reference/<family>.py) shares no code with the
program.
"""

import argparse
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load_family(family: str):
    path = os.path.join(HERE, family + ".py")
    spec = importlib.util.spec_from_file_location("reference_" + family, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def program_weights(config_path: str):
    """The engine's `--random_weights` parameters, made on the host CPU."""
    sys.path.insert(0, ROOT)
    argv, sys.argv = sys.argv, sys.argv[:1]  # model_server parses argv at import
    try:
        import jax
        from kserve_tpu.models.llama import LlamaConfig
        from kserve_tpu.parallel import sharding as shd
    finally:
        sys.argv = argv
    config = LlamaConfig.from_hf_config(config_path)
    mesh = shd.create_mesh(tp=1, devices=jax.devices()[:1])
    return shd.init_params_on_mesh(config, jax.random.PRNGKey(1), mesh)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--family", required=True)
    ap.add_argument("--probes", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    t0 = time.monotonic()
    with open(args.config) as f:
        cfg = json.load(f)
    with open(args.probes) as f:
        probes = json.load(f)
    family = load_family(args.family)
    family.check_supported(cfg)
    params = program_weights(args.config)
    t_weights = time.monotonic() - t0
    import numpy as np

    gaps, matches, total = [], 0, 0
    for probe in probes:
        prompt, served = probe["prompt"], probe["served"]
        logits = np.asarray(family.forward(params, cfg, prompt + served[:-1]))
        rows = logits[len(prompt) - 1:]  # position of each served token
        row_gaps = []
        for row, token in zip(rows, served):
            row_gaps.append(float(row.max() - row[token]))
            matches += int(int(row.argmax()) == token)
            total += 1
        gaps.append(row_gaps)
    result = {
        "gaps": gaps,
        "max_gap": max(max(g) for g in gaps),
        "argmax_match_share": matches / max(1, total),
        "weights_s": t_weights,
        "total_s": time.monotonic() - t0,
    }
    with open(args.out + ".tmp", "w") as f:
        json.dump(result, f)
    os.replace(args.out + ".tmp", args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
