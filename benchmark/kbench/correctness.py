"""What decides `correct`, from the client's side and outside the window.

1. every streamed response that ended has exactly its `max_tokens` tokens,
   ids inside the vocabulary, `finish_reason: length`;
2. a fixed set of greedy probes, sent before the ramp and again after the
   window, returns identical tokens both times (the second time through a
   prefix-cache hit);
3. the probes' served tokens agree with the plain float32 reference of the
   configuration's family (`benchmark/reference/<family>.py`, run in a
   child on the host CPU): each served token's reference logit lies within
   the configuration's stated tolerance of the reference's maximum;
4. nothing compiled inside the window.
"""

import hashlib
import json
import os
import random
import subprocess
import sys

from .manifest import BENCH_DIR
from .server import MODEL_NAME, die_with_parent

#: the probes are the same for every --seed: the reference's result is
#: kept in the checkout's cache by (configuration, probes, served tokens),
#: so only a checkout's first run of a configuration pays for the host
#: forward.  The traffic follows --seed; the weights cannot (the engine
#: makes them from a constant).
PROBE_SEED = 24
N_PROBES = 4
PROBE_PROMPT_LEN = 48
PROBE_OUTPUT_LEN = 8


def probe_prompts(vocab: int, prompt_len: int = PROBE_PROMPT_LEN,
                  n: int = N_PROBES) -> list:
    rng = random.Random(PROBE_SEED)
    return [[rng.randrange(vocab) for _ in range(prompt_len)] for _ in range(n)]


def run_probes(server, prompts, output_len: int = PROBE_OUTPUT_LEN) -> list:
    """Greedy, one at a time, not streamed; returns the served ids."""
    served = []
    for prompt in prompts:
        status, body = server.post_json("/openai/v1/completions", {
            "model": MODEL_NAME, "prompt": prompt, "max_tokens": output_len,
            "temperature": 0, "ignore_eos": True})
        if status != 200:
            raise RuntimeError(f"probe -> HTTP {status}: {body}")
        ids = [int(w) for w in body["choices"][0]["text"].split()]
        usage = body["usage"]
        if (len(ids) != output_len or usage["completion_tokens"] != output_len
                or usage["prompt_tokens"] != len(prompt)):
            raise RuntimeError(
                f"probe returned {len(ids)} readable ids, usage {usage}; "
                f"asked for {output_len}")
        served.append(ids)
    return served


def check_streams(records, vocab: int) -> list:
    """Faults among the streamed responses that ended."""
    faults = []
    for r in records:
        if r.error is not None:
            faults.append(f"request {r.index}: {r.error}")
        elif r.done:
            if len(r.token_times) != r.output_len:
                faults.append(
                    f"request {r.index}: {len(r.token_times)} tokens, "
                    f"asked {r.output_len}")
            elif r.finish_reason != "length":
                faults.append(
                    f"request {r.index}: finish_reason {r.finish_reason!r}")
            elif any(t is None or not 0 <= t < vocab for t in r.token_ids):
                faults.append(f"request {r.index}: an id outside the vocabulary")
    return faults


class ReferenceCheck:
    """The reference child, started early so that it overlaps the warm-up;
    `result()` waits for it.  Results are kept by content hash."""

    def __init__(self, cache: str, model_dir: str, hf_config: dict,
                 family: str, prompts: list, served: list):
        probes = [{"prompt": p, "served": s} for p, s in zip(prompts, served)]
        with open(os.path.join(BENCH_DIR, "reference", family + ".py"), "rb") as f:
            source = f.read()
        key = hashlib.sha256(
            json.dumps([hf_config, probes], sort_keys=True).encode() + source
        ).hexdigest()[:24]
        out_dir = os.path.join(cache, "reference")
        os.makedirs(out_dir, exist_ok=True)
        self.out_path = os.path.join(out_dir, key + ".json")
        self.cached = os.path.exists(self.out_path)
        self.proc = None
        self._log = None
        if not self.cached:
            probes_path = os.path.join(out_dir, key + ".probes.json")
            with open(probes_path, "w") as f:
                json.dump(probes, f)
            self._log = open(os.path.join(out_dir, key + ".log"), "wb")
            env = dict(os.environ, JAX_PLATFORMS="cpu", TF_CPP_MIN_LOG_LEVEL="3")
            env.pop("JAX_COMPILATION_CACHE_DIR", None)
            self.proc = subprocess.Popen(
                [sys.executable,
                 os.path.join(BENCH_DIR, "reference", "check.py"),
                 "--config", os.path.join(model_dir, "config.json"),
                 "--family", family, "--probes", probes_path,
                 "--out", self.out_path],
                env=env, stdout=self._log, stderr=subprocess.STDOUT,
                start_new_session=True, preexec_fn=die_with_parent())

    def result(self, timeout_s: float = 900.0) -> dict:
        if self.proc is not None:
            try:
                code = self.proc.wait(timeout_s)
            except subprocess.TimeoutExpired:
                self.kill()
                raise RuntimeError("the reference child timed out") from None
            finally:
                self._log.close()
            if code != 0:
                with open(self._log.name, errors="replace") as f:
                    tail = "".join(f.readlines()[-20:])
                raise RuntimeError(f"the reference child exited {code}:\n{tail}")
        with open(self.out_path) as f:
            return json.load(f)

    def kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
