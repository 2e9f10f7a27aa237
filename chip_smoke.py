#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path starts on the chip.

    python3 chip_smoke.py            # one chip (the driver's check)
    python3 chip_smoke.py --chips 4  # one four-chip host: tp=4 and dp=4 legs

Drives the generative server through the entry point a user calls
(`python -m kserve_tpu.runtimes.generative_server`) at the full published
widths of a model the repo supports, with seeded random weights, and checks
what comes back.  It times nothing for the record; the seconds it prints
are there to tell set-up (compilation) from serving.

One process per chip: this parent never imports JAX.  It runs
  1. a short-lived child (this file, `--kernel-check`) that reports the
     device as JAX sees it and runs every Pallas kernel, compiled, against
     its XLA reference on the device — and has EXITED before
  2. the server child starts, is driven over HTTP, and is stopped with
     SIGTERM (it must drain and exit 0).

It cannot pass anywhere but on a TPU: both children run under
JAX_PLATFORMS=tpu (no accelerator -> they fail at start-up, in seconds),
the server's own report must say platform `tpu` and the compiled Pallas
ragged kernel for `mixed`, and the kernels are called with interpret=False.
Any failed phase ends the run non-zero with the server's log tail and no
result line.  The last line of a passing run's standard output is
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

import argparse
import concurrent.futures
import json
import os
import random
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
PLATFORM = "tpu"
SEED = 0
MODEL_NAME = "smoke"
#: seconds; a first dispatch compiles, and compilation blocks the server's
#: event loop, so every wait is generous and every request is retried never
READY_TIMEOUT_S = 900
REQUEST_TIMEOUT_S = 900
STOP_TIMEOUT_S = 120


class SmokeFailure(Exception):
    """A phase failed; the message names it."""


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


_T0 = time.monotonic()


# --------------------------------------------------------------------------
# child: device report + kernels against their references (imports JAX)
# --------------------------------------------------------------------------

#: (label, n_q_heads, n_kv_heads, head_dim) as ONE device sees them
KERNEL_SHAPES = (
    ("qwen3-0.6b", 16, 8, 128),
    ("llama3-8b tp=4 shard", 8, 2, 128),
)
#: bf16 pages and queries, f32 accumulation in kernel and reference alike;
#: the outputs are bf16, whose rounding alone is 2^-8 relative, and the
#: kernels' matmuls run at the MXU's default precision against a reference
#: held to `highest` — 2e-2 (the bound the repo's interpret-mode bf16 tests
#: use) covers both with O(1)-magnitude outputs
KERNEL_TOL = 2e-2


def kernel_check() -> int:
    """Runs in its own process.  Prints one JSON line; exit 0 = every
    kernel compiled for the device and matched its reference."""
    import importlib.metadata

    import jax
    import jax.numpy as jnp
    import jaxlib
    import numpy as np

    from kserve_tpu.ops import attention as att
    from kserve_tpu.ops import pallas_paged_attention as pk

    devices = jax.devices()  # raises under JAX_PLATFORMS=tpu without a chip
    report = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "versions": {
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "libtpu": importlib.metadata.version("libtpu"),
        },
        "kernels": {},
    }
    if report["platform"] != PLATFORM:
        print(json.dumps(report))
        print(f"device platform is {report['platform']!r}, not {PLATFORM!r}",
              file=sys.stderr)
        return 1

    ps, B, W = 16, 8, 64
    num_pages = B * W + 1
    rng = np.random.RandomState(SEED)

    def cache(nkv, d):
        pages = rng.standard_normal((num_pages, 2, nkv, ps, d))
        # every lane owns W distinct pages; page 0 is the null page
        table = 1 + rng.permutation(B * W).reshape(B, W)
        return (jnp.asarray(pages, jnp.bfloat16),
                jnp.asarray(table, jnp.int32))

    def compare(name, got, want):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = float(np.max(np.abs(got - want)))
        ok = bool(
            np.isfinite(got).all()
            and float(np.max(np.abs(want))) > 1e-2  # not vacuous
            and np.allclose(got, want, rtol=KERNEL_TOL, atol=KERNEL_TOL))
        report["kernels"][name] = {"max_abs_err": round(err, 5), "ok": ok}
        return ok

    def reference(fn, *args, **kw):
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda *a: fn(*a, **kw))(*args)

    def i32(values):
        return jnp.asarray(values, jnp.int32)

    ok = True
    for label, nq, nkv, d in KERNEL_SHAPES:
        pages, table = cache(nkv, d)
        # _ragged_kernel: a prompt chunk over history, decode lanes at short
        # and long context, a fresh prompt, an inactive lane
        lanes = [(40, 100), (1, 517), (1, 33), (0, 0), (8, 0), (1, 1000),
                 (3, 64), (1, 15)]
        q_len = i32([n for n, _ in lanes])
        kv_start = i32([s for _, s in lanes])
        starts, offset = [], 0
        for n, _ in lanes:
            starts.append(offset)
            offset += -(-n // pk.RAGGED_BQ) * pk.RAGGED_BQ
        T = max(offset, pk.RAGGED_BQ)
        q = jnp.asarray(rng.standard_normal((T, nq, d)), jnp.bfloat16)
        args = (q, pages, table, i32(starts), q_len, kv_start)
        got = jax.jit(pk.ragged_paged_attention_pallas)(*args)
        ok &= compare(f"ragged[{label}]", got,
                      reference(att.ragged_paged_attention_xla, *args))
        # _dense_ragged_kernel: 3-token verify slices at stride 4
        stride = 4
        q_len = i32([3, 3, 0, 3, 3, 3, 0, 3])
        kv_start = i32([5, 517, 0, 64, 1000, 15, 0, 250])
        q = jnp.asarray(
            rng.standard_normal((B * stride, nq, d)), jnp.bfloat16)
        args = (q, pages, table, i32(np.arange(B) * stride), q_len, kv_start)
        got = jax.jit(
            lambda *a: pk.ragged_paged_attention_pallas(
                *a, dense_stride=stride))(*args)
        ok &= compare(f"dense_ragged[{label}]", got,
                      reference(att.ragged_paged_attention_xla, *args))
        # _decode_kernel: one token per lane, context 1 .. the full table
        seq_lens = i32([1, 15, 16, 17, 517, 1000, W * ps, 250])
        q = jnp.asarray(rng.standard_normal((B, nq, d)), jnp.bfloat16)
        args = (q, pages, table, seq_lens)
        got = jax.jit(pk.paged_attention_pallas)(*args)
        ok &= compare(f"decode[{label}]", got,
                      reference(att.paged_attention_xla, *args))
    print(json.dumps(report))
    return 0 if ok else 1


# --------------------------------------------------------------------------
# parent: server legs over HTTP (never imports JAX)
# --------------------------------------------------------------------------


@dataclass
class Leg:
    """One server launch and what it must show."""

    name: str
    server_args: list
    n_devices: int
    #: prompt lengths (tokens) served one at a time, twice: the second pass
    #: must return identical text and compile nothing (on one engine it
    #: hits the prefix cache, whose short tail reuses the decode-shaped
    #: program — keep lengths off multiples of the page size)
    sequential: tuple
    #: prompt lengths sent concurrently first (mixed dispatches); () = none
    concurrent: tuple = ()
    max_tokens: int = 20  # spans several steps_per_sync=8 syncs
    stream_len: int = 0  # >0: one SSE stream with this prompt length
    logprobs_len: int = 0  # >0: one logprobs request (the legacy programs)
    expect_mixed_attention: str = "pallas_ragged"
    #: GiB of weights + cache the whole mesh should hold (0 = unchecked)
    resident_gib: float = 0.0
    replicas: int = 1


COMMON_ARGS = [
    "--random_weights", f"--model_name={MODEL_NAME}", "--enable_grpc=false",
    "--page_size=16", "--max_batch_size=8", "--max_model_len=1024",
    "--max_prefill_len=256",
]


def legs_for(chips: int) -> list:
    if chips == 1:
        return [Leg(
            name="qwen3-0.6b bf16, one chip",
            # 28 layers x 4096 pages: a 7 GiB cache, allocated at
            # deployment size next to 1.2 GB of weights
            server_args=["--model_config=qwen3-0.6b", "--kv_pages=4096"],
            n_devices=1,
            # 530 tokens: 3 prefill chunks, 34 pages -> table width 64,
            # where the decode kernel takes over from the gather
            concurrent=(20, 60, 120, 530),
            sequential=(24, 530),
            stream_len=24,
            logprobs_len=530,
        )]
    return [
        Leg(
            name="llama3-8b bf16, tp=4",
            # 16 GB of weights: starts only if created sharded
            server_args=["--model_config=llama3-8b", "--tp=4",
                         "--kv_pages=2048"],
            n_devices=4,
            sequential=(24, 530),
            max_tokens=12,
            # 8.03e9 bf16 parameters (14.96 GiB) + 32 layers x 2048 pages x
            # 64 KiB (4.0 GiB)
            resident_gib=18.96,
        ),
        Leg(
            name="qwen3-0.6b bf16, dp=4",
            server_args=["--model_config=qwen3-0.6b", "--dp=4",
                         "--kv_pages=1024"],
            n_devices=4,
            replicas=4,
            # each replica compiles its own programs: one prompt each, short
            # enough (24 tokens x 8 new ones) that the first pass and the
            # prefix-hit repeat both run in ONE program shape per replica
            sequential=(24,) * 4,
            max_tokens=8,
        ),
    ]


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """The server child: started in its own process group so that nothing
    it spawns outlives the smoke."""

    def __init__(self, args: list, platform: str, log_path: str):
        self.port = free_port()
        self.log_path = log_path
        env = dict(os.environ, JAX_PLATFORMS=platform, PYTHONPATH=HERE,
                   PYTHONUNBUFFERED="1")
        self._log = open(log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "kserve_tpu.runtimes.generative_server",
             f"--http_port={self.port}", *args],
            cwd=HERE, env=env, stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True)

    def url(self, path: str) -> str:
        return f"http://127.0.0.1:{self.port}{path}"

    def get(self, path: str, timeout: float = 30.0):
        with urllib.request.urlopen(self.url(path), timeout=timeout) as r:
            return r.status, r.read().decode()

    def post(self, path: str, body: dict, timeout: float = REQUEST_TIMEOUT_S):
        req = urllib.request.Request(
            self.url(path), data=json.dumps(body).encode(),
            headers={"content-type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.status, r.read().decode()
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode()

    def wait_ready(self) -> float:
        t0 = time.monotonic()
        while time.monotonic() - t0 < READY_TIMEOUT_S:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"server exited with code {self.proc.returncode} "
                    "before turning ready")
            try:
                status, _ = self.get(f"/v2/models/{MODEL_NAME}/ready", 5.0)
                if status == 200:
                    return time.monotonic() - t0
            except (urllib.error.URLError, OSError):
                pass  # not listening yet, or the loop is busy compiling
            time.sleep(1.0)
        raise SmokeFailure(f"server not ready within {READY_TIMEOUT_S}s")

    def stop(self) -> int:
        """SIGTERM: the server must drain and exit on its own."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(STOP_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"server still running {STOP_TIMEOUT_S}s after SIGTERM"
            ) from None

    def kill(self) -> None:
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()
        self._log.close()

    def log_tail(self, lines: int = 60) -> str:
        self._log.flush()
        with open(self.log_path, errors="replace") as f:
            return "".join(f.readlines()[-lines:])


def make_prompt(rng: random.Random, n: int) -> list:
    """`n` token ids the byte tokenizer and every vocab accept."""
    return [rng.randrange(3, 255) for _ in range(n)]


def complete(server: Server, prompt: list, max_tokens: int, **extra) -> dict:
    """One greedy /openai/v1/completions request; checks status and the
    asked number of tokens."""
    status, body = server.post("/openai/v1/completions", {
        "model": MODEL_NAME, "prompt": prompt, "max_tokens": max_tokens,
        "temperature": 0, "ignore_eos": True, **extra})
    if status != 200:
        raise SmokeFailure(
            f"completion ({len(prompt)} prompt tokens) -> HTTP {status}: "
            f"{body[:300]}")
    out = json.loads(body)
    usage = out["usage"]
    if (usage["completion_tokens"] != max_tokens
            or usage["prompt_tokens"] != len(prompt)):
        raise SmokeFailure(
            f"completion ({len(prompt)} prompt tokens) returned usage "
            f"{usage}, asked for {max_tokens} tokens")
    return out


def stream(server: Server, prompt: list, max_tokens: int) -> int:
    """One SSE stream; returns the number of data events before [DONE]."""
    req = urllib.request.Request(
        server.url("/openai/v1/completions"),
        data=json.dumps({
            "model": MODEL_NAME, "prompt": prompt, "max_tokens": max_tokens,
            "temperature": 0, "ignore_eos": True, "stream": True,
            "stream_options": {"include_usage": True}}).encode(),
        headers={"content-type": "application/json"})
    events, usage, done = 0, None, False
    with urllib.request.urlopen(req, timeout=REQUEST_TIMEOUT_S) as r:
        if r.status != 200:
            raise SmokeFailure(f"stream -> HTTP {r.status}")
        for raw in r:
            line = raw.decode().strip()
            if not line.startswith("data:"):
                continue
            data = line[len("data:"):].strip()
            if data == "[DONE]":
                done = True
                break
            chunk = json.loads(data)
            if "error" in chunk:
                raise SmokeFailure(f"stream error event: {data[:300]}")
            events += 1
            usage = chunk.get("usage") or usage
    if not done or usage is None or usage["completion_tokens"] != max_tokens:
        raise SmokeFailure(
            f"stream ended done={done} usage={usage}, asked for "
            f"{max_tokens} tokens")
    if events < 2:
        raise SmokeFailure(f"stream delivered {events} event(s): not a stream")
    return events


def metric_by_label(server: Server, name: str) -> dict:
    """{first label value: sample} of one single-label counter on /metrics."""
    _, text = server.get("/metrics")
    out = {}
    for line in text.splitlines():
        if line.startswith(name + "{"):
            label, value = line.rsplit(" ", 1)
            out[label.split('"')[1]] = int(float(value))
    return out


def check_state(server: Server, leg: Leg, platform: str) -> dict:
    """What the server says it was built with and where it lives."""
    _, body = server.get("/v1/internal/scheduler/state")
    state = json.loads(body)["models"][MODEL_NAME]
    engines = state.get("replicas") or [state]
    if len(engines) != leg.replicas:
        raise SmokeFailure(
            f"{len(engines)} engine replica(s), expected {leg.replicas}")
    seen = []
    for eng in engines:
        dispatch = eng["dispatch"]
        attention = dispatch["attention"]
        if attention["backend"] != platform:
            raise SmokeFailure(
                f"server built its programs for {attention['backend']!r}, "
                f"not {platform!r}")
        if dispatch["regime"] != "mixed":
            raise SmokeFailure(f"dispatch regime is {dispatch['regime']!r}")
        if attention["mixed"] != leg.expect_mixed_attention:
            raise SmokeFailure(
                f"`mixed` attention is {attention['mixed']!r}, expected "
                f"{leg.expect_mixed_attention!r}: {attention}")
        if leg.n_devices // leg.replicas > 1 and not attention["shard_map"]:
            raise SmokeFailure("tp>1 attention is not under shard_map")
        for dev in eng["devices"]:
            if dev["platform"] != platform:
                raise SmokeFailure(f"engine device {dev} is not {platform}")
            seen.append(dev)
    ids = [d["id"] for d in seen]
    if len(set(ids)) != leg.n_devices:
        raise SmokeFailure(
            f"engines hold devices {ids}; expected {leg.n_devices} distinct")
    in_use = [d["bytes_in_use"] for d in seen]
    peak = [d["peak_bytes_in_use"] for d in seen]
    if platform == PLATFORM:
        # the device keeps memory stats: placement is checkable
        mean = sum(in_use) / len(in_use)
        if max(in_use) > 1.3 * mean or min(in_use) < 0.7 * mean:
            raise SmokeFailure(
                f"devices hold uneven shares: bytes_in_use={in_use}")
        if max(peak) > 1.5 * mean:
            raise SmokeFailure(
                "a device's peak is far above its resident share (staged "
                f"on one device and moved?): peak={peak} in_use={in_use}")
        if leg.resident_gib:
            share = leg.resident_gib * 2**30 / len(seen)
            if not 0.9 * share < mean < 1.3 * share:
                raise SmokeFailure(
                    f"devices hold {_gib(mean)} each, expected "
                    f"~{_gib(share)} (weights + cache / {len(seen)})")
    log(f"  dispatch: {engines[0]['dispatch']}")
    log("  devices: " + ", ".join(
        f"#{d['id']} {d['kind']} in_use={_gib(d['bytes_in_use'])} "
        f"peak={_gib(d['peak_bytes_in_use'])}" for d in seen))
    return state


def _gib(n) -> str:
    return "n/a" if n is None else f"{n / 2**30:.2f}GiB"


def drive(server: Server, leg: Leg, platform: str) -> None:
    """Every request phase of one leg, against a ready server."""
    rng = random.Random(SEED)
    check_state(server, leg, platform)

    if leg.concurrent:
        prompts = [make_prompt(rng, n) for n in leg.concurrent]
        t0 = time.monotonic()
        with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
            futures = [pool.submit(complete, server, p, leg.max_tokens)
                       for p in prompts]
            for f in futures:
                f.result()
        log(f"  (a) {len(prompts)} concurrent completions, prompts "
            f"{list(leg.concurrent)} x {leg.max_tokens} tokens: "
            f"{time.monotonic() - t0:.1f}s (first dispatches compile)")

    if leg.stream_len:
        t0 = time.monotonic()
        events = stream(server, make_prompt(rng, leg.stream_len),
                        leg.max_tokens)
        log(f"  (b) SSE stream: {events} events, {leg.max_tokens} tokens: "
            f"{time.monotonic() - t0:.1f}s")

    prompts = [make_prompt(rng, n) for n in leg.sequential]

    def one_pass():
        texts = [complete(server, p, leg.max_tokens)["choices"][0]["text"]
                 for p in prompts]
        if leg.logprobs_len:
            # a FRESH prompt each pass: the legacy chunked prefill has no
            # decode-shaped program for a prefix hit's short tail to reuse,
            # so a repeated prompt would (rightly) compile one more bucket
            out = complete(server, make_prompt(rng, leg.logprobs_len),
                           leg.max_tokens, logprobs=1)
            lps = out["choices"][0]["logprobs"]["token_logprobs"]
            if len(lps) != leg.max_tokens or not all(
                    lp is not None and lp <= 0.0 for lp in lps):
                raise SmokeFailure(f"logprobs malformed: {lps}")
        return texts

    t0 = time.monotonic()
    first = one_pass()
    first_s = time.monotonic() - t0
    before = metric_by_label(server, "engine_xla_compiles_total")
    if before.get("mixed", 0) < 1:
        raise SmokeFailure(f"no `mixed` compile recorded: {before}")
    if leg.replicas > 1:
        # one-at-a-time requests rotate over equally idle replicas
        by_engine = metric_by_label(server, "engine_generated_tokens_total")
        idle = [f"engine-dp{g}" for g in range(leg.replicas)
                if by_engine.get(f"engine-dp{g}", 0) <= 0]
        if idle:
            raise SmokeFailure(
                f"replica(s) {idle} generated nothing: {by_engine}")
        log(f"      tokens generated per replica: {by_engine}")
    t0 = time.monotonic()
    second = one_pass()
    warm_s = time.monotonic() - t0
    after = metric_by_label(server, "engine_xla_compiles_total")
    log(f"  (c,d) sequential prompts {list(leg.sequential)}"
        + (f" + logprobs@{leg.logprobs_len}" if leg.logprobs_len else "")
        + f": first pass {first_s:.1f}s (set-up: compiles), repeat "
        f"{warm_s:.1f}s")
    log(f"      compiles by program: {after}")
    if after != before:
        raise SmokeFailure(
            f"the warm repeat compiled: before={before} after={after}")
    if second != first:
        raise SmokeFailure(
            "repeated greedy prompts returned different text:\n"
            f"  first:  {first!r}\n  repeat: {second!r}")


def run_leg(leg: Leg, platform: str, workdir: str) -> None:
    log(f"leg: {leg.name}")
    log_path = os.path.join(workdir, f"server-{leg.n_devices}x-"
                            f"{'dp' if leg.replicas > 1 else 'tp'}.log")
    server = Server(COMMON_ARGS + leg.server_args, platform, log_path)
    try:
        try:
            ready_s = server.wait_ready()
            log(f"  server ready after {ready_s:.1f}s (weights + cache "
                "created on the device; programs compile on first use)")
            drive(server, leg, platform)
            rc = server.stop()
            if rc != 0:
                raise SmokeFailure(f"server exited {rc} after SIGTERM")
            log("  SIGTERM: drained, exit 0")
        except (SmokeFailure, urllib.error.URLError, OSError, KeyError,
                ValueError) as exc:
            tail = server.log_tail()
            raise SmokeFailure(
                f"{leg.name}: {type(exc).__name__}: {exc}\n"
                f"--- server log tail ({log_path}) ---\n{tail}") from exc
    finally:
        server.kill()


def run_kernel_check(chips: int) -> dict:
    """The device report + kernel parity child; it has exited (and let go
    of the chip) by the time this returns."""
    env = dict(os.environ, JAX_PLATFORMS=PLATFORM, PYTHONPATH=HERE)
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--kernel-check"],
        cwd=HERE, env=env, capture_output=True, text=True, timeout=900)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    report = json.loads(lines[-1]) if lines else None
    if report is None or proc.returncode != 0:
        err_tail = "\n".join(proc.stderr.splitlines()[-8:])
        if report is None:
            raise SmokeFailure(
                f"no {PLATFORM} device: JAX could not start on it "
                f"(exit {proc.returncode})\n{err_tail}")
        raise SmokeFailure(
            f"kernel check failed (exit {proc.returncode}): "
            f"{json.dumps(report)}\n{err_tail}")
    log(f"device: platform={report['platform']} kind={report['kind']} "
        f"count={report['count']} versions={report['versions']}")
    for name, res in report["kernels"].items():
        log(f"  kernel {name}: compiled, max|err| vs XLA reference "
            f"{res['max_abs_err']} (tolerance {KERNEL_TOL})")
    log(f"  kernel check took {time.monotonic() - t0:.1f}s, child exited")
    if report["count"] != chips:
        raise SmokeFailure(
            f"JAX sees {report['count']} device(s); --chips={chips}")
    return report


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--kernel-check", action="store_true",
                        help="(internal) run as the kernel-check child")
    args = parser.parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, "kserve_tpu")):
        print("chip_smoke.py must sit at the root of the repository "
              "(no kserve_tpu/ beside it)", file=sys.stderr)
        return 2
    if args.kernel_check:
        # kserve_tpu.model_server parses sys.argv when imported
        sys.argv = sys.argv[:1]
        return kernel_check()
    try:
        report = run_kernel_check(args.chips)
        with tempfile.TemporaryDirectory(prefix="chip_smoke-") as workdir:
            for leg in legs_for(args.chips):
                run_leg(leg, PLATFORM, workdir)
    except SmokeFailure as exc:
        print(f"FAIL: {exc}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": report["platform"], "kind": report["kind"],
        "count": report["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
