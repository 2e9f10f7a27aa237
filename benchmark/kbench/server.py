"""The system under test as a child process, and what it reports over HTTP.

The parent never imports JAX: one process per chip, and that process is
`python -m kserve_tpu.runtimes.generative_server`.  From the program the
benchmark takes only the served endpoints, `/metrics`, `/admin/telemetry`,
`/v1/internal/scheduler/state` and `/admin/profile`.
"""

import ctypes
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

from .manifest import ROOT

MODEL_NAME = "bench"
READY_TIMEOUT_S = 900.0
STOP_TIMEOUT_S = 60.0


class ServerFailure(Exception):
    """The server child did not start, died or misreported its device."""


def cache_root(root: str = ROOT) -> str:
    """Everything a run leaves behind lives here, inside the checkout and
    at a fixed path (the path is part of the compile cache's key)."""
    return os.path.join(root, ".bench_cache")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def write_model_dir(path: str, hf_config: dict) -> None:
    """`config.json` as run, plus a synthetic `tokenizer.json`: a word-level
    vocabulary of `vocab_size` strings, token i spelled as the decimal
    string of i, so that the served ids can be read off the stream (the
    byte fallback drops every id >= 256) and a real detokenizer sits on the
    host path.  Written once per checkout."""
    os.makedirs(path, exist_ok=True)
    cfg_path = os.path.join(path, "config.json")
    wanted = json.dumps(hf_config, indent=1, sort_keys=True)
    tok_path = os.path.join(path, "tokenizer.json")
    if os.path.exists(cfg_path) and os.path.exists(tok_path):
        with open(cfg_path) as f:
            if f.read() == wanted:
                return
    vocab = {str(i): i for i in range(int(hf_config["vocab_size"]))}
    tokenizer = {
        "version": "1.0", "truncation": None, "padding": None,
        "added_tokens": [], "normalizer": None,
        "pre_tokenizer": {"type": "WhitespaceSplit"},
        "post_processor": None, "decoder": None,
        "model": {"type": "WordLevel", "vocab": vocab, "unk_token": "0"},
    }
    with open(tok_path + ".tmp", "w") as f:
        json.dump(tokenizer, f)
    os.replace(tok_path + ".tmp", tok_path)
    with open(cfg_path + ".tmp", "w") as f:
        f.write(wanted)
    os.replace(cfg_path + ".tmp", cfg_path)


def flags_to_argv(flags: dict) -> list:
    argv = []
    for key, value in flags.items():
        if value is True:
            argv.append(f"--{key}")
        elif value is not False and value is not None:
            argv.append(f"--{key}={value}")
    return argv


def child_env(platform: str, cache: str, n_cpu_devices: int = 0) -> dict:
    env = dict(os.environ, JAX_PLATFORMS=platform, PYTHONPATH=ROOT,
               PYTHONUNBUFFERED="1", TF_CPP_MIN_LOG_LEVEL="3")
    # take the compile cache the machine gives; else a fixed one of ours
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(cache, "jax"))
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    env["KSERVE_TPU_PROFILE_DIR"] = os.path.join(cache, "profiles")
    if n_cpu_devices:
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_cpu_devices}")
    return env


PR_SET_PDEATHSIG = 1  # <linux/prctl.h>


def die_with_parent():
    """A `preexec_fn`: the kernel sends the child SIGKILL when the thread
    that started it ends, however it ends.  A `run.py` killed by SIGKILL (a
    run at its limit) runs no handler and no `finally`, and a child in a
    session of its own would go on holding the chip (the ledger's
    `process_left_running`, PR 44)."""
    libc = ctypes.CDLL(None)  # loaded here, by the parent, not after the fork
    parent = os.getpid()

    def in_child():
        libc.prctl(PR_SET_PDEATHSIG, signal.SIGKILL)
        if os.getppid() != parent:  # it ended between the fork and the call
            os._exit(1)

    return in_child


class Server:
    """The server child, in its own process group so that nothing it
    spawns outlives the run, and not the run either: SIGTERM to this
    process kills the group before the process exits (code 143, through
    the callers' `finally`), SIGKILL leaves it to `die_with_parent`."""

    #: what python runs as the server; a test puts a sleeping stand-in here
    ENTRY = ("-m", "kserve_tpu.runtimes.generative_server")

    def __init__(self, flags: dict, platform: str, cache: str, log_name: str,
                 n_cpu_devices: int = 0):
        self.port = free_port()
        self.base_url = f"http://127.0.0.1:{self.port}"
        os.makedirs(os.path.join(cache, "logs"), exist_ok=True)
        self.log_path = os.path.join(cache, "logs", log_name + ".server.log")
        self._log = open(self.log_path, "wb")
        argv = [sys.executable, *self.ENTRY,
                f"--http_port={self.port}", f"--model_name={MODEL_NAME}",
                "--enable_grpc=false", *flags_to_argv(flags)]
        self.proc = subprocess.Popen(
            argv, cwd=ROOT, env=child_env(platform, cache, n_cpu_devices),
            stdout=self._log, stderr=subprocess.STDOUT, start_new_session=True,
            preexec_fn=die_with_parent())
        # signal handlers are the main thread's to set; run.py starts its
        # server there
        self._sigterm_was = None
        if threading.current_thread() is threading.main_thread():
            self._sigterm_was = signal.signal(signal.SIGTERM, self._on_sigterm)

    def _on_sigterm(self, signum, frame):
        self._kill_group()
        raise SystemExit(128 + signum)

    def _kill_group(self) -> None:
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        self.proc.wait()

    # ---- HTTP, from the parent's side ----

    def get(self, path: str, timeout: float = 30.0) -> str:
        with urllib.request.urlopen(self.base_url + path, timeout=timeout) as r:
            return r.read().decode()

    def get_json(self, path: str, timeout: float = 30.0) -> dict:
        return json.loads(self.get(path, timeout))

    def post_json(self, path: str, body: dict, timeout: float = 600.0):
        req = urllib.request.Request(
            self.base_url + path, data=json.dumps(body).encode(),
            headers={"content-type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.status, json.loads(r.read().decode() or "null")
        except urllib.error.HTTPError as e:
            return e.code, {"error": e.read().decode()[:300]}

    def wait_ready(self) -> float:
        t0 = time.monotonic()
        while time.monotonic() - t0 < READY_TIMEOUT_S:
            if self.proc.poll() is not None:
                raise ServerFailure(
                    f"server exited with code {self.proc.returncode} before "
                    f"turning ready:\n{self.log_tail()}")
            try:
                with urllib.request.urlopen(
                        self.base_url + f"/v2/models/{MODEL_NAME}/ready",
                        timeout=5.0) as r:
                    if r.status == 200:
                        return time.monotonic() - t0
            except (urllib.error.URLError, OSError):
                pass  # not listening yet, or its loop is busy compiling
            time.sleep(0.5)
        raise ServerFailure(f"server not ready within {READY_TIMEOUT_S:.0f} s")

    def state(self, timeout: float = 30.0) -> dict:
        return self.get_json(
            "/v1/internal/scheduler/state", timeout)["models"][MODEL_NAME]

    def stop(self) -> int:
        """SIGTERM, wait, and make sure the whole group is gone."""
        code = None
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                code = self.proc.wait(STOP_TIMEOUT_S)
            except subprocess.TimeoutExpired:
                code = None
        self._kill_group()
        self._log.close()
        if self._sigterm_was is not None:
            signal.signal(signal.SIGTERM, self._sigterm_was)
            self._sigterm_was = None
        return self.proc.returncode if code is None else code

    def log_tail(self, lines: int = 40) -> str:
        self._log.flush()
        with open(self.log_path, errors="replace") as f:
            return "".join(f.readlines()[-lines:])


# ---- /metrics ----


def parse_metrics(text: str) -> dict:
    """{(name, frozenset of (label, value))): sample} of a Prometheus page."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, value = line.rpartition(" ")
        name, _, rest = head.partition("{")
        labels = []
        if rest:
            for part in rest.rstrip("}").split('",'):
                if "=" in part:
                    k, _, v = part.partition("=")
                    labels.append((k.strip(), v.strip().strip('"')))
        try:
            out[(name, frozenset(labels))] = float(value)
        except ValueError:
            continue
    return out


def metric_sum(snapshot: dict, name: str, **labels) -> float:
    """Sum of a metric's samples whose labels include `labels`."""
    want = set(labels.items())
    return sum(v for (n, ls), v in snapshot.items() if n == name and want <= ls)


def metric_delta(before: dict, after: dict, name: str, **labels) -> float:
    return metric_sum(after, name, **labels) - metric_sum(before, name, **labels)
