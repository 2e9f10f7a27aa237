"""Persistent AOT executable cache: zero-compile replica start.

A replica restart today re-traces and re-compiles every engine program
(`mixed`, the legacy prefill/decode set, the inject scatters) even though
the programs are 100% identical across replicas of the same deployment —
restart cost is dominated by redundant work (SLINFER
arXiv:2507.00507 and DeepServe arXiv:2501.14417 both put cold start on
the critical path of scale-to-zero).  This module makes compiled
executables a *persistent artifact*:

- ``AOTProgram`` replaces ``jax.jit(fn)`` at the engine dispatch seam.
  Each distinct input signature (pytree structure + leaf shape/dtype) is
  lowered ONCE with ``jax.jit(fn).lower(*args).compile()`` and the
  resulting executable is serialized to a disk cache via
  ``jax.experimental.serialize_executable`` (the XLA executable
  serialization path ``jax.export`` also rides).  Subsequent dispatches
  call the loaded executable directly — no tracing, no lowering, no XLA.
- On replica start, ``preload()`` deserializes every cached entry for
  this configuration digest, so a warm start performs **zero** XLA
  compiles (pinned by ``engine_xla_compiles_total`` in
  tests/test_retrace_budget.py) and its first request pays neither
  trace nor compile nor deserialize latency.
- The cache key is a content digest of everything that changes the
  compiled artifact: the model config, the engine-config fields the
  compiled programs read (``AOT_KEY_ENGINE_FIELDS`` — the jaxlint rule
  ``aot-cache-key-drift`` pins this list against the fields
  ``build_compiled`` actually reads), the mesh topology and device
  assignment, and the jax/jaxlib versions.  Any drift lands in a fresh
  digest directory; stale executables are structurally unreachable.
- Corrupt or version-skewed entries NEVER crash a start: they log a
  structured warning, count an ``invalid`` cache event, and fall back to
  trace-and-compile (which then overwrites the bad entry).

Deploy story (docs/coldstart.md): point ``EngineConfig.aot_cache_dir``
(env ``KSERVE_TPU_AOT_CACHE``) at a node-local hostPath or a warmed PVC;
the first replica on a node pays the compile and every later start —
scale-up burst, crash restart, scale-from-zero wake — is weight-I/O
bound instead of compile-bound.
"""

from __future__ import annotations

import functools
import json
import os
import pickle
import tempfile
import time
from dataclasses import dataclass, field
from hashlib import sha256
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..logging import logger
from ..metrics import AOT_CACHE_EVENTS, XLA_COMPILE_SECONDS, XLA_COMPILES
from ..observability.pauses import PROGRAM_COMPILE

# bump when the on-disk entry layout changes; old entries become
# structurally invalid (logged + recompiled) instead of misread
AOT_CACHE_FORMAT = 1

#: EngineConfig fields that participate in the cache-key digest.  This is
#: the canonical list the jaxlint rule ``aot-cache-key-drift`` checks
#: ``engine/compiled.py`` against: every engine-config field read during
#: compiled-program construction MUST appear here, or two configs that
#: differ in that field would silently share executables (the
#: stale-executable hazard).  Fields that only steer host-side scheduling
#: (queue policy, offload tiers, deadlines) are deliberately excluded so
#: tuning them does not cold-start the fleet.
AOT_KEY_ENGINE_FIELDS = (
    "max_batch_size",
    "page_size",
    "num_pages",
    "max_pages_per_seq",
    "max_prefill_len",
    "prefill_buckets",
    "tp",
    "dp",
    "sp",
    "pp",
    "pp_microbatches",
    "dtype",
    "kv_quant",
    "weight_quant",
    "use_pallas",
    "steps_per_sync",
    "prefill_batch",
    "max_logprobs",
    "use_ragged",
)


def aot_cache_dir_from_env() -> Optional[str]:
    """The deploy knob: ``KSERVE_TPU_AOT_CACHE`` names the cache dir the
    llmisvc reconciler mounts (hostPath/warmed PVC).  Empty/unset = the
    cache is disabled and every start compiles (today's behavior)."""
    value = os.environ.get("KSERVE_TPU_AOT_CACHE", "").strip()
    return value or None


def _jsonable(value: Any) -> Any:
    """Digest-stable view of a config value (tuples/dtypes -> plain)."""
    if isinstance(value, (list, tuple)):
        return [_jsonable(v) for v in value]
    if isinstance(value, dict):
        return {str(k): _jsonable(v) for k, v in sorted(value.items())}
    if isinstance(value, (str, int, float, bool)) or value is None:
        return value
    return str(value)


def _mesh_devices(mesh) -> list:
    return list(mesh.devices.flat) if mesh is not None else jax.devices()


#: where the device programs are written: a change to any of these files
#: may change the compiled artifact, so their bytes are part of the key
_PROGRAM_SOURCES = ("models", "ops", "parallel", "engine/compiled.py",
                    "engine/sampling.py",
                    # StateLayout shapes a program's state, `inject` its pages
                    "engine/kvcache.py",
                    # `mixed` cuts its arguments apart by shapes.MixedLayout
                    "engine/shapes.py")


@functools.lru_cache(maxsize=1)
def program_source_digest() -> str:
    """Digest of the source files that define the device programs.  An
    executable is loaded without tracing, so nothing else would notice
    that the program it was compiled from has since been edited: after an
    upgrade a warm cache directory would go on serving the old program."""
    package = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    h = sha256()
    for entry in _PROGRAM_SOURCES:
        path = os.path.join(package, entry)
        files = [path] if os.path.isfile(path) else sorted(
            os.path.join(d, f) for d, _, fs in os.walk(path)
            for f in fs if f.endswith(".py"))
        for name in files:
            h.update(os.path.relpath(name, package).encode())
            with open(name, "rb") as f:
                h.update(f.read())
    return h.hexdigest()[:16]


def aot_cache_key(model_config, engine_config, mesh) -> str:
    """Content digest of everything that determines the compiled
    artifact.  Model config is digested WHOLE (any architectural field
    changes the HLO); engine config is digested through the explicit
    ``AOT_KEY_ENGINE_FIELDS`` list; the mesh contributes axis names,
    shape, and the concrete device assignment (serialized executables
    bake device ids, so dp groups on disjoint device sets must not share
    entries); jax/jaxlib versions guard serialization-format skew; the
    programs' own source guards an edited program."""
    import dataclasses as _dc

    import jaxlib

    devices = _mesh_devices(mesh)
    payload = {
        "format": AOT_CACHE_FORMAT,
        "model": _jsonable(_dc.asdict(model_config)),
        "engine": {
            name: _jsonable(getattr(engine_config, name, None))
            for name in AOT_KEY_ENGINE_FIELDS
        },
        "mesh": {
            "axis_names": list(getattr(mesh, "axis_names", ()) or ()),
            "shape": _jsonable(dict(getattr(mesh, "shape", {}) or {})),
            "devices": [
                (d.id, d.platform, getattr(d, "device_kind", ""))
                for d in devices
            ],
        },
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "source": program_source_digest(),
    }
    blob = json.dumps(payload, sort_keys=True).encode()
    return sha256(blob).hexdigest()[:32]


def _leaf_sig(x: Any) -> Tuple:
    """Signature atom for one pytree leaf: shape + dtype + weak-type +
    sharding spelling.  Two calls with equal signatures are guaranteed to
    hit the same jit-cache entry, so they may share one executable."""
    aval = getattr(x, "aval", None)
    if aval is not None:
        sharding = getattr(x, "sharding", None)
        spec = getattr(sharding, "spec", None)
        return (
            tuple(aval.shape),
            str(aval.dtype),
            bool(getattr(aval, "weak_type", False)),
            str(spec) if spec is not None else "",
        )
    arr = np.asarray(x)
    return (tuple(arr.shape), str(arr.dtype), isinstance(x, (int, float)), "")


def signature_of(args: Tuple) -> Tuple:
    """Hashable signature of a positional arg tuple (pytree structure +
    per-leaf signatures) — the in-memory executable cache key.
    PyTreeDefs are hashable, so they key directly."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (treedef, tuple(_leaf_sig(leaf) for leaf in leaves))


def signature_digest(sig: Tuple) -> str:
    return sha256(repr(sig).encode()).hexdigest()[:24]


def _discard_tmp(tmp_name: Optional[str]) -> None:
    """Remove a temp file that never made it to its rename (None = it
    did); best-effort, the cache dir may be going away underneath us."""
    if tmp_name is None:
        return
    try:
        os.unlink(tmp_name)
    except OSError:
        pass


def _compile_fresh(lowered):
    """Compile `lowered` with a genuine backend compile.  An executable
    that jax's own persistent compilation cache returned on a HIT is itself
    deserialized, and serialize(deserialized) is lossy on the CPU backend
    (jax 0.9.0: the reloaded entry dies at its first dispatch with
    "Function <fusion> not found"), so the artifact this module persists
    must never come from that cache.  With jax's cache unconfigured (the
    serving default) this is a plain compile; with it configured (the test
    suite's conftest) the cache is switched off around the compile — and
    reset, because jax latches its is-the-cache-used verdict per process."""
    if not jax.config.jax_compilation_cache_dir:
        return lowered.compile()
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    try:
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        return lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


@dataclass
class AOTCacheStats:
    """Per-engine accounting behind ``engine_startup_seconds`` and the
    coldstart bench: wall seconds per startup phase plus event counts."""

    trace_s: float = 0.0
    compile_s: float = 0.0
    aot_load_s: float = 0.0
    compiles: int = 0
    loads: int = 0
    stores: int = 0
    invalid: int = 0

    def as_dict(self) -> Dict[str, float]:
        return {
            "trace_s": round(self.trace_s, 6),
            "compile_s": round(self.compile_s, 6),
            "aot_load_s": round(self.aot_load_s, 6),
            "compiles": self.compiles,
            "loads": self.loads,
            "stores": self.stores,
            "invalid": self.invalid,
        }


class AOTExecutableCache:
    """Disk cache of serialized engine executables for ONE configuration
    digest.  Thread-compatible with the engine's single-dispatcher model:
    all loads/stores happen on the engine loop thread."""

    def __init__(self, cache_dir: str, model_config, engine_config, mesh,
                 label: str = "engine"):
        self.digest = aot_cache_key(model_config, engine_config, mesh)
        self.root = os.path.join(cache_dir, self.digest)
        self.label = label
        # deserialization binds an executable to the devices it runs on;
        # left to its default that is EVERY device of the backend, and a
        # one-device engine on an eight-device host then fails its first
        # dispatch ("expected 8 shards")
        self._devices = _mesh_devices(mesh)
        self.stats = AOTCacheStats()
        os.makedirs(self.root, exist_ok=True)
        self._write_meta(model_config, engine_config)

    def _write_meta(self, model_config, engine_config) -> None:
        """Human-auditable digest description (never read back for
        validation — the digest dir name IS the validation)."""
        meta_path = os.path.join(self.root, "meta.json")
        if os.path.exists(meta_path):
            return
        import dataclasses as _dc

        tmp_name = None
        try:
            with tempfile.NamedTemporaryFile(
                "w", dir=self.root, suffix=".tmp", delete=False
            ) as f:
                tmp_name = f.name
                json.dump({
                    "format": AOT_CACHE_FORMAT,
                    "jax": jax.__version__,
                    "backend": jax.default_backend(),
                    "model": _jsonable(_dc.asdict(model_config)),
                    "engine": {
                        k: _jsonable(getattr(engine_config, k, None))
                        for k in AOT_KEY_ENGINE_FIELDS
                    },
                }, f, sort_keys=True, indent=1)
            os.replace(tmp_name, meta_path)
            tmp_name = None
        except OSError:
            logger.warning("aot-cache meta write failed under %s", self.root)
        finally:
            _discard_tmp(tmp_name)

    # ---------------- entry IO ----------------

    def _entry_path(self, program: str, sig_hash: str) -> str:
        return os.path.join(self.root, f"{program}.{sig_hash}.aotexe")

    def entries(self, program: str) -> List[str]:
        """Signature hashes cached on disk for `program`."""
        prefix = f"{program}."
        try:
            names = os.listdir(self.root)
        except OSError:
            return []
        return sorted(
            n[len(prefix):-len(".aotexe")]
            for n in names
            if n.startswith(prefix) and n.endswith(".aotexe")
        )

    def oracle_reports(self) -> Dict[str, dict]:
        """The per-compile oracle metric snapshots persisted alongside
        the executables ({"<program>.<sig_hash>": report}; see
        AOTProgram._observe).  Written only on genuine cold compiles, so
        this is the cost record of what THIS digest's fleet actually
        built — unreadable/corrupt snapshots are skipped."""
        out: Dict[str, dict] = {}
        suffix = ".oracle.json"
        try:
            names = os.listdir(self.root)
        except OSError:
            return out
        for n in sorted(names):
            if not n.endswith(suffix):
                continue
            try:
                with open(os.path.join(self.root, n),
                          encoding="utf-8") as f:
                    out[n[:-len(suffix)]] = json.load(f)
            except (OSError, ValueError):
                continue
        return out

    def load(self, program: str, sig_hash: str):
        """Deserialize one executable; None on any miss/corruption/skew
        (the caller falls back to trace-and-compile — a bad cache entry
        must cost a compile, never a crash)."""
        path = self._entry_path(program, sig_hash)
        if not os.path.exists(path):
            AOT_CACHE_EVENTS.labels(program=program, event="miss").inc()
            return None
        t0 = time.perf_counter()
        try:
            from jax.experimental import serialize_executable as _se

            with open(path, "rb") as f:
                entry = pickle.load(f)
            if (entry.get("format") != AOT_CACHE_FORMAT
                    or entry.get("jax") != jax.__version__):
                raise ValueError(
                    f"format/version skew: entry {entry.get('format')}/"
                    f"{entry.get('jax')} vs {AOT_CACHE_FORMAT}/{jax.__version__}"
                )
            compiled = _se.deserialize_and_load(
                entry["payload"], entry["in_tree"], entry["out_tree"],
                execution_devices=self._devices)
        except Exception as exc:  # noqa: BLE001 — any deserialization
            # failure (truncated write, pickle drift, backend skew) must
            # degrade to a compile, not a crashed replica start
            self.stats.invalid += 1
            AOT_CACHE_EVENTS.labels(program=program, event="invalid").inc()
            logger.warning(
                "aot-cache-entry-invalid program=%s path=%s error=%s: "
                "falling back to trace-and-compile", program, path,
                f"{type(exc).__name__}: {exc}",
            )
            return None
        dt = time.perf_counter() - t0
        self.stats.aot_load_s += dt
        self.stats.loads += 1
        AOT_CACHE_EVENTS.labels(program=program, event="hit").inc()
        return compiled

    def store(self, program: str, sig_hash: str, compiled) -> None:
        """Serialize one executable (atomic tmp+rename so a concurrent
        reader never sees a torn entry).  Best-effort: a full disk must
        not take down serving."""
        tmp_name = None
        try:
            from jax.experimental import serialize_executable as _se

            payload, in_tree, out_tree = _se.serialize(compiled)
            # round-trip verification BEFORE persisting: a payload that
            # cannot load back would force a compile on every future
            # restart while looking cached, so it is never written.  (It
            # cannot catch a payload that loads but fails to run — the
            # jax-cache-hit case _compile_fresh exists to prevent.)
            _se.deserialize_and_load(
                payload, in_tree, out_tree, execution_devices=self._devices)
            entry = {
                "format": AOT_CACHE_FORMAT,
                "jax": jax.__version__,
                "program": program,
                "payload": payload,
                "in_tree": in_tree,
                "out_tree": out_tree,
            }
            with tempfile.NamedTemporaryFile(
                "wb", dir=self.root, suffix=".tmp", delete=False
            ) as f:
                tmp_name = f.name
                pickle.dump(entry, f)
            os.replace(tmp_name, self._entry_path(program, sig_hash))
            tmp_name = None
            self.stats.stores += 1
            AOT_CACHE_EVENTS.labels(program=program, event="store").inc()
        except Exception as exc:  # noqa: BLE001 — persistence is an
            # optimization; serving continues with the in-memory executable
            logger.warning(
                "aot-cache-store-failed program=%s error=%s",
                program, f"{type(exc).__name__}: {exc}")
        finally:
            # a write that died before the rename (disk full mid-pickle —
            # the exact survivable failure) must not leave a giant orphan
            # .tmp accumulating on the shared node volume
            _discard_tmp(tmp_name)


#: callables invoked as ``observer(program, sig_hash, lowered, compiled)``
#: after every genuine AOTProgram compile — the HLO perf oracle's
#: extraction seam (analysis/hlo_oracle).  Warm starts never compile, so
#: a warm fleet pays zero extraction cost by construction.
_COMPILE_OBSERVERS: List[Callable] = []


def register_compile_observer(fn: Callable) -> Callable:
    _COMPILE_OBSERVERS.append(fn)
    return fn


def unregister_compile_observer(fn: Callable) -> None:
    try:
        _COMPILE_OBSERVERS.remove(fn)
    except ValueError:
        pass


class AOTProgram:
    """Callable standing where ``jax.jit(fn)`` stood in CompiledPrograms:
    per-signature ahead-of-time compiled executables, persisted across
    process restarts.

    Dispatch path per call: build the (cheap, hashable) arg signature ->
    in-memory executable table -> disk cache -> trace+lower+compile.
    Only the last leg counts into ``engine_xla_compiles_total`` — which
    is exactly what makes "warm start performs zero XLA compiles" an
    assertable property rather than a log line."""

    __slots__ = ("_name", "_jit", "_cache", "_mem", "_sig_hash",
                 "_arg_memo")

    def __init__(self, name: str, fn: Callable, cache: AOTExecutableCache,
                 donate_argnums: Tuple[int, ...] = ()):
        self._name = name
        self._jit = jax.jit(fn, donate_argnums=donate_argnums)
        self._cache = cache
        self._mem: Dict[str, Any] = {}  # sig hash -> loaded executable
        self._sig_hash: Dict[Tuple, str] = {}  # signature -> hash memo
        # per-arg-position signature memo keyed by OBJECT IDENTITY (strong
        # ref held, so the id cannot be recycled): the params pytree —
        # hundreds of leaves on a real model — is the same object on every
        # dispatch, and re-flattening it per step would put Python pytree
        # work on the decode hot path
        self._arg_memo: Dict[int, Tuple[Any, Tuple]] = {}

    @property
    def name(self) -> str:
        return self._name

    @property
    def compiles(self) -> int:
        """Compiles so far (of the whole cache: it only ever rises); the
        engine reads it around a launch to mark the dispatch that compiled."""
        return self._cache.stats.compiles

    def preload(self) -> int:
        """Deserialize every on-disk entry for this program into memory
        (replica start: first request pays zero trace/compile/load).
        Returns the number of executables loaded."""
        n = 0
        for sig_hash in self._cache.entries(self._name):
            if sig_hash in self._mem:
                continue
            compiled = self._cache.load(self._name, sig_hash)
            if compiled is not None:
                self._mem[sig_hash] = compiled
                n += 1
        return n

    def loaded_shapes(self, *argnums: int) -> List[Tuple[Tuple[int, ...], ...]]:
        """Per executable held in memory, the shapes of the positional
        arguments `argnums`: which shape buckets this program is loaded in
        (the engine reads its `mixed` pairs here after `preload`)."""
        return [
            tuple(tuple(exe.in_avals[0][i].shape) for i in argnums)
            for exe in self._mem.values()]

    def _compile(self, args: Tuple, sig_hash: str = ""):
        stats = self._cache.stats
        t0 = time.perf_counter()
        lowered = self._jit.lower(*args)
        t1 = time.perf_counter()
        with PROGRAM_COMPILE:  # the process's listener books it as ours
            compiled = _compile_fresh(lowered)
        t2 = time.perf_counter()
        stats.trace_s += t1 - t0
        stats.compile_s += t2 - t1
        stats.compiles += 1
        XLA_COMPILES.labels(program=self._name).inc()
        XLA_COMPILE_SECONDS.labels(program=self._name).inc(t2 - t0)
        self._observe(lowered, compiled, sig_hash)
        return compiled

    def _observe(self, lowered, compiled, sig_hash: str) -> None:
        """Post-compile extraction seam (cold compiles only — warm starts
        dispatch straight from the loaded executable and never get here):
        record the compile fingerprint, notify registered observers, and
        persist a best-effort oracle metrics snapshot next to the cached
        executable so the perf deltas of a fleet's cold starts are
        inspectable after the fact (AOTExecutableCache.oracle_reports)."""
        try:
            from .compiled import record_compile_fingerprint

            hlo_hash = sha256(lowered.as_text().encode()).hexdigest()[:12]
            record_compile_fingerprint(
                self._name, f"aot-sig:{sig_hash}", hlo_hash)
        except Exception:
            logger.debug("aot-fingerprint-failed program=%s",
                         self._name, exc_info=True)
        for obs in list(_COMPILE_OBSERVERS):
            try:
                obs(self._name, sig_hash, lowered, compiled)
            except Exception as exc:  # noqa: BLE001 — an observer must
                # never take down a compile that already succeeded
                logger.warning(
                    "aot-compile-observer-failed program=%s error=%s",
                    self._name, f"{type(exc).__name__}: {exc}")
        try:
            # donation intent is audited by the oracle's keep_unused
            # builds (analysis/hlo_oracle/oracle.py); the snapshot keeps
            # the artifact-level metrics + raw honored-alias count
            from ..analysis.hlo_oracle import extract as _extract

            report = _extract.compiled_report(compiled)
            hlo = _extract.hlo_text(compiled)
            if hlo is not None:
                report["alias_entries"] = len(_extract.alias_table(hlo))
            report["program"] = self._name
            report["sig_hash"] = sig_hash
            report["jax"] = jax.__version__
            path = os.path.join(
                self._cache.root, f"{self._name}.{sig_hash}.oracle.json")
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(report, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except Exception:  # snapshots are diagnostics, never load-bearing
            logger.debug("aot-oracle-snapshot-failed program=%s",
                         self._name, exc_info=True)

    def _signature(self, args: Tuple) -> Tuple:
        """signature_of with a per-arg identity memo: stable big subtrees
        (params) skip re-flattening on the hot path."""
        parts = []
        for i, a in enumerate(args):
            memo = self._arg_memo.get(i)
            if memo is not None and memo[0] is a:
                parts.append(memo[1])
                continue
            leaves, treedef = jax.tree_util.tree_flatten(a)
            part = (treedef, tuple(_leaf_sig(leaf) for leaf in leaves))
            if len(leaves) > 8:
                self._arg_memo[i] = (a, part)
            parts.append(part)
        return tuple(parts)

    def __call__(self, *args):
        sig = self._signature(args)
        sig_hash = self._sig_hash.get(sig)
        if sig_hash is None:
            sig_hash = self._sig_hash[sig] = signature_digest(sig)
        exe = self._mem.get(sig_hash)
        if exe is None:
            exe = self._cache.load(self._name, sig_hash)
            if exe is None:
                exe = self._compile(args, sig_hash)
                self._cache.store(self._name, sig_hash, exe)
            self._mem[sig_hash] = exe
        return exe(*args)
