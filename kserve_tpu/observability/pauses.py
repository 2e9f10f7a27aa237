"""What stops the whole process, whoever asked for it: every XLA compile
and every pause of Python's collector (docs/observability.md "Compiles and
collector pauses of the process").

`engine_xla_compiles_total` counts the misses of the engine's OWN wrapped
programs (engine/compiled._CompileCounting, engine/aot_cache.AOTProgram).
A compile of anything else in the process (a `jnp` operation on a new
shape, an `.at[].set`, `jax.random.fold_in`) stalls the loop just as long
and is invisible to it; so is a collection of an old generation.  Here one
listener on jax.monitoring's backend-compile event and one `gc.callbacks`
entry feed the process's counters (`engine_other_compile_seconds_total`,
`engine_gc_pause_seconds_total`) and tell every started engine (`watch`),
which notes the seconds on the dispatch row of the iteration they fell in
(DispatchPhases.paused).  Both are registered when the first engine starts
and removed when the last stops; an engine is held weakly, so one that
never reaches the end of its `stop()` is not kept alive from here.
"""

from __future__ import annotations

import gc
import threading
import time
import weakref
from typing import Callable, List

import jax.monitoring

from ..metrics import GC_PAUSE_SECONDS, OTHER_COMPILE_SECONDS

#: `jax._src.dispatch.BACKEND_COMPILE_EVENT` (JAX 0.9.0): recorded around
#: every backend compile, a hit of JAX's persistent cache included, with
#: the compiled function's name as `fun_name`
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the collector's generations that are timed (a collection of generation 0
#: takes microseconds and happens hundreds of times a second)
GC_GENERATIONS = (1, 2)

#: weak references to the callables `watcher(pause, seconds, what)` of the
#: started engines; `pause` is one of observability.timeline.PAUSES
_watchers: List[weakref.ReferenceType] = []
_registered = False  # the listener and the callback are
_lock = threading.Lock()


class _ProgramCompile(threading.local):
    """A compile is synchronous in the thread that calls the program, so
    the depth is the calling thread's own."""

    depth = 0

    def __enter__(self) -> None:
        self.depth += 1

    def __exit__(self, *exc) -> None:
        self.depth -= 1


#: `with PROGRAM_COMPILE:` around a call that may compile one of the
#: engine's own programs: a compile inside it is the engine's, counted by
#: engine_xla_compiles_total, and not told here
PROGRAM_COMPILE = _ProgramCompile()


def _tell(pause: str, seconds: float, what: str = "") -> None:
    for ref in list(_watchers):
        watcher = ref()
        if watcher is not None:
            watcher(pause, seconds, what)


def _on_event_duration(event: str, seconds: float, **kwargs) -> None:
    if event != COMPILE_EVENT or PROGRAM_COMPILE.depth:
        return
    OTHER_COMPILE_SECONDS.inc(seconds)
    _tell("other_compile", seconds, str(kwargs.get("fun_name", "")))


_gc_started = 0.0


def _on_gc(phase: str, info: dict) -> None:
    global _gc_started
    generation = info["generation"]
    if generation == 0:
        return
    if phase == "start":
        _gc_started = time.perf_counter()
        return
    seconds = time.perf_counter() - _gc_started
    GC_PAUSE_SECONDS.labels(generation=str(generation)).inc(seconds)
    _tell("gc", seconds)


def _ref(watcher) -> weakref.ReferenceType:
    bound = hasattr(watcher, "__self__")
    return (weakref.WeakMethod if bound else weakref.ref)(watcher)


def _settle() -> None:
    """Drop the watchers that are gone; register the listener and the
    callback for the first one there is, remove them after the last."""
    global _registered
    _watchers[:] = [ref for ref in _watchers if ref() is not None]
    if _watchers and not _registered:
        jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
        gc.callbacks.append(_on_gc)
    elif _registered and not _watchers:
        jax.monitoring.unregister_event_duration_listener(_on_event_duration)
        gc.callbacks.remove(_on_gc)
    _registered = bool(_watchers)


def watch(watcher: Callable[[str, float, str], None]) -> None:
    """Tell `watcher` of every pause from now on, for as long as it lives
    or until `unwatch`; puts the collector's series on the page at zero (a
    reader tells "none" from "not counted")."""
    with _lock:
        if _ref(watcher) not in _watchers:
            _watchers.append(_ref(watcher))
        for generation in GC_GENERATIONS:
            GC_PAUSE_SECONDS.labels(generation=str(generation))
        _settle()


def unwatch(watcher: Callable[[str, float, str], None]) -> None:
    """Stop telling `watcher`."""
    with _lock:
        if _ref(watcher) in _watchers:
            _watchers.remove(_ref(watcher))
        _settle()
