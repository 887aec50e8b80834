"""Programs JAX compiled or loaded, counted by JAX itself.

`jax.monitoring` reports one backend-compile duration for every program it
has to produce, whether the compiler ran or the persistent cache answered
(the cache's own hit event tells the two apart).  That covers every jitted
function of the process, the program's off-ledger ones included, and needs
no name from inside the program.
"""

import threading
from typing import NamedTuple

from jax import monitoring

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class Compiles(NamedTuple):
    programs: int      # produced so far, compiled or loaded
    seconds: float     # spent producing them
    cache_hits: int    # of them, answered by the persistent cache


class CompileWatch:
    """Counts from `install()` on."""

    def __init__(self):
        self._lock = threading.Lock()
        self._programs, self._seconds, self._cache_hits = 0, 0.0, 0

    def install(self) -> "CompileWatch":
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def _on_duration(self, event: str, seconds: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            with self._lock:
                self._programs += 1
                self._seconds += seconds

    def _on_event(self, event: str, **_) -> None:
        if event == _CACHE_HIT:
            with self._lock:
                self._cache_hits += 1

    def snapshot(self) -> Compiles:
        with self._lock:
            return Compiles(self._programs, self._seconds, self._cache_hits)
