"""Compiled-step cache: reuse jitted executables across runtime rebuilds.

``jax.jit`` caches compiled executables per *wrapper object*, but
``BlockRuntime._build`` historically created a fresh closure and a fresh
``jax.jit`` wrapper on every attach — so a preemption resume on the very
same chips, or a paged serve block rebuilding its ``DecodeScheduler`` after
re-admission, recompiled the whole step from scratch.  On the 400B-class
cells that is minutes of XLA time on the resume critical path.

The cache keys the *logical* build signature — (step family, model config,
shape, optimizer config, mesh geometry + device ids, donate signature) —
and hands back the previously built jit wrapper.  A hit on the same device
set reuses the wrapper's internal executable cache outright (zero
recompilation); a different chip set or mesh geometry is a different key
and compiles its own entry.  Cached wrappers only close over values derived
from the key (configs, meshes with identical device sets, shardings built
from both), so reuse is semantically transparent.

Hits and misses are announced as kind="compile" events on the bus attached
via ``set_bus`` (the controller attaches its own), and the ``Monitor``
counts them — a resume that recompiles when it should not shows up as a
miss where the preemption tests expect a hit.
"""
from __future__ import annotations

import dataclasses
import os
import threading
from typing import Any, Callable, Dict, Optional, Tuple

#: the checkout's own persistent compilation cache (listed in .gitignore).
#: A fixed path: JAX keys cache entries by it, so a moving directory never
#: hits.
PERSISTENT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX
    already uses it and nothing else is set; otherwise the cache goes to
    ``PERSISTENT_DIR``.  The entry points (``repro.launch.train``/``serve`` and
    ``chip_smoke.py``) call this before their first compile; no module
    calls it on import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", PERSISTENT_DIR)
    return PERSISTENT_DIR


def freeze(obj) -> Any:
    """Recursively convert configs (dataclasses / dicts / lists / sets)
    into hashable nested tuples for cache keys."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return (type(obj).__name__,) + tuple(
            (f.name, freeze(getattr(obj, f.name)))
            for f in dataclasses.fields(obj))
    if isinstance(obj, dict):
        return tuple(sorted((k, freeze(v)) for k, v in obj.items()))
    if isinstance(obj, (list, tuple)):
        return tuple(freeze(v) for v in obj)
    if isinstance(obj, (set, frozenset)):
        return tuple(sorted(freeze(v) for v in obj))
    return obj


def mesh_fingerprint(mesh) -> Tuple:
    """(axis layout, device ids): two meshes with the same fingerprint can
    share a jitted wrapper *and* its compiled executables (jax ``Mesh``
    hashes by content, so equal-fingerprint rebuilds hit jax's own cache)."""
    if mesh is None:
        return ("default",)
    return (tuple(zip(mesh.axis_names, mesh.devices.shape)),
            tuple(int(d.id) for d in mesh.devices.flat))


class CompileCache:
    """Thread-safe keyed store of built (usually jitted) step callables."""

    def __init__(self, bus=None):
        self._lock = threading.Lock()
        self._entries: Dict[Any, Any] = {}
        self.hits = 0
        self.misses = 0
        self._bus = bus

    def set_bus(self, bus) -> None:
        """Attach the event bus hit/miss events are published on (the
        controller attaches its own at construction)."""
        self._bus = bus

    def get(self, key, builder: Callable[[], Any], *,
            label: str = "step", block_id: Optional[str] = None,
            app_id: Optional[str] = None, now: Optional[float] = None) -> Any:
        """Return the cached artifact for ``key``, building (and caching)
        it with ``builder()`` on a miss.  Publishes a kind="compile" event
        either way."""
        with self._lock:
            hit = key in self._entries
            if hit:
                self.hits += 1
                out = self._entries[key]
        if not hit:
            out = builder()          # build outside the lock: XLA is slow
            with self._lock:
                # a racing builder may have landed first; keep the winner
                # so every caller shares one wrapper (and its jit cache)
                out = self._entries.setdefault(key, out)
                self.misses += 1
        bus = self._bus
        if bus is not None:
            bus.publish("compile", block_id=block_id, app_id=app_id,
                        now=now, action="hit" if hit else "miss",
                        label=label)
        return out

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "entries": len(self._entries)}

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.hits = 0
            self.misses = 0


#: process-wide default — BlockRuntime and DecodeScheduler build through
#: this so any rebuild anywhere in the process can reuse prior work
GLOBAL = CompileCache()
