"""AOT executable cache: compile once per (bucket, tier, backend), then hit.

Keys are built by the engine from everything that changes the lowered
program: phase (prefill/decode/insert), bucket or pool shape, cache length,
n_repeats tier, backend, and noise kind. Values are
``jax.jit(...).lower(...).compile()`` executables — calling one can *never*
re-trace, so a 100% steady-state hit rate is equivalent to zero steady-state
retraces.

Hit/miss/compile-time counters are first-class: the serving bench asserts
on them and they belong in any production dashboard. ``max_entries`` bounds
the cache with LRU eviction — continuous batching multiplies the key space
(pool shapes x prefill buckets x tiers x families), so a long-lived engine
serving many tiers can cap resident executables; the default is unbounded,
preserving the classic behavior (an evicted key simply recompiles on its
next use, surfacing as a miss + eviction in ``stats()``).
"""
from __future__ import annotations

import time
from collections import OrderedDict
from typing import Any, Callable, Hashable, Optional

from repro.serving import trace


def mesh_fingerprint(mesh, layout: tuple = ()) -> tuple:
    """Hashable identity of a device mesh for AOT cache keys.

    Everything that changes a lowered program's device assignment — axis
    names, axis sizes, the concrete device ordering, and the weights'
    ``layout`` on the mesh (their PartitionSpecs) — and nothing else.
    ``()`` for no mesh, so unmeshed engines keep their exact legacy keys
    (appending an empty tuple is the identity). Two meshes with equal
    fingerprints produce interchangeable executables, which is what lets a
    reshard *back* to a previous mesh hit its still-warm entries.
    """
    if mesh is None:
        return ()
    return (
        tuple(mesh.axis_names),
        tuple(mesh.devices.shape),
        tuple(int(d.id) for d in mesh.devices.flat),
        hash(tuple(layout)),
    )


def spec_tree(tree, *, shardings: bool = False):
    """ShapeDtypeStructs of a tree of arrays: the AOT argument specs of a
    weight tree. With ``shardings``, each spec carries its leaf's sharding,
    so an executable takes every weight as it lies on the mesh."""
    import jax

    if shardings:
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding), tree
        )
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


class ExecutableBuildError(RuntimeError):
    """An executable failed to lower or compile. This is a program error,
    not a transient fault: the engine lets it propagate instead of
    retrying the requests that needed the executable."""


class ExecutableCache:
    """Maps hashable keys -> compiled executables, counting hits/misses.

    ``max_entries=None`` (default) never evicts. With a bound, the cache is
    LRU: a hit refreshes the key, an insert beyond the bound evicts the
    least-recently-used executable (counted in ``evictions``).

    ``fault_hook`` is the fault-injection seam (serving/faults.py): called
    with the cache key before *every* invocation of a cached executable,
    raising to simulate a transient executable failure. The guard fires
    strictly pre-dispatch, so donated buffers (decode caches) are never
    consumed by a faulted call — the engine can retry against intact state.
    ``None`` (the default) wraps nothing: the cache returns the raw
    executable exactly as before.
    """

    def __init__(self, max_entries: Optional[int] = None, fault_hook=None):
        if max_entries is not None and max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}")
        self.max_entries = max_entries
        self.fault_hook = fault_hook
        self._exes: "OrderedDict[Hashable, Any]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.compile_s = 0.0

    def _guard(self, key: Hashable, exe: Any) -> Any:
        """Wrap an executable so ``fault_hook(key)`` runs before dispatch."""
        if self.fault_hook is None:
            return exe
        hook = self.fault_hook

        def guarded(*args, **kwargs):
            hook(key)  # may raise TransientExecutableFault — pre-dispatch
            return exe(*args, **kwargs)

        return guarded

    def get(self, key: Hashable, build: Callable[[], Any]) -> Any:
        """Return the executable for ``key``, compiling via ``build`` on miss."""
        exe = self._exes.get(key)
        if exe is not None:
            self.hits += 1
            self._exes.move_to_end(key)  # LRU refresh (no-op when unbounded)
            return self._guard(key, exe)
        self.misses += 1
        t0 = time.perf_counter()
        # a miss is a compile: the span names it on the device trace's clock
        with trace.span(trace.COMPILE, key=lambda: trace.text(key)):
            try:
                exe = build()
            except Exception as e:
                raise ExecutableBuildError(f"building {key!r} failed: {e}") from e
        self.compile_s += time.perf_counter() - t0
        self._exes[key] = exe
        if self.max_entries is not None:
            while len(self._exes) > self.max_entries:
                self._exes.popitem(last=False)
                self.evictions += 1
        return self._guard(key, exe)

    def lookup(self, key: Hashable) -> Optional[Any]:
        """The cached executable for ``key`` (unguarded), or None. Counts
        nothing: for inspecting compiled programs, not for dispatch."""
        return self._exes.get(key)

    def __len__(self) -> int:
        return len(self._exes)

    def __contains__(self, key: Hashable) -> bool:
        return key in self._exes

    def reset_stats(self) -> None:
        """Zero the counters, keeping compiled executables (warmup -> steady)."""
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.compile_s = 0.0

    def stats(self) -> dict:
        total = self.hits + self.misses
        return {
            "hits": self.hits,
            "misses": self.misses,
            "hit_rate": self.hits / total if total else 0.0,
            "entries": len(self._exes),
            "evictions": self.evictions,
            "max_entries": self.max_entries,
            "compile_s": self.compile_s,
        }


def aot_compile(fn, *arg_specs, donate_argnums=(), out_shardings=None) -> Any:
    """``jax.jit(fn).lower(specs).compile()`` — the cache's build helper.

    ``out_shardings`` (a single sharding applied to every output leaf, or
    None) pins the executable's outputs; mesh-attached engines pass their
    replicated sharding so a donated decode cache comes back exactly as the
    next call's input spec expects it. ``None`` lowers precisely as before.
    """
    import jax

    kw = {} if out_shardings is None else {"out_shardings": out_shardings}
    return (
        jax.jit(fn, donate_argnums=donate_argnums, **kw)
        .lower(*arg_specs)
        .compile()
    )
