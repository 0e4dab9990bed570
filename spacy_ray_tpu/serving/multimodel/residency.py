"""Replica model residency: an LRU hot set of warmed engines
(docs/SERVING.md "Multi-model fleet").

PR 8's staged-then-flip swap replaced the WEIGHTS of one model at a
dispatch boundary; residency generalizes the same discipline to WHICH
MODELS a replica hosts. A replica holds up to ``capacity`` engines —
each a full ``InferenceEngine`` with its own dispatch thread and its
own per-model warmed bucket programs — keyed by registry model name:

* a request for a resident model touches the LRU order and submits —
  the hot path takes one dict lookup under the manager lock, and is
  NEVER blocked by another model's cold load;
* a request for a known-but-absent model triggers a load (pipeline
  from disk + warmup sweep) OUTSIDE the manager lock; concurrent
  requests for the same model wait on one load instead of stampeding;
* once over capacity, the least-recently-used engine is evicted at its
  dispatch boundary: ``drain`` lets queued batches finish, ``stop``
  releases the device buffers. An eviction is a refused residency,
  never a dropped request — in-flight work on the victim completes.

The manager itself makes ZERO telemetry calls (the guard extends to
this subsystem); engines carry whatever telemetry the injected factory
gives them. The clock is injectable for LRU-order tests.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from ..batcher import ServingError, UnknownModel

__all__ = ["ResidencyManager"]

logger = logging.getLogger("spacy_ray_tpu.serving")


class ResidencyManager:
    """``engine_factory(spec) -> engine`` must return a STARTED, WARMED
    engine (the server's factory builds ``InferenceEngine`` + ``warmup``
    + ``start`` with the replica's serving knobs); the manager only
    decides which engines exist."""

    def __init__(
        self,
        registry: Any,
        engine_factory: Callable[[Any], Any],
        *,
        capacity: int = 2,
        evict_drain_s: float = 5.0,
        pinned: Optional[set] = None,
        clock: Callable[[], float] = time.monotonic,
    ) -> None:
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.registry = registry
        self.engine_factory = engine_factory
        self.capacity = int(capacity)
        self.evict_drain_s = float(evict_drain_s)
        # pinned models (the manifest's default, normally) are never
        # chosen as the LRU victim: the legacy /v1/parse contract says
        # the default model is ALWAYS servable without a cold load. When
        # everything else resident is pinned the hot set may transiently
        # exceed capacity rather than evict a pinned engine.
        self.pinned = set(pinned or ())
        self.clock = clock
        self._lock = threading.Lock()
        self._engines: Dict[str, Any] = {}
        self._last_used: Dict[str, float] = {}
        self._loading: Dict[str, threading.Event] = {}
        self._load_errors: Dict[str, str] = {}
        # residency churn ledger (plain ints; /healthz reads them —
        # no telemetry objects constructed here)
        self.loads = 0
        self.evictions = 0

    def adopt(self, name: str, engine: Any) -> None:
        """Pre-register an externally built engine without counting a
        load — the server's default engine, whose warmup/start the
        server lifecycle owns (listener-first banner), arrives here."""
        self.registry.spec(name)  # typed 404 for unknown names
        with self._lock:
            self._engines[name] = engine
            self._last_used[name] = self.clock()

    # -- hot path --------------------------------------------------------
    def engine_for(self, name: str, *, load: bool = True) -> Any:
        """The engine serving ``name``, loading it into the hot set if
        absent (and ``load``). Raises ``UnknownModel`` for names the
        registry does not know; raises ``ServingError`` when a load
        fails (the model stays non-resident — a failed load is a
        refused load, never a half-resident engine)."""
        spec = self.registry.spec(name)  # typed 404 for unknown names
        while True:
            with self._lock:
                engine = self._engines.get(name)
                if engine is not None:
                    self._last_used[name] = self.clock()
                    return engine
                if not load:
                    raise ServingError(
                        f"model {name!r} is not resident on this replica"
                    )
                ev = self._loading.get(name)
                if ev is None:
                    ev = self._loading[name] = threading.Event()
                    break  # this thread leads the load
            # another thread is loading this model: wait, then re-check
            ev.wait()
            with self._lock:
                err = self._load_errors.get(name)
            if err is not None:
                raise ServingError(f"model {name!r} failed to load: {err}")
        return self._load(name, spec, ev)

    def _load(self, name: str, spec: Any, ev: threading.Event) -> Any:
        """Leader path: build the engine outside the lock (seconds of
        from-disk + warmup must not block resident models), insert,
        then evict past capacity."""
        started = self.clock()
        try:
            engine = self.engine_factory(spec)
        except Exception as exc:
            with self._lock:
                self._load_errors[name] = str(exc)
                self._loading.pop(name, None)
            ev.set()
            logger.exception("model %r load failed", name)
            raise ServingError(f"model {name!r} failed to load: {exc}")
        victims: List[Any] = []
        with self._lock:
            self._engines[name] = engine
            self._last_used[name] = self.clock()
            self._load_errors.pop(name, None)
            self._loading.pop(name, None)
            self.loads += 1
            while len(self._engines) > self.capacity:
                lru = min(
                    (
                        m for m in self._engines
                        if m != name and m not in self.pinned
                    ),
                    key=lambda m: self._last_used[m],
                    default=None,
                )
                if lru is None:
                    break
                victims.append((lru, self._engines.pop(lru)))
                self._last_used.pop(lru, None)
                self.evictions += 1
        ev.set()
        for victim_name, victim in victims:
            self._retire(victim_name, victim)
        logger.info(
            "model %r resident after %.2fs (hot set: %s)",
            name, self.clock() - started, self.resident(),
        )
        return engine

    def _retire(self, name: str, engine: Any) -> None:
        """Evict at the dispatch boundary: queued batches finish, then
        the dispatch thread stops and device buffers are released."""
        try:
            engine.drain(self.evict_drain_s)
        except Exception:
            logger.exception("evicting model %r: drain failed", name)
        try:
            engine.stop()
        except Exception:
            logger.exception("evicting model %r: stop failed", name)
        logger.info("model %r evicted (LRU)", name)

    # -- introspection ---------------------------------------------------
    def engines(self) -> Dict[str, Any]:
        """A point-in-time copy of the hot set (the /metrics per-model
        snapshot walk reads this; an engine may be evicted right after,
        which is fine — snapshots of a draining engine are still true)."""
        with self._lock:
            return dict(self._engines)

    def resident(self) -> List[str]:
        """Resident model names, least- to most-recently used."""
        with self._lock:
            return sorted(self._engines, key=lambda m: self._last_used[m])

    def resident_info(self) -> Dict[str, Dict[str, Any]]:
        """Per-model residency facts for /healthz: the router's probe
        loop learns placement from this block for free."""
        with self._lock:
            engines = dict(self._engines)
        out: Dict[str, Dict[str, Any]] = {}
        for name, engine in engines.items():
            out[name] = {
                "generation": getattr(engine, "serving_generation", None),
                "swap_count": int(getattr(engine, "swap_count", 0) or 0),
                "warmed": bool(getattr(engine, "warmed", False)),
            }
        return out

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            resident = sorted(
                self._engines, key=lambda m: self._last_used[m]
            )
            return {
                "resident": resident,
                "capacity": self.capacity,
                "loads": self.loads,
                "evictions": self.evictions,
                "residency_swaps": self.loads + self.evictions,
            }

    # -- lifecycle -------------------------------------------------------
    def begin_drain(self) -> None:
        with self._lock:
            engines = list(self._engines.items())
        for _, engine in engines:
            batcher = getattr(engine, "batcher", None)
            if batcher is not None:
                batcher.begin_drain()

    def stop_all(self, drain_timeout_s: Optional[float] = None) -> bool:
        """Drain + stop every resident engine (server shutdown). Returns
        True iff every drain completed within its timeout."""
        timeout = (
            self.evict_drain_s if drain_timeout_s is None
            else float(drain_timeout_s)
        )
        with self._lock:
            engines = list(self._engines.items())
            self._engines.clear()
            self._last_used.clear()
        clean = True
        for name, engine in engines:
            try:
                if not engine.drain(timeout):
                    clean = False
            except Exception:
                logger.exception("stopping model %r: drain failed", name)
                clean = False
            try:
                engine.stop()
            except Exception:
                logger.exception("stopping model %r: stop failed", name)
                clean = False
        return clean
