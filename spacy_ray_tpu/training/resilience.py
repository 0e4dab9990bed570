"""Resilience subsystem: preemption-safe shutdown, hung-step watchdog,
retry-with-backoff for transient I/O, a deterministic fault-injection
harness, and the supervisor that relaunches a crashed training child.

The north-star is a trainer serving real TPU fleets, where preemption is
routine and a single wedged collective or torn checkpoint costs the whole
run. The reference has no fault story at all (SURVEY.md §2.4/§5.4:
checkpointing unreachable, no resume), and Ray's lineage-based fault
tolerance (Moritz et al., arXiv:1712.05889) is exactly the capability the
JAX port dropped with the actor runtime. This module restores it in SPMD
terms:

* :class:`ShutdownCoordinator` — SIGTERM/SIGINT set a flag the training
  loop polls at step boundaries; on multi-host the flag is allgathered so
  every rank checkpoints the SAME step, then the process exits with
  :data:`RC_PREEMPTED`.
* :class:`Watchdog` — a daemon thread fed a heartbeat after each completed
  step/eval. A desynced multi-host collective wedges forever with no
  exception to catch; the watchdog dumps every Python thread stack plus
  the input-pipeline stats to stderr and hard-exits :data:`RC_WATCHDOG`
  so the supervisor (or the cluster scheduler) can restart the run.
* :class:`RetryPolicy` / :func:`retry_io` — exponential backoff + jitter
  around transient I/O (corpus/DocBin opens, checkpoint writes), with an
  injectable clock/sleep/rng so tests never touch the wall clock.
* :class:`FaultPlan` — env/config-driven "fail site X on call N with
  error E" for the named sites in :data:`FAULT_SITES`; the resilience
  tests drive preemption, torn checkpoints, and retry paths with it
  deterministically.
* :class:`Supervisor` — ``train --max-restarts N`` wraps the training
  child: nonzero exits relaunch with ``--resume`` (recovering from the
  last intact checkpoint generation), relayed signals escalate
  SIGTERM → SIGKILL after a grace period (:func:`terminate_with_grace`).

Every event the subsystem emits goes through :func:`log_event`, which both
logs to the ``spacy_ray_tpu.training`` logger and queues a structured
record that the jsonl training logger drains into its next row — resume
anomalies and retries land in machine-readable logs, not just stderr.
"""

from __future__ import annotations

import logging
import os
import random
import signal
import subprocess
import sys
import threading
import time
import traceback
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

__all__ = [
    "RC_PREEMPTED",
    "RC_WATCHDOG",
    "FAULT_SITES",
    "FAULT_PLAN_ENV",
    "ShutdownCoordinator",
    "Watchdog",
    "RetryPolicy",
    "retry_io",
    "set_default_retry_policy",
    "FaultInjected",
    "FaultPlan",
    "set_fault_plan",
    "get_fault_plan",
    "activate_env_fault_plan",
    "maybe_fail",
    "consume_poison",
    "consume_wire_fault",
    "partitioned",
    "corrupt_bytes",
    "terminate_with_grace",
    "Supervisor",
    "log_event",
    "drain_events",
]

# Distinct exit codes so supervisors/schedulers can tell outcomes apart:
# RC_PREEMPTED = clean preemption shutdown (checkpoint written at a step
# boundary, safe to resume); RC_WATCHDOG = hung step, state of the last
# checkpoint is intact but the process had to be hard-killed.
RC_PREEMPTED = 75  # EX_TEMPFAIL: transient by design — restart and resume
RC_WATCHDOG = 79

logger = logging.getLogger("spacy_ray_tpu.training")


# ----------------------------------------------------------------------
# Structured event log
# ----------------------------------------------------------------------

# bounded: a retry storm must not grow memory without bound before the
# next jsonl row drains it
_EVENTS: "deque[Dict[str, Any]]" = deque(maxlen=256)
_EVENTS_LOCK = threading.Lock()


def log_event(
    event: str, message: str, level: int = logging.WARNING, **fields: Any
) -> Dict[str, Any]:
    """Record a resilience event: the training logger (human path) plus a
    structured record the jsonl logger drains into its next row (machine
    path — resume anomalies and retries must be visible in jsonl logs,
    not only on a scrolled-away stderr)."""
    rec = {"event": event, "message": message, **fields}
    logger.log(level, "[%s] %s", event, message)
    with _EVENTS_LOCK:
        _EVENTS.append(rec)
    return rec


def drain_events() -> List[Dict[str, Any]]:
    """Return and clear the queued structured events (jsonl logger hook)."""
    with _EVENTS_LOCK:
        out = list(_EVENTS)
        _EVENTS.clear()
    return out


# ----------------------------------------------------------------------
# Preemption-aware shutdown
# ----------------------------------------------------------------------


class ShutdownCoordinator:
    """SIGTERM/SIGINT → a flag the training loop polls at step boundaries.

    The handler only sets an event (async-signal-safe); the loop decides
    when to act, so the checkpoint is always written at a step boundary
    with a consistent (params, opt_state, data-position) triple. On
    multi-host, :meth:`coordinated_stop` allgathers the flag so every rank
    stops — and checkpoints — the same step, even when the preemption
    notice only reached one host. A second SIGINT escalates to the
    previous handler (normally KeyboardInterrupt) for operators who really
    mean it.
    """

    SIGNALS = (signal.SIGTERM, signal.SIGINT)

    def __init__(self) -> None:
        self._flag = threading.Event()
        self._signum: Optional[int] = None
        self._prev: Dict[int, Any] = {}
        self._installed = False
        self._callbacks: List[Callable[[Optional[int]], Any]] = []

    # -- flag --------------------------------------------------------
    def add_callback(self, fn: Callable[[Optional[int]], Any]) -> None:
        """Register a hook fired from :meth:`request` (i.e. from the
        signal handler) — it must be async-signal-safe in practice: set
        an Event, flip a flag, never block. The serving front-end uses
        this to trip its drain gate the instant SIGTERM lands instead of
        waiting for the next admission poll."""
        self._callbacks.append(fn)

    def request(self, signum: Optional[int] = None) -> None:
        self._signum = signum
        self._flag.set()
        for cb in self._callbacks:
            try:
                cb(signum)
            except Exception:  # a broken hook must not break the handler
                pass

    @property
    def requested(self) -> bool:
        return self._flag.is_set()

    @property
    def signum(self) -> Optional[int]:
        return self._signum

    # -- signal wiring ------------------------------------------------
    def _handle(self, signum: int, frame: Any) -> None:
        if self._flag.is_set() and signum == signal.SIGINT:
            # second Ctrl-C: the operator wants OUT, not another graceful
            # lap — fall through to the previous handler
            prev = self._prev.get(signum)
            if callable(prev):
                prev(signum, frame)
                return
            raise KeyboardInterrupt
        self.request(signum)

    def install(self) -> "ShutdownCoordinator":
        """Install handlers (main thread only — elsewhere signal.signal
        raises, and a worker-thread train() can still poll a flag set by
        whoever owns the signals)."""
        if threading.current_thread() is not threading.main_thread():
            return self
        for signum in self.SIGNALS:
            try:
                self._prev[signum] = signal.signal(signum, self._handle)
            except (ValueError, OSError):  # pragma: no cover — exotic hosts
                pass
        self._installed = True
        return self

    def restore(self) -> None:
        if not self._installed:
            return
        for signum, prev in self._prev.items():
            try:
                signal.signal(signum, prev)
            except (ValueError, OSError):  # pragma: no cover
                pass
        self._prev.clear()
        self._installed = False

    # -- multi-host agreement -----------------------------------------
    def coordinated_stop(self, process_count: int = 1) -> bool:
        """Should the loop stop at THIS step boundary?

        Single-process: the local flag. Multi-host: allgather the flag —
        if ANY rank was signalled, every rank returns True at the same
        step, so all ranks write (rank 0) or participate in (all ranks,
        the opt-state gather is collective) the same checkpoint. This is
        one tiny allgather per step — noise next to the update's own
        collectives, and the price of never tearing a pod checkpoint.
        """
        if process_count <= 1:
            return self.requested
        import numpy as np
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(
            np.array([1 if self.requested else 0], np.int32)
        )
        return bool(int(np.max(flags)) > 0)


# ----------------------------------------------------------------------
# Hung-step watchdog
# ----------------------------------------------------------------------


class Watchdog:
    """Daemon thread that hard-exits the process when no heartbeat arrives
    within ``timeout_s``.

    A desynced multi-host collective (one rank crashed mid-allgather)
    blocks inside compiled code with no exception to
    catch — the process sits forever and the whole pod's allocation burns.
    The watchdog's only job is to turn "wedged forever" into "dump
    diagnostics, exit :data:`RC_WATCHDOG`, let the supervisor resume from
    the last checkpoint".

    Diagnostics on fire: every Python thread's stack (the training thread
    shows WHERE it wedged) plus the input-pipeline stats snapshot. The
    exit is ``os._exit`` — a wedged collective ignores interpreter-level
    unwinding by definition.

    ``clock``/``sleep``/``exit_fn`` are injectable so tests drive the
    fire path with a fake clock and never wait on (or kill) anything real.
    """

    def __init__(
        self,
        timeout_s: float,
        *,
        stats_fn: Optional[Callable[[], Any]] = None,
        clock: Callable[[], float] = time.monotonic,
        sleep: Callable[[float], None] = time.sleep,
        exit_fn: Optional[Callable[[int], None]] = None,
        stream: Any = None,
    ) -> None:
        if timeout_s <= 0:
            raise ValueError("watchdog timeout_s must be > 0 (0 disables it)")
        self.timeout_s = float(timeout_s)
        self._stats_fn = stats_fn
        self._clock = clock
        self._sleep = sleep
        self._exit_fn = exit_fn or (lambda rc: os._exit(rc))
        self._stream = stream
        self._last_beat = clock()
        self._stop = threading.Event()
        self._fired = False
        self._thread: Optional[threading.Thread] = None

    def beat(self) -> None:
        """Feed the watchdog — called after each completed step/eval."""
        self._last_beat = self._clock()

    def check(self) -> bool:
        """One poll: fire if the heartbeat is older than the timeout.
        Returns True when it fired (tests call this directly)."""
        if self._fired:
            return True
        if self._clock() - self._last_beat <= self.timeout_s:
            return False
        self._fired = True
        self._dump()
        self._exit_fn(RC_WATCHDOG)
        return True

    def _dump(self) -> None:
        stream = self._stream if self._stream is not None else sys.stderr
        stalled = self._clock() - self._last_beat
        lines = [
            f"[watchdog] no step heartbeat for {stalled:.1f}s "
            f"(timeout {self.timeout_s:.1f}s) — dumping threads and "
            f"exiting {RC_WATCHDOG}",
        ]
        frames = sys._current_frames()
        names = {t.ident: t.name for t in threading.enumerate()}
        for ident, frame in frames.items():
            lines.append(
                f"--- thread {names.get(ident, '?')} (ident {ident}) ---"
            )
            lines.append("".join(traceback.format_stack(frame)).rstrip())
        if self._stats_fn is not None:
            try:
                lines.append(f"[watchdog] input pipeline: {self._stats_fn()}")
            except Exception as e:  # diagnostics must never mask the exit
                lines.append(f"[watchdog] stats unavailable: {e!r}")
        try:
            stream.write("\n".join(lines) + "\n")
            stream.flush()
        except Exception:  # pragma: no cover — dead stderr
            pass

    def _run(self) -> None:
        poll = min(self.timeout_s / 4.0, 1.0)
        while not self._stop.is_set():
            if self.check():
                return
            self._sleep(poll)

    def start(self) -> "Watchdog":
        self._last_beat = self._clock()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="train-watchdog"
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None


# ----------------------------------------------------------------------
# Retry with exponential backoff + jitter
# ----------------------------------------------------------------------


class RetryPolicy:
    """Exponential backoff with jitter; clock-free and fully injectable.

    delay(attempt) = min(max_delay, base * 2**(attempt-1)) * (1 + U[0, jitter])

    Jitter decorrelates retries across ranks/workers hammering the same
    filesystem after a shared blip (the classic thundering-herd fix).
    """

    def __init__(
        self,
        max_retries: int = 3,
        base_delay: float = 0.5,
        max_delay: float = 8.0,
        jitter: float = 0.5,
        sleep: Callable[[float], None] = time.sleep,
        rng: Optional[random.Random] = None,
    ) -> None:
        self.max_retries = max(int(max_retries), 0)
        self.base_delay = float(base_delay)
        self.max_delay = float(max_delay)
        self.jitter = float(jitter)
        self.sleep = sleep
        self.rng = rng or random.Random()

    def delay(self, attempt: int) -> float:
        base = min(self.max_delay, self.base_delay * (2.0 ** max(attempt - 1, 0)))
        return base * (1.0 + self.jitter * self.rng.random())


_DEFAULT_RETRY = RetryPolicy()


def set_default_retry_policy(policy: RetryPolicy) -> RetryPolicy:
    """Install the process-wide default policy (the training loop sets it
    from ``[training] io_retries`` / ``io_retry_base_s``). Returns the
    previous policy so callers can restore it."""
    global _DEFAULT_RETRY
    prev = _DEFAULT_RETRY
    _DEFAULT_RETRY = policy
    return prev


def retry_io(
    site: str,
    fn: Callable[[], Any],
    policy: Optional[RetryPolicy] = None,
    retry_on: Tuple[type, ...] = (OSError,),
) -> Any:
    """Run ``fn`` retrying transient errors with backoff + jitter.

    OSError covers the transient family that matters on fleet storage
    (NFS/GCS-FUSE flakes, EIO, stale handles); everything else — corrupt
    data, logic errors — must NOT be retried into an infinite loop and
    propagates immediately. Deterministic config errors that merely WEAR
    an OSError (missing path, permissions) are exempted too: retrying a
    typo'd [paths] entry only delays the real message by the full backoff.
    """
    pol = policy or _DEFAULT_RETRY
    attempt = 0
    while True:
        try:
            return fn()
        except retry_on as e:
            if isinstance(
                e,
                (FileNotFoundError, NotADirectoryError, IsADirectoryError,
                 PermissionError),
            ):
                raise
            attempt += 1
            if attempt > pol.max_retries:
                raise
            d = pol.delay(attempt)
            log_event(
                "io-retry",
                f"{site}: {type(e).__name__}: {e} — retry "
                f"{attempt}/{pol.max_retries} in {d:.2f}s",
                site=site,
                attempt=attempt,
            )
            pol.sleep(d)


# ----------------------------------------------------------------------
# Fault-injection harness
# ----------------------------------------------------------------------

FAULT_SITES = (
    "corpus-read", "collate", "checkpoint-write", "step", "grad-push",
    "param-pull", "checkpoint-wire",
)
FAULT_PLAN_ENV = "SPACY_RAY_TPU_FAULT_PLAN"

_FAULT_KINDS = ("oserror", "runtime", "sigterm", "nan")

#: wire-chaos kinds (the PR 17 harness): they never raise — they queue
#: an ACTION the fleet's wire call sites consume via
#: :func:`consume_wire_fault`, or (partition/heal) flip a peer's
#: membership in the partitioned set read by :func:`partitioned`.
_WIRE_FAULT_KINDS = ("corrupt", "delay", "dup", "partition", "heal")

#: sites whose calls move bytes between fleet peers — the only sites a
#: wire-chaos kind may target (elsewhere it would be a silent no-op).
_WIRE_FAULT_SITES = ("grad-push", "param-pull", "checkpoint-wire")


class FaultInjected(RuntimeError):
    """Base marker for injected RuntimeErrors (so tests can catch exactly
    the injected failure and nothing else)."""


class FaultPlan:
    """Deterministic "fail site X on call N with error E" schedule.

    Spec grammar (env var :data:`FAULT_PLAN_ENV` or programmatic):

        spec     := rule ("," rule)*
        rule     := site ":" call ":" kind [":" arg]
        site     := one of FAULT_SITES
        call     := 1-based call number at that site
        kind     := "oserror" | "runtime" | "sigterm" | "nan"
                  | "corrupt" | "delay" | "dup" | "partition" | "heal"

    ``oserror`` raises OSError (the retryable family — exercises backoff),
    ``runtime`` raises :class:`FaultInjected` (non-retryable — exercises
    crash/restart), ``sigterm`` sends SIGTERM to this process (exercises
    the preemption path at an exact step), ``nan`` raises nothing but
    marks the site POISONED — the training loop polls
    :func:`consume_poison` after ``maybe_fail("step")`` and turns that
    step's reported loss into NaN, driving the telemetry NaN-loss
    anomaly detector end-to-end without corrupting real training math.

    The WIRE-CHAOS kinds (PR 17 harness; fleet wire sites only —
    ``grad-push``, ``param-pull``, ``checkpoint-wire``) never raise.
    They queue an action the wire call site consumes via
    :func:`consume_wire_fault` right where the bytes move:

    * ``corrupt`` — the next frame at the site has a byte flipped
      (:func:`corrupt_bytes`) → the receiver's :class:`WireError` path;
    * ``delay[:seconds]`` — the next call sleeps ``seconds`` (default
      1.0) first — injected latency past a step deadline;
    * ``dup`` — the next frame is delivered twice (exercises the
      buffer-overwrite / idempotent-pull semantics);
    * ``partition[:peer]`` — ALL traffic to/from ``peer`` (every peer
      when omitted) fails with OSError until a ``heal`` rule fires —
      call sites poll :func:`partitioned`;
    * ``heal[:peer]`` — lift a partition (all partitions when omitted).

    Counters are per-site and per-plan; activating a plan resets them.
    """

    def __init__(
        self, rules: Sequence[Tuple[str, int, str, Optional[str]]]
    ) -> None:
        normalized: List[Tuple[str, int, str, Optional[str]]] = []
        for rule in rules:
            site, call, kind = rule[0], rule[1], rule[2]
            arg = rule[3] if len(rule) > 3 else None
            if site not in FAULT_SITES:
                raise ValueError(
                    f"unknown fault site {site!r} (known: {', '.join(FAULT_SITES)})"
                )
            if kind not in _FAULT_KINDS and kind not in _WIRE_FAULT_KINDS:
                known = ", ".join(_FAULT_KINDS + _WIRE_FAULT_KINDS)
                raise ValueError(
                    f"unknown fault kind {kind!r} (known: {known})"
                )
            if call < 1:
                raise ValueError(f"fault call number must be >= 1, got {call}")
            if kind == "nan" and site != "step":
                # only the training loop's step site polls consume_poison;
                # a nan rule anywhere else would be a silent no-op — the
                # operator would conclude the NaN detector works (or is
                # broken) from a drill that never ran
                raise ValueError(
                    f"fault kind 'nan' is only wired at the 'step' site "
                    f"(got {site!r}): the loop polls consume_poison there"
                )
            if kind in _WIRE_FAULT_KINDS and site not in _WIRE_FAULT_SITES:
                # same silent-no-op discipline for the chaos kinds
                raise ValueError(
                    f"fault kind {kind!r} is only wired at the fleet wire "
                    f"sites {', '.join(_WIRE_FAULT_SITES)} (got {site!r})"
                )
            if arg is not None:
                if kind == "delay":
                    try:
                        float(arg)
                    except ValueError:
                        raise ValueError(
                            f"delay arg {arg!r} is not a number of seconds"
                        )
                elif kind in ("partition", "heal"):
                    try:
                        int(arg)
                    except ValueError:
                        raise ValueError(
                            f"{kind} arg {arg!r} is not a peer id"
                        )
                else:
                    raise ValueError(
                        f"fault kind {kind!r} takes no arg (got {arg!r})"
                    )
            normalized.append((site, call, kind, arg))
        self.rules = normalized
        self._counts: Dict[str, int] = {}
        self._poisoned: set = set()
        # site -> queued (kind, arg) wire actions, consumed FIFO by the
        # wire call sites; partitions live in a separate peer set
        self._wire_actions: Dict[str, List[Tuple[str, Optional[str]]]] = {}
        self._partitioned: set = set()
        self._partition_all = False
        self._lock = threading.Lock()

    @classmethod
    def parse(cls, spec: str) -> "FaultPlan":
        rules: List[Tuple[str, int, str, Optional[str]]] = []
        for chunk in spec.split(","):
            chunk = chunk.strip()
            if not chunk:
                continue
            parts = chunk.split(":")
            if len(parts) not in (3, 4):
                raise ValueError(
                    f"bad fault rule {chunk!r} (want site:call:kind[:arg])"
                )
            site, call_s, kind = parts[0], parts[1], parts[2]
            arg = parts[3].strip() if len(parts) == 4 else None
            try:
                call = int(call_s)
            except ValueError:
                raise ValueError(
                    f"bad fault rule {chunk!r}: call {call_s!r} is not an int"
                )
            rules.append((site.strip(), call, kind.strip().lower(), arg))
        return cls(rules)

    def check(self, site: str) -> None:
        """Count one call at ``site``; trigger any rule scheduled for it."""
        with self._lock:
            n = self._counts.get(site, 0) + 1
            self._counts[site] = n
        for rule in self.rules:
            r_site, r_call, r_kind, r_arg = rule
            if r_site == site and r_call == n:
                self._trigger(site, n, r_kind, r_arg)

    def _trigger(
        self, site: str, call: int, kind: str, arg: Optional[str] = None
    ) -> None:
        log_event(
            "fault-injected", f"{site} call {call}: {kind}",
            site=site, call=call, kind=kind,
            **({"arg": arg} if arg is not None else {}),
        )
        if kind == "oserror":
            raise OSError(f"injected fault: {site} call {call}")
        if kind == "runtime":
            raise FaultInjected(f"injected fault: {site} call {call}")
        if kind == "sigterm":
            os.kill(os.getpid(), signal.SIGTERM)
        if kind == "nan":
            with self._lock:
                self._poisoned.add(site)
        if kind in ("corrupt", "delay", "dup"):
            with self._lock:
                self._wire_actions.setdefault(site, []).append((kind, arg))
        if kind == "partition":
            with self._lock:
                if arg is None:
                    self._partition_all = True
                else:
                    self._partitioned.add(int(arg))
        if kind == "heal":
            with self._lock:
                if arg is None:
                    self._partition_all = False
                    self._partitioned.clear()
                else:
                    self._partitioned.discard(int(arg))

    def consume_poison(self, site: str) -> bool:
        """True exactly once per triggered ``nan`` rule at ``site``."""
        with self._lock:
            if site in self._poisoned:
                self._poisoned.discard(site)
                return True
        return False

    def consume_wire_fault(
        self, site: str
    ) -> Optional[Tuple[str, Optional[str]]]:
        """Pop the next queued ``(kind, arg)`` wire action at ``site``
        (corrupt/delay/dup), or None. FIFO; each triggered rule is
        consumed exactly once."""
        with self._lock:
            queue = self._wire_actions.get(site)
            if queue:
                return queue.pop(0)
        return None

    def partitioned(self, peer: Any) -> bool:
        """Is traffic to/from ``peer`` currently severed?"""
        with self._lock:
            if self._partition_all:
                return True
            try:
                return int(peer) in self._partitioned
            except (TypeError, ValueError):
                return False


_ACTIVE_PLAN: Optional[FaultPlan] = None


def set_fault_plan(plan: Optional[FaultPlan]) -> Optional[FaultPlan]:
    """Install (or clear, with None) the active plan. Returns the previous
    one so tests can restore it."""
    global _ACTIVE_PLAN
    prev = _ACTIVE_PLAN
    _ACTIVE_PLAN = plan
    return prev


def get_fault_plan() -> Optional[FaultPlan]:
    return _ACTIVE_PLAN


def activate_env_fault_plan() -> Optional[FaultPlan]:
    """(Re-)read :data:`FAULT_PLAN_ENV` and install the parsed plan with
    fresh counters — called at train() start so a supervisor-relaunched
    child picks the plan up from its environment."""
    spec = os.environ.get(FAULT_PLAN_ENV, "").strip()
    if not spec:
        return _ACTIVE_PLAN
    set_fault_plan(FaultPlan.parse(spec))
    return _ACTIVE_PLAN


def maybe_fail(site: str) -> None:
    """Fault hook compiled into the named sites; free when no plan is
    active (one global read)."""
    plan = _ACTIVE_PLAN
    if plan is not None:
        plan.check(site)


def consume_poison(site: str) -> bool:
    """Did a ``nan`` rule trigger at ``site`` since the last poll? Free
    when no plan is active (one global read) — the training loop polls
    this every step right after ``maybe_fail("step")``."""
    plan = _ACTIVE_PLAN
    if plan is not None:
        return plan.consume_poison(site)
    return False


def consume_wire_fault(site: str) -> Optional[Tuple[str, Optional[str]]]:
    """Next queued wire-chaos action (corrupt/delay/dup) at ``site``, or
    None. Free when no plan is active (one global read) — the fleet's
    wire call sites poll this right after ``maybe_fail(site)``."""
    plan = _ACTIVE_PLAN
    if plan is not None:
        return plan.consume_wire_fault(site)
    return None


def partitioned(peer: Any) -> bool:
    """Is ``peer`` behind an injected partition? Free when no plan is
    active — the fleet's wire call sites check this before every
    exchange and surface True as the same OSError a real severed link
    produces."""
    plan = _ACTIVE_PLAN
    if plan is not None:
        return plan.partitioned(peer)
    return False


def corrupt_bytes(body: bytes) -> bytes:
    """Deterministically flip one byte in the middle of a frame — the
    ``corrupt`` chaos kind's payload mutation. Applied to an SRTF1 frame
    it lands inside the header/data region (past the magic), so the
    receiver sees a :class:`~.fleet.wire.WireError`-shaped failure, not
    an unrecognized protocol."""
    if not body:
        return body
    b = bytearray(body)
    i = len(b) // 2
    b[i] ^= 0xFF
    return bytes(b)


# ----------------------------------------------------------------------
# Graceful termination + supervisor
# ----------------------------------------------------------------------


def terminate_with_grace(
    proc: "subprocess.Popen",
    grace_s: float = 10.0,
    kill_grace_s: float = 5.0,
) -> Optional[int]:
    """SIGTERM, wait ``grace_s``, then escalate to SIGKILL.

    SIGTERM-only shutdown hangs forever on a child that ignores or can't
    service the signal (wedged in a collective, masked handlers); a bare
    SIGKILL gives a healthy child no chance to finish its checkpoint. This
    is the one escalation sequence every launcher here shares. Returns the child's returncode (None if it survived even
    SIGKILL, which means an unkillable D-state process).
    """
    if proc.poll() is not None:
        return proc.returncode
    try:
        proc.terminate()
    except OSError:  # already gone
        return proc.poll()
    try:
        return proc.wait(timeout=grace_s)
    except subprocess.TimeoutExpired:
        pass
    log_event(
        "shutdown-escalated",
        f"child pid {proc.pid} ignored SIGTERM for {grace_s:.1f}s — SIGKILL",
        pid=proc.pid,
    )
    try:
        proc.kill()
    except OSError:
        return proc.poll()
    try:
        return proc.wait(timeout=kill_grace_s)
    except subprocess.TimeoutExpired:  # pragma: no cover — D-state zombie
        return None


class Supervisor:
    """``--max-restarts N``: relaunch the training child on nonzero exit.

    ``build_cmd(attempt)`` returns the child argv for launch ``attempt``
    (0 = first); the CLI appends ``--resume`` for every relaunch so the
    child recovers from the last intact checkpoint generation. Signals
    received by the supervisor are relayed to the child with the
    SIGTERM → SIGKILL escalation, and a relayed shutdown is NOT restarted
    — the operator (or the scheduler) asked the whole tree to stop.

    A child that exits 0 ends supervision. A child that keeps dying past
    ``max_restarts`` propagates its final returncode.
    """

    def __init__(
        self,
        build_cmd: Callable[[int], List[str]],
        max_restarts: int,
        *,
        grace_s: float = 10.0,
        popen: Callable[..., "subprocess.Popen"] = subprocess.Popen,
        restart_delay_s: float = 1.0,
        sleep: Callable[[float], None] = time.sleep,
    ) -> None:
        self.build_cmd = build_cmd
        self.max_restarts = max(int(max_restarts), 0)
        self.grace_s = float(grace_s)
        self.popen = popen
        self.restart_delay_s = float(restart_delay_s)
        self.sleep = sleep
        self.restarts_used = 0
        self._shutdown = threading.Event()
        self._child: Optional["subprocess.Popen"] = None

    def _relay(self, signum: int, frame: Any) -> None:
        self._shutdown.set()
        child = self._child
        if child is not None and child.poll() is None:
            # escalate on a helper thread: a signal handler must not block
            # for the whole grace period
            threading.Thread(
                target=terminate_with_grace,
                args=(child, self.grace_s),
                daemon=True,
                name="supervisor-escalate",
            ).start()

    def request_shutdown(self) -> None:
        """Programmatic equivalent of a relayed signal, for a parent that
        multiplexes several supervisors on worker threads (the trainer-
        fleet coordinator): only the parent's MAIN thread can own signal
        handlers, so it fans the one OS signal out to each supervisor
        through this."""
        self._relay(signal.SIGTERM, None)

    def run(self) -> int:
        prev_handlers: Dict[int, Any] = {}
        in_main = threading.current_thread() is threading.main_thread()
        if in_main:
            for signum in (signal.SIGTERM, signal.SIGINT):
                try:
                    prev_handlers[signum] = signal.signal(signum, self._relay)
                except (ValueError, OSError):  # pragma: no cover
                    pass
        try:
            attempt = 0
            while True:
                if self._shutdown.is_set():
                    # a signal that arrived between children (e.g. during
                    # the restart delay) must not launch a fresh child
                    return RC_PREEMPTED
                cmd = self.build_cmd(attempt)
                self._child = self.popen(cmd)
                if self._shutdown.is_set():
                    # signal landed while popen was in flight: _relay saw
                    # only the previous (dead) child — escalate this one
                    # ourselves or wait() blocks for the child's whole run
                    threading.Thread(
                        target=terminate_with_grace,
                        args=(self._child, self.grace_s),
                        daemon=True,
                        name="supervisor-escalate",
                    ).start()
                rc = self._child.wait()
                if rc == 0:
                    return 0
                if self._shutdown.is_set():
                    # relayed shutdown: the child may have died on the
                    # escalated SIGKILL (negative waitpid code, which the
                    # shell would render as a meaningless 128+N) — report
                    # the tree's outcome, a clean preemption
                    return RC_PREEMPTED
                if self.restarts_used >= self.max_restarts:
                    log_event(
                        "supervisor-giving-up",
                        f"child exited rc={rc}; {self.restarts_used} restart(s) "
                        "used — giving up",
                        rc=rc,
                    )
                    return rc
                self.restarts_used += 1
                attempt += 1
                log_event(
                    "supervisor-restart",
                    f"child exited rc={rc} — restart "
                    f"{self.restarts_used}/{self.max_restarts} (resuming from "
                    "the last intact checkpoint)",
                    rc=rc,
                    restart=self.restarts_used,
                )
                if self.restart_delay_s > 0:
                    self.sleep(self.restart_delay_s)
        finally:
            self._child = None
            if in_main:
                for signum, prev in prev_handlers.items():
                    try:
                        signal.signal(signum, prev)
                    except (ValueError, OSError):  # pragma: no cover
                        pass
