"""Train-and-serve orchestration: one training subprocess and one
serving fleet sharing a checkpoint directory, under a single
ShutdownCoordinator — the continuous-learning loop as one command.

Process tree (``spacy_ray_tpu train-and-serve``)::

    train-and-serve                      <- this process (coordinator)
      |- train subprocess                -> writes <output>/last-model/
      |                                     generations (digest-stamped)
      |- Fleet (router + controller)     <- watches <output>/last-model
           |- serve replica #0..N-1      <- hot-swap via /admin/swap

Lifecycle contracts:

* **Bootstrap.** The fleet needs a servable model directory before
  training has produced anything. Either the caller supplies one
  (``FleetConfig.model_path`` already set — serve the previous best
  while the new run improves it), or the orchestrator waits for the
  training run's first ``best-model/`` save and snapshots it into
  ``<output>/serve-bootstrap`` (a copy, because ``best-model/`` is
  rewritten in place on every improvement and a replica must never read
  a directory mid-rewrite).
* **SIGTERM drains BOTH, in parallel.** The coordinator callback
  forwards SIGTERM to the trainer (its step-boundary preemption path:
  checkpoint, exit :data:`~...training.resilience.RC_PREEMPTED`) and
  trips the fleet drain (router stops admitting, replicas finish
  in-flight work). Exit 0 iff the fleet drained clean AND the trainer
  exited 0 (finished) or RC_PREEMPTED (checkpointed out) — preemption
  is the *designed* shutdown here, not a failure.
* **A dead trainer does not kill serving.** A trainer crash is a loud
  structured event; the fleet keeps serving the last good generation —
  that is the entire point of generation-verified hot-swap.
"""

from __future__ import annotations

import logging
import shutil
import subprocess
import threading
import time
from collections import deque
from pathlib import Path
from typing import Callable, Dict, List, Optional

from ...training.resilience import (
    RC_PREEMPTED,
    ShutdownCoordinator,
    log_event,
    terminate_with_grace,
)

__all__ = ["TrainAndServe", "wait_for_best_model"]

logger = logging.getLogger("spacy_ray_tpu.serving")


def wait_for_best_model(
    output_dir,
    stop: threading.Event,
    *,
    timeout_s: float = 600.0,
    settle_s: float = 1.0,
    poll_s: float = 0.5,
) -> Optional[Path]:
    """Block until ``<output>/best-model`` holds a complete model
    (config + params), then snapshot-copy it to
    ``<output>/serve-bootstrap`` and return that path. None on timeout
    or when ``stop`` is set first. ``settle_s`` lets the writer finish
    the sidecar files that land after params.npz before the copy."""
    output_dir = Path(output_dir)
    best = output_dir / "best-model"
    deadline = time.monotonic() + float(timeout_s)
    while not stop.is_set() and time.monotonic() < deadline:
        if (best / "config.cfg").exists() and (best / "params.npz").exists():
            stop.wait(settle_s)
            snapshot = output_dir / "serve-bootstrap"
            try:
                # best-model/ is rewritten IN PLACE on every improvement
                # (per-file os.replace) — a copy racing the rewrite can
                # see a listed file vanish mid-walk. That is a retry,
                # not a failure: loop around and copy the newer save.
                shutil.rmtree(snapshot, ignore_errors=True)
                shutil.copytree(best, snapshot)
            except OSError:
                stop.wait(poll_s)
                continue
            return snapshot
        stop.wait(poll_s)
    return None


class TrainAndServe:
    """Own the whole loop: spawn the trainer, bootstrap a model,
    run the fleet, drain both on shutdown.

    ``fleet_config.watch_dir`` should point at ``<output>/last-model``
    (the CLI wires this); ``fleet_config.model_path`` may be empty, in
    which case ``model_bootstrap`` (default: :func:`wait_for_best_model`
    over ``output_dir``) supplies it after training starts.
    """

    def __init__(
        self,
        train_cmd: List[str],
        fleet_config,
        *,
        output_dir,
        train_env: Optional[Dict[str, str]] = None,
        model_bootstrap: Optional[
            Callable[["TrainAndServe"], Optional[Path]]
        ] = None,
        bootstrap_timeout_s: float = 600.0,
        train_grace_s: float = 75.0,
    ) -> None:
        self.train_cmd = list(train_cmd)
        self.fleet_config = fleet_config
        self.output_dir = Path(output_dir)
        self.train_env = train_env
        self.model_bootstrap = model_bootstrap
        self.bootstrap_timeout_s = float(bootstrap_timeout_s)
        self.train_grace_s = float(train_grace_s)
        self.train_proc: Optional[subprocess.Popen] = None
        self.train_rc: Optional[int] = None
        self.fleet = None
        self.train_tail: "deque[str]" = deque(maxlen=40)
        self._shutdown = threading.Event()

    # -- shutdown (signal-handler-safe: flag + signal forward only) ------
    def request_shutdown(self, signum: Optional[int] = None) -> None:
        self._shutdown.set()
        fleet = self.fleet
        if fleet is not None:
            fleet.request_shutdown(signum)
        proc = self.train_proc
        if proc is not None and proc.poll() is None:
            try:
                proc.terminate()  # the trainer's preemption path
            except OSError:
                pass

    # -- trainer ---------------------------------------------------------
    def _spawn_train(self) -> None:
        import os

        env = dict(os.environ)
        if self.train_env:
            env.update(self.train_env)
        self.train_proc = subprocess.Popen(
            self.train_cmd,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        threading.Thread(
            target=self._forward_train_output, daemon=True, name="train-stdout"
        ).start()

    def _forward_train_output(self) -> None:
        proc = self.train_proc
        assert proc is not None and proc.stdout is not None
        try:
            for line in proc.stdout:
                line = line.rstrip("\n")
                self.train_tail.append(line)
                print(f"[train] {line}", flush=True)
        except (ValueError, OSError):
            pass
        rc = proc.wait()
        self.train_rc = rc
        if self._shutdown.is_set() or rc in (0, RC_PREEMPTED):
            return
        # crash while we were supposed to keep learning: loud event,
        # serving continues on the last good generation
        tail = " | ".join(list(self.train_tail)[-3:])
        log_event(
            "train-and-serve-trainer-crash",
            f"training subprocess exited rc={rc} — the fleet keeps "
            "serving the last promoted generation"
            + (f" (last output: {tail})" if tail else ""),
            rc=rc,
        )

    def _stop_train(self) -> Optional[int]:
        proc = self.train_proc
        if proc is None:
            return None
        if proc.poll() is None:
            if self._shutdown.is_set():
                # the coordinator callback already SIGTERMed the trainer;
                # it is mid-drain (checkpointing at a step boundary). A
                # second SIGTERM could land AFTER it restored default
                # handlers and kill the graceful exit (-15 instead of
                # 75) — wait for the exit it is already performing,
                # escalate only past the grace budget
                try:
                    rc: Optional[int] = proc.wait(
                        timeout=self.train_grace_s
                    )
                except subprocess.TimeoutExpired:
                    rc = terminate_with_grace(proc, grace_s=5.0)
            else:
                rc = terminate_with_grace(proc, grace_s=self.train_grace_s)
        else:
            rc = proc.returncode
        self.train_rc = rc
        return rc

    def _train_clean(self) -> bool:
        # 0 = ran to completion; RC_PREEMPTED = checkpointed out on our
        # SIGTERM — the designed shutdown, not a failure
        return self.train_rc in (0, RC_PREEMPTED)

    # -- the run ---------------------------------------------------------
    def run(self, *, banner: bool = True) -> int:
        from ..fleet import Fleet

        coordinator = ShutdownCoordinator()
        coordinator.add_callback(self.request_shutdown)
        coordinator.install()
        try:
            self._spawn_train()
            assert self.train_proc is not None
            if banner:
                print(
                    f"train-and-serve: training pid {self.train_proc.pid} "
                    f"-> {self.output_dir}",
                    flush=True,
                )
            if not self.fleet_config.model_path:
                bootstrap = self.model_bootstrap or (
                    lambda ts: wait_for_best_model(
                        ts.output_dir, ts._shutdown,
                        timeout_s=ts.bootstrap_timeout_s,
                    )
                )
                model_path = bootstrap(self)
                if model_path is None:
                    rc = self._stop_train()
                    if self._shutdown.is_set():
                        # SIGTERM before serving began: clean iff the
                        # trainer checkpointed out cleanly
                        print("shutdown before fleet start; trainer "
                              f"exited {rc}", flush=True)
                        return 0 if self._train_clean() else 1
                    print(
                        "no best-model appeared within "
                        f"{self.bootstrap_timeout_s:.0f}s (trainer rc "
                        f"{rc}) — nothing to serve", flush=True,
                    )
                    return 1
                self.fleet_config.model_path = str(model_path)
                if banner:
                    print(
                        f"bootstrapped serving model from {model_path}",
                        flush=True,
                    )
            self.fleet = Fleet(self.fleet_config)
            if self._shutdown.is_set():
                # SIGTERM landed between bootstrap and fleet start: the
                # callback missed the fleet — trip it now, then drain
                self.fleet.request_shutdown()
            host, port = self.fleet.start()
            if banner:
                print(
                    f"train-and-serve fleet on http://{host}:{port} "
                    f"({self.fleet_config.replicas} replica(s), watching "
                    f"{self.fleet_config.watch_dir})",
                    flush=True,
                )
            if self.fleet.wait_ready() and banner:
                print(
                    f"fleet ready: "
                    f"{len(self.fleet.router.ready_handles())} replica(s) "
                    "warmed", flush=True,
                )
            fleet_rc = self.fleet.wait()
            train_rc = self._stop_train()
            clean = fleet_rc == 0 and self._train_clean()
            print(
                f"train-and-serve drained (fleet rc {fleet_rc}, trainer "
                f"rc {train_rc}{' = preempted-clean' if train_rc == RC_PREEMPTED else ''})",
                flush=True,
            )
            return 0 if clean else 1
        except BaseException:
            # an orchestrator crash must not orphan the training
            # subprocess it spawned — SIGTERM it (request_shutdown also
            # trips the fleet drain if one is running), reap it, then
            # surface the error
            self.request_shutdown()
            self._stop_train()
            raise
        finally:
            coordinator.restore()
