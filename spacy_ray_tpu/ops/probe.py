"""The start-up gate every pallas kernel goes through.

Each kernel is armed by a one-time probe that compiles it on the current
backend and compares it with its jnp reference. The policy lives here so
the four kernels (flash attention, hash-embed lookup, fused update, int8
matmul) cannot drift apart on it:

* ``<ENV>=0`` switches the kernel off; unset, it is armed on TPU only;
  ``<ENV>=1`` runs the probe on any backend (tests, interpret mode).
* On a TPU a probe that does not compile, or whose numbers disagree with
  the reference, RAISES with the compiler's or the comparison's own words.
  A kernel that silently gave way to its reference there would leave the
  run's records claiming a path the chip never executed. Off-TPU a failed
  forced probe reads as "off" with the reason in the status string.
* An armed kernel still gives way on some shapes and meshes (a sequence
  past the VMEM budget, a multi-device mesh). Its dispatcher notes the path
  it takes each time it is traced (:meth:`Gate.took`), and the status a run
  prints is those paths (:meth:`Gate.status`) — what the program did, not
  only what the probe allowed.
"""

from __future__ import annotations

import os
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp


class KernelProbeError(RuntimeError):
    """A pallas kernel failed its start-up probe on a TPU backend."""


def mismatch(
    what: str, got: jnp.ndarray, want: jnp.ndarray, *, atol: float,
    rtol: float = 0.0,
) -> Optional[str]:
    """None when ``got`` is within ``atol + rtol*|want|`` of ``want``
    everywhere, else the comparison in words (the text a probe reports)."""
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    excess = jnp.abs(got - want) - (atol + rtol * jnp.abs(want))
    # written as all(excess <= 0) so that a NaN fails the comparison
    if bool(jnp.all(excess <= 0)):
        return None
    worst = float(jnp.max(jnp.abs(got - want)))
    return (
        f"{what}: max |kernel - reference| = {worst:.4g} "
        f"(atol={atol}, rtol={rtol})"
    )


def checked(kernel: str, check: Callable[[], Optional[str]]) -> Optional[str]:
    """Run a kernel's compile-and-compare ``check`` (None = agrees, else a
    description of the disagreement; may raise what the compiler raised).
    Returns None on success or the failure in words; on a TPU backend a
    failure raises :class:`KernelProbeError` instead."""
    try:
        # the first caller is usually inside the train step's trace, where
        # the probe's own arrays would be staged as tracers and its
        # comparison could not be read: step out to the eager trace
        with jax.core.eval_context():
            problem = check()
    except Exception as e:  # the compiler's refusal arrives as many types
        problem = f"{type(e).__name__}: {e}"
        if jax.default_backend() == "tpu":
            raise KernelProbeError(
                f"{kernel} kernel did not compile on tpu: {problem}"
            ) from e
        return problem
    if problem is not None and jax.default_backend() == "tpu":
        raise KernelProbeError(
            f"{kernel} kernel disagrees with its reference on tpu: {problem}"
        )
    return problem


def active(interpret: bool = False, how: str = "") -> str:
    """The label of a kernel that runs; ``interpret`` says it runs in the
    pallas interpreter (tests), never to be read as the compiled kernel."""
    mode = "pallas interpret-mode" if interpret else "pallas"
    return f"active ({mode}, {how})" if how else f"active ({mode})"


def probe(
    kernel: str, env_var: str, check: Callable[[], Optional[str]],
    interpret: bool = False, backend: Optional[str] = None,
) -> Tuple[bool, str]:
    """Resolve one kernel: ``(armed, status)``. The status names the reason
    whenever the kernel is off. ``backend`` is the backend the answer is
    for (default: this process's); a kernel can only be proven by the
    process that holds that backend, so asked about another one the probe
    answers "off" instead of compiling for the wrong chip."""
    env = os.environ.get(env_var)
    here = jax.default_backend()
    backend = backend or here
    if env == "0":
        return False, f"off ({env_var}=0)"
    if env != "1" and backend != "tpu":
        return False, f"off (auto-off on {backend}; {env_var}=1 forces it)"
    if backend != here:
        return False, (
            f"off (the kernel can only be probed on {backend} itself; "
            f"this process runs on {here})"
        )
    problem = checked(kernel, check)
    if problem is not None:
        first_line = problem.strip().splitlines()[0]
        return False, f"off (probe failed on {backend}: {first_line})"
    return True, active(interpret)


class Gate:
    """One kernel's gate for the life of the process: the probe's verdict,
    taken once, and the paths the kernel's dispatcher took afterwards."""

    def __init__(
        self, kernel: str, env_var: str, check: Callable[[], Optional[str]],
        unprobed: str,
    ) -> None:
        self.kernel, self.env_var, self.check = kernel, env_var, check
        self.armed: Optional[bool] = None
        self.verdict = f"not probed ({unprobed})"
        self.paths: List[str] = []

    def enabled(self, interpret: bool = False) -> bool:
        if self.armed is None:
            try:
                self.armed, self.verdict = probe(
                    self.kernel, self.env_var, self.check, interpret
                )
            except KernelProbeError as e:
                # whoever catches this (``info --probe``) still reads the
                # failure, in its own words, from the status
                self.verdict = f"FAILED ({e})"
                raise
        return self.armed

    def reset(self) -> None:
        """Forget the verdict and the paths: the next caller probes again
        (a test that changes the kernel's env, a second run in one
        process whose status must be its own)."""
        self.armed = None
        self.paths.clear()

    def took(self, path: str) -> None:
        """Note the path the dispatcher took in the program being traced
        (trace time: once per compiled program, nothing at run time)."""
        if path not in self.paths:
            self.paths.append(path)

    def status(self) -> str:
        """The kernel's state in this process, in words: the paths its
        dispatcher took or, where nothing was traced through it, the
        probe's verdict."""
        return "; ".join(self.paths) if self.paths else self.verdict
