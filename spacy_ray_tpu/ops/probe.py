"""The start-up gate every pallas kernel goes through.

Each kernel is armed by a one-time probe that compiles it on the current
backend and compares it with its jnp reference. The policy lives here so
the four kernels cannot drift apart on it:

* ``<ENV>=0`` switches the kernel off; unset, it is armed on TPU only;
  ``<ENV>=1`` runs the probe on any backend (tests, interpret mode).
* On a TPU a probe that does not compile, or whose numbers disagree with
  the reference, RAISES with the compiler's or the comparison's own words.
  A kernel that silently gave way to its reference there would leave the
  run's records claiming a path the chip never executed. Off-TPU a failed
  forced probe reads as "off" with the reason in the status string.
"""

from __future__ import annotations

import os
from typing import Callable, Optional, Tuple

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


def probe(
    kernel: str, env_var: str, check: Callable[[], Optional[str]],
    interpret: bool = False,
) -> Tuple[bool, str]:
    """Resolve one kernel on the current backend: ``(armed, status)``. The
    status names the reason whenever the kernel is off; ``interpret`` says
    the check ran the pallas interpreter (tests), for an honest label."""
    env = os.environ.get(env_var)
    backend = jax.default_backend()
    if env == "0":
        return False, f"off ({env_var}=0)"
    if env != "1" and backend != "tpu":
        return False, f"off (auto-off on {backend}; {env_var}=1 forces it)"
    problem = checked(kernel, check)
    if problem is not None:
        first_line = problem.strip().splitlines()[0]
        return False, f"off (probe failed on {backend}: {first_line})"
    return True, (
        "active (pallas interpret-mode)" if interpret else "active (pallas)"
    )
