"""Failure classes + retry/backoff for preemptible device capacity.

Preemptions, dropped connections and deadline kills end runs that no
simulation bug touched.  This module turns that class of failure from
a run-killer into a tolerated condition:

  * :func:`classify` — the two classes.  An exception raised by device
    dispatch or backend bring-up is either TRANSIENT (connection reset, preempted/unavailable device, deadline, resource
    exhaustion — retry with backoff) or FATAL (shape/type/value errors,
    invalid arguments — a retry would fail identically; raise now).
    Classification is by exception type first, then by status markers in
    the message (XLA runtime errors surface as a generic RuntimeError
    whose text carries the gRPC-style status).
  * :func:`with_retry` — wrap any thunk in jittered exponential backoff
    over transient failures.  The jitter is SEEDED
    (``random.Random(policy.seed)``) so fleet workers retrying in lockstep
    de-synchronize deterministically instead of thundering back onto the
    backend together.
  * :func:`acquire_backend` — bring-up under the retry policy: probe
    the ambient jax backend; transient failures are retried, and when
    the attempts (or the wall-clock budget) run out the last error
    RAISES.  There is no degradation to another platform: a run that
    asked for the chip and did not get it fails, it never continues on
    the CPU under the same name.

No jax import at module scope: the whole point is to run BEFORE a
backend exists.
"""

from __future__ import annotations

import dataclasses
import random
import sys
import time

TRANSIENT = "transient"
FATAL = "fatal"

# exception TYPES that are transient wherever they appear: every flavor
# of I/O, socket, and timeout failure a device transport can surface
_TRANSIENT_TYPES = (
    ConnectionError,        # incl. BrokenPipeError / ConnectionResetError
    TimeoutError,
    InterruptedError,
    OSError,                # fds, sockets, NFS checkpoints
)

# message markers of transient device failures.  XLA runtime
# errors reach Python as RuntimeError/XlaRuntimeError with a gRPC-style
# status prefix in the text — match the text so we need no jaxlib import.
_TRANSIENT_MARKERS = (
    "unavailable",
    "deadline exceeded",
    "deadline_exceeded",
    "resource exhausted",
    "resource_exhausted",
    "aborted",
    "cancelled",
    "connection reset",
    "connection refused",
    "broken pipe",
    "socket closed",
    "preempt",
    "timed out",
    "timeout",
    "temporarily",
    "try again",
    "too many open files",
    "failed to connect",
    "transport",
)

# message markers that are FATAL even on an otherwise-transient type:
# retrying an invalid program never helps
_FATAL_MARKERS = (
    "invalid_argument",
    "invalid argument",
    "failed_precondition",
    "failed precondition",
    "unimplemented",
    "not_found",
    "out_of_range",
)

# exception types where a retry would fail identically
_FATAL_TYPES = (ValueError, TypeError, KeyError, IndexError,
                AttributeError, AssertionError, NotImplementedError)


class RetryBudgetExceeded(RuntimeError):
    """The total-wall-clock retry budget ran out mid-storm.

    Raised by :func:`with_retry` when ``policy.max_total_seconds`` would
    be exceeded by the next backoff sleep — a transient-error storm
    fails LOUD at a bounded time instead of backing off through the
    whole attempt schedule.  Carries the full retry ``history``
    (``[(attempt, delay_s, error), ...]``) and the ``last_error`` so
    the operator sees every failure that burned the budget, not just
    the final one."""

    def __init__(self, label: str, elapsed_s: float, budget_s: float,
                 history: list, last_error: BaseException):
        self.label = label
        self.elapsed_s = elapsed_s
        self.budget_s = budget_s
        self.history = list(history)
        self.last_error = last_error
        lines = "; ".join(f"attempt {a + 1}: {err}"
                          for a, _d, err in self.history) or "none"
        super().__init__(
            f"{label or 'retry'}: total retry budget exceeded "
            f"({elapsed_s:.1f}s elapsed of {budget_s:.1f}s) — retry "
            f"history: {lines}; last error: {last_error}")


def classify(exc: BaseException) -> str:
    """The failure classes: ``"transient"`` (retry with backoff) or
    ``"fatal"`` (raise immediately).  Unknown errors default to FATAL —
    silently retrying a bug would hide it."""
    # a blown retry budget only ever wraps a transient storm (fatal
    # errors raise before any budget check) — an outer retry loop
    # treats it like the storm itself
    if isinstance(exc, RetryBudgetExceeded):
        return TRANSIENT
    text = f"{type(exc).__name__}: {exc}".lower()
    for marker in _FATAL_MARKERS:
        if marker in text:
            return FATAL
    if isinstance(exc, _FATAL_TYPES):
        return FATAL
    if isinstance(exc, _TRANSIENT_TYPES):
        return TRANSIENT
    for marker in _TRANSIENT_MARKERS:
        if marker in text:
            return TRANSIENT
    return FATAL


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Jittered exponential backoff knobs.

    ``seed`` makes the jitter DETERMINISTIC: two policies with the same
    seed produce the same delay sequence (testable), and fleet workers
    seeded by worker index de-synchronize reproducibly."""

    attempts: int = 5           # total tries (first call included)
    base_s: float = 0.5         # first backoff delay
    factor: float = 2.0         # exponential growth per attempt
    max_s: float = 30.0         # delay ceiling (pre-jitter)
    jitter: float = 0.5         # delay *= 1 + uniform(0, jitter)
    seed: int = 0
    # total-wall-clock deadline across ALL attempts and sleeps; None =
    # unbounded (the attempt count alone bounds the loop).  When the
    # next backoff sleep would cross it, with_retry raises
    # RetryBudgetExceeded with the full retry history attached.
    max_total_seconds: float | None = None


def backoff_delays(policy: RetryPolicy) -> list:
    """The policy's full delay schedule (``attempts - 1`` sleeps),
    jittered by the seeded rng — pure, deterministic, unit-testable."""
    rnd = random.Random(policy.seed)
    out = []
    for i in range(max(0, policy.attempts - 1)):
        base = min(policy.max_s, policy.base_s * policy.factor ** i)
        out.append(base * (1.0 + policy.jitter * rnd.random()))
    return out


def with_retry(fn, *, policy: RetryPolicy | None = None,
               classify_fn=classify, on_retry=None, sleep=time.sleep,
               clock=time.monotonic, label: str = ""):
    """Call ``fn()`` under the retry policy.

    Transient failures sleep the next backoff delay and retry; fatal
    failures (and transient ones past the attempt budget) re-raise.
    ``policy.max_total_seconds`` additionally bounds the TOTAL wall
    clock: when the elapsed time plus the next sleep would cross it,
    :class:`RetryBudgetExceeded` is raised with the retry history
    attached — a transient storm fails loud at a bounded time.
    ``on_retry(attempt, delay_s, exc)`` observes every retry (the fleet
    worker logs them into its heartbeat); ``sleep`` and ``clock`` are
    injectable for tests."""
    policy = policy or RetryPolicy()
    delays = backoff_delays(policy)
    budget = policy.max_total_seconds
    t0 = clock()
    history: list = []
    for attempt in range(policy.attempts):
        try:
            return fn()
        except BaseException as exc:  # noqa: BLE001 — classified below
            if classify_fn(exc) != TRANSIENT or attempt >= len(delays):
                raise
            delay = delays[attempt]
            history.append((attempt, delay, repr(exc)))
            if budget is not None:
                elapsed = clock() - t0
                if elapsed + delay > budget:
                    raise RetryBudgetExceeded(
                        label, elapsed, budget, history, exc) from exc
            if on_retry is not None:
                on_retry(attempt, delay, exc)
            else:
                sys.stderr.write(
                    "elastic.retry: %stransient failure (attempt %d/%d, "
                    "retry in %.1fs): %s\n"
                    % (f"{label}: " if label else "", attempt + 1,
                       policy.attempts, delay, exc))
            sleep(delay)
    raise AssertionError("unreachable")  # pragma: no cover


def _default_probe():
    """Touch the backend for real: device list + one tiny computation
    through the whole dispatch path."""
    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    jnp.zeros(()).block_until_ready()
    return dev.platform


def acquire_backend(policy: RetryPolicy | None = None, *, probe=None,
                    sleep=time.sleep, clock=time.monotonic) -> dict:
    """Acquire a usable jax backend under the retry policy, or raise.

    Runs ``probe`` (default: ``jax.devices()`` + a tiny dispatch) under
    the retry policy.  Success returns ``{"platform": ..., "attempts":
    n}`` — the caller merges this dict into its run manifest
    (``run_manifest(extra={"elastic": ann})``).  When every attempt
    fails transiently (device preempted, backend unavailable) the last
    error raises — :class:`RetryBudgetExceeded`, with the whole storm
    log, when the wall-clock budget ran out first.  Fatal probe errors
    raise at once.  Nothing here ever sets ``JAX_PLATFORMS``."""
    probe = probe or _default_probe
    attempts = 0

    def counted():
        nonlocal attempts
        attempts += 1
        return probe()

    platform = with_retry(counted, policy=policy or RetryPolicy(),
                          sleep=sleep, clock=clock,
                          label="backend acquisition")
    return {"platform": str(platform), "attempts": attempts}
