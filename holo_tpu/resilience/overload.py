"""Overload policy vocabulary: priority classes + transient-retry taxonomy.

ISSUE 19's survivability plane needs two host-side policy decisions
that must NOT live inside ``pipeline/dispatch.py`` (the queue mechanism
should not own the admission semantics):

- **priority classes** — every pipeline ticket carries one of
  :data:`CLASSES`.  ``correctness`` is FIB-feeding work (SPF / FRR /
  RIB derivation): it keeps the bounded-blocking submit contract and is
  NEVER shed.  ``advisory`` is what-if / digital-twin traffic: nobody
  is owed a stale advisory result, so it carries optional submit-time
  deadlines and is the first thing shed under overload.  ``background``
  is below advisory (re-probes, warming) — shed before anything else.
  Lower rank = more important; the class-aware dequeue in
  ``DispatchPipeline`` serves the lowest rank first, FIFO within a
  rank.

- **transient-vs-deterministic failure taxonomy** — the breaker FSM
  counts every guarded exception as a strike, so a single runtime blip
  (connection reset, UNAVAILABLE, a timed-out collective) burns 1/3 of
  the failure budget even though an immediate retry would have
  succeeded.  :func:`is_transient` splits the device-shaped errors the
  platform documents as retryable from deterministic ones (a shape
  bug, an injected forced failure, a poisoned input reproduces
  identically — retrying is pure added latency).  ``_guarded_launch``
  grants transient errors exactly one jittered-backoff retry BEFORE
  the breaker counts; deterministic errors go straight to the
  bit-identical scalar fallback.

Jitter is deterministic — a hash of (context, attempt), the
``RestartPolicy.delay`` precedent — so chaos scenarios replay
bit-for-bit under the seeded plan + virtual clock contract.

Import-light like the rest of ``resilience/``: telemetry + stdlib only.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass

from holo_tpu import telemetry

#: ticket classes, most- to least-important (index = rank)
CLASSES = ("correctness", "advisory", "background")
#: class name -> rank (0 = never shed, keeps bounded-blocking submit)
CLASS_RANK = {c: i for i, c in enumerate(CLASSES)}

_RETRIES = telemetry.counter(
    "holo_pipeline_transient_retries_total",
    "Transient-classified launch failures retried once before the "
    "breaker counts, by outcome",
    ("outcome",),
)


#: lowercase substrings of device error text the platform
#: documents as retryable service conditions (gRPC-style status names
#: the XLA runtime surfaces, plus the socket-layer phrasings).
_TRANSIENT_MARKERS = (
    "unavailable",
    "deadline_exceeded",
    "deadline exceeded",
    "resource_exhausted",
    "resource exhausted",
    "timed out",
    "timeout",
    "connection reset",
    "connection refused",
    "temporarily",
    "transient",
)


def is_transient(exc: BaseException) -> bool:
    """True when ``exc`` looks like a retryable service hiccup rather
    than a deterministic failure.

    OS-level transport errors (``ConnectionError``/``TimeoutError``/
    other ``OSError``) are transient by type: they are how a runtime blip
    presents at the socket boundary.  Everything else is classified by
    message against :data:`_TRANSIENT_MARKERS` — deliberately
    conservative, because a wrong "transient" verdict costs a wasted
    retry while a wrong "deterministic" verdict only skips an
    optimization.  ``InjectedFault`` forced failures carry none of the
    markers, so chaos plans keep their exact breaker strike counts."""
    if isinstance(exc, OSError):
        return True
    msg = str(exc).lower()
    return any(m in msg for m in _TRANSIENT_MARKERS)


@dataclass(frozen=True)
class RetryPolicy:
    """One-retry backoff budget for transient launch failures.

    ``retries=0`` disables the taxonomy entirely (every failure counts
    immediately — the pre-ISSUE-19 behavior, and the chaos-determinism
    arm for plans that pin exact breaker strike sequences)."""

    retries: int = 1
    base_delay: float = 0.05
    jitter: float = 0.5  # + fraction of the backoff delay (never early)

    def backoff(self, context: str, attempt: int) -> float:
        """Delay before retry ``attempt`` (1-based) of one guarded
        dispatch at ``context`` — exponential with deterministic
        jitter (hash of (context, attempt), never random: the chaos
        replay contract)."""
        d = self.base_delay * (2.0 ** (attempt - 1))
        if not self.jitter:
            return d
        h = int.from_bytes(
            hashlib.sha256(f"{context}:{attempt}".encode()).digest()[:4],
            "big",
        )
        return d * (1.0 + self.jitter * (h / 0xFFFFFFFF))


#: process-wide policy consulted by ``_guarded_launch`` (daemon boot
#: overrides from ``[pipeline]``; tests pin retries=0 for strike-exact
#: chaos arms).
_DEFAULT_RETRY = RetryPolicy()


def configure_retry(policy: RetryPolicy | None) -> RetryPolicy:
    """Install the process-wide transient-retry policy (None restores
    the default)."""
    global _DEFAULT_RETRY
    _DEFAULT_RETRY = policy if policy is not None else RetryPolicy()
    return _DEFAULT_RETRY


def default_retry_policy() -> RetryPolicy:
    return _DEFAULT_RETRY


def note_retry(outcome: str) -> None:
    """Tally one retry verdict (``recovered`` | ``exhausted``)."""
    _RETRIES.labels(outcome=outcome).inc()
