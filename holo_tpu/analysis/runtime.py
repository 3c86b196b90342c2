"""holo-lint runtime sanitizer mode: ``jax.transfer_guard`` wiring.

Static analysis proves what it can see; this module catches the rest
at run time.  Under :func:`transfer_sanitizer` every *implicit*
device↔host transfer — ``np.asarray`` on a device array, a numpy
operand silently device_put by a jnp op, a traced value forced
concrete — raises instead of silently syncing.  The SPF/FRR parity
and e2e suites run under it (see ``holo_tpu.testing``), so any new
code that smuggles a transfer onto the dispatch path fails the tier-1
gate even when no HL1xx rule matches the pattern.

The counterpart is :func:`sanctioned_transfer`: the ONE place a
marshal/unmarshal transfer is supposed to happen (the backend's
dispatch boundary in ``spf/backend.py`` / ``frr/manager.py``) opens an
explicit ``allow`` window.  The same marker is what the static HL101
rule treats as exempt — one annotation serves both checks.

Relation to the native TSan job (tests/test_native_sanitizers.py):
TSan watches the C/C++ side for data races; the transfer guard watches
the Python/JAX side for hidden syncs; the HL2xx lock rules watch the
Python side for the lock-discipline classes neither sanitizer can see.

JAX is imported lazily: the lint gate itself must stay import-light.
"""

from __future__ import annotations

import contextlib
import os

# Observability for the sanctioned windows: how often the dispatch
# boundary opens tells a test whether marshal traffic is growing.
_SANCTIONED: dict[str, int] = {}


def transfer_sanitizer():
    """Context manager: disallow implicit device↔host transfers.

    Explicit transfers (``jax.device_put``) and sanctioned windows
    (:func:`sanctioned_transfer`) stay allowed.  Nesting follows JAX's
    innermost-wins semantics.
    """
    import jax

    return jax.transfer_guard("disallow")


@contextlib.contextmanager
def sanctioned_transfer(reason: str):
    """Open an explicit allow-window for a marshal/unmarshal boundary.

    ``reason`` names the boundary (it keys the per-boundary counter in
    :func:`sanctioned_counts`); the static HL101 rule exempts code
    inside ``with sanctioned_transfer(...):`` blocks, so the runtime
    window and the static exemption can never drift apart.
    """
    import jax

    _SANCTIONED[reason] = _SANCTIONED.get(reason, 0) + 1
    with jax.transfer_guard("allow"):
        yield


def sanctioned_counts() -> dict[str, int]:
    """How many times each sanctioned boundary opened (tests/debug)."""
    return dict(_SANCTIONED)


def sanitizer_enabled_by_env() -> bool:
    """Opt-in knob for ad-hoc runs: HOLO_TPU_TRANSFER_SANITIZER=1."""
    return os.environ.get("HOLO_TPU_TRANSFER_SANITIZER", "") not in (
        "",
        "0",
        "false",
    )


# -- donation guard (the runtime half of HL109) -------------------------
#
# ``jax.jit(..., donate_argnums=...)`` hands the argument's buffers to
# the kernel.  On a real TPU the input is CONSUMED: reading it after
# dispatch is undefined.  On the CPU platform the tests run on, XLA
# quietly ignores the donation, so a use-after-donate bug passes every
# CPU suite and detonates only on hardware.  The guard closes that gap:
# while armed (test mode), :func:`note_donated` — called by the dispatch
# seams right after a donating kernel call — actually ``delete()``s the
# donated ``jax.Array`` leaves, so ANY later read (a force, a readback,
# a re-dispatch, an ``np.asarray``) raises exactly as it would have
# failed on device.  Disarmed cost is one module-global check per seam.
#
# :func:`consumes_donated` is the shared exemption vocabulary with the
# static HL109 rule (the ``sanctioned_transfer`` ↔ HL101 pattern): the
# legitimate re-deposit seams — where a *fresh* output takes the donated
# name's place — open the window, the static rule exempts reads inside
# it, and the runtime guard counts the window per reason so tests can
# probe that the seam actually ran.

_DONATION_ARMED = False
_DONATED_COUNTS: dict[str, int] = {}
_CONSUME_COUNTS: dict[str, int] = {}


class DonatedBufferError(RuntimeError):
    """A donated device buffer was read after its dispatch consumed it."""


def _donated_leaves(value):
    """Flatten arbitrarily nested tuples/lists/NamedTuples down to the
    leaf objects a donating jit would have consumed."""
    if value is None:
        return []
    if isinstance(value, (tuple, list)):
        out = []
        for v in value:
            out.extend(_donated_leaves(v))
        return out
    return [value]


def note_donated(reason: str, *values) -> None:
    """Poison the donated operand(s) of a dispatch that just launched.

    Call AFTER the donating kernel call, with the exact objects whose
    buffers were donated.  Disarmed: one global check, nothing else.
    Armed: every ``jax.Array`` leaf is ``delete()``d — XLA's runtime
    keeps the underlying buffer alive until the in-flight execution
    completes, so this only invalidates the *Python handle*, which is
    precisely the donation contract the CPU platform fails to enforce.
    """
    if not _DONATION_ARMED:
        return
    _DONATED_COUNTS[reason] = _DONATED_COUNTS.get(reason, 0) + 1
    for leaf in _donated_leaves(tuple(values)):
        delete = getattr(leaf, "delete", None)
        if delete is None:
            continue
        try:
            if not getattr(leaf, "is_deleted", lambda: False)():
                delete()
        except Exception:  # pragma: no cover - platform quirk, not a gate
            pass


def assert_live(reason: str, *values) -> None:
    """The guard's force/readback assertion: raise
    :class:`DonatedBufferError` if any leaf of ``values`` is a poisoned
    (deleted) array handle.

    ``note_donated`` invalidates the Python handles; a buggy path that
    kept a donated alias would otherwise surface as XLA's generic
    "Array has been deleted" somewhere deep inside a readback.  The
    finish seams call this right before they force, so a leaked alias
    fails at the *boundary*, named, with the donation reason attached.
    Disarmed cost: one module-global check.
    """
    if not _DONATION_ARMED:
        return
    for leaf in _donated_leaves(tuple(values)):
        if getattr(leaf, "is_deleted", lambda: False)():
            raise DonatedBufferError(
                f"{reason}: value aliases a donated buffer — the "
                "dispatch that consumed it already owns these bytes "
                "(use-after-donate; see HL109)"
            )


@contextlib.contextmanager
def consumes_donated(reason: str):
    """Mark a legitimate re-deposit seam for a donated name.

    Static half: HL109 exempts reads inside a ``with
    consumes_donated(...):`` block, so the one place a donated name's
    *replacement* is legitimately handled does not need a suppression.
    Runtime half: the per-reason counter lets tests pin that the seam
    executed.  The window deliberately does NOT un-poison anything —
    the donated buffers stay dead; only fresh outputs may flow here.
    """
    _CONSUME_COUNTS[reason] = _CONSUME_COUNTS.get(reason, 0) + 1
    yield


@contextlib.contextmanager
def donation_guard():
    """Arm the donation guard for the enclosing block (test mode).

    Nested arming is refcount-free on purpose: the parity suites wrap
    whole tests, not overlapping regions.
    """
    global _DONATION_ARMED
    prev = _DONATION_ARMED
    _DONATION_ARMED = True
    try:
        yield
    finally:
        _DONATION_ARMED = prev


def donation_guard_armed() -> bool:
    return _DONATION_ARMED


def donated_counts() -> dict[str, int]:
    """Per-reason count of poisoned donations (tests/debug)."""
    return dict(_DONATED_COUNTS)


def consumed_counts() -> dict[str, int]:
    """Per-reason count of consumes_donated window entries."""
    return dict(_CONSUME_COUNTS)


def donation_guard_enabled_by_env() -> bool:
    """Opt-in knob for ad-hoc runs: HOLO_TPU_DONATION_GUARD=1."""
    return os.environ.get("HOLO_TPU_DONATION_GUARD", "") not in (
        "",
        "0",
        "false",
    )


# Ad-hoc opt-in: a process imported with HOLO_TPU_DONATION_GUARD=1 is
# armed from the start — scripts and whole pytest runs alike, no
# per-test wrapping needed.  donation_guard() still nests and restores
# around this base state.
if donation_guard_enabled_by_env():
    _DONATION_ARMED = True
