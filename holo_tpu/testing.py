"""Deterministic test/dry-run environment helpers.

Mirrors the reference's `testing`/`deterministic` feature discipline
(holo-ospf/Cargo.toml:49-52): one place that knows how to force the
virtual multi-device CPU platform regardless of the host's default
(a machine with a chip defaults to the TPU).
"""

from __future__ import annotations

import contextlib
import os


@contextlib.contextmanager
def no_implicit_transfers():
    """Run the enclosed block under the holo-lint runtime sanitizer:
    ``jax.transfer_guard("disallow")``.

    The SPF/FRR parity and e2e suites wrap every test in this: any
    device↔host transfer OUTSIDE the sanctioned marshal/unmarshal
    boundaries (``sanctioned_transfer(...)`` in ``spf/backend.py`` /
    ``frr/manager.py`` / ``ops/cspf.py``) raises, catching hidden
    syncs that static analysis (HL101) cannot prove.  Explicit
    ``jax.device_put`` stays allowed — that is what "explicit" means.
    """
    from holo_tpu.analysis.runtime import transfer_sanitizer

    with transfer_sanitizer():
        yield


@contextlib.contextmanager
def donation_guarded():
    """Run the enclosed block under the holo-lint DONATION guard.

    The runtime half of HL109: inside this block every donating
    dispatch seam (``note_donated`` in ``spf/backend.py`` /
    ``ops/spf_engine.py``) actually ``delete()``s the donated buffers,
    so a use-after-donate bug that the CPU platform would silently
    forgive raises at force/readback time exactly as it would fail on
    real hardware.  Parity suites compose it with
    :func:`no_implicit_transfers`.
    """
    from holo_tpu.analysis.runtime import donation_guard

    with donation_guard():
        yield


def force_virtual_cpu_mesh(n_devices: int) -> None:
    """Force an n-device virtual CPU platform before backend init.

    Must run before any JAX backend initializes (jax.devices(), any
    device_put/jit execution).  Safe to call multiple times.  Raises if the
    platform was already initialized differently or the count can't be met.
    """
    import jax

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    jax.config.update("jax_platforms", "cpu")
    have = len(jax.devices())
    if have < n_devices:
        raise RuntimeError(
            f"need {n_devices} devices, have {have} ({jax.devices()}); "
            "XLA_FLAGS with a conflicting xla_force_host_platform_device_count "
            "was probably set before startup"
        )
