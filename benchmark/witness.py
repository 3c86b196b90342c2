"""The benchmark's own witnesses of a sound run: its copies of
``chip_smoke.py``'s ``device_info``, ``SetupClock`` and
``FallbackWitness`` (sound on the v5e, PR 21), kept here so that no
later PR to the program can soften them."""

from __future__ import annotations


def device_info() -> dict:
    """The devices as JAX reports them."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


class SetupClock:
    """Seconds XLA spent compiling, or JAX spent fetching the program
    from the persistent cache instead, since construction; the number
    of such programs; and how many the cache served.  (Trace and lower
    events nest inside one another and would count twice.)"""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.programs += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class FallbackWitness:
    """Turns the dispatch breaker from a mask into a witness.

    The breaker serves a failed device dispatch from the scalar oracle
    with identical bits, so parity alone proves nothing about the
    chip.  Snapshots the fallback counter and the live breakers at
    construction; :meth:`check` says whether any dispatch since was
    served by the fallback or left a breaker unclean."""

    def __init__(self):
        from holo_tpu.resilience.breaker import breakers

        self._base = self._fallbacks()
        self._old = set(breakers())

    @staticmethod
    def _fallbacks() -> int:
        from holo_tpu import telemetry

        family = "holo_resilience_fallback_total"
        return int(sum(
            v for k, v in telemetry.snapshot(family).items()
            if k.split("{", 1)[0] == family
        ))

    def check(self) -> dict:
        from holo_tpu.resilience.breaker import breakers

        live = {
            n: b.snapshot() for n, b in breakers().items()
            if n not in self._old
        }
        bad = sorted(
            n for n, s in live.items()
            if s["state"] != "closed" or s["consecutive-failures"]
        )
        errors = {
            n: str(s["last-error"]) for n, s in live.items()
            if s["last-error"]
        }
        fell = self._fallbacks() - self._base
        return {
            "clean": fell == 0 and not bad,
            "fallbacks": fell,
            "breakers": len(live),
            "unclean": bad,
            "errors": errors,
        }
