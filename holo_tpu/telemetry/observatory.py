"""Dispatch observatory: always-on roofline attribution, streaming
quantile sketches, and an online perf-regression sentinel (ISSUE 12).

The next kernel arc (tropical min-plus SPF, hierarchical partitioning —
ROADMAP items 1-2) is graded observationally: "cost_analysis() shows
the flops moving from gather bytes to contraction flops".  This module
is the always-on instrument every subsequent kernel PR reports through:

- **Streaming quantile sketches** — DDSketch-style relative-error
  buckets (:class:`DDSketch`): each value lands in the log-spaced
  bucket ``ceil(log_gamma(v))`` with ``gamma = (1+alpha)/(1-alpha)``,
  so any quantile estimate is within ``alpha`` relative error of the
  true sample quantile.  Sketches are **deterministic** (no sampling),
  **mergeable** (bucket-count addition — fleet aggregation composes),
  and **bounded** (``max_bins`` with lowest-bucket collapse).  One
  sketch per key ``(site, stage, engine, shape-bucket⊃mesh, kind)``,
  fed from the existing ``holo_profile_stage_seconds`` observe path
  (:func:`holo_tpu.telemetry.profiling.stage`) behind ``[telemetry]
  observatory``: the armed hot path pays one dict hit + int adds per
  sub-span, the disarmed path ONE module-global check, and — by design
  — **no new locks**: sketch updates ride the same GIL-atomic
  dict/int discipline as the registry's write stamp (racing observers
  may coalesce an increment; quantile estimates already carry the
  sketch's own ``alpha`` envelope, which dominates).

- **Roofline attribution** — :meth:`Observatory.roofline` joins the
  compile-time ``cost_analysis()`` FLOP / bytes-accessed estimates per
  fresh (engine, shape) jit bucket (the backends call
  :func:`note_cost` right where they feed ``EngineTuner.cost_prior``)
  with the measured ``device`` sub-span sketch into achieved FLOP/s,
  bytes/s, arithmetic intensity, and a memory-/compute-bound verdict
  per bucket.  The verdict is the classic ridge-point test — AI below
  ``peak_flops / peak_bytes`` ⇒ the kernel CANNOT be compute-bound on
  that machine — so it is deterministic (compile-time numerators,
  configured peaks), while the achieved-rate rows carry the measured
  p50.  Peaks come from ``[telemetry] roofline-peaks``, else from the
  attached device's kind (:func:`device_peaks`); a device with no
  published peaks gets verdict ``unknown`` and no roofline fraction.

- **Online regression sentinel** — every ``check_every`` observations
  of a key, its sketch p50/p99 are compared against a persisted
  runtime baseline: unseen keys are SEEDED from the current run, >10% drift
  (plus a small absolute floor) flags a regression — a warn-only
  flight-ring event (``observatory-regression``) plus
  ``holo_observatory_regressions_total{bucket,quantile}`` — and >5%
  improvements RATCHET the baseline down.  Never a breaker, never a
  fallback: the DeltaPath-style incremental paths make regressions
  easy to hide inside warm medians, and the sentinel's only job is to
  make them loud.

Surfaces: ``holo-tpu-tools explain`` (top-k cost centers + roofline
fractions + the tuner's win/loss ledger), the
``holo-telemetry/observatory`` gNMI leaf
(:mod:`holo_tpu.telemetry.provider`) and the Prometheus families
above; ``tests/test_observatory.py`` holds the contracts.

Determinism: :class:`DeterministicTimer` swaps the profiling stage
timer for a counter clock (each read advances a fixed quantum), so a
seeded workload produces **byte-identical** sketch serializations and
reports across runs — the classification/structure signal stays real
(cost-analysis numerators, bucket keys, verdicts); the walls become
read-counts and the report says so (``timing: deterministic``).
"""

from __future__ import annotations

import json
import logging
import math
import os
import threading
from dataclasses import dataclass
from pathlib import Path

from holo_tpu import telemetry
from holo_tpu.telemetry import flight, profiling

log = logging.getLogger("holo_tpu.telemetry")

#: sketch values at or below this are exact zeros (a stage wall of 0.0
#: only happens under a deterministic timer that was never advanced)
MIN_TRACKABLE = 1e-9

#: sentinel drift thresholds: >10% worse flags, >5% better ratchets,
#: plus an absolute floor.  The floor
#: is 5ms: below it live the async-launch overlap artifacts (a device
#: sub-span measures time-until-ready, so host work between launch and
#: sync makes small walls bimodal — 0.2ms vs 2.5ms on the same kernel)
#: and scheduler noise; the regressions the always-on sentinel exists
#: for — injected stalls, platform slowdowns, accidental recompile
#: storms — move quantiles on the scale of a dispatch wall by far more.
DRIFT_FLAG = 0.10
DRIFT_RATCHET = 0.05
DRIFT_FLOOR_S = 5e-3

_REGRESSIONS = telemetry.counter(
    "holo_observatory_regressions_total",
    "Sketch-bucket quantiles that drifted >10% past the persisted "
    "runtime baseline (warn-only; ledger-seeded keys never flag on "
    "their seeding run)",
    ("bucket", "quantile"),
)
# Population gauges update from the sentinel tick / stats() only —
# stamped=False so observatory bookkeeping can never wake the gNMI
# fan-out's skip-the-walk short-circuit (the delta.py discipline).
_SKETCHES = telemetry.gauge(
    "holo_observatory_sketches",
    "Live (site, stage, engine, shape-bucket, kind) sketch keys",
    stamped=False,
)
_OBSERVATIONS = telemetry.gauge(
    "holo_observatory_observations",
    "Total stage observations folded into the sketches",
    stamped=False,
)


class DDSketch:
    """Relative-error streaming quantile sketch (DDSketch-style).

    ``quantile(q)`` is within ``alpha`` relative error of the true
    sample quantile; memory is bounded by ``max_bins`` (lowest buckets
    collapse together — the tail quantiles the sentinel watches keep
    full accuracy); two sketches with the same ``alpha`` merge by
    bucket-count addition, associatively and commutatively; and the
    whole state serializes to a canonical JSON document
    (:meth:`serialize`) that is byte-identical for identical
    observation multisets.  No locks: see the module docstring.
    """

    __slots__ = (
        "alpha", "max_bins", "_gamma", "_log_gamma",
        "bins", "zero", "count", "total", "vmin", "vmax",
    )

    def __init__(self, alpha: float = 0.01, max_bins: int = 512):
        if not 0.0 < alpha < 1.0:
            raise ValueError(f"alpha must be in (0, 1), got {alpha}")
        self.alpha = float(alpha)
        self.max_bins = int(max_bins)
        self._gamma = (1.0 + self.alpha) / (1.0 - self.alpha)
        self._log_gamma = math.log(self._gamma)
        self.bins: dict[int, int] = {}
        self.zero = 0
        self.count = 0
        self.total = 0.0
        self.vmin = math.inf
        self.vmax = -math.inf

    def observe(self, value: float) -> None:
        v = float(value)
        if v < 0.0:
            v = 0.0  # durations; a clock step backwards clamps
        self.count += 1
        self.total += v
        if v < self.vmin:
            self.vmin = v
        if v > self.vmax:
            self.vmax = v
        if v <= MIN_TRACKABLE:
            self.zero += 1
            return
        i = math.ceil(math.log(v) / self._log_gamma)
        # Deliberately lock-free (ISSUE 12 contract: the dispatch hot
        # path gains no new locks): dict get/set on the GIL; a racing
        # observe may coalesce one count — inside the sketch's own
        # alpha error envelope, which dominates.
        self.bins[i] = self.bins.get(i, 0) + 1  # holo-lint: disable=HL204
        if len(self.bins) > self.max_bins:
            self._collapse()

    def _collapse(self) -> None:
        # Collapse the two LOWEST buckets together (tail accuracy is
        # what the p99 sentinel needs; the collapsed floor only ever
        # UNDER-reports how fast the fastest dispatches were).  Racing
        # collapses tolerate an already-popped bin (lock-free
        # contract): pop(lo, 0) + get(nxt, 0) never raise.
        idxs = sorted(self.bins)
        lo, nxt = idxs[0], idxs[1]
        self.bins[nxt] = self.bins.get(nxt, 0) + self.bins.pop(lo, 0)

    def _bucket_value(self, i: int) -> float:
        # Midpoint of bucket (gamma^(i-1), gamma^i]: within alpha
        # relative of every value the bucket holds.
        return 2.0 * self._gamma ** i / (self._gamma + 1.0)

    def quantile(self, q: float) -> float | None:
        """Estimate the ``q``-quantile (None on an empty sketch)."""
        if not self.count:
            return None
        rank = q * (self.count - 1)
        acc = self.zero
        if acc > rank:
            return 0.0
        # items() snapshot in one C call (GIL-atomic): a concurrent
        # observe/collapse can never fault the walk.
        for i, c in sorted(self.bins.items()):
            acc += c
            if acc > rank:
                return self._bucket_value(i)
        return float(self.vmax)

    def merge(self, other: "DDSketch") -> "DDSketch":
        """Fold ``other`` into self (same ``alpha`` required)."""
        if abs(other.alpha - self.alpha) > 1e-12:
            raise ValueError(
                f"cannot merge sketches with alpha {self.alpha} != "
                f"{other.alpha}"
            )
        for i, c in other.bins.items():
            self.bins[i] = self.bins.get(i, 0) + c
        while len(self.bins) > self.max_bins:
            self._collapse()
        self.zero += other.zero
        self.count += other.count
        self.total += other.total
        self.vmin = min(self.vmin, other.vmin)
        self.vmax = max(self.vmax, other.vmax)
        return self

    def to_doc(self) -> dict:
        """Canonical JSON-able state (sorted bins, rounded floats)."""
        return {
            "alpha": self.alpha,
            "zero": self.zero,
            "count": self.count,
            "sum": round(self.total, 9),
            "min": round(self.vmin, 9) if self.count else None,
            "max": round(self.vmax, 9) if self.count else None,
            "bins": [[i, self.bins[i]] for i in sorted(self.bins)],
        }

    @classmethod
    def from_doc(cls, doc: dict, max_bins: int = 512) -> "DDSketch":
        sk = cls(float(doc["alpha"]), max_bins)
        sk.zero = int(doc.get("zero", 0))
        sk.count = int(doc.get("count", 0))
        sk.total = float(doc.get("sum", 0.0))
        sk.vmin = float(doc["min"]) if doc.get("min") is not None else math.inf
        sk.vmax = (
            float(doc["max"]) if doc.get("max") is not None else -math.inf
        )
        sk.bins = {int(i): int(c) for i, c in doc.get("bins", [])}
        return sk

    def serialize(self) -> bytes:
        """Byte-identical canonical encoding of :meth:`to_doc`."""
        return json.dumps(
            self.to_doc(), sort_keys=True, separators=(",", ":")
        ).encode()


@dataclass(frozen=True)
class RooflinePeaks:
    """Peak specs of one device, which the roofline verdict tests
    against: ``[telemetry] roofline-peaks`` when configured, else
    :func:`device_peaks` for the attached device."""

    flops_per_sec: float
    bytes_per_sec: float
    source: str

    @property
    def ridge(self) -> float:
        """Arithmetic intensity (flops/byte) where the machine stops
        being bandwidth-limited: AI below this ⇒ memory-bound."""
        return self.flops_per_sec / self.bytes_per_sec

    @classmethod
    def from_config(cls, raw) -> "RooflinePeaks | None":
        """``[telemetry] roofline-peaks`` table / dict; None selects
        the attached device's peaks (None again if it has none)."""
        if raw is None:
            return device_peaks()
        if isinstance(raw, RooflinePeaks):
            return raw
        return cls(
            flops_per_sec=float(raw["flops"]),
            bytes_per_sec=float(raw["bytes"]),
            source=str(raw.get("name", "configured")),
        )


#: Published per-chip peaks by ``jax.devices()[0].device_kind``.  A
#: device that is not listed has NO peaks (never another device's).
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s (bf16),
    # 16 GB of HBM at 819 GB/s.
    "TPU v5 lite": RooflinePeaks(
        1.97e14, 8.19e11, "tpu-v5e (Google Cloud TPU v5e documentation)"
    ),
}
#: A commodity-CPU guess (~50 GFLOP/s SIMD, ~10 GB/s DRAM stream), for
#: platform ``cpu`` only: what the test suite's rooflines read against.
CPU_GUESS = RooflinePeaks(5.0e10, 1.0e10, "cpu-default")


def device_peaks() -> RooflinePeaks | None:
    """Peaks of the attached device, or None when it has no entry."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return CPU_GUESS
    return DEVICE_PEAKS.get(dev.device_kind)


def key_str(key: tuple) -> str:
    """Canonical string form of a sketch key — the ledger key, the
    metric ``bucket`` label, and the report row id.  Square brackets
    are rendered as parens: the string rides gNMI list-key path
    segments (``metric[<name>{bucket=...}]``), whose grammar reserves
    ``[``/``]``."""
    site, stage, engine, bucket, kind = key
    b = (
        "-"
        if bucket in (None, "-")
        else json.dumps(list(bucket), separators=(",", ":"), default=str)
        .replace("[", "(")
        .replace("]", ")")
    )
    return f"{site}/{stage}|{engine}|{kind}|{b}"


class DeterministicTimer:
    """Counter clock for byte-identical observatory runs: every read
    advances ``quantum``, so stage walls count timer reads instead of
    wall time.  Install via ``profiling.set_stage_timer``; a seeded
    workload then produces identical sketches on every run."""

    def __init__(self, quantum: float = 1e-4):
        self.t = 0.0
        self.quantum = float(quantum)

    def __call__(self) -> float:
        self.t += self.quantum
        return self.t


class Observatory:
    """One process-wide instrument (module singleton via
    :func:`configure`).  Hot path = :meth:`_observe`, installed as the
    profiling stage observer; everything else is cold reporting."""

    def __init__(
        self,
        alpha: float = 0.01,
        max_bins: int = 512,
        check_every: int = 32,
        ledger_path: str | Path | None = None,
        peaks: RooflinePeaks | dict | None = None,
    ):
        self.alpha = float(alpha)
        self.max_bins = int(max_bins)
        self.check_every = int(check_every)
        self.peaks = RooflinePeaks.from_config(peaks)
        self.ledger_path = Path(ledger_path) if ledger_path else None
        self._sketches: dict[tuple, DDSketch] = {}
        self._costs: dict[tuple, dict] = {}
        # Sentinel state: the persisted quantile baseline plus the
        # per-(key, quantile) regressed latch (events fire on the
        # TRANSITION into regressed, not on every re-check).
        self._ledger: dict[str, dict] = {}
        self._regressed: dict[tuple, bool] = {}
        self._seeded = 0
        self._ratcheted = 0
        self._flags = 0
        self._n_obs = 0
        self._dirty = False
        if self.ledger_path is not None:
            self.load_ledger()

    # -- hot path (no locks; see module docstring) ----------------------

    def _observe(self, site: str, stage: str, device: str, seconds: float):
        """Profiling stage observer.  ``device != "-"`` rows are the
        per-device skew split of one already-observed sharded span —
        folding them in would double-count the dispatch."""
        if device != "-":
            return
        ctx = profiling.dispatch_ctx()
        if ctx is None:
            engine = kind = "-"
            bucket = "-"
        else:
            engine = ctx.get("engine", "-")
            kind = ctx.get("kind", "-")
            bucket = ctx.get("bucket") or "-"
        key = (site, stage, engine, bucket, kind)
        sk = self._sketches.get(key)
        if sk is None:
            # Lock-free by contract (see module docstring): setdefault
            # is atomic under the GIL, so two racing first-observers
            # both get the one surviving sketch.
            sk = self._sketches.setdefault(  # holo-lint: disable=HL204
                key, DDSketch(self.alpha, self.max_bins)
            )
        sk.observe(seconds)
        self._n_obs += 1
        if self.check_every and sk.count % self.check_every == 0:
            self._sentinel_check(key, sk)

    # -- cost join (called by the backends next to cost_prior) ----------

    def note_cost(
        self, site: str, kind: str, engine: str, bucket, entry: dict | None
    ) -> None:
        """Attach a compile-time ``cost_analysis()`` estimate for one
        (site, engine, shape-bucket, kind) — the roofline numerator."""
        if not entry:
            return
        # Lock-free single-key write (cold path — once per fresh XLA
        # compile); readers iterate a point-in-time view via list().
        self._costs[  # holo-lint: disable=HL204
            (site, str(engine), bucket or "-", str(kind))
        ] = {
            "flops": float(entry.get("flops", 0.0)),
            "bytes": float(entry.get("bytes", 0.0)),
        }

    # -- regression sentinel --------------------------------------------

    def _sentinel_check(self, key: tuple, sk: DDSketch) -> None:
        p50 = sk.quantile(0.5)
        p99 = sk.quantile(0.99)
        if p50 is None:
            return
        ks = key_str(key)
        ent = self._ledger.get(ks)
        if ent is None:
            self._ledger[ks] = {
                "p50": round(p50, 9), "p99": round(p99, 9)
            }
            self._seeded += 1
            self._dirty = True
            self._update_gauges()
            return
        dirty = False
        for qname, measured in (("p50", p50), ("p99", p99)):
            base = ent.get(qname)
            if base is None:
                ent[qname] = round(measured, 9)
                dirty = True
                continue
            floor = max(base * DRIFT_FLAG, DRIFT_FLOOR_S)
            regressed = measured > base + floor
            latch = (ks, qname)
            was = self._regressed.get(latch, False)
            if regressed and not was:
                # Lock-free latch write (sentinel tick, 1/check_every
                # observes): GIL-atomic bool flip; a racing reader of
                # sentinel() sees before-or-after, both valid.
                self._regressed[latch] = True  # holo-lint: disable=HL204
                self._flags += 1
                _REGRESSIONS.labels(bucket=ks, quantile=qname).inc()
                flight.event(
                    "observatory-regression",
                    bucket=ks,
                    quantile=qname,
                    baseline=round(base, 6),
                    measured=round(measured, 6),
                )
                log.warning(
                    "observatory: %s %s regressed %.3fms -> %.3fms "
                    "(baseline +%d%%) — warn-only, dispatch unaffected",
                    ks, qname, base * 1e3, measured * 1e3,
                    int(DRIFT_FLAG * 100),
                )
            elif not regressed:
                if was:
                    self._regressed[latch] = False
                if measured < base - max(
                    base * DRIFT_RATCHET, DRIFT_FLOOR_S
                ):
                    ent[qname] = round(measured, 9)
                    self._ratcheted += 1
                    dirty = True
        if dirty:
            self._dirty = True
        self._update_gauges()

    def checkpoint(self) -> dict:
        """Force one sentinel pass over every populated sketch — seed
        and compare NOW instead of at each key's next ``check_every``
        boundary.  The explain CLI and the sentinel tests bracket
        their clean/regressed phases with it (a key whose count never
        crosses the modulo must still get a pre-regression baseline),
        and the daemon's
        stop path closes its final window the same way.  Returns
        :meth:`sentinel`."""
        for key, sk in list(self._sketches.items()):
            if sk.count:
                self._sentinel_check(key, sk)
        if self._dirty and self.ledger_path is not None:
            self.save_ledger()
        return self.sentinel()

    def _update_gauges(self) -> None:
        _SKETCHES.set(len(self._sketches))
        _OBSERVATIONS.set(self._n_obs)

    def load_ledger(self, path: str | Path | None = None) -> bool:
        """Load the persisted quantile baseline; a corrupt file is
        discarded (the sentinel just re-seeds — ledger discipline)."""
        p = Path(path) if path is not None else self.ledger_path
        if p is None or not p.exists():
            return False
        try:
            doc = json.loads(p.read_text())
        except (OSError, ValueError) as e:
            log.warning("observatory ledger load from %s failed: %s", p, e)
            return False
        if not isinstance(doc, dict):
            return False
        self._ledger = {
            str(k): dict(v) for k, v in doc.items() if isinstance(v, dict)
        }
        return True

    def save_ledger(self, path: str | Path | None = None) -> bool:
        """Atomic (tmp + rename) write of the baseline; never raises —
        a full disk must not take a dispatch down."""
        p = Path(path) if path is not None else self.ledger_path
        if p is None:
            return False
        try:
            doc = json.dumps(self._ledger, sort_keys=True, indent=1)
            tmp = p.with_suffix(p.suffix + ".tmp")
            tmp.write_text(doc + "\n")
            os.replace(tmp, p)
            self._dirty = False
            return True
        except OSError as e:
            log.warning("observatory ledger save to %s failed: %s", p, e)
            return False

    # Seeds/ratchets only MARK the ledger dirty — the actual JSON
    # write happens at checkpoint boundaries (explain brackets,
    # daemon stop, explicit save_ledger), never as a synchronous disk
    # write on the dispatch thread that happened to seed a new key.

    # -- reporting (cold path) ------------------------------------------

    def quantiles(self, key: tuple) -> dict | None:
        sk = self._sketches.get(key)
        if sk is None or not sk.count:
            return None
        return {
            "count": sk.count,
            "total_s": round(sk.total, 9),
            "p50_s": round(sk.quantile(0.5), 9),
            "p99_s": round(sk.quantile(0.99), 9),
        }

    def site_p99(self, site: str) -> float | None:
        """Worst p99 seconds across every (stage, engine, shape-bucket,
        kind) sketch at ``site`` — the hung-dispatch watchdog's learned
        budget base (conservative by construction: a hang is declared
        only well past the slowest bucket's observed tail).  None while
        the site is cold."""
        worst = None
        # list() = one GIL-atomic snapshot (the cost_centers idiom).
        for key, sk in list(self._sketches.items()):
            if key[0] != site or not sk.count:
                continue
            q = sk.quantile(0.99)
            if worst is None or q > worst:
                worst = q
        return worst

    def cost_centers(self, top: int | None = None) -> list[dict]:
        """Sketch keys ranked by total attributed seconds — where the
        dispatch time actually went, with sketch-derived quantiles."""
        rows = []
        # list() = one GIL-atomic snapshot: dispatch threads keep
        # inserting sketch keys while a scrape renders.
        for key, sk in list(self._sketches.items()):
            if not sk.count:
                continue
            site, stage, engine, bucket, kind = key
            rows.append(
                {
                    "key": key_str(key),
                    "site": site,
                    "stage": stage,
                    "engine": engine,
                    "kind": kind,
                    "bucket": (
                        list(bucket) if isinstance(bucket, tuple) else bucket
                    ),
                    "count": sk.count,
                    "total_s": round(sk.total, 9),
                    "p50_s": round(sk.quantile(0.5), 9),
                    "p99_s": round(sk.quantile(0.99), 9),
                }
            )
        rows.sort(key=lambda r: (-r["total_s"], r["key"]))
        return rows[:top] if top else rows

    @property
    def peaks_source(self) -> str:
        return self.peaks.source if self.peaks else "unknown-device"

    def roofline(self) -> list[dict]:
        """Per (site, engine, shape-bucket, kind): the cost-model join.

        Verdict = ridge-point test on the kernel's arithmetic intensity
        (deterministic); achieved rates divide the compile-time
        numerators by the measured device-stage sketch p50."""
        rows = []
        for (site, engine, bucket, kind), cost in list(self._costs.items()):
            flops, nbytes = cost["flops"], cost["bytes"]
            ai = flops / nbytes if nbytes else math.inf
            if self.peaks is None:
                verdict = "unknown"
            else:
                verdict = (
                    "memory-bound"
                    if ai < self.peaks.ridge
                    else "compute-bound"
                )
            row = {
                "site": site,
                "engine": engine,
                "kind": kind,
                "bucket": (
                    list(bucket) if isinstance(bucket, tuple) else bucket
                ),
                "flops": flops,
                "bytes": nbytes,
                "ai_flops_per_byte": (
                    round(ai, 6) if math.isfinite(ai) else None
                ),
                "verdict": verdict,
                "peaks": self.peaks_source,
            }
            q = self.quantiles((site, "device", engine, bucket, kind))
            if q is not None and q["p50_s"] > 0:
                p50 = q["p50_s"]
                achieved_flops = flops / p50
                achieved_bytes = nbytes / p50
                row.update(
                    device_p50_s=p50,
                    device_p99_s=q["p99_s"],
                    dispatches=q["count"],
                    achieved_flops_per_sec=round(achieved_flops, 3),
                    achieved_bytes_per_sec=round(achieved_bytes, 3),
                )
                if self.peaks is not None:
                    # The bucket's attainable ceiling: bandwidth-capped
                    # below the ridge, compute-capped above it.
                    attainable = min(
                        self.peaks.flops_per_sec,
                        ai * self.peaks.bytes_per_sec,
                    )
                    row["roofline_fraction"] = (
                        round(achieved_flops / attainable, 9)
                        if attainable
                        else None
                    )
            rows.append(row)
        rows.sort(
            key=lambda r: (r["site"], str(r["bucket"]), r["engine"], r["kind"])
        )
        return rows

    def sentinel(self) -> dict:
        regressed = sorted(
            f"{ks}:{q}"
            for (ks, q), on in list(self._regressed.items())
            if on
        )
        return {
            "ledger-entries": len(self._ledger),
            "seeded": self._seeded,
            "ratcheted": self._ratcheted,
            "flags": self._flags,
            "regressed": regressed,
            "path": str(self.ledger_path) if self.ledger_path else None,
        }

    def report(self, top: int | None = None) -> dict:
        """The full explain document (canonical field order)."""
        pk = self.peaks
        return {
            "timing": (
                "deterministic"
                if profiling.stage_timer_overridden()
                else "wall"
            ),
            "peaks": {
                "flops_per_sec": pk and pk.flops_per_sec,
                "bytes_per_sec": pk and pk.bytes_per_sec,
                "ridge_flops_per_byte": pk and round(pk.ridge, 6),
                "source": self.peaks_source,
            },
            "cost_centers": self.cost_centers(top),
            "roofline": self.roofline(),
            "sentinel": self.sentinel(),
        }

    def serialize(self) -> bytes:
        """Canonical byte encoding of every sketch — the byte-identity
        surface (two same-seed deterministic runs compare equal)."""
        doc = {
            key_str(k): sk.to_doc()
            for k, sk in list(self._sketches.items())
            if sk.count
        }
        return json.dumps(
            doc, sort_keys=True, separators=(",", ":")
        ).encode()

    def stats(self) -> dict:
        """holo-telemetry/observatory gNMI leaf."""
        self._update_gauges()
        return {
            "sketches": len(self._sketches),
            "observations": self._n_obs,
            "cost-buckets": len(self._costs),
            "alpha": self.alpha,
            "check-every": self.check_every,
            "peaks-source": self.peaks_source,
            "sentinel": self.sentinel(),
        }


# -- process-wide singleton ---------------------------------------------

_ACTIVE: Observatory | None = None
_CONFIG_LOCK = threading.Lock()


def configure(
    enabled: bool = True,
    *,
    alpha: float = 0.01,
    max_bins: int = 512,
    check_every: int = 32,
    ledger_path: str | Path | None = None,
    peaks: RooflinePeaks | dict | None = None,
) -> Observatory | None:
    """Arm (install the profiling stage observer) or disarm the
    process-wide observatory.  The daemon calls this at boot from
    ``[telemetry] observatory`` / ``observatory-ledger`` /
    ``roofline-peaks``; the explain CLI and tests flip it
    directly.  Disarming restores the one-global-check stage path."""
    global _ACTIVE
    with _CONFIG_LOCK:
        if not enabled:
            _ACTIVE = None
            profiling.set_observer(None)
            return None
        obs = Observatory(
            alpha=alpha,
            max_bins=max_bins,
            check_every=check_every,
            ledger_path=ledger_path,
            peaks=peaks,
        )
        _ACTIVE = obs
        profiling.set_observer(obs._observe)
        return obs


def active() -> Observatory | None:
    return _ACTIVE


def note_cost(
    site: str, kind: str, engine: str, bucket, entry: dict | None
) -> None:
    """Backend seam: forward a fresh-compile cost entry when armed."""
    obs = _ACTIVE
    if obs is not None:
        obs.note_cost(site, kind, engine, bucket, entry)
