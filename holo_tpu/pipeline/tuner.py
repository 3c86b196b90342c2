"""Per-shape engine auto-tuner (ISSUE 9 tentpole, part b).

On JAX-CPU the winning gather-path fixpoint engine flipped with
topology size, and on the chip no engine but ``seq`` has been measured
(ROADMAP S3) — yet the engine has been a static config knob
(``TpuSpfBackend(one_engine=...)``).
This module turns it into a measured decision per **shape bucket**:

    bucket = (pow2(V), pow2(E), pow2(batch), mesh identity)

For each (kind, bucket) the tuner runs a deterministic explore/exploit
schedule over the parity-identical engine set (every engine computes
the bit-exact same SPF, so flipping engines can never change routing
state — only latency):

- **explore** — until every candidate engine has ``explore_rounds``
  measured dispatches, pick engines round-robin, ordered by the
  compile-time ``cost_analysis()`` prior when one was captured
  (cheapest estimated bytes first — the profile-guided search-space
  cut of Bounded Dijkstra, arXiv:1903.00436, applied to engine
  selection);
- **exploit** — pick the engine with the lowest measured median wall;
  every ``reprobe_every`` dispatches one non-winner is re-measured
  (round-robin) so a drifting platform can flip the winner back.

Decisions, promotions (winner changes), and the exploration phase are
all counted in the ``holo_pipeline_tuner_*`` metric family.

The same per-bucket table also carries the DeltaPath depth knob
(ROADMAP item 1 follow-up): the backend feeds measured ``delta``-stage
vs full-rebuild walls per bucket, and
:meth:`EngineTuner.max_delta_depth` derives the chain-depth cap from
their ratio — a bucket whose in-place delta is 40x cheaper than a
re-marshal can afford a much longer chain than one where the delta
barely wins (`holo_tpu.ops.spf_engine.DeviceGraphCache` consults this
through :func:`active_tuner`).

Persistence: the whole table round-trips through a **versioned** JSON
file (``[pipeline] tuner-cache`` in holod.toml) written atomically
(tmp + rename), so a restarted daemon starts in the exploit phase with
the learned winners instead of re-learning them ("restarts don't
re-learn"); a version bump discards stale tables wholesale.

Everything here is import-light (telemetry + stdlib) and O(1) per
decision: the hot path pays two dict hits and a deque median over at
most ``SAMPLE_WINDOW`` floats.
"""

from __future__ import annotations

import json
import logging
import os
import threading
from collections import deque
from pathlib import Path

from holo_tpu import telemetry

log = logging.getLogger("holo_tpu.pipeline.tuner")

#: persisted-table format version: bump to invalidate old tables
#: (v2: shape buckets grew the multipath parent-set width element;
#: v3: the tropical min-plus engine joined the candidate sets, ISSUE 13)
TABLE_VERSION = 3

#: k=1 fixpoint engines (all bit-identical; see ops/spf_engine +
#: ops/tropical — the tropical entry is the blocked min-plus program)
ENGINES = ("seq", "fused", "packed", "hybrid", "tropical")

#: k>1 multipath formulations: the packed row-gather kernel ("mp") and
#: its tropical DAG-tile-contraction variant.  A/B'd per shape bucket
#: for kind=one only — the widened tropical program scatters per-run
#: DAG tiles, which a big what-if batch would multiply by B.
MP_ENGINES = ("mp", "mp_tropical")

#: measured samples retained per (kind, bucket, engine) — medians over
#: a short window track platform drift without unbounded memory
SAMPLE_WINDOW = 9

#: DeltaPath depth-cap derivation bounds (satellite: auto-tuned
#: max_delta_depth).  depth = clamp(round(full/delta) * DEPTH_SCALE).
DEPTH_SCALE = 32
DEPTH_MIN = 32
DEPTH_MAX = 4096
#: samples of each arm required before the cap leaves the default
DEPTH_MIN_SAMPLES = 3

_DECISIONS = telemetry.counter(
    "holo_pipeline_tuner_decisions_total",
    "Engine-tuner picks by schedule phase",
    ("kind", "engine", "phase"),
)
_PROMOTIONS = telemetry.counter(
    "holo_pipeline_tuner_promotions_total",
    "Shape buckets whose measured winner changed",
    ("kind",),
)
_BUCKETS = telemetry.gauge(
    "holo_pipeline_tuner_buckets",
    "Shape buckets the tuner currently tracks",
)


def _pow2(n: int) -> int:
    """Smallest power of two >= n (bucket quantization; >= 1)."""
    out = 1
    n = max(int(n), 1)
    while out < n:
        out *= 2
    return out


def shape_bucket(
    n_vertices: int, n_edges: int, batch: int = 1, mesh=None, k: int = 1
) -> tuple:
    """The tuner's shape key: pow2-quantized (V, E, batch) + the mesh
    identity (the same shapes under a different sharding are a
    different XLA program — see ``TpuSpfBackend._track_compile``) + the
    multipath parent-set width ``k`` (ISSUE 10: the widened kernel is a
    different program with different walls — k=8 samples must never
    outvote the k=1 engine medians, and the DeltaPath depth ratio of a
    multipath chain is its own measurement)."""
    return (_pow2(n_vertices), _pow2(n_edges), _pow2(batch), mesh, int(k))


def bgp_shape_bucket(n_prefixes: int, n_peers: int) -> tuple:
    """Observatory/tuner bucket for the device BGP table (ISSUE 16):
    pow2-quantized (prefixes, peers), tagged with a leading ``"bgp"``
    discriminant so a BGP fold wall can never land in — or outvote —
    an SPF bucket (SPF keys are 5-tuples of ints/mesh; this is a
    3-tuple led by a string, disjoint by construction)."""
    return ("bgp", _pow2(max(1, n_prefixes)), _pow2(max(1, n_peers)))


def _median(vals) -> float | None:
    """Lower median: with an even sample count, prefer the smaller
    middle value — stray one-off spikes (GC, scheduler) must not
    outvote a warm measurement in a 2-sample window."""
    if not vals:
        return None
    s = sorted(vals)
    return float(s[(len(s) - 1) // 2])


class _BucketState:
    """Per-(kind, bucket) tuner state (mutated under the tuner lock)."""

    __slots__ = ("dispatches", "samples", "cost", "winner", "explored")

    def __init__(self):
        self.dispatches = 0
        # engine -> deque of measured wall seconds (most recent last)
        self.samples: dict[str, deque] = {}
        # engine -> {"flops": f, "bytes": b} compile-time prior
        self.cost: dict[str, dict] = {}
        self.winner: str | None = None
        self.explored = 0  # decisions spent in the explore phase


class EngineTuner:
    """Measured per-shape engine selection + DeltaPath depth tuning.

    Thread-shared (instance threads dispatch concurrently under
    ``[runtime] isolation=threaded``; the pipeline worker observes from
    its own thread): all state mutates under one lock, decisions are
    O(1), and nothing here ever touches a device value.
    """

    def __init__(
        self,
        path: str | Path | None = None,
        engines: tuple[str, ...] = ENGINES,
        mp_engines: tuple[str, ...] = MP_ENGINES,
        explore_rounds: int = 2,
        reprobe_every: int = 64,
        default_engine: str = "seq",
        default_delta_depth: int = 256,
    ):
        self.engines = tuple(engines)
        self.mp_engines = tuple(mp_engines)
        self.explore_rounds = int(explore_rounds)
        self.reprobe_every = int(reprobe_every)
        self.default_engine = default_engine
        self.default_delta_depth = int(default_delta_depth)
        self.path = Path(path) if path is not None else None
        self._lock = threading.Lock()
        self._table: dict[tuple, _BucketState] = {}
        # (bucket) -> {"delta": deque, "full": deque} stage walls
        self._depth: dict[tuple, dict[str, deque]] = {}
        self._promotions = 0
        self._loaded = False
        if self.path is not None:
            self.load()

    # -- keys ----------------------------------------------------------

    @staticmethod
    def _key(kind: str, bucket: tuple) -> tuple:
        return (str(kind), *bucket)

    def _state(self, key: tuple) -> _BucketState:
        st = self._table.get(key)
        if st is None:
            st = self._table[key] = _BucketState()
            _BUCKETS.set(len(self._table))
        return st

    # -- engine selection ----------------------------------------------

    def _candidates(self, kind: str, bucket: tuple) -> tuple[str, ...]:
        """The engine set this (kind, bucket) chooses among: the k=1
        gather+tropical family, or — for k>1 single-SPF dispatches —
        the multipath pair (``mp`` vs ``mp_tropical``).  k>1 what-if
        batches stay on ``mp`` (see MP_ENGINES)."""
        k = bucket[4] if len(bucket) > 4 and isinstance(bucket[4], int) else 1
        if k > 1:
            return self.mp_engines if kind == "one" else ("mp",)
        return self.engines

    def pick(self, kind: str, bucket: tuple) -> str:
        """The engine this dispatch should run.  Deterministic: the
        schedule depends only on the bucket's dispatch counter and the
        recorded samples, never on an RNG — two daemons replaying the
        same dispatch sequence make identical choices."""
        key = self._key(kind, bucket)
        cands = self._candidates(kind, bucket)
        with self._lock:
            st = self._state(key)
            st.dispatches += 1
            # Explore until every candidate has explore_rounds samples.
            needy = [
                e
                for e in self._explore_order(st, cands)
                if len(st.samples.get(e, ())) < self.explore_rounds
            ]
            if needy:
                engine = needy[st.explored % len(needy)]
                st.explored += 1
                phase = "explore"
            else:
                winner = self._winner_locked(st, cands)
                if (
                    self.reprobe_every
                    and st.dispatches % self.reprobe_every == 0
                    and len(cands) > 1
                ):
                    # Deterministic round-robin over the non-winners.
                    others = [e for e in cands if e != winner]
                    engine = others[
                        (st.dispatches // self.reprobe_every) % len(others)
                    ]
                    phase = "reprobe"
                else:
                    engine = winner
                    phase = "exploit"
        _DECISIONS.labels(kind=kind, engine=engine, phase=phase).inc()
        return engine

    def _explore_order(
        self, st: _BucketState, cands: tuple[str, ...] | None = None
    ) -> tuple[str, ...]:
        """Candidate order for exploration: engines with a compile-time
        cost prior first, cheapest estimated bytes-accessed leading —
        the likely winner gets measured earliest, so even a truncated
        explore phase tends to have sampled it."""
        if cands is None:
            cands = self.engines
        if not st.cost:
            return cands
        return tuple(
            sorted(
                cands,
                key=lambda e: st.cost.get(e, {}).get("bytes", float("inf")),
            )
        )

    def _winner_locked(
        self, st: _BucketState, cands: tuple[str, ...] | None = None
    ) -> str:
        if cands is None:
            # Measured engines outside the k=1 set (the mp family) must
            # still be able to win their own buckets.
            cands = tuple(
                dict.fromkeys(self.engines + tuple(sorted(st.samples)))
            )
        best, best_med = None, None
        for e in cands:
            med = _median(st.samples.get(e))
            if med is not None and (best_med is None or med < best_med):
                best, best_med = e, med
        if best is not None:
            return best
        return self.default_engine if self.default_engine in cands else cands[0]

    def current_winner(self, kind: str, bucket: tuple) -> str | None:
        """Read-only peek at a bucket's measured winner (no schedule
        advance, no metrics): the backend routes engine-fixed kernels —
        the DeltaPath incremental dispatch — through the tropical tiles
        when this bucket's full-dispatch winner is tropical.  None when
        the bucket has never been measured."""
        key = self._key(kind, bucket)
        with self._lock:
            st = self._table.get(key)
            if st is None or not st.samples:
                return None
            return self._winner_locked(st, self._candidates(kind, bucket))

    def observe(
        self, kind: str, bucket: tuple, engine: str, seconds: float
    ) -> None:
        """Record one measured dispatch wall for (bucket, engine); a
        winner change is a promotion (counted, and the table is
        persisted so the restart picks it cold)."""
        key = self._key(kind, bucket)
        promoted = False
        with self._lock:
            st = self._state(key)
            dq = st.samples.get(engine)
            if dq is None:
                dq = st.samples[engine] = deque(maxlen=SAMPLE_WINDOW)
            dq.append(float(seconds))
            new_winner = self._winner_locked(st)
            if new_winner != st.winner:
                promoted = st.winner is not None
                st.winner = new_winner
                if promoted:
                    self._promotions += 1
        if promoted:
            _PROMOTIONS.labels(kind=kind).inc()
            self.save()

    def cost_prior(
        self, kind: str, bucket: tuple, engine: str, entry: dict | None
    ) -> None:
        """Attach a compile-time ``cost_analysis()`` estimate (the
        backends call this right after a fresh jit compile — see
        ``profiling.record_cost``).  None is a no-op (platforms without
        cost analysis)."""
        if not entry:
            return
        key = self._key(kind, bucket)
        with self._lock:
            self._state(key).cost[engine] = {
                "flops": float(entry.get("flops", 0.0)),
                "bytes": float(entry.get("bytes", 0.0)),
            }

    # -- partitioned-SPF arbitration (ISSUE 15) ------------------------

    def observe_partitioned(self, bucket: tuple, seconds: float) -> None:
        """One measured partitioned-SPF dispatch wall for this shape
        bucket.  Partitioned rows live under their own kind (they are a
        different PROGRAM STRUCTURE, not another parity-identical
        engine), so the kind=one explore/exploit schedule can never
        pick 'partitioned' for a monolithic dispatch — the threshold
        contract in ``TpuSpfBackend`` stays the routing authority and
        the table carries the measured evidence."""
        self.observe("partitioned", bucket, "partitioned", seconds)

    def partitioned_advantage(self, bucket: tuple) -> float | None:
        """median(monolithic winner wall) / median(partitioned wall)
        for one shape bucket — >1 means the partitioned path is
        measured faster at this shape.  None until both arms have
        samples (operators read this; the backend's
        ``partition_threshold`` is deliberately not auto-flipped by
        it)."""
        with self._lock:
            st_p = self._table.get(self._key("partitioned", bucket))
            p_med = (
                _median(st_p.samples.get("partitioned", ()))
                if st_p is not None
                else None
            )
            st_o = self._table.get(self._key("one", bucket))
            o_med = None
            if st_o is not None:
                w = self._winner_locked(st_o)
                if w is not None:
                    o_med = _median(st_o.samples.get(w, ()))
        if not p_med or not o_med:
            return None
        return o_med / p_med

    # -- DeltaPath depth tuning ----------------------------------------

    def observe_delta(self, bucket: tuple, seconds: float) -> None:
        """One measured incremental (delta-path) dispatch wall."""
        self._observe_depth(bucket, "delta", seconds)

    def observe_full(self, bucket: tuple, seconds: float) -> None:
        """One measured full-rebuild (re-marshal) dispatch wall."""
        self._observe_depth(bucket, "full", seconds)

    def _observe_depth(self, bucket: tuple, arm: str, seconds: float) -> None:
        with self._lock:
            d = self._depth.setdefault(
                tuple(bucket),
                {
                    "delta": deque(maxlen=SAMPLE_WINDOW),
                    "full": deque(maxlen=SAMPLE_WINDOW),
                },
            )
            d[arm].append(float(seconds))

    def max_delta_depth(self, bucket: tuple, default: int | None = None) -> int:
        """The chain-depth cap for this shape bucket: proportional to
        how much cheaper the measured delta path is than a full
        rebuild (clamped to [DEPTH_MIN, DEPTH_MAX]).  Until both arms
        have DEPTH_MIN_SAMPLES per-bucket measurements, fall back to
        the process-wide ``holo_profile_stage_seconds`` medians of the
        ``delta`` vs ``marshal`` stages (the PR 7 profiling data that
        motivated this satellite) when device profiling is armed, and
        to ``default`` otherwise."""
        if default is None:
            default = self.default_delta_depth
        with self._lock:
            d = self._depth.get(tuple(bucket))
            delta_med = _median(d["delta"]) if d else None
            full_med = _median(d["full"]) if d else None
            enough = d is not None and (
                len(d["delta"]) >= DEPTH_MIN_SAMPLES
                and len(d["full"]) >= DEPTH_MIN_SAMPLES
            )
        if not enough or not delta_med or full_med is None:
            # Global fallback: the aggregate delta vs marshal stage
            # medians — shape-blind, but directionally right for a
            # bucket the backend has not measured yet.
            from holo_tpu.telemetry import profiling

            delta_med = profiling.stage_median("spf.one", "delta")
            full_med = profiling.stage_median("spf.one", "marshal")
            if not delta_med or full_med is None:
                return int(default)
        ratio = max(full_med / delta_med, 1.0)
        return max(DEPTH_MIN, min(DEPTH_MAX, int(round(ratio)) * DEPTH_SCALE))

    # -- persistence ----------------------------------------------------

    @staticmethod
    def _bucket_str(key: tuple) -> str:
        return json.dumps(list(key))

    @staticmethod
    def _bucket_from_str(s: str) -> tuple:
        out = []
        for v in json.loads(s):
            out.append(tuple(v) if isinstance(v, list) else v)
        return tuple(out)

    def snapshot(self) -> dict:
        """The persisted document (also the debugging surface)."""
        with self._lock:
            buckets = {}
            for key, st in self._table.items():
                buckets[self._bucket_str(key)] = {
                    "dispatches": st.dispatches,
                    "winner": st.winner,
                    "samples": {
                        e: [round(v, 9) for v in dq]
                        for e, dq in st.samples.items()
                    },
                    "cost": dict(st.cost),
                }
            depth = {
                self._bucket_str(b): {
                    arm: [round(v, 9) for v in dq] for arm, dq in d.items()
                }
                for b, d in self._depth.items()
            }
        return {
            "version": TABLE_VERSION,
            "engines": list(self.engines),
            "buckets": buckets,
            "depth": depth,
        }

    def save(self, path: str | Path | None = None) -> bool:
        """Atomic write (tmp + rename) of the versioned table; False
        when no path is configured.  Never raises: a full disk must not
        take an SPF dispatch down."""
        p = Path(path) if path is not None else self.path
        if p is None:
            return False
        try:
            doc = json.dumps(self.snapshot(), sort_keys=True, indent=1)
            tmp = p.with_suffix(p.suffix + ".tmp")
            tmp.write_text(doc + "\n")
            os.replace(tmp, p)
            return True
        except OSError as e:
            log.warning("tuner table save to %s failed: %s", p, e)
            return False

    def load(self, path: str | Path | None = None) -> bool:
        """Load a persisted table; version mismatch or a corrupt file
        discards it (the tuner just re-learns).  Returns True when
        state was restored."""
        p = Path(path) if path is not None else self.path
        if p is None or not p.exists():
            return False
        try:
            doc = json.loads(p.read_text())
        except (OSError, ValueError) as e:
            log.warning("tuner table load from %s failed: %s", p, e)
            return False
        if doc.get("version") != TABLE_VERSION:
            log.info(
                "tuner table %s has version %r (want %d); discarding",
                p, doc.get("version"), TABLE_VERSION,
            )
            return False
        with self._lock:
            self._table.clear()
            for bstr, entry in doc.get("buckets", {}).items():
                try:
                    key = self._bucket_from_str(bstr)
                except ValueError:
                    continue
                st = _BucketState()
                st.dispatches = int(entry.get("dispatches", 0))
                st.winner = entry.get("winner")
                for e, vals in entry.get("samples", {}).items():
                    st.samples[e] = deque(
                        [float(v) for v in vals], maxlen=SAMPLE_WINDOW
                    )
                st.cost = {
                    e: dict(c) for e, c in entry.get("cost", {}).items()
                }
                self._table[key] = st
            self._depth.clear()
            for bstr, d in doc.get("depth", {}).items():
                try:
                    b = self._bucket_from_str(bstr)
                except ValueError:
                    continue
                self._depth[b] = {
                    arm: deque(
                        [float(v) for v in vals], maxlen=SAMPLE_WINDOW
                    )
                    for arm, vals in d.items()
                }
            _BUCKETS.set(len(self._table))
            self._loaded = True
        return True

    # -- introspection --------------------------------------------------

    def ledger(self) -> list[dict]:
        """Per-bucket win/loss rows for ``holo-tpu-tools explain`` —
        the tuner's decisions made explainable: the winner, every
        measured engine's median wall + compile-time cost prior, and
        the resource axis the winner actually leads on (``packed beat
        fused on bytes, not flops``)."""
        rows = []
        with self._lock:
            items = sorted(
                self._table.items(), key=lambda kv: self._bucket_str(kv[0])
            )
            for key, st in items:
                kind, bucket = key[0], key[1:]
                winner = st.winner or self.default_engine
                measured = [
                    e for e in st.samples
                    if _median(st.samples[e]) is not None
                ]
                if len(measured) == 1 and winner not in measured:
                    # A bucket with one formulation outside the tuned
                    # set (the k>1 "mp" kernel): there was no choice —
                    # report the engine that actually ran, not the
                    # never-dispatched default.
                    winner = measured[0]
                engines = {}
                for e in sorted(st.samples):
                    med = _median(st.samples[e])
                    engines[e] = {
                        "median_ms": (
                            round(med * 1e3, 4) if med is not None else None
                        ),
                        "samples": len(st.samples[e]),
                        "cost": st.cost.get(e),
                    }
                rows.append(
                    {
                        "kind": kind,
                        "bucket": list(bucket),
                        "winner": winner,
                        "dispatches": st.dispatches,
                        "engines": engines,
                        "basis": self._win_basis(st, winner),
                    }
                )
        return rows

    def _win_basis(self, st: _BucketState, winner: str) -> str:
        """Why the winner wins, on the cost model's axes: strictly the
        lowest estimated bytes among measured rivals -> "bytes",
        strictly the lowest flops -> "flops", otherwise the measured
        wall alone decided (call under the tuner lock)."""
        if _median(st.samples.get(winner)) is None:
            return "default (no samples)"
        rivals = [
            e
            for e in st.samples
            if e != winner and _median(st.samples[e]) is not None
        ]
        if not rivals:
            return "only measured engine"
        wc = st.cost.get(winner)
        priced = [e for e in rivals if st.cost.get(e)]
        basis = "wall"
        if wc and priced:
            inf = float("inf")
            if all(
                wc.get("bytes", inf) < st.cost[e].get("bytes", inf)
                for e in priced
            ):
                basis = "bytes"
            elif all(
                wc.get("flops", inf) < st.cost[e].get("flops", inf)
                for e in priced
            ):
                basis = "flops"
        # Name only the rivals the claim was actually checked against:
        # a cost-axis basis compared the PRICED rivals; an unpriced
        # rival (no cost_analysis on this platform) was only ever
        # beaten on the measured wall.
        named = sorted(priced if basis in ("bytes", "flops") else rivals)
        return f"{winner} beat {', '.join(named)} on {basis}"

    def stats(self) -> dict:
        """holo-telemetry state-leaf view."""
        with self._lock:
            winners = {}
            for key, st in self._table.items():
                winners[self._bucket_str(key)] = {
                    "winner": st.winner or self.default_engine,
                    "dispatches": st.dispatches,
                    "measured-engines": sorted(st.samples),
                }
            return {
                "buckets": len(self._table),
                "promotions": self._promotions,
                "loaded-from-disk": self._loaded,
                "path": str(self.path) if self.path else None,
                "winners": winners,
                "depth-buckets": len(self._depth),
            }


# -- process-wide singleton --------------------------------------------

_TUNER: EngineTuner | None = None
_TUNER_LOCK = threading.Lock()


def configure_engine_tuner(
    path: str | Path | None = None, **kw
) -> EngineTuner:
    """Install the process-wide tuner (daemon boot from ``[pipeline]``;
    tests call directly).  Replaces any previous tuner."""
    global _TUNER
    with _TUNER_LOCK:
        _TUNER = EngineTuner(path=path, **kw)
        return _TUNER


def active_tuner() -> EngineTuner | None:
    """The installed tuner, or None (backends then keep their pinned
    engine and DeviceGraphCache its static depth cap)."""
    return _TUNER


def reset_engine_tuner() -> None:
    """Uninstall (test teardown)."""
    global _TUNER
    with _TUNER_LOCK:
        _TUNER = None
