"""Performance sentry: per-plan baselines + live attributed anomalies.

It catches regressions in serving traffic, each plan shape against
its OWN history.
On every completed statement it:

1. folds the query's wall clock into a rolling robust baseline keyed
   by (plan digest, session-property fingerprint) — median + MAD, a
   warmup minimum before any verdict, bounded retention;
2. when a warmed baseline exists and the query ran anomalously slow,
   names the **driver** — not just "slow" but WHICH flight-recorder
   bucket grew (xla_compile storm vs scan vs exchange vs
   straggler_slack), or ``cache_miss_expected_hit`` when a plan that
   reliably served from the result cache suddenly missed;
3. emits a typed :class:`AnomalyVerdict`, counts it in
   ``trino_anomalies_total{driver=...}``, and captures a diagnostics
   bundle for the anomalous-but-*successful* query (failures already
   get bundles; a silent 3× slowdown deserves the same post-mortem).

Baselines are rebuilt from :mod:`trino_tpu.history`'s JSONL on
startup, so a coordinator restart keeps its learned normal instead of
re-warming from scratch.

Thresholds (all env-tunable) are deliberately conservative — the
contract is zero false positives on a healthy repeat:

* ``TRINO_TPU_SENTRY_MIN_SAMPLES`` (default 5): verdicts only after
  this many clean samples per key;
* ``TRINO_TPU_SENTRY_MADS`` (default 5.0): wall must exceed
  median + MADS × scaled-MAD;
* ``TRINO_TPU_SENTRY_MIN_RATIO`` (default 1.5): AND exceed this
  multiple of the median (a tight MAD alone would flag micro-noise);
* ``TRINO_TPU_SENTRY_MIN_DELTA_MS`` (default 50): AND be this many
  absolute ms over the median (sub-50ms regressions are not worth a
  bundle).

Anomalous samples are NOT folded into the baseline — a regression
must keep looking like one until it is fixed, not become the new
normal after ``retention`` occurrences.

``TRINO_TPU_SENTRY=0`` disables the listener entirely.
"""

from __future__ import annotations

import dataclasses
import os
import statistics
import threading
import time
from collections import deque
from dataclasses import dataclass

from trino_tpu import events, history, telemetry

__all__ = [
    "AnomalyVerdict", "BaselineModel", "Sentry", "SentryListener",
    "active", "set_active", "ensure_installed", "enabled",
    "baseline_footer",
]

#: 1.4826 × MAD estimates the standard deviation for normal data —
#: the usual robust-scale constant
_MAD_SCALE = 1.4826

#: buckets eligible for driver attribution, checked in breakdown
#: order; "other" is a last resort (it names unattributed wall, which
#: is a finding too — "driver: other" means the flight recorder could
#: not see the regression, itself actionable)
_DRIVER_BUCKETS = (
    "queued", "slot_wait", "planning", "xla_compile",
    "admission_wait", "scan", "compute", "exchange",
    "straggler_slack", "other",
)


def enabled() -> bool:
    return os.environ.get("TRINO_TPU_SENTRY", "1") not in ("0", "off", "OFF")


@dataclass(frozen=True)
class AnomalyVerdict:
    """One attributed completion-time anomaly."""

    query_id: str
    ts: float
    plan_digest: str
    fingerprint: str
    wall_ms: float
    baseline_p50_ms: float
    baseline_mad_ms: float
    ratio: float
    #: the bucket that grew the most vs its own baseline median (or
    #: ``cache_miss_expected_hit`` when a reliably-cached plan missed)
    driver: str
    #: how many ms the driver bucket grew vs its baseline median
    driver_delta_ms: float
    samples: int
    message: str

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


class BaselineModel:
    """Rolling robust stats for one (plan digest, fingerprint) key.

    Keeps the last ``retention`` clean samples: wall clock, the bucket
    decomposition, and whether the result cache served the statement.
    """

    def __init__(self, retention: int = 64):
        self.retention = max(2, int(retention))
        self._walls: deque[float] = deque(maxlen=self.retention)
        self._buckets: deque[dict] = deque(maxlen=self.retention)
        self._result_hits: deque[bool] = deque(maxlen=self.retention)

    def observe(self, wall_ms: float, buckets: dict | None,
                cache_hit_tier: str | None) -> None:
        self._walls.append(float(wall_ms))
        self._buckets.append(dict(buckets or {}))
        self._result_hits.append(cache_hit_tier == "result")

    @property
    def samples(self) -> int:
        return len(self._walls)

    def p50(self) -> float:
        return statistics.median(self._walls) if self._walls else 0.0

    def mad(self) -> float:
        """Median absolute deviation of the wall samples."""
        if len(self._walls) < 2:
            return 0.0
        med = self.p50()
        return statistics.median(abs(w - med) for w in self._walls)

    def bucket_median(self, name: str) -> float:
        vals = [float(b.get(name, 0.0) or 0.0) for b in self._buckets]
        return statistics.median(vals) if vals else 0.0

    def result_hit_rate(self) -> float:
        if not self._result_hits:
            return 0.0
        return sum(self._result_hits) / len(self._result_hits)


class Sentry:
    """Baseline store + completion-time anomaly detector."""

    def __init__(
        self,
        history_store: history.QueryHistory | None = None,
        *,
        min_samples: int | None = None,
        mads: float | None = None,
        min_ratio: float | None = None,
        min_delta_ms: float | None = None,
        retention: int | None = None,
        max_anomalies: int = 256,
    ):
        env = os.environ.get
        self.min_samples = int(
            min_samples if min_samples is not None
            else env("TRINO_TPU_SENTRY_MIN_SAMPLES", "") or 5
        )
        self.mads = float(
            mads if mads is not None
            else env("TRINO_TPU_SENTRY_MADS", "") or 5.0
        )
        self.min_ratio = float(
            min_ratio if min_ratio is not None
            else env("TRINO_TPU_SENTRY_MIN_RATIO", "") or 1.5
        )
        self.min_delta_ms = float(
            min_delta_ms if min_delta_ms is not None
            else env("TRINO_TPU_SENTRY_MIN_DELTA_MS", "") or 50.0
        )
        self.retention = int(
            retention if retention is not None
            else env("TRINO_TPU_SENTRY_RETENTION", "") or 64
        )
        self._lock = threading.Lock()
        self._models: dict[tuple[str, str], BaselineModel] = {}
        self._anomalies: deque[AnomalyVerdict] = deque(
            maxlen=max_anomalies
        )
        if history_store is not None:
            self.reload(history_store)

    # ---- baseline persistence --------------------------------------
    def reload(self, store: history.QueryHistory) -> int:
        """Rebuild baselines by replaying the history store (restart
        path). Replay never emits verdicts — the past was already
        judged when it happened — but it DOES re-judge: a sample that
        was anomalous then is still excluded from the baseline now,
        so a restart cannot launder a regression into the normal."""
        n = 0
        for entry in store.entries():
            if entry.get("state") != "FINISHED":
                continue
            key = self._key(entry)
            if key is None:
                continue
            with self._lock:
                model = self._models.get(key)
            if model is not None and model.samples >= self.min_samples:
                wall = float(entry.get("wall_ms", 0.0) or 0.0)
                if self._judge(entry, key, model, wall) is not None:
                    continue
            if self._feed(entry):
                n += 1
        return n

    def _key(self, entry: dict) -> tuple[str, str] | None:
        digest = entry.get("plan_digest")
        if not digest:
            return None
        return (str(digest), str(entry.get("fingerprint") or ""))

    def _feed(self, entry: dict) -> bool:
        """Fold one clean FINISHED record into its baseline."""
        if entry.get("state") != "FINISHED":
            return False
        key = self._key(entry)
        if key is None:
            return False
        with self._lock:
            model = self._models.get(key)
            if model is None:
                model = self._models[key] = BaselineModel(self.retention)
            model.observe(
                float(entry.get("wall_ms", 0.0) or 0.0),
                entry.get("buckets"),
                entry.get("cache_hit_tier"),
            )
        return True

    # ---- detection -------------------------------------------------
    def model_for(self, plan_digest: str,
                  fingerprint: str = "") -> BaselineModel | None:
        with self._lock:
            return self._models.get((str(plan_digest), str(fingerprint)))

    def compare(self, plan_digest: str | None, fingerprint: str,
                wall_ms: float) -> dict | None:
        """Non-judging baseline lookup (the EXPLAIN ANALYZE footer):
        ``{"p50_ms", "ratio", "samples", "warm"}`` or None when the
        plan shape has no history at all."""
        if not plan_digest:
            return None
        model = self.model_for(plan_digest, fingerprint)
        if model is None or model.samples == 0:
            return None
        p50 = model.p50()
        return {
            "p50_ms": round(p50, 3),
            "ratio": round(wall_ms / p50, 3) if p50 > 0 else 0.0,
            "samples": model.samples,
            "warm": model.samples >= self.min_samples,
        }

    def observe(self, entry: dict) -> AnomalyVerdict | None:
        """Judge one completed-query record, then (when clean) fold it
        into its baseline. Returns the verdict for an anomalous
        FINISHED query; failures and warmup samples return None."""
        if entry.get("state") != "FINISHED":
            return None  # failures get bundles through their own path
        key = self._key(entry)
        if key is None:
            return None
        wall = float(entry.get("wall_ms", 0.0) or 0.0)
        with self._lock:
            model = self._models.get(key)
        verdict = None
        if model is not None and model.samples >= self.min_samples:
            verdict = self._judge(entry, key, model, wall)
        if verdict is None:
            self._feed(entry)
        else:
            with self._lock:
                self._anomalies.append(verdict)
            telemetry.ANOMALIES.inc(driver=verdict.driver)
        return verdict

    def _judge(self, entry: dict, key: tuple[str, str],
               model: BaselineModel, wall: float
               ) -> AnomalyVerdict | None:
        p50 = model.p50()
        mad = model.mad()
        band = p50 + self.mads * _MAD_SCALE * mad
        anomalous = (
            wall > band
            and p50 > 0
            and wall / p50 >= self.min_ratio
            and wall - p50 >= self.min_delta_ms
        )
        if not anomalous:
            return None
        driver, delta = self._attribute(entry, model)
        ratio = wall / p50 if p50 > 0 else 0.0
        return AnomalyVerdict(
            query_id=str(entry.get("query_id") or ""),
            ts=time.time(),
            plan_digest=key[0],
            fingerprint=key[1],
            wall_ms=round(wall, 3),
            baseline_p50_ms=round(p50, 3),
            baseline_mad_ms=round(mad, 3),
            ratio=round(ratio, 3),
            driver=driver,
            driver_delta_ms=round(delta, 3),
            samples=model.samples,
            message=(
                f"{ratio:.1f}x baseline p50 "
                f"({wall:.0f} ms vs {p50:.0f} ms over "
                f"{model.samples} samples), driver: {driver} "
                f"(+{delta:.0f} ms)"
            ),
        )

    def _attribute(self, entry: dict,
                   model: BaselineModel) -> tuple[str, float]:
        """Name the bucket that grew the most vs its own baseline
        median — the flight-recorder decomposition makes 'slow' say
        WHERE. A plan that reliably hit the result cache and suddenly
        missed is its own driver class: every bucket grew, but the
        cause is the miss, not any one of them."""
        wall = float(entry.get("wall_ms", 0.0) or 0.0)
        if (
            model.result_hit_rate() >= 0.8
            and entry.get("cache_hit_tier") != "result"
        ):
            return (
                "cache_miss_expected_hit",
                max(wall - model.p50(), 0.0),
            )
        buckets = entry.get("buckets") or {}
        best, best_delta = "other", 0.0
        for name in _DRIVER_BUCKETS:
            delta = (
                float(buckets.get(name, 0.0) or 0.0)
                - model.bucket_median(name)
            )
            if delta > best_delta:
                best, best_delta = name, delta
        return best, best_delta

    # ---- reading ---------------------------------------------------
    def anomalies(self, limit: int | None = None) -> list[AnomalyVerdict]:
        with self._lock:
            out = list(self._anomalies)
        if limit is not None and limit >= 0:
            out = out[-limit:]
        return out

    def baseline_count(self) -> int:
        with self._lock:
            return len(self._models)


class SentryListener(events.EventListener):
    """The EventListener that feeds history + sentry on every
    completed statement (both node shapes fire it), and captures a
    diagnostics bundle when a *successful* query judged anomalous."""

    def query_completed(self, event) -> None:
        if not enabled():
            return
        store = history.active()
        entry = history.entry_from_event(event)
        store.append(entry)
        verdict = active().observe(entry)
        if verdict is not None:
            self._capture_bundle(event, verdict)

    def _capture_bundle(self, event, verdict: AnomalyVerdict) -> None:
        """Post-mortem for a query that SUCCEEDED anomalously — today
        only failures get bundles, but a silent regression needs the
        same evidence (plan, trace, task stats, breakdown)."""
        from trino_tpu import diagnostics

        trace = getattr(event, "trace", None)
        bundle = diagnostics.build_bundle(
            event.query_id,
            error="",
            sql=event.sql,
            state=event.state,
            plan=getattr(event, "plan_text", None),
            trace=trace,
            task_stats=list(getattr(event, "task_stats", None) or ()),
            time_breakdown=getattr(event, "time_breakdown", None),
            extra={
                "error_class": "anomaly",
                "anomaly": verdict.to_dict(),
            },
        )
        diagnostics.record_bundle(bundle)


# ---- process-global sentry ----------------------------------------

_active: Sentry | None = None
_active_lock = threading.Lock()


def active() -> Sentry:
    """The process sentry, created on first use with baselines
    replayed from the durable history store (restart survival)."""
    global _active
    with _active_lock:
        if _active is None:
            _active = Sentry(history.active())
        return _active


def set_active(s: Sentry | None) -> None:
    """Install (or drop, for lazy re-creation) the process sentry —
    the test/bench seam."""
    global _active
    with _active_lock:
        _active = s


def ensure_installed(metadata) -> None:
    """Idempotently register the SentryListener on a Metadata's
    EventListener list. Runners call this at construction so the
    sentry observes every statement without any user configuration."""
    if not enabled():
        return
    listeners = getattr(metadata, "event_listeners", None)
    if listeners is None:
        return
    if any(isinstance(lst, SentryListener) for lst in listeners):
        return
    listeners.append(SentryListener())


def baseline_footer(plan_digest: str | None, fingerprint: str,
                    wall_ms: float, breakdown: dict | None) -> str | None:
    """The EXPLAIN ANALYZE footer line ("vs baseline: 2.3x p50,
    driver: xla_compile"), or None when no baseline exists yet. The
    current statement is judged against history that does NOT yet
    include it (the footer renders before completion fires)."""
    if not enabled():
        return None
    sen = active()
    cmp = sen.compare(plan_digest, fingerprint, wall_ms)
    if cmp is None:
        return None
    if not cmp["warm"]:
        return (
            f"vs baseline: warming "
            f"({cmp['samples']}/{sen.min_samples} samples)"
        )
    line = f"vs baseline: {cmp['ratio']:.1f}x p50 ({cmp['p50_ms']:.0f} ms)"
    if cmp["ratio"] >= sen.min_ratio and breakdown:
        model = sen.model_for(plan_digest or "", fingerprint)
        if model is not None:
            driver, _delta = sen._attribute(
                {
                    "wall_ms": wall_ms,
                    "buckets": (breakdown or {}).get("buckets"),
                    "cache_hit_tier": None,
                },
                model,
            )
            line += f", driver: {driver}"
    return line
