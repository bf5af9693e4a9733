"""Arrival & scaling observatory: what the *load* would pay for.

The roadmap's next wall — the elastic autoscaling control loop — needs a
measured signal surface before any scale decision is more than a guess:
what rate is traffic arriving at (and is it trending up), how much of it
can each replica actually serve, and how long until the live SLO burns?
This module answers those three questions from data the serving engine
already holds, repeating the repo's measure→price→build loop (kvscope →
host KV, commscope → quantized collectives, workload → speculation):

- **arrival-process analytics** — a bounded event ring over the submit
  hook (injectable clock, zero device syncs): rolling arrival rate over
  a time window, interarrival coefficient of variation (burstiness —
  ~0 uniform, ~1 Poisson, >1 bursty), prompt/decode token demand rates,
  and a rate-trend estimator (first-vs-second half-window slope).
  Exported as ``Serve/arrival_*`` gauges.
- **service-rate & utilization estimation** — decode slot-throughput
  (tokens per slot-second from the span ring's ``decode_step`` spans)
  and prefill token rate (the ``_prefill_rate`` spelling the tiered_kv
  lever already trusts) give a serviceable token rate; utilization is
  the queueing-model ρ = offered token rate / serviceable token rate,
  with a predicted steady-state queue wait from an M/G/k-style
  (Allen–Cunneen) approximation. Unmeasured inputs degrade to ``None``
  with a stated reason — never an exception (the PR-6/13 contract).
- **SLO-burn forecasting** — arrival trend + ρ + the live
  :class:`~.slo.SLOConfig` join into a time-to-violation horizon
  (``Serve/slo_ttv_s``; null when not trending toward violation), and
  :func:`score_what_ifs` prices add_replica / remove_replica /
  prefill↔decode-rebalance moves by predicted goodput and queue-wait
  delta — the ``scaling`` lever in the capacity advisor and the input
  ``FleetEngine.scaling_report()`` aggregates.

Cost discipline: everything is host-side arithmetic over a bounded
deque plus one pass over the span ring at *readout* time (scrape /
report cadence, never per token). Disabled (the default) the serving
engine holds ``loadscope = None`` and pays one ``is not None`` per
submit — zero new compiled programs (``tests/unit/test_loadscope.py``
compares compile counts on and off). Validation is replay-
backtested: :func:`~.replay.scaling_backtest` replays a synthetic
diurnal+bursty trace on the fake clock at two fleet sizes and scores
predicted queue-wait/goodput deltas against achieved (±10 pt band).
"""

from __future__ import annotations

import dataclasses
import math
from collections import deque
from typing import Callable, Optional

from .metrics import MetricsRegistry

SCALING_SCHEMA = "dstpu.loadscope.v1"


@dataclasses.dataclass
class LoadScopeConfig:
    """Arrival/scaling-observatory knobs (``ServingConfig.loadscope``).
    Constructing one (or passing a dict) opts in; ``None`` on the
    serving config means no observatory is built at all."""

    enabled: bool = True
    # Rolling window for the arrival estimators, seconds on the
    # injectable clock. Rates, CV, and trend are computed over events
    # younger than this; size it to a few times the scrape interval.
    window_s: float = 60.0
    # Bounded arrival ring (one small tuple per submit) — the window
    # above trims by age, this caps worst-case memory under floods.
    max_events: int = 8192
    # Utilization above which the scaling advisor starts scoring
    # add_replica urgency (score ramps 0→100 between here and ρ=1).
    rho_high: float = 0.85
    # TTV values beyond this horizon report as null ("not trending
    # toward violation on any actionable timescale").
    ttv_horizon_s: float = 3600.0

    def __post_init__(self):
        if self.window_s <= 0:
            raise ValueError(f"loadscope window_s must be > 0, "
                             f"got {self.window_s}")
        if self.max_events < 2:
            raise ValueError(f"loadscope max_events must be >= 2, "
                             f"got {self.max_events}")
        if not 0.0 < self.rho_high < 1.0:
            raise ValueError(f"loadscope rho_high must be in (0, 1), "
                             f"got {self.rho_high}")
        if self.ttv_horizon_s <= 0:
            raise ValueError(f"loadscope ttv_horizon_s must be > 0, "
                             f"got {self.ttv_horizon_s}")

    @classmethod
    def from_any(cls, cfg: "LoadScopeConfig | dict | None") \
            -> "LoadScopeConfig | None":
        if cfg is None or isinstance(cfg, cls):
            return cfg
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(cfg) - known
        if unknown:
            raise ValueError(f"unknown loadscope config keys: "
                             f"{sorted(unknown)}")
        return cls(**cfg)


def _clamp01(x: float) -> float:
    return 0.0 if x < 0.0 else (1.0 if x > 1.0 else x)


def goodput_frac(rho: "float | None") -> Optional[float]:
    """Steady-state serviceable fraction of offered work at utilization
    ``rho``: 1 under capacity, capacity/offered past saturation. The
    model side of the backtest's window-throughput measurement."""
    if rho is None:
        return None
    if rho <= 1.0:
        return 1.0
    return 1.0 / rho


def predicted_queue_wait_s(rho: "float | None", k: "int | None",
                           mean_service_s: "float | None",
                           arrival_cv: "float | None" = None) \
        -> Optional[float]:
    """Predicted steady-state queue wait for an M/G/k-style station:
    the Allen–Cunneen approximation ``(Ca²+Cs²)/2 · Wq(M/M/k)`` with
    ``Wq(M/M/k) ≈ ρ^√(2(k+1)) / (k(1-ρ)) · E[S]`` (Sakasegawa's form).
    Service-time variability is unmeasured, so Cs² is taken as 1
    (exponential); ``arrival_cv`` defaults to Poisson when unmeasured.
    None when any input is unmeasured or the station is saturated
    (ρ ≥ 1: the steady-state wait is unbounded — callers report the
    saturation flag instead of a fabricated number)."""
    if rho is None or mean_service_s is None or not k or k < 1:
        return None
    if rho <= 0.0:
        return 0.0
    if rho >= 1.0:
        return None
    ca2 = arrival_cv * arrival_cv if arrival_cv is not None else 1.0
    mmk = (rho ** math.sqrt(2.0 * (k + 1))) / (k * (1.0 - rho))
    return max(0.0, 0.5 * (ca2 + 1.0) * mmk * float(mean_service_s))


def time_to_violation_s(*, rate_per_s: "float | None",
                        trend_per_s2: "float | None",
                        rho: "float | None", slo=None,
                        horizon_s: float = 3600.0) -> Optional[float]:
    """Seconds until the arrival trend pushes utilization to saturation
    (ρ → 1), the point past which every latency SLO burns: 0 when
    already saturated, null when any input is unmeasured, no latency
    SLO is armed, the trend is flat/falling, or the crossing lies
    beyond ``horizon_s`` (not trending toward violation on any
    actionable timescale)."""
    if slo is None or not (getattr(slo, "ttft_p99_s", 0.0)
                           or getattr(slo, "tpot_p99_s", 0.0)):
        return None
    if rate_per_s is None or rho is None or rate_per_s <= 0:
        return None
    if rho >= 1.0:
        return 0.0
    if trend_per_s2 is None or trend_per_s2 <= 0:
        return None
    # ρ scales linearly with the arrival rate: the violating rate is
    # rate/ρ, and the trend says how fast we approach it
    ttv = (rate_per_s / rho - rate_per_s) / trend_per_s2
    return ttv if ttv <= horizon_s else None


def score_what_ifs(*, rho: "float | None", replicas: int = 1,
                   slots: "int | None" = None,
                   mean_service_s: "float | None" = None,
                   arrival_cv: "float | None" = None,
                   rho_high: float = 0.85,
                   rho_prefill: "float | None" = None,
                   rho_decode: "float | None" = None,
                   prefill_replicas: int = 0) -> list:
    """Score the scaling moves the autoscaler could make, from measured
    utilization. Each entry carries the predicted ρ / queue-wait /
    goodput before and after plus a 0–100 urgency score:

    - ``add_replica`` — scores the overload headroom: 0 at/below
      ``rho_high``, ramping to 100 at saturation (monotone in ρ).
    - ``remove_replica`` — scores idle capacity: high only when the
      fleet is far under ``rho_high`` AND removing one keeps it there.
    - ``rebalance_prefill_decode`` — only on a disaggregated fleet with
      both per-phase utilizations measured: scores their imbalance.

    ρ unmeasured → empty list (the capacity lever self-demotes with the
    reason; this function never guesses)."""
    if rho is None:
        return []
    out = []
    n = max(1, int(replicas))
    k_each = max(1, int(slots or 1))

    def _wait(r, k):
        return predicted_queue_wait_s(r, k, mean_service_s, arrival_cv)

    def _entry(action, rho_after, k_after, score):
        w_now = _wait(rho, k_each * n)
        w_after = _wait(rho_after, k_after)
        g_now, g_after = goodput_frac(rho), goodput_frac(rho_after)
        return {
            "action": action,
            "rho_now": rho, "rho_after": rho_after,
            "saturated_now": rho >= 1.0,
            "predicted_queue_wait_s_now": w_now,
            "predicted_queue_wait_s_after": w_after,
            "queue_wait_delta_s": (w_now - w_after
                                   if w_now is not None
                                   and w_after is not None else None),
            "goodput_now": g_now, "goodput_after": g_after,
            "goodput_delta": (g_after - g_now
                              if g_now is not None and g_after is not None
                              else None),
            "score": round(float(score), 2),
        }

    # add_replica: homogeneous replicas — n→n+1 scales serviceable rate
    # by (n+1)/n, so ρ falls by n/(n+1)
    rho_add = rho * n / (n + 1)
    score_add = 100.0 * _clamp01((rho - rho_high)
                                 / max(1e-9, 1.0 - rho_high))
    out.append(_entry("add_replica", rho_add, k_each * (n + 1), score_add))

    if n >= 2:
        rho_rm = rho * n / (n - 1)
        rho_low = 0.5 * rho_high
        score_rm = (100.0 * _clamp01((rho_low - rho) / max(1e-9, rho_low))
                    if rho_rm < rho_high else 0.0)
        out.append(_entry("remove_replica", rho_rm, k_each * (n - 1),
                          score_rm))

    if (prefill_replicas >= 1 and n - prefill_replicas >= 1
            and rho_prefill is not None and rho_decode is not None):
        # moving one replica across the prefill/decode split helps only
        # when the phases are imbalanced AND the hot side is actually hot
        imbalance = abs(rho_prefill - rho_decode)
        hot = max(rho_prefill, rho_decode)
        score_rb = 100.0 * _clamp01(imbalance) * _clamp01(
            (hot - rho_high) / max(1e-9, 1.0 - rho_high))
        donor_ok = ((n - prefill_replicas >= 2)
                    if rho_prefill > rho_decode
                    else (prefill_replicas >= 2))
        out.append({
            "action": "rebalance_prefill_decode",
            "direction": ("decode_to_prefill"
                          if rho_prefill > rho_decode
                          else "prefill_to_decode"),
            "rho_prefill": rho_prefill, "rho_decode": rho_decode,
            "imbalance": imbalance,
            "score": round(float(score_rb if donor_ok else 0.0), 2),
        })
    return out


class LoadScope:
    """Submit-path arrival analytics into ``Serve/arrival_*`` plus the
    utilization / forecast readout (:meth:`report`).

    ``on_submit`` runs on the serving intake (the submit hook in
    ``ServingEngine.submit``); :meth:`report` is the scrape-cadence
    readout — the engine feeds it the span-measured service rates and
    the live SLO config, and it degrades field-by-field to ``None``
    when any input is unmeasured. All state is host-side and bounded;
    ``clock`` is injectable like every observability clock."""

    def __init__(self, cfg: "LoadScopeConfig | dict | None" = None,
                 registry: Optional[MetricsRegistry] = None,
                 clock: Optional[Callable[[], float]] = None):
        self.cfg = LoadScopeConfig.from_any(cfg) or LoadScopeConfig()
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self.clock = clock if clock is not None else (lambda: 0.0)
        # (t, prompt_tokens, decode_budget_tokens) per submit, trimmed
        # by window age at readout and capped by max_events always
        self._events: deque = deque(maxlen=self.cfg.max_events)
        self.requests = 0
        self.prompt_tokens = 0
        self.decode_tokens = 0          # budgeted (max_new), not emitted
        # backtest attachment: when a scaling_backtest has validated the
        # advisor on this build, its predicted-vs-achieved block rides
        # every report (and the capacity lever marks itself backtested)
        self.achieved: Optional[dict] = None
        # calibration seam: replaces the engine's span-measured service
        # rates in report(). Needed when span time and loop time diverge
        # — on a ticking fake clock most reads land OUTSIDE the compute
        # spans, so the replay harness measures capacity with a
        # saturation probe instead. None (the default) trusts the spans.
        self.service_override: Optional[dict] = None

    # --------------------------------------------------------------- intake
    def on_submit(self, prompt_len: int, max_new: int,
                  queue_depth: int = 0) -> None:
        """Record one accepted submit (the engine calls this after the
        scheduler admitted the request to its queue)."""
        t = self.clock()
        self._events.append((t, int(prompt_len), int(max_new)))
        self.requests += 1
        self.prompt_tokens += int(prompt_len)
        self.decode_tokens += int(max_new)
        arr = self.arrival(now=t)
        r = self.registry
        r.counter("Serve/arrival_requests").inc()
        for key, name in ((arr["rate_per_s"], "Serve/arrival_rate_per_s"),
                          (arr["interarrival_cv"], "Serve/arrival_cv"),
                          (arr["trend_per_s2"], "Serve/arrival_trend_per_s2"),
                          (arr["prompt_tokens_per_s"],
                           "Serve/arrival_prompt_tokens_per_s"),
                          (arr["decode_tokens_per_s"],
                           "Serve/arrival_decode_tokens_per_s"),
                          (arr["offered_tokens_per_s"],
                           "Serve/offered_tokens_per_s")):
            if key is not None:
                r.gauge(name).set(key)

    # -------------------------------------------------------------- readout
    def _window(self, now: "float | None" = None) -> list:
        t = self.clock() if now is None else now
        lo = t - self.cfg.window_s
        return [e for e in self._events if e[0] >= lo]

    def arrival(self, now: "float | None" = None) -> dict:
        """The arrival-process estimate over the rolling window. Every
        field is ``None`` until enough events support it: rates need 2,
        CV needs 3, the trend needs 4 — unmeasured, not guessed."""
        win = self._window(now)
        out = {"window_s": self.cfg.window_s,
               "requests_in_window": len(win),
               "rate_per_s": None, "interarrival_cv": None,
               "trend_per_s2": None, "prompt_tokens_per_s": None,
               "decode_tokens_per_s": None, "offered_tokens_per_s": None}
        if len(win) < 2:
            return out
        span = win[-1][0] - win[0][0]
        if span <= 0:
            return out
        # rate over the observed span: (n-1) interarrivals across it
        out["rate_per_s"] = (len(win) - 1) / span
        out["prompt_tokens_per_s"] = sum(e[1] for e in win[:-1]) / span
        out["decode_tokens_per_s"] = sum(e[2] for e in win[:-1]) / span
        out["offered_tokens_per_s"] = (out["prompt_tokens_per_s"]
                                       + out["decode_tokens_per_s"])
        gaps = [b[0] - a[0] for a, b in zip(win, win[1:])]
        if len(gaps) >= 2:
            mean = sum(gaps) / len(gaps)
            if mean > 0:
                var = sum((g - mean) ** 2 for g in gaps) / len(gaps)
                out["interarrival_cv"] = math.sqrt(var) / mean
        if len(win) >= 4:
            # rate slope: second half-window rate minus first, over the
            # half-window gap — a two-point regression that is robust to
            # the bursty on/off structure a full LSQ fit would chase
            mid_t = win[0][0] + 0.5 * span
            first = [e for e in win if e[0] < mid_t]
            second = [e for e in win if e[0] >= mid_t]
            if len(first) >= 2 and len(second) >= 2:
                s1 = first[-1][0] - first[0][0]
                s2 = second[-1][0] - second[0][0]
                if s1 > 0 and s2 > 0:
                    r1 = (len(first) - 1) / s1
                    r2 = (len(second) - 1) / s2
                    out["trend_per_s2"] = (r2 - r1) / (0.5 * span)
        return out

    def mean_decode_budget(self, now: "float | None" = None) \
            -> Optional[float]:
        """Mean decode-token budget (max_new) per windowed request — the
        per-request service demand the queue-wait model prices."""
        win = self._window(now)
        if not win:
            return None
        return sum(e[2] for e in win) / len(win)

    # --------------------------------------------------------------- report
    def report(self, *, service: "dict | None" = None, slo=None,
               queue_depth: "int | None" = None,
               replicas: int = 1) -> dict:
        """Join the arrival estimate with engine-measured service rates
        into the scaling snapshot (``GET /scaling``'s body, the
        ``loadscope`` section of the capacity report, and the per-
        replica row of ``FleetEngine.scaling_report()``).

        ``service`` is the engine's measured side: ``slots`` plus
        (possibly ``None``) ``decode_tokens_per_slot_s`` and
        ``prefill_tokens_per_s``. Missing measurements degrade the
        dependent fields to ``None`` with a reason — never raise."""
        arr = self.arrival()
        if self.service_override is not None:
            service = self.service_override
        svc = dict(service or {})
        slots = int(svc.get("slots") or 0)
        per_slot = svc.get("decode_tokens_per_slot_s")
        prefill_rate = svc.get("prefill_tokens_per_s")
        serviceable = (slots * per_slot
                       if per_slot is not None and slots > 0 else None)
        svc.setdefault("serviceable_decode_tokens_per_s", serviceable)

        reasons = []
        if arr["rate_per_s"] is None:
            reasons.append("arrival rate unmeasured "
                           "(fewer than 2 submits in the window)")
        rho_decode = rho_prefill = None
        if serviceable is None:
            reasons.append("decode service rate unmeasured "
                           "(spans off or no decode steps in the ring)")
        elif arr["decode_tokens_per_s"] is not None and serviceable > 0:
            rho_decode = arr["decode_tokens_per_s"] / serviceable
        if prefill_rate is None:
            reasons.append("prefill rate unmeasured "
                           "(spans off or no prefill chunks in the ring)")
        elif arr["prompt_tokens_per_s"] is not None and prefill_rate > 0:
            rho_prefill = arr["prompt_tokens_per_s"] / prefill_rate
        rho = (max(v for v in (rho_decode, rho_prefill) if v is not None)
               if rho_decode is not None or rho_prefill is not None
               else None)

        mean_budget = self.mean_decode_budget()
        mean_service_s = (mean_budget / per_slot
                          if mean_budget is not None and per_slot
                          else None)
        wait = predicted_queue_wait_s(rho, slots * max(1, int(replicas)),
                                      mean_service_s,
                                      arr["interarrival_cv"])
        ttv = time_to_violation_s(
            rate_per_s=arr["rate_per_s"],
            trend_per_s2=arr["trend_per_s2"], rho=rho, slo=slo,
            horizon_s=self.cfg.ttv_horizon_s)
        slo_armed = bool(slo is not None
                         and (getattr(slo, "ttft_p99_s", 0.0)
                              or getattr(slo, "tpot_p99_s", 0.0)))
        if not slo_armed:
            reasons.append("no latency SLO armed "
                           "(serving.slo ttft/tpot targets unset) — "
                           "time-to-violation undefined")

        what_ifs = score_what_ifs(
            rho=rho, replicas=replicas, slots=slots,
            mean_service_s=mean_service_s,
            arrival_cv=arr["interarrival_cv"],
            rho_high=self.cfg.rho_high)

        r = self.registry
        for v, name in ((rho, "Serve/utilization"),
                        (wait, "Serve/predicted_queue_wait_s"),
                        (ttv, "Serve/slo_ttv_s")):
            if v is not None:
                r.gauge(name).set(v)

        out = {
            "schema": SCALING_SCHEMA,
            "requests": self.requests,
            "queue_depth": queue_depth,
            "replicas": int(replicas),
            "arrival": arr,
            "service": svc,
            "utilization": {
                "rho": rho, "rho_decode": rho_decode,
                "rho_prefill": rho_prefill,
                "saturated": (rho >= 1.0) if rho is not None else None,
                "mean_service_s": mean_service_s,
                "predicted_queue_wait_s": wait,
                "rho_high": self.cfg.rho_high,
            },
            "forecast": {
                "slo_armed": slo_armed,
                "slo_ttv_s": ttv,
                "trend_per_s2": arr["trend_per_s2"],
            },
            "what_ifs": what_ifs,
            "unmeasured": reasons,
        }
        if self.achieved is not None:
            out["achieved"] = dict(self.achieved)
        return out
