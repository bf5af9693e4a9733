"""Elastic fleet autoscaler: the actuation half of the scaling loop.

PR 17's loadscope observatory (``observability/loadscope.py``) landed
the *estimation* half — :meth:`~.fleet.FleetEngine.scaling_report`
measures arrival rate, per-phase utilization ρ, SLO time-to-violation,
and scores the add/remove/rebalance what-ifs. This module is the
*control loop* the ROADMAP carved out for it: :class:`Autoscaler`
CONSUMES that report verbatim (it never re-derives an estimate — every
actuation's decision record embeds the ``scaling_report()`` inputs it
fired on) and decides WHEN a score is trustworthy enough to act on,
under explicit robustness guards. Reference analog: DeepSpeed's
elasticity package, rebuilt as a serving-fleet control plane with
ZeRO-Infinity's degrade-gracefully discipline applied to scale events.

The guards, each of which exists because the naive loop fails without
it:

- **per-direction hysteresis** — a score must stay armed for
  ``up_ticks`` / ``down_ticks`` consecutive evaluations before the
  loop actuates, so one bursty window cannot trigger a scale event;
- **cooldown windows** — after any actuation the SAME direction holds
  for ``cooldown_up_s`` / ``cooldown_down_s`` (capacity changes take
  a window to show up in ρ; acting again before the estimator
  re-converges double-corrects);
- **a flap budget** — direction reversals (add after remove or vice
  versa) inside ``flap_window_s`` are counted; at ``flap_budget`` the
  loop FREEZES itself and alarms instead of oscillating (an
  oscillating trace must cost at most ``flap_budget`` reversals:
  ``test_flap_budget_exhaustion_freezes_the_loop``);
- **score-trust gating** — a what-if that self-demoted to 0 with a
  stated reason, an unmeasured ρ (null report / empty what-ifs), or a
  ``saturated`` forecast (the queue-wait prediction is null past the
  knee) NEVER actuates: the loop records an alarm decision and holds.
  Saturation in particular means the estimator can no longer price the
  move — paging a human beats acting on an unpriceable forecast;
- **drain-before-remove** — scale-down drains the victim first
  (:meth:`~.fleet.FleetEngine.begin_drain_replica`: intake closes,
  backlog finishes, pending handoffs re-route to its siblings) and
  removes it only once idle, so a clean scale-down requeues NOTHING.
  The drain is bounded by ``drain_deadline_s`` — past the deadline the
  victim is removed anyway and its stragglers requeue onto survivors
  (zero loss either way); and it aborts on load reversal: if the
  scale-up signal arms while a victim drains, ``end_drain_replica``
  reopens intake and the replica is NOT removed;
- **an incident cooldown latch** — a chaos/replica kill
  (:meth:`~.fleet.FleetEngine.kill_replica` calls
  :meth:`Autoscaler.on_incident`) latches scale-down and rebalance off
  for ``incident_cooldown_s``: failover requeues depress the measured
  arrival exactly like a real lull, and a loop without the latch reads
  its own incident as "remove a replica";
- **manual freeze/pin** — ``POST /autoscale {"freeze": true}``
  (token-gated, for deploys) stops all actuation while evaluations and
  alarms continue; ``{"pin": [names]}`` shields specific replicas from
  ever being chosen as drain victims.

Every evaluation that matters produces a typed
:class:`AutoscaleDecision` (inputs snapshot, rule fired, action,
outcome) in a bounded audit ring — the ring feeds ``GET /autoscale``,
``Fleet/autoscale_*`` metrics, the doctor's ``[autoscale]`` section,
and the fleet's incident dumps (``fleet/autoscale_audit.jsonl``).

Inert by default: ``serving.autoscale=None`` builds NOTHING — the
fleet pays one ``is not None`` per step, zero threads, zero new
compiled programs, zero syncs
(``test_fleet_attach_inert_and_config_reject``). The loop has no thread of its own even
when on: it piggybacks on :meth:`~.fleet.FleetEngine.step` at
``tick_s`` cadence on the fleet's injectable clock, so fake-clock
tests drive it deterministically.
"""

from __future__ import annotations

import dataclasses
import json
from collections import deque
from typing import Optional

__all__ = ["AutoscaleConfig", "AutoscaleDecision", "Autoscaler"]

# decision outcomes (the audit ring's closed vocabulary)
ACTUATED = "actuated"
DRAIN_STARTED = "drain_started"
DRAIN_ABORTED = "drain_aborted"
REMOVED = "removed"
REMOVED_AT_DEADLINE = "removed_at_deadline"
ALARM = "alarm"
SUPPRESSED = "suppressed"


@dataclasses.dataclass
class AutoscaleConfig:
    """``serving.autoscale`` — the control-loop knobs. All windows are
    in the fleet clock's seconds (fake seconds under a test clock).
    Sizing guidance lives in docs/OPERATIONS.md ("running the
    autoscaler"): thresholds come from the what-if score distribution
    of ``GET /scaling`` on your traffic, cooldowns from the loadscope window,
    the flap budget from how often you can stomach a reversal."""

    enabled: bool = True
    # evaluation cadence: scaling_report() is consulted at most once
    # per tick_s (the drain progress check runs every step — it is one
    # idle probe, the report is a registry walk)
    tick_s: float = 5.0
    # score thresholds (0-100, against loadscope's what-if scores):
    # the signal "arms" when the action's score reaches its threshold
    add_score_min: float = 60.0
    remove_score_min: float = 60.0
    rebalance_score_min: float = 60.0
    # per-direction hysteresis: consecutive armed evaluations required
    # before actuating (scale-down is slower by default — adding
    # capacity late costs SLO, removing it early costs SLO twice)
    up_ticks: int = 2
    down_ticks: int = 3
    # post-actuation cooldowns per direction
    cooldown_up_s: float = 30.0
    cooldown_down_s: float = 60.0
    # direction reversals tolerated inside flap_window_s before the
    # loop freezes itself (0 = any reversal freezes)
    flap_budget: int = 2
    flap_window_s: float = 600.0
    # bounded drain: a victim still busy past the deadline is removed
    # anyway (its stragglers requeue — zero loss, bounded latency)
    drain_deadline_s: float = 60.0
    # scale-down/rebalance latch after a replica kill or incident
    incident_cooldown_s: float = 120.0
    # fleet size rails (min_replicas also floors rebalance donors)
    min_replicas: int = 1
    max_replicas: int = 8
    # decision audit ring capacity
    audit_ring: int = 256

    def __post_init__(self):
        if self.tick_s <= 0:
            raise ValueError(f"tick_s must be > 0, got {self.tick_s}")
        for knob in ("add_score_min", "remove_score_min",
                     "rebalance_score_min"):
            v = getattr(self, knob)
            if not 0.0 <= v <= 100.0:
                raise ValueError(f"{knob} must be in [0, 100], got {v}")
        for knob in ("up_ticks", "down_ticks"):
            if getattr(self, knob) < 1:
                raise ValueError(f"{knob} must be >= 1, "
                                 f"got {getattr(self, knob)}")
        for knob in ("cooldown_up_s", "cooldown_down_s",
                     "incident_cooldown_s"):
            if getattr(self, knob) < 0:
                raise ValueError(f"{knob} must be >= 0, "
                                 f"got {getattr(self, knob)}")
        if self.flap_budget < 0:
            raise ValueError(f"flap_budget must be >= 0, "
                             f"got {self.flap_budget}")
        if self.flap_window_s <= 0:
            raise ValueError(f"flap_window_s must be > 0, "
                             f"got {self.flap_window_s}")
        if self.drain_deadline_s <= 0:
            raise ValueError(f"drain_deadline_s must be > 0, "
                             f"got {self.drain_deadline_s}")
        if self.min_replicas < 1:
            raise ValueError(f"min_replicas must be >= 1, "
                             f"got {self.min_replicas}")
        if self.max_replicas < self.min_replicas:
            raise ValueError(
                f"max_replicas={self.max_replicas} < "
                f"min_replicas={self.min_replicas}")
        if self.audit_ring < 1:
            raise ValueError(f"audit_ring must be >= 1, "
                             f"got {self.audit_ring}")

    @classmethod
    def from_any(cls, cfg: "AutoscaleConfig | dict | None") \
            -> "Optional[AutoscaleConfig]":
        if cfg is None or isinstance(cfg, cls):
            return cfg
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(cfg) - known
        if unknown:
            raise ValueError(
                f"unknown autoscale config keys: {sorted(unknown)}")
        return cls(**cfg)


@dataclasses.dataclass
class AutoscaleDecision:
    """One control-loop decision: what the loop saw (``inputs`` is the
    ``scaling_report()`` excerpt it fired on — fleet aggregates plus
    the relevant what-if entry, verbatim), which rule fired, what it
    did about it, and how that turned out. The audit ring holds these
    so a bad scale event is explicable after the fact."""

    seq: int
    t: float
    rule: str                    # which guard/signal produced this
    action: str                  # add_replica / remove_replica / ...
    outcome: str                 # actuated / drain_started / alarm / ...
    target: str = ""             # replica name, when one is involved
    reason: str = ""             # human-readable why
    inputs: dict = dataclasses.field(default_factory=dict)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def _report_inputs(report: "Optional[dict]",
                   what_if: "Optional[dict]" = None) -> dict:
    """The inputs snapshot an actuation must trace to: the report's
    fleet aggregate block and the scoring entry, copied verbatim (no
    re-derived numbers — the acceptance contract)."""
    if report is None:
        return {"fleet": None, "what_if": what_if}
    return {"fleet": dict(report.get("fleet") or {}),
            "what_if": dict(what_if) if what_if is not None else None}


class Autoscaler:
    """The hysteresis-guarded actuation loop over one
    :class:`~.fleet.FleetEngine`. Built by the fleet when
    ``serving.autoscale`` is configured and enabled; never constructs
    threads — :meth:`on_step` is called from ``FleetEngine.step()``."""

    def __init__(self, fleet, cfg: "AutoscaleConfig | dict | None"):
        from .fleet import ROLE_DECODE, ROLE_PREFILL, ROLE_SERVE

        self.fleet = fleet
        self.cfg = AutoscaleConfig.from_any(cfg) or AutoscaleConfig()
        self._roles = (ROLE_SERVE, ROLE_PREFILL, ROLE_DECODE)
        self._clock = fleet._clock
        self.registry = fleet.registry
        self.audit: deque = deque(maxlen=self.cfg.audit_ring)
        self._seq = 0
        self.evals = 0
        self._last_eval: Optional[float] = None
        # per-direction streaks (consecutive armed evaluations)
        self._streak = {"add": 0, "remove": 0, "rebalance": 0}
        # cooldown horizons per direction ("up" = add, "down" = remove
        # AND rebalance — both take capacity out of a role)
        self._cooldown_until = {"up": float("-inf"),
                                "down": float("-inf")}
        # recent direction reversals (timestamps) inside flap_window_s
        self._flaps: deque = deque()
        self._last_direction: Optional[str] = None
        self._last_actuation_t: Optional[float] = None
        # drain-before-remove in flight: (victim, deadline, add_role)
        # — add_role is the role to add after removal (rebalance), or
        # "" for a plain scale-down
        self._drain: Optional[tuple] = None
        # incident latch horizon (on_incident pushes it forward)
        self._incident_until = float("-inf")
        self.incidents = 0
        # manual overrides (POST /autoscale)
        self._frozen = False
        self._frozen_since: Optional[float] = None
        self._frozen_by = ""           # "manual" | "flap_budget"
        self._pinned: set = set()
        # dedup key for alarm/suppress decisions so a held state does
        # not flood the ring once per tick
        self._last_quiet_key: Optional[tuple] = None
        self._export_gauges()

    # ------------------------------------------------------------- audit
    def _record(self, rule: str, action: str, outcome: str,
                target: str = "", reason: str = "",
                inputs: "Optional[dict]" = None,
                dedup: bool = False) -> AutoscaleDecision:
        """Append one decision; ``dedup=True`` (alarms/suppressions)
        collapses consecutive repeats of the same (rule, action,
        outcome) so a held guard writes one entry, not one per tick."""
        key = (rule, action, outcome, target)
        if dedup and key == self._last_quiet_key:
            return None
        self._last_quiet_key = key if dedup else None
        self._seq += 1
        d = AutoscaleDecision(
            seq=self._seq, t=self._clock(), rule=rule, action=action,
            outcome=outcome, target=target, reason=reason,
            inputs=inputs if inputs is not None else {})
        self.audit.append(d)
        r = self.registry
        r.counter("Fleet/autoscale_decisions").inc()
        if outcome == ALARM:
            r.counter("Fleet/autoscale_alarms").inc()
        elif outcome == SUPPRESSED:
            r.counter("Fleet/autoscale_suppressed").inc()
        return d

    def audit_entries(self) -> list:
        """The decision ring, oldest first, as plain dicts."""
        return [d.as_dict() for d in self.audit]

    def audit_jsonl(self) -> str:
        return "\n".join(json.dumps(d.as_dict(), separators=(",", ":"),
                                    default=str)
                         for d in self.audit) + "\n"

    # ----------------------------------------------------------- metrics
    def _flap_budget_remaining(self, now: float) -> int:
        while self._flaps and now - self._flaps[0] > self.cfg.flap_window_s:
            self._flaps.popleft()
        return max(0, self.cfg.flap_budget - len(self._flaps))

    def _export_gauges(self) -> None:
        now = self._clock()
        frozen_stale = (now - self._frozen_since
                        if self._frozen and self._frozen_since is not None
                        else 0.0)
        self.registry.set_gauges({
            "Fleet/autoscale_enabled": 1.0,
            "Fleet/autoscale_frozen": 1.0 if self._frozen else 0.0,
            "Fleet/autoscale_frozen_stale_s": float(frozen_stale),
            "Fleet/autoscale_flap_budget_remaining":
                float(self._flap_budget_remaining(now)),
            "Fleet/autoscale_draining": 1.0 if self._drain else 0.0,
            "Fleet/autoscale_incident_latched":
                1.0 if now < self._incident_until else 0.0,
        })

    # ------------------------------------------------------------ intake
    def on_incident(self, kind: str, replica: str = "") -> None:
        """A replica kill / chaos fault just happened: latch scale-down
        and rebalance for ``incident_cooldown_s`` so the failover's
        arrival dip is never misread as a remove signal. An in-flight
        drain on the KILLED victim is cleared (nothing left to remove);
        a drain on another replica aborts — post-incident capacity
        math is stale."""
        now = self._clock()
        self.incidents += 1
        self._incident_until = now + self.cfg.incident_cooldown_s
        self.registry.counter("Fleet/autoscale_incidents").inc()
        if self._drain is not None:
            victim, _deadline, _add_role = self._drain
            self._drain = None
            if victim != replica and victim in self.fleet.replicas:
                self.fleet.end_drain_replica(victim)
                self.registry.counter("Fleet/autoscale_drain_aborts").inc()
            self._record("incident", "end_drain", DRAIN_ABORTED,
                         target=victim,
                         reason=f"{kind} on {replica or '?'} during "
                                "drain — post-incident capacity is "
                                "stale; victim keeps serving")
        self._record("incident", "hold", ALARM, target=replica,
                     reason=f"{kind}: scale-down latched for "
                            f"{self.cfg.incident_cooldown_s:g}s",
                     dedup=False)
        self._export_gauges()

    # ----------------------------------------------------------- control
    def freeze(self, on: bool = True, by: str = "manual") -> None:
        if on and not self._frozen:
            self._frozen = True
            self._frozen_since = self._clock()
            self._frozen_by = by
            self._record("freeze", "hold", SUPPRESSED,
                         reason=f"frozen by {by}")
        elif not on and self._frozen:
            self._frozen = False
            self._frozen_since = None
            self._frozen_by = ""
            self._last_quiet_key = None
            self._record("unfreeze", "resume", ACTUATED,
                         reason="actuation re-enabled")
        self._export_gauges()

    def control(self, body: dict) -> dict:
        """The ``POST /autoscale`` hook: ``{"freeze": bool}`` and/or
        ``{"pin": [names]}`` / ``{"unpin": [names]}``. Unknown keys
        raise (→ 400); returns the post-change status."""
        if not isinstance(body, dict):
            raise ValueError("autoscale control body must be a JSON "
                             "object")
        unknown = set(body) - {"freeze", "pin", "unpin"}
        if unknown:
            raise ValueError(f"unknown autoscale control keys: "
                             f"{sorted(unknown)} (know: freeze, pin, "
                             "unpin)")
        if "freeze" in body:
            if not isinstance(body["freeze"], bool):
                raise ValueError('"freeze" must be true or false')
            self.freeze(body["freeze"], by="manual")
        for key, op in (("pin", self._pinned.update),
                        ("unpin", self._pinned.difference_update)):
            if key in body:
                names = body[key]
                if not isinstance(names, list) \
                        or not all(isinstance(n, str) for n in names):
                    raise ValueError(f'"{key}" must be a list of '
                                     "replica names")
                op(names)
        self._export_gauges()
        return self.status()

    def status(self) -> dict:
        """The ``GET /autoscale`` body: live control-loop state plus
        the audit tail. Never raises; safe to scrape."""
        now = self._clock()
        drain = None
        if self._drain is not None:
            victim, deadline, add_role = self._drain
            drain = {"victim": victim,
                     "deadline_in_s": max(0.0, deadline - now),
                     "add_role_after": add_role or None}
        return {
            "enabled": True,
            "frozen": self._frozen,
            "frozen_by": self._frozen_by or None,
            "frozen_for_s": (now - self._frozen_since
                             if self._frozen_since is not None else None),
            "pinned": sorted(self._pinned),
            "evaluations": self.evals,
            "last_eval_t": self._last_eval,
            "streaks": dict(self._streak),
            "cooldown_remaining_s": {
                d: max(0.0, until - now)
                for d, until in self._cooldown_until.items()},
            "flap_budget": self.cfg.flap_budget,
            "flap_budget_remaining": self._flap_budget_remaining(now),
            "incident_latch_remaining_s":
                max(0.0, self._incident_until - now),
            "draining": drain,
            "decisions": self.audit_entries()[-32:],
            "config": dataclasses.asdict(self.cfg),
        }

    # -------------------------------------------------------------- loop
    def on_step(self) -> None:
        """One fleet iteration's control work: drain progress every
        step (one idle probe), a full evaluation at ``tick_s``
        cadence."""
        now = self._clock()
        if self._drain is not None:
            self._tick_drain(now)
        if self._last_eval is not None \
                and now - self._last_eval < self.cfg.tick_s:
            return
        self._last_eval = now
        self.evals += 1
        self.registry.counter("Fleet/autoscale_evals").inc()
        self._evaluate(now)
        self._export_gauges()

    # The decision order inside one evaluation is deliberate:
    # trust gate -> arm streaks -> load-reversal drain abort ->
    # freeze/fleet-drain holds -> add (safety first) -> incident latch
    # -> rebalance -> remove.
    def _evaluate(self, now: float) -> None:
        fleet = self.fleet
        report = fleet.scaling_report()
        # ---- score-trust gate: no report / unmeasured rho / saturated
        if report is None:
            self._streak = dict.fromkeys(self._streak, 0)
            self._record("signal_untrusted", "hold", ALARM,
                         reason="no scaling report (serving.loadscope "
                                "off, or no replica measured)",
                         inputs=_report_inputs(None), dedup=True)
            return
        what_ifs = {w.get("action"): w
                    for w in (report.get("what_ifs") or [])}
        fleet_agg = report.get("fleet") or {}
        if not what_ifs or fleet_agg.get("rho") is None:
            self._streak = dict.fromkeys(self._streak, 0)
            reasons = sorted({r for s in (report.get("replicas")
                                          or {}).values()
                              for r in (s.get("unmeasured") or [])})
            self._record("signal_untrusted", "hold", ALARM,
                         reason="utilization unmeasured: "
                                + ("; ".join(reasons) or "no what-ifs"),
                         inputs=_report_inputs(report), dedup=True)
            return
        add_wi = what_ifs.get("add_replica")
        rm_wi = what_ifs.get("remove_replica")
        rb_wi = what_ifs.get("rebalance_prefill_decode")
        if add_wi is not None and add_wi.get("saturated_now"):
            # past the knee the queue-wait forecast is null — the
            # estimator cannot price ANY move. Alarm, never actuate.
            self._streak = dict.fromkeys(self._streak, 0)
            self._record("signal_untrusted", "hold", ALARM,
                         reason=f"saturated (rho="
                                f"{fleet_agg.get('rho'):.3f}): forecast "
                                "is null past the knee — operator "
                                "attention required",
                         inputs=_report_inputs(report, add_wi),
                         dedup=True)
            return
        # ---- arm the per-direction streaks (hysteresis state)
        c = self.cfg
        armed_add = (add_wi is not None
                     and add_wi.get("score", 0.0) >= c.add_score_min)
        armed_rm = (rm_wi is not None
                    and rm_wi.get("score", 0.0) >= c.remove_score_min)
        armed_rb = (rb_wi is not None
                    and rb_wi.get("score", 0.0) >= c.rebalance_score_min)
        self._streak["add"] = self._streak["add"] + 1 if armed_add else 0
        self._streak["remove"] = (self._streak["remove"] + 1
                                  if armed_rm else 0)
        self._streak["rebalance"] = (self._streak["rebalance"] + 1
                                     if armed_rb else 0)
        # ---- load reversal beats everything: an armed scale-up signal
        # while a victim drains reopens it immediately (no hysteresis —
        # the drain itself was hysteresis-guarded; keeping capacity is
        # the safe direction)
        if self._drain is not None and armed_add:
            self._abort_drain(reason=f"load reversed mid-drain "
                                     f"(add score "
                                     f"{add_wi.get('score'):.0f} >= "
                                     f"{c.add_score_min:g})",
                              inputs=_report_inputs(report, add_wi))
            return
        if self._frozen:
            if armed_add or armed_rm or armed_rb:
                which = ("add_replica" if armed_add else
                         "remove_replica" if armed_rm else
                         "rebalance_prefill_decode")
                self._record("frozen", which, SUPPRESSED,
                             reason=f"frozen by {self._frozen_by}; "
                                    "signal held",
                             inputs=_report_inputs(
                                 report, what_ifs.get(which)),
                             dedup=True)
            return
        if fleet.draining:
            # a fleet-wide drain (shutdown in progress) outranks the
            # control loop entirely
            if armed_add or armed_rm or armed_rb:
                self._record("fleet_draining", "hold", SUPPRESSED,
                             reason="fleet-wide drain in progress",
                             inputs=_report_inputs(report), dedup=True)
            return
        if self._drain is not None:
            return      # a drain is in flight; one actuation at a time
        # ---- scale up (the safe direction: allowed during the
        # incident latch — failover just REDUCED capacity)
        if armed_add and self._streak["add"] >= c.up_ticks:
            self._try_add(now, report, add_wi)
            return
        # ---- the incident latch gates everything that removes
        # capacity from a role
        if (armed_rm or armed_rb) and now < self._incident_until:
            which = "remove_replica" if armed_rm \
                else "rebalance_prefill_decode"
            self._record("incident_latch", which, SUPPRESSED,
                         reason="scale-down latched after an incident "
                                f"({max(0.0, self._incident_until - now):.0f}s "
                                "remaining) — failover is not a lull",
                         inputs=_report_inputs(report,
                                               what_ifs.get(which)),
                         dedup=True)
            return
        if armed_rb and self._streak["rebalance"] >= c.down_ticks:
            self._try_rebalance(now, report, rb_wi)
            return
        if armed_rm and self._streak["remove"] >= c.down_ticks:
            self._try_remove(now, report, rm_wi)

    # --------------------------------------------------------- actuation
    def _guard_common(self, now: float, direction: str, action: str,
                      inputs: dict) -> bool:
        """Cooldown + flap-budget guards shared by every actuation;
        True = clear to actuate (and the flap, if this is a reversal,
        is booked)."""
        until = self._cooldown_until[direction]
        if now < until:
            self._record("cooldown", action, SUPPRESSED,
                         reason=f"{direction} cooldown "
                                f"({until - now:.0f}s remaining)",
                         inputs=inputs, dedup=True)
            return False
        reversal = (self._last_direction is not None
                    and self._last_direction != direction)
        if reversal:
            if self._flap_budget_remaining(now) <= 0:
                # budget exhausted: freeze the loop rather than keep
                # oscillating — unfreezing is a manual decision
                self._record("flap_budget", action, SUPPRESSED,
                             reason=f"flap budget ({self.cfg.flap_budget}"
                                    f" per {self.cfg.flap_window_s:g}s) "
                                    "exhausted — loop frozen; unfreeze "
                                    "via POST /autoscale",
                             inputs=inputs)
                self.freeze(True, by="flap_budget")
                return False
            self._flaps.append(now)
            self.registry.counter("Fleet/autoscale_flaps").inc()
        return True

    def _try_add(self, now: float, report: dict, wi: dict) -> None:
        fleet = self.fleet
        inputs = _report_inputs(report, wi)
        if len(fleet.replicas) >= self.cfg.max_replicas:
            self._record("max_replicas", "add_replica", SUPPRESSED,
                         reason=f"at max_replicas="
                                f"{self.cfg.max_replicas}; cannot add "
                                "— operator attention required",
                         inputs=inputs, dedup=True)
            return
        if not self._guard_common(now, "up", "add_replica", inputs):
            return
        role = None
        if fleet._disagg:
            # add to the hotter phase; decode when unknown (decode
            # replicas also absorb handoff backlog)
            rp = (report.get("fleet") or {}).get("rho_prefill")
            rd = (report.get("fleet") or {}).get("rho_decode")
            role = (self._roles[1]
                    if rp is not None and rd is not None and rp > rd
                    else self._roles[2])
        name = fleet.add_replica(role=role)
        self._after_actuation(now, "up")
        self.registry.counter("Fleet/autoscale_adds").inc()
        self._record("hysteresis_up", "add_replica", ACTUATED,
                     target=name,
                     reason=f"add score {wi.get('score'):.0f} armed "
                            f"{self._streak['add']} ticks (warm join "
                            "from the shared program cache)",
                     inputs=inputs)
        self._streak["add"] = 0

    def _pick_victim(self, role: "Optional[str]") -> Optional[str]:
        """Least-loaded legally-removable replica of ``role`` (or of
        the fleet when None), skipping pinned names. Ranked best-first
        by the router's own policy — removing the least-loaded victim
        strands the least work."""
        fleet = self.fleet
        killable = set(fleet._killable())
        names = [i["name"] for i in
                 (fleet._ranked(role, admission=False) if role is not None
                  else [j for r in set(fleet.roles.values())
                        for j in fleet._ranked(r, admission=False)])]
        for name in names:
            if name in killable and name not in self._pinned:
                return name
        return None

    def _try_remove(self, now: float, report: dict, wi: dict) -> None:
        fleet = self.fleet
        inputs = _report_inputs(report, wi)
        if len(fleet.replicas) <= self.cfg.min_replicas:
            self._record("min_replicas", "remove_replica", SUPPRESSED,
                         reason=f"at min_replicas="
                                f"{self.cfg.min_replicas}",
                         inputs=inputs, dedup=True)
            return
        if not self._guard_common(now, "down", "remove_replica", inputs):
            return
        role = None
        if fleet._disagg:
            # shed from the colder phase (the hotter one needs its
            # capacity); _killable keeps the last replica of each role
            rp = (report.get("fleet") or {}).get("rho_prefill")
            rd = (report.get("fleet") or {}).get("rho_decode")
            role = (self._roles[1]
                    if rp is not None and rd is not None and rp < rd
                    else self._roles[2])
        victim = self._pick_victim(role)
        if victim is None:
            self._record("no_victim", "remove_replica", SUPPRESSED,
                         reason="no removable un-pinned replica "
                                f"(pinned: {sorted(self._pinned)})",
                         inputs=inputs, dedup=True)
            return
        self._begin_drain(now, victim, add_role="", inputs=inputs,
                          rule="hysteresis_down",
                          reason=f"remove score {wi.get('score'):.0f} "
                                 f"armed {self._streak['remove']} ticks")
        self._streak["remove"] = 0

    def _try_rebalance(self, now: float, report: dict, wi: dict) -> None:
        fleet = self.fleet
        inputs = _report_inputs(report, wi)
        if not fleet._disagg:
            return
        if not self._guard_common(now, "down",
                                  "rebalance_prefill_decode", inputs):
            return
        direction = wi.get("direction") or ""
        donor_role, add_role = (
            (self._roles[2], self._roles[1])
            if direction == "decode_to_prefill"
            else (self._roles[1], self._roles[2]))
        victim = self._pick_victim(donor_role)
        if victim is None:
            self._record("no_victim", "rebalance_prefill_decode",
                         SUPPRESSED,
                         reason=f"no removable {donor_role} donor",
                         inputs=inputs, dedup=True)
            return
        self._begin_drain(now, victim, add_role=add_role, inputs=inputs,
                          rule="rebalance",
                          reason=f"{direction}: score "
                                 f"{wi.get('score'):.0f} armed "
                                 f"{self._streak['rebalance']} ticks")
        self._streak["rebalance"] = 0

    def _begin_drain(self, now: float, victim: str, add_role: str,
                     inputs: dict, rule: str, reason: str) -> None:
        """Drain-before-remove: close the victim's intake; removal
        happens in :meth:`_tick_drain` once idle or at the deadline."""
        fleet = self.fleet
        deadline = now + self.cfg.drain_deadline_s
        fleet.begin_drain_replica(victim)
        self._drain = (victim, deadline, add_role)
        self.registry.counter("Fleet/autoscale_drains").inc()
        self._record(rule,
                     "rebalance_prefill_decode" if add_role
                     else "remove_replica",
                     DRAIN_STARTED, target=victim,
                     reason=reason + f"; drain deadline "
                                     f"{self.cfg.drain_deadline_s:g}s",
                     inputs=inputs)

    def _abort_drain(self, reason: str, inputs: dict) -> None:
        victim, _deadline, add_role = self._drain
        self._drain = None
        if victim in self.fleet.replicas:
            self.fleet.end_drain_replica(victim)
        self.registry.counter("Fleet/autoscale_drain_aborts").inc()
        self._record("load_reversal",
                     "rebalance_prefill_decode" if add_role
                     else "remove_replica",
                     DRAIN_ABORTED, target=victim, reason=reason,
                     inputs=inputs)
        # the reversal consumed the down intent; restart its hysteresis
        self._streak["remove"] = self._streak["rebalance"] = 0
        self._export_gauges()

    def _tick_drain(self, now: float) -> None:
        victim, deadline, add_role = self._drain
        fleet = self.fleet
        eng = fleet.replicas.get(victim)
        if eng is None:
            # removed/killed underneath us (operator or chaos): the
            # on_incident path already recorded the kill case
            self._drain = None
            self._export_gauges()
            return
        idle = eng.sched.idle and eng._prefill is None
        if not idle and now < deadline:
            return
        requeued = fleet.remove_replica(victim)
        self._after_actuation(now, "down")
        self.registry.counter("Fleet/autoscale_removes").inc()
        outcome = REMOVED if idle else REMOVED_AT_DEADLINE
        reason = ("drained clean (nothing requeued)" if idle else
                  f"drain deadline hit; {len(requeued)} stragglers "
                  "requeued onto survivors")
        self._drain = None
        self._record("drain_complete",
                     "rebalance_prefill_decode" if add_role
                     else "remove_replica",
                     outcome, target=victim,
                     reason=reason,
                     inputs={"requeued_rids": list(requeued)})
        if add_role:
            name = fleet.add_replica(role=add_role)
            self.registry.counter("Fleet/autoscale_rebalances").inc()
            self._record("rebalance_join", "add_replica", ACTUATED,
                         target=name,
                         reason=f"rebalance: {victim} removed, {name} "
                                f"joined as {add_role} (warm join)",
                         inputs={})
        self._export_gauges()

    def _after_actuation(self, now: float, direction: str) -> None:
        cd = (self.cfg.cooldown_up_s if direction == "up"
              else self.cfg.cooldown_down_s)
        self._cooldown_until[direction] = now + cd
        self._last_direction = direction
        self._last_actuation_t = now
        self._last_quiet_key = None
