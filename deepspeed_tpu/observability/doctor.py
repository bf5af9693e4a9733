"""Ops triage CLI: ``python -m deepspeed_tpu.observability.doctor``.

Pretty-prints the artifacts the runbooks point at, from files alone (no
running engine, no device):

- the newest Prometheus textfile (``*.prom``) — current gauges;
- the newest per-request log (``*.requests.jsonl``) — last requests,
  grouped by terminal status;
- the newest flight record (``flight_*/``) — reason, markers, the
  slowest spans, and where the trace.json lives for Perfetto;
- the newest incident dir (``incident_*/`` — the fleet's correlated
  cross-replica capture) — which replicas dumped, the merged
  cross-replica timeline, the route-audit summary, and where the merged
  Perfetto trace lives;
- the newest capacity report (``CAPACITY_REPORT*.json``) — HBM ledger
  totals and the advisor's ranked levers (docs/OPERATIONS.md
  capacity-planning runbook);
- ``[replay]`` — the newest traffic trace (``*traffic_trace*.jsonl``,
  the record half of record→replay, bundled into flight/incident dumps)
  schema-validated, plus the last replay parity verdict
  (``REPLAY_REPORT*.json`` — ``observability/replay.py``);
- ``[comm]`` — the communication observatory
  (``observability/commscope.py``): exposed/overlap collective
  fractions, per-kind achieved bus bandwidth, and the per-device skew
  table, from the latest .prom; a BURNING straggler gauge gates.
- ``[kv]`` — the KV residency observatory
  (``observability/kvscope.py``): eviction-regret rate, session heat,
  hottest evicted sessions, and the ``tiered_kv`` lever verdict from
  the newest capacity report; RUNAWAY regret (regret_frac above
  ``--kv-regret-max``) gates.

Exit code is the CI/cron gate: **nonzero** when the newest flight record
contains a why-marker (watchdog stall, SLO breach, anomaly, compile
storm — something fired since the record was cut), when any
``dstpu_*_burn`` SLO gauge in the latest .prom is above zero, when
the newest incident dir is UNRECONCILED (per-replica dumps from fewer
replicas than the fleet had live — the post-mortem is incomplete), when
the newest traffic trace is invalid or the last replay verdict is a
parity FAILURE, or when a straggler gauge is burning
(``dstpu_train_straggler_active`` > 0); 0 on a clean replica. ``--no-gate``
restores the always-0 report-only behavior. ``--targets`` combined with
``--flight-dir`` runs the incident gate alongside fleet triage.

``--url http://host:port`` switches to **live mode**: instead of files,
the doctor scrapes a running engine's telemetry plane
(``observability/server.py``) — ``/metrics``, ``/healthz``, ``/readyz``,
``/goodput``, the newest flight manifest via ``/flight`` — with the
same gate semantics (burning SLO gauges or why-markers in the newest
flight record exit nonzero). Endpoints the engine doesn't expose (no
goodput ledger, no flight recorder, a training engine's missing
``/requests``) degrade to a note, never an error; an entirely
unreachable target is itself a gate finding.

Usage::

    python -m deepspeed_tpu.observability.doctor [--dir ./monitor]
        [--flight-dir <dir>] [--requests N] [--no-gate]
        [--url http://host:port] [--timeout S]

Stdout is this module's interface (it is a CLI report tool, exempt from
the bare-print lint like ``env_report.py``).
"""

from __future__ import annotations

import argparse
import json
import math
from collections import Counter as _Counter
from pathlib import Path
from typing import Optional


def _newest(dirpath: Path, pattern: str):
    cands = sorted(dirpath.glob(pattern),
                   key=lambda p: (p.stat().st_mtime, p.name))
    return cands[-1] if cands else None




def _fmt(v: float) -> str:
    if isinstance(v, float) and not math.isfinite(v):
        from .sinks import format_prometheus_value

        return format_prometheus_value(v)     # the NaN/+Inf/-Inf spellings
    if isinstance(v, float) and v and abs(v) < 1e-3:
        return f"{v:.3e}"
    return f"{v:g}" if isinstance(v, float) else str(v)


def _print_metrics(vals: dict, where: str) -> list:
    """Shared by file and live modes: print every metric (serving
    first, then training, then the rest — a process that both trains
    and serves shows both halves) and return the gate findings: every
    SLO burn gauge currently above zero. One implementation so the two
    modes cannot drift on what gates."""
    shown: set[str] = set()
    for prefix in ("dstpu_serve_", "dstpu_train_", ""):
        for k, v in sorted(vals.items()):
            if k.startswith(prefix) and k not in shown:
                shown.add(k)
                print(f"  {k:<44s} {_fmt(v)}")
    return [f"SLO burn gauge {k} = {_fmt(v)} {where}"
            for k, v in sorted(vals.items())
            if k.endswith("_burn") and "_slo_" in k
            and isinstance(v, float) and v > 0]


def report_prometheus(d: Path) -> list:
    """Print the latest .prom; returns gate findings — every SLO burn
    gauge (``dstpu_*_burn``) currently above zero."""
    from .sinks import parse_prometheus_textfile

    prom = _newest(d, "*.prom")
    if prom is None:
        print(f"[prom] no *.prom under {d}")
        return []
    vals = parse_prometheus_textfile(prom.read_text())
    print(f"[prom] {prom} ({len(vals)} metrics)")
    return _print_metrics(vals, f"in {prom.name}")


def report_requests(d: Path, limit: int) -> None:
    log = _newest(d, "*.requests.jsonl")
    if log is None:
        print(f"[requests] no *.requests.jsonl under {d}")
        return
    from .flight import load_jsonl_tolerant

    rows, skipped = load_jsonl_tolerant(log)
    by_status = _Counter(r.get("status", "?") for r in rows)
    torn = f" {skipped} torn line(s) skipped" if skipped else ""
    print(f"[requests] {log} ({len(rows)} records){torn} "
          + " ".join(f"{k}={n}" for k, n in sorted(by_status.items())))
    for r in rows[-limit:]:
        ttft = r.get("ttft_s")
        qw = r.get("queue_wait_s")
        print(f"  rid={str(r.get('rid')):<6} {r.get('status', '?'):<10} "
              f"tokens={r.get('tokens')} "
              f"ttft={_fmt(ttft) if ttft is not None else '-'} "
              f"queue_wait={_fmt(qw) if qw is not None else '-'}"
              + (f" error={r['error']}" if r.get("error") else ""))


def report_flight(d: Path, slow: int = 5) -> list:
    """Print the newest flight record; returns gate findings — the
    why-markers it contains (a record with markers means something
    fired: watchdog stall, SLO breach, anomaly, compile storm)."""
    from .flight import newest_flight_record, read_flight_record

    rec_dir = newest_flight_record(d)
    if rec_dir is None:
        print(f"[flight] no flight_* record under {d}")
        return []
    rec = read_flight_record(rec_dir)
    mf = rec["manifest"]
    print(f"[flight] {rec_dir}")
    print(f"  reason={mf.get('reason')} at {mf.get('wall_time')} "
          f"events={mf.get('events')} requests={mf.get('requests')}")
    markers = [e for e in rec["events"] if e.get("kind") == "marker"]
    for m in markers[-8:]:
        meta = dict(m.get("meta", {}))
        name = meta.pop("name", "?")
        extra = " ".join(f"{k}={_fmt(v) if isinstance(v, float) else v}"
                         for k, v in meta.items())
        print(f"  marker t={m['t0']:.6g} {name} {extra}".rstrip())
    spans = [e for e in rec["events"] if "t1" in e]
    spans.sort(key=lambda e: e["t1"] - e["t0"], reverse=True)
    if spans:
        print(f"  slowest spans (of {len(spans)}):")
        for e in spans[:slow]:
            who = " ".join(f"{k}={e[k]}" for k in ("rid", "slot", "step")
                           if k in e)
            print(f"    {e['kind']:<14s} {e['t1'] - e['t0']:.6g}s {who}")
    if rec.get("trace") is not None:
        print(f"  perfetto: load {rec_dir}/trace.json at "
              "https://ui.perfetto.dev")
    names = sorted({str(dict(m.get("meta", {})).get("name", "?"))
                    for m in markers})
    if names:
        return [f"flight record {rec_dir.name} contains why-marker(s): "
                + ", ".join(names)]
    return []


def newest_incident_dir(d: Path) -> Optional[Path]:
    """Most recent ``incident_*`` directory (the fleet's correlated
    cross-replica capture — serving/fleet.py) under ``d``, or None."""
    if not d.is_dir():
        return None
    cands = [p for p in d.iterdir()
             if p.is_dir() and p.name.startswith("incident_")]
    if not cands:
        return None
    return max(cands, key=lambda p: (p.stat().st_mtime, p.name))


def report_incidents(d: Path, events: int = 12) -> list:
    """Print the newest incident dir and reconstruct the cross-replica
    timeline (every replica's dumped events + the fleet ring, merged by
    timestamp — all rings share the fleet's injectable clock). Gate
    finding: an UNRECONCILED incident — per-replica dumps from fewer
    replicas than the fleet had live when it opened (a replica's
    recorder hit max_dumps, an unwritable disk, or a crash mid-fan-out:
    the post-mortem is incomplete and someone should know)."""
    from .flight import load_jsonl_tolerant

    inc = newest_incident_dir(d)
    if inc is None:
        return []
    findings: list = []
    try:
        mf = json.loads((inc / "incident.json").read_text(errors="replace"))
    except (OSError, json.JSONDecodeError):
        mf = {}
    if not isinstance(mf, dict):
        mf = {}
    live = mf.get("replicas_live")
    expected = mf.get("replicas") if isinstance(mf.get("replicas"), list) \
        else []
    # a replica's dump is real only when its subdir carries a manifest —
    # an empty directory left by a crashed dump does not reconcile
    sub = sorted(p.name for p in inc.iterdir()
                 if p.is_dir() and p.name != "fleet"
                 and (p / "manifest.json").exists())
    print(f"[incident] {inc}")
    print(f"  id={mf.get('incident_id', inc.name)} "
          f"reason={mf.get('reason')} "
          f"trigger={mf.get('trigger_replica')} at {mf.get('wall_time')}")
    print(f"  replica dumps: {len(sub)}/{live if live is not None else '?'}"
          f" live ({', '.join(sub) or 'none'})")
    if isinstance(live, int) and len(sub) < live:
        missing = sorted(set(str(n) for n in expected) - set(sub))
        findings.append(
            f"unreconciled incident {inc.name}: dumps from {len(sub)} of "
            f"{live} live replicas"
            + (f" (missing: {', '.join(missing)})" if missing else ""))
    # cross-replica timeline: merge the dumped rings by t0 (one shared
    # injectable clock), label each event with where it happened
    rows: list = []
    for name in sub:
        p = inc / name / "events.jsonl"
        if p.exists():
            evs, _ = load_jsonl_tolerant(p)
            rows += [(e.get("t0", 0.0), name, e) for e in evs
                     if isinstance(e, dict)]
    fev = inc / "fleet" / "events.jsonl"
    if fev.exists():
        evs, _ = load_jsonl_tolerant(fev)
        rows += [(e.get("t0", 0.0), "fleet", e) for e in evs
                 if isinstance(e, dict)]
    rows.sort(key=lambda r: r[0])
    if rows:
        print(f"  timeline (last {min(events, len(rows))} of {len(rows)} "
              "events across replicas):")
        for t0, who, e in rows[-events:]:
            kind = e.get("kind", "?")
            if kind == "marker":
                kind = f"marker:{dict(e.get('meta', {})).get('name', '?')}"
            extra = " ".join(f"{k}={e[k]}" for k in ("rid", "slot", "step")
                             if k in e)
            meta = dict(e.get("meta", {}))
            status = meta.get("status")
            if status:
                extra = (extra + f" status={status}").strip()
            print(f"    t={t0:<12.6g} [{who:>8s}] {kind:<18s} "
                  f"{extra}".rstrip())
    audit = inc / "fleet" / "route_audit.jsonl"
    if audit.exists():
        entries, _ = load_jsonl_tolerant(audit)
        by_ev = _Counter(e.get("event", "?") for e in entries)
        print("  route audit: " + " ".join(f"{k}={n}" for k, n
                                           in sorted(by_ev.items())))
    tr = inc / "fleet" / "trace_merged.json"
    if tr.exists():
        print(f"  perfetto: load {tr} at https://ui.perfetto.dev "
              "(replicas as processes, requests as flows)")
    return findings


def _newest_trace_file(dirs) -> Optional[Path]:
    """Newest traffic-trace JSONL across the given dirs, searched
    recursively — traces live beside the monitor artifacts AND inside
    flight/incident dumps (the capture ring's tail)."""
    cands: list[Path] = []
    seen: set = set()
    for d in dirs:
        d = Path(d)
        if not d.is_dir() or d in seen:
            continue
        seen.add(d)
        cands += [p for p in d.rglob("*traffic_trace*.jsonl")
                  if p.is_file()]
    if not cands:
        return None
    return max(cands, key=lambda p: (p.stat().st_mtime, str(p)))


def report_replay(dirs) -> list:
    """Print the ``[replay]`` picture: the newest traffic trace
    (present/valid, event counts) and the last replay parity verdict.
    Gate findings: an INVALID trace (the incident is not replayable as
    recorded) and a parity-FAILED replay report (same traffic, different
    bits — the regression the replay loop exists to catch)."""
    from .replay import TrafficTrace

    findings: list = []
    tr_path = _newest_trace_file(dirs)
    if tr_path is None:
        print(f"[replay] no traffic trace under {', '.join(map(str, dirs))}")
    else:
        tr = TrafficTrace.read(tr_path)
        problems = tr.validate()
        torn = f" {tr.torn_lines} torn line(s)" if tr.torn_lines else ""
        print(f"[replay] {tr_path}")
        print(f"  requests={len(tr.requests)} results={len(tr.results)} "
              f"chaos={len(tr.chaos_events)}"
              f" dropped={tr.meta.get('dropped_events', 0)}{torn}")
        if problems:
            for p in problems[:4]:
                print(f"  INVALID: {p}")
            findings.append(
                f"traffic trace {tr_path.name} is invalid "
                f"({len(problems)} schema problems)")
    rep_path = None
    for d in dirs:
        cand = _newest(Path(d), "REPLAY_REPORT*.json") \
            if Path(d).is_dir() else None
        if cand is not None and (rep_path is None
                                 or cand.stat().st_mtime
                                 > rep_path.stat().st_mtime):
            rep_path = cand
    if rep_path is None:
        print("[replay] no REPLAY_REPORT*.json (no replay run yet — see "
              "docs/OPERATIONS.md incident-replay runbook)")
        return findings
    try:
        rep = json.loads(rep_path.read_text(errors="replace"))
    except (OSError, json.JSONDecodeError) as e:
        print(f"[replay] {rep_path} unreadable ({e!r})")
        return findings
    rep = rep if isinstance(rep, dict) else {}
    parity = rep.get("parity")
    verdict = {True: "PARITY", False: "DIVERGED",
               None: "no oracle (trace carried no recorded outputs)"}
    print(f"[replay] last replay {rep_path.name}: "
          f"{verdict.get(parity, parity)} — "
          f"matched {rep.get('matched')}/{rep.get('requests')}, "
          f"{len(rep.get('diverged') or [])} diverged, "
          f"chaos applied {rep.get('chaos_applied')}")
    if parity is False:
        div = rep.get("diverged") or []
        rids = ", ".join(str(x.get("rid")) for x in div[:8]
                         if isinstance(x, dict))
        findings.append(
            f"replay parity FAILED in {rep_path.name}: "
            f"{len(div)} request(s) diverged"
            + (f" (rids {rids})" if rids else ""))
    return findings


def report_capacity(d: Path, levers: int = 4) -> None:
    """Print the newest capacity report's ledger totals + ranked advisor
    levers (informational — the advisor ranks levers, it doesn't gate)."""
    import json

    from .capacity import validate_capacity_report

    rep_path = _newest(d, "CAPACITY_REPORT*.json")
    if rep_path is None:
        print(f"[capacity] no CAPACITY_REPORT*.json under {d}")
        return
    try:
        rep = json.loads(rep_path.read_text(errors="replace"))
    except (OSError, json.JSONDecodeError) as e:
        print(f"[capacity] {rep_path} unreadable ({e!r})")
        return
    errs = validate_capacity_report(rep)
    valid = "" if not errs else f" INVALID ({len(errs)} schema problems)"
    print(f"[capacity] {rep_path}{valid}")
    if not isinstance(rep, dict):
        return
    led = rep.get("ledger")
    led = led if isinstance(led, dict) else {}
    gib = 1 << 30
    for k in ("weights_bytes", "kv_bytes", "temp_bytes", "total_bytes",
              "limit_bytes", "headroom_bytes"):
        v = led.get(k)
        print(f"  {k:<28s} "
              + (f"{v / gib:.3f} GiB" if isinstance(v, (int, float))
                 else "unknown"))
    for k in ("projected_max_slots", "projected_max_context"):
        print(f"  {k:<28s} {led.get(k)}")
    adv = rep.get("advisor")
    lvs = adv.get("levers") if isinstance(adv, dict) else None
    for i, lv in enumerate((lvs if isinstance(lvs, list) else [])[:levers]):
        # an INVALID report's levers still print, field by field — the
        # triage contract is degrade, never crash on a torn artifact
        lv = lv if isinstance(lv, dict) else {}
        score = lv.get("score")
        if isinstance(score, (int, float)):
            score = _fmt(float(score))
        print(f"  #{i + 1} {str(lv.get('name')):<22s} "
              f"score={score}  {lv.get('why') or ''}")


def report_comm(d: Path) -> list:
    """Print the ``[comm]`` picture from the latest .prom — the
    communication observatory's gauges (``observability/commscope.py``):
    exposed/overlap fractions, per-kind achieved bus bandwidth, and the
    per-device skew table. Gate finding: a BURNING straggler gauge
    (``dstpu_train_straggler_active`` > 0 — a device is currently
    dragging every step; docs/OPERATIONS.md "diagnosing a slow multichip
    step")."""
    from .sinks import parse_prometheus_textfile

    prom = _newest(d, "*.prom")
    if prom is None:
        return []
    vals = parse_prometheus_textfile(prom.read_text())
    comm = {k: v for k, v in vals.items() if k.startswith("dstpu_comm_")}
    strag = {k: v for k, v in vals.items()
             if k.startswith("dstpu_train_straggler_")}
    if not comm and not strag:
        return []          # no observatory ran: no section, no gate
    print(f"[comm] {prom.name}")
    for key, label in (("dstpu_comm_exposed_frac", "exposed_comm_frac"),
                       ("dstpu_comm_overlap_frac", "overlap_frac"),
                       ("dstpu_comm_exposed_s", "exposed_s"),
                       ("dstpu_comm_collective_s", "collective_s")):
        if key in comm:
            print(f"  {label:<24s} {_fmt(comm[key])}")
    for k in sorted(comm):
        if k.endswith(("_busbw_gbps", "_algbw_gbps", "_roofline")):
            print(f"  {k.replace('dstpu_comm_', ''):<34s} {_fmt(comm[k])}")
    findings: list = []
    active = strag.get("dstpu_train_straggler_active")
    skews = sorted((k, v) for k, v in strag.items()
                   if "_skew_s_d" in k)
    if skews:
        print("  per-device skew (s):")
        for k, v in skews:
            dev = k.rsplit("_d", 1)[-1]
            print(f"    device {dev:<6s} {_fmt(v)}")
    if isinstance(active, float) and active > 0:
        dev = strag.get("dstpu_train_straggler_device")
        worst = strag.get("dstpu_train_straggler_skew_s")
        print(f"  STRAGGLER burning: device={_fmt(dev) if dev is not None else '?'} "
              f"skew={_fmt(worst) if worst is not None else '?'}s")
        findings.append(
            "straggler gauge burning in " + prom.name
            + (f": device {_fmt(dev)}" if dev is not None else "")
            + (f" skew {_fmt(worst)}s" if worst is not None else ""))
    eps = strag.get("dstpu_train_straggler_episodes")
    if eps:
        print(f"  straggler episodes (lifetime): {_fmt(eps)}")
    return findings


def report_kv(d: Path, regret_max: float = 0.5) -> list:
    """Print the ``[kv]`` picture — the KV residency observatory
    (``observability/kvscope.py``): eviction-regret rate, session heat,
    the hottest evicted sessions, and the ``tiered_kv`` lever verdict
    from the newest capacity report. Gate finding: RUNAWAY REGRET — the
    regretted share of prefill work (``dstpu_serve_eviction_regret_frac``
    in the latest .prom) above ``regret_max``: the pool is thrashing and
    every resume re-pays its prefill (docs/OPERATIONS.md "sizing the
    host KV tier from the regret ledger")."""
    from .sinks import parse_prometheus_textfile

    prom = _newest(d, "*.prom")
    if prom is None:
        return []
    vals = parse_prometheus_textfile(prom.read_text())
    kv = {k: v for k, v in vals.items()
          if k.startswith(("dstpu_serve_eviction_regret",
                           "dstpu_serve_kv_", "dstpu_serve_session",
                           "dstpu_serve_host_tier",
                           "dstpu_serve_nvme_", "dstpu_serve_demote_ahead",
                           "dstpu_fleet_affinity_regret",
                           "dstpu_fleet_resume_regret"))}
    if not kv:
        return []          # no observatory ran: no section, no gate
    print(f"[kv] {prom.name}")
    for key, label in (
            ("dstpu_serve_eviction_regret_tokens", "regret_tokens"),
            ("dstpu_serve_eviction_regret_frac", "regret_frac"),
            ("dstpu_serve_kv_ghost_entries", "ghost_entries"),
            ("dstpu_serve_sessions_active", "sessions_active"),
            ("dstpu_serve_sessions_idle", "sessions_idle"),
            ("dstpu_serve_sessions_dead", "sessions_dead"),
            ("dstpu_serve_session_resumed", "session_resumes"),
            ("dstpu_serve_session_regret_resumes", "regret_resumes"),
            ("dstpu_serve_session_idle_kv_byte_s", "idle_kv_byte_s"),
            ("dstpu_fleet_affinity_regret", "fleet_affinity_regret"),
            ("dstpu_serve_host_tier_pages", "host_tier_pages"),
            ("dstpu_serve_host_tier_bytes", "host_tier_bytes"),
            ("dstpu_serve_host_tier_occupancy", "host_tier_occupancy"),
            ("dstpu_serve_host_tier_restores", "host_tier_restores"),
            ("dstpu_serve_host_tier_restored_tokens",
             "host_restored_tokens"),
            ("dstpu_serve_host_tier_prunes", "host_tier_prunes"),
            ("dstpu_serve_host_tier_fallbacks", "host_tier_fallbacks"),
            ("dstpu_serve_session_host_restored_resumes",
             "host_restored_resumes"),
            ("dstpu_serve_host_tier_staged_ahead", "staged_ahead_pages"),
            ("dstpu_serve_host_tier_demote_wait_s", "demote_wait_s"),
            ("dstpu_serve_demote_ahead_staged", "demote_ahead_staged"),
            ("dstpu_serve_demote_ahead_fastfrees",
             "demote_ahead_fastfrees"),
            ("dstpu_serve_nvme_tier_pages", "nvme_tier_pages"),
            ("dstpu_serve_nvme_tier_bytes", "nvme_tier_bytes"),
            ("dstpu_serve_nvme_tier_occupancy", "nvme_tier_occupancy"),
            ("dstpu_serve_nvme_tier_promotions", "nvme_promotions"),
            ("dstpu_serve_host_tier_spills", "nvme_spilled_in"),
            ("dstpu_serve_nvme_tier_fallbacks", "nvme_tier_fallbacks"),
            ("dstpu_serve_nvme_aio_errors", "nvme_aio_errors")):
        if key in kv:
            print(f"  {label:<24s} {_fmt(kv[key])}")
    # host-tier verdict: restores without fallbacks is the tier working;
    # pressure means the next demotion prunes cold history
    if "dstpu_serve_host_tier_pages" in kv:
        pressed = kv.get("dstpu_serve_host_tier_pressure")
        fb = kv.get("dstpu_serve_host_tier_fallbacks") or 0
        verdict = ("DEGRADED: lost/corrupt host copies" if fb
                   else "under pressure (next demotion prunes)"
                   if pressed else "clean")
        print(f"  host tier verdict: {verdict}")
    # NVMe rung verdict beside it: promotions without fallbacks/errors
    # is the disk rung working (host prune spills instead of losing
    # history); aio errors mean the transport itself is failing
    if "dstpu_serve_nvme_tier_pages" in kv:
        nfb = kv.get("dstpu_serve_nvme_tier_fallbacks") or 0
        nae = kv.get("dstpu_serve_nvme_aio_errors") or 0
        npr = kv.get("dstpu_serve_nvme_tier_pressure")
        verdict = ("DEGRADED: aio transport errors" if nae
                   else "DEGRADED: torn/corrupt disk copies" if nfb
                   else "under pressure (next spill prunes)"
                   if npr else "clean")
        print(f"  nvme tier verdict: {verdict}")
    # hottest evicted sessions + the lever verdict come from the newest
    # capacity report's kvscope section (per-session data never lands in
    # the scalar exposition)
    rep_path = _newest(d, "CAPACITY_REPORT*.json")
    if rep_path is not None:
        try:
            rep = json.loads(rep_path.read_text(errors="replace"))
        except (OSError, json.JSONDecodeError):
            rep = {}
        rep = rep if isinstance(rep, dict) else {}
        ks = rep.get("kvscope")
        ks = ks if isinstance(ks, dict) else {}
        hot = (ks.get("sessions") or {}).get("hottest") or []
        if hot:
            print("  hottest evicted sessions (regretted tokens):")
            for h in hot[:5]:
                h = h if isinstance(h, dict) else {}
                print(f"    {str(h.get('session')):<16s} "
                      f"regret={h.get('regret_tokens')} "
                      f"resumes={h.get('resumes')} "
                      f"state={h.get('state')}")
        adv = rep.get("advisor")
        lvs = adv.get("levers") if isinstance(adv, dict) else None
        for lv in (lvs if isinstance(lvs, list) else []):
            lv = lv if isinstance(lv, dict) else {}
            if lv.get("name") == "tiered_kv":
                score = lv.get("score")
                print(f"  tiered_kv lever: score="
                      f"{_fmt(float(score)) if isinstance(score, (int, float)) else score}"
                      f"  {lv.get('why') or ''}")
    findings: list = []
    frac = kv.get("dstpu_serve_eviction_regret_frac")
    if isinstance(frac, float) and frac > regret_max:
        print(f"  RUNAWAY REGRET: {_fmt(frac)} of prefill work re-paid "
              f"because of evictions (gate at {regret_max:g})")
        findings.append(
            f"runaway eviction regret in {prom.name}: regret_frac "
            f"{_fmt(frac)} > {regret_max:g} — the KV pool is thrashing; "
            "see the tiered_kv lever / host-tier sizing runbook")
    fb = kv.get("dstpu_serve_host_tier_fallbacks")
    if isinstance(fb, (int, float)) and fb > 0:
        print(f"  HOST-TIER FALLBACKS: {_fmt(fb)} lost/corrupt host "
              "copies degraded to recompute")
        findings.append(
            f"host-tier fallbacks in {prom.name}: {_fmt(fb)} demoted KV "
            "copies failed verification and were recomputed — host "
            "memory corruption or a torn demotion; serving degraded "
            "safely but the tier is not trustworthy")
    nfb = kv.get("dstpu_serve_nvme_tier_fallbacks")
    if isinstance(nfb, (int, float)) and nfb > 0:
        print(f"  NVME-TIER FALLBACKS: {_fmt(nfb)} torn/corrupt/missing "
              "disk copies degraded to recompute")
        findings.append(
            f"nvme-tier fallbacks in {prom.name}: {_fmt(nfb)} disk KV "
            "copies failed CRC/read verification and were recomputed — "
            "torn writes or a failing device; serving degraded safely "
            "but the disk rung is not trustworthy")
    nae = kv.get("dstpu_serve_nvme_aio_errors")
    if isinstance(nae, (int, float)) and nae > 0:
        print(f"  NVME AIO ERRORS: {_fmt(nae)} async I/O "
              "submit/wait failures (ds_aio_errors)")
        findings.append(
            f"nvme aio errors in {prom.name}: {_fmt(nae)} async I/O "
            "operations failed on the swap files — check the "
            "serving.nvme_path mount (space, permissions, device "
            "health); the tier degrades to recompute but disk "
            "bandwidth is being wasted")
    return findings


def report_load(d: Path, rho_max: float = 0.9) -> list:
    """Print the ``[load]`` picture — the arrival & scaling observatory
    (``observability/loadscope.py``): arrival rate / burstiness / trend,
    utilization ρ per engine, the SLO time-to-violation horizon, and
    the ``scaling`` lever verdict from the newest capacity report. Gate
    finding: SUSTAINED OVERLOAD — utilization at or above ``rho_max``
    with queue pressure (a non-empty queue or a rising arrival trend)
    and a finite time-to-violation: the fleet is trending into SLO burn
    and needs a scale-out (docs/OPERATIONS.md "deciding when to
    scale")."""
    from .sinks import parse_prometheus_textfile

    prom = _newest(d, "*.prom")
    if prom is None:
        return []
    vals = parse_prometheus_textfile(prom.read_text())
    load = {k: v for k, v in vals.items()
            if k.startswith(("dstpu_serve_arrival_",
                             "dstpu_serve_offered_tokens_per_s",
                             "dstpu_serve_utilization",
                             "dstpu_serve_predicted_queue_wait_s",
                             "dstpu_serve_slo_ttv_s",
                             "dstpu_fleet_arrival_",
                             "dstpu_fleet_offered_",
                             "dstpu_fleet_utilization_max",
                             "dstpu_fleet_slo_ttv_min_s"))}
    if not load:
        return []          # no observatory ran: no section, no gate
    print(f"[load] {prom.name}")
    for key, label in (
            ("dstpu_serve_arrival_rate_per_s", "arrival_rate_per_s"),
            ("dstpu_serve_arrival_cv", "interarrival_cv"),
            ("dstpu_serve_arrival_trend_per_s2", "arrival_trend_per_s2"),
            ("dstpu_serve_offered_tokens_per_s", "offered_tokens_per_s"),
            ("dstpu_serve_utilization", "utilization_rho"),
            ("dstpu_serve_predicted_queue_wait_s", "pred_queue_wait_s"),
            ("dstpu_serve_slo_ttv_s", "slo_ttv_s"),
            ("dstpu_fleet_arrival_rate_per_s", "fleet_arrival_per_s"),
            ("dstpu_fleet_offered_tokens_per_s", "fleet_offered_tok_s"),
            ("dstpu_fleet_utilization_max", "fleet_utilization_max"),
            ("dstpu_fleet_slo_ttv_min_s", "fleet_slo_ttv_min_s")):
        if key in load:
            print(f"  {label:<24s} {_fmt(load[key])}")
    # per-replica ρ table + the advisor verdict come from the newest
    # capacity report's loadscope section / scaling lever
    rep_path = _newest(d, "CAPACITY_REPORT*.json")
    if rep_path is not None:
        try:
            rep = json.loads(rep_path.read_text(errors="replace"))
        except (OSError, json.JSONDecodeError):
            rep = {}
        rep = rep if isinstance(rep, dict) else {}
        ls = rep.get("loadscope")
        ls = ls if isinstance(ls, dict) else {}
        reps = ls.get("replicas")
        if isinstance(reps, dict) and reps:
            print("  per-replica utilization:")
            for name, row in sorted(reps.items()):
                row = row if isinstance(row, dict) else {}
                u = row.get("utilization") or {}
                rho = u.get("rho")
                print(f"    {str(name):<12s} "
                      f"rho={_fmt(rho) if isinstance(rho, (int, float)) else 'unmeasured'} "
                      f"wait={u.get('predicted_queue_wait_s')}")
        adv = rep.get("advisor")
        lvs = adv.get("levers") if isinstance(adv, dict) else None
        for lv in (lvs if isinstance(lvs, list) else []):
            lv = lv if isinstance(lv, dict) else {}
            if lv.get("name") == "scaling":
                score = lv.get("score")
                rec = (lv.get("estimate") or {}).get("recommendation") \
                    if isinstance(lv.get("estimate"), dict) else None
                print(f"  scaling lever: score="
                      f"{_fmt(float(score)) if isinstance(score, (int, float)) else score}"
                      + (f"  recommends {rec}" if rec else "")
                      + f"  {lv.get('why') or ''}")
    findings: list = []
    rho = max((v for k, v in load.items()
               if k in ("dstpu_serve_utilization",
                        "dstpu_fleet_utilization_max")
               and isinstance(v, float)), default=None)
    ttv = min((v for k, v in load.items()
               if k in ("dstpu_serve_slo_ttv_s",
                        "dstpu_fleet_slo_ttv_min_s")
               and isinstance(v, float)), default=None)
    trend = load.get("dstpu_serve_arrival_trend_per_s2")
    qd = vals.get("dstpu_serve_queue_depth")
    pressure = (isinstance(qd, float) and qd > 0) \
        or (isinstance(trend, float) and trend > 0)
    if rho is not None and rho >= rho_max and pressure \
            and ttv is not None:
        print(f"  SUSTAINED OVERLOAD: rho {_fmt(rho)} >= {rho_max:g} "
              f"with queue pressure and TTV {_fmt(ttv)}s")
        findings.append(
            f"sustained overload in {prom.name}: utilization {_fmt(rho)} "
            f">= {rho_max:g} with queue pressure and a finite "
            f"time-to-violation ({_fmt(ttv)}s) — trending into SLO burn; "
            "see the scaling lever / deciding-when-to-scale runbook")
    return findings


def report_autoscale(d: Path, frozen_max: float = 900.0) -> list:
    """Print the ``[autoscale]`` picture — the elastic autoscaler's
    control-loop state (``serving/autoscaler.py``) from the newest
    ``Fleet/autoscale_*`` gauges. Gate findings: FLAP BUDGET EXHAUSTED
    (the loop hit its reversal budget and froze itself — traffic is
    oscillating around a threshold; widen the hysteresis or cooldowns,
    docs/OPERATIONS.md "running the autoscaler") and FROZEN STALE (the
    loop has been frozen longer than ``frozen_max`` seconds — a deploy
    freeze somebody forgot to lift, or a flap freeze nobody triaged)."""
    from .sinks import parse_prometheus_textfile

    prom = _newest(d, "*.prom")
    if prom is None:
        return []
    vals = parse_prometheus_textfile(prom.read_text())
    auto = {k: v for k, v in vals.items()
            if k.startswith("dstpu_fleet_autoscale_")}
    if not auto:
        return []          # no autoscaler ran: no section, no gate
    print(f"[autoscale] {prom.name}")
    for key, label in (
            ("dstpu_fleet_autoscale_evals", "evaluations"),
            ("dstpu_fleet_autoscale_adds", "adds"),
            ("dstpu_fleet_autoscale_removes", "removes"),
            ("dstpu_fleet_autoscale_rebalances", "rebalances"),
            ("dstpu_fleet_autoscale_drains", "drains_started"),
            ("dstpu_fleet_autoscale_drain_aborts", "drain_aborts"),
            ("dstpu_fleet_autoscale_alarms", "alarms"),
            ("dstpu_fleet_autoscale_suppressed", "suppressed"),
            ("dstpu_fleet_autoscale_flaps", "flaps"),
            ("dstpu_fleet_autoscale_flap_budget_remaining",
             "flap_budget_remaining"),
            ("dstpu_fleet_autoscale_frozen", "frozen"),
            ("dstpu_fleet_autoscale_frozen_stale_s", "frozen_stale_s"),
            ("dstpu_fleet_autoscale_incident_latched",
             "incident_latched"),
            ("dstpu_fleet_autoscale_draining", "drain_in_flight")):
        if key in auto:
            print(f"  {label:<24s} {_fmt(auto[key])}")
    findings: list = []
    remaining = auto.get("dstpu_fleet_autoscale_flap_budget_remaining")
    frozen = auto.get("dstpu_fleet_autoscale_frozen")
    stale = auto.get("dstpu_fleet_autoscale_frozen_stale_s")
    if isinstance(remaining, float) and remaining <= 0 \
            and isinstance(frozen, float) and frozen >= 1:
        print("  FLAP BUDGET EXHAUSTED: the loop froze itself after "
              "too many scale reversals")
        findings.append(
            f"autoscaler flap budget exhausted in {prom.name}: the "
            "control loop froze itself — traffic oscillates around a "
            "threshold; widen hysteresis/cooldowns and unfreeze via "
            "POST /autoscale (docs/OPERATIONS.md)")
    elif isinstance(frozen, float) and frozen >= 1 \
            and isinstance(stale, float) and stale > frozen_max:
        print(f"  FROZEN STALE: frozen {_fmt(stale)}s "
              f"> {frozen_max:g}s")
        findings.append(
            f"autoscaler frozen-stale in {prom.name}: frozen for "
            f"{_fmt(stale)}s (> {frozen_max:g}s) — a forgotten deploy "
            "freeze or untriaged flap freeze; the fleet is not "
            "elastic while frozen")
    return findings


def report_tenants(d: Path, fairness_min: float = 0.0) -> list:
    """Print the ``[tenants]`` picture — the per-tenant cost attribution
    observatory (``observability/tenantscope.py``) from the newest
    .prom's labeled ``dstpu_serve_tenant_*`` series: top consumers by
    completed tokens, the Jain fairness index, and any active
    noisy-neighbor episode. Gate finding: FAIRNESS FLOOR BREACHED —
    the fairness index below ``fairness_min`` (0 disables; Jain's
    index is 1.0 when every tenant gets an equal token share,
    approaching 1/n under full capture by one tenant)."""
    from .expfmt import parse_labels, split_series
    from .sinks import parse_prometheus_textfile

    prom = _newest(d, "*.prom")
    if prom is None:
        return []
    vals = parse_prometheus_textfile(prom.read_text())
    tnt = {k: v for k, v in vals.items()
           if k.startswith("dstpu_serve_tenant_")}
    if not tnt:
        return []          # no tenantscope ran: no section, no gate
    # fold the labeled series into per-tenant rows
    per: dict = {}
    for k, v in tnt.items():
        base, block = split_series(k)
        if not block:
            continue
        tid = parse_labels(block).get("tenant")
        if tid is None:
            continue
        per.setdefault(tid, {})[base] = v
    print(f"[tenants] {prom.name} ({len(per)} tenant(s))")
    top = sorted(per.items(),
                 key=lambda kv: kv[1].get(
                     "dstpu_serve_tenant_completed_tokens", 0.0),
                 reverse=True)
    for tid, row in top[:8]:
        toks = row.get("dstpu_serve_tenant_completed_tokens")
        share = row.get("dstpu_serve_tenant_goodput_share")
        dom = row.get("dstpu_serve_tenant_dominant_share")
        ps = row.get("dstpu_serve_tenant_page_seconds")
        sheds = row.get("dstpu_serve_tenant_sheds")
        print(f"  {tid:<16s} "
              f"tokens={_fmt(toks) if toks is not None else '-'} "
              f"share={_fmt(share) if share is not None else '-'} "
              f"dominant={_fmt(dom) if dom is not None else '-'} "
              f"page_s={_fmt(ps) if ps is not None else '-'}"
              + (f" sheds={_fmt(sheds)}" if sheds else ""))
    jain = tnt.get("dstpu_serve_tenant_fairness_jain")
    if jain is not None:
        print(f"  fairness_jain          {_fmt(jain)}")
    episodes = tnt.get("dstpu_serve_tenant_noisy_episodes")
    active = tnt.get("dstpu_serve_tenant_noisy_active")
    if episodes:
        state = "ACTIVE" if isinstance(active, float) and active >= 1 \
            else "ended"
        print(f"  noisy_neighbor         {_fmt(episodes)} episode(s), "
              f"{state} (triage: docs/OPERATIONS.md)")
    findings: list = []
    if fairness_min > 0 and isinstance(jain, float) \
            and jain < fairness_min:
        print(f"  FAIRNESS FLOOR BREACHED: jain {_fmt(jain)} "
              f"< {fairness_min:g}")
        findings.append(
            f"tenant fairness floor breached in {prom.name}: Jain "
            f"index {_fmt(jain)} < {fairness_min:g} — one tenant is "
            "capturing the fleet; see the noisy-neighbor runbook "
            "(docs/OPERATIONS.md)")
    return findings


# ----------------------------------------------------------- live (--url)
def _http_get(url: str, timeout: float) -> "tuple[Optional[int], str]":
    """(status, body) for a GET; (None, error-repr) when the target is
    unreachable. 4xx/5xx return their status — live-mode triage treats
    a 404 as "endpoint absent", not a failure."""
    from urllib.error import HTTPError, URLError
    from urllib.request import urlopen

    try:
        with urlopen(url, timeout=timeout) as r:
            return int(r.status), r.read().decode("utf-8",
                                                  errors="replace")
    except HTTPError as e:
        try:
            return int(e.code), e.read().decode("utf-8", errors="replace")
        except OSError:
            return int(e.code), ""
    except (URLError, OSError) as e:
        return None, repr(e)


def report_live(url: str, timeout: float = 3.0,
                fairness_min: float = 0.0) -> list:
    """Triage one LIVE engine over its telemetry endpoints; returns gate
    findings with the same semantics as the file mode (burning SLO
    gauges, a breached tenant-fairness floor, why-markers in the newest
    flight record, plus: target unreachable)."""
    from .expfmt import parse_prometheus_textfile

    url = url.rstrip("/")
    findings: list = []
    # ---- /metrics: the live analog of the newest .prom
    code, body = _http_get(url + "/metrics", timeout)
    if code is None:
        print(f"[live] {url} unreachable ({body})")
        return [f"telemetry target {url} unreachable"]
    if code != 200:
        print(f"[live] {url}/metrics -> {code}")
    else:
        vals = parse_prometheus_textfile(body)
        print(f"[live] {url}/metrics ({len(vals)} metrics)")
        findings += _print_metrics(vals, f"at {url}")
    # ---- probes
    for ep in ("/healthz", "/readyz"):
        code, body = _http_get(url + ep, timeout)
        if code is None:
            print(f"[live] {ep} unreachable")
            continue
        try:
            h = json.loads(body)
        except json.JSONDecodeError:
            h = {}
        keys = ("state", "ready", "degraded", "queue_depth", "occupancy",
                "pool_pressure", "global_steps")
        brief = " ".join(f"{k}={h[k]}" for k in keys if k in h)
        print(f"[live] {ep} -> {code} {brief}".rstrip())
    # ---- /goodput: the wall-time decomposition
    code, body = _http_get(url + "/goodput", timeout)
    if code == 200:
        try:
            g = json.loads(body)
        except json.JSONDecodeError:
            g = {}
        wall = g.get("wall_s")
        frac = g.get("goodput_frac")
        print(f"[goodput] wall={_fmt(wall) if wall is not None else '?'}s "
              f"productive={_fmt(g.get('productive_s', 0.0))}s "
              f"frac={_fmt(frac) if frac is not None else '?'}")
        for b, v in sorted((g.get("badput_s") or {}).items()):
            if v:
                print(f"  badput_{b:<12s} {_fmt(v)}s")
    elif code is not None:
        print(f"[goodput] endpoint absent ({code}) — goodput ledger "
              "disabled on this engine")
    # ---- /tenants: the live analog of the [tenants] file section
    code, body = _http_get(url + "/tenants", timeout)
    if code == 200:
        try:
            tr = json.loads(body)
        except json.JSONDecodeError:
            tr = {}
        rows = tr.get("tenants")
        rows = rows if isinstance(rows, dict) else {}
        print(f"[tenants] {len(rows)} tenant(s)")
        top = sorted(rows.items(),
                     key=lambda kv: (kv[1] or {}).get(
                         "completed_tokens", 0) or 0, reverse=True)
        for tid, row in top[:8]:
            row = row if isinstance(row, dict) else {}
            share = row.get("goodput_share")
            print(f"  {str(tid):<16s} "
                  f"tokens={row.get('completed_tokens')} "
                  f"share={_fmt(share) if isinstance(share, float) else '-'} "
                  f"sheds={row.get('sheds')}")
        fair = tr.get("fairness")
        jain = fair.get("jain") if isinstance(fair, dict) else None
        if jain is not None:
            print(f"  fairness_jain          {_fmt(float(jain))}")
        noisy = tr.get("noisy")
        noisy = noisy if isinstance(noisy, dict) else {}
        if noisy.get("episodes"):
            state = "ACTIVE" if noisy.get("active") else "ended"
            print(f"  noisy_neighbor         {noisy['episodes']} "
                  f"episode(s), {state}")
        if fairness_min > 0 and isinstance(jain, (int, float)) \
                and jain < fairness_min:
            print(f"  FAIRNESS FLOOR BREACHED: jain {_fmt(float(jain))} "
                  f"< {fairness_min:g}")
            findings.append(
                f"tenant fairness floor breached at {url}: Jain index "
                f"{_fmt(float(jain))} < {fairness_min:g} — one tenant "
                "is capturing the fleet; see the noisy-neighbor "
                "runbook (docs/OPERATIONS.md)")
    elif code is not None:
        print(f"[tenants] endpoint absent ({code}) — tenantscope "
              "disabled on this engine (set serving.tenantscope)")
    # ---- /flight: newest manifest + why-markers (the live flight gate)
    code, body = _http_get(url + "/flight", timeout)
    if code == 200:
        try:
            fl = json.loads(body)
        except json.JSONDecodeError:
            fl = {}
        newest = fl.get("newest")
        if newest:
            mf = newest.get("manifest") or {}
            print(f"[flight] newest {newest.get('path')} "
                  f"reason={mf.get('reason')} events={mf.get('events')}")
            names = [str(n) for n in newest.get("markers", [])]
            if names:
                findings.append(
                    f"flight record at {url} contains why-marker(s): "
                    + ", ".join(sorted(names)))
        else:
            print(f"[flight] recorder configured, no dumps yet "
                  f"({len(fl.get('dumps', []))} taken)")
    elif code is not None:
        print(f"[flight] endpoint absent ({code}) — no flight recorder "
              "on this engine")
    return findings


def report_fleet(targets: list, timeout: float = 3.0) -> list:
    """Fleet triage (``--targets a,b,c``): one
    :class:`~.fleet_scrape.FleetScraper` pass over N engine telemetry
    endpoints plus each live target's ``/flight`` manifest, with the
    SAME gate semantics as single-engine triage — findings are every
    DOWN replica, every burning SLO gauge anywhere, and every flight
    record carrying why-markers. A dead target is a finding, never an
    exception (the scraper's degradation contract)."""
    from .fleet_scrape import FleetScraper

    findings: list = []
    scraper = FleetScraper(targets, timeout=timeout)
    snap = scraper.scrape()
    fl = snap["fleet"]
    print(f"[fleet] {fl['up']}/{fl['engines']} up, "
          f"{fl['ready']} ready"
          + (f", goodput_frac={_fmt(fl['goodput_frac'])}"
             if fl["goodput_frac"] is not None else "")
          + (f", slo_burn_max={_fmt(fl['slo_burn_max'])}"
             if fl["slo_burn_max"] is not None else ""))
    for e in snap["engines"]:
        if not e["up"]:
            print(f"[fleet] {e['engine']} ({e['target']}) DOWN "
                  f"({e['error']})")
            findings.append(f"replica {e['engine']} at {e['target']} "
                            "is down")
            continue
        vals = e["metrics"]
        keys = ("dstpu_serve_ready", "dstpu_serve_draining",
                "dstpu_serve_degraded", "dstpu_serve_queue_depth",
                "dstpu_serve_slot_occupancy", "dstpu_serve_goodput_frac")
        brief = " ".join(f"{k.replace('dstpu_serve_', '')}={_fmt(vals[k])}"
                         for k in keys if k in vals)
        ready = {True: "ready", False: "NOT-ready", None: "ready?"}
        print(f"[fleet] {e['engine']} up ({ready[e['ready']]}, "
              f"{len(vals)} metrics) {brief}".rstrip())
        findings += [f"SLO burn gauge {k} = {_fmt(v)} on {e['engine']}"
                     for k, v in sorted(vals.items())
                     if k.endswith("_burn") and "_slo_" in k
                     and isinstance(v, float) and v > 0]
        # the live flight gate, per replica: why-markers in the newest
        # record mean something fired there since it was cut
        code, body = _http_get(e["target"] + "/flight", timeout)
        if code == 200:
            try:
                flr = json.loads(body)
            except json.JSONDecodeError:
                flr = {}
            newest = flr.get("newest")
            if newest and newest.get("markers"):
                names = sorted(str(n) for n in newest["markers"])
                print(f"[fleet]   flight why-markers: {', '.join(names)}")
                findings.append(
                    f"flight record on {e['engine']} contains "
                    "why-marker(s): " + ", ".join(names))
    return findings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m deepspeed_tpu.observability.doctor",
        description="Pretty-print the latest .prom, request log, flight "
                    "record, and capacity report for ops triage; exit "
                    "nonzero when something fired (see --no-gate).")
    ap.add_argument("--dir", default="./monitor",
                    help="monitor output directory (default ./monitor)")
    ap.add_argument("--flight-dir", default=None,
                    help="flight-record / incident directory (default: "
                         "--dir); with --targets, enables the "
                         "unreconciled-incident gate alongside live "
                         "triage")
    ap.add_argument("--requests", type=int, default=8,
                    help="recent request rows to show (default 8)")
    ap.add_argument("--no-gate", action="store_true",
                    help="always exit 0 (report-only; the default exits "
                         "1 on why-markers / burning SLOs so CI and cron "
                         "can gate on this command)")
    ap.add_argument("--url", default=None,
                    help="triage a LIVE engine at this base URL "
                         "(http://host:port) via its telemetry "
                         "endpoints instead of reading files")
    ap.add_argument("--targets", default=None,
                    help="fleet triage: comma-separated telemetry base "
                         "URLs (http://host:port,...) scraped via the "
                         "fleet aggregator; any down replica, burning "
                         "SLO gauge, or flight why-marker gates")
    ap.add_argument("--timeout", type=float, default=3.0,
                    help="per-endpoint timeout in live mode (default 3s)")
    ap.add_argument("--kv-regret-max", type=float, default=0.5,
                    help="[kv] gate: regretted share of prefill work "
                         "above this trips (default 0.5)")
    ap.add_argument("--load-rho-max", type=float, default=0.9,
                    help="[load] gate: utilization rho at/above this "
                         "with queue pressure and a finite TTV trips "
                         "(default 0.9)")
    ap.add_argument("--autoscale-frozen-max", type=float, default=900.0,
                    help="[autoscale] gate: a control loop frozen "
                         "longer than this (seconds) trips "
                         "(default 900)")
    ap.add_argument("--tenant-fairness-min", type=float, default=0.0,
                    help="[tenants] gate: a Jain fairness index below "
                         "this floor trips (default 0 = disabled; 1.0 "
                         "is perfectly even token shares)")
    args = ap.parse_args(argv)
    if args.targets:
        findings = report_fleet(
            [t for t in args.targets.split(",") if t],
            timeout=args.timeout)
        if args.flight_dir:
            # fleet triage + a shared flight dir: the incident gate runs
            # too — an unreconciled incident (dumps from fewer replicas
            # than were live) trips CI even when every target is up
            findings += report_incidents(Path(args.flight_dir))
    elif args.url:
        findings = report_live(args.url, timeout=args.timeout,
                               fairness_min=args.tenant_fairness_min)
    else:
        d = Path(args.dir)
        findings = report_prometheus(d)
        report_requests(d, args.requests)
        fdir = Path(args.flight_dir) if args.flight_dir else d
        findings += report_flight(fdir)
        findings += report_incidents(fdir)
        report_capacity(d)
        findings += report_comm(d)
        findings += report_kv(d, regret_max=args.kv_regret_max)
        findings += report_load(d, rho_max=args.load_rho_max)
        findings += report_autoscale(
            d, frozen_max=args.autoscale_frozen_max)
        findings += report_tenants(
            d, fairness_min=args.tenant_fairness_min)
        findings += report_replay([d] if fdir == d else [d, fdir])
    if findings:
        print(f"[gate] {len(findings)} finding(s):")
        for f in findings:
            print(f"  - {f}")
        return 0 if args.no_gate else 1
    print("[gate] clean")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
