"""Deterministic fault injection: the chaos half of every resilience test.

Two delivery mechanisms, both inert-by-default:

- **Config-gated** (:class:`ChaosConfig` → :class:`ChaosMonkey`): serving
  faults that must be seeded and repeatable — poison ONE occupied slot's
  logits with NaN on decode step N, sleep through an iteration to trip
  the decode-step watchdog, flood the queue at startup. The serving
  engine only constructs a monkey when ``chaos.enabled`` is true; with
  chaos off the engine holds ``None`` and the hot path pays a single
  ``is not None`` check — no extra host syncs, no extra programs.

- **Environment-gated** (:func:`kill_point` / :func:`preempt_step`):
  process-death faults that only make sense in a subprocess test — die
  with ``os._exit`` between the checkpoint state write and the ``latest``
  pointer flip, or raise SIGTERM at train step N to simulate a scheduler
  preemption. Library call sites are one dict lookup when the env var is
  unset.

Injection points are *named*; every firing is recorded (``injected`` audit
log for the monkey, an unbuffered stderr line for the kill points) so a
test asserts both the guard's reaction AND that the fault actually fired.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import time

import numpy as np

# Environment variables driving the process-death injection points.
# DSTPU_CHAOS_KILL="<point>" or "<point>:<k>" — os._exit(137) at the k-th
# (0-based, default 0) hit of that named kill point.
KILL_ENV = "DSTPU_CHAOS_KILL"
# DSTPU_CHAOS_PREEMPT="<step>" — SIGTERM this process at train step <step>.
PREEMPT_ENV = "DSTPU_CHAOS_PREEMPT"

# Named kill points wired into the checkpoint commit sequence
# (runtime/checkpoint/engine.py). The crash-mid-commit test kills at
# AFTER_STATE: the tag's arrays are durable but its manifest (the commit
# marker) and the 'latest' flip never happen — load must resume from the
# previous verified tag.
KILL_AFTER_STATE_WRITE = "ckpt:after-state-write"
KILL_BEFORE_LATEST_FLIP = "ckpt:before-latest-flip"

_kill_hits: dict[str, int] = {}


def kill_point(name: str) -> None:
    """Die HERE (``os._exit(137)`` — no atexit, no finally, the shape of a
    SIGKILL/OOM death) if ``DSTPU_CHAOS_KILL`` names this point.

    Format: ``"point"`` (die on first hit) or ``"point:k"`` (die on the
    k-th hit, 0-based) — so a test can let save #1 commit cleanly and
    kill save #2 mid-commit. Inert when the env var is unset (one dict
    lookup)."""
    spec = os.environ.get(KILL_ENV)
    if not spec:
        return
    # point names themselves contain ':' — the occurrence index is only
    # the LAST segment, and only when it's numeric
    point, sep, k = spec.rpartition(":")
    if not sep or not k.isdigit():
        point, k = spec, ""
    if point != name:
        return
    hit = _kill_hits.get(name, 0)
    _kill_hits[name] = hit + 1
    if hit != (int(k) if k else 0):
        return
    # unbuffered: the dying process must leave evidence the fault fired
    sys.stderr.write(f"[chaos] kill_point {name!r} hit {hit}: os._exit(137)\n")
    sys.stderr.flush()
    os._exit(137)


def preempt_step():
    """The train step at which chaos delivers SIGTERM to this process
    (simulated scheduler preemption), or None. Parsed per call but the
    engine caches the result once at init — the per-step cost with chaos
    off is a host ``is not None``."""
    spec = os.environ.get(PREEMPT_ENV)
    if not spec:
        return None
    return int(spec)


def deliver_preemption() -> None:
    """Raise SIGTERM in this process — the PreemptionGuard (or the default
    handler) takes it from here, exactly as under a real scheduler."""
    import signal

    sys.stderr.write("[chaos] delivering simulated SIGTERM preemption\n")
    sys.stderr.flush()
    os.kill(os.getpid(), signal.SIGTERM)


@dataclasses.dataclass
class ChaosConfig:
    """Seeded serving-fault schedule (``serving.chaos`` in ServingConfig).

    All injection points are deterministic: same config + same workload →
    same fault at the same step against the same slot. ``enabled: false``
    (the default) makes the whole config inert — the engine builds no
    monkey and the serving step is byte-for-byte the production program.
    """

    enabled: bool = False
    seed: int = 0
    # Poison ONE occupied slot's logits with NaN on the Nth serving decode
    # step (0-based; -1 = never). The slot is a seeded choice among the
    # occupied slots at that step. Proves the per-row non-finite guard:
    # exactly that request retires NONFINITE, every other slot's output
    # stays bit-identical to the no-fault run.
    nonfinite_decode_step: int = -1
    # Sleep ``hang_seconds`` inside the Nth serving iteration's decode
    # window (-1 = never): a hung/slow device step, as the watchdog sees it.
    hang_iteration: int = -1
    hang_seconds: float = 0.0
    # Submit this many junk one-token requests before the first iteration:
    # a queue flood. With ``max_queue`` set, the overflow sheds through
    # QueueFullError and the Serve/shed counter proves the backpressure path.
    flood_submits: int = 0

    def __post_init__(self):
        if self.hang_seconds < 0:
            raise ValueError(f"hang_seconds must be >= 0, "
                             f"got {self.hang_seconds}")
        if self.flood_submits < 0:
            raise ValueError(f"flood_submits must be >= 0, "
                             f"got {self.flood_submits}")

    @classmethod
    def from_any(cls, cfg: "ChaosConfig | dict | None") -> "ChaosConfig | None":
        if cfg is None or isinstance(cfg, cls):
            return cfg
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(cfg) - known
        if unknown:
            raise ValueError(f"unknown chaos config keys: {sorted(unknown)}")
        return cls(**cfg)


@dataclasses.dataclass
class FleetChaosConfig:
    """Seeded fleet-level fault schedule (``FleetEngine(chaos=...)``).

    One fault class for now: replica loss. At fleet iteration
    ``kill_replica_step`` the fleet abruptly drops one live replica —
    its queued and in-flight requests requeue onto survivors with a
    typed ``REQUEUED`` transition and a bumped ``attempts`` counter (the
    zero-request-loss oracle of ``tests/unit/test_fleet.py``). The victim
    is ``kill_replica`` when named, else a seeded choice among the live
    replicas at that instant. ``enabled: false`` (default) builds no
    monkey — the fleet step pays one ``is not None`` check."""

    enabled: bool = False
    seed: int = 0
    kill_replica_step: int = -1     # fleet iteration of the kill (-1 never)
    kill_replica: str = ""          # victim name; "" = seeded choice

    @classmethod
    def from_any(cls, cfg: "FleetChaosConfig | dict | None") \
            -> "FleetChaosConfig | None":
        if cfg is None or isinstance(cfg, cls):
            return cfg
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(cfg) - known
        if unknown:
            raise ValueError(
                f"unknown fleet chaos config keys: {sorted(unknown)}")
        return cls(**cfg)


class FleetChaosMonkey:
    """Drives one :class:`FleetChaosConfig` against one FleetEngine:
    counts fleet iterations, picks the victim, keeps the ``injected``
    audit log tests assert against (the fault must actually fire)."""

    def __init__(self, cfg: FleetChaosConfig):
        self.cfg = cfg
        self.rng = np.random.default_rng(cfg.seed)
        self.injected: list[dict] = []
        self._iterations = 0

    def maybe_kill(self, live: list) -> "str | None":
        """Name of the replica to kill THIS fleet iteration, or None.
        ``live`` is the current replica-name list; a configured victim
        that already left the fleet degrades to a seeded choice."""
        it = self._iterations
        self._iterations += 1
        c = self.cfg
        if c.kill_replica_step < 0 or it != c.kill_replica_step or not live:
            return None
        victim = c.kill_replica if c.kill_replica in live \
            else str(self.rng.choice(sorted(live)))
        self.injected.append({"point": "replica_kill", "iteration": it,
                              "replica": victim})
        return victim


class ChaosMonkey:
    """Drives one :class:`ChaosConfig` against one ServingEngine.

    Owns its own iteration/decode-step counters (the engine just reports
    events), a seeded RNG for slot choice, and the ``injected`` audit log
    tests assert against. ``sleep`` is injectable for fake-time tests.
    """

    def __init__(self, cfg: ChaosConfig, sleep=time.sleep):
        self.cfg = cfg
        self.sleep = sleep
        self.rng = np.random.default_rng(cfg.seed)
        self.injected: list[dict] = []
        self._decode_steps = 0
        self._iterations = 0

    def on_iteration(self) -> int:
        """Count one serving iteration; returns its 0-based index."""
        it = self._iterations
        self._iterations += 1
        return it

    def maybe_hang(self, iteration: int) -> None:
        """Inside the decode timing window: simulate a hung step."""
        c = self.cfg
        if c.hang_iteration >= 0 and iteration == c.hang_iteration \
                and c.hang_seconds > 0:
            self.injected.append({"point": "hang", "iteration": iteration,
                                  "seconds": c.hang_seconds})
            self.sleep(c.hang_seconds)

    def poison_slot(self, occupied) -> int:
        """Slot whose logits this decode step poisons, or -1.

        Counts decode steps internally; fires once, on
        ``nonfinite_decode_step``, against a seeded choice among the
        occupied slots (never an empty batch — an unoccupied row has no
        request to retire)."""
        i = self._decode_steps
        self._decode_steps += 1
        c = self.cfg
        if c.nonfinite_decode_step >= 0 and i == c.nonfinite_decode_step \
                and len(occupied):
            slot = int(self.rng.choice(sorted(occupied)))
            self.injected.append({"point": "nonfinite", "decode_step": i,
                                  "slot": slot})
            return slot
        return -1
