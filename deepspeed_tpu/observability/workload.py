"""Serving traffic analytics: what the *workload* would pay for.

The roadmap's next perf levers — paged KV with prefix sharing, n-gram
self-speculative decoding, quantized KV — are each justified only on
traffic with particular structure (shared prompt prefixes, repetitive
text, long contexts). This module measures that structure on the live
admission stream, so every what-if in the capacity advisor
(``capacity.py``) is computed on *observed* traffic rather than assumed:

- **prefix-overlap estimator** — a rolling-hash sketch over admitted
  prompt tokens: prefixes are hashed at ``block``-token boundaries into a
  bounded LRU of recently seen prefixes; an admitted prompt's longest
  matching boundary estimates the tokens a radix-style prefix cache would
  NOT have to prefill again. Reported as the shared-prefix token fraction
  (``Serve/workload_prefix_overlap``) and the cumulative dedupable-token
  count — the prefill work prefix sharing saves at the current overlap.
  The estimate is additionally SPLIT by attribution: same-session resume
  overlap (``Serve/workload_resume_overlap`` — the share a host KV tier
  could restore from demoted session pages; the input the ``tiered_kv``
  capacity lever sizes on) vs cross-request overlap
  (``Serve/workload_cross_overlap`` — shared system prompts that stay
  HBM-hot regardless).
- **self-speculation estimator** — an n-gram / prompt-lookup scan over
  each prompt: the fraction of positions where the preceding ``ngram``
  tokens have occurred before *and* correctly predict the next token is
  the acceptance rate a draft-free prompt-lookup speculator would get on
  this text (``Serve/workload_selfspec_accept``).
- **shape histograms** — prompt and decode length distributions
  (``Serve/workload_prompt_len`` / ``Serve/workload_decode_len``), the
  inputs every KV-budget what-if needs.

Cost discipline: everything here is host-side Python/numpy over prompt
arrays the scheduler already holds — O(tokens) per request, zero device
syncs, zero new compiled programs (``tests/unit/test_capacity.py``
compares compile counts with the analytics on and off). Disabled (the default)
the serving engine holds ``workload = None`` and pays one ``is not
None`` per admission. The analyzer's own overhead is measured into
``Serve/workload_analysis_s`` so the capacity report carries the cost of
its measurement.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Callable, Optional

import numpy as np

from .metrics import MetricsRegistry

# Polynomial rolling hash over token ids, mod a Mersenne prime: cheap,
# incremental per block, and collision-safe enough for an estimator with
# a ±5-point acceptance band (a collision can only OVERSTATE overlap,
# and at 2^61 space it is vanishingly rare at any realistic table size).
_HASH_P = 1_000_003
_HASH_M = (1 << 61) - 1


@dataclasses.dataclass
class WorkloadConfig:
    """Traffic-analytics knobs (``ServingConfig.workload``). Constructing
    one (or passing a dict) opts in; ``None`` on the serving config means
    no analyzer is built at all."""

    enabled: bool = True
    # Prefix hashes are taken at multiples of this many tokens: the
    # granularity of the overlap estimate AND the page size a paged-KV
    # prefix cache would share at (align them to make the estimate the
    # cache's actual hit rate).
    block: int = 16
    # Bounded LRU of distinct prefix hashes kept (each entry is one dict
    # slot — a few MB at the default). Evicting old prefixes makes the
    # estimate "overlap against *recent* traffic", which is what a
    # finite-size prefix cache would experience.
    max_prefixes: int = 65536
    # Context length for the prompt-lookup / self-speculation scan.
    ngram: int = 3
    # Bounded LRU of per-session prefix sets: the resume-vs-cross
    # overlap split (sessions beyond the cap fall back to cross-only).
    max_sessions: int = 4096

    def __post_init__(self):
        if self.block < 1:
            raise ValueError(f"workload block must be >= 1, got {self.block}")
        if self.max_prefixes < 1:
            raise ValueError(f"workload max_prefixes must be >= 1, "
                             f"got {self.max_prefixes}")
        if self.ngram < 1:
            raise ValueError(f"workload ngram must be >= 1, got {self.ngram}")
        if self.max_sessions < 1:
            raise ValueError(f"workload max_sessions must be >= 1, "
                             f"got {self.max_sessions}")

    @classmethod
    def from_any(cls, cfg: "WorkloadConfig | dict | None") \
            -> "WorkloadConfig | None":
        if cfg is None or isinstance(cfg, cls):
            return cfg
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(cfg) - known
        if unknown:
            raise ValueError(f"unknown workload config keys: "
                             f"{sorted(unknown)}")
        return cls(**cfg)


def prefix_hashes(tokens: np.ndarray, block: int) -> list:
    """Rolling hash of every ``block``-aligned prefix of ``tokens``:
    ``[(length, hash), ...]`` for lengths ``block, 2*block, ...`` — one
    O(tokens) pass, each entry extending the previous hash."""
    toks = np.asarray(tokens).reshape(-1)
    out = []
    h = 0
    for i, t in enumerate(toks.tolist()):
        h = (h * _HASH_P + (int(t) + 1)) % _HASH_M
        if (i + 1) % block == 0:
            out.append((i + 1, h))
    return out


def token_hash(tokens) -> int:
    """The same polynomial rolling hash over a WHOLE token sequence —
    one shared spelling so the prefix sketch here and the ghost-tree
    ledger (``kvscope.py``) key identical prefixes identically."""
    h = 0
    for t in np.asarray(tokens).reshape(-1).tolist():
        h = (h * _HASH_P + (int(t) + 1)) % _HASH_M
    return h


def selfspec_acceptance(tokens: np.ndarray, ngram: int) -> Optional[float]:
    """Prompt-lookup acceptance potential of one token sequence: the
    fraction of scored positions whose next token is correctly predicted
    by the most recent earlier occurrence of the preceding ``ngram``
    tokens — exactly what an n-gram self-speculator drafts. None when the
    sequence is too short to score a single position.

    Runs on the SAME :class:`~..inference.speculation.NGramTable` the
    live drafter uses, so the estimate and the serving engine's achieved
    acceptance cannot drift: both are one implementation scored two ways
    (here unconditionally — a position with no table entry counts as a
    miss — because the estimator prices the whole stream)."""
    from ..inference.speculation import acceptance_stats

    stats = acceptance_stats(tokens, ngram)
    return None if stats is None else stats["rate"]


class WorkloadAnalyzer:
    """Admission-path traffic analytics into ``Serve/workload_*``.

    ``on_admit(prompt)`` runs when the scheduler picks a request for
    prefill (the admission hook in ``ServingEngine.step``);
    ``on_retire(request)`` when it terminates. All state is host-side and
    bounded; ``clock`` is injectable like every observability clock and
    is used ONLY to measure the analyzer's own overhead."""

    def __init__(self, cfg: "WorkloadConfig | dict | None" = None,
                 registry: Optional[MetricsRegistry] = None,
                 clock: Optional[Callable[[], float]] = None):
        self.cfg = WorkloadConfig.from_any(cfg) or WorkloadConfig()
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self.clock = clock
        # LRU of recently seen prefix hashes: hash -> prefix length. The
        # dict is keyed by hash alone (not (len, hash)) so a longer
        # prefix with the same boundary hash refreshes recency.
        self._prefixes: OrderedDict = OrderedDict()
        # per-session boundary sets (hash -> length of that session's own
        # most recent prompt): the RESUME overlap — the share of a
        # prompt a session replays from its OWN earlier turns, which is
        # what a host KV tier can serve from demoted pages. The
        # remainder of the total overlap is CROSS-request (shared system
        # prompts), which stays hot in HBM regardless.
        self._sessions: OrderedDict = OrderedDict()
        self.prompt_tokens = 0          # all admitted prompt tokens
        self.shared_tokens = 0          # tokens covered by a seen prefix
        self.resume_tokens = 0          # covered by the SAME session
        self.requests = 0
        # live self-speculation tallies (``on_spec``): what the drafter
        # ACHIEVED, exported next to the offline estimate above so
        # predicted-vs-achieved is one snapshot read.
        self.spec_steps = 0             # verify steps scored
        self.spec_proposed = 0          # draft tokens proposed
        self.spec_accepted = 0          # draft tokens accepted
        self.spec_emitted = 0           # tokens emitted by verify steps
        self.spec_first_scored = 0      # slots with a non-empty draft
        self.spec_first_hits = 0        # ... whose FIRST draft token hit

    # ------------------------------------------------------------ admission
    def _match_and_insert(self, bounds: list) -> int:
        """Longest block-aligned prefix already in the sketch (tokens),
        then record this prompt's own boundaries."""
        shared = 0
        for length, h in bounds:
            if self._prefixes.get(h) == length:
                # each boundary hash covers the WHOLE prefix from 0, so a
                # hit at any length stands alone — no contiguity needed.
                # (The LRU evicts a prompt's shorter boundaries first;
                # breaking at the first miss would score a fully resident
                # longer prefix as 0 near capacity.) Lengths ascend, so
                # the last hit is the longest resident match.
                shared = length
                self._prefixes.move_to_end(h)
        for length, h in bounds:
            self._prefixes[h] = length
            self._prefixes.move_to_end(h)
        while len(self._prefixes) > self.cfg.max_prefixes:
            self._prefixes.popitem(last=False)
        return shared

    def _session_match(self, session_id, bounds: list) -> int:
        """Longest boundary this SESSION itself registered before, then
        replace its set with this prompt's boundaries (conversations
        replay a growing prefix — the latest prompt's set covers every
        earlier one)."""
        if session_id is None:
            return 0
        prev = self._sessions.get(session_id)
        shared = 0
        if prev is not None:
            for length, h in bounds:
                if prev.get(h) == length:
                    shared = length
        self._sessions[session_id] = {h: length for length, h in bounds}
        self._sessions.move_to_end(session_id)
        while len(self._sessions) > self.cfg.max_sessions:
            self._sessions.popitem(last=False)
        return shared

    def on_admit(self, prompt: np.ndarray, session_id=None) -> dict:
        """Score one admitted prompt; returns the per-request estimates
        (the scheduler ignores them — callers like benches may not)."""
        t0 = self.clock() if self.clock is not None else None
        prompt = np.asarray(prompt).reshape(-1)
        P = len(prompt)
        bounds = prefix_hashes(prompt, self.cfg.block)
        shared = self._match_and_insert(bounds)
        resume = min(self._session_match(session_id, bounds), shared)
        accept = selfspec_acceptance(prompt, self.cfg.ngram)
        self.requests += 1
        self.prompt_tokens += P
        self.shared_tokens += shared
        self.resume_tokens += resume
        r = self.registry
        r.counter("Serve/workload_prompt_tokens").inc(P)
        r.counter("Serve/workload_shared_prefix_tokens").inc(shared)
        r.counter("Serve/workload_resume_tokens").inc(resume)
        r.histogram("Serve/workload_prompt_len").observe(P)
        r.histogram("Serve/workload_prefix_share").observe(
            shared / P if P else 0.0)
        if self.prompt_tokens:
            r.gauge("Serve/workload_prefix_overlap").set(
                self.shared_tokens / self.prompt_tokens)
            # the split the host-tier advisor sizes on: resume overlap
            # (same-session replay — host-restorable) vs cross-request
            # overlap (shared system prompts — stays HBM-hot anyway)
            r.gauge("Serve/workload_resume_overlap").set(
                self.resume_tokens / self.prompt_tokens)
            r.gauge("Serve/workload_cross_overlap").set(
                (self.shared_tokens - self.resume_tokens)
                / self.prompt_tokens)
        if accept is not None:
            r.histogram("Serve/workload_selfspec_accept").observe(accept)
        if t0 is not None:
            r.histogram("Serve/workload_analysis_s").observe(
                self.clock() - t0)
        return {"prompt_len": P, "shared_prefix_tokens": shared,
                "resume_prefix_tokens": resume,
                "selfspec_accept": accept}

    # ---------------------------------------------------------- speculation
    def on_spec(self, proposed: int, accepted: int, emitted: int,
                first_scored: int = 0, first_hits: int = 0) -> None:
        """Record one verify step's live outcome (the serving engine's
        decode lane calls this once per speculative step, summed over
        slots). ``first_scored`` / ``first_hits`` isolate the FIRST draft
        token per slot — the live counterpart of the offline estimator's
        per-position hit rate, which is what the replay backtest compares
        against the prediction."""
        self.spec_steps += 1
        self.spec_proposed += int(proposed)
        self.spec_accepted += int(accepted)
        self.spec_emitted += int(emitted)
        self.spec_first_scored += int(first_scored)
        self.spec_first_hits += int(first_hits)
        r = self.registry
        r.counter("Serve/workload_spec_proposed_tokens").inc(int(proposed))
        r.counter("Serve/workload_spec_accepted_tokens").inc(int(accepted))
        r.counter("Serve/workload_spec_emitted_tokens").inc(int(emitted))
        if self.spec_proposed:
            r.gauge("Serve/workload_spec_accept_rate").set(
                self.spec_accepted / self.spec_proposed)
        if self.spec_first_scored:
            r.gauge("Serve/workload_spec_first_accept_rate").set(
                self.spec_first_hits / self.spec_first_scored)

    @property
    def spec_accept_rate(self) -> "float | None":
        """Achieved draft-token acceptance fraction (live), None before
        any draft was verified."""
        return (self.spec_accepted / self.spec_proposed
                if self.spec_proposed else None)

    @property
    def spec_first_accept_rate(self) -> "float | None":
        """Achieved FIRST-draft-token acceptance (live) — the comparable
        of the offline estimator's conditional ``hit_rate``."""
        return (self.spec_first_hits / self.spec_first_scored
                if self.spec_first_scored else None)

    # ----------------------------------------------------------- retirement
    def on_retire(self, request) -> None:
        """Record the decode-side shape of a terminated request (accepts
        anything with ``.tokens``; the scheduler's ``Request``)."""
        self.registry.histogram("Serve/workload_decode_len").observe(
            len(getattr(request, "tokens", ())))

    # -------------------------------------------------------------- readout
    @property
    def prefix_overlap(self) -> float:
        """Shared-prefix token fraction over all admitted prompt tokens —
        the fraction of prefill work a prefix cache would have skipped."""
        return (self.shared_tokens / self.prompt_tokens
                if self.prompt_tokens else 0.0)

    @property
    def resume_overlap(self) -> float:
        """Same-session replayed-prefix fraction — the share of prefill
        work a HOST KV tier could serve from demoted session pages."""
        return (self.resume_tokens / self.prompt_tokens
                if self.prompt_tokens else 0.0)

    def snapshot(self) -> dict:
        snap = self.registry.snapshot()
        h = snap["histograms"]
        accept = h.get("Serve/workload_selfspec_accept", {})
        return {
            "requests": self.requests,
            "prompt_tokens": self.prompt_tokens,
            "shared_prefix_tokens": self.shared_tokens,
            "prefix_overlap": self.prefix_overlap,
            "resume_prefix_tokens": self.resume_tokens,
            "resume_overlap": self.resume_overlap,
            "cross_overlap": self.prefix_overlap - self.resume_overlap,
            "dedupable_prefill_tokens": self.shared_tokens,
            "distinct_prefixes": len(self._prefixes),
            "tracked_sessions": len(self._sessions),
            "block": self.cfg.block,
            "ngram": self.cfg.ngram,
            "selfspec_accept": accept,
            "spec_live": {
                "steps": self.spec_steps,
                "proposed_tokens": self.spec_proposed,
                "accepted_tokens": self.spec_accepted,
                "emitted_tokens": self.spec_emitted,
                "accept_rate": self.spec_accept_rate,
                "first_accept_rate": self.spec_first_accept_rate,
            },
            "prompt_len": h.get("Serve/workload_prompt_len", {}),
            "decode_len": h.get("Serve/workload_decode_len", {}),
            "analysis_s": h.get("Serve/workload_analysis_s", {}),
        }
