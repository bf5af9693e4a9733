"""Host-side continuous-batching scheduler: queue, slots, chunk plans.

Reference analog: DeepSpeed-MII / FastGen's Dynamic SplitFuse scheduler —
the policy half of continuous batching, split from the device half
(``slots.py`` / ``engine.py``) so it runs on plain numpy + floats and is
testable with a fake clock and no accelerator.

Policy, per serving iteration (see ``ServingEngine.step``):

1. admission — if a slot is free, no prefill is in flight, and the queue
   is non-empty, the head request starts prefilling;
2. chunked prefill — at most ONE prompt chunk runs per iteration, so a
   long prompt never stalls running requests' TPOT for more than a chunk
   (Dynamic SplitFuse's interleave-heterogeneous-work principle, applied
   as program interleaving instead of a fused megabatch — static shapes
   stay static);
3. decode — every occupied slot advances one token;
4. retirement — rows that hit eos or their max_new free their slot
   immediately; the slot is reusable the very next iteration.

Chunk plans are shape-bucketed: every chunk is either exactly
``prefill_chunk`` tokens or a power-of-two bucket below it, so the steady
state reuses a compiled-program set bounded by the bucket count — no
matter what prompt lengths traffic brings (docs/SERVING.md).
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Optional

import numpy as np

from ..observability import spans as _spans
from ..observability.export import hop_trace
from ..observability.tracing import ServingStats
from ..resilience.guards import QueueFullError, RequestStatus

_MIN_BUCKET = 8   # smallest residual-chunk program; below this, right-pad


@dataclasses.dataclass
class ChunkPlan:
    """One prefill chunk: run ``ids`` (already bucket-sized) with the cache
    length rewound to ``start``; ``final`` chunks also sample the first
    token from position ``last_index`` and set the cache to ``true_len``.

    Two bucketing tricks keep shapes bounded WITHOUT corrupting the cache:
    - overlap: a residual of r tokens re-runs the last ``size`` >= r prompt
      tokens (recomputing a suffix writes bit-identical KV, so rewinding
      ``start`` is free) — used whenever the prompt is long enough;
    - right-pad: short prompts pad up to the bucket; the pad's garbage KV
      lands at positions >= ``true_len``, which the attention mask already
      ignores and the first decode steps progressively overwrite.
    """

    start: int                    # cache position this chunk writes from
    ids: np.ndarray               # (size,) int32 token ids (padded if needed)
    final: bool = False
    last_index: int = 0           # position of the last REAL token in ids
    true_len: int = 0             # prompt length the cache ends at

    @property
    def size(self) -> int:
        return len(self.ids)


def _pow2_ceil(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def plan_chunks(prompt: np.ndarray, chunk: int, skip: int = 0,
                overlap: bool = True) -> list:
    """Split one prompt into bucket-shaped prefill chunks.

    Full ``chunk``-size chunks cover the head of the prompt; the residual
    runs as the smallest power-of-two bucket >= max(residual, 8), via
    overlap when the prompt affords it, else right-padding.

    ``skip`` (prefix sharing, serving/pages.py) drops the first ``skip``
    tokens from the plan: their KV is hydrated from shared pool pages, so
    only the suffix is recomputed. Chunk shapes stay in the same bucket
    set regardless of ``skip`` — sharing never compiles a new program —
    and the final overlap bucket may rewind INTO the hydrated region,
    rewriting bit-identical KV (the chunked==whole prefill oracle).

    ``overlap=False`` (a model with a recurrent state,
    ``Scheduler.recurrent``): a token must pass the state once, so the
    residual is never rewound — it starts where the full chunks end and is
    right-padded to its bucket (the same sizes in the same order: no other
    program is built), and the model stops its state at ``last_index``."""
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    P = len(prompt)
    if P < 1:
        raise ValueError("empty prompt")
    if not 0 <= skip < P:
        raise ValueError(f"skip={skip} outside [0, {P})")
    Q = P - skip                         # tokens actually recomputed
    k = (Q - 1) // chunk                 # full chunks before the residual
    r = Q - k * chunk                    # residual, in (0, chunk]
    plans = [ChunkPlan(start=skip + i * chunk,
                       ids=prompt[skip + i * chunk:skip + (i + 1) * chunk])
             for i in range(k)]
    b = max(_MIN_BUCKET, _pow2_ceil(r))
    if overlap and P >= b:      # recompute the last b prompt tokens
        plans.append(ChunkPlan(start=P - b, ids=prompt[P - b:], final=True,
                               last_index=b - 1, true_len=P))
    else:       # right-pad to the bucket: a short prompt whole, or (never
        #         rewound) the residual from where the full chunks end
        at = 0 if overlap else P - r
        ids = np.concatenate([prompt[at:], np.zeros(b - (P - at), np.int32)])
        plans.append(ChunkPlan(start=at, ids=ids, final=True,
                               last_index=P - at - 1, true_len=P))
    return plans


@dataclasses.dataclass
class Request:
    """One served request, host-side.

    ``status`` is the terminal outcome (:class:`RequestStatus`) — callers
    branch on it instead of inferring from token shapes. ``deadline_ttft``
    / ``deadline_total`` are ABSOLUTE times on the stats clock (submit
    time + the configured budgets), None when no deadline applies."""

    rid: int
    prompt: np.ndarray
    max_new: int
    seed: int
    # failover visibility (serving/fleet.py): how many times this request
    # was REQUEUED onto another replica after its original replica was
    # lost. 0 on the single-engine path; surfaced in inflight_table and
    # the request-log record so failover is never silent.
    attempts: int = 0
    # fleet session affinity key (None outside the fleet router)
    session_id: "object | None" = None
    # cost-attribution dimension (observability/tenantscope.py): which
    # tenant this request bills to. "default" when the caller never set
    # one — the inert value every pre-tenant record upgrades to.
    tenant_id: str = "default"
    submit_t: float = 0.0
    admit_t: Optional[float] = None       # left the queue (prefill starts)
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    slot: int = -1
    tokens: list = dataclasses.field(default_factory=list)
    status: RequestStatus = RequestStatus.OK
    error: str = ""
    deadline_ttft: Optional[float] = None
    deadline_total: Optional[float] = None
    # distributed-trace hop stamps (observability/export.py hop_trace):
    # requeue_t — when a failover pulled this request off its dead
    # replica (kill → re-admission is Serve/requeue_delay_s); export_t —
    # when the prefill replica finished exporting its pages to host;
    # import_t0/import_t1 — the disaggregated decode-side import window.
    # All None on the plain single-engine path.
    requeue_t: Optional[float] = None
    export_t: Optional[float] = None
    import_t0: Optional[float] = None
    import_t1: Optional[float] = None
    # paged-KV admission plan (serving/pages.py PageAllocation): the
    # slot's page-table row, shared-prefix skip, and hydrate plan. None
    # on the contiguous path.
    page_alloc: Optional[object] = None

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def finished(self) -> bool:
        return self.finish_t is not None

    @property
    def ok(self) -> bool:
        return self.finished and self.status is RequestStatus.OK


class Scheduler:
    """Queue + slot bookkeeping; all decisions, no device code.

    The engine asks ``pop_next()`` for the next request to prefill, then
    ``place()``s it into a slot (or ``complete_at_prefill()`` if its first
    token already finished it), and reports every decode step through
    ``on_step`` — which appends tokens, retires rows at eos / max_new, and
    frees their slots. FIFO admission; retirement order is whatever the
    tokens dictate.
    """

    def __init__(self, slots: int, max_len: int, prefill_chunk: int,
                 max_queue: int = 0, eos_token_id: Optional[int] = None,
                 stats: Optional[ServingStats] = None,
                 ttft_deadline_s: float = 0.0,
                 total_deadline_s: float = 0.0,
                 spans: "Optional[_spans.SpanRecorder]" = None,
                 pages=None, rid_source=None, recurrent: bool = False):
        self.slots = slots
        self.max_len = max_len
        self.prefill_chunk = prefill_chunk
        # a model that keeps a recurrent state (models/hybrid.py): plans
        # never rewind (plan_chunks overlap=False)
        self.recurrent = recurrent
        self.max_queue = max_queue
        self.eos_token_id = eos_token_id
        # paged-KV pool (serving/pages.py PagePool): admission consults
        # the prefix tree — and, when a host tier is attached
        # (serving/hostkv.py), the pinned-host cold store right after
        # it — and takes page refs; every terminal path releases them.
        # A restored admission's plan() shrinks exactly like a tree
        # hit's (skip covers the restored blocks); the engine scatters
        # the host tiles before the first chunk runs. None = contiguous
        # slot cache, nothing paged.
        self.pages = pages
        self._defer_key = None   # (rid, pool generation) of a failed admit
        self.stats = stats if stats is not None else ServingStats()
        self.ttft_deadline_s = float(ttft_deadline_s)
        self.total_deadline_s = float(total_deadline_s)
        # lifecycle span emission (observability/spans.py): every edge the
        # scheduler already stamps becomes a typed event, through the
        # seam's emit(): into this ring if there is one, and into the
        # capture ring while a profiler capture is live. None (default)
        # and no capture = a check per edge.
        self.spans = spans
        self.queue: deque[Request] = deque()
        self.free: list[int] = list(range(slots))
        self.running: dict[int, Request] = {}
        # rid allocation seam: the fleet router shares ONE counter across
        # every replica's scheduler so a request id names a request
        # fleet-wide (pop_result routes by rid, requeue keeps the id).
        # None (default) = this scheduler owns its own namespace.
        self.rid_source = rid_source
        self._next_rid = 0

    # -------------------------------------------------------------- intake
    def submit(self, prompt, max_new: int, seed: int = 0,
               ttft_deadline_s: Optional[float] = None,
               total_deadline_s: Optional[float] = None,
               session_id=None, tenant_id: Optional[str] = None) -> Request:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) < 1:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        if len(prompt) + max_new > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new ({max_new}) exceeds the "
                f"slot capacity max_len={self.max_len} — raise "
                f"serving.max_len or trim the request")
        if self.recurrent:
            # a plan that never rewinds pads its last chunk behind the
            # prompt: the pad has to fit too (an update past the cache's
            # end would be clamped back onto live positions)
            last = plan_chunks(prompt, self.prefill_chunk, overlap=False)[-1]
            if last.start + last.size > self.max_len:
                raise ValueError(
                    f"a prompt of {len(prompt)} tokens, its last chunk "
                    f"padded to {last.size}, exceeds max_len={self.max_len}")
        if self.max_queue and len(self.queue) >= self.max_queue:
            self.stats.on_shed(len(self.queue))
            raise QueueFullError(
                f"serving queue full ({self.max_queue}); apply backpressure",
                queue_depth=len(self.queue), max_queue=self.max_queue)
        if self.pages is not None:
            # typed PagePoolExhausted (status SHED) when the pool could
            # NEVER cover this request's worst-case pages — a transient
            # shortage instead defers at the queue head (pop_next)
            try:
                self.pages.check_submit(len(prompt), int(max_new))
            except QueueFullError:
                self.stats.on_shed(len(self.queue))
                raise
        if self.rid_source is not None:
            rid = int(self.rid_source())
        else:
            rid = self._next_rid
            self._next_rid += 1
        req = Request(rid=rid, prompt=prompt, max_new=int(max_new),
                      seed=int(seed), session_id=session_id,
                      tenant_id="default" if tenant_id is None
                      else str(tenant_id))
        self.queue.append(req)
        req.submit_t = self.stats.on_submit(len(self.queue))
        ttft = self.ttft_deadline_s if ttft_deadline_s is None \
            else float(ttft_deadline_s)
        total = self.total_deadline_s if total_deadline_s is None \
            else float(total_deadline_s)
        if ttft > 0:
            req.deadline_ttft = req.submit_t + ttft
        if total > 0:
            req.deadline_total = req.submit_t + total
        return req

    # ----------------------------------------------------------- admission
    def pop_next(self) -> Optional[Request]:
        """Head-of-queue request to start prefilling, if a slot is free —
        and, on the paged path, if the page pool can cover its worst-case
        pages right now (admission consults the prefix tree; a transient
        shortage leaves the head queued until a retirement frees pages,
        so a mid-decode pool OOM is impossible by construction). The
        engine guarantees at most one prefill in flight."""
        if not self.queue or not self.free:
            return None
        if self.pages is not None:
            head = self.queue[0]
            # retry gate: a failed try_admit re-runs the full tree match
            # + eviction walk, so only retry once admission prospects
            # changed (a release freed pages / new prefixes registered)
            key = (head.rid, self.pages.generation)
            if key == self._defer_key:
                return None
            alloc = self.pages.try_admit(head.prompt, head.max_new,
                                         head.rid)
            if alloc is None:
                self._defer_key = key
                return None          # pool transiently full: FIFO holds
            self._defer_key = None
            head.page_alloc = alloc
        req = self.queue.popleft()
        admit_t = self.stats.on_admit(len(self.queue), submit_t=req.submit_t)
        req.admit_t = admit_t
        if req.requeue_t is not None:
            # failover attribution: kill → re-admission, its OWN series
            # so TTFT and requeue delay stay separable in the logs
            self.stats.on_requeue_delay(admit_t - req.requeue_t)
        # the queue-wait span: submitted → picked for prefill. A
        # requeued ATTEMPT's span starts at the requeue (its first
        # attempt already burned the wait from submit_t) and carries
        # the attempt index, so per-attempt timings never conflate.
        _spans.emit(self.spans, _spans.QUEUED,
                    req.submit_t if req.requeue_t is None
                    else req.requeue_t,
                    admit_t, rid=req.rid, **self._attempt_meta(req))
        return req

    @staticmethod
    def _attempt_meta(req: Request) -> dict:
        """Span meta labeling which failover attempt an event belongs
        to — empty on the never-requeued path, so single-engine span
        streams are byte-identical to before the fleet existed."""
        return {"attempt": req.attempts} if req.attempts else {}

    def plan(self, req: Request) -> list:
        skip = req.page_alloc.skip if req.page_alloc is not None else 0
        return plan_chunks(req.prompt, self.prefill_chunk, skip=skip,
                           overlap=not self.recurrent)

    def _release_pages(self, req: Request) -> None:
        """Every terminal path funnels here: drop the request's page
        refcounts (shared pages survive for future sharing via their
        tree reference; private pages free immediately)."""
        if self.pages is not None and req.page_alloc is not None:
            self.pages.release(req.rid)

    def place(self, req: Request, first_tok: int) -> int:
        """Prefill finished: record the first token, occupy a slot (the
        serial order: the token was read before the seat)."""
        slot = self.seat(req)
        self.first_token(req, first_tok)
        return slot

    def seat(self, req: Request) -> int:
        """Occupy a slot for a prefilled request whose first token the
        host may not have read yet (``first_token`` follows)."""
        slot = self.free.pop(0)
        req.slot = slot
        self.running[slot] = req
        return slot

    def first_token(self, req: Request, first_tok: int) -> None:
        """The first token of a seated request, as the host reads it."""
        req.first_token_t = self.stats.on_first_token(req.submit_t)
        req.tokens.append(int(first_tok))
        _spans.emit(self.spans, _spans.PLACED, req.first_token_t,
                    rid=req.rid, slot=req.slot, **self._attempt_meta(req))

    def unseat(self, req: Request) -> None:
        """Give back the slot of a request seated ahead of its first
        token, which then ended it (``complete_at_prefill`` follows): the
        slot is the next one taken again, and the request held none."""
        del self.running[req.slot]
        self.free.insert(0, req.slot)
        req.slot = -1

    def adopt(self, req: Request) -> int:
        """Seat an ALREADY-prefilled request into a free slot without
        re-recording its first token (disaggregated serving: the prefill
        replica stamped ``first_token_t`` and appended the first token;
        this decode-side scheduler only takes over the residency). The
        caller guarantees a free slot exists."""
        slot = self.free.pop(0)
        req.slot = slot
        self.running[slot] = req
        if self.spans is not None:
            self.spans.emit(_spans.PLACED, self.stats.clock(), rid=req.rid,
                            slot=slot, imported=True,
                            **self._attempt_meta(req))
        return slot

    def requeue(self, req: Request) -> Request:
        """Failover intake (serving/fleet.py): re-queue a request whose
        replica was lost. The typed ``REQUEUED`` transition + ``attempts``
        bump make the move visible; everything transient (tokens, slot,
        first-token stamp, page plan) resets so the request re-runs from
        prefill on THIS scheduler — per-request RNG folds from the seed,
        so the rerun's bits match a fresh submission. ``submit_t`` and the
        ABSOLUTE deadlines are preserved: failover does not give a
        request more wall time than its caller asked for."""
        if len(req.prompt) + req.max_new > self.max_len:
            raise ValueError(
                f"requeued request {req.rid} (prompt {len(req.prompt)} + "
                f"max_new {req.max_new}) exceeds max_len={self.max_len}")
        req.status = RequestStatus.REQUEUED
        req.attempts += 1
        req.tokens = []
        req.slot = -1
        req.first_token_t = None
        req.finish_t = None
        req.admit_t = None
        req.page_alloc = None
        req.error = ""
        # per-attempt trace stamps restart with the attempt: the NEW
        # requeue_t anchors Serve/requeue_delay_s (kill → re-admission)
        # and the surviving attempt's hop decomposition; a stale import
        # window from the dead replica must not leak into it
        req.requeue_t = self.stats.clock()
        req.export_t = None
        req.import_t0 = None
        req.import_t1 = None
        # oldest-first at the head: a requeued request already spent its
        # queue wait once; survivors' fresher submissions queue behind it
        self.queue.appendleft(req)
        self.stats.on_requeue(len(self.queue))
        _spans.emit(self.spans, _spans.RETIRED, req.requeue_t, rid=req.rid,
                    slot=None, status=req.status.value, tokens=0,
                    attempt=req.attempts)
        return req

    def take_live(self) -> list:
        """Pull EVERY live request out of this scheduler (queue + running
        slots), oldest submission first — the replica-loss path: the
        fleet requeues them onto survivors. Slots free and the queue
        empties; page refs are NOT released (the pool dies with the
        replica)."""
        live = list(self.queue) + list(self.running.values())
        self.queue.clear()
        self.running.clear()
        self.free = list(range(self.slots))
        return sorted(live, key=lambda r: (r.submit_t, r.rid))

    def complete_at_prefill(self, req: Request, first_tok: int) -> Request:
        """max_new == 1, or the first token was eos: done without ever
        occupying a slot."""
        req.first_token_t = self.stats.on_first_token(req.submit_t)
        req.tokens.append(int(first_tok))
        req.finish_t = self.stats.on_retire(len(req.tokens),
                                            req.first_token_t)
        self._release_pages(req)
        self._span_retire(req)
        return req

    def _span_retire(self, req: Request) -> None:
        """Terminal span pair: the decode-residency span (first token →
        retirement, when the request ever held a slot) plus the typed
        RETIRED instant every terminal path emits."""
        if req.slot >= 0 and req.first_token_t is not None \
                and req.finish_t is not None:
            _spans.emit(self.spans, _spans.DECODE_RESIDENCY,
                        req.import_t1 if req.import_t1 is not None
                        else req.first_token_t,
                        req.finish_t, rid=req.rid, slot=req.slot,
                        tokens=len(req.tokens), **self._attempt_meta(req))
        _spans.emit(self.spans, _spans.RETIRED,
                    req.finish_t if req.finish_t is not None
                    else req.submit_t,
                    rid=req.rid, slot=req.slot if req.slot >= 0 else None,
                    status=req.status.value, tokens=len(req.tokens),
                    **self._attempt_meta(req))

    # -------------------------------------------------------------- decode
    def on_step(self, toks: np.ndarray, dones: np.ndarray,
                rows: Optional[dict] = None) -> list:
        """Account one slot decode step: per-slot next tokens + done flags
        (device read-back). ``rows`` (slot -> request) are the rows the
        step's tokens belong to — the engine's snapshot of the step's
        dispatch less what was retired since; None: everything running.
        Returns the requests retired this step."""
        rows = self.running if rows is None else rows
        finished = []
        for slot in sorted(rows):
            req = rows[slot]
            req.tokens.append(int(toks[slot]))
            if bool(dones[slot]) or len(req.tokens) >= req.max_new:
                req.status = RequestStatus.OK
                req.finish_t = self.stats.on_retire(len(req.tokens),
                                                    req.first_token_t)
                del self.running[slot]
                self.free.append(slot)
                self._release_pages(req)
                finished.append(req)
                self._span_retire(req)
        return finished

    def on_spec_step(self, emitted: dict) -> list:
        """Account one speculative verify step: ``emitted`` maps slot →
        the tokens that step committed for that slot (the carried token's
        verification plus every accepted draft — at least one token,
        already truncated at the first eos by the engine's host-side
        acceptance). Retirement is the same predicate as :meth:`on_step`
        applied to the LAST committed token, so a request retires on the
        exact step the plain lane would have reached that token.

        Paged retirements first roll the page table back to the final
        committed KV extent (``prompt + tokens - 1``: the last emitted
        token is the next step's carry, its KV never written) — the
        rejected drafts' garbage tail drops its pages via
        :meth:`~.pages.PagePool.truncate` before the ordinary release,
        so rollback-then-release refcounts stay exact."""
        finished = []
        for slot in sorted(emitted):
            req = self.running.get(slot)
            toks = emitted[slot]
            if req is None or not toks:
                continue
            req.tokens.extend(int(t) for t in toks)
            hit_eos = self.eos_token_id is not None \
                and int(req.tokens[-1]) == self.eos_token_id
            if hit_eos or len(req.tokens) >= req.max_new:
                req.status = RequestStatus.OK
                req.finish_t = self.stats.on_retire(len(req.tokens),
                                                    req.first_token_t)
                del self.running[slot]
                self.free.append(slot)
                if self.pages is not None:
                    self.pages.truncate(
                        req.rid, len(req.prompt) + len(req.tokens) - 1)
                self._release_pages(req)
                finished.append(req)
                self._span_retire(req)
        return finished

    # ------------------------------------------------------------- guards
    def abort(self, req: Request, status: RequestStatus,
              error: str = "") -> Request:
        """Terminate ``req`` with a non-OK status: free its slot if it
        holds one, record the typed outcome, count it in Serve/*. The
        engine uses this for requests it holds itself (the in-flight
        prefill); queue/slot residents go through :meth:`cancel` /
        :meth:`expire_deadlines`."""
        if req.slot >= 0 and req.slot in self.running \
                and self.running[req.slot] is req:
            del self.running[req.slot]
            self.free.append(req.slot)
        req.status = status
        req.error = error
        req.finish_t = self.stats.on_abort(status)
        self._release_pages(req)
        self._span_retire(req)
        return req

    def cancel(self, rid: int) -> Optional[Request]:
        """Cancel a queued or running request by id; returns it (status
        ``CANCELLED``) or None if this scheduler doesn't hold it (already
        finished, unknown, or held by the engine's prefill lane)."""
        for req in self.queue:
            if req.rid == rid:
                self.queue.remove(req)
                self.stats.registry.gauge("Serve/queue_depth").set(
                    len(self.queue))
                return self.abort(req, RequestStatus.CANCELLED,
                                  "cancelled while queued")
        for slot, req in list(self.running.items()):
            if req.rid == rid:
                return self.abort(req, RequestStatus.CANCELLED,
                                  "cancelled while decoding")
        return None

    def expire_deadlines(self, now: float) -> list:
        """Retire every request whose deadline passed: queued requests
        against BOTH deadlines (a request that cannot make TTFT from the
        queue is dead weight), running requests against the total-wall
        one (their first token already landed). Returns the expired
        requests, status ``TIMEOUT``."""
        expired = []
        for req in [r for r in self.queue
                    if (r.deadline_ttft is not None and now >= r.deadline_ttft)
                    or (r.deadline_total is not None
                        and now >= r.deadline_total)]:
            self.queue.remove(req)
            which = "ttft" if (req.deadline_ttft is not None
                              and now >= req.deadline_ttft) else "total"
            expired.append(self.abort(req, RequestStatus.TIMEOUT,
                                      f"{which} deadline expired in queue"))
        if expired:
            self.stats.registry.gauge("Serve/queue_depth").set(len(self.queue))
        for slot, req in list(self.running.items()):
            if req.deadline_total is not None and now >= req.deadline_total:
                expired.append(self.abort(req, RequestStatus.TIMEOUT,
                                          "total deadline expired"))
        return expired

    def retire_nonfinite(self, bad_slots) -> list:
        """The per-row logit guard tripped: retire exactly the poisoned
        slots' requests with ``NONFINITE``. Called BEFORE ``on_step``
        accounting, so the poisoned row's garbage token of this step is
        never appended; every other slot's bookkeeping is untouched."""
        out = []
        for slot in bad_slots:
            req = self.running.get(int(slot))
            if req is not None:
                out.append(self.abort(
                    req, RequestStatus.NONFINITE,
                    f"non-finite logits in slot {int(slot)}"))
        return out

    # ------------------------------------------------------------- readout
    def inflight_table(self, prefill: Optional[Request] = None) -> list:
        """Live in-flight request table for the telemetry plane's
        ``GET /requests``: the engine's prefill-lane resident (passed
        in — the scheduler doesn't hold it), every decoding slot, then
        the queue in FIFO order. Pure host bookkeeping, copied
        defensively so the HTTP thread never iterates a mutating
        container."""

        def row(req: Request, state: str) -> dict:
            return {
                "rid": req.rid, "state": state,
                "slot": req.slot if req.slot >= 0 else None,
                "prompt_len": req.prompt_len, "max_new": req.max_new,
                "tokens": len(req.tokens), "submit_t": req.submit_t,
                "admit_t": req.admit_t,
                "deadline_ttft": req.deadline_ttft,
                "deadline_total": req.deadline_total,
                # failover visibility: a requeued request shows its typed
                # status and move count while it waits again
                "status": req.status.value,
                "attempts": req.attempts,
                "tenant_id": req.tenant_id,
                # live hop decomposition: hops the request has completed
                # so far (the rest null) — /requests shows where an
                # in-flight request's time is going
                "trace": hop_trace(req),
                # tiered-KV visibility: how much of this request's
                # prefix came from the pool/host tier instead of
                # recompute (0 without a page allocation)
                "skip_tokens": (req.page_alloc.skip
                                if req.page_alloc is not None else 0),
                "restored_pages": (getattr(req.page_alloc, "restored", 0)
                                   if req.page_alloc is not None else 0),
            }

        rows = []
        if prefill is not None:
            rows.append(row(prefill, "prefill"))
        running = dict(self.running)
        for slot in sorted(running):
            rows.append(row(running[slot], "decoding"))
        for req in list(self.queue):
            rows.append(row(req, "queued"))
        return rows

    @property
    def queue_depth(self) -> int:
        return len(self.queue)

    @property
    def occupancy(self) -> int:
        return len(self.running)

    @property
    def idle(self) -> bool:
        return not self.queue and not self.running
