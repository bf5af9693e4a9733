"""What the serving kinds (``backlog``, ``open_loop``) share: requests
through ``ds.init_inference`` / ``ds.ServingEngine.submit`` / ``step`` from
one thread, the benchmark's own stamps around them.

Set-up: weights made on the device from the seed in the served type; the
reference check (last-position logits of a short and a long prompt, float32
``highest``); served requests against solo ``generate()``; one warm-up
request per prefill bucket the mix's lengths can produce; for a backlog, the
ramp that fills the slots. Window: the loop below. One iteration is what a
server's main loop does: hand over what is due, one ``step()``, read what it
emitted. Every request is timed from when it was *due*, which only this file
knows; tokens are stamped when ``step()`` hands them back, which is when a
streaming client could have them.

``serve_tokens_per_s`` is all the window's output tokens over all the
window's time, and ``itl_p95_ms`` the tail of all its gaps. The chip machine
stands still for 0.13-0.19 s (now and then for seconds) inside a read-back,
with no CPU time, a few times a window; that is in both, as it is in what a
user of the machine gets. Beside them, per layer and in every run's notes:
the time spent in such iterations (``host.stall_ms``; ``reduce.stalls`` says
which they are) and the rate without what they took beyond a median
iteration (``serve.tokens_per_s_less_stalls``), so that a rate that moved
can be told from a window that held a stall.
"""

from __future__ import annotations

import statistics
import time

import numpy as np

from ..harness import Cell, Outcome, TraceTail, settle_host, span
from ..reduce import STALL_OVER, percentile, stalls, window_rate
from ..traffic import Planned, plan_requests, rng_for

# two candidates whose decision values differ by less than 2^-6 of the row's
# largest logit may swap between two differently shaped bf16 programs (8
# mantissa bits, a few roundings deep in the trunk): chip_smoke.py's rule,
# here on logit + Gumbel noise, which is what a sampled draw compares
BF16_TIE = 2.0 ** -6


# ------------------------------------------------------------------ set-up
def build(cell: Cell):
    import jax

    import deepspeed_tpu as ds

    conf = cell.config
    dtype = conf["serve"]["dtype"]
    cfg, model = cell.family.build(cell.published, dtype,
                                   flash_attention=False)
    served = cfg.dtype
    with span("make_weights"):
        params = jax.jit(lambda key: jax.tree.map(
            lambda a: a.astype(served), model.init(key)))(
                jax.random.PRNGKey(cell.jax_seed()))
    eng = ds.init_inference(model, params, {"dtype": dtype})
    return cfg, params, eng


def check_logits(cell: Cell, cfg, params, eng, notes: list) -> bool:
    """Last-position logits through ``InferenceEngine.forward`` against the
    plain reference on the same weights, for a short and a long prompt."""
    import jax

    rng = rng_for(cell.seed + 1)
    ok = True
    tol = float(cell.mix["logit_tolerance"])
    for n in cell.mix["check_prompt_tokens"]:
        ids = rng.integers(0, cfg.vocab_size, (1, int(n)), dtype=np.int32)
        got = np.asarray(eng.forward(ids)[0, -1], np.float32)
        want = np.asarray(jax.block_until_ready(cell.reference.run_highest(
            cell.reference.logits, params, jax.numpy.asarray(ids),
            n_head=cell.published["n_head"],
            eps=cell.published["layer_norm_epsilon"], last_only=True)))[0]
        rel = float(np.abs(got - want).max() / np.abs(want).max())
        good = bool(np.isfinite(got).all()) and rel <= tol
        ok &= good
        notes.append(f"last-position logits, prompt of {n}: max difference "
                     f"from the float32 reference {rel:.2e} of the largest "
                     f"logit ({'within' if good else 'OUTSIDE'} {tol:.1e})")
    return ok


def decision_margin(eng, prompt, toks, pos: int, other, seed: int):
    """Served and solo tokens first differ at ``pos``. The draw there is
    argmax(logits + Gumbel noise) with the request's own key chain (one split
    per token, ``inference/sampling.py``); returns the two candidates'
    difference in that sum as a share of the row's largest logit, or None if
    neither candidate is the reconstruction's argmax (then this rebuild of
    the sampler is wrong, and the difference is not excused)."""
    import jax

    ctx = np.concatenate([prompt, np.asarray(toks[:pos], np.int32)])
    row = np.asarray(eng.forward(ctx[None])[0, -1], np.float32)
    key = jax.random.PRNGKey(int(seed))
    for _ in range(pos + 1):
        key, sub = jax.random.split(key)
    val = row + np.asarray(jax.random.gumbel(sub, row.shape, np.float32))
    a, b = int(toks[pos]), int(other[pos])
    if int(val.argmax()) not in (a, b):
        return None
    return abs(float(val[a]) - float(val[b])) / max(
        float(np.abs(row).max()), 1e-9)


def check_served(cell: Cell, cfg, eng, srv, notes: list) -> bool:
    """Served requests equal solo ``generate()`` with the same seeds and the
    serving cache width, token for token; a first difference is excused only
    as a near-tie at bf16 rounding level."""
    rng = rng_for(cell.seed + 2)
    max_len = int(cell.mix["engine"]["max_len"])
    ok = True
    for shape in cell.mix["check_requests"]:
        k, p, n = int(shape["count"]), int(shape["prompt"]), int(shape["answer"])
        prompts = rng.integers(0, cfg.vocab_size, (k, p), dtype=np.int32)
        seeds = [int(s) for s in rng.integers(0, 2 ** 31 - 1, k)]
        rids = [srv.submit(prompts[i], n, seed=seeds[i]) for i in range(k)]
        srv.drain()
        srv.end_drain()
        got = [np.asarray(srv.pop_result(r).tokens) for r in rids]
        want = np.asarray(eng.generate(prompts, n, request_seeds=seeds,
                                       cache_len=max_len))
        for i in range(k):
            same = len(got[i]) == n and (got[i] == want[i]).all()
            if same:
                continue
            if len(got[i]) != n:
                ok = False
                notes.append(f"served answer of {len(got[i])} tokens, "
                             f"asked for {n}")
                continue
            pos = int(np.nonzero(got[i] != want[i])[0][0])
            m = decision_margin(eng, prompts[i], got[i], pos, want[i],
                                seeds[i])
            tie = m is not None and m <= BF16_TIE
            ok &= tie
            notes.append(f"served and solo tokens first differ at position "
                         f"{pos} of a {p}-token prompt: decision margin "
                         f"{m if m is None else format(m, '.2e')} of the "
                         f"row's largest logit ({'a near-tie' if tie else 'NOT a near-tie'})")
        notes.append(f"{k} served requests (prompt {p}, answer {n}) against "
                     "solo generate(): "
                     + ("equal or near-tied" if ok else "DIFFERENT"))
    return ok


def warm_buckets(cell: Cell, cfg, srv) -> None:
    """One request per distinct sequence of prefill programs the mix's prompt
    lengths can produce (the scheduler's own ``plan_chunks`` says which): the
    full chunk, every final bucket, and each after each. A program fed the
    output of another program is compiled apart from the same program fed a
    fresh cache, so warming every bucket once is not enough (PERF.md F9)."""
    from deepspeed_tpu.serving.scheduler import plan_chunks

    chunk = int(cell.mix["engine"]["prefill_chunk"])
    lo, hi = (int(cell.mix["prompt_tokens"][k]) for k in ("min", "max"))
    rng = rng_for(cell.seed + 3)
    seen = set()
    for p in range(lo, hi + 1):
        shape = tuple(c.size for c in plan_chunks(np.zeros(p, np.int32),
                                                  chunk))
        if shape not in seen:
            seen.add(shape)
            srv.submit(rng.integers(0, cfg.vocab_size, p, dtype=np.int32), 2,
                       seed=p)
    srv.drain()
    srv.end_drain()
    srv.results.clear()


# ------------------------------------------------------------------ window
class Book:
    """The benchmark's own record of every request: due, submitted, and each
    token's arrival. The program's ``Request`` stamps (admit, first token,
    finish) are read once, when the request is done."""

    def __init__(self):
        self.rows: dict = {}       # rid -> row
        self.gaps: list = []       # seconds between successive tokens
        self.tokens = 0            # output tokens emitted

    def sent(self, rid: int, due_t: float, now: float, req) -> None:
        self.rows[rid] = {"due": due_t, "submit": now, "req": req,
                          "seen": 0, "last": None}

    def emitted(self, reqs, now: float) -> None:
        for req in reqs:
            row = self.rows.get(req.rid)
            if row is None:        # a set-up request
                continue
            n = len(req.tokens)
            new = n - row["seen"]
            if new <= 0:
                continue
            last = row["last"]
            if last is None:
                # the first token is stamped by the program when prefill
                # places the request, before the same iteration's decode; a
                # request already in flight when the window opened has no
                # stamp inside it, so its first token seen only anchors
                last = req.first_token_t if row["seen"] == 0 else now
                new -= 1
            if new > 0:
                # tokens that come back from one step() arrive together
                self.gaps.append(now - last)
                self.gaps.extend([0.0] * (new - 1))
                last = now
            self.tokens += n - row["seen"]
            row["seen"], row["last"] = n, last

    def records(self, t_end: float) -> list:
        """One dict per request for the reducers, times in seconds on the
        host's clock; a request with no first token by ``t_end`` waited at
        least until then."""
        out = []
        for row in self.rows.values():
            req = row["req"]
            out.append({
                "due": row["due"], "submit": row["submit"],
                "late": row["submit"] - row["due"],
                "submit_t": req.submit_t, "admit_t": req.admit_t,
                "first_token_t": req.first_token_t, "finish_t": req.finish_t,
                "ttft": (req.first_token_t if req.first_token_t is not None
                         else t_end) - row["due"],
                "ok": bool(req.ok), "finished": bool(req.finished),
                "tokens": len(req.tokens)})
        return out


class WindowLog:
    """Every iteration of the window: when it began, how long it took
    until what it emitted had been booked, and how many output tokens that
    was. What the stalls and the rate without them are worked out from,
    once the window has closed."""

    def __init__(self):
        self.began: list = []
        self.durations: list = []
        self.counts: list = []
        self.occupied: list = []     # slots running after the step

    def iteration(self, t_in: float, now: float, tokens: int,
                  occupied: int) -> None:
        self.began.append(t_in)
        self.durations.append(now - t_in)
        self.counts.append(tokens)
        self.occupied.append(occupied)

    def facts(self, t0: float, t1: float) -> dict:
        """For ``reduce.window_rate`` and ``reduce.stall_time``."""
        return {"t0": t0, "t1": t1, "counts": self.counts,
                "durations": self.durations}

    def note(self, w: dict) -> str:
        """One line on the window ``w`` (what ``facts`` gave)."""
        stall_s, _, n = stalls(w["durations"])
        slow = sorted(zip(w["durations"], self.began), reverse=True)[:5]
        facts = {"window": w}
        return (
            f"{len(self.began)} iterations, median "
            f"{1e3 * statistics.median(w['durations']):.3f} ms, "
            f"{statistics.fmean(self.occupied):.2f} slots running after a "
            f"step on average; tokens/s of the whole window "
            f"{window_rate(facts):.3f}; {1e3 * stall_s:.1f} ms in {n} "
            f"iterations over {STALL_OVER:g}x the median, without their "
            f"excess {window_rate(facts, less_stalls=True):.3f} tokens/s; "
            "slowest iterations (ms, at s): "
            + ", ".join(f"{1e3 * d:.0f} at {at - w['t0']:.1f}"
                        for d, at in slow))


def serve(cell: Cell, open_loop: bool) -> Outcome:
    import deepspeed_tpu as ds

    mix = cell.mix
    notes: list = []
    phases = [("start", cell.t_process)]

    def phase(name: str) -> None:
        phases.append((name, time.perf_counter()))

    cfg, params, eng = build(cell)
    phase("import, weights, engine")
    correct = check_logits(cell, cfg, params, eng, notes)
    phase("reference check")
    srv = ds.ServingEngine(eng, dict(mix["engine"]),
                           clock=time.perf_counter)
    slots = int(mix["engine"]["slots"])
    correct &= check_served(cell, cfg, eng, srv, notes)
    phase("served against solo")
    warm_buckets(cell, cfg, srv)
    phase("warm-ups")
    planned = plan_requests(mix, cfg.vocab_size, cell.seed, cell.seconds)
    book = Book()
    sched = srv.sched
    nxt = 0

    def submit_due(t0: float, upto: float) -> None:
        nonlocal nxt
        while nxt < len(planned) and planned[nxt].due <= upto:
            p: Planned = planned[nxt]
            rid = srv.submit(p.prompt, p.max_new, seed=p.seed)
            book.sent(rid, t0 + p.due, time.perf_counter(), sched.queue[-1])
            nxt += 1

    def iterate() -> None:
        t_in = time.perf_counter()
        with span("engine_step"):
            done = srv.step()
        with span("bookkeeping"):
            now = time.perf_counter()
            before = book.tokens
            book.emitted(list(sched.running.values()) + done, now)
            log.iteration(t_in, now, book.tokens - before,
                          len(sched.running))
            for r in done:
                srv.results.pop(r.rid, None)
            if tail.on:
                live.append(sum(r.prompt_len + len(r.tokens) - 1
                                for r in sched.running.values()))

    tail = TraceTail(cell)
    live: list = []
    log = WindowLog()
    t0 = time.perf_counter()
    if not open_loop:
        # the whole backlog is there before the window opens, and the slots
        # are brought to their steady occupancy: set-up the traffic needs
        submit_due(t0, 0.0)
        ramp = 0
        while len(sched.running) < slots and ramp < int(
                mix["ramp_max_iterations"]):
            srv.step()
            ramp += 1
        notes.append(f"after a ramp of {ramp} iterations {len(sched.running)}"
                     f" of {slots} slots are occupied")
    compiles0 = srv.compiles
    mark = cell.watch.mark()
    settle_host()
    t0 = time.perf_counter()
    setup_s = t0 - cell.t_process
    phases.append(("plan, ramp, settle", t0))
    notes.append("set-up by phase (s): " + ", ".join(
        f"{name} {b - a:.2f}" for (_, a), (name, b) in
        zip(phases, phases[1:])))
    if not open_loop:
        # requests in flight when the window opens count from here on
        for row in book.rows.values():
            row["seen"] = len(row["req"].tokens)
        book.tokens = 0
    drain_s = float(mix.get("drain_seconds", 0.0))
    queue_at: dict = {}            # the queue's depth at mid-window and end
    while True:
        elapsed = time.perf_counter() - t0
        for mark_at, label in ((cell.seconds / 2, "middle"),
                               (cell.seconds, "end")):
            if elapsed >= mark_at and label not in queue_at:
                queue_at[label] = sched.queue_depth
        started = nxt >= len(planned) and not sched.queue \
            and srv._prefill is None       # every request has its first token
        if elapsed >= cell.seconds and (not open_loop or started
                                        or elapsed >= cell.seconds + drain_s):
            break
        tail.tick(elapsed)
        if open_loop:
            with span("submit"):
                submit_due(t0, elapsed)
            if sched.idle and srv._prefill is None:
                # nothing to serve until the next arrival or the window's end
                wake = planned[nxt].due if nxt < len(planned) \
                    else cell.seconds
                with span("wait_for_arrival"):
                    time.sleep(max(0.0, min(0.002, wake - elapsed)))
                continue
        iterate()
    t_end = time.perf_counter()
    tail.stop()
    built = cell.watch.since(mark)
    built["serving_programs_built"] = srv.compiles - compiles0
    records = book.records(t_end)
    if open_loop:
        # failed: ended otherwise than OK, or no first token by the end of
        # the drain. A request still decoding then is cut, not failed: at
        # this engine's pace a long answer outlasts any short drain, and its
        # gaps so far are in the pool
        attempted = len(records)
        failed = sum((r["finished"] and not r["ok"])
                     or r["first_token_t"] is None for r in records)
        cut = sum(not r["finished"] and r["first_token_t"] is not None
                  for r in records)
        notes.append(f"{cut} requests were still decoding {drain_s:g} s "
                     "after the window and were cut there")
    else:
        taken = [r for r in records if r["admit_t"] is not None
                 and r["admit_t"] >= t0]
        attempted = len(taken)
        failed = sum(r["finish_t"] is not None and not r["ok"] for r in taken)
        if nxt >= len(planned) and not sched.queue:
            notes.append("INVALID: the backlog ran out inside the window; "
                         "give the mix more requests")
            correct = False
    if built["programs_built"] or built["serving_programs_built"]:
        notes.append("INVALID: a program was compiled inside the window")
        correct = False
    if open_loop:
        notes.append(f"queue depth at the window's middle and end: "
                     f"{queue_at.get('middle')} and {queue_at.get('end')}")
    if open_loop and records:
        tt = sorted(1e3 * r["ttft"] for r in records)
        notes.append("ttft ms: " + ", ".join(
            f"{k} {v:.1f}" for k, v in (
                ("p50", percentile(tt, 50)), ("mean", sum(tt) / len(tt)),
                ("p90", percentile(tt, 90)), ("p95", percentile(tt, 95)),
                ("max", tt[-1]))))
    if open_loop and failed:
        notes.append(f"{failed} of {attempted} requests failed or had no "
                     f"first token {drain_s:g} s after the window")
    gaps = book.gaps
    window = log.facts(t0, t_end)
    # first-token times are per-layer metrics (request_stat over the records)
    e2e = {"serve_tokens_per_s": book.tokens / (t_end - t0),
           "itl_p95_ms": 1e3 * percentile(gaps, 95) if gaps else None}
    samples = {"itl_p95_ms": len(gaps)}
    if log.began:
        notes.append(log.note(window))
    notes.append(
        f"{attempted} requests, {failed} failed, {book.tokens} output "
        f"tokens in {t_end - t0:.3f} s ({srv._iterations} iterations in "
        f"all); {len(gaps)} gaps between tokens, p95 "
        f"{e2e['itl_p95_ms'] or 0.0:.4f} ms; in the window {built}")
    return Outcome(
        correct=bool(correct), attempted=attempted, failed=int(failed),
        end_to_end=e2e, setup_s=setup_s, samples=samples,
        facts={"requests": records, "decode_live_tokens": live,
               "window": window, "token_gaps": gaps,
               "slots": slots, "seq_len": int(mix["engine"]["max_len"])},
        notes=notes)
