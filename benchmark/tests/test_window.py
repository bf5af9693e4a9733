"""The serving kinds' window driven end to end on a test double: the real
``serve()`` loop, book and window log around an engine that emits tokens on
a clock the test owns. One iteration is made to stand still for 300 ms, as
the chip machine does a few times a window: ``serve_tokens_per_s``, all the
window's tokens over all its time, falls by the stall's share and
``host.stall_ms`` counts it; ``serve.tokens_per_s_less_stalls`` beside them
does not move."""

import json
import os
import types

import pytest

from benchmark import harness
from benchmark import reduce as R
from benchmark.kinds import _serving

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
STEP_S = 0.05
ANSWER = 50


class Clock:
    def __init__(self):
        self.t = 100.0

    def perf_counter(self):
        return self.t

    def sleep(self, s):
        self.t += s


class FakeRequest:
    def __init__(self, rid, prompt_len, max_new, now):
        self.rid, self.prompt_len, self.max_new = rid, prompt_len, max_new
        self.tokens = []
        self.submit_t = now
        self.admit_t = self.first_token_t = self.finish_t = None
        self.ok = self.finished = False


class FakeScheduler:
    def __init__(self):
        self.queue, self.running = [], {}

    @property
    def queue_depth(self):
        return len(self.queue)

    @property
    def idle(self):
        return not self.queue and not self.running


class FakeEngine:
    """One request admitted and one token per running request each step,
    ``STEP_S`` on the clock; step number ``stall_at`` takes 300 ms more.
    Every answer is ``ANSWER`` tokens, so once the ramp is over the same
    number of requests is running at every step and every stretch of the
    window holds the same work: what a stall does to a rate is then the
    statistic's doing alone."""

    def __init__(self, clock, slots, stall_at):
        self.clock, self.slots, self.stall_at = clock, slots, stall_at
        self.sched = FakeScheduler()
        self.results = {}
        self.compiles = 0
        self._prefill = None
        self._iterations = 0

    def submit(self, prompt, max_new, seed=None):
        rid = len(self.results) + len(self.sched.queue) + len(
            self.sched.running)
        self.sched.queue.append(
            FakeRequest(rid, len(prompt), max_new, self.clock.t))
        return rid

    def step(self):
        self._iterations += 1
        self.clock.t += STEP_S + (0.3 if self._iterations == self.stall_at
                                  else 0.0)
        now, sched = self.clock.t, self.sched
        if sched.queue and len(sched.running) < self.slots:
            r = sched.queue.pop(0)
            r.admit_t = r.first_token_t = now
            sched.running[r.rid] = r
        done = []
        for r in list(sched.running.values()):
            r.tokens.append(7)
            if len(r.tokens) >= ANSWER:
                r.finish_t, r.ok, r.finished = now, True, True
                done.append(sched.running.pop(r.rid))
                self.results[r.rid] = r
        return done


def run_backlog(monkeypatch, stall_at):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = harness.load_cell(spec, "gpt2-774m.serve-backlog", 3000000017,
                             40.0, False, False, 0.0)
    clock = Clock()
    cell.t_process = clock.t
    cell.watch = types.SimpleNamespace(
        mark=lambda: None,
        since=lambda _: {"cache_hits": 0, "cache_misses": 0,
                         "programs_built": 0})
    slots = int(cell.mix["engine"]["slots"])
    import deepspeed_tpu as ds

    monkeypatch.setattr(_serving, "time", clock)
    monkeypatch.setattr(_serving, "settle_host", lambda: None)
    monkeypatch.setattr(_serving, "build", lambda c: (
        types.SimpleNamespace(vocab_size=50257), None, None))
    for check in ("check_logits", "check_served"):
        monkeypatch.setattr(_serving, check, lambda *a, **k: True)
    monkeypatch.setattr(_serving, "warm_buckets", lambda *a, **k: None)
    monkeypatch.setattr(ds, "ServingEngine",
                        lambda eng, conf, **_: FakeEngine(
                            clock, slots, stall_at))
    out = _serving.serve(cell, open_loop=False)
    return spec, cell, out


def test_an_injected_stall_moves_the_rate_and_is_counted(monkeypatch):
    spec, cell, clean = run_backlog(monkeypatch, stall_at=None)
    assert clean.correct and clean.failed == 0
    assert len(clean.facts["window"]["durations"]) == 800
    # the ramp is set-up; stall the 300th iteration of the window
    _, _, stalled = run_backlog(monkeypatch,
                                stall_at=stalled_iteration(clean, 300))
    assert stalled.correct
    a, b = (o.end_to_end["serve_tokens_per_s"] for o in (clean, stalled))
    # one request admitted a step and ANSWER steps to an answer: ANSWER are
    # running at every step, or as many as the mix has slots
    slots = int(cell.mix["engine"]["slots"])
    assert a == pytest.approx(min(ANSWER, slots) / STEP_S)
    # the window closes at the first iteration's end past 40 s
    assert b == pytest.approx(a * 40.0 / 40.3, rel=2e-3)
    layers = [harness.per_layer_metrics(spec, cell, o.facts)
              for o in (clean, stalled)]
    less = [m["serve.tokens_per_s_less_stalls"]["value"] for m in layers]
    assert less == [pytest.approx(a), pytest.approx(a, rel=2e-3)]
    assert [m["host.stall_ms"]["value"] for m in layers] == \
        [0.0, pytest.approx(350.0)]
    # every token the window's iterations handed back is in both rates
    w = stalled.facts["window"]
    assert sum(w["counts"]) == pytest.approx(b * (w["t1"] - w["t0"]))
    assert sum(w["counts"]) == pytest.approx(
        less[1] * (w["t1"] - w["t0"] - 0.3))
    assert any("350.0 ms in 1 iterations" in n for n in stalled.notes)
    # the tail is the tail of all the window's gaps, on both sides
    for o in (clean, stalled):
        assert o.end_to_end["itl_p95_ms"] == pytest.approx(
            R.token_gap_stat(o.facts))


def stalled_iteration(clean, nth):
    """The engine's count of the window's ``nth`` iteration: the ramp's
    iterations come first."""
    note = next(n for n in clean.notes if n.startswith("after a ramp of"))
    return int(note.split()[4]) + nth
