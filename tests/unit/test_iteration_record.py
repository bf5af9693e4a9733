"""The serving iteration's record (observability/spans.py ``Iteration``,
``iterations``, ``explain``): one row a ``ServingEngine.step()``, always on,
on the lifecycle ring's clock, and every long row in exactly one cause."""

import gc
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import build_model, tiny_test
from deepspeed_tpu.observability import get_registry
from deepspeed_tpu.observability import spans as S

from _fake_clock import TickClock

SCFG = {"slots": 2, "max_len": 48, "prefill_chunk": 16}


@pytest.fixture(scope="module")
def tiny():
    """An inference engine and prompts of 9, 21, 12 and 44 tokens."""
    model = build_model(tiny_test(max_seq=64, dtype=jnp.float32))
    eng = ds.init_inference(model, model.init(jax.random.PRNGKey(0)),
                            {"dtype": "float32"})
    rng = np.random.default_rng(7)
    return eng, [rng.integers(0, 256, (n,)).astype(np.int32)
                 for n in (9, 21, 12, 44)]


@pytest.fixture(scope="module")
def warm(tiny):
    """A server on ``time.perf_counter`` that has served the prompts, so
    every program it needs for them is built: with chunks of 32 they end
    in buckets of 16, 32, 16 and (behind a whole chunk) 16, none in 8."""
    eng, prompts = tiny
    srv = ds.ServingEngine(eng, {**SCFG, "prefill_chunk": 32},
                           clock=time.perf_counter)
    srv.serve_batch(prompts, 4, seeds=list(range(4)))
    return srv


class NoRecord:
    """In place of an engine's ``_row``: the loop as it was without the
    record (a wait is the bare call)."""

    stepped = ahead = read_step = read_first = slots = 0
    chunks = finals = seats = 0

    def open(self, *a):
        pass

    close = write = open

    def wait(self, fetch, on):
        return fetch(on)

    def cause(self, built):
        return "off_cpu"


def mark() -> int:
    return S._rows_written


def since(mark: int) -> np.ndarray:
    """The rows written since ``mark``, whatever clock stamped them."""
    return S.iterations()[mark - S._rows_written:]


# ------------------------------------------------------------- the rows
def test_one_row_a_step_and_the_counts_are_the_registry_s(tiny):
    eng, prompts = tiny
    srv = ds.ServingEngine(eng, SCFG, clock=time.perf_counter)
    t = mark()
    out = srv.serve_batch(prompts * 2, 4, seeds=list(range(8)))
    rows = since(t)
    c = srv.stats.registry.snapshot()["counters"]
    assert len(rows) == c["Serve/iterations"] == srv._iterations
    assert list(rows["step"]) == list(range(srv._iterations))
    assert (rows["chunks"] + rows["finals"]).sum() \
        == c["Serve/prefill_chunks"]
    assert rows["finals"].sum() == rows["seats"].sum() == 8
    assert rows["read_first"].sum() == 8
    assert rows["stepped"].sum() == c["Serve/decode_steps"]
    assert rows["ahead"].sum() == c["Serve/decode_steps_ahead"] > 0
    # (the last step out ran rows the device had retired: never read)
    assert 0 <= rows["stepped"].sum() - rows["read_step"].sum() <= 1
    assert rows["tokens"].sum() == sum(len(o) for o in out) == 32
    # a row that dispatched a step says how many rows it ran
    stepped = rows[rows["stepped"] == 1]
    assert stepped["slots"].min() >= 1 and stepped["slots"].max() == 2
    # (the first iteration admits one request in front of its step and
    # the next behind it)
    assert rows["queue"][0] == 6 and rows["queue"][-1] == 0
    # the first iterations built programs; a row's stamps are in order and
    # its thread cannot have run longer than the row lasted
    assert rows["compiles"][0] > 0 and rows["compiles"][-1] == 0
    assert (rows["t1"] > rows["t0"]).all()
    assert (rows["t0"][1:] >= rows["t1"][:-1]).all()
    assert (rows["cpu_s"] <= rows["t1"] - rows["t0"]).all()
    assert (rows["cpu_s"] > 0).all() and (rows["wait_s"] >= 0).all()
    assert (rows["wait_s"] <= rows["t1"] - rows["t0"]).all()


def test_a_row_s_stamps_lie_inside_its_srv_step_span_s(tiny):
    """Row for span by ``step``, within 5 us where both clocks are
    ``perf_counter``: one clock read apart. (A shared sandbox can take the
    thread off the CPU between any two reads, so nine rows in ten and the
    median are held to it, and every row to the span's inside.)"""
    eng, prompts = tiny
    srv = ds.ServingEngine(eng, {**SCFG, "spans": True},
                           clock=time.perf_counter)
    t = mark()
    srv.serve_batch(prompts, 4, seeds=list(range(4)))
    rows = since(t)
    spans = {e.step: e for e in srv.spans.events() if e.kind == S.SRV_STEP}
    assert len(rows) == len(spans) == srv._iterations
    d0 = np.array([r["t0"] - spans[int(r["step"])].t0 for r in rows])
    d1 = np.array([spans[int(r["step"])].t1 - r["t1"] for r in rows])
    assert (d0 >= 0).all() and (d1 >= 0).all()
    for d in (d0, d1):
        assert np.median(d) < 5e-6 and np.mean(d < 5e-6) >= 0.9, d


def test_an_engine_s_clock_is_read_as_often_as_without_the_record(tiny):
    eng, prompts = tiny
    reads = []
    for record in (True, False):
        clock = TickClock()
        srv = ds.ServingEngine(eng, {**SCFG, "spans": True}, clock=clock)
        if not record:
            srv._row = NoRecord()
        for p in prompts:
            srv.submit(p, 4)
        for _ in range(10):
            srv.step()
        reads.append(round(clock.t / clock.dt))
    assert reads[0] == reads[1] > 10


def test_the_record_adds_no_program(tiny):
    eng, prompts = tiny
    backend = get_registry().counter("Compile/programs")
    built = []
    for record in (True, False, True):
        b0 = backend.value
        srv = ds.ServingEngine(eng, SCFG)
        if not record:
            srv._row = NoRecord()
        srv.serve_batch(prompts, 4, seeds=list(range(4)))
        built.append((srv.compiles, backend.value - b0))
    assert built[0] == built[1] == built[2]


def test_the_ring_keeps_the_newest_rows(monkeypatch):
    monkeypatch.setattr(S, "ROWS", 8)
    monkeypatch.setattr(S, "_rows", np.zeros(8, S.ROW))
    monkeypatch.setattr(S, "_rows_written", 0)
    it = S.Iteration()
    for step in range(5):
        it.open(step, 0, 0)
        it.close()
        it.write(0, 1, 2, 3)
    assert list(S.iterations()["step"]) == [0, 1, 2, 3, 4]
    for step in range(5, 21):
        it.open(step, 0, 0)
        it.close()
        it.write(0, 1, 2, 3)
    rows = S.iterations()
    assert list(rows["step"]) == list(range(13, 21))
    assert (np.diff(rows["t0"]) > 0).all()
    # cut to a window by when a row began
    assert list(S.iterations(t0=rows["t0"][2], t1=rows["t0"][5])["step"]) \
        == [15, 16, 17, 18]
    assert S.long_iterations()["rows"] == 8


# ------------------------------------------------------------ the causes
def fake_rows(walls, **fields) -> np.ndarray:
    rows = np.zeros(len(walls), S.ROW)
    rows["step"] = np.arange(len(walls))
    rows["t0"] = np.concatenate([[0.0], np.cumsum(walls)[:-1]])
    rows["t1"] = rows["t0"] + walls
    rows["gc_gen"] = -1
    rows["cpu_s"], rows["wait_s"] = 0.002, 0.007
    for name, values in fields.items():
        for i, v in values.items():
            rows[name][i] = v
    return rows


def test_every_long_row_stands_in_one_cause_and_they_add_up():
    walls = np.full(40, 0.010)
    long = {3: 0.5, 7: 0.13, 11: 0.12, 12: 0.05, 20: 0.125, 25: 0.115,
            30: 0.021, 33: 0.0199}
    for i, w in long.items():
        walls[i] = w
    rows = fake_rows(
        walls,
        compiles={3: 2}, gc_s={3: 0.4, 7: 0.07}, gc_gen={3: 2, 7: 2},
        cpu_s={7: 0.08, 20: 0.115},
        chunks={11: 1}, wait_s={11: 0.11, 12: 0.045, 25: 0.11, 30: 0.008})
    ex = S.explain(rows)
    assert ex["rows"] == 40 and ex["median_ms"] == pytest.approx(10.0)
    # 0.0199 is not over twice the median: seven long rows
    assert ex["long"] == 7
    got = {c: (v["count"], round(v["ms"], 3))
           for c, v in ex["causes"].items()}
    assert got == {"compile": (1, 500.0), "gc": (1, 130.0),
                   # 11 dispatched the chunk; 12 reads behind it
                   "prefill": (2, 170.0), "on_cpu": (1, 125.0),
                   "device_wait": (1, 115.0), "off_cpu": (1, 21.0)}
    assert ex["long_ms"] == pytest.approx(
        sum(v["ms"] for v in ex["causes"].values())) \
        == pytest.approx(1e3 * sum(w for w in long.values() if w > 0.02))
    assert ex["program_ms"] == pytest.approx(755.0)
    assert ex["machine_ms"] == pytest.approx(136.0)
    assert ex["program_ms"] + ex["machine_ms"] <= ex["long_ms"]
    assert [r["step"] for r in ex["longest"]] == [3, 7, 20, 11, 25]
    assert ex["longest"][0]["cause"] == "compile"
    assert set(ex["longest"][0]) == set(S.ROW.names) | {"ms", "cause"}
    json.dumps(ex)                      # a flight dump's metrics.json
    # a freeze inside a wait behind a chunk is not the chunk's: among
    # twenty sound waits of 30 ms one of 150 is the device's or the thread's
    walls = np.full(60, 0.010)
    walls[20:40] = 0.035
    walls[30] = 0.155
    frozen = fake_rows(walls, finals={i: 1 for i in range(20, 40)},
                       wait_s={**{i: 0.030 for i in range(20, 40)},
                               30: 0.150})
    ex = S.explain(frozen)
    assert (ex["causes"]["prefill"]["count"],
            ex["causes"]["device_wait"]["count"]) == (19, 1)
    assert ex["longest"][0]["step"] == 30
    # the thread clock reads in ticks of 10 ms on some hosts: a tick in a
    # row of 21 ms says nothing, twelve in one of 125 ms do
    ticks = fake_rows(walls[:20].copy(), cpu_s={i: 0.0 for i in range(20)})
    ticks["t1"][5], ticks["cpu_s"][5] = ticks["t0"][5] + 0.021, 0.010
    ticks["t1"][9], ticks["cpu_s"][9] = ticks["t0"][9] + 0.125, 0.120
    ticks["cpu_s"][12] = 0.010
    ex = S.explain(ticks)
    assert {r["step"]: r["cause"] for r in ex["longest"]} \
        == {5: "off_cpu", 9: "on_cpu"}
    # the line is the caller's: at 1.9x the row of 19.9 ms counts too
    assert S.explain(rows, over=1.9)["long"] == 8
    none = S.explain(rows[:0])
    assert none["long"] == 0 and none["long_ms"] == 0.0


def served_rows(srv, prompts, inject, *, running=2, before=12, fresh=()):
    """Rows of a warm server: ``before`` iterations with ``running``
    requests decoding, then ``inject()`` (which arms what makes one
    iteration long; it returns what disarms it), one iteration with
    ``fresh`` in the queue, and a few more."""
    for p in prompts[:running]:
        srv.submit(p, 24)
    t = mark()
    for _ in range(before):
        srv.step()
    for p in fresh:
        srv.submit(p, 3)
    disarm = inject()
    srv.step()
    at = srv._iterations - 1
    disarm()
    for _ in range(3):
        srv.step()
    rows = since(t)
    srv.drain()
    srv.end_drain()
    srv.results.clear()
    return rows, at


def patched(obj, name, new):
    old = getattr(obj, name)
    setattr(obj, name, new)
    return lambda: setattr(obj, name, old)


TAKEN = [0.0]       # CPU seconds this sandbox's load took from ``spin``


def spin(seconds):
    """A busy loop. What the machine's other tenants take from it meanwhile
    is booked to ``TAKEN``, which the case's thread clock gives back: the
    case is the loop's own Python, not a loaded sandbox (where a thread
    that gets half a core IS off the CPU half the time)."""
    t, cpu = time.perf_counter(), time.thread_time()
    while time.perf_counter() - t < seconds:
        pass
    TAKEN[0] += (time.perf_counter() - t) - (time.thread_time() - cpu)


def test_a_forced_collection_is_gc(warm, tiny):
    srv, was = warm, warm.stats.on_iteration
    reg = get_registry()
    s0 = reg.counter("Host/gc_s").value
    n0 = reg.counter("Host/gc_passes_gen2").value

    def collecting(*a, **k):
        gc.collect(2)
        return was(*a, **k)

    rows, at = served_rows(
        srv, tiny[1], lambda: patched(srv.stats, "on_iteration", collecting))
    row = rows[rows["step"] == at][0]
    assert row["gc_gen"] == 2 and row["gc_s"] > 0
    assert row["gc_s"] < row["t1"] - row["t0"]
    ex = S.explain(rows)
    assert [(r["step"], r["cause"]) for r in ex["longest"][:1]] \
        == [(at, "gc")]
    assert reg.counter("Host/gc_passes_gen2").value >= n0 + 1
    assert reg.counter("Host/gc_s").value - s0 >= row["gc_s"]


@pytest.mark.parametrize("cause", ["off_cpu", "on_cpu", "compile",
                                   "device_wait"])
def test_a_long_iteration_says_why(warm, tiny, cause, monkeypatch):
    srv, prompts = warm, tiny[1]
    monkeypatch.setattr(S, "_thread_time",
                        lambda: time.thread_time() + TAKEN[0])
    admit, fetch = srv._admit, jax.device_get
    fresh, running = [prompts[0]], 1

    def slow_admit(how):
        def admitting():
            how(0.06)
            return admit()
        return lambda: patched(srv, "_admit", admitting)

    if cause == "off_cpu":
        inject = slow_admit(time.sleep)
    elif cause == "on_cpu":
        inject = slow_admit(spin)
    elif cause == "compile":
        # five tokens end in a bucket of 8, which nothing has built
        fresh, inject = [prompts[0][:5]], lambda: (lambda: None)
    else:
        # both slots taken, nothing queued: no chunk in front of the read
        fresh, running = [], 2

        def slow_fetch(what):
            time.sleep(0.06)
            return fetch(what)
        inject = lambda: patched(jax, "device_get", slow_fetch)  # noqa: E731
    rows, at = served_rows(srv, prompts, inject, running=running,
                           fresh=fresh)
    ex = S.explain(rows)
    long = {r["step"]: r for r in ex["longest"]}
    assert at in long, (cause, ex)
    row = long[at]
    assert row["cause"] == cause, row
    if cause == "compile":
        assert row["compiles"] > 0
    elif cause == "device_wait":
        assert row["wait_s"] >= 0.06 and row["chunks"] + row["finals"] == 0
    else:
        assert row["compiles"] == 0 and row["wait_s"] < 0.03
        assert (row["cpu_s"] >= 0.05) == (cause == "on_cpu")
    assert ex["causes"][cause]["count"] >= 1


def test_a_chunk_in_front_of_a_long_read_is_prefill(warm, tiny, monkeypatch):
    """On a fake clock, set on the lifecycle ring and not on the engine:
    the read behind a chunk takes 0.2 s of it."""
    srv, prompts = warm, tiny[1]
    clock = TickClock()
    monkeypatch.setattr(S._LIFECYCLE, "clock", clock)
    fetch = jax.device_get

    def slow_fetch(what):
        clock.advance(0.2)
        return fetch(what)

    rows, at = served_rows(
        srv, prompts, lambda: patched(jax, "device_get", slow_fetch),
        running=1, fresh=[prompts[3]])
    row = rows[rows["step"] == at][0]
    assert row["chunks"] + row["finals"] >= 1 and row["wait_s"] >= 0.2
    # the engine's own clock was not touched: its stamps are perf_counter's
    assert srv.stats.clock is time.perf_counter
    ex = S.explain(rows)
    assert ex["longest"][0]["step"] == at
    assert ex["longest"][0]["cause"] == "prefill"
    assert ex["program_ms"] + ex["machine_ms"] \
        == pytest.approx(ex["long_ms"] - ex["causes"]["prefill"]["ms"])


# ------------------------------------------------ the collector's callbacks
def test_the_collector_s_callbacks_are_registered_once():
    others = [cb for cb in gc.callbacks
              if not getattr(cb, "of_the_seam", False)]
    for _ in range(3):
        S._watch_gc()
    assert gc.callbacks[0] is S._gc_starts
    assert gc.callbacks[-1] is S._gc_stops
    assert gc.callbacks[1:-1] == others
    # JAX's own stands between them: its work is inside the pass
    assert any(getattr(cb, "__name__", "") == "_xla_gc_callback"
               for cb in others)
    snap = get_registry().snapshot()["counters"]
    assert "Host/gc_s" in snap and "Host/gc_passes_gen2" in snap
    s0, w0 = S._gc_s, S._gc_weighted
    gc.collect(1)
    assert S._gc_s > s0 and (S._gc_weighted - w0) >> 16 == 1


# ------------------------------------------------------- the operator's use
def test_a_flight_dump_holds_the_long_iterations(tiny, tmp_path):
    from deepspeed_tpu.observability.flight import read_flight_record

    eng, prompts = tiny
    clock = TickClock()
    srv = ds.ServingEngine(eng, {**SCFG, "flight_dir": str(tmp_path),
                                 "watchdog_s": 1e-9, "spans": True},
                           clock=clock)
    srv.serve_batch(prompts[:2], 4, seeds=[0, 1])
    # every step is over a watchdog of a nanosecond: the first dumped
    rec = read_flight_record(srv.flight.dumps[0])
    assert rec["manifest"]["reason"] == "watchdog_stall"
    long = rec["metrics"]["long_iterations"]
    assert set(long["causes"]) == set(S.CAUSES) and long["rows"] >= 1
    notes = [e for e in srv.spans.events() if e.kind == S.MARKER
             and e.meta["name"] == "watchdog_stall"]
    assert notes and all(n.meta["cause"] in S.CAUSES for n in notes)
    # the dump that a manual call makes holds the ring as it stands now
    rec = read_flight_record(srv.dump_flight("manual"))
    assert rec["metrics"]["long_iterations"]["rows"] >= srv._iterations
