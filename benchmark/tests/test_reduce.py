"""The reducers, on cases small enough to check by hand and on two cuts
recorded from this PR's own first traced runs on the v5e (PR 24)."""

import json
import os

import pytest

from benchmark import reduce as R

DATA = os.path.join(os.path.dirname(__file__), "data")
DEV = "/device:TPU:0"


def recorded(name, window=None):
    with open(os.path.join(DATA, name)) as f:
        d = json.load(f)
    if window is not None:
        d["window"] = list(window)
    return R.Trace.from_json(d)


def by_hand():
    """One device, window [0, 10]: compute 0-4, an all-gather in flight 3-6
    (its start and done are short ops), compute 7-8; two runs of program p
    (0-4, 7-8) with one of q between them."""
    ops = {DEV: [("while.1", 0.0, 4.0), ("fusion.1", 0.0, 2.0),
                 ("flash_attention_fwd.3", 2.0, 4.0),
                 ("all-gather-start.1", 3.0, 3.1),
                 ("all-gather-done.1", 5.9, 6.0), ("fusion.2", 7.0, 8.0)]}
    async_ops = {DEV: [("all-gather-start.1", 3.0, 6.0),
                       ("copy-start.2", 0.5, 9.5)]}
    modules = {DEV: [("jit_p(1)", 0.0, 4.0), ("jit_q(2)", 5.0, 6.5),
                     ("jit_p(1)", 7.0, 8.0)]}
    spans = [("bench.traced_window", 0.0, 10.0), ("bench.step", 0.0, 6.6),
             ("bench.readback", 4.0, 5.0), ("bench.book", 6.6, 10.0)]
    return R.Trace(ops, modules, spans, None, async_ops,
                   {"fusion.1": "bf16[8,8]"})


@pytest.mark.parametrize("iv, want", [
    ([(0, 1), (0.5, 2), (3, 4), (4, 5), (7, 6)], [(0, 2), (3, 5)]),
    ([], []),
])
def test_merge(iv, want):
    assert R.merge(iv) == want


def test_subtract_clip_gaps():
    assert R.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert R.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]
    assert R.gaps([(1, 2), (4, 6)], 0, 7) == [(0, 1), (2, 4), (6, 7)]
    assert R.total([(0, 1), (2, 4)]) == 3


def test_idle_share_by_hand():
    # busy: 0-4, 5.9-6, 7-8 = 5.1 of 10 (the collective in flight is no op)
    assert R.idle_share({"trace": by_hand()}) == pytest.approx(49.0)


def test_collective_exposed_by_hand():
    # in flight 3-6, compute covers 3-4: 2 of 10 exposed; the copy is no
    # collective
    assert R.collective_exposed({"trace": by_hand()}) == pytest.approx(20.0)


def test_program_time_and_gap_by_hand():
    f = {"trace": by_hand()}
    assert R.program_time(f, program=r"^jit_p\(", statistic="mean") == \
        pytest.approx(2500.0)
    # busy inside the first run of p is all 4 s; inside the second, 1 s
    assert R.program_time(f, program=r"^jit_p\(", measure="busy",
                          statistic="sum") == pytest.approx(5000.0)
    # after the first p the next program starts 1 s later; after the last, none
    assert R.gap_after(f, program=r"^jit_p\(") == pytest.approx(1000.0)
    assert R.program_time(f, program="nothing") is None


def test_kernel_roofline_by_hand():
    from benchmark.kernels import flash_attention as K

    model = {"n_head": 2, "n_embd": 128}
    flops, nbytes = K.ops_and_bytes("flash_attention_fwd", batch=1, heads=2,
                                    seq=256, head_dim=64)
    assert flops == 2 * 2 * (256 * 257 // 2) * 64 * 2
    peaks = {"bf16_flops_per_s": flops / 1.0, "hbm_bytes_per_s": nbytes * 9}
    facts = {"trace": by_hand(), "model": model, "rows_per_chip": 1,
             "seq_len": 256, "peaks": peaks}
    # the one call took 2 s; at these made-up peaks its least time is 1 s
    assert R.kernel_roofline(facts, kernel="flash_attention") == \
        pytest.approx(50.0)
    assert any("bound by compute" in n for n in facts["notes"])


def test_breakdown_by_hand():
    b = R.breakdown(by_hand())
    ops = dict((k.split()[0], v) for k, v in b["device_ops"])
    assert "while.1" not in ops                   # control flow is no work
    assert ops["fusion.1"] == pytest.approx(2.0)
    assert b["device_ops"][0][0] in ("fusion.1 bf16[8,8]",
                                     "flash_attention_fwd.3")
    idle = dict(b["idle_gaps"])
    # 4-5 under the read-back (the inner span), 5-5.9 and 6-6.6 under step,
    # 8-10 (and 6.6-7) under book
    assert idle["bench.readback"] == pytest.approx(1.0)
    assert idle["bench.step"] == pytest.approx(1.5)
    assert idle["bench.book"] == pytest.approx(2.4)


def test_self_times_nested():
    t = R.self_times([("while.1", 0, 10), ("a", 1, 3), ("b", 4, 9),
                      ("c", 5, 6)])
    assert t == {"while.1": 3, "a": 2, "b": 4, "c": 1}


def test_request_stat_and_percentile():
    reqs = [{"late": 0.001 * i, "admit_t": 1.0 + i, "submit_t": 1.0}
            for i in range(1, 101)]
    f = {"requests": reqs}
    assert R.request_stat(f, field="late", statistic="p95") == \
        pytest.approx(95.0)
    assert R.request_stat(f, field="admit_t", minus="submit_t",
                          statistic="median", scale=1.0) == pytest.approx(50.5)
    assert R.percentile([5, 1, 3], 95) == 5
    assert R.request_stat({"requests": []}, field="late") is None


def test_recorded_train_step_boundary():
    """24 ms around the end of one train step and the start of the next."""
    tr = recorded("train_step_boundary.json")
    assert tr.devices == [DEV]
    assert any(n.startswith("jit__train_step_impl(") for n, _, _ in
               tr.modules[DEV])
    names = {R.base_name(n) for n, _, _ in tr.ops[DEV]}
    assert "fused_xent_fwd" in names or "flash_attention_fwd" in names \
        or any(n.startswith("fusion") for n in names)
    # one step follows the other without a pause the host could fill
    assert R.idle_share({"trace": tr}) < 1.0
    b = R.breakdown(tr)
    assert 0 < len(b["device_ops"]) <= 10
    assert all(s > 0 for _, s in b["device_ops"])


def test_recorded_decode_step_start():
    """2 ms before and 6 ms into one slot decode step of the backlog cell."""
    tr = recorded("decode_step_start.json")
    mod = [m for m in tr.modules[DEV] if m[0].startswith("jit__step_impl(")]
    assert len(mod) == 1
    calls = [e for e in tr.ops[DEV]
             if R.base_name(e[0]) == "decode_attention"]
    assert calls and all(b > a for _, a, b in calls)
    # the device idles until the step is launched, and the host is inside
    # ServingEngine.step meanwhile
    idle = dict(R.breakdown(tr)["idle_gaps"])
    assert max(idle, key=idle.get) in ("bench.engine_step",
                                       "bench.bookkeeping")
    assert 5.0 < R.idle_share({"trace": tr}) < 40.0
    whole = recorded("decode_step_start.json",
                     window=(mod[0][1] - 0.001, mod[0][2] + 0.001))
    assert R.program_time({"trace": whole},
                          program=r"^jit__step_impl\(") == \
        pytest.approx(1e3 * (mod[0][2] - mod[0][1]))


def test_idle_gaps_are_named_by_the_program_s_spans_inside_the_benchmark_s():
    """``load_trace`` keeps ``ds.``-prefixed annotations beside ``bench.``:
    a gap goes to the innermost, so the admission inside ``engine_step``
    gets its own name and what it leaves stays the step's."""
    ops = {DEV: [("fusion.1", 0.0, 1.0), ("fusion.2", 4.0, 5.0)]}
    spans = [("bench.traced_window", 0.0, 5.0), ("bench.engine_step", 0.5, 4.5),
             ("ds.srv.step", 0.6, 4.4), ("ds.srv.admit", 1.2, 2.0),
             ("ds.srv.decode_readback", 3.0, 4.4)]
    b = R.breakdown(R.Trace(ops, {DEV: []}, spans))
    idle = dict(b["idle_gaps"])
    assert idle["ds.srv.admit"] == pytest.approx(0.8)
    assert idle["ds.srv.decode_readback"] == pytest.approx(1.0)
    assert idle["ds.srv.step"] == pytest.approx(1.2)      # 1-1.2 and 2-3
    assert "bench.engine_step" not in idle               # all of it is inside
    # no metric reads the spans: the same trace without the program's
    without = R.Trace(ops, {DEV: []}, [s for s in spans
                                       if s[0].startswith("bench.")])
    for tr in (R.Trace(ops, {DEV: []}, spans), without):
        assert R.idle_share({"trace": tr}) == pytest.approx(60.0)
        assert tr.window == (0.0, 5.0)


def uniform(n, dt, tokens, stall_at=None, stall=0.0, slower=1.0):
    """``n`` iterations of ``dt`` seconds (times ``slower``) handing back
    ``tokens`` each, one of them ``stall`` seconds longer: the window they
    make, as the serving kinds record it."""
    durations = [dt * slower + (stall if i == stall_at else 0.0)
                 for i in range(n)]
    return {"window": {"t0": 0.0, "t1": sum(durations),
                       "counts": [tokens] * n, "durations": durations}}


def test_stalls_by_hand():
    assert R.STALL_OVER == 2.0
    d = uniform(800, 0.05, 60, stall_at=300, stall=0.3)["window"]["durations"]
    assert R.stalls(d) == (pytest.approx(0.35), pytest.approx(0.3), 1)
    # 1.9x the median is a long iteration, not a stall; 2.1x is one
    assert R.stalls([0.05] * 9 + [0.095]) == (0.0, 0.0, 0)
    assert R.stalls([0.05] * 9 + [0.105])[2] == 1
    # a run that is slow throughout has none: the median moves with it
    assert R.stalls([0.2] * 50) == (0.0, 0.0, 0)
    assert R.stalls([]) == (0.0, 0.0, 0)


def test_a_stall_moves_the_window_s_rate_and_not_the_rate_less_stalls():
    clean = uniform(800, 0.05, 60)
    stalled = uniform(800, 0.05, 60, stall_at=300, stall=0.3)
    # all the tokens over all the time: the end-to-end rate's arithmetic
    assert R.window_rate(clean) == pytest.approx(1200.0)
    assert R.window_rate(stalled) == pytest.approx(1200 * 40 / 40.3)
    assert R.window_rate(stalled, less_stalls=True) == pytest.approx(1200.0)
    assert R.stall_time(clean) == 0.0
    assert R.stall_time(stalled) == pytest.approx(350.0)
    # every token of the window is counted once in both
    w = stalled["window"]
    assert sum(w["counts"]) == 48000
    assert R.window_rate(stalled) * (w["t1"] - w["t0"]) == pytest.approx(48000)
    assert R.stall_time({}) is None and R.window_rate({}) is None


def test_a_uniform_slowdown_moves_both_rates_by_as_much():
    slow = uniform(800, 0.05, 60, slower=1.02)
    assert R.window_rate(slow) == pytest.approx(1200 / 1.02)
    assert R.window_rate(slow, less_stalls=True) == pytest.approx(1200 / 1.02)
    assert R.stall_time(slow) == 0.0


def test_token_gap_stat_is_the_end_to_end_tail_s_arithmetic():
    gaps = [0.05] * 90 + [0.0] * 5 + [0.06] * 4 + [0.4]
    assert R.token_gap_stat({"token_gaps": gaps}) == \
        pytest.approx(1e3 * R.percentile(gaps, 95)) == pytest.approx(50.0)
    assert R.token_gap_stat({"token_gaps": gaps}, statistic="p99") == \
        pytest.approx(60.0)
    assert R.token_gap_stat({"token_gaps": []}) is None
    assert R.token_gap_stat({}) is None
