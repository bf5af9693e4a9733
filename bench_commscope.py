"""Communication-observatory bench: measure the measurement harness.

Full mode (bench_all chain; in this process, exits non-zero without a
TPU — only a TPU's profiler has the device op timeline the anatomy is
reduced from): run a short sharded train job with the profiler TraceWindow
open, decompose the capture through
``deepspeed_tpu/observability/commscope.py`` (exposed vs overlapped
collective time, per-kind achieved bus bandwidth vs the ICI roofline), and
write the rows into ``COMMSCOPE_BENCH.json``.

``--smoke`` is the CPU tier-1 gate (wired via
tests/unit/test_commscope.py, same pattern as bench_capacity.py):

1. fake-trace decomposition TILES the step wall — compute + exposed
   collective + other sums to the window within 1% (exact by
   construction; the gate pins it numerically);
2. the achieved-bandwidth ledger's byte column matches
   ``comm.hlo_analysis.collective_totals`` EXACTLY for a hand-built HLO
   program covering every collective kind;
3. compile freeze: a training engine with the observatory ENABLED takes
   the same number of compiled programs as one without, loss
   bit-identical, and ``comm_observatory()`` on the CPU capture degrades
   to nulls without raising;
4. the doctor's ``[comm]`` gate trips on a burning straggler gauge and
   passes clean;
5. the straggler detector flags a single slow device (right id) and
   stays silent on a uniform slowdown.

Prints one JSON line ending in "smoke-pass"; exits nonzero on failure.
"""

import json
import os
import sys
import tempfile

_ROOT = os.path.dirname(os.path.abspath(__file__))
_OUT = os.path.join(_ROOT, "COMMSCOPE_BENCH.json")


# ------------------------------------------------------------- fake trace
def make_fake_trace(n_steps=3, step_ms=100.0, devices=2):
    """Synthetic profiler capture with KNOWN anatomy per 100ms step:
    compute [0,40)+[50,70), an all-reduce [35,55) (10ms exposed), a
    reduce-scatter [80,90) (fully exposed) → per step: compute 60ms,
    collective 30ms, exposed 20ms, other 20ms. Returns (trace dict,
    windows, truth dict)."""
    evs = []
    for d in range(devices):
        pid = 10 + d
        evs.append({"ph": "M", "name": "process_name", "pid": pid,
                    "args": {"name": f"/device:TPU:{d}"}})
        for s in range(n_steps):
            base = s * step_ms * 1e3          # us
            for ts, dur, name in (
                    (0.0, 40e3, f"fusion.{s}"),
                    (35e3, 20e3, f"all-reduce.{s}"),
                    (50e3, 20e3, f"fusion.tail.{s}"),
                    (80e3, 10e3, f"reduce-scatter.{s}")):
                evs.append({"ph": "X", "pid": pid, "tid": 1 + (d % 2),
                            "ts": base + ts, "dur": dur, "name": name})
    windows = [(s * step_ms * 1e-3, (s + 1) * step_ms * 1e-3)
               for s in range(n_steps)]
    truth = {"wall_s": step_ms * 1e-3 * n_steps,
             "compute_s": 0.060 * n_steps,
             "collective_s": 0.030 * n_steps,
             "exposed_s": 0.020 * n_steps,
             "other_s": 0.020 * n_steps}
    return {"traceEvents": evs}, windows, truth


# every collective kind, hand-built (the ledger-bytes oracle)
_HAND_HLO = """
ENTRY main {
  %ar = f32[8,128]{1,0} all-reduce(%p0), to_apply=%add
  %rs = f32[2,128]{1,0} reduce-scatter(%p0), dimensions={0}, to_apply=%add
  %ag = bf16[16,128]{1,0} all-gather(%p0), dimensions={0}
  %a2a = (f32[1,16]{1,0}, f32[1,16]{1,0}) all-to-all(%a, %b), replica_groups={{0,1}}
  %cp = f32[64]{0} collective-permute(%p0), source_target_pairs={{0,1}}
  %cb = f32[32]{0} collective-broadcast(%p0), replica_groups={{0,1}}
}
"""


def build_engine(commscope: bool, trace_dir=None, seed=0):
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, tiny_test

    obs = {}
    if commscope:
        obs = {"commscope": {"enabled": True}, "spans": True}
        if trace_dir:
            obs.update({"trace_steps": [1, 3], "trace_dir": trace_dir})
    n = len(jax.devices())
    mesh = {"data": n // 2, "model": 2} if n % 2 == 0 and n > 1 \
        else {"data": n}
    return ds.initialize({
        "train_batch_size": 2 * max(1, mesh["data"]),
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
        "seed": seed,
        "mesh": mesh,
        "observability": obs,
    }, build_model(tiny_test(max_seq=32)))


def train_steps(eng, steps):
    from deepspeed_tpu.runtime.dataloader import (DataLoader,
                                                  random_token_dataset)

    data = random_token_dataset(eng.train_batch_size, seq_len=32,
                                vocab_size=256)
    batch = DataLoader(data, local_batch_size=eng.train_batch_size,
                       shuffle=False).collate_fn(data)
    return [float(eng.train_batch(batch)["loss"]) for _ in range(steps)]


# ------------------------------------------------------------------ smoke
def smoke():
    from deepspeed_tpu.comm.hlo_analysis import collective_totals
    from deepspeed_tpu.observability import doctor
    from deepspeed_tpu.observability.commscope import (CommScope,
                                                       CommScopeConfig,
                                                       StragglerDetector)

    # (1) fake-trace decomposition tiles the step wall within 1%
    trace, windows, truth = make_fake_trace()
    cs = CommScope(CommScopeConfig(enabled=True), n_devices=8)
    by_kind = collective_totals(_HAND_HLO)["by_kind"]
    cs.set_collective_bytes(by_kind)
    rep = cs.analyze(trace, windows=windows, peak_ici_gbps=300.0)
    an = rep["anatomy"]
    tile = an["compute_s"] + an["exposed_collective_s"] + an["other_s"]
    assert abs(tile - an["wall_s"]) <= 0.01 * an["wall_s"], \
        f"anatomy does not tile the wall: {tile} vs {an['wall_s']}"
    assert abs(an["wall_s"] - truth["wall_s"]) < 1e-9
    assert abs(an["exposed_collective_s"] - truth["exposed_s"]) < 1e-9, \
        f"exposed {an['exposed_collective_s']} != truth {truth['exposed_s']}"
    assert abs(an["exposed_comm_frac"] - 0.2) < 1e-9

    # (2) ledger bytes == collective_totals, EXACTLY, for every kind
    led = rep["ledger"]["by_kind"]
    for kind, row in by_kind.items():
        assert kind in led, f"ledger missing census kind {kind}"
        assert led[kind]["mbytes_per_step"] == row["mbytes"], \
            f"{kind}: ledger {led[kind]['mbytes_per_step']} != " \
            f"census {row['mbytes']}"
        assert led[kind]["count_per_step"] == row["count"]
    # measured kinds carry achieved bandwidth; unmeasured stay null
    assert led["all-reduce"]["busbw_gbps"] is not None
    assert led["collective-permute"]["algbw_gbps"] is None

    # (3) compile freeze + loss parity with the observatory ENABLED, and
    # CPU-capture null degradation without a raise
    tdir = tempfile.mkdtemp(prefix="commscope_smoke_trace_")
    eng_on = build_engine(commscope=True, trace_dir=tdir)
    eng_off = build_engine(commscope=False)
    losses_on = train_steps(eng_on, 5)
    losses_off = train_steps(eng_off, 5)
    assert losses_on == losses_off, \
        f"observatory perturbed training: {losses_on} vs {losses_off}"
    c_on = eng_on._train_step._cache_size()
    c_off = eng_off._train_step._cache_size()
    assert c_on == c_off, \
        f"observatory added programs: {c_on} vs {c_off}"
    obs_rep = eng_on.comm_observatory()
    assert obs_rep["anatomy"]["exposed_comm_frac"] is None or \
        obs_rep["anatomy"]["exposed_comm_frac"] >= 0.0
    import jax
    if jax.devices()[0].platform != "tpu":
        assert obs_rep["anatomy"]["exposed_comm_frac"] is None, \
            "CPU capture must degrade anatomy to nulls"
    # static bytes still flowed into the ledger rows (sharded program)
    eng_on.close()
    eng_off.close()

    # (4) doctor [comm] gate: burning straggler trips, clean passes
    with tempfile.TemporaryDirectory() as td:
        prom = os.path.join(td, "m.prom")
        with open(prom, "w", encoding="utf-8") as f:
            f.write("dstpu_comm_exposed_frac 0.3\n"
                    "dstpu_train_straggler_active 1\n"
                    "dstpu_train_straggler_device 5\n"
                    "dstpu_train_straggler_skew_s 0.2\n")
        assert doctor.main(["--dir", td]) == 1, \
            "doctor must gate on a burning straggler gauge"
        with open(prom, "w", encoding="utf-8") as f:
            f.write("dstpu_comm_exposed_frac 0.3\n"
                    "dstpu_train_straggler_active 0\n")
        assert doctor.main(["--dir", td]) == 0, \
            "doctor must pass with the straggler gauge clear"

    # (5) straggler detector: right device flagged, uniform slowdown not
    det = StragglerDetector(k=4.0, confirm=3, clear=3, min_skew_s=1e-3)
    edges = []
    for step in range(8):
        stamps = {i: float(step) + (0.4 if i == 5 and step >= 2 else 0.0)
                  for i in range(8)}
        edges += det.observe(step, stamps)
    assert [e[:2] for e in edges if e[0] == "open"] == [("open", 5)], edges
    det2 = StragglerDetector(k=4.0, confirm=2)
    for step in range(8):
        base = float(step) * (4.0 if step > 3 else 1.0)
        assert det2.observe(step, {i: base for i in range(8)}) == []

    print(json.dumps({
        "smoke": True,
        "anatomy_tiles_within": abs(tile - an["wall_s"]) / an["wall_s"],
        "exposed_comm_frac": an["exposed_comm_frac"],
        "overlap_frac": an["overlap_frac"],
        "ledger_kinds": sorted(led),
        "compiled_programs_on": c_on,
        "compiled_programs_off": c_off,
        "straggler_flagged_device": 5,
        "verdict": "smoke-pass",
    }))


# ------------------------------------------------------------------- full
def main():
    import time

    import jax

    import bench_common as bc

    platform = bc.require_tpu("commscope")[0].platform
    tdir = tempfile.mkdtemp(prefix="commscope_bench_trace_")
    t0 = time.time()
    eng = build_engine(commscope=True, trace_dir=tdir)
    train_steps(eng, 6)
    rep = eng.comm_observatory(n_steps=3)
    eng.close()
    an = rep["anatomy"]
    led = rep["ledger"]
    rows = {k: {"mbytes_per_step": v["mbytes_per_step"],
                "busbw_gbps": v["busbw_gbps"],
                "algbw_gbps": v["algbw_gbps"],
                "roofline_ratio": v["roofline_ratio"],
                "exposed_s_per_step": v["exposed_s_per_step"]}
            for k, v in led["by_kind"].items()}
    out = {
        "metric": "commscope_step_anatomy",
        "value": an["exposed_comm_frac"],
        "unit": "exposed-collective fraction of step wall "
                f"(platform={platform})",
        "platform": platform,
        "n_devices": len(jax.devices()),
        "exposed_comm_frac": an["exposed_comm_frac"],
        "overlap_frac": an["overlap_frac"],
        "compute_s": an["compute_s"],
        "collective_s": an["collective_s"],
        "exposed_collective_s": an["exposed_collective_s"],
        "by_kind": rows,
        "straggler_episodes": rep["straggler"]["episodes"],
        "seconds": round(time.time() - t0, 1),
        "iso": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    with open(_OUT, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        smoke()
    else:
        main()
