"""Tier-1 wiring of ``bench_overlap.py --smoke`` — the quantized +
overlapped collectives gate: measured exposed-fraction drop on the
fake-trace seam, bucketed-fp bitwise parity vs the fused flat spelling,
int8 error-feedback convergence, quantized-TP-decode greedy parity,
zero new steady-state programs with every knob off, and the compiled
int8 wire matching the static plan summary."""

import os
import subprocess
import sys

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def test_overlap_bench_smoke_gate():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench_overlap.py"),
         "--smoke"], capture_output=True, text=True, timeout=420, env=env,
        cwd=_ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "smoke-pass" in out.stdout, out.stdout


def test_ledger_directions_for_overlap_series():
    """An 'overlap' spelled into a step-time series name must not flip
    the direction of good: only overlap FRACTIONS are up-is-good."""
    from deepspeed_tpu.observability.perf_ledger import direction_of

    assert direction_of("grad_overlap.step_time_overlap_int8_s") == "down"
    assert direction_of("grad_overlap.step_time_fused_fp_s") == "down"
    assert direction_of("grad_overlap.wire_ratio_vs_fp32") == "down"
    assert direction_of("train.overlap_int8.wire.wire_mbytes_per_step") \
        == "down"
    assert direction_of("commscope.overlap_frac") == "up"
    assert direction_of("predicted_overlap") == "up"
