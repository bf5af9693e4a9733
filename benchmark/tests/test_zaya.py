"""What PR 44 added to the yardstick, on hand cases: the ZAYA1 configuration
against its catalog row and its two copies of the source's keys, the family's
counts and refusals, where the cell is listed, the new reducer and the new
kernel's count (and the shared expert kernels' count through the alias key),
the kind's comparisons with their controls (each of which has to fail) at the
rehearsal's size, and a CPU rehearsal of the cell."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.kernels import cca_decode_attention, moe_experts
from benchmark.models import zaya as fam
from benchmark.reducers import cca_step_hbm_share, program_span
from deepspeed_tpu.observability.spans import SpanEvent

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME = "zaya1-8b-l20"
CELL = NAME + ".serve-backlog-longthink"
REDUCED = ["num_hidden_layers", "layer_types"]
EXTRA = {"n_head": "num_attention_heads",
         "layer_norm_epsilon": "rms_norm_eps",
         "n_routed_experts": "num_experts"}
NEW = ["cca_decode_attention_roofline", "cca.decode_step_hbm_share",
       "cca.state_bytes_per_slot", "moe.router_top_p"]


@pytest.fixture(scope="module")
def zy_conf():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def zy_spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_zaya_s_two_copies_of_the_source_s_keys_agree(zy_conf, zy_spec):
    for key, value in zy_conf["config"].items():
        if key in EXTRA:
            assert key in zy_conf["assumed"], key
            assert value == zy_conf["config"][EXTRA[key]]
        else:
            assert zy_conf[key] == value, key
    assert zy_conf["reduced"] == REDUCED
    assert zy_conf["family"] == "zaya"
    pub, c = zy_conf["published"], zy_conf["config"]
    assert pub["num_hidden_layers"] == 40 and c["num_hidden_layers"] == 20
    assert c["layer_types"] == pub["layer_types"][:20] == ["hybrid"] * 20
    # every line of the equations that config.json does not carry
    for line in ("cca", "conv_bias", "rope", "temperature", "router",
                 "residual_scaling", "mod", "weights"):
        assert line in zy_conf["assumed"], line
    assert "PER-CHANNEL FORM IS ASSUMED" in zy_conf["assumed"][
        "residual_scaling"]
    for key in ("deployment", "bytes"):
        assert zy_conf[key], key
    assert "two one-chip pipeline stages" in zy_conf["deployment"]
    assert "(x, s)" in zy_conf["deployment"]
    entry = next(e for e in zy_spec["configs"] if e["name"] == NAME)
    assert entry["source"] == zy_conf["source"]
    assert entry["reduced"] == REDUCED
    assert entry["file"] == f"benchmark/configs/{NAME}.json"


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_zaya_has_every_key_of_its_catalog_row(zy_conf):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "ZAYA1-8B")
    assert zy_conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert zy_conf["published"][key] == value, key
        else:
            assert zy_conf[key] == value \
                and zy_conf["config"][key] == value, key
    # depth alone is cut: no width, head count, kernel size, expert count,
    # top-k, router width or vocabulary row
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size"))]


def test_zaya_s_cut_keeps_the_guide_s_floors(zy_conf):
    c = zy_conf["config"]
    # every layer is of the one kind: any depth is whole periods; 20 >= 4
    assert c["num_hidden_layers"] == 20 == len(c["layer_types"])
    assert (c["num_experts"], c["num_experts_per_tok"], c["vocab_size"],
            c["router_hidden_size"], c["hidden_size"], c["head_dim"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["cca_time0"], c["cca_time1"], c["moe_intermediate_size"]) \
        == (16, 1, 262272, 256, 2048, 128, 8, 2, 2, 2, 2048)


def test_zaya_s_cell_is_listed_where_its_readers_find_something(zy_spec):
    cell = next(w for w in zy_spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "longthink-backlog", 1)
    listed = {m["name"] for m in zy_spec["per_layer"] + zy_spec["end_to_end"]
              if CELL in m.get("workloads", [CELL])}
    assert listed == {
        "serve_tokens_per_s", "setup_s", "sched.decode_gap_ms",
        "prog.decode_step_ms", "prog.prefill_chunk_ms",
        "device.idle_share.serve", "sched.host_self_ms", "prog.retraces",
        "prog.decode_fallback_builds", "serve.itl_p95_ms.backlog",
        "moe.load_max_over_mean", "moe.rows_over_routed",
        "moe_experts_roofline", "cache.bytes_per_token",
        "attn.fetched_over_live", "sched.prefill_ahead_share",
        "setup.import_s", "setup.engine_init_s", "setup.trace_lower_s",
        "setup.backend_s", "setup.programs", "setup.cache_misses", *NEW}
    # NOT decode_attention_roofline (it counts K/V by n_head, and no
    # pallas_call of that name runs here), nor the two that call an
    # iteration with a chunk before its step a stall, nor the share of a
    # held part (every expert is held; test_nemotron_h.py pins that metric)
    assert not listed & {"decode_attention_roofline", "host.stall_ms",
                         "serve.tokens_per_s_less_stalls",
                         "moe.held_rows_share", "ssm.state_bytes_per_slot",
                         "cache.append_moved_over_new"}
    new = [m for m in zy_spec["per_layer"] if m.get("workloads") == [CELL]]
    # (no pin that they are the LAST of per_layer: the next PR appends)
    assert [m["name"] for m in new] == NEW
    assert all(m["moves"] == "serve_tokens_per_s" for m in new)
    for m in new:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               m["name"] + ".json")) as f:
            reader = json.load(f)
        assert (reader["layer"], reader["unit"], reader["moves"]) \
            == (m["layer"], m["unit"], m["moves"])
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "longthink-backlog.json")) as f:
        mix = json.load(f)
    assert mix["kind"] == "backlog_cca"
    assert mix["engine"] == {"slots": 48, "max_len": 4096,
                             "prefill_chunk": 512}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 256,
                                    "sigma": 0.9, "min": 32, "max": 1024}
    assert mix["answer_tokens"] == {"dist": "lognormal", "median": 1024,
                                    "sigma": 0.7, "min": 128, "max": 3072}
    assert (mix["requests"], mix["ramp_max_iterations"]) == (512, 1500)
    # the convs' left edge; short; 1 and 2 behind a chunk boundary in a
    # padded bucket; a last chunk ending on a bucket of 128; two chunks
    assert mix["check_prompt_tokens"] == [1, 24, 513, 514, 640, 1000]
    assert mix["check_decode_steps"] == 8
    # the longest prompt with the longest answer fills a slot exactly
    assert mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"] \
        == mix["engine"]["max_len"]


def test_zaya_s_family_counts_the_published_sizes(zy_conf):
    n = fam.layer_params(zy_conf["config"])
    assert [round(n[k] / 1e6, 2) for k in
            ("attention", "router", "expert", "head")] \
        == [5.57, 0.66, 12.58, 537.13]
    cfg = fam.model_config(zy_conf["config"], "bfloat16")
    held = 20 * (n["attention"] + n["router"] + 16 * n["expert"]) + n["head"]
    assert cfg.param_count() == held and round(held * 2 / 1e9, 2) == 9.38
    assert (cfg.attention, cfg.moe_router, cfg.n_head, cfg.kv_heads,
            cfg.head_dim, cfg.v_dim, cfg.rotary_dim, cfg.rope_halves,
            cfg.cca_conv, cfg.num_experts, cfg.moe_top_k, cfg.router_hidden,
            cfg.residual_scale, cfg.tie_embeddings, cfg.segments) \
        == ("cca", "zaya", 8, 2, 128, 128, 64, True, (2, 2), 16, 1, 256,
            True, True, (("moe", 20),))
    whole = fam.model_config(dict(
        zy_conf["config"], **{k: zy_conf["published"][k] for k in REDUCED}),
        "bfloat16")
    assert round(whole.param_count(non_embedding=True) / 1e9, 2) == 8.30
    assert round(whole.param_count(non_embedding=True, active_only=True)
                 / 1e9, 3) == 0.753
    at = fam.flops_per_token(zy_conf["config"], 1500)
    assert at["attention"] == 20 * (2 * n["attention"] + 2 * 8 * 256 * 1500)
    assert at["experts"] == 2 * 20 * (n["router"] + n["expert"])
    assert at["head"] == 2 * n["head"]


@pytest.mark.parametrize("key, other", [
    ("hidden_act", "gelu"), ("attention_bias", True),
    ("tie_word_embeddings", False), ("num_experts_per_tok", 2),
    ("sliding_window", 4096), ("layer_types", ["hybrid"] * 3),
    ("n_head", 16), ("n_routed_experts", 8)])
def test_zaya_s_family_refuses_what_it_runs_one_value_of(zy_conf, key, other):
    with pytest.raises(ValueError, match=key.split("_")[0]):
        fam.model_config(dict(zy_conf["config"], **{key: other}), "bfloat16")


# ------------------------------------------------ reducers and kernel counts
def zy_step_span(step, running, live, touched=15.0, top_p=0.4):
    return SpanEvent("decode_step", step, step + 0.02, step=step, meta={
        "slots": running, "cache_bytes_per_token": 20480,
        "state_bytes_per_slot": 107520, "live_positions": live,
        "experts_touched": touched, "router_top_p": top_p,
        "moe_rows_over_routed": 5.0, "moe_rows_routed": 48})


def test_cca_step_hbm_share_on_a_hand_case(zy_conf, monkeypatch):
    evs = [zy_step_span(0, 48, 70000), zy_step_span(1, 46, 74000,
                                                    touched=16.0, top_p=0.5)]
    monkeypatch.setattr(cca_step_hbm_share, "_captured", lambda: evs)
    monkeypatch.setattr(cca_step_hbm_share, "program_time",
                        lambda facts, **kw: 16.0)           # ms
    facts = {"family": "zaya", "model": zy_conf["config"], "slots": 48,
             "peaks": {"hbm_bytes_per_s": 819e9}}
    n = fam.layer_params(zy_conf["config"])
    moved = 20 * (n["attention"] + n["router"]) * 2 + n["head"] * 2 \
        + 20 * 15.5 * n["expert"] * 2 + 72000 * 20480 \
        + 2 * 48 * 107520 + 47 * 128 * 20480
    got = cca_step_hbm_share.reduce(facts, program="^jit__step_impl\\(")
    assert got == pytest.approx(100 * 1e3 * moved / 819e9 / 16.0)
    assert 50 < got < 100
    assert any("experts 0.249 GB" in note and "head 1.074 GB" in note
               and "touched 7.801 GB" in note and "K/V 1.475 GB" in note
               and "in and out 0.0103 GB" in note and "back 0.123 GB" in note
               and "top p 0.450" in note for note in facts["notes"]), \
        facts["notes"]
    # a program that records no such span (the parent), another family
    monkeypatch.setattr(cca_step_hbm_share, "_captured", lambda: [
        SpanEvent("decode_step", 0, 1, step=0, meta={"slots": 12})])
    assert cca_step_hbm_share.reduce(facts, program="x") is None
    monkeypatch.setattr(cca_step_hbm_share, "_captured", lambda: evs)
    assert cca_step_hbm_share.reduce(dict(facts, family="gpt2"),
                                     program="x") is None
    monkeypatch.setattr(program_span, "_captured", lambda: evs)
    assert program_span.reduce({}, parent="decode_step", statistic="mean",
                               meta="router_top_p") == pytest.approx(0.45)
    assert program_span.reduce({}, parent="decode_step", statistic="mean",
                               meta="state_bytes_per_slot") == 107520


def test_the_attention_and_expert_kernels_counts_on_hand_cases(zy_conf,
                                                               monkeypatch):
    evs = [zy_step_span(0, 48, 70000), zy_step_span(1, 46, 74000,
                                                    touched=16.0)]
    monkeypatch.setattr(program_span, "_captured", lambda: evs)
    facts = {"model": zy_conf["config"], "slots": 48}
    flops, nbytes = cca_decode_attention.calls(facts)["cca_decode_attention"]
    assert flops == 2.0 * 72000 * 8 * 256
    # by the 2 KV heads: a quarter of what a count by n_head would say
    assert nbytes == (72000 * 2 * 256 + 47 * 2 * 256 * 128
                      + 47 * 8 * 256) * 2
    assert nbytes / 819e9 > flops / 197e12                  # memory-bound
    assert cca_decode_attention.calls({"model": {"n_embd": 1280}}) == {}
    # the shared expert count, through the alias key: E 16, k 1, one step of
    # 48 rows that touched 15.5 experts of a layer
    up, down = (moe_experts.calls(facts)[k] for k in
                ("moe_experts_up", "moe_experts_down"))
    d = f = 2048
    assert up == (2.0 * 48 * d * f * 2,
                  15.5 * 2 * d * f * 2 + 48 * (d + f) * 2)
    assert down == (2.0 * 48 * f * d, 15.5 * f * d * 2 + 48 * (d + f) * 2)
    monkeypatch.setattr(program_span, "_captured", lambda: [
        SpanEvent("decode_step", 0, 1, step=0, meta={"slots": 12})])
    assert cca_decode_attention.calls(facts) == {}


# ------------------------------------------- the kind's own comparisons
@pytest.fixture(scope="module")
def zy_small(zy_conf):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    # (kept out of the persistent compilation cache: test_mimo_v2_flash.py)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    from benchmark.reference import zaya as ref
    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh

    published = dict(zy_conf["config"], **zy_conf["rehearsal"])
    cfg, model = fam.build(published, "float32", flash_attention=False)
    params = model.init(jax.random.PRNGKey(3))
    mesh = build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    cell = types.SimpleNamespace(
        seed=11, reference=ref, published=published,
        mix={"engine": {"slots": 8, "max_len": 512, "prefill_chunk": 64},
             "check_prompt_tokens": [1, 24, 65, 66, 80, 125],
             "check_decode_steps": 4, "logit_tolerance": 1e-4,
             "route_gap": 1e-6})
    yield cfg, model, params, mesh, cell
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def zy_engine(zy_small):
    import deepspeed_tpu as ds

    _, model, params, mesh, _ = zy_small
    return ds.init_inference(model, params,
                             {"dtype": "float32", "flash_decode": True},
                             mesh=mesh)


def test_zaya_s_two_comparisons_pass_on_the_system(zy_small):
    """The rehearsal's sizes in float32, the decode kernel interpreted: both
    comparisons at 1e-4, the retired slot's planes and tails bit-equal."""
    from benchmark.kinds import backlog_cca as kind

    cfg, _, params, _, cell = zy_small
    notes: list = []
    assert kind.check_logits(cell, cfg, params, zy_engine(zy_small), notes)
    assert sum("through the cache, prompt" in n for n in notes) == 6
    assert sum("last-position logits" in n for n in notes) == 6
    # 8 slots: one retired with a predecessor's tails in it, 6 prompts in 7
    assert sum("seated in 2 slots" in n for n in notes) == 1
    assert all("retired slots bit-equal: True" in n for n in notes
               if "through the cache" in n)
    assert not any("OUTSIDE" in n or "NOT" in n for n in notes), notes


@pytest.fixture(scope="module")
def zy_rows(zy_small):
    from benchmark.kinds import backlog_cca as kind

    cfg, _, _, _, cell = zy_small
    few = types.SimpleNamespace(**{**vars(cell), "mix": {
        **cell.mix, "check_prompt_tokens": [66, 125]}})
    return kind.cache_rows(few, cfg, zy_engine(zy_small))


def test_zaya_s_controls_are_the_ones_the_chip_run_takes():
    from benchmark.kinds import backlog_cca as kind

    assert set(kind.CONTROLS) == {
        "tail-zeroed-at-chunk-boundary", "value-shift-dropped",
        "qk-mean-dropped", "l2-norm-dropped", "weight-one",
        "temperature-dropped", "gamma-zero", "bias-dropped",
        "residual-scales-dropped", "rope-on-all-dims", "weights-8bit"}


@pytest.mark.parametrize("control", [
    "tail-zeroed-at-chunk-boundary", "value-shift-dropped", "qk-mean-dropped",
    "l2-norm-dropped", "weight-one", "temperature-dropped", "gamma-zero",
    "bias-dropped", "residual-scales-dropped", "rope-on-all-dims",
    "weights-8bit"])
def test_zaya_s_controls_fail_the_cache_comparison(zy_small, zy_rows,
                                                   control):
    """What ``python3 -m benchmark.kinds.backlog_cca`` runs on the chip at
    the timed sizes, here at the rehearsal's: the system's rows once, the
    kind's own comparison under each control of the reference, which is the
    reference again when the control ends."""
    from benchmark.kinds import backlog_cca as kind

    _, _, params, _, cell = zy_small
    ref, notes = cell.reference, []
    with kind.control(control, ref, params, chunk=64) as theirs:
        assert not kind.compare_rows(cell, theirs, zy_rows, notes)
    assert all("OUTSIDE" in n for n in notes), notes
    assert kind.compare_rows(cell, params, zy_rows, [])


def test_zaya_s_retired_slot_stepped_like_a_running_one_fails(zy_small,
                                                              monkeypatch):
    """A row at length 0 that appends all the same: the retired slot's
    buffers change."""
    import jax.numpy as jnp

    from benchmark.kinds import backlog_cca as kind
    from deepspeed_tpu.ops import decode_attention as da

    real = da.decode_attention

    def step(q, ck, cv, length, **kw):
        return real(q, ck, cv, jnp.maximum(length, 1), **kw)

    cfg, _, params, _, cell = zy_small
    monkeypatch.setattr(da, "decode_attention", step)
    few = types.SimpleNamespace(**{**vars(cell), "mix": {
        **cell.mix, "check_prompt_tokens": [24]}})
    notes: list = []
    assert not kind.compare_rows(few, params, kind.cache_rows(
        few, cfg, zy_engine(zy_small)), notes)
    assert any("did NOT come out of the steps bit-equal" in n for n in notes)


def test_zaya_s_cell_rehearses_on_the_cpu():
    """The command itself at the rehearsal's sizes: it runs to its last
    line, which is a rehearsal's and never ``correct``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "3000000001", "--seconds", "3", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["metrics"] == {}
    assert line["rehearsal"]["passed"] is True, line["notes"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert any("the ramp went on" in n for n in line["notes"])
