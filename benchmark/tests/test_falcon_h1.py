"""What PR 48 added to the yardstick, on hand cases: the Falcon-H1
configuration against its catalog row and its two copies of the source's
keys, the family's counts and refusals, where the cell is listed and what its
mix says, the new reducer and the new kernel's count (and the shared state
step's count through the alias keys), the kind's comparison with its controls
(each of which has to fail) at the rehearsal's size, and a CPU rehearsal of
the cell."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.kernels import gqa_decode_attention, ssm_state_step
from benchmark.models import falcon_h1 as fam
from benchmark.reducers import parallel_step_hbm_share, program_span
from deepspeed_tpu.observability.spans import SpanEvent

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME = "falcon-h1-34b-l6"
CELL = NAME + ".serve-backlog-shortchat"
REDUCED = ["num_hidden_layers"]
EXTRA = dict(fam.ALIASES)
NEW = ["gqa_decode_attention_roofline", "parallel.decode_step_hbm_share",
       "ssm.state_share_of_step_bytes"]


@pytest.fixture(scope="module")
def fh_conf():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def fh_spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_falcon_s_two_copies_of_the_source_s_keys_agree(fh_conf, fh_spec):
    for key, value in fh_conf["config"].items():
        if key in EXTRA:
            assert key in fh_conf["assumed"], key
            assert value == fh_conf["config"][EXTRA[key]]
        else:
            assert fh_conf[key] == value, key
    assert fh_conf["reduced"] == REDUCED and fh_conf["family"] == "falcon_h1"
    assert fh_conf["published"]["num_hidden_layers"] == 72
    assert fh_conf["config"]["num_hidden_layers"] == 6
    # every line of the equations that config.json does not carry, and the
    # init that the multipliers make part of correctness
    for line in ("weights", "mamba", "mamba_init", "attention", "mlp",
                 "state_dtypes", "head"):
        assert line in fh_conf["assumed"], line
    assert "THE INIT IS PART OF CORRECTNESS" in fh_conf["assumed"]["weights"]
    for key in ("deployment", "bytes"):
        assert fh_conf[key], key
    assert "twelve one-chip pipeline stages" in fh_conf["deployment"]
    assert "embedding and the untied head" in fh_conf["deployment"]
    entry = next(e for e in fh_spec["configs"] if e["name"] == NAME)
    assert entry["source"] == fh_conf["source"]
    assert entry["reduced"] == REDUCED
    assert entry["file"] == f"benchmark/configs/{NAME}.json"


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_falcon_has_every_key_of_its_catalog_row(fh_conf):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    assert fh_conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert fh_conf["published"][key] == value, key
        else:
            assert fh_conf[key] == value \
                and fh_conf["config"][key] == value, key
    # depth alone is cut: no width, head count, state size or vocabulary row
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size"))]


def test_falcon_s_cut_keeps_the_guide_s_floors(fh_conf):
    c = fh_conf["config"]
    # every layer is of the one kind: a period is one layer; 6 >= 4
    assert c["num_hidden_layers"] == 6 and 72 % 6 == 0
    assert (c["hidden_size"], c["intermediate_size"], c["vocab_size"],
            c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
            c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
            c["mamba_n_groups"], c["mamba_d_ssm"], c["mamba_d_conv"]) \
        == (5120, 21504, 261120, 20, 4, 128, 32, 128, 256, 2, 4096, 4)


def test_falcon_s_cell_is_listed_where_its_readers_find_something(fh_spec):
    cell = next(w for w in fh_spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "shortchat-backlog", 1)
    listed = {m["name"] for m in fh_spec["per_layer"] + fh_spec["end_to_end"]
              if CELL in m.get("workloads", [CELL])}
    assert listed == {
        "serve_tokens_per_s", "setup_s", "sched.decode_gap_ms",
        "prog.decode_step_ms", "device.idle_share.serve",
        "sched.host_self_ms", "prog.retraces", "prog.decode_fallback_builds",
        "serve.itl_p95_ms.backlog", "prog.prefill_chunk_ms",
        "cache.bytes_per_token", "attn.fetched_over_live",
        "cache.append_moved_over_new", "ssm.state_bytes_per_slot",
        "ssm_state_step_roofline", *NEW}
    # NOT decode_attention_roofline (it counts K/V by n_head, five times this
    # model's 4 KV heads, and no pallas_call of that name runs here), nor the
    # reducer of a trunk of one mixer a layer, nor any expert layer's metric
    assert not listed & {"decode_attention_roofline",
                         "hybrid.decode_step_hbm_share",
                         "moe.load_max_over_mean", "host.stall_ms"}
    new = [m for m in fh_spec["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == NEW
    assert all(m["moves"] == "serve_tokens_per_s" for m in new)
    for m in new:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               m["name"] + ".json")) as f:
            reader = json.load(f)
        assert (reader["layer"], reader["unit"], reader["moves"]) \
            == (m["layer"], m["unit"], m["moves"])


def test_falcon_s_mix_says_what_the_issue_gives():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "shortchat-backlog.json")) as f:
        mix = json.load(f)
    assert mix["kind"] == "backlog_parallel"
    assert mix["engine"] == {"slots": 96, "max_len": 1536,
                             "prefill_chunk": 256}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 256,
                                    "sigma": 0.9, "min": 32, "max": 1024}
    assert mix["answer_tokens"] == {"dist": "lognormal", "median": 192,
                                    "sigma": 0.7, "min": 16, "max": 512}
    assert (mix["requests"], mix["trace_seconds"]) == (4096, 4)
    assert isinstance(mix["shape_seed"], int)
    # one shorter than a chunk; one ending on a bucket of 128; four whole
    # chunks; 3 and 2 tokens behind a chunk boundary in a padded bucket
    assert mix["check_prompt_tokens"] == [24, 640, 1024, 515, 770]
    chunk = mix["engine"]["prefill_chunk"]
    assert [n % chunk for n in mix["check_prompt_tokens"]] == [24, 128, 0, 3,
                                                               2]
    assert mix["check_decode_steps"] == 8
    # the longest prompt with the longest answer fills a slot exactly
    assert mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"] \
        == mix["engine"]["max_len"]
    assert 0 < mix["logit_tolerance"] < 0.1
    assert len(mix["logit_tolerance_why"]) > 200


def test_falcon_s_family_counts_the_published_sizes(fh_conf):
    n = fam.layer_params(fh_conf["config"])
    assert [round(n[k] / 1e6, 2) for k in ("attention", "ssm", "mlp", "head")] \
        == [31.46, 68.32, 330.3, 1336.93]
    cfg = fam.model_config(fh_conf["config"], "bfloat16")
    held = 6 * (n["attention"] + n["ssm"] + n["mlp"]) + 2 * n["head"]
    assert cfg.param_count() == held and round(held * 2 / 1e9, 2) == 10.51
    assert (cfg.block_pattern, cfg.n_head, cfg.kv_heads, cfg.head_dim,
            cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state,
            cfg.rope_halves, cfg.rope_theta, cfg.tie_embeddings,
            cfg.segments) == ("PPPPPP", 20, 4, 128, 32, 128, 2, 256, True,
                              1e11, False, (("P", 6),))
    whole = fam.model_config(dict(fh_conf["config"], num_hidden_layers=72),
                             "bfloat16")
    assert round(whole.param_count() / 1e9, 2) == 33.64
    at = fam.flops_per_token(fh_conf["config"], 400)
    assert at["attention"] == 6 * (2 * n["attention"] + 2 * 20 * 256 * 400)
    assert at["ssm"] == 6 * (2 * n["ssm"] + 5 * 32 * 128 * 256)
    assert at["mlp"] == 2 * 6 * n["mlp"] and at["head"] == 2 * n["head"]


@pytest.mark.parametrize("key, other", [
    ("hidden_act", "gelu"), ("attention_bias", True),
    ("tie_word_embeddings", True), ("mamba_norm_before_gate", True),
    ("mamba_conv_bias", False), ("rope_scaling", {"type": "yarn"}),
    ("n_head", 16), ("mamba_num_heads", 64), ("ssm_state_size", 128)])
def test_falcon_s_family_refuses_what_it_runs_one_value_of(fh_conf, key,
                                                           other):
    with pytest.raises(ValueError, match=key.split("_")[0]):
        fam.model_config(dict(fh_conf["config"], **{key: other}), "bfloat16")


# ------------------------------------------------ reducers and kernel counts
STATE = 25350144            # a slot: 6 x (4 MiB + 30 KiB)


def fh_step_span(step, running, live):
    moved = {"state_bytes_step": 2 * running * STATE,
             "kv_bytes_step": live * 12288,
             "weight_bytes_step": 5161000000, "head_bytes_step": 2673868800}
    return SpanEvent("decode_step", step, step + 0.02, step=step, meta={
        "slots": running, "cache_bytes_per_token": 12288,
        "state_bytes_per_slot": STATE, "live_positions": live, **moved,
        "state_share_of_step_bytes":
            moved["state_bytes_step"] / sum(moved.values())})


def test_parallel_step_hbm_share_on_a_hand_case(fh_conf, monkeypatch):
    evs = [fh_step_span(0, 96, 40000), fh_step_span(1, 92, 44000)]
    monkeypatch.setattr(parallel_step_hbm_share, "_captured", lambda: evs)
    monkeypatch.setattr(parallel_step_hbm_share, "program_time",
                        lambda facts, **kw: 25.0)           # ms
    facts = {"family": "falcon_h1", "model": fh_conf["config"], "slots": 96,
             "decode_live_tokens": [40000, 42000, 44000],
             "peaks": {"hbm_bytes_per_s": 819e9}}
    n = fam.layer_params(fh_conf["config"])
    moved = 6 * (n["attention"] + n["ssm"] + n["mlp"]) * 2 + n["head"] * 2 \
        + 2 * 94 * STATE + 42000 * 12288
    got = parallel_step_hbm_share.reduce(facts, program="^jit__step_impl\\(")
    assert got == pytest.approx(100 * 1e3 * moved / 819e9 / 25.0)
    assert 50 < got < 100
    assert any("mixers' weights 1.197 GB" in note and "MLPs' 3.964 GB" in note
               and "head 2.674 GB" in note and "in and out 4.766 GB" in note
               and "K/V 0.516 GB" in note for note in facts["notes"]), \
        facts["notes"]
    # a program that records no such span (the parent), another family
    monkeypatch.setattr(parallel_step_hbm_share, "_captured", lambda: [
        SpanEvent("decode_step", 0, 1, step=0, meta={
            "slots": 12, "state_bytes_per_slot": STATE})])
    assert parallel_step_hbm_share.reduce(facts, program="x") is None
    monkeypatch.setattr(parallel_step_hbm_share, "_captured", lambda: evs)
    assert parallel_step_hbm_share.reduce(dict(facts, family="gpt2"),
                                          program="x") is None
    monkeypatch.setattr(program_span, "_captured", lambda: evs)
    share = program_span.reduce({}, parent="decode_step", statistic="mean",
                                meta="state_share_of_step_bytes")
    assert 0.35 < share < 0.4
    assert program_span.reduce({}, parent="decode_step", statistic="mean",
                               meta="state_bytes_per_slot") == STATE


def test_the_attention_and_state_kernels_counts_on_hand_cases(fh_conf,
                                                              monkeypatch):
    evs = [fh_step_span(0, 96, 40000), fh_step_span(1, 92, 44000)]
    monkeypatch.setattr(program_span, "_captured", lambda: evs)
    facts = {"model": fh_conf["config"], "slots": 96}
    flops, nbytes = gqa_decode_attention.calls(facts)["gqa_decode_attention"]
    assert flops == 2.0 * 42000 * 20 * 256
    # by the 4 KV heads: a fifth of what a count by n_head would say
    assert nbytes == (42000 * 4 * 256 + 94 * 4 * 256 * 128
                      + 94 * 20 * 256) * 2
    assert nbytes / 819e9 > flops / 197e12                  # memory-bound
    # another family's model, a program without the spans' count
    assert gqa_decode_attention.calls({"model": {"n_embd": 1280}}) == {}
    # the shared state step's count, through the alias keys: 32 heads of
    # 128 x 256 float32 in and out a running slot, two groups' side operands
    flops, nbytes = ssm_state_step.calls(facts)["ssm_state_step"]
    state = 32 * 128 * 256 * 4
    assert nbytes == 94 * (2 * state + 2 * (2 * 128 * 128 + 2 * 256) * 4)
    assert flops == 5.0 * 94 * 32 * 128 * 256
    monkeypatch.setattr(program_span, "_captured", lambda: [
        SpanEvent("decode_step", 0, 1, step=0, meta={"slots": 12})])
    assert gqa_decode_attention.calls(facts) == {}


# ------------------------------------------- the kind's own comparisons
@pytest.fixture(scope="module")
def fh_small(fh_conf):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    # (kept out of the persistent compilation cache: test_mimo_v2_flash.py)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    from benchmark.reference import falcon_h1 as ref
    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh

    published = dict(fh_conf["config"], **fh_conf["rehearsal"])
    cfg, model = fam.build(published, "float32", flash_attention=False)
    params = model.init(jax.random.PRNGKey(3))
    mesh = build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    cell = types.SimpleNamespace(
        seed=11, reference=ref, published=published,
        mix={"engine": {"slots": 16, "max_len": 128, "prefill_chunk": 16},
             "check_prompt_tokens": [9, 32, 35, 50],
             "check_decode_steps": 4, "logit_tolerance": 1e-4})
    yield cfg, model, params, mesh, cell
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def fh_engine(fh_small):
    import deepspeed_tpu as ds

    _, model, params, mesh, _ = fh_small
    return ds.init_inference(model, params,
                             {"dtype": "float32", "flash_decode": True},
                             mesh=mesh)


def test_falcon_s_two_comparisons_pass_on_the_system(fh_small):
    """The rehearsal's sizes in float32, the decode kernels interpreted: both
    comparisons at 1e-4, the retired slot's state, window and planes
    bit-equal."""
    from benchmark.kinds import backlog_parallel as kind

    cfg, _, params, _, cell = fh_small
    notes: list = []
    assert kind.check_logits(cell, cfg, params, fh_engine(fh_small), notes)
    assert sum("through the cache, prompt" in n for n in notes) == 4
    assert sum("last-position logits" in n for n in notes) == 4
    # 16 slots: one retired with a predecessor's state in it, 4 prompts in 15
    assert sum("seated in 4 slots" in n for n in notes) == 3
    assert all("retired slots bit-equal: True" in n for n in notes
               if "through the cache" in n)
    assert not any("OUTSIDE" in n or "NOT" in n for n in notes), notes


@pytest.fixture(scope="module")
def fh_rows(fh_small):
    from benchmark.kinds import backlog_parallel as kind

    cfg, _, _, _, cell = fh_small
    few = types.SimpleNamespace(**{**vars(cell), "mix": {
        **cell.mix, "check_prompt_tokens": [35, 50]}})
    return few, kind.cache_rows(few, cfg, fh_engine(fh_small))


def test_falcon_s_controls_are_the_ones_the_chip_run_takes():
    from benchmark.kinds import backlog_parallel as kind

    assert set(kind.CONTROLS) == {
        "ssm-branch-dropped", "attention-branch-dropped",
        "key-multiplier-left-out", "ssm-multipliers-left-out",
        "window-zeroed-at-chunk-boundary", "products-8bit",
        "padding-advances-the-state"}


@pytest.mark.parametrize("control", [
    "ssm-branch-dropped", "attention-branch-dropped",
    "key-multiplier-left-out", "ssm-multipliers-left-out",
    "window-zeroed-at-chunk-boundary", "products-8bit"])
def test_falcon_s_controls_fail_the_cache_comparison(fh_small, fh_rows,
                                                     control):
    """What ``python3 -m benchmark.kinds.backlog_parallel`` runs on the chip
    at the timed sizes, here at the rehearsal's: the system's rows once, the
    kind's own comparison under each control of the reference, which is the
    reference again when the control ends."""
    from benchmark.kinds import backlog_parallel as kind

    _, _, params, _, _ = fh_small
    few, rows = fh_rows
    notes: list = []
    with kind.control(control, few.reference, chunk=16):
        assert not kind.compare_rows(few, params, rows, notes)
    assert any("OUTSIDE" in n for n in notes), notes
    assert kind.compare_rows(few, params, rows, [])


def test_falcon_s_padding_that_advances_the_state_fails(fh_small, fh_rows):
    """The system's own fault: the true length not handed on, so the padded
    bucket's tokens advance the state and the window; the prompt's own last
    position is read before them, the steps behind it part."""
    from benchmark.kinds import backlog_parallel as kind

    cfg, _, params, _, _ = fh_small
    few, _ = fh_rows
    notes: list = []
    rows = kind.cache_rows(few, cfg, fh_engine(fh_small),
                           fault="padding-advances-the-state")
    assert not kind.compare_rows(few, params, rows, notes)
    assert all("OUTSIDE" in n for n in notes), notes


def test_falcon_s_retired_slot_stepped_like_a_running_one_fails(fh_small,
                                                                monkeypatch):
    """A row at length 0 whose state is stepped all the same: the retired
    slot's buffers change."""
    import jax.numpy as jnp

    from benchmark.kinds import backlog_parallel as kind
    from deepspeed_tpu.ops import ssm_step

    real = ssm_step.ssm_state_step

    def step(S, layer, x, dt, A, Bv, Cv, length, **kw):
        return real(S, layer, x, dt, A, Bv, Cv, jnp.maximum(length, 1), **kw)

    cfg, _, params, _, cell = fh_small
    monkeypatch.setattr(ssm_step, "ssm_state_step", step)
    few = types.SimpleNamespace(**{**vars(cell), "mix": {
        **cell.mix, "check_prompt_tokens": [9]}})
    notes: list = []
    assert not kind.compare_rows(few, params, kind.cache_rows(
        few, cfg, fh_engine(fh_small)), notes)
    assert any("did NOT come out of the steps bit-equal" in n for n in notes)


def test_falcon_s_cell_rehearses_on_the_cpu():
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
    assert sum("through the cache, prompt" in n for n in line["notes"]) == 5
