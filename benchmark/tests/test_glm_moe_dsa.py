"""What PR 51 added to the yardstick, on hand cases: the GLM-5.2
configuration against its catalog row and its two copies of the source's
keys, the cut against the guide's floors, the family's counts and refusals,
where the cell is listed and what its mix says, the new reducer and the new
kernels' counts, the reference's near-tie rule, the kind's comparison with
its controls (each of which has to fail) at the rehearsal's size, and a CPU
rehearsal of the cell."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark.kernels import dsa_index_score, sparse_mla_decode_attention
from benchmark.models import glm_moe_dsa as fam
from benchmark.reducers import program_span, sparse_step_hbm_share
from deepspeed_tpu.observability.spans import SpanEvent

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME = "glm-5.2-l7-e16"
CELL = NAME + ".serve-backlog-longctx"
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "mlp_layer_types",
           "indexer_types", "n_routed_experts", "vocab_size",
           "num_nextn_predict_layers"]
EXTRA = {"n_head": "num_attention_heads",
         "layer_norm_epsilon": "rms_norm_eps", "router_experts": None,
         "first_expert_held": None}
NEW = ["sparse_mla_decode_attention_roofline", "dsa_index_score_roofline",
       "sparse.decode_step_hbm_share", "dsa.selected_over_live",
       "dsa.fetched_over_selected"]


@pytest.fixture(scope="module")
def glm_conf():
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def glm_spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def glm_mix():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "longctx-backlog.json")) as f:
        return json.load(f)


def test_glm_s_two_copies_of_the_source_s_keys_agree(glm_conf, glm_spec):
    for key, value in glm_conf["config"].items():
        if key in EXTRA:
            assert key in glm_conf["assumed"], key
            if EXTRA[key]:
                assert value == glm_conf["config"][EXTRA[key]]
        else:
            assert glm_conf[key] == value, key
    assert glm_conf["reduced"] == REDUCED
    assert glm_conf["family"] == "glm_moe_dsa" and glm_conf["chips"] == 1
    assert glm_conf["config"]["router_experts"] \
        == glm_conf["published"]["n_routed_experts"] == 256
    for line in ("weights", "rope", "indexer", "indexer_hadamard_fp8",
                 "index_topk_pattern", "e_score_correction_bias",
                 "multi_token_prediction"):
        assert line in glm_conf["assumed"], line
    for key in ("source", "published", "deployment", "bytes", "rehearsal"):
        assert glm_conf[key], key
    assert "each layer shared by 16 chips" in glm_conf["deployment"]
    entry = next(e for e in glm_spec["configs"] if e["name"] == NAME)
    assert entry["source"] == glm_conf["source"]
    assert entry["reduced"] == REDUCED
    assert entry["file"] == f"benchmark/configs/{NAME}.json"


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_glm_has_every_key_of_its_catalog_row(glm_conf):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5.2")
    assert glm_conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert glm_conf["published"][key] == value, key
        else:
            assert glm_conf[key] == value \
                and glm_conf["config"][key] == value, key
    # no width, head count, index_topk, indexer size, top-k or router width
    assert not [k for k in REDUCED if k != "vocab_size" and k.endswith(
        ("_dim", "_rank", "_size", "_heads", "_topk", "_per_tok"))]
    # the importer reads the same row's keys into the same native config
    from deepspeed_tpu.models import config_from_hf, glm_moe_dsa

    assert config_from_hf(row["config"]) == glm_moe_dsa("5.2")


def test_glm_s_cut_keeps_the_guide_s_floors(glm_conf):
    c, p = glm_conf["config"], glm_conf["published"]
    # published layers 2..8: one leading dense layer, six expert layers (>= 4
    # behind the dense one), a whole period F s s s inside
    assert c["mlp_layer_types"] == p["mlp_layer_types"][2:9] \
        == ["dense"] + ["sparse"] * 6
    assert c["indexer_types"] == p["indexer_types"][2:9] == [
        "full", "shared", "shared", "shared", "full", "shared", "shared"]
    assert (c["num_hidden_layers"], c["first_k_dense_replace"]) == (7, 1)
    assert c["n_routed_experts"] == 16 >= 8 and c["router_experts"] == 256
    assert c["vocab_size"] * 8 == p["vocab_size"] == 154880
    assert c["num_nextn_predict_layers"] == 0
    assert (c["hidden_size"], c["intermediate_size"],
            c["moe_intermediate_size"], c["num_attention_heads"],
            c["q_lora_rank"], c["kv_lora_rank"], c["qk_nope_head_dim"],
            c["qk_rope_head_dim"], c["v_head_dim"], c["index_topk"],
            c["index_n_heads"], c["index_head_dim"],
            c["num_experts_per_tok"]) == (
        6144, 12288, 2048, 64, 2048, 512, 192, 64, 256, 2048, 32, 128, 8)


def test_glm_s_family_counts_the_published_sizes(glm_conf):
    n = fam.layer_params(glm_conf["config"])
    assert n == {"attention": 165019648, "indexer": 9371648,
                 "dense": 226492416, "router": 1572864, "shared": 37748736,
                 "expert": 37748736, "head": 118947840}
    # ISSUE 51's reckoning: 5.498 B parameters on this chip
    total = (7 * n["attention"] + 2 * n["indexer"] + n["dense"]
             + 6 * (n["router"] + n["shared"] + 16 * n["expert"])
             + 2 * n["head"])
    assert round(total / 1e9, 3) == 5.498
    assert fam.cache_bytes_per_token(glm_conf["config"]) == {
        "used": 7 * 1152, "stored": 7 * 1536, "indexer_keys": 2 * 256}
    cfg = fam.model_config(glm_conf["config"], "bfloat16")
    assert (cfg.index_pattern, cfg.segments) == (
        "FsssFss", (("dense", 1), ("moe", 6)))
    assert (cfg.num_experts, cfg.held_experts, cfg.vocab_size) \
        == (256, 16, 19360)
    flops = fam.flops_per_token(glm_conf["config"], 18000)
    assert flops["attention"] == 7 * (
        2.0 * n["attention"] + 2.0 * 64 * 2048 * 512)
    assert flops["indexer"] == 2 * (2.0 * n["indexer"]
                                    + 2.0 * 32 * 128 * 18000)


@pytest.mark.parametrize("key,value", [
    ("scoring_func", "softmax"), ("n_group", 8), ("rope_interleave", False),
    ("index_topk_pattern", [1]), ("num_nextn_predict_layers", 1)])
def test_glm_s_family_refuses_what_it_runs_one_value_of(glm_conf, key,
                                                        value):
    with pytest.raises(ValueError, match=key):
        fam.model_config(dict(glm_conf["config"], **{key: value}),
                         "bfloat16")


def test_glm_s_cell_is_listed_where_its_readers_find_something(glm_spec):
    cell = next(w for w in glm_spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "longctx-backlog", 1)
    # (no position is pinned: later PRs append behind these entries)
    listed = {m["name"] for m in glm_spec["per_layer"]
              + glm_spec["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert listed == {
        "serve_tokens_per_s", "setup_s", "sched.decode_gap_ms",
        "prog.decode_step_ms", "prog.prefill_chunk_ms",
        "device.idle_share.serve", "sched.host_self_ms", "prog.retraces",
        "prog.decode_fallback_builds", "serve.itl_p95_ms.backlog",
        "cache.bytes_per_token", "moe.held_rows_share",
        "moe.load_max_over_mean", "sched.prefill_ahead_share", *NEW}
    # NOT the latent kind's kernel (another kernel: it counts every live
    # position) nor the routed rows' roofline (its count knows no held share)
    assert not listed & {"mla_decode_attention_roofline",
                         "moe_experts_roofline", "attn.fetched_over_live"}
    new = [m for m in glm_spec["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new][:len(NEW)] == NEW
    assert all(m["moves"] == "serve_tokens_per_s" for m in new)
    for m in new:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               m["name"] + ".json")) as f:
            reader = json.load(f)
        assert (reader["layer"], reader["unit"], reader["moves"]) \
            == (m["layer"], m["unit"], m["moves"])


def test_glm_s_mix_says_what_the_issue_gives(glm_mix):
    m = glm_mix
    assert m["kind"] == "backlog_sparse"
    # 12 x 32 768 by the issue, 10 with the reason (its own allowance)
    assert m["engine"] == {"slots": 10, "max_len": 32768,
                           "prefill_chunk": 512}
    assert "10 slots x 32 768, not ISSUE 51's 12" in m["why"] \
        and "memory_analysis()" in m["why"]
    assert m["requests"] == 256
    assert m["prompt_tokens"] == {"dist": "lognormal", "median": 16384,
                                  "sigma": 0.5, "min": 4096, "max": 30720}
    assert m["answer_tokens"] == {"dist": "lognormal", "median": 384,
                                  "sigma": 0.6, "min": 64, "max": 1024}
    assert m["check_prompt_tokens"][:3] == [24, 2100, 6200] \
        and m["check_prompt_tokens"][3] in (12300, 8200)
    assert m["check_decode_steps"] == 8 and m["ramp_more_iterations"] == 400
    assert [(r["count"], r["prompt"]) for r in m["check_requests"]] \
        == [(2, 48), (2, 5000)]
    assert m["prompt_tokens"]["min"] > 2048        # the indexer always decides
    for key in ("weights_seed", "shape_seed", "logit_tolerance",
                "logit_tolerance_why", "route_gap", "select_gap",
                "select_gap_why"):
        assert m[key], key


# ------------------------------------------------- the reducer, the kernels
def glm_step_span(step, selected, live, touched=5.0):
    return SpanEvent(
        kind="decode_step", t0=float(step), t1=float(step) + 0.5, meta={
            "step": step, "slots": 10, "dsa_selected": selected,
            "dsa_live": live, "dsa_selected_over_live": selected / live,
            "dsa_fetched_over_selected": 4 / 3, "experts_touched": touched,
            "held_rows_share": 0.0625, "cache_bytes_per_token": 11264})


def test_sparse_step_hbm_share_on_a_hand_case(glm_conf, monkeypatch):
    spans = [glm_step_span(i, 20480, 180000) for i in range(4)]
    monkeypatch.setattr(program_span, "_captured", lambda: spans)
    monkeypatch.setattr(sparse_step_hbm_share, "_captured", lambda: spans)
    monkeypatch.setattr(sparse_step_hbm_share, "program_time",
                        lambda facts, **kw: 20.0)
    facts = {"family": "glm_moe_dsa", "model": glm_conf["config"],
             "peaks": {"hbm_bytes_per_s": 819e9}, "notes": []}
    n = fam.layer_params(glm_conf["config"])
    other = (7 * n["attention"] + 2 * n["indexer"] + n["dense"]
             + 6 * (n["router"] + n["shared"])) * 2
    moved = (other + 2 * n["head"] + 6 * 5.0 * n["expert"] * 2
             + 20480 * 7 * 1152 + 180000 * 2 * 256
             + 10 * (7 * 1152 + 2 * 256))
    got = sparse_step_hbm_share.reduce(facts, program="^jit__step_impl\\(")
    assert got == pytest.approx(100.0 * 1e3 * moved / 819e9 / 20.0)
    assert 0 < got < 100 and "the selected latents 0.165 GB" in facts[
        "notes"][0]
    # a program whose spans carry no count (any parent) reads nothing
    bare = [SpanEvent(kind="decode_step", t0=0.0, t1=1.0,
                      meta={"experts_touched": 5.0, "held_rows_share": 0.06})]
    monkeypatch.setattr(sparse_step_hbm_share, "_captured", lambda: bare)
    assert sparse_step_hbm_share.reduce(facts, program="x") is None


def test_the_new_kernels_counts_on_hand_cases(glm_conf, monkeypatch):
    spans = [glm_step_span(i, 20480, 180000) for i in range(3)]
    monkeypatch.setattr(program_span, "_captured", lambda: spans)
    facts = {"model": glm_conf["config"], "slots": 10, "seq_len": 32768}
    flops, nbytes = sparse_mla_decode_attention.calls(facts)[
        "sparse_mla_decode_attention"]
    assert flops == 2.0 * 20480 * 64 * (576 + 512)
    assert nbytes == (20480 * 576 + 10 * (64 * (576 + 512) + 576)) * 2
    flops, nbytes = dsa_index_score.calls(facts)["dsa_index_score"]
    assert flops == 2.0 * 180000 * 32 * 128
    assert nbytes == 180000 * 256 + 10 * 32768 * 4 + 10 * 32 * (256 + 4)
    monkeypatch.setattr(program_span, "_captured", lambda: [])
    assert sparse_mla_decode_attention.calls(facts) == {}
    assert dsa_index_score.calls(facts) == {}


def test_the_kind_s_spans_say_what_the_readers_read():
    """The kind's own arithmetic (``SparseLatent._dsa``) at the cell's
    shapes: 10 slots at 18 000 positions read 2048 each."""
    import jax.numpy as jnp

    from deepspeed_tpu.inference.kinds import kind_of

    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        cfg = fam.model_config(json.load(f)["config"], "bfloat16")
    kind = kind_of(cfg, 10, jnp.bfloat16)
    meta = kind._dsa(np.full(10, 18000))
    assert meta["dsa_selected"] == 20480 and meta["dsa_live"] == 180000
    assert meta["dsa_selected_over_live"] == pytest.approx(2048 / 18000)
    assert meta["dsa_fetched_over_selected"] == pytest.approx(1536 / 1152)
    assert kind.token_bytes == 11264
    assert kind._dsa(np.asarray([100]))["dsa_selected_over_live"] == 1.0
    chunk = types.SimpleNamespace(start=4096, size=512, final=False,
                                  last_index=511)
    assert kind.chunk_meta(chunk)["dsa_selected_over_live"] \
        == pytest.approx(2048 * 512 / sum(range(4097, 4609)))


# --------------------------------------------------------- the reference
def test_the_near_tie_rule_on_a_hand_case():
    """A query whose system-selected set differs from the reference's own
    only in positions within ``select_gap`` of the reference's K-th score is
    followed; one that differs further away is not, whatever it chose."""
    import jax.numpy as jnp

    from benchmark.reference import glm_moe_dsa as ref

    c = {"index_n_heads": 1, "index_head_dim": 2, "qk_rope_head_dim": 0,
         "index_topk": 2, "rope_parameters": {"rope_theta": 1e4},
         "rms_norm_eps": 1e-5}
    # one head, no rope, no norm: I[t, s] = ReLU(q_t . k_s) with w = 1
    ip = {"wq_b": jnp.eye(2), "wk": jnp.eye(2),
          "k_norm_scale": jnp.ones(2), "k_norm_bias": jnp.zeros(2),
          "weights_proj": jnp.full((2, 1), 0.5)}
    h = jnp.asarray([[1.0, 1.0], [1.0, 1.0], [1.01, 0.99], [1.0, 1.0],
                     [0.0, 2.0]])
    cq = jnp.asarray([[1.0, 0.0]] * 5)
    ref.CONTROL.add("k-norm-dropped")
    try:
        def run(theirs, gap):
            mask, took, far = ref.select(h, cq, ip, c, jnp.asarray(theirs),
                                         gap)
            return np.asarray(mask[4]).tolist(), int(took), float(far)

        none = [[-1, -1]] * 5
        own, took, _ = run(none, 0.1)
        # scores of query 4 over keys 0..4: 1, 1, 1.01, 1, 0 (x w): the top
        # two are key 2 and, of the tied, the lowest position: key 0
        assert own == [True, False, True, False, False] and took == 0
        near = [[0, -1], [0, 1], [0, 2], [0, 2], [2, 3]]   # 3 for 0: a tie
        assert run(near, 1e-3) == ([False, False, True, True, False], 1, 0.0)
        wrong = [[0, -1], [0, 1], [0, 2], [0, 2], [2, 4]]  # 4 scores 0
        mask, took, far = run(wrong, 1e-3)
        assert mask == own and took == 0 and far > 0.5
    finally:
        ref.CONTROL.clear()


# ------------------------------------------- the kind, at the rehearsal's size
@pytest.fixture(scope="module")
def glm_small(glm_conf):
    import time

    from benchmark import harness
    from benchmark.kinds import backlog_sparse

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = harness.load_cell(spec, CELL, 5100000977, 0.0, False, True,
                             time.perf_counter())
    cell.mix["check_prompt_tokens"] = [24, 131]
    cfg, params, eng = backlog_sparse.build(cell)
    rows = backlog_sparse.cache_rows(cell, cfg, eng)
    return cell, params, rows


def test_glm_s_comparison_passes_on_the_system(glm_small):
    from benchmark.kinds import backlog_sparse

    cell, params, rows = glm_small
    notes: list = []
    assert backlog_sparse.compare_rows(cell, params, rows, notes), notes
    assert len(notes) == 2 and "its selection for up to" in notes[1]


def test_a_served_request_that_differs_from_solo_is_held_to_the_reference(
        glm_small):
    """On the chip two runs in four meet a near-tie of the draw between the
    served path and solo ``generate()``. Here solo's answer is falsified: the
    served tokens then go to the reference, following the routing and the
    selection the serving engine logged, and every one is its draw."""
    import time

    import deepspeed_tpu as ds
    from benchmark.kinds import backlog_sparse

    import jax

    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh

    cell, params, _ = glm_small
    cfg, model = cell.family.build(cell.published, "bfloat16", False)
    eng = ds.init_inference(
        model, params, {"dtype": "bfloat16"},
        mesh=build_mesh(MeshSpec(data=1), devices=jax.devices()[:1]))
    backlog_sparse._SOLO[:] = [
        [np.full(n, -1) for _ in range(k)]
        for k, _, n, _, _ in backlog_sparse.check_requests(cell, cfg)]
    srv = ds.ServingEngine(eng, dict(cell.mix["engine"]),
                           clock=time.perf_counter)
    notes: list = []
    assert backlog_sparse.check_served(cell, cfg, eng, srv, notes)
    assert len(backlog_sparse._PENDING) == 4 and srv.routing_log is None
    assert backlog_sparse.held_to_the_reference(cell, notes), notes
    assert sum("served tokens are its draw" in n for n in notes) == 4
    assert not backlog_sparse._PENDING


def test_glm_s_controls_are_the_ones_the_chip_run_takes():
    from benchmark.kinds.backlog_sparse import CONTROLS

    assert CONTROLS == (
        "newest-selected", "shared-takes-first", "shared-selects-itself",
        "relu-dropped", "head-weights-dropped", "k-norm-dropped",
        "k-rope-dropped", "q-norm-dropped", "weights-8bit")


def test_glm_s_controls_fail_the_cache_comparison(glm_small):
    """Four of the nine through the kind's own comparison, as the chip run
    takes them, in ONE test (the fixture is a model and two prompts through
    the cache: not once a worker); all nine fail against the system's
    forward in ``tests/unit/test_sparse_latent.py``."""
    from benchmark.kinds import backlog_sparse

    cell, params, rows = glm_small
    for name in ("newest-selected", "shared-takes-first", "k-rope-dropped",
                 "weights-8bit"):
        notes: list = []
        with backlog_sparse.control(name, cell.reference):
            assert not backlog_sparse.compare_rows(cell, params, rows,
                                                   notes), (name, notes)
        assert any("OUTSIDE" in n for n in notes), name


def test_glm_s_cell_rehearses_on_the_cpu():
    """The command itself at the rehearsal's sizes: it runs to its last
    line, which is a rehearsal's and never ``correct``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "5100000977", "--seconds", "2", "--rehearse", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1500)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["metrics"] == {}
    assert line["rehearsal"]["passed"] is True, line["notes"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert sum("through the cache, prompt" in n for n in line["notes"]) == 4
