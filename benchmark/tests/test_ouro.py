"""What PR 34 added to the yardstick, on hand cases: the Ouro configuration's
two copies of the source's keys, the family's counts and refusals, the plain
reference's rope basis and exit rule, the reducer of a looped step's traffic,
and the kind's cache-path comparison with its controls at a small size."""

import json
import os
import types

import pytest

from benchmark.models import ouro as fam
from benchmark.reducers import looped_step_hbm_share, program_span
from deepspeed_tpu.observability.spans import SpanEvent

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "ouro-2.6b.serve-backlog-reason"
ALIASES = dict(fam.ALIASES)


@pytest.fixture(scope="module")
def ouro_conf():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ouro-2.6b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ouro_spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_ouro_s_two_copies_of_the_source_s_keys_agree(ouro_conf, ouro_spec):
    for key, value in ouro_conf["config"].items():
        if key in ALIASES:
            assert value == ouro_conf["config"][ALIASES[key]]
            assert key in ouro_conf["assumed"]
        else:
            assert ouro_conf[key] == value, key
    assert ouro_conf["reduced"] == [] and ouro_conf["family"] == "ouro"
    assert (ouro_conf["num_hidden_layers"], ouro_conf["total_ut_steps"],
            ouro_conf["early_exit_threshold"]) == (48, 4, 1)
    # every line of the equations that config.json does not carry
    for line in ("sandwich_norms", "closing_norm", "cache_planes",
                 "exit_gate", "exit_rule", "weights"):
        assert line in ouro_conf["assumed"], line
    entry = next(c for c in ouro_spec["configs"] if c["name"] == "ouro-2.6b")
    assert entry["source"] == ouro_conf["source"] and entry["reduced"] == []
    assert entry["file"] == "benchmark/configs/ouro-2.6b.json"


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_ouro_has_every_key_of_its_catalog_row(ouro_conf):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    assert ouro_conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert ouro_conf[key] == value and ouro_conf["config"][key] == value, key


def test_the_cell_is_listed_where_its_readers_find_something(ouro_spec):
    cell = next(w for w in ouro_spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro-2.6b", "reason-backlog", 1)
    listed = {m["name"] for m in ouro_spec["per_layer"] + ouro_spec["end_to_end"]
              if CELL in m.get("workloads", [CELL])}
    assert listed == {
        "serve_tokens_per_s", "setup_s", "prog.decode_step_ms",
        "sched.decode_gap_ms", "sched.host_self_ms",
        "device.idle_share.serve", "prog.retraces",
        "prog.decode_fallback_builds", "serve.tokens_per_s_less_stalls",
        "serve.itl_p95_ms.backlog", "host.stall_ms",
        "decode_attention_roofline", "attn.fetched_over_live",
        "cache.append_moved_over_new", "cache.bytes_per_token",
        "loop.decode_step_hbm_share", "loop.weight_bytes_per_token"}
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "reason-backlog.json")) as f:
        mix = json.load(f)
    assert mix["engine"] == {"slots": 12, "max_len": 384,
                             "prefill_chunk": 128}
    # every prompt is one final bucket: no intermediate chunk in the window,
    # which is why prog.prefill_chunk_ms does not list the cell
    assert mix["prompt_tokens"]["max"] <= mix["engine"]["prefill_chunk"]
    assert mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"] \
        <= mix["engine"]["max_len"]
    assert max(mix["check_prompt_tokens"]) + mix["check_decode_steps"] \
        <= mix["engine"]["max_len"]


def test_ouro_s_family_counts_the_published_sizes(ouro_conf):
    n = fam.layer_params(ouro_conf["config"])
    assert round(n["attention"] / 1e6, 2) == 16.78
    assert round(n["mlp"] / 1e6, 2) == 34.60
    assert round(n["head"] / 1e6, 2) == 100.66
    total = 48 * (n["attention"] + n["mlp"] + 4 * 2048) + 2 * n["head"] \
        + 2048 + 2049
    assert round(total / 1e9, 3) == 2.668
    cfg = fam.model_config(ouro_conf["config"], "bfloat16")
    assert cfg.param_count() == 48 * (n["attention"] + n["mlp"]) \
        + 2 * n["head"]
    assert (cfg.loop_steps, cfg.sandwich_norm, cfg.exit_gate,
            cfg.head_dim, cfg.kv_heads) == (4, True, True, 128, 16)


@pytest.mark.parametrize("key, other", [
    ("rope_scaling", {"type": "linear", "factor": 2.0}),
    ("use_sliding_window", True), ("hidden_act", "gelu"),
    ("tie_word_embeddings", True), ("early_exit_threshold", 0.9),
    ("layer_types", ["sliding_attention"] * 48), ("head_dim", 64),
    ("n_head", 32)])
def test_ouro_s_family_refuses_what_it_runs_one_value_of(ouro_conf, key, other):
    with pytest.raises(ValueError, match=key):
        fam.model_config(dict(ouro_conf["config"], **{key: other}), "bfloat16")


# ---------------------------------------------------------- the reference
def test_the_reference_s_two_rope_bases_are_one_function(ouro_conf):
    """HF's half-rotating basis on HF-ordered q / k columns equals the
    pair-rotating basis (the repo's) on the permuted columns, to rounding."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import numpy as np

    from benchmark.reference import ouro as ref

    published = dict(ouro_conf["config"], **ouro_conf["rehearsal"])
    cfg, model = fam.build(published, "float32", flash_attention=False)
    hf = model.init(jax.random.PRNGKey(1))
    ours = dict(hf, layers=dict(
        hf["layers"], wq=ref.pairs_from_halves(hf["layers"]["wq"], 4),
        wk=ref.pairs_from_halves(hf["layers"]["wk"], 4)))
    assert np.abs(np.asarray(ours["layers"]["wq"] - hf["layers"]["wq"])
                  ).max() > 0.1
    ids = np.random.default_rng(2).integers(0, cfg.vocab_size, (2, 19))
    a = ref.run_highest(ref.logits, hf, jax.numpy.asarray(ids), rope="halves")
    b = ref.run_highest(ref.logits, ours, jax.numpy.asarray(ids))
    c = ref.run_highest(ref.logits, hf, jax.numpy.asarray(ids))
    assert float(np.abs(a - b).max() / np.abs(a).max()) < 1e-5
    assert float(np.abs(a - c).max() / np.abs(a).max()) > 1e-2


def test_the_reference_s_exit_rule_on_hand_cases():
    import numpy as np

    from benchmark.reference import ouro as ref

    lam = np.asarray([[0.5, 0.1], [0.5, 0.2], [0.9, 0.9]], np.float32)
    pdf = np.asarray(ref.exit_pdf(lam))
    np.testing.assert_allclose(pdf[:, 0], [0.5, 0.25, 0.25])
    np.testing.assert_allclose(pdf[:, 1], [0.1, 0.18, 0.72], rtol=1e-6)
    # the published threshold 1 is reached by no pass before the last
    assert np.asarray(ref.exit_pass(pdf, 1.0)).tolist() == [2, 2]
    assert np.asarray(ref.exit_pass(pdf, 0.7)).tolist() == [1, 2]
    assert np.asarray(ref.exit_pass(pdf, 0.05)).tolist() == [0, 0]


# ------------------------------------------------------------ the reducer
def loop_span(step, running, moved=128.0):
    return SpanEvent("decode_step", step, step + 0.045, step=step, meta={
        "slots": running, "loop_steps": 4, "cache_planes": 192,
        "cache_bytes_per_token": 1572864,
        "weight_bytes_per_token": 19.9e9 / running,
        "append_moved_over_new": moved, "exit_pdf": [0.3, 0.2, 0.2, 0.3]})


def test_looped_step_hbm_share_on_a_hand_case(ouro_conf, monkeypatch):
    evs = [loop_span(0, 12), loop_span(1, 10, moved=153.6)]
    monkeypatch.setattr(looped_step_hbm_share, "_captured", lambda: evs)
    monkeypatch.setattr(looped_step_hbm_share, "program_time",
                        lambda facts, **kw: 45.0)           # ms
    facts = {"family": "ouro", "model": ouro_conf["config"],
             "decode_live_tokens": [1800, 2000],
             "peaks": {"hbm_bytes_per_s": 819e9}}
    n = fam.layer_params(ouro_conf["config"])
    weights = 4 * 48 * (n["attention"] + n["mlp"]) * 2
    assert round(weights / 1e9, 1) == 19.7
    moved = weights + 2 * n["head"] + 1900 * 1572864 + 1536 * 1572864
    got = looped_step_hbm_share.reduce(facts, program="^jit__step_impl\\(")
    assert got == pytest.approx(100 * 1e3 * moved / 819e9 / 45.0)
    assert 60 < got < 100
    assert any("19.730 GB" in note and "2.416 GB" in note
               for note in facts["notes"])
    # a program that records no such span (the parent), another family
    monkeypatch.setattr(looped_step_hbm_share, "_captured", lambda: [
        SpanEvent("decode_step", 0, 1, step=0, meta={"slots": 12})])
    assert looped_step_hbm_share.reduce(facts, program="x") is None
    monkeypatch.setattr(looped_step_hbm_share, "_captured", lambda: evs)
    assert looped_step_hbm_share.reduce(dict(facts, family="gpt2"),
                                        program="x") is None
    monkeypatch.setattr(program_span, "_captured", lambda: evs)
    assert program_span.reduce({}, parent="decode_step", statistic="mean",
                               meta="weight_bytes_per_token") \
        == pytest.approx((19.9e9 / 12 + 19.9e9 / 10) / 2)


# ------------------------------------------- the kind's own comparison
@pytest.fixture(scope="module")
def ouro_small(ouro_conf):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    from benchmark.reference import ouro as ref
    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh

    published = dict(ouro_conf["config"], **ouro_conf["rehearsal"])
    cfg, model = fam.build(published, "float32", flash_attention=False)
    params = model.init(jax.random.PRNGKey(3))
    mesh = build_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    cell = types.SimpleNamespace(
        seed=11, reference=ref, published=published,
        mix={"engine": {"slots": 4, "max_len": 128, "prefill_chunk": 16},
             "check_prompt_tokens": [9, 50], "check_decode_steps": 4,
             "logit_tolerance": 1e-4})
    return cfg, model, params, mesh, cell


def engine(ouro_small, model=None):
    import deepspeed_tpu as ds

    _, own, params, mesh, _ = ouro_small
    return ds.init_inference(model or own, params, {"dtype": "float32"},
                             mesh=mesh)


def test_the_cache_path_comparison_passes_on_the_system(ouro_small):
    from benchmark.kinds import backlog_looped as kind

    cfg, _, params, _, cell = ouro_small
    notes: list = []
    assert kind.check_logits(cell, cfg, params, engine(ouro_small), notes)
    assert sum("through the cache" in n for n in notes) == 2
    assert sum("last-position logits" in n for n in notes) == 2
    assert not any("OUTSIDE" in n for n in notes), notes


def test_planes_shared_between_passes_fail_the_cache_path_alone(ouro_small):
    from benchmark.kinds import backlog_looped as kind
    from deepspeed_tpu.models.transformer import TransformerLM

    class SharedPlanes(TransformerLM):
        def loop_passes(self, params, x, carry, one_pass):
            return super().loop_passes(
                params, x, carry, lambda x, c, r: one_pass(x, c, r * 0))

    cfg, _, params, _, cell = ouro_small
    notes: list = []
    assert not kind.check_logits(cell, cfg, params,
                                 engine(ouro_small, SharedPlanes(cfg)), notes)
    for n in notes:
        assert ("OUTSIDE" in n) == ("through the cache" in n), n


def test_a_closing_norm_after_the_last_pass_only_fails_both(ouro_small):
    import jax.numpy as jnp

    from benchmark.kinds import backlog_looped as kind
    from deepspeed_tpu.models.transformer import TransformerLM

    class LastOnly(TransformerLM):
        def loop_passes(self, params, x, carry, one_pass):
            for r in range(self.cfg.loop_steps):
                x, carry = one_pass(x, carry, jnp.int32(r))
            return self._final_norm(params, x), carry, {}

    cfg, _, params, _, cell = ouro_small
    notes: list = []
    assert not kind.check_logits(cell, cfg, params,
                                 engine(ouro_small, LastOnly(cfg)), notes)
    assert all("OUTSIDE" in n for n in notes), notes


def test_served_tokens_are_the_reference_s_draws_and_a_changed_one_is_not(
        ouro_small):
    """What excuses a served request that differs from solo ``generate()``:
    every served token is the draw of the reference's logits over prompt +
    answer with the request's own noise; a token changed by hand is not."""
    import numpy as np

    import deepspeed_tpu as ds
    from benchmark.kinds import backlog_looped as kind

    cfg, _, params, _, cell = ouro_small
    srv = ds.ServingEngine(engine(ouro_small), {
        "slots": 3, "max_len": 128, "prefill_chunk": 16})
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, 37,
                                               dtype=np.int32)
    rid = srv.submit(prompt, 6, seed=5)
    srv.drain()
    toks = np.asarray(srv.pop_result(rid).tokens)
    srv.close()
    assert kind.drawn_from_the_reference(cell, params, prompt, toks, 5) == 0
    wrong = toks.copy()
    wrong[3] = (toks[3] + 1) % cfg.vocab_size
    assert kind.drawn_from_the_reference(cell, params, prompt, wrong, 5) >= 1
