"""Serving bench: static batching vs the continuous-batching engine.

Workload: a Poisson-ish mix of request shapes — prompt lengths drawn from
a small bucket set, output budgets with a heavy tail (most requests want
a handful of tokens, a minority wants many). That tail is exactly what
static batching cannot absorb: every row of a static ``generate()`` batch
pays decode steps until the LONGEST row finishes, and a new request
cannot join until the whole batch drains. The continuous engine retires a
row the moment it finishes and admits the next request into the freed
slot, interleaving chunked prefill with the running decode.

Reported per mode: wall-clock goodput (completed tokens/s over the whole
workload), plus the deterministic slot-step efficiency model — useful
decode tokens divided by (decode steps x batch slots). The efficiency
ratio is the scheduling win with host/compile noise removed; wall clock
is what you actually get (CPU wall numbers carry per-iteration host-sync
overhead that shrinks on real accelerators where the step dominates).
The static baseline is generous: requests are grouped by equal prompt
length (no padding waste), only the dead tail and drain barrier remain.

``--smoke`` is the CPU tier-1 gate (wired via tests/unit/test_serving.py,
same pattern as bench_woq_probe.py): asserts (1) serving outputs are
bit-identical to single-request ``generate()`` with the same per-request
seed, (2) steady-state compiles are frozen after warmup, (3) the
slot-step efficiency win on the ragged workload is >= 1.5x. Prints one
JSON line ending in "smoke-pass"; exits nonzero on any failure.
"""

import json
import sys
import time

import numpy as np


def make_workload(n, seed=0, prompt_buckets=(8, 16, 24), short=(2, 8),
                  long=(28, 40), long_frac=0.25, vocab=256):
    """n requests: (prompt, max_new, seed) with a heavy-tailed max_new."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        p = int(rng.choice(prompt_buckets))
        if rng.random() < long_frac:
            mn = int(rng.integers(long[0], long[1] + 1))
        else:
            mn = int(rng.integers(short[0], short[1] + 1))
        prompt = rng.integers(0, vocab, (p,)).astype(np.int32)
        reqs.append((prompt, mn, 1000 + i))
    return reqs


def make_multiturn_plan(sessions=4, turns=3, seed=0, vocab=256,
                        sys_tokens=32, user=(6, 12), max_new=(4, 8)):
    """Deterministic multi-turn session plan: every session opens with
    one SHARED system prompt, and each turn's prompt replays the whole
    conversation so far (system + prior user turns + prior replies) plus
    fresh user tokens — the structure chat/agent traffic has and the one
    prefix sharing monetizes. Replies come from the engine at run time
    (bit-identical across engine modes by the parity oracle, so the
    traffic is identical too); everything else is pre-drawn here."""
    rng = np.random.default_rng(seed)
    sys_p = rng.integers(0, vocab, (sys_tokens,)).astype(np.int32)
    users = {(s, t): rng.integers(
        0, vocab, (int(rng.integers(user[0], user[1] + 1)),)).astype(
            np.int32) for s in range(sessions) for t in range(turns)}
    new = {(s, t): int(rng.integers(max_new[0], max_new[1] + 1))
           for s in range(sessions) for t in range(turns)}
    return {"sessions": sessions, "turns": turns, "sys": sys_p,
            "users": users, "max_new": new}


def run_multiturn(srv, plan, max_iterations=200_000, ttfts=None):
    """Drive a session plan through a ServingEngine: turn t+1 submits
    only after turn t retires (its reply is part of the next prompt).
    Returns (prompts in admission order, outputs keyed (session, turn))
    — the prompt list feeds the PR-6 workload estimator for the
    predicted-vs-achieved savings comparison. Each submit carries its
    session id, so the kvscope residency observatory (and fleet
    affinity) see the session structure. Pass a dict as ``ttfts`` to
    additionally collect per-(session, turn) TTFT — turn 0 is the cold
    prefill, turns >= 1 are RESUMES: the per-turn resume-TTFT series the
    perf ledger tracks against the coming host-tier PR."""
    sessions, turns = plan["sessions"], plan["turns"]
    hist = {s: plan["sys"] for s in range(sessions)}
    turn = {s: 0 for s in range(sessions)}
    pending, prompts, outs = {}, [], {}

    def submit(s):
        p = np.concatenate([hist[s], plan["users"][(s, turn[s])]])
        prompts.append(p)
        rid = srv.submit(p, plan["max_new"][(s, turn[s])],
                         seed=1000 + 97 * s + turn[s], session_id=s)
        pending[rid] = s

    for s in range(sessions):
        submit(s)
    it = 0
    while pending:
        for req in srv.step():
            s = pending.pop(req.rid, None)
            if s is None:
                continue
            out = np.asarray(req.tokens, np.int32)
            outs[(s, turn[s])] = out
            if ttfts is not None and req.first_token_t is not None:
                ttfts[(s, turn[s])] = req.first_token_t - req.submit_t
            hist[s] = np.concatenate(
                [hist[s], plan["users"][(s, turn[s])], out])
            turn[s] += 1
            if turn[s] < turns:
                submit(s)
        it += 1
        if it > max_iterations:
            raise RuntimeError("multi-turn driver stuck")
    return prompts, outs


def ttft_by_turn(ttfts, turns):
    """Per-turn mean TTFT rows (``turn<k>_ttft_s``) from a
    ``run_multiturn(ttfts=...)`` collection — turn 0 cold, later turns
    the resume series the perf ledger gates on (down is good)."""
    out = {}
    for t in range(turns):
        vals = [v for (s, tt), v in ttfts.items() if tt == t]
        if vals:
            out[f"turn{t}_ttft_s"] = round(sum(vals) / len(vals), 6)
    return out


def build(slots, max_len, chunk, temperature=0.8, top_k=20,
          n_layer=4, d_model=128, n_head=4, clock=None, **serving_extra):
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import build_model, tiny_test

    cfg = tiny_test(n_layer=n_layer, d_model=d_model, d_ff=2 * d_model,
                    n_head=n_head, max_seq=max_len, dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ds.init_inference(model, params, {"dtype": "float32"})
    kw = {"clock": clock} if clock is not None else {}
    srv = ds.ServingEngine(eng, {"slots": slots, "max_len": max_len,
                                 "prefill_chunk": chunk,
                                 "temperature": temperature, "top_k": top_k,
                                 **serving_extra}, **kw)
    return model, params, eng, srv


def run_static(eng, reqs, slots, temperature=0.8, top_k=20):
    """Static batching, generously bucketed: groups of <= slots requests
    with EQUAL prompt length, each decoding until the group max_new."""
    import jax

    groups, by_len = [], {}
    for r in reqs:             # arrival order within each length bucket
        by_len.setdefault(len(r[0]), []).append(r)
        bucket = by_len[len(r[0])]
        if len(bucket) == slots:
            groups.append(bucket[:])
            bucket.clear()
    groups += [b for b in by_len.values() if b]
    slot_steps = useful = 0
    outs = []
    for g in groups:
        prompts = np.stack([p for p, _, _ in g])
        mx = max(mn for _, mn, _ in g)
        out = eng.generate(prompts, mx, temperature=temperature, top_k=top_k,
                           request_seeds=[s for _, _, s in g])
        outs.append(out)
        slot_steps += len(g) * (mx - 1)
        useful += sum(mn - 1 for _, mn, _ in g)
    jax.block_until_ready(outs)
    return {"groups": len(groups), "decode_slot_steps": slot_steps,
            "useful_decode_tokens": useful,
            "completed_tokens": sum(mn for _, mn, _ in reqs)}


def run_continuous(srv, reqs):
    outs = srv.serve_batch([p for p, _, _ in reqs],
                           [mn for _, mn, _ in reqs],
                           [s for _, _, s in reqs])
    return outs


def bench(n=48, slots=6, max_len=80, chunk=16, seed=1):
    # decode-dominated mix — short prompts, heavy output tail — is the
    # regime continuous batching targets (chat/agent traffic); the static
    # baseline's batch rides its longest row while most rows sit finished
    reqs = make_workload(n, seed=seed, prompt_buckets=(8, 16),
                         short=(2, 8), long=(32, 56), long_frac=0.3)
    model, params, eng, srv = build(slots, max_len, chunk,
                                    n_layer=6, d_model=384, n_head=8)

    # pass 1: warmup (compiles); pass 2: timed. Reset the Serve/* series
    # between passes so the reported TTFT/TPOT/goodput reflect steady
    # state, not compile-laden warmup samples.
    run_static(eng, reqs, slots)
    run_continuous(srv, reqs)
    warm_compiles = srv.compiles
    srv.stats.reset()

    t0 = time.perf_counter()
    st = run_static(eng, reqs, slots)
    t1 = time.perf_counter()
    run_continuous(srv, reqs)
    t2 = time.perf_counter()

    snap = srv.stats.snapshot()
    cont_decode_steps = snap["decode_steps"]
    total_tokens = st["completed_tokens"]
    static_s, cont_s = t1 - t0, t2 - t1
    static_eff = st["useful_decode_tokens"] / max(1, st["decode_slot_steps"])
    cont_eff = st["useful_decode_tokens"] / max(1, cont_decode_steps * slots)
    res = {
        "workload": {"requests": n, "slots": slots, "max_len": max_len,
                     "prefill_chunk": chunk,
                     "completed_tokens": total_tokens},
        "static": {"wall_s": round(static_s, 3),
                   "tokens_per_s": round(total_tokens / static_s, 1),
                   "groups": st["groups"],
                   "decode_slot_steps": st["decode_slot_steps"],
                   "slot_step_efficiency": round(static_eff, 3)},
        "continuous": {"wall_s": round(cont_s, 3),
                       "tokens_per_s": round(total_tokens / cont_s, 1),
                       "decode_steps": cont_decode_steps,
                       "slot_step_efficiency": round(cont_eff, 3),
                       "compiled_programs": warm_compiles,
                       "new_compiles_after_warmup":
                           srv.compiles - warm_compiles,
                       "ttft_s": snap["ttft_s"], "tpot_s": snap["tpot_s"]},
        "goodput_speedup_wall": round(static_s / cont_s, 2),
        "efficiency_speedup": round(cont_eff / static_eff, 2),
    }
    return res


def bench_multiturn(slots=4, max_len=128, chunk=16, page_size=16,
                    sessions=6, turns=4):
    """Multi-turn/session row: the same session traffic through the
    contiguous engine and the paged+prefix-sharing engine. The paged
    engine prefills each replayed conversation prefix once; the report
    carries prefill tokens paid/saved, TTFT, and pool state
    (bench_paged_kv.py is the deeper paged bench + tier-1 gate)."""
    plan = make_multiturn_plan(sessions=sessions, turns=turns, seed=3,
                               sys_tokens=32, user=(6, 12), max_new=(4, 8))
    rows = {}
    for mode, extra in (("contiguous", {}),
                        ("paged_sharing", {"page_size": page_size})):
        import deepspeed_tpu as ds

        _, _, eng, srv = build(slots, max_len, chunk, n_layer=4,
                               d_model=256, n_head=8, **extra)
        run_multiturn(srv, plan)            # warmup (compiles only)
        # measure on a FRESH serving state over the same engine: the
        # program LRU lives on the InferenceEngine so compiles stay
        # warm, but the pool/prefix tree start cold — the row reports
        # what the sharing actually earns on this traffic, not a replay
        # against a tree pre-warmed with the identical prompts
        srv = ds.ServingEngine(eng, {"slots": slots, "max_len": max_len,
                                     "prefill_chunk": chunk,
                                     "temperature": 0.8, "top_k": 20,
                                     **extra})
        pre = srv.pool.snapshot() if srv.pool is not None else None
        ttfts = {}
        t0 = time.perf_counter()
        prompts, outs = run_multiturn(srv, plan, ttfts=ttfts)
        wall = time.perf_counter() - t0
        snap = srv.stats.snapshot()
        total_prompt = int(sum(len(p) for p in prompts))
        saved = (srv.pool.snapshot()["prefill_tokens_saved"]
                 - pre["prefill_tokens_saved"]) if pre is not None else 0
        rows[mode] = {
            "wall_s": round(wall, 3),
            "completed_tokens": int(sum(len(o) for o in outs.values())),
            "prompt_tokens": total_prompt,
            "prefill_tokens_paid": total_prompt - saved,
            "prefill_tokens_saved": saved,
            "ttft_s": snap["ttft_s"],
            # per-turn resume TTFT: turn 0 is the cold prefill; later
            # turns replay the conversation — the series the host-tier
            # PR must move (perf ledger direction: down)
            "resume_ttft": ttft_by_turn(ttfts, turns),
        }
        if srv.pool is not None:
            ps = srv.pool.snapshot()
            rows[mode]["pool"] = {k: ps[k] for k in (
                "usable_pages", "free_pages", "tree_held_pages",
                "prefix_hit_rate", "cow_copies", "fragmentation")}
    return {"workload": {"sessions": sessions, "turns": turns,
                         "page_size": page_size},
            **rows}


def spec_workload(eng, n=8, seed=5, n_cand=16, plen=(16, 28), max_new=88,
                  vocab=256, cache_len=160):
    """Decode traffic in the regime prompt-lookup drafting monetizes:
    prompts that steer the model into its stable greedy attractors
    (constant / short-period continuations — the synthetic stand-in for
    templated JSON, agentic retries, code edits, where real decodes
    repeat the context). Candidate tokens are probed against the ACTUAL
    engine and ranked by the shared n-gram helper's decode-region hit
    rate, so the workload tracks whatever model the bench builds
    instead of hard-coding one seed's attractors. Output budgets are
    uniform so the wall-clock row measures steady-state decode, not the
    ragged-tail drain (bench() owns that regime)."""
    import jax.numpy as jnp

    from deepspeed_tpu.inference.speculation import acceptance_stats

    rng = np.random.default_rng(seed)
    scored = []
    for t in rng.choice(vocab, size=n_cand, replace=False):
        p = np.full((16,), int(t), np.int32)
        out = np.asarray(eng.generate(jnp.asarray(p[None]), 48,
                                      temperature=0.0,
                                      cache_len=cache_len))[0]
        full = acceptance_stats(p.tolist() + out.tolist(), 3)
        head = acceptance_stats(p.tolist(), 3)
        pred = full["predicted"] - head["predicted"]
        hit = (full["hits"] - head["hits"]) / pred if pred else 0.0
        scored.append((hit, int(t)))
    pool = [t for _, t in sorted(scored, reverse=True)[:max(2, n // 2)]]
    reqs = []
    for i in range(n):
        ln = int(rng.integers(plen[0], plen[1] + 1))
        reqs.append((np.full((ln,), pool[i % len(pool)], np.int32),
                     max_new, 1000 + i))
    return reqs


def bench_speculation(n=8, slots=4, max_len=160, chunk=16, page_size=16,
                      ngram=3, max_draft=6, reps=3):
    """Self-speculative decoding row: the same greedy paged traffic
    spec-off vs spec-on. Spec-on drafts up to ``max_draft`` tokens per
    slot from the slot's own n-gram history and scores them in ONE
    fixed-shape verify forward per step, so each decode iteration can
    commit several tokens. Greedy spec-on is bit-identical to spec-off
    (asserted here as ``parity``); the headline numbers are
    ``accepted_tokens_per_step`` (>1 means the verify lane is paying)
    and the wall-clock goodput speedup at equal traffic. The
    ``verify_step_overhead`` ratio is what one length-(k+1) verify
    iteration costs relative to a plain decode step — acceptance must
    beat it for spec to win, which is why the engine only drafts when
    the table actually predicts."""
    from collections import OrderedDict

    import deepspeed_tpu as ds

    _, _, eng, _ = build(slots, max_len, chunk, n_layer=2, d_model=64,
                         n_head=4, greedy=True, page_size=page_size)
    reqs = spec_workload(eng, n=n, cache_len=max_len)
    rows, outs, walls = {}, {}, {}
    progs: OrderedDict = OrderedDict()      # shared program cache, the
    for mode, extra in (("spec_off", {}),   # fleet's replica pattern —
                        ("spec_on",         # timed passes compile zero
                         {"speculation": {"ngram": ngram,
                                          "max_draft": max_draft}})):
        cfg = {"slots": slots, "max_len": max_len, "prefill_chunk": chunk,
               "greedy": True, "page_size": page_size, **extra}
        srv = ds.ServingEngine(eng, cfg, programs=progs)
        run_continuous(srv, reqs)           # warmup (compiles only)
        srv.close()
        # timed reps on fresh serving state over the warm program cache;
        # best-of-reps strips CPU scheduler noise from the ~100ms walls
        # (token streams and counters are deterministic across reps)
        walls[mode] = float("inf")
        for _ in range(reps):
            srv = ds.ServingEngine(eng, cfg, programs=progs)
            t0 = time.perf_counter()
            outs[mode] = run_continuous(srv, reqs)
            walls[mode] = min(walls[mode], time.perf_counter() - t0)
            if _ < reps - 1:
                srv.close()
        snap = srv.stats.snapshot()
        spec = srv.spec_snapshot()
        total = int(sum(len(o) for o in outs[mode]))
        rows[mode] = {
            "wall_s": round(walls[mode], 3),
            "tokens_per_s": round(total / walls[mode], 1),
            "completed_tokens": total,
            "decode_steps": snap["decode_steps"],
        }
        if spec is not None:
            rows[mode]["speculation"] = {k: spec[k] for k in (
                "ngram", "max_draft", "verify_steps", "proposed_tokens",
                "accepted_tokens", "accept_rate", "first_accept_rate")}
            rows[mode]["accepted_tokens_per_step"] = (
                round(spec["accepted_tokens_per_step"], 4)
                if spec["accepted_tokens_per_step"] is not None else None)
        srv.close()
    parity = all(np.array_equal(a, b) for a, b in
                 zip(outs["spec_off"], outs["spec_on"]))
    per_off = walls["spec_off"] / max(1, rows["spec_off"]["decode_steps"])
    per_on = walls["spec_on"] / max(1, rows["spec_on"]["decode_steps"])
    assert parity, "greedy spec-on diverged from spec-off"
    return {
        "workload": {"requests": n, "slots": slots, "max_len": max_len,
                     "page_size": page_size, "ngram": ngram,
                     "max_draft": max_draft},
        **rows,
        "parity_spec_on_vs_off": parity,
        "accepted_tokens_per_step":
            rows["spec_on"].get("accepted_tokens_per_step"),
        "verify_step_overhead": round(per_on / per_off, 3),
        "goodput_speedup_wall": round(walls["spec_off"]
                                      / walls["spec_on"], 2),
    }


# ------------------------------------------------------------------ smoke
def smoke():
    """CPU tier-1 gate: parity + bounded compiles + scheduling win."""
    import jax.numpy as jnp
    from functools import partial

    from deepspeed_tpu.inference.decode import generate_tokens
    from deepspeed_tpu.inference.sampling import (per_request_keys,
                                                  sample_logits)

    slots, max_len, chunk = 6, 64, 16
    reqs = make_workload(40, seed=1)
    model, params, eng, srv = build(slots, max_len, chunk)

    # (1) bit-identical parity vs single-request generate(), same seed
    outs = run_continuous(srv, reqs)
    cont_steps = srv.stats.snapshot()["decode_steps"]
    smp = partial(sample_logits, temperature=0.8, top_k=20)
    for (p, mn, s), got in zip(reqs, outs):
        want = np.asarray(generate_tokens(
            model, params, jnp.asarray(p[None]), per_request_keys([s]),
            max_new=mn, sampler=smp, cache_len=max_len))[0]
        assert np.array_equal(got, want[:len(got)]), \
            f"parity broke for prompt_len={len(p)} max_new={mn} seed={s}"

    # (2) steady state compiles a bounded set: warm engine, zero new ones
    warm = srv.compiles
    run_continuous(srv, make_workload(24, seed=2))
    assert srv.compiles == warm, \
        f"{srv.compiles - warm} new compiles after warmup"

    # (3) scheduling win on the ragged tail, deterministic slot-step model
    st = run_static(eng, reqs, slots)
    static_eff = st["useful_decode_tokens"] / st["decode_slot_steps"]
    cont_eff = st["useful_decode_tokens"] / (cont_steps * slots)
    speedup = cont_eff / static_eff
    assert speedup >= 1.5, \
        f"continuous-batching efficiency win {speedup:.2f}x < 1.5x"
    print(json.dumps({
        "smoke": True, "parity_requests": len(reqs),
        "compiled_programs": warm, "efficiency_speedup": round(speedup, 2),
        "static_slot_step_efficiency": round(static_eff, 3),
        "continuous_slot_step_efficiency": round(cont_eff, 3),
        "verdict": "smoke-pass",
    }))


def main():
    res = bench()
    res["multiturn"] = bench_multiturn()
    res["speculation"] = bench_speculation()
    import os

    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "SERVING_BENCH.json")
    with open(out, "w") as f:
        json.dump(res, f, indent=2)
    print(json.dumps(res))


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        smoke()
    else:
        main()
