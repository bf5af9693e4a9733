#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process, which owns the chip from start to end, drives the main path
once through the entry points a user calls, at the published width and depth
of GPT-2 774M (36 layers, d_model 1280, 20 heads, vocab 50257; weights and
data from ``--seed``):

- **train**: ``ds.initialize`` -> ``engine.train_batch`` for a few steps
  (seq 1024, bf16, flash attention, fused cross-entropy on auto, ZeRO-1,
  Lion, ``save_names`` remat) — the loss is finite and falls;
- **serve**: ``ds.init_inference`` -> ``ds.ServingEngine.serve_batch`` on the
  contiguous slot cache and on the paged pool, each answer equal to solo
  ``generate()``; then int8 weight-only quantization with the Pallas
  ``woq_matmul`` kernel against the XLA dequant path.

Every phase prints the programs it compiled, the Pallas kernels
(``tpu_custom_call``) found in their compiled text, peak device memory and
cold compile seconds. A kernel a phase expects that is absent, a check that
fails or a phase that raises ends the run non-zero. The last line of stdout
is ``{"ok": true, "device": {...}}`` and is printed only when everything
passed on a TPU.

    python chip_smoke.py                 # one chip, as the driver runs it
    python chip_smoke.py --four-chips    # sharded train + TP=4 generate only
    python chip_smoke.py --float32-too   # + every token comparison in float32
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse     # tiny, CPU, never "ok"
"""

from __future__ import annotations

import argparse
import gc
import json
import re
import sys
import time

import numpy as np

TRAIN_KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
                 "flash_attention_bwd_dkv", "fused_xent_fwd",
                 "fused_xent_bwd_dx", "fused_xent_bwd_dw")
# near-tied logits: a bf16 mantissa holds 8 bits, so two candidates whose
# logits differ by less than 2^-6 of the row's largest magnitude (a couple
# of roundings deep in a 36-layer trunk) may legitimately swap
BF16_TIE = 2.0 ** -6
ON_TPU = False          # set in main() from jax.devices()
FLOAT32_TOO = False     # --float32-too: compare in float32 even where bf16 agrees


class SmokeFailure(AssertionError):
    pass


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    say(f"ok: {what}")


# ------------------------------------------------------------ observation
class CompileWatch:
    """Counts backend compiles and persistent-cache hits/misses through
    jax.monitoring — the program's own events, no wrapper around jit."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event.endswith("/compilation_cache/cache_hits"):
            self.hits += 1
        elif event.endswith("/compilation_cache/cache_misses"):
            self.misses += 1

    def _duration(self, event, secs, **_):
        if event.endswith("/backend_compile_duration"):
            self.compile_s += secs

    def mark(self):
        return (self.hits, self.misses, self.compile_s)

    def since(self, mark) -> str:
        h, m, s = mark
        return (f"cache hits {self.hits - h}, misses {self.misses - m}, "
                f"backend compile {self.compile_s - s:.1f} s")


def true_float32():
    """On a TPU a float32 matmul multiplies in bf16 passes unless asked
    otherwise; a float32 comparison that has to decide whether bf16
    rounding explains a difference needs the real thing."""
    import jax

    return jax.default_matmul_precision("highest")


def kernels_in(text: str) -> list[str]:
    """Names of the Pallas kernels in a compiled program's text (each
    ``pallas_call`` carries a stable ``name=``, see deepspeed_tpu/ops)."""
    found = set()
    for line in text.splitlines():
        if "tpu_custom_call" in line:
            m = re.search(r'op_name="[^"]*?([A-Za-z0-9_]+)\)*/pallas_call', line)
            found.add(m.group(1) if m else "unnamed")
    return sorted(found)


def report_programs(phase: str, texts: dict, expect=()) -> None:
    seen = set()
    for name, text in texts.items():
        ks = kernels_in(text)
        seen.update(ks)
        say(f"{phase}: program {name}: tpu_custom_call "
            f"{'x'.join(ks) if ks else 'absent'}")
    if not ON_TPU:    # rehearsal off the chip: Pallas runs interpreted
        say(f"{phase}: not on a TPU, kernels {list(expect)} not checked")
        return
    missing = [k for k in expect if k not in seen]
    check(not missing, f"{phase}: expected kernels present in the compiled "
          f"programs {list(expect)} (missing {missing})")


def memory_line(phase: str, devices) -> None:
    for d in devices:
        st = d.memory_stats() or {}
        say(f"{phase}: device {d.id} memory_stats peak "
            f"{_gib(st.get('peak_bytes_in_use'))} in use "
            f"{_gib(st.get('bytes_in_use'))} limit "
            f"{_gib(st.get('bytes_limit'))}")


def _gib(b) -> str:
    return "n/a" if b is None else f"{b / 2**30:.2f} GiB"


STEP_TEMP_LIMIT = 2 ** 30


def step_temporaries(phase: str, compiled):
    """Bytes the compiled step program holds beside its arguments and
    outputs, by the compiler's own account (None where the backend gives
    none)."""
    mem = compiled.memory_analysis()
    temp = getattr(mem, "temp_size_in_bytes", None)
    say(f"{phase}: step program temporaries {_gib(temp)} beside arguments "
        f"{_gib(getattr(mem, 'argument_size_in_bytes', None))}")
    return temp


# ------------------------------------------------------------------ sizes
def sizes(rehearse: bool) -> dict:
    if rehearse:
        # tiny: finds wrong paths, arguments and sharding rules, nothing
        # else. The vocabulary is odd like GPT-2's, and the fused loss is
        # forced on (off a TPU "auto" means the XLA loss path).
        return dict(model=dict(size="125m", n_layer=2, d_model=128, n_head=4,
                               vocab_size=509, max_seq=256, fused_xent=True),
                    seq=256, micro=2, micro4=2, steps=4, lr=1e-3, slots=2,
                    max_len=256,
                    chunk=64, page=64,
                    prompts=(160, 160, 40, 9), new=(6, 6, 5, 4))
    return dict(model=dict(size="774m", max_seq=1024),
                seq=1024, micro=16, micro4=4, steps=6, lr=2e-5, slots=4,
                max_len=1024,
                chunk=256, page=128,
                prompts=(640, 640, 520, 96, 17), new=(12, 12, 16, 24, 32))


def build(sz: dict, flash: bool, dtype=None):
    from deepspeed_tpu.models import build_model, gpt2

    kw = dict(sz["model"])
    if dtype is not None:
        kw["dtype"] = dtype
    cfg = gpt2(kw.pop("size"), **kw)
    attn = None
    if flash:
        from deepspeed_tpu.ops.flash_attention import make_flash_attention

        attn = make_flash_attention()
    return cfg, build_model(cfg, attention_fn=attn)


def train_batch_for(sz: dict, cfg, n: int, seed: int) -> dict:
    from deepspeed_tpu.runtime.dataloader import (DataLoader,
                                                  random_token_dataset)

    data = random_token_dataset(n, seq_len=sz["seq"],
                                vocab_size=cfg.vocab_size, seed=seed,
                                learnable=True)
    return DataLoader(data, local_batch_size=n,
                      shuffle=False).collate_fn(data[:n])


# ------------------------------------------------------------------ train
def train_steps(phase, sz, seed, watch, *, micro, global_batch=None,
                mesh=None, stage=1, expect=TRAIN_KERNELS):
    """A few optimizer steps through ds.initialize/train_batch on ``mesh``
    (default: every device) over one fixed global batch, accumulated where
    the mesh's data-parallel world is smaller. Returns the per-step losses
    (host floats) and the engine (the caller frees it)."""
    import jax

    import deepspeed_tpu as ds

    cfg, model = build(sz, flash=True)
    dp = int(mesh.shape["data"]) if mesh is not None else len(jax.devices())
    global_batch = global_batch or micro * dp
    mark = watch.mark()
    engine = ds.initialize({
        "train_batch_size": global_batch,
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": global_batch // (micro * dp),
        "optimizer": {"type": "lion", "params": {"lr": sz["lr"]}},
        "gradient_clipping": 1.0,
        "zero_optimization": {"stage": stage},
        "remat": {"enabled": True, "policy": "save_names"},
        "steps_per_print": 10 ** 9,
    }, model, mesh=mesh, seed=seed)
    batch = train_batch_for(sz, cfg, global_batch, seed)
    t0 = time.perf_counter()
    text = engine.compiled_step_text(batch)
    shape = {a: n for a, n in engine.mesh.shape.items() if n > 1}
    say(f"{phase}: train_step compiled cold in "
        f"{time.perf_counter() - t0:.1f} s ({cfg.param_count() / 1e6:.0f}M "
        f"params, micro-batch {micro} of global {global_batch}, seq "
        f"{sz['seq']}, mesh {shape or 'one device'}, zero {stage})")
    report_programs(phase, {"train_step": text}, expect)
    losses = []
    for _ in range(sz["steps"]):
        m = engine.train_batch(dict(batch))
        losses.append(float(jax.block_until_ready(m["loss"])))
    say(f"{phase}: losses {[round(x, 4) for x in losses]}")
    check(all(np.isfinite(losses)), f"{phase}: every loss is finite")
    check(losses[-1] < losses[0],
          f"{phase}: loss fell on learnable data "
          f"({losses[0]:.4f} -> {losses[-1]:.4f})")
    say(f"{phase}: {watch.since(mark)}")
    memory_line(phase, engine.mesh.devices.reshape(-1).tolist())
    return losses, engine


def phase_train(sz, seed, watch, devices):
    _, engine = train_steps("train", sz, seed, watch, micro=sz["micro"])
    del engine
    gc.collect()


# ------------------------------------------------------------------ serve
def requests(sz, cfg, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (p,)).astype(np.int32)
            for p in sz["prompts"]]


def serve(eng, sz, prompts, **extra):
    import deepspeed_tpu as ds

    srv = ds.ServingEngine(eng, {"slots": sz["slots"], "max_len": sz["max_len"],
                                 "prefill_chunk": sz["chunk"], "greedy": True,
                                 **extra})
    return srv, srv.serve_batch(prompts, list(sz["new"]))   # host tokens


def solo(eng, sz, prompts):
    outs = []
    for p, n in zip(prompts, sz["new"]):
        o = eng.generate(p[None], n, greedy=True, cache_len=sz["max_len"])
        outs.append(np.asarray(o)[0])
    return outs


def first_divergence(a_outs, b_outs):
    """(request, position) of the first differing token, or None."""
    for r, (a, b) in enumerate(zip(a_outs, b_outs)):
        n = min(len(a), len(b))
        bad = np.nonzero(np.asarray(a[:n]) != np.asarray(b[:n]))[0]
        if len(bad) or len(a) != len(b):
            return r, int(bad[0]) if len(bad) else n
    return None


def tie_gap(eng, prompt, toks, pos, other):
    """Logit gap between the two candidate tokens at the first differing
    position, from a full forward over the shared context; relative to the
    row's largest magnitude."""
    ctx = np.concatenate([prompt, np.asarray(toks[:pos], np.int32)])
    row = np.asarray(eng.forward(ctx[None])[0, -1], np.float32)
    gap = abs(float(row[int(toks[pos])]) - float(row[int(other[pos])]))
    return gap, gap / max(float(np.abs(row).max()), 1e-9)


def compare(what, eng, prompts, got, want, make_f32):
    """Greedy tokens must match. Where bf16 breaks exactness between two
    differently-shaped programs, it must be a near-tie AND float32 must
    restore exactness — anything else is a bug."""
    div = first_divergence(got, want)
    if div is None:
        check(True, f"{what}: bf16 tokens exact")
        if FLOAT32_TOO:
            got32, want32 = make_f32()
            check(first_divergence(got32, want32) is None,
                  f"{what}: float32 tokens exact")
        return
    r, pos = div
    if pos >= min(len(got[r]), len(want[r])):
        raise SmokeFailure(f"{what}: request {r} answer lengths differ "
                           f"({len(got[r])} vs {len(want[r])})")
    gap, rel = tie_gap(eng, prompts[r], got[r], pos, want[r])
    say(f"{what}: bf16 tokens first differ at request {r} position {pos} "
        f"({int(got[r][pos])} vs {int(want[r][pos])}); logit gap between "
        f"the two {gap:.5f} = {rel:.2e} of the row's max")
    check(rel <= BF16_TIE, f"{what}: the differing tokens are a near-tie at "
          f"bf16 rounding level (relative gap {rel:.2e} <= {BF16_TIE:.2e})")
    got32, want32 = make_f32()
    check(first_divergence(got32, want32) is None,
          f"{what}: float32 restores exact token parity")


def phase_serve(sz, seed, watch, devices):
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu as ds
    from deepspeed_tpu.inference.quantization import woq_dot

    cfg, model = build(sz, flash=False)
    params = model.init(jax.random.PRNGKey(seed))
    prompts = requests(sz, cfg, seed)
    mark = watch.mark()
    t0 = time.perf_counter()

    def engine(dtype="bfloat16", **kw):
        return ds.init_inference(model, params, {"dtype": dtype, **kw})

    def f32_pair(a_kw, b_kw=None, a_srv=None, b_srv=None):
        """Served tokens of engine a against served tokens of engine b, or
        against a's own solo generate, in float32."""
        def run():
            with true_float32():
                ea = engine("float32", **a_kw)
                _, got = serve(ea, sz, prompts, **(a_srv or {}))
                if b_kw is None:
                    return got, solo(ea, sz, prompts)
                del ea
                _, want = serve(engine("float32", **b_kw), sz, prompts,
                                **(b_srv or {}))
                return got, want
        return run

    # -- bf16, contiguous slot cache, against solo generate() ------------
    eng = engine()
    ref = solo(eng, sz, prompts)
    srv, got = serve(eng, sz, prompts)
    for o, n in zip(got, sz["new"]):
        check(len(o) == n and (o >= 0).all() and (o < cfg.vocab_size).all(),
              f"serve: answer of {n} in-vocab tokens")
    programs = srv.compiled_programs()
    texts = {name: c.as_text() for name, c in programs.items()}
    say(f"serve/contiguous: {srv.compiles} programs compiled")
    check(any(name.startswith("chunk_") for name in texts),
          "serve: chunked prefill ran (a prompt longer than prefill_chunk)")
    report_programs("serve/contiguous", texts, expect=("decode_attention",))
    check("cache_append" not in kernels_in(texts["step"]),
          "serve/contiguous: the step appends inside decode_attention "
          "(no cache_append kernel)")
    # the step carries the cache donated and only its one kernel touches it:
    # temporaries the size of a cache mean it is being copied or re-laid
    # out again (PERF.md F10: 6.25 GiB at 32 slots)
    temp = step_temporaries("serve/contiguous", programs["step"])
    check(temp is None or temp <= STEP_TEMP_LIMIT,
          f"serve/contiguous: step program temporaries {_gib(temp)} at or "
          f"under {_gib(STEP_TEMP_LIMIT)}")
    compare("serve/contiguous vs solo generate", eng, prompts, got, ref,
            f32_pair({}))
    del srv

    # -- bf16, paged pool ------------------------------------------------
    srv, paged = serve(eng, sz, prompts, page_size=sz["page"])
    programs = srv.compiled_programs()
    texts = {name: c.as_text() for name, c in programs.items()}
    report_programs("serve/paged", texts)
    say("serve/paged: decode kernel in the paged step program: "
        f"{'decode_attention' in kernels_in(texts['step'])} (the page gather "
        "runs first either way — ROADMAP S2(b))")
    # read, not judged: the paged step gathers every slot's pages into a
    # contiguous view per layer, and that view is a temporary by design
    step_temporaries("serve/paged", programs["step"])
    compare("serve/paged vs solo generate", eng, prompts, paged, ref,
            f32_pair({}, a_srv={"page_size": sz["page"]}))
    del srv, eng

    # -- int8 WOQ: Pallas kernel against the XLA dequant path -------------
    q8 = dict(quantize=True, quant_bits=8)
    eng_k = engine(**q8, woq_kernel=True)
    srv, got_k = serve(eng_k, sz, prompts)
    report_programs("serve/int8-kernel", srv.compiled_texts(),
                    expect=("decode_attention", "woq_matmul"))
    del srv
    # one real quantized leaf through both consumers: the logit-level gap
    lay = jax.tree.map(lambda a: a[0], eng_k.params["layers"])
    w_in = lay["w_in"]
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (sz["slots"], w_in.shape[-2]), jnp.bfloat16)
    yk = jax.jit(lambda x, w: woq_dot(x, w, use_kernel=True))(x, w_in)
    yx = jax.jit(lambda x, w: woq_dot(x, w, use_kernel=False))(x, w_in)
    yk, yx = (np.asarray(jax.block_until_ready(y), np.float32)
              for y in (yk, yx))
    err = float(np.abs(yk - yx).max() / max(np.abs(yx).max(), 1e-9))
    check(np.isfinite(yk).all() and err <= 2.0 ** -6,
          f"serve/int8: woq_matmul kernel vs XLA dequant on layer-0 w_in, "
          f"max relative error {err:.2e} (bf16 output)")
    eng_x = engine(**q8, woq_kernel=False)
    _, got_x = serve(eng_x, sz, prompts)
    compare("serve/int8 kernel vs XLA dequant", eng_x, prompts, got_k, got_x,
            f32_pair(dict(q8, woq_kernel=True), dict(q8, woq_kernel=False)))
    say(f"serve: all engines built, served and compared in "
        f"{time.perf_counter() - t0:.1f} s; {watch.since(mark)}")
    memory_line("serve", devices)


# ------------------------------------------------------------- four chips
def shard_report(phase, engine, devices):
    """Shard shapes of the largest parameter (the stacked MLP weight) and
    its optimizer state — ZeRO >= 1 shards both over every device — and
    the share of device memory the first device holds."""
    import jax

    n_dev = len(devices)

    def biggest(tree):
        return max(jax.tree.leaves(tree), key=lambda a: a.size)

    for name, tree in (("master param", engine.state.master_params),
                       ("optimizer state", engine.state.opt_state)):
        leaf = biggest(tree)
        shard = leaf.addressable_shards[0].data.shape
        say(f"{phase}: largest {name} {tuple(leaf.shape)} {leaf.dtype} -> "
            f"shard on device 0 {tuple(shard)}")
        check(int(np.prod(shard)) * n_dev <= leaf.size * 1.01,
              f"{phase}: device 0 holds 1/{n_dev} of the largest {name}")
    check_share(phase, devices)


def check_share(phase, devices):
    """Fail if the first device holds more than its share of the bytes in
    use (where the backend reports them)."""
    used = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    if all(u is not None for u in used) and len(used) > 1:
        share = used[0] / max(sum(used), 1)
        check(share <= 1.0 / len(used) * 1.10,
              f"{phase}: device 0 holds {share:.3f} of the bytes in use, "
              f"its share is {1.0 / len(used):.3f}")


def phase_four_train(sz, seed, watch, devices):
    """The train step on {"data": 4} ZeRO-3 and {"data": 2, "model": 2}
    against one device of the same process (a mesh of one device beside
    three idle ones), all over the same global batch."""
    from deepspeed_tpu.platform import MeshSpec, build_mesh

    n = len(devices)
    micro = sz["micro4"]
    meshes = {
        "one-device": (build_mesh(MeshSpec(), devices=devices[:1]), 1),
        "data4-zero3": (build_mesh(MeshSpec(data=n), devices=devices), 3),
        "data2-model2": (build_mesh(MeshSpec(data=n // 2, model=2),
                                    devices=devices), 1),
    }
    results = {}
    for tag, (mesh, stage) in meshes.items():
        losses, engine = train_steps(f"four/{tag}", sz, seed, watch,
                                     micro=micro, global_batch=micro * n,
                                     mesh=mesh, stage=stage)
        if mesh.size > 1:
            shard_report(f"four/{tag}", engine,
                         mesh.devices.reshape(-1).tolist())
        results[tag] = losses
        del engine
        gc.collect()
    ref = results.pop("one-device")
    for tag, got in results.items():
        say(f"four/{tag}: loss differences from one device "
            f"{[round(g - r, 4) for g, r in zip(got, ref)]}")
        check(abs(got[0] - ref[0]) <= 2e-2 * abs(ref[0]),
              f"four/{tag}: first-step loss {got[0]:.4f} matches the "
              f"one-device {ref[0]:.4f} to bf16 tolerance")
        check(abs(got[-1] - ref[-1]) <= 0.1 * abs(ref[0]),
              f"four/{tag}: last-step loss {got[-1]:.4f} tracks the "
              f"one-device {ref[-1]:.4f}")


def phase_four_tp(sz, seed, watch, devices):
    """Greedy generate() and first-step logits with tensor_parallel = 4
    against one device of the same process, in bf16 and in float32."""
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.platform import MeshSpec, build_mesh

    cfg, model = build(sz, flash=False)
    # the unsharded source weights wait on the host, so that what a device
    # holds is its engine's share and nothing else
    params = jax.device_get(model.init(jax.random.PRNGKey(seed)))
    prompts = requests(sz, cfg, seed)[-2:]
    new = sz["new"][-2:]
    n = len(devices)
    one_dev = build_mesh(MeshSpec(), devices=devices[:1])

    def run(dtype, tp):
        mark = watch.mark()
        eng = ds.init_inference(
            model, params, {"dtype": dtype, "tensor_parallel": tp},
            mesh=one_dev if tp == 1 else None)
        toks = [np.asarray(eng.generate(p[None], k, greedy=True))[0]
                for p, k in zip(prompts, new)]
        logits = [np.asarray(eng.forward(p[None])[0, -1], np.float32)
                  for p in prompts]
        say(f"four/tp: {dtype} tensor_parallel={tp}: {watch.since(mark)}")
        if tp > 1:
            w = max(jax.tree.leaves(eng.params["layers"]),
                    key=lambda a: a.size)
            shard = w.addressable_shards[0].data.shape
            say(f"four/tp: largest layer weight {tuple(w.shape)} -> shard "
                f"on device 0 {tuple(shard)}")
            check(int(np.prod(shard)) * tp == w.size,
                  f"four/tp: device 0 holds 1/{tp} of it")
            memory_line(f"four/tp {dtype}", devices)
            check_share(f"four/tp {dtype}", devices)
        return toks, logits, eng

    def against_one_device(dtype, tol):
        t1, l1, e1 = run(dtype, 1)
        del e1
        gc.collect()
        tn, ln, en = run(dtype, n)
        for a, b in zip(l1, ln):
            rel = float(np.abs(a - b).max() / max(np.abs(a).max(), 1e-9))
            check(np.isfinite(b).all() and rel <= tol,
                  f"four/tp: {dtype} first-step logits TP={n} vs one device, "
                  f"max relative error {rel:.2e} <= {tol:.1e}")
        div = first_divergence(tn, t1)
        if div is None:
            check(True, f"four/tp: {dtype} greedy tokens exact, TP={n} vs "
                  "one device")
            return
        if dtype == "float32":
            raise SmokeFailure(f"four/tp: float32 greedy tokens differ at "
                               f"(request, position) {div}")
        r, pos = div
        gap, rel = tie_gap(en, prompts[r], tn[r], pos, t1[r])
        say(f"four/tp: bf16 tokens first differ at request {r} position "
            f"{pos}; logit gap {gap:.5f} = {rel:.2e} of the row's max")
        check(rel <= BF16_TIE, "four/tp: the differing bf16 tokens are a "
              f"near-tie (relative gap {rel:.2e}); float32 decides below")

    against_one_device("bfloat16", 2.0 ** -5)
    gc.collect()
    with true_float32():
        against_one_device("float32", 1e-4)


# ------------------------------------------------------------------- main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train steps and TP=4 "
                         "generate, each against one device (needs 4 chips)")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever backend there is (CPU: "
                         "Pallas in interpret mode); never prints ok: true")
    ap.add_argument("--float32-too", action="store_true",
                    help="serve phase: repeat every token comparison in "
                         "float32 even where bf16 already agrees")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from deepspeed_tpu.platform import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    global ON_TPU, FLOAT32_TOO
    ON_TPU = dev["platform"] == "tpu"
    FLOAT32_TOO = args.float32_too
    say(f"devices: {dev}; jax {jax.__version__}; compile cache {cache_dir}")
    if not args.rehearse and dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0].platform == "
              f"{dev['platform']!r}); this check only passes on the chip",
              file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} devices, found {len(devices)}",
              file=sys.stderr)
        return 2

    watch = CompileWatch()
    sz = sizes(args.rehearse)
    t0 = time.perf_counter()
    if args.four_chips:
        devices = devices[:4]
        dev["count"] = len(devices)
        phases = (phase_four_train, phase_four_tp)
    else:
        phases = (phase_train, phase_serve)
    for phase in phases:
        t = time.perf_counter()
        phase(sz, args.seed, watch, devices)
        say(f"{phase.__name__} passed in {time.perf_counter() - t:.1f} s")
    say(f"all phases passed in {time.perf_counter() - t0:.1f} s; "
        f"{watch.since((0, 0, 0.0))}")
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": "passed", "device": dev}))
        return 0
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
