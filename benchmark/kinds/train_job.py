"""Traffic kind ``train_job``: optimizer steps through ``ds.initialize`` /
``engine.train_batch`` for the length of the window.

Set-up: the engine (weights and optimizer state made on the device from the
seed), the reference check on a few sequences, and the warm-up steps that
compile the one step program. Window: the host keeps one step in flight
ahead of the device, so the device never waits for it and every counted
step has really finished; the rate is taken over all of them and the time
to the last ``block_until_ready``.
"""

from __future__ import annotations

import time

import numpy as np

from ..harness import Cell, Outcome, TraceTail, say, settle_host, span
from ..traffic import train_batches


def build_engine(cell: Cell):
    import deepspeed_tpu as ds
    from deepspeed_tpu.platform import MeshSpec, build_mesh

    conf, mix = cell.config, cell.mix
    tr = conf["train"]
    cfg, model = cell.family.build(cell.published, conf["compute_dtype"],
                                   tr["flash_attention"])
    mesh = build_mesh(MeshSpec(**conf["mesh"]) if conf["mesh"]
                      else MeshSpec(), devices=cell.devices)
    dp = int(np.prod([n for a, n in mesh.shape.items()
                      if a in ("data", "zero", "expert")]))
    micro = int(mix["micro_batch_per_chip"])
    gas = int(mix["gradient_accumulation_steps"])
    optimizer = dict(tr["optimizer"])
    if "optimizer_lr" in mix:      # a rehearsal's tiny model wants a larger lr
        optimizer = dict(optimizer, params=dict(optimizer["params"],
                                                lr=mix["optimizer_lr"]))
    engine = ds.initialize({
        "train_batch_size": micro * dp * gas,
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "optimizer": optimizer,
        "gradient_clipping": tr["gradient_clipping"],
        "zero_optimization": {"stage": tr["zero_stage"]},
        "remat": tr["remat"],
        "steps_per_print": 10 ** 9,
    }, model, mesh=mesh, seed=cell.jax_seed())
    return cfg, engine, dp, micro * dp * gas


def check_against_reference(cell: Cell, engine, batch: dict, dp: int,
                            notes: list) -> bool:
    """The system's loss of a few sequences (``eval_batch``: the step's own
    forward and loss, bf16 with the kernels) against the plain float32
    reference on the engine's own master weights."""
    import jax

    n = max(int(cell.mix["check_sequences"]), dp)
    n += -n % dp                       # the batch axis must divide
    ids = batch["input_ids"][:n]
    got = float(engine.eval_batch({"input_ids": ids}))
    want = float(jax.block_until_ready(cell.reference.run_highest(
        cell.reference.loss, engine.state.master_params,
        jax.numpy.asarray(ids), n_head=cell.published["n_head"],
        eps=cell.published["layer_norm_epsilon"])))
    tol = float(cell.mix["loss_tolerance"])
    ok = bool(np.isfinite(got)) and abs(got - want) <= tol * abs(want)
    notes.append(f"loss of {n} sequences: system {got:.6f}, float32 "
                 f"reference {want:.6f}, relative difference "
                 f"{abs(got - want) / abs(want):.2e} "
                 f"({'within' if ok else 'OUTSIDE'} {tol:.0e})")
    return ok


def run(cell: Cell) -> Outcome:
    import jax

    mix = cell.mix
    notes: list = []
    cfg, engine, dp, rows = build_engine(cell)
    batches = train_batches(mix, cfg.vocab_size, cell.seed, rows)
    correct = check_against_reference(cell, engine, next(batches), dp, notes)
    program_bytes = 0
    first = next(batches)
    if not cell.rehearse:
        # memory_stats' peak left out the step's temporaries on this runtime
        # (PERF.md F6): the compiler's own figure for the step stands beside
        # it, and the larger is reported
        ma = engine.compile_train_step(first)
        program_bytes = int(ma.get("temp_size_in_bytes", 0)
                            + ma.get("argument_size_in_bytes", 0)
                            + ma.get("output_size_in_bytes", 0)
                            - ma.get("alias_size_in_bytes", 0))
    for _ in range(int(mix["warmup_steps"])):
        jax.block_until_ready(engine.train_batch(first)["loss"])
    mark = cell.watch.mark()
    settle_host()
    tail = TraceTail(cell)
    seq = int(mix["seq_len"])
    losses: list = []
    t0 = time.perf_counter()
    setup_s = t0 - cell.t_process
    while True:
        elapsed = time.perf_counter() - t0
        if elapsed >= cell.seconds:
            break
        tail.tick(elapsed)
        with span("collate"):
            batch = next(batches)
        with span("train_batch"):
            losses.append(engine.train_batch(batch)["loss"])
        if len(losses) > 1:
            with span("wait_previous_step"):
                jax.block_until_ready(losses[-2])
    with span("wait_last_step"):
        jax.block_until_ready(losses[-1])
    t_done = time.perf_counter()
    tail.stop()
    built = cell.watch.since(mark)
    losses = [float(x) for x in losses]
    steps = len(losses)
    finite = bool(np.all(np.isfinite(losses)))
    k = min(5, max(1, steps // 2))
    fell = steps >= 2 and np.mean(losses[-k:]) < np.mean(losses[:k])
    notes.append(f"{steps} steps of {rows} x {seq} tokens in "
                 f"{t_done - t0:.3f} s; loss {losses[0]:.4f} -> "
                 f"{losses[-1]:.4f}; in the window {built}")
    if built["programs_built"]:
        notes.append("INVALID: a program was compiled inside the window")
    if not finite:
        notes.append("INVALID: a loss is not finite")
    if not fell:
        notes.append("INVALID: the loss did not fall (mean of the last "
                     f"{k} steps against the first {k})")
    say(f"losses {[round(x, 4) for x in losses[:3]]} ... "
        f"{[round(x, 4) for x in losses[-3:]]}")
    return Outcome(
        correct=correct and finite and fell and not built["programs_built"],
        attempted=steps, failed=int(sum(not np.isfinite(x) for x in losses)),
        end_to_end={"train_tokens_per_s": steps * rows * seq / (t_done - t0)},
        setup_s=setup_s,
        facts={"seq_len": seq, "rows_per_chip": rows // (dp or 1)
               // int(mix["gradient_accumulation_steps"]),
               "program_bytes": program_bytes},
        notes=notes)
