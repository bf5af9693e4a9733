"""Checkpoint save/load.

TPU-native analog of the reference checkpoint stack (``engine.py:2653,2982``
+ pluggable ``runtime/checkpoint_engine/``): one *logical* checkpoint in a
sharded array store (orbax/tensorstore), written collectively by all hosts —
universal-by-construction. Where the reference writes per-(dp,tp,pp)-rank
shard files and needs an offline converter (``checkpoint/ds_to_universal.py``)
to reshape between topologies, here restore-onto-any-mesh is native: load
targets are specified as abstract (shape, sharding) and tensorstore reshards.

Layout per tag directory:
    <dir>/<tag>/state/...      sharded TrainState (master params, moments, step)
    <dir>/<tag>/meta.json      config + model metadata
    <dir>/<tag>/manifest.json  integrity manifest — the commit marker,
                               written LAST (resilience/integrity.py)
    <dir>/latest               tag pointer (same contract as the reference)

Commit protocol (crash-safe by ordering, chaos-tested): state → meta →
manifest → ``latest``. A death anywhere in between leaves ``latest`` at
the previous durable checkpoint, and load-time verification falls back
to the newest VERIFIED tag if the pointed-at one is torn.
"""

from __future__ import annotations

import json
import os
from pathlib import Path
from typing import Optional

import jax
import numpy as np
import orbax.checkpoint as ocp

from ...resilience import chaos
from ...resilience.guards import CheckpointIntegrityError
from ...resilience.integrity import (list_tags, newest_verified_tag,
                                     prune_tags, verify_tag, write_manifest)
from ...utils.logging import log_dist, warning_once


def _checkpointer(engine=None):
    """Sync or async checkpointer per ``config.checkpoint.async_save``
    (reference pluggable CheckpointEngine / Nebula async service): the async
    path initiates the tensorstore writes and returns — training resumes
    while the commit happens in background threads. One AsyncCheckpointer is
    cached per engine so in-flight saves can be awaited."""
    async_save = (engine is not None
                  and getattr(engine.config.checkpoint, "async_save", False))
    if not async_save:
        return ocp.PyTreeCheckpointer(), False
    ck = getattr(engine, "_async_ckptr", None)
    if ck is None:
        ck = ocp.AsyncCheckpointer(ocp.PyTreeCheckpointHandler())
        engine._async_ckptr = ck
    return ck, True


def _model_config_dict(model):
    """JSON-safe dump of the model's TransformerConfig (None if absent)."""
    import dataclasses

    cfg = getattr(model, "cfg", None)
    if cfg is None or not dataclasses.is_dataclass(cfg):
        return None
    out = {}
    for f in dataclasses.fields(cfg):
        v = getattr(cfg, f.name)
        if f.name == "dtype":
            v = getattr(v, "__name__", str(v))
        try:
            json.dumps(v)
        except (TypeError, ValueError):
            # custom-dataclass fields (callables, enums, ...) must never
            # break save_checkpoint itself; drop them from the meta dump
            continue
        out[f.name] = v
    return out


def _validate_tag(engine, tag: str) -> None:
    """Cross-process tag consistency (reference ``engine.py:2965``
    ``checkpoint_tag_validation``). Uses an allgather so EVERY rank sees the
    mismatch and fails/warns uniformly — a one-sided check would leave rank 0
    entering the collective save alone and hanging."""
    mode = engine.config.checkpoint.tag_validation
    if mode == "ignore" or jax.process_count() == 1:
        return
    import hashlib

    from jax.experimental import multihost_utils

    mine = int.from_bytes(hashlib.sha256(tag.encode()).digest()[:4], "big")
    all_hashes = np.asarray(multihost_utils.process_allgather(np.int64(mine)))
    if not np.all(all_hashes == all_hashes[0]):
        msg = (f"checkpoint tag {tag!r} differs across processes "
               "(hash mismatch) — ranks would write inconsistent checkpoints")
        if mode == "fail":
            raise ValueError(msg)
        log_dist(f"WARNING: {msg}")


def _commit_tag(engine, base: Path, tag: str) -> None:
    """The durable-commit epilogue, shared by the sync and async paths:
    write the manifest (the commit marker — LAST artifact inside the
    tag), flip ``latest``, prune old tags. Rank 0 only; the chaos kill
    points bracket exactly the window the crash-mid-commit test targets."""
    chaos.kill_point(chaos.KILL_AFTER_STATE_WRITE)
    if jax.process_index() == 0:
        level = getattr(engine.config.checkpoint, "verify", "size")
        write_manifest(base / tag, level,
                       extra={"global_steps": engine.global_steps})
        chaos.kill_point(chaos.KILL_BEFORE_LATEST_FLIP)
        (base / "latest").write_text(tag)
        keep = int(getattr(engine.config.checkpoint, "keep_last", 0) or 0)
        if keep:
            prune_tags(base, keep, protect={tag})


def wait_for_checkpoint(engine) -> None:
    """Block until any in-flight async save has committed, then write the
    manifest and flip the 'latest' pointer — so a crash mid-commit leaves
    'latest' at the previous DURABLE checkpoint, never at a half-written
    one, and every tag 'latest' ever names carries a commit marker."""
    ck = getattr(engine, "_async_ckptr", None)
    if ck is not None:
        ck.wait_until_finished()
    pending = getattr(engine, "_pending_latest", None)
    if pending is not None:
        base, tag = pending
        _commit_tag(engine, Path(base), tag)
        engine._pending_latest = None


def save_checkpoint(engine, save_dir: str, tag: str | None = None) -> str:
    tag = tag or f"global_step{engine.global_steps}"
    _validate_tag(engine, tag)
    base = Path(save_dir).absolute()
    path = base / tag
    ckptr, is_async = _checkpointer(engine)
    if is_async:
        wait_for_checkpoint(engine)   # one in-flight save at a time
    if getattr(engine, "offload", False):
        # host-resident state (ZeRO-Offload/Infinity): numpy trees
        m, v = engine.host_opt.moment_trees()
        state = {"master_params": engine.host_opt.master_tree(),
                 "mu": m, "count": np.int32(engine.host_opt.count)}
        if v is not None:
            state["nu"] = v
        ckptr.save(path / "state", state, force=True)
    else:
        ckptr.save(path / "state", engine.state, force=True)
    if jax.process_index() == 0:
        meta = {
            "tag": tag,
            "global_steps": engine.global_steps,
            "config": engine.config.to_dict(),
            "param_count": engine.param_count,
            "mesh": dict(engine.mesh.shape),
            # model architecture, when the model exposes a TransformerConfig:
            # lets the standalone dstpu_to_fp32 converter rebuild the HF
            # export without the engine (reference utils/zero_to_fp32.py,
            # which ships INSIDE every checkpoint for the same reason)
            "model_config": _model_config_dict(engine.model),
            # state layout on disk: "host" = offload engine's numpy trees,
            # "device" = TrainState. load_checkpoint converts across layouts
            # so offload <-> device restores work in both directions.
            "layout": "host" if getattr(engine, "offload", False) else "device",
        }
        ls = getattr(engine, "_offload_ls", None)
        if getattr(engine, "offload", False) and ls is not None:
            # host-side fp16 loss-scale state (bf16/fp32 runs carry the
            # inert scale=1 record — harmless, kept for layout uniformity)
            meta["offload_loss_scale"] = {
                "scale": float(ls.scale), "good_steps": int(ls.good_steps),
                "hysteresis": int(ls.hysteresis)}
        moq = getattr(engine, "_moq", None)
        if moq is not None:
            # the MoQ schedule lives outside the jitted state (bit width is
            # a static argument): resume must not restart QAT at start_bits
            meta["moq"] = {"bits": moq.bits, "initial_eig": moq.initial_eig,
                           "history": moq.history}
        (path / "meta.json").write_text(json.dumps(meta, indent=2))
    if is_async:
        # manifest + 'latest' flip only after the background commit is
        # durable (wait_for_checkpoint → _commit_tag)
        engine._pending_latest = (str(base), tag)
    else:
        _commit_tag(engine, base, tag)
    log_dist(f"saved checkpoint {path}"
             + (" (async, committing in background)" if is_async else ""),
             ranks=[0])
    return str(path)


def _resolve_verified_tag(engine, base: Path, tag: str | None) -> str:
    """Pick the tag to restore: ``latest`` (or the explicit ``tag``),
    verified against its manifest; on corruption fall back to the newest
    tag that DOES verify. Explicit tags never fall back silently —
    restoring a different checkpoint than the one the caller pinned would
    be worse than failing."""
    level = getattr(engine.config.checkpoint, "verify", "size")
    explicit = tag is not None
    if tag is None:
        latest = base / "latest"
        if latest.exists():
            tag = latest.read_text().strip()
        else:
            # no pointer (crash before the first flip, or manual surgery):
            # the newest verified tag is the best truth available
            tag = newest_verified_tag(base, level)
            if tag is None:
                raise FileNotFoundError(
                    f"no 'latest' tag file and no loadable tag in {base}")
            log_dist(f"load_checkpoint: no 'latest' pointer in {base}; "
                     f"using newest verified tag {tag!r}", ranks=[0],
                     level="WARNING")
    status, reason = verify_tag(base / tag, level)
    if status == "legacy":
        warning_once(f"checkpoint {tag!r} has no integrity manifest "
                     "(pre-resilience save?) — loading unverified; re-save "
                     "to get crash-safe commits")
    elif status == "corrupt":
        if explicit:
            raise CheckpointIntegrityError(
                f"checkpoint tag {tag!r} failed verification ({reason}); "
                "refusing to restore a pinned tag from torn bytes",
                tag=tag, reason=reason)
        fb = newest_verified_tag(base, level, exclude={tag})
        if fb is None:
            raise CheckpointIntegrityError(
                f"checkpoint {tag!r} failed verification ({reason}) and no "
                f"older verified tag exists in {base}", tag=tag,
                reason=reason)
        log_dist(f"load_checkpoint: tag {tag!r} failed verification "
                 f"({reason}) — falling back to newest verified tag {fb!r}",
                 ranks=[0], level="WARNING")
        tag = fb
    return tag


def load_checkpoint(engine, load_dir: str, tag: str | None = None) -> str:
    wait_for_checkpoint(engine)   # an in-flight save must commit first
    base = Path(load_dir).absolute()
    tag = _resolve_verified_tag(engine, base, tag)
    _validate_tag(engine, tag)
    if engine.config.checkpoint.load_universal:
        # universal-by-construction: every checkpoint already restores onto
        # any topology (abstract-target reshard); the flag is satisfied
        log_dist("load_universal: checkpoints reshard natively; no offline "
                 "conversion needed", ranks=[0])
    path = base / tag
    ckptr = ocp.PyTreeCheckpointer()
    meta_file = path / "meta.json"
    meta_pre = json.loads(meta_file.read_text()) if meta_file.exists() else {}
    layout = meta_pre.get("layout")
    to_host = getattr(engine, "offload", False)
    raw = None
    if layout is None:
        # pre-"layout" checkpoints: the store is OCDBT (no per-leaf dirs on
        # disk), so sniff the tree structure — the host layout alone has a
        # top-level optimizer step "count". Metadata reads no array data;
        # fall back to a full (unsharded) restore only if it's unavailable.
        try:
            keys = set(ckptr.metadata(path / "state").keys())
        except Exception:
            raw = ckptr.restore(path / "state")
            keys = set(raw)
        layout = "host" if "count" in keys else "device"

    def _host_trees():
        """(master, mu, nu, count) from either on-disk layout. The count is
        the *applied-update* count (fp16 overflow skips excluded) — Adam
        bias correction depends on it, so it must never be seeded from the
        every-batch ``step`` counter."""
        r = raw if raw is not None else ckptr.restore(path / "state")
        src = r if layout == "host" else r["opt_state"]
        return (r["master_params"], src.get("mu"), src.get("nu"),
                int(np.asarray(src["count"])))

    if to_host:
        # restore into the host optimizer (offload engine), whichever engine
        # kind wrote the checkpoint
        master, mu, nu, count = _host_trees()
        engine.host_opt.load_state(master, mu, nu, count=count)
        with engine.mesh:
            engine.compute_params = engine.host_opt.device_compute_params()
        ls_meta = meta_pre.get("offload_loss_scale")
        if ls_meta is not None and engine.config.fp16.enabled:
            import jax.numpy as jnp

            from ..loss_scaler import LossScaleState
            engine._offload_ls = LossScaleState(
                scale=jnp.float32(ls_meta["scale"]),
                good_steps=jnp.int32(ls_meta["good_steps"]),
                hysteresis=jnp.int32(ls_meta["hysteresis"]))
        step_guess = count
    elif layout == "host":
        # host optimizer trees -> device TrainState: rebuild the state pytree
        # around the stored master/moments, then shard onto this engine's
        # mesh (fresh loss-scale/residual slots — the host engine has none).
        master, mu, nu, count = _host_trees()
        state = engine.state
        opt_state = state.opt_state._replace(
            mu=jax.tree.map(lambda cur, new: np.asarray(new, cur.dtype),
                            state.opt_state.mu, mu),
            nu=(jax.tree.map(lambda cur, new: np.asarray(new, cur.dtype),
                             state.opt_state.nu, nu)
                if nu is not None else state.opt_state.nu),
            count=np.asarray(count, dtype=np.int32),
        )
        new_state = state._replace(
            step=np.asarray(count, dtype=np.int32),
            master_params=jax.tree.map(
                lambda cur, new: np.asarray(new, cur.dtype),
                state.master_params, master),
            opt_state=opt_state,
        )
        engine.state = jax.tree.map(
            lambda x, s: jax.device_put(x, s), new_state, engine.state_shardings)
        step_guess = count
    else:
        # Abstract target + explicit per-leaf restore_args carry this
        # engine's shardings: restoring onto a different mesh/topology
        # reshards transparently (elastic resume). restore_args is required —
        # without it orbax re-applies the *saved* topology's shardings from
        # the sharding file, and the train step then rejects the arrays.
        abstract = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            engine.state, engine.state_shardings)
        restore_args = jax.tree.map(
            lambda x, s: ocp.ArrayRestoreArgs(sharding=s, dtype=x.dtype),
            engine.state, engine.state_shardings)
        # The engine may want error-feedback residuals the checkpoint
        # can't supply: a pre-error-feedback int8 save (comm_err == {}),
        # an fp-mode save resumed under int8/onebit, or an elastic/
        # bucket-plan change that resized the flat residual vectors.
        # Probe the checkpoint's ACTUAL saved structure up front and
        # zero-init only on a genuine mismatch — catching restore
        # failures instead would zero valid residuals on a transient
        # error and mask unrelated corruption with the retry's traceback.
        want_err = getattr(engine.state, "comm_err", None) or None
        mismatch = False
        if want_err:
            want_shapes = {k: tuple(v.shape) for k, v in want_err.items()}
            saved = ckptr.metadata(path / "state").item_metadata.tree.get(
                "comm_err") or {}
            saved_shapes = {k: tuple(m.shape) for k, m in saved.items()}
            mismatch = saved_shapes != want_shapes
        if mismatch:
            restored = ckptr.restore(
                path / "state", item=abstract._replace(comm_err={}),
                restore_args=restore_args._replace(comm_err={}))
            restored = restored._replace(comm_err=engine.state.comm_err)
            log_dist("load_checkpoint: checkpoint comm_err residuals "
                     f"{saved_shapes or 'absent'} don't match this run's "
                     f"{want_shapes} (pre-error-feedback save, changed "
                     "bucket plan, or changed data world) — zero-"
                     "initialized; error feedback re-debiases from the "
                     "next step", ranks=[0])
        else:
            restored = ckptr.restore(path / "state", item=abstract,
                                     restore_args=restore_args)
        engine.state = restored
        step_guess = int(restored.step)
    engine.global_steps = int(meta_pre.get("global_steps", step_guess))
    moq_meta = meta_pre.get("moq")
    if getattr(engine, "_moq", None) is not None:
        if moq_meta:
            engine._moq.bits = int(moq_meta["bits"])
            engine._moq.initial_eig = moq_meta.get("initial_eig")
            engine._moq.history = [tuple(h)
                                   for h in moq_meta.get("history", [])]
        else:
            # no schedule in the checkpoint (pre-MoQ save): RESET to the
            # fresh state — keeping an already-narrowed in-process schedule
            # would silently diverge from a fresh-process resume of the
            # same checkpoint
            moq = engine._moq
            cfg_wq = engine.config.compression.weight_quantization
            moq.bits = int(cfg_wq.start_bits or cfg_wq.bits)
            moq.initial_eig = None
            moq.history = []
            log_dist("load_checkpoint: MoQ enabled but the checkpoint "
                     "carries no schedule (pre-MoQ save?) — QAT restarts "
                     f"at start_bits={moq.bits}", ranks=[0])
    # Re-baseline the non-finite sentinel: the restored state carries the
    # run's HISTORICAL skipped_steps total — without this, the first
    # report boundary after a resume would read all of history as one
    # fresh all-skipped window and halt a healthy run (and resume="auto"
    # would then halt every incarnation the same way).
    if hasattr(engine, "_skipped_total_prev") and not to_host:
        engine._skipped_total_prev = float(
            np.asarray(engine.state.skipped_steps))
    if hasattr(engine, "_bad_step_streak"):
        engine._bad_step_streak = 0
    log_dist(f"loaded checkpoint {path} (step {engine.global_steps})", ranks=[0])
    return str(path)


def auto_resume(engine, load_dir: str | None) -> Optional[str]:
    """``resilience.resume == "auto"``: restore the newest loadable
    checkpoint under ``load_dir`` if the directory holds any, else start
    fresh. Returns the restored path or None (fresh run). This is what
    makes a restart-loop incarnation (elasticity/agent.py) and a manual
    relaunch indistinguishable: both just construct the engine."""
    if not load_dir:
        raise ValueError(
            'resilience.resume == "auto" requires resilience.resume_dir '
            "(the directory save_checkpoint writes to)")
    base = Path(load_dir).absolute()
    if not base.is_dir() or not list_tags(base):
        log_dist(f"auto-resume: no checkpoints in {base} — fresh run",
                 ranks=[0])
        return None
    try:
        return load_checkpoint(engine, str(base))
    except FileNotFoundError as e:
        # tag dirs exist but none is committed (e.g. the FIRST save of the
        # run died mid-state-write): that's a fresh run, not an error —
        # there was never a durable checkpoint to lose
        log_dist(f"auto-resume: no committed checkpoint in {base} ({e}) — "
                 "fresh run", ranks=[0], level="WARNING")
        return None
