"""Compressed data-parallel gradient sync: qgZ int8 + 1-bit error feedback.

Reference: ``runtime/comm/nccl.py:51`` (compressed_allreduce with worker/
server error feedback), ``runtime/comm/coalesced_collectives.py:31``
(quantized reduce-scatter), ``runtime/zero/config.py:268``
(zero_quantized_gradients). Checks: primitive accuracy vs exact mean,
engine convergence vs uncompressed, and compiled-HLO wire-bytes reduction.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu.comm.compressed import (chunk_elems, int8_allreduce_mean,
                                           int8_psum, onebit_allreduce_mean,
                                           plan_buckets,
                                           plan_comm_err_shapes,
                                           plan_wire_mbytes)
from deepspeed_tpu.comm.hlo_analysis import (collective_summary,
                                             collective_totals)
from deepspeed_tpu.models import build_model, tiny_test
from deepspeed_tpu.runtime.dataloader import DataLoader, random_token_dataset


def _mesh():
    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh

    return build_mesh(MeshSpec(data=8))


class TestPrimitives:
    def test_int8_close_to_exact_mean(self):
        mesh = _mesh()
        rng = np.random.default_rng(0)
        x = rng.normal(size=(8, 10_000)).astype(np.float32)

        fn = jax.jit(jax.shard_map(
            lambda v: int8_allreduce_mean(v[0], "data")[None],
            mesh=mesh, axis_names=frozenset({"data"}),
            in_specs=P("data"), out_specs=P("data"), check_vma=False))
        with mesh:
            out = np.asarray(fn(x))
        exact = x.mean(axis=0)
        for r in range(8):
            np.testing.assert_allclose(out[r], exact, atol=2e-2)

    def test_onebit_error_feedback_converges(self):
        """Feeding the SAME vector repeatedly with error feedback: the
        running average of decompressed outputs converges to the true mean
        (the unbiasing property of error feedback)."""
        mesh = _mesh()
        rng = np.random.default_rng(1)
        x = rng.normal(size=(8, 4096)).astype(np.float32)
        n = 4096
        per = chunk_elems(n, 8)

        def body(v, w, s):
            red, nw, ns = onebit_allreduce_mean(v[0], w[0], s[0], "data")
            return red[None], nw[None], ns[None]

        fn = jax.jit(jax.shard_map(
            body, mesh=mesh, axis_names=frozenset({"data"}),
            in_specs=(P("data"), P("data"), P("data")),
            out_specs=(P("data"), P("data"), P("data")), check_vma=False))
        w = np.zeros((8, per * 8), np.float32)
        s = np.zeros((8, per), np.float32)
        acc = np.zeros(n, np.float32)
        exact = x.mean(axis=0)
        corrs = []
        with mesh:
            for i in range(30):
                red, w, s = fn(x, w, s)
                acc += np.asarray(red)[0]
                corrs.append(np.corrcoef(acc / (i + 1), exact)[0, 1])
        # error feedback debiases over steps: correlation with the exact
        # mean climbs monotonically-ish and ends strong
        assert corrs[-1] > 0.97, corrs[-1]
        assert corrs[-1] > corrs[4] > corrs[0]
        assert np.mean(np.abs(acc / 30 - exact)) < 0.3


def _engine(mode=None, zero=None, lr=2e-3, overlap=False, bucket=0,
            stage=2):
    cfg = {
        "train_batch_size": 8,
        "optimizer": {"type": "adamw", "params": {"lr": lr}},
        "zero_optimization": {"stage": stage, **(zero or {})},
        "mesh": {"data": 8},
        "seed": 3,
    }
    if mode:
        cfg["gradient_compression"] = {"enabled": True, "type": mode,
                                       "overlap": overlap,
                                       "bucket_elems": bucket}
    return ds.initialize(cfg, build_model(tiny_test()))


def _batch(n=8):
    data = random_token_dataset(n, 32, 256, learnable=True)
    return DataLoader(data, local_batch_size=n,
                      shuffle=False).collate_fn(data[:n])


class TestEngine:
    def test_convergence_matches_uncompressed(self):
        b = _batch()
        ref = _engine(None)
        ref_losses = [float(ref.train_batch(b)["loss"]) for _ in range(6)]
        for mode in ("int8", "onebit"):
            eng = _engine(mode)
            losses = [float(eng.train_batch(b)["loss"]) for _ in range(6)]
            assert losses[-1] < losses[0], (mode, losses)
            # within a loose band of the exact-gradient trajectory
            assert abs(losses[-1] - ref_losses[-1]) < 0.35, (mode, losses,
                                                             ref_losses)

    def test_qgz_knob_enables_int8(self):
        eng = _engine(None, zero={"zero_quantized_gradients": True})
        assert eng.grad_comp == "int8"
        m = eng.train_batch(_batch())
        assert np.isfinite(m["loss"])

    def test_wire_bytes_drop(self):
        """Compiled-step collective payload must shrink under compression."""
        b = _batch()
        ref, comp = _engine(None), _engine("onebit")
        gref = ref._make_global(b)
        gcmp = comp._make_global(b)
        with ref.mesh:
            href = ref._train_step.lower(ref.state, gref).compile().as_text()
        with comp.mesh:
            hcmp = comp._train_step.lower(comp.state, gcmp).compile().as_text()
        sref, scmp = collective_summary(href), collective_summary(hcmp)
        # the uncompressed grad sync all-reduces fp32 grads; the compressed
        # one moves u8 bitmaps through all-to-all/all-gather
        ar_ref = sref.get("all-reduce", {"mbytes": 0})["mbytes"]
        ar_cmp = scmp.get("all-reduce", {"mbytes": 0})["mbytes"]
        assert ar_cmp < ar_ref, (sref, scmp)
        assert "u8[" in hcmp  # packed sign bitmaps on the wire

    def test_zero3_requires_hpz(self):
        import pytest

        with pytest.raises(ValueError, match="hpz"):
            _engine("int8", zero={"stage": 3})

    def test_fast_axes_compose_with_compressed_grads(self):
        """A model sub-axis under the manual-'data' compressed grad
        shard_map (a manual subgroup for the SPMD partitioner) inits and
        steps."""
        cfg = {
            "train_batch_size": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2},
            "gradient_compression": {"enabled": True, "type": "int8"},
            "mesh": {"data": 4, "model": 2},
            "seed": 3,
        }
        eng = ds.initialize(cfg, build_model(tiny_test()))
        assert np.isfinite(float(eng.train_batch(_batch())["loss"]))


class TestBucketing:
    """Bucketed backward-overlap grad reduction (comm/compressed.py
    plan_buckets + bucketed_grad_reduce, engine gradient_compression
    overlap/bucket_elems)."""

    def test_plan_layer_aligned_segments(self):
        # a stacked (L, ...) leaf splits into L per-layer segments; an
        # unstacked leaf is one segment
        plan = plan_buckets([(4, 8, 8), (16,)],
                            [True, False], bucket_elems=100)
        assert plan.seg_sizes == (64, 64, 64, 64, 16)
        # one 64-elem layer per bucket until the tail, which packs the
        # last layer + the small unstacked leaf (64 + 16 <= 100)
        assert plan.buckets == ((0, 1), (1, 2), (2, 3), (3, 5))

    def test_plan_tree_smaller_than_one_bucket(self):
        plan = plan_buckets([(4, 8, 8), (16,)], [True, False],
                            bucket_elems=10_000)
        assert plan.buckets == ((0, 5),)
        assert plan.bucket_elems() == [4 * 64 + 16]

    def test_plan_uneven_last_bucket(self):
        plan = plan_buckets([(4, 8, 8), (16,)], [True, False],
                            bucket_elems=128)
        assert plan.buckets == ((0, 2), (2, 4), (4, 5))
        assert plan.bucket_elems() == [128, 128, 16]

    def test_plan_zero_is_fused(self):
        plan = plan_buckets([(4, 8, 8), (16,)], [True, False], 0)
        assert plan.buckets == ((0, 5),)

    def test_comm_err_shapes_match_fused_for_one_bucket(self):
        # single-bucket plan residual shapes == the historical flat
        # onebit shapes (checkpoint-state compatibility when overlap is
        # off)
        from deepspeed_tpu.runtime.onebit import comm_err_shapes

        n = 4 * 64 + 16
        plan = plan_buckets([(4, 8, 8), (16,)], [True, False], 0)
        assert plan_comm_err_shapes(plan, 8) == comm_err_shapes(n, 8)

    def test_fp_overlap_bit_identical_to_fused(self):
        """The parity oracle: bucketed fp (overlap) grads/params are
        BITWISE identical to the fused flat fp collective — the
        reduction is elementwise, so chunking cannot change a single
        bit."""
        b = _batch()
        fused = _engine("fp")
        bucketed = _engine("fp", overlap=True, bucket=2000)
        assert len(bucketed._grad_plan.buckets) > 1, \
            bucketed._grad_plan.buckets
        lf = [float(fused.train_batch(b)["loss"]) for _ in range(4)]
        lb = [float(bucketed.train_batch(b)["loss"]) for _ in range(4)]
        assert lf == lb, (lf, lb)
        for a, c in zip(jax.tree.leaves(fused.state.master_params),
                        jax.tree.leaves(bucketed.state.master_params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))

    def test_int8_overlap_converges_with_residuals(self):
        b = _batch()
        eng = _engine("int8", overlap=True, bucket=2000)
        assert set(eng._comm_err_shapes) == {"worker", "server"}
        losses = [float(eng.train_batch(b)["loss"]) for _ in range(6)]
        assert losses[-1] < losses[0], losses
        # residuals are carried (nonzero after a step) — int8 no longer
        # silently drops its quantization error
        w = np.asarray(eng.state.comm_err["worker"])
        assert float(np.abs(w).max()) > 0.0

    def test_onebit_overlap_converges(self):
        b = _batch()
        eng = _engine("onebit", overlap=True, bucket=2000)
        losses = [float(eng.train_batch(b)["loss"]) for _ in range(6)]
        assert losses[-1] < losses[0], losses

    def test_wire_summary_math(self):
        # Padding-negligible plan: 4 layers x 1Mi elems, 2Mi buckets —
        # every chunk lands exactly on the world*block quantum.
        plan = plan_buckets([(4, 1024, 1024)], [True], 2 * 1024 * 1024)
        w = plan_wire_mbytes(plan, 8, "int8")
        # int8 two-hop payload ≈ 2 bytes/elem vs 4 fp32 → ratio ~0.5
        # plus the scale planes
        assert 0.4 < w["wire_ratio"] < 0.6, w
        assert w["buckets"] == 2
        wf = plan_wire_mbytes(plan, 8, "fp")
        assert wf["wire_ratio"] == 1.0
        wb = plan_wire_mbytes(plan, 8, "onebit")
        assert wb["wire_ratio"] < w["wire_ratio"]

    def test_wire_summary_degenerate_padding_reported(self):
        """Tiny buckets near the world*block padding quantum: quantized
        padding can cost MORE wire than the fused fp32 baseline — the
        summary reports the over-unity ratio honestly (the engine clamps
        bucket_elems to the quantum so real plans never sit here)."""
        plan = plan_buckets([(4, 8, 8), (16,)], [True, False], 128)
        assert plan_wire_mbytes(plan, 8, "int8")["wire_ratio"] > 1.0
        # fp reduces each bucket with a plain unpadded pmean — exactly
        # the baseline's bytes regardless of how the plan slices it
        assert plan_wire_mbytes(plan, 8, "fp")["wire_ratio"] == 1.0
        fused = plan_buckets([(4, 8, 8), (16,)], [True, False], 0)
        assert plan_wire_mbytes(fused, 8, "fp")["wire_ratio"] == 1.0


class TestInt8ErrorFeedback:
    def test_residuals_debias_repeated_vector(self):
        """Feeding the SAME vector with EF: the running average of
        outputs converges to the exact mean (the unbiasing property the
        int8 path gains); without EF the bias persists forever."""
        mesh = _mesh()
        rng = np.random.default_rng(2)
        x = rng.normal(size=(8, 4096)).astype(np.float32)
        per = chunk_elems(4096, 8)

        def body(v, w, s):
            red, nw, ns = int8_allreduce_mean(
                v[0], "data", worker_err=w[0], server_err=s[0])
            return red[None], nw[None], ns[None]

        fn = jax.jit(jax.shard_map(
            body, mesh=mesh, axis_names=frozenset({"data"}),
            in_specs=(P("data"), P("data"), P("data")),
            out_specs=(P("data"), P("data"), P("data")), check_vma=False))
        fn0 = jax.jit(jax.shard_map(
            lambda v: int8_allreduce_mean(v[0], "data")[None],
            mesh=mesh, axis_names=frozenset({"data"}),
            in_specs=P("data"), out_specs=P("data"), check_vma=False))
        w = np.zeros((8, per * 8), np.float32)
        s = np.zeros((8, per), np.float32)
        exact = x.mean(axis=0)
        acc = np.zeros(4096, np.float64)
        with mesh:
            base = np.asarray(fn0(x))[0]
            for i in range(16):
                red, w, s = fn(x, w, s)
                acc += np.asarray(red)[0]
        ef_err = float(np.mean(np.abs(acc / 16 - exact)))
        raw_err = float(np.mean(np.abs(base - exact)))
        # the EF running mean beats the one-shot (biased) quantization
        assert ef_err < raw_err * 0.5, (ef_err, raw_err)

    def test_residuals_are_unscale_aware(self):
        """Residuals are stored in TRUE gradient units: under fp16
        dynamic loss scaling the scale is divided out before compression
        (the fused path's discipline, kept per bucket), so the carried
        residual magnitudes are independent of the loss scale."""
        import deepspeed_tpu as _ds

        def run(power):
            cfg = {
                "train_batch_size": 8,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 2},
                "gradient_compression": {"enabled": True, "type": "int8",
                                         "overlap": True,
                                         "bucket_elems": 2000},
                "fp16": {"enabled": True, "initial_scale_power": power},
                "mesh": {"data": 8}, "seed": 3,
            }
            eng = _ds.initialize(cfg, build_model(
                tiny_test(dtype=jnp.float16)))
            m = eng.train_batch(_batch())
            assert m["skipped"] == 0, m
            return float(np.abs(np.asarray(
                eng.state.comm_err["worker"])).max())

        r4, r8 = run(4), run(8)
        # a 16x loss-scale change must not scale the residuals 16x
        assert r4 > 0 and r8 > 0
        assert 0.5 < r4 / r8 < 2.0, (r4, r8)


class TestCommErrCheckpoint:
    """Restoring error-feedback residuals across checkpoints: matching
    shapes round-trip bitwise; a checkpoint that can't supply this run's
    residuals (pre-error-feedback int8 save, fp-mode save resumed under
    int8, resized bucket plan) zero-inits them and restores the rest —
    detected from the checkpoint's saved structure, never by catching
    the strict restore's failure."""

    def test_residuals_roundtrip_bitwise(self, tmp_path):
        b = _batch()
        eng = _engine("int8", overlap=True, bucket=2000)
        for _ in range(2):
            eng.train_batch(b)
        w0 = np.asarray(eng.state.comm_err["worker"])
        assert float(np.abs(w0).max()) > 0.0
        eng.save_checkpoint(str(tmp_path / "ck"))
        eng2 = _engine("int8", overlap=True, bucket=2000)
        eng2.load_checkpoint(str(tmp_path / "ck"))
        np.testing.assert_array_equal(
            w0, np.asarray(eng2.state.comm_err["worker"]))
        np.testing.assert_array_equal(
            np.asarray(eng.state.comm_err["server"]),
            np.asarray(eng2.state.comm_err["server"]))

    def test_residualless_checkpoint_zero_inits(self, tmp_path):
        b = _batch()
        eng = _engine("fp")          # comm_err == {} on disk
        for _ in range(2):
            eng.train_batch(b)
        eng.save_checkpoint(str(tmp_path / "ck"))
        eng2 = _engine("int8", overlap=True, bucket=2000)
        eng2.load_checkpoint(str(tmp_path / "ck"))
        assert eng2.global_steps == 2
        for k in ("worker", "server"):
            assert float(np.abs(
                np.asarray(eng2.state.comm_err[k])).max()) == 0.0
        # everything else restored: continue training from the loaded step
        for a, c in zip(jax.tree.leaves(eng.state.master_params),
                        jax.tree.leaves(eng2.state.master_params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


class TestQuantizedByteCensus:
    """comm.hlo_analysis must report quantized collectives' TRUE bytes —
    int8 payload + fp32 scale plane — so the census/ledger byte join
    stays exact when the wire dtype changes."""

    def test_hand_hlo_int8_plus_scale_bytes(self):
        hlo = """
ENTRY main {
  %q = (s8[2,16,2048]{2,1,0}, s8[2,16,2048]{2,1,0}) all-to-all(%a, %b)
  %s = (f32[2,16,1]{2,1,0}, f32[2,16,1]{2,1,0}) all-to-all(%c, %d)
  %qg = s8[8,16,2048]{2,1,0} all-gather(%e), dimensions={0}
  %sg = f32[8,16,1]{2,1,0} all-gather(%f), dimensions={0}
}
"""
        t = collective_totals(hlo)
        a2a = t["by_kind"]["all-to-all"]
        ag = t["by_kind"]["all-gather"]
        # variadic tuples SUM members: 2x s8 payloads + 2x f32 scales
        assert a2a["mbytes"] == (2 * 2 * 16 * 2048 * 1
                                 + 2 * 2 * 16 * 1 * 4) / 1e6
        assert ag["mbytes"] == (8 * 16 * 2048 * 1 + 8 * 16 * 1 * 4) / 1e6
        assert a2a["count"] == 2 and ag["count"] == 2

    def test_compiled_int8_wire_matches_plan(self):
        """The compiled int8 train step's a2a + gather payload equals the
        plan's static wire summary (stage 0: the grad path is the only
        a2a/all-gather in the program)."""
        b = _batch()
        eng = _engine("int8", stage=0, overlap=True, bucket=4000)
        g = eng._make_global(b)
        with eng.mesh:
            hlo = eng._train_step.lower(eng.state, g).compile().as_text()
        summ = collective_summary(hlo)
        got = sum(summ.get(k, {"mbytes": 0.0})["mbytes"]
                  for k in ("all-to-all", "all-gather"))
        want = eng.grad_comm_summary()["wire_mbytes_per_step"]
        assert abs(got - want) <= 0.02 * want, (got, want, summ)
        assert "s8[" in hlo


class TestCapacityLever:
    """The quantized_collectives lever's achieved-vs-projected contract
    (observability/capacity.py): achieved block beside the projection,
    score = the REMAINING measured exposed fraction, self-demoting, 0
    with the reason stated when unmeasured."""

    @staticmethod
    def _lever(commscope):
        from deepspeed_tpu.observability.capacity import capacity_report

        rep = capacity_report(ledger={}, commscope=commscope)
        return {d["name"]: d for d in rep["advisor"]["levers"]}[
            "quantized_collectives"]

    def test_achieved_with_remaining_exposed(self):
        lv = self._lever({
            "anatomy": {"exposed_comm_frac": 0.12, "overlap_frac": 0.6},
            "ledger": {"by_kind": {"all-to-all": {"busbw_gbps": 40.0}}},
            "quantized": {"active": True, "mode": "int8", "overlap": True,
                          "buckets": 4, "wire_ratio": 0.5,
                          "wire_mbytes_per_step": 1.0,
                          "fp32_equivalent_mbytes": 2.0}})
        assert lv["score"] == 0.12          # the REMAINING exposed wall
        ach = lv["estimate"]["achieved"]
        assert ach["mode"] == "int8" and ach["wire_ratio"] == 0.5
        assert "ACTIVE" in lv["why"]

    def test_self_demotes_to_zero_exposed(self):
        lv = self._lever({
            "anatomy": {"exposed_comm_frac": 0.0},
            "quantized": {"active": True, "mode": "int8",
                          "wire_ratio": 0.5}})
        assert lv["score"] == 0.0           # overlap absorbed the wall

    def test_active_but_unmeasured_scores_zero_with_reason(self):
        lv = self._lever({
            "anatomy": {"exposed_comm_frac": None},
            "quantized": {"active": True, "mode": "int8",
                          "wire_ratio": 0.5}})
        assert lv["score"] == 0.0
        assert "unmeasured" in lv["why"]
        assert lv["estimate"]["achieved"]["wire_ratio"] == 0.5

    def test_engine_observatory_carries_quantized_summary(self):
        import deepspeed_tpu as _ds

        eng = _ds.initialize({
            "train_batch_size": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2},
            "gradient_compression": {"enabled": True, "type": "int8",
                                     "overlap": True,
                                     "bucket_elems": 4000},
            "observability": {"commscope": {"enabled": True}},
            "mesh": {"data": 8}, "seed": 3,
        }, build_model(tiny_test()))
        eng.train_batch(_batch())
        rep = eng.comm_observatory(trace_source={"traceEvents": []})
        gq = rep["quantized"]
        assert gq["active"] and gq["mode"] == "int8" and gq["overlap"]
        assert gq["buckets"] > 1 and 0 < gq["wire_ratio"] < 1
        eng.close()


class TestInt8Psum:
    def test_close_to_exact_sum(self):
        mesh = _mesh()
        rng = np.random.default_rng(7)
        x = rng.normal(size=(8, 4, 96)).astype(np.float32)

        fn = jax.jit(jax.shard_map(
            lambda v: int8_psum(v[0], "data")[None],
            mesh=mesh, axis_names=frozenset({"data"}),
            in_specs=P("data"), out_specs=P("data"), check_vma=False))
        with mesh:
            out = np.asarray(fn(x))
        exact = x.sum(axis=0)
        scale = float(np.abs(exact).max())
        for r in range(8):
            np.testing.assert_allclose(out[r], exact,
                                       atol=0.05 * max(scale, 1.0))
