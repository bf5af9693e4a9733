"""Aux parity: env report, op registry, eigenvalue, tiled matmul, sparse
embedding grads, progressive layer drop, MoE generation
(reference env_report.py, op_builder registry, runtime/eigenvalue.py,
zero/tiling.py, sparse_tensor.py, progressive_layer_drop.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import build_model, mixtral, tiny_test
from deepspeed_tpu.runtime.dataloader import DataLoader, random_token_dataset


# -------------------------------------------------------------- env report
def test_env_report(capsys):
    from deepspeed_tpu.env_report import collect_report, main

    rep = collect_report()
    assert rep["devices"] >= 1 and rep["versions"]["jax"]
    assert "flash_attention" in rep["registered_ops"]
    main()
    out = capsys.readouterr().out
    assert "environment report" in out and "op compatibility" in out


def test_registry_resolves_real_ops():
    from deepspeed_tpu.platform.accelerator import get_accelerator

    builder = get_accelerator().create_op_builder("flash_attention")
    from deepspeed_tpu.ops.flash_attention import flash_attention

    assert builder() is flash_attention
    with pytest.raises(KeyError):
        get_accelerator().create_op_builder("nonexistent_op")


# -------------------------------------------------------------- eigenvalue
def test_power_iteration_quadratic():
    from deepspeed_tpu.utils.eigenvalue import max_eigenvalue

    diag = jnp.asarray([1.0, 3.0, 7.0])

    def loss(p):
        return 0.5 * jnp.sum(diag * p["x"] ** 2)

    eig, vec = max_eigenvalue(loss, {"x": jnp.asarray([1.0, 1.0, 1.0])},
                              iters=30)
    np.testing.assert_allclose(float(eig), 7.0, rtol=1e-3)
    v = np.abs(np.asarray(vec["x"]))
    assert v[2] > 0.99  # dominant direction


def test_layer_eigenvalues_ranks_model_layers():
    from deepspeed_tpu.utils.eigenvalue import layer_eigenvalues

    cfg = tiny_test(dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = {"input_ids": jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (2, 16)), jnp.int32)}
    eigs = layer_eigenvalues(lambda p: model.loss(p, batch), params, iters=4)
    assert eigs.shape == (cfg.n_layer,)
    assert np.all(np.isfinite(np.asarray(eigs)))


# ------------------------------------------------------------ tiled matmul
@pytest.mark.parametrize("n_tiles", [1, 2, 4])
def test_tiled_matmul_matches_dense(n_tiles):
    from deepspeed_tpu.ops.tiled import tiled_matmul

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((3, 5, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
    np.testing.assert_allclose(np.asarray(tiled_matmul(x, w, n_tiles)),
                               np.asarray(x @ w), rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError):
        tiled_matmul(x, w, 3)


# ----------------------------------------------------- sparse embed grads
def test_sparse_rows_roundtrip():
    from deepspeed_tpu.runtime.sparse_grads import (SparseRows, add_into,
                                                    compress_rows,
                                                    decompress_rows,
                                                    maybe_compress)

    dense = np.zeros((100, 8), np.float32)
    rows = [3, 17, 42]
    dense[rows] = np.random.default_rng(0).standard_normal((3, 8))
    sp = compress_rows(dense)
    assert sorted(sp.indices.tolist()) == rows
    assert sp.density == pytest.approx(0.03)
    np.testing.assert_array_equal(decompress_rows(sp), dense)
    acc = np.ones((100, 8), np.float32)
    add_into(acc, sp)
    np.testing.assert_allclose(acc, dense + 1.0)
    assert isinstance(maybe_compress(dense), SparseRows)
    full = np.ones((4, 2), np.float32)
    assert maybe_compress(full) is full          # dense stays dense


# --------------------------------------------------- progressive layer drop
def test_pld_trains_and_eval_runs_full_depth():
    engine = ds.initialize({
        "train_batch_size": 8,
        "optimizer": {"type": "adamw", "params": {"lr": 2e-3}},
        "progressive_layer_drop": {"enabled": True, "theta": 0.5,
                                   "gamma": 0.01},
    }, build_model(tiny_test(n_layer=4)))
    data = random_token_dataset(16, 32, 256, learnable=True)
    batch = DataLoader(data, local_batch_size=8, shuffle=False).collate_fn(data[:8])
    losses = [float(engine.train_batch(dict(batch))["loss"]) for _ in range(5)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    ev = engine.eval_batch(dict(batch))
    assert np.isfinite(ev)
    # eval path left the model in full-depth mode
    assert engine.model.pld_step is None


def test_pld_drop_actually_changes_output():
    from deepspeed_tpu.runtime.progressive_layer_drop import (
        convert_to_progressive_layer_drop)

    cfg = tiny_test(n_layer=4, dtype=jnp.float32)
    model = convert_to_progressive_layer_drop(build_model(cfg), theta=0.1,
                                              gamma=10.0)
    params = model.init(jax.random.PRNGKey(0))
    batch = {"input_ids": jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (2, 16)), jnp.int32)}
    model.set_pld_step(None)
    full = float(model.loss(params, batch))
    model.set_pld_step(jnp.int32(10 ** 6))   # theta ~ 0.1: heavy dropping
    dropped = float(model.loss(params, batch))
    assert np.isfinite(dropped) and abs(dropped - full) > 1e-6


# ----------------------------------------------------------- MoE generate
def test_moe_generate():
    """VERDICT gap: no test covered MoE generation (decode must route)."""
    from deepspeed_tpu.inference import init_inference

    cfg = mixtral("tiny", vocab_size=256, max_seq=64, dtype=jnp.float32)
    eng = init_inference(build_model(cfg), config={"dtype": "float32"})
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 8)),
                      jnp.int32)
    out = np.asarray(eng.generate(ids, 8, greedy=True))
    assert out.shape == (2, 8)
    assert np.all((out >= 0) & (out < 256))


# (the former PLD-under-pipeline rejection is lifted:
#  test_pld_composes_with_pipeline proves the composition trains)


def test_pld_no_tracer_leak():
    """Direct model.loss after train_batch must not see a leaked tracer."""
    engine = ds.initialize({
        "train_batch_size": 8,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "progressive_layer_drop": {"enabled": True},
    }, build_model(tiny_test(n_layer=4)))
    data = random_token_dataset(8, 32, 256)
    batch = DataLoader(data, local_batch_size=8, shuffle=False).collate_fn(data)
    engine.train_batch(dict(batch))
    assert engine.model.pld_step is None
    # direct loss call runs full-depth with no UnexpectedTracerError
    loss = float(engine.model.loss(
        jax.tree.map(lambda a: a.astype(jnp.float32),
                     engine.state.master_params),
        {"input_ids": jnp.asarray(batch["input_ids"])}))
    assert np.isfinite(loss)


def test_comm_bench_cli(capsys):
    """dstpu_bench sweep runs on the virtual mesh (ds_bench analog)."""
    from deepspeed_tpu.comm.bench import main as bench_main

    bench_main(["--min_elems", "4096", "--max_elems", "4096", "--iters", "2",
                "--ops", "all_reduce,all_to_all"])
    out = capsys.readouterr().out
    assert "all_reduce" in out and "all_to_all" in out and "GB/s" in out
    assert "done" in out


def test_profiler_trace_capture(tmp_path):
    """engine.start/stop_profile_trace writes an xplane trace (the
    nsys/NVTX-analog observability path, SURVEY §5)."""
    import os

    engine = ds.initialize({
        "train_batch_size": 8,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
    }, build_model(tiny_test()))
    data = random_token_dataset(8, 32, 256)
    batch = DataLoader(data, local_batch_size=8, shuffle=False).collate_fn(data)
    engine.train_batch(batch)          # compile outside the trace
    engine.start_profile_trace(str(tmp_path))
    engine.train_batch(batch)
    engine.stop_profile_trace()
    found = [os.path.join(r, f) for r, _, fs in os.walk(tmp_path) for f in fs]
    assert any("xplane" in f or f.endswith(".pb") or "trace" in f
               for f in found), found


def test_spatial_ops():
    """Spatial inference ops (reference csrc/spatial fused bias-add family)."""
    from deepspeed_tpu.ops.spatial import (bias_add, bias_add_add, bias_geglu,
                                           group_norm)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 4, 4, 8)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((8,)), jnp.float32)
    np.testing.assert_allclose(np.asarray(bias_add(x, b)), np.asarray(x) + np.asarray(b))
    np.testing.assert_allclose(np.asarray(bias_add_add(x, b, x)),
                               np.asarray(x) * 2 + np.asarray(b), rtol=1e-6)
    g = bias_geglu(jnp.concatenate([x, x], -1), jnp.concatenate([b, b]))
    assert g.shape == x.shape
    gn = group_norm(x, jnp.ones((8,)), jnp.zeros((8,)), num_groups=2)
    assert gn.shape == x.shape
    flat = np.asarray(gn).reshape(2, -1, 2, 4).transpose(0, 2, 1, 3).reshape(2, 2, -1)
    np.testing.assert_allclose(flat.mean(-1), 0.0, atol=1e-5)


# ------------------------------------------------- aio microbench (round 3)
def test_aio_bench_sweep(tmp_path):
    """Reference csrc/aio/py_test analog: the sweep must produce verified
    MB/s cells for every (threads, block, direct) combination."""
    from deepspeed_tpu.ops.aio_bench import run_sweep

    cells = run_sweep(str(tmp_path), 4 << 20, threads=[1, 2],
                      blocks=[256 << 10], direct_opts=[False])
    assert len(cells) == 2
    for c in cells:
        assert c["verified"] and c["read_mb_s"] > 0 and c["write_mb_s"] > 0


# --------------------------------------- multinode runner builders (round 3)
def test_multinode_command_builders():
    """SLURM/OpenMPI/MPICH lines (reference multinode_runner.py:108-366):
    correct starter, per-node fan-out flags, env export, node-rank source."""
    from collections import OrderedDict
    from types import SimpleNamespace

    import pytest as _pytest

    from deepspeed_tpu.launcher.multinode import (mpich_command,
                                                  openmpi_command,
                                                  slurm_command)
    from deepspeed_tpu.launcher.runner import _launch_cmd

    args = SimpleNamespace(script="train.py", script_args=["--x", "1"],
                           log_dir=None, module=False, slurm_partition=None)
    hosts = OrderedDict([("node1", [0, 1, 2, 3]), ("node2", [0, 1, 2, 3])])
    # comma-bearing value must survive (srun --export would split on it)
    env = OrderedDict([("LIBTPU_INIT_ARGS", "--xla_a=1,--xla_b=2")])

    s = slurm_command(args, hosts, "node1:1234", env, _launch_cmd)
    assert s[0] == "srun" and "--ntasks-per-node" in s
    inner = s[-1]
    assert "SLURM_NODEID" in inner
    assert "export LIBTPU_INIT_ARGS=--xla_a=1,--xla_b=2;" in inner

    o = openmpi_command(args, hosts, "node1:1234", env, _launch_cmd)
    assert o[0] == "mpirun" and "--host" in o
    assert "OMPI_COMM_WORLD_RANK" in o[-1]

    m = mpich_command(args, hosts, "node1:1234", env, _launch_cmd)
    assert m[0] == "mpiexec" and "-ppn" in m
    assert "PMI_RANK" in m[-1]

    # user args containing $ stay literal (shlex-quoted), placeholders don't
    args2 = SimpleNamespace(script="train.py", script_args=["--out", "run$v"],
                            log_dir=None, module=False, slurm_partition=None)
    s2 = slurm_command(args2, hosts, "node1:1234", env, _launch_cmd)
    assert "'run$v'" in s2[-1]

    # heterogeneous or slot-filtered allocations fail loudly
    with _pytest.raises(SystemExit):
        slurm_command(args, OrderedDict([("a", [0, 1]), ("b", [0])]),
                      "a:1", env, _launch_cmd)
    with _pytest.raises(SystemExit):
        slurm_command(args, OrderedDict([("a", [1, 2]), ("b", [1, 2])]),
                      "a:1", env, _launch_cmd)


# ------------------------------------- curriculum metric clusters (round 3)
def test_metric_index_build_save_load(tmp_path):
    from deepspeed_tpu.data_pipeline import MetricIndex, build_metric_index

    values = np.array([5, 1, 9, 3, 7, 1, 9, 2], dtype=np.int64)
    idx = build_metric_index(values=values, n_buckets=4,
                             path=str(tmp_path / "idx"))
    # eligible = exactly the samples with metric <= difficulty
    for difficulty in (0, 1, 3, 6, 9):
        got = sorted(idx.eligible(difficulty).tolist())
        want = sorted(np.nonzero(values <= difficulty)[0].tolist()) or [
            int(np.argmin(values))]
        assert got == want, (difficulty, got, want)
    # round-trips through the .npy files
    idx2 = MetricIndex.load(str(tmp_path / "idx"))
    np.testing.assert_array_equal(idx2.sorted_indices, idx.sorted_indices)
    np.testing.assert_array_equal(idx2.bounds, idx.bounds)


def test_curriculum_sampler_from_metric_index(tmp_path):
    """The sampler draws from precomputed cluster files without scoring the
    dataset (reference data_sampler.py:36 semantics)."""
    from deepspeed_tpu.data_pipeline import (CurriculumScheduler,
                                             CurriculumSampler,
                                             build_metric_index)

    lengths = np.array([4, 8, 16, 32, 4, 8, 16, 32])
    idx = build_metric_index(values=lengths, path=str(tmp_path / "idx"))

    class NoScore:
        def __len__(self):
            return 8

        def __getitem__(self, i):
            raise AssertionError("sampler must not score the dataset")

    sched = CurriculumScheduler(min_difficulty=4, max_difficulty=32,
                                schedule_type="fixed_linear",
                                total_curriculum_step=4, difficulty_step=4)
    sampler = CurriculumSampler(NoScore(), sched, metric_index=idx,
                                batch_size=4, shard_by_process=False)
    it = iter(sampler)
    picks, difficulty = next(it)
    assert difficulty < 32
    assert all(lengths[i] <= difficulty for i in picks), (picks, difficulty)
    for _ in range(5):
        picks, difficulty = next(it)
    assert difficulty == 32


def test_pld_composes_with_pipeline():
    """PLD + pipe (lifted exclusion): the stage-local scan recovers the
    GLOBAL layer index via lax.axis_index('pipe'), so the depth-scaled
    keep probability follows the paper's global rule. Train must run,
    converge, and actually drop (late-schedule loss differs from
    full-depth eval of the same params)."""
    from deepspeed_tpu.models import PipelinedTransformerLM

    model = PipelinedTransformerLM(tiny_test(n_layer=4, max_seq=32),
                                   n_stages=2, num_micro=4)
    engine = ds.initialize({
        "train_batch_size": 16,
        "train_micro_batch_size_per_gpu": 4,
        "optimizer": {"type": "adamw", "params": {"lr": 2e-3}},
        "mesh": {"pipe": 2, "data": 4},
        "progressive_layer_drop": {"enabled": True, "theta": 0.5,
                                   "gamma": 0.01},
    }, model)
    data = random_token_dataset(16, 32, 256, learnable=True)
    batch = DataLoader(data, local_batch_size=8,
                       shuffle=False).collate_fn(data[:8])
    losses = [float(engine.train_batch(dict(batch))["loss"])
              for _ in range(5)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert np.isfinite(engine.eval_batch(dict(batch)))
    assert engine.model.pld_step is None


def test_pld_global_offset_under_pipe_axis():
    """The global-depth wiring itself: under a bound pipe axis the offset
    is stage*L_local; without one it is 0. A regression to 0-under-pipe
    would silently turn PLD's depth rule per-stage (the bug the old
    engine exclusion guarded against)."""
    from functools import partial

    from jax.sharding import PartitionSpec as P
    from jax.experimental.shard_map import shard_map

    from deepspeed_tpu.platform.mesh import build_mesh, MeshSpec
    from deepspeed_tpu.runtime.progressive_layer_drop import (
        pipe_stage_layer_offset)

    mesh = build_mesh(MeshSpec(pipe=2, data=4))
    f = shard_map(lambda: pipe_stage_layer_offset(3)[None],
                  mesh=mesh, in_specs=(), out_specs=P("pipe"))
    offs = np.asarray(jax.jit(f)())
    np.testing.assert_allclose(sorted(offs), [0.0, 3.0])
    assert float(pipe_stage_layer_offset(3)) == 0.0   # no axis bound


def test_unbound_axis_raises_name_error():
    """JAX-pin test (jax==0.9.0): lax.axis_index on an unbound axis raises
    NameError — the exact type pipe_stage_layer_offset catches to detect
    the dense trunk. If a JAX upgrade changes this type, the narrow catch
    goes loud (good) but this test localizes the change immediately
    (see the CAUTION comment in progressive_layer_drop.py)."""
    from jax import lax

    with pytest.raises(NameError):
        jax.jit(lambda: lax.axis_index("pipe"))()


def test_pld_rejects_nonmanual_pipe_mesh():
    """PLD on the dense trunk under a pipe-sharded (non-manual) mesh must
    fail loud: axis_index('pipe') would be unbound, the stage offset would
    silently become 0, and the depth rule would regress to per-stage
    (advisor r3)."""
    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh
    from deepspeed_tpu.runtime.progressive_layer_drop import (
        convert_to_progressive_layer_drop)

    model = convert_to_progressive_layer_drop(
        build_model(tiny_test(n_layer=2, max_seq=32)))
    model.set_pld_step(jnp.float32(10.0))
    ids = jnp.zeros((4, 16), jnp.int32)
    with jax.set_mesh(build_mesh(MeshSpec(pipe=2, data=4))):
        with pytest.raises(ValueError, match="pipeline engine"):
            model.apply(model.init(jax.random.PRNGKey(0)), ids)


# ------------------------------------------------------------------ monitor
def test_monitor_csv_receives_throughput_events(tmp_path, monkeypatch):
    """Engine-wired monitor fan-out (reference monitor/monitor.py:29):
    at a steps_per_print boundary the csv backend receives loss/lr/
    samples_per_sec AND the utilization events (tflops, mfu) computed by
    the throughput timer. The peak table knows no CPU, so the test names
    one (the documented override) to exercise the MFU plumbing."""
    import csv as _csv

    monkeypatch.setenv("DSTPU_PEAK_FLOPS", "1e12")

    engine = ds.initialize({
        "train_batch_size": 8,
        "steps_per_print": 1,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "monitor": {"csv_monitor": {"enabled": True,
                                    "output_path": str(tmp_path)}},
    }, build_model(tiny_test(n_layer=2)))
    data = random_token_dataset(8, 32, 256)
    batch = DataLoader(data, local_batch_size=8, shuffle=False).collate_fn(data)
    for _ in range(2):
        engine.train_batch(dict(batch))
    names = {p.name for p in tmp_path.iterdir()}
    assert {"Train_loss.csv", "Train_lr.csv",
            "Train_samples_per_sec.csv"} <= names, names
    assert {"Train_tflops.csv", "Train_mfu.csv"} <= names, names
    with open(tmp_path / "Train_mfu.csv") as f:
        rows = list(_csv.reader(f))
    assert rows[0] == ["step", "Train/mfu"] and len(rows) >= 2
    assert 0.0 <= float(rows[1][1]) <= 1.0


# ------------------------------------------- sparse/tiled wiring (round 4)
def test_sparse_gradients_offload_matches_dense():
    """The sparse_gradients flag flips a REAL path (VERDICT r3 #8): on the
    offload engine, untied embedding grads leave the device as
    (indices, values) pairs — k·(d+1) floats instead of V·d — and training
    is numerically identical to the dense transfer."""
    from deepspeed_tpu.runtime.sparse_grads import SparseGradRows

    def run(sparse):
        cfg = {
            "train_batch_size": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 2e-3}},
            "zero_optimization": {
                "stage": 1, "offload_optimizer": {"device": "cpu"}},
            "sparse_gradients": sparse,
        }
        model = build_model(tiny_test(n_layer=2, vocab_size=1024,
                                      tie_embeddings=False, max_seq=16))
        engine = ds.initialize(cfg, model)
        data = random_token_dataset(16, 16, 1024, learnable=True)
        batch = DataLoader(data, local_batch_size=8,
                           shuffle=False).collate_fn(data[:8])
        losses = [float(engine.train_batch(dict(batch))["loss"])
                  for _ in range(3)]
        return engine, batch, losses

    eng_s, batch, sparse_losses = run(True)
    # the plan kicked in: 8*16=128 tokens < 1024/2 vocab rows
    assert eng_s._sparse_plan == {"tok_embed": 128}, eng_s._sparse_plan
    gbatch = {k: jnp.asarray(v)[None] for k, v in batch.items()}
    grads, _ = eng_s._grad_step(eng_s.compute_params, gbatch,
                                jnp.float32(1.0))
    sp = grads["tok_embed"]
    assert isinstance(sp, SparseGradRows)
    assert sp.values.shape == (128, 64) and sp.indices.shape == (128,)
    dense_bytes = 1024 * 64 * 4
    sparse_bytes = 128 * (64 + 1) * 4
    assert sparse_bytes < dense_bytes / 2   # the measured transfer saving

    _, _, dense_losses = run(False)
    np.testing.assert_allclose(sparse_losses, dense_losses, rtol=1e-4)


def test_sparse_gradients_refuses_tied_embeddings():
    """Tied tables also carry the (dense) unembedding softmax grad: the
    model must not offer them for row-sparse selection — silent top-k
    there would drop real gradient mass."""
    tied = build_model(tiny_test(tie_embeddings=True))
    untied = build_model(tiny_test(tie_embeddings=False))
    assert tied.sparse_grad_names() == ()
    assert untied.sparse_grad_names() == ("tok_embed",)

    engine = ds.initialize({
        "train_batch_size": 8,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1,
                              "offload_optimizer": {"device": "cpu"}},
        "sparse_gradients": True,
    }, tied)
    assert engine._sparse_plan == {}


def test_tiled_head_flag_matches_dense_head():
    """tiled_head=N computes the unembedding as a column-tile scan
    (ops/tiled.py; reference TiledLinear zero/tiling.py:32) with identical
    logits — the config flag now flips a real model path (VERDICT r3 #8)."""
    cfg_plain = tiny_test(n_layer=2, dtype=jnp.float32, fused_xent=False)
    cfg_tiled = tiny_test(n_layer=2, dtype=jnp.float32, fused_xent=False,
                          tiled_head=4)
    model_p, model_t = build_model(cfg_plain), build_model(cfg_tiled)
    params = model_p.init(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 16)),
                      jnp.int32)
    np.testing.assert_allclose(np.asarray(model_t.apply(params, ids)),
                               np.asarray(model_p.apply(params, ids)),
                               rtol=1e-5, atol=1e-5)
    # and the loss path trains through it
    engine = ds.initialize({
        "train_batch_size": 8,
        "optimizer": {"type": "adamw", "params": {"lr": 2e-3}},
    }, build_model(cfg_tiled))
    data = random_token_dataset(16, 16, 256, learnable=True)
    batch = DataLoader(data, local_batch_size=8,
                       shuffle=False).collate_fn(data[:8])
    losses = [float(engine.train_batch(dict(batch))["loss"])
              for _ in range(3)]
    assert losses[-1] < losses[0]


def test_comm_get_rank_both_modes():
    """deepspeed.comm.get_rank parity: host process index with no axis,
    shard index inside a shard_map body with one."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.comm import get_rank

    assert get_rank() == jax.process_index()
    from deepspeed_tpu.platform.mesh import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(data=8))
    out = jax.jit(jax.shard_map(lambda: get_rank("data")[None],
                                mesh=mesh, in_specs=(),
                                out_specs=P("data")))()
    np.testing.assert_array_equal(np.asarray(out), np.arange(8))
