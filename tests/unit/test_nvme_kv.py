"""NVMe KV rung (serving/tiering.py NVMeKVTier + TieringEngine).

Oracles:

- round-trip: pages put into the disk rung come back bit-exact through
  match → consume (one flat CRC-checked file per block, dtypes and
  shapes reconstructed from in-RAM specs — bfloat16-safe);
- degradation: torn (short), corrupt (bit-rot), and lost (unlinked)
  files all fail verification at MATCH time — counted in
  ``fallbacks``, never an exception, never served;
- the hierarchy: a host tier over budget spills its LRU victims DOWN
  (verified first, counted) instead of dropping them; a match can span
  rungs and consume promotes each page from wherever it lives;
- engine-level: fp NVMe-restore serving output is bit-identical to
  prefill-recompute under TP=4 (the gather/scatter programs are
  sharding-transparent; the disk hop must not change bits);
- plumbing: config refuses an NVMe rung without the host tier above
  it; fleet ``kv_residency()`` rolls the rung up; the optimizer
  offload rides the same ``AIOFileStore`` seam.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _fake_clock import TickClock

import deepspeed_tpu as ds
from deepspeed_tpu.models import build_model, tiny_test
from deepspeed_tpu.ops.aio import AIOFileStore
from deepspeed_tpu.serving.hostkv import HostKVTier
from deepspeed_tpu.serving.tiering import NVMeKVTier, TieringEngine

PS = 8
P = 32
MAX_NEW = 8
M = 64
POOL = 1 + (P + MAX_NEW - 1 + PS - 1) // PS
EOS = 7
PAGE_NBYTES = 2 * 2 * PS * 64 * 4        # n_layer x (k,v) x PS x d_model x fp32


def _tiles(seed=0, nbytes=256):
    rng = np.random.default_rng(seed)
    return {"k": rng.integers(-4, 4, (nbytes // 2,)).astype(np.int8),
            "v": rng.standard_normal(nbytes // 8).astype(np.float32)}


def _mk_nvme(tmp, cap=1 << 20, page=4):
    return NVMeKVTier(cap, page_size=page, path=str(tmp),
                      clock=TickClock())


# --------------------------------------------------------- round-trip
def test_nvme_put_match_consume_roundtrip(tmp_path):
    tier = _mk_nvme(tmp_path)
    p = np.arange(12, dtype=np.int32)
    t1, t2 = _tiles(1), _tiles(2)
    tier.put(p[:4], t1)
    tier.put(p[:8], t2)
    tier.flush()
    # payloads live on disk, not in RAM, until a match verifies them
    assert all(e["tiles"] is None for e in tier.entries.values())
    assert all(tier.store.exists(tier._file(k)) for k in tier.entries)
    keys = tier.match(p, start_block=0)
    assert len(keys) == 2 and tier.fallbacks == 0
    tiles, nbytes, toks = tier.consume(keys)
    assert toks == 8
    np.testing.assert_array_equal(tiles["k"][:, 0], t1["k"])
    np.testing.assert_array_equal(tiles["k"][:, 1], t2["k"])
    np.testing.assert_array_equal(tiles["v"][:, 0], t1["v"])
    assert tiles["v"].dtype == np.float32
    assert tier.promotions == 2 and tier.read_bytes > 0
    # consumed entries dropped their files with them
    assert not any(os.scandir(tier.store.dir))
    tier.close()


def test_nvme_release_keeps_file_drops_staging(tmp_path):
    tier = _mk_nvme(tmp_path)
    p = np.arange(4, dtype=np.int32)
    tier.put(p, _tiles(3))
    keys = tier.match(p, start_block=0)
    ent = tier.entries[keys[0]]
    assert ent["tiles"] is not None          # verified: staged in RAM
    tier.release(keys)
    assert ent["tiles"] is None              # unfetched on release
    assert not ent["pinned"]
    assert tier.store.exists(tier._file(next(iter(tier.entries))))
    tier.close()


# -------------------------------------------------------- degradation
def test_torn_corrupt_and_lost_files_fall_back(tmp_path):
    tier = _mk_nvme(tmp_path)
    p = np.arange(12, dtype=np.int32)
    for n in (4, 8, 12):
        tier.put(p[:n], _tiles(n))
    tier.flush()
    keys = sorted(tier.entries)              # by prefix length
    f0, f1, f2 = (tier.store.path(tier._file(k)) for k in keys)
    with open(f0, "r+b") as f:               # torn: half the bytes
        f.truncate(os.path.getsize(f0) // 2)
    with open(f1, "r+b") as f:               # bit rot
        f.write(b"\x5a" * 16)
    tier.store.unlink(tier._file(keys[2]))   # lost
    assert tier.match_one(keys[0], p[:4], 4) == "corrupt"
    assert tier.match_one(keys[1], p[:8], 8) == "corrupt"
    assert tier.match_one(keys[2], p[:12], 12) == "corrupt"
    assert tier.fallbacks == 3
    # corrupt entries were evicted wholesale — nothing to serve twice
    assert not tier.entries and tier.bytes_used == 0
    assert tier.match(p, start_block=0) == []
    tier.close()


def test_write_error_degrades_to_absent(tmp_path):
    """A page whose file write failed (dir vanished) is ABSENT at match
    time, not a crash: the read-side CRC guard covers the write side
    too."""
    tier = _mk_nvme(tmp_path)
    p = np.arange(4, dtype=np.int32)
    tier.put(p, _tiles(5))
    tier.flush()
    tier.store.unlink(tier._file(next(iter(tier.entries))))
    assert tier.match(p, start_block=0) == []
    assert tier.fallbacks == 1
    tier.close()


# ---------------------------------------------------------- hierarchy
def test_host_prune_spills_down_and_consume_spans_rungs(tmp_path):
    host = HostKVTier(600, page_size=4, clock=TickClock())
    nvme = _mk_nvme(tmp_path, page=4)
    eng = TieringEngine([host, nvme])
    p = np.arange(16, dtype=np.int32)
    t1, t2, t3 = _tiles(1), _tiles(2), _tiles(3)
    eng.put(p[:4], t1)        # 256+128 B
    eng.put(p[:8], t2)
    eng.put(p[:12], t3)       # over 600 B: LRU spills DOWN, not away
    assert host.spills >= 1 and nvme.demotes >= 1
    assert host.prunes >= 1
    # the full prefix is still matchable — across rungs
    keys = eng.match(p, start_block=0)
    assert len(keys) == 3
    ranks = sorted({r for r, _k in keys})
    assert ranks == [0, 1], ranks            # genuinely mixed rungs
    tiles, nbytes, toks = eng.consume(keys)
    assert toks == 12
    np.testing.assert_array_equal(tiles["k"][:, 0], t1["k"])
    np.testing.assert_array_equal(tiles["k"][:, 2], t3["k"])
    assert nvme.promotions >= 1
    nvme.close()


def test_spill_chain_caps_at_the_bottom(tmp_path):
    """The bottom rung prunes into nothing (bounded disk): over ITS
    budget, victims drop."""
    host = HostKVTier(600, page_size=4, clock=TickClock())
    nvme = NVMeKVTier(600, page_size=4, path=str(tmp_path),
                      clock=TickClock())
    eng = TieringEngine([host, nvme])
    p = np.arange(32, dtype=np.int32)
    for n in range(4, 33, 4):
        eng.put(p[:n], _tiles(n))
    assert host.bytes_used <= 600 and nvme.bytes_used <= 600
    assert nvme.prunes >= 1                  # the chain terminates
    files = list(os.scandir(nvme.store.dir))
    assert len(files) == len(nvme.entries)   # pruned files unlinked
    nvme.close()


# ------------------------------------------------------ engine parity
def test_nvme_restore_parity_under_tensor_parallel(devices, tmp_path):
    """TP=4 x disk rung: a host tier too small for one request spills
    to NVMe; resumes promote disk→host→HBM — output bit-identical to
    the tierless engine AND the TP=1 NVMe run."""
    mcfg = tiny_test(max_seq=M, dtype=jnp.float32)
    model = build_model(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    base = {"dtype": "float32", "eos_token_id": EOS}

    def scfg(host):
        cfg = {"slots": 2, "max_len": M, "prefill_chunk": 16,
               "greedy": True, "page_size": PS, "pool_pages": POOL}
        if host:
            cfg.update(host_pool_bytes=3 * PAGE_NBYTES,
                       nvme_pool_bytes=64 << 20,
                       nvme_path=str(tmp_path))
        return cfg

    def cycle(srv, rounds=2):
        rng = np.random.default_rng(7)
        A, B = (rng.integers(0, 256, (P,)).astype(np.int32)
                for _ in range(2))
        toks = []
        for r in range(rounds):
            for prompt, sid, s in ((A, "sa", 1000), (B, "sb", 2000)):
                rid = srv.submit(prompt, MAX_NEW, seed=s + r,
                                 session_id=sid)
                for _ in range(200_000):
                    req = srv.pop_result(rid)
                    if req is not None:
                        toks.append(req.tokens)
                        break
                    srv.step()
                else:
                    raise RuntimeError("serving stuck")
        return toks

    e1 = ds.init_inference(model, params, dict(base))
    etp = ds.init_inference(model, params, {**base, "tensor_parallel": 4})
    o1 = cycle(ds.ServingEngine(e1, scfg(host=True)))
    stp = ds.ServingEngine(etp, scfg(host=True))
    otp = cycle(stp)
    ooff = cycle(ds.ServingEngine(etp, scfg(host=False)))
    assert o1 == otp == ooff
    ns = stp.nvmekv.snapshot()
    assert ns["promotions"] >= 1 and ns["fallbacks"] == 0, ns
    assert stp.hostkv.spills >= 1
    stp.nvmekv.close()


# ------------------------------------------------------------ config
def test_nvme_config_validation():
    from deepspeed_tpu.inference.config import ServingConfig

    with pytest.raises(ValueError, match="nvme_pool_bytes"):
        ServingConfig.from_any({"page_size": 8, "max_len": 64,
                                "prefill_chunk": 16,
                                "nvme_pool_bytes": 1 << 20})
    with pytest.raises(ValueError, match="nvme_pool_bytes"):
        ServingConfig.from_any({"page_size": 8, "max_len": 64,
                                "prefill_chunk": 16,
                                "host_pool_bytes": 1 << 20,
                                "nvme_pool_bytes": -1})
    cfg = ServingConfig.from_any({"page_size": 8, "max_len": 64,
                                  "prefill_chunk": 16,
                                  "host_pool_bytes": 1 << 20,
                                  "nvme_pool_bytes": 1 << 24,
                                  "nvme_path": "/tmp/x"})
    assert cfg.nvme_pool_bytes == 1 << 24 and cfg.nvme_path == "/tmp/x"


# ------------------------------------------------------------- fleet
def test_fleet_kv_residency_rolls_up_nvme(tmp_path):
    mcfg = tiny_test(max_seq=M, dtype=jnp.float32)
    model = build_model(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ds.init_inference(model, params,
                            {"dtype": "float32", "eos_token_id": EOS})
    from deepspeed_tpu.serving import FleetEngine

    fleet = FleetEngine(eng, {
        "slots": 2, "max_len": M, "prefill_chunk": 16, "greedy": True,
        "page_size": PS, "pool_pages": POOL,
        "host_pool_bytes": 3 * PAGE_NBYTES,
        "nvme_pool_bytes": 64 << 20, "nvme_path": str(tmp_path),
        "kvscope": {"dead_after_s": 3600.0}}, replicas=2)
    rng = np.random.default_rng(7)
    A = rng.integers(0, 256, (P,)).astype(np.int32)
    rid = fleet.submit(A, MAX_NEW, seed=1, session_id="sa")
    for _ in range(200_000):
        if fleet.pop_result(rid) is not None:
            break
        fleet.step()
    kv = fleet.kv_residency()
    for name, rep in kv["replicas"].items():
        assert "nvme_tier" in rep, (name, sorted(rep))
    for k in ("nvme_tier_promotions", "nvme_tier_bytes",
              "nvme_tier_fallbacks", "nvme_aio_errors"):
        assert k in kv["totals"], sorted(kv["totals"])
    fleet.close()


# ----------------------------------------------------------- offload
def test_offload_rides_the_same_seam(tmp_path):
    """runtime/offload.py's NVMe swap consumes AIOFileStore — the one
    pin/copy/verify discipline's transport — not a private aio copy."""
    from deepspeed_tpu.config.config import OffloadConfig
    from deepspeed_tpu.runtime.offload import HostOffloadOptimizer
    from deepspeed_tpu.runtime.optimizers import adam

    host_master = {"w": np.ones((8, 8), np.float32)}
    o = HostOffloadOptimizer(
        host_master, adam(),
        OffloadConfig(device="nvme", nvme_path=str(tmp_path),
                      buffer_count=2))
    assert isinstance(o.aio, AIOFileStore)
    assert o.nvme_dir == o.aio.dir
    assert o.nvme_dir.startswith(str(tmp_path))
    o.step({"w": np.full((8, 8), 0.1, np.float32)}, 0.01)
    assert o.aio.errors == 0
    # master + moments really swapped through the store's files
    assert any(f.name.endswith(".bin") for f in os.scandir(o.nvme_dir))
