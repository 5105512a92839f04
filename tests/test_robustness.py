"""PR-8 robustness: deterministic fault injection, checksummed
epoch-granular checkpoints, bitwise kill-and-resume, degraded serving.

1. :class:`FaultPlan` is a pure seeded schedule — same seed, same faults,
   including the corruption helpers' byte offsets.
2. ``save_pytree``/``load_pytree`` integrity: atomic writes leave no tmp
   droppings, a bit-flip raises :class:`CheckpointCorruptError` naming the
   offending entry, and template/archive key drift reports the FULL
   missing + unexpected sets in one :class:`CheckpointKeyError`.
3. :class:`RunCheckpointer`: last-K retention, manifest rebuild after a
   torn index write, and newest-valid fallback past corrupted archives.
4. Kill-and-resume parity (the tentpole contract): a run crashed by an
   injected fault at ANY epoch boundary and resumed from its checkpoint
   finishes with final params and val micro-F1 **bit-for-bit identical**
   to the uninterrupted run — f32 in-process here (phase-0 and phase-1
   crash points, halo cache on), fp64 in subprocesses for both the
   stacked and shard_map engines (``jax_enable_x64`` cannot leak).
5. Degraded serving: a failed partition's queries keep answering from its
   frozen store with staleness tags, updates touching its cone queue with
   bounded-backoff retry, and after recovery the FIFO replay reconverges
   bitwise against BOTH oracles (``refresh_full`` on the same engine and
   a fresh engine over ``apply_updates_to_graph``'s rebuilt graph).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from _jax_cache import CACHE_PRELUDE, subprocess_env

SUBPROC_ENV = subprocess_env()


# --------------------------------------------------------------------------
# 1. FaultPlan determinism
# --------------------------------------------------------------------------

def test_fault_plan_random_deterministic():
    from repro.robustness import FaultPlan

    kw = dict(num_parts=4, max_epochs=20, serve_ticks=10,
              serve_fail_prob=0.3)
    a = FaultPlan.random(3, **kw)
    b = FaultPlan.random(3, **kw)
    assert a.crash_epochs == b.crash_epochs
    assert a.straggler == b.straggler
    assert a.drop_refresh_epochs == b.drop_refresh_epochs
    assert a.serve_fail == b.serve_fail and a.serve_recover == b.serve_recover
    c = FaultPlan.random(4, **kw)
    assert (a.crash_epochs, a.straggler, a.drop_refresh_epochs) != \
           (c.crash_epochs, c.straggler, c.drop_refresh_epochs)


def test_fault_plan_straggler_vector_and_queries():
    from repro.robustness import FaultPlan

    plan = FaultPlan(crash_epochs=frozenset({2}),
                     straggler={1: {0: 0.5, 3: 1.5}},
                     drop_refresh_epochs=frozenset({4}),
                     serve_fail={2: (1,)}, serve_recover={5: (1,)})
    assert plan.crash_at(2) and not plan.crash_at(1)
    np.testing.assert_array_equal(plan.straggler_delay(1, 4),
                                  [0.5, 0.0, 0.0, 1.5])
    assert plan.straggler_delay(0, 4).sum() == 0.0
    assert plan.drop_halo_refresh(4) and not plan.drop_halo_refresh(3)
    assert plan.serve_events(2) == [("fail", 1)]
    assert plan.serve_events(5) == [("recover", 1)]
    assert plan.serve_events(3) == []


def test_fault_plan_corrupt_offsets_deterministic(tmp_path):
    from repro.robustness import FaultPlan

    payload = bytes(range(256)) * 40
    p1, p2 = tmp_path / "ck.npz", tmp_path / "same_name"
    os.mkdir(p2)
    p2 = p2 / "ck.npz"
    p1.write_bytes(payload)
    p2.write_bytes(payload)
    plan = FaultPlan(seed=9)
    info1 = plan.corrupt(str(p1))
    info2 = plan.corrupt(str(p2))
    assert info1 == info2                       # offset is seed+name+size pure
    assert p1.read_bytes() == p2.read_bytes() != payload
    tr = plan.corrupt(str(p1), mode="truncate")
    assert tr["kept_bytes"] < tr["orig_bytes"]
    assert os.path.getsize(p1) == tr["kept_bytes"]


# --------------------------------------------------------------------------
# 2. save_pytree / load_pytree integrity
# --------------------------------------------------------------------------

def _small_tree():
    return {"a": np.arange(12, dtype=np.float32).reshape(3, 4),
            "b": {"w": np.ones((2, 2), np.float64)}}


def test_save_pytree_atomic_no_tmp_left(tmp_path):
    from repro.train.checkpoint import load_pytree, save_pytree

    path = str(tmp_path / "t.npz")
    save_pytree(path, _small_tree())
    assert not [n for n in os.listdir(tmp_path) if ".tmp." in n]
    out = load_pytree(path, _small_tree())
    np.testing.assert_array_equal(out["a"], _small_tree()["a"])
    assert out["b"]["w"].dtype == np.float64


def test_crc_mismatch_names_offending_entry(tmp_path):
    from repro.train.checkpoint import (CheckpointCorruptError, load_pytree,
                                        save_pytree)

    path = str(tmp_path / "t.npz")
    save_pytree(path, _small_tree())
    mp = path + ".meta.json"
    with open(mp) as f:
        doc = json.load(f)
    doc["crc32"]["a"] ^= 1                      # silent-corruption model
    with open(mp, "w") as f:
        json.dump(doc, f)
    with pytest.raises(CheckpointCorruptError, match="entry 'a'.*crc32"):
        load_pytree(path, _small_tree())


def test_bitflipped_archive_raises_corrupt_error(tmp_path):
    import struct
    import zipfile

    from repro.robustness import flip_bit
    from repro.train.checkpoint import (CheckpointCorruptError, load_pytree,
                                        save_pytree)

    path = str(tmp_path / "t.npz")
    save_pytree(path, _small_tree())
    with zipfile.ZipFile(path) as z:            # locate entry 'a's payload
        zi = z.getinfo("a.npy")
    with open(path, "rb") as f:
        f.seek(zi.header_offset + 26)
        nlen, elen = struct.unpack("<HH", f.read(4))
    data_start = zi.header_offset + 30 + nlen + elen
    flip_bit(path, data_start + zi.file_size - 4)   # lands in array bytes
    with pytest.raises(CheckpointCorruptError, match="entry 'a'"):
        load_pytree(path, _small_tree())


def test_key_mismatch_reports_both_sets(tmp_path):
    from repro.train.checkpoint import (CheckpointKeyError, load_pytree,
                                        save_pytree)

    path = str(tmp_path / "t.npz")
    save_pytree(path, {"a": np.ones(2), "b": np.ones(2)})
    bad_template = {"b": np.ones(2), "c": np.ones(2)}
    with pytest.raises(CheckpointKeyError) as ei:
        load_pytree(path, bad_template)
    msg = str(ei.value)
    assert "missing" in msg and "'c'" in msg     # template wants, archive lacks
    assert "unexpected" in msg and "'a'" in msg  # archive has, template lacks


# --------------------------------------------------------------------------
# 3. RunCheckpointer retention / fallback
# --------------------------------------------------------------------------

def _run_ck(tmp_path, **kw):
    from repro.robustness import RunCheckpointer

    return RunCheckpointer(str(tmp_path / "ck"), **kw)


def _arrays(step):
    return {"p": np.full((3,), float(step)), "o": np.arange(4) + step}


def test_run_checkpointer_retention(tmp_path):
    ck = _run_ck(tmp_path, keep_last=3)
    for s in range(1, 6):
        ck.save(s, _arrays(s), {"epoch": s})
    assert ck.steps() == [3, 4, 5]
    assert ck.latest_step() == 5
    on_disk = sorted(n for n in os.listdir(ck.dir) if n.endswith(".npz"))
    assert on_disk == ["ckpt_000003.npz", "ckpt_000004.npz",
                       "ckpt_000005.npz"]
    assert ck.peek(4) == {"epoch": 4}
    arrays, host = ck.load(4, _arrays(0))
    assert host == {"epoch": 4}
    np.testing.assert_array_equal(arrays["p"], [4.0, 4.0, 4.0])


def test_run_checkpointer_falls_back_past_corruption(tmp_path):
    from repro.robustness import FaultPlan
    from repro.train.checkpoint import CheckpointCorruptError

    ck = _run_ck(tmp_path, keep_last=3)
    for s in range(1, 4):
        ck.save(s, _arrays(s), {"epoch": s})
    FaultPlan(seed=2).corrupt(ck._npz(3))        # newest archive damaged
    arrays, host, step = ck.load_latest(lambda h: _arrays(0))
    assert step == 2 and host == {"epoch": 2}
    np.testing.assert_array_equal(arrays["p"], [2.0, 2.0, 2.0])
    for s in (1, 2):                             # now everything is corrupt
        from repro.robustness import truncate_file
        truncate_file(ck._npz(s), 0.3)
    with pytest.raises(CheckpointCorruptError, match="no valid checkpoint"):
        ck.load_latest(lambda h: _arrays(0))


def test_run_checkpointer_rebuilds_torn_manifest(tmp_path):
    ck = _run_ck(tmp_path, keep_last=5)
    for s in (1, 2):
        ck.save(s, _arrays(s), {"epoch": s})
    with open(ck._manifest_path(), "w") as f:
        f.write('{"steps": [1, 2')                # torn mid-write
    assert ck.steps() == [1, 2]                   # rebuilt from the archives
    _, host, step = ck.load_latest(lambda h: _arrays(0))
    assert step == 2


def test_load_latest_empty_dir_returns_none(tmp_path):
    assert _run_ck(tmp_path).load_latest(lambda h: _arrays(0)) is None


# --------------------------------------------------------------------------
# 4a. f32 in-process kill-and-resume parity (stacked, halo cache on)
# --------------------------------------------------------------------------

_PIPE_KW = dict(dataset="tiny", num_parts=4, batch_size=32, hidden_dim=16,
                fanouts=(3, 3), max_epochs=6, phase0_fraction=0.5, seed=7,
                engine_mode="stacked", halo_cache=True, halo_refresh_every=2)


@pytest.fixture(scope="module")
def baseline_run():
    from repro.pipeline import EATConfig, run_eat_distgnn

    return run_eat_distgnn(EATConfig(**_PIPE_KW))


def _tree_equal(a, b):
    import jax

    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    return ta == tb and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def _crash_and_resume(tmp_path, crash_epoch, baseline):
    from repro.pipeline import EATConfig, run_eat_distgnn
    from repro.robustness import FaultPlan, InjectedCrash

    ck = str(tmp_path / "ck")
    with pytest.raises(InjectedCrash):
        run_eat_distgnn(EATConfig(**_PIPE_KW, checkpoint_dir=ck),
                        fault_plan=FaultPlan(
                            crash_epochs=frozenset({crash_epoch})))
    res = run_eat_distgnn(EATConfig(**_PIPE_KW, checkpoint_dir=ck,
                                    resume=True))
    assert res.resumed_from_epoch == crash_epoch
    assert _tree_equal(res.final_params, baseline.final_params), \
        "resumed final params are not bitwise the uninterrupted run's"
    assert res.f1.micro == baseline.f1.micro
    assert res.val_history == baseline.val_history
    assert res.loss_history == baseline.loss_history


def test_resume_parity_phase0_crash(tmp_path, baseline_run):
    _crash_and_resume(tmp_path, 1, baseline_run)


def test_resume_parity_phase1_crash(tmp_path, baseline_run):
    _crash_and_resume(tmp_path, 4, baseline_run)


def test_straggler_and_dropped_refresh_leave_numerics_alone(baseline_run):
    from repro.pipeline import EATConfig, run_eat_distgnn
    from repro.robustness import FaultPlan

    plan = FaultPlan(straggler={1: {2: 0.75}},
                     drop_refresh_epochs=frozenset({2}))
    res = run_eat_distgnn(EATConfig(**_PIPE_KW), fault_plan=plan)
    assert _tree_equal(res.final_params, baseline_run.final_params)
    assert res.straggler_delay_s == 0.75
    # epoch 2 would have paid a full refresh (age % 2 == 0): the dropped
    # payload shows up as zero exchanged bytes, the cache serves stale
    assert baseline_run.halo_exchange_history[2] > 0
    assert res.halo_exchange_history[2] == 0
    assert res.halo_exchange_history[4] == baseline_run.halo_exchange_history[4]


def test_resume_refuses_mismatched_fingerprint(tmp_path):
    from repro.pipeline import EATConfig, run_eat_distgnn
    from repro.robustness import FaultPlan, InjectedCrash

    ck = str(tmp_path / "ck")
    with pytest.raises(InjectedCrash):
        run_eat_distgnn(EATConfig(**_PIPE_KW, checkpoint_dir=ck),
                        fault_plan=FaultPlan(crash_epochs=frozenset({1})))
    other = dict(_PIPE_KW, seed=8)
    with pytest.raises(ValueError, match="refusing to resume"):
        run_eat_distgnn(EATConfig(**other, checkpoint_dir=ck, resume=True))


def test_engine_drop_next_halo_refresh_plan():
    import jax.numpy as jnp
    from repro.core import GPHyperParams, partition_graph
    from repro.engine import EngineConfig, SPMDEngine
    from repro.graph import (BENCHMARKS, GraphSAGE, build_partitioned_graph,
                             make_benchmark)
    from repro.train.optim import AdamW

    g = make_benchmark(BENCHMARKS["tiny"])
    r = partition_graph(g.indptr, g.indices, g.features, g.labels, 4,
                        method="ew", seed=0)
    pg = build_partitioned_graph(g, r.parts, 4)
    model = GraphSAGE(feature_dim=g.feature_dim, hidden_dim=16,
                      num_classes=g.num_classes)
    eng = SPMDEngine(model, model.make_loss_fn(), AdamW(lr=1e-3), pg,
                     GPHyperParams(),
                     EngineConfig(mode="stacked", use_pallas_agg=False,
                                  halo_cache=True, halo_refresh_every=2))
    assert eng._halo_plan() != (0, 0)            # age 0 → full refresh due
    eng.drop_next_halo_refresh()
    assert eng._halo_plan() == (0, 0)            # payload lost in transit
    assert eng.halo_refresh_drops == 1
    assert eng._halo_plan() != (0, 0)            # one-shot: next is normal
    st = eng.halo_cache_state()
    assert st is not None and st[1] == 0
    eng.restore_halo_cache_state(st[0], 5)
    assert eng.halo_cache_state()[1] == 5


# --------------------------------------------------------------------------
# 4b. fp64 kill-and-resume parity (subprocess; stacked AND shard_map)
# --------------------------------------------------------------------------

_FP64_RESUME_BODY = """
import json, os, tempfile
import numpy as np
from repro.pipeline import EATConfig, run_eat_distgnn
from repro.robustness import FaultPlan, InjectedCrash

KW = dict(dataset="tiny", num_parts=4, batch_size=32, hidden_dim=16,
          fanouts=(3, 3), max_epochs=6, phase0_fraction=0.5, seed=7,
          engine_mode=MODE, halo_cache=True, halo_refresh_every=2,
          dtype="float64")
base = run_eat_distgnn(EATConfig(**KW))
leaves_a = jax.tree.leaves(base.final_params)
out = {}
for crash in (1, 4):                 # a phase-0 and a phase-1 boundary
    ck = tempfile.mkdtemp()
    try:
        run_eat_distgnn(EATConfig(**KW, checkpoint_dir=ck),
                        fault_plan=FaultPlan(
                            crash_epochs=frozenset({crash})))
        raise AssertionError("fault did not fire")
    except InjectedCrash:
        pass
    res = run_eat_distgnn(EATConfig(**KW, checkpoint_dir=ck, resume=True))
    leaves_b = jax.tree.leaves(res.final_params)
    md = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
             for a, b in zip(leaves_a, leaves_b))
    out[f"crash{crash}"] = {
        "resumed_from": res.resumed_from_epoch,
        "params_maxdiff": md,
        "f1_equal": bool(res.f1.micro == base.f1.micro),
        "val_hist_equal": bool(res.val_history == base.val_history)}
print("RESULTS " + json.dumps(out))
"""


def _run_fp64_resume(mode, extra_env=None):
    script = (CACHE_PRELUDE
              + "import jax\njax.config.update('jax_enable_x64', True)\n"
              + f"MODE = {mode!r}\n" + _FP64_RESUME_BODY)
    env = dict(SUBPROC_ENV, **(extra_env or {}))
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=1800,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULTS ")][-1]
    return json.loads(line[len("RESULTS "):])


def _check_fp64_resume(out):
    for crash, r in out.items():
        assert r["params_maxdiff"] == 0.0, (crash, r)
        assert r["f1_equal"] and r["val_hist_equal"], (crash, r)
    assert out["crash1"]["resumed_from"] == 1
    assert out["crash4"]["resumed_from"] == 4


@pytest.mark.slow
def test_fp64_resume_bitwise_stacked():
    _check_fp64_resume(_run_fp64_resume("stacked"))


@pytest.mark.slow
def test_fp64_resume_bitwise_spmd():
    _check_fp64_resume(_run_fp64_resume(
        "spmd",
        extra_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}))


# --------------------------------------------------------------------------
# 5. degraded-mode serving
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serve_parts():
    """Graph + partition assignment + a builder for FRESH serving engines
    (each degradation test mutates its own engine)."""
    import jax.numpy as jnp
    from repro.core import GPHyperParams, partition_graph
    from repro.engine import EngineConfig, SPMDEngine
    from repro.graph import (BENCHMARKS, GraphSAGE, build_partitioned_graph,
                             make_benchmark)
    from repro.serve import GNNServingEngine
    from repro.train.optim import AdamW

    g = make_benchmark(BENCHMARKS["tiny"])
    r = partition_graph(g.indptr, g.indices, g.features, g.labels, 4,
                        method="ew", seed=0)
    model = GraphSAGE(feature_dim=g.feature_dim, hidden_dim=16,
                      num_classes=g.num_classes)
    prm = model.init(0)
    cfg = EngineConfig(mode="stacked", use_pallas_agg=False,
                       dtype=jnp.float32)

    def build(graph=None):
        pg = build_partitioned_graph(graph if graph is not None else g,
                                     r.parts, 4)
        eng = SPMDEngine(model, model.make_loss_fn(), AdamW(lr=1e-3), pg,
                         GPHyperParams(), cfg)
        return GNNServingEngine(model, prm, pg,
                                eng.export_serving_state(prm))

    owned = [np.where(build().owner_part == p)[0].astype(int)
             for p in range(4)]
    return g, build, owned


def test_degraded_queries_staleness_and_frozen_store(serve_parts):
    g, build, owned = serve_parts
    srv = build()
    gid = int(owned[1][0])
    row = int(srv.owner_row[gid])
    frozen = srv.h[0][1][row].copy()

    srv.fail_partition(1)
    vec = np.full(g.feature_dim, 3.5, np.float32)
    srv.update_features(gid, vec)
    assert srv.stats["updates_queued"] == 1
    np.testing.assert_array_equal(srv.h[0][1][row], frozen)  # applied nowhere

    srv.submit([gid, int(owned[0][0])])
    results, st = srv.tick()
    assert gid in results                        # still answered, from frozen
    assert st["staleness"] == {gid: 1}           # failed 1 tick ago
    assert st["health"][1] == "failed"
    assert srv.stats["degraded_queries"] == 1
    srv.tick()
    srv.submit([gid])
    _, st3 = srv.tick()
    assert st3["staleness"][gid] == 3            # age grows per tick

    # updates NOT touching the failed cone still apply immediately
    far = None
    for cand in owned[0]:
        srv2_probe = srv._should_queue_feat(int(cand))
        if not srv2_probe:
            far = int(cand)
            break
    if far is not None:
        before = srv.stats["updates_queued"]
        srv.update_features(far, np.zeros(g.feature_dim, np.float32))
        assert srv.stats["updates_queued"] == before
    with pytest.raises(RuntimeError, match="healthy"):
        srv.refresh_full()


def test_flaky_partition_retry_backoff_and_bitwise_reconvergence(serve_parts):
    from repro.serve import apply_updates_to_graph

    g, build, owned = serve_parts
    srv = build()
    rng = np.random.default_rng(11)

    srv.set_fault_plan(_flaky_plan())
    feats, adds, removes = {}, [], []
    down_ticks = 9
    for t in range(1, 16):
        if t == 2:                               # ops landing mid-outage
            for k in range(3):
                gid = int(owned[1][k])
                vec = rng.standard_normal(g.feature_dim).astype(np.float32)
                srv.update_features(gid, vec)
                feats[gid] = vec
            u, v = int(owned[2][0]), int(owned[1][1])
            srv.add_edge(u, v)
            adds.append((u, v))
            vrow = int(srv.owner_row[v])
            if len(srv.nbr_gid[1][vrow]):
                ru = int(srv.nbr_gid[1][vrow][0])
                srv.remove_edge(ru, v)
                removes.append((ru, v))
        srv.tick()

    assert srv.health == ["healthy"] * 4
    assert srv._queue == [] and srv.stats["replayed"] == len(feats) + 2
    # backoff keeps retries bounded: 1,2,4,8,8... gated attempts while down
    assert srv.stats["replay_attempts"] <= 2 + down_ticks // 2

    inc = srv.export_logits()
    srv.refresh_full()                           # full-vs-incremental oracle
    np.testing.assert_array_equal(inc, srv.export_logits())
    fresh = build(apply_updates_to_graph(g, feature_updates=feats,
                                         add_edges=adds,
                                         remove_edges=removes))
    np.testing.assert_array_equal(inc, fresh.export_logits())


def _flaky_plan():
    from repro.robustness import FaultPlan

    return FaultPlan(serve_fail={1: (1,)}, serve_recover={10: (1,)})


def test_fifo_replay_order_last_write_wins(serve_parts):
    g, build, owned = serve_parts
    srv = build()
    gid = int(owned[2][0])
    srv.fail_partition(2)
    first = np.full(g.feature_dim, 1.0, np.float32)
    second = np.full(g.feature_dim, 2.0, np.float32)
    srv.update_features(gid, first)
    srv.update_features(gid, second)             # FIFO behind the first
    assert srv.stats["updates_queued"] == 2
    srv.recover_partition(2)
    srv.tick()
    np.testing.assert_array_equal(
        srv.h[0][2][int(srv.owner_row[gid])], second)


def test_random_plan_drives_serve_events(serve_parts):
    from repro.robustness import FaultPlan

    _, build, _ = serve_parts
    srv = build()
    plan = FaultPlan.random(5, num_parts=4, max_epochs=0, serve_ticks=12,
                            serve_fail_prob=0.4, down_ticks=2)
    assert plan.serve_fail                       # seed 5 does schedule faults
    srv.set_fault_plan(plan)
    saw_failed = False
    for _ in range(20):
        _, st = srv.tick()
        saw_failed = saw_failed or "failed" in st["health"]
    assert saw_failed
    assert srv.health == ["healthy"] * 4         # every failure recovered
    assert srv.stats["recoveries"] == srv.stats["failovers"] > 0
