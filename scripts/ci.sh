#!/usr/bin/env bash
# Tier-1 CI: fast deterministic suite (including the fixed-seed statistical
# tier for the on-device CBS sampler, tests/test_cbs_device.py), then a
# pass/fail delta against the checked-in seed baseline
# (tests/seed_baseline.txt), then a runtime gate: any slow-unmarked test
# exceeding 30 s that is not grandfathered in tests/tier1_slowlist.txt
# fails the build.
#
#   scripts/ci.sh          tier-1 (-m "not slow and not timing") + baseline
#                          delta + 30s gate + the timing quarantine lane
#   scripts/ci.sh grad     grad-parity smoke only: jax.grad through the
#                          custom-VJP Pallas aggregation op vs the jnp
#                          reference, with fwd+bwd kernel-staging evidence
#   scripts/ci.sh halo-cache
#                          halo-cache smoke only: staleness 0 bitwise vs the
#                          sync eval forward + pure-cached evals ship zero
#                          halo bytes
#   scripts/ci.sh serve    serving smoke only: incremental dirty-set
#                          recomputation after scripted updates must be
#                          BITWISE a from-scratch forward over the rebuilt
#                          graph (runs outside the 30 s gate)
#   scripts/ci.sh faults   robustness smoke only: injected kill-at-epoch ->
#                          resume must be bitwise the uninterrupted run,
#                          plus one degraded serving tick (frozen-store
#                          answer + staleness tag + queued replay); runs
#                          outside the 30 s gate
#   scripts/ci.sh comm     compressed-communication smoke only: one tiny
#                          int8-halo + bucketed-gradient epoch pair in BOTH
#                          engine modes (stacked and forced-4-device spmd);
#                          the gradient wire bytes must be exactly half the
#                          uncompressed run's and the halo exchange bytes
#                          under half; runs outside the 30 s gate
#   scripts/ci.sh featstore
#                          feature-store smoke only: one tiny two-tier
#                          feat-store epoch in BOTH engine modes (stacked and
#                          forced-4-device spmd) against an all-resident
#                          baseline; micro-F1 must match, the cold-row h2d
#                          counter must equal the closed form, and the
#                          resident feature footprint must shrink; runs
#                          outside the 30 s gate
#   scripts/ci.sh timing   the timing quarantine lane only: wall-clock-
#                          sensitive tests, one automatic retry, never part
#                          of the 30 s runtime gate
#   scripts/ci.sh slow     the -m slow stage (kernel sweeps, multi-device
#                          subprocess compiles, the full fp64 parity matrix)
#   scripts/ci.sh all      tier-1 (incl. the grad smoke) + timing + slow
set -u
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}
# ONE shared persistent XLA compile cache for the whole run: the in-process
# tests pick it up from the environment, the subprocess scripts point at the
# same directory via tests/_jax_cache.py, so every stage reuses every other
# stage's lowered executables across reruns.  A directory the caller set is
# kept; otherwise the checkout's .jax_cache
export JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache}"
export JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0.5

mode=${1:-tier1}
if [ "$mode" = "slow" ]; then
    exec python -m pytest -m slow -q
fi

# ---- timing quarantine lane ------------------------------------------------
# Wall-clock-sensitive tests (@pytest.mark.timing) compare elapsed times, so
# a loaded machine can flake them through no fault of the code.  They run
# OUTSIDE tier-1 (excluded from the pass/fail baseline and the 30 s runtime
# gate) with ONE automatic retry; only a double failure fails the build.
timing_lane() {
    if python -m pytest -m timing -q; then
        return 0
    fi
    echo "timing lane failed once; retrying (wall-clock tests are load-sensitive)"
    python -m pytest -m timing -q --last-failed || {
        echo "REGRESSION: timing lane failed twice in a row"
        return 1
    }
}

if [ "$mode" = "timing" ]; then
    timing_lane
    exit $?
fi

# ---- grad-parity smoke -----------------------------------------------------
# Fast standalone witness (also the first step of every tier-1 run): jax.grad
# through segment_mean_op must match the jnp reference AND stage the Pallas
# kernel in BOTH directions of the pass.  This intentionally duplicates
# assertions that tests/test_kernels.py makes again minutes later — it is
# the ~10 s FAIL-FAST in front of the ~25 min suite, and `scripts/ci.sh
# grad` gives the same witness without pytest at all.
grad_smoke() {
    python - <<'PY'
import numpy as np, jax, jax.numpy as jnp
from repro.kernels import ops, ref
from repro.kernels import segment_agg as sa

rng = np.random.default_rng(0)
n, d = 200, 32
deg = rng.integers(0, 6, n); deg[rng.random(n) < 0.3] = 0
indptr = np.zeros(n + 1, np.int64); np.cumsum(deg, out=indptr[1:])
indices = rng.integers(0, n, int(indptr[-1])).astype(np.int64)
x = jnp.asarray(rng.normal(0, 1, (n, d)).astype(np.float32))
w = jnp.asarray(rng.normal(0, 1, (n, d)).astype(np.float32))
agg = ops.make_segment_agg(indptr, indices)
src = jnp.asarray(indices)
dst = jnp.asarray(np.repeat(np.arange(n), deg))
before = sa.pallas_call_count()
g_op = jax.grad(lambda x: (agg(x) * w).sum())(x)
staged = sa.pallas_call_count() - before
g_ref = jax.grad(lambda x: (ref.segment_agg_ref(x, src, dst, n) * w).sum())(x)
np.testing.assert_allclose(np.asarray(g_op), np.asarray(g_ref),
                           atol=1e-5, rtol=1e-5)
assert staged >= 2, f"fwd+bwd kernels not both staged ({staged})"
print(f"grad-parity smoke OK (pallas calls staged in grad trace: {staged})")
PY
}

if [ "$mode" = "grad" ]; then
    grad_smoke || exit 1
    exit 0
fi

# ---- halo-cache smoke ------------------------------------------------------
# Second fail-fast witness: the historical-embedding halo cache.  At refresh
# cadence 1 the cached eval forward must be BITWISE the sync forward (same
# trace structure, full exchange every eval); at cadence 2 the pure-cached
# eval must report zero halo bytes while the refresh eval reports the full
# two-layer payload.  ~15 s on the tiny benchmark; the fp64 oracle tier runs
# minutes later in tests/test_engine_parity.py.
halo_cache_smoke() {
    python - <<'PY'
import numpy as np, jax, jax.numpy as jnp
from repro.core import partition_graph, GPHyperParams
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
mk = lambda **kw: SPMDEngine(model, model.make_loss_fn(), AdamW(lr=1e-3),
                             pg, GPHyperParams(),
                             EngineConfig(mode="stacked",
                                          use_pallas_agg=False, **kw))
sync = mk()
k1 = mk(halo_cache=True, halo_refresh_every=1)
k2 = mk(halo_cache=True, halo_refresh_every=2)
full = 2 * pg.halo_bytes_per_layer
k2_bytes = []
for i in range(2):
    prm = jax.tree.map(lambda x: x * (1.0 + 0.1 * i), model.init(0))
    mS, prS = sync.evaluate(prm, "val", per_partition_params=False)
    mC, prC = k1.evaluate(prm, "val", per_partition_params=False)
    assert float(jnp.abs(mS - mC).max()) == 0.0, "staleness-0 micro drifted"
    assert (np.asarray(prS) == np.asarray(prC)).all(), \
        "staleness-0 preds drifted"
    assert k1.last_halo_exchange_bytes == full, k1.last_halo_exchange_bytes
    k2.evaluate(prm, "val", per_partition_params=False)
    k2_bytes.append(k2.last_halo_exchange_bytes)
assert k2_bytes == [full, 0], k2_bytes
print(f"halo-cache smoke OK (staleness 0 bitwise; K=2 bytes {k2_bytes})")
PY
}

if [ "$mode" = "halo-cache" ]; then
    halo_cache_smoke || exit 1
    exit 0
fi

# ---- serving smoke ---------------------------------------------------------
# Third fail-fast witness: the partitioned serving engine (PR 7).  Scripted
# feature updates + a cross-partition edge add (halo growth) + a removal,
# flushed through the incremental dirty-set path, must reproduce a fresh
# engine's export over the REBUILT graph bit-for-bit, and the served argmax
# must equal evaluate()'s predictions.  Not a pytest test, so it sits
# outside the 30 s runtime gate by construction; the fp64 two-round oracle
# runs in the slow lane (tests/test_serve_gnn.py).
serve_smoke() {
    python - <<'PY'
import numpy as np, jax, jax.numpy as jnp
from repro.core import partition_graph, GPHyperParams
from repro.engine import EngineConfig, SPMDEngine
from repro.graph import (BENCHMARKS, GraphSAGE, build_partitioned_graph,
                         make_benchmark)
from repro.serve import GNNServingEngine, apply_updates_to_graph
from repro.train.optim import AdamW

g = make_benchmark(BENCHMARKS["tiny"])
P = 4
r = partition_graph(g.indptr, g.indices, g.features, g.labels, P,
                    method="ew", seed=0)
pg = build_partitioned_graph(g, r.parts, P)
model = GraphSAGE(feature_dim=g.feature_dim, hidden_dim=16,
                  num_classes=g.num_classes)
cfg = EngineConfig(mode="stacked", use_pallas_agg=False)
eng = SPMDEngine(model, model.make_loss_fn(), AdamW(lr=1e-3), pg,
                 GPHyperParams(), cfg)
prm = model.init(0)
srv = GNNServingEngine.from_engine(eng, pg, prm)

rng = np.random.default_rng(0)
fupd = {int(v): rng.normal(0, 1, g.feature_dim).astype(np.float32)
        for v in rng.choice(g.num_nodes, 3, replace=False)}
v = next(x for x in range(g.num_nodes) if len(g.neighbors(x)) > 1)
u = next(x for x in range(g.num_nodes)
         if x != v and r.parts[x] != r.parts[v] and x not in g.neighbors(v))
adds, rems = [(u, v)], [(int(g.neighbors(v)[0]), v)]
for gid, vec in fupd.items():
    srv.update_features(gid, vec)
assert srv.add_edge(*adds[0]) and srv.remove_edge(*rems[0])
st = srv.flush()

g2 = apply_updates_to_graph(g, fupd, adds, rems)
pg2 = build_partitioned_graph(g2, r.parts, P)
eng2 = SPMDEngine(model, model.make_loss_fn(), AdamW(lr=1e-3), pg2,
                  GPHyperParams(), cfg)
fresh = eng2.export_serving_state(prm)
want = np.zeros((g.num_nodes, model.num_classes), np.float32)
for p in range(P):
    n = int(pg2.n_own[p])
    want[np.asarray(pg2.global_ids[p])[:n]] = np.asarray(fresh["logits"][p])[:n]
got = srv.export_logits()
assert (got == want).all(), f"not bitwise: {np.abs(got - want).max()}"
_, preds = eng2.evaluate(prm, "val", per_partition_params=False)
for p in range(P):
    n = int(pg2.n_own[p])
    own = np.asarray(pg2.global_ids[p])[:n]
    assert (got[own].argmax(-1) == np.asarray(preds)[p][:n]).all()
print(f"serve smoke OK ({st['rows_recomputed']} rows recomputed "
      "incrementally, bitwise vs fresh forward)")
PY
}

if [ "$mode" = "serve" ]; then
    serve_smoke || exit 1
    exit 0
fi

# ---- faults smoke ----------------------------------------------------------
# Fourth fail-fast witness: the PR-8 robustness layer.  A run killed by an
# injected crash at an epoch boundary and resumed from its checksummed
# checkpoint must finish with final params BIT-FOR-BIT identical to the
# uninterrupted run (f32 stacked here; the fp64 stacked+shard_map matrix
# runs in tests/test_robustness.py), and one degraded serving tick must
# answer a failed partition's query from its frozen store with a staleness
# tag while queueing the update for replay.  Not a pytest test, so it sits
# outside the 30 s runtime gate by construction.
faults_smoke() {
    python - <<'PY'
import os, tempfile
import numpy as np, jax
from repro.pipeline import EATConfig, run_eat_distgnn
from repro.robustness import FaultPlan, InjectedCrash

KW = dict(dataset="tiny", num_parts=4, batch_size=32, hidden_dim=16,
          fanouts=(3, 3), max_epochs=6, phase0_fraction=0.5, seed=7,
          engine_mode="stacked", halo_cache=True, halo_refresh_every=2)
base = run_eat_distgnn(EATConfig(**KW))
ck = tempfile.mkdtemp()
try:
    run_eat_distgnn(EATConfig(**KW, checkpoint_dir=ck),
                    fault_plan=FaultPlan(crash_epochs=frozenset({4})))
    raise AssertionError("injected crash did not fire")
except InjectedCrash:
    pass
res = run_eat_distgnn(EATConfig(**KW, checkpoint_dir=ck, resume=True))
assert res.resumed_from_epoch == 4, res.resumed_from_epoch
la, lb = jax.tree.leaves(base.final_params), jax.tree.leaves(res.final_params)
assert all(np.array_equal(np.asarray(a), np.asarray(b))
           for a, b in zip(la, lb)), "resume is not bitwise"
assert res.f1.micro == base.f1.micro and res.val_history == base.val_history

# one degraded serving tick
from repro.core import partition_graph, GPHyperParams
from repro.engine import EngineConfig, SPMDEngine
from repro.graph import (BENCHMARKS, GraphSAGE, build_partitioned_graph,
                         make_benchmark)
from repro.serve import GNNServingEngine
from repro.train.optim import AdamW
g = make_benchmark(BENCHMARKS["tiny"])
r = partition_graph(g.indptr, g.indices, g.features, g.labels, 4,
                    method="ew", seed=0)
pg = build_partitioned_graph(g, r.parts, 4)
model = GraphSAGE(feature_dim=g.feature_dim, hidden_dim=16,
                  num_classes=g.num_classes)
eng = SPMDEngine(model, model.make_loss_fn(), AdamW(lr=1e-3), pg,
                 GPHyperParams(),
                 EngineConfig(mode="stacked", use_pallas_agg=False))
srv = GNNServingEngine.from_engine(eng, pg, model.init(0))
gid = int(np.where(srv.owner_part == 1)[0][0])
frozen = srv.h[0][1][int(srv.owner_row[gid])].copy()
srv.fail_partition(1)
srv.update_features(gid, np.ones(g.feature_dim, np.float32))
assert srv.stats["updates_queued"] == 1
assert (srv.h[0][1][int(srv.owner_row[gid])] == frozen).all()
srv.submit([gid])
results, st = srv.tick()
assert gid in results and st["staleness"] == {gid: 1}, st
srv.recover_partition(1)
srv.tick()
assert srv.stats["replayed"] == 1 and not srv._queue
print("faults smoke OK (kill@4 -> resume bitwise; degraded tick answered "
      f"stale query, queued+replayed the update)")
PY
}

if [ "$mode" = "faults" ]; then
    faults_smoke || exit 1
    exit 0
fi

# ---- compressed-communication smoke ----------------------------------------
# Fifth fail-fast witness: the PR-9 compression layer.  One tiny run with
# int8 halo quantization + bucketed gradient reduction in each engine mode
# (stacked, and shard_map on 4 forced host devices) against an uncompressed
# baseline: the accounted gradient wire bytes must be EXACTLY 2/P of the
# all_gather spelling (0.5 at P=4), the eval halo exchange bytes under half,
# and the compressed micro-F1 in the baseline's neighbourhood.  Not a pytest
# test, so it sits outside the 30 s runtime gate by construction; the fp64
# bitwise oracle tier runs in tests/test_engine_parity.py.
comm_smoke() {
    python - <<'PY'
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
import numpy as np
from repro.pipeline import EATConfig, run_eat_distgnn

KW = dict(dataset="tiny", num_parts=4, batch_size=32, hidden_dim=16,
          fanouts=(3, 3), max_epochs=2, phase0_fraction=1.0, seed=3)
base = run_eat_distgnn(EATConfig(**KW, engine_mode="stacked"))
assert base.comm_grad_bytes > 0 and base.comm_halo_exchange_bytes > 0
micros = {}
for mode in ("stacked", "spmd"):
    res = run_eat_distgnn(EATConfig(**KW, engine_mode=mode,
                                    halo_compress="int8",
                                    grad_compress="bucketed"))
    g_ratio = res.comm_grad_bytes / base.comm_grad_bytes
    h_ratio = res.comm_halo_exchange_bytes / base.comm_halo_exchange_bytes
    assert g_ratio == 0.5, (mode, g_ratio)          # 2*(P-1) / (P*(P-1))
    assert h_ratio <= 0.5, (mode, h_ratio)          # (d+4) / 4d at f32
    assert np.isfinite(res.f1.micro)
    micros[mode] = res.f1.micro
assert abs(micros["stacked"] - micros["spmd"]) < 1.0, micros
print(f"comm smoke OK (grad bytes 0.5x, halo bytes <=0.5x, micro "
      f"{micros['stacked']:.2f}/{micros['spmd']:.2f} vs base "
      f"{base.f1.micro:.2f})")
PY
}

if [ "$mode" = "comm" ]; then
    comm_smoke || exit 1
    exit 0
fi

# ---- feature-store smoke ----------------------------------------------------
# Sixth fail-fast witness, at the HEAD of every tier-1 run: the PR-10
# two-tier feature store.  One tiny epoch pair per engine mode (stacked, and
# shard_map on 4 forced host devices): the feat-store run must reproduce the
# all-resident micro-F1, report cold h2d bytes, and shrink the resident
# feature footprint; hot_frac=1.0 must report EXACTLY the all-resident
# counters (the pre-PR-10 accounting lock).  Not a pytest test, so it sits
# outside the 30 s runtime gate by construction; the fp64 bitwise oracle
# tier runs in tests/test_engine_parity.py.
featstore_smoke() {
    python - <<'PY'
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
import numpy as np
from repro.pipeline import EATConfig, run_eat_distgnn

KW = dict(dataset="tiny", num_parts=4, batch_size=32, hidden_dim=16,
          fanouts=(3, 3), max_epochs=2, phase0_fraction=1.0, seed=3)
stats = {}
bases = {}
for mode in ("stacked", "spmd"):
    base = run_eat_distgnn(EATConfig(**KW, engine_mode=mode))
    fs = run_eat_distgnn(EATConfig(**KW, engine_mode=mode, feat_store=True,
                                   hot_frac=0.25))
    assert abs(fs.f1.micro - base.f1.micro) <= 1e-6, \
        (mode, fs.f1.micro, base.f1.micro)
    assert fs.cold_h2d_bytes > 0 and base.cold_h2d_bytes == 0
    assert 0 < fs.resident_feature_bytes < base.resident_feature_bytes
    stats[mode] = (fs.cold_h2d_bytes,
                   fs.resident_feature_bytes / base.resident_feature_bytes)
    bases[mode] = base
hot1 = run_eat_distgnn(EATConfig(**KW, engine_mode="stacked",
                                 feat_store=True, hot_frac=1.0))
b = bases["stacked"]
assert hot1.cold_h2d_bytes == 0
assert hot1.f1.micro == b.f1.micro
assert hot1.host_to_device_bytes_phase0 == b.host_to_device_bytes_phase0
assert hot1.host_to_device_bytes_phase1 == b.host_to_device_bytes_phase1
print("featstore smoke OK (cold bytes stacked/spmd "
      f"{stats['stacked'][0]}/{stats['spmd'][0]}, resident ratio "
      f"{stats['stacked'][1]:.2f}; hot_frac=1.0 stages zero cold bytes)")
PY
}

if [ "$mode" = "featstore" ]; then
    featstore_smoke || exit 1
    exit 0
fi

featstore_smoke || { echo "REGRESSION: feature-store smoke failed"; exit 1; }
grad_smoke || { echo "REGRESSION: grad-parity smoke failed"; exit 1; }
halo_cache_smoke || { echo "REGRESSION: halo-cache smoke failed"; exit 1; }
serve_smoke || { echo "REGRESSION: serving smoke failed"; exit 1; }
faults_smoke || { echo "REGRESSION: faults smoke failed"; exit 1; }
comm_smoke || { echo "REGRESSION: compressed-communication smoke failed"; exit 1; }

out=$(python -m pytest -m "not slow and not timing" -q --durations=0 2>&1)
pytest_status=$?
echo "$out" | tail -25

# exit codes >= 2 mean pytest itself broke (interrupted / internal / usage
# error) — the printed counts are unreliable, never report OK from them
if [ "$pytest_status" -ge 2 ]; then
    echo "ABORT: pytest exited with status $pytest_status (not a test-failure exit)"
    exit "$pytest_status"
fi

count() { echo "$out" | grep -oE "[0-9]+ $1" | tail -1 | grep -oE "[0-9]+" || echo 0; }
passed=$(count passed)
failed=$(count failed)
errors=$(count "errors?")

baseline=tests/seed_baseline.txt
read -r bpass bfail berr <<<"$(awk '/^counts/{print $2, $3, $4}' "$baseline")"

echo
echo "tier-1:        passed=$passed failed=$failed errors=$errors"
echo "seed baseline: passed=$bpass failed=$bfail errors=$berr"
bad_now=$((failed + errors))
bad_seed=$((bfail + berr))
echo "delta:         passed=$((passed - bpass)) failing=$((bad_now - bad_seed))"

if [ "$bad_now" -ge "$bad_seed" ] && [ "$bad_seed" -gt 0 ]; then
    echo "REGRESSION: failing count did not strictly decrease vs seed"
    exit 1
fi
if [ "$bad_seed" -eq 0 ] && [ "$bad_now" -gt 0 ]; then
    echo "REGRESSION: new failures vs clean baseline"
    exit 1
fi
if [ "$passed" -lt "$bpass" ]; then
    echo "REGRESSION: fewer tests passing than at seed"
    exit 1
fi
echo "OK: no regression vs seed baseline"

# ---- 30 s runtime gate -----------------------------------------------------
# A tier-1 test that needs > 30 s (call or fixture setup) must either carry
# the `slow` marker or be grandfathered in tests/tier1_slowlist.txt.
# Slowlist line format: <test-id> [baseline-seconds]; the optional baseline
# drives the wall-time delta report below.
slowlist=tests/tier1_slowlist.txt
offenders=$(echo "$out" | awk '
    $1 ~ /^[0-9]+(\.[0-9]+)?s$/ && ($2 == "call" || $2 == "setup") {
        sec = substr($1, 1, length($1) - 1) + 0
        if (sec > 30) print sec "s " $3
    }')
new_offenders=""
while IFS= read -r line; do
    [ -z "$line" ] && continue
    id=${line#* }
    if ! awk '$1 !~ /^#/ {print $1}' "$slowlist" 2>/dev/null | grep -qxF "$id"; then
        new_offenders="$new_offenders$line"$'\n'
    fi
done <<EOF
$offenders
EOF
if [ -n "$new_offenders" ]; then
    echo "REGRESSION: slow-unmarked tier-1 tests exceeding 30 s"
    echo "(mark them @pytest.mark.slow or add to $slowlist):"
    printf '%s' "$new_offenders"
    exit 1
fi
echo "OK: no new tier-1 test exceeds 30 s"

# ---- wall-time delta vs recorded baselines ---------------------------------
# Non-gating visibility: suite total and the grandfathered tests' durations
# against the baselines recorded in the slowlist, so kernel/test additions
# don't silently regress tier-1 runtime.
total_s=$(echo "$out" | grep -oE "in [0-9]+(\.[0-9]+)?s" | tail -1 | grep -oE "[0-9]+(\.[0-9]+)?")
base_total=$(awk '/^# total-baseline-seconds:/{print $3}' "$slowlist" 2>/dev/null)
if [ -n "$total_s" ] && [ -n "$base_total" ]; then
    awk -v c="$total_s" -v b="$base_total" 'BEGIN{
        printf "tier-1 wall time: %.0fs (baseline %.0fs, delta %+.0fs)\n", c, b, c-b}'
elif [ -n "$total_s" ]; then
    echo "tier-1 wall time: ${total_s}s (no baseline recorded in $slowlist)"
fi
while read -r id base; do
    cur=$(echo "$out" | awk -v id="$id" '
        $1 ~ /^[0-9]+(\.[0-9]+)?s$/ && ($2 == "call" || $2 == "setup") && $3 == id {
            s += substr($1, 1, length($1) - 1) + 0 } END { if (s) print s }')
    [ -z "$cur" ] && continue
    awk -v id="$id" -v c="$cur" -v b="$base" 'BEGIN{
        printf "  %-70s %6.0fs (baseline %.0fs, delta %+.0fs)\n", id, c, b, c-b}'
done <<EOF
$(awk '$1 !~ /^#/ && NF >= 2 {print $1, $2}' "$slowlist" 2>/dev/null)
EOF

timing_lane || exit 1

if [ "$mode" = "all" ]; then
    python -m pytest -m slow -q || exit 1
fi
exit 0
