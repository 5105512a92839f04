"""Launch-layer tests: spec sanitisation rules + a REAL (small-mesh)
lower/compile of every step kind in a subprocess with 8 host devices —
the same code path the production dry-run exercises at 256/512 chips."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest


def test_sanitize_spec_rules():
    from jax.sharding import PartitionSpec as P
    from repro.launch.steps import sanitize_spec

    class FakeMesh:
        shape = {"data": 4, "model": 8, "pod": 2}

    m = FakeMesh()
    # divisible: kept
    assert sanitize_spec(P(None, "model"), (3, 64), m) == P(None, "model")
    # not divisible: dropped
    assert sanitize_spec(P(None, "model"), (3, 51865 % 100 + 3), m)[1] is None
    # tuple axes: partial drop from the right
    s = sanitize_spec(P(("pod", "data"), None), (4, 7), m)
    assert s[0] is None or s[0] == "pod"  # 8 doesn't divide 4 -> drop data
    s2 = sanitize_spec(P(("pod", "data"),), (8,), m)
    assert s2[0] == ("pod", "data")


from _jax_cache import CACHE_PRELUDE, subprocess_env

# flaky-surface hardening: the cache prelude persists lowered/compiled
# artifacts under the repo's .jax_cache so repeated runs of this
# (compile-bound) test skip XLA
SMALL_MESH_SCRIPT = (
    'import os\n'
    'os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"\n'
    + CACHE_PRELUDE
) + r"""
import json
import jax
from repro.configs import get_config, SHAPES, InputShape
from jax.sharding import AxisType
from repro.launch.steps import build_step

def small_mesh(multi_pod=False):
    shape = (2, 2, 2) if multi_pod else (4, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))

results = {}
cfg = get_config("llama3.2-1b").reduced()
shapes = {
    "train": InputShape("train", 64, 8, "train"),
    "prefill": InputShape("prefill", 64, 8, "prefill"),
    "decode": InputShape("decode", 64, 8, "decode"),
}
for mp in (False, True):
    mesh = small_mesh(mp)
    # the ambient mesh context lets with_sharding_constraint resolve bare
    # PartitionSpecs inside the model; `with mesh:` is the 0.4.x spelling of
    # the newer jax.set_mesh
    with mesh:
        for name, shape in shapes.items():
            built = build_step(cfg, shape, mesh)
            compiled = built.lower().compile()
            cost = compiled.cost_analysis()
            if isinstance(cost, list):  # 0.4.x: one dict per computation
                cost = cost[0]
            results[f"{name}@{'2pod' if mp else '1pod'}"] = cost["flops"] > 0
        # phase-1 personalized step lowers too (the GP feature, distributed).
        # KNOWN LIMITATION: on the CPU backend, XLA's SPMD partitioner
        # aborts (SIGABRT after 'involuntary full rematerialization'
        # warnings, tracked as XLA b/433785288) when the vmapped per-replica
        # scan is partitioned across a THIRD mesh axis — so the personalize
        # compile is asserted on the single-pod mesh only.
        if not mp:
            built = build_step(cfg, shapes["train"], mesh, phase="personalize")
            compiled = built.lower().compile()
            results["personalize@1pod"] = True
print("RESULTS", json.dumps(results))
"""


@pytest.mark.slow
def test_small_mesh_all_step_kinds_compile():
    res = subprocess.run([sys.executable, "-c", SMALL_MESH_SCRIPT],
                         capture_output=True, text=True, timeout=1800,
                         env=subprocess_env())
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULTS")][0]
    results = json.loads(line[len("RESULTS "):])
    assert len(results) == 7 and all(results.values()), results


def test_input_specs_all_archs_all_shapes():
    """input_specs builds ShapeDtypeStructs (no allocation) for all 40."""
    from repro.configs import ARCH_IDS, SHAPES, get_config, input_specs
    for arch in ARCH_IDS:
        for shape in SHAPES.values():
            variant = None
            cfg = get_config(arch)
            if shape.name == "long_500k" and not cfg.supports_long_context:
                cfg = get_config(arch, "swa")
            spec = input_specs(cfg, shape)
            assert isinstance(spec, dict) and spec
            if shape.kind == "decode":
                assert spec["token"].shape == (shape.global_batch, 1)
                leaves = [l for l in
                          __import__("jax").tree_util.tree_leaves(spec["caches"])]
                assert leaves, f"{arch} x {shape.name}: empty cache"


CACHE_SCRIPT = r"""
import jax, jax.numpy as jnp
from repro.launch.cache import enable_compile_cache
d = enable_compile_cache()
jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
print("CACHE", d, jax.config.jax_compilation_cache_dir)
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(tmp_path, from_env):
    """Every entry point's cache helper: ``$JAX_COMPILATION_CACHE_DIR`` is
    used as given (JAX reads it; the helper sets nothing) and receives the
    entries; unset, the cache goes to ``<checkout>/.jax_cache``."""
    from _jax_cache import REPO_ROOT

    env = subprocess_env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    res = subprocess.run([sys.executable, "-c", CACHE_SCRIPT],
                         capture_output=True, text=True, timeout=600, env=env)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("CACHE")][0]
    _, used, configured = line.split()
    want = (str(tmp_path / "cache") if from_env
            else os.path.join(REPO_ROOT, ".jax_cache"))
    assert used == configured == want
    if from_env:
        assert any((tmp_path / "cache").iterdir()), "no cache entry written"
