"""The four-chip DistEGNN cell at a tiny size on four CPU devices: a sound
run agrees with the reference on the union of the shards' graphs; with the
timed step broken underneath (the exchange between chips left out, the
state left unchanged, half of the shards' loss terms left out, the loss
altered where it is made) ``correct`` is false.  The runs share one child
process, which sets the device count before JAX starts.

At this size a scene has 150 particles a shard, and the sound run's
relative loss gaps (up to ~2e-5) lie above the cell's loss limit: the
sound run is held to float32 agreement (1e-4), and each fault has to fail
a number past both that and the cell's own limit.  The virtual nodes after
the forward (``virtual_gap``) read ~2e-6 in a sound run."""
import json
import os
import subprocess
import sys

import pytest

from bench.tests.tiny import ROOT, tiny_cell

CELL = "fluid113k.dist_train"

SCRIPT = r"""
import json, sys
sys.path[:0] = [ROOT, ROOT + "/src"]
import jax
from bench import calibrate, driver
from bench.tests.tiny import run_tiny
from repro.distributed import dist_egnn
from repro.training import optim
driver.enable_compile_cache = lambda: "(off)"
CELL = "fluid113k.dist_train"
mse = dist_egnn.masked_mse


def half_shards(p, t, m, axis_name=None):
    keep = jax.lax.axis_index(axis_name) < jax.lax.axis_size(axis_name) // 2
    return mse(p, t, m * keep.astype(m.dtype), axis_name)


out = {"sound": run_tiny(CELL)}
with calibrate.exchange_left_out():
    out["exchange"] = run_tiny(CELL)
with calibrate.loss_sums_local():
    out["loss_local"] = run_tiny(CELL)
dist_egnn.masked_mse = half_shards
out["half_shards"] = run_tiny(CELL)
dist_egnn.masked_mse = lambda *a, **kw: mse(*a, **kw) * 1.01
out["answer_altered"] = run_tiny(CELL)
dist_egnn.masked_mse = mse
optim.Adam.update = lambda self, g, s, p: (p, s)
out["unchanged"] = run_tiny(CELL)
print(json.dumps({k: dict(correct=v["correct"], checks=v["checks"],
                          count=v["device"]["count"]) for k, v in out.items()}))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c",
                        SCRIPT.replace("ROOT", repr(ROOT), 2)],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_dist_cell_on_four_cpu_devices(runs):
    """The sound run, virtual nodes included, agrees with the reference to
    float32 rounding."""
    assert runs["sound"]["count"] == 4
    assert set(runs["sound"]["checks"]) == {"loss_gap", "grad_gap",
                                            "update_gap", "virtual_gap"}
    assert all(v["value"] < 1e-4 for v in runs["sound"]["checks"].values()), \
        runs["sound"]["checks"]


@pytest.mark.parametrize("fault", ["exchange", "unchanged", "half_shards",
                                   "answer_altered"])
def test_dist_fault_is_not_correct(runs, fault):
    """Each fault fails a number past its limit and past the float32
    agreement that the sound run is held to at this size."""
    checks = runs[fault]["checks"]
    assert runs[fault]["correct"] is False, checks
    assert any(v["value"] > max(v["limit"], 1e-4) for v in checks.values()), \
        checks


def test_exchange_fails_on_virtual_gap(runs):
    """With the exchange between chips left out, virtual_gap alone fails:
    past its limit and by ten times the sound run's reading or more."""
    got = runs["exchange"]["checks"]["virtual_gap"]
    sound = runs["sound"]["checks"]["virtual_gap"]["value"]
    assert got["value"] > max(got["limit"], 10 * sound), got


@pytest.mark.parametrize("fault", ["loss_local", "half_shards",
                                   "answer_altered", "unchanged"])
def test_loss_side_fault_leaves_virtual_nodes(runs, fault):
    """A fault in the loss or the update leaves the forward at the initial
    weights, and so the virtual nodes, as the sound run has them."""
    assert runs[fault]["checks"]["virtual_gap"]["value"] == pytest.approx(
        runs["sound"]["checks"]["virtual_gap"]["value"], rel=1e-3)


def test_loss_sums_local_change_nothing_on_balanced_shards(runs):
    """Shards of equal size give each shard's own mean the global mean's
    value and gradient: this fault is no fault the cell can have."""
    assert all(v["value"] < 1e-4
               for v in runs["loss_local"]["checks"].values()), \
        runs["loss_local"]["checks"]


def test_dist_control_is_not_correct():
    """The control: the reference on the union of the shards' graphs at
    ``Precision.HIGH``, put in the program's place, fails a number."""
    import jax

    from bench import calibrate, check, registry, scenes, weights

    win = registry.window("train")
    c = tiny_cell(CELL)
    cfg, traffic = c["config"], c["traffic"]
    seed = 2 ** 32 + 91
    pool = scenes.scene_pool(seed, traffic["pool_scenes"], cfg)
    params0 = jax.device_get(weights.make_weights(cfg,
                                                  weights.seed_key(seed)))
    keys = win.step_keys(seed, win.CHECKED_STEPS)
    ref = registry.reference(cfg["reference"])
    _, batches, rkeys = win.reference_batches(cfg, traffic, pool, keys,
                                              win.CHECKED_STEPS)
    want = ref.train(params0, batches, rkeys, cfg, mode="highest")
    high = ref.train(params0, batches, rkeys, cfg, mode="high")
    values = check.gaps(calibrate.as_checked(high, cfg["devices"]), want,
                        params0)
    ok, table = check.judge(values, c["limits"])
    assert "virtual_gap" in values and not ok, table
