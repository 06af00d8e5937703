"""The one-chip training cell at a tiny size on the CPU: a sound run is
correct, and the lower-precision controls are not."""
import jax
import pytest

from bench import calibrate, check, registry, scenes, weights
from bench.tests.tiny import result_json, run_tiny, tiny_cell

CELL = "water3d.train"
WIN = registry.window("train")


def test_tiny_run_is_correct():
    """A one-chip cell is held to the three training numbers alone: no
    virtual_gap in its result line."""
    from bench.run import result_line

    res = result_json(run_tiny(CELL))
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > WIN.CHECKED_STEPS
    assert set(res["metrics"]) == {"setup_s", "train_scenes_per_s"}
    assert res["metrics"]["train_scenes_per_s"]["value"] > 0
    line = result_line(res, registry.cell(CELL), trace=False)
    assert list(line["checks"]) == ["loss_gap", "grad_gap", "update_gap"]
    assert res["device"]["count"] == 1


def test_program_bf16_path_is_not_correct():
    """The program's own bfloat16 kernel path, where the configuration
    states float32, fails the comparison."""
    res = run_tiny(CELL, precision="bf16")
    assert res["correct"] is False
    assert any(v["value"] > v["limit"] for v in res["checks"].values())


@pytest.mark.parametrize("mode", ["high", "bf16"])
def test_reference_at_lower_precision_is_not_correct(mode):
    """The control: the reference itself, put in the program's place and
    computed at a lower precision, fails at least one number."""
    c = tiny_cell(CELL)
    cfg, traffic = c["config"], c["traffic"]
    seed = 2 ** 32 + 77
    pool = scenes.scene_pool(seed, traffic["pool_scenes"], cfg)
    params0 = jax.tree.map(
        lambda a: jax.device_get(a),
        weights.make_weights(cfg, weights.seed_key(seed)))
    keys = WIN.step_keys(seed, WIN.CHECKED_STEPS)
    ref = registry.reference(cfg["reference"])
    _, batches, rkeys = WIN.reference_batches(cfg, traffic, pool, keys,
                                              WIN.CHECKED_STEPS)
    want = ref.train(params0, batches, rkeys, cfg, mode="highest")
    low = ref.train(params0, batches, rkeys, cfg, mode=mode)
    ok, table = check.judge(
        check.gaps(calibrate.as_checked(low), want, params0), c["limits"])
    assert not ok, table
