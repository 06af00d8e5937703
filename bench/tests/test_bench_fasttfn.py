"""The FastTFN cell at a tiny size on the CPU: a sound run is correct, the
program's first steps follow the reference, and the check sees the
reference with its cross-product path left out, or computed in a lower
precision."""
import time

import jax
import pytest

from bench import calibrate, check, driver, registry
from bench.tests.tiny import result_json

CELL = "water3d.train_fasttfn"
WIN = registry.window("train_fasttfn")
#: tiny sizes for this cell: scenes of a few hundred particles
TINY = dict(cfg=dict(n_particles=300, edge_cap=3072),
            traffic=dict(pool_scenes=6, batch=2))
SEED = 2 ** 33 + 29


def tiny_cell() -> dict:
    c = registry.cell(CELL)
    c["config"].update(TINY["cfg"])
    c["traffic"].update(TINY["traffic"])
    c["limits"] = {k: v["limit"] for k, v in c["limits"]["limits"].items()}
    return c


@pytest.fixture(scope="module")
def kept():
    """The program's first steps at the tiny size (the timed path's set-up),
    kept as the check keeps them."""
    c = tiny_cell()
    return c, WIN.Warm(c["config"], c["traffic"], SEED,
                       WIN.CHECKED_STEPS).keep()


def test_tiny_run_is_correct():
    """Through ``driver.run_cell``: correct, the three training numbers,
    both end-to-end metrics, and the fused TFN edge and virtual kernels in
    the step."""
    from bench.run import result_line
    from repro.core import message_passing as mp

    c = tiny_cell()
    res = result_json(driver.run_cell(
        c["config"], c["traffic"], c["limits"], seed=SEED, seconds=0.5,
        trace=False, t_start=time.perf_counter()))
    counts = mp.dispatch_counts()
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > WIN.CHECKED_STEPS
    assert res["compile"]["window_compiles"] == 0
    assert set(res["metrics"]) == {"setup_s", "train_scenes_per_s"}
    line = result_line(res, registry.cell(CELL), trace=False)
    assert list(line["checks"]) == ["loss_gap", "grad_gap", "update_gap"]
    layers = c["config"]["n_layers"]
    assert counts.get("tfn_edge_kernel") == layers, counts
    assert not counts.get("tfn_edge_jnp"), counts
    assert counts.get("virtual_kernel") == layers, counts


def test_program_follows_the_reference(kept):
    """``build_pipeline("fast_tfn")``'s train step against
    ``reference/fast_tfn.py`` from the same weights and keys: the first
    loss, the first gradient and the parameters' change over three steps."""
    c, k = kept
    values, ref_out = WIN.reference_gaps(c["config"], c["traffic"], k.pool,
                                         k.keys, k.params0, k.checked)
    steps = check.step_loss_gaps(k.checked, ref_out)
    # step 1: one forward at the same weights in f32, summed in another
    # order over ~3K edges and 300 particles: a few ulps
    assert steps[0] < 1e-5, steps
    # the first gradient: the same, through the backward's longer chain
    assert values["grad_gap"] < 5e-5, values
    # three Adam steps: normalised updates of near-zero gradient entries
    # carry their round-off into whole steps (bench/check.py), so the
    # change is held to the cell's own limit
    assert values["update_gap"] < c["limits"]["update_gap"], values
    assert values["loss_gap"] < c["limits"]["loss_gap"], values


def test_reference_without_cross_product_is_not_correct(kept):
    """The reference with its cross-product path left out (a configuration
    key of the reference alone) reads past a limit of the check."""
    c, k = kept
    values, _ = WIN.reference_gaps(c["config"], c["traffic"], k.pool,
                                   k.keys, k.params0, k.checked,
                                   left_out="cross")
    ok, table = check.judge(values, c["limits"])
    assert not ok, table


@pytest.mark.parametrize("mode", ["high", "bf16"])
def test_reference_at_lower_precision_is_not_correct(kept, mode):
    """The control: the reference itself, put in the program's place and
    computed at a lower precision, fails at least one number."""
    c, k = kept
    cfg, traffic = c["config"], c["traffic"]
    ref, batches, rkeys = WIN.reference_batches(cfg, traffic, k.pool, k.keys,
                                                WIN.CHECKED_STEPS)
    want = ref.train(k.params0, batches, rkeys, cfg, mode="highest")
    low = ref.train(k.params0, batches, rkeys, cfg, mode=mode)
    ok, table = check.judge(
        check.gaps(calibrate.as_checked(low), want, k.params0), c["limits"])
    assert not ok, table


def test_weights_match_the_program_layout():
    """The window's FastTFN weights have the program's parameter tree."""
    from repro.models.registry import resolve_model

    cfg = tiny_cell()["config"]
    _, params, _ = resolve_model("fast_tfn", jax.random.PRNGKey(0),
                                 **{k: cfg[k] for k in WIN._WIDTH_KEYS})
    made = WIN.make_weights(cfg, jax.random.PRNGKey(3))
    shapes = lambda t: jax.tree.map(lambda a: a.shape, t)
    assert shapes(made) == shapes(params)


def test_tfn_edge_counts_by_hand():
    from bench.work import tfn_edge as work

    # 5 nodes, 8 edges, hidden 2, 3 radial bases.  Per edge: vector,
    # length and direction 13; bases 4*3 = 12; radial network
    # 2*5*2+2 = 22 and 2*2*6+6 = 30; paths 5+3+3+9+3+9+3+9+1 = 45; sums
    # 3+2+1 = 6  ->  128.  Per node: the degree mean 3+2+1 = 6.
    assert work.forward_flops(5, 8, 2, 3) == 8 * 128 + 5 * 6
    assert work.backward_flops(5, 8, 2, 3) == 2 * (8 * 128 + 5 * 6)
    # weights: 5*2+2 + 2*6+6  ->  30
    assert work.weight_count(2, 3) == 30
    # forward: read x, v, h (5*8), endpoints (2*8), weights (30); write the
    # two means and the degree (5*6)
    assert work.forward_bytes(5, 8, 2, 3) == 4 * (40 + 16 + 30 + 30)
    # backward: those reads and the cotangents and degree (5*6); write the
    # gradients of x, v, h (5*8) and of the weights (30)
    assert work.backward_bytes(5, 8, 2, 3) == 4 * (40 + 16 + 30 + 30 + 40
                                                    + 30)


def _ctx(device_ops):
    from bench import trace as T

    cfg = dict(registry.cell(CELL)["config"], n_layers=1)
    ctx = T.Context(trace=T.Trace([device_ops], [T.Event("block", 0, 1e6,
                                                          "")]),
                    cfg=cfg, traffic=dict(batch=2), window_s=1e-3, chips=1,
                    peaks=registry.peaks("TPU v5 lite"), pool=[],
                    batch_index=[0], n_batches=1, steps=1)
    ctx.memo["shard_sizes"] = [[[(100, 800)], [(100, 900)]]]
    return ctx


def test_tfn_roofline_reader_by_hand():
    """Three passes a layer and scene, named inside ``vmap`` by their
    wrapping ``closed_call``: least time over their summed time.  The
    virtual kernel is not one of them; a count off the layers x scenes
    grid reads nothing."""
    from bench import trace as T
    from bench.work import tfn_edge as work

    read = registry.metric_reader("tfn_edge_kernel_roofline.train")
    call = lambda k: T.op_event(
        f"%closed_call.{k} = f32[8]{{0}} fusion(), kind=kCustom",
        1000 * k, 1000 * k + 500)
    virtual = T.op_event("%virtual_pathway_fused_fwd.1 = custom-call()",
                         9000, 9900)
    ctx = _ctx([call(k) for k in range(6)] + [virtual])
    p = ctx.peaks
    h, r = ctx.cfg["hidden"], ctx.cfg["n_rbf"]
    least = sum(max(work.forward_flops(100, e, h, r) / p["bf16_flops"],
                    work.forward_bytes(100, e, h, r) / p["hbm_bytes_per_s"])
                + max(work.backward_flops(100, e, h, r) / p["bf16_flops"],
                      work.backward_bytes(100, e, h, r)
                      / p["hbm_bytes_per_s"])
                for e in (800, 900))
    assert read(ctx) == pytest.approx(100 * least / (6 * 500e-9))
    assert read(_ctx([call(k) for k in range(5)])) is None
    assert read(_ctx([virtual])) is None
