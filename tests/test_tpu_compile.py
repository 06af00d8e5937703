"""Compile-only tests for the TPU v5e: the fused kernels and the main-path
programs at real widths, lowered and compiled for a described (not
attached) chip.  Interpret mode cannot catch what these catch: stores the
chip cannot do, blocks not aligned to its tiling, and more VMEM than a
kernel may use.  Nothing runs, so nothing here is a result or a time.

The topology is described inside the ``topo`` fixture only (never at
import): one process holds the TPU compiler library at a time.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import repro.kernels.runtime as runtime
from repro.core import message_passing as mp
from repro.core.graph import make_graph
from repro.core.mlp import init_mlp
from repro.kernels import ops
from repro.kernels.edge_message import (EdgeLayout, LayoutMeta,
                                        layout_capacity, pick_windows)

N = 8192  # Water-3D
FLUID113K = 113_000
HID = 64
DEG = 12  # edges per node at r = 0.035 in data/fluid.py scenes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def chip(topo, monkeypatch):
    """One described chip; kernels compile (not interpret) and no
    persistent-cache entry is written that a chip-less process cannot
    read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(runtime, "default_interpret", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()  # no interpret-mode trace may be reused
    yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", prev)


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _edge_args(n, hid, precision, sharding, batch=2):
    """Shapes of a vmapped edge pathway call with host layouts, as the
    trainer issues it (batched scalar-prefetch operands)."""
    e = DEG * n
    window, swindow, n_pad = pick_windows(n)
    cap = layout_capacity(e, n_pad // window, n_pad // swindow,
                          mp.EDGE_KERNEL_BLOCK_E)
    lp = {"phi1": init_mlp(jax.random.PRNGKey(0), [2 * hid + 1, hid, hid]),
          "gate": init_mlp(jax.random.PRNGKey(1), [hid, hid, 1],
                           final_bias=False)}
    b = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        (batch,) + shape, dt, sharding=sharding)
    graph = dict(x=b((n, 3)), h=b((n, hid)), snd=b((e,), jnp.int32),
                 rcv=b((e,), jnp.int32), em=b((e,)))
    lay = dict(senders=b((cap,), jnp.int32), receivers=b((cap,), jnp.int32),
               edge_mask=b((cap,)),
               block_rwin=b((cap // mp.EDGE_KERNEL_BLOCK_E,), jnp.int32),
               block_swin=b((cap // mp.EDGE_KERNEL_BLOCK_E,), jnp.int32))
    meta = LayoutMeta(window, swindow, n_pad, mp.EDGE_KERNEL_BLOCK_E)
    spec = mp.EdgeSpec(coord_clamp=100.0, precision=precision)

    def loss(lp, graph, lay):
        def one(gr, ly):
            g = make_graph(gr["x"], None, gr["h"], gr["snd"], gr["rcv"],
                           edge_mask=gr["em"])
            # the kernel itself, whatever kernel_supported would say
            dx, mh = ops.edge_pathway(lp, g.h, g.x, g, spec,
                                      layout=EdgeLayout(**ly, meta=meta))
            return jnp.sum(dx) + jnp.sum(mh)
        return jnp.sum(jax.vmap(one)(graph, lay))

    def step(lp, graph, lay):
        return jax.value_and_grad(loss)(lp, graph, lay)

    return step, lp, graph, lay, spec


@pytest.mark.parametrize("n", [N, FLUID113K])
@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_edge_fwd_bwd_compiles(chip, n, precision):
    step, lp, graph, lay, spec = _edge_args(n, HID, precision, chip)
    g = make_graph(jnp.zeros((n, 3)), None, jnp.zeros((n, HID)))
    assert mp.kernel_supported(lp, g, spec)
    _compile(step, _shapes(lp, chip), graph, lay)


@pytest.mark.parametrize("hid,precision", [(256, "f32"), (320, "f32"),
                                           (512, "bf16"), (576, "bf16")])
def test_edge_eligibility_agrees_with_compiler(chip, hid, precision):
    """Either side of the VMEM budget: what kernel_supported admits
    compiles, and what it refuses does not."""
    step, lp, graph, lay, spec = _edge_args(N, hid, precision, chip)
    g = make_graph(jnp.zeros((N, 3)), None, jnp.zeros((N, hid)))
    admitted = mp.kernel_supported(lp, g, spec)
    try:
        _compile(step, _shapes(lp, chip), graph, lay)
        compiles = True
    except Exception as e:  # noqa: BLE001
        assert "vmem" in str(e).lower(), e
        compiles = False
    assert admitted == compiles, (hid, precision, admitted)


def test_virtual_fwd_bwd_compiles(chip):
    from repro.core.virtual_nodes import VirtualState, init_virtual_block

    c = 3
    vb = init_virtual_block(jax.random.PRNGKey(0), c, HID, HID, HID)
    s = jnp.zeros((c, HID))

    def loss(vb, x, h, z, mv, mask):
        out = ops.virtual_pathway(vb, h, x, VirtualState(z=z, s=s), mv, mask)
        return sum(jnp.sum(o) for o in out)

    f = jax.value_and_grad(loss, argnums=(0, 1, 2, 3))
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=chip)
    _compile(f, _shapes(vb, chip), sds((N, 3)), sds((N, HID)), sds((c, 3)),
             sds((c, c)), sds((N,)))


def test_mmd_fwd_bwd_compiles(chip):
    f = jax.value_and_grad(lambda x, z, w: ops.mmd_cross(x, z, w, 1.5),
                           argnums=(0, 1))
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=chip)
    _compile(f, sds((N, 3)), sds((3, 3)), sds((N,)))


def test_fast_egnn_value_and_grad_compiles(chip):
    """One whole FastEGNN training objective at Water-3D scale, fused."""
    from repro.pipeline import build_pipeline
    from repro.training.trainer import TrainConfig

    pipe = build_pipeline("fast_egnn", jax.random.PRNGKey(0),
                          train_cfg=TrainConfig(lam_mmd=0.03), n_layers=4,
                          hidden=HID, h_in=1, n_virtual=3, s_dim=HID,
                          use_kernel=True)
    e = DEG * N
    x = np.random.default_rng(0).uniform(0, 1, (N, 3)).astype(np.float32)
    g = make_graph(x, None, np.ones((N, 1), np.float32),
                   np.zeros(e, np.int32), np.zeros(e, np.int32))
    batch = jax.tree.map(lambda a: a[None], g)

    def loss(params, graph):
        def one(gr):
            x_pred, _ = pipe.apply_full(params, pipe.cfg, gr)
            return jnp.mean((x_pred - gr.x) ** 2)
        return jnp.mean(jax.vmap(one)(graph))

    mp.reset_dispatch_counts()
    compiled = _compile(jax.value_and_grad(loss), _shapes(pipe.params, chip),
                        _shapes(batch, chip))
    counts = mp.dispatch_counts()
    assert counts.get("edge_kernel") == 4 and not counts.get("edge_jnp")
    assert counts.get("virtual_kernel") == 4 and not counts.get("virtual_jnp")
    assert compiled.as_text().count("tpu_custom_call") >= 16


def test_dist_egnn_forward_compiles_on_four_chips(topo, chip):
    """The 4-shard DistEGNN forward over a described 2x2 mesh: each shard
    a quarter of a Water-3D scene, with host layouts."""
    from repro.distributed.dist_egnn import (GRAPH_AXIS, ShardedBatch,
                                             build_dist_apply)
    from repro.models.fast_egnn import FastEGNNConfig, init_fast_egnn

    d, n_cap = 4, N // 4
    e_cap = DEG * n_cap
    window, swindow, n_pad = pick_windows(n_cap)
    cap = layout_capacity(e_cap, n_pad // window, n_pad // swindow,
                          mp.EDGE_KERNEL_BLOCK_E)
    mesh = Mesh(np.array(topo.devices[:d]), (GRAPH_AXIS,))
    sharded = NamedSharding(mesh, P(GRAPH_AXIS))
    s = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        (d, 1) + shape, dt, sharding=sharded)
    nb = cap // mp.EDGE_KERNEL_BLOCK_E
    sb = ShardedBatch(
        x=s((n_cap, 3)), v=s((n_cap, 3)), h=s((n_cap, 1)),
        senders=s((e_cap,), jnp.int32), receivers=s((e_cap,), jnp.int32),
        node_mask=s((n_cap,)), edge_mask=s((e_cap,)), x_target=s((n_cap, 3)),
        lay_senders=s((cap,), jnp.int32), lay_receivers=s((cap,), jnp.int32),
        lay_edge_mask=s((cap,)), lay_block_rwin=s((nb,), jnp.int32),
        lay_block_swin=s((nb,), jnp.int32))
    cfg = FastEGNNConfig(n_layers=4, hidden=HID, h_in=1, n_virtual=3,
                         s_dim=HID, use_kernel=True)
    params = _shapes(init_fast_egnn(jax.random.PRNGKey(0), cfg),
                     NamedSharding(mesh, P()))
    mp.reset_dispatch_counts()
    compiled = build_dist_apply(cfg, mesh).lower(params, sb).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    counts = mp.dispatch_counts()
    assert counts.get("edge_layout_host") and not counts.get(
        "edge_layout_regroup")
    assert not counts.get("edge_jnp") and not counts.get("virtual_jnp")


def test_train_step_op_names_carry_kernel_passes_by_layer(chip):
    """The compiled FastEGNN train step keeps, in its ``op_name`` metadata,
    each kernel pass's ``pallas_call`` name under the scope of its layer
    and pathway, and the loss and optimizer scopes: a profile of the chip
    tells the passes and layers apart by them."""
    import re

    from repro.data.nbody import generate_nbody_dataset
    from repro.pipeline import build_pipeline
    from repro.training.trainer import TrainConfig, build_train_step

    layers = 2
    pipe = build_pipeline("fast_egnn", jax.random.PRNGKey(0),
                          train_cfg=TrainConfig(lam_mmd=0.03),
                          n_layers=layers, hidden=HID, h_in=1, n_virtual=3,
                          s_dim=HID, use_kernel=True)
    batch = pipe.make_batches(generate_nbody_dataset(2, n_nodes=24, seed=0),
                              2)[0]
    step, _ = build_train_step(pipe.apply_full, pipe.cfg, pipe.train_cfg,
                               pipe.opt)
    args = (pipe.params, pipe.opt.init(pipe.params), batch,
            jax.random.PRNGKey(1))
    text = step.lower(*_shapes(args, chip)).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    kernel_calls = [o for o in op_names if o.endswith("/pallas_call")]
    passes = {"edge_pathway": ("edge_pathway_fused_fwd",
                               "edge_pathway_bwd_fused_recv",
                               "edge_pathway_bwd_fused_send"),
              "virtual_pathway": ("virtual_pathway_fused_fwd",
                                  "virtual_pathway_bwd_fused_grads")}
    for k in range(layers):
        for scope, names in passes.items():
            for name in names:
                pat = re.compile(rf"\blayer_{k}\)*/{scope}/.*/{name}/")
                assert any(pat.search(o) for o in kernel_calls), (k, name)
    for name in ("mmd_cross_sum_fwd", "mmd_cross_grads_bwd"):
        assert any(re.search(rf"\bmmd_loss\)*/.*/{name}/", o)
                   for o in kernel_calls), name
    assert any("mse_loss" in o for o in op_names)
    assert any(o.startswith("jit(train_step)/adam_update/")
               for o in op_names)


def _batched(sharding, batch=2):
    """Shapes with a leading batch axis on the described chip."""
    return lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        (batch,) + shape, dt, sharding=sharding)


def _layout_shapes(b):
    """A batch of host banded layouts at Water-3D scale, as shapes."""
    window, swindow, n_pad = pick_windows(N)
    be = mp.EDGE_KERNEL_BLOCK_E
    cap = layout_capacity(DEG * N, n_pad // window, n_pad // swindow, be)
    return EdgeLayout(b((cap,), jnp.int32), b((cap,), jnp.int32), b((cap,)),
                      b((cap // be,), jnp.int32), b((cap // be,), jnp.int32),
                      meta=LayoutMeta(window, swindow, n_pad, be))


def _tfn_train_args(sharding, precision):
    """The FastTFN train step at Water-3D scale and its operands' shapes,
    as ``Pipeline.train_step`` gets them: a layout-carrying
    ``GraphBatch``."""
    from repro.data.loader import GraphBatch
    from repro.pipeline import build_pipeline
    from repro.training.trainer import TrainConfig, build_train_step

    pipe = build_pipeline("fast_tfn", jax.random.PRNGKey(0),
                          train_cfg=TrainConfig(lam_mmd=0.03),
                          n_layers=4, hidden=HID, h_in=1, n_virtual=3,
                          s_dim=HID, rbf_cutoff=0.035, use_kernel=True,
                          precision=precision)
    b = _batched(sharding)
    graph = make_graph(jnp.zeros((N, 3)), None, jnp.zeros((N, 1)),
                       jnp.zeros(DEG * N, jnp.int32),
                       jnp.zeros(DEG * N, jnp.int32))
    gb = GraphBatch(jax.tree.map(lambda a: b(a.shape, a.dtype), graph),
                    b((N, 3)), _layout_shapes(b), b(()))
    step, _ = build_train_step(pipe.apply_full, pipe.cfg, pipe.train_cfg,
                               pipe.opt)
    args = (_shapes(pipe.params, sharding),
            _shapes(pipe.opt.init(pipe.params), sharding), gb,
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=sharding))
    return step, args


@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_fast_tfn_train_step_compiles_fused(chip, precision):
    """The whole FastTFN train step at Water-3D scale, as the trainer
    calls it: the fused TFN edge kernel's three passes and the virtual
    kernel in every layer, the MMD kernel in the objective."""
    import re

    mp.reset_dispatch_counts()
    step, args = _tfn_train_args(chip, precision)
    text = step.lower(*args).compile().as_text()
    counts = mp.dispatch_counts()
    assert counts.get("tfn_edge_kernel") == 4 and not counts.get(
        "tfn_edge_jnp"), counts
    assert counts.get("virtual_kernel") == 4 and counts.get("mmd_kernel")
    kernel_calls = set(re.findall(r'op_name="([^"]*/pallas_call)"', text))
    for k in range(4):
        for name in ("tfn_edge_fused_fwd", "tfn_edge_bwd_fused_recv",
                     "tfn_edge_bwd_fused_send"):
            pat = re.compile(rf"\blayer_{k}\)*/edge_pathway/.*/{name}/")
            assert any(pat.search(o) for o in kernel_calls), (k, name)


def _tfn_edge_step(hid, sharding):
    """A vmapped value-and-grad of the TFN edge pathway alone at Water-3D
    scale with host layouts, and its operands' shapes."""
    b = _batched(sharding)
    radial = init_mlp(jax.random.PRNGKey(0), [16 + hid, hid, 6])

    def loss(radial, x, h, v, lay):
        def one(x, h, v, lay):
            dx, h_agg = ops.tfn_edge_pathway(radial, h, x, v, lay,
                                             cutoff=0.035, clamp=100.0)
            return jnp.sum(dx) + jnp.sum(h_agg)
        return jnp.sum(jax.vmap(one)(x, h, v, lay))

    return (jax.value_and_grad(loss, argnums=(0, 1, 2, 3)),
            (_shapes(radial, sharding), b((N, 3)), b((N, hid)), b((N, 3)),
             _layout_shapes(b)))


@pytest.mark.parametrize("hid", [384, 448])
def test_tfn_eligibility_agrees_with_compiler(chip, hid):
    """Either side of the VMEM budget (f32): what the TFN kernel's model
    admits compiles, and what it refuses does not; hidden 64 is admitted
    at Water-3D scale and at 113K."""
    from repro.kernels.edge_message import VMEM_LIMIT_BYTES
    from repro.kernels.tfn_edge import tfn_edge_vmem_bytes

    for n in (N, FLUID113K):
        assert tfn_edge_vmem_bytes(n, HID, 16) <= VMEM_LIMIT_BYTES
    admitted = tfn_edge_vmem_bytes(N, hid, 16) <= VMEM_LIMIT_BYTES
    step, args = _tfn_edge_step(hid, chip)
    try:
        _compile(step, *args)
        compiles = True
    except Exception as e:  # noqa: BLE001
        assert "vmem" in str(e).lower(), e
        compiles = False
    assert admitted == compiles, (hid, admitted)
