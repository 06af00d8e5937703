"""Per-kernel shape/dtype sweeps: pallas_call(interpret=True) ≍ ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import walkcases

from repro.core import message_passing as mp
from repro.core.graph import make_graph
from repro.core.mlp import init_mlp
from repro.core.virtual_nodes import (VirtualState, init_virtual_block,
                                      real_from_virtual, virtual_global_message,
                                      virtual_messages, virtual_node_sums)
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.edge_message import edge_pathway_fused
from repro.kernels.mmd_rbf import mmd_cross_sum
from repro.kernels.virtual_message import virtual_pathway_fused


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n,c,dh,hid", [(64, 1, 8, 16), (100, 3, 32, 32),
                                        (257, 10, 16, 64), (512, 5, 64, 64)])
def test_virtual_pathway_kernel_shapes(n, c, dh, hid):
    ks = jax.random.split(jax.random.PRNGKey(n + c), 8)
    x = jax.random.normal(ks[0], (n, 3))
    h = jax.random.normal(ks[1], (n, dh))
    z = jax.random.normal(ks[2], (c, 3))
    s = jax.random.normal(ks[3], (c, 16))
    mask = (jax.random.uniform(ks[4], (n,)) > 0.1).astype(jnp.float32)
    mv = virtual_global_message(z, x.mean(0))
    vb = init_virtual_block(ks[5], c, dh, 16, hid)
    vs = VirtualState(z=z, s=s)

    w = kops.unpack_virtual_block(vb, s, mv, dh)
    flat = (x, h, z, mask, w["w1h"], w["w1d"], w["const1"], w["w2"], w["b2"],
            w["wg1"], w["bg1"], w["wg2"], w["wz1"], w["bz1"], w["wz2"])
    got = virtual_pathway_fused(*flat, block_n=128, interpret=True)
    want = ref.virtual_pathway_ref(*flat)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-4, atol=1e-4)

    # and both match the model's jnp path
    msgs = virtual_messages(vb, h, x, vs, mv)
    dx, mh = real_from_virtual(vb, x, vs, msgs)
    dz, ms = virtual_node_sums(vb, x, vs, msgs, mask)
    for g, r in zip(got, (dx, mh, dz, ms)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-3, atol=1e-3)


def test_virtual_pathway_kernel_grads():
    n, c, dh, hid = 96, 3, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    x = jax.random.normal(ks[0], (n, 3))
    h = jax.random.normal(ks[1], (n, dh))
    z = jax.random.normal(ks[2], (c, 3))
    s = jax.random.normal(ks[3], (c, 8))
    mask = jnp.ones((n,))
    mv = virtual_global_message(z, x.mean(0))
    vb = init_virtual_block(ks[5], c, dh, 8, hid)
    vs = VirtualState(z=z, s=s)

    def loss_kernel(vb, x):
        dx, mh, dz, ms = kops.virtual_pathway(vb, h, x, vs, mv, mask)
        return jnp.sum(dx**2) + jnp.sum(mh**2) + jnp.sum(dz**2) + jnp.sum(ms**2)

    def loss_jnp(vb, x):
        m = virtual_messages(vb, h, x, vs, mv)
        dx, mh = real_from_virtual(vb, x, vs, m)
        dz, ms = virtual_node_sums(vb, x, vs, m, mask)
        return jnp.sum(dx**2) + jnp.sum(mh**2) + jnp.sum(dz**2) + jnp.sum(ms**2)

    gk = jax.grad(loss_kernel, argnums=(0, 1))(vb, x)
    gj = jax.grad(loss_jnp, argnums=(0, 1))(vb, x)

    def assert_close(a, b):
        scale = float(jnp.max(jnp.abs(b))) + 1e-6
        np.testing.assert_allclose(np.asarray(a) / scale, np.asarray(b) / scale,
                                   rtol=1e-4, atol=1e-5)

    jax.tree.map(assert_close, gk, gj)


# ------------------------------------------------------------- edge pathway
def _edge_graph(n, e, dh, seed=0, csr=True, masked=True):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (n, 3))
    h = jax.random.normal(ks[1], (n, dh)) if dh else jnp.zeros((n, 0))
    snd = jax.random.randint(ks[2], (e,), 0, n)
    rcv = jax.random.randint(ks[3], (e,), 0, n)
    if csr:  # the data layer's CSR contract (padding tail handled via mask)
        order = jnp.argsort(rcv)
        snd, rcv = snd[order], rcv[order]
    em = ((jax.random.uniform(ks[4], (e,)) > 0.25).astype(jnp.float32)
          if masked else jnp.ones((e,)))
    g = make_graph(x, None, h, snd, rcv, edge_mask=em)
    return x, h, g, ks[5]


_EDGE_SPECS = {
    "egnn": mp.EdgeSpec(use_h=True, use_d2=True, gate="mlp", rel="raw",
                        coord_clamp=100.0),
    "schnet": mp.EdgeSpec(use_h=True, use_d2=True, gate="identity",
                          rel="raw", coord_clamp=100.0),
    "rf": mp.EdgeSpec(use_h=False, use_d2=True, gate="identity",
                      rel="inv1p", coord_clamp=100.0),
    "mpnn": mp.EdgeSpec(use_h=True, use_d2=False, gate="none"),
}


def _edge_params(key, dh, hid, spec):
    n_in = (2 * dh if spec.use_h else 0) + (1 if spec.use_d2 else 0)
    width = hid if spec.gate == "mlp" or spec.gate == "none" else 1
    lp = {"phi1": init_mlp(key, [n_in, hid, width],
                           final_bias=spec.gate != "identity")}
    if spec.gate == "mlp":
        lp["gate"] = init_mlp(jax.random.fold_in(key, 1), [hid, hid, 1],
                              final_bias=False)
    return lp


@pytest.mark.parametrize("variant", sorted(_EDGE_SPECS))
@pytest.mark.parametrize("n,e,dh,hid,block", [
    (33, 70, 4, 16, 32), (128, 400, 16, 32, 128), (257, 900, 8, 64, 256)])
def test_edge_pathway_kernel_matches_jnp(variant, n, e, dh, hid, block):
    spec = _EDGE_SPECS[variant]
    x, h, g, kp = _edge_graph(n, e, dh if spec.use_h else 0, seed=n + e)
    lp = _edge_params(kp, dh, hid, spec)
    assert mp.kernel_supported(lp, g, spec)
    want = mp.edge_pathway(lp, h, x, g, spec)

    hk, ws = kops.unpack_edge_params(lp, h, spec)
    got = edge_pathway_fused(
        x, hk, g.senders, g.receivers, g.edge_mask, *ws,
        gate_mode=spec.gate, rel_mode=spec.rel, clamp=spec.coord_clamp,
        block_e=block, interpret=True)
    oracle = ref.edge_pathway_ref(
        x, hk, g.senders, g.receivers, g.edge_mask, *ws,
        gate_mode=spec.gate, rel_mode=spec.rel, clamp=spec.coord_clamp)
    for k, r in zip(got, oracle):
        np.testing.assert_allclose(np.asarray(k), np.asarray(r),
                                   rtol=1e-4, atol=1e-4)
    if spec.gate != "none":
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want.dx),
                                   rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want.mh),
                               rtol=1e-4, atol=1e-4)


def test_edge_pathway_kernel_empty_graph():
    """p=1.0 edge dropping: zero edges must yield zero updates, no NaNs."""
    spec = _EDGE_SPECS["egnn"]
    x, h, g, kp = _edge_graph(12, 0, 4, seed=3)
    lp = _edge_params(kp, 4, 16, spec)
    out = mp.edge_pathway(lp, h, x, g, spec, use_kernel=True)
    assert float(jnp.max(jnp.abs(out.dx))) == 0.0
    assert float(jnp.max(jnp.abs(out.mh))) == 0.0


def test_edge_pathway_kernel_all_edges_masked():
    spec = _EDGE_SPECS["egnn"]
    x, h, g, kp = _edge_graph(16, 40, 4, seed=4)
    g = g._replace(edge_mask=jnp.zeros_like(g.edge_mask))
    lp = _edge_params(kp, 4, 16, spec)
    out = mp.edge_pathway(lp, h, x, g, spec, use_kernel=True)
    np.testing.assert_allclose(np.asarray(out.dx), 0.0, atol=1e-7)
    np.testing.assert_allclose(np.asarray(out.mh), 0.0, atol=1e-7)


@pytest.mark.parametrize("variant", sorted(_EDGE_SPECS))
def test_edge_pathway_kernel_grads(variant):
    """custom_vjp (remat through the oracle) ≍ jnp-substrate gradients."""
    spec = _EDGE_SPECS[variant]
    dh = 8 if spec.use_h else 0
    x, h, g, kp = _edge_graph(48, 120, dh, seed=11)
    lp = _edge_params(kp, dh, 16, spec)

    def loss(use_kernel):
        def f(lp, x, h):
            o = mp.edge_pathway(lp, h, x, g, spec, use_kernel=use_kernel)
            t = jnp.sum(o.mh ** 2)
            if o.dx is not None:
                t = t + jnp.sum(o.dx ** 2)
            return t
        return f

    gk = jax.grad(loss(True), argnums=(0, 1, 2))(lp, x, h)
    gj = jax.grad(loss(False), argnums=(0, 1, 2))(lp, x, h)

    def assert_close(a, b):
        if b.size == 0:  # zero-width feature grads (geometry-only RF)
            return
        scale = float(jnp.max(jnp.abs(b))) + 1e-6
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale,
                                   rtol=1e-3, atol=1e-5)

    jax.tree.map(assert_close, gk, gj)


def test_edge_pathway_kernel_vmap_batch():
    """Batched (vmap) dispatch — the trainer's usage pattern."""
    spec = _EDGE_SPECS["egnn"]
    x, h, g, kp = _edge_graph(24, 60, 4, seed=5)
    lp = _edge_params(kp, 4, 16, spec)
    xb = jnp.stack([x, x + 0.1, x * 1.2])
    hb = jnp.stack([h, h * 0.5, h + 0.3])
    fk = jax.vmap(lambda x, h: mp.edge_pathway(lp, h, x, g, spec,
                                               use_kernel=True).dx)
    fj = jax.vmap(lambda x, h: mp.edge_pathway(lp, h, x, g, spec).dx)
    np.testing.assert_allclose(np.asarray(fk(xb, hb)), np.asarray(fj(xb, hb)),
                               rtol=1e-4, atol=1e-4)


def _skewed_graph(n, e, dh, seed=0):
    """Receiver-sorted graph with a power-law receiver-band distribution
    (some node windows carry ~30× the mean edge load) and senders drawn
    uniformly — so most edge blocks gather from sender windows far from
    their receiver window."""
    rng = np.random.default_rng(seed)
    rcv = np.minimum((n * rng.random(e) ** 3).astype(np.int64), n - 1)
    snd = rng.integers(0, n, e)
    rcv = np.sort(rcv)  # CSR contract
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(ks[0], (n, 3))
    h = jax.random.normal(ks[1], (n, dh)) if dh else jnp.zeros((n, 0))
    em = (rng.random(e) > 0.1).astype(np.float32)
    g = make_graph(x, None, h, snd.astype(np.int32), rcv.astype(np.int32),
                   edge_mask=em)
    return x, h, g


def test_edge_pathway_kernel_8k_skewed_bands():
    """Tentpole acceptance: fwd parity at n=8192 (past the old 4096 node
    ceiling), non-uniform receiver bands, senders outside the receiver
    window.  Multi-window tiling: 16 receiver × 2 sender windows."""
    n, e, dh, hid = 8192, 16384, 16, 32
    spec = _EDGE_SPECS["egnn"]
    x, h, g = _skewed_graph(n, e, dh, seed=8)
    lp = _edge_params(jax.random.PRNGKey(1), dh, hid, spec)
    assert mp.kernel_supported(lp, g, spec)

    hk, ws = kops.unpack_edge_params(lp, h, spec)
    got = edge_pathway_fused(
        x, hk, g.senders, g.receivers, g.edge_mask, *ws,
        gate_mode=spec.gate, rel_mode=spec.rel, clamp=spec.coord_clamp,
        interpret=True)
    want = ref.edge_pathway_ref(
        x, hk, g.senders, g.receivers, g.edge_mask, *ws,
        gate_mode=spec.gate, rel_mode=spec.rel, clamp=spec.coord_clamp)
    for k, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(k), np.asarray(r),
                                   rtol=1e-4, atol=1e-4)


def test_edge_pathway_kernel_8k_grads():
    """Grad parity (custom_vjp remat through the oracle) at n=8192."""
    n, e, dh, hid = 8192, 8192, 8, 16
    spec = _EDGE_SPECS["egnn"]
    x, h, g = _skewed_graph(n, e, dh, seed=9)
    lp = _edge_params(jax.random.PRNGKey(2), dh, hid, spec)
    assert mp.kernel_supported(lp, g, spec)

    def loss(use_kernel):
        def f(lp, x, h):
            o = mp.edge_pathway(lp, h, x, g, spec, use_kernel=use_kernel)
            return jnp.sum(o.mh ** 2) + jnp.sum(o.dx ** 2)
        return f

    gk = jax.grad(loss(True), argnums=(0, 1, 2))(lp, x, h)
    gj = jax.grad(loss(False), argnums=(0, 1, 2))(lp, x, h)

    def assert_close(a, b):
        scale = float(jnp.max(jnp.abs(b))) + 1e-6
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale,
                                   rtol=1e-3, atol=1e-5)

    jax.tree.map(assert_close, gk, gj)


def test_edge_pathway_kernel_vmap_above_old_ceiling():
    """vmap'd dispatch at n > 4096 (the old EDGE_KERNEL_MAX_NODES bound)."""
    n, e, dh, hid = 4608, 4096, 8, 16
    spec = _EDGE_SPECS["egnn"]
    x, h, g = _skewed_graph(n, e, dh, seed=10)
    lp = _edge_params(jax.random.PRNGKey(3), dh, hid, spec)
    assert mp.kernel_supported(lp, g, spec)
    xb = jnp.stack([x, x + 0.1])
    hb = jnp.stack([h, h * 0.5])
    fk = jax.vmap(lambda x, h: mp.edge_pathway(lp, h, x, g, spec,
                                               use_kernel=True).dx)
    fj = jax.vmap(lambda x, h: mp.edge_pathway(lp, h, x, g, spec).dx)
    np.testing.assert_allclose(np.asarray(fk(xb, hb)), np.asarray(fj(xb, hb)),
                               rtol=1e-4, atol=1e-4)


def test_edge_pathway_kernel_explicit_small_windows():
    """Sweep explicit (window, swindow) overrides: every tiling must hit
    the same oracle numbers, including blocks whose senders fall outside
    the (much narrower) receiver window."""
    n, e, dh, hid = 700, 1500, 8, 16
    spec = _EDGE_SPECS["schnet"]
    x, h, g = _skewed_graph(n, e, dh, seed=11)
    lp = _edge_params(jax.random.PRNGKey(4), dh, hid, spec)
    hk, ws = kops.unpack_edge_params(lp, h, spec)
    want = ref.edge_pathway_ref(
        x, hk, g.senders, g.receivers, g.edge_mask, *ws,
        gate_mode=spec.gate, rel_mode=spec.rel, clamp=spec.coord_clamp)
    for window, swindow in [(128, 128), (128, 256), (256, 512), (512, 512)]:
        got = edge_pathway_fused(
            x, hk, g.senders, g.receivers, g.edge_mask, *ws,
            gate_mode=spec.gate, rel_mode=spec.rel, clamp=spec.coord_clamp,
            block_e=64, window=window, swindow=swindow, interpret=True)
        for k, r in zip(got, want):
            np.testing.assert_allclose(np.asarray(k), np.asarray(r),
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"tiling {window}x{swindow}")


# ------------------------------------------- exact one-hot products (f32)
F32_MAX = np.finfo(np.float32).max
#: below 2**-103 the low piece can fall under f32's least normal, and
#: arithmetic that flushes subnormals (XLA's, here and on the TPU) drops it
EXACT_FLOOR = np.float32(2.0 ** -103)
#: subnormal f32: flushed to zero by that same arithmetic
SUBNORMALS = np.float32([1e-40, -3e-42, 1.4e-45, 1.1754942e-38])


def _wide_f32(rng, shape):
    """Signed f32 values log-uniform over 1e-30..1e30, with zeros of both
    signs, subnormals, the exactness floor, and the largest finite f32 and
    the values about bf16's overflow edge, which a rounding split would
    send to inf."""
    v = (10.0 ** rng.uniform(-30, 30, shape)).astype(np.float32)
    v *= rng.choice(np.float32([-1, 1]), shape)
    edge = np.float32((2.0 - 2.0 ** -8) * 2.0 ** 127)
    special = np.concatenate([
        np.float32([0.0, -0.0, F32_MAX, -F32_MAX, edge,
                    np.nextafter(edge, np.float32(0)), EXACT_FLOOR,
                    -EXACT_FLOOR]), SUBNORMALS])
    flat = v.reshape(-1)
    flat[:special.size] = special
    return v


def _assert_bitwise(got, want):
    """Bit for bit, where a zero may come back as either zero and a
    subnormal (which the arithmetic reads as zero) as a zero."""
    got, want = np.asarray(got), np.asarray(want)
    normal = np.abs(want) >= np.finfo(np.float32).tiny
    np.testing.assert_array_equal(got[normal].view(np.uint32),
                                  want[normal].view(np.uint32))
    assert np.all(got[~normal] == 0)


@pytest.mark.parametrize("jit", [False, True])
def test_split_pieces_round_trip_bitwise(jit):
    """hi + mid + lo gives back every finite f32 of magnitude 2**-103 or
    more bit for bit (a zero as +0, a subnormal as 0), compiled too."""
    from repro.kernels.edge_message import split_pieces

    v = _wide_f32(np.random.default_rng(0), (4096,))
    split = jax.jit(split_pieces, static_argnums=1) if jit else split_pieces
    pieces = split(jnp.asarray(v), 3)
    assert all(p.dtype == jnp.bfloat16 for p in pieces)
    hi, mid, lo = (p.astype(jnp.float32) for p in pieces)
    _assert_bitwise((hi + mid) + lo, v)


@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
def test_packed_onehot_gather_equals_indexing(compute):
    """A bf16 one-hot against the packed pieces, accumulated in f32,
    returns the gathered rows bitwise: the f32 values under f32 compute
    (three pieces), their bf16 rounding under bf16 compute (one)."""
    from repro.kernels.edge_message import _gather, onehot_pieces, pack

    rng = np.random.default_rng(1)
    h = _wide_f32(rng, (512, 64))
    x = _wide_f32(rng, (512, 3))
    if compute == "bfloat16":  # in range of the bf16 operands
        h, x = (np.where(np.abs(a) < 1e38, a, np.float32(1)) for a in (h, x))
    ids = rng.integers(0, 512, (128, 1)).astype(np.int32)
    cdt = jnp.dtype(compute)
    pieces = onehot_pieces(cdt)
    assert pieces == (3 if compute == "float32" else 1)
    packed = pack([jnp.asarray(h).astype(cdt), jnp.asarray(x).astype(cdt)],
                  pieces)
    assert packed.dtype == jnp.bfloat16 and packed.shape[1] % 128 == 0
    got = _gather(jnp.asarray(ids), packed, 67, pieces, jnp.float32)
    want = np.concatenate([h, x], axis=1)[ids[:, 0]]
    _assert_bitwise(got, jnp.asarray(want).astype(cdt).astype(jnp.float32))


@pytest.mark.parametrize("gate_mode", ["mlp", "identity", "none"])
def test_edge_kernel_multiwindow_fwd_and_vjp_match_ref(gate_mode):
    """Several receiver and several sender windows (4 x 2 bands): the
    packed-piece forward and both fused backward passes match the
    oracle and its vjp to f32 round-off."""
    from repro.kernels.edge_message import (edge_pathway_bwd_fused,
                                            pick_windows)

    n, e, dh, hid = 900, 2400, 8, 16
    spec = {"mlp": _EDGE_SPECS["egnn"], "identity": _EDGE_SPECS["schnet"],
            "none": _EDGE_SPECS["mpnn"]}[gate_mode]
    x, h, g = _skewed_graph(n, e, dh, seed=21)
    lp = _edge_params(jax.random.PRNGKey(22), dh, hid, spec)
    hk, ws = kops.unpack_edge_params(lp, h, spec)
    tiling = dict(window=256, swindow=512)
    window, swindow, n_pad = pick_windows(n, **tiling)
    assert n_pad // window == 4 and n_pad // swindow == 2
    kw = dict(gate_mode=spec.gate, rel_mode=spec.rel, clamp=spec.coord_clamp)
    args = (g.senders, g.receivers, g.edge_mask)

    def oracle(x, hk, *ws):
        return ref.edge_pathway_ref(x, hk, *args, *ws, **kw)

    want, vjp = jax.vjp(oracle, x, hk, *ws)
    got = edge_pathway_fused(x, hk, *args, *ws, interpret=True, **tiling,
                             **kw)
    ks = jax.random.split(jax.random.PRNGKey(23), 2)
    g_dx = jax.random.normal(ks[0], want[0].shape)
    g_mh = jax.random.normal(ks[1], want[1].shape)
    want_g = vjp((g_dx, g_mh, jnp.zeros_like(want[2])))
    got_g = edge_pathway_bwd_fused(x, hk, *args, *ws, got[2], g_dx, g_mh,
                                   interpret=True, **tiling, **kw)

    def close(a, b):
        if b.size == 0:
            return
        scale = float(jnp.max(jnp.abs(b))) + 1e-30
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, rtol=0, atol=1e-5)

    for a, b in zip(got, want):
        close(a, b)
    assert len(got_g) == len(want_g)
    for a, b in zip(got_g, want_g):
        close(a, b)


# ------------------------------------------------ live prefix of the walk
@pytest.mark.parametrize("batched", [False, True])
@pytest.mark.parametrize("case", walkcases.CASES)
def test_edge_kernel_live_walk_bitwise_full_walk(case, batched, walks_agree):
    """The FastEGNN edge kernel's forward and both backward passes give
    bit for bit what a walk of the whole static grid gives, on layouts
    with dead blocks, alone and under vmap over samples whose live counts
    differ."""
    from repro.kernels.edge_message import edge_pathway_bwd_fused

    spec = _EDGE_SPECS["egnn"]
    edges, lays, live, n_blocks = walkcases.walk_layouts(
        case, 2 if batched else 1)
    if case != "no_edges":
        assert max(live) < n_blocks, (live, n_blocks)
    if batched and case == "pads":
        assert live[0] != live[1], live
    lp = _edge_params(jax.random.PRNGKey(31), 8, 16, spec)
    ks = jax.random.split(jax.random.PRNGKey(32), 4)
    b = len(lays)
    n = walkcases.N
    x = jax.random.normal(ks[0], (b, n, 3))
    h = jax.random.normal(ks[1], (b, n, 8))
    g_dx = jax.random.normal(ks[2], (b, n, 3))
    g_mh = jax.random.normal(ks[3], (b, n, 16))
    snd, rcv, em = (jnp.asarray(np.stack(a)) for a in zip(*edges))
    lay = jax.tree.map(lambda *a: jnp.stack(a), *lays)
    kw = dict(gate_mode=spec.gate, rel_mode=spec.rel, clamp=spec.coord_clamp,
              block_e=walkcases.BE, interpret=True, **walkcases.TILING)

    def one(x, h, snd, rcv, em, lay, g_dx, g_mh):
        hk, ws = kops.unpack_edge_params(lp, h, spec)
        out = edge_pathway_fused(x, hk, snd, rcv, em, *ws, layout=lay, **kw)
        return out, edge_pathway_bwd_fused(x, hk, snd, rcv, em, *ws, out[2],
                                           g_dx, g_mh, layout=lay, **kw)

    args = (x, h, snd, rcv, em, lay, g_dx, g_mh)
    run = (lambda: jax.vmap(one)(*args)) if batched else \
        (lambda: one(*jax.tree.map(lambda a: a[0], args)))
    (dx, mh, deg), grads = walks_agree(run)
    assert np.all(np.isfinite(dx)) and np.all(np.isfinite(grads[0]))
    assert float(deg.sum()) == float(em.sum())


@pytest.mark.parametrize("n,c,sigma,block", [(100, 3, 1.5, 64), (1024, 10, 3.0, 256),
                                             (33, 1, 0.7, 1024)])
def test_mmd_kernel(n, c, sigma, block):
    ks = jax.random.split(jax.random.PRNGKey(n), 3)
    x = jax.random.normal(ks[0], (n, 3))
    z = jax.random.normal(ks[1], (c, 3))
    mask = (jax.random.uniform(ks[2], (n,)) > 0.2).astype(jnp.float32)
    got = mmd_cross_sum(x, z, mask, sigma=sigma, block_n=block, interpret=True)
    want = ref.mmd_cross_ref(x, z, mask, sigma)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


def test_mmd_loss_kernel_matches_core():
    from repro.core.mmd import mmd_loss
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(ks[0], (200, 3))
    z = jax.random.normal(ks[1], (5, 3))
    mask = jnp.ones((200,))
    np.testing.assert_allclose(
        float(kops.mmd_loss_kernel(z, x, mask, sigma=1.5)),
        float(mmd_loss(z, x, mask, sigma=1.5)), rtol=1e-5)


@pytest.mark.parametrize("sampled", [False, True])
def test_mmd_loss_use_kernel_parity_fwd_grad(sampled):
    """Satellite: ``mmd_loss(use_kernel=True)`` — the Pallas cross term
    under the same ``use_kernel``-style switch the edge pathway uses —
    matches the jnp form in value AND gradient (w.r.t. both z and x), with
    and without real-node sampling, and records its dispatch."""
    from repro.core import message_passing as mp
    from repro.core.mmd import mmd_loss

    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    x = jax.random.normal(ks[0], (150, 3))
    z = jax.random.normal(ks[1], (4, 3))
    mask = (jax.random.uniform(ks[2], (150,)) > 0.3).astype(jnp.float32)
    kw = dict(sigma=1.2)
    if sampled:
        kw.update(sample_size=8, key=ks[3])

    def loss(use_kernel):
        return lambda z, x: mmd_loss(z, x, mask, use_kernel=use_kernel, **kw)

    mp.reset_dispatch_counts()
    v_k, (gz_k, gx_k) = jax.value_and_grad(loss(True), argnums=(0, 1))(z, x)
    assert mp.dispatch_counts().get("mmd_kernel", 0) > 0
    v_j, (gz_j, gx_j) = jax.value_and_grad(loss(False), argnums=(0, 1))(z, x)
    np.testing.assert_allclose(float(v_k), float(v_j), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gz_k), np.asarray(gz_j),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gx_k), np.asarray(gx_j),
                               rtol=1e-4, atol=1e-6)


def test_combined_objective_use_kernel_parity():
    """The trainer-facing switch: ``combined_objective(use_kernel=True)``
    equals the jnp objective (the MMD route is the only difference)."""
    from repro.training.losses import combined_objective

    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    xp = jax.random.normal(ks[0], (64, 3))
    xt = xp + 0.1 * jax.random.normal(ks[1], (64, 3))
    z = jax.random.normal(ks[2], (3, 3))
    mask = jnp.ones((64,))
    out = {}
    for uk in (False, True):
        (l, aux), g = jax.value_and_grad(
            lambda z: combined_objective(xp, xt, mask, z, lam=0.5,
                                         mmd_sample=5, key=ks[3],
                                         use_kernel=uk),
            has_aux=True)(z)
        out[uk] = (float(l), float(aux["mmd"]), np.asarray(g))
    np.testing.assert_allclose(out[True][0], out[False][0], rtol=1e-5)
    np.testing.assert_allclose(out[True][1], out[False][1], rtol=1e-5)
    np.testing.assert_allclose(out[True][2], out[False][2],
                               rtol=1e-4, atol=1e-6)
