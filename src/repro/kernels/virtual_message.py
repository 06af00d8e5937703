"""Fused virtual-node pathway Pallas TPU kernel.

The hot loop of FastEGNN/DistEGNN (Sec. IV-D: N·C of the N·K+N·C total work;
after edge dropping it *is* the model).  The GPU reference implementation
materialises the (N, C, hidden) message tensor and runs 4 separate kernels
(dist² / φ2 / gather-scatter / reductions).  TPU-native redesign:

  * grid over blocks of BN real nodes; per step one HBM read of the block's
    (x, h) and NO HBM write of messages — all C-channel work happens in VMEM
    registers, raising arithmetic intensity from O(1) to O(C·hid) per byte;
  * the entire virtual state + per-channel MLP stacks live in VMEM for the
    whole grid (index_map → block 0: Pallas keeps them resident);
  * the virtual-side reductions (dz_sum, ms_sum — the tensors DistEGNN
    all-reduces) are accumulated across grid steps in the output block,
    exploiting TPU's sequential-grid guarantee;
  * the per-channel loop is unrolled at trace time (C ≤ 16) so the MXU sees
    C back-to-back (BN×Dh)·(Dh×hid) matmuls with hardware-aligned shapes
    (BN, hid multiples of 8×128 when the caller pads).

Fused backward (DESIGN.md §9): :func:`virtual_pathway_bwd_fused` walks the
same node-block grid, **recomputes** every per-channel activation (pre-silu
values, messages, both gate MLPs) in VMEM from the streamed (x, h) block —
no residuals beyond the primals — and backpropagates the four output
cotangents in one pass: per-node gradients (dL/dx, dL/dh) land in the
node-blocked outputs, while dL/dz and all twelve per-channel weight/bias
gradients accumulate across the sequential grid exactly like dz_sum/ms_sum
do on the forward.  Nothing of size (N, C, hidden) exists in either
direction.  The node mask participates as a multiplicative weight only and
is not differentiated (``ops.virtual_pathway`` returns a zero cotangent).

Both directions honour the static ``precision`` contract
(``kernels.runtime.Precision``): matmul operands in ``precision.compute``,
every reduction in ``precision.accumulate``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.edge_message import _mm, _silu_grad

Array = jax.Array


def _kernel(
    x_ref, h_ref, mask_ref, z_ref,
    w1h_ref, w1d_ref, c1_ref, w2_ref, b2_ref,
    wg1_ref, bg1_ref, wg2_ref, wz1_ref, bz1_ref, wz2_ref,
    dx_ref, mh_ref, dz_ref, ms_ref,
    *, compute: str, accum: str,
):
    i = pl.program_id(0)
    mm = functools.partial(_mm, cdt=jnp.dtype(compute), adt=jnp.dtype(accum))
    xb = x_ref[...]  # (BN, 3)
    hb = h_ref[...]  # (BN, Dh)
    mb = mask_ref[...]  # (BN, 1)
    z = z_ref[...]  # (C, 3)
    n_chan = z.shape[0]

    @pl.when(i == 0)
    def _init():
        dz_ref[...] = jnp.zeros_like(dz_ref)
        ms_ref[...] = jnp.zeros_like(ms_ref)

    dx_acc = jnp.zeros(dx_ref.shape, dx_ref.dtype)
    mh_acc = jnp.zeros(mh_ref.shape, mh_ref.dtype)
    # Unrolled per-channel pipeline: every channel owns its MLP weights
    # (ordered set / mutual distinctiveness — Sec. IV-A).
    for c in range(n_chan):
        rel = xb - z[c][None, :]  # (BN, 3)
        d2 = jnp.sum(rel * rel, axis=-1, keepdims=True)  # (BN, 1)
        t1 = mm(hb, w1h_ref[c]) + d2 * w1d_ref[c][None, :] + c1_ref[c][None, :]
        msg = mm(jax.nn.silu(t1), w2_ref[c]) + b2_ref[c][None, :]  # (BN, hid)
        gate_x = mm(jax.nn.silu(mm(msg, wg1_ref[c]) + bg1_ref[c][None, :]),
                    wg2_ref[c])
        gate_z = mm(jax.nn.silu(mm(msg, wz1_ref[c]) + bz1_ref[c][None, :]),
                    wz2_ref[c])
        dx_acc += (rel * gate_x).astype(dx_acc.dtype)
        mh_acc += msg.astype(mh_acc.dtype)
        dz_ref[c, :] += jnp.sum(-rel * gate_z * mb, axis=0).astype(dz_ref.dtype)
        ms_ref[c, :] += jnp.sum(msg * mb, axis=0).astype(ms_ref.dtype)
    dx_ref[...] = dx_acc / n_chan
    mh_ref[...] = mh_acc / n_chan


@functools.partial(jax.jit,
                   static_argnames=("block_n", "interpret", "precision"))
def virtual_pathway_fused(
    x: Array, h: Array, z: Array, node_mask: Array,
    w1h: Array, w1d: Array, const1: Array, w2: Array, b2: Array,
    wg1: Array, bg1: Array, wg2: Array,
    wz1: Array, bz1: Array, wz2: Array,
    *, block_n: int = 512, interpret: bool | None = None, precision=None,
):
    """See `repro.kernels.ref.virtual_pathway_ref` for the exact contract.

    ``interpret=None`` auto-detects (compile on TPU, interpret elsewhere);
    ``precision`` (static) selects the compute/accumulate dtype pair —
    outputs keep ``x.dtype``.
    """
    from repro.kernels.runtime import resolve_interpret, resolve_precision

    interpret = resolve_interpret(interpret)
    prec = resolve_precision(precision)
    n, dh = h.shape
    c, _, hid = w1h.shape
    out_dt = x.dtype
    # pad N to a multiple of block_n (mask zeroes the padded rows' sums)
    n_pad = -(-n // block_n) * block_n
    if n_pad != n:
        pad = n_pad - n
        x = jnp.pad(x, ((0, pad), (0, 0)))
        h = jnp.pad(h, ((0, pad), (0, 0)))
        node_mask = jnp.pad(node_mask, (0, pad))
    cdt = prec.compute_dtype
    x, h = x.astype(cdt), h.astype(cdt)
    ws = tuple(a.astype(cdt) for a in (z, w1h, w1d, const1, w2, b2,
                                       wg1, bg1, wg2, wz1, bz1, wz2))
    mask2d = node_mask[:, None].astype(out_dt)
    grid = (n_pad // block_n,)

    full = lambda *shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    blocked = lambda width: pl.BlockSpec((block_n, width), lambda i: (i, 0))

    out_shapes = (
        jax.ShapeDtypeStruct((n_pad, 3), out_dt),  # dx
        jax.ShapeDtypeStruct((n_pad, hid), out_dt),  # mh
        jax.ShapeDtypeStruct((c, 3), out_dt),  # dz_sum
        jax.ShapeDtypeStruct((c, hid), out_dt),  # ms_sum
    )
    dx, mh, dz, ms = pl.pallas_call(
        functools.partial(_kernel, compute=prec.compute,
                          accum=prec.accumulate),
        grid=grid,
        in_specs=[
            blocked(3), blocked(dh), blocked(1), full(c, 3),
            full(c, dh, hid), full(c, hid), full(c, hid), full(c, hid, hid), full(c, hid),
            full(c, hid, hid), full(c, hid), full(c, hid, 1),
            full(c, hid, hid), full(c, hid), full(c, hid, 1),
        ],
        out_specs=(
            blocked(3), blocked(hid),
            full(c, 3), full(c, hid),
        ),
        out_shape=out_shapes,
        interpret=interpret,
        name="virtual_pathway_fused_fwd",
    )(x, h, mask2d, *ws)
    return dx[:n], mh[:n], dz, ms


# ------------------------------------------------------------ fused backward
def _bwd_kernel(
    x_ref, h_ref, mask_ref, z_ref,
    gdx_ref, gmh_ref, gdz_ref, gms_ref,
    w1h_ref, w1d_ref, c1_ref, w2_ref, b2_ref,
    wg1_ref, bg1_ref, wg2_ref, wz1_ref, bz1_ref, wz2_ref,
    dxg_ref, dhg_ref, dzg_ref,
    dw1h_ref, dw1d_ref, dc1_ref, dw2_ref, db2_ref,
    dwg1_ref, dbg1_ref, dwg2_ref, dwz1_ref, dbz1_ref, dwz2_ref,
    *, compute: str, accum: str,
):
    i = pl.program_id(0)
    mm = functools.partial(_mm, cdt=jnp.dtype(compute), adt=jnp.dtype(accum))
    xb = x_ref[...]  # (BN, 3)
    hb = h_ref[...]  # (BN, Dh)
    mb = mask_ref[...]  # (BN, 1)
    z = z_ref[...]  # (C, 3)
    n_chan = z.shape[0]
    inv_c = 1.0 / n_chan

    @pl.when(i == 0)
    def _init():  # grid-wide accumulators (z grad + every weight grad)
        for r in (dzg_ref, dw1h_ref, dw1d_ref, dc1_ref, dw2_ref, db2_ref,
                  dwg1_ref, dbg1_ref, dwg2_ref, dwz1_ref, dbz1_ref, dwz2_ref):
            r[...] = jnp.zeros_like(r)

    # the mean over channels folds into the per-node upstream once
    u_x = gdx_ref[...] * inv_c  # (BN, 3)
    g_mh = gmh_ref[...] * inv_c  # (BN, hid)
    dx_acc = jnp.zeros(dxg_ref.shape, dxg_ref.dtype)
    dh_acc = jnp.zeros(dhg_ref.shape, dhg_ref.dtype)
    for c in range(n_chan):
        # ---- recompute the channel's forward chain in VMEM -------------
        rel = xb - z[c][None, :]
        d2 = jnp.sum(rel * rel, axis=-1, keepdims=True)
        pre1 = mm(hb, w1h_ref[c]) + d2 * w1d_ref[c][None, :] + c1_ref[c][None, :]
        t1 = jax.nn.silu(pre1)
        msg = mm(t1, w2_ref[c]) + b2_ref[c][None, :]
        gpx = mm(msg, wg1_ref[c]) + bg1_ref[c][None, :]
        sx = jax.nn.silu(gpx)
        gate_x = mm(sx, wg2_ref[c])  # (BN, 1)
        gpz = mm(msg, wz1_ref[c]) + bz1_ref[c][None, :]
        sz = jax.nn.silu(gpz)
        gate_z = mm(sz, wz2_ref[c])
        # ---- backprop the four output cotangents -----------------------
        u_z = -mb * gdz_ref[c][None, :]  # (BN, 3): dz_sum = Σ −rel·gz·m
        g_gx = jnp.sum(u_x * rel, axis=-1, keepdims=True)
        g_gz = jnp.sum(u_z * rel, axis=-1, keepdims=True)
        g_msg = g_mh + mb * gms_ref[c][None, :]
        # gate-x MLP
        g_gpx = mm(g_gx, wg2_ref[c].T) * _silu_grad(gpx)
        g_msg = g_msg + mm(g_gpx, wg1_ref[c].T)
        dwg1_ref[c] += mm(msg.T, g_gpx).astype(dwg1_ref.dtype)
        dbg1_ref[c, :] += jnp.sum(g_gpx, axis=0).astype(dbg1_ref.dtype)
        dwg2_ref[c] += mm(sx.T, g_gx).astype(dwg2_ref.dtype)
        # gate-z MLP
        g_gpz = mm(g_gz, wz2_ref[c].T) * _silu_grad(gpz)
        g_msg = g_msg + mm(g_gpz, wz1_ref[c].T)
        dwz1_ref[c] += mm(msg.T, g_gpz).astype(dwz1_ref.dtype)
        dbz1_ref[c, :] += jnp.sum(g_gpz, axis=0).astype(dbz1_ref.dtype)
        dwz2_ref[c] += mm(sz.T, g_gz).astype(dwz2_ref.dtype)
        # message MLP
        dw2_ref[c] += mm(t1.T, g_msg).astype(dw2_ref.dtype)
        db2_ref[c, :] += jnp.sum(g_msg, axis=0).astype(db2_ref.dtype)
        g_pre1 = mm(g_msg, w2_ref[c].T) * _silu_grad(pre1)
        dw1h_ref[c] += mm(hb.T, g_pre1).astype(dw1h_ref.dtype)
        dh_acc += mm(g_pre1, w1h_ref[c].T).astype(dh_acc.dtype)
        dw1d_ref[c, :] += jnp.sum(d2 * g_pre1, axis=0).astype(dw1d_ref.dtype)
        dc1_ref[c, :] += jnp.sum(g_pre1, axis=0).astype(dc1_ref.dtype)
        g_d2 = jnp.sum(g_pre1 * w1d_ref[c][None, :], axis=-1, keepdims=True)
        # rel = x − z_c: x gets +, z gets −(column sum)
        g_rel = u_x * gate_x + u_z * gate_z + 2.0 * rel * g_d2
        dx_acc += g_rel.astype(dx_acc.dtype)
        dzg_ref[c, :] += -jnp.sum(g_rel, axis=0).astype(dzg_ref.dtype)
    dxg_ref[...] = dx_acc
    dhg_ref[...] = dh_acc


@functools.partial(jax.jit,
                   static_argnames=("block_n", "interpret", "precision"))
def virtual_pathway_bwd_fused(
    x: Array, h: Array, z: Array, node_mask: Array,
    w1h: Array, w1d: Array, const1: Array, w2: Array, b2: Array,
    wg1: Array, bg1: Array, wg2: Array,
    wz1: Array, bz1: Array, wz2: Array,
    g_dx: Array, g_mh: Array, g_dz: Array, g_ms: Array,
    *, block_n: int = 512, interpret: bool | None = None, precision=None,
):
    """Fused backward of :func:`virtual_pathway_fused` (module docstring).

    Inputs are the forward primals plus the four output cotangents; no
    intermediate residuals exist — all per-channel activations are
    recomputed per node block.  Returns the 14 gradients in forward
    argument order *minus* the node mask (not differentiated):
    ``(gx, gh, gz, gw1h, gw1d, gc1, gw2, gb2, gwg1, gbg1, gwg2, gwz1,
    gbz1, gwz2)`` in the accumulate dtype.

    Matches ``jax.vjp(ref.virtual_pathway_ref)`` with a zero mask
    cotangent (the const1 cotangent flows back to s/m^v/b1 through the
    traced ``ops.unpack_virtual_block``).
    """
    from repro.kernels.runtime import resolve_interpret, resolve_precision

    interpret = resolve_interpret(interpret)
    prec = resolve_precision(precision)
    adt = prec.accumulate_dtype
    cdt = prec.compute_dtype
    n, dh = h.shape
    c, _, hid = w1h.shape
    n_pad = -(-n // block_n) * block_n
    pad = n_pad - n
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        h = jnp.pad(h, ((0, pad), (0, 0)))
        node_mask = jnp.pad(node_mask, (0, pad))
    # padded rows: zero cotangents × zero mask ⇒ exact no-ops everywhere
    g_dx = jnp.pad(g_dx.astype(adt), ((0, pad), (0, 0)))
    g_mh = jnp.pad(g_mh.astype(adt), ((0, pad), (0, 0)))
    g_dz = g_dz.astype(adt)
    g_ms = g_ms.astype(adt)
    mask2d = node_mask[:, None].astype(adt)
    x, h = x.astype(cdt), h.astype(cdt)
    weights = (z, w1h, w1d, const1, w2, b2, wg1, bg1, wg2, wz1, bz1, wz2)
    ws = tuple(a.astype(cdt) for a in weights)
    grid = (n_pad // block_n,)

    full = lambda *shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    blocked = lambda width: pl.BlockSpec((block_n, width), lambda i: (i, 0))
    f = lambda shape: jax.ShapeDtypeStruct(shape, adt)

    out = pl.pallas_call(
        functools.partial(_bwd_kernel, compute=prec.compute,
                          accum=prec.accumulate),
        grid=grid,
        in_specs=[
            blocked(3), blocked(dh), blocked(1), full(c, 3),
            blocked(3), blocked(hid), full(c, 3), full(c, hid),
            full(c, dh, hid), full(c, hid), full(c, hid), full(c, hid, hid),
            full(c, hid),
            full(c, hid, hid), full(c, hid), full(c, hid, 1),
            full(c, hid, hid), full(c, hid), full(c, hid, 1),
        ],
        out_specs=(
            blocked(3), blocked(dh), full(c, 3),
            full(c, dh, hid), full(c, hid), full(c, hid), full(c, hid, hid),
            full(c, hid),
            full(c, hid, hid), full(c, hid), full(c, hid, 1),
            full(c, hid, hid), full(c, hid), full(c, hid, 1),
        ),
        out_shape=(
            f((n_pad, 3)), f((n_pad, dh)), f((c, 3)),
            f((c, dh, hid)), f((c, hid)), f((c, hid)), f((c, hid, hid)),
            f((c, hid)),
            f((c, hid, hid)), f((c, hid)), f((c, hid, 1)),
            f((c, hid, hid)), f((c, hid)), f((c, hid, 1)),
        ),
        interpret=interpret,
        name="virtual_pathway_bwd_fused_grads",
    )(x, h, mask2d, ws[0], g_dx, g_mh, g_dz, g_ms, *ws[1:])
    gx, gh, *rest = out
    return (gx[:n], gh[:n], *rest)
