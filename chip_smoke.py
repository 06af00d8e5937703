"""Smoke run of the system's main paths on a TPU, through the user entry points.

    python chip_smoke.py             # one chip: FastEGNN training and
                                     # served rollouts at Water-3D scale
    python chip_smoke.py --chips 4   # four chips: DistEGNN training at
                                     # Fluid113K scale, and its forward
                                     # against the single-device model

One chip runs two phases in one process:

* training: ``build_pipeline("fast_egnn", use_kernel=True)`` →
  ``Pipeline.make_batches`` → ``Pipeline.train_step`` for a few steps on
  8192-particle fluid scenes (4 layers, hidden 64, 3 virtual nodes,
  λ_MMD = 0.03, f32), random weights from ``--seed``;
* serving: two 8192-particle scenes through ``RolloutService`` (one
  capacity bucket, device neighbour rebuilds); the second must reuse the
  first one's compiled program.

``--chips 4`` runs only the DistEGNN path on a ``make_gnn_mesh(4)``
pipeline: one 113,000-particle scene partitioned four ways, a few train
steps, and the distributed forward compared with the single-device forward
on the union of the shards' graphs.  The scene is the simulator's freshly
poured blob and its state one simulator step later: generating it costs
two host neighbour searches instead of the 26 a settled frame takes.

After each phase the pipeline's dispatch report must show the fused Pallas
edge and virtual kernels compiled for the chip (``mode == "tpu"``), no
jnp fallback, and one trace of the phase's program.  Each phase compares
what the program under test computed, at the precision it runs, with the
plain jnp path (``use_kernel=False``) on the same parameters under
``jax.default_matmul_precision("highest")``: the training forward, the
served trajectory frame by frame, the distributed forward.  The error is
reported as ``max_abs`` (largest coordinate difference) and ``max_rel``
(the largest over particles of a particle's error over its own reference
displacement, or over the cutoff ``R`` where it moves less), and the run
fails when ``max_rel`` exceeds ``REF_TOL``.

Diagnostics go to earlier lines; the last line of standard output is one
JSON object, ``{"ok": true, "device": {"platform", "kind", "count"}}``.  Any
failure exits non-zero without printing it.  The script refuses to run
where JAX finds no TPU: the CPU rehearsal of these phases is
``tests/test_chip_smoke.py``.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import message_passing as mp  # noqa: E402
from repro.core.graph import make_graph  # noqa: E402
from repro.data.fluid import generate_fluid_dataset  # noqa: E402
from repro.distributed.dist_egnn import make_gnn_mesh  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.fast_egnn import fast_egnn_apply  # noqa: E402
from repro.pipeline import build_pipeline  # noqa: E402
from repro.serving import RolloutService  # noqa: E402
from repro.serving.service import ServiceConfig  # noqa: E402
from repro.training.trainer import TrainConfig  # noqa: E402

#: the launcher's widths (launch/train.py defaults), f32 kernels
WIDTHS = dict(n_layers=4, hidden=64, n_virtual=3, s_dim=64, h_in=1,
              precision="f32")
LAM_MMD = 0.03
R = 0.035  # Water-3D / Fluid113K cutoff (launch/train.py --dataset fluid)
SKIN = 0.5 * R
DT = 15 * 0.005  # data/fluid.py: dt_frames sim steps of dt between frames
BOX = 1.0  # data/fluid.py's container; rollouts wrap into it
WATER3D_N = 8192
FLUID113K_N = 113_000
#: kernel-vs-jnp tolerance on max_rel (see module docstring) — the repo's
#: f32 kernel-parity tolerance (tests/test_kernels.py)
REF_TOL = 1e-4
#: displacement floor of max_rel: the length scale of the model's messages
#: (every relative vector it sees is shorter than the cutoff)
REF_FLOOR = R


class SmokeFailure(RuntimeError):
    """A phase produced a wrong, non-finite or unproven result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(*parts) -> None:
    print(*parts, flush=True)


class CompileLog:
    """Backend-compile seconds and persistent-cache hits, via jax.monitoring
    (a cache hit's "compile" is the time to read the entry back)."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> tuple:
        return (self.seconds, self.hits, self.misses)

    def since(self, mark: tuple) -> dict:
        return dict(compile_s=self.seconds - mark[0],
                    cache_hits=self.hits - mark[1],
                    cache_misses=self.misses - mark[2])


def check_dispatch(report: dict, *, mode: str, layouts_from_data: bool,
                   phase: str, n_layers: int) -> dict:
    """The fused edge and virtual kernels ran, compiled as ``mode``, and the
    phase's program was traced once (the counts are per-layer trace-time
    events)."""
    c = report["counts"]
    log(f"[{phase}] dispatch: mode={report['mode']} counts={c}")
    check(report["mode"] == mode,
          f"{phase}: dispatch mode {report['mode']!r}, expected {mode!r}")
    check(c.get("edge_kernel", 0) > 0 and c.get("edge_jnp", 0) == 0,
          f"{phase}: edge pathway did not run fused only: {c}")
    check(c.get("virtual_kernel", 0) > 0 and c.get("virtual_jnp", 0) == 0,
          f"{phase}: virtual pathway did not run fused only: {c}")
    check(c.get("edge_kernel", 0) == n_layers,
          f"{phase}: program traced more than once: {c}")
    if layouts_from_data:
        check(c.get("edge_layout_regroup", 0) == 0,
              f"{phase}: trace-time layout regroup despite data-plane "
              f"layouts: {c}")
    return c


def compare(got, want, x0, *, what: str, tol: float,
            box: float | None = None) -> dict:
    """max_abs / max_rel of fused ``got`` against reference ``want``
    (coordinates; ``x0`` the positions they were predicted from).  Each
    particle's error is measured against its own reference displacement,
    floored at ``REF_FLOOR``.  With ``box``, ``got`` is wrapped into the box
    and ``want`` is not: the difference is taken by minimum image, the
    displacement is the unwrapped step the reference took."""
    got, want, x0 = (np.asarray(a, np.float64) for a in (got, want, x0))
    check(np.isfinite(got).all(), f"{what}: non-finite fused output")
    check(np.isfinite(want).all(), f"{what}: non-finite reference output")
    diff, disp = got - want, want - x0
    if box is not None:
        diff -= box * np.round(diff / box)
    err = np.abs(diff).max(axis=-1)
    scale = np.maximum(np.abs(disp).max(axis=-1), REF_FLOOR)
    rel = err / scale
    worst = int(np.argmax(rel))
    max_abs, max_rel = float(err.max()), float(rel[worst])
    log(f"[{what}] fused vs jnp reference: max_abs={max_abs!r} "
        f"max_rel={max_rel!r} (worst particle's scale "
        f"{float(scale[worst])!r}, largest reference displacement "
        f"{float(scale.max())!r}, tolerance {tol!r})")
    check(max_rel <= tol, f"{what}: max_rel {max_rel!r} > {tol!r}")
    return dict(max_abs=max_abs, max_rel=max_rel)


def peak_bytes(devices) -> list:
    out = []
    for d in devices:
        st = d.memory_stats() or {}
        out.append(st.get("peak_bytes_in_use"))
    return out


# ------------------------------------------------------------------- phases
def train_phase(*, n_particles: int = WATER3D_N, batch: int = 2,
                steps: int = 4, seed: int = 0, mode: str = "tpu",
                tol: float = REF_TOL, widths: dict = WIDTHS,
                clog: CompileLog):
    """FastEGNN training through the pipeline; returns (pipe, ref, info)."""
    t0 = time.perf_counter()
    data = generate_fluid_dataset(batch, n_particles=n_particles, seed=seed)
    tc = TrainConfig(lam_mmd=LAM_MMD, seed=seed)
    key = jax.random.PRNGKey(seed)
    pipe = build_pipeline("fast_egnn", key, train_cfg=tc, use_kernel=True,
                          **widths)
    batches = list(pipe.make_batches(data, batch, r=R))
    log(f"[train] {batch} scenes x {n_particles} particles, "
        f"edges/scene {batches[0].graph.senders.shape[-1]}, "
        f"setup {time.perf_counter() - t0:.3f}s")

    mp.reset_dispatch_counts()
    mark = clog.mark()
    params, opt_state = pipe.params, pipe.opt.init(pipe.params)
    losses, times = [], []
    for i, b in zip(range(steps), itertools.cycle(batches)):
        t = time.perf_counter()
        params, opt_state, m = pipe.train_step(
            params, opt_state, b, jax.random.fold_in(key, i))
        loss = float(m["loss"])  # blocks on the step
        times.append(time.perf_counter() - t)
        losses.append(loss)
        log(f"[train] step {i}: loss={loss!r} "
            f"mse={float(m.get('mse', float('nan')))!r} "
            f"wall={times[-1]:.3f}s")
        check(np.isfinite(loss), f"train: non-finite loss at step {i}")
    comp = clog.since(mark)
    log(f"[train] compile {comp}")
    counts = check_dispatch(pipe.dispatch_report(), mode=mode,
                            layouts_from_data=True, phase="train",
                            n_layers=pipe.cfg.n_layers)
    pipe.params = params

    ref = build_pipeline("fast_egnn", key, train_cfg=tc, use_kernel=False,
                         **widths)
    b = batches[0]
    got = pipe.predict(params, b)
    with jax.default_matmul_precision("highest"):
        want = ref.predict(params, b)
    nm = np.asarray(b.graph.node_mask) > 0
    err = compare(np.asarray(got)[nm], np.asarray(want)[nm],
                  np.asarray(b.graph.x)[nm], what="train forward", tol=tol)
    return pipe, ref, dict(losses=losses, step_s=times, counts=counts,
                           ref=err, **comp)


def serve_phase(pipe, ref, *, n_particles: int = WATER3D_N, steps: int = 20,
                ref_frames: int = 5, seed: int = 0, mode: str = "tpu",
                tol: float = REF_TOL, clog: CompileLog):
    """Two same-bucket requests through RolloutService; returns info."""
    scenes = [(s.x0, s.v0, s.h) for s in generate_fluid_dataset(
        2, n_particles=n_particles, seed=seed + 1)]
    mp.reset_dispatch_counts()
    mark = clog.mark()
    reqs = []
    with RolloutService(pipe, model="fast_egnn",
                        config=ServiceConfig(max_batch=1)) as svc:
        handles = [svc.submit(x0, v0, h, steps, r=R, skin=SKIN, dt=DT,
                              wrap_box=BOX) for x0, v0, h in scenes]
        trajs = [hd.result() for hd in handles]
    # closing joined the worker: each handle's batch bookkeeping is done
    m = svc.metrics()
    for i, (hd, traj) in enumerate(zip(handles, trajs)):
        check(traj.shape == (steps, n_particles, 3),
              f"serve: request {i} trajectory shape {traj.shape}")
        check(np.isfinite(traj).all(),
              f"serve: request {i} non-finite trajectory")
        reqs.append(dict(steps=traj.shape[0], rebuilds=hd.rebuilds,
                         recompiles=hd.recompiles, latency_s=hd.latency_s,
                         first_frame_s=hd.first_frame_s))
        log(f"[serve] request {i}: {reqs[-1]}")
    builds = m["program_cache"]["builds"]
    comp = clog.since(mark)
    log(f"[serve] program builds={builds} compile {comp}")
    check(builds == 1, f"serve: {builds} program builds for one bucket")
    check(reqs[1]["recompiles"] == 0,
          f"serve: second request recompiled {reqs[1]['recompiles']}x")
    counts = check_dispatch(pipe.dispatch_report(), mode=mode,
                            layouts_from_data=False, phase="serve",
                            n_layers=pipe.cfg.n_layers)

    # each served frame of the first request against one unwrapped
    # reference step from the served trajectory's previous state, with the
    # engine's f32 finite-difference velocity: free-running trajectories of
    # an untrained model part at the first neighbour-list difference, and
    # once a particle wraps its velocity is of order BOX / DT, so a step
    # can move it many box lengths — the wrapped frame keeps f32 rounding
    # of that step, measured against the step itself
    x0, v0, h = scenes[0]
    served, box, dt = trajs[0], np.float32(BOX), np.float32(DT)
    prev = [x0 - box * np.floor(x0 / box)] + list(served)
    errs = []
    for t in range(ref_frames):
        vt = v0 if t == 0 else (prev[t] - prev[t - 1]) / dt
        with jax.default_matmul_precision("highest"):
            want = ref.rollout(pipe.params, (prev[t], vt, h), 1, r=R,
                               skin=SKIN, dt=DT).trajectory[0]
        errs.append(compare(served[t], want, prev[t],
                            what=f"served frame {t}, one step",
                            tol=tol, box=BOX))
    err = max(errs, key=lambda e: e["max_rel"])
    return dict(requests=reqs, program_builds=builds, counts=counts,
                ref=err, **comp)


def _union_graph(sb):
    """The shards' local graphs side by side as one graph (host numpy),
    plus the mask that picks real nodes out of the (D, n_cap) layout."""
    f = {k: np.asarray(getattr(sb, k))[:, 0] for k in
         ("x", "v", "h", "senders", "receivers", "node_mask", "edge_mask")}
    xs, vs, hs, snd, rcv, off = [], [], [], [], [], 0
    for d in range(f["x"].shape[0]):
        n_d = int((f["node_mask"][d] > 0).sum())
        em = f["edge_mask"][d] > 0
        xs.append(f["x"][d][:n_d])
        vs.append(f["v"][d][:n_d])
        hs.append(f["h"][d][:n_d])
        snd.append(f["senders"][d][em] + off)
        rcv.append(f["receivers"][d][em] + off)
        off += n_d
    g = make_graph(np.concatenate(xs), np.concatenate(vs), np.concatenate(hs),
                   np.concatenate(snd), np.concatenate(rcv))
    return g, f["node_mask"] > 0


def dist_phase(*, n_dev: int = 4, n_particles: int = FLUID113K_N,
               steps: int = 3, seed: int = 0, mode: str = "tpu",
               tol: float = REF_TOL, widths: dict = WIDTHS,
               clog: CompileLog):
    """DistEGNN over ``n_dev`` devices vs the single-device forward."""
    devices = jax.devices()[:n_dev]
    t0 = time.perf_counter()
    data = generate_fluid_dataset(1, n_particles=n_particles, seed=seed,
                                  warmup=0, dt_frames=1)
    tc = TrainConfig(lam_mmd=LAM_MMD, seed=seed)
    key = jax.random.PRNGKey(seed)
    pipe = build_pipeline("fast_egnn", key, mesh=make_gnn_mesh(n_dev),
                          train_cfg=tc, use_kernel=True, **widths)
    sb = list(pipe.make_batches(data, 1, r=R))[0]
    nodes = np.asarray(sb.node_mask).sum(axis=(1, 2)).astype(int).tolist()
    edges = np.asarray(sb.edge_mask).sum(axis=(1, 2)).astype(int).tolist()
    log(f"[dist] {n_particles} particles over {n_dev} shards: nodes "
        f"{nodes}, edges {edges}, n_cap {sb.x.shape[2]}, batch sharding "
        f"{sb.x.sharding}, setup {time.perf_counter() - t0:.3f}s")
    check(len(sb.x.sharding.device_set) == n_dev,
          f"dist: batch placed on {sb.x.sharding.device_set}")

    mp.reset_dispatch_counts()
    mark = clog.mark()
    params, opt_state = pipe.params, pipe.opt.init(pipe.params)
    losses = []
    for i in range(steps):
        t = time.perf_counter()
        params, opt_state, m = pipe.train_step(params, opt_state, sb)
        losses.append(float(m["loss"]))
        log(f"[dist] step {i}: loss={losses[-1]!r} "
            f"wall={time.perf_counter() - t:.3f}s")
        check(np.isfinite(losses[-1]), f"dist: non-finite loss at step {i}")
    comp = clog.since(mark)
    log(f"[dist] compile {comp}")
    counts = check_dispatch(pipe.dispatch_report(), mode=mode,
                            layouts_from_data=True, phase="dist",
                            n_layers=pipe.cfg.n_layers)

    x_dist = np.asarray(pipe.predict(params, sb))[:, 0]
    peaks = peak_bytes(devices)  # before the reference lands on device 0
    log(f"[dist] peak_bytes_in_use per device: {peaks}")
    g, real = _union_graph(sb)
    cfg_ref = pipe.cfg._replace(use_kernel=False)
    with jax.default_matmul_precision("highest"):
        x_ref = jax.jit(lambda p, g: fast_egnn_apply(p, cfg_ref, g)[0])(
            params, g)
    err = compare(x_dist[real], np.asarray(x_ref), np.asarray(g.x),
                  what=f"DistEGNN({n_dev}) vs union graph", tol=tol)
    return dict(losses=losses, counts=counts, peak_bytes=peaks, ref=err,
                nodes=nodes, edges=edges, **comp)


# --------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: training + serving on one chip; 4: only the "
                         "DistEGNN path over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU ({devices}); this script runs "
              f"only on the chip", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    n_entries = lambda: (len(os.listdir(cache_dir))
                         if os.path.isdir(cache_dir) else 0)
    log(f"devices: {devices}")
    log(f"compile cache: {cache_dir} ({n_entries()} entries at start)")
    clog = CompileLog()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            dist_phase(n_dev=4, seed=args.seed, clog=clog)
        else:
            pipe, ref, _ = train_phase(seed=args.seed, clog=clog)
            serve_phase(pipe, ref, seed=args.seed, clog=clog)
            log(f"peak_bytes_in_use: {peak_bytes(devices[:1])}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"total {time.perf_counter() - t0:.3f}s; compile "
        f"{clog.seconds:.3f}s; persistent cache hits={clog.hits} "
        f"misses={clog.misses}; {n_entries()} cache entries at end")
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
