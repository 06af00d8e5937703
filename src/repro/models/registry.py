"""Uniform model registry: explicit spec composition, no name magic.

Every entry is a :class:`ModelSpec` — either a *base* model or a base model
composed with the virtual-node plug-in via :func:`compose_virtual` (the
Sec. V "Fast" variants).  What used to be inferred from name prefixes
(``fast_*`` ⇒ virtual defaults, ``_FORCE_VIRTUAL0`` ⇒ disable the plug-in)
is now carried by the spec itself:

  * ``cfg_forced``   — config fields the spec pins regardless of caller
    overrides (plain RF/SchNet/TFN pin ``n_virtual=0`` so the registry name
    fully determines the model family);
  * ``cfg_defaults`` — overridable defaults (``fast_*`` compositions default
    ``n_virtual=3``, the paper's C).

Because every config carries ``use_kernel`` and every apply routes its edge
aggregation through ``core.message_passing`` (and the virtual pathway
through ``models.plugin``), *every* registry entry — base or composed —
gets the fused Pallas pathways with ``make_model(name, key,
use_kernel=True)``; no per-model wiring.

Every apply returns the predicted coordinates (N,3); feature outputs and
virtual states are exposed through ``apply_full`` where the model has them
(needed for the MMD term of the training objective).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax

from repro.core.graph import GeometricGraph
from repro.models import baselines, egnn, fast_egnn, rf, schnet, tfn

Array = jax.Array


class ModelSpec(NamedTuple):
    make_config: Callable[..., Any]
    init: Callable[..., Any]
    # apply_full(params, cfg, graph, axis_name) -> (x_pred, aux dict)
    apply_full: Callable[..., tuple]
    has_virtual: bool
    cfg_forced: dict = {}  # pinned config fields (override the caller)
    cfg_defaults: dict = {}  # overridable config defaults


def compose_virtual(base: ModelSpec, n_virtual: int = 3) -> ModelSpec:
    """Base model × virtual-node plug-in (Sec. V).

    Unpins ``n_virtual`` and defaults it to the paper's C=3; everything
    else — init, apply, kernel dispatch — is inherited from the base spec,
    whose apply activates the plug-in pathway when ``n_virtual > 0``.
    """
    forced = {k: v for k, v in base.cfg_forced.items() if k != "n_virtual"}
    return base._replace(
        has_virtual=True,
        cfg_forced=forced,
        cfg_defaults={**base.cfg_defaults, "n_virtual": n_virtual},
    )


# Every apply_full shares one signature:
#   apply_full(params, cfg, graph, axis_name=None, edge_layout=None)
# ``edge_layout`` is the batch's host-precomputed banded layout (the
# layout-carrying batch contract, DESIGN.md §7), which the fused edge
# kernels walk; linear, with no edge pathway, accepts and ignores it.
# Models with the virtual-node plug-in return its final state as
# ``aux["virtual"]``, so the objective adds the MMD term (Eq. 11).
def _egnn_full(p, cfg, g, axis_name=None, edge_layout=None):
    x, h = egnn.egnn_apply(p, cfg, g, edge_layout=edge_layout)
    return x, {"h": h}


def _fast_egnn_full(p, cfg, g, axis_name=None, edge_layout=None):
    x, h, vs = fast_egnn.fast_egnn_apply(p, cfg, g, axis_name=axis_name,
                                         edge_layout=edge_layout)
    return x, {"h": h, "virtual": vs}


def _with_virtual(aux: dict, vs) -> dict:
    """``aux`` with the plug-in's final virtual state under ``"virtual"``
    (where the trainer finds Z for the MMD term), when the model has one."""
    return aux if vs is None else {**aux, "virtual": vs}


def _rf_full(p, cfg, g, axis_name=None, edge_layout=None):
    x, vs = rf.rf_apply(p, cfg, g, axis_name, edge_layout=edge_layout)
    return x, _with_virtual({}, vs)


def _schnet_full(p, cfg, g, axis_name=None, edge_layout=None):
    x, h, vs = schnet.schnet_apply(p, cfg, g, axis_name,
                                   edge_layout=edge_layout)
    return x, _with_virtual({"h": h}, vs)


def _tfn_full(p, cfg, g, axis_name=None, edge_layout=None):
    x, h, vs = tfn.tfn_apply(p, cfg, g, axis_name, edge_layout=edge_layout)
    return x, _with_virtual({"h": h}, vs)


def _linear_full(p, cfg, g, axis_name=None, edge_layout=None):
    return baselines.linear_dyn_apply(p, cfg, g), {}


def _mpnn_full(p, cfg, g, axis_name=None, edge_layout=None):
    return baselines.mpnn_apply(p, cfg, g, edge_layout=edge_layout), {}


_BASE: dict[str, ModelSpec] = {
    "linear": ModelSpec(baselines.LinearConfig, baselines.init_linear_dyn,
                        _linear_full, False),
    "mpnn": ModelSpec(baselines.MPNNConfig, baselines.init_mpnn,
                      _mpnn_full, False),
    "egnn": ModelSpec(egnn.EGNNConfig, egnn.init_egnn, _egnn_full, False),
    "rf": ModelSpec(rf.RFConfig, rf.init_rf, _rf_full, False,
                    cfg_forced={"n_virtual": 0}),
    "schnet": ModelSpec(schnet.SchNetConfig, schnet.init_schnet,
                        _schnet_full, False, cfg_forced={"n_virtual": 0}),
    "tfn": ModelSpec(tfn.TFNConfig, tfn.init_tfn, _tfn_full, False,
                     cfg_forced={"n_virtual": 0}),
}

REGISTRY: dict[str, ModelSpec] = dict(_BASE)
# FastEGNN has its own apply (ordered virtual nodes are structural, Sec. IV)
REGISTRY["fast_egnn"] = ModelSpec(fast_egnn.FastEGNNConfig,
                                  fast_egnn.init_fast_egnn,
                                  _fast_egnn_full, True)
# Sec. V plug-in variants: explicit base × virtual composition
for _name in ("rf", "schnet", "tfn"):
    REGISTRY[f"fast_{_name}"] = compose_virtual(_BASE[_name])


def resolve_model(name: str, key, **cfg_overrides):
    """Registry name + overrides → (cfg, params, apply_full).

    The spec-composition core shared by ``repro.pipeline.build_pipeline``
    (the supported entry point) and the deprecated :func:`make_model` shim.
    """
    spec = REGISTRY[name]
    for k, v in spec.cfg_defaults.items():
        cfg_overrides.setdefault(k, v)
    cfg_overrides.update(spec.cfg_forced)
    cfg = spec.make_config(**cfg_overrides)
    params = spec.init(key, cfg)
    return cfg, params, spec.apply_full


def make_model(name: str, key, **cfg_overrides):
    """Deprecated: use ``repro.pipeline.build_pipeline`` (DESIGN.md §7).

    Kept as a thin shim with the exact historical contract — returns
    ``(cfg, params, apply_full)`` built by the pipeline factory — so
    external callers and old scripts keep working unchanged.
    """
    import warnings

    warnings.warn(
        "make_model is deprecated; use repro.pipeline.build_pipeline "
        "(returns a Pipeline whose .cfg/.params/.apply_full match this "
        "shim's return)", DeprecationWarning, stacklevel=2)
    from repro.pipeline import build_pipeline

    p = build_pipeline(name, key, **cfg_overrides)
    return p.cfg, p.params, p.apply_full
