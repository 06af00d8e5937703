"""Backend detection + the precision contract shared by every Pallas kernel.

Before this module each call site hand-rolled the same check:
``kernels.ops`` had a private ``_interpret()``, ``repro.pipeline`` and the
benches re-spelled ``"tpu" if jax.default_backend() == "tpu" else
"interpret"``, and the raw kernels defaulted ``interpret=True`` — which
silently ran the *emulated* kernels on a real TPU for anyone calling them
directly.  This is now the single home of that decision:

* :func:`default_interpret` — should Pallas kernels run in interpret mode
  on this backend?  Everything that is not a TPU interprets; nothing else
  steers the answer (tests that need to monkeypatch this function).
* :func:`resolve_interpret` — resolve a kernel's ``interpret`` argument:
  ``None`` (the kernels' new default) auto-detects, an explicit bool is
  honoured (tests force ``interpret=True`` to exercise emulation on any
  backend).
* :func:`backend_mode` — the ``'tpu'`` / ``'interpret'`` tag the dispatch
  telemetry and bench rows record (``message_passing.dispatch_mode``).

The checks are deliberately *call-time* (not import-time constants): jax
may be reconfigured between imports, and trace-time resolution keeps jit
caches keyed on the actual decision via the static ``interpret`` argument.

Precision contract (DESIGN.md §9.2)
-----------------------------------
:class:`Precision` is the static ``(compute, accumulate)`` dtype pair every
fused kernel (forward *and* backward) honours: inputs and weights are cast
to ``compute`` before the MXU matmuls, while every reduction — segment
sums, the virtual dz/ms accumulators, weight-gradient accumulation —
carries ``accumulate`` via ``preferred_element_type``.  ``'f32'`` (the
default) is exact; ``'bf16'`` halves the VMEM working set and doubles MXU
throughput on TPU while the f32 accumulators keep segment sums from
drifting with graph size.  The pair is threaded from the model configs
(``cfg.precision``) through ``EdgeSpec.precision`` / the virtual dispatcher
into the kernels, and pairs with ``TrainConfig.loss_scale`` in the trainer.
"""
from __future__ import annotations

from typing import NamedTuple, Union

import jax
import jax.numpy as jnp


def default_interpret() -> bool:
    """True unless running on a real TPU backend (Pallas compiles there)."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve a kernel's ``interpret`` argument: ``None`` → auto-detect."""
    return default_interpret() if interpret is None else bool(interpret)


def backend_mode() -> str:
    """The dispatch-telemetry tag for this backend: ``'tpu'`` or
    ``'interpret'`` (what a dispatched fused kernel actually ran as)."""
    return "interpret" if default_interpret() else "tpu"


# ------------------------------------------------------------- precision
class Precision(NamedTuple):
    """Static compute/accumulate dtype pair for the fused kernels.

    Holds dtype *names* (strings) so a Precision is hashable and rides
    jit static arguments / lru_cache keys unchanged.  ``compute`` is the
    dtype operands are cast to before matmuls; ``accumulate`` is the
    ``preferred_element_type`` of every matmul and the dtype of every
    cross-block accumulator (kernel outputs stay in the caller's dtype).
    """

    compute: str = "float32"
    accumulate: str = "float32"

    @property
    def compute_dtype(self):
        return jnp.dtype(self.compute)

    @property
    def accumulate_dtype(self):
        return jnp.dtype(self.accumulate)


F32 = Precision("float32", "float32")
BF16 = Precision("bfloat16", "float32")

_PRECISIONS = {
    None: F32,
    "f32": F32, "float32": F32, "fp32": F32,
    "bf16": BF16, "bfloat16": BF16,
}


def resolve_precision(p: Union[str, Precision, None]) -> Precision:
    """``None``/``'f32'``/``'bf16'``/``Precision`` → :class:`Precision`.

    The accepted spellings are the ``cfg.precision`` model-config values;
    anything else raises (a typo'd precision silently running f32 would
    invalidate every bf16 benchmark row downstream).
    """
    if isinstance(p, Precision):
        return p
    try:
        return _PRECISIONS[p]
    except KeyError:
        raise ValueError(
            f"unknown precision {p!r}: expected 'f32', 'bf16', or a "
            f"kernels.runtime.Precision") from None
