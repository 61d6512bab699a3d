"""Kernel dispatch registry — one fast data path, many entry points.

Every kernel package ships two interchangeable implementations: ``ref``
(pure jnp, the test oracle, fast under plain XLA on any backend) and
``pallas`` (the hand-tiled TPU kernel; its public wrapper falls back to
interpret mode off-TPU, which validates the kernel body but is far too
slow for throughput).  Before this module, every method hand-rolled its
own inline import + backend test to choose between them; now call sites
say ``dispatch("xtx", x, y)`` and the policy lives in exactly one place.

Dispatch policy (``impl`` argument):

* ``"auto"``    — compiled Pallas on TPU when the entry's ``supports``
  hook accepts the call, jnp reference everywhere else.  This is what
  ``use_kernel=True`` in the method layer means.
* ``"ref"``     — force the jnp oracle.
* ``"pallas"``  — force the Pallas wrapper.  Off-TPU this warns ONCE per
  kernel (interpret mode: the correctness path kernel tests pin, far too
  slow for throughput); on TPU a call the ``supports`` hook rejects
  raises instead of silently degrading to ``ref``.

``supports`` is a *ranker*, not just a gate: it may return ``True``
(take the call), ``False`` (can't), or a non-empty dict of tuned keyword
arguments (take the call with these tile/block parameters — typically
read from the active measured calibration, see
:mod:`repro.core.calibration`).  Tuned kwargs only flow into the pallas
implementation; explicit caller kwargs always win.

Every dispatch records a ``kind="kernel"`` event on active traces
(:mod:`repro.core.trace`) carrying the RESOLVED implementation, so
benchmarks and tests can assert which kernel actually ran.

Built-in entries (registered lazily on first lookup, so importing this
module never drags in kernel bodies): ``xtx``, ``kmeans_assign``,
``countmin``, ``flash_attention``, and the whole-fold grouped kernels
``segment_linregr`` / ``segment_countmin`` / ``segment_fm``
(kernels/segment_fold).  New kernels call :func:`register`.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable

import jax

IMPLS = ("auto", "ref", "pallas")

# kernels already warned about forced-pallas interpret mode (once per
# kernel per process, so parity matrices don't drown the signal)
_WARNED_INTERPRET: set[str] = set()


def _trace_kernel(name: str, resolved: str, requested: str) -> None:
    # lazy: core.trace lives above an import cycle (core -> aggregates ->
    # this module); by dispatch time it is always importable
    from ..core.trace import record
    record("kernel", engine=resolved, name=name, requested=requested)


@dataclasses.dataclass(frozen=True)
class KernelEntry:
    """A named (ref, pallas) implementation pair.

    ``supports(*args, **kwargs) -> bool | dict`` gates shape/dtype
    combinations the compiled Pallas kernel cannot take — and, as a
    ranker, may return tuned kwargs for the ones it can.  When it
    rejects, auto-dispatch degrades to ``ref``; a forced ``"pallas"`` on
    TPU raises loudly instead.
    """

    name: str
    ref: Callable[..., Any]
    pallas: Callable[..., Any] | None = None
    supports: Callable[..., Any] | None = None

    def resolve(self, impl: str, *args, **kwargs) -> tuple[str, dict]:
        """Resolve ``impl`` for a concrete call: which implementation runs,
        and with which tuned kwargs.  Works on ShapeDtypeStruct args (the
        hooks use shapes/dtypes only), so callers can resolve host-side
        before tracing."""
        if impl == "ref":
            return "ref", {}
        if impl == "auto":
            if self.pallas is None or jax.default_backend() != "tpu":
                return "ref", {}
            ok = True if self.supports is None \
                else self.supports(*args, **kwargs)
            if not ok:
                return "ref", {}
            return "pallas", (ok if isinstance(ok, dict) else {})
        if impl == "pallas":
            if self.pallas is None:
                raise ValueError(
                    f"kernel {self.name!r} has no pallas implementation")
            if jax.default_backend() != "tpu":
                if self.name not in _WARNED_INTERPRET:
                    _WARNED_INTERPRET.add(self.name)
                    warnings.warn(
                        f"kernel {self.name!r}: impl='pallas' forced on "
                        f"backend {jax.default_backend()!r} — running the "
                        "kernel body in interpret mode (correctness path, "
                        "far too slow for throughput)", stacklevel=3)
                return "pallas", {}
            ok = True if self.supports is None \
                else self.supports(*args, **kwargs)
            if not ok:
                shapes = [getattr(a, "shape", a) for a in args]
                raise ValueError(
                    f"kernel {self.name!r}: impl='pallas' forced but the "
                    f"supports gate rejected the call (args shapes "
                    f"{shapes}, kwargs {kwargs}); use impl='auto' to "
                    "degrade to the jnp ref, or reshape to a supported "
                    "layout")
            return "pallas", (ok if isinstance(ok, dict) else {})
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")

    def pick(self, *args, **kwargs) -> str:
        """Resolve "auto" for a concrete call: which impl would run?"""
        return self.resolve("auto", *args, **kwargs)[0]


_REGISTRY: dict[str, KernelEntry] = {}
_BUILTINS_LOADED = False


def register(name: str, *, ref: Callable, pallas: Callable | None = None,
             supports: Callable | None = None,
             overwrite: bool = False) -> KernelEntry:
    if not overwrite and name in _REGISTRY:
        raise ValueError(f"kernel {name!r} already registered")
    entry = KernelEntry(name, ref, pallas, supports)
    _REGISTRY[name] = entry
    return entry


def get(name: str) -> KernelEntry:
    _ensure_builtins()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown kernel {name!r}; registered: {available()}") from None


def available() -> tuple[str, ...]:
    _ensure_builtins()
    return tuple(sorted(_REGISTRY))


def dispatch(name: str, *args, impl: str = "auto", _record: bool = True,
             **kwargs):
    """Run kernel ``name`` on ``args`` under the dispatch policy above.

    ``_record=False`` suppresses the trace event — engine paths that
    resolve host-side (and record there, once per physical execution)
    pass it so the traced inner call doesn't double-count."""
    entry = get(name)
    resolved, tuned = entry.resolve(impl, *args, **kwargs)
    if _record:
        _trace_kernel(name, resolved, impl)
    if resolved == "ref":
        return entry.ref(*args, **kwargs)
    return entry.pallas(*args, **{**tuned, **kwargs})


def resolve_impl(use_kernel: bool | str) -> str | None:
    """Method-layer ``use_kernel`` flag -> dispatch impl (None = inline
    jnp transition, no registry call)."""
    if use_kernel is False:
        return None
    if use_kernel is True:
        return "auto"
    if use_kernel in IMPLS:
        return use_kernel
    raise ValueError(f"use_kernel must be bool or one of {IMPLS}, "
                     f"got {use_kernel!r}")


# ---------------------------------------------------------------------------
# Built-in kernels.  Registration is deferred to first lookup: the ref
# modules import the method layer (countmin's oracle shares the method
# hash) and the method layer imports this module, so import-time
# registration would cycle.
# ---------------------------------------------------------------------------

def _calibrated(kernel: str, param: str):
    """Measured tile/block parameter from the active calibration, or None.
    Lazy import: calibration sits in core, which imports this module."""
    from ..core.calibration import kernel_param
    return kernel_param(kernel, param)


def _ensure_builtins() -> None:
    global _BUILTINS_LOADED
    if _BUILTINS_LOADED:
        return

    # overwrite=True + flag set at the END: if any import below raises,
    # the next lookup retries the whole registration instead of leaving a
    # permanently partial registry with misleading unknown-kernel errors.
    from .xtx import ops as xtx_ops, ref as xtx_ref

    def xtx_supports(x, y, *, tile_n=1024):
        # ranker: no shape constraints (ops.py pads), but a measured
        # calibration may pin a better row tile for this backend
        t = _calibrated("xtx", "tile_n")
        return {"tile_n": int(t)} if t else True

    register("xtx", ref=xtx_ref.xtx_xty_ref, pallas=xtx_ops.xtx_xty,
             supports=xtx_supports, overwrite=True)

    from .kmeans_assign import ops as ka_ops, ref as ka_ref
    register("kmeans_assign", ref=ka_ref.assign_and_reduce_ref,
             pallas=ka_ops.assign_and_reduce, overwrite=True)

    from .countmin import ops as cm_ops, ref as cm_ref

    def countmin_supports(items, mask, depth, width, *, tile_n=None):
        # the tile shrinks with the width so the kernel's (width, tile)
        # one-hot stays within VMEM; a caller's explicit tile is final
        cap = cm_ops.max_tile(width)
        if tile_n is not None:
            return tile_n <= cap
        if not cap:
            return False
        t = _calibrated("countmin", "tile_n") or 2048
        return {"tile_n": min(int(t), cap)}

    register("countmin", ref=cm_ref.countmin_block_ref,
             pallas=cm_ops.countmin_block, supports=countmin_supports,
             overwrite=True)

    from .segment_fold import ops as sf_ops, ref as sf_ref
    register("segment_linregr", ref=sf_ref.segment_linregr_ref,
             pallas=sf_ops.segment_linregr,
             supports=sf_ops.segment_linregr_supports, overwrite=True)
    register("segment_countmin", ref=sf_ref.segment_countmin_ref,
             pallas=sf_ops.segment_countmin,
             supports=sf_ops.segment_countmin_supports, overwrite=True)
    register("segment_fm", ref=sf_ref.segment_fm_ref,
             pallas=sf_ops.segment_fm,
             supports=sf_ops.segment_fm_supports, overwrite=True)

    from .flash_attention import ops as fa_ops, ref as fa_ref

    def flash_ref(q, k, v, *, causal=True, **_):
        return fa_ref.attention_ref(
            q, k, v, scale=1.0 / (q.shape[-1] ** 0.5), causal=causal)

    def flash_pallas(q, k, v, *, causal=True, tile_q=256, tile_k=256):
        # force=True so off-TPU requests genuinely run the Pallas body
        # (interpret mode) instead of the wrapper's own jnp fallback.
        s = q.shape[2]
        return fa_ops.flash_attention(
            q, k, v, causal=causal, tile_q=min(tile_q, s),
            tile_k=min(tile_k, s), force=True)

    def flash_supports(q, k, v, *, causal=True, tile_q=256, tile_k=256):
        s = q.shape[2]
        return s % min(tile_q, s) == 0 and s % min(tile_k, s) == 0

    register("flash_attention", ref=flash_ref, pallas=flash_pallas,
             supports=flash_supports, overwrite=True)
    _BUILTINS_LOADED = True
