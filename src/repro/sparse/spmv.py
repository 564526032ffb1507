"""SPMV engine dispatch — one entry point, per-format/per-engine backends.

``spmv(A, x, engine=...)`` routes on (matrix type, engine) through a
registry instead of a hard-coded isinstance chain. The full selection
matrix (engine x format):

    engine      DIAMatrix            BellMatrix          CSRMatrix
    ---------   ------------------   -----------------   ------------------
    "jnp"       spmv_dia (shifts)    spmv_bell (gather)  spmv_csr (scatter)
    "pallas"    kernels.spmv_dia     — (jnp fallback)    — (jnp fallback)
                (banded, 3-window)
    "segsum"    — (jnp fallback)     — (jnp fallback)    spmv_csr_segsum
    "bf16"      spmv_dia_bf16        — (jnp fallback)    — (jnp fallback)
                (bf16 storage,
                 f32 accumulate)

    jax.Array            -> A @ x (dense "jnp" fallback)
    object with .matvec  -> protocol fallback (matrix-free FunctionOperator)

BellMatrix runs on "jnp" on every backend: the B-ELL kernel
(``kernels.spmv_bell``) gathers ``x[cols]`` in VMEM, which the TPU
compiler refuses ("Only 2D gather is supported"), so it is not registered
here until a BELL kernel compiles for the chip.

``engine="auto"`` resolution (see :func:`resolve_engine`): "pallas" on
TPU when registered for the format; otherwise the fastest registered
non-reference engine for this backend — today that is "segsum" for
``CSRMatrix`` on CPU/GPU (a sorted segmented reduction, much faster than
the scatter-add reference) — falling back to "jnp". An engine that is
not registered for the format falls back to jnp, so callers can request
"pallas" unconditionally. New formats/backends plug in via
``register_spmv`` without touching any solver code; re-registering an
existing (format, engine) pair raises unless ``overwrite=True``.

"bf16" is the mixed-precision engine the communication-reduced CG
variants lean on (arXiv 2501.03743): band data and x are stored/streamed
as bf16 (half the HBM traffic of f32) while products accumulate in f32.
It is meant to be paired with residual replacement — ``repro.plan``
turns ``replace_every`` on by default for plans that select it.

The jnp implementations double as the oracles the Pallas kernels are
validated against (tests/test_kernels.py, tests/test_sparse.py).
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from .formats import BellMatrix, CSRMatrix, DIAMatrix

__all__ = [
    "spmv",
    "spmv_dia",
    "spmv_dia_bf16",
    "spmv_bell",
    "spmv_csr",
    "spmv_csr_segsum",
    "shifted",
    "register_spmv",
    "resolve_engine",
    "spmv_engines",
]


def shifted(x: jax.Array, offset: int) -> jax.Array:
    """x shifted by a static offset with zero fill: out[i] = x[i+offset]."""
    n = x.shape[0]
    if offset == 0:
        return x
    if offset > 0:
        return jnp.concatenate([x[offset:], jnp.zeros((offset,), x.dtype)])
    return jnp.concatenate([jnp.zeros((-offset,), x.dtype), x[:offset]])


def spmv_dia(A: DIAMatrix, x: jax.Array) -> jax.Array:
    """y[i] = sum_j data[j, i] * x[i + offsets[j]] (zero outside [0, n))."""
    y = jnp.zeros_like(x)
    for j, o in enumerate(A.offsets):
        y = y + A.data[j] * shifted(x, o)
    return y


def spmv_bell(A: BellMatrix, x: jax.Array) -> jax.Array:
    gathered = x[A.cols]  # (n, R)
    return (A.vals * gathered).sum(axis=1)


def spmv_csr(A: CSRMatrix, x: jax.Array) -> jax.Array:
    """Reference CSR SPMV: gather columns, scatter-add into rows."""
    return jnp.zeros((A.n,), x.dtype).at[A.rows].add(A.vals * x[A.cols])


def spmv_csr_segsum(A: CSRMatrix, x: jax.Array) -> jax.Array:
    """CSR SPMV as a sorted segment-sum over per-entry products.

    ``rows`` is sorted by construction, so XLA lowers this to a single
    contiguous segmented reduction instead of generic scatter-adds.
    """
    return jax.ops.segment_sum(
        A.vals * x[A.cols], A.rows, num_segments=A.n, indices_are_sorted=True
    )


def spmv_dia_bf16(A: DIAMatrix, x: jax.Array) -> jax.Array:
    """Mixed-precision DIA SPMV: bf16 storage/streaming, f32 accumulation.

    Band data and x are cast to bf16 (halving the per-iteration HBM
    traffic of the memory-bound SPMV), every product accumulates in at
    least f32, and the result is returned in x's dtype. On TPU this runs
    the Pallas banded kernel on the bf16 operands (it accumulates f32
    internally); elsewhere the jnp shift form with explicit f32 upcasts.

    Expect O(1e-2) relative error per apply — pair with residual
    replacement (``replace_every``) for full-accuracy solves; plans
    default it on for this engine.
    """
    acc = jnp.promote_types(x.dtype, jnp.float32)
    data16 = A.data.astype(jnp.bfloat16)
    x16 = x.astype(jnp.bfloat16)
    if jax.default_backend() == "tpu":
        from ..kernels.spmv_dia import spmv_dia_pallas

        A16 = DIAMatrix(data16, A.offsets, A.n)
        return spmv_dia_pallas(A16, x16, out_dtype=acc).astype(x.dtype)
    y = jnp.zeros(x.shape, acc)
    for j, o in enumerate(A.offsets):
        y = y + data16[j].astype(acc) * shifted(x16, o).astype(acc)
    return y.astype(x.dtype)


def _spmv_dense(A, x: jax.Array) -> jax.Array:
    return A @ x


def _spmv_dia_pallas(A: DIAMatrix, x: jax.Array) -> jax.Array:
    from ..kernels.spmv_dia import spmv_dia_pallas  # lazy: avoid import cycle

    return spmv_dia_pallas(A, x)


# (matrix type) -> (engine name) -> fn(A, x) -> y
_REGISTRY: Dict[type, Dict[str, Callable]] = {}


def register_spmv(mat_type: type, engine: str, fn: Callable, *, overwrite: bool = False) -> None:
    """Register an SPMV backend for ``mat_type`` under ``engine``.

    Raises ValueError if that (format, engine) pair is already registered,
    unless ``overwrite=True`` — silent replacement hides plug-in clashes.
    """
    table = _REGISTRY.setdefault(mat_type, {})
    if engine in table and not overwrite:
        raise ValueError(
            f"SPMV engine {engine!r} already registered for "
            f"{mat_type.__name__}; pass overwrite=True to replace it"
        )
    table[engine] = fn


register_spmv(DIAMatrix, "jnp", spmv_dia)
register_spmv(DIAMatrix, "pallas", _spmv_dia_pallas)
register_spmv(DIAMatrix, "bf16", spmv_dia_bf16)
register_spmv(BellMatrix, "jnp", spmv_bell)
register_spmv(CSRMatrix, "jnp", spmv_csr)
register_spmv(CSRMatrix, "segsum", spmv_csr_segsum)


def _spmv_matvec(A, x: jax.Array) -> jax.Array:
    return A.matvec(x)


def _engines_for(A) -> Dict[str, Callable]:
    # merge along the MRO: a subclass inherits its base format's engines
    # and may override/extend them
    table: Dict[str, Callable] = {}
    for klass in reversed(type(A).__mro__):
        table.update(_REGISTRY.get(klass, {}))
    if table:
        return table
    if hasattr(A, "matvec"):  # LinearOperator protocol (matrix-free etc.)
        return {"jnp": _spmv_matvec}
    if isinstance(A, jax.Array) or hasattr(A, "ndim"):
        return {"jnp": _spmv_dense}
    raise TypeError(f"unsupported matrix type {type(A)}")


def spmv_engines(A) -> Tuple[str, ...]:
    """Engine names available for this matrix (after fallback: always >=1)."""
    return tuple(sorted(_engines_for(A)))


def resolve_engine(A, engine: str = "auto") -> str:
    """The engine name ``spmv(A, x, engine=...)`` will actually run.

    "auto" resolution, in order:

    1. "pallas" on TPU when registered for this format;
    2. "segsum" when registered (CSRMatrix on CPU/GPU: the sorted
       segmented reduction beats the scatter-add reference everywhere);
    3. "jnp".

    A concrete engine name resolves to itself when registered, else to
    the "jnp" fallback.
    """
    table = _engines_for(A)
    if engine == "auto":
        if jax.default_backend() == "tpu" and "pallas" in table:
            return "pallas"
        if "segsum" in table:
            return "segsum"
        return "jnp"
    return engine if engine in table else "jnp"


def spmv(A, x: jax.Array, engine: str = "auto") -> jax.Array:
    """y = A @ x through the engine registry.

    engine="auto" — see :func:`resolve_engine` (pallas on TPU, segsum for
    CSR elsewhere, else jnp). An engine not registered for this format
    falls back to "jnp".
    """
    table = _engines_for(A)
    fn = table.get(resolve_engine(A, engine))
    if fn is None:
        raise ValueError(f"no SPMV engine {engine!r} (or jnp fallback) for {type(A).__name__}")
    return fn(A, x)
