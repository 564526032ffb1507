"""The benchmark's operators and its plain reference, independent of the program.

A configuration names a box stencil: a ``(2r+1)^3``-point stencil on a
``side^3`` grid with Dirichlet truncation in grid coordinates, ``-1`` on
every tap that stays inside the grid, and on the centre either the
constant ``diagonal`` or, without it, ``(#taps inside - 1) + sigma``.
``r = 1`` with ``diagonal = 26`` is HPCG's 27-point operator; ``r = 2``
with ``sigma = 1`` the repository's 125-point Poisson operator.

* :func:`build` makes the operator on the device in one jitted call, in
  DIA storage (one row of ``data`` per diagonal, offsets sorted, the
  layout the program takes as ``DIAMatrix``): ``data[j, i] = A[i, i +
  offsets[j]]``.
* :func:`apply_f64` is the plain reference: ``A x`` in float64 on the
  host, computed matrix-free as ``(c + 1) x - box(x)``, where ``box``
  sums the ``(2r+1)^3`` neighbourhood with zero padding and ``c`` is the
  centre's value. It shares no code and no
  layout with the DIA form, and :func:`rel_residual` judges a solution
  by it.
* :func:`spmv` is a plain DIA product in jnp, for the reference solver
  that stands in for the program in the control.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from functools import partial

import numpy as np


@dataclass(frozen=True)
class Stencil:
    side: int
    radius: int
    sigma: float = 0.0
    dtype: str = "float32"
    diagonal: float | None = None   # a constant centre, in place of (#taps inside - 1) + sigma

    @property
    def n(self) -> int:
        return self.side**3

    @property
    def taps(self) -> list[tuple[int, int, int]]:
        r = self.radius
        return list(itertools.product(range(-r, r + 1), repeat=3))

    def offset(self, tap) -> int:
        # axis k has stride side**k: index i = c0 + c1*side + c2*side**2
        return sum(t * self.side**k for k, t in enumerate(tap))

    @property
    def offsets(self) -> tuple[int, ...]:
        return tuple(sorted({self.offset(t) for t in self.taps}))

    @property
    def n_diags(self) -> int:
        return len(self.offsets)


def from_config(op: dict) -> Stencil:
    if op.get("kind") != "box_stencil":
        raise ValueError(f"unknown operator kind {op.get('kind')!r}; have 'box_stencil'")
    if ("sigma" in op) == ("diagonal" in op):
        raise ValueError("a box_stencil states exactly one of 'sigma' and 'diagonal'")
    diagonal = op.get("diagonal")
    st = Stencil(side=int(op["side"]), radius=int(op["radius"]),
                 sigma=float(op.get("sigma", 0.0)), dtype=str(op.get("dtype", "float32")),
                 diagonal=None if diagonal is None else float(diagonal))
    if st.side < 2 * st.radius + 1:
        raise ValueError(f"grid side {st.side} is below the stencil width {2 * st.radius + 1}")
    return st


def build(st: Stencil):
    """The operator's diagonals, shape ``(n_diags, n)``, made on the device."""
    import jax

    return jax.jit(partial(_build, st))()


def _build(st: Stencil):
    import jax.numpy as jnp

    n, s, r = st.n, st.side, st.radius
    idx = jnp.arange(n, dtype=jnp.int32)
    coords = [(idx // s**k) % s for k in range(3)]
    # inside[k][t]: coordinate k moved by t stays on the grid
    inside = [{t: (c + t >= 0) & (c + t < s) for t in range(-r, r + 1)} for c in coords]
    count = None
    for k in range(3):
        per_axis = sum(m.astype(jnp.int32) for m in inside[k].values())
        count = per_axis if count is None else count * per_axis
    if st.diagonal is None:
        centre = (count - 1).astype(st.dtype) + jnp.asarray(st.sigma, st.dtype)
    else:
        centre = jnp.full((n,), st.diagonal, st.dtype)
    rows = {}
    for tap in st.taps:
        if not any(tap):
            continue
        ok = inside[0][tap[0]] & inside[1][tap[1]] & inside[2][tap[2]]
        rows[st.offset(tap)] = jnp.where(ok, -1.0, 0.0).astype(st.dtype)
    rows[0] = centre
    return jnp.stack([rows[o] for o in st.offsets])


def _box_sum(X: np.ndarray, r: int) -> np.ndarray:
    """Sum over the (2r+1)^3 neighbourhood, zero outside the grid (separable)."""
    s = X.shape[0]
    for axis in range(3):
        pad = [(0, 0)] * 3
        pad[axis] = (r, r)
        P = np.pad(X, pad)
        acc = np.zeros_like(X)
        for i in range(2 * r + 1):
            sl = [slice(None)] * 3
            sl[axis] = slice(i, i + s)
            acc += P[tuple(sl)]
        X = acc
    return X


def _inside_count(st: Stencil) -> np.ndarray:
    c = np.arange(st.side)
    per_axis = (np.minimum(c + st.radius, st.side - 1) - np.maximum(c - st.radius, 0) + 1)
    per_axis = per_axis.astype(np.float64)
    # array axes are (c2, c1, c0): index i = c0 + c1*side + c2*side**2
    return per_axis[:, None, None] * per_axis[None, :, None] * per_axis[None, None, :]


def apply_f64(st: Stencil, x) -> np.ndarray:
    """Reference ``A x`` in float64 on the host, matrix-free."""
    s = st.side
    X = np.asarray(x, dtype=np.float64).reshape(s, s, s)
    centre = _inside_count(st) - 1 + st.sigma if st.diagonal is None else st.diagonal
    out = (centre + 1) * X - _box_sum(X, st.radius)
    return out.reshape(-1)


def rel_residual(st: Stencil, x, b) -> float:
    """``||b - A x|| / ||b||`` in float64 by the reference operator."""
    b64 = np.asarray(b, dtype=np.float64).reshape(-1)
    r = b64 - apply_f64(st, x)
    return float(np.linalg.norm(r) / np.linalg.norm(b64))


def spmv(data, offsets: tuple[int, ...], x):
    """Plain DIA product: ``y[i] = sum_j data[j, i] * x[i + offsets[j]]``."""
    import jax.numpy as jnp

    n = x.shape[-1]
    y = jnp.zeros_like(x)
    for j, o in enumerate(offsets):
        if o >= 0:
            xs = jnp.concatenate([x[o:], jnp.zeros((o,), x.dtype)])
        else:
            xs = jnp.concatenate([jnp.zeros((-o,), x.dtype), x[: n + o]])
        y = y + data[j] * xs
    return y
