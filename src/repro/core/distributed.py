"""Distributed PIPECG over a TPU mesh — the paper's hybrid methods, plus
communication-reduced deep pipelines and hierarchical reductions.

The paper's CPU+GPU task/data split is re-targeted to inter-chip
parallelism (DESIGN.md §2). Rows of the banded operator are partitioned
across the mesh; each method is pure *configuration* of a shared solver
loop — a reduction strategy (``core.reduce``), a distributed SPMV
strategy, and a pipeline depth (``core.iteration``):

    method   reduction           SPMV         depth  (analogue)
    ------   -----------------   ----------   -----  ------------------------
    "h1"     3 separate psums    all_gather   1      Hybrid-PIPECG-1
    "h2"     1 packed psum       all_gather   1      Hybrid-PIPECG-2
    "h3"     1 packed psum       halo         1      Hybrid-PIPECG-3 (2-D)
    "h4"     hierarchical 2-st.  halo         1      intra-pod + inter-pod
    "pl2"    1 packed Gram psum  halo         2      deep pipeline, 1 red/2 it
    "pl3"    1 packed Gram psum  halo         3      deep pipeline, 1 red/3 it

See ``docs/distributed.md`` for the full selection matrix
(reductions/iteration, when to use which, residual-replacement guidance).

SPMV strategies:

``allgather`` — full-vector SPMV (N elements over the interconnect per
    SPMV, like the paper's full-vector PCIe copies); equal shards only.
``halo`` — local band part (paper's nnz1, needs only resident x) plus
    boundary corrections (nnz2) fed by ring ``ppermute``s of
    bandwidth-sized slabs. The halo exchange is dataflow-independent of
    SPMV part 1 — exactly the overlap the paper engineers with CUDA
    streams. Supports performance-model (unequal) partitions, and —
    for equal shards — *multi-hop* halos when the band is wider than a
    shard (tiny shards on big stencils): ``ceil(bandwidth/rows)`` ring
    shifts build the halo from as many neighbors as the band reaches.

Reduction strategies come from ``core.reduce`` (``separate``/``packed``/
``h4`` hierarchical); the ``reducer=``/``spmv=`` overrides recombine any
method with any strategy. The hierarchical reducer needs a 2-D
``(pod, sub)`` mesh — build one with ``make_solver_mesh(n, sub=...)``.

All methods run a canonical loop inside one ``shard_map``-ped
``lax.while_loop``: ``run_pipecg`` for depth-1 methods, the depth-l
coordinate loop from ``make_deep_pipecg_core`` for ``pl2``/``pl3``
(jaxpr census: ONE global reduction per *l* iterations). Residual
replacement (``replace_every``) threads through every method. With
``nrhs=k`` the whole k-rhs batch runs as ONE program — the solver loop
is ``vmap``-ed *inside* the shard_map block, so every global reduction
carries k systems' partials at once (k-fold useful work per reduction,
zero Python-level per-rhs loops). New methods = new registry entries.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..obs.trace import trace_scope
from ..sparse.partition import ShardedDIA
from .iteration import get_core, make_deep_pipecg_core, run_pipecg
from .reduce import make_reducer, reducer_needs_subaxis
from .types import SolveResult

__all__ = [
    "pipecg_distributed",
    "build_distributed_solver",
    "make_solver_mesh",
    "spmv_halo",
    "spmv_allgather",
    "DistMethod",
    "get_method",
    "register_dist_spmv",
    "register_method",
    "method_names",
]


def make_solver_mesh(n_shards: int, axis: str = "rows", sub: Optional[int] = None) -> Mesh:
    """Mesh over the first n_shards devices.

    ``sub=None`` — 1-D mesh ``(axis,)``. ``sub=k`` — 2-D hierarchical
    mesh ``("pod", axis)`` of shape ``(n_shards // k, k)``: ``k`` devices
    per pod, linear device order preserved (pod-major), as the
    hierarchical "h4" reducer requires. Row sharding then runs over the
    flattened ``("pod", axis)`` axes, so every SPMV strategy keeps its
    linear ring/gather order.
    """
    devs = np.array(jax.devices()[:n_shards])
    if sub is None:
        return Mesh(devs, (axis,))
    if sub < 1 or n_shards % sub:
        raise ValueError(
            f"sub-axis size {sub} must divide the shard count {n_shards} "
            f"(pods of equal size)"
        )
    return Mesh(devs.reshape(n_shards // sub, sub), ("pod", axis))


# ---------------------------------------------------------------------------
# distributed SPMV strategies (called inside shard_map)
# ---------------------------------------------------------------------------
#
# Uniform signature:
#   fn(data, x, rows, *, offsets, hw, axis, n_shards, hops) -> y_local
# ``axis`` is a mesh-axis name or tuple of names (2-D hierarchical mesh);
# linear shard order is the flattened axis order either way. ``hops`` is
# the static halo reach in whole shards (ceil(hw / rows)) when shards are
# equal-sized, or None for the dynamic unequal-shard path.


def spmv_allgather(data, x, rows, offsets: Tuple[int, ...], hw: int, axis, n_shards: int = 0,
                   hops: Optional[int] = 1):
    """Full-vector SPMV: all_gather m, then band-multiply my row block.

    Requires equal shard sizes (rows == R on every shard). This is the
    h1/h2 communication pattern: N elements over the interconnect per
    SPMV, like the paper's full-vector PCIe copies. ``n_shards``/``hops``
    are part of the uniform strategy signature but unused (all_gather
    discovers the mesh, and a full gather has no hop structure). Band
    width may exceed the shard size — the gathered vector covers any
    offset.
    """
    R = x.shape[0]
    xfull = jax.lax.all_gather(x, axis)  # (..., R): leading mesh axes
    flat = xfull.reshape(-1)
    flat = jnp.concatenate([jnp.zeros((hw,), x.dtype), flat, jnp.zeros((hw,), x.dtype)])
    p = jax.lax.axis_index(axis)  # linear index, also for tuple axes
    y = jnp.zeros((R,), x.dtype)
    for j, o in enumerate(offsets):
        seg = jax.lax.dynamic_slice(flat, (hw + p * R + o,), (R,))
        y = y + data[j] * seg
    del rows  # equal shards: validity handled by zero data/x padding
    return y


def _shift_segment(x, o: int):
    """x shifted by offset o with zero fill — valid for any |o| (>= R too)."""
    R = x.shape[0]
    if o == 0:
        return x
    if o > 0:
        return jnp.concatenate([x[o:], jnp.zeros((min(o, R),), x.dtype)])
    return jnp.concatenate([jnp.zeros((min(-o, R),), x.dtype), x[:o] if -o < R else x[:0]])


def spmv_halo(data, x, rows, offsets: Tuple[int, ...], hw: int, axis, n_shards: int,
              hops: Optional[int] = 1):
    """2-D decomposed SPMV: local band (nnz1) + halo corrections (nnz2).

    Only boundary slabs cross the interconnect (ring ppermute); SPMV
    part 1 has no data dependency on them — the overlap surface.

    Two paths, chosen statically at build time:

    * ``hops=None`` — unequal (performance-model) shard sizes, halo width
      ``hw`` <= smallest shard: one dynamic-sliced slab per direction
      from the ring neighbors (the original h3 exchange).
    * ``hops=k`` (equal shards) — static path supporting ``hw`` larger
      than a shard: ``k = ceil(hw / R)`` whole-block ring shifts per
      direction assemble a ``k*R``-wide halo buffer, so a band that spans
      several shards reads every neighbor it touches (multi-hop). For
      ``k=1`` this degenerates to the classic single-slab exchange with
      static slices. Edge shards receive zero-filled halos (ppermute
      semantics), matching the DIA zero-outside-band convention.
    """
    R = x.shape[0]
    if hops is not None:
        # ---- equal shards: static (possibly multi-hop) halo path ----
        if hops * R < hw:
            raise ValueError(f"hops={hops} x rows={R} cannot cover bandwidth {hw}")
        # issue all halo shifts first (independent of part 1)
        right_blocks = [
            jax.lax.ppermute(x, axis, [(p, p - k) for p in range(k, n_shards)])
            for k in range(1, hops + 1)
        ]  # blocks of shards p+1 .. p+hops, in order
        left_blocks = [
            jax.lax.ppermute(x, axis, [(p, p + k) for p in range(n_shards - k)])
            for k in range(hops, 0, -1)
        ]  # blocks of shards p-hops .. p-1, in order
        right_buf = jnp.concatenate(right_blocks) if right_blocks else x[:0]
        left_buf = jnp.concatenate(left_blocks) if left_blocks else x[:0]
        L = hops * R

        # --- SPMV part 1: local columns only (paper's nnz1) ---
        y = jnp.zeros((R,), x.dtype)
        for j, o in enumerate(offsets):
            y = y + data[j] * _shift_segment(x, o)

        # --- SPMV part 2: boundary corrections (paper's nnz2) ---
        for j, o in enumerate(offsets):
            if o > 0:
                # rows [max(R-o,0), R) read the right halo buffer
                start = max(R - o, 0)
                w = R - start
                y = y.at[start:].add(
                    data[j][start:] * jax.lax.slice(right_buf, (o - R + start,),
                                                    (o - R + start + w,))
                )
            elif o < 0:
                # rows [0, min(-o,R)) read the left halo buffer
                w = min(-o, R)
                y = y.at[:w].add(
                    data[j][:w] * jax.lax.slice(left_buf, (L + o,), (L + o + w,))
                )
        return y

    # ---- unequal shards: dynamic single-hop path (hw <= min shard) ----
    # --- issue halo exchange (independent of part 1) ---
    head = x[:hw]  # my first hw valid rows -> left neighbor's right halo
    tail = jax.lax.dynamic_slice(x, (rows - hw,), (hw,))  # my last hw valid rows
    right_halo = jax.lax.ppermute(head, axis, [(p, p - 1) for p in range(1, n_shards)])
    left_halo = jax.lax.ppermute(tail, axis, [(p, p + 1) for p in range(n_shards - 1)])

    # --- SPMV part 1: local columns only (paper's nnz1) ---
    y = jnp.zeros((R,), x.dtype)
    for j, o in enumerate(offsets):
        y = y + data[j] * _shift_segment(x, o)

    # --- SPMV part 2: boundary corrections (paper's nnz2) ---
    for j, o in enumerate(offsets):
        if o > 0:
            # rows [rows-o, rows) read the right neighbor's first o entries
            dslab = jax.lax.dynamic_slice(data[j], (rows - o,), (o,))
            yslab = jax.lax.dynamic_slice(y, (rows - o,), (o,))
            y = jax.lax.dynamic_update_slice(y, yslab + dslab * right_halo[:o], (rows - o,))
        elif o < 0:
            # rows [0, -o) read the left neighbor's last -o entries
            y = y.at[: -o].add(data[j][: -o] * left_halo[hw + o :])
    return y


_DIST_SPMV = {"allgather": spmv_allgather, "halo": spmv_halo}
# strategies that index the gathered vector by p*R: all shards one size
_EQUAL_ONLY_SPMV = {"allgather"}


def register_dist_spmv(name: str, fn, *, overwrite: bool = False,
                       equal_shards_only: bool = False) -> None:
    """Register a distributed SPMV strategy (uniform signature above).

    Raises ValueError if ``name`` is already registered, unless
    ``overwrite=True`` — silent replacement hides plug-in clashes.
    """
    if name in _DIST_SPMV and not overwrite:
        raise ValueError(
            f"distributed SPMV strategy {name!r} already registered; pass "
            f"overwrite=True to replace it"
        )
    _DIST_SPMV[name] = fn
    if equal_shards_only:
        _EQUAL_ONLY_SPMV.add(name)


# ---------------------------------------------------------------------------
# methods = (reduction, SPMV, pipeline depth) configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DistMethod:
    """A distributed execution strategy for the shared solver loops.

    ``pipeline_depth`` selects the loop: 1 = PIPECG (``run_pipecg``,
    one reduction per iteration, overlapped with one SPMV); l >= 2 = the
    depth-l communication-reduced loop (``make_deep_pipecg_core``, ONE
    packed Gram reduction per l iterations).
    """

    reduce: str  # core.reduce strategy name
    spmv: str  # key into _DIST_SPMV
    equal_shards_only: bool  # allgather indexes by p*R: all shards same size
    pipeline_depth: int = 1  # iterations amortized per global reduction


_METHODS = {
    "h1": DistMethod(reduce="separate", spmv="allgather", equal_shards_only=True),
    "h2": DistMethod(reduce="packed", spmv="allgather", equal_shards_only=True),
    "h3": DistMethod(reduce="packed", spmv="halo", equal_shards_only=False),
    "h4": DistMethod(reduce="h4", spmv="halo", equal_shards_only=False),
    "pl2": DistMethod(reduce="packed", spmv="halo", equal_shards_only=False,
                      pipeline_depth=2),
    "pl3": DistMethod(reduce="packed", spmv="halo", equal_shards_only=False,
                      pipeline_depth=3),
}


def register_method(name: str, method: DistMethod, *, overwrite: bool = False) -> None:
    """Register a new (reducer, spmv, depth) combination as a named method.

    Raises ValueError if ``name`` is already registered, unless
    ``overwrite=True`` — silent replacement hides plug-in clashes.
    """
    from .reduce import reducer_names

    if name in _METHODS and not overwrite:
        raise ValueError(
            f"distributed method {name!r} already registered; pass "
            f"overwrite=True to replace it"
        )
    if method.spmv not in _DIST_SPMV:
        raise ValueError(
            f"unknown SPMV strategy {method.spmv!r}; register it first via "
            f"register_dist_spmv (have {tuple(sorted(_DIST_SPMV))})"
        )
    if method.reduce not in reducer_names():
        raise ValueError(
            f"unknown reduction strategy {method.reduce!r}; register it first "
            f"via core.reduce.register_reducer (have {reducer_names()})"
        )
    if method.pipeline_depth < 1:
        raise ValueError(f"pipeline_depth must be >= 1, got {method.pipeline_depth}")
    _METHODS[name] = method


def method_names() -> Tuple[str, ...]:
    return tuple(sorted(_METHODS))


def get_method(name: str) -> DistMethod:
    """Look up a registered distributed method (for introspection/plans)."""
    if name not in _METHODS:
        raise ValueError(f"method must be one of {method_names()}, got {name}")
    return _METHODS[name]


# ---------------------------------------------------------------------------
# the distributed solver: shard_map around the shared loop
# ---------------------------------------------------------------------------

def build_distributed_solver(
    As: ShardedDIA,
    *,
    mesh: Mesh,
    axis: str = "rows",
    method: str = "h3",
    engine: str = "jnp",
    maxiter: int = 10000,
    reducer: Optional[str] = None,
    spmv: Optional[str] = None,
    replace_every: int = 0,
    nrhs: Optional[int] = None,
):
    """Build (once) the shard_map'd solver program for one sharded operator.

    This is the setup half of the plan/execute split: validation, strategy
    lookup and the ``shard_map`` closure happen here; the returned
    ``runner(b_sh, inv_diag_sh, atol, rtol) -> SolveResult`` only executes.
    ``atol``/``rtol`` are traced arguments, so one built runner serves any
    tolerance without recompilation; callers (``repro.plan``) wrap the
    runner in a single pinned ``jax.jit``.

    ``reducer``/``spmv`` override the method's registered strategies (any
    method x reducer x spmv recombination); ``replace_every`` threads the
    full-precision residual-replacement safety net through every method —
    recommended (e.g. 50) for the deep pipelines ``pl2``/``pl3``.

    ``nrhs=k`` builds the mesh-level *batched* program: ``b_sh`` then
    carries a rhs axis — shape (P, k, R) — and the solver loop runs
    ``vmap``-ed inside the shard_map block, ONE program for the whole
    batch whose every global reduction carries k systems' partials.
    Returned ``x`` is (P, k, R); the other fields gain a leading k.
    """
    cfg = get_method(method)
    depth = cfg.pipeline_depth
    reduce_name = cfg.reduce if reducer is None else reducer
    spmv_name = cfg.spmv if spmv is None else spmv
    if spmv_name not in _DIST_SPMV:
        raise ValueError(
            f"unknown SPMV strategy {spmv_name!r}; have {tuple(sorted(_DIST_SPMV))}"
        )
    Pn = As.n_shards
    R = As.rows_max
    hw = As.bandwidth
    offsets = As.offsets
    sizes = np.diff(np.asarray(As.boundaries))
    equal = bool((sizes == R).all())
    if (cfg.equal_shards_only or spmv_name in _EQUAL_ONLY_SPMV) and not equal:
        raise ValueError(f"{method} requires equal shards (use balanced_rows); sizes={sizes}")

    axis_names = tuple(mesh.axis_names)
    if int(np.prod(mesh.devices.shape)) != Pn:
        raise ValueError(
            f"mesh has {int(np.prod(mesh.devices.shape))} devices but the "
            f"operator is sharded {Pn} ways"
        )
    # 1-D mesh -> plain axis name; 2-D hierarchical mesh -> the axis tuple
    # (psum/all_gather/ppermute/axis_index all accept tuples; linear shard
    # order is the flattened axis order)
    ax = axis_names[0] if len(axis_names) == 1 else axis_names
    if reducer_needs_subaxis(reduce_name) and len(axis_names) < 2:
        raise ValueError(
            f"reducer {reduce_name!r} is hierarchical and needs a 2-D (pod, sub) "
            f"mesh; build one with make_solver_mesh(n_shards, sub=...)"
        )
    # static halo reach: whole shards per direction (multi-hop when the
    # band is wider than a shard); None selects the dynamic unequal path
    hops = -(-hw // R) if equal else None
    if not equal and R < hw:
        raise ValueError(
            f"bandwidth {hw} > shard rows {R} needs equal shards for the "
            f"multi-hop halo path (use balanced_rows)"
        )

    raw_spmv = partial(_DIST_SPMV[spmv_name], offsets=offsets, hw=hw, axis=ax,
                       n_shards=Pn, hops=hops)
    base_reducer = make_reducer(reduce_name, ax)
    if depth > 1:
        if engine not in ("jnp", "auto"):
            raise ValueError(
                f"deep-pipeline method {method!r} runs the coordinate loop "
                f"(no {engine!r} VMA-core backend); use engine='jnp'/'auto'"
            )
        loop = make_deep_pipecg_core(depth)
        core = None
    else:
        loop = run_pipecg
        core = get_core(engine)

    # phase annotations: the distributed SPMV and the global reduction get
    # their own HLO names (per strategy), so XLA profiles attribute
    # collective time to the schedule that caused it. trace_scope adds no
    # primitives — a no-op unless repro.obs is enabled at trace time.
    def local_spmv(data, v, rows):
        with trace_scope(f"dist.spmv.{spmv_name}"):
            return raw_spmv(data, v, rows)

    def reducer_fn(*partials):
        with trace_scope(f"dist.reduce.{reduce_name}"):
            return base_reducer(*partials)

    reducer_fn.array = getattr(base_reducer, "array", None)

    spec_mat = P(ax, None, None)
    spec_vec = P(ax, None)
    spec_scalar = P(ax)
    spec_rhs = spec_vec if nrhs is None else P(ax, None, None)

    def _one_solve(data, rows, inv_diag, b, atol, rtol):
        kwargs = dict(
            spmv_fn=lambda v: local_spmv(data, v, rows),
            pc_fn=lambda r: inv_diag * r,
            reducer=reducer_fn,
            inv_diag=inv_diag,  # PC fused into the canonical core
            atol=atol,
            rtol=rtol,
            maxiter=maxiter,
            replace_every=replace_every,
        )
        if core is not None:
            kwargs["core"] = core
        return loop(b, jnp.zeros_like(b), **kwargs)

    # check_vma=False: the Pallas iteration cores run inside this block,
    # and their kernel bodies mix replicated scalars (alpha, beta) with
    # row-sharded vector blocks, which the varying-axes check refuses
    @partial(
        jax.shard_map,
        mesh=mesh,
        check_vma=False,
        in_specs=(spec_mat, spec_scalar, spec_rhs, spec_vec, P(), P()),
        out_specs=(spec_rhs, P(), P(), P(), P()),
    )
    def _solve(data_blk, rows_blk, b_blk, inv_blk, atol, rtol):
        data = data_blk[0]  # (k_diags, R)
        rows = rows_blk[0]
        b = b_blk[0]  # (R,) — or (nrhs, R) for the batched program
        inv_diag = inv_blk[0]

        if nrhs is None:
            i, x, norm, converged, hist = _one_solve(data, rows, inv_diag, b, atol, rtol)
            return x[None], i, norm, converged, hist
        # mesh-level rhs batching: ONE program, the loop vmapped over the
        # rhs axis INSIDE shard_map — each psum/ppermute carries the whole
        # batch (k-fold useful work per global reduction)
        i, x, norm, converged, hist = jax.vmap(
            lambda bb: _one_solve(data, rows, inv_diag, bb, atol, rtol)
        )(b)
        return x[None], i, norm, converged, hist

    def runner(b_sh, inv_diag_sh, atol=1e-5, rtol=0.0) -> SolveResult:
        x, iters, norm, conv, hist = _solve(
            As.data, As.rows_valid, b_sh, inv_diag_sh,
            jnp.float32(atol), jnp.float32(rtol),
        )
        shape = (Pn, R) if nrhs is None else (Pn, nrhs, R)
        return SolveResult(
            x=x.reshape(shape), iterations=iters, residual_norm=norm,
            converged=conv, history=hist,
        )

    runner.pipeline_depth = depth
    runner.reduce_name = reduce_name
    runner.spmv_name = spmv_name
    return runner


def pipecg_distributed(
    As: ShardedDIA,
    b_sh: jax.Array,
    inv_diag_sh: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "rows",
    method: str = "h3",
    engine: str = "jnp",
    atol: float = 1e-5,
    rtol: float = 0.0,
    maxiter: int = 10000,
    reducer: Optional[str] = None,
    spmv: Optional[str] = None,
    replace_every: int = 0,
) -> SolveResult:
    """One-shot distributed PIPECG on row-sharded banded A.

    Builds the shard_map program and runs it once — the convenience form of
    :func:`build_distributed_solver` (which amortizes the build across many
    right-hand sides; ``repro.plan`` goes through that path).

    As          — ShardedDIA from repro.sparse.shard_dia (halo methods may
                  use performance-model/unequal partitions; allgather
                  methods require equal).
    b_sh        — (P, R) sharded rhs from shard_vector.
    inv_diag_sh — (P, R) sharded Jacobi inverse diagonal (use ones for no PC).
    engine      — iteration-core engine for the local block ("jnp"/"pallas"/
                  "auto"), same registry as the single-device solver
                  (depth-1 methods only — the deep pipelines run the
                  coordinate loop).
    reducer / spmv / replace_every — strategy overrides and the residual-
                  replacement period (see build_distributed_solver).
    Returns SolveResult with x of shape (P, R) padded; use unshard_vector.
    """
    runner = build_distributed_solver(
        As, mesh=mesh, axis=axis, method=method, engine=engine, maxiter=maxiter,
        reducer=reducer, spmv=spmv, replace_every=replace_every,
    )
    return runner(b_sh, inv_diag_sh, atol, rtol)
