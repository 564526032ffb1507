"""Mixture-of-Experts FFN: top-k routing with capacity-bounded dispatch.

Two execution paths:

* ``moe_ffn`` — GShard-style "dropping" dispatch in plain pjit ops.
  Baseline: GSPMD must reshard the (E, C, d) dispatch buffer between the
  token layout (batch over data) and the expert layout (E over model),
  which it does with gather fall-backs ("involuntary full
  rematerialization") — the collective storm visible in the 40-cell
  baseline (EXPERIMENTS.md §Roofline: olmoe/granite cells).

* ``moe_ffn_sharded`` — explicit ``shard_map`` dispatch (§Perf fix).
  Key observation: activations are REPLICATED over the model axis (only
  batch is sharded over data), so every (data, model) device already holds
  its tokens AND its expert shard. Dispatch/combine are then purely local
  per device, each device computes its local experts' contribution for its
  local tokens, and ONE ``psum`` over the model axis assembles the output —
  the same single-AR cost as a dense tensor-parallel FFN. Capacity is per
  (data shard x expert), so routing quality matches the baseline on
  uniformly-shuffled batches.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import ParamSpec, current_mesh, shard_hint

__all__ = ["moe_params", "moe_ffn", "moe_ffn_sharded", "moe_capacity"]


def moe_params(d: int, f: int, n_experts: int) -> dict:
    return {
        "router": ParamSpec((d, n_experts), ("embed", None)),
        "w_gate": ParamSpec((n_experts, d, f), ("experts", "embed", "expert_mlp")),
        "w_up": ParamSpec((n_experts, d, f), ("experts", "embed", "expert_mlp")),
        "w_down": ParamSpec((n_experts, f, d), ("experts", "expert_mlp", "embed")),
    }


def moe_capacity(n_tokens: int, top_k: int, n_experts: int, capacity_factor: float) -> int:
    c = int(capacity_factor * n_tokens * top_k / n_experts)
    return max(8, ((c + 7) // 8) * 8)


def moe_ffn(p: dict, x: jax.Array, top_k: int, capacity_factor: float = 1.25,
            norm_topk: bool = True) -> tuple[jax.Array, jax.Array]:
    """x (T, d) -> (y (T, d), aux_loss scalar).

    aux_loss is the standard load-balancing loss (mean over experts of
    frac_tokens * frac_prob * E).
    """
    T, d = x.shape
    E = p["router"].shape[-1] if isinstance(p["router"], jax.Array) else p["router"].shape[-1]
    logits = (x @ p["router"]).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)  # (T, k)
    if norm_topk:
        gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

    C = moe_capacity(T, top_k, E, capacity_factor)
    A = T * top_k
    flat_e = expert_idx.reshape(A)  # assignment -> expert
    tok_of = jnp.arange(A) // top_k  # assignment -> token

    # rank each assignment within its expert (stable: earlier tokens first)
    order = jnp.argsort(flat_e, stable=True)  # (A,)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")  # (E,)
    pos_sorted = jnp.arange(A) - first[sorted_e]
    pos = jnp.zeros((A,), jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))
    keep = pos < C

    # dispatch: (E, C, d) buffer; dropped assignments scatter out of bounds
    drop_pos = jnp.where(keep, pos, C)  # == C -> dropped by mode="drop"
    buf = jnp.zeros((E, C, d), x.dtype)
    buf = buf.at[flat_e, drop_pos].set(x[tok_of], mode="drop")
    buf = shard_hint(buf, ("experts", None, None))

    # expert computation (E-parallel)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) * jnp.einsum(
        "ecd,edf->ecf", buf, p["w_up"]
    )
    out_e = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    out_e = shard_hint(out_e, ("experts", None, None))

    # combine: gather each kept assignment's output, weight by its gate
    safe_pos = jnp.minimum(pos, C - 1)
    y_a = out_e[flat_e, safe_pos]  # (A, d)
    wts = gate_vals.reshape(A).astype(x.dtype) * keep.astype(x.dtype)
    y = (y_a * wts[:, None]).reshape(T, top_k, d).sum(axis=1)

    # load-balance aux loss (Switch): E * sum_e f_e * P_e
    assign_onehot = jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32)
    f_e = assign_onehot.mean(axis=0)
    P_e = probs.mean(axis=0)
    aux = E * jnp.sum(f_e * P_e)
    return y, aux


def moe_ffn_sharded(p: dict, x3: jax.Array, top_k: int, capacity_factor: float = 1.25,
                    norm_topk: bool = True) -> tuple[jax.Array, jax.Array]:
    """Explicit shard_map MoE (see module docstring). x3 is (B, T, d).

    Per (data, model) device: route MY tokens, keep only assignments to MY
    expert shard, compute locally, then ONE psum over 'model' combines the
    per-expert-shard partial outputs. No dispatch buffer ever crosses the
    interconnect.
    """
    mesh = current_mesh()
    assert mesh is not None, "moe_ffn_sharded requires use_sharding_rules(..., mesh=...)"
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    E = p["router"].shape[-1]
    M = mesh.shape["model"]
    assert E % M == 0, (E, M)
    E_loc = E // M
    n_data = 1
    for a in batch_axes:
        n_data *= mesh.shape[a]

    def body(xb, router, wg, wu, wd):
        B_loc, T, d = xb.shape
        n_tok = B_loc * T
        xf = xb.reshape(n_tok, d)
        logits = (xf @ router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, top_k)
        if norm_topk:
            gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

        C = moe_capacity(n_tok, top_k, E, capacity_factor)
        A = n_tok * top_k
        flat_e = expert_idx.reshape(A)
        tok_of = jnp.arange(A) // top_k
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        first = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
        pos_sorted = jnp.arange(A) - first[sorted_e]
        pos = jnp.zeros((A,), jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))
        keep = pos < C

        # keep only MY expert shard's assignments
        e0 = jax.lax.axis_index("model") * E_loc
        mine = (flat_e >= e0) & (flat_e < e0 + E_loc)
        local_e = jnp.clip(flat_e - e0, 0, E_loc - 1)
        drop_pos = jnp.where(keep & mine, pos, C)  # others dropped by mode="drop"
        buf = jnp.zeros((E_loc, C, d), xb.dtype)
        buf = buf.at[local_e, drop_pos].set(xf[tok_of], mode="drop")

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * jnp.einsum("ecd,edf->ecf", buf, wu)
        out_e = jnp.einsum("ecf,efd->ecd", h, wd)

        safe_pos = jnp.minimum(pos, C - 1)
        y_a = out_e[local_e, safe_pos]
        wts = gate_vals.reshape(A).astype(xb.dtype) * (keep & mine).astype(xb.dtype)
        y = (y_a * wts[:, None]).reshape(n_tok, top_k, d).sum(axis=1)
        y = jax.lax.psum(y, "model")  # the ONLY cross-device traffic

        # aux is identical on every model shard (same tokens, same router):
        # reduce over the batch axes only (mean over data shards)
        assign_onehot = jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32)
        aux = E * jnp.sum(assign_onehot.mean(0) * probs.mean(0))
        aux = jax.lax.psum(aux, batch_axes) / n_data
        return y.reshape(B_loc, T, d), aux

    spec_x = P(batch_axes if len(batch_axes) > 1 else batch_axes[0], None, None)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec_x, P(None, None), P("model", None, None), P("model", None, None), P("model", None, None)),
        out_specs=(spec_x, P()),
    )
    return fn(x3, p["router"], p["w_gate"], p["w_up"], p["w_down"])
