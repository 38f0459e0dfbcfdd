"""Mixture-of-Experts layer with IPS4o-style sort-based token dispatch.

This is the paper's technique as a first-class framework feature (DESIGN.md
§3): routing n tokens to E experts *is* the paper's distribution problem —
the "classifier" is the router's expert id instead of a splitter-tree
descent, and the rest of the machinery is identical:

  local classification -> per-tile expert histograms  (core.partition)
  prefix sum           -> per-expert write offsets
  block permutation    -> the stable partition permutation groups tokens
                          into contiguous per-expert runs
  cleanup / overflow   -> capacity clamping: tokens ranked beyond an
                          expert's capacity land in a *drop bucket* — the
                          equality-bucket/overflow-block analogue.

The grouped tokens feed a dense batched expert matmul (E-contiguous runs =
the MXU-friendly layout), then the inverse permutation + top-k combine
weights scatter results back.  Under EP the expert dimension is sharded over
the ``model`` mesh axis; XLA turns the gather/scatter into the
all-to-all pair, matching the paper's "data distribution in distributed
memory algorithms" use.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.partition import partition_permutation
from repro.models.layers import dense, init_dense, shard_hint
from repro.models.policy import current_policy

__all__ = ["init_moe", "moe_ffn", "sort_dispatch", "expert_capacity"]

Params = Dict[str, Any]


def expert_capacity(num_tokens: int, num_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    cap = int(math.ceil(num_tokens * top_k / num_experts * capacity_factor))
    return max(8, -(-cap // 8) * 8)


def init_moe(
    key,
    d_model: int,
    *,
    num_experts: int,
    d_ff_expert: int,
    top_k: int,
    num_shared: int = 0,
    d_ff_shared: int = 0,
    dtype=jnp.bfloat16,
) -> Params:
    kr, ke, ks = jax.random.split(key, 3)
    scale = 1.0 / math.sqrt(d_model)
    kg, ku, kd = jax.random.split(ke, 3)
    p: Params = {
        "router": init_dense(kr, d_model, num_experts, dtype=jnp.float32),
        "experts": {
            "gate": (jax.random.normal(kg, (num_experts, d_model, d_ff_expert),
                                       jnp.float32) * scale).astype(dtype),
            "up": (jax.random.normal(ku, (num_experts, d_model, d_ff_expert),
                                     jnp.float32) * scale).astype(dtype),
            "down": (jax.random.normal(kd, (num_experts, d_ff_expert, d_model),
                                       jnp.float32) / math.sqrt(d_ff_expert)
                     ).astype(dtype),
        },
    }
    if num_shared:
        kg2, ku2, kd2 = jax.random.split(ks, 3)
        dff = d_ff_shared or d_ff_expert * num_shared
        p["shared"] = {
            "gate": init_dense(kg2, d_model, dff, dtype=dtype),
            "up": init_dense(ku2, d_model, dff, dtype=dtype),
            "down": init_dense(kd2, dff, d_model, dtype=dtype),
        }
    return p


def sort_dispatch(
    expert_id: jax.Array,   # (n*k,) or (L, n*k) int32 expert assignment
    num_experts: int,
    capacity: int,
    *,
    tile: int = 2048,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The paper's partition machinery applied to MoE routing.

    Returns (slot, kept, counts):
      slot (n*k,) int32: destination slot in the (E*capacity,) grouped
        buffer; dropped (over-capacity) entries point at slot E*capacity
        (a trash slot — the overflow block).
      kept (n*k,) bool; counts (E,) tokens per expert pre-clamp.

    A 2-D ``expert_id`` (L, n*k) dispatches L independent routing problems
    (e.g. every MoE layer of a step) in ONE call and one trace — the
    batch-axis-native form (DESIGN.md §6): per-row stable partitions,
    outputs gain the leading L dimension.  The 1-D path is the L=1 case
    of the same implementation, so per-layer parity is structural.
    """
    if expert_id.ndim == 2:
        return _sort_dispatch_batched(expert_id, num_experts, capacity, tile)
    slot, kept, counts = _sort_dispatch_batched(
        expert_id[None, :], num_experts, capacity, tile
    )
    return slot[0], kept[0], counts[0]


def _sort_dispatch_batched(
    expert_id: jax.Array, num_experts: int, capacity: int, tile: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-layer routing in one call: L stable partitions, one trace."""
    L, m = expert_id.shape
    t = min(tile, m)
    if m % t:
        t = m
    perm, offsets = jax.vmap(
        lambda e: partition_permutation(e, num_experts, t)
    )(expert_id)  # (L, m), (L, E+1)
    inv = jax.vmap(
        lambda p: jnp.zeros((m,), jnp.int32).at[p].set(
            jnp.arange(m, dtype=jnp.int32), mode="promise_in_bounds"
        )
    )(perm)
    rank = inv - jnp.take_along_axis(offsets[:, :-1], expert_id, axis=1)
    kept = rank < capacity
    slot = jnp.where(kept, expert_id * capacity + rank, num_experts * capacity)
    counts = jnp.diff(offsets, axis=1)
    return slot, kept, counts


def _expert_mlp(experts: Params, xg: jax.Array) -> jax.Array:
    """xg: (E, cap, D) -> (E, cap, D); dense grouped SwiGLU."""
    g = jnp.einsum("ecd,edf->ecf", xg, experts["gate"])
    u = jnp.einsum("ecd,edf->ecf", xg, experts["up"])
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, experts["down"])


def _ambient_mesh():
    """The mesh set by ``jax.set_mesh``, or None outside one."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _moe_ep_shard_map(p, xf, gate_vals, eids, *, num_experts, top_k,
                      capacity_factor, mesh, ep_axis="model"):
    """Explicit expert parallelism (§Perf, ``ComputePolicy.explicit_ep``).

    The Megatron-TP contract makes activations entering the FFN replicated
    over the ``model`` axis, so every model-column already HOLDS every
    token of its dp shard: no dispatch all-to-all is needed at all.  Each
    column selects the (token, k) entries routed to its E/TP local experts
    with the IPS4o partition machinery, computes the grouped MLP, combines
    locally, and a single psum over ``model`` (the same reduce a dense
    MLP's row-parallel matmul needs) sums the per-column partials.

    This replaces the baseline's GSPMD-lowered scatter into a globally
    sharded (E, cap, d) buffer — which XLA implements as all-reduces of
    the WHOLE buffer per layer (the dominant collective term of both MoE
    archs' baseline roofline).
    """
    from jax.sharding import PartitionSpec as P

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_total = 1
    for a in dp or ():
        dp_total *= mesh.shape[a]
    dp = dp if dp else None
    e_loc = num_experts // mesh.shape[ep_axis]
    n, d = xf.shape
    # per-dp-shard capacity: each column only ever sees n/dp tokens, so the
    # buffer (and the grouped matmul) must be sized for THAT — the paper's
    # per-thread buffer blocks, not one global buffer (fixes the 2.4x
    # compute regression of the first explicit-EP cut, §Perf iteration 2b)
    cap = expert_capacity(n // dp_total, num_experts, top_k, capacity_factor)

    def column(xf, gates, eids, experts):
        nl = xf.shape[0]
        j = jax.lax.axis_index(ep_axis)
        lo = j * e_loc
        flat_e = eids.reshape(nl * top_k).astype(jnp.int32)
        local_e = flat_e - lo
        mine = (local_e >= 0) & (local_e < e_loc)
        # foreign entries land in pseudo-bucket e_loc; its slots are never
        # fed to an expert (the trash region of the buffer)
        bucket = jnp.where(mine, local_e, e_loc)
        slot, kept, counts = sort_dispatch(bucket, e_loc + 1, cap)
        kept = kept & mine
        buf = jnp.zeros(((e_loc + 1) * cap + 1, d), xf.dtype)
        tok_idx = jnp.repeat(jnp.arange(nl, dtype=jnp.int32), top_k)
        buf = buf.at[slot].set(jnp.take(xf, tok_idx, axis=0),
                               mode="promise_in_bounds")
        xg = buf[: e_loc * cap].reshape(e_loc, cap, d)
        yg = _expert_mlp(experts, xg).reshape(e_loc * cap, d)
        pad = jnp.zeros((cap + 1, d), yg.dtype)        # trash region reads 0
        yg = jnp.concatenate([yg, pad], axis=0)
        y_tok = jnp.take(yg, slot, axis=0)
        wts = (gates.reshape(nl * top_k) * kept).astype(jnp.float32)
        y = jnp.zeros((nl, d), jnp.float32).at[tok_idx].add(
            y_tok.astype(jnp.float32) * wts[:, None],
            mode="promise_in_bounds",
        )
        # the Megatron row-parallel reduce — the ONLY collective of the
        # routed path (replaces the baseline's whole-buffer all-reduces)
        y = jax.lax.psum(y, ep_axis)
        dropped = jnp.sum(mine & ~kept)
        counts = counts[:e_loc]
        if dp:  # per-dp-shard partials -> global stats
            dropped = jax.lax.psum(dropped, dp)
            counts = jax.lax.psum(counts, dp)
        return y, dropped, counts

    espec = jax.tree.map(lambda _: P(ep_axis, None, None), p["experts"])
    f = jax.shard_map(
        column,
        mesh=mesh,
        in_specs=(P(dp, None), P(dp, None), P(dp, None), espec),
        out_specs=(P(dp, None), P(), P(ep_axis)),
        check_vma=False,
    )
    return f(xf, gate_vals, eids, p["experts"])


def moe_ffn(
    p: Params,
    x: jax.Array,   # (B, S, D)
    *,
    num_experts: int,
    top_k: int,
    capacity_factor: float = 1.25,
    router_softmax_after: bool = True,
    ep_axis: Optional[str] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Returns (output, aux) where aux carries load-balancing stats."""
    b, s, d = x.shape
    n = b * s
    xf = x.reshape(n, d)
    logits = dense(p["router"], xf.astype(jnp.float32))  # (n, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, eids = jax.lax.top_k(probs, top_k)         # (n, k)
    if router_softmax_after:
        gate_vals = gate_vals / (jnp.sum(gate_vals, axis=-1, keepdims=True) + 1e-9)

    cap = expert_capacity(n, num_experts, top_k, capacity_factor)

    mesh = _ambient_mesh()
    if (current_policy().explicit_ep and mesh is not None
            and "model" in mesh.axis_names
            and num_experts % mesh.shape["model"] == 0):
        y, dropped, counts = _moe_ep_shard_map(
            p, xf, gate_vals, eids, num_experts=num_experts, top_k=top_k,
            capacity_factor=capacity_factor, mesh=mesh)
        if "shared" in p:
            sh = p["shared"]
            g = dense(sh["gate"], xf)
            u = dense(sh["up"], xf)
            y = y + dense(sh["down"], jax.nn.silu(g) * u).astype(jnp.float32)
        me = jnp.mean(probs, axis=0)
        ce = counts.astype(jnp.float32) / (n * top_k)
        aux = {
            "lb_loss": num_experts * jnp.sum(me * ce),
            "dropped": dropped.astype(jnp.int32),
            "max_load": jnp.max(counts),
        }
        return y.reshape(b, s, d).astype(x.dtype), aux

    flat_e = eids.reshape(n * top_k).astype(jnp.int32)
    slot, kept, counts = sort_dispatch(flat_e, num_experts, cap)

    # scatter tokens into the grouped (E, cap) buffer (trash slot at the end)
    buf = jnp.zeros((num_experts * cap + 1, d), x.dtype)
    tok_idx = jnp.repeat(jnp.arange(n, dtype=jnp.int32), top_k)
    buf = buf.at[slot].set(jnp.take(xf, tok_idx, axis=0),
                           mode="promise_in_bounds")
    # EP: grouped buffer sharded expert-major over the model axis — the
    # scatter above + gather below become the dispatch/return all-to-alls
    xg = shard_hint(buf[:-1].reshape(num_experts, cap, d), "model", None, None)
    yg = _expert_mlp(p["experts"], xg).reshape(num_experts * cap, d)
    yg = jnp.concatenate([yg, jnp.zeros((1, d), yg.dtype)], axis=0)

    # combine: gather back + weight; dropped entries read the zero trash slot
    y_tok = jnp.take(yg, slot, axis=0)  # (n*k, d)
    wts = (gate_vals.reshape(n * top_k) * kept).astype(jnp.float32)
    y = jnp.zeros((n, d), jnp.float32).at[tok_idx].add(
        y_tok.astype(jnp.float32) * wts[:, None], mode="promise_in_bounds"
    )

    if "shared" in p:
        sh = p["shared"]
        g = dense(sh["gate"], xf)
        u = dense(sh["up"], xf)
        y = y + dense(sh["down"], jax.nn.silu(g) * u).astype(jnp.float32)

    # load-balance aux loss terms (Switch-style)
    me = jnp.mean(probs, axis=0)                       # (E,)
    ce = counts.astype(jnp.float32) / (n * top_k)
    aux = {
        "lb_loss": num_experts * jnp.sum(me * ce),
        "dropped": jnp.sum(~kept).astype(jnp.int32),
        "max_load": jnp.max(counts),
    }
    return y.reshape(b, s, d).astype(x.dtype), aux
