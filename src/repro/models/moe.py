"""Mixture-of-Experts FFN: top-k routing with capacity-based dense dispatch.

Routing divisions go through the paper's unit: the router softmax and the
top-k renormalization are both ``division_modes`` call sites.

Dispatch is the capacity-C scatter/gather scheme (Switch/GShard style):
tokens sort into per-expert buffers of capacity C = ceil(T*k/E * cf); tokens
over capacity drop to the residual path. Expert weights carry the 'experts'
logical axis, so the same code runs EP (experts over a mesh axis, all-to-all
inserted by GSPMD at the scatter/gather) or expert-TP ('expert_mlp' sharded).

A layer that holds only some routed experts (``cfg.experts_held``, the
share of one chip under expert parallelism) is a mode of the ``sort``
dispatch: it routes over all E experts, sorts the (token, choice) pairs by
expert, and runs its own experts' MLPs as grouped matmuls
(``jax.lax.ragged_dot``) over the pairs routed to them, with no capacity
and no drop. Pairs routed to experts held elsewhere contribute nothing
here; the shared expert is added on every holder.

Load-balance aux loss (Switch eq. 4): aux = E * sum_e f_e * P_e.

Named scopes (op metadata only): ``moe.route`` (router, gates),
``moe.experts`` (dispatch, the routed experts' matmuls, combine) and
``moe.shared`` (the shared expert).
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import division_modes as dm

SCOPE_ROUTE = "moe.route"
SCOPE_EXPERTS = "moe.experts"
SCOPE_SHARED = "moe.shared"


def _local_shard_count(T: int) -> int:
    """Batch-shard count for moe_dispatch='local' (1 without an active mesh)."""
    from repro.sharding.rules import active_mesh

    mesh = active_mesh()
    if mesh is None:
        return 1
    D = 1
    for ax in ("pod", "data"):
        D *= mesh.shape.get(ax, 1)
    return D if (D > 1 and T % D == 0 and T // D >= 1) else 1


def _dispatch_local(p, xt, probs, gates, idx, cfg: ModelConfig, D: int):
    """Shard-local gather-based dispatch: positions, capacity and every
    gather are computed within each data shard's row block, so GSPMD keeps
    the whole dispatch collective-free (the global-scatter formulation makes
    the partitioner replicate updates across shards). Capacity is per-shard
    (standard 'local capacity' semantics of production MoE systems)."""
    from repro.sharding.rules import shard_dim

    T, d = xt.shape
    E, k = cfg.n_experts, cfg.experts_per_tok
    Tl = T // D
    capacity = max(math.ceil(Tl * k / E * cfg.capacity_factor), min(Tl * k, 4))

    xr = shard_dim(xt.reshape(D, Tl, d), 0, "data")
    er = idx.reshape(D, Tl * k)                       # expert ids per row
    gr = gates.reshape(D, Tl * k)

    def row(x_row, e_row, g_row):
        order = jnp.argsort(e_row, stable=True)       # (Tl*k,)
        sorted_e = e_row[order]
        first = jnp.searchsorted(sorted_e, jnp.arange(E))
        counts = jnp.diff(jnp.append(first, Tl * k))
        # gather-based dispatch: source token for (expert, slot)
        slot = jnp.arange(capacity)
        src_sorted_idx = first[:, None] + slot[None, :]          # (E, C)
        valid = slot[None, :] < jnp.minimum(counts[:, None], capacity)
        src_choice = order[jnp.clip(src_sorted_idx, 0, Tl * k - 1)]
        src_token = src_choice // k                              # (E, C)
        buf = jnp.where(valid[..., None], x_row[src_token], 0)   # (E, C, d)
        # return-trip bookkeeping: position of each (token, choice)
        pos_sorted = jnp.arange(Tl * k) - first[sorted_e]
        pos = jnp.zeros((Tl * k,), jnp.int32).at[order].set(
            pos_sorted.astype(jnp.int32))
        keep = pos < capacity
        return buf, pos, keep

    buf, pos, keep = jax.vmap(row)(xr, er, gr)         # (D,E,C,d),(D,Tlk),(D,Tlk)
    buf = shard_dim(buf, 0, "data")

    h = jnp.einsum("recd,edf->recf", buf, p["wi"])
    g = jax.nn.silu(jnp.einsum("recd,edf->recf", buf, p["wg"]).astype(jnp.float32))
    eo = jnp.einsum("recf,efd->recd", g.astype(h.dtype) * h, p["wo"])
    eo = shard_dim(eo, 0, "data")

    def combine(eo_row, e_row, pos_row, keep_row, g_row):
        tok = eo_row[e_row, jnp.clip(pos_row, 0, capacity - 1)]  # (Tl*k, d)
        return tok * (g_row * keep_row).astype(tok.dtype)[:, None]

    tok_out = jax.vmap(combine)(eo, er, pos, keep, gr)  # (D, Tl*k, d)
    out = tok_out.reshape(D, Tl, k, d).sum(axis=2).reshape(T, d)

    counts_f = jax.vmap(lambda e, kp: jnp.zeros((E,), jnp.float32).at[e].add(
        kp.astype(jnp.float32)))(er, keep).sum(axis=0)
    return out, counts_f


def _held_experts(p, xt, flat_e, flat_g, cfg: ModelConfig):
    """The held experts' part of the layer, no token dropped: (T, d).

    ``flat_e``/``flat_g``: expert id and gate of each (token, choice) pair,
    token-major. Pairs are sorted by held expert, the pairs of experts held
    elsewhere last, and each held expert's gated MLP runs as a grouped
    matmul over its run of pairs."""
    lo, hi = cfg.experts_held
    T, d = xt.shape
    k = cfg.experts_per_tok
    Eh = hi - lo
    held = (flat_e >= lo) & (flat_e < hi)
    local = jnp.where(held, flat_e - lo, Eh)
    order = jnp.argsort(local, stable=True)
    sizes = jnp.zeros((Eh + 1,), jnp.int32).at[local].add(1)[:Eh]
    # Rows past the held experts' runs are in no group: what a grouped
    # matmul leaves there is not defined (zeros on the CPU, not on the TPU,
    # where a zero gate times a non-finite leftover is nan), so each
    # product is masked to the rows in a group.
    mine = held[order, None]
    rows = jnp.where(mine, xt[order // k], 0).astype(xt.dtype)
    h = jnp.where(mine, jax.lax.ragged_dot(rows, p["wi"], sizes), 0)
    g = jax.nn.silu(jnp.where(mine, jax.lax.ragged_dot(rows, p["wg"], sizes),
                              0).astype(jnp.float32))
    eo = jax.lax.ragged_dot(g.astype(h.dtype) * h, p["wo"], sizes)
    w = jnp.where(held, flat_g, 0.0)[order]
    eo = jnp.where(mine, eo * w.astype(eo.dtype)[:, None], 0)
    inv = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.arange(T * k, dtype=jnp.int32))
    return eo[inv].reshape(T, k, d).sum(axis=1)


def moe_ffn(p: Dict, x, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """x: (b, s, d) -> (out, aux_loss)."""
    b, s, d = x.shape
    E, k = cfg.n_experts, cfg.experts_per_tok
    T = b * s
    xt = x.reshape(T, d)

    with jax.named_scope(SCOPE_ROUTE):
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                            p["router"].astype(jnp.float32))
        probs = dm.softmax(logits, axis=-1, cfg=cfg.division)      # (T, E)
        gate_vals, idx = jax.lax.top_k(probs, k)                   # (T, k)
        # top-k renormalization — another divider site
        denom = jnp.sum(gate_vals, axis=-1, keepdims=True)
        gates = gate_vals * dm.recip(denom, cfg.division)          # (T, k)

    def shared(out):
        if not cfg.n_shared_experts:
            return out
        from .layers import gated_mlp
        with jax.named_scope(SCOPE_SHARED):
            return out + gated_mlp(p["shared"], xt)

    def aux_loss(counts):
        f_e = counts / (T * k) * E
        P_e = jnp.mean(probs, axis=0)
        return E * jnp.sum(f_e * P_e) * cfg.router_aux_weight

    if cfg.experts_held is not None:
        if cfg.moe_dispatch != "sort":
            raise ValueError(
                f"{cfg.name}: experts_held is a mode of the 'sort' dispatch, "
                f"not of {cfg.moe_dispatch!r}")
        flat_e = idx.reshape(T * k)
        with jax.named_scope(SCOPE_EXPERTS):
            out = _held_experts(p, xt, flat_e, gates.reshape(T * k), cfg)
        out = shared(out)
        counts = jnp.zeros((E,), jnp.float32).at[flat_e].add(1.0)
        return out.reshape(b, s, d), aux_loss(counts)

    # Capacity floor: tiny token counts (decode steps) get no-drop capacity so
    # serving is deterministic; large batches use the standard cf bound.
    if cfg.moe_dispatch == "local":
        D = _local_shard_count(T)
        with jax.named_scope(SCOPE_EXPERTS):
            out, counts = _dispatch_local(p, xt, probs, gates, idx, cfg, D)
        return shared(out).reshape(b, s, d), aux_loss(counts)

    capacity = max(math.ceil(T * k / E * cfg.capacity_factor), min(T * k, 8))

    flat_e = idx.reshape(T * k)                                    # expert ids
    flat_g = gates.reshape(T * k)
    with jax.named_scope(SCOPE_EXPERTS):
        if cfg.moe_dispatch == "sort":
            # megablocks-style: stable-sort by expert, position = rank within
            # the expert's run. O(Tk log Tk); same first-come-first-served
            # drops as the cumsum scheme, but no O(Tk*E) global cumsum (which
            # XLA models as reduce-window and SPMD executes
            # near-quadratically).
            order = jnp.argsort(flat_e, stable=True)
            sorted_e = flat_e[order]
            first = jnp.searchsorted(sorted_e, jnp.arange(E))      # (E,)
            pos_sorted = jnp.arange(T * k) - first[sorted_e]
            flat_pos = jnp.zeros((T * k,), jnp.int32).at[order].set(
                pos_sorted.astype(jnp.int32))
        else:
            onehot_pos = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (T*k, E)
            pos_in_e = jnp.cumsum(onehot_pos, axis=0) * onehot_pos   # 1-based
            flat_pos = jnp.sum(pos_in_e, axis=-1) - 1                # (T*k,)
        keep = (flat_pos >= 0) & (flat_pos < capacity)
        flat_pos = jnp.clip(flat_pos, 0, capacity - 1)

        # dispatch: (E, C, d)
        xr = jnp.repeat(xt, k, axis=0)                             # (T*k, d)
        contrib = jnp.where(keep[:, None], xr, 0).astype(x.dtype)
        buf = jnp.zeros((E, capacity, d), x.dtype)
        buf = buf.at[flat_e, flat_pos].add(contrib)

        # expert compute: gated MLP batched over experts
        h = jnp.einsum("ecd,edf->ecf", buf, p["wi"])
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wg"])
                        .astype(jnp.float32))
        eo = jnp.einsum("ecf,efd->ecd", g.astype(h.dtype) * h, p["wo"])

        # combine
        tok_out = eo[flat_e, flat_pos]                             # (T*k, d)
        tok_out = tok_out * (flat_g * keep).astype(tok_out.dtype)[:, None]
        out = tok_out.reshape(T, k, d).sum(axis=1)
    out = shared(out)

    # load-balance aux (scatter-add counts; no (T*k, E) one-hot materialized)
    counts = jnp.zeros((E,), jnp.float32).at[flat_e].add(
        keep.astype(jnp.float32))
    return out.reshape(b, s, d), aux_loss(counts)
