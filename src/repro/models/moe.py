"""Mixture-of-Experts layer: top-k routing with capacity-bounded dispatch.

Two implementations with identical semantics (tested against each other):

``moe_ffn_local`` — single-shard sort-based dispatch (MegaBlocks/MaxText
style, no ragged ops): router → top-k → argsort by expert → position-in-group
→ scatter into an (E, C, D) buffer → batched expert GEMMs → combine.

``moe_ffn_sharded`` — the production expert-parallel path.  Under plain pjit
a *global* sort-based dispatch forces XLA to replicate the data-dependent
scatter (measured on granite train_4k: 177 GB temp, 7.5 TB collective bytes —
EXPERIMENTS §Perf iteration 1).  Here tokens stay in their (pod, data,
model-SP) shard; each device routes locally, packs per-destination send
buffers, and two ``all_to_all`` ops over the 'model' axis move tokens to
their expert shard and results back.  No global scatter exists; the MoE
communication term becomes the textbook 2×(tokens·D) per direction.

Tokens beyond capacity are dropped (standard capacity-factor semantics); a
Switch-style aux load-balancing loss is returned.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

def init_moe_params(rng, d_model: int, d_ff: int, n_experts: int,
                    dtype=jnp.float32):
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    s_in = 1.0 / np.sqrt(d_model)
    s_out = 1.0 / np.sqrt(d_ff)
    return {
        "router": (jax.random.normal(k1, (d_model, n_experts)) * s_in
                   ).astype(jnp.float32),      # router stays f32
        "w_in": (jax.random.normal(k2, (n_experts, d_model, d_ff)) * s_in
                 ).astype(dtype),
        "w_gate": (jax.random.normal(k3, (n_experts, d_model, d_ff)) * s_in
                   ).astype(dtype),
        "w_out": (jax.random.normal(k4, (n_experts, d_ff, d_model)) * s_out
                  ).astype(dtype),
    }


def _route(router, xf, top_k: int, n_experts: int):
    """Shared router math: returns (weights (N,k), expert ids (N,k), probs)."""
    logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, expert_idx = jax.lax.top_k(probs, top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    return weights, expert_idx, probs


def _aux_loss(expert_idx, probs, n_experts: int):
    density = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], n_experts), axis=0)
    density_prob = jnp.mean(probs, axis=0)
    return density, density_prob


def _group_positions(sorted_ids, n_groups: int):
    """Position of each element within its (sorted) group."""
    n = sorted_ids.shape[0]
    gsz = jax.ops.segment_sum(jnp.ones_like(sorted_ids), sorted_ids,
                              num_segments=n_groups)
    gstart = jnp.cumsum(gsz) - gsz
    pos = jnp.arange(n, dtype=jnp.int32) - jnp.take(
        gstart, jnp.clip(sorted_ids, 0, n_groups - 1))
    return pos, gsz


def _expert_mlp(buf, w_in, w_gate, w_out, act: str):
    h = jnp.einsum("ecd,edf->ecf", buf, w_in.astype(buf.dtype))
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(buf.dtype))
    g = jax.nn.gelu(g) if act == "geglu" else jax.nn.silu(g)
    return jnp.einsum("ecf,efd->ecd", h * g, w_out.astype(buf.dtype))


# ---------------------------------------------------------------------------
# local (single-shard) path
# ---------------------------------------------------------------------------

def moe_ffn_local(params, x, *, top_k: int, capacity_factor: float = 1.25,
                  act: str = "swiglu"):
    """x: (B, S, D) → (B, S, D), aux_loss (scalar)."""
    B, S, D = x.shape
    E = params["router"].shape[1]
    N = B * S
    xf = x.reshape(N, D)
    weights, expert_idx, probs = _route(params["router"], xf, top_k, E)
    density, density_prob = _aux_loss(expert_idx, probs, E)
    aux = jnp.sum(density * density_prob) * E

    C = max(int(np.ceil(N * top_k / E * capacity_factor)), 1)
    ids = expert_idx.reshape(-1)                               # (N·k,)
    order = jnp.argsort(ids)
    sorted_ids = ids[order]
    pos, _ = _group_positions(sorted_ids, E)
    keep = pos < C
    pos_w = jnp.where(keep, pos, C)                            # OOB → dropped
    token_of = order // top_k

    buf = jnp.zeros((E, C, D), dtype=x.dtype)
    buf = buf.at[sorted_ids, pos_w].add(
        jnp.where(keep[:, None], xf[token_of], 0).astype(x.dtype),
        mode="drop")
    out_buf = _expert_mlp(buf, params["w_in"], params["w_gate"],
                          params["w_out"], act)

    slot_vals = out_buf[sorted_ids, jnp.where(keep, pos, 0)]   # (N·k, D)
    slot_vals = jnp.where(keep[:, None], slot_vals, 0)
    w_sorted = weights.reshape(-1)[order]
    contrib = slot_vals * w_sorted[:, None].astype(x.dtype)
    out = jax.ops.segment_sum(contrib, token_of, num_segments=N)
    return out.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# expert-parallel shard_map path
# ---------------------------------------------------------------------------

def moe_ffn_sharded(params, x, *, top_k: int, capacity_factor: float,
                    act: str, mesh):
    """x: (B, S, D) sharded P(dp, 'model', None) → same layout, aux scalar."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import batch_axes

    dp = batch_axes(mesh)
    M = mesh.shape["model"]
    E = params["router"].shape[1]
    E_loc = E // M
    B, S, D = x.shape

    def body(router, w_in, w_gate, w_out, xb):
        N_loc = xb.shape[0] * xb.shape[1]
        xf = xb.reshape(N_loc, D)
        weights, expert_idx, probs = _route(router, xf, top_k, E)
        density, density_prob = _aux_loss(expert_idx, probs, E)
        axes = dp + ("model",)
        aux = jnp.sum(jax.lax.pmean(density, axes)
                      * jax.lax.pmean(density_prob, axes)) * E

        Nk = N_loc * top_k
        cap = max(int(np.ceil(Nk / M * capacity_factor)), 1)
        ids = expert_idx.reshape(-1)                    # (Nk,)
        w_flat = weights.reshape(-1)
        dest = ids // E_loc                             # target model shard
        order = jnp.argsort(dest)
        d_sorted = dest[order]
        pos, _ = _group_positions(d_sorted, M)
        keep = pos < cap
        pos_w = jnp.where(keep, pos, cap)               # OOB → dropped
        tok = order // top_k

        send = jnp.zeros((M, cap, D), x.dtype).at[d_sorted, pos_w].add(
            jnp.where(keep[:, None], xf[tok], 0).astype(x.dtype), mode="drop")
        send_eid = jnp.full((M, cap), E_loc, jnp.int32).at[
            d_sorted, pos_w].set(ids[order] % E_loc, mode="drop")
        send_src = jnp.full((M, cap), -1, jnp.int32).at[
            d_sorted, pos_w].set(order, mode="drop")

        # === all_to_all #1: tokens → their expert's shard ===
        recv = jax.lax.all_to_all(send, "model", 0, 0)
        recv_eid = jax.lax.all_to_all(send_eid, "model", 0, 0)
        re = recv.reshape(M * cap, D)
        re_id = recv_eid.reshape(M * cap)               # in [0, E_loc] (pad=E_loc)

        # local grouped GEMM over my E_loc experts
        cap2 = max(int(np.ceil(M * cap / max(E_loc, 1))), 1)
        order2 = jnp.argsort(re_id)
        id2 = re_id[order2]
        pos2, _ = _group_positions(id2, E_loc + 1)
        keep2 = (id2 < E_loc) & (pos2 < cap2)
        pos2_w = jnp.where(keep2, pos2, cap2)
        buf = jnp.zeros((E_loc, cap2, D), x.dtype).at[
            jnp.where(keep2, id2, 0), pos2_w].add(
            jnp.where(keep2[:, None], re[order2], 0).astype(x.dtype),
            mode="drop")
        ob = _expert_mlp(buf, w_in, w_gate, w_out, act)

        # un-permute locally; all_to_all #2: results → token owners
        gathered = ob[jnp.where(keep2, id2, 0), jnp.where(keep2, pos2, 0)]
        out_rows = jnp.zeros((M * cap, D), x.dtype).at[order2].add(
            jnp.where(keep2[:, None], gathered, 0))
        back = jax.lax.all_to_all(out_rows.reshape(M, cap, D), "model", 0, 0)

        # back[m, c] is the result for my original send[m, c]
        flat_back = back.reshape(M * cap, D)
        src = send_src.reshape(M * cap)                 # flat (token·k) slots
        valid = src >= 0
        src_c = jnp.clip(src, 0, Nk - 1)
        w_g = jnp.where(valid, w_flat[src_c], 0.0)
        contrib = jnp.where(valid[:, None], flat_back, 0) \
            * w_g[:, None].astype(x.dtype)
        out = jax.ops.segment_sum(
            contrib, jnp.where(valid, src_c // top_k, N_loc),
            num_segments=N_loc + 1)[:N_loc]
        return out.reshape(xb.shape), aux

    espec = P("model", None, None)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(None, None), espec, espec, espec,
                                 P(dp, "model", None)),
                       out_specs=(P(dp, "model", None), P()),
                       check_vma=False)
    return fn(params["router"], params["w_in"], params["w_gate"],
              params["w_out"], x)


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------

def moe_ffn(params, x, *, top_k: int, capacity_factor: float = 1.25,
            act: str = "swiglu"):
    """Routes to the expert-parallel path when a mesh with a usable 'model'
    axis is bound and shapes divide; otherwise the local path (single-device
    tests, decode steps with S=1 where the token count is trivial)."""
    from repro.distributed import sharding as shd
    mesh = shd.current_mesh()
    B, S, D = x.shape
    if mesh is not None and "model" in mesh.axis_names:
        M = mesh.shape["model"]
        dpn = 1
        for a in shd.batch_axes(mesh):
            dpn *= mesh.shape[a]
        E = params["router"].shape[1]
        if M > 1 and E % M == 0 and S % M == 0 and B % max(dpn, 1) == 0:
            return moe_ffn_sharded(params, x, top_k=top_k,
                                   capacity_factor=capacity_factor,
                                   act=act, mesh=mesh)
    return moe_ffn_local(params, x, top_k=top_k,
                         capacity_factor=capacity_factor, act=act)
