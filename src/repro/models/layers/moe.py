"""Mixture-of-Experts: router + three dispatch strategies.

Routing (top-k + aux loss) is computed with plain jnp ops outside any
shard_map, so all strategies share identical expert assignments:

  * ``ep_a2a``  — production EP: tokens are sequence-sharded over the
    ``model`` mesh axis, dispatched to expert owners with a fixed-capacity
    ``lax.all_to_all`` (DeepSpeed-MoE style), expert FFN runs on the owner,
    results return via a second all-to-all.  Used when a mesh is active and
    the token count divides the model axis (train / prefill).
  * ``einsum``  — GShard one-hot dispatch; cheap only when per-group capacity
    is tiny, so it serves decode (S==1) and small test shapes.
  * ``dense``   — every expert applied to every token, masked combine; the
    O(E x T) oracle for unit tests.

DeepSeekMoE extensions: shared experts (always-on, fused into one SwiGLU) and
``first_k_dense`` leading dense layers are handled in the block, not here.

A layer told which experts it holds (``MoEConfig.held``: its share under
expert parallelism) serves through ``moe_held`` instead: no capacity, so
no token is dropped and a token's output does not depend on which other
tokens share its chunk or batch.  It computes the held experts' share of
every token with one grouped SwiGLU (``kernels/moe_expert_ffn``).
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import constrain, current_mesh, current_rules
from repro.models.layers.module import bias, weight


def moe_table(d_model: int, num_experts: int, d_ff_expert: int, *,
              held: int | None = None, router_bias: bool = False):
    """Router + stacked expert SwiGLU weights (expert dim sharded for EP).
    The router scores all ``num_experts``; the stacks hold ``held`` of
    them (all by default)."""
    e, d, f = num_experts, d_model, d_ff_expert
    h = e if held is None else held
    t = {
        "router": weight((d, e), ("embed", None), stddev=0.02),
        "w_gate": weight((h, d, f), ("experts", "embed", "ff_expert")),
        "w_up": weight((h, d, f), ("experts", "embed", "ff_expert")),
        "w_down": weight((h, f, d), ("experts", "ff_expert", "embed")),
    }
    if router_bias:
        t["router_bias"] = bias((e,), (None,))
    return t


def route(cfg_moe, params, x: jax.Array):
    """Top-k routing decisions + Switch-style load-balance aux loss.

    Args:
      x: (B, S, D) activations.
    Returns:
      idx (B, S, k) int32 expert ids, prob (B, S, k) f32 combine weights,
      aux_loss scalar f32.
    """
    e = cfg_moe.num_experts
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    if cfg_moe.scoring == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    elif cfg_moe.scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown router scoring {cfg_moe.scoring!r}")
    if cfg_moe.router_bias:
        # the correction bias steers which experts are chosen, not how
        # much each chosen one weighs
        choose = probs + params["router_bias"].astype(jnp.float32)
        _, idx = jax.lax.top_k(choose, cfg_moe.top_k)
        prob = jnp.take_along_axis(probs, idx, axis=-1)
    else:
        prob, idx = jax.lax.top_k(probs, cfg_moe.top_k)
    if cfg_moe.norm_topk_prob:
        prob = prob / jnp.maximum(jnp.sum(prob, axis=-1, keepdims=True), 1e-9)
    if cfg_moe.routed_scaling != 1.0:
        prob = prob * cfg_moe.routed_scaling
    # aux = E * mean_e( frac_tokens(e) * mean_prob(e) )  (Switch eq. 4)
    one_hot = jax.nn.one_hot(idx, e, dtype=jnp.float32)       # (B,S,k,E)
    frac = jnp.mean(jnp.sum(one_hot, axis=2), axis=(0, 1))    # (E,)
    mean_p = jnp.mean(probs, axis=(0, 1))                     # (E,)
    aux = e * jnp.sum(frac * mean_p) / cfg_moe.top_k
    return idx, prob.astype(jnp.float32), aux * cfg_moe.router_aux_loss_weight


def expert_ffn(w_gate, w_up, w_down, xs: jax.Array) -> jax.Array:
    """xs: (E, C, D) -> (E, C, D); per-expert SwiGLU."""
    dt = xs.dtype
    g = jnp.einsum("ecd,edf->ecf", xs, w_gate.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", xs, w_up.astype(dt))
    h = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h, w_down.astype(dt))


# ---------------------------------------------------------------------------
# dense oracle
# ---------------------------------------------------------------------------

def moe_dense(cfg_moe, params, x, idx, prob):
    """O(E x T) oracle: every expert on every token, masked combine."""
    B, S, D = x.shape
    e = cfg_moe.num_experts
    xs = jnp.broadcast_to(x.reshape(1, B * S, D), (e, B * S, D))
    ys = expert_ffn(params["w_gate"], params["w_up"], params["w_down"], xs)
    ys = ys.reshape(e, B, S, D)
    combine = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32)
                      * prob[..., None], axis=2)               # (B,S,E)
    return jnp.einsum("ebsd,bse->bsd", ys.astype(jnp.float32),
                      combine).astype(x.dtype)


# ---------------------------------------------------------------------------
# GShard einsum dispatch (decode / small shapes)
# ---------------------------------------------------------------------------

def moe_einsum(cfg_moe, params, x, idx, prob, *, capacity: int | None = None):
    """One-hot dispatch within per-batch-row groups; capacity per (row, expert)."""
    B, S, D = x.shape
    e, k = cfg_moe.num_experts, cfg_moe.top_k
    if capacity is None:
        capacity = max(1, math.ceil(S * k * cfg_moe.capacity_factor / e))
    # position of each (token, choice) within its expert, per batch row
    sel = jax.nn.one_hot(idx, e, dtype=jnp.int32)              # (B,S,k,E)
    flat = sel.reshape(B, S * k, e)
    pos = jnp.cumsum(flat, axis=1) - 1                          # (B,S*k,E)
    pos = jnp.sum(pos * flat, axis=-1).reshape(B, S, k)         # (B,S,k)
    keep = pos < capacity
    disp = (jax.nn.one_hot(idx, e, dtype=x.dtype)[..., :, None]
            * jax.nn.one_hot(pos, capacity, dtype=x.dtype)[..., None, :])
    disp = disp * keep[..., None, None].astype(x.dtype)         # (B,S,k,E,C)
    disp_tok = jnp.sum(disp, axis=2)                            # (B,S,E,C)
    xs = jnp.einsum("bsec,bsd->ebcd", disp_tok, x)              # (E,B,C,D)
    xs = constrain(xs, "experts", "batch", None, None)
    ys = expert_ffn(params["w_gate"], params["w_up"], params["w_down"],
                    xs.reshape(e, B * capacity, D))
    ys = constrain(ys.reshape(e, B, capacity, D),
                   "experts", "batch", None, None)
    comb = jnp.sum(disp * prob[..., None, None].astype(x.dtype), axis=2)
    out = jnp.einsum("bsec,ebcd->bsd", comb, ys)
    return constrain(out, "batch", "seq", "embed_act")


# ---------------------------------------------------------------------------
# the held experts' share, without a capacity
# ---------------------------------------------------------------------------

# tokens per grouped call: the kernel's VMEM holds one group of at most
# this many rows
HELD_TOKENS = 256
EXPERT_STACKS = ("w_gate", "w_up", "w_down")


class Stacked(NamedTuple):
    """One layer's expert weights left inside the stack of every layer's,
    ``stack[layer]``: the grouped kernel reads that layer in place, where a
    slice taken before the call would first be copied whole."""
    stack: jax.Array
    layer: jax.Array


def _row_tile(tokens: int, k: int, experts: int) -> int:
    """Rows per kernel tile: the least power of two, 16 or more, at least
    the mean rows an expert gets (at most 128)."""
    mean = -(-tokens * k // experts)
    tm = 16
    while tm < mean and tm < 128:
        tm *= 2
    return tm


def _held_share(cfg_moe, params, x, idx, prob):
    """x: (T, D); idx/prob: (T, k).  Returns the held experts' weighted
    share (T, D) and (rows computed, held experts with a row)."""
    from repro.kernels.moe_expert_ffn.kernel import group_offsets, rows_bound
    from repro.kernels.moe_expert_ffn.ops import moe_expert_ffn
    T, D = x.shape
    k = cfg_moe.top_k
    first, count = cfg_moe.held
    tm = _row_tile(T, k, cfg_moe.num_experts)
    M = rows_bound(T, k, count, tm)
    local = idx.reshape(T * k) - first
    held = (local >= 0) & (local < count)
    group = jnp.where(held, local, count)                 # count: not held
    sizes_all = jnp.bincount(group, length=count + 1)
    sizes = sizes_all[:count].astype(jnp.int32)
    # rank of each token-choice within its group, in token order
    order = jnp.argsort(group, stable=True)
    starts = jnp.cumsum(sizes_all) - sizes_all
    rank_sorted = jnp.arange(T * k, dtype=jnp.int32) - starts[group[order]]
    rank = jnp.zeros((T * k,), jnp.int32).at[order].set(rank_sorted)
    offsets = group_offsets(sizes, tm)
    dest = jnp.where(held, offsets[jnp.clip(local, 0, count - 1)] + rank, M)
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    xs = jnp.zeros((M, D), x.dtype).at[dest].set(x[tok], mode="drop")
    ws = [params[n] for n in EXPERT_STACKS]
    layer = ws[0].layer if isinstance(ws[0], Stacked) else None
    ws = [w.stack if isinstance(w, Stacked) else w for w in ws]
    ys = moe_expert_ffn(xs, *ws, sizes, layer=layer, tm=tm, group_rows=T)
    # rows outside the groups are unwritten: select, never multiply, them
    got = jnp.where(held[:, None], ys[jnp.minimum(dest, M - 1)], 0)
    y = jnp.sum((got.astype(jnp.float32)
                 * prob.reshape(T * k, 1)).reshape(T, k, D), axis=1)
    counts = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0)]).astype(jnp.int32)
    return y.astype(x.dtype), counts


def moe_held(cfg_moe, params, x, idx, prob):
    """The held experts' share of every token, dropless.

    x: (B, S, D); idx/prob: (B, S, k) from :func:`route` over all
    ``num_experts``; the expert stacks are arrays or :class:`Stacked`.  The token-choices routed to held experts are sorted
    into one group per expert and run through one grouped SwiGLU (at most
    :data:`HELD_TOKENS` tokens per call); the others contribute nothing
    here (another chip holds them).  Returns (out (B, S, D), counts) with
    counts int32 (2,): token-choices the held experts computed, and held
    experts that computed a row.
    """
    B, S, D = x.shape
    T, k = B * S, cfg_moe.top_k
    xf, idf, pf = x.reshape(T, D), idx.reshape(T, k), prob.reshape(T, k)
    outs, counts = [], jnp.zeros((2,), jnp.int32)
    for t0 in range(0, T, HELD_TOKENS):
        sl = slice(t0, min(t0 + HELD_TOKENS, T))
        y, c = _held_share(cfg_moe, params, xf[sl], idf[sl], pf[sl])
        outs.append(y)
        counts = counts + c
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
    return out.reshape(B, S, D), counts


# ---------------------------------------------------------------------------
# production EP: all-to-all dispatch under shard_map
# ---------------------------------------------------------------------------

def _positions_within(dest: jax.Array, num_dest: int) -> tuple[jax.Array, jax.Array]:
    """For each entry, its arrival rank among same-destination entries.

    dest: (N,) int32 in [0, num_dest). Returns (pos (N,), counts (num_dest,)).
    """
    n = dest.shape[0]
    order = jnp.argsort(dest, stable=True)
    sorted_dest = dest[order]
    counts = jnp.bincount(dest, length=num_dest)
    starts = jnp.cumsum(counts) - counts
    pos_sorted = jnp.arange(n, dtype=jnp.int32) - starts[sorted_dest]
    pos = jnp.zeros((n,), jnp.int32).at[order].set(pos_sorted)
    return pos, counts


def _ep_local(x_loc, idx_loc, prob_loc, w_gate, w_up, w_down, *,
              cfg_moe, model_axis: str, model_size: int):
    """Per-device body: dispatch -> all_to_all -> expert FFN -> return."""
    Bl, Sl, D = x_loc.shape
    k = cfg_moe.top_k
    e_local = cfg_moe.num_experts // model_size
    T = Bl * Sl
    xf = x_loc.reshape(T, D)
    ef = idx_loc.reshape(T * k)
    pf = prob_loc.reshape(T * k)
    tok_of = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)

    dest_shard = ef // e_local                                  # (T*k,)
    c_send = max(1, math.ceil(T * k * cfg_moe.capacity_factor / model_size))
    pos, _ = _positions_within(dest_shard, model_size)
    keep = pos < c_send
    slot = dest_shard * c_send + pos                            # (T*k,)
    slot = jnp.where(keep, slot, model_size * c_send)           # drop slot

    send = jnp.zeros((model_size * c_send + 1, D), x_loc.dtype)
    send = send.at[slot].set(xf[tok_of], mode="drop")[:-1]
    send_eid = jnp.full((model_size * c_send + 1,), 0, jnp.int32)
    send_eid = send_eid.at[slot].set(ef % e_local, mode="drop")[:-1]

    recv = jax.lax.all_to_all(
        send.reshape(model_size, c_send, D), model_axis, 0, 0, tiled=False)
    recv_eid = jax.lax.all_to_all(
        send_eid.reshape(model_size, c_send), model_axis, 0, 0, tiled=False)
    R = model_size * c_send
    recv = recv.reshape(R, D)
    recv_eid = recv_eid.reshape(R)

    # second-level fixed capacity per local expert
    c_exp = max(1, math.ceil(R * cfg_moe.capacity_factor / max(e_local, 1)))
    pos2, _ = _positions_within(recv_eid, e_local)
    keep2 = pos2 < c_exp
    slot2 = jnp.where(keep2, recv_eid * c_exp + pos2, e_local * c_exp)
    buf = jnp.zeros((e_local * c_exp + 1, D), x_loc.dtype)
    buf = buf.at[slot2].set(recv, mode="drop")[:-1]

    ys = expert_ffn(w_gate, w_up, w_down, buf.reshape(e_local, c_exp, D))
    ys = ys.reshape(e_local * c_exp, D)

    # route results back through the same slots
    back = jnp.take(jnp.pad(ys, ((0, 1), (0, 0))),
                    jnp.where(keep2, slot2, e_local * c_exp), axis=0)
    ret = jax.lax.all_to_all(
        back.reshape(model_size, c_send, D), model_axis, 0, 0, tiled=False)
    ret = ret.reshape(model_size * c_send, D)
    contrib = jnp.take(jnp.pad(ret, ((0, 1), (0, 0))),
                       jnp.where(keep, slot, model_size * c_send), axis=0)
    contrib = contrib.astype(jnp.float32) * pf[:, None]
    y = jnp.zeros((T, D), jnp.float32).at[tok_of].add(contrib)
    return y.reshape(Bl, Sl, D).astype(x_loc.dtype)


def moe_ep(cfg_moe, params, x, idx, prob, *, mesh, batch_axes, model_axis):
    """Sequence-sharded EP dispatch. x: (B, S, D) with S % model_size == 0."""
    model_size = dict(zip(mesh.axis_names, mesh.devices.shape))[model_axis]
    x = constrain(x, "batch", "seq_model", None)  # reshard: seq over model
    body = partial(_ep_local, cfg_moe=cfg_moe, model_axis=model_axis,
                   model_size=model_size)
    bspec = P(batch_axes, model_axis, None)
    ispec = P(batch_axes, model_axis, None)
    especs = (P(model_axis, None, None),) * 3
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(bspec, ispec, ispec, *especs),
        out_specs=bspec,
        check_vma=False,
    )(x, idx, prob, params["w_gate"], params["w_up"], params["w_down"])
    return constrain(out, "batch", "seq", "embed_act")


# ---------------------------------------------------------------------------
# strategy selection
# ---------------------------------------------------------------------------

def moe_apply(cfg_moe, params, x: jax.Array, idx, prob) -> jax.Array:
    """Pick dispatch strategy from the active mesh/rules. Differentiable."""
    mesh = current_mesh()
    rules = current_rules()
    if mesh is not None and rules is not None:
        model_axis = rules.rules.get("experts")
        if model_axis is not None and isinstance(model_axis, str):
            msize = dict(zip(mesh.axis_names, mesh.devices.shape)).get(model_axis, 1)
            S = x.shape[1]
            if msize > 1 and S % msize == 0 and S >= msize and \
                    cfg_moe.num_experts % msize == 0:
                batch_axes = rules.rules.get("batch")
                return moe_ep(cfg_moe, params, x, idx, prob, mesh=mesh,
                              batch_axes=batch_axes, model_axis=model_axis)
        return moe_einsum(cfg_moe, params, x, idx, prob)
    return moe_einsum(cfg_moe, params, x, idx, prob)
