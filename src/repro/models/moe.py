"""Mixture-of-Experts: a router over every expert, and the experts a layer holds.

A layer holds ``moe.held`` experts from ``moe.first_held`` (all of them
unless the configuration says otherwise, as one chip of an expert-parallel
layer holds its block).  The router scores all ``num_experts``; the layer
computes the part of the result that its own experts give, for the tokens
routed to them, plus the shared experts for every token.

Three implementations share parameters:

* ``grouped`` -- the served path (prefill and decode) whenever the layer
  makes no exchange (at most one device).  Dropless: the (token, held
  expert) pairs are sorted by expert and cut into blocks of ``rows`` rows,
  each expert's pairs padded to at most one block, and a loop over the
  blocks in use runs each block through its expert.  Work and weight reads
  follow the routed pairs, not tokens x held experts.
* ``ep`` -- training over a mesh.  Tokens are sequence-sharded over the
  ``model`` mesh axis; each rank routes its local tokens, packs per-expert
  capacity buffers, and exchanges them with ``jax.lax.all_to_all`` over the
  expert-parallel axis (experts live sharded over ``model``).  GShard-style
  capacity with token dropping; a sort-based dispatch (gather/scatter, no
  one-hot dispatch einsum, so dispatch costs O(N k d) not O(N E C d)).
* ``dense`` -- reference path: computes every held expert on every token;
  exact, trivially correct; the tests' oracle, training without a mesh, and
  serving over more than one device.

The router aux (load-balance) loss follows Switch: ``E * sum_e f_e * P_e``.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import normal_init

Params = dict[str, Any]


def init_moe(cfg, key) -> Params:
    mo = cfg.moe
    d, f, E, held = cfg.d_model, mo.expert_d_ff, mo.num_experts, mo.held
    ks = jax.random.split(key, 7)
    std, std_out = d**-0.5, f**-0.5
    p = {
        "router": normal_init(ks[0], (d, E), std, cfg.param_dtype),
        "w_gate": normal_init(ks[1], (held, d, f), std, cfg.param_dtype),
        "w_up": normal_init(ks[2], (held, d, f), std, cfg.param_dtype),
        "w_down": normal_init(ks[3], (held, f, d), std_out, cfg.param_dtype),
    }
    if mo.num_shared > 0:
        fs = mo.num_shared * f
        p["shared"] = {
            "w_gate": normal_init(ks[4], (d, fs), std, cfg.param_dtype),
            "w_up": normal_init(ks[5], (d, fs), std, cfg.param_dtype),
            "w_down": normal_init(ks[6], (fs, d), fs**-0.5, cfg.param_dtype),
        }
    return p


def _router(cfg, p, x2d):
    """x2d: (N, d) -> softmax scores over every expert, the top-k expert ids
    and their weights (fp32 router): the scores themselves, or renormalised
    to sum to 1 (``norm_topk_prob``), times ``routed_scaling_factor``."""
    mo = cfg.moe
    logits = x2d.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, mo.top_k)
    if mo.norm_topk_prob:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    return probs, top_i, top_w * mo.routed_scaling_factor


def _held_index(cfg, top_i):
    """Each routed pair's index among the held experts, ``held`` where the
    expert is held elsewhere."""
    mo = cfg.moe
    local = top_i - mo.first_held
    return jnp.where((local >= 0) & (local < mo.held), local, mo.held)


def _aux_loss(cfg, probs, top_i):
    mo = cfg.moe
    E = mo.num_experts
    # fraction of tokens routed to each expert (first choice counts all k)
    routed = jax.nn.one_hot(top_i, E, dtype=jnp.float32).sum(1)  # (N, E)
    f_e = routed.mean(0) / mo.top_k
    p_e = probs.mean(0)
    return E * jnp.sum(f_e * p_e)


def _expert_ffn(cfg, w_gate, w_up, w_down, z):
    """z: (E_loc, T, d) -> (E_loc, T, d), swiglu per expert."""
    ct = cfg.compute_dtype
    g = jnp.einsum("etd,edf->etf", z, w_gate.astype(ct))
    u = jnp.einsum("etd,edf->etf", z, w_up.astype(ct))
    return jnp.einsum("etf,efd->etd", jax.nn.silu(g) * u, w_down.astype(ct))


def _shared_ffn(cfg, p, x):
    ct = cfg.compute_dtype
    sp = p["shared"]
    g = x @ sp["w_gate"].astype(ct)
    u = x @ sp["w_up"].astype(ct)
    return (jax.nn.silu(g) * u) @ sp["w_down"].astype(ct)


# -- dense reference path -------------------------------------------------------

def apply_moe_dense(cfg, p: Params, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    mo = cfg.moe
    ct = cfg.compute_dtype
    B, S, d = x.shape
    x2 = x.reshape(-1, d).astype(ct)
    probs, top_i, top_w = _router(cfg, p, x2)
    # combine weights over all experts: (N, E)
    combine = jnp.zeros_like(probs)
    nidx = jnp.arange(x2.shape[0])[:, None]
    combine = combine.at[nidx, top_i].add(top_w)[:, mo.first_held:mo.first_held + mo.held]
    # all held experts on all tokens (exact; the tests' oracle)
    y_all = _expert_ffn(
        cfg, p["w_gate"], p["w_up"], p["w_down"],
        jnp.broadcast_to(x2[None], (mo.held, *x2.shape)),
    )  # (held, N, d)
    y = jnp.einsum("end,ne->nd", y_all, combine.astype(ct))
    if mo.num_shared > 0:
        y = y + _shared_ffn(cfg, p, x2)
    aux = _aux_loss(cfg, probs, top_i)
    return y.reshape(B, S, d), aux


# -- grouped (served) path --------------------------------------------------------

def block_rows(n_tokens: int, cfg) -> int:
    """Rows per block of the grouped path: twice the pairs an expert expects
    (``n_tokens * top_k / num_experts``), a power of two in [8, 512]."""
    mo = cfg.moe
    want = max(1, 2 * n_tokens * mo.top_k // mo.num_experts)
    return min(512, max(8, 1 << (want - 1).bit_length()))


def expert_blocks(local: jax.Array, held: int, n_tokens: int, top_k: int, rows: int):
    """Cut the routed pairs on held experts into blocks of ``rows``.

    ``local``: (P,) held-expert index of each pair (``held`` = held
    elsewhere).  Pairs are sorted by expert and each expert's pairs padded to
    a whole number of blocks, so at most one block per expert is part
    padding.  Returns ``(block_expert (NB,), block_pairs (NB, rows),
    n_blocks)``: the expert of each block, the pair index of each row (``P``
    for padding), and how many blocks are in use; ``NB`` is the static most.
    """
    P = local.shape[0]
    nb = -(-n_tokens * min(top_k, held) // rows) + held
    counts = jnp.bincount(local, length=held + 1)[:held]
    blocks = (counts + rows - 1) // rows
    ends = jnp.cumsum(blocks)
    block_expert = jnp.minimum(jnp.searchsorted(ends, jnp.arange(nb), side="right"), held - 1)
    # row r of block b holds the expert's pair number (b - its first block) * rows + r
    within = ((jnp.arange(nb) - (ends - blocks)[block_expert])[:, None] * rows
              + jnp.arange(rows)[None, :])
    valid = (within < counts[block_expert][:, None]) & (jnp.arange(nb) < ends[-1])[:, None]
    order = jnp.argsort(local, stable=True).astype(jnp.int32)    # by expert, held ones first
    at = (jnp.cumsum(counts) - counts)[block_expert][:, None] + within
    pairs = jnp.where(valid, order[jnp.minimum(at, P - 1)], P)
    return block_expert, pairs, ends[-1]


def apply_moe_grouped(cfg, p: Params, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Dropless held-expert layer with no exchange (the served path)."""
    mo = cfg.moe
    ct = cfg.compute_dtype
    B, S, d = x.shape
    x2 = x.reshape(-1, d).astype(ct)
    N, k = x2.shape[0], mo.top_k
    with jax.named_scope("router"):
        _, top_i, top_w = _router(cfg, p, x2)
    rows = block_rows(N, cfg)
    with jax.named_scope("dispatch"):
        block_expert, pairs, n_blocks = expert_blocks(
            _held_index(cfg, top_i).reshape(-1), mo.held, N, k, rows)
        # pair N * k (padding) reads a zero row, weighs 0 and lands out of range
        tok = jnp.append(jnp.arange(N * k, dtype=jnp.int32) // k, N)
        wts = jnp.append(top_w.reshape(-1), 0.0)
    w_gate, w_up, w_down = (p[n].astype(ct) for n in ("w_gate", "w_up", "w_down"))

    def block(b, y):
        with jax.named_scope("dispatch"):
            pair = pairs[b]
            t = tok[pair]
            xb = x2.at[t].get(mode="fill", fill_value=0)
        with jax.named_scope("experts"):
            e = block_expert[b]
            h = jax.nn.silu(xb @ w_gate[e]) * (xb @ w_up[e])
            out = h @ w_down[e]
        with jax.named_scope("combine"):
            return y.at[t].add(out.astype(jnp.float32) * wts[pair][:, None], mode="drop")

    y = jax.lax.fori_loop(0, n_blocks, block, jnp.zeros((N, d), jnp.float32))
    with jax.named_scope("combine"):
        y = y.astype(ct)
    if mo.num_shared > 0:
        with jax.named_scope("shared"):
            y = y + _shared_ffn(cfg, p, x2)
    return y.reshape(B, S, d), jnp.zeros((), jnp.float32)


# -- expert-parallel path -------------------------------------------------------

def _dispatch_pack(cfg, x2, top_i, top_w, capacity):
    """Sort-based capacity packing.

    Returns send buffer (E, C, d), and bookkeeping to combine results:
    sorted expert ids, destination slots (C = dropped), source token index,
    and routing weights in sorted order.
    """
    mo = cfg.moe
    E, k = mo.num_experts, mo.top_k
    N, d = x2.shape
    flat_e = top_i.reshape(-1)                      # (N*k,)
    flat_t = jnp.repeat(jnp.arange(N), k)           # source token per slot
    flat_w = top_w.reshape(-1)
    order = jnp.argsort(flat_e)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(N * k) - starts[se]            # position within expert
    dest = jnp.where(pos < capacity, pos, capacity) # overflow -> slot C (dropped)
    send = jnp.zeros((E, capacity + 1, d), x2.dtype)
    send = send.at[se, dest].set(x2[st])
    return send[:, :capacity], (se, dest, st, sw)


def _combine_unpack(cfg, recv, book, n_tokens, capacity):
    """Inverse of _dispatch_pack: weighted scatter-add back to tokens."""
    se, dest, st, sw = book
    # slot C reads are garbage; zero them via the keep mask
    keep = (dest < capacity).astype(recv.dtype)
    recv_pad = jnp.pad(recv, ((0, 0), (0, 1), (0, 0)))
    contrib = recv_pad[se, dest] * (sw.astype(recv.dtype) * keep)[:, None]
    y = jnp.zeros((n_tokens, recv.shape[-1]), recv.dtype)
    return y.at[st].add(contrib)


def apply_moe_ep(
    cfg,
    p: Params,
    x: jax.Array,
    *,
    mesh: jax.sharding.Mesh,
    dp_axes: tuple[str, ...] = ("data",),
    ep_axis: str = "model",
) -> tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE via shard_map + all_to_all over ``ep_axis``."""
    mo = cfg.moe
    ct = cfg.compute_dtype
    ep = mesh.shape[ep_axis]
    E = mo.num_experts
    assert mo.held == E, "the expert-parallel path shards every expert over the mesh"
    assert E % ep == 0, f"experts {E} must divide EP axis {ep}"
    B, S, d = x.shape

    seq_spec = ep_axis if (S % ep == 0 and S >= ep) else None
    x_spec = P(dp_axes, seq_spec, None)
    w_spec = P(ep_axis, None, None)
    all_axes = tuple(mesh.axis_names)

    # local token count (static) -> static capacity
    dp = math.prod(mesh.shape[a] for a in dp_axes)
    n_loc = (B // dp) * (S // ep if seq_spec else S)
    capacity = max(1, math.ceil(n_loc * mo.top_k / E * mo.capacity_factor))

    def block(xb, router_w, w_gate, w_up, w_down):
        Bl, Sl, _ = xb.shape
        x2 = xb.reshape(-1, d).astype(ct)
        probs, top_i, top_w = _router(cfg, {"router": router_w}, x2)
        aux = _aux_loss(cfg, probs, top_i)
        aux = jax.lax.pmean(aux, all_axes)

        send, book = _dispatch_pack(cfg, x2, top_i, top_w, capacity)
        # (E, C, d) -> (ep, E_loc, C, d) -> exchange - > (ep(src), E_loc, C, d)
        send = send.reshape(ep, E // ep, capacity, d)
        recv = jax.lax.all_to_all(send, ep_axis, split_axis=0, concat_axis=0)
        z = recv.transpose(1, 0, 2, 3).reshape(E // ep, ep * capacity, d)
        z = _expert_ffn(cfg, w_gate, w_up, w_down, z)
        back = z.reshape(E // ep, ep, capacity, d).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(back, ep_axis, split_axis=0, concat_axis=0)
        y = _combine_unpack(
            cfg, back.reshape(E, capacity, d), book, x2.shape[0], capacity
        )
        return y.reshape(Bl, Sl, d), aux

    y, aux = jax.shard_map(
        block,
        mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, w_spec),
        out_specs=(x_spec, P()),
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if mo.num_shared > 0:
        y = y + _shared_ffn(cfg, p, x.astype(ct))
    return y, aux


def apply_moe(
    cfg,
    p: Params,
    x: jax.Array,
    *,
    mesh: jax.sharding.Mesh | None = None,
    dp_axes: tuple[str, ...] = ("data",),
    ep_axis: str = "model",
    serving: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """``serving``: a prefill or decode call, which takes no gradient."""
    if serving and (mesh is None or mesh.size == 1):
        return apply_moe_grouped(cfg, p, x)
    if cfg.moe_impl == "ep" and mesh is not None and not serving:
        return apply_moe_ep(
            cfg, p, x, mesh=mesh, dp_axes=dp_axes, ep_axis=ep_axis,
        )
    return apply_moe_dense(cfg, p, x)
