"""Hand-written expert-parallel MoE dispatch (shard_map + all_to_all).

Why this exists: under pjit/SPMD, expert parallelism must be *inferred*
by XLA from sharding annotations — and when the batch also owns the
model axis (ZeRO-3 training), the partitioner replicates the dispatch
instead of emitting an all-to-all (measured 47 -> 542 GiB/dev on qwen3
when hints tried to force it; EXPERIMENTS.md §Perf cell 1 #6). The
SPMD-expressible fallback (ZeRO weight-gather of ALL experts per layer)
costs 19 GiB/layer on jamba. This module writes the collective program
by hand instead:

* the ``model`` axis is MANUAL (shard_map): rank r holds E/R experts
  and B·S/R token rows (in ZeRO-3 training the batch is already spread
  over the model axis — exactly what EP wants);
* each rank routes its local tokens, buckets them by destination rank
  (owner(e) = e // E_local) with capacity C per (src, dst) pair, and
  ``jax.lax.all_to_all`` moves one (R, C, d) buffer each way —
  expert weights NEVER move;
* expert ids travel with the payload (packed as an extra channel), so
  the receiving rank computes its local experts' FFN on exactly the
  tokens it owns;
* the return all_to_all routes outputs back; gates combine locally.
  Dropped (over-capacity) tokens contribute zero, same policy as the
  pjit path.

Differentiable end-to-end (shard_map + all_to_all transpose = the
reverse all_to_all). Verified against the pjit ``moe_ffn`` reference at
drop-free capacity on an 8-device mesh (tests/test_multidevice.py).
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.config import ModelConfig

Array = jax.Array


def _local_dispatch(x2d: Array, logits: Array, n_ranks: int, e_local: int,
                    k: int, cap: int):
    """Bucket local tokens by destination rank.

    x2d (T, d); logits (T, E). Returns (send (R, C, d), send_eid (R, C)
    in [0, e_local) or -1, send_tok (R, C) source token index or -1,
    gates (T, k), top_idx (T, k)).
    """
    t, d = x2d.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, top_idx = jax.lax.top_k(probs, k)                    # (T, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    flat_e = top_idx.reshape(-1)                                # (T*k,)
    dest = flat_e // e_local                                    # (T*k,)
    # slot within (dest rank, capacity): running count per destination
    onehot = jax.nn.one_hot(dest, n_ranks, dtype=jnp.float32)   # (T*k, R)
    slot = (jnp.cumsum(onehot, axis=0) - onehot) * onehot       # pos within dest
    slot = jnp.sum(slot, axis=-1).astype(jnp.int32)             # (T*k,)
    keep = slot < cap

    x_rep = jnp.repeat(x2d, k, axis=0)                          # (T*k, d)
    send = jnp.zeros((n_ranks, cap, d), x2d.dtype)
    send_eid = jnp.full((n_ranks, cap), -1, jnp.int32)
    send_tok = jnp.full((n_ranks, cap), -1, jnp.int32)
    upd = jnp.where(keep[:, None], x_rep, 0).astype(x2d.dtype)
    send = send.at[dest, slot].add(jnp.where(keep[:, None], upd, 0), mode="drop")
    send_eid = send_eid.at[dest, slot].set(
        jnp.where(keep, flat_e % e_local, -1), mode="drop"
    )
    tok_ids = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    send_tok = send_tok.at[dest, slot].set(jnp.where(keep, tok_ids, -1), mode="drop")
    return send, send_eid, send_tok, gates, top_idx, dest, slot, keep


def _expert_ffn(recv: Array, recv_eid: Array, w1, w3, w2) -> Array:
    """(R*C, d) tokens with local-expert ids -> outputs (R*C, d)."""
    e_local = w1.shape[0]
    sel = jnp.clip(recv_eid, 0, e_local - 1)
    valid = (recv_eid >= 0)[:, None]
    w1g = w1[sel]                                  # (N, d, f)
    w3g = w3[sel]
    w2g = w2[sel]                                  # (N, f, d)
    h = jnp.einsum("nd,ndf->nf", recv, w1g.astype(recv.dtype))
    g = jnp.einsum("nd,ndf->nf", recv, w3g.astype(recv.dtype))
    h = jax.nn.silu(h.astype(jnp.float32)).astype(recv.dtype) * g
    out = jnp.einsum("nf,nfd->nd", h, w2g.astype(h.dtype))
    return jnp.where(valid, out, 0)


def ep_moe_ffn(
    p: dict[str, Array],
    x: Array,
    cfg: ModelConfig,
    mesh: Mesh,
    *,
    axis: str = "model",
    batch_spec: P | None = None,
) -> tuple[Array, Array]:
    """Expert-parallel MoE FFN. x (B, S, d) with its batch sharded over
    (at least) ``axis``; expert weights (E, d, f) sharded on E over
    ``axis``. Router weights replicated over ``axis``.

    Returns (out (B, S, d), aux load-balance loss).
    """
    n_ranks = mesh.shape[axis]
    e, k = cfg.moe_experts, cfg.moe_top_k
    assert e % n_ranks == 0, f"{e} experts on a {n_ranks}-way axis"
    e_local = e // n_ranks
    b, s, d = x.shape
    t_local = (b * s) // n_ranks  # token rows per rank (batch spread over axis)
    # per-(src,dst) capacity: average tokens*k per expert * factor, split by rank
    cap = max(1, math.ceil(t_local * k / n_ranks * cfg.moe_capacity_factor))

    other = tuple(a for a in mesh.axis_names if a != axis)

    def body(xl, router, w1, w3, w2):
        # xl: this rank's (b_l, s, d) token rows; weights: (e_local, ...)
        bl = xl.shape[0] * xl.shape[1]
        x2d = xl.reshape(bl, d)
        logits = x2d.astype(jnp.float32) @ router.astype(jnp.float32)
        send, send_eid, send_tok, gates, top_idx, dest, slot, keep = _local_dispatch(
            x2d, logits, n_ranks, e_local, k, cap
        )
        # move buckets: (R, C, *) -> received-from-each-rank (R, C, *)
        recv = jax.lax.all_to_all(send, axis, 0, 0, tiled=False)
        recv_eid = jax.lax.all_to_all(send_eid, axis, 0, 0, tiled=False)
        out_e = _expert_ffn(
            recv.reshape(n_ranks * cap, d), recv_eid.reshape(n_ranks * cap), w1, w3, w2
        ).reshape(n_ranks, cap, d)
        back = jax.lax.all_to_all(out_e, axis, 0, 0, tiled=False)  # (R, C, d)
        # combine: token i's k results live at (dest[i*k+j], slot[i*k+j])
        got = back[dest, slot] * keep[:, None]                     # (T*k, d)
        y = (got.reshape(bl, k, d) * gates[..., None].astype(got.dtype)).sum(axis=1)
        # aux loss from local stats (averaged over ranks by the outer psum)
        onehot_e = jax.nn.one_hot(top_idx.reshape(-1), e, dtype=jnp.float32)
        frac = onehot_e.mean(axis=0) * e / k
        mean_prob = jax.nn.softmax(logits, axis=-1).mean(axis=0)
        aux = jnp.sum(frac * mean_prob) * e / e  # E * sum(f_e * P_e) shape
        aux = jax.lax.pmean(aux, axis)
        return y.reshape(xl.shape).astype(x.dtype), aux

    in_specs = (
        batch_spec if batch_spec is not None else P(axis, None, None),  # x rows over axis
        P(),                      # router replicated over axis
        P(axis, None, None),      # w1 (E, d, f) experts over axis
        P(axis, None, None),      # w3
        P(axis, None, None),      # w2
    )
    del other
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(in_specs[0], P()),
        axis_names={axis},   # MANUAL over the model axis only
        check_vma=False,
    )
    return fn(x, p["router"], p["w1"], p["w3"], p["w2"])
