"""GPipe pipeline parallelism via shard_map + ppermute.

An optional schedule for the ``pod`` axis: each pod holds a contiguous
block of layers ("stage"); microbatches stream through stages with the
classic GPipe fill/drain bubble (bubble fraction = (P-1)/(P-1+M)).

Implementation notes (and why it looks the way it does):

* The stage function is *uniform* across ranks (SPMD): every rank holds
  its own stage's stacked params; non-resident microbatch slots carry
  zeros and are masked. The rotating buffer moves activations between
  neighbouring stages with ``ppermute`` — one neighbour hop per tick,
  which is exactly the physical DCN/ICI topology cost model.
* ``ppermute`` is pairwise-neighbour-only: tick t sends stage s's
  output to stage s+1. After P-1+M ticks all microbatches have exited.
* Backward pass comes from jax.grad through the whole scan (the scan is
  remat-wrapped) — gradients flow back through the reversed permutes
  automatically; no hand-written backward schedule is needed for GPipe
  semantics (XLA sees the full fwd+bwd graph and schedules both).
* First/last stage embed/unembed: handled by the caller (the pipeline
  moves *hidden states*; embedding and loss run data-parallel on the
  edge stages' ranks via the usual pjit path).

This module is exercised by tests on an 8-device CPU sub-mesh and is
selectable in the launcher with ``--pipeline_stages N`` (maps the `pod`
axis to stages, DESIGN.md §6).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def stage_split(n_layers: int, n_stages: int) -> list[tuple[int, int]]:
    """Contiguous [start, end) layer ranges per stage (balanced)."""
    base, rem = divmod(n_layers, n_stages)
    out, s = [], 0
    for i in range(n_stages):
        e = s + base + (1 if i < rem else 0)
        out.append((s, e))
        s = e
    return out


def gpipe(
    stage_fn: Callable[[Any, Array], Array],
    *,
    mesh: Mesh,
    axis: str = "pod",
    n_microbatches: int,
) -> Callable[[Any, Array], Array]:
    """Build a pipelined apply: (stage_params_stacked, x (M*b, ...)) -> y.

    ``stage_fn(stage_params, x_mb)`` applies ONE stage to ONE microbatch.
    ``stage_params_stacked`` has a leading stage axis sharded over
    ``axis``; x is split into ``n_microbatches`` along dim 0.
    """
    n_stages = mesh.shape[axis]

    def pipelined(stage_params: Any, x: Array) -> Array:
        m = n_microbatches
        mb = x.shape[0] // m
        xs = x.reshape(m, mb, *x.shape[1:])

        @functools.partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(P(axis), P()),          # params: stage-sharded; x: replicated
            out_specs=P(),
            check_vma=False,
        )
        def run(sp: Any, xs_rep: Array) -> Array:
            stage = jax.lax.axis_index(axis)
            sp_local = jax.tree.map(lambda t: t[0], sp)  # this rank's stage
            n_ticks = n_stages - 1 + m
            buf = jnp.zeros((mb, *xs_rep.shape[2:]), xs_rep.dtype)
            outs = jnp.zeros_like(xs_rep)

            def tick(carry, t):
                buf, outs = carry
                # stage 0 ingests microbatch t (if any remain)
                mb_idx = jnp.clip(t, 0, m - 1)
                feed = jax.lax.dynamic_index_in_dim(xs_rep, mb_idx, keepdims=False)
                buf = jnp.where((stage == 0) & (t < m), feed, buf)
                # apply this stage
                y = stage_fn(sp_local, buf)
                # last stage emits microbatch t-(P-1)
                out_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
                emit = (stage == n_stages - 1) & (t >= n_stages - 1)
                outs = jax.lax.cond(
                    emit,
                    lambda o: jax.lax.dynamic_update_index_in_dim(o, y, out_idx, 0),
                    lambda o: o,
                    outs,
                )
                # rotate: stage s -> s+1 (ring; stage P-1 -> 0 carries junk,
                # overwritten by the stage-0 ingest next tick)
                perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
                buf = jax.lax.ppermute(y, axis, perm)
                return (buf, outs), None

            (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(n_ticks))
            # only the last stage holds real outputs; share them back
            outs = jax.lax.psum(
                jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)), axis
            )
            return outs

        ys = run(stage_params, xs)
        return ys.reshape(m * mb, *ys.shape[2:])

    return pipelined


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    """GPipe bubble overhead: (P-1) / (P-1+M)."""
    return (n_stages - 1) / (n_stages - 1 + n_microbatches)
