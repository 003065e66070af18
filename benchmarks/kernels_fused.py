"""Fused decode-tick kernel gate — wall time + bit-exactness.

Two layers of evidence for the PR-6 fused path, both against the same
unfused baseline (binarize -> ``pack_bits`` -> Hamming kernel -> affine
correction -> rescale as separate XLA ops):

  1. kernel-level: ``ops.fused_bnn_matmul`` vs the unfused op chain at
     decode-shaped operands, interleaved paired timing + exactness
     against the raw ``dense`` reference math (f32 einsum);
  2. serving-level: ``serving_latency.fused_sweep`` decode ticks — the
     fused target vs the same target with ``fused=False``, decode
     streams required bit-identical.

Gate: every comparison bit-exact AND the pooled median paired delta
(unfused - fused) strictly positive at both levels. Interpret mode on
CPU CI is acceptable per the acceptance criteria; the shapes are wide
enough (512/1024 features) that the structural difference dominates the
interpreter's fixed per-launch floor.
"""

from __future__ import annotations

import statistics

from benchmarks import _timing

# decode-shaped operands: (rows, m) x (m, n) as served by a 512/1024
# model — qkv (8 heads + 2 kv of head-dim 64, concatenated), o-proj,
# and the two FF projections.
SHAPES = (
    ("qkv", 4, 512, 768),
    ("o_proj", 4, 512, 512),
    ("ff_in", 4, 512, 1024),
    ("ff_out", 4, 1024, 512),
)


def kernel_rows(*, reps):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import bnn
    from repro.core.engine import PackedEngine

    eng = PackedEngine()
    rng = np.random.default_rng(0)
    rows = []
    for name, b, m, n in SHAPES:
        x = jnp.asarray(rng.normal(size=(b, m)), jnp.bfloat16)
        w = bnn.binarize_ste(jnp.asarray(rng.normal(size=(m, n)), jnp.float32))
        pw = eng.prepare(w)
        alpha = jnp.asarray(rng.uniform(0.5, 2.0, size=(n,)), jnp.float32)

        fused = jax.jit(lambda x, pw=pw, alpha=alpha: eng.fused_dense(x, pw, alpha))

        def unfused(x, pw=pw, alpha=alpha):
            beta = bnn.activation_scale(x)
            xb = bnn.binarize_ste(x.astype(jnp.float32))
            return eng.binary_vmm(xb, pw).astype(jnp.float32) * (alpha * beta)

        unfused = jax.jit(unfused)

        # oracle: the dense() reference math, no kernels involved
        beta = bnn.activation_scale(x)
        xb = bnn.binarize_ste(x.astype(jnp.float32))
        ref = jnp.einsum("bk,kn->bn", xb, w).astype(jnp.float32) * (alpha * beta)

        exact = bool(jnp.array_equal(ref, fused(x))) and bool(
            jnp.array_equal(ref, unfused(x))
        )
        # interleaved fenced pairs — the shared _timing methodology
        tf, tu = _timing.paired_times(
            lambda: fused(x), lambda: unfused(x), reps=reps
        )
        deltas = _timing.paired_deltas(tf, tu, scale=1e6)
        rows.append({
            "shape": name,
            "dims": f"({b},{m})x({m},{n})",
            "fused_us": statistics.median(tf) * 1e6,
            "unfused_us": statistics.median(tu) * 1e6,
            "paired_deltas_us": deltas,
            "paired_delta_us": _timing.pooled_median(deltas),
            "exact": exact,
        })
    return rows


def run(smoke: bool = False) -> tuple[int, dict]:
    from benchmarks import serving_latency

    reps = 50 if smoke else 200
    sizes = (dict(max_batch=4, prompt_len=5, warmup=3, ticks=20) if smoke
             else dict(max_batch=4, prompt_len=6, warmup=3, ticks=32))

    rows = kernel_rows(reps=reps)
    print("\n== fused BitLinear kernel vs unfused op chain "
          f"(median of {reps} interleaved call pairs) ==")
    print(f"{'shape':>8s} {'dims':>18s} {'fused_us':>9s} {'unfused_us':>11s} "
          f"{'pair_d_us':>10s} {'exact':>6s}")
    for r in rows:
        print(f"{r['shape']:>8s} {r['dims']:>18s} {r['fused_us']:9.1f} "
              f"{r['unfused_us']:11.1f} {r['paired_delta_us']:10.1f} "
              f"{str(r['exact']):>6s}")

    kernel_deltas = [d for r in rows for d in r["paired_deltas_us"]]
    kernel_faster = _timing.pooled_median(kernel_deltas) > 0
    kernel_exact = all(r["exact"] for r in rows)
    print(f"kernel pooled median delta (unfused - fused): "
          f"{_timing.pooled_median(kernel_deltas):+.1f}us; "
          f"strictly faster: {kernel_faster}; bit-exact vs reference: "
          f"{kernel_exact}")

    tick_rows = serving_latency.fused_sweep((1, 4), **sizes)
    print("\n== packed decode tick: fused vs unfused "
          f"(median of {sizes['ticks']} interleaved tick pairs) ==")
    print(f"{'K':>3s} {'fused_ms':>9s} {'unfused_ms':>11s} {'pair_d_ms':>10s} "
          f"{'exact':>6s}")
    for r in tick_rows:
        print(f"{r['k']:3d} {r['tick_ms_fused']:9.2f} "
              f"{r['tick_ms_unfused']:11.2f} {r['paired_delta_ms']:10.3f} "
              f"{str(r['exact']):>6s}")
    tick_deltas = [d for r in tick_rows for d in r["paired_deltas_ms"]]
    tick_faster = _timing.pooled_median(tick_deltas) > 0
    tick_exact = all(r["exact"] for r in tick_rows)
    print(f"tick pooled median delta (unfused - fused): "
          f"{_timing.pooled_median(tick_deltas):+.3f}ms; strictly faster: "
          f"{tick_faster}; decode streams bit-identical: {tick_exact}")

    rc = 0 if (kernel_exact and tick_exact and kernel_faster and tick_faster) else 1
    payload = {
        "kernel": rows,
        "ticks": tick_rows,
        "kernel_strictly_faster": kernel_faster,
        "kernel_bit_exact": kernel_exact,
        "tick_strictly_faster": tick_faster,
        "tick_bit_exact": tick_exact,
    }
    return rc, payload


def main(smoke: bool = False) -> int:
    return run(smoke=smoke)[0]


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true", help="CI-sized sweep")
    args = ap.parse_args()
    raise SystemExit(main(smoke=args.smoke))
