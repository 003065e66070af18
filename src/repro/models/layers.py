"""Core transformer layers: RMSNorm, RoPE, GQA attention (flash-style
chunked), SwiGLU — pure JAX, scan/remat-friendly, with the paper's BNN
quantization available on every projection (``quant="bnn"``).

Conventions: activations bf16, accumulations/normalizations fp32,
params fp32. All attention shapes are (B, S, H, D).

Named scopes (``jax.named_scope``, HLO ``op_name`` metadata only) mark
the parts of a layer in the compiled programs: ``attn.qkv`` (q/k/v
projections and rope), ``attn.kv_write`` (the new K/V rows into the
cache), ``attn.core`` (the attention itself), ``attn.o``, ``ffn`` and,
innermost, ``bitlinear`` (every binarized projection). A profile's
device time is summed per scope from them.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import bnn
from repro.distributed.hints import hint
from repro.models.config import ModelConfig

Array = jax.Array
Params = dict[str, Any]

ACT_DTYPE = jnp.bfloat16
NEG_INF = -1e30

# Compiler options of the serving programs, whose tokens every execution
# engine must reproduce bit for bit. By default XLA may keep a bf16
# intermediate (a residual add, a BitLinear output) in f32 inside one
# fusion and round it in another, and which it does follows each
# program's own fusions: on a TPU v5e the reference and the packed
# prefill then drifted apart from layer 1 on. With excess precision off
# every ACT_DTYPE value is rounded as written, as op-by-op execution does.
EXACT_COMPILER_OPTIONS = {"xla_allow_excess_precision": False}


# ---------------------------------------------------------------------------
# Init helpers
# ---------------------------------------------------------------------------


def dense_init(key: jax.Array, d_in: int, d_out: int, bias: bool = False) -> Params:
    w = jax.random.normal(key, (d_in, d_out), jnp.float32) * (1.0 / math.sqrt(d_in))
    p: Params = {"w": w}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


# ---------------------------------------------------------------------------
# Primitives
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x: Array, scale: Array, eps: float) -> Array:
    """RMSNorm with a hand-written VJP.

    Why custom: the autodiff residual of the naive version is the fp32
    upcast of x — and JAX saves that fp32 copy per layer *in addition
    to* the bf16 carry under scan (measured: a second (L, B, S, d) fp32
    residual stack, 10 GiB/device on qwen2-72b train). This VJP saves
    only the bf16 x and recomputes the fp32 statistics in the backward.
    """
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def _rms_norm_fwd(x: Array, scale: Array, eps: float):
    return rms_norm(x, scale, eps), (x, scale)


def _rms_norm_bwd(eps: float, res, g):
    x, scale = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = xf * inv
    d_scale = jnp.sum(gf * xhat, axis=tuple(range(x.ndim - 1)))
    gs = gf * scale.astype(jnp.float32)
    # d/dx of x*inv: inv * (gs - xhat * mean(gs * xhat))
    dx = inv * (gs - xhat * jnp.mean(gs * xhat, axis=-1, keepdims=True))
    return dx.astype(x.dtype), d_scale.astype(scale.dtype)


rms_norm.defvjp(_rms_norm_fwd, _rms_norm_bwd)


def infer_engine(cfg: ModelConfig, plan=None):
    """Resolve ``cfg.bnn_engine`` into an execution backend for the
    binarized projections of the *inference* paths (prefill/decode).

    Returns ``None`` for the reference backend: the plain matmul below
    is both the reference numerics and the only differentiable (STE)
    path, so training always goes through it.

    ``plan`` (a ``repro.mapping.allocator.MappingPlan``) binds the
    ``tiled`` backend to a compiled layer->tile placement; without one
    the engine places each projection on the fly under
    ``cfg.mapping_policy``. Other backends ignore the plan (their layout
    is implicit in the backend itself).
    """
    if cfg.quant != "bnn" or cfg.bnn_engine in ("", "reference"):
        return None
    from repro.core import engine as engine_lib

    if cfg.bnn_engine == "tiled":
        return engine_lib.get_engine(
            "tiled", plan=plan, policy=cfg.mapping_policy or "tacitmap"
        )
    return engine_lib.get_engine(cfg.bnn_engine)


def _require_latent(p: Params, w, engine) -> None:
    """Programmed projections carry only the engine artifact: reaching a
    path that needs the latent weights is a caller error — fail with the
    reason instead of a NoneType crash deep inside a scan."""
    if w is None:
        prepared = p.get("prepared")
        programmed_for = getattr(prepared, "engine", "<unknown>")
        raise ValueError(
            f"projection was programmed for engine {programmed_for!r} "
            "(lm.program_weights replaced the latent 'w' with 'prepared'/"
            "'alpha'); run it through that engine with quant='bnn', or use "
            f"the original un-programmed params (engine passed: {engine!r})"
        )


def dense(p: Params, x: Array, quant: str = "none", engine=None) -> Array:
    """Linear layer; ``quant="bnn"`` routes through the paper's BitLinear:
    sign-binarized weights/activations (STE in training) with a
    per-tensor weight scale and a per-token activation scale — first/last
    layers of a model never use it (§II-B).

    The activation scale is per-token (mean |x| along the feature axis)
    so every batch row's output depends only on that row: continuous
    batching and the serving engine's K-group gather (which may repeat
    rows in ragged tails) are then semantically invisible. A per-tensor
    activation scale would couple rows through the batch mean.

    ``engine`` (a ``repro.core.engine.Engine``) executes the ±1 matmul
    through any registered backend — e.g. the packed XNOR+popcount
    Pallas kernel. Engines are bit-exact vs the plain matmul but not
    differentiable; inference callers resolve one via ``infer_engine``.

    Two-phase execution: when ``p`` carries a programmed projection
    (``p["prepared"]``/``p["alpha"]`` from ``lm.program_weights`` — the
    crossbar-programming phase) and an engine is bound, the weight-side
    transforms are skipped entirely and only activations stream.
    Otherwise the engine's per-instance ``WeightCache`` memoizes the
    programming on the latent param's identity (concrete arrays only —
    tracers prepare inline, exactly the pre-PR-4 graph).
    """
    w = p.get("w")  # absent on programmed projections (prepared replaces it)
    if quant == "bnn":
        with jax.named_scope("bitlinear"):
            out = _bitlinear(p, w, x, engine)
    else:
        _require_latent(p, w, engine)
        out = jnp.matmul(x, w.astype(x.dtype))
    if "b" in p:
        out = out + p["b"].astype(out.dtype)
    return out


def _bitlinear(p: Params, w, x: Array, engine) -> Array:
    """The binarized branch of :func:`dense`, without its bias."""
    pw = p.get("prepared") if engine is not None else None
    if pw is not None and getattr(engine, "supports_fused_dense", False):
        # Fused decode-tick path: binarize + bit-pack + XNOR +
        # popcount + Eq. 1 affine + α/β rescale in ONE kernel launch
        # (kernels/fused_decode.py) — the raw activation block
        # crosses HBM exactly once. Bit-exact vs the unfused chain
        # below; engines advertise it via ``supports_fused_dense``.
        return engine.fused_dense(x, pw, p["alpha"]).astype(ACT_DTYPE)
    beta = bnn.activation_scale(x)
    xb = bnn.binarize_ste(x.astype(jnp.float32))
    if pw is not None:
        alpha = p["alpha"]
        dot = engine.binary_vmm(xb, pw).astype(jnp.float32)
    else:
        _require_latent(p, w, engine)
        # hoisted by lm.attach_weight_scales in compiled models
        alpha = p["alpha"] if "alpha" in p else bnn.weight_scale(w)
        if engine is None:
            dot = xb @ bnn.binarize_ste(w)
        elif hasattr(engine, "prepare_cached"):
            # lazy: binarization runs only on a weight-cache miss
            wx = engine.prepare_cached(lambda: bnn.binarize_ste(w), key=w)
            dot = engine.binary_vmm(xb, wx).astype(jnp.float32)
        else:
            dot = engine.binary_vmm(xb, bnn.binarize_ste(w)).astype(jnp.float32)
    # The barrier keeps XLA from fusing the exact integer dot and
    # its row scale into the rescale: without it, on a TPU v5e one
    # row in 128 of the reference's FFN output missed the exact
    # dot * (alpha * beta) (bf16-rounded) that the packed kernel
    # returns.
    dot, beta = jax.lax.optimization_barrier((dot, beta))
    return (dot * (alpha * beta)).astype(ACT_DTYPE)


def fused_qkv_dense(p_attn: Params, x: Array, cfg: ModelConfig, quant: str, engine):
    """Shared-activation QKV fusion: one fused kernel over the
    concatenated ``[q|k|v]`` prepared weights instead of three.

    q/k/v all consume the same attention input, so the unfused path
    binarizes and bit-packs that block three times. When
    ``lm.program_weights`` has attached the derived ``qkv`` artifact
    (the three sign matrices concatenated along the output axis before
    packing) and the engine supports fused dense, the input streams
    through ONE kernel launch and the output splits at the static head
    boundaries. Column j of the fused kernel depends only on weight
    column j, so the split halves are bit-identical to three separate
    calls. Returns (q, k, v) pre-reshape activations, or ``None`` when
    the fused artifact/capability is absent (callers fall back to three
    ``dense`` calls).
    """
    fused = p_attn.get("qkv")
    if (
        quant != "bnn"
        or fused is None
        or engine is None
        or not getattr(engine, "supports_fused_dense", False)
    ):
        return None
    with jax.named_scope("bitlinear"):
        out = engine.fused_dense(x, fused["prepared"], fused["alpha"]).astype(ACT_DTYPE)
    nq = cfg.n_heads * cfg.hd
    nkv = cfg.n_kv_heads * cfg.hd
    parts = (out[..., :nq], out[..., nq : nq + nkv], out[..., nq + nkv :])
    outs = []
    for name, o in zip(("q", "k", "v"), parts):
        b = p_attn[name].get("b")
        outs.append(o + b.astype(o.dtype) if b is not None else o)
    return tuple(outs)


def _qkv(p_attn: Params, x: Array, cfg: ModelConfig, quant: str, engine):
    """(q, k, v) pre-reshape activations: one fused launch where the
    engine and artifact allow it, else three :func:`dense` calls."""
    qkv = fused_qkv_dense(p_attn, x, cfg, quant, engine)
    if qkv is None:
        qkv = tuple(dense(p_attn[n], x, quant, engine) for n in ("q", "k", "v"))
    return qkv


def rope(x: Array, positions: Array, theta: float) -> Array:
    """Rotary embedding. x (B, S, H, D), positions (B, S) or (S,)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def attn_init(key: jax.Array, cfg: ModelConfig, cross: bool = False) -> Params:
    ks = jax.random.split(key, 4)
    d, hd = cfg.d_model, cfg.hd
    return {
        "q": dense_init(ks[0], d, cfg.n_heads * hd, cfg.qkv_bias),
        "k": dense_init(ks[1], d, cfg.n_kv_heads * hd, cfg.qkv_bias),
        "v": dense_init(ks[2], d, cfg.n_kv_heads * hd, cfg.qkv_bias),
        "o": dense_init(ks[3], cfg.n_heads * hd, d),
    }


def _flash_body(q, kc, vc, qpos, kpos, carry, scale, causal, head_map):
    """One KV-chunk step of the streaming-softmax attention.

    q (B,Sq,H,D); kc/vc (B,C,KV,D); carry = (m, l, acc) with
    m,l (B,H,Sq) and acc (B,H,Sq,D). GQA is handled by gathering each
    head's KV *per chunk* (``head_map`` (H,) -> kv index): the gathered
    (B,C,H,D) chunk is tiny, and — unlike a (KV, G) reshape of the head
    dim — every tensor here keeps a plain H axis, which shards cleanly
    over the model axis under SPMD (H % tp == 0 covers the big archs).
    """
    m, l, acc = carry
    kh = jnp.take(kc, head_map, axis=2)  # (B,C,H,D)
    vh = jnp.take(vc, head_map, axis=2)
    s = jnp.einsum("bqhd,bchd->bhqc", q.astype(jnp.float32), kh.astype(jnp.float32)) * scale
    if causal:
        mask = kpos[None, :] <= qpos[:, None]  # (Sq, C)
        s = jnp.where(mask[None, None], s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    if causal:
        p = jnp.where(mask[None, None], p, 0.0)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    # p in bf16 for the AV contraction: halves the probability-tensor
    # HBM traffic (the dominant memory-roofline component at 32k
    # prefill) and feeds the MXU natively; l/m corrections stay fp32.
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bhqc,bchd->bhqd", p.astype(jnp.bfloat16), vh.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_new


def multi_head_attention(
    q: Array,
    k: Array,
    v: Array,
    q_positions: Array,
    kv_positions: Array,
    *,
    causal: bool,
    chunk: int,
    impl: str = "jnp",
) -> Array:
    """Flash-style chunked attention: O(S·C) live memory, fp32 softmax.

    q (B, Sq, H, D); k/v (B, Skv, KV, D); positions (S,)-shaped (shared
    across batch). Returns (B, Sq, H, D) in q.dtype.

    ``impl="pallas"`` routes through the fused VMEM-resident kernel
    (kernels/flash_attention.py) — contiguous positions only (the model
    paths always are); the jnp path remains the lowering-anywhere
    reference.
    """
    if impl == "pallas":
        from repro.kernels.flash_attention import flash_attention

        out = flash_attention(
            q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2), causal=causal
        )
        return out.swapaxes(1, 2)
    b, sq, h, d = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    scale = 1.0 / math.sqrt(d)
    head_map = jnp.arange(h, dtype=jnp.int32) // g  # head -> kv head

    n_chunks = math.ceil(skv / chunk)
    pad = n_chunks * chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, (0, pad), constant_values=2**30)
        causal = True  # padded keys must be masked out via positions

    kcs = k.reshape(b, n_chunks, chunk, kvh, d).swapaxes(0, 1)
    vcs = v.reshape(b, n_chunks, chunk, kvh, d).swapaxes(0, 1)
    pcs = kv_positions.reshape(n_chunks, chunk)

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, d), jnp.float32)

    @jax.checkpoint
    def step(carry, xs):
        # checkpointed (flash-style): backward recomputes this chunk's
        # scores/probabilities from (q, kc, vc, m, l) instead of saving
        # the (B, H, Sq, C) probability + mask tensors per chunk.
        kc, vc, kpos = xs
        return _flash_body(q, kc, vc, q_positions, kpos, carry, scale, causal, head_map), None

    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kcs, vcs, pcs))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.swapaxes(1, 2).astype(q.dtype)


def decode_attention(
    q: Array, k_cache: Array, v_cache: Array, cache_len: Array
) -> Array:
    """Single-token attention against a (B, T, KV, D) cache.

    ``cache_len`` masks positions >= current length. q (B, 1, H, D).
    GQA via the grouped einsum (no repeat: the cache is the big operand
    and stays KV-shaped; T shards over the model axis and the softmax
    reductions psum — sequence-parallel decode).
    """
    b, _, h, d = q.shape
    t, kvh = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    qg = q.reshape(b, kvh, g, d)
    s = jnp.einsum("bkgd,btkd->bkgt", qg.astype(jnp.float32), k_cache.astype(jnp.float32))
    s = s / math.sqrt(d)
    mask = jnp.arange(t)[None, :] < cache_len[:, None]  # (B, T)
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, h, d).astype(q.dtype)


def attention_block(
    p: Params,
    x: Array,
    positions: Array,
    cfg: ModelConfig,
    *,
    causal: bool = True,
    quant: str = "none",
    engine=None,
) -> tuple[Array, tuple[Array, Array]]:
    """Full-sequence attention (train/prefill). Returns (out, (k, v))."""
    b, s, _ = x.shape
    hd = cfg.hd
    with jax.named_scope("attn.qkv"):
        qkv = _qkv(p, x, cfg, quant, engine)
        # hints pin head-parallel attention over the model axis (dropped
        # per-dim when indivisible — e.g. tinyllama's 4 KV heads on tp=16)
        q = hint(qkv[0].reshape(b, s, cfg.n_heads, hd), "dp", None, "model", None)
        k = hint(qkv[1].reshape(b, s, cfg.n_kv_heads, hd), "dp", None, "model", None)
        v = hint(qkv[2].reshape(b, s, cfg.n_kv_heads, hd), "dp", None, "model", None)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    with jax.named_scope("attn.core"):
        out = multi_head_attention(
            q, k, v, positions, positions, causal=causal, chunk=cfg.attn_chunk,
            impl=cfg.attn_impl,
        )
        out = hint(out, "dp", None, "model", None)
    with jax.named_scope("attn.o"):
        out = dense(p["o"], out.reshape(b, s, cfg.n_heads * hd), quant, engine)
    return out, (k, v)


def attention_continue(
    p: Params,
    x: Array,
    positions: Array,
    prefix_k: Array,
    prefix_v: Array,
    cfg: ModelConfig,
    *,
    quant: str = "none",
    engine=None,
) -> tuple[Array, tuple[Array, Array]]:
    """Prefill continuation over a grafted KV prefix (prefix caching).

    ``x`` holds the suffix positions ``positions`` (absolute, starting
    at the prefix length); ``prefix_k``/``prefix_v`` are the cached
    rows for positions ``[0, start)`` taken from an earlier prefill of
    the same token prefix. Returns (out, (k, v)) where k/v cover only
    the suffix — the caller concatenates them after the prefix rows.

    Bit-exactness with a from-scratch prefill is load-bearing (the
    serving prefix-graft invariant) and holds for two reasons:

    * prefill KV rows are prompt-length-invariant — causal masking in
      :func:`multi_head_attention` zeroes future contributions *exactly*
      (``p = where(mask, p, 0)``), so a shared prefix's cached rows are
      bit-identical whatever followed it in the donor prompt;
    * the suffix runs through the SAME streaming-softmax graph a full
      prefill uses (not :func:`decode_attention`, whose
      normalize-then-contract order rounds differently in bf16).
    """
    b, s, _ = x.shape
    hd = cfg.hd
    with jax.named_scope("attn.qkv"):
        qkv = _qkv(p, x, cfg, quant, engine)
        q = hint(qkv[0].reshape(b, s, cfg.n_heads, hd), "dp", None, "model", None)
        k = hint(qkv[1].reshape(b, s, cfg.n_kv_heads, hd), "dp", None, "model", None)
        v = hint(qkv[2].reshape(b, s, cfg.n_kv_heads, hd), "dp", None, "model", None)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    with jax.named_scope("attn.kv_write"):
        # rope returns the input dtype, so cached bf16 prefix rows and
        # the fresh suffix rows concatenate without a lossy cast
        k_full = jnp.concatenate([prefix_k.astype(k.dtype), k], axis=1)
        v_full = jnp.concatenate([prefix_v.astype(v.dtype), v], axis=1)
    kv_positions = jnp.arange(k_full.shape[1])
    with jax.named_scope("attn.core"):
        out = multi_head_attention(
            q, k_full, v_full, positions, kv_positions, causal=True,
            chunk=cfg.attn_chunk, impl=cfg.attn_impl,
        )
        out = hint(out, "dp", None, "model", None)
    with jax.named_scope("attn.o"):
        out = dense(p["o"], out.reshape(b, s, cfg.n_heads * hd), quant, engine)
    return out, (k, v)


def cross_attention_block(
    p: Params,
    x: Array,
    kv: tuple[Array, Array],
    positions: Array,
    cfg: ModelConfig,
    quant: str = "none",
    engine=None,
) -> Array:
    """Decoder cross-attention against precomputed encoder K/V."""
    b, s, _ = x.shape
    hd = cfg.hd
    k, v = kv
    q = dense(p["q"], x, quant, engine).reshape(b, s, cfg.n_heads, hd)
    src_pos = jnp.arange(k.shape[1])
    out = multi_head_attention(
        q, k, v, positions, src_pos, causal=False, chunk=cfg.attn_chunk,
        impl=cfg.attn_impl,
    )
    return dense(p["o"], out.reshape(b, s, cfg.n_heads * hd), quant, engine)


def attention_decode_step(
    p: Params,
    x: Array,
    pos: Array,
    cache_k: Array,
    cache_v: Array,
    cfg: ModelConfig,
    quant: str = "none",
    engine=None,
) -> tuple[Array, Array, Array]:
    """One-token step. x (B, 1, d); pos scalar int32 OR (B,) per-slot
    positions (continuous batching); caches (B, T, KV, D).

    Returns (out, new_cache_k, new_cache_v).
    """
    b = x.shape[0]
    hd = cfg.hd
    pos_vec = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    with jax.named_scope("attn.qkv"):
        qkv = _qkv(p, x, cfg, quant, engine)
        q = hint(qkv[0].reshape(b, 1, cfg.n_heads, hd), "dp", None, "model", None)
        k = qkv[1].reshape(b, 1, cfg.n_kv_heads, hd)
        v = qkv[2].reshape(b, 1, cfg.n_kv_heads, hd)
        posb = pos_vec[:, None]
        q = rope(q, posb, cfg.rope_theta)
        k = rope(k, posb, cfg.rope_theta)
    with jax.named_scope("attn.kv_write"):
        rows = jnp.arange(b)
        cache_k = cache_k.at[rows, pos_vec].set(k[:, 0].astype(cache_k.dtype))
        cache_v = cache_v.at[rows, pos_vec].set(v[:, 0].astype(cache_v.dtype))
    with jax.named_scope("attn.core"):
        out = decode_attention(q, cache_k, cache_v, pos_vec + 1)
    with jax.named_scope("attn.o"):
        out = dense(p["o"], out.reshape(b, 1, cfg.n_heads * hd), quant, engine)
    return out, cache_k, cache_v


# ---------------------------------------------------------------------------
# SwiGLU FFN
# ---------------------------------------------------------------------------


def ffn_init(key: jax.Array, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 3)
    d, f = cfg.d_model, cfg.d_ff
    return {
        "w1": dense_init(ks[0], d, f),
        "w3": dense_init(ks[1], d, f),
        "w2": dense_init(ks[2], f, d),
    }


def ffn(p: Params, x: Array, quant: str = "none", engine=None) -> Array:
    with jax.named_scope("ffn"):
        h = jax.nn.silu(dense(p["w1"], x, quant, engine).astype(jnp.float32)).astype(x.dtype)
        h = hint(h * dense(p["w3"], x, quant, engine), "dp", None, "model")
        return dense(p["w2"], h, quant, engine)
