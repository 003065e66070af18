"""Plain reference of the served model, independent of the program.

A Qwen2-style decoder (RMSNorm, rotary attention with QKV bias and
grouped KV heads, SwiGLU feed-forward) whose seven projections per layer
are BitLinear: ``out = (sign(x) @ sign(W)) * (mean|W| * mean|x|)`` with
``sign(0) = +1`` and ``mean|x|`` per token, in plain ``jax.numpy``.

A binarized network amplifies any rounding difference: one ulp in a
residual value near zero flips a sign, and after a few layers the
tokens are others. So the reference follows the arithmetic the
configuration states, step for step, and not merely the same
mathematics: bfloat16 activations rounded where written, float32
statistics, a per-token ``mean|x|`` summed exactly (so no summation
order matters), the prompt's attention as a streaming softmax over
512-position chunks with bfloat16 probabilities in the value product,
each generated token's attention as a float32 softmax over the whole
``max_len`` cache, and float32 matmuls at the platform's default
precision. Only the logits are computed at ``HIGHEST``. Nothing here
imports the program; the weights are drawn again from the seed
(``weights.py``) one layer at a time.

``Checker`` prefills each sampled prompt, then decodes teacher-forced
through a cache of the served shape, feeding the served tokens, and
reads at every position the gap by which the served token's logit lies
below the reference's best. With ``control=True`` it runs, in lockstep,
the same arithmetic with every activation rounding done to per-token
symmetric int8 (the nearest precision below bfloat16), and reads the
gap of the token that the control puts first.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import weights as weights_lib

F32 = jnp.float32
ACT = jnp.bfloat16
NEG_INF = -1e30
ATTN_CHUNK = 512
HIGHEST = jax.lax.Precision.HIGHEST
# bfloat16 values rounded as written, never kept in float32 by a fusion
EXACT = {"xla_allow_excess_precision": False}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration file, from its published key names."""

    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    eps: float
    tied: bool

    @property
    def table_rows(self) -> int:
        """Embedding rows as the program lays them out: the vocabulary
        padded to a multiple of 256, the padding columns masked."""
        return -(-self.vocab // 256) * 256

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        heads = c["num_attention_heads"]
        return cls(
            layers=c["num_hidden_layers"],
            d=c["hidden_size"],
            heads=heads,
            kv_heads=c["num_key_value_heads"],
            head_dim=c.get("head_dim") or c["hidden_size"] // heads,
            d_ff=c["intermediate_size"],
            vocab=c["vocab_size"],
            rope_theta=float(c["rope_theta"]),
            eps=float(c["rms_norm_eps"]),
            tied=bool(c["tie_word_embeddings"]),
        )


# ---------------------------------------------------------------------------
# rounding: bfloat16 (the reference) or per-token int8 (the control)
# ---------------------------------------------------------------------------


def _bf16(x):
    return x.astype(ACT)


def _int8(x):
    x = x.astype(F32)
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------


def _pow2(k):
    """``2.0 ** k`` in float32, exactly, for int32 k in [-126, 127]."""
    return jax.lax.bitcast_convert_type((k + 127) << 23, F32)


def mean_abs(x):
    """Per-row ``mean|x|`` over the last axis, the same bits in any
    program: ``|x|`` is cut into three parts on power-of-two grids,
    each summed exactly in float32."""
    a = jnp.abs(x).astype(F32)
    m = a.shape[-1]
    bits = max(1, (m - 1).bit_length())
    big = jax.lax.bitcast_convert_type(jnp.max(a, axis=-1, keepdims=True), jnp.int32)
    e = ((big >> 23) & 0xFF) - 126
    k = jnp.clip(e, 72 - 125 - 3 * bits, 100)
    total = jnp.zeros(a.shape[:-1] + (1,), F32)
    for _ in range(3):
        k = k + bits - 24
        part = jnp.floor(a * _pow2(-k)) * _pow2(k)
        a = a - part
        total = total + jnp.sum(part, axis=-1, keepdims=True)
    return total * np.float32(1 / m)


def rms_norm(x, scale, eps):
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return xf * jax.lax.rsqrt(var + eps) * scale


def bitlinear(x, proj):
    signs, alpha = proj
    beta = mean_abs(x)
    xs = jnp.where(x.astype(F32) >= 0, 1.0, -1.0).astype(jnp.bfloat16)
    dot = jnp.dot(xs, signs, preferred_element_type=F32)  # exact: sums of +-1
    dot, beta = jax.lax.optimization_barrier((dot, beta))
    return dot * (alpha * beta)


def rope(x, positions, theta):
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=F32) / half))
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(F32) * freqs
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def prompt_attention(q, k, v, positions):
    """Causal streaming softmax over 512-position KV chunks (B,S,H,D)."""
    b, sq, h, d = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    scale = 1.0 / math.sqrt(d)
    head_map = jnp.arange(h, dtype=jnp.int32) // (h // kvh)
    n_chunks = math.ceil(skv / ATTN_CHUNK)
    pad = n_chunks * ATTN_CHUNK - skv
    kv_positions = positions
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, (0, pad), constant_values=2**30)
    kcs = k.reshape(b, n_chunks, ATTN_CHUNK, kvh, d).swapaxes(0, 1)
    vcs = v.reshape(b, n_chunks, ATTN_CHUNK, kvh, d).swapaxes(0, 1)
    pcs = kv_positions.reshape(n_chunks, ATTN_CHUNK)

    def step(carry, xs):
        m, l, acc = carry
        kc, vc, kpos = xs
        kh = jnp.take(kc, head_map, axis=2)
        vh = jnp.take(vc, head_map, axis=2)
        s = jnp.einsum("bqhd,bchd->bhqc", q.astype(F32), kh.astype(F32)) * scale
        mask = kpos[None, :] <= positions[:, None]
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqc,bchd->bhqd", p.astype(jnp.bfloat16), vh.astype(jnp.bfloat16),
            preferred_element_type=F32,
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), NEG_INF, F32)
    l0 = jnp.zeros((b, h, sq), F32)
    a0 = jnp.zeros((b, h, sq, d), F32)
    # checkpointed as the program's chunk step is: it changes no value,
    # but the barriers it lowers to shape how XLA fuses the step
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(step), (m0, l0, a0), (kcs, vcs, pcs))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).swapaxes(1, 2)


def token_attention(q, k_cache, v_cache, cache_len):
    """One query per row against its (B, T, KV, D) cache, float32."""
    b, _, h, d = q.shape
    t, kvh = k_cache.shape[1], k_cache.shape[2]
    qg = q.reshape(b, kvh, h // kvh, d)
    s = jnp.einsum("bkgd,btkd->bkgt", qg.astype(F32), k_cache.astype(F32))
    s = s / math.sqrt(d)
    mask = jnp.arange(t)[None, :] < cache_len[:, None]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p, v_cache.astype(F32))
    return out.reshape(b, 1, h, d)


def _ffn(x, w, rnd):
    gate = rnd(jax.nn.silu(rnd(bitlinear(x, w["w1"])).astype(F32)))
    return rnd(bitlinear(rnd(gate * rnd(bitlinear(x, w["w3"]))), w["w2"]))


def _qkv(x, w, dims, rnd, b, s):
    hd = dims.head_dim
    q = rnd(bitlinear(x, w["q"])).reshape(b, s, dims.heads, hd)
    k = rnd(bitlinear(x, w["k"])).reshape(b, s, dims.kv_heads, hd)
    v = rnd(bitlinear(x, w["v"])).reshape(b, s, dims.kv_heads, hd)
    return q, k, v


@functools.partial(jax.jit, static_argnums=(3, 4), compiler_options=EXACT)
def _prefill(w, tables, tokens, dims: Dims, control: bool):
    """(1, S) prompt -> (final-normed last row (1, d), caches (L,1,S,KV,D))."""
    rnd = _int8 if control else _bf16
    b, s = tokens.shape
    positions = jnp.arange(s)
    h = rnd(tables["embed"][tokens])

    def body(h, lw):
        x = rnd(rms_norm(h, lw["norm"], dims.eps))
        q, k, v = _qkv(x, lw, dims, rnd, b, s)
        q = rnd(rope(q, positions, dims.rope_theta))
        k = rnd(rope(k, positions, dims.rope_theta))
        a = rnd(prompt_attention(q, k, v, positions))
        h = rnd(h + rnd(bitlinear(a.reshape(b, s, -1), lw["o"])))
        h = rnd(h + _ffn(rnd(rms_norm(h, lw["norm"], dims.eps)), lw, rnd))
        return h, {"k": rnd(k), "v": rnd(v)}

    h, caches = jax.lax.scan(body, h, w)
    return rnd(rms_norm(h, tables["final_norm"], dims.eps))[:, -1], caches


@functools.partial(jax.jit, static_argnums=(5, 6), compiler_options=EXACT, donate_argnums=(4,))
def _decode(w, tables, token, pos, caches, dims: Dims, control: bool):
    """One teacher-forced token per row at per-row positions."""
    rnd = _int8 if control else _bf16
    b = token.shape[0]
    h = rnd(tables["embed"][token[:, None]])
    rows = jnp.arange(b)

    def body(h, xs):
        lw, c = xs
        x = rnd(rms_norm(h, lw["norm"], dims.eps))
        q, k, v = _qkv(x, lw, dims, rnd, b, 1)
        q = rnd(rope(q, pos[:, None], dims.rope_theta))
        k = rnd(rope(k, pos[:, None], dims.rope_theta))
        ck = c["k"].at[rows, pos].set(k[:, 0].astype(c["k"].dtype))
        cv = c["v"].at[rows, pos].set(v[:, 0].astype(c["v"].dtype))
        a = rnd(token_attention(q, ck, cv, pos + 1))
        h = rnd(h + rnd(bitlinear(a.reshape(b, 1, -1), lw["o"])))
        h = rnd(h + _ffn(rnd(rms_norm(h, lw["norm"], dims.eps)), lw, rnd))
        return h, {"k": ck, "v": cv}

    h, caches = jax.lax.scan(body, h, (w, caches))
    return rnd(rms_norm(h, tables["final_norm"], dims.eps))[:, 0], caches


@functools.partial(jax.jit, static_argnums=(2,))
def _logits(h, head, vocab: int):
    logits = jnp.dot(h.astype(F32), head, precision=HIGHEST)
    return jnp.where(jnp.arange(head.shape[1]) < vocab, logits, -jnp.inf)


@jax.jit
def _gap_rows(ref_logits, chosen):
    """Per row: the reference's best logit minus that of ``chosen``."""
    got = jnp.take_along_axis(ref_logits, chosen[:, None], axis=-1)[:, 0]
    return ref_logits.max(axis=-1) - got


@functools.partial(jax.jit, donate_argnums=(0,))
def _graft(caches, rows, lane):
    return jax.tree.map(
        lambda c, r: jax.lax.dynamic_update_slice_in_dim(c, r.astype(c.dtype), lane, axis=1),
        caches, rows,
    )


def _layer_weights(dims: Dims, seed: int):
    """Stacked per-layer signs (bfloat16) and scales, drawn again from
    the seed. Each scale is ``mean|W|`` taken eagerly on the float32
    matrix, as a standalone reduction of that one matrix."""
    _, _, k_layers = weights_lib.table_keys(seed)
    layers = []
    for r in range(dims.layers):
        lk = jax.random.fold_in(k_layers, r)
        lw = {}
        for name in weights_lib.PROJECTIONS:
            w = weights_lib.projection(lk, name, dims)
            lw[name] = (
                jnp.where(w >= 0, 1.0, -1.0).astype(jnp.bfloat16),
                jnp.mean(jnp.abs(w)),
            )
            del w
        lw["norm"] = jnp.ones((dims.d,), F32)
        layers.append(lw)
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


class Checker:
    """Runs the reference (and optionally the control) over served
    requests, ``lanes`` at a time, in a cache of ``max_len`` rows."""

    def __init__(self, dims: Dims, seed: int, lanes: int, max_len: int):
        self.dims, self.lanes, self.max_len = dims, lanes, max_len
        k_embed, k_head, _ = weights_lib.table_keys(seed)
        embed = weights_lib.embedding(k_embed, dims)
        self.head = embed.T if dims.tied else weights_lib.head(k_head, dims)
        self.tables = {"embed": embed, "final_norm": jnp.ones((dims.d,), F32)}
        self.w = _layer_weights(dims, seed)

    def _cache(self):
        # the control's int8-rounded rows are kept in bfloat16 as well
        d = self.dims
        shape = (d.layers, self.lanes, self.max_len, d.kv_heads, d.head_dim)
        return {"k": jnp.zeros(shape, ACT), "v": jnp.zeros(shape, ACT)}

    def gaps(self, prompts, served, control: bool = False) -> dict:
        """``prompts[i]`` (P,) and ``served[i]`` (G,), ``lanes`` requests
        at a time. Returns the widest gap of the served tokens, and with
        ``control`` that of the control's first choices."""
        out: dict = {}
        for i in range(0, len(prompts), self.lanes):
            part = self._gaps(prompts[i:i + self.lanes], served[i:i + self.lanes], control)
            out = {k: max(v, out.get(k, 0.0)) for k, v in part.items()}
        return out

    def _gaps(self, prompts, served, control: bool) -> dict:
        d = self.dims
        n = len(prompts)
        sides = [False, True] if control else [False]
        caches = {c: self._cache() for c in sides}
        widest = {c: 0.0 for c in sides}
        first = {c: [] for c in sides}
        for lane, prompt in enumerate(prompts):
            toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
            for c in sides:
                h, rows = _prefill(self.w, self.tables, toks, d, c)
                caches[c] = _graft(caches[c], rows, lane)
                first[c].append(h[0])
        hs = {c: jnp.stack(first[c]) for c in sides}
        n_steps = max(len(s) for s in served)
        pad = np.zeros((self.lanes - n,), np.int32)
        pos = np.concatenate([np.array([len(p) for p in prompts], np.int32), pad])
        for j in range(n_steps):
            live = np.array([j < len(s) for s in served])
            want = np.array([s[j] if j < len(s) else 0 for s in served], np.int32)
            ref = _logits(hs[False], self.head, d.vocab)
            widest[False] = max(widest[False], _widest(_gap_rows(ref, jnp.asarray(want)), live))
            if control:
                pick = jnp.argmax(_logits(hs[True], self.head, d.vocab), axis=-1)
                widest[True] = max(widest[True], _widest(_gap_rows(ref, pick), live))
            if j + 1 == n_steps:
                break
            # fresh host arrays every step: a buffer handed to the
            # device may be read after the call returns
            tok = np.concatenate([want, pad])
            for c in sides:
                out, caches[c] = _decode(
                    self.w, self.tables, jnp.asarray(tok), jnp.asarray(pos),
                    caches[c], d, c,
                )
                hs[c] = out[:n]
            pos = pos + (np.arange(self.lanes) < n)
        result = {"served_gap": widest[False]}
        if control:
            result["control_gap"] = widest[True]
        return result


def _widest(gaps, live) -> float:
    g = np.asarray(gaps)[: len(live)]
    return float(g[live].max()) if live.any() else 0.0
