"""Decoder-only LM assembled from the layer pattern (dense / MoE / SSM /
hybrid all share this spine).

Scan-over-repeats with stacked per-slot parameters keeps the HLO O(1)
in depth (an 80-layer qwen2 lowers as one scanned block), and
``jax.checkpoint`` on the scan body gives per-layer activation
rematerialization. The softmax loss is sequence-chunked so the full
(B, S, V) logits tensor never materializes (a 152k vocab at 1M tokens
would otherwise dominate memory).

Inference consumers should reach ``prefill`` / ``decode_step`` /
``program_weights`` through the one-call hardware-compilation API
rather than threading engines by hand::

    # was: eng = GroupedEngine(get_engine(name), k);
    #      params, _ = program_weights(params, cfg, eng);
    #      lm.prefill(params, tokens, cfg, engine=eng); ...
    cm = repro.compiler.compile(cfg, params, HardwareTarget(engine=name))
    logits, caches = cm.prefill(tokens)
    logits, caches = cm.decode_step(tok, pos, caches)
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import bnn
from repro.distributed.hints import hint
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.config import ModelConfig
from repro.models.layers import (
    ACT_DTYPE,
    attention_block,
    attention_continue,
    attention_decode_step,
    attn_init,
    dense,
    ffn,
    ffn_init,
    infer_engine,
    rms_norm,
)

Array = jax.Array
Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _has_ffn(kind, cfg: ModelConfig) -> bool:
    """mamba2-style stacks set d_ff=0: the block is mixer-only."""
    return kind.moe or cfg.d_ff > 0


def _init_slot(key: jax.Array, kind, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    p: Params = {"norm1": jnp.ones((cfg.d_model,), jnp.float32)}
    if kind.mixer == "attn":
        p["attn"] = attn_init(k1, cfg)
    else:
        p["mamba"] = ssm_lib.mamba_init(k1, cfg)
    if _has_ffn(kind, cfg):
        p["norm2"] = jnp.ones((cfg.d_model,), jnp.float32)
        if kind.moe:
            p["moe"] = moe_lib.moe_init(k2, cfg)
        else:
            p["ffn"] = ffn_init(k2, cfg)
    return p


def init_params(key: jax.Array, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, len(cfg.pattern) + 3)
    vp = cfg.padded_vocab  # tables padded for vocab-parallel sharding
    params: Params = {
        "embed": jax.random.normal(keys[0], (vp, cfg.d_model), jnp.float32) * 0.02,
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["head"] = jax.random.normal(keys[1], (cfg.d_model, vp), jnp.float32) * (
            1.0 / math.sqrt(cfg.d_model)
        )
    blocks = {}
    for i, kind in enumerate(cfg.pattern):
        slot_keys = jax.random.split(keys[3 + i - 1], cfg.n_repeats)
        blocks[f"slot{i}"] = jax.vmap(lambda k: _init_slot(k, kind, cfg))(slot_keys)
    params["blocks"] = blocks
    return params


# ---------------------------------------------------------------------------
# Forward (train) — scan over repeats, remat per repeat
# ---------------------------------------------------------------------------


def _apply_repeat(h: Array, slot_params: Params, positions: Array, cfg: ModelConfig):
    aux = jnp.zeros((), jnp.float32)
    for i, kind in enumerate(cfg.pattern):
        sp = slot_params[f"slot{i}"]
        hn = rms_norm(h, sp["norm1"], cfg.norm_eps)
        if kind.mixer == "attn":
            mix, _ = attention_block(sp["attn"], hn, positions, cfg, quant=cfg.quant)
        else:
            mix, _ = ssm_lib.mamba_block(sp["mamba"], hn, cfg)
        h = h + mix
        if _has_ffn(kind, cfg):
            hn = rms_norm(h, sp["norm2"], cfg.norm_eps)
            if kind.moe:
                f, a = _moe(sp["moe"], hn, cfg)
                aux = aux + a
            else:
                f = ffn(sp["ffn"], hn, cfg.quant)
            h = h + f
    return h, aux


def _moe(p: Params, hn: Array, cfg: ModelConfig):
    """MoE with selectable dispatch (ModelConfig.moe_impl)."""
    if cfg.moe_impl == "ep_shard_map":
        from repro.distributed.ep import ep_moe_ffn
        from repro.distributed.hints import current_mesh

        mesh = current_mesh()
        if (
            mesh is not None
            and "model" in mesh.shape
            and cfg.moe_experts % mesh.shape["model"] == 0
            and (hn.shape[0] * hn.shape[1]) % mesh.shape["model"] == 0
        ):
            return ep_moe_ffn(p, hn, cfg, mesh)
    return moe_lib.moe_ffn(p, hn, cfg)


def backbone(params: Params, embeds: Array, positions: Array, cfg: ModelConfig):
    """(B, S, d) -> (hidden (B, S, d), moe_aux scalar)."""
    from repro.models.scan import remat_scan

    h = hint(embeds.astype(ACT_DTYPE), "dp", None, None)

    def body(carry, slot_p):
        h, aux = carry
        h = hint(h, "dp", None, None)  # re-pin batch sharding in the remat replay
        h2, a = _apply_repeat(h, slot_p, positions, cfg)
        return (hint(h2, "dp", None, None), aux + a)

    carry0 = (h, jnp.zeros((), jnp.float32))
    if cfg.remat:
        # remat_scan: per-layer recompute with a SINGLE bf16 residual
        # stack (scan+checkpoint writes an extra fp32 stack — see
        # models/scan.py)
        h, aux = remat_scan(body, carry0, params["blocks"])
    else:
        (h, aux), _ = jax.lax.scan(lambda c, x: (body(c, x), None), carry0, params["blocks"])
    return rms_norm(h, params["final_norm"], cfg.norm_eps), aux


def embed_tokens(params: Params, tokens: Array) -> Array:
    return params["embed"][tokens]


def _head_weights(params: Params, cfg: ModelConfig) -> Array:
    return params["embed"].T if cfg.tie_embeddings else params["head"]


def _mask_padded_vocab(logits: Array, cfg: ModelConfig) -> Array:
    """-inf on the padding columns (see ModelConfig.padded_vocab)."""
    if cfg.padded_vocab == cfg.vocab_size:
        return logits
    valid = jnp.arange(logits.shape[-1]) < cfg.vocab_size
    return jnp.where(valid, logits, -1e30)


def lm_loss(params: Params, hidden: Array, targets: Array, cfg: ModelConfig) -> Array:
    """Sequence-chunked softmax cross-entropy. targets < 0 are masked."""
    w = _head_weights(params, cfg)
    b, s, d = hidden.shape
    ck = min(cfg.loss_chunk, s)
    pad = (-s) % ck
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)), constant_values=-1)
    nc = hidden.shape[1] // ck
    hs = hidden.reshape(b, nc, ck, d).swapaxes(0, 1)
    ts = targets.reshape(b, nc, ck).swapaxes(0, 1)

    @jax.checkpoint
    def step(acc, xs):
        # checkpointed: the backward recomputes this chunk's logits
        # instead of saving a (B, ck, V) fp32 tensor per chunk — without
        # this the loss scan alone materializes the full (B, S, V)
        # logits (tens of GiB/device at 150k vocabs).
        hc, tc = xs
        # vocab-parallel loss: batch over the pure-DP axes only; the
        # model axis belongs to the vocab dim of w/logits here
        hc = hint(hc, "dp_strict", None, None)
        logits = jnp.einsum("bsd,dv->bsv", hc.astype(jnp.float32), w)
        logits = hint(_mask_padded_vocab(logits, cfg), "dp_strict", None, "model_strict")
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, jnp.maximum(tc, 0)[..., None], axis=-1)[..., 0]
        mask = (tc >= 0).astype(jnp.float32)
        n_tok, tot = acc
        return (n_tok + mask.sum(), tot + ((lse - ll) * mask).sum()), None

    (n_tok, total), _ = jax.lax.scan(step, (jnp.zeros(()), jnp.zeros(())), (hs, ts))
    return total / jnp.maximum(n_tok, 1.0)


def loss_fn(params: Params, batch: dict, cfg: ModelConfig, aux_coef: float = 0.01) -> Array:
    """Next-token loss over a {tokens, (optional) extra_embeds} batch."""
    tokens = batch["tokens"]
    embeds = embed_tokens(params, tokens)
    if "extra_embeds" in batch:  # modality frontend stub (VLM)
        embeds = jnp.concatenate([batch["extra_embeds"].astype(embeds.dtype), embeds], axis=1)
    positions = jnp.arange(embeds.shape[1])
    hidden, aux = backbone(params, embeds, positions, cfg)
    n_extra = embeds.shape[1] - tokens.shape[1]
    hidden = hidden[:, n_extra:, :]
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.full((tokens.shape[0], 1), -1, tokens.dtype)], axis=1
    )
    return lm_loss(params, hidden, targets, cfg) + aux_coef * aux


# ---------------------------------------------------------------------------
# Prefill / decode (serving)
# ---------------------------------------------------------------------------

# The binarized projection tensors of the LM spine (== the mapping IR's
# coverage): these are the weights a crossbar holds resident.
BINARIZED_PROJECTIONS = {"attn": ("q", "k", "v", "o"), "ffn": ("w1", "w3", "w2")}


def _fused_qkv_artifact(attn: Params, base) -> Params | None:
    """Derived shared-activation QKV artifact for fused-dense engines.

    q/k/v all consume the same attention input; engines that fuse the
    whole BitLinear into one kernel (``supports_fused_dense``) can then
    run ONE launch over the three sign matrices concatenated along the
    output axis (``layers.fused_qkv_dense`` splits at the static head
    boundaries). Packing is column-independent, so the concatenated
    artifact is exactly the three per-projection artifacts side by side
    — bit-identical outputs. The per-column scale vector repeats each
    projection's scalar ``mean|w|`` across its n columns. Derived (not
    counted in ``n_programmed``): the per-projection artifacts still
    exist and serve every non-fused path.
    """
    if not all(k in attn for k in ("q", "k", "v")):
        return None
    projs = [attn[k] for k in ("q", "k", "v")]
    prepared = [
        base.prepare(bnn.binarize_ste(jnp.concatenate([p["w"][i] for p in projs], axis=1)))
        for i in range(projs[0]["w"].shape[0])
    ]
    # (L,) scalar scales -> (L, n_q + n_k + n_v) per-column vectors
    alpha = jnp.concatenate(
        [
            jnp.broadcast_to(p["alpha"][:, None], (p["alpha"].shape[0], p["w"].shape[2]))
            for p in projs
        ],
        axis=1,
    )
    return {
        "prepared": jax.tree.map(lambda *xs: jnp.stack(xs), *prepared),
        "alpha": alpha,
    }


def attach_weight_scales(params: Params, cfg: ModelConfig) -> Params:
    """Compute each binarized projection's per-tensor scale
    (``bnn.weight_scale``) once, eagerly per scan repeat, and store it
    beside ``w`` as ``alpha``, where ``layers.dense`` reads it instead of
    reducing ``w`` inside the compiled step.

    Every target compiled from the same params then uses the same f32
    alpha. XLA picks a large reduction's summation order per program: on
    a TPU v5e the mean of a 1024x2816 weight computed inside the layer
    scan differed in the last bit from the same mean computed alone, and
    one ulp of alpha flips binarized activations downstream. Projections
    already holding ``alpha`` are kept; no-op unless ``quant == "bnn"``.
    """
    if cfg.quant != "bnn" or "blocks" not in params:
        return params
    blocks = {}
    for slot_name, slot in params["blocks"].items():
        new_slot = dict(slot)
        for part, projs in BINARIZED_PROJECTIONS.items():
            if part not in slot:
                continue
            sub = dict(slot[part])
            for proj_name in projs:
                proj = sub.get(proj_name)
                if proj is None or "alpha" in proj or "w" not in proj:
                    continue
                w = proj["w"]  # (L, m, n): stacked over scan repeats
                sub[proj_name] = dict(
                    proj, alpha=jnp.stack([bnn.weight_scale(w[i]) for i in range(w.shape[0])])
                )
            new_slot[part] = sub
        blocks[slot_name] = new_slot
    return dict(params, blocks=blocks)


def program_weights(params: Params, cfg: ModelConfig, engine) -> tuple[Params, int]:
    """Crossbar-programming phase: compile every binarized projection
    into ``engine``'s resident form ONCE, before serving starts.

    Walks the stacked block params and attaches a
    :class:`repro.core.engine.PreparedWeights` (plus the precomputed
    per-tensor weight scale) alongside each attn q/k/v/o and FFN
    w1/w3/w2 projection — exactly the transforms ``layers.dense``
    applies per call, hoisted to bind time, so prefill/decode traces
    carry zero weight-side work (the paper's stationary-weight premise:
    program the PCM once, stream only activations). Per-repeat slices
    are programmed individually and stacked, so ``lax.scan`` slices the
    artifact back per layer bit-identically.

    Returns ``(programmed_params, n_programmed)`` where ``n_programmed``
    counts projection *instances* (stacked repeats each count). The
    input pytree is not mutated. No-op (0 programmed) unless
    ``cfg.quant == "bnn"`` and an engine is bound.
    """
    if cfg.quant != "bnn" or engine is None or "blocks" not in params:
        return params, 0
    base = getattr(engine, "base", engine)  # unwrap a GroupedEngine
    if not hasattr(base, "prepare"):
        # a minimal third-party backend without the two-phase contract:
        # serve it raw (same fallback as layers.dense / model._programmed)
        return params, 0
    params = attach_weight_scales(params, cfg)
    n_programmed = 0
    blocks = {}
    for slot_name, slot in params["blocks"].items():
        new_slot = dict(slot)
        for part, projs in BINARIZED_PROJECTIONS.items():
            if part not in slot:
                continue
            sub = dict(slot[part])
            for proj_name in projs:
                if proj_name not in sub:
                    continue
                proj = dict(sub[proj_name])
                w = proj.pop("w")  # (L, m, n): stacked over scan repeats
                prepared = [base.prepare(bnn.binarize_ste(w[i])) for i in range(w.shape[0])]
                # the latent weights are NOT carried along: the
                # programmed artifact fully replaces them on the serving
                # path (like the hardware, which holds only cell states),
                # and dropping them halves the per-tick weight bytes the
                # decode scan slices
                proj["prepared"] = jax.tree.map(
                    lambda *xs: jnp.stack(xs), *prepared
                )
                sub[proj_name] = proj
                n_programmed += int(w.shape[0])
            if part == "attn" and getattr(base, "supports_fused_dense", False):
                qkv = _fused_qkv_artifact(slot["attn"], base)
                if qkv is not None:
                    sub["qkv"] = qkv
            new_slot[part] = sub
        blocks[slot_name] = new_slot
    return dict(params, blocks=blocks), n_programmed


def _apply_repeat_prefill(
    h: Array, slot_params: Params, positions: Array, cfg: ModelConfig, engine=None
):
    # binarized projections run on cfg.bnn_engine unless the caller
    # passes an engine (e.g. the serving engine's K-group adapter)
    eng = engine if engine is not None else infer_engine(cfg)
    caches = {}
    for i, kind in enumerate(cfg.pattern):
        sp = slot_params[f"slot{i}"]
        hn = rms_norm(h, sp["norm1"], cfg.norm_eps)
        if kind.mixer == "attn":
            mix, (k, v) = attention_block(
                sp["attn"], hn, positions, cfg, quant=cfg.quant, engine=eng
            )
            caches[f"slot{i}"] = {"k": k.astype(ACT_DTYPE), "v": v.astype(ACT_DTYPE)}
        else:
            mix, st = ssm_lib.mamba_block(sp["mamba"], hn, cfg)
            caches[f"slot{i}"] = st
        h = h + mix
        if _has_ffn(kind, cfg):
            hn = rms_norm(h, sp["norm2"], cfg.norm_eps)
            if kind.moe:
                f, _ = moe_lib.moe_ffn(sp["moe"], hn, cfg)
            else:
                f = ffn(sp["ffn"], hn, cfg.quant, eng)
            h = h + f
    return h, caches


def prefill(
    params: Params,
    tokens: Array,
    cfg: ModelConfig,
    extra_embeds: Array | None = None,
    engine=None,
):
    """Forward pass that also returns stacked per-layer caches and the
    last-position logits. Cache seq capacity == prompt length (callers
    pad to their serving window). ``extra_embeds`` (B, L, d) prepends
    modality-frontend embeddings (VLM prefill). ``engine`` overrides
    ``cfg.bnn_engine`` for the binarized projections (serving passes its
    K-group ``GroupedEngine`` here)."""
    embeds = embed_tokens(params, tokens)
    if extra_embeds is not None:
        embeds = jnp.concatenate([extra_embeds.astype(embeds.dtype), embeds], axis=1)
    positions = jnp.arange(embeds.shape[1])
    h = embeds.astype(ACT_DTYPE)

    def body(h, slot_p):
        h2, caches = _apply_repeat_prefill(h, slot_p, positions, cfg, engine)
        return h2, caches

    h, caches = jax.lax.scan(body, h, params["blocks"])
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    w = _head_weights(params, cfg)
    logits = jnp.einsum("bd,dv->bv", h[:, -1, :].astype(jnp.float32), w)
    return _mask_padded_vocab(logits, cfg), caches


class PrefixContinuationError(ValueError):
    """``prefill_continue`` was asked to continue a stack it cannot
    slice at a prefix boundary (SSM/hybrid mixers carry recurrent state,
    not per-position KV rows) or was given inconsistent caches."""


def prefill_continue(
    params: Params,
    tokens: Array,
    prefix_caches: Params,
    cfg: ModelConfig,
    engine=None,
):
    """Prefill only the suffix of a prompt whose prefix KV is cached.

    ``tokens`` (B, S) are the prompt positions AFTER the shared prefix;
    ``prefix_caches`` is a prefill-shaped cache pytree (per-layer
    ``{"k"/"v": (R, B, Lp, KV, D)}``) covering positions ``[0, Lp)`` of
    the SAME token prefix — typically sliced from an earlier prompt's
    :func:`prefill` caches. Returns ``(logits, caches)`` exactly like
    :func:`prefill` over the full prompt: last-position logits and
    full-prompt-shaped caches (prefix rows concatenated back in), so a
    serving slot graft is indistinguishable from a from-scratch prefill.

    Bit-exactness vs the full prefill (the serving prefix-graft
    invariant) follows from :func:`~repro.models.layers
    .attention_continue`'s two properties: cached prefix rows are
    prompt-length-invariant, and the suffix runs through the prefill
    attention graph. Attention-only stacks only — an SSM mixer's
    recurrent state cannot be cut at a token boundary — and the decoder
    LM path only (no ``extra_embeds``: VLM prompts prepend frontend
    embeddings whose positions a token-hash prefix cannot name).
    """
    bad = [
        f"slot{i}" for i, kind in enumerate(cfg.pattern) if kind.mixer != "attn"
    ]
    if bad:
        raise PrefixContinuationError(
            f"prefix continuation needs per-position KV rows; {cfg.name} "
            f"has non-attention mixer(s) at {', '.join(bad)} whose "
            "recurrent state cannot be sliced at a prefix boundary"
        )
    start = next(iter(prefix_caches.values()))["k"].shape[2]
    embeds = embed_tokens(params, tokens)
    positions = jnp.arange(start, start + tokens.shape[1])
    h = embeds.astype(ACT_DTYPE)
    eng = engine if engine is not None else infer_engine(cfg)

    def body(h, xs):
        slot_p, pre_r = xs
        caches = {}
        for i, kind in enumerate(cfg.pattern):
            sp = slot_p[f"slot{i}"]
            pk, pv = pre_r[f"slot{i}"]["k"], pre_r[f"slot{i}"]["v"]
            hn = rms_norm(h, sp["norm1"], cfg.norm_eps)
            mix, (k, v) = attention_continue(
                sp["attn"], hn, positions, pk, pv, cfg, quant=cfg.quant,
                engine=eng,
            )
            caches[f"slot{i}"] = {
                "k": jnp.concatenate([pk, k.astype(ACT_DTYPE)], axis=1),
                "v": jnp.concatenate([pv, v.astype(ACT_DTYPE)], axis=1),
            }
            h = h + mix
            if _has_ffn(kind, cfg):
                hn = rms_norm(h, sp["norm2"], cfg.norm_eps)
                if kind.moe:
                    f, _ = moe_lib.moe_ffn(sp["moe"], hn, cfg)
                else:
                    f = ffn(sp["ffn"], hn, cfg.quant, eng)
                h = h + f
        return h, caches

    h, caches = jax.lax.scan(body, h, (params["blocks"], prefix_caches))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    w = _head_weights(params, cfg)
    logits = jnp.einsum("bd,dv->bv", h[:, -1, :].astype(jnp.float32), w)
    return _mask_padded_vocab(logits, cfg), caches


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=ACT_DTYPE) -> Params:
    """Zero-initialized decode cache pytree (stacked over repeats)."""
    caches = {}
    for i, kind in enumerate(cfg.pattern):
        r = cfg.n_repeats
        if kind.mixer == "attn":
            shape = (r, batch, max_len, cfg.n_kv_heads, cfg.hd)
            caches[f"slot{i}"] = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        else:
            tail, gn = cfg.ssm_conv - 1, cfg.ssm_groups * cfg.ssm_state
            caches[f"slot{i}"] = {
                "conv_x": jnp.zeros((r, batch, tail, cfg.d_inner), dtype),
                "conv_b": jnp.zeros((r, batch, tail, gn), dtype),
                "conv_c": jnp.zeros((r, batch, tail, gn), dtype),
                "ssm": jnp.zeros(
                    (r, batch, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim), jnp.float32
                ),
            }
    return caches


def decode_step(
    params: Params, token: Array, pos: Array, caches: Params, cfg: ModelConfig, engine=None
):
    """One serving step: token (B,) int32, pos scalar int32 or (B,)
    per-slot positions, caches from ``init_cache``/``prefill``. Returns
    (logits (B, V), new_caches). ``engine`` overrides ``cfg.bnn_engine``
    (the serving engine passes its K-group ``GroupedEngine``)."""
    embeds = embed_tokens(params, token[:, None])  # (B, 1, d)
    h = embeds.astype(ACT_DTYPE)
    eng = engine if engine is not None else infer_engine(cfg)

    def body(h, xs):
        slot_p, cache_r = xs
        new_cache = {}
        for i, kind in enumerate(cfg.pattern):
            sp, cp = slot_p[f"slot{i}"], cache_r[f"slot{i}"]
            hn = rms_norm(h, sp["norm1"], cfg.norm_eps)
            if kind.mixer == "attn":
                mix, nk, nv = attention_decode_step(
                    sp["attn"], hn, pos, cp["k"], cp["v"], cfg, quant=cfg.quant,
                    engine=eng,
                )
                new_cache[f"slot{i}"] = {"k": nk, "v": nv}
            else:
                mix, st = ssm_lib.mamba_step(sp["mamba"], hn, cp, cfg)
                new_cache[f"slot{i}"] = st
            h = h + mix
            if _has_ffn(kind, cfg):
                hn = rms_norm(h, sp["norm2"], cfg.norm_eps)
                if kind.moe:
                    f, _ = moe_lib.moe_ffn(sp["moe"], hn, cfg)
                else:
                    f = ffn(sp["ffn"], hn, cfg.quant, eng)
                h = h + f
        return h, new_cache

    h, new_caches = jax.lax.scan(body, h, (params["blocks"], caches))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    w = _head_weights(params, cfg)
    logits = jnp.einsum("bd,dv->bv", h[:, 0, :].astype(jnp.float32), w)
    return _mask_padded_vocab(logits, cfg), new_caches
