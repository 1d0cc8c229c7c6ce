"""Model assembly: embeddings/frontends -> stack(s) -> head (+MTP), loss.

``init_model``/``forward`` are the only entry points the train/serve steps
use. Modality frontends are STUBS per the assignment: ``input_specs``
provides precomputed frame/patch embeddings, and the model consumes them
as leading sequence positions (vlm) or as the encoder input (audio).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .layers import embed_init, rmsnorm, rmsnorm_init, sinusoidal_positions, dense_init
from .transformer import block_apply, block_init, segments, stack_apply, stack_init

__all__ = [
    "init_model",
    "forward",
    "lm_loss",
    "count_params",
    "active_params",
    "routed_expert_params",
    "mrope_positions",
    "LEARNED_POS_MAX",
]

LEARNED_POS_MAX = 32768  # whisper decode_32k needs absolute slots up to 32k


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def init_model(cfg: ArchConfig, key) -> Dict:
    dtype = jnp.dtype(cfg.dtype)
    k_emb, k_stack, k_enc, k_head, k_mtp = jax.random.split(key, 5)
    params: Dict = {"embed": embed_init(k_emb, cfg.vocab, cfg.d_model, dtype)}
    if cfg.rope == "learned":
        params["pos_embed"] = (
            jax.random.normal(jax.random.fold_in(k_emb, 1), (LEARNED_POS_MAX, cfg.d_model), jnp.float32)
            * 0.01
        ).astype(dtype)
    if cfg.enc_dec:
        enc_segs = [((("attn", "mlp"),), cfg.n_enc_layers)]
        params["encoder"] = stack_init(k_enc, cfg, dtype, cross=False, segs=enc_segs)
        params["enc_norm"] = rmsnorm_init(cfg.d_model, dtype, cfg.rms_offset)
        params["decoder"] = stack_init(k_stack, cfg, dtype, cross=True)
    else:
        params["stack"] = stack_init(k_stack, cfg, dtype, cross=False)
    params["final_norm"] = rmsnorm_init(cfg.d_model, dtype, cfg.rms_offset)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, (cfg.d_model, cfg.vocab), dtype)
    if cfg.mtp:
        # DeepSeek-V3's MTP module (arXiv:2412.19437 section 2.2) is one more
        # block of the main model's kind: MLA and, in an MoE model, an MoE
        # layer with the same routed and shared experts as the stack's
        km1, km2 = jax.random.split(k_mtp)
        params["mtp"] = {
            "norm_h": rmsnorm_init(cfg.d_model, dtype, cfg.rms_offset),
            "norm_e": rmsnorm_init(cfg.d_model, dtype, cfg.rms_offset),
            "proj": dense_init(km1, (2 * cfg.d_model, cfg.d_model), dtype),
            "block": block_init(km2, cfg, "attn", _mtp_ffn(cfg), dtype),
            "final_norm": rmsnorm_init(cfg.d_model, dtype, cfg.rms_offset),
        }
    return params


def _mtp_ffn(cfg: ArchConfig) -> str:
    return "moe" if cfg.moe is not None else "mlp"


# ---------------------------------------------------------------------------
# Positions
# ---------------------------------------------------------------------------
def mrope_positions(cfg: ArchConfig, batch: int, n_vision: int, n_text: int, offset=0):
    """Qwen2-VL M-RoPE ids (B, 3, S): vision patches get (t=0, h, w) grid
    ids; text gets synchronized ids continuing after the grid extent."""
    g = max(1, int(math.ceil(math.sqrt(max(n_vision, 1)))))
    vis_i = jnp.arange(n_vision)
    vis = jnp.stack([jnp.zeros_like(vis_i), vis_i // g, vis_i % g])  # (3, Nv)
    start = g  # text ids start after the spatial extent
    txt_i = start + jnp.arange(n_text) + offset
    txt = jnp.stack([txt_i, txt_i, txt_i])  # (3, Nt)
    pos = jnp.concatenate([vis, txt], axis=1)  # (3, S)
    return jnp.broadcast_to(pos[None], (batch, 3, pos.shape[1]))


def _text_positions(batch: int, seq: int, offset, like=None) -> jnp.ndarray:
    """Position ids. ``like`` (the token array) donates its sharding: ids
    built from bare iota are unsharded, and an unsharded (B, S[, S]) mask
    bias makes GSPMD replicate the attention path across the data axis
    (measured 4.5x FLOP inflation on deepseek -- EXPERIMENTS.md §Perf)."""
    pos = jnp.arange(seq)[None, :] + jnp.asarray(offset).reshape(-1, 1)
    pos = jnp.broadcast_to(pos, (batch, seq))
    if like is not None:
        pos = pos + jnp.zeros_like(like, dtype=pos.dtype)
    return pos


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def _embed(cfg, params, tokens):
    x = params["embed"][tokens]
    if cfg.emb_scale:
        x = (x.astype(jnp.float32) * math.sqrt(cfg.d_model)).astype(x.dtype)
    return x


def _head(cfg, params, x):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("bsd,dv->bsv", x, w)


def forward_hidden(
    params: Dict,
    cfg: ArchConfig,
    batch: Dict,
    *,
    caches: Optional[Dict] = None,
    impl: str = "auto",
    remat: str = "none",
    want_mtp: bool = False,
) -> Tuple[jnp.ndarray, Optional[Dict], Dict]:
    """Backbone only: returns (normed hidden (B,S,d), new_caches, extras
    {'aux', 'mtp_hidden'?}). The head is applied by the caller -- training
    uses :func:`chunked_ce` so full (tokens x vocab) logits never
    materialize; serving applies the head to the positions it needs.

    batch keys: 'tokens' (B,S); optional 'frontend' (B,F,d) patch/frame
    embeddings (vlm: prepended; audio: encoder input); optional
    'cache_index' () int for decode; optional 'positions' override.
    """
    tokens = batch["tokens"]
    b, s = tokens.shape
    offset = batch.get("cache_index", 0)
    x = _embed(cfg, params, tokens)

    enc_out = None  # only non-None when cross K/V must be (re)computed
    new_caches = dict(caches) if caches is not None else None
    if cfg.enc_dec:
        if caches is not None and "enc_out" in caches:
            # decode: cross K/V already live in the per-layer caches; the
            # stack must NOT see enc_out again (it would re-append K/V)
            new_caches["enc_out"] = caches["enc_out"]
        else:
            enc_in = batch["frontend"].astype(x.dtype)
            ns = enc_in.shape[1]
            enc_in = enc_in + sinusoidal_positions(ns, cfg.d_model)[None].astype(x.dtype)
            enc_pos = _text_positions(b, ns, 0)
            enc_out, _, _ = stack_apply(
                params["encoder"], cfg, enc_in, positions=enc_pos, mode="bidir",
                impl=impl, remat=remat, segs=[((("attn", "mlp"),), cfg.n_enc_layers)],
            )
            enc_out = rmsnorm(params["enc_norm"], enc_out, cfg.rms_offset)
            if new_caches is not None:
                new_caches["enc_out"] = enc_out

    if cfg.frontend == "vision" and batch.get("frontend") is not None:
        vis = batch["frontend"].astype(x.dtype)
        x = jnp.concatenate([vis, x], axis=1)
        positions = mrope_positions(cfg, b, vis.shape[1], s, offset=offset)
        positions = positions + jnp.zeros(
            (b, 1, 1), positions.dtype
        ) * 0  # keep shape; batch sharding follows the concat below
    elif cfg.rope == "mrope":
        # text-only step (e.g. decode): all three ids follow the text id
        nv = cfg.n_frontend_tokens
        g = max(1, int(math.ceil(math.sqrt(max(nv, 1)))))
        txt = _text_positions(b, s, offset, like=tokens) + g
        positions = jnp.broadcast_to(txt[:, None, :], (b, 3, s))
    else:
        positions = batch.get("positions")
        if positions is None:
            positions = _text_positions(b, s, offset, like=tokens)

    if cfg.rope == "learned":
        pos_tab = params["pos_embed"]
        x = x + pos_tab[jnp.clip(positions, 0, LEARNED_POS_MAX - 1)].astype(x.dtype)

    stack_name = "decoder" if cfg.enc_dec else "stack"
    stack_caches = caches.get("stack") if caches is not None else None
    h, stack_caches_out, aux = stack_apply(
        params[stack_name], cfg, x, positions=positions, mode="causal",
        caches=stack_caches, enc_out=enc_out, impl=impl, remat=remat,
        cross=cfg.enc_dec,
    )
    if new_caches is not None:
        new_caches["stack"] = stack_caches_out

    hn = rmsnorm(params["final_norm"], h, cfg.rms_offset)
    extras = {"aux": aux}

    if cfg.mtp and want_mtp and caches is None:
        # DeepSeek-V3 MTP: fuse h_t with emb(tok_{t+1}), one extra block,
        # shared head -> predicts tok_{t+2}. (Sequence shortened by 1.)
        mp = params["mtp"]
        h_in = rmsnorm(mp["norm_h"], h[:, :-1], cfg.rms_offset)
        e_in = rmsnorm(mp["norm_e"], _embed(cfg, params, tokens[:, 1:]), cfg.rms_offset)
        fused = jnp.einsum(
            "bsd,de->bse", jnp.concatenate([h_in, e_in], -1), mp["proj"]
        )
        fused, _, mtp_aux = block_apply(
            mp["block"], cfg, "attn", _mtp_ffn(cfg), fused,
            positions=positions[:, :-1] if positions.ndim == 2 else positions,
            mode="causal", cache=None, enc_out=None, impl=impl,
        )
        extras["aux"] = aux + mtp_aux
        extras["mtp_hidden"] = rmsnorm(mp["final_norm"], fused, cfg.rms_offset)

    return hn, new_caches, extras


def forward(
    params: Dict,
    cfg: ArchConfig,
    batch: Dict,
    *,
    caches: Optional[Dict] = None,
    impl: str = "auto",
    remat: str = "none",
    want_mtp: bool = False,
) -> Tuple[jnp.ndarray, Optional[Dict], Dict]:
    """Full-logits forward (tests/small models/serving). Training uses
    forward_hidden + chunked_ce instead."""
    hn, new_caches, extras = forward_hidden(
        params, cfg, batch, caches=caches, impl=impl, remat=remat, want_mtp=want_mtp
    )
    logits = _head(cfg, params, hn)
    if "mtp_hidden" in extras:
        extras["mtp_logits"] = _head(cfg, params, extras.pop("mtp_hidden"))
    return logits, new_caches, extras


def chunked_ce(
    cfg: ArchConfig,
    params: Dict,
    hidden: jnp.ndarray,
    labels: jnp.ndarray,
    n_chunks: int = 1,
) -> jnp.ndarray:
    """Masked CE without materializing (B, S, V) logits: the sequence is
    split into n_chunks, each chunk's logits are computed, reduced, and
    *rematerialized* in the backward pass (jax.checkpoint), so live logits
    are (B, S/n, V) -- the standard streamed-softmax-CE memory fix.
    """
    b, s, d = hidden.shape
    while s % n_chunks:
        n_chunks -= 1  # largest divisor <= requested
    if n_chunks <= 1:
        return lm_loss(_head(cfg, params, hidden), labels)
    hc = hidden.reshape(b, n_chunks, s // n_chunks, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, n_chunks, s // n_chunks).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_stats(h_chunk, l_chunk):
        logits = _head(cfg, params, h_chunk).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, jnp.maximum(l_chunk, 0)[..., None], axis=-1
        )[..., 0]
        mask = (l_chunk >= 0).astype(jnp.float32)
        return jnp.sum((lse - tgt) * mask), jnp.sum(mask)

    def body(carry, xs):
        tot, cnt = carry
        t, c = chunk_stats(*xs)
        return (tot + t, cnt + c), None

    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), (hc, lc)
    )
    return tot / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------
def lm_loss(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Masked CE in f32; labels < 0 are ignored (vision slots, padding)."""
    v = logits.shape[-1]
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    tgt = jnp.take_along_axis(lf, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((lse - tgt) * mask) / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# Parameter accounting (for MODEL_FLOPS / roofline)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=64)
def _param_counts(cfg: ArchConfig) -> Tuple[int, int]:
    """(total, routed-expert) parameter counts from one ``eval_shape`` over
    the real init (no alloc); configs are frozen, so the counts are kept."""
    shapes = jax.eval_shape(lambda: init_model(cfg, jax.random.PRNGKey(0)))
    total = routed = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(shapes):
        n = int(math.prod(leaf.shape))
        total += n
        if any(isinstance(p, jax.tree_util.DictKey) and p.key == "experts" for p in path):
            routed += n
    return total, routed


def count_params(cfg: ArchConfig) -> int:
    """Exact parameter count via eval_shape over the real init (no alloc)."""
    return _param_counts(cfg)[0]


def routed_expert_params(cfg: ArchConfig) -> int:
    """Parameters of the routed experts: every ``experts`` stack of the
    init, the MTP block's included (what expert parallelism spreads; the
    shared experts and routers are not in it)."""
    return _param_counts(cfg)[1]


def active_params(cfg: ArchConfig) -> int:
    """Active-per-token parameters (MoE: routed top-k + shared only)."""
    total, routed = _param_counts(cfg)
    if cfg.moe is None:
        return total
    m = cfg.moe
    return total - routed // m.n_experts * (m.n_experts - m.top_k)
