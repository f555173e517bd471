"""GQA attention: full/sliding-window causal, cross-attention, ring-buffer
KV cache for decode.

The dense-math path here doubles as the flash-attention kernel's oracle
(kernels/ref.py imports `attend`).  The models do not call the Pallas
kernel: `ModelConfig.attn_impl` picks between this dense path and the
chunked online-softmax one.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, dense_init, rms_norm

NEG_INF = -2.0 ** 20  # large-but-finite; avoids NaN from all-masked rows


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def attn_init(key, d, n_heads, n_kv_heads, head_dim, dtype,
              cross: bool = False, qk_norm: bool = False):
    ks = jax.random.split(key, 8)
    p = {"wq": dense_init(ks[0], (d, n_heads, head_dim), dtype, fan_in=d),
         "wk": dense_init(ks[1], (d, n_kv_heads, head_dim), dtype, fan_in=d),
         "wv": dense_init(ks[2], (d, n_kv_heads, head_dim), dtype, fan_in=d),
         "wo": dense_init(ks[3], (n_heads, head_dim, d), dtype,
                          fan_in=n_heads * head_dim)}
    if cross:
        p["xwq"] = dense_init(ks[4], (d, n_heads, head_dim), dtype, fan_in=d)
        p["xwk"] = dense_init(ks[5], (d, n_kv_heads, head_dim), dtype,
                              fan_in=d)
        p["xwv"] = dense_init(ks[6], (d, n_kv_heads, head_dim), dtype,
                              fan_in=d)
        p["xwo"] = dense_init(ks[7], (n_heads, head_dim, d), dtype,
                              fan_in=n_heads * head_dim)
    if qk_norm:
        p["q_norm"] = jnp.zeros((head_dim,), dtype)
        p["k_norm"] = jnp.zeros((head_dim,), dtype)
    return p


# ---------------------------------------------------------------------------
# core attention math (the kernel oracle)
# ---------------------------------------------------------------------------

def attend(q, k, v, mask=None):
    """q: (B,S,H,hd), k/v: (B,T,Hkv,hd); GQA via head grouping.

    mask: broadcastable to (B, H_or_1, S, T), True = attend.
    """
    b, s, h, hd = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    qg = q.reshape(b, s, hkv, g, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(hd)
    if mask is not None:
        m = mask if mask.ndim == 4 else mask[:, None]
        m = m.reshape(b, -1, 1, s, t) if m.shape[1] not in (1, hkv) \
            else m[:, :, None]
        scores = jnp.where(m, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v.astype(jnp.float32))
    return out.reshape(b, s, h, hd).astype(q.dtype)


def causal_window_mask(q_pos, k_pos, window: int = 0):
    """True where q may attend k: k<=q and (optionally) q-k < window."""
    m = k_pos[..., None, :] <= q_pos[..., :, None]
    if window:
        m = m & (k_pos[..., None, :] > q_pos[..., :, None] - window)
    return m


def attend_chunked(q, k, v, *, causal: bool = True, window: int = 0,
                   block_q: int = 1024, block_k: int = 1024):
    """Flash-style streaming attention in jnp (mirrors the Pallas
    kernel's online softmax): never materializes the (S,T) score matrix.

    Used by the §Perf prefill optimization; the Pallas flash kernel is
    the TPU-native version of exactly this loop.
    """
    b, s, h, hd = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    bq = min(block_q, s)
    bk = min(block_k, t)
    nq, nk = -(-s // bq), -(-t // bk)
    s_pad, t_pad = nq * bq, nk * bk
    qp = jnp.pad(q, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
    qg = qp.reshape(b, nq, bq, hkv, g, hd).astype(jnp.float32) \
        / jnp.sqrt(hd)
    kc = kp.reshape(b, nk, bk, hkv, hd).astype(jnp.float32)
    vc = vp.reshape(b, nk, bk, hkv, hd).astype(jnp.float32)

    def q_block_impl(qi, q_blk, kc_b, vc_b):
        q_pos = qi * bq + jnp.arange(bq)

        def kv_step(carry, inp):
            m_run, l_run, acc = carry
            ki, k_blk, v_blk = inp
            k_pos = ki * bk + jnp.arange(bk)
            sc = jnp.einsum("qkgd,tkd->kgqt", q_blk, k_blk)
            valid = (k_pos[None, :] < t) & (q_pos[:, None] < s)
            if causal:
                valid &= k_pos[None, :] <= q_pos[:, None]
            if window:
                valid &= k_pos[None, :] > q_pos[:, None] - window
            sc = jnp.where(valid[None, None], sc, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(sc, axis=-1))
            p = jnp.exp(sc - m_new[..., None])
            alpha = jnp.exp(m_run - m_new)
            l_new = l_run * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] \
                + jnp.einsum("kgqt,tkd->kgqd", p, v_blk)
            return (m_new, l_new, acc_new), None

        init = (jnp.full((hkv, g, bq), NEG_INF, jnp.float32),
                jnp.zeros((hkv, g, bq), jnp.float32),
                jnp.zeros((hkv, g, bq, hd), jnp.float32))
        (m_f, l_f, acc), _ = jax.lax.scan(
            kv_step, init, (jnp.arange(nk), kc_b, vc_b))
        out = acc / jnp.maximum(l_f, 1e-30)[..., None]
        return out.transpose(2, 0, 1, 3)                 # (bq,hkv,g,hd)

    out = jax.vmap(
        lambda q_b, k_b, v_b: jax.lax.map(
            lambda qi: q_block_impl(qi, q_b[qi], k_b, v_b),
            jnp.arange(nq)))(qg, kc, vc)                 # (B,nq,bq,hkv,g,hd)
    out = out.reshape(b, s_pad, h, hd)[:, :s]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# full-sequence (train / prefill) self-attention
# ---------------------------------------------------------------------------

def self_attention(params, x, positions, *, n_kv_heads, rope_theta,
                   causal: bool = True, window: int = 0,
                   qk_norm: bool = False, norm_eps: float = 1e-6,
                   impl: str = "naive", block_q: int = 1024,
                   block_k: int = 1024):
    """x: (B,S,d) -> (B,S,d); also returns (k,v) for cache seeding."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if qk_norm:
        q = rms_norm(q, params["q_norm"], norm_eps)
        k = rms_norm(k, params["k_norm"], norm_eps)
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    if impl == "chunked" and causal:
        o = attend_chunked(q, k, v, causal=causal, window=window,
                           block_q=block_q, block_k=block_k)
    else:
        if causal:
            mask = causal_window_mask(positions, positions,
                                      window)[:, None]
        else:
            mask = None
        o = attend(q, k, v, mask)
    return jnp.einsum("bshk,hkd->bsd", o, params["wo"]), (k, v)


def cross_attention(params, x, enc_kv, *, qk_norm: bool = False,
                    norm_eps: float = 1e-6):
    """Decoder cross-attn; enc_kv = (k, v) precomputed from the encoder."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["xwq"])
    k, v = enc_kv
    o = attend(q, k, v, None)
    return jnp.einsum("bshk,hkd->bsd", o, params["xwo"])


def encode_kv(params, enc_out):
    k = jnp.einsum("btd,dhk->bthk", enc_out, params["xwk"])
    v = jnp.einsum("btd,dhk->bthk", enc_out, params["xwv"])
    return k, v


# ---------------------------------------------------------------------------
# ring-buffer KV cache (decode)
# ---------------------------------------------------------------------------

def init_kv_cache(batch, n_kv_heads, head_dim, capacity, dtype):
    """capacity = window for SWA archs, max_seq for full attention."""
    return {
        "k": jnp.zeros((batch, capacity, n_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, capacity, n_kv_heads, head_dim), dtype),
        "pos": jnp.full((batch, capacity), -1, jnp.int32),
    }


def decode_attention(params, x, cache, cur_pos, *, rope_theta,
                     window: int = 0, qk_norm: bool = False,
                     norm_eps: float = 1e-6):
    """One-token decode: x (B,1,d), cur_pos (B,) absolute position.

    Writes (k,v) at slot cur_pos % capacity (ring), attends over all valid
    slots.  Returns (out (B,1,d), new_cache).
    """
    b = x.shape[0]
    cap = cache["k"].shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if qk_norm:
        q = rms_norm(q, params["q_norm"], norm_eps)
        k = rms_norm(k, params["k_norm"], norm_eps)
    pos = cur_pos[:, None]                     # (B,1)
    q = apply_rope(q, pos, rope_theta)
    k = apply_rope(k, pos, rope_theta)

    slot = jnp.mod(cur_pos, cap)               # (B,)
    bidx = jnp.arange(b)
    new_k = cache["k"].at[bidx, slot].set(k[:, 0])
    new_v = cache["v"].at[bidx, slot].set(v[:, 0])
    new_pos = cache["pos"].at[bidx, slot].set(cur_pos)

    valid = (new_pos >= 0) & (new_pos <= cur_pos[:, None])
    if window:
        valid = valid & (new_pos > cur_pos[:, None] - window)
    o = attend(q, new_k, new_v, valid[:, None, None, :])
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"])
    return out, {"k": new_k, "v": new_v, "pos": new_pos}


def seed_kv_cache(cache, k, v, positions):
    """Write a prefill's (k,v) into the ring cache (last `cap` tokens)."""
    cap = cache["k"].shape[1]
    s = k.shape[1]
    take = min(cap, s)
    k_t, v_t = k[:, -take:], v[:, -take:]
    p_t = positions[:, -take:]
    slots = jnp.mod(p_t, cap)                  # (B,take)
    bidx = jnp.arange(k.shape[0])[:, None]
    return {
        "k": cache["k"].at[bidx, slots].set(k_t),
        "v": cache["v"].at[bidx, slots].set(v_t),
        "pos": cache["pos"].at[bidx, slots].set(p_t),
    }
