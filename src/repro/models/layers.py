"""Transformer / SSM building blocks for the ten assigned architectures.

Pure-JAX reference implementations (the lowering default; Pallas TPU kernels
in repro.kernels are drop-in replacements for the hot spots and are
validated against these).

Conventions:
  x          : (B, S, D) activations, cfg.dtype (bf16)
  q, k, v    : (B, S, H, Dh)
  GQA        : kv heads are *grouped-einsummed*, never materialised repeated
  attention  : KV-chunked online-softmax (flash-style) — O(S * chunk) memory
  caches     : dicts of arrays; decode writes in-place via .at[] on a
               static-size buffer (rolling for sliding-window)
  MoE        : scatter/gather token dispatch with per-expert capacity —
               compiled FLOPs scale with top_k (active experts), not E
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# norms & basics
# ---------------------------------------------------------------------------


def rms_norm(x: jnp.ndarray, gamma: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * gamma


def silu(x):
    return x * jax.nn.sigmoid(x)


# ---------------------------------------------------------------------------
# RoPE (full / half / M-RoPE)
# ---------------------------------------------------------------------------


def _rope_cos_sin(positions: jnp.ndarray, dim: int, base: float = 10000.0, scaling=None):
    """positions (...,) -> cos, sin of shape (..., dim//2); ``scaling`` (a
    ``YarnScaling``) selects YaRN's frequencies and rotation scale."""
    inv = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    mult = 1.0
    if scaling is not None:
        inv = yarn_inv_freq(inv, dim, base, scaling)
        mult = yarn_mscale(scaling.factor, scaling.mscale) / yarn_mscale(
            scaling.factor, scaling.mscale_all_dim
        )
    ang = positions.astype(jnp.float32)[..., None] * inv  # (..., dim//2)
    return jnp.cos(ang) * mult, jnp.sin(ang) * mult


def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature term ``0.1 * mscale * ln(scale) + 1``."""
    if scale <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(inv: jnp.ndarray, dim: int, base: float, scaling) -> jnp.ndarray:
    """YaRN's rotary frequencies: the original ``inv`` below the
    ``beta_fast`` correction dimension, ``inv / factor`` past the
    ``beta_slow`` one, and a linear ramp over the dimensions between."""
    orig = scaling.original_max_position_embeddings

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(correction_dim(scaling.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return inv / scaling.factor * ramp + inv * (1.0 - ramp)


def attention_scale(cfg: ModelConfig, head_dim: int) -> float:
    """Softmax scale ``head_dim ** -0.5``, times YaRN's ``mscale ** 2``
    where the config's rope variant is YaRN."""
    scale = 1.0 / math.sqrt(head_dim)
    if cfg.rope_variant == "yarn":
        m = yarn_mscale(cfg.rope_scaling.factor, cfg.rope_scaling.mscale_all_dim)
        scale = scale * m * m
    return scale


def _rotate(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotate pairs (even, odd) of the last dim. x (..., d), cos/sin (..., d//2)."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape)


def apply_rope(
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cfg: ModelConfig,
    rope_dim: int | None = None,
) -> jnp.ndarray:
    """Apply the config's RoPE variant.

    x: (B, S, H, Dh); positions: (B, S) int32, or (B, S, 3) for M-RoPE.
    rope_dim: rotate only the first ``rope_dim`` dims (MLA decoupled rope /
    chatglm half-rope); None = variant default.
    """
    dh = x.shape[-1]
    if cfg.rope_variant == "half" and rope_dim is None:
        rope_dim = dh // 2
    rope_dim = rope_dim or dh

    if cfg.rope_variant == "mrope":
        # positions (B, S, 3): (t, h, w). Each section of the rotary dims
        # uses its own position stream (Qwen2-VL §3.1).
        sections = cfg.mrope_sections  # halves; sum == rope_dim // 2
        assert sum(sections) == rope_dim // 2, (sections, rope_dim)
        cos_parts, sin_parts = [], []
        off = 0
        for i, sec in enumerate(sections):
            inv = 1.0 / (10000.0 ** ((jnp.arange(off, off + sec, dtype=jnp.float32) * 2) / rope_dim))
            ang = positions[..., i].astype(jnp.float32)[..., None] * inv  # (B,S,sec)
            cos_parts.append(jnp.cos(ang))
            sin_parts.append(jnp.sin(ang))
            off += sec
        cos = jnp.concatenate(cos_parts, -1)[:, :, None, :]  # (B,S,1,rope_dim//2)
        sin = jnp.concatenate(sin_parts, -1)[:, :, None, :]
    else:
        scaling = cfg.rope_scaling if cfg.rope_variant == "yarn" else None
        cos, sin = _rope_cos_sin(positions, rope_dim, scaling=scaling)  # (B,S,rd//2)
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]

    rot = _rotate(x[..., :rope_dim], cos.astype(jnp.float32), sin.astype(jnp.float32))
    if rope_dim == dh:
        return rot.astype(x.dtype)
    return jnp.concatenate([rot.astype(x.dtype), x[..., rope_dim:]], axis=-1)


# ---------------------------------------------------------------------------
# attention — chunked online-softmax (train/prefill) and cached decode
# ---------------------------------------------------------------------------


def _expand_kv(k: jnp.ndarray, h: int) -> jnp.ndarray:
    """Repeat kv heads to the full q-head count.

    SPMD rationale: the flat projection output (B,T,Hkv*Dh) shards over
    `model` only when Hkv >= n_model; repeating to H (which IS >= n_model
    for every assigned arch on the 16-way model axis) lets the head dim
    carry the TP sharding through the attention einsums. Memory cost is
    bounded by the chunked contraction; FLOPs are identical.
    """
    hkv = k.shape[2]
    if hkv == h:
        return k
    k = jnp.repeat(k, h // hkv, axis=2)
    from repro.launch import context as ctx

    return ctx.constrain(k, "dp", None, "model", None)


def chunked_attention(
    q: jnp.ndarray,       # (B, S, H, Dq)
    k: jnp.ndarray,       # (B, T, Hkv, Dq)
    v: jnp.ndarray,       # (B, T, Hkv, Dv)
    q_positions: jnp.ndarray,   # (S,) absolute positions of queries
    kv_positions: jnp.ndarray,  # (T,)
    causal: bool = True,
    window: int = 0,      # >0: sliding window
    chunk: int = 1024,
    scale: float | None = None,
) -> jnp.ndarray:
    """Flash-style attention: scan over KV chunks with online softmax.

    Returns (B, S, H, Dv). This is the pure-jnp oracle; the Pallas kernel in
    repro.kernels.flash_attention is the TPU version of the same contraction.
    """
    b, s, h, dq = q.shape
    t, dv = k.shape[1], v.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(dq)
    k = _expand_kv(k, h)
    v = _expand_kv(v, h)

    chunk = min(chunk, t)
    n_chunks = -(-t // chunk)
    pad = n_chunks * chunk - t
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, (0, pad), constant_values=-(10 ** 9))
    kc = k.reshape(b, n_chunks, chunk, h, dq).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, h, dv).transpose(1, 0, 2, 3, 4)
    pc = kv_positions.reshape(n_chunks, chunk)

    neg = jnp.float32(-1e30)

    def step(carry, xs):
        m, l, acc = carry
        kj, vj, pj = xs  # (B,chunk,H,Dq), (B,chunk,H,Dv), (chunk,)
        sc = jnp.einsum("bshd,bchd->bhsc", q, kj, preferred_element_type=jnp.float32)
        sc = sc * scale
        mask = pj[None, :] <= q_positions[:, None] if causal else jnp.ones((s, kj.shape[1]), bool)
        mask = mask & (pj[None, :] >= 0)
        if window:
            mask = mask & (pj[None, :] > q_positions[:, None] - window)
        sc = jnp.where(mask[None, None], sc, neg)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhsc,bchd->bshd", p.astype(v.dtype), vj, preferred_element_type=jnp.float32)
        acc_new = acc * corr.transpose(0, 2, 1)[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, s), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)
    a0 = jnp.zeros((b, s, h, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def decode_attention(
    q: jnp.ndarray,        # (B, 1, H, Dq)
    k_cache: jnp.ndarray,  # (B, T, Hkv, Dq)
    v_cache: jnp.ndarray,  # (B, T, Hkv, Dv)
    kv_positions: jnp.ndarray,  # (T,) absolute positions; -1 = empty slot
    pos: jnp.ndarray,      # () current decode position
    window: int = 0,
    scale: float | None = None,
) -> jnp.ndarray:
    """Single-token cached attention. Returns (B, 1, H, Dv).

    The KV cache stays in its compact Hkv layout (sharded batch x seq);
    grouped einsum keeps the contraction over the seq shards so XLA lowers a
    partial-softmax + psum (flash-decode) schedule rather than gathering the
    cache.
    """
    b, _, h, dq = q.shape
    hkv, dv = k_cache.shape[2], v_cache.shape[-1]
    g = h // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(dq)
    qg = q.reshape(b, hkv, g, dq)
    sc = jnp.einsum("bhgd,bthd->bhgt", qg, k_cache, preferred_element_type=jnp.float32) * scale
    valid = (kv_positions >= 0) & (kv_positions <= pos)
    if window:
        valid = valid & (kv_positions > pos - window)
    sc = jnp.where(valid[None, None, None], sc, jnp.float32(-1e30))
    # two-pass softmax written max/sum-explicitly so seq-sharding reduces
    m = sc.max(axis=-1, keepdims=True)
    p = jnp.exp(sc - m)
    l = p.sum(axis=-1, keepdims=True)
    p = p / jnp.maximum(l, 1e-30)
    out = jnp.einsum("bhgt,bthd->bhgd", p.astype(v_cache.dtype), v_cache, preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block (init / train / prefill / decode)
# ---------------------------------------------------------------------------


def init_gqa(rng, cfg: ModelConfig):
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    sd = 0.02
    dt = jnp.dtype(cfg.dtype)
    return {
        "wq": (jax.random.normal(k1, (d, h * dh)) * sd).astype(dt),
        "wk": (jax.random.normal(k2, (d, hkv * dh)) * sd).astype(dt),
        "wv": (jax.random.normal(k3, (d, hkv * dh)) * sd).astype(dt),
        "wo": (jax.random.normal(k4, (h * dh, d)) * sd / math.sqrt(2 * cfg.n_layers)).astype(dt),
    }


def gqa_attention(p, x, positions, cfg: ModelConfig, *, cache=None, window=0, mode="train"):
    """mode: train | prefill | decode. Returns (out, new_cache)."""
    b, s, d = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    from repro.launch import context as ctx

    q = ctx.constrain((x @ p["wq"]).reshape(b, s, h, dh), "dp", None, "model", None)
    k = (x @ p["wk"]).reshape(b, s, hkv, dh)
    v = (x @ p["wv"]).reshape(b, s, hkv, dh)

    if cfg.rope_variant == "mrope":
        rope_pos = positions  # (B,S,3)
        lin_pos = positions[0, :, 0]  # text-linear positions for masking
    elif positions.ndim == 0:  # decode scalar
        rope_pos = jnp.full((b, 1), positions, jnp.int32)
        lin_pos = rope_pos[0]
    else:
        rope_pos = positions if positions.ndim == 2 else positions[None].repeat(b, 0)
        lin_pos = rope_pos[0]
    q = apply_rope(q, rope_pos, cfg)
    k = apply_rope(k, rope_pos, cfg)

    new_cache = None
    if mode == "train":
        out = chunked_attention(q, k, v, lin_pos, lin_pos, causal=True, window=window)
    elif mode == "prefill":
        out = chunked_attention(q, k, v, lin_pos, lin_pos, causal=True, window=window)
        if window:
            w = min(window, s)
            new_cache = {
                "k": k[:, -w:], "v": v[:, -w:], "kv_pos": lin_pos[-w:],
            }
        else:
            new_cache = {"k": k, "v": v, "kv_pos": lin_pos}
    else:  # decode: s == 1
        if cfg.rope_variant == "mrope":
            pos = positions[0, 0, 0].reshape(())
        else:
            pos = positions.reshape(()) if positions.ndim == 0 else lin_pos[0].reshape(())
        slot = (pos % cache["k"].shape[1]) if window else pos
        kc = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot.astype(jnp.int32), 0, 0))
        vc = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot.astype(jnp.int32), 0, 0))
        kv_pos = jax.lax.dynamic_update_slice(cache["kv_pos"], pos[None].astype(jnp.int32), (slot.astype(jnp.int32),))
        out = decode_attention(q, kc, vc, kv_pos, pos, window=window)
        new_cache = {"k": kc, "v": vc, "kv_pos": kv_pos}
    return out.reshape(b, s, h * dh) @ p["wo"], new_cache


def init_gqa_cache(cfg: ModelConfig, batch: int, seq: int, window: int = 0):
    hkv, dh = cfg.n_kv_heads, cfg.head_dim_
    t = min(window, seq) if window else seq
    dt = jnp.dtype(cfg.dtype)
    return {
        "k": jnp.zeros((batch, t, hkv, dh), dt),
        "v": jnp.zeros((batch, t, hkv, dh), dt),
        "kv_pos": jnp.full((t,), -1, jnp.int32),
    }


# ---------------------------------------------------------------------------
# MLA attention block (DeepSeek-V2) — compressed KV cache
# ---------------------------------------------------------------------------


def init_mla(rng, cfg: ModelConfig):
    d, h = cfg.d_model, cfg.n_heads
    r, rd, nd, vd = cfg.kv_lora_rank, cfg.qk_rope_dim, cfg.qk_nope_dim, cfg.v_head_dim
    k1, k2, k3, k4, k5, k6 = jax.random.split(rng, 6)
    sd = 0.02
    dt = jnp.dtype(cfg.dtype)
    return {
        "wq": (jax.random.normal(k1, (d, h * (nd + rd))) * sd).astype(dt),
        "wdkv": (jax.random.normal(k2, (d, r)) * sd).astype(dt),
        "wkr": (jax.random.normal(k3, (d, rd)) * sd).astype(dt),
        "kv_norm": jnp.ones((r,), dt),  # RMSNorm on the latent (kv_a_layernorm)
        "wuk": (jax.random.normal(k4, (r, h * nd)) * sd).astype(dt),
        "wuv": (jax.random.normal(k5, (r, h * vd)) * sd).astype(dt),
        "wo": (jax.random.normal(k6, (h * vd, d)) * sd / math.sqrt(2 * cfg.n_layers)).astype(dt),
    }


@jax.named_scope("mla.attn")
def mla_attention(p, x, positions, cfg: ModelConfig, *, cache=None, window=0, mode="train"):
    """Multi-head Latent Attention with decoupled RoPE (arXiv:2405.04434).

    The latent ``c_kv`` passes an RMSNorm (``kv_norm``, the published
    ``kv_a_layernorm``) before its up-projections. Cache stores the
    normalised COMPRESSED c_kv (B,T,r) + shared rope key (B,T,rd) — the MLA
    memory saving; decode re-expands k_nope/v from c_kv.
    """
    b, s, d = x.shape
    h = cfg.n_heads
    r, rd, nd, vd = cfg.kv_lora_rank, cfg.qk_rope_dim, cfg.qk_nope_dim, cfg.v_head_dim

    q = (x @ p["wq"]).reshape(b, s, h, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    c_kv = rms_norm(x @ p["wdkv"], p["kv_norm"], cfg.norm_eps)  # (B,S,r)
    k_rope = (x @ p["wkr"]).reshape(b, s, 1, rd)

    if positions.ndim == 0:  # decode scalar
        rope_pos = jnp.full((b, 1), positions, jnp.int32)
    elif positions.ndim == 2:
        rope_pos = positions
    else:
        rope_pos = positions[None].repeat(b, 0)
    lin_pos = rope_pos[0]
    q_rope = apply_rope(q_rope, rope_pos, cfg, rope_dim=rd)
    k_rope = apply_rope(k_rope, rope_pos, cfg, rope_dim=rd)

    def expand(c):  # c (B,T,r) -> k_nope (B,T,H,nd), v (B,T,H,vd)
        t = c.shape[1]
        kn = (c @ p["wuk"]).reshape(b, t, h, nd)
        vv = (c @ p["wuv"]).reshape(b, t, h, vd)
        return kn, vv

    scale = attention_scale(cfg, nd + rd)
    new_cache = None
    if mode in ("train", "prefill"):
        k_nope, v = expand(c_kv)
        k_full = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, s, h, rd))], -1)
        q_full = jnp.concatenate([q_nope, q_rope], -1)
        out = chunked_attention(q_full, k_full, v, lin_pos, lin_pos, causal=True, window=window, scale=scale)
        if mode == "prefill":
            if window:
                w = min(window, s)
                new_cache = {"c_kv": c_kv[:, -w:], "k_rope": k_rope[:, -w:, 0], "kv_pos": lin_pos[-w:]}
            else:
                new_cache = {"c_kv": c_kv, "k_rope": k_rope[:, :, 0], "kv_pos": lin_pos}
    else:
        pos = positions.reshape(())
        t_buf = cache["c_kv"].shape[1]
        slot = (pos % t_buf) if window else pos
        cc = jax.lax.dynamic_update_slice(cache["c_kv"], c_kv, (0, slot.astype(jnp.int32), 0))
        kr = jax.lax.dynamic_update_slice(cache["k_rope"], k_rope[:, :, 0], (0, slot.astype(jnp.int32), 0))
        kv_pos = jax.lax.dynamic_update_slice(cache["kv_pos"], pos[None].astype(jnp.int32), (slot.astype(jnp.int32),))
        import os as _os

        if _os.environ.get("REPRO_MLA_DECODE", "naive") == "absorbed":
            # §Perf: absorbed MLA decode (DeepSeek-V2 §2.1.2) — fold W_uk
            # into the query and W_uv into the output so attention runs
            # directly against the COMPRESSED cache: per-step FLOPs drop
            # from O(T·r·H·(nd+vd)) (re-expansion) to O(T·H·(r+rd)).
            wuk_r = p["wuk"].reshape(r, h, nd)
            wuv_r = p["wuv"].reshape(r, h, vd)
            q_eff = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], wuk_r)       # (B,H,r)
            sc = (
                jnp.einsum("bhr,btr->bht", q_eff.astype(jnp.float32), cc.astype(jnp.float32))
                + jnp.einsum("bhd,btd->bht", q_rope[:, 0].astype(jnp.float32), kr.astype(jnp.float32))
            ) * scale
            valid = (kv_pos >= 0) & (kv_pos <= pos)
            if window:
                valid = valid & (kv_pos > pos - window)
            sc = jnp.where(valid[None, None], sc, jnp.float32(-1e30))
            pr = jax.nn.softmax(sc, axis=-1)
            out_lat = jnp.einsum("bht,btr->bhr", pr.astype(cc.dtype), cc)  # (B,H,r)
            out = jnp.einsum("bhr,rhv->bhv", out_lat, wuv_r)[:, None]      # (B,1,H,vd)
        else:
            k_nope, v = expand(cc)   # faithful MLA decode: re-expand from latent
            k_full = jnp.concatenate([k_nope, jnp.broadcast_to(kr[:, :, None, :], k_nope.shape[:3] + (rd,))], -1)
            q_full = jnp.concatenate([q_nope, q_rope], -1)
            out = decode_attention(q_full, k_full, v, kv_pos, pos, window=window, scale=scale)
        new_cache = {"c_kv": cc, "k_rope": kr, "kv_pos": kv_pos}
    return out.reshape(b, s, h * vd) @ p["wo"], new_cache


def init_mla_cache(cfg: ModelConfig, batch: int, seq: int, window: int = 0):
    t = min(window, seq) if window else seq
    dt = jnp.dtype(cfg.dtype)
    return {
        "c_kv": jnp.zeros((batch, t, cfg.kv_lora_rank), dt),
        "k_rope": jnp.zeros((batch, t, cfg.qk_rope_dim), dt),
        "kv_pos": jnp.full((t,), -1, jnp.int32),
    }


# ---------------------------------------------------------------------------
# FFN: SwiGLU (dense) and MoE (scatter/gather dispatch)
# ---------------------------------------------------------------------------


def init_swiglu(rng, cfg: ModelConfig, d_ff: int | None = None):
    d = cfg.d_model
    dff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(rng, 3)
    sd = 0.02
    dt = jnp.dtype(cfg.dtype)
    return {
        "wg": (jax.random.normal(k1, (d, dff)) * sd).astype(dt),
        "wu": (jax.random.normal(k2, (d, dff)) * sd).astype(dt),
        "wd": (jax.random.normal(k3, (dff, d)) * sd / math.sqrt(2 * cfg.n_layers)).astype(dt),
    }


def swiglu(p, x):
    return (silu(x @ p["wg"]) * (x @ p["wu"])) @ p["wd"]


def init_moe(rng, cfg: ModelConfig, n_held: int | None = None):
    """An MoE layer: the router over all ``cfg.n_experts``, the routed
    experts (``n_held`` of them where given: a chip's share of an
    expert-parallel group, ``moe_apply_held``), and the shared experts."""
    d, e = cfg.d_model, cfg.n_experts
    dff = cfg.d_ff_expert or cfg.d_ff
    n = e if n_held is None else n_held
    k1, k2, k3, k4, k5 = jax.random.split(rng, 5)
    sd = 0.02
    dt = jnp.dtype(cfg.dtype)
    p = {
        "router": (jax.random.normal(k1, (d, e)) * sd).astype(jnp.float32),
        "wg": (jax.random.normal(k2, (n, d, dff)) * sd).astype(dt),
        "wu": (jax.random.normal(k3, (n, d, dff)) * sd).astype(dt),
        "wd": (jax.random.normal(k4, (n, dff, d)) * sd / math.sqrt(2 * cfg.n_layers)).astype(dt),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_swiglu(k5, cfg, d_ff=dff * cfg.n_shared_experts)
    return p


def moe_apply(p, x, cfg: ModelConfig):
    """Token-choice top-k MoE. Dispatches to the expert-parallel shard_map
    implementation when lowering under a mesh context, else the local
    scatter path. Returns (y, aux_loss)."""
    from repro.launch import context as ctx

    mesh = ctx.get_mesh()
    if (
        ctx.moe_ep_enabled()
        and mesh is not None
        and cfg.n_experts % mesh.shape["model"] == 0
    ):
        return moe_apply_ep(p, x, cfg)
    return moe_apply_local(p, x, cfg)


def moe_route(router, xf, cfg: ModelConfig):
    """Token-choice routing over all ``cfg.n_experts``: float32 softmax
    scores, greedy top-k. Returns ``(probs (N,E), gate (N,k), idx (N,k))``;
    the gates are renormalised to sum 1 where ``cfg.norm_topk_prob``."""
    logits = xf.astype(jnp.float32) @ router                 # (N,E) fp32 router
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, cfg.top_k)              # (N,k)
    if cfg.norm_topk_prob:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    return probs, gate, idx


def moe_apply_local(p, x, cfg: ModelConfig):
    """Token-choice top-k MoE with per-expert capacity (scatter dispatch).

    Compiled FLOPs ~ N * top_k * ffn (active experts only) — the dispatch is
    scatter/gather (O(N*k*D) data movement), NOT the O(N*E*C*D) one-hot
    einsum of GShard, which would dominate the roofline.

    Returns (y, aux_loss).
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    n = b * s
    xf = x.reshape(n, d)

    probs, gate, idx = moe_route(p["router"], xf, cfg)        # (N,E), (N,k), (N,k)

    # load-balance aux loss (Switch-style): E * sum_e f_e * P_e
    me = jnp.mean(probs, axis=0)                              # (E,)
    one_top = jax.nn.one_hot(idx, e, dtype=jnp.float32)       # (N,k,E)
    fe = jnp.mean(one_top.sum(1), axis=0) / k                 # frac tokens -> e
    aux = e * jnp.sum(fe * me)

    cap = max(1, int(math.ceil(n * k * cfg.capacity_factor / e)))

    fidx = idx.reshape(-1)                                    # (N*k,)
    # position of each routed token inside its expert's queue:
    # pos[i] = (# of j <= i with expert[j] == expert[i]) - 1
    onehot = jax.nn.one_hot(fidx, e, dtype=jnp.int32)         # (N*k,E)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0), fidx[:, None], axis=1)[:, 0] - 1
    keep = pos < cap
    safe_pos = jnp.minimum(pos, cap - 1)

    x_rep = jnp.repeat(xf, k, axis=0)                         # (N*k, D)
    contrib = jnp.where(keep[:, None], x_rep, 0).astype(x.dtype)
    buf = jnp.zeros((e, cap, d), x.dtype).at[fidx, safe_pos].add(contrib)

    def expert_ffn(w_g, w_u, w_d, h):
        return (silu(h @ w_g) * (h @ w_u)) @ w_d

    expert_out = jax.vmap(expert_ffn)(p["wg"], p["wu"], p["wd"], buf)  # (E,cap,D)

    gathered = expert_out[fidx, safe_pos]                     # (N*k, D)
    gflat = gate.reshape(-1)
    y = (gathered * (gflat * keep.astype(jnp.float32))[:, None].astype(x.dtype))
    y = y.reshape(n, k, d).sum(axis=1)
    if cfg.routed_scaling_factor != 1.0:
        y = y * cfg.routed_scaling_factor

    if cfg.n_shared_experts:
        y = y + swiglu(p["shared"], xf)
    return y.reshape(b, s, d), aux


def _one_at_a_time(fn):
    """``fn`` under ``jax.vmap`` as a loop over the batch: the TPU's grouped
    matmul takes no batch dimension, and the federated phases vmap a
    client's step over the clients a device runs."""
    fn = jax.custom_batching.custom_vmap(fn)

    @fn.def_vmap
    def rule(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, in_batched)]
        out = jax.lax.map(lambda a: fn(*a), tuple(args))
        return out, jax.tree.map(lambda _: True, out)

    return fn


@_one_at_a_time
def _ragged(x, w, sizes):
    return jax.lax.ragged_dot(x, w, sizes)


@_one_at_a_time
def _ragged_vjp(x, w, sizes, g):
    return jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, sizes), x, w)[1](g)


@jax.custom_vjp
def _grouped(x, w, sizes):
    """``jax.lax.ragged_dot``: the rows of ``x`` grouped by ``sizes``, group
    e times ``w[e]``; differentiated and batched by the loops above."""
    return _ragged(x, w, sizes)


def _grouped_fwd(x, w, sizes):
    return _ragged(x, w, sizes), (x, w, sizes)


def _grouped_bwd(res, g):
    x, w, sizes = res
    return (*_ragged_vjp(x, w, sizes, g), None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def _held_matmul(x, w, sizes, valid):
    """The grouped matmul of the rows of ``x`` with the held experts' ``w``,
    and rows past the last group zeroed: the TPU leaves them unwritten, in
    the forward and in the transposes alike, so every output of a grouped
    matmul passes through ``valid``."""
    return jnp.where(valid[:, None], _grouped(x, w, sizes), 0.0)


def moe_apply_held(p, x, cfg: ModelConfig, first_expert: int = 0):
    """Dropless token-choice MoE over the experts this chip holds.

    Routes every token over all ``cfg.n_experts`` (``moe_route``), and
    computes only the part of the result that the held experts
    ``[first_expert, first_expert + n_held)`` give (``p["wg"]`` etc. hold
    ``n_held`` experts): a (token, expert) pair routed to an absent expert
    contributes nothing, as on a chip of an expert-parallel group before
    its exchange. The pairs routed to held experts are sorted by expert
    into a buffer with room for every pair (``N * top_k`` rows, so none is
    ever dropped), each held expert runs on its own rows only
    (``jax.lax.ragged_dot``), and each token sums its rows weighted by its
    gates. The shared experts run on every token. Returns ``(y, counts)``:
    ``held`` (pairs routed to held experts) and ``max_load`` (the largest
    held expert's pairs over the held experts' mean; 0 where none is
    routed there).
    """
    b, s, d = x.shape
    n_held = p["wg"].shape[0]
    n = b * s
    k = cfg.top_k
    xf = x.reshape(n, d)
    with jax.named_scope("moe.route"):
        _, gate, idx = moe_route(p["router"], xf, cfg)
    with jax.named_scope("moe.dispatch"):
        local = (idx - first_expert).reshape(-1)                      # (N*k,)
        expert = jnp.where((local >= 0) & (local < n_held), local, n_held)
        order = jnp.argsort(expert, stable=True)  # held pairs by expert, absent last
        loads = jnp.sum(expert[:, None] == jnp.arange(n_held), axis=0)   # (H,)
        valid = jnp.arange(n * k) < loads.sum()
        rows = jnp.where(valid[:, None], jnp.take(xf, order // k, axis=0), 0.0)
    with jax.named_scope("moe.experts"):
        hg = _held_matmul(rows, p["wg"], loads, valid)
        hu = _held_matmul(rows, p["wu"], loads, valid)
        ys = _held_matmul(silu(hg) * hu, p["wd"], loads, valid)         # (N*k, D)
    with jax.named_scope("moe.combine"):
        slot = jnp.zeros((n * k,), jnp.int32).at[order].set(jnp.arange(n * k, dtype=jnp.int32))
        pairs = jnp.take(ys, slot, axis=0).reshape(n, k, d)  # absent pairs read zeroed rows
        y = jnp.einsum("nk,nkd->nd", gate.astype(ys.dtype), pairs)
        if cfg.routed_scaling_factor != 1.0:
            y = y * cfg.routed_scaling_factor
    if cfg.n_shared_experts:
        with jax.named_scope("moe.shared"):
            y = y + swiglu(p["shared"], xf)
    held = loads.sum()
    mean = held.astype(jnp.float32) / n_held
    max_load = jnp.where(held > 0, loads.max() / jnp.maximum(mean, 1e-9), 0.0)
    return y.reshape(b, s, d).astype(x.dtype), {"held": held, "max_load": max_load}


def moe_apply_ep(p, x, cfg: ModelConfig):
    """Expert-parallel MoE via shard_map (the TPU-native EP layout).

    Experts are sharded over `model`; tokens are replicated across the model
    axis (their hidden dim is gathered at entry). Each model shard routes,
    scatters and runs ONLY its local experts on a local VMEM-friendly
    capacity buffer — no cross-device scatter — then the partial outputs are
    psum-combined over `model` (the EP combine collective).

    Per-layer collective cost: one psum of (B_loc*S, D) — identical to a
    Megatron FFN all-reduce; the dispatch itself is local.
    """
    from jax.sharding import PartitionSpec as P

    from repro.launch import context as ctx

    mesh = ctx.get_mesh()
    dp = ctx.dp_spec()
    n_mp = mesh.shape["model"]
    e, k = cfg.n_experts, cfg.top_k
    e_local = e // n_mp
    b, s, d = x.shape
    n = b * s
    n_dp = 1
    for a in ctx.dp_axes():
        n_dp *= mesh.shape[a]
    if b % n_dp != 0:
        dp = None  # decode batch=1: tokens replicated over the data axes

    def local_fn(router, wg, wu, wd, xl):
        bl, sl, _ = xl.shape
        nl = bl * sl
        xf = xl.reshape(nl, d)
        probs, gate, idx = moe_route(router, xf, cfg)

        me = jnp.mean(probs, axis=0)
        one_top = jax.nn.one_hot(idx, e, dtype=jnp.float32)
        fe = jnp.mean(one_top.sum(1), axis=0) / k
        aux = e * jnp.sum(fe * me)

        my_first = jax.lax.axis_index("model") * e_local
        rel = idx - my_first                      # (nl, k)
        mine = (rel >= 0) & (rel < e_local)
        cap = max(1, int(math.ceil(nl * k * cfg.capacity_factor / e)))

        flat_rel = jnp.where(mine, rel, e_local).reshape(-1)   # (nl*k,) dump row = e_local
        onehot = jax.nn.one_hot(flat_rel, e_local + 1, dtype=jnp.int32)
        pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0), flat_rel[:, None], axis=1)[:, 0] - 1
        keep = (pos < cap) & (flat_rel < e_local)
        safe_e = jnp.minimum(flat_rel, e_local - 1)
        safe_pos = jnp.clip(pos, 0, cap - 1)

        x_rep = jnp.repeat(xf, k, axis=0)
        contrib = jnp.where(keep[:, None], x_rep, 0).astype(x.dtype)
        buf = jnp.zeros((e_local, cap, d), x.dtype).at[safe_e, safe_pos].add(contrib)

        def expert_ffn(w_g, w_u, w_d, h):
            return (silu(h @ w_g) * (h @ w_u)) @ w_d

        expert_out = jax.vmap(expert_ffn)(wg, wu, wd, buf)      # (E_loc, cap, D)
        gathered = expert_out[safe_e, safe_pos]                 # (nl*k, D)
        gflat = gate.reshape(-1) * keep.astype(jnp.float32)
        y_part = (gathered.astype(jnp.float32) * gflat[:, None]).reshape(nl, k, d).sum(axis=1)
        y = jax.lax.psum(y_part, "model")
        if cfg.routed_scaling_factor != 1.0:
            y = y * cfg.routed_scaling_factor
        return y.reshape(bl, sl, d).astype(x.dtype), aux

    y, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(), P("model", None, None), P("model", None, None), P("model", None, None), P(dp, None, None)),
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )(p["router"], p["wg"], p["wu"], p["wd"], x)

    if cfg.n_shared_experts:
        y = y + swiglu(p["shared"], x.reshape(n, d)).reshape(b, s, d)
    return y, aux


# ---------------------------------------------------------------------------
# Mamba-1 block (falcon-mamba, jamba)
# ---------------------------------------------------------------------------


def init_mamba(rng, cfg: ModelConfig):
    d, di, ds, dtr, dc = cfg.d_model, cfg.d_inner, cfg.d_state, cfg.dt_rank_, cfg.d_conv
    keys = jax.random.split(rng, 6)
    sd = 0.02
    dt = jnp.dtype(cfg.dtype)
    # S4D-real A init: A[n] = n+1 per state dim
    a_init = jnp.broadcast_to(jnp.arange(1, ds + 1, dtype=jnp.float32), (di, ds))
    return {
        "in_proj": (jax.random.normal(keys[0], (d, 2 * di)) * sd).astype(dt),
        "conv_w": (jax.random.normal(keys[1], (dc, di)) * sd).astype(dt),
        "conv_b": jnp.zeros((di,), dt),
        "x_proj": (jax.random.normal(keys[2], (di, dtr + 2 * ds)) * sd).astype(dt),
        "dt_proj": (jax.random.normal(keys[3], (dtr, di)) * sd).astype(dt),
        "dt_bias": jnp.full((di,), -4.6, jnp.float32),  # softplus^-1(0.01)
        "A_log": jnp.log(a_init),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": (jax.random.normal(keys[4], (di, d)) * sd / math.sqrt(2 * cfg.n_layers)).astype(dt),
    }


def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv along seq. x (B,S,di), w (dc,di).

    state (B, dc-1, di) holds the trailing context (decode); returns
    (y, new_state)."""
    dc = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (dc - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i : i + x.shape[1]] * w[i] for i in range(dc))
    new_state = xp[:, -(dc - 1):] if dc > 1 else None
    return y + b, new_state


def mamba_block(p, x, cfg: ModelConfig, *, cache=None, mode="train"):
    """Selective-scan SSM (Mamba-1). Returns (out, new_cache).

    train/prefill: lax.scan over the sequence (the Pallas ssm_scan kernel is
    the TPU-optimised chunked equivalent). decode: O(1) state update.
    """
    b, s, d = x.shape
    di, ds = cfg.d_inner, cfg.d_state
    dtr = cfg.dt_rank_

    u = x @ p["in_proj"]                      # (B,S,2di)
    xs, z = u[..., :di], u[..., di:]

    conv_state = cache["conv"] if cache is not None else None
    xs, new_conv = _causal_conv(xs, p["conv_w"], p["conv_b"], conv_state)
    xs = silu(xs)

    xdb = xs @ p["x_proj"]                    # (B,S,dtr+2ds)
    dt_raw, bmat, cmat = jnp.split(xdb, [dtr, dtr + ds], axis=-1)
    # dt matmul in bf16 (fp32 here materialises a full (B,S,di) fp32
    # activation + its gradient — §Perf hillclimb-1); softplus + bias in fp32
    dt = jax.nn.softplus((dt_raw @ p["dt_proj"]).astype(jnp.float32) + p["dt_bias"])  # (B,S,di)
    a = -jnp.exp(p["A_log"])                  # (di, ds)

    # §Perf hillclimb-1: stream scan inputs in bf16 (dt included — standard
    # for Mamba) and upcast INSIDE the step, halving the scan's HBM input
    # traffic; the recurrence itself stays fp32 (h, da).
    # REPRO_MAMBA_SCAN_DTYPE=fp32 restores the baseline for A/B measurement.
    import os as _os

    _scan_dt = jnp.float32 if _os.environ.get("REPRO_MAMBA_SCAN_DTYPE") == "fp32" else jnp.bfloat16
    dt = dt.astype(_scan_dt)
    bmat = bmat.astype(_scan_dt)
    cmat = cmat.astype(_scan_dt)
    xs32 = xs.astype(_scan_dt)

    h0 = cache["ssm"] if cache is not None else jnp.zeros((b, di, ds), jnp.float32)

    if mode == "decode":  # s == 1: single update
        dt1, b1, c1, x1 = dt[:, 0], bmat[:, 0], cmat[:, 0], xs32[:, 0]
        dt1, b1, c1, x1 = (t.astype(jnp.float32) for t in (dt1, b1, c1, x1))
        da = jnp.exp(dt1[..., None] * a[None])              # (B,di,ds)
        h = da * h0 + dt1[..., None] * b1[:, None, :] * x1[..., None]
        y = (h * c1[:, None, :]).sum(-1) + p["D"] * x1      # (B,di)
        y = y[:, None, :]
        new_cache = {"conv": new_conv, "ssm": h}
    elif mode == "train" and _os.environ.get("REPRO_MAMBA_VJP", "custom") == "custom":
        # §Perf hillclimb-1 (main lever): custom-VJP selective scan with
        # chunked recomputation — autodiff of lax.scan stores the full
        # (S, B, di, ds) state trajectory; this stores only chunk-boundary
        # states (128x less) and recomputes within chunks in the backward.
        from repro.launch import context as ctx
        from repro.models.ssm_vjp import selective_scan

        dtc = ctx.constrain(dt.astype(jnp.float32), "dp", None, "model")
        xc = ctx.constrain(xs32.astype(jnp.float32), "dp", None, "model")
        y, _ = selective_scan(dtc, a, bmat.astype(jnp.float32), cmat.astype(jnp.float32), xc, p["D"])
        new_cache = None
    else:
        def step(h, inp):
            dt_t, b_t, c_t, x_t = inp                        # (B,di),(B,ds),(B,ds),(B,di)
            dt_t = dt_t.astype(jnp.float32)
            b_t = b_t.astype(jnp.float32)
            c_t = c_t.astype(jnp.float32)
            x_t = x_t.astype(jnp.float32)
            da = jnp.exp(dt_t[..., None] * a[None])
            h = da * h + dt_t[..., None] * b_t[:, None, :] * x_t[..., None]
            y_t = (h * c_t[:, None, :]).sum(-1) + p["D"] * x_t
            return h, y_t

        # Chunked double scan with inner remat: backward recomputes the
        # state trajectory chunk-by-chunk instead of storing all S carries
        # (h is di*ds = 16x the activation width — storing it for 4k+ steps
        # is 100s of GiB; this is Mamba's standard recompute trick).
        chunk = min(128, s)
        pad = (-s) % chunk
        inps = (dt, bmat, cmat, xs32)
        if pad:
            # dt=0 padding: exp(0)=1 and dB=0 leave the state unchanged
            inps = jax.tree.map(lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0))), inps)
        nc = (s + pad) // chunk
        inps_c = jax.tree.map(
            lambda t: t.reshape(b, nc, chunk, -1).transpose(1, 2, 0, 3), inps
        )  # (nc, chunk, B, d)

        # §Perf: without explicit constraints XLA replicates the scan over
        # the data axis (16x compute/memory). Pin batch->dp and di->model on
        # every scan operand and the carried state.
        from repro.launch import context as ctx

        inps_c = tuple(
            ctx.constrain(t, None, None, "dp", "model" if t.shape[-1] == di else None)
            for t in inps_c
        )
        h0 = ctx.constrain(h0, "dp", "model", None)

        @jax.checkpoint
        def inner(h, xs):
            return jax.lax.scan(step, h, xs)

        h, ys = jax.lax.scan(inner, h0, inps_c)              # ys (nc, chunk, B, di)
        y = ys.transpose(2, 0, 1, 3).reshape(b, s + pad, di)[:, :s]
        new_cache = {"conv": new_conv, "ssm": h} if mode == "prefill" else None

    out = (y.astype(x.dtype) * silu(z)) @ p["out_proj"]
    return out, new_cache


def init_mamba_cache(cfg: ModelConfig, batch: int):
    return {
        "conv": jnp.zeros((batch, cfg.d_conv - 1, cfg.d_inner), jnp.dtype(cfg.dtype)),
        "ssm": jnp.zeros((batch, cfg.d_inner, cfg.d_state), jnp.float32),
    }
