"""Shared functional layers for every model family.

Everything is pure-functional: ``init_*`` returns a params pytree (dict of
jnp arrays), ``apply``-style functions take ``(params, inputs, ...)``.
dtype policy: params in ``param_dtype`` (default float32 for CPU numerics,
bfloat16 in production configs), activations in ``dtype``.

KV caches are plain dicts ``{"k": (B,S,H,Dh), "v": ..., "pos": int32}``;
``decode_*`` functions append one token at ``pos`` via dynamic updates.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.runtime import scopes

Params = dict
Array = jax.Array


# --------------------------------------------------------------------------
# Initializers / norms
# --------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int, dtype=jnp.float32) -> Array:
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim)) * scale).astype(dtype)


def rms_norm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(dtype)


def layer_norm(x: Array, scale: Array, bias: Array, eps: float = 1e-5) -> Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


# --------------------------------------------------------------------------
# Rotary position embeddings
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: Array, positions: Array, theta: float = 10000.0) -> Array:
    """x: (..., S, H, Dh); positions: broadcastable to (..., S)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)                       # (Dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    cos = jnp.cos(angles)[..., None, :]                        # (..., S, 1, Dh/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Attention (GQA / MQA / MHA, causal + sliding window), dense reference.
# --------------------------------------------------------------------------

def _attn_mask(q_len: int, kv_len: int, *, causal: bool, window: int | None,
               q_offset: Array | int = 0) -> Array:
    """(q_len, kv_len) boolean mask. q_offset = absolute pos of query row 0."""
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    k_pos = jnp.arange(kv_len)[None, :]
    mask = jnp.ones((q_len, kv_len), dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    return mask


@jax.named_scope(scopes.ATTENTION)
def attention(q: Array, k: Array, v: Array, *, causal: bool = True,
              window: int | None = None, q_offset: Array | int = 0,
              kv_valid_len: Array | None = None) -> Array:
    """Grouped-query attention. q: (B,S,Hq,Dh), k/v: (B,T,Hkv,Dh)."""
    B, S, Hq, Dh = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    groups = Hq // Hkv
    qg = q.reshape(B, S, Hkv, groups, Dh)
    logits = jnp.einsum("bshgd,bthd->bhgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32))
    logits *= 1.0 / math.sqrt(Dh)
    mask = _attn_mask(S, T, causal=causal, window=window, q_offset=q_offset)
    if kv_valid_len is not None:
        mask = mask & (jnp.arange(T)[None, :] < kv_valid_len)
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", probs, v.astype(jnp.float32))
    return out.reshape(B, S, Hq, v.shape[-1]).astype(q.dtype)


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    window: int | None = None       # sliding-window size (None = full)
    causal: bool = True
    qk_norm: bool = False           # Qwen3-style per-head q/k RMSNorm


def init_attention(key, cfg: AttnConfig, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * cfg.head_dim, dtype),
        "wk": dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * cfg.head_dim, dtype),
        "wv": dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * cfg.head_dim, dtype),
        "wo": dense_init(ks[3], cfg.n_heads * cfg.head_dim, cfg.d_model, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.head_dim,), dtype)
        p["k_norm"] = jnp.ones((cfg.head_dim,), dtype)
    return p


def apply_attention(p: Params, x: Array, cfg: AttnConfig, *,
                    positions: Array | None = None,
                    cache: Params | None = None,
                    cross_kv: tuple[Array, Array] | None = None,
                    ) -> tuple[Array, Params | None]:
    """Self- or cross-attention.  With ``cache`` (decode), x is (B,1,D) and
    the cache is updated in place (functionally).  ``cross_kv`` supplies
    precomputed encoder K/V (whisper-style cross attention; no cache update).
    """
    B, S, _ = x.shape
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, Dh)
    if cross_kv is None:
        k = (x @ p["wk"]).reshape(B, S, Hkv, Dh)
        v = (x @ p["wv"]).reshape(B, S, Hkv, Dh)
    else:
        k, v = cross_kv
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        if cross_kv is None:
            k = rms_norm(k, p["k_norm"])
    if positions is None:
        positions = jnp.arange(S)[None, :]
    if cfg.rope_theta > 0 and cross_kv is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None and cross_kv is None:
        pos = cache["pos"]
        k_all = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), pos, axis=1)
        v_all = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), pos, axis=1)
        new_cache = {"k": k_all, "v": v_all, "pos": pos + S}
        out = attention(q, k_all, v_all, causal=cfg.causal, window=cfg.window,
                        q_offset=pos, kv_valid_len=pos + S)
    else:
        out = attention(q, k, v, causal=cfg.causal and cross_kv is None,
                        window=cfg.window)
    out = out.reshape(B, S, H * Dh) @ p["wo"]
    return out, new_cache


def init_kv_cache(batch: int, max_len: int, cfg: AttnConfig, dtype=jnp.float32) -> Params:
    shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "pos": jnp.zeros((), jnp.int32)}


# --------------------------------------------------------------------------
# MLA — DeepSeek-V3 multi-head latent attention
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MLAConfig:
    d_model: int
    n_heads: int
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0


def init_mla(key, cfg: MLAConfig, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 8)
    H = cfg.n_heads
    return {
        "wq_a": dense_init(ks[0], cfg.d_model, cfg.q_lora_rank, dtype),
        "q_norm": jnp.ones((cfg.q_lora_rank,), dtype),
        "wq_b": dense_init(ks[1], cfg.q_lora_rank,
                           H * (cfg.qk_nope_dim + cfg.qk_rope_dim), dtype),
        "wkv_a": dense_init(ks[2], cfg.d_model,
                            cfg.kv_lora_rank + cfg.qk_rope_dim, dtype),
        "kv_norm": jnp.ones((cfg.kv_lora_rank,), dtype),
        "wkv_b": dense_init(ks[3], cfg.kv_lora_rank,
                            H * (cfg.qk_nope_dim + cfg.v_head_dim), dtype),
        "wo": dense_init(ks[4], H * cfg.v_head_dim, cfg.d_model, dtype),
    }


def apply_mla(p: Params, x: Array, cfg: MLAConfig, *,
              positions: Array | None = None,
              cache: Params | None = None) -> tuple[Array, Params | None]:
    """MLA with a *compressed* KV cache (kv_lora + k_rope per token)."""
    B, S, _ = x.shape
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    if positions is None:
        positions = jnp.arange(S)[None, :]

    q = rms_norm(x @ p["wq_a"], p["q_norm"]) @ p["wq_b"]
    q = q.reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv_a = x @ p["wkv_a"]                       # (B,S, r + dr)
    kv_latent = rms_norm(kv_a[..., :cfg.kv_lora_rank], p["kv_norm"])
    k_rope = apply_rope(kv_a[..., None, cfg.kv_lora_rank:], positions,
                        cfg.rope_theta)          # (B,S,1,dr) shared across heads

    q_offset: Array | int = 0
    kv_valid: Array | None = None
    new_cache = None
    if cache is not None:
        pos = cache["pos"]
        kv_latent = jax.lax.dynamic_update_slice_in_dim(
            cache["kv"], kv_latent.astype(cache["kv"].dtype), pos, axis=1)
        k_rope = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), pos, axis=1)
        new_cache = {"kv": kv_latent, "k_rope": k_rope, "pos": pos + S}
        q_offset, kv_valid = pos, pos + S

    # Decompress latent -> per-head K_nope and V (einsum keeps it fused).
    kv = kv_latent @ p["wkv_b"]                 # (B,T,H*(dn+dv))
    T = kv.shape[1]
    kv = kv.reshape(B, T, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, T, H, dr))], -1)
    qq = jnp.concatenate([q_nope, q_rope], -1)
    out = attention(qq, k, v, causal=True, q_offset=q_offset,
                    kv_valid_len=kv_valid)
    out = out.reshape(B, S, H * dv) @ p["wo"]
    return out, new_cache


def init_mla_cache(batch: int, max_len: int, cfg: MLAConfig, dtype=jnp.float32) -> Params:
    return {
        "kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, 1, cfg.qk_rope_dim), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


# --------------------------------------------------------------------------
# FFN: SwiGLU and MoE
# --------------------------------------------------------------------------

def init_swiglu(key, d_model: int, d_ff: int, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], d_model, d_ff, dtype),
        "w_up": dense_init(ks[1], d_model, d_ff, dtype),
        "w_down": dense_init(ks[2], d_ff, d_model, dtype),
    }


def apply_swiglu(p: Params, x: Array) -> Array:
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def init_gelu_mlp(key, d_model: int, d_ff: int, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 2)
    return {
        "w_up": dense_init(ks[0], d_model, d_ff, dtype),
        "b_up": jnp.zeros((d_ff,), dtype),
        "w_down": dense_init(ks[1], d_ff, d_model, dtype),
        "b_down": jnp.zeros((d_model,), dtype),
    }


@jax.named_scope(scopes.MLP)
def apply_gelu_mlp(p: Params, x: Array) -> Array:
    return jax.nn.gelu(x @ p["w_up"] + p["b_up"]) @ p["w_down"] + p["b_down"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                  # expert intermediate size
    n_experts: int
    top_k: int
    n_shared: int = 0          # shared (always-on) experts
    shared_d_ff: int = 0       # their intermediate size (0 => d_ff)
    capacity_factor: float = 1.25
    router_dtype: Any = jnp.float32


def init_moe(key, cfg: MoEConfig, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 5)
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": dense_init(ks[0], d, E, jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (E, d, f)) * scale).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (E, d, f)) * scale).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (E, f, d)) / math.sqrt(f)).astype(dtype),
    }
    if cfg.n_shared:
        sf = cfg.shared_d_ff or cfg.d_ff
        p["shared"] = init_swiglu(ks[4], d, cfg.n_shared * sf, dtype)
    return p


def apply_moe(p: Params, x: Array, cfg: MoEConfig, *,
              dispatch: str = "onehot") -> tuple[Array, Array]:
    """Top-k MoE with capacity-based SPMD-safe dispatch.

    Returns (output, aux_loss).  ``dispatch``:
      - "onehot": GShard/MaxText-style one-hot dispatch/combine einsums.
        Cost of the dispatch einsums is O(T*E*C*d) which for fine-grained
        MoE (small d_ff, large top_k: qwen3/deepseek-v3) exceeds the expert
        FFN FLOPs by >10x — kept as the historical baseline.
      - "scatter": sort-based dispatch — argsort assignments by expert,
        scatter rows into the (E, C, d) buffer, grouped FFN, gather back.
        O(T*k*d) data movement, zero matmul overhead; the scalable default
        for the large MoE configs (see EXPERIMENTS.md §Perf).
      - "dense": every token through its selected experts via weight gather
        (exact FLOPs, memory-heavy; small models / decode only).
    """
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    logits = (xt.astype(cfg.router_dtype) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                      # (T,E)
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)               # (T,k)
    top_p = top_p / jnp.sum(top_p, -1, keepdims=True)            # renormalise

    # load-balancing aux loss (Switch-style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_i, cfg.n_experts), axis=1), axis=0)
    aux = cfg.n_experts * jnp.sum(me * ce) / cfg.top_k

    if dispatch == "dense":
        wg = p["w_gate"][top_i]                                  # (T,k,d,f)
        wu = p["w_up"][top_i]
        wd = p["w_down"][top_i]
        h = jax.nn.silu(jnp.einsum("td,tkdf->tkf", xt, wg))
        h = h * jnp.einsum("td,tkdf->tkf", xt, wu)
        y = jnp.einsum("tkf,tkfd,tk->td", h, wd, top_p)
    elif dispatch == "scatter":
        # Grouped sort-based dispatch, vmapped over batch rows so the sort
        # and scatters stay local to each data shard under GSPMD (a global
        # argsort would force an all-gather).  Capacity is per row.
        E = cfg.n_experts
        Tr = S                                                   # row tokens
        cap = max(1, int(math.ceil(Tr * cfg.top_k / E
                                   * cfg.capacity_factor)))
        top_i_r = top_i.reshape(B, S, cfg.top_k)
        top_p_r = top_p.reshape(B, S, cfg.top_k)
        x_r = x

        def row(xr, ir, pr):
            eid = ir.reshape(-1)                                 # (S*k,)
            gates = pr.reshape(-1)
            tok = jnp.repeat(jnp.arange(Tr), cfg.top_k)
            order = jnp.argsort(eid)
            eid_s, tok_s, gate_s = eid[order], tok[order], gates[order]
            counts = jnp.bincount(eid, length=E)
            starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                                      jnp.cumsum(counts)[:-1]])
            pos = jnp.arange(Tr * cfg.top_k) - starts[eid_s]
            keep = pos < cap
            slot = eid_s * cap + jnp.where(keep, pos, 0)
            buf = jnp.zeros((E * cap, d), xr.dtype)
            buf = buf.at[jnp.where(keep, slot, E * cap)].set(
                xr[tok_s], mode="drop")
            return buf.reshape(E, cap, d), (slot, keep, tok_s, gate_s)

        xe, (slot, keep, tok_s, gate_s) = jax.vmap(row)(
            x_r, top_i_r, top_p_r)                               # (B,E,cap,d)
        h = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, p["w_gate"]))
        h = h * jnp.einsum("becd,edf->becf", xe, p["w_up"])
        ye = jnp.einsum("becf,efd->becd", h, p["w_down"])
        ye = ye.reshape(B, E * cap, d)

        def combine(yer, slot_r, keep_r, tok_r, gate_r):
            rows = jnp.where(keep_r[:, None], yer[slot_r], 0.0) \
                * gate_r[:, None].astype(yer.dtype)
            return jnp.zeros((Tr, d), yer.dtype).at[tok_r].add(rows)

        y = jax.vmap(combine)(ye, slot, keep, tok_s, gate_s)     # (B,S,d)
        y = y.reshape(T, d)
    else:
        E = cfg.n_experts
        cap = max(1, int(math.ceil(T * cfg.top_k / E * cfg.capacity_factor)))
        # position of each (token, slot) within its expert
        onehot = jax.nn.one_hot(top_i, E, dtype=jnp.int32)       # (T,k,E)
        flat = onehot.reshape(T * cfg.top_k, E)
        pos_in_e = jnp.cumsum(flat, axis=0) * flat - 1            # (T*k,E)
        pos = jnp.max(pos_in_e, axis=-1).reshape(T, cfg.top_k)    # (T,k)
        keep = (pos < cap) & (pos >= 0)
        gate = jnp.where(keep, top_p, 0.0)
        # dispatch tensor (T, E, cap) one-hot
        d_onehot = (
            jax.nn.one_hot(top_i, E, dtype=x.dtype)[..., None]
            * jax.nn.one_hot(jnp.clip(pos, 0, cap - 1), cap, dtype=x.dtype)[..., None, :]
            * keep[..., None, None].astype(x.dtype)
        ).sum(axis=1)                                            # (T,E,cap)
        xe = jnp.einsum("tec,td->ecd", d_onehot, xt)             # (E,cap,d)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"]))
        h = h * jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
        ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"])          # (E,cap,d)
        combine = (
            jax.nn.one_hot(top_i, E, dtype=x.dtype)[..., None]
            * jax.nn.one_hot(jnp.clip(pos, 0, cap - 1), cap, dtype=x.dtype)[..., None, :]
            * (gate[..., None, None].astype(x.dtype))
        ).sum(axis=1)                                            # (T,E,cap)
        y = jnp.einsum("tec,ecd->td", combine, ye)

    if "shared" in p:
        y = y + apply_swiglu(p["shared"], xt)
    return y.reshape(B, S, d), aux
