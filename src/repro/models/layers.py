"""Model-zoo building blocks (pure-function JAX; params are nested dicts).

Covers every attention/FFN variant the 10 assigned architectures need:
  - RMSNorm / LayerNorm
  - RoPE and M-RoPE (Qwen2-VL §3: temporal/height/width sections)
  - GQA attention (chunked online-softmax path for long sequences — the
    XLA twin of kernels/flash_attention) with KV cache decode
  - MLA (DeepSeek-V2 §2.1: low-rank KV compression, decoupled RoPE keys)
  - SwiGLU and GELU MLPs
  - MoE with top-k routing, capacity-based scatter dispatch (GShard-style,
    TPU-friendly: no ragged ops), shared experts, aux load-balance loss

Dtype policy: params and activations in ``cfg.dtype`` (bf16 by default),
softmax/logsumexp accumulations in f32, RNG-free forward.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


# -- initializers -------------------------------------------------------------

def dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# -- norms --------------------------------------------------------------------

def rms_norm(x: Array, w: Array, eps: float = 1e-5) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(dt)


def layer_norm(x: Array, w: Array, b: Array, eps: float = 1e-5) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


# -- rotary embeddings ----------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def apply_rope(x: Array, positions: Array, theta: float = 1e4,
               mrope_sections: Optional[tuple] = None) -> Array:
    """x: [B, T, H, D]; positions: [B, T] or [3, B, T] for M-RoPE.

    M-RoPE (Qwen2-VL): the head_dim/2 frequency slots are split into
    (temporal, height, width) sections, each rotated by its own position id.
    """
    B, T, H, D = x.shape
    freqs = jnp.asarray(rope_freqs(D, theta))          # [D/2]
    if mrope_sections is None:
        ang = positions[..., None].astype(jnp.float32) * freqs  # [B,T,D/2]
    else:
        assert positions.ndim == 3, "M-RoPE needs [3, B, T] positions"
        secs = mrope_sections
        assert sum(secs) == D // 2
        parts = []
        off = 0
        for i, s in enumerate(secs):
            parts.append(positions[i][..., None].astype(jnp.float32) * freqs[off:off + s])
            off += s
        ang = jnp.concatenate(parts, axis=-1)          # [B,T,D/2]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# -- attention core -------------------------------------------------------------

def _repeat_kv(k: Array, n_rep: int) -> Array:
    if n_rep == 1:
        return k
    B, T, Hkv, D = k.shape
    return jnp.repeat(k, n_rep, axis=2)


def sdpa(q: Array, k: Array, v: Array, causal: bool, q_offset: int = 0,
         kv_len: Optional[Array] = None, chunk: int = 1024) -> Array:
    """Online-softmax attention, chunked over KV (XLA twin of the Pallas
    flash kernel — same blocking idea, lets 32k prefill compile without a
    T×T score buffer).

    q: [B, Tq, H, D]; k/v: [B, Tk, Hkv, D]. Returns [B, Tq, H, D].
    kv_len: optional [B] valid KV lengths (decode with ragged cache).
    """
    B, Tq, H, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]                       # MLA: v head dim may differ from k
    n_rep = H // Hkv
    scale = 1.0 / np.sqrt(D)
    nchunks = max(1, (Tk + chunk - 1) // chunk)
    pad = nchunks * chunk - Tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, nchunks, chunk, Hkv, D)
    vc = v.reshape(B, nchunks, chunk, Hkv, Dv)

    qs = q * jnp.asarray(scale, q.dtype)
    qpos = q_offset + jnp.arange(Tq)

    def body(carry, inp):
        m, l, acc = carry
        kc_i, vc_i, c = inp
        kc_r = jnp.repeat(kc_i, n_rep, axis=2)          # [B, chunk, H, D]
        vc_r = jnp.repeat(vc_i, n_rep, axis=2)
        # bf16 operands, f32 accumulation (MXU contract; halves traffic)
        s = jnp.einsum("bqhd,bkhd->bhqk", qs, kc_r,
                       preferred_element_type=jnp.float32)
        kpos = c * chunk + jnp.arange(chunk)
        mask = jnp.ones((Tq, chunk), bool)
        if causal:
            mask = mask & (kpos[None, :] <= qpos[:, None])
        mask = mask & (kpos[None, :] < Tk)
        if kv_len is not None:
            mask = mask[None] & (kpos[None, None, :] < kv_len[:, None, None])
            s = jnp.where(mask[:, None], s, -jnp.inf)
        else:
            s = jnp.where(mask[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(vc_r.dtype), vc_r,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Tq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Tq), jnp.float32)
    a0 = jnp.zeros((B, H, Tq, Dv), jnp.float32)
    kc_t = jnp.moveaxis(kc, 1, 0)
    vc_t = jnp.moveaxis(vc, 1, 0)
    # remat the chunk body: backward recomputes per-chunk scores instead of
    # saving [B,H,Tq,chunk] p-matrices per chunk (flash-style O(T) memory)
    body = jax.checkpoint(body)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (kc_t, vc_t, jnp.arange(nchunks)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)     # [B, Tq, H, D]


def sdpa_simple(q, k, v, causal, q_offset: int = 0, kv_len=None):
    """Plain attention for short sequences (and as an oracle in tests).

    Operands stay in their storage dtype (bf16 on TPU) with f32
    accumulation via preferred_element_type — matches the MXU contract and
    halves attention operand traffic (incl. the decode-path KV cache reads)
    vs pre-casting to f32 (§Perf-3 measurement)."""
    B, Tq, H, D = q.shape
    n_rep = H // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) / np.sqrt(D)
    Tk = k.shape[1]
    qpos = q_offset + jnp.arange(Tq)
    kpos = jnp.arange(Tk)
    mask = jnp.ones((Tq, Tk), bool)
    if causal:
        mask = mask & (kpos[None] <= qpos[:, None])
    if kv_len is not None:
        m2 = mask[None] & (kpos[None, None] < kv_len[:, None, None])
        s = jnp.where(m2[:, None], s, -jnp.inf)
    else:
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def decode_attention_sharded(q, k, v, q_offset, kv_len):
    """Decode attention with the KV cache kept sequence-sharded (shard_map).

    GSPMD insists on gathering the cache to match head-sharded projections
    (an S×Hkv×hd buffer per layer — 8.6 GB/step/device on grok decode);
    here the score/softmax/PV pipeline runs on each device's S-shard and
    the cross-shard combine is an online-softmax psum of [B,H,1] stats and
    [B,H,1,dv] partial outputs — KBs instead of GBs on the wire (§Perf-3).

    Falls back to sdpa_simple when no mesh policy is active.
    """
    from ..parallel import api as P

    pol = P.current_policy()
    if pol is None or not pol.kv_seq_axes:
        return sdpa_simple(q, k, v, causal=False, q_offset=q_offset,
                           kv_len=kv_len)
    mesh = pol.mesh
    kv_axes = tuple(pol.kv_seq_axes)
    b_axes = tuple(pol.batch_axes) if pol.batch_axes else ()
    # guard: S and B must divide their axes, and axes must be disjoint
    S_total = k.shape[1]
    import numpy as np_
    kv_size = int(np_.prod([mesh.shape[a] for a in kv_axes]))
    b_size = int(np_.prod([mesh.shape[a] for a in b_axes])) if b_axes else 1
    if (S_total % kv_size or q.shape[0] % b_size
            or set(kv_axes) & set(b_axes)):
        return sdpa_simple(q, k, v, causal=False, q_offset=q_offset,
                           kv_len=kv_len)
    S_local = S_total // kv_size
    scale = 1.0 / np.sqrt(q.shape[-1])

    def local(q_l, k_l, v_l, len_l):
        B, Tq, H, Dk = q_l.shape
        n_rep = H // k_l.shape[2]
        k_r = jnp.repeat(k_l, n_rep, axis=2)
        v_r = jnp.repeat(v_l, n_rep, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_l, k_r,
                       preferred_element_type=jnp.float32) * scale
        # global kv positions of this shard (major→minor over kv_axes)
        shard = jnp.zeros((), jnp.int32)
        for a in kv_axes:
            shard = shard * mesh.shape[a] + jax.lax.axis_index(a)
        pos = shard * S_local + jnp.arange(S_local)
        mask = pos[None, None, None, :] < len_l[:, None, None, None]
        s = jnp.where(mask, s, -jnp.inf)
        m_l = s.max(axis=-1)                                   # [B,H,Tq]
        m = jax.lax.pmax(m_l, kv_axes)
        m = jnp.maximum(m, -1e30)                              # all-masked guard
        p = jnp.exp(s - m[..., None])
        p = jnp.where(mask, p, 0.0)
        l = jax.lax.psum(p.sum(axis=-1), kv_axes)              # [B,H,Tq]
        o = jax.lax.psum(
            jnp.einsum("bhqk,bkhd->bhqd", p.astype(v_r.dtype), v_r,
                       preferred_element_type=jnp.float32), kv_axes)
        o = o / jnp.maximum(l[..., None], 1e-30)
        return jnp.moveaxis(o, 1, 2).astype(q_l.dtype)         # [B,Tq,H,dv]

    from jax.sharding import PartitionSpec as PSpec
    bspec = b_axes if b_axes else None
    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(PSpec(bspec, None, None, None),
                  PSpec(bspec, kv_axes, None, None),
                  PSpec(bspec, kv_axes, None, None),
                  PSpec(bspec)),
        out_specs=PSpec(bspec, None, None, None),
        check_vma=False,
    )(q, k, v, kv_len)
    return out


# -- GQA attention block --------------------------------------------------------

def gqa_init(key, cfg, dtype):
    D, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (D, H * hd), dtype),
        "wk": dense_init(ks[1], (D, Hkv * hd), dtype),
        "wv": dense_init(ks[2], (D, Hkv * hd), dtype),
        "wo": dense_init(ks[3], (H * hd, D), dtype),
    }


def gqa_apply(p, cfg, x: Array, positions: Array, cache=None, cache_index=None,
              causal: bool = True):
    """Returns (out, new_cache). cache = {'k','v'}: [B, S, Hkv, hd]."""
    B, T, D = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, T, H, hd)
    k = (x @ p["wk"]).reshape(B, T, Hkv, hd)
    v = (x @ p["wv"]).reshape(B, T, Hkv, hd)
    mrope = cfg.mrope_sections if getattr(cfg, "mrope", False) else None
    q = apply_rope(q, positions, cfg.rope_theta, mrope)
    k = apply_rope(k, positions, cfg.rope_theta, mrope)

    if cache is None:
        if T <= 2048:
            o = sdpa_simple(q, k, v, causal)
        else:
            o = sdpa(q, k, v, causal)
        new_cache = None
    else:
        from ..parallel import api as P
        q = P.shard_decode_head_replicated(q)
        k = P.shard_decode_head_replicated(k)
        v = P.shard_decode_head_replicated(v)
        ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, cache_index, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, cache_index, 0, 0))
        ck = P.shard_kv_cache(ck)
        cv = P.shard_kv_cache(cv)
        kv_len = jnp.full((B,), cache_index + T)
        # decode: sequence-sharded manual attention (no cache gather; §Perf-3)
        o = decode_attention_sharded(q, ck, cv, cache_index, kv_len)
        new_cache = {"k": ck, "v": cv}
    o = o.reshape(B, T, H * hd) @ p["wo"]
    return o, new_cache


# -- MLA (DeepSeek-V2) ----------------------------------------------------------

def mla_init(key, cfg, dtype):
    D = cfg.d_model
    H = cfg.n_heads
    r_kv = cfg.kv_lora_rank
    d_nope, d_rope, d_v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        # queries (V2-Lite: no q compression)
        "wq": dense_init(ks[0], (D, H * (d_nope + d_rope)), dtype),
        # KV joint compression + decoupled rope key
        "wkv_a": dense_init(ks[1], (D, r_kv + d_rope), dtype),
        "kv_norm": jnp.ones((r_kv,), dtype),
        "wkv_b": dense_init(ks[2], (r_kv, H * (d_nope + d_v)), dtype),
        "wo": dense_init(ks[3], (H * d_v, D), dtype),
    }


def mla_apply(p, cfg, x: Array, positions: Array, cache=None, cache_index=None,
              causal: bool = True):
    """MLA with compressed-KV cache: cache = {'ckv': [B,S,r_kv], 'krope': [B,S,d_rope]}."""
    B, T, D = x.shape
    H = cfg.n_heads
    r_kv, d_nope, d_rope, d_v = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                                 cfg.qk_rope_head_dim, cfg.v_head_dim)
    q = (x @ p["wq"]).reshape(B, T, H, d_nope + d_rope)
    q_nope, q_rope = q[..., :d_nope], q[..., d_nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv_a = x @ p["wkv_a"]                               # [B,T,r_kv+d_rope]
    ckv = rms_norm(kv_a[..., :r_kv], p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(kv_a[..., r_kv:][:, :, None, :], positions,
                        cfg.rope_theta)                 # [B,T,1,d_rope]

    if cache is not None:
        from ..parallel import api as P
        ckv = jax.lax.dynamic_update_slice(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, cache_index, 0))
        k_rope = jax.lax.dynamic_update_slice(
            cache["krope"], k_rope.astype(cache["krope"].dtype),
            (0, cache_index, 0, 0))
        ckv = P.shard_kv_cache(ckv)
        k_rope = P.shard_kv_cache(k_rope)
        new_cache = {"ckv": ckv, "krope": k_rope}
        S = ckv.shape[1]
        kv_len = jnp.full((B,), cache_index + T)
        q_offset = cache_index
    else:
        new_cache = None
        S = T
        kv_len = None
        q_offset = 0

    # expand compressed cache to per-head K (nope part) and V
    kv = (ckv @ p["wkv_b"]).reshape(B, S, H, d_nope + d_v)
    k_nope, v = kv[..., :d_nope], kv[..., d_nope:]
    k_rope_b = jnp.broadcast_to(k_rope, (B, S, H, d_rope))
    k_full = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    if cache is not None:
        # decode: sequence-sharded manual attention (see gqa_apply)
        o = decode_attention_sharded(q_full, k_full, v, q_offset, kv_len)
    elif S <= 2048:
        o = sdpa_simple(q_full, k_full, v, causal)
    else:
        o = sdpa(q_full, k_full, v, causal=causal)
    o = o.reshape(B, T, H * d_v) @ p["wo"]
    return o, new_cache


# -- MLPs ------------------------------------------------------------------------

def swiglu_init(key, d_model: int, d_ff: int, dtype):
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], (d_model, d_ff), dtype),
        "w_up": dense_init(ks[1], (d_model, d_ff), dtype),
        "w_down": dense_init(ks[2], (d_ff, d_model), dtype),
    }


def swiglu_apply(p, x: Array) -> Array:
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def gelu_mlp_init(key, d_model: int, d_ff: int, dtype):
    ks = jax.random.split(key, 2)
    return {
        "w_in": dense_init(ks[0], (d_model, d_ff), dtype),
        "b_in": jnp.zeros((d_ff,), dtype),
        "w_out": dense_init(ks[1], (d_ff, d_model), dtype),
        "b_out": jnp.zeros((d_model,), dtype),
    }


def gelu_mlp_apply(p, x: Array) -> Array:
    return jax.nn.gelu((x @ p["w_in"]) + p["b_in"]) @ p["w_out"] + p["b_out"]


# -- Mixture of Experts ----------------------------------------------------------

def moe_init(key, cfg, dtype):
    D, E, F = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (D, E), jnp.float32, scale=0.02),
        "w_gate": dense_init(ks[1], (E, D, F), dtype),
        "w_up": dense_init(ks[2], (E, D, F), dtype),
        "w_down": dense_init(ks[3], (E, F, D), dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = swiglu_init(ks[4], D, F * cfg.n_shared_experts, dtype)
    return p


def moe_apply(p, cfg, x: Array, capacity_factor: Optional[float] = None):
    """Top-k MoE with capacity-based scatter dispatch (GShard-style).

    Returns (out, aux_loss).  Dispatch avoids the [T, E, C] one-hot tensor:
    position-in-expert comes from a cumsum over the [T·K, E] one-hot and
    tokens land in the [E, C, D] buffer via scatter-add — TPU-friendly
    (static shapes, no ragged ops), and sharding E over the 'model' axis
    turns the scatter into the MoE all-to-all in SPMD.
    """
    B, T, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    if capacity_factor is None:
        capacity_factor = cfg.capacity_factor
    xt = x.reshape(B * T, D)
    N = B * T
    logits = (xt.astype(jnp.float32) @ p["router"])      # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)        # [N, K]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # aux load-balance loss (Switch): E * Σ_e f_e · p_e
    me = probs.mean(axis=0)
    onehot_top1 = jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32)
    fe = onehot_top1.mean(axis=0)
    aux = E * jnp.sum(fe * me)

    C = int(np.ceil(K * N * capacity_factor / E))
    C = max(C, 4)
    flat_idx = gate_idx.reshape(-1)                      # [N*K]
    onehot = jax.nn.one_hot(flat_idx, E, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1                 # position within expert
    pos_in_e = jnp.take_along_axis(pos, flat_idx[:, None], axis=1)[:, 0]
    keep = pos_in_e < C
    dest_e = jnp.where(keep, flat_idx, E)                # E = drop bucket
    dest_c = jnp.where(keep, pos_in_e, 0)

    xk = jnp.repeat(xt, K, axis=0)                       # [N*K, D]
    buf = jnp.zeros((E + 1, C, D), x.dtype)
    buf = buf.at[dest_e, dest_c].add(xk)
    ex = buf[:E]                                         # [E, C, D]

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ex, p["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", ex, p["w_up"])
    eo = jnp.einsum("ecf,efd->ecd", h, p["w_down"])      # [E, C, D]

    gathered = eo[jnp.minimum(dest_e, E - 1), dest_c]    # [N*K, D]
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    w = gate_vals.reshape(-1)[:, None].astype(x.dtype)
    out = (gathered * w).reshape(N, K, D).sum(axis=1)

    if "shared" in p:
        out = out + swiglu_apply(p["shared"], xt)
    return out.reshape(B, T, D), aux
