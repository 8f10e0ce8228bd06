"""Transformer building blocks shared by the assigned architectures.

Norms (RMS/LayerNorm), rotary embeddings (full/partial, NTK theta),
GQA attention with qk-norm / sliding window / logit softcap / cross-attn,
DeepSeek MLA (training path + absorbed latent decode path), and dense MLPs
(SwiGLU / GeGLU / GELU).

All forward functions are pure: ``fn(params, cfg, x, ...)``. Attention has
three entry points:
  * ``attention_train``   — full-sequence causal (XLA einsum path; the
                            Pallas flash kernel is selected by cfg.use_flash
                            on TPU runtimes),
  * ``attention_decode``  — single-step with a KV cache,
  * same pair for MLA.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.params import Maker


# ---------------------------------------------------------------------------
# Config fragments
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0       # stablelm: 0.25 partial rotary
    qk_norm: bool = False            # qwen3
    window: int | None = None        # gemma2 local layers
    attn_softcap: float | None = None  # gemma2
    cross: bool = False              # llama-3.2-vision cross-attn layers
    d_cross: int | None = None       # encoder width for cross-attn
    qk_scale: float | None = None
    impl: str = "ref"                # "ref" | "chunked" (online softmax)
    chunk: int = 2048                # KV chunk for the chunked impl

    @property
    def q_dim(self):
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self):
        return self.n_kv_heads * self.head_dim


@dataclasses.dataclass(frozen=True)
class MlpConfig:
    d_model: int
    d_ff: int
    kind: str = "swiglu"  # swiglu | geglu | gelu


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(mk: Maker, d: int):
    return {"scale": mk((d,), (None,), init="zeros")}  # (1+scale) convention


def rmsnorm(p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + p["scale"].astype(jnp.float32))).astype(x.dtype)


def init_layernorm(mk: Maker, d: int):
    return {"scale": mk((d,), (None,), init="ones"),
            "bias": mk((d,), (None,), init="zeros")}


def layernorm(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


def make_norm(kind: str):
    if kind == "rms":
        return init_rmsnorm, rmsnorm
    if kind == "layer":
        return init_layernorm, layernorm
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, fraction: float, theta: float):
    rot = int(head_dim * fraction) // 2 * 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, jnp.float32) / rot))
    return inv, rot


def apply_rope(x: jax.Array, positions: jax.Array, *, theta: float,
               fraction: float = 1.0) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32."""
    d = x.shape[-1]
    inv, rot = rope_frequencies(d, fraction, theta)
    if rot == 0:
        return x
    ang = positions[..., None].astype(jnp.float32) * inv  # (B,S,rot/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(xr.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out.astype(x.dtype), xp], axis=-1)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

def init_attention(mk: Maker, cfg: AttnConfig):
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    d_kv_in = cfg.d_cross if (cfg.cross and cfg.d_cross) else d
    p = {
        "wq": mk((d, hq, hd), ("embed", "heads", "head_dim"), init="fan_in"),
        "wk": mk((d_kv_in, hkv, hd), ("embed", "kv_heads", "head_dim"),
                 init="fan_in"),
        "wv": mk((d_kv_in, hkv, hd), ("embed", "kv_heads", "head_dim"),
                 init="fan_in"),
        "wo": mk((hq, hd, d), ("heads", "head_dim", "embed"), init="fan_in"),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(mk, hd)
        p["k_norm"] = init_rmsnorm(mk, hd)
    return p


def _qkv(p, cfg: AttnConfig, x, kv_src, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", kv_src, p["wk"].astype(kv_src.dtype))
    v = jnp.einsum("bsd,dhk->bshk", kv_src, p["wv"].astype(kv_src.dtype))
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q)
        k = rmsnorm(p["k_norm"], k)
    if not cfg.cross:
        q = apply_rope(q, positions, theta=cfg.rope_theta,
                       fraction=cfg.rope_fraction)
        k = apply_rope(k, positions, theta=cfg.rope_theta,
                       fraction=cfg.rope_fraction)
    return q, k, v


def _sdpa(q, k, v, cfg: AttnConfig, *, causal: bool, q_offset=None,
          kv_valid_len=None):
    """Grouped softmax attention, fp32 logits.

    q: (B,Sq,Hq,D); k/v: (B,Skv,Hkv,D). q_offset: (B,) absolute position of
    q[0] (decode); kv_valid_len: (B,) #valid cache entries.
    """
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = cfg.qk_scale if cfg.qk_scale is not None else d ** -0.5
    qg = q.reshape(b, sq, hkv, g, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if cfg.attn_softcap is not None:
        logits = jnp.tanh(logits / cfg.attn_softcap) * cfg.attn_softcap

    ki = jnp.arange(skv)[None, None, :]
    if q_offset is None:
        qi = jnp.arange(sq)[None, :, None] + (skv - sq)
    else:
        qi = jnp.arange(sq)[None, :, None] + q_offset[:, None, None]
    mask = jnp.ones((b, sq, skv), bool)
    if causal:
        mask &= qi >= ki
    if cfg.window is not None:
        mask &= qi - ki < cfg.window
    if kv_valid_len is not None:
        mask &= ki < kv_valid_len[:, None, None]
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, d).astype(q.dtype)


def _sdpa_chunked(q, k, v, cfg: AttnConfig, *, causal: bool):
    """Online-softmax attention over KV chunks — the XLA-level equivalent
    of the Pallas flash kernel (kernels/flash_attention.py): the (Sq, Skv)
    score matrix never exists; the live working set is (Sq, chunk).

    Numerically identical to ``_sdpa`` (same fp32 accumulation; tested to
    2e-4). This is the "flashlike" hillclimb lever in EXPERIMENTS.md §Perf.
    """
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = cfg.qk_scale if cfg.qk_scale is not None else d ** -0.5
    ck = min(cfg.chunk, skv)
    skv_pad = -(-skv // ck) * ck
    if skv_pad != skv:
        k = jnp.pad(k, ((0, 0), (0, skv_pad - skv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, skv_pad - skv), (0, 0), (0, 0)))
    nc = skv_pad // ck

    qg = (q.astype(jnp.float32) * scale).reshape(b, sq, hkv, g, d)
    kc = k.reshape(b, nc, ck, hkv, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nc, ck, hkv, d).transpose(1, 0, 2, 3, 4)
    qi = jnp.arange(sq)[:, None] + (skv - sq)          # (sq, 1)

    def body(carry, inp):
        m, l, acc = carry
        kj, vj, j = inp
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                            kj.astype(jnp.float32))    # (b,hkv,g,sq,ck)
        if cfg.attn_softcap is not None:
            logits = jnp.tanh(logits / cfg.attn_softcap) * cfg.attn_softcap
        ki = j * ck + jnp.arange(ck)[None, :]
        mask = ki < skv
        if causal:
            mask &= qi >= ki
        if cfg.window is not None:
            mask &= qi - ki < cfg.window
        logits = jnp.where(mask, logits, -1e30)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, vj.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((b, hkv, g, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    acc0 = jnp.zeros((b, hkv, g, sq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0),
                                  (kc, vc, jnp.arange(nc)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, d).astype(q.dtype)


def sdpa_any(q, k, v, cfg: AttnConfig, *, causal: bool):
    if cfg.impl == "chunked":
        return _sdpa_chunked(q, k, v, cfg, causal=causal)
    return _sdpa(q, k, v, cfg, causal=causal)


def attention_train(p, cfg: AttnConfig, x, *, positions=None, kv_src=None,
                    use_flash: bool = False,
                    flash_interpret: bool | None = None):
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    kv_src = x if kv_src is None else kv_src
    q, k, v = _qkv(p, cfg, x, kv_src, positions)
    causal = not cfg.cross
    if use_flash:
        from repro.kernels.flash_attention import flash_attention
        from repro.kernels.ops import resolve_interpret
        out = flash_attention(q, k, v, causal=causal, window=cfg.window,
                              softcap=cfg.attn_softcap, scale=cfg.qk_scale,
                              interpret=resolve_interpret(flash_interpret))
    else:
        out = sdpa_any(q, k, v, cfg, causal=causal)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(out.dtype))


def init_kv_cache(mk_or_none, cfg: AttnConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16):
    shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    axes_k = ("batch", "kv_seq", "kv_heads", "head_dim")
    if mk_or_none is not None:
        return {"k": mk_or_none(shape, axes_k),
                "v": mk_or_none(shape, axes_k)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def attention_decode(p, cfg: AttnConfig, x, cache, pos):
    """x: (B, 1, D); cache {"k","v"}: (B, Smax, Hkv, D); pos: (B,) int32.

    Returns (out (B,1,D), new_cache). Cross-attn layers use a static cache
    (precomputed encoder KV) and do not update it. On TPU runtimes the
    inner attention is served by the split-KV Pallas kernel
    (repro.kernels.flash_decode, same ragged-length masking semantics —
    validated against this path in tests/test_kernels.py); the XLA einsum
    here is the dry-run/CPU form.
    """
    b = x.shape[0]
    positions = pos[:, None]
    if cfg.cross:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
        if cfg.qk_norm:
            q = rmsnorm(p["q_norm"], q)
        out = _sdpa(q, cache["k"].astype(x.dtype), cache["v"].astype(x.dtype),
                    cfg, causal=False, q_offset=pos)
        return (jnp.einsum("bshk,hkd->bsd", out,
                           p["wo"].astype(out.dtype)), cache)

    q, k_new, v_new = _qkv(p, cfg, x, x, positions)
    k = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
        c, u.astype(c.dtype), (i, 0, 0)))(cache["k"], k_new, pos)
    v = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
        c, u.astype(c.dtype), (i, 0, 0)))(cache["v"], v_new, pos)
    out = _sdpa(q, k.astype(x.dtype), v.astype(x.dtype), cfg, causal=True,
                q_offset=pos, kv_valid_len=pos + 1)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(out.dtype))
    return out, {"k": k, "v": v}


# ---------------------------------------------------------------------------
# DeepSeek MLA (multi-head latent attention)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MlaConfig:
    d_model: int
    n_heads: int
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    d_nope: int = 128
    d_rope: int = 64
    d_v: int = 128
    rope_theta: float = 10000.0

    @property
    def qk_dim(self):
        return self.d_nope + self.d_rope


def init_mla(mk: Maker, cfg: MlaConfig):
    d, h = cfg.d_model, cfg.n_heads
    return {
        "wq_a": mk((d, cfg.q_lora_rank), ("embed", "q_lora"), init="fan_in"),
        "q_a_norm": init_rmsnorm(mk, cfg.q_lora_rank),
        "wq_b": mk((cfg.q_lora_rank, h, cfg.qk_dim),
                   ("q_lora", "heads", "head_dim"), init="fan_in"),
        "wkv_a": mk((d, cfg.kv_lora_rank + cfg.d_rope), ("embed", "kv_lora"),
                    init="fan_in"),
        "kv_a_norm": init_rmsnorm(mk, cfg.kv_lora_rank),
        "wk_b": mk((cfg.kv_lora_rank, h, cfg.d_nope),
                   ("kv_lora", "heads", "head_dim"), init="fan_in"),
        "wv_b": mk((cfg.kv_lora_rank, h, cfg.d_v),
                   ("kv_lora", "heads", "head_dim"), init="fan_in"),
        "wo": mk((h, cfg.d_v, d), ("heads", "head_dim", "embed"),
                 init="fan_in"),
    }


def _mla_qkr(p, cfg: MlaConfig, x, positions):
    """Queries + latent + rope-key shared by train/decode."""
    q_a = rmsnorm(p["q_a_norm"],
                  jnp.einsum("bsd,dr->bsr", x, p["wq_a"].astype(x.dtype)))
    q = jnp.einsum("bsr,rhk->bshk", q_a, p["wq_b"].astype(x.dtype))
    q_nope, q_rope = q[..., :cfg.d_nope], q[..., cfg.d_nope:]
    q_rope = apply_rope(q_rope, positions, theta=cfg.rope_theta)

    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(x.dtype))
    c_kv = rmsnorm(p["kv_a_norm"], kv_a[..., :cfg.kv_lora_rank])
    k_rope = kv_a[..., cfg.kv_lora_rank:][:, :, None, :]  # shared head
    k_rope = apply_rope(k_rope, positions, theta=cfg.rope_theta)
    return q_nope, q_rope, c_kv, k_rope[:, :, 0, :]


def mla_train(p, cfg: MlaConfig, x, *, positions=None, impl: str = "ref",
              chunk: int = 2048):
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    q_nope, q_rope, c_kv, k_rope = _mla_qkr(p, cfg, x, positions)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["wk_b"].astype(x.dtype))
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["wv_b"].astype(x.dtype))

    # q·k = q_nope·k_nope + q_rope·k_rope  ==  concat(q)·concat(k) with the
    # shared rope key broadcast per head -> reuse the standard SDPA paths
    # (incl. the chunked/flash-like one).
    h = cfg.n_heads
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (b, s, h, cfg.d_rope)).astype(k_nope.dtype)],
        axis=-1)
    acfg = AttnConfig(d_model=cfg.d_model, n_heads=h, n_kv_heads=h,
                      head_dim=cfg.qk_dim, qk_scale=cfg.qk_dim ** -0.5,
                      impl=impl, chunk=chunk)
    # v has d_v dims (may differ from qk_dim): pad v to qk_dim then slice.
    if cfg.d_v != cfg.qk_dim:
        v_in = jnp.pad(v, ((0, 0), (0, 0), (0, 0),
                           (0, cfg.qk_dim - cfg.d_v)))
    else:
        v_in = v
    out = sdpa_any(q_full, k_full, v_in, acfg, causal=True)[..., :cfg.d_v]
    return jnp.einsum("bshv,hvd->bsd", out, p["wo"].astype(x.dtype))


def init_mla_cache(mk_or_none, cfg: MlaConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16):
    """The MLA decode cache stores only the latent + shared rope key —
    (kv_lora_rank + d_rope) per token instead of 2*H*D (the paper-point of
    MLA; 576 vs 32768 floats/token for deepseek-v3)."""
    shape = (batch, max_len, cfg.kv_lora_rank + cfg.d_rope)
    if mk_or_none is not None:
        return {"ckv": mk_or_none(shape, ("batch", "kv_seq", None))}
    return {"ckv": jnp.zeros(shape, dtype)}


def mla_decode(p, cfg: MlaConfig, x, cache, pos):
    """Absorbed-matmul latent decode: attention runs in the 512-dim latent
    space; W_uk is folded into the query and W_uv into the output."""
    b = x.shape[0]
    positions = pos[:, None]
    q_nope, q_rope, c_kv_new, k_rope_new = _mla_qkr(p, cfg, x, positions)

    entry = jnp.concatenate([c_kv_new, k_rope_new], axis=-1)  # (B,1,R+dr)
    ckv = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
        c, u.astype(c.dtype), (i, 0)))(cache["ckv"], entry, pos)
    c_lat = ckv[..., :cfg.kv_lora_rank].astype(jnp.float32)   # (B,S,R)
    k_rope = ckv[..., cfg.kv_lora_rank:].astype(jnp.float32)  # (B,S,dr)

    # absorb W_uk: q_lat (B,1,H,R)
    q_lat = jnp.einsum("bqhk,rhk->bqhr", q_nope.astype(jnp.float32),
                       p["wk_b"].astype(jnp.float32))
    scale = cfg.qk_dim ** -0.5
    logits = (jnp.einsum("bqhr,bkr->bhqk", q_lat, c_lat)
              + jnp.einsum("bqhn,bkn->bhqk", q_rope.astype(jnp.float32),
                           k_rope)) * scale
    ki = jnp.arange(ckv.shape[1])[None, None, None, :]
    logits = jnp.where(ki <= pos[:, None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    o_lat = jnp.einsum("bhqk,bkr->bqhr", probs, c_lat)        # (B,1,H,R)
    out = jnp.einsum("bqhr,rhv->bqhv", o_lat,
                     p["wv_b"].astype(jnp.float32))           # absorb W_uv
    out = out.astype(x.dtype)
    return jnp.einsum("bshv,hvd->bsd", out, p["wo"].astype(x.dtype)), \
        {"ckv": ckv}


# ---------------------------------------------------------------------------
# Dense MLP
# ---------------------------------------------------------------------------

def init_mlp(mk: Maker, cfg: MlpConfig):
    d, f = cfg.d_model, cfg.d_ff
    if cfg.kind in ("swiglu", "geglu"):
        return {
            "w_gate": mk((d, f), ("embed", "mlp"), init="fan_in"),
            "w_up": mk((d, f), ("embed", "mlp"), init="fan_in"),
            "w_down": mk((f, d), ("mlp", "embed"), init="fan_in"),
        }
    return {
        "w_up": mk((d, f), ("embed", "mlp"), init="fan_in"),
        "w_down": mk((f, d), ("mlp", "embed"), init="fan_in"),
    }


def mlp(p, cfg: MlpConfig, x):
    if cfg.kind in ("swiglu", "geglu"):
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype))
        u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype))
        act = jax.nn.silu(g) if cfg.kind == "swiglu" else jax.nn.gelu(g)
        h = act * u
    else:
        h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x,
                                   p["w_up"].astype(x.dtype)))
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(x.dtype))
