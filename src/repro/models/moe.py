"""Mixture-of-Experts with expert parallelism over the "model" mesh axis.

Design notes (DESIGN.md §4/§5): the paper's TDT insight — turn irregular,
input-dependent gathers into *bounded, schedulable tile traffic* — maps to
MoE token->expert dispatch. We deliberately do NOT use the GShard dense
one-hot dispatch einsum: at DeepSeek scale (E=256) its T*E*C*D MAC cost is
~600x the expert FFN itself. Instead dispatch is gather/scatter into
static *capacity slots* (the "tiles"):

  * tokens are replicated across the "model" axis (the usual TP activation
    layout after attention);
  * each model rank owns E/ep experts; it selects its own (token, k) pairs
    with a cumsum-position capacity assignment (static shapes), scatters
    them into (E_loc, C, D) slot buffers, runs the expert FFN as one
    batched einsum, gathers results back, and the ranks' partial outputs
    are combined with a single psum — no all-to-all at all;
  * expert weights are additionally FSDP-sharded over ("pod","data") and
    all-gathered just-in-time per layer (the scan-over-layers structure
    bounds the transient to one layer's experts).

The block runs under ``jax.shard_map`` (fully manual) when a mesh is
present, and as plain single-device JAX otherwise (the oracle path used by
tests).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.params import Maker


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    d_model: int
    d_ff: int                 # per-expert intermediate width
    n_experts: int            # logical expert count (pre-padding)
    top_k: int
    n_shared: int = 0         # shared-expert multiplier (deepseek: 1)
    router: str = "softmax"   # "softmax" | "sigmoid" (deepseek aux-free)
    capacity_factor: float = 1.25
    ep: int = 1               # expert-parallel degree (model-axis size)
    routed_scale: float = 1.0  # deepseek routed_scaling_factor
    # "fsdp": expert weights sharded (E/model, D/dp) and all-gathered
    #         just-in-time (training layout: bytes ~ params/step).
    # "tp_f": weights stationary, F additionally sharded over dp, tokens
    #         replicated, one psum over (dp, model) (decode layout:
    #         bytes ~ activations/step). §Perf "serve_tp" hillclimb.
    weight_mode: str = "fsdp"

    @property
    def n_experts_padded(self) -> int:
        return math.ceil(self.n_experts / self.ep) * self.ep

    @property
    def e_loc(self) -> int:
        return self.n_experts_padded // self.ep


def init_moe(mk: Maker, cfg: MoeConfig):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts_padded
    p = {
        "router": mk((d, e), ("embed", None), init="fan_in"),
        "w_gate": mk((e, d, f), ("expert", "embed_fsdp", "mlp"),
                     init="fan_in"),
        "w_up": mk((e, d, f), ("expert", "embed_fsdp", "mlp"), init="fan_in"),
        "w_down": mk((e, f, d), ("expert", "mlp_fsdp", "embed"),
                     init="fan_in"),
    }
    if cfg.router == "sigmoid":
        p["e_bias"] = mk((e,), (None,), init="zeros")  # aux-loss-free bias
    if cfg.n_shared:
        fs = f * cfg.n_shared
        p["shared"] = {
            "w_gate": mk((d, fs), ("embed", "mlp"), init="fan_in"),
            "w_up": mk((d, fs), ("embed", "mlp"), init="fan_in"),
            "w_down": mk((fs, d), ("mlp", "embed"), init="fan_in"),
        }
    return p


def _route(p, cfg: MoeConfig, x_flat):
    """-> gates (T, K) f32, expert ids (T, K) i32, aux loss scalar."""
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    e = cfg.n_experts_padded
    if cfg.n_experts < e:  # mask padded experts off
        pad_mask = jnp.arange(e) >= cfg.n_experts
        logits = jnp.where(pad_mask[None], -1e30, logits)

    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores + p["e_bias"].astype(jnp.float32)[None]
        if cfg.n_experts < e:
            sel = jnp.where(jnp.arange(e)[None] >= cfg.n_experts, -1e30, sel)
        _, eids = jax.lax.top_k(sel, cfg.top_k)
        picked = jnp.take_along_axis(scores, eids, axis=-1)
        gates = picked / jnp.maximum(picked.sum(-1, keepdims=True), 1e-9)
        gates = gates * cfg.routed_scale
        probs = scores / jnp.maximum(scores.sum(-1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates, eids = jax.lax.top_k(probs, cfg.top_k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance aux (a metric for sigmoid/aux-free).
    t = x_flat.shape[0]
    counts = jnp.zeros((e,), jnp.float32).at[eids.reshape(-1)].add(1.0)
    frac = counts / (t * cfg.top_k)
    imp = probs.mean(0)
    aux = cfg.n_experts * jnp.sum(frac * imp)
    return gates, eids, aux


def _expert_ffn(x_slots, w_gate, w_up, w_down):
    """(E_loc, C, D) -> (E_loc, C, D), SwiGLU per expert."""
    dt = x_slots.dtype
    g = jnp.einsum("ecd,edf->ecf", x_slots, w_gate.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", x_slots, w_up.astype(dt))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, w_down.astype(dt))


def _moe_core(p, cfg: MoeConfig, x, *, rank, wgather, psum):
    """The per-rank math. x: (B_loc, S, D). rank: this device's EP index."""
    b, s, d = x.shape
    t = b * s
    x_flat = x.reshape(t, d)
    gates, eids, aux = _route(p, cfg, x_flat)

    e_loc = cfg.e_loc
    cap = max(8, int(t * cfg.top_k / cfg.n_experts_padded
                     * cfg.capacity_factor))
    lo = rank * e_loc

    w_gate = wgather(p["w_gate"], 1)   # (E_loc, D, F) after FSDP gather
    w_up = wgather(p["w_up"], 1)
    w_down = wgather(p["w_down"], 1)

    n_slots = e_loc * cap
    x_slots = jnp.zeros((n_slots + 1, d), x.dtype)   # last row = drop bin
    slot_of = []
    keep_of = []
    # Per-k dispatch keeps transients at (T, D) instead of (T*K, D).
    occupancy = jnp.zeros((e_loc,), jnp.int32)
    for k in range(cfg.top_k):
        le = eids[:, k] - lo                                   # (T,)
        local = (le >= 0) & (le < e_loc)
        le_c = jnp.clip(le, 0, e_loc - 1)
        onehot = (le_c[:, None] == jnp.arange(e_loc)[None]) & local[:, None]
        pos = jnp.cumsum(onehot, axis=0) - 1                   # (T, E_loc)
        pos_k = jnp.take_along_axis(pos, le_c[:, None], axis=1)[:, 0]
        pos_k = pos_k + occupancy[le_c]
        occupancy = occupancy + onehot.sum(0, dtype=jnp.int32)
        keep = local & (pos_k < cap)
        slot = jnp.where(keep, le_c * cap + pos_k, n_slots)
        x_slots = x_slots.at[slot].add(jnp.where(keep[:, None], x_flat, 0))
        slot_of.append(slot)
        keep_of.append(keep)

    y_slots = _expert_ffn(x_slots[:n_slots].reshape(e_loc, cap, d),
                          w_gate, w_up, w_down)
    y_slots = jnp.concatenate(
        [y_slots.reshape(n_slots, d), jnp.zeros((1, d), y_slots.dtype)], 0)

    y = jnp.zeros((t, d), jnp.float32)
    for k in range(cfg.top_k):
        contrib = y_slots[slot_of[k]].astype(jnp.float32)
        w = jnp.where(keep_of[k], gates[:, k], 0.0)
        y = y + contrib * w[:, None]
    y = psum(y)
    out = y.astype(x.dtype).reshape(b, s, d)

    if cfg.n_shared:
        sh = p["shared"]
        g = jnp.einsum("bsd,df->bsf", x, sh["w_gate"].astype(x.dtype))
        u = jnp.einsum("bsd,df->bsf", x, sh["w_up"].astype(x.dtype))
        out = out + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                               sh["w_down"].astype(x.dtype))
    return out, aux


def moe_apply(p, cfg: MoeConfig, x, *, mesh: jax.sharding.Mesh | None = None,
              dp_axes: tuple[str, ...] = ("pod", "data"),
              ep_axis: str = "model"):
    """MoE forward. With a mesh: fully-manual shard_map EP/FSDP; without:
    single-device oracle path (rank 0 owns all experts; requires ep == 1).
    """
    if mesh is None:
        assert cfg.ep == 1, "local path requires ep=1"
        return _moe_core(p, cfg, x, rank=0, wgather=lambda w, ax: w,
                         psum=lambda y: y)

    dp = tuple(a for a in dp_axes if a in mesh.shape)
    # Small batches (e.g. long_500k decode with B=1) can't shard over dp:
    # drop axes until the batch divides (tokens then replicate over the
    # dropped axes — unavoidable and cheap at that batch size).
    while dp and x.shape[0] % math.prod(mesh.shape[a] for a in dp):
        dp = dp[:-1]
    tp_f = cfg.weight_mode == "tp_f"
    if tp_f:
        # weights stationary: tokens replicate (tiny at decode), F shards
        # over dp, one psum combines F-partials and expert-partials.
        batch_spec = P(None, None, None)
        wspec = {
            "router": P(None, None),
            "w_gate": P(ep_axis, None, dp), "w_up": P(ep_axis, None, dp),
            "w_down": P(ep_axis, dp, None),
        }
    else:
        batch_spec = P(dp if len(dp) > 1 else (dp[0] if dp else None),
                       None, None)
        wspec = {
            "router": P(None, None),
            "w_gate": P(ep_axis, dp, None), "w_up": P(ep_axis, dp, None),
            "w_down": P(ep_axis, dp, None),
        }
    if "e_bias" in p:
        wspec["e_bias"] = P(None)
    if "shared" in p:
        wspec["shared"] = {"w_gate": P(None, ep_axis),
                           "w_up": P(None, ep_axis),
                           "w_down": P(ep_axis, None)}

    all_axes = dp + (ep_axis,)

    def body(p_loc, x_loc):
        rank = jax.lax.axis_index(ep_axis)

        if tp_f:
            def wgather(w, ax):
                return w  # stationary: F-sharded partials, no movement

            def psum(y):
                return jax.lax.psum(y, dp + (ep_axis,)) if dp \
                    else jax.lax.psum(y, ep_axis)
        else:
            def wgather(w, ax):
                return jax.lax.all_gather(w, dp, axis=ax, tiled=True) \
                    if dp else w

            def psum(y):
                return jax.lax.psum(y, ep_axis)

        if "shared" in p_loc:  # shared expert runs TP over ep_axis
            routed, aux = _moe_core(
                {k: v for k, v in p_loc.items() if k != "shared"},
                dataclasses.replace(cfg, n_shared=0), x_loc,
                rank=rank, wgather=wgather, psum=lambda y: y)
            sh = p_loc["shared"]
            g = jnp.einsum("bsd,df->bsf", x_loc,
                           sh["w_gate"].astype(x_loc.dtype))
            u = jnp.einsum("bsd,df->bsf", x_loc,
                           sh["w_up"].astype(x_loc.dtype))
            shared = jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                                sh["w_down"].astype(x_loc.dtype)) \
                .astype(jnp.float32)
            if tp_f:
                # shared partials vary over ep only; routed vary over dp+ep
                out = (psum(routed.astype(jnp.float32))
                       + jax.lax.psum(shared, ep_axis))
            else:
                out = psum(routed.astype(jnp.float32) + shared)
            out = out.astype(x_loc.dtype)
            aux = jax.lax.pcast(aux, (dp + (ep_axis,)) if tp_f
                                else (ep_axis,), to="varying")
            return out, jax.lax.pmean(aux, all_axes)

        out, aux = _moe_core(p_loc, cfg, x_loc, rank=rank,
                             wgather=wgather, psum=psum)
        aux = jax.lax.pcast(aux, (dp + (ep_axis,)) if tp_f
                            else (ep_axis,), to="varying")
        return out, jax.lax.pmean(aux, all_axes)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(wspec, batch_spec),
        out_specs=(batch_spec, P()),
    )(p, x)
