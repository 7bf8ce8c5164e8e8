"""LM building blocks: norms, MLP, GQA attention, MoE (shard_map EP), RG-LRU,
mLSTM / sLSTM, and the paper's LinearReservoir layer as a first-class mixer.

Every ``init_*`` returns ``(params, specs)`` where ``specs`` mirrors the params
pytree with ``jax.sharding.PartitionSpec`` leaves, derived from a
``ShardProfile`` (TP axis for heads/d_ff/experts/state, optional FSDP axis).

All recurrent mixers (RG-LRU, mLSTM, sLSTM, reservoir) lower onto the paper's
diagonal-scan machinery (`repro.core.scan` / the Pallas kernel): their state
update is element-wise, so tensor-parallel sharding of the state dimension
needs ZERO collectives inside the recurrence — the systems-level payoff of the
paper's diagonalization.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import scan as scan_mod
from repro.core import spectral
from . import attention as attn_mod

Params = Any


# --------------------------------------------------------------------------- #
# Sharding profile                                                             #
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class ShardProfile:
    """How this arch maps onto the mesh.  All-None = single-device smoke run."""
    mesh: Optional[Any] = None
    tp: Optional[str] = None          # tensor-parallel axis name ("model")
    fsdp: Optional[str] = None        # weight-sharding axis name ("data")
    dp: tuple = ()                    # activation batch axes ("pod", "data")
    tp_size: int = 1
    seq: Optional[str] = None         # sequence-parallel residual stream axis

    def axis(self, name):
        return name if self.mesh is not None else None

    @property
    def dp_spec(self):
        return self.dp if self.dp else None


NULL_PROFILE = ShardProfile()


def _tp_dim(prof: ShardProfile, size: int):
    """Return the tp axis name iff `size` divides evenly, else None."""
    if prof.tp and size % prof.tp_size == 0:
        return prof.tp
    return None


def _fsdp_dim(prof: ShardProfile, size: int):
    if prof.fsdp and prof.mesh is not None:
        if size % prof.mesh.shape[prof.fsdp] == 0:
            return prof.fsdp
    return None


def constrain(x, spec, prof: ShardProfile):
    if prof.mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(prof.mesh, spec))


# --------------------------------------------------------------------------- #
# Norms                                                                        #
# --------------------------------------------------------------------------- #
def init_norm(d, dtype, kind="rmsnorm"):
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype)}, {"scale": P(None)}
    return ({"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)},
            {"scale": P(None), "bias": P(None)})


def apply_norm(p, x, kind="rmsnorm", eps=1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        nrm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        return (nrm * p["scale"].astype(jnp.float32)).astype(x.dtype)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
    nrm = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (nrm * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


def _dense_init(key, shape, dtype, scale=None):
    fan_in = shape[0]
    scale = (1.0 / math.sqrt(fan_in)) if scale is None else scale
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# --------------------------------------------------------------------------- #
# MLP (SwiGLU / GELU)                                                          #
# --------------------------------------------------------------------------- #
def init_mlp(key, d, f, dtype, prof, gated=True, bias=False):
    ks = jax.random.split(key, 3)
    tp_f = _tp_dim(prof, f)
    fs = _fsdp_dim(prof, d)
    p = {"wi": _dense_init(ks[0], (d, f), dtype),
         "wo": _dense_init(ks[2], (f, d), dtype)}
    s = {"wi": P(fs, tp_f), "wo": P(tp_f, fs)}
    if gated:
        p["wg"] = _dense_init(ks[1], (d, f), dtype)
        s["wg"] = P(fs, tp_f)
    if bias:
        p["bi"] = jnp.zeros((f,), dtype)
        p["bo"] = jnp.zeros((d,), dtype)
        s["bi"] = P(tp_f)
        s["bo"] = P(None)
    return p, s


def apply_mlp(p, x, act="silu", gated=True):
    h = x @ p["wi"]
    if "bi" in p:
        h = h + p["bi"]
    a = getattr(jax.nn, act)
    if gated:
        h = a(x @ p["wg"]) * h
    else:
        h = a(h)
    out = h @ p["wo"]
    if "bo" in p:
        out = out + p["bo"]
    return out


# --------------------------------------------------------------------------- #
# GQA attention block                                                          #
# --------------------------------------------------------------------------- #
def init_attention(key, cfg, dtype, prof):
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    ks = jax.random.split(key, 4)
    # 3D weight layout (d, H, hd) lets the sharder pick the head axis.
    tp_h = _tp_dim(prof, hq)
    tp_kv = _tp_dim(prof, hkv)
    fs = _fsdp_dim(prof, d)
    # Perf iteration (§Perf): head_dim (contraction) sharding made XLA psum
    # full (B,H,S,S_chunk) f32 score tensors — 135 GiB/step on smollm prefill.
    # Rule now: shard heads when divisible; GQA KV heads that don't divide are
    # REPLICATED across tp (Megatron-style KV duplication — KV weights are
    # tiny); fully indivisible head counts replicate attention weights (tp
    # still carries d_ff/vocab/state for those archs).
    q_spec = P(fs, tp_h, None)
    kv_spec = P(fs, tp_kv if (tp_kv and tp_h) else None, None)
    o_spec = P(tp_h, None, fs)
    p = {
        "wq": _dense_init(ks[0], (d, hq, hd), dtype),
        "wk": _dense_init(ks[1], (d, hkv, hd), dtype),
        "wv": _dense_init(ks[2], (d, hkv, hd), dtype),
        "wo": _dense_init(ks[3], (hq, hd, d), dtype, scale=1.0 / math.sqrt(hq * hd)),
    }
    s = {"wq": q_spec, "wk": kv_spec, "wv": kv_spec, "wo": o_spec}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq, hd), dtype)
        p["bk"] = jnp.zeros((hkv, hd), dtype)
        p["bv"] = jnp.zeros((hkv, hd), dtype)
        s["bq"] = P(tp_h, None)
        s["bk"] = P(tp_kv if (tp_kv and tp_h) else None, None)
        s["bv"] = s["bk"]
    return p, s


def _qkv(p, x, rope_theta, positions):
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"])
    if "bq" in p:
        q = q + p["bq"][None, :, None, :]
        k = k + p["bk"][None, :, None, :]
        v = v + p["bv"][None, :, None, :]
    if rope_theta:
        q = attn_mod.apply_rope(q, positions, rope_theta)
        k = attn_mod.apply_rope(k, positions, rope_theta)
    return q, k, v


def apply_attention(p, x, cfg, *, causal=True, window=None, positions=None,
                    cache=None, impl="auto"):
    """Full-sequence path.  Returns (out, new_cache_kv) — cache_kv = (k, v)
    full-length (caller builds the decode cache from them at prefill)."""
    b, s, d = x.shape
    if positions is None:
        positions = jnp.arange(s)
    q, k, v = _qkv(p, x, cfg.rope_theta, positions)
    o = attn_mod.attention(q, k, v, causal=causal, window=window, impl=impl)
    out = jnp.einsum("bhsk,hkd->bsd", o, p["wo"])
    return out, (k, v)


def apply_attention_decode(p, x, cfg, cache, *, window=None):
    """x: (B, 1, d); cache: {"k": (B,Hkv,S,hd), "v": ..., "len": scalar}.

    When the cache is window-sized (ring buffer — long-context decode for
    SWA/local attention), writes wrap modulo the window: O(window) memory for
    arbitrarily long sequences.  RoPE is applied at the absolute position
    before caching, so ring order is irrelevant to attention.
    """
    cur = cache["len"]
    smax = cache["k"].shape[2]
    ring = window is not None and smax <= window
    positions = cur[None] if cur.ndim == 0 else cur
    q, k_new, v_new = _qkv(p, x, cfg.rope_theta, jnp.asarray(positions))
    slot = jax.lax.rem(cur, jnp.asarray(smax, cur.dtype)) if ring else cur
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k_new.astype(cache["k"].dtype), slot, axis=2)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v_new.astype(cache["v"].dtype), slot, axis=2)
    o = attn_mod.decode_attention(q, k_cache, v_cache, cur + 1,
                                  window=window, ring=ring)
    out = jnp.einsum("bhsk,hkd->bsd", o, p["wo"])
    new_cache = {"k": k_cache, "v": v_cache, "len": cur + 1}
    return out, new_cache


# --------------------------------------------------------------------------- #
# Mixture of Experts (shard_map expert parallelism)                            #
# --------------------------------------------------------------------------- #
def init_moe(key, cfg, dtype, prof):
    d, f, e = cfg.d_model, cfg.moe_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    ep = _tp_dim(prof, e)  # experts sharded over the model axis
    fs = _fsdp_dim(prof, f)
    p = {
        "router": _dense_init(ks[0], (d, e), jnp.float32),
        "wg": _dense_init(ks[1], (e, d, f), dtype),
        "wu": _dense_init(ks[2], (e, d, f), dtype),
        "wd": _dense_init(ks[3], (e, f, d), dtype),
    }
    s = {"router": P(None, None),
         "wg": P(ep, None, fs), "wu": P(ep, None, fs), "wd": P(ep, fs, None)}
    return p, s


def _moe_local(x2d, router, wg, wu, wd, *, top_k, capacity, e_total, e_offset,
               act="silu"):
    """Dispatch the local token block against the LOCAL expert slice.

    x2d: (T, d) — every token this shard can see (replicated over the EP axis);
    w*: (E_local, ...).  Tokens routed to remote experts contribute zero here;
    the caller psums over the EP axis.
    Returns (out (T, d), aux dict with router stats).
    """
    t, d = x2d.shape
    e_local = wg.shape[0]
    logits = x2d.astype(jnp.float32) @ router  # (T, E_total)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, top_k)  # (T, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    flat_e = top_e.reshape(-1)                       # (T*k,)
    flat_w = top_w.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t), top_k)
    local_e = flat_e - e_offset
    is_local = (local_e >= 0) & (local_e < e_local)
    le = jnp.where(is_local, local_e, 0)
    # Position of each assignment within its expert's capacity buffer.
    onehot = jax.nn.one_hot(jnp.where(is_local, le, e_local),
                            e_local + 1, dtype=jnp.int32)  # (T*k, E_local+1)
    pos = jnp.cumsum(onehot, axis=0) - 1                   # running count
    pos = jnp.take_along_axis(pos, jnp.where(is_local, le, e_local)[:, None],
                              axis=1)[:, 0]
    keep = is_local & (pos < capacity)
    slot = jnp.where(keep, le * capacity + pos, e_local * capacity)  # drop row

    # Scatter token INDICES (cheap) then gather activations (E*C, d).
    token_idx = jnp.full((e_local * capacity + 1,), t, jnp.int32)
    token_idx = token_idx.at[slot].set(jnp.where(keep, flat_t, t).astype(jnp.int32))
    token_idx = token_idx[:-1]
    x_pad = jnp.concatenate([x2d, jnp.zeros((1, d), x2d.dtype)], 0)
    xg = x_pad[token_idx].reshape(e_local, capacity, d)

    h = jnp.einsum("ecd,edf->ecf", xg, wu)
    g = jnp.einsum("ecd,edf->ecf", xg, wg)
    h = getattr(jax.nn, act)(g) * h
    y = jnp.einsum("ecf,efd->ecd", h, wd).reshape(e_local * capacity, d)
    y = jnp.concatenate([y, jnp.zeros((1, d), y.dtype)], 0)

    # Combine: out[t] += w * y[slot]  (loop over k: (T, d) gathers, no T*k*d blowup)
    out = jnp.zeros((t, d), x2d.dtype)
    slot_tk = slot.reshape(t, top_k)
    keep_tk = keep.reshape(t, top_k)
    w_tk = top_w
    for j in range(top_k):
        sj = jnp.where(keep_tk[:, j], slot_tk[:, j], e_local * capacity)
        out = out + (w_tk[:, j, None] * y[sj]).astype(x2d.dtype)

    # Load-balance aux (global stats — computed on full router probs).
    me = probs.mean(axis=0)                       # (E_total,)
    ce = jax.nn.one_hot(top_e[:, 0], e_total).mean(axis=0)
    aux = {"load_balance": e_total * jnp.sum(me * ce),
           "router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)}
    return out, aux


def apply_moe(p, x, cfg, prof: ShardProfile):
    """x: (B, S, d).  EP over the tp axis via shard_map when distributed.

    Capacity (and therefore token dropping) is SHARD-LOCAL, exactly as on a
    real EP fleet: each data shard routes its own tokens against per-expert
    buffers sized cf * T_local * k / E.
    """
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    e_total = cfg.n_experts

    if prof.mesh is None or _tp_dim(prof, e_total) is None:
        cap = int(cfg.capacity_factor * b * s * cfg.top_k / e_total) + 1
        out, aux = _moe_local(x2d, p["router"], p["wg"], p["wu"], p["wd"],
                              top_k=cfg.top_k, capacity=cap, e_total=e_total,
                              e_offset=0, act=cfg.act)
        return out.reshape(b, s, d), aux

    tp = prof.tp
    tp_size = prof.tp_size
    fs = _fsdp_dim(prof, cfg.moe_ff)
    sizes = dict(zip(prof.mesh.axis_names, prof.mesh.devices.shape))
    dp_size = 1
    for a in prof.dp:
        dp_size *= sizes[a]
    t_local = (b * s) // dp_size
    cap = int(cfg.capacity_factor * t_local * cfg.top_k / e_total) + 1

    # Beyond-paper perf option (§Perf): when the residual stream is
    # sequence-sharded over tp, combine with reduce-scatter instead of
    # all-reduce — the dominant MoE collective's payload drops tp_size-fold
    # and the output lands already in the downstream seq-sharded layout.
    use_scatter = (prof.seq == tp and t_local % tp_size == 0)

    def shard_fn(x2d, router, wg, wu, wd):
        idx = jax.lax.axis_index(tp)
        e_local = e_total // tp_size
        out, aux = _moe_local(x2d, router, wg, wu, wd,
                              top_k=cfg.top_k, capacity=cap, e_total=e_total,
                              e_offset=idx * e_local, act=cfg.act)
        if use_scatter:
            out = jax.lax.psum_scatter(out, tp, scatter_dimension=0,
                                       tiled=True)
        else:
            out = jax.lax.psum(out, tp)
        mean_axes = tuple(prof.dp) + (tp,)
        aux = jax.tree.map(lambda v: jax.lax.pmean(v, mean_axes), aux)
        return out, aux

    # Tokens: sharded over dp axes, replicated over tp.  Experts: sharded on E.
    dp_ax = tuple(prof.dp)
    tok_out_spec = P(dp_ax + (tp,) if use_scatter else prof.dp_spec, None)
    in_specs = (P(prof.dp_spec, None), P(None, None),
                P(tp, None, fs), P(tp, None, fs), P(tp, fs, None))
    out_specs = (tok_out_spec,
                 {"load_balance": P(), "router_z": P()})
    fn = jax.shard_map(shard_fn, mesh=prof.mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    out, aux = fn(x2d, p["router"], p["wg"], p["wu"], p["wd"])
    return out.reshape(b, s, d), aux


# --------------------------------------------------------------------------- #
# RG-LRU recurrent block (recurrentgemma) — the paper's scan, gated            #
# --------------------------------------------------------------------------- #
def init_rglru_block(key, cfg, dtype, prof):
    d, dr = cfg.d_model, cfg.d_rnn
    ks = jax.random.split(key, 7)
    tp_r = _tp_dim(prof, dr)
    fs = _fsdp_dim(prof, d)
    # Recurrence magnitude init: DPG-style controlled spectrum on (0.9, 0.999)
    # (paper's "direct selection of eigenvalues" applied to the RG-LRU gate).
    u = np.random.default_rng(0).uniform(0.9, 0.999, size=dr)
    c = 8.0
    # a = exp(-c * softplus(lam_p)) at r=1  =>  softplus(lam_p) = -log(u)/c
    sp = -np.log(u) / c
    lam_p = np.log(np.expm1(sp))
    p = {
        "w_x": _dense_init(ks[0], (d, dr), dtype),      # recurrence branch
        "w_gate": _dense_init(ks[1], (d, dr), dtype),   # gelu gate branch
        "conv": (jax.random.normal(ks[2], (cfg.conv_width, dr), jnp.float32)
                 * 0.1).astype(dtype),
        "w_a": _dense_init(ks[3], (dr, dr), dtype),
        "b_a": jnp.zeros((dr,), dtype),
        "w_i": _dense_init(ks[4], (dr, dr), dtype),
        "b_i": jnp.zeros((dr,), dtype),
        "lam_p": jnp.asarray(lam_p, jnp.float32),
        "w_out": _dense_init(ks[5], (dr, d), dtype),
    }
    s = {"w_x": P(fs, tp_r), "w_gate": P(fs, tp_r), "conv": P(None, tp_r),
         "w_a": P(None, tp_r), "b_a": P(tp_r), "w_i": P(None, tp_r),
         "b_i": P(tp_r), "lam_p": P(tp_r), "w_out": P(tp_r, fs)}
    return p, s


def _causal_conv(x, w, state=None):
    """Depthwise causal conv over time.  x: (B, S, C); w: (W, C).
    state: (B, W-1, C) trailing context for decode.  Returns (y, new_state)."""
    width = w.shape[0]
    if state is None:
        pad = jnp.zeros(x.shape[:1] + (width - 1,) + x.shape[2:], x.dtype)
    else:
        pad = state
    xp = jnp.concatenate([pad, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(width))
    new_state = xp[:, -(width - 1):] if width > 1 else None
    return y, new_state


def _rglru_core(p, xr, h0=None, scan_method="chunked", prof=NULL_PROFILE):
    """xr: (B, S, dr) post-conv.  Returns (states (B,S,dr), last_state)."""
    c = 8.0
    # Perf iteration (§Perf, recurrentgemma train): the (dr, dr) gate matmuls
    # from a dr-sharded input made XLA psum the full (B,S,dr) f32 gate
    # pre-activations (2.6 GiB x 2 gates x layer).  Gathering the bf16 INPUT
    # once (16x fewer bytes) and computing output-sharded gate slices locally
    # replaces both psums; the recurrence itself stays dr-sharded (the
    # paper's element-wise update needs no collectives).
    xg = constrain(xr, P(prof.dp_spec, None, None), prof)
    r = jax.nn.sigmoid(xg @ p["w_a"] + p["b_a"]).astype(jnp.float32)
    i = jax.nn.sigmoid(xg @ p["w_i"] + p["b_i"]).astype(jnp.float32)
    log_a = -c * r * jax.nn.softplus(p["lam_p"])     # (B, S, dr), <= 0
    a = jnp.exp(log_a)
    gated_x = (i * xr.astype(jnp.float32)) * jnp.sqrt(
        jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    h = scan_mod.diag_scan(a, gated_x, h0, method=scan_method)
    return h.astype(xr.dtype), h[:, -1]


def apply_rglru_block(p, x, cfg, *, cache=None, scan_method="chunked",
                      prof=NULL_PROFILE):
    """Griffin-style recurrent block.  cache: {"conv": (B,W-1,dr), "h": (B,dr)}."""
    xr = x @ p["w_x"]
    gate = jax.nn.gelu(x @ p["w_gate"])
    conv_state = None if cache is None else cache["conv"]
    xc, new_conv = _causal_conv(xr, p["conv"], conv_state)
    h0 = None if cache is None else cache["h"]
    if cache is not None and x.shape[1] == 1:
        # Decode fast-path: ONE realified step, no scan at all (the paper's
        # O(N) update in its purest form).
        hs, last = _rglru_core(p, xc, h0, scan_method="sequential", prof=prof)
    else:
        hs, last = _rglru_core(p, xc, h0, scan_method=scan_method, prof=prof)
    out = (hs * gate) @ p["w_out"]
    new_cache = {"conv": new_conv, "h": last.astype(jnp.float32)}
    return out, new_cache


# --------------------------------------------------------------------------- #
# mLSTM (matrix memory, chunkwise) and sLSTM (scalar memory, stabilized)       #
# --------------------------------------------------------------------------- #
def init_mlstm(key, cfg, dtype, prof):
    d, h = cfg.d_model, cfg.n_heads
    hd = d // h
    ks = jax.random.split(key, 6)
    tp_h = _tp_dim(prof, h)
    p = {
        "wq": _dense_init(ks[0], (d, h, hd), dtype),
        "wk": _dense_init(ks[1], (d, h, hd), dtype),
        "wv": _dense_init(ks[2], (d, h, hd), dtype),
        "wi": _dense_init(ks[3], (d, h), dtype),
        "wf": _dense_init(ks[4], (d, h), dtype),
        "bf": jnp.full((h,), 3.0, dtype),   # open forget gates at init
        "wo": _dense_init(ks[5], (h, hd, d), dtype),
    }
    s = {"wq": P(None, tp_h, None), "wk": P(None, tp_h, None),
         "wv": P(None, tp_h, None), "wi": P(None, tp_h), "wf": P(None, tp_h),
         "bf": P(tp_h), "wo": P(tp_h, None, None)}
    return p, s


def apply_mlstm(p, x, cfg, *, cache=None, chunk=64):
    """Chunkwise mLSTM: C_t = f_t C + i_t k v^T; h = C^T q / max(|n.q|, 1).

    Simplification recorded in DESIGN.md: i = sigmoid (bounded) instead of
    exp-with-max-stabilizer.  cache: {"C": (B,H,hd,hd), "n": (B,H,hd), "len"}.
    """
    b, s, d = x.shape
    h = cfg.n_heads
    hd = d // h
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"]).astype(jnp.float32) * hd ** -0.5
    k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"]).astype(jnp.float32)
    v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"]).astype(jnp.float32)
    ig = jax.nn.sigmoid(jnp.einsum("bsd,dh->bhs", x, p["wi"])
                        ).astype(jnp.float32)
    fg = jax.nn.sigmoid(jnp.einsum("bsd,dh->bhs", x, p["wf"])
                        + p["bf"][None, :, None].astype(jnp.float32))

    c0 = jnp.zeros((b, h, hd, hd), jnp.float32) if cache is None else cache["C"]
    n0 = jnp.zeros((b, h, hd), jnp.float32) if cache is None else cache["n"]

    if s % chunk != 0:
        chunk = s  # single chunk for odd smoke shapes
    nc = s // chunk
    qc = q.reshape(b, h, nc, chunk, hd)
    kc = k.reshape(b, h, nc, chunk, hd)
    vc = v.reshape(b, h, nc, chunk, hd)
    ic = ig.reshape(b, h, nc, chunk)
    fc = fg.reshape(b, h, nc, chunk)

    def chunk_step(carry, inp):
        C, n = carry
        qk, kk, vk, ik, fk = inp  # (b,h,chunk,hd) / (b,h,chunk)
        logf = jnp.log(jnp.maximum(fk, 1e-9))
        cum = jnp.cumsum(logf, axis=-1)               # (b,h,c) log prod_{<=t}
        total = cum[..., -1:]
        # intra-chunk decay matrix D[t,s] = exp(cum_t - cum_s) * i_s, s<=t
        dec = cum[..., :, None] - cum[..., None, :]
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))
        amat = jnp.where(tri, jnp.exp(dec) * ik[..., None, :], 0.0)
        scores = jnp.einsum("bhtd,bhsd->bhts", qk, kk) * amat
        inter_q = jnp.exp(cum)                          # P_t
        num = jnp.einsum("bhts,bhsd->bhtd", scores, vk) + \
            inter_q[..., None] * jnp.einsum("bhtd,bhde->bhte", qk, C)
        den = scores.sum(-1) + inter_q * jnp.einsum("bhtd,bhd->bht", qk, n)
        out = num / jnp.maximum(jnp.abs(den), 1.0)[..., None]
        # state update: C' = F C + sum_s (F/P_s) i_s k_s v_s^T
        wts = jnp.exp(total - cum) * ik                 # (b,h,c)
        C = jnp.exp(total)[..., None] * C + jnp.einsum(
            "bhs,bhsd,bhse->bhde", wts, kk, vk)
        n = jnp.exp(total) * n + jnp.einsum("bhs,bhsd->bhd", wts, kk)
        return (C, n), out

    (c_f, n_f), outs = jax.lax.scan(
        chunk_step, (c0, n0),
        tuple(jnp.moveaxis(t, 2, 0) for t in (qc, kc, vc, ic, fc)))
    hs = jnp.moveaxis(outs, 0, 2).reshape(b, h, s, hd)
    out = jnp.einsum("bhsk,hkd->bsd", hs.astype(x.dtype), p["wo"])
    new_cache = {"C": c_f, "n": n_f}
    return out, new_cache


def init_slstm(key, cfg, dtype, prof):
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    tp_d = _tp_dim(prof, d)
    p = {"wz": _dense_init(ks[0], (d, d), dtype),
         "wi": _dense_init(ks[1], (d, d), dtype),
         "wf": _dense_init(ks[2], (d, d), dtype),
         "bf": jnp.full((d,), 3.0, dtype),
         "wog": _dense_init(ks[3], (d, d), dtype),
         "wo": _dense_init(ks[4], (d, d), dtype)}
    s = {"wz": P(None, tp_d), "wi": P(None, tp_d), "wf": P(None, tp_d),
         "bf": P(tp_d), "wog": P(None, tp_d), "wo": P(tp_d, None)}
    return p, s


def apply_slstm(p, x, cfg, *, cache=None, scan_method="chunked"):
    """Parallel sLSTM (input-conditioned gates, exp-input-gate with max-plus
    stabilizer scan; hidden-to-gate recurrence dropped — see DESIGN.md).

    cache: {"c": (B,d), "n": (B,d), "m": (B,d)}.
    """
    zf = jnp.tanh(x @ p["wz"]).astype(jnp.float32)
    itil = (x @ p["wi"]).astype(jnp.float32)
    ftil = jax.nn.log_sigmoid((x @ p["wf"] + p["bf"]).astype(jnp.float32))
    og = jax.nn.sigmoid((x @ p["wog"]).astype(jnp.float32))

    m_prev0 = None if cache is None else cache["m"]
    # Stabilizer: m_t = max(f~_t + m_{t-1}, i~_t) — max-plus associative scan.
    def combine(e1, e2):
        f1, i1 = e1
        f2, i2 = e2
        return f1 + f2, jnp.maximum(i1 + f2, i2)

    ft = jnp.moveaxis(ftil, 1, 0)
    it = jnp.moveaxis(itil, 1, 0)
    if m_prev0 is not None:
        it = it.at[0].set(jnp.maximum(ft[0] + m_prev0, it[0]))
        # (fold carry into first element like diag_scan h0 folding)
    _, m = jax.lax.associative_scan(combine, (ft, it), axis=0)
    m = jnp.moveaxis(m, 0, 1)  # (B, S, d)
    m0 = (jnp.zeros_like(m[:, 0]) if m_prev0 is None else m_prev0)
    m_prev = jnp.concatenate([m0[:, None], m[:, :-1]], axis=1)
    fprime = jnp.exp(ftil + m_prev - m)
    iprime = jnp.exp(itil - m)
    c0 = None if cache is None else cache["c"]
    n0 = None if cache is None else cache["n"]
    c = scan_mod.diag_scan(fprime, iprime * zf, c0, method=scan_method)
    n = scan_mod.diag_scan(fprime, iprime, n0, method=scan_method)
    hval = og * c / jnp.maximum(jnp.abs(n), 1.0)
    out = hval.astype(x.dtype) @ p["wo"]
    new_cache = {"c": c[:, -1], "n": n[:, -1], "m": m[:, -1]}
    return out, new_cache


# --------------------------------------------------------------------------- #
# Linear Reservoir layer — the paper's model as an LM sequence mixer           #
# --------------------------------------------------------------------------- #
def init_reservoir(key, cfg, dtype, prof, *, n_state=None, distribution="noisy_golden",
                   trainable=True):
    """LRU-style diagonal complex recurrence with DPG spectral init.

    State stored realified (Appendix A): lam as (nu, theta) polar params so
    |lambda| = exp(-exp(nu)) < 1 always (trainable-stable), or frozen from a
    DPG distribution.  gamma = sqrt(1 - |lam|^2) input normalization.
    """
    d = cfg.d_model
    n = n_state or d
    ks = jax.random.split(key, 3)
    try:  # concrete seed when eager; fixed seed under eval_shape/jit tracing
        seed = int(jax.random.randint(ks[0], (), 0, 1 << 30))
    except jax.errors.ConcretizationTypeError:
        seed = 0
    spec, _ = spectral.dpg(2 * n, 0.95, seed, distribution)
    lam = spec.lam_cpx[:n] if spec.n_cpx >= n else np.concatenate(
        [spec.lam_cpx, 0.9 * np.exp(1j * np.linspace(0.1, 3.0, n - spec.n_cpx))])
    mag = np.clip(np.abs(lam), 1e-3, 0.999)
    nu = np.log(-np.log(mag))
    theta = np.angle(lam)
    tp_n = _tp_dim(prof, n)
    p = {
        "nu": jnp.asarray(nu, jnp.float32),
        "theta": jnp.asarray(theta, jnp.float32),
        "b_re": _dense_init(ks[1], (d, n), dtype),
        "b_im": _dense_init(ks[1], (d, n), dtype),
        "c_re": _dense_init(ks[2], (n, d), dtype),
        "c_im": _dense_init(ks[2], (n, d), dtype),
        "dskip": jnp.ones((d,), dtype),
    }
    s = {"nu": P(tp_n), "theta": P(tp_n), "b_re": P(None, tp_n),
         "b_im": P(None, tp_n), "c_re": P(tp_n, None), "c_im": P(tp_n, None),
         "dskip": P(None)}
    return p, s


def apply_reservoir(p, x, cfg, *, cache=None, scan_method="chunked",
                    use_pallas=False):
    """x: (B, S, d) -> (B, S, d).  cache: {"h_re": (B,N), "h_im": (B,N)}."""
    mag = jnp.exp(-jnp.exp(p["nu"]))
    a = mag * jnp.exp(1j * p["theta"])                 # (N,) complex64
    gamma = jnp.sqrt(jnp.maximum(1.0 - mag * mag, 1e-8))
    xf = x.astype(jnp.float32)
    u_re = xf @ p["b_re"].astype(jnp.float32) * gamma
    u_im = xf @ p["b_im"].astype(jnp.float32) * gamma
    u = jax.lax.complex(u_re, u_im)
    h0 = None if cache is None else jax.lax.complex(cache["h_re"], cache["h_im"])
    if use_pallas:
        from repro.kernels import ops as kops
        h = kops.diag_scan(a.astype(jnp.complex64), u.astype(jnp.complex64),
                           h0)
    else:
        h = scan_mod.diag_scan(a, u, h0, method=scan_method)
    y = (h.real @ p["c_re"].astype(jnp.float32)
         - h.imag @ p["c_im"].astype(jnp.float32))
    out = y.astype(x.dtype) + x * p["dskip"]
    new_cache = {"h_re": h[:, -1].real, "h_im": h[:, -1].imag}
    return out, new_cache
