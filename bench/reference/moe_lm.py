"""Plain float32 reference of a decoder-only mixture-of-experts language
model, as the published configurations describe it (Qwen3-MoE,
``Qwen3MoeForCausalLM``; OLMoE, ``OlmoeForCausalLM``):

  * each layer: ``x += o_proj(attn(rms(x)))`` then ``x += moe(rms(x))``;
  * attention: q/k/v projections, RMSNorm of q and k -- over each head's
    ``head_dim`` values (Qwen3) or over the whole q and k projections
    (OLMoE) -- rotary embedding (the rotate-half form, base
    ``rope_theta``), grouped-query causal softmax attention;
  * experts: a softmax router over all ``num_experts``, the top
    ``num_experts_per_tok``, renormalised to sum to one only where
    ``norm_topk_prob`` is true; SwiGLU experts
    ``down(silu(gate(x)) * up(x))`` weighted by those probabilities;
  * a final RMSNorm and the output head.

Everything runs in float32 with "highest" matmul precision.  A layer is one
jitted call that takes the stacked weights and a layer index, so one
layer's weights are upcast at a time.  The experts are evaluated one after
another over every token, each weighted by its gate (zero where the token
did not choose it): the same sum as evaluating only each token's chosen
experts.  Imports nothing of the program.

Norm weights are the scales the description multiplies by.  ``low=True``
gives the control: every matrix product on float8 (e4m3) operands, each
operand scaled per tensor into e4m3's range -- one precision step below the
bfloat16 the configurations state.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0

# where each architecture normalises q and k: each head, or the projection
QK_NORM_SCOPE = {"qwen3_moe": "head", "olmoe": "projection"}


@dataclass(frozen=True)
class Arch:
    """The sizes and choices of the published configuration."""
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    experts: int
    top_k: int
    vocab: int
    layers: int
    eps: float
    theta: float
    norm_topk: bool
    qk_scope: str


def arch(cfg: Dict[str, Any]) -> Arch:
    """``Arch`` of a configuration file (the published ``config.json``
    keys).  The expert width is ``moe_intermediate_size`` where the
    configuration has one (Qwen3-MoE) and ``intermediate_size`` otherwise
    (OLMoE)."""
    mt = cfg["model_type"]
    if mt not in QK_NORM_SCOPE:
        raise ValueError(f"no reference for model_type {mt!r}")
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return Arch(
        d=d, heads=H, kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or d // H,
        ff=cfg.get("moe_intermediate_size") or cfg["intermediate_size"],
        experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        vocab=cfg["vocab_size"], layers=cfg["num_hidden_layers"],
        eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
        norm_topk=bool(cfg["norm_topk_prob"]), qk_scope=QK_NORM_SCOPE[mt])


# ------------------------------------------------------------------ pieces

def fp8(x: jax.Array) -> jax.Array:
    """``x`` rounded to e4m3 under one scale for the tensor, back in
    float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def matmul(a, b, low: bool):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if low:
        a, b = fp8(a), fp8(b)
    return jnp.matmul(a, b, precision=HI)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x (T, heads, hd) at positions ``pos`` (T,): the rotate-half form."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]       # (T, hd/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(a: Arch, w, x, low):
    T = x.shape[0]
    H, KV, hd = a.heads, a.kv_heads, a.head_dim
    q = matmul(x, w["wq"], low)
    k = matmul(x, w["wk"], low)
    v = matmul(x, w["wv"], low).reshape(T, KV, hd)
    if a.qk_scope == "projection":
        q = rms(q, w["q_norm"], a.eps).reshape(T, H, hd)
        k = rms(k, w["k_norm"], a.eps).reshape(T, KV, hd)
    else:
        q = rms(q.reshape(T, H, hd), w["q_norm"], a.eps)
        k = rms(k.reshape(T, KV, hd), w["k_norm"], a.eps)
    pos = jnp.arange(T)
    q, k = rope(q, pos, a.theta), rope(k, pos, a.theta)
    G = H // KV
    qg = q.reshape(T, KV, G, hd)
    if low:
        qg, k, v = fp8(qg), fp8(k), fp8(v)
    s = jnp.einsum("tkgd,skd->kgts", qg, k, precision=HI) * hd ** -0.5
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if low:
        p = fp8(p)
    o = jnp.einsum("kgts,skd->tkgd", p, v, precision=HI).reshape(T, H * hd)
    return matmul(o, w["wo"], low)


def gates(a: Arch, w, x, low):
    """(T, E) weight of each expert for each token: the top-k softmax
    probabilities (renormalised where the configuration says so), zero
    elsewhere."""
    probs = jax.nn.softmax(matmul(x, w["router"], low), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, a.top_k)
    if a.norm_topk:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    T = x.shape[0]
    return jnp.zeros((T, a.experts), jnp.float32).at[
        jnp.arange(T)[:, None], top_e].set(top_p)


def experts(a: Arch, w, x, g, low):
    def one(y, ew):
        wg, wu, wd, ge = ew
        h = jax.nn.silu(matmul(x, wg, low)) * matmul(x, wu, low)
        return y + ge[:, None] * matmul(h, wd, low), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (w["w_gate"], w["w_up"], w["w_down"], g.T))
    return y


# ------------------------------------------------------------------ forward

@partial(jax.jit, static_argnames=("a", "low"))
def layer(a: Arch, stacked, i, x, low: bool = False):
    """Layer ``i`` of the stacked weights applied to ``x`` (T, d)."""
    w = jax.tree_util.tree_map(lambda s: s[i], stacked)
    x = x + attention(a, w, rms(x, w["ln1"], a.eps), low)
    h = rms(x, w["ln2"], a.eps)
    return x + experts(a, w, h, gates(a, w, h, low), low)


@partial(jax.jit, static_argnames=("a", "low"))
def head(a: Arch, final_norm, out, x, rows, low: bool = False):
    """Logits (len(rows), V) at the positions ``rows`` of ``x``."""
    h = rms(x[rows], final_norm.astype(jnp.float32), a.eps)
    return matmul(h, out, low)


@partial(jax.jit, static_argnames=("a",))
def embed(a: Arch, table, tokens):
    return table[tokens].astype(jnp.float32)


def hidden(a: Arch, w: Dict[str, Any], tokens: jax.Array,
           low: bool = False) -> jax.Array:
    """The last layer's output (T, d) over ``tokens`` (T,).  ``w``:
    ``embed`` (V, d), ``final_norm`` (d,), ``head`` (d, V) and ``layers``,
    the per-layer weights stacked on a leading axis: ``ln1``, ``ln2``,
    ``q_norm``, ``k_norm`` (scales), ``wq``, ``wk``, ``wv``, ``wo``,
    ``router`` (d, E), ``w_gate``, ``w_up`` (E, d, ff), ``w_down``
    (E, ff, d).  Positions after the last one wanted may be padding: the
    attention is causal, so they change nothing before them."""
    x = embed(a, w["embed"], tokens)
    for i in range(a.layers):
        x = layer(a, w["layers"], i, x, low)
    return x


def logits(a: Arch, w: Dict[str, Any], tokens: jax.Array, rows: jax.Array,
           low: bool = False) -> jax.Array:
    """The whole forward over ``tokens`` and the logits at ``rows``."""
    return head(a, w["final_norm"], w["head"], hidden(a, w, tokens, low),
                rows, low)
