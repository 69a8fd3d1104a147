"""Plain reference of the ``deepseek_v3`` forward pass, independent of
the code under test: straight ``jax.numpy``, no cache, no kernel, no
batching, attention un-absorbed, experts by a loop with a mask.  Call it
under ``jax.default_matmul_precision("highest")`` with float32 weights
for the reference proper.  It reads the program's parameter tree
(``embed, head, norm_f, dense{...}, moe{...}`` with the expert layers
stacked on a leading axis) and takes nothing else from the program.
``benchmark/lib/reference_deepseek_v3.py`` is a copy of this file; a
test holds the two to the same numbers.

The model, as published (``config.json`` of
kakaocorp/kanana-2-30b-a3b-instruct-2601, ``model_type: deepseek_v3``):
hidden 2048; 48 layers (``first_k_dense_replace`` 1, ``moe_layer_freq``
1: layer 0 dense, 47 expert layers); 32 heads; ``qk_nope_head_dim`` 128,
``qk_rope_head_dim`` 64 (``qk_head_dim`` 192), ``v_head_dim`` 128;
``kv_lora_rank`` 512; ``q_lora_rank`` null; dense ``intermediate_size``
6144; ``n_routed_experts`` 128 x ``moe_intermediate_size`` 768,
``num_experts_per_tok`` 6, ``n_shared_experts`` 2 (one gated MLP of
width 1536), ``scoring_func`` sigmoid, ``topk_method`` noaux_tc,
``n_group`` 1, ``topk_group`` 1, ``norm_topk_prob`` true,
``routed_scaling_factor`` 2.448; RMSNorm eps 1e-6; ``rope_theta`` 1e6,
``rope_interleave`` true, no rope scaling; vocabulary 128256, head
untied; no biases.  For a layer's input ``x`` at position ``p``::

    h      = rmsnorm(x; g_in)
    q      = h Wq                      -> [32, 192] = [q_nope(128) | q_rope(64)] per head
    ckr    = h Wdkv                    -> [576]     = [c_raw(512) | kr_raw(64)]
    c      = rmsnorm(c_raw; g_kv)  ;  kr = rope(kr_raw, p)  ;  q_rope = rope(q_rope, p)
    [k_nope | v] = c Wukv              -> [32, 128 + 128]
    score_j = (q_nope . k_nope_j + q_rope . kr_j) / sqrt(192)    causal, softmax in float32
    x      = x + concat_heads(sum_j P_j v_j) Wo
    h2     = rmsnorm(x; g_post)
    layer 0 :  x = x + (silu(h2 Wg) * (h2 Wu)) Wd
    layer>=1:  s = sigmoid(h2 Wr)                               float32
               chosen = top6(s + b)           b: e_score_correction_bias, selection only
               w = s[chosen] / (sum s[chosen] + 1e-20) * 2.448
               x = x + sum_e w_e * mlp_e(h2) + mlp_shared(h2)
    logits = rmsnorm(x_last; g_f) Whead

``rope`` rotates ADJACENT pairs ``(2i, 2i+1)`` of the rope columns at
frequency ``theta^(-2i/d)`` (the interleaved convention the weights are
stored in).  Hugging Face's ``rotate_half`` form first permutes the
columns to ``[0, 2, 4, ..., 1, 3, 5, ...]`` and rotates column ``i``
with column ``i + d/2``: its output is this one's under that same
permutation.  Scores do not depend on it, as q and k share it.

Departures from the published model: none in the mathematics.  Ties in
the top-k go to the lower expert id (a stable sort), which the
published code leaves to ``torch.topk``.
"""
import math

import jax
import jax.numpy as jnp


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    """x [N, ..., d] at positions pos [N]: pairs (2i, 2i+1) rotated."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) * inv
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     even * jnp.sin(ang) + odd * jnp.cos(ang)], -1)
    return out.reshape(x.shape)


def gated_mlp(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def attention(x, blk, hp):
    """x [N, H] -> the attention sublayer's output [N, H] (no residual)."""
    n = x.shape[0]
    nh, nope, dr = hp["num_attention_heads"], hp["qk_nope_head_dim"], \
        hp["qk_rope_head_dim"]
    rank, dv = hp["kv_lora_rank"], hp["v_head_dim"]
    pos = jnp.arange(n)
    h = rmsnorm(x, blk["g_in"], hp["rms_norm_eps"])
    q = (h @ blk["wq"]).reshape(n, nh, nope + dr)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], pos, hp["rope_theta"])
    ckr = h @ blk["wdkv"]
    c = rmsnorm(ckr[:, :rank], blk["g_kv"], hp["rms_norm_eps"])
    kr = rope(ckr[:, rank:], pos, hp["rope_theta"])
    kv = (c @ blk["wukv"]).reshape(n, nh, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
              + jnp.einsum("qhd,kd->hqk", q_rope, kr)) / math.sqrt(nope + dr)
    scores = jnp.where(jnp.tril(jnp.ones((n, n), bool)), scores, -jnp.inf)
    probs = jax.nn.softmax(scores, -1)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(n, nh * dv) \
        @ blk["wo"]


def route(h2, wr, b, hp):
    """h2 [N, H] -> (chosen [N, k], weights [N, k])."""
    s = jax.nn.sigmoid(h2 @ wr)
    chosen = jnp.argsort(-(s + b), axis=-1, stable=True)[
        :, :hp["num_experts_per_tok"]]
    w = jnp.take_along_axis(s, chosen, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * hp["routed_scaling_factor"]
    return chosen, w


def experts(h2, blk, hp):
    """Every expert over every token, masked to the tokens that chose
    it: a loop, so nothing about dispatch is shared with the program."""
    chosen, w = route(h2, blk["wr"], blk["b"], hp)

    def one(y, expert):
        e, eg, eu, ed = expert
        weight = jnp.sum(jnp.where(chosen == e, w, 0.0), -1)      # [N]
        return y + weight[:, None] * gated_mlp(h2, eg, eu, ed), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h2), (
        jnp.arange(blk["wr"].shape[-1]), blk["eg"], blk["eu"], blk["ed"]))
    return y + gated_mlp(h2, blk["sg"], blk["su"], blk["sd"])


def layer(x, blk, hp):
    x = x + attention(x, blk, hp)
    h2 = rmsnorm(x, blk["g_post"], hp["rms_norm_eps"])
    if "wr" in blk:
        return x + experts(h2, blk, hp)
    return x + gated_mlp(h2, blk["wg"], blk["wu"], blk["wd"])


def moe_layer(params, i):
    """Layer ``i + 1``: the ``i``-th of the stacked expert layers."""
    return {k: v[i] for k, v in params["moe"].items()}


def logits(params, tokens, hp):
    """tokens [N] int32 -> logits [N, V], in the weights' own dtype."""
    x = layer(params["embed"][tokens], params["dense"], hp)
    for i in range(params["moe"]["wr"].shape[0]):
        x = layer(x, moe_layer(params, i), hp)
    return rmsnorm(x, params["norm_f"], hp["rms_norm_eps"]) @ params["head"]


def layer_at_a_time(hp):
    """``rows_of(params, tokens [N], rows [R]) -> logits [R, V]``: the
    same forward for weights whose float32 copy does not fit whole (the
    benchmark's 5B parameters in bf16).  Each layer is one jitted call
    that upcasts the leaves it is handed, so one layer's float32 copy
    lives at a time; the head runs on ``rows`` only."""
    f32 = jnp.float32

    def up(tree):
        return jax.tree_util.tree_map(lambda x: x.astype(f32), tree)

    one = jax.jit(lambda x, blk: layer(x, up(blk), hp))
    first = jax.jit(lambda table, t: table[t].astype(f32))
    last = jax.jit(lambda x, rows, g, w: rmsnorm(
        x[rows], g.astype(f32), hp["rms_norm_eps"]) @ w.astype(f32))

    def rows_of(params, tokens, rows):
        x = one(first(params["embed"], tokens), params["dense"])
        for i in range(params["moe"]["wr"].shape[0]):
            x = one(x, moe_layer(params, i))
        return last(x, rows, params["norm_f"], params["head"])

    return rows_of
