"""Plain reference of the ``ouro`` forward pass (a looped language
model), independent of the code under test: straight ``jax.numpy``, one
sequence, no cache, no kernel, no batching, a Python loop over the
passes and the layers.  Call it under
``jax.default_matmul_precision("highest")`` with float32 weights for the
reference proper.  It reads the program's parameter tree (``embed, head,
norm_f, gate_w, gate_b, layers{...}`` with the layers stacked on a
leading axis; ``wq`` and ``wk`` stored (out, in), the others (in, out))
and takes nothing else from the program.
``benchmark/lib/reference_ouro.py`` is a copy of this file; a test holds
the two to the same numbers.

The model, as published (``config.json`` of ByteDance/Ouro-2.6B,
``model_type: ouro``, arXiv 2510.25741, and the model's public modeling
file for what the config does not carry): hidden 2048; 48 layers; 16
heads of 128, as many key/value heads; ``intermediate_size`` 5632, silu;
RMSNorm eps 1e-6; ``rope_theta`` 1e6, no scaling; vocabulary 49152, head
untied; no biases but the gate's; ``total_ut_steps`` 4,
``early_exit_threshold`` 1.  With ``N(x; g) = x * rsqrt(mean(x^2) + eps)
* g``, one layer on ``x`` at positions ``p``::

    h = N(x; g1) ;  q, k, v = h Wq^T, h Wk^T, h Wv      -> [16, 128] each
    q, k = rope(q, p), rope(k, p)
    x = x + N(softmax(q k^T / sqrt(128)) v  Wo ; g2)     causal, float32 softmax
    h = N(x; g3)
    x = x + N((silu(h Wg) * (h Wu)) Wd ; g4)             four norms a layer

and the model, for ``t = 1..4``::

    x^0 = E[tokens]
    x^t = N(layer_48(... layer_1(x^(t-1))); g_f)         the same 48 layers every pass
    lam_t = sigmoid(w_e . x^t + b_e)
    p_t = lam_t prod_{j<t}(1 - lam_j)  (t < 4),  p_4 the remainder
    leave at the first t whose  sum_{j<=t} p_j >= early_exit_threshold
    logits = Whead x^(that t)

``rope`` is Hugging Face's ``rotate_half``: with ``f_i = p *
theta^(-2i/128)`` for ``i < 64``, column ``i`` becomes ``x_i cos f_i -
x_(i+64) sin f_i`` and column ``i + 64`` becomes ``x_(i+64) cos f_i +
x_i sin f_i``.

Departures from the published model: none in the mathematics.  Where no
cumulative probability of passes 1..T-1 reaches the threshold the token
leaves at the last pass, as the published ``argmax`` over the mask with
its fall-back does.
"""
import math

import jax
import jax.numpy as jnp


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, theta):
    """x [N, nh, hd] at positions 0..N-1: rotate_half over the head."""
    n, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / hd)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None, None] * freq    # [N,1,half]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def attention(h, blk, hp):
    """h [N, H] (already normed) -> the attention's output [N, H]."""
    n = h.shape[0]
    nh, hd = hp["num_attention_heads"], hp["head_dim"]
    q = rope((h @ blk["wq"].T).reshape(n, nh, hd), hp["rope_theta"])
    k = rope((h @ blk["wk"].T).reshape(n, nh, hd), hp["rope_theta"])
    v = (h @ blk["wv"]).reshape(n, nh, hd)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    scores = jnp.where(jnp.tril(jnp.ones((n, n), bool)), scores, -jnp.inf)
    probs = jax.nn.softmax(scores, -1)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(n, nh * hd) \
        @ blk["wo"]


def layer(x, blk, hp):
    eps = hp["rms_norm_eps"]
    a = attention(rmsnorm(x, blk["g1"], eps), blk, hp)
    x = x + rmsnorm(a, blk["g2"], eps)
    h = rmsnorm(x, blk["g3"], eps)
    m = (jax.nn.silu(h @ blk["wg"]) * (h @ blk["wu"])) @ blk["wd"]
    return x + rmsnorm(m, blk["g4"], eps)


def layer_of(params, i):
    return {k: v[i] for k, v in params["layers"].items()}


def gate(x, params):
    return jax.nn.sigmoid(jnp.sum(x * params["gate_w"], -1)
                          + params["gate_b"])


def exit_pass(gates, threshold):
    """gates: the T values lam_t of one or many tokens, [T, ...].  The
    0-based pass each token leaves at."""
    T = gates.shape[0]
    remaining = jnp.ones_like(gates[0])
    total = jnp.zeros_like(gates[0])
    cdf = []
    for t in range(T - 1):
        total = total + gates[t] * remaining
        remaining = remaining * (1.0 - gates[t])
        cdf.append(total)
    leave = jnp.full(gates[0].shape, T - 1)
    for t in reversed(range(T - 1)):        # the FIRST pass that reaches it
        leave = jnp.where(cdf[t] >= threshold, t, leave)
    return leave


def logits(params, tokens, hp):
    """tokens [N] int32 -> (logits [N, V], gates [T, N]), in the
    weights' own dtype."""
    n_layers = params["layers"]["wq"].shape[0]
    x = params["embed"][tokens]
    states, gates = [], []
    for _ in range(hp["total_ut_steps"]):
        for i in range(n_layers):
            x = layer(x, layer_of(params, i), hp)
        x = rmsnorm(x, params["norm_f"], hp["rms_norm_eps"])
        states.append(x)
        gates.append(gate(x, params))
    gates = jnp.stack(gates)
    leave = exit_pass(gates, hp["early_exit_threshold"])
    x = jnp.take_along_axis(jnp.stack(states), leave[None, :, None], 0)[0]
    return x @ params["head"], gates


def layer_at_a_time(hp):
    """``rows_of(params, tokens [N], rows [R]) -> logits [R, V]``: the
    same forward for weights whose float32 copy does not fit whole (the
    benchmark's 2.67B parameters are 10.7 GB in float32, beside 5.3 GB
    in bf16).  Each layer is one jitted call that upcasts the leaves it
    is handed, so one layer's float32 copy lives at a time; the head
    runs on ``rows`` only."""
    f32 = jnp.float32
    eps = hp["rms_norm_eps"]

    def up(tree):
        return jax.tree_util.tree_map(lambda x: x.astype(f32), tree)

    one = jax.jit(lambda x, blk: layer(x, up(blk), hp))
    first = jax.jit(lambda table, t: table[t].astype(f32))

    def closed(x, p):
        x = rmsnorm(x, p["norm_f"], eps)
        return x, gate(x, p)

    close = jax.jit(lambda x, p: closed(x, up(p)))
    last = jax.jit(lambda states, gates, rows, w: jnp.take_along_axis(
        states[:, rows], exit_pass(gates[:, rows],
                                   hp["early_exit_threshold"])[None, :, None],
        0)[0] @ w.astype(f32))

    def rows_of(params, tokens, rows):
        small = {k: params[k] for k in ("norm_f", "gate_w", "gate_b")}
        x = first(params["embed"], tokens)
        states, gates = [], []
        for _ in range(hp["total_ut_steps"]):
            for i in range(params["layers"]["wq"].shape[0]):
                x = one(x, layer_of(params, i))
            x, g = close(x, small)
            states.append(x)
            gates.append(g)
        return last(jnp.stack(states), jnp.stack(gates), rows,
                    params["head"])

    return rows_of
