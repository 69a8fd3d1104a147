"""Plain reference of the ``phi4flash`` forward pass (a
decoder-hybrid-decoder language model), independent of the code under
test: straight ``jax.numpy``, one sequence, no cache, no ring, no kernel,
no batching, every layer over every position, the state-space recurrence
one position at a time, attention as full masked score matrices.  Call
it under ``jax.default_matmul_precision("highest")`` with float32 weights
for the reference proper.  It reads the program's parameter tree
(``embed, norm_f, first{ssm, ssm_mlp, attn, attn_mlp}, mid{...},
second{gmu, gmu_mlp, cross, cross_mlp}``; ``first`` and ``second``
stacked on a leading axis of (ssm, window) and (gmu, cross) pairs;
matmul weights (in, out)) and takes nothing else from the program.
``benchmark/lib/reference_phi4flash.py`` is a copy of this file; a test
holds the two to the same numbers.

The model, as published (``config.json`` of
microsoft/Phi-4-mini-flash-reasoning, ``model_type: phi4flash``; arXiv
2507.06607 for the architecture, 2410.05258 for the differential
attention, 2312.00752 for the state-space layer, and the model's public
modeling file for what the config does not carry): hidden 2560; 32
layers; 40 query heads and 20 key/value heads of 64; MLP 10240, silu;
LayerNorm (gain and bias) eps 1e-5; window 512; vocabulary 200064,
embedding and head tied; NO positional term.  Layer ``i``::

    x = x + Mix_i(LN(x)) ;  x = x + W2 (silu(g) * u), [g, u] = W1 LN'(x)

``i`` even, ``i <= 16`` — selective state space (inner 5120, state 16,
dt_rank 160, causal depthwise convolution of 4)::

    [u, z] = W_in h ;  u = silu(conv4(u) + b_c) ;  [d, B, C] = W_x u
    D = softplus(W_dt d + b_dt) ;  A = -exp(A_log)
    S_t = exp(D_t A) * S_(t-1) + (D_t * u_t) B_t^T
    y_t = S_t C_t + D_skip * u_t ;  Mix = W_out (y * silu(z))

and layer 16's ``y`` is the memory ``m``.  ``i`` odd, ``i < 16`` —
differential attention where position ``t`` sees ``t - 511 .. t``; ``i =
17`` the same over everything before; with query heads paired ``(2p, 2p
+ 1)`` and pair ``p`` reading K/V pair ``p' = p // 2``::

    A1 = softmax(q[2p] k[2p']^T / 8) ;  A2 = softmax(q[2p+1] k[2p'+1]^T / 8)
    o_p = RMSNorm_128((A1 - lam A2) [v[2p'], v[2p'+1]]; g) (1 - lam_init)
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init
    lam_init = 0.8 - 0.6 exp(-0.3 i) ;  Mix = Wo [o_0 .. o_19] + bo

``i`` even, ``i >= 18`` — ``Mix = W_out (m * silu(W_in h))``.  ``i``
odd, ``i >= 19`` — the same attention with ``q = W_q h + b_q`` alone and
layer 17's K and V, causal.  Logits ``E^T LN_f(x)``.

Departures from the published model: none in the mathematics as the
configuration file's ``assumed`` block states it.
"""
import math

import jax
import jax.numpy as jnp


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def mlp(x, blk, hp):
    h = layer_norm(x, blk["ln_g"], blk["ln_b"], hp["layer_norm_eps"])
    gu = h @ blk["w1"]
    inner = gu.shape[-1] // 2
    return x + (jax.nn.silu(gu[:, :inner]) * gu[:, inner:]) @ blk["w2"]


def ssm(x, blk, hp, count=None):
    """x [N, H] -> (x + Mix, y [N, di], S [di, s]); one position at a
    time.  ``S`` is the state after the first ``count`` positions (all
    of them when None): later positions do not move it, and their rows
    of the other two mean nothing."""
    n = x.shape[0]
    h = layer_norm(x, blk["ln_g"], blk["ln_b"], hp["layer_norm_eps"])
    uz = h @ blk["w_in"]
    di = uz.shape[-1] // 2
    u, z = uz[:, :di], uz[:, di:]
    taps = blk["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, di), u.dtype), u], 0)
    conv = sum(padded[j:j + n] * blk["conv_w"][j] for j in range(taps))
    u = jax.nn.silu(conv + blk["conv_b"])
    dbc = u @ blk["w_x"]
    r = blk["w_dt"].shape[0]
    s = (dbc.shape[-1] - r) // 2
    step = jax.nn.softplus(dbc[:, :r] @ blk["w_dt"] + blk["b_dt"])
    if count is not None:
        step = jnp.where(jnp.arange(n)[:, None] < count, step, 0.0)
    a = -jnp.exp(blk["a_log"])                               # [di, s]

    def one(state, xs):
        d_t, u_t, b_t, c_t = xs
        state = (jnp.exp(d_t[:, None] * a) * state
                 + (d_t * u_t)[:, None] * b_t[None, :])
        return state, state @ c_t

    last, y = jax.lax.scan(one, jnp.zeros((di, s), x.dtype),
                           (step, u, dbc[:, r:r + s], dbc[:, r + s:]))
    y = y + blk["d_skip"] * u
    return x + (y * jax.nn.silu(z)) @ blk["w_out"], y, last


def lam_init(layer):
    """``layer``: a number, or a traced scalar (one compile a kind)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))


def diff_attention(q, k, v, visible, blk, layer, hp):
    """q [N, nq, hd], k and v [N, nkv, hd], ``visible`` bool [N, N] ->
    the mixer's output [N, H].  One K/V pair at a time (``lax.map``), so
    that the score matrices of one pair's two query pairs live at
    once."""
    n, nq, hd = q.shape
    li = lam_init(layer)
    lam = (jnp.exp(jnp.sum(blk["lam_q1"] * blk["lam_k1"]))
           - jnp.exp(jnp.sum(blk["lam_q2"] * blk["lam_k2"])) + li)

    def weights(qh, kh):
        scores = (qh @ kh.T) / math.sqrt(hd)
        return jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), -1)

    def kv_pair(xs):
        """K/V pair p' = (k[2p'], k[2p'+1]; v likewise) and the query
        pairs 2p' and 2p' + 1 that read it: qs [N, 4, hd]."""
        qs, ks, vs = xs
        both = jnp.concatenate([vs[:, 0], vs[:, 1]], -1)     # [N, 2 * hd]
        outs = []
        for r in range(2):                  # query pair p = 2p' + r
            a1 = weights(qs[:, 2 * r], ks[:, 0])
            a2 = weights(qs[:, 2 * r + 1], ks[:, 1])
            o = (a1 - lam * a2) @ both
            o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True)
                             + hp["layer_norm_eps"]) * blk["subln_g"]
            outs.append(o * (1.0 - li))
        return jnp.concatenate(outs, -1)                     # [N, 4 * hd]

    def by_kv_pair(x, per):                 # [N, h, hd] -> [h / per, N, per, hd]
        return jnp.moveaxis(x.reshape(n, -1, per, hd), 1, 0)

    out = jax.lax.map(kv_pair, (by_kv_pair(q, 4), by_kv_pair(k, 2),
                                by_kv_pair(v, 2)))            # [g, N, 4 * hd]
    return jnp.moveaxis(out, 0, 1).reshape(n, -1) @ blk["wo"] + blk["bo"]


def heads(x, hd):
    return x.reshape(x.shape[0], -1, hd)


def attention(x, blk, layer, window, hp):
    """A window or full layer: x [N, H] -> (x + Mix, k, v)."""
    n, width = x.shape
    hd = width // hp["num_attention_heads"]
    kvw = hp["num_key_value_heads"] * hd
    h = layer_norm(x, blk["ln_g"], blk["ln_b"], hp["layer_norm_eps"])
    qkv = h @ blk["wqkv"] + blk["bqkv"]
    q, k, v = (heads(qkv[:, :width], hd), heads(qkv[:, width:width + kvw], hd),
               heads(qkv[:, width + kvw:], hd))
    t = jnp.arange(n)
    visible = t[None, :] <= t[:, None]
    if window:
        visible &= t[None, :] > t[:, None] - window
    return x + diff_attention(q, k, v, visible, blk, layer, hp), k, v


def gmu(x, blk, m, hp):
    h = layer_norm(x, blk["ln_g"], blk["ln_b"], hp["layer_norm_eps"])
    return x + (m * jax.nn.silu(h @ blk["w_in"])) @ blk["w_out"]


def cross(x, blk, layer, k, v, hp):
    n, width = x.shape
    hd = width // hp["num_attention_heads"]
    h = layer_norm(x, blk["ln_g"], blk["ln_b"], hp["layer_norm_eps"])
    q = heads(h @ blk["wq"] + blk["bq"], hd)
    t = jnp.arange(n)
    return x + diff_attention(q, k, v, t[None, :] <= t[:, None], blk, layer,
                              hp)


def pair_of(stack, i):
    return jax.tree_util.tree_map(lambda w: w[i], stack)


def first_pair(x, blk, i, hp, count=None):
    """Layers 2i (state space) and 2i + 1 (window): (x, the state-space
    layer's state after ``count`` positions)."""
    x, _, state = ssm(x, blk["ssm"], hp, count)
    x = mlp(x, blk["ssm_mlp"], hp)
    x, _, _ = attention(x, blk["attn"], 2 * i + 1, hp["sliding_window"], hp)
    return mlp(x, blk["attn_mlp"], hp), state


def middle(x, blk, half, hp):
    """Layer ``half`` (its ``y`` is the memory) and ``half + 1`` (its K
    and V are the later layers')."""
    x, m, _ = ssm(x, blk["ssm"], hp)
    x = mlp(x, blk["ssm_mlp"], hp)
    x, k, v = attention(x, blk["attn"], half + 1, None, hp)
    return mlp(x, blk["attn_mlp"], hp), m, k, v


def second_pair(x, blk, i, half, m, k, v, hp):
    """Layers half + 2 + 2i (memory unit) and half + 3 + 2i (cross)."""
    x = mlp(gmu(x, blk["gmu"], m, hp), blk["gmu_mlp"], hp)
    x = cross(x, blk["cross"], half + 3 + 2 * i, k, v, hp)
    return mlp(x, blk["cross_mlp"], hp)


def logits(params, tokens, hp):
    """tokens [N] int32 -> logits [N, V], in the weights' own dtype."""
    half = hp["num_hidden_layers"] // 2
    x = params["embed"][tokens]
    for i in range(half // 2):
        x, _ = first_pair(x, pair_of(params["first"], i), i, hp)
    x, m, k, v = middle(x, params["mid"], half, hp)
    for i in range((hp["num_hidden_layers"] - half - 2) // 2):
        x = second_pair(x, pair_of(params["second"], i), i, half, m, k, v, hp)
    x = layer_norm(x, params["norm_f"]["g"], params["norm_f"]["b"],
                   hp["layer_norm_eps"])
    return x @ params["embed"].T


def up(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def layer_at_a_time(hp):
    """``rows_of(params, tokens [N], rows [R]) -> logits [R, V]``: the
    same forward for weights whose float32 copy does not fit whole (the
    benchmark's 3.85B parameters are 15.4 GB in float32, beside 7.7 GB
    in bf16).  Each pair of layers is one jitted call that upcasts the
    leaves it is handed, so one pair's float32 copy lives at a time; the
    head runs on ``rows`` only."""
    f32 = jnp.float32
    half = hp["num_hidden_layers"] // 2

    one_first = jax.jit(lambda x, blk, i: first_pair(x, up(blk), i, hp)[0])
    the_middle = jax.jit(lambda x, blk: middle(x, up(blk), half, hp))
    one_second = jax.jit(
        lambda x, blk, i, m, k, v: second_pair(x, up(blk), i, half, m, k, v,
                                               hp))
    first = jax.jit(lambda table, t: table[t].astype(f32))
    last = jax.jit(lambda x, rows, norm, table: layer_norm(
        x[rows], norm["g"].astype(f32), norm["b"].astype(f32),
        hp["layer_norm_eps"]) @ table.astype(f32).T)

    def rows_of(params, tokens, rows):
        x = first(params["embed"], tokens)
        for i in range(half // 2):
            x = one_first(x, pair_of(params["first"], i), i)
        x, m, k, v = the_middle(x, params["mid"])
        for i in range((hp["num_hidden_layers"] - half - 2) // 2):
            x = one_second(x, pair_of(params["second"], i), i, m, k, v)
        return last(x, rows, params["norm_f"], params["embed"])

    return rows_of


def states_at_a_time(hp):
    """``states_of(params, tokens [N], count) -> S float32 [n, di, s]``:
    what each of the ``n`` state-space layers holds after the first
    ``count`` positions of ``tokens`` (the rest is padding), in layer
    order, a pair of layers upcast at a time as :func:`layer_at_a_time`
    does.  Layers past the last state-space one keep no state and are
    not run."""
    half = hp["num_hidden_layers"] // 2
    one_first = jax.jit(
        lambda x, blk, i, count: first_pair(x, up(blk), i, hp, count))
    the_last = jax.jit(lambda x, blk, count: ssm(x, up(blk), hp, count)[2])
    first = jax.jit(lambda table, t: table[t].astype(jnp.float32))

    def states_of(params, tokens, count):
        x = first(params["embed"], tokens)
        states = []
        for i in range(half // 2):
            x, state = one_first(x, pair_of(params["first"], i), i, count)
            states.append(state)
        states.append(the_last(x, params["mid"]["ssm"], count))
        return jnp.stack(states)

    return states_of
