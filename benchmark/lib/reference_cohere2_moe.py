"""Plain reference of the ``cohere2_moe`` forward pass, independent of
the code under test: straight ``jax.numpy``, no cache, no kernel, no
batching, each K/V head met by its group of query heads, experts by a
loop with a mask.  Call it under ``jax.default_matmul_precision("highest")`` with
float32 weights for the reference proper.  It reads the program's
parameter tree (``embed, norm_f`` and the layers stacked by kind,
``window`` and ``full``, each ``ln, wqkv, wo, wr, sg, su, sd, eg, eu,
ed``) and takes nothing else from the program.  Where the shared experts
are averaged the tree's ``sd`` holds the published down-projections
times ``1 / n_s`` (folded in when the weights are made); the reference
takes the fold back out (``shared_down``: times ``n_s``, exact for a
power of two) and averages as published.
``benchmark/lib/reference_cohere2_moe.py`` is a copy of this file; a
test holds the two to the same numbers.

The model, as published (``config.json`` of
CohereLabs/command-a-plus-05-2026, the language model): hidden 4096; 32
layers, ``layer_types`` repeating [sliding, sliding, sliding, full]; 128
query heads and 8 K/V heads of 128; ``sliding_window`` 4096; rope
``rope_gptj`` (adjacent pairs), theta 50,000, ``rotary_pct`` 1, on the
sliding layers only (global NoPE); LayerNorm with a gain, eps 1e-5; the
parallel block; 128 routed experts of 4096 (``intermediate_size`` read
as one expert's width), sigmoid selection, top 8, ``norm_topk_prob``;
4 shared experts, averaged; tied embedding, ``logit_scale`` 1; a
vocabulary of 262,144.  For a layer's input ``x`` at position ``p``::

    h  = LN(x; g)
    x' = x + Attn(h) + MoE(h)
    Attn(h): q = h Wq [nq, hd] ; k = h Wk, v = h Wv [nkv, hd]
             sliding: q, k <- rope(., p) ; keys p - W < j <= p
             full:    keys j <= p
             query head i meets K/V head i // (nq / nkv)
             out = concat_i(softmax(q_i . k / sqrt(hd)) v) Wo
    MoE(h):  s = sigmoid(h Wr) ; chosen = top_8(s) ; w = s[chosen] / sum
             MoE = sum_e w_e SwiGLU_e(h) + (1/4) sum_j SwiGLU^s_j(h)
    logits = logit_scale * LN(x_last; g_f) E^T

The expert share: of the routed experts the weights hold ``num_experts``
from ``first_held_expert`` (a chip's share of a deployment that divides
each layer over chips); the router is the published width and the
result is the held experts' part plus the shared term — what the
program computes on such a chip.  Given every routed expert it is the
published layer.

Departures from the published description: the LayerNorm has no bias
(the family's published code has none); the router has no bias and no
selection-correction buffer (the config has neither); the embedding is
not scaled; ties in the top-k go to the lower expert id (a stable sort).

:func:`run_at_a_time` also plants the CONTROLS that the benchmark's
comparison must refuse (``control``): ``full_history`` every sliding
layer reading the whole history (no window), ``bf16`` the whole
reference computed in bf16 (weights, activations, norms, softmax and
router), ``int8`` every matmul weight but the router's rounded to int8
per output channel.  :func:`expert_layer` and :func:`kv_of` take the
same controls, for the checks of the program's expert layer and of the
K/V it stores.
"""
import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 128           # query rows a block of the attention's scores
CONTROLS = ("full_history", "bf16", "int8")


def layer_norm(x, g, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g


def rope(x, pos, theta):
    """x [N, heads, d] at positions pos [N]: pairs (2i, 2i+1) rotated."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape)


def swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def sliding(layer):
    """Whether published layer ``layer`` is a sliding-window layer."""
    return layer % 4 != 3


def project(h, wqkv, hp, rotary):
    """h [N, H] at positions 0 .. N - 1 -> q [N, nq, hd], k and v [N,
    nkv, hd]; q and k rotated where ``rotary`` (a sliding layer)."""
    n = h.shape[0]
    nq, nkv, hd = (hp["num_attention_heads"], hp["num_key_value_heads"],
                   hp["head_dim"])
    pos = jnp.arange(n)
    qkv = h @ wqkv
    q = qkv[:, :nq * hd].reshape(n, nq, hd)
    k = qkv[:, nq * hd:(nq + nkv) * hd].reshape(n, nkv, hd)
    v = qkv[:, (nq + nkv) * hd:].reshape(n, nkv, hd)
    if rotary:
        q, k = rope(q, pos, hp["rope_theta"]), rope(k, pos, hp["rope_theta"])
    return q, k, v


def attention(h, blk, hp, layer, window=True):
    """h [N, H] (already normed) -> the attention sublayer's output [N,
    H]; the scores a block of ``Q_BLOCK`` query rows at a time (a
    ``lax.map`` over the blocks).  ``window`` False: a sliding layer
    reads the whole history (a control)."""
    n = h.shape[0]
    nq, nkv, hd = (hp["num_attention_heads"], hp["num_key_value_heads"],
                   hp["head_dim"])
    g, W = nq // nkv, hp["sliding_window"]
    pos = jnp.arange(n)
    q, k, v = project(h, blk["wqkv"], hp, sliding(layer))
    # query head i = c g + j meets K/V head c = i // g
    rows = min(Q_BLOCK, n)
    blocks = -(-n // rows)
    qg = jnp.pad(q, ((0, blocks * rows - n), (0, 0), (0, 0))).reshape(
        blocks * rows, nkv, g, hd)

    def block(q0):
        at = q0 + jnp.arange(rows)
        qb = jax.lax.dynamic_slice_in_dim(qg, q0, rows)
        scores = jnp.einsum("qcjd,kcd->cjqk", qb, k) / math.sqrt(hd)
        seen = pos[None, :] <= at[:, None]
        if sliding(layer) and window:
            seen = seen & (pos[None, :] > at[:, None] - W)
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, -1).astype(v.dtype)
        return jnp.einsum("cjqk,kcd->qcjd", probs, v)

    out = jax.lax.map(block, jnp.arange(blocks) * rows)
    return out.reshape(blocks * rows, nq * hd)[:n] @ blk["wo"]


def route(h, wr, hp):
    """h [N, H] -> (chosen [N, k] over the whole router, weights [N, k])."""
    s = jax.nn.sigmoid(h @ wr)
    chosen = jnp.argsort(-s, axis=-1, stable=True)[:, :hp["num_experts_per_tok"]]
    w = jnp.take_along_axis(s, chosen, -1)
    if hp["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return chosen, w


def shared_down(sd, hp):
    """The published shared down-projections from the tree's ``sd``
    (the average's ``1 / n_s`` taken back out)."""
    if hp["shared_expert_combination_strategy"] == "average":
        return sd * hp["num_shared_experts"]
    return sd


def moe(h, blk, hp, weights=lambda w: w, li=None):
    """The expert layer's result on this share: every HELD expert over
    every token, masked to the tokens that chose it (a loop, so nothing
    about dispatch is shared with the program), plus the shared
    experts' mean.  ``weights`` maps each expert's leaves as they are
    met (one expert's copy at a time).  ``li``: the experts' leaves are
    the whole stacks and this is the layer's index in them."""
    chosen, w = route(h, blk["wr"], hp)
    first = hp["first_held_expert"]

    def expert(name, i):
        stack = blk[name]
        return stack[i] if li is None else stack[li, i]

    def one(y, i):
        weight = jnp.sum(jnp.where(chosen == first + i, w, 0.0), -1)
        out = swiglu(h, weights(expert("eg", i)), weights(expert("eu", i)),
                     weights(expert("ed", i)))
        return y + weight[:, None].astype(out.dtype) * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        jnp.arange(hp["num_experts"]))
    n_s, I = hp["num_shared_experts"], hp["intermediate_size"]
    sd = shared_down(blk["sd"], hp)
    shared = [swiglu(h, weights(blk["sg"][:, j * I:(j + 1) * I]),
                     weights(blk["su"][:, j * I:(j + 1) * I]),
                     weights(sd[j * I:(j + 1) * I]))
              for j in range(n_s)]
    if hp["shared_expert_combination_strategy"] == "average":
        return y + sum(shared) / n_s
    return y + sum(shared)


def layer_of(params, layer):
    """Published layer ``layer``'s weights, from the stacks by kind."""
    p, kind = divmod(layer, 4)
    stack, at = (("window", 3 * p + kind) if sliding(layer)
                 else ("full", p))
    return jax.tree_util.tree_map(lambda v: v[at], params[stack])


def logits(params, tokens, hp):
    """tokens [N] int32 -> logits [N, V], in the weights' own dtype."""
    eps = hp["layer_norm_eps"]
    x = params["embed"][tokens]
    for layer in range(hp["num_hidden_layers"]):
        blk = layer_of(params, layer)
        h = layer_norm(x, blk["ln"], eps)
        x = x + attention(h, blk, hp, layer) + moe(h, blk, hp)
    h = layer_norm(x, params["norm_f"], eps)
    return hp["logit_scale"] * (h @ params["embed"].T)


def int8_rounded(w):
    """``w`` [..., in, out] rounded to int8 per output channel."""
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(w / scale) * scale


def _dtype_of(control):
    if control not in (None,) + CONTROLS:
        raise ValueError(f"no such control {control!r}")
    return jnp.bfloat16 if control == "bf16" else jnp.float32


def _weight_of(control):
    """A matmul weight's copy as the reference (or the control) computes
    with it."""
    dt = _dtype_of(control)

    def weight(w):
        w = w.astype(dt)
        return int8_rounded(w) if control == "int8" else w
    return weight


def stack_of(layer):
    """(the stack by kind, the layer's index in it) of published layer
    ``layer``."""
    p, kind = divmod(layer, 4)
    return ("window", 3 * p + kind) if sliding(layer) else ("full", p)


def _at(stack, i, names):
    """Layer ``i`` (traced) of the leaves ``names`` of a stack."""
    return {k: stack[k][i] for k in names}


EXPERT_LEAVES = ("wr", "sg", "su", "sd", "eg", "eu", "ed")


def expert_layer(hp, control=None):
    """``fn(h [N, H], stack, i) -> (y [N, H], chosen int32 [N, k])``: the
    expert layer of layer ``i`` of ``stack`` (a stack by kind's
    :data:`EXPERT_LEAVES`, whole) over its normed input, and the
    router's choice; weights upcast one expert at a time, through the
    control's ``weight``."""
    dt = _dtype_of(control)
    weight = _weight_of(control)

    @jax.jit
    def fn(h, stack, i):
        blk = dict(_at(stack, i, ("wr", "sg", "su", "sd")),
                   eg=stack["eg"], eu=stack["eu"], ed=stack["ed"])
        blk["wr"] = blk["wr"].astype(dt)
        for k in ("sg", "su", "sd"):
            blk[k] = blk[k].astype(dt)
        h = h.astype(dt)
        return (moe(h, blk, hp, weights=weight, li=i),
                route(h, blk["wr"], hp)[0].astype(jnp.int32))
    return fn


def kv_of(hp, control=None):
    """``fn(h [N, H], wqkv stack, i, rotary) -> (k, v)`` [N, nkv * hd]:
    the K and V layer ``i`` of a stack keeps for positions ``0 .. N - 1``
    of one history, rotated where ``rotary`` (static), from its normed
    input."""
    dt = _dtype_of(control)
    weight = _weight_of(control)

    @functools.partial(jax.jit, static_argnames=("rotary",))
    def fn(h, wqkv, i, rotary):
        _, k, v = project(h.astype(dt), weight(wqkv[i]), hp, rotary)
        return k.reshape(k.shape[0], -1), v.reshape(v.shape[0], -1)
    return fn


def run_at_a_time(hp, control=None):
    """``run(params, tokens [N], rows [R]) -> (logits [R, V] float32,
    h [layers, N, H], a [layers, R, H])``: the forward for weights whose
    float32 copy does not fit whole (the benchmark's 4.7B parameters in
    bf16), ONE SUBLAYER a jitted call — a layer's attention, its expert
    layer (which upcasts one expert at a time) — each handed the stacks
    whole and the layer's index, upcasting only that layer's weights,
    the head on ``rows`` only; beside the logits each layer's normed
    input ``h`` at every position and its attention sublayer's output
    ``a`` at ``rows`` (what the benchmark's checks of the program's
    sublayers feed and compare).  ``control``: one of :data:`CONTROLS`,
    or None for the reference proper."""
    dt = _dtype_of(control)
    eps = hp["layer_norm_eps"]
    weight = _weight_of(control)
    window = control != "full_history"

    @jax.jit
    def norm(x, g, i):
        return layer_norm(x, g[i].astype(dt), eps)

    def attn_of(kind):
        @jax.jit
        def attn(h, stack, i):
            blk = {k: weight(v) for k, v in
                   _at(stack, i, ("wqkv", "wo")).items()}
            return attention(h, blk, hp, kind, window=window)
        return attn

    # the attention's program by the kind of layer: sliding, full
    attn = {True: attn_of(0), False: attn_of(3)}
    experts = expert_layer(hp, control)

    @jax.jit
    def head(x, rows, g, table):
        h = layer_norm(x[rows], g.astype(dt), eps)
        return (hp["logit_scale"] * (h @ weight(table).T)).astype(
            jnp.float32)

    def run(params, tokens, rows):
        x = params["embed"][tokens].astype(dt)
        hs, taps = [], []
        for layer in range(hp["num_hidden_layers"]):
            name, i = stack_of(layer)
            stack, i = params[name], jnp.int32(i)
            h = norm(x, stack["ln"], i)
            a = attn[sliding(layer)](
                h, {k: stack[k] for k in ("wqkv", "wo")}, i)
            x = x + a + experts(
                h, {k: stack[k] for k in EXPERT_LEAVES}, i)[0]
            hs.append(h)
            taps.append(a[rows])
        return (head(x, rows, params["norm_f"], params["embed"]),
                jnp.stack(hs), jnp.stack(taps))

    return run


def layer_at_a_time(hp, control=None):
    """``rows_of(params, tokens [N], rows [R]) -> logits [R, V]``: the
    logits of :func:`run_at_a_time` alone."""
    run = run_at_a_time(hp, control)
    return lambda params, tokens, rows: run(params, tokens, rows)[0]
