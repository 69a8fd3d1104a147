"""The ``deepseek_v3`` model family (the config's own ``model_type``):
multi-head latent attention (MLA) over a shared low-rank KV latent, and
a mixture of experts with sigmoid scores, a selection-only bias, no
capacity factor and shared experts — served through
``inference/serving.py::PagedServingEngine``.

Keys are the published ones (``config.json`` of e.g.
kakaocorp/kanana-2-30b-a3b-instruct-2601).  For a layer's input ``x`` at
position ``p``::

    h      = rmsnorm(x; g_in)
    q      = h Wq                    -> [nh, nope + rope] per head
    ckr    = h Wdkv                  -> [kv_lora_rank + rope]   (no head axis)
    c      = rmsnorm(c_raw; g_kv) ;  kr = rope(kr_raw, p) ;  q_rope = rope(q_rope, p)
    [k_nope | v] = c Wukv            -> [nh, nope + v]
    score_j = (q_nope . k_nope_j + q_rope . kr_j) / sqrt(nope + rope)
    x      = x + concat_heads(sum_j P_j v_j) Wo
    h2     = rmsnorm(x; g_post)
    dense  :  x = x + (silu(h2 Wg) * (h2 Wu)) Wd
    expert :  s = sigmoid(h2 Wr)                     float32
              chosen = top_k(s + b)                  b: selection only
              w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor
              x = x + sum_e w_e mlp_e(h2) + mlp_shared(h2)
    logits = rmsnorm(x_last; g_f) Whead

``rope`` rotates ADJACENT pairs ``(2i, 2i+1)`` at ``theta^(-2i/rope)``
(``rope_interleave``).  Prefill (:func:`prefill_paged`,
:func:`forward`) computes the attention as written.  Decode
(:func:`decode_paged`) computes the same function with the
up-projections absorbed: ``q_abs = q_nope Wuk^T`` meets the cached
latent ``c`` directly, ``o_lat = sum_j P_j c_j`` and ``o = o_lat Wuv`` —
so the cache holds per position per layer only ``c`` (after its norm)
and ``kr`` (after rope), and ``ops/pallas/paged_mla.py`` reads it.

The latent pool is two arrays in the engine's two donated slots:
``[L, pages, page_size, kv_lora_rank]`` and ``[L, pages, page_size,
128]`` (the rope half in a whole 128-lane row, ``rope`` columns used),
so both are stored major-to-minor as the kernel takes them and every
layer writes them where they lie (``gpt._layer_scan``).

The expert layer is exact top-k and DROPLESS: assignments are sorted by
expert and the three products run as grouped matmuls over every expert
held — one executable per bucket whatever the routing mix.  On the chip
they are ``ops/pallas/grouped_matmul.py``'s kernel at every row count
(decode's 384 rows and every prefill bucket: it streams each touched
expert's weights once), two calls a layer — gate and up in one, writing
``silu(g) * u``, then down; off the chip ``jax.lax.ragged_dot``.  Both
take the expert STACK whole with the layer's index.

The expert layer is shared with ``models/longcat_flash.py`` and is TOLD
which experts it holds (:class:`ExpertShare`): it routes over all of
them (sigmoid or softmax scores, ``norm_topk_prob`` or not), computes
the held experts' part for the tokens routed to them, counts and skips
the assignments other chips' experts would take (the exchange is not
built), and adds the identity ("zero-compute") experts' term where the
config has them.  This family holds every expert and no identity one:
the share ``(0, n_routed_experts)``, the program it always had.  The
attention sublayer likewise takes the query low-rank path
(``q_lora_rank``: ``q = rmsnorm(h Wqa; g_qa) Wqb``) and the two latent
scales (``mla_scale_q_lora`` / ``_kv_lora``: sqrt(H / rank) on the normed
latents) where a block has them; this family's config builds neither.

Parameter tree: ``embed [V, H]``, ``head [H, V]``, ``norm_f [H]``,
``dense`` (layer 0: attention leaves + ``wg, wu, wd``) and ``moe`` (the
expert layers stacked on a leading axis: attention leaves + ``wr, b,
eg, eu, ed, sg, su, sd``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import gpt

ROPE_LANES = 128        # the rope half of the pool: one whole lane row


class ExpertShare(NamedTuple):
    """What an expert layer holds of the experts it routes over: the
    routed experts ``[first, first + held)`` of ``routed``, and ``zero``
    identity experts after them (router columns ``routed .. routed +
    zero - 1``).  The router is ``routed + zero`` wide whatever is held;
    every chip adds the identity term alike.  (A tuple, not a
    dataclass: a family module's one dataclass is its config,
    ``benchmark/drivers/serve_family.py::family_modules``.)"""
    first: int
    held: int
    routed: int
    zero: int = 0

    def checked(self):
        """This share, or ValueError where it is not one."""
        if not (0 <= self.first and 1 <= self.held
                and self.first + self.held <= self.routed
                and self.zero >= 0):
            raise ValueError(f"no such expert share: {self}")
        return self

    @property
    def width(self):
        return self.routed + self.zero

    @property
    def partial(self):
        """Whether the router has columns the layer does not hold
        (routed experts held elsewhere, or identity experts)."""
        return self.held < self.width

    @property
    def count_width(self):
        """Entries of :func:`moe_ffn`'s count vector: the held experts'
        loads, the identity and the remote assignments, and for a
        partial share the rows its combine walked and its output's
        rows."""
        return self.held + (4 if self.partial else 2)


@dataclasses.dataclass
class DeepseekV3Config:
    vocab_size: int = 128256
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    q_lora_rank: int | None = None
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.448
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    rope_interleave: bool = True
    rope_scaling: dict | None = None
    max_position_embeddings: int = 32768
    hidden_act: str = "silu"
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"          # compute dtype
    param_dtype: str = "bfloat16"
    initializer_range: float = 0.02
    router_bias_range: float = 0.01  # b is trained, not initialised: drawn

    def __post_init__(self):
        unbuilt = {
            "q_lora_rank": self.q_lora_rank is not None,
            "first_k_dense_replace != 1": self.first_k_dense_replace != 1,
            "moe_layer_freq != 1": self.moe_layer_freq != 1,
            "n_group/topk_group != 1": (self.n_group, self.topk_group)
            != (1, 1),
            "scoring_func != sigmoid": self.scoring_func != "sigmoid",
            "rope_interleave false": not self.rope_interleave,
            "norm_topk_prob false": not self.norm_topk_prob,
            "rope_scaling": self.rope_scaling is not None,
            "hidden_act != silu": self.hidden_act != "silu",
            "attention_bias": self.attention_bias,
            "tie_word_embeddings": self.tie_word_embeddings,
        }
        bad = [k for k, v in unbuilt.items() if v]
        if bad:
            raise ValueError(f"deepseek_v3: not built here: {bad}")
        if self.num_hidden_layers < 2:
            raise ValueError("deepseek_v3 needs the dense layer and at "
                             "least one expert layer")
        if self.qk_rope_head_dim % 2 or self.qk_rope_head_dim > ROPE_LANES:
            raise ValueError(f"qk_rope_head_dim {self.qk_rope_head_dim}")

    # what the engine asks of any family's config
    @property
    def max_seq_len(self):
        return self.max_position_embeddings

    @property
    def num_layers(self):
        return self.num_hidden_layers

    @property
    def num_heads(self):
        return self.num_attention_heads

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def expert_share(self):
        return ExpertShare(0, self.n_routed_experts, self.n_routed_experts)


def deepseek_v3_tiny(**kw):
    """The CPU tests' size: every mechanism, no published width."""
    base = dict(vocab_size=512, hidden_size=64, num_hidden_layers=3,
                num_attention_heads=4, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
                intermediate_size=96, moe_intermediate_size=24,
                n_routed_experts=8, num_experts_per_tok=2,
                n_shared_experts=1, max_position_embeddings=256,
                dtype="float32", param_dtype="float32")
    base.update(kw)
    return DeepseekV3Config(**base)


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def init_params(cfg: DeepseekV3Config, key):
    """Seeded random weights.  Layer 0 apart; the expert layers stacked
    on a leading axis, so the layer scan carries the pool through them."""
    H, nh = cfg.hidden_size, cfg.num_attention_heads
    Lm = cfg.num_hidden_layers - 1
    E, Im = cfg.n_routed_experts, cfg.moe_intermediate_size
    Is = cfg.n_shared_experts * Im
    pd = jnp.dtype(cfg.param_dtype)
    std = cfg.initializer_range
    res = std / math.sqrt(2.0 * cfg.num_hidden_layers)
    keys = iter(jax.random.split(key, 32))

    def nrm(shape, scale=std):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(pd)

    def attn(lead):
        return {
            "g_in": jnp.ones(lead + (H,), pd),
            "wq": nrm(lead + (H, nh * cfg.qk_head_dim)),
            "wdkv": nrm(lead + (H, cfg.kv_lora_rank + cfg.qk_rope_head_dim)),
            "g_kv": jnp.ones(lead + (cfg.kv_lora_rank,), pd),
            "wukv": nrm(lead + (cfg.kv_lora_rank,
                                nh * (cfg.qk_nope_head_dim + cfg.v_head_dim))),
            "wo": nrm(lead + (nh * cfg.v_head_dim, H), res),
            "g_post": jnp.ones(lead + (H,), pd),
        }

    dense = attn(())
    dense.update(wg=nrm((H, cfg.intermediate_size)),
                 wu=nrm((H, cfg.intermediate_size)),
                 wd=nrm((cfg.intermediate_size, H), res))
    moe = attn((Lm,))
    moe.update(
        wr=nrm((Lm, H, E)),
        b=(jax.random.uniform(next(keys), (Lm, E), jnp.float32, -1.0, 1.0)
           * cfg.router_bias_range),
        eg=nrm((Lm, E, H, Im)), eu=nrm((Lm, E, H, Im)),
        ed=nrm((Lm, E, Im, H), res),
        sg=nrm((Lm, H, Is)), su=nrm((Lm, H, Is)), sd=nrm((Lm, Is, H), res))
    return {"embed": nrm((cfg.vocab_size, H)),
            "head": nrm((H, cfg.vocab_size)),
            "norm_f": jnp.ones((H,), pd), "dense": dense, "moe": moe}


# --------------------------------------------------------------------------
# the sublayers
# --------------------------------------------------------------------------

def _rmsnorm(x, g, eps, scale=None):
    """``scale``: a constant on the normed output (a latent scale),
    applied in float32 before the one rounding."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    y = y * g.astype(jnp.float32)
    if scale is not None:
        y = y * scale
    return y.astype(x.dtype)


def _lora_scale(cfg, flag, rank):
    """sqrt(H / rank) where the config's ``flag`` (``mla_scale_q_lora``,
    ``mla_scale_kv_lora``) asks for it, else None."""
    if not getattr(cfg, flag, False):
        return None
    return math.sqrt(cfg.hidden_size / rank)


def _rope(x, pos, theta):
    """Rotate adjacent pairs (2i, 2i+1) of the last axis by
    ``pos * theta^(-2i/d)``; ``pos`` broadcasts against x's leading
    axes.  float32 inside."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], -1)
    return out.reshape(x.shape).astype(x.dtype)


def _mla_project(cfg, x, blk, pos):
    """The projections both attention paths share.  x: [..., H]; pos:
    int32 positions broadcastable to x's leading axes.  Returns
    (q_nope [..., nh, nope], q_rope [..., nh, rope], c [..., rank],
    kr [..., rope]) — ``c`` after its norm (and its scale), both rope
    halves rotated."""
    cd = jnp.dtype(cfg.dtype)
    nh = cfg.num_attention_heads
    h = _rmsnorm(x, blk["g_in"], cfg.rms_norm_eps)
    with jax.named_scope("mla_q"):
        if "wqa" in blk:
            with jax.named_scope("mla_q_lora"):
                h_q = _rmsnorm(h @ blk["wqa"].astype(cd), blk["g_qa"],
                               cfg.rms_norm_eps, _lora_scale(
                                   cfg, "mla_scale_q_lora", cfg.q_lora_rank))
            q = h_q @ blk["wqb"].astype(cd)
        else:
            q = h @ blk["wq"].astype(cd)
        q = q.reshape(*x.shape[:-1], nh, cfg.qk_head_dim)
        q_nope = q[..., :cfg.qk_nope_head_dim]
        q_rope = _rope(q[..., cfg.qk_nope_head_dim:], pos[..., None],
                       cfg.rope_theta)
    with jax.named_scope("mla_latent"):
        ckr = h @ blk["wdkv"].astype(cd)
        c = _rmsnorm(ckr[..., :cfg.kv_lora_rank], blk["g_kv"],
                     cfg.rms_norm_eps, _lora_scale(
                         cfg, "mla_scale_kv_lora", cfg.kv_lora_rank))
        kr = _rope(ckr[..., cfg.kv_lora_rank:], pos, cfg.rope_theta)
    return q_nope, q_rope, c, kr


def _wukv(cfg, blk):
    """Wukv as [rank, nh, nope + v]: (Wuk, Wuv) per head."""
    cd = jnp.dtype(cfg.dtype)
    w = blk["wukv"].astype(cd).reshape(
        cfg.kv_lora_rank, cfg.num_attention_heads,
        cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def _mla_attend(cfg, blk, q_nope, q_rope, c, kr, mask):
    """Attention as published: keys and values expanded from the latent.
    q_*: [B, T, nh, .]; c: [B, K, rank]; kr: [B, K, rope]; mask:
    bool [B|1, T, K].  Returns [B, T, nh * v]."""
    cd = jnp.dtype(cfg.dtype)
    wuk, wuv = _wukv(cfg, blk)
    k_nope = jnp.einsum("bkc,chd->bkhd", c.astype(cd), wuk)
    v = jnp.einsum("bkc,chd->bkhd", c.astype(cd), wuv)
    f32 = jnp.float32
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                         preferred_element_type=f32)
              + jnp.einsum("bqhd,bkd->bhqk", q_rope, kr.astype(cd),
                           preferred_element_type=f32))
    scores = scores / math.sqrt(cfg.qk_head_dim)
    scores = jnp.where(mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, -1).astype(cd)
    a = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return a.reshape(*a.shape[:2], -1)


def _mla_attend_flash(cfg, blk, q_nope, q_rope, c, kr, lens):
    """:func:`_mla_attend` for a prefill wave (T == K, causal, rows at
    or past ``lens`` [B] padding) with the scores kept in VMEM
    (``ops/pallas/flash_prefill.py``): the same expansion from the
    latent, the same bf16 operands and float32 softmax; blocking and
    where the normalisation lands differ.  Heads stay side by side
    along the lanes, as the projections write them; the rope halves go
    in whole lane rows, as the pool holds them; the kernel takes the
    values and gives the output with positions along the lanes."""
    from ..ops.pallas import flash_prefill
    cd = jnp.dtype(cfg.dtype)
    wuk, wuv = _wukv(cfg, blk)
    B, T = c.shape[:2]
    k_nope = jnp.einsum("bkc,chd->bkhd", c.astype(cd), wuk)
    v_t = jnp.einsum("bkc,chd->bhdk", c.astype(cd), wuv)
    a_t = flash_prefill.flash_prefill_attention(
        q_nope.reshape(B, T, -1), k_nope.reshape(B, T, -1),
        v_t.reshape(B, -1, T), lens, heads=cfg.num_attention_heads,
        scale=1.0 / math.sqrt(cfg.qk_head_dim),
        q_rope=_pad_rope(q_rope, cd).reshape(B, T, -1),
        k_rope=_pad_rope(kr, cd))
    return jnp.swapaxes(a_t, 1, 2)


def _gated_mlp(h, wg, wu, wd, cd):
    g = h @ wg.astype(cd)
    u = h @ wu.astype(cd)
    return (jax.nn.silu(g) * u) @ wd.astype(cd)


def route(cfg, h2, wr, b):
    """h2 [T, H] -> (chosen int32 [T, k], weights float32 [T, k]) over
    the router's whole width (``cfg.expert_share.width``): sigmoid or
    softmax scores (``cfg.scoring_func``), the bias for selection only,
    the chosen scores renormalised where ``cfg.norm_topk_prob``, then
    scaled.  Scores and sums in float32, the product at HIGHEST (a
    float32 matmul is one bf16 pass on the chip otherwise); ties go to
    the lower expert id (``lax.top_k``'s rule).  ``b`` None: a router
    with no selection bias (``models/cohere2_moe.py``)."""
    logits = jnp.dot(h2.astype(jnp.float32), wr.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if cfg.scoring_func == "softmax":
        s = jax.nn.softmax(logits, -1)
    else:
        s = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(s if b is None else s + b.astype(jnp.float32),
                              cfg.num_experts_per_tok)
    w = jnp.take_along_axis(s, chosen, -1)
    if cfg.norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * cfg.routed_scaling_factor
    return chosen.astype(jnp.int32), w


def _held_part(h2, blk, order, sizes, w_held, cd):
    """The held experts' part of a PARTIAL share's layer: (y [T, H]
    float32, int32 [2]: the rows the combine walked and the output's
    rows ``R``).  ``order`` sorts the T * k assignments by expert, the
    held ones first (``sizes`` [held] of them, a group each);
    ``w_held`` [T, k] is 0 where an assignment is not held.

    The sorted rows go to the grouped matmul padded to its whole
    windows, ``R`` rows (``grouped_matmul.window_rows``), so its output
    needs no cut; a pad row is no group's, comes out zero and weighs 0.
    Only the windows the held rows reach are combined, one at a time:
    each window's rows, times their weights, are added into their
    tokens.  Rows past the held ones inside the last window are zeros
    of weight 0, and every held row lies in a walked window whatever
    the routing: the sum a whole share's einsum takes over the same
    terms, added in another order."""
    from ..ops.pallas import grouped_matmul as gmm
    T, H = h2.shape
    n = order.shape[0]
    tm = gmm.window_rows(n, h2.dtype.itemsize)
    R = -(-n // tm) * tm
    tok = jnp.pad(order // w_held.shape[1], (0, R - n))    # pad: token 0
    wt = jnp.pad(w_held.reshape(-1)[order], (0, R - n))    # pad: weight 0
    li = blk.get("li")
    mid = gmm.grouped_gate_up(h2[tok], sizes, blk["eg"].astype(cd),
                              blk["eu"].astype(cd), li)
    y = gmm.grouped_matmul(mid, sizes, blk["ed"].astype(cd), li)
    windows = (jnp.sum(sizes) + (tm - 1)) // tm

    def window(i, out):
        rows = jax.lax.dynamic_slice_in_dim(y, i * tm, tm)
        at = jax.lax.dynamic_slice_in_dim(tok, i * tm, tm)
        wi = jax.lax.dynamic_slice_in_dim(wt, i * tm, tm)
        return out.at[at].add(rows * wi[:, None])

    out = jax.lax.fori_loop(0, windows, window,
                            jnp.zeros((T, H), jnp.float32))
    return out, jnp.stack([windows * tm, jnp.int32(R)]).astype(jnp.int32)


def moe_ffn(cfg, h2, blk, row_mask=None):
    """The expert layer over ``h2`` [T, H]: routed + shared + identity,
    no residual; a block without ``b`` routes with no selection bias,
    one without ``sg`` has no shared term.  Returns (y [T, H], counts
    int32 [``share.count_width``]: the assignments each HELD expert got from
    the rows ``row_mask`` admits — every row when None — then the
    assignments to identity experts and to routed experts held
    elsewhere, from the same rows, this family's two 0; a partial share
    adds the rows its combine walked and its output's rows, from every
    row: :func:`_held_part`).

    No token is dropped: the T * k assignments are sorted by expert and
    each held expert's rows meet its weights in a grouped matmul whose
    group sizes are data.  Shapes depend on T alone.  Of a share
    (``cfg.expert_share``), the assignments the layer does not hold sort
    past its own groups: the grouped matmul computes none of them and
    gives zeros there (``ops/pallas/grouped_matmul.py``), and they add
    nothing to a token — what the experts' exchange would carry (not
    built).  The identity experts' weights are summed a token and meet
    ``h2`` in one weighted add.  A share that holds every column (this
    family's ``(0, E)``) unsorts the whole output and sums it with the
    weights; a partial one adds its live rows into their tokens
    (:func:`_held_part`).

    ``blk["eg"], ["eu"], ["ed"]`` are one layer's experts [E, ., .], or
    the WHOLE stack [layers, E, ., .] with ``blk["li"]`` naming the
    layer: the grouped matmul is a custom call, so a layer sliced out
    of the stack for it would be copied (1.2 GB a layer at the
    published widths); instead the stack goes in whole with its layer
    index (``ops/pallas/grouped_matmul.py``: on the chip the kernel's
    index map names the layer; off it ``ragged_dot`` sees layers * E
    groups of which only this layer's have rows).  ``silu(g) * u`` is
    formed in float32 and rounded to the compute dtype once, before
    the down projection, on either path."""
    from ..ops.pallas import grouped_matmul as gmm
    cd = jnp.dtype(cfg.dtype)
    share = cfg.expert_share
    T, H = h2.shape
    K, E = cfg.num_experts_per_tok, share.held
    with jax.named_scope("moe_router"):
        chosen, w = route(cfg, h2, blk["wr"], blk.get("b"))
        local = chosen.reshape(-1) - share.first           # [T * K]
        held = (local >= 0) & (local < E)
        zero = chosen.reshape(-1) >= share.routed
        # every assignment not held here sorts past the held groups
        flat = jnp.where(held, local, E)
        sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1, mode="drop")
        rows = jnp.repeat(jnp.ones((T,), jnp.int32) if row_mask is None
                          else row_mask.astype(jnp.int32), K)
        counts = jnp.concatenate([
            jnp.zeros((E,), jnp.int32).at[flat].add(rows, mode="drop"),
            jnp.sum(jnp.where(zero, rows, 0))[None],
            jnp.sum(jnp.where(held | zero, 0, rows))[None]])
    with jax.named_scope("moe_held"):
        order = jnp.argsort(flat, stable=True)
        if share.partial:
            y, walked = _held_part(h2, blk, order, sizes,
                                   jnp.where(held.reshape(T, K), w, 0.0), cd)
            counts = jnp.concatenate([counts, walked])
        else:
            xs = h2[order // K]                            # expert-sorted
            li = blk.get("li")
            mid = gmm.grouped_gate_up(xs, sizes, blk["eg"].astype(cd),
                                      blk["eu"].astype(cd), li)
            y = gmm.grouped_matmul(mid, sizes, blk["ed"].astype(cd), li)
            y = y[jnp.argsort(order)].reshape(T, K, H)     # unsort
            y = jnp.einsum("tkh,tk->th", y,
                           jnp.where(held.reshape(T, K), w, 0.0))
    if share.zero:
        with jax.named_scope("moe_zero"):
            w_zero = jnp.sum(jnp.where(zero.reshape(T, K), w, 0.0), -1)
            y = y + w_zero[:, None] * h2.astype(jnp.float32)
    y = y.astype(cd)
    if "sg" in blk:
        with jax.named_scope("moe_shared"):
            y = y + _gated_mlp(h2, blk["sg"], blk["su"], blk["sd"], cd)
    return y, counts


def _after_attention(cfg, x, blk, a, row_mask=None):
    """The rest of a layer once attention gave ``a`` [..., nh * v]: the
    output projection, the post-attention norm and the feed-forward
    half, residuals included.  x: [..., H].  Returns (x, the expert
    layer's counts or None)."""
    cd = jnp.dtype(cfg.dtype)
    with jax.named_scope("mla_out"):
        x = x + a @ blk["wo"].astype(cd)
    h2 = _rmsnorm(x, blk["g_post"], cfg.rms_norm_eps)
    if "wr" not in blk:
        with jax.named_scope("dense_mlp"):
            return x + _gated_mlp(h2, blk["wg"], blk["wu"], blk["wd"],
                                  cd), None
    mask = None if row_mask is None else row_mask.reshape(-1)
    y, counts = moe_ffn(cfg, h2.reshape(-1, h2.shape[-1]), blk, mask)
    return x + y.reshape(x.shape), counts


EXPERT_STACKS = ("eg", "eu", "ed")


def _layers(params, body, x, pools=()):
    """Layer 0, then the stacked expert layers with ``pools`` carried
    (``gpt._layer_scan``).  ``body(x, blk, layer, pools) -> (x, pools,
    out)``.  The scan slices every stacked leaf but the experts' own,
    which :func:`moe_ffn` takes whole.  Returns (x, pools, outs of the
    expert layers)."""
    x, pools, _ = body(x, params["dense"], jnp.int32(0), tuple(pools))
    stacks = {k: params["moe"][k] for k in EXPERT_STACKS}
    sliced = {k: v for k, v in params["moe"].items() if k not in stacks}
    return gpt._layer_scan(
        lambda xx, blk, i, pp: body(xx, dict(blk, li=i, **stacks), i + 1, pp),
        x, sliced, pools)


def _head(cfg, params, x):
    with jax.named_scope("head_sample"):
        x = _rmsnorm(x, params["norm_f"], cfg.rms_norm_eps)
        return (x @ params["head"].astype(x.dtype)).astype(jnp.float32)


def _embed(cfg, params, tokens):
    with jax.named_scope("embed"):
        return jnp.take(params["embed"], tokens, axis=0).astype(
            jnp.dtype(cfg.dtype))


def _causal(T, K, offset=0):
    """bool [1, T, K]: query i (at absolute offset + i) meets key j."""
    return (jnp.arange(K)[None, :]
            <= offset + jnp.arange(T)[:, None])[None]


def forward(params, tokens, cfg: DeepseekV3Config):
    """tokens [B, N] int32 -> logits [B, N, V] float32.  No cache."""
    B, N = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32), (B, N))

    def body(x, blk, layer, pools):
        q_nope, q_rope, c, kr = _mla_project(cfg, x, blk, pos)
        with jax.named_scope("mla_attn"):
            a = _mla_attend(cfg, blk, q_nope, q_rope, c, kr, _causal(N, N))
        return _after_attention(cfg, x, blk, a)[0], pools, None

    x, _, _ = _layers(params, body, _embed(cfg, params, tokens))
    return _head(cfg, params, x)


# --------------------------------------------------------------------------
# the paged engine's family interface (inference/serving.py names it)
# --------------------------------------------------------------------------

def check_serving(cfg, *, engine, quant=None, kv_dtype=None, tp=1, pp=1,
                  kv_handoff=False, host_tier_mb=0.0):
    """Raise, by name, for every composition this family does not build
    (as ``gpt_pp.check_pp_config`` does for pipeline stages); the
    latent families' alike (``models/longcat_flash.py`` too), each
    named by its module."""
    why = {
        "the slot engine (ServingEngine)": (
            engine == "ServingEngine",
            "its pool is a per-slot K/V strip; the latent cache is paged "
            "— use PagedServingEngine"),
        "speculative decoding": (
            engine not in ("ServingEngine", "PagedServingEngine"),
            "the verify step and the draft model are GPT programs"),
        "quant=": (quant is not None,
                   "gpt.quantize_params knows GPT's leaves only"),
        "kv_dtype='int8'": (kv_dtype == "int8",
                            "the int8 pool's scales are per head and the "
                            "latent has no head axis"),
        "tp > 1": (int(tp) > 1,
                   "the latent pool has no head axis to shard and the "
                   "experts' exchange is not built"),
        "pp > 1": (int(pp) > 1, "gpt_pp's stage step is a GPT program"),
        "kv_handoff (KV extract/inject)": (
            bool(kv_handoff), "the payload format is K and V per head"),
        "the host KV tier": (
            float(host_tier_mb or 0) > 0,
            "spills ride the extract/inject executables"),
    }
    family = type(cfg).__module__.rsplit(".", 1)[-1]
    for name, (hit, reason) in why.items():
        if hit:
            raise ValueError(f"{family} does not compose with {name} "
                             f"yet — {reason}")


def shard_params_for_serving(params, cfg, mesh):
    raise ValueError("deepseek_v3 does not compose with tp > 1 / pp > 1 yet")


def kv_pool_spec(mesh):
    return (None, None, None, None)


def prefix_salt(cfg):
    """What the pager's prefix hashes are salted with, so that a latent
    page never aliases another family's page of the same tokens."""
    return (f"/family=deepseek_v3/latent={cfg.kv_lora_rank}"
            f"+{cfg.qk_rope_head_dim}")


def kv_bytes_per_position(cfg, itemsize):
    """Bytes one cached position NEEDS, all layers: c and kr."""
    return (cfg.num_hidden_layers
            * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * itemsize)


def decode_group_pages(cfg, pools, table_width, tp=1):
    """Pages a grid step of the decode kernel takes over these pools
    (ops/pallas/paged_mla.py::group_pages; a cached row is ``c`` and
    the rope lane row side by side, and every head meets it: 'tp' does
    not split it)."""
    from ..ops.pallas.paged_mla import group_pages
    c_pool, r_pool = pools[:2]
    return group_pages(table_width, c_pool.shape[2],
                       c_pool.shape[3] + r_pool.shape[3],
                       c_pool.dtype.itemsize, cfg.num_heads)


def decode_extra_stats(cfg, flat):
    """The engine's counters from what :func:`decode_paged` returned
    beside the logits (host side, numpy): ``flat`` is the expert
    layers' count vectors end to end (:func:`moe_ffn`'s), from the
    active slots.  The first three are over the HELD experts; the
    identity and the remote assignments are 0 in this family
    (``models/longcat_flash.py`` shares this).  A partial share also
    gives the rows its combine walked and the rows the whole output
    has (every slot's: the combine walks rows, not slots)."""
    share = cfg.expert_share
    E = share.held
    rows = flat.reshape(-1, share.count_width)
    loads = rows[:, :E]
    out = {"moe_assignments": int(loads.sum()),
           "moe_experts_touched": int((loads > 0).sum()),
           "moe_max_expert_load": int(loads.max(axis=1).sum()),
           "moe_zero_assignments": int(rows[:, E].sum()),
           "moe_remote_assignments": int(rows[:, E + 1].sum())}
    if share.partial:
        out["moe_combine_rows"] = int(rows[:, E + 2].sum())
        out["moe_output_rows"] = int(rows[:, E + 3].sum())
    return out


def paged_pool_shapes(cfg, num_pages, page_size):
    lead = (cfg.num_hidden_layers, num_pages, page_size)
    return lead + (cfg.kv_lora_rank,), lead + (ROPE_LANES,)


def init_paged_pools(cfg, num_pages, page_size, dtype=None, mesh=None,
                     kv_quant=False):
    """The latent pool: (c, kr), zeros.  Page 0 is the scratch page.
    The unused lanes of ``kr`` stay zero for ever: every write pads."""
    assert mesh is None and not kv_quant
    cd = jnp.dtype(dtype or cfg.dtype)
    return tuple(gpt._pool_zeros(s, cd)
                 for s in paged_pool_shapes(cfg, num_pages, page_size))


def _pad_rope(kr, dtype):
    pad = [(0, 0)] * (kr.ndim - 1) + [(0, ROPE_LANES - kr.shape[-1])]
    return jnp.pad(kr.astype(dtype), pad)


def prefill_attend(cfg, blk, x, pos, pools, layer, flat, lens, flash):
    """One attention block of a prefill wave over ``x`` [b, s, H]: the
    projections, the latents scattered into ``pools`` at ``layer``
    through the wave's flat page table ``flat`` (pad rows and pad pages
    target the scratch page), attention — the flash forward where
    ``flash`` (``flash_prefill.use_flash_prefill``).  Returns (a [b, s,
    nh * v], pools)."""
    b, s = x.shape[:2]
    ps = pools[0].shape[2]
    q_nope, q_rope, c, kr = _mla_project(cfg, x, blk, pos)
    with jax.named_scope("kv_scatter"):
        pc, pr = pools
        pc = pc.at[layer, flat].set(
            c.astype(pc.dtype).reshape(b * (s // ps), ps, -1))
        pr = pr.at[layer, flat].set(
            _pad_rope(kr, pr.dtype).reshape(b * (s // ps), ps, -1))
    with jax.named_scope("mla_attn"):
        if flash:
            a = _mla_attend_flash(cfg, blk, q_nope, q_rope, c, kr, lens)
        else:
            a = _mla_attend(cfg, blk, q_nope, q_rope, c, kr, _causal(s, s))
    return a, (pc, pr)


def prefill_uses_flash(cfg, b, s):
    from ..ops.pallas.flash_prefill import use_flash_prefill
    return use_flash_prefill(b, s, cfg.num_attention_heads,
                             cfg.qk_nope_head_dim, cfg.v_head_dim,
                             ROPE_LANES)


def prefill_paged(params, cfg, pools, tokens, lens, ptab):
    """Causal forward over padded prompts ``tokens`` [b, s]; every
    layer scatters its latents into the pools through ``ptab`` [b,
    s / page_size] (pad rows and pad pages target the scratch page).
    Returns (logits of each row's last true position [b, V], pools) —
    the head runs on those b rows only."""
    b, s = tokens.shape
    flat = ptab.reshape(-1)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    flash = prefill_uses_flash(cfg, b, s)

    def body(x, blk, layer, pp):
        a, pp = prefill_attend(cfg, blk, x, pos, pp, layer, flat, lens,
                               flash)
        return _after_attention(cfg, x, blk, a)[0], pp, None

    x, pools, _ = _layers(params, body, _embed(cfg, params, tokens), pools)
    return _head(cfg, params, last_rows(x, lens)), pools


def last_rows(x, lens):
    """Each row's last true position of ``x`` [b, s, H] -> [b, H]."""
    idx = jnp.clip(lens - 1, 0, x.shape[1] - 1)
    return jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]


def chunk_attend(cfg, blk, x, pos, pools, layer, pt_row, offset):
    """One attention block of a chunked-prefill piece ``x`` [1, C, H]
    at absolute positions ``pos``: gathers the slot's page view of
    ``layer``, splices the chunk's latents in at ``offset`` and scatters
    the view back (every page of the table's row is rewritten, the
    earlier ones with what they held), attends the view.  Returns (a,
    pools)."""
    C = x.shape[1]
    maxP = pt_row.shape[0]
    ps = pools[0].shape[2]
    q_nope, q_rope, c, kr = _mla_project(cfg, x, blk, pos)
    views = []
    for pool, new in zip(pools, (c, _pad_rope(kr, pools[1].dtype))):
        view = pool[layer, pt_row].reshape(1, maxP * ps, -1)
        views.append(jax.lax.dynamic_update_slice(
            view, new.astype(pool.dtype), (0, offset, 0)))
    pools = tuple(pool.at[layer, pt_row].set(v[0].reshape(maxP, ps, -1))
                  for pool, v in zip(pools, views))
    with jax.named_scope("mla_attn"):
        a = _mla_attend(cfg, blk, q_nope, q_rope, views[0],
                        views[1][..., :cfg.qk_rope_head_dim],
                        _causal(C, maxP * ps, offset))
    return a, pools


def chunk_paged(params, cfg, pools, tokens, pt_row, offset):
    """One chunked-prefill piece for one slot: ``tokens`` [1, C] from
    absolute position ``offset`` (traced), attending the slot's filled
    pages and the chunk's causal prefix (:func:`chunk_attend`).
    Returns (logits [1, C, V], pools)."""
    C = tokens.shape[1]
    pos = (offset + jnp.arange(C, dtype=jnp.int32))[None]

    def body(x, blk, layer, pp):
        a, pp = chunk_attend(cfg, blk, x, pos, pp, layer, pt_row, offset)
        return _after_attention(cfg, x, blk, a)[0], pp, None

    x, pools, _ = _layers(params, body, _embed(cfg, params, tokens), pools)
    return _head(cfg, params, x), pools


def decode_attend(cfg, blk, x, lens, pools, layer, page_table, write_pages,
                  write_offs, absorbed=True):
    """One attention block of a decode step over ``x`` [S, H], a token
    a slot at ``lens``: the projections, the new latents written at
    (``layer``, ``write_pages``, ``write_offs``), attention over the
    slot's pages — absorbed through ``paged_mla`` (the engine's form)
    or, with ``absorbed`` False, keys and values expanded from the
    gathered view (the published order, for the tests).  Returns (a
    [S, nh * v], pools)."""
    from ..ops.pallas import paged_mla
    S = x.shape[0]
    q_nope, q_rope, c, kr = _mla_project(cfg, x, blk, lens)
    with jax.named_scope("kv_write"):
        pc, pr = pools
        pc = pc.at[layer, write_pages, write_offs].set(c.astype(pc.dtype))
        pr = pr.at[layer, write_pages, write_offs].set(
            _pad_rope(kr, pr.dtype))
    with jax.named_scope("mla_attn"):
        if absorbed:
            wuk, wuv = _wukv(cfg, blk)
            q_abs = jnp.einsum("shd,chd->shc", q_nope, wuk)
            o_lat = paged_mla.paged_mla_attention(
                q_abs, q_rope, pc, pr, page_table, lens, layer,
                1.0 / math.sqrt(cfg.qk_head_dim))
            a = jnp.einsum("shc,chd->shd", o_lat, wuv).reshape(S, -1)
        else:
            view = page_table.shape[1] * pc.shape[2]
            cv = pc[layer][page_table].reshape(S, view, -1)
            rv = pr[layer][page_table].reshape(S, view, -1)
            mask = (jnp.arange(view)[None, :] <= lens[:, None])[:, None]
            a = _mla_attend(cfg, blk, q_nope[:, None], q_rope[:, None],
                            cv, rv[..., :cfg.qk_rope_head_dim], mask)[:, 0]
    return a, (pc, pr)


def decode_paged(params, cfg, pools, page_table, write_pages, write_offs,
                 lens, tokens, mesh=None, absorbed=True):
    """One decode iteration for every slot: one token per slot at its
    own ``lens[s]``.  Returns (logits [S, V] float32, pools, counts
    int32 [expert layers, E]: the assignments each expert received from
    the ACTIVE slots — ``lens > 0``; an idle slot's row is computed,
    its table is scratch, and it counts nothing).

    ``absorbed`` (the engine's form) folds Wuk into the query and Wuv
    into the output so the kernel meets the latent pool as it lies;
    False expands keys and values from the gathered view — the same
    function in the published order, kept for the tests."""
    active = lens > 0

    def body(x, blk, layer, pp):
        a, pp = decode_attend(cfg, blk, x, lens, pp, layer, page_table,
                              write_pages, write_offs, absorbed)
        x, counts = _after_attention(cfg, x, blk, a, row_mask=active)
        return x, pp, counts

    x, pools, counts = _layers(params, body, _embed(cfg, params, tokens),
                               pools)
    return _head(cfg, params, x), pools, counts
