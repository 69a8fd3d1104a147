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

Parameter tree: ``embed [V, H]``, ``head [H, V]``, ``norm_f [H]``,
``dense`` (layer 0: attention leaves + ``wg, wu, wd``) and ``moe`` (the
expert layers stacked on a leading axis: attention leaves + ``wr, b,
eg, eu, ed, sg, su, sd``).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from . import gpt

ROPE_LANES = 128        # the rope half of the pool: one whole lane row


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

def _rmsnorm(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


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
    kr [..., rope]) — ``c`` after its norm, both rope halves rotated."""
    cd = jnp.dtype(cfg.dtype)
    nh = cfg.num_attention_heads
    h = _rmsnorm(x, blk["g_in"], cfg.rms_norm_eps)
    with jax.named_scope("mla_q"):
        q = (h @ blk["wq"].astype(cd)).reshape(
            *x.shape[:-1], nh, cfg.qk_head_dim)
        q_nope = q[..., :cfg.qk_nope_head_dim]
        q_rope = _rope(q[..., cfg.qk_nope_head_dim:], pos[..., None],
                       cfg.rope_theta)
    with jax.named_scope("mla_latent"):
        ckr = h @ blk["wdkv"].astype(cd)
        c = _rmsnorm(ckr[..., :cfg.kv_lora_rank], blk["g_kv"],
                     cfg.rms_norm_eps)
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
    """h2 [T, H] -> (chosen int32 [T, k], weights float32 [T, k]).
    Scores and sums in float32, the product at HIGHEST (a float32
    matmul is one bf16 pass on the chip otherwise); ties go to the
    lower expert id (``lax.top_k``'s rule)."""
    s = jax.nn.sigmoid(jnp.dot(h2.astype(jnp.float32),
                               wr.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(s + b.astype(jnp.float32),
                              cfg.num_experts_per_tok)
    w = jnp.take_along_axis(s, chosen, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scaling_factor
    return chosen.astype(jnp.int32), w


def moe_ffn(cfg, h2, blk, row_mask=None):
    """The expert layer over ``h2`` [T, H]: routed + shared, no residual.
    Returns (y [T, H], counts int32 [E]: assignments each expert got
    from the rows ``row_mask`` admits — every row when None).

    No token is dropped: the T * k assignments are sorted by expert and
    each expert's rows meet its weights in a grouped matmul whose group
    sizes are data.  Shapes depend on T alone.

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
    T, H = h2.shape
    K, E = cfg.num_experts_per_tok, cfg.n_routed_experts
    with jax.named_scope("moe_router"):
        chosen, w = route(cfg, h2, blk["wr"], blk["b"])
        flat = chosen.reshape(-1)                          # [T * K]
        sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1)
        if row_mask is None:
            counts = sizes
        else:
            counts = jnp.zeros((E,), jnp.int32).at[flat].add(
                jnp.repeat(row_mask.astype(jnp.int32), K))
    with jax.named_scope("moe_routed"):
        order = jnp.argsort(flat, stable=True)
        xs = h2[order // K]                                # expert-sorted
        li = blk.get("li")
        mid = gmm.grouped_gate_up(xs, sizes, blk["eg"].astype(cd),
                                  blk["eu"].astype(cd), li)
        y = gmm.grouped_matmul(mid, sizes, blk["ed"].astype(cd), li)
        y = y[jnp.argsort(order)].reshape(T, K, H)         # unsort
        y = jnp.einsum("tkh,tk->th", y, w).astype(cd)
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
    (as ``gpt_pp.check_pp_config`` does for pipeline stages)."""
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
    for name, (hit, reason) in why.items():
        if hit:
            raise ValueError(f"deepseek_v3 does not compose with {name} "
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
    layers' load vectors end to end."""
    loads = flat.reshape(-1, cfg.n_routed_experts)
    return {"moe_assignments": int(loads.sum()),
            "moe_experts_touched": int((loads > 0).sum()),
            "moe_max_expert_load": int(loads.max(axis=1).sum())}


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


def prefill_paged(params, cfg, pools, tokens, lens, ptab):
    """Causal forward over padded prompts ``tokens`` [b, s]; every
    layer scatters its latents into the pools through ``ptab`` [b,
    s / page_size] (pad rows and pad pages target the scratch page).
    Returns (logits of each row's last true position [b, V], pools) —
    the head runs on those b rows only."""
    from ..ops.pallas.flash_prefill import use_flash_prefill
    b, s = tokens.shape
    ps = pools[0].shape[2]
    flat = ptab.reshape(-1)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    flash = use_flash_prefill(b, s, cfg.num_attention_heads,
                              cfg.qk_nope_head_dim, cfg.v_head_dim,
                              ROPE_LANES)

    def body(x, blk, layer, pp):
        q_nope, q_rope, c, kr = _mla_project(cfg, x, blk, pos)
        with jax.named_scope("kv_scatter"):
            pc, pr = pp
            pc = pc.at[layer, flat].set(
                c.astype(pc.dtype).reshape(b * (s // ps), ps, -1))
            pr = pr.at[layer, flat].set(
                _pad_rope(kr, pr.dtype).reshape(b * (s // ps), ps, -1))
        with jax.named_scope("mla_attn"):
            if flash:
                a = _mla_attend_flash(cfg, blk, q_nope, q_rope, c, kr, lens)
            else:
                a = _mla_attend(cfg, blk, q_nope, q_rope, c, kr,
                                _causal(s, s))
        return _after_attention(cfg, x, blk, a)[0], (pc, pr), None

    x, pools, _ = _layers(params, body, _embed(cfg, params, tokens), pools)
    idx = jnp.clip(lens - 1, 0, s - 1)
    last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    return _head(cfg, params, last), pools


def chunk_paged(params, cfg, pools, tokens, pt_row, offset):
    """One chunked-prefill piece for one slot: ``tokens`` [1, C] from
    absolute position ``offset`` (traced), attending the slot's filled
    pages and the chunk's causal prefix.  Returns (logits [1, C, V],
    pools).  Each layer gathers the slot's page view, splices the
    chunk's latents in and scatters the view back: every page of the
    table's row is rewritten, the earlier ones with what they held."""
    C = tokens.shape[1]
    maxP = pt_row.shape[0]
    ps = pools[0].shape[2]
    pos = (offset + jnp.arange(C, dtype=jnp.int32))[None]

    def body(x, blk, layer, pp):
        q_nope, q_rope, c, kr = _mla_project(cfg, x, blk, pos)
        views = []
        for pool, new in zip(pp, (c, _pad_rope(kr, pp[1].dtype))):
            view = pool[layer, pt_row].reshape(1, maxP * ps, -1)
            views.append(jax.lax.dynamic_update_slice(
                view, new.astype(pool.dtype), (0, offset, 0)))
        pp = tuple(pool.at[layer, pt_row].set(v[0].reshape(maxP, ps, -1))
                   for pool, v in zip(pp, views))
        with jax.named_scope("mla_attn"):
            a = _mla_attend(cfg, blk, q_nope, q_rope, views[0],
                            views[1][..., :cfg.qk_rope_head_dim],
                            _causal(C, maxP * ps, offset))
        return _after_attention(cfg, x, blk, a)[0], pp, None

    x, pools, _ = _layers(params, body, _embed(cfg, params, tokens), pools)
    return _head(cfg, params, x), pools


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
    from ..ops.pallas import paged_mla
    S = tokens.shape[0]
    active = lens > 0
    scale = 1.0 / math.sqrt(cfg.qk_head_dim)

    def body(x, blk, layer, pp):
        q_nope, q_rope, c, kr = _mla_project(cfg, x, blk, lens)
        with jax.named_scope("kv_write"):
            pc, pr = pp
            pc = pc.at[layer, write_pages, write_offs].set(
                c.astype(pc.dtype))
            pr = pr.at[layer, write_pages, write_offs].set(
                _pad_rope(kr, pr.dtype))
        with jax.named_scope("mla_attn"):
            if absorbed:
                wuk, wuv = _wukv(cfg, blk)
                q_abs = jnp.einsum("shd,chd->shc", q_nope, wuk)
                o_lat = paged_mla.paged_mla_attention(
                    q_abs, q_rope, pc, pr, page_table, lens, layer, scale)
                a = jnp.einsum("shc,chd->shd", o_lat, wuv).reshape(S, -1)
            else:
                view = page_table.shape[1] * pc.shape[2]
                cv = pc[layer][page_table].reshape(S, view, -1)
                rv = pr[layer][page_table].reshape(S, view, -1)
                mask = (jnp.arange(view)[None, :] <= lens[:, None])[:, None]
                a = _mla_attend(cfg, blk, q_nope[:, None], q_rope[:, None],
                                cv, rv[..., :cfg.qk_rope_head_dim],
                                mask)[:, 0]
        x, counts = _after_attention(cfg, x, blk, a, row_mask=active)
        return x, (pc, pr), counts

    x, pools, counts = _layers(params, body, _embed(cfg, params, tokens),
                               pools)
    return _head(cfg, params, x), pools, counts
