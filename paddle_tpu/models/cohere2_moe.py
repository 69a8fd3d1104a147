"""The ``cohere2_moe`` model family (the config's own ``model_type``):
Command A+'s language model — grouped-query attention over three
windowed rope layers and one global layer with no positional term a
period, attention and the expert layer side by side on one norm (the
parallel block), sigmoid routing over the routed experts beside shared
experts that are averaged — served through
``inference/serving.py::PagedServingEngine``.

Keys are the published ones (``config.json`` of
CohereLabs/command-a-plus-05-2026).  With ``LN(x; g)`` a LayerNorm with
a gain and no bias in float32, a layer ``l`` on ``x`` at position ``p``::

    h  = LN(x; g)
    x' = x + Attn(h) + MoE(h)                        use_parallel_block
    Attn(h): q = h Wq -> [nq, hd] ; k = h Wk, v = h Wv -> [nkv, hd]
             query head i reads K/V head i // (nq / nkv)
             window layer (l mod 4 < 3): q, k <- rope(., p), adjacent
                 pairs (rope_gptj), keys p - W < j <= p
             full layer (l mod 4 = 3): no positional term, keys j <= p
             a = softmax(q . k_j / sqrt(hd)) v ; Attn = concat(a) Wo
    MoE(h):  s = sigmoid(h Wr) float32 ; chosen = top_k(s) ;
             w = s[chosen] / sum s[chosen]           norm_topk_prob
             MoE = sum_e w_e SwiGLU_e(h) + (1 / n_s) sum_j SwiGLU^s_j(h)
    logits = logit_scale * LN(x_last; g_f) E^T       tied embedding

A window layer's keys are stored ROTATED at their own position, so
``q . k`` depends on ``p - j`` alone and the softmax over a window needs
no row position: a slot's window is a RING of ``sliding_window`` rows
(row ``j mod W``) read in any order.

What a sequence keeps on the device, and where:

* each full layer's K and V for every position: the page pool,
  ``[full layers, pages, page_size, nkv * hd]`` each, GPT's layout,
  owned by the pager;
* per SLOT, not per page: each window layer's last ``W`` K and V rows,
  stored as the strip of ring pages a slot owns, ``[window layers,
  slots * W / rp, rp, nkv * hd]`` (``slot_state_arrays``: the rings ride
  behind the pool, are overwritten by prefill for the wave's slots and
  left alone by a decode step for a slot it does not run), as
  ``models/phi4flash.py`` keeps its rings.

Decode reads both through ``ops/pallas/paged_attn.py``'s kernel with its
query heads in groups (the pool through the page table, a ring through
a fixed table over the slot's ring pages, masked by ``min(p + 1, W)``);
prefill waves through ``ops/pallas/flash_prefill.py``'s forward with the
same group factor where the scores would leave the chip, and no wave
longer than the window may take it (the forward masks causality, not
the window).  The expert layer is ``models/deepseek_v3.py``'s
``moe_ffn`` over this chip's SHARE of the routed experts
(``ExpertShare``: ``num_experts`` held from ``first_held_expert`` of
``num_experts_total``; the others counted and skipped: the exchange is
not built) with no selection bias, and ``shared_experts`` averaged.

Parameter tree: ``embed [V, H]``, ``norm_f [H]``, and the layers stacked
by kind, ``window`` (``W_l = 3 P``) and ``full`` (``P``), ``P`` periods,
each with ``ln [., H]``, ``wqkv [., H, (nq + 2 nkv) hd]``, ``wo [., nq
hd, H]``, ``wr [., H, routed]``, the shared experts side by side ``sg,
su [., H, n_s I]``, ``sd [., n_s I, H]`` and the held experts ``eg, eu
[., E, H, I]``, ``ed [., E, I, H]`` (the stacks go whole to the grouped
matmul with the layer's index).  Where the shared experts are averaged,
``sd`` holds the published down-projections times ``1 / n_s``
(:func:`shared_down_scale`, folded in when the weights are made: exact
for a power of two), so the program sums the shared experts.  Window layer ``3 p + j`` is published
layer ``4 p + j``, full layer ``p`` is ``4 p + 3``.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from . import deepseek_v3 as ds
from . import gpt
from ..ops.pallas import flash_prefill, paged_attn

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass
class Cohere2MoeConfig:
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 4096        # one expert's width
    num_hidden_layers: int = 32
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    head_dim: int = 128
    layer_types: list | None = None      # None: from layer_switch
    layer_switch: int = 4
    order_of_interleaved_layers: str = "local_attn_first"
    sliding_window: int = 4096
    rope_theta: float = 50000.0
    rotary_pct: float = 1.0
    position_embedding_type: str = "rope_gptj"
    layer_norm_eps: float = 1e-5
    # routed experts HELD here, from ``first_held_expert``, of the
    # ``num_experts_total`` the router routes over
    num_experts: int = 128
    num_experts_total: int = 128
    first_held_expert: int = 0
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    shared_expert_combination_strategy: str = "average"
    expert_selection_fn: str = "sigmoid"
    norm_topk_prob: bool = True
    first_k_dense_replace: int = 0
    use_parallel_block: bool = True
    use_qk_norm: bool = False
    attention_bias: bool = False
    tie_word_embeddings: bool = True
    use_embedding_sharing: bool = True
    logit_scale: float = 1.0
    max_position_embeddings: int = 200000
    hidden_act: str = "silu"
    use_gated_activation: bool = True
    dtype: str = "bfloat16"              # compute dtype
    param_dtype: str = "bfloat16"
    initializer_range: float = 0.02

    def __post_init__(self):
        L, n = self.num_hidden_layers, self.layer_switch
        if self.layer_types is None:
            self.layer_types = [FULL if i % n == n - 1 else SLIDING
                                for i in range(L)]
        pattern = [FULL if i % 4 == 3 else SLIDING for i in range(L)]
        unbuilt = {
            "layer_types other than [sliding x 3, full] a period of 4":
                (n != 4 or L % 4 or list(self.layer_types[:L]) != pattern
                 or self.order_of_interleaved_layers != "local_attn_first"),
            "first_k_dense_replace != 0": self.first_k_dense_replace != 0,
            "use_parallel_block false": not self.use_parallel_block,
            "use_qk_norm": self.use_qk_norm,
            "attention_bias": self.attention_bias,
            "tie_word_embeddings false": not (
                self.tie_word_embeddings and self.use_embedding_sharing),
            "hidden_act != silu, or no gate": (
                self.hidden_act != "silu" or not self.use_gated_activation),
            "expert_selection_fn != sigmoid":
                self.expert_selection_fn != "sigmoid",
            "position_embedding_type != rope_gptj, or rotary_pct != 1": (
                self.position_embedding_type != "rope_gptj"
                or self.rotary_pct != 1),
            "shared_expert_combination_strategy not average or sum":
                self.shared_expert_combination_strategy not in (
                    "average", "sum"),
            "num_attention_heads not a multiple of num_key_value_heads":
                self.num_attention_heads % self.num_key_value_heads != 0,
        }
        bad = [k for k, v in unbuilt.items() if v]
        if bad:
            raise ValueError(f"cohere2_moe: not built here: {bad}")
        self.expert_share          # raises for a share that is not one

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
    def periods(self):
        return self.num_hidden_layers // 4

    @property
    def kv_width(self):
        """A page (or ring) row: the K (or V) heads side by side."""
        return self.num_key_value_heads * self.head_dim

    @property
    def q_group(self):
        return self.num_attention_heads // self.num_key_value_heads

    # what deepseek_v3's expert layer asks
    @property
    def scoring_func(self):
        return self.expert_selection_fn

    @property
    def routed_scaling_factor(self):
        return 1.0

    @property
    def expert_share(self):
        return ds.ExpertShare(self.first_held_expert, self.num_experts,
                              self.num_experts_total).checked()


def cohere2_moe_tiny(**kw):
    """The CPU tests' size: one period (three window layers and a full
    one), 16 query heads on 2 K/V heads, a window of 8 so that a short
    run wraps the rings, a share of 4 of 8 routed experts from the 3rd;
    no published width."""
    base = dict(vocab_size=512, hidden_size=64, intermediate_size=32,
                num_hidden_layers=4, num_attention_heads=16,
                num_key_value_heads=2, head_dim=16, sliding_window=8,
                num_experts=4, num_experts_total=8, first_held_expert=2,
                num_experts_per_tok=2, num_shared_experts=2,
                max_position_embeddings=256, dtype="float32",
                param_dtype="float32")
    base.update(kw)
    return Cohere2MoeConfig(**base)


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def shared_down_scale(cfg):
    """What the shared experts' down-projections are multiplied by when
    the weights are made: ``1 / n_s`` where they are averaged."""
    if cfg.shared_expert_combination_strategy == "average":
        return 1.0 / cfg.num_shared_experts
    return 1.0


def init_params(cfg: Cohere2MoeConfig, key):
    """Seeded random weights: normal, std ``initializer_range``, the
    projections into the residual (``Wo``, the experts' down
    projections) scaled by 1 / sqrt(2 L); gains 1; the shared experts'
    down-projections with :func:`shared_down_scale` folded in."""
    H, I = cfg.hidden_size, cfg.intermediate_size
    nq, hd, C = cfg.num_attention_heads, cfg.head_dim, cfg.kv_width
    E, Is = cfg.num_experts, cfg.num_shared_experts * cfg.intermediate_size
    pd = jnp.dtype(cfg.param_dtype)
    std = cfg.initializer_range
    res = std / math.sqrt(2.0 * cfg.num_hidden_layers)
    keys = iter(jax.random.split(key, 32))

    def stack(n):
        def nrm(shape, scale=std):
            return (jax.random.normal(next(keys), (n,) + shape, jnp.float32)
                    * scale).astype(pd)
        return {"ln": jnp.ones((n, H), pd),
                "wqkv": nrm((H, nq * hd + 2 * C)), "wo": nrm((nq * hd, H), res),
                "wr": nrm((H, cfg.expert_share.width)),
                "sg": nrm((H, Is)), "su": nrm((H, Is)),
                "sd": nrm((Is, H), res * shared_down_scale(cfg)),
                "eg": nrm((E, H, I)), "eu": nrm((E, H, I)),
                "ed": nrm((E, I, H), res)}

    embed = (jax.random.normal(next(keys), (cfg.vocab_size, H), jnp.float32)
             * std).astype(pd)
    return {"embed": embed, "norm_f": jnp.ones((H,), pd),
            "window": stack(3 * cfg.periods), "full": stack(cfg.periods)}


# --------------------------------------------------------------------------
# the sublayers
# --------------------------------------------------------------------------

def _layer_norm(x, g, eps):
    """LayerNorm with a gain and no bias, float32 inside."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)
            * g.astype(jnp.float32)).astype(x.dtype)


def _qkv(cfg, blk, h, pos, rotary):
    """h [..., H] at positions ``pos`` (broadcastable to h's leading
    axes) -> q [..., nq, hd], k and v [..., nkv, hd]; q and k rotated
    where ``rotary`` (a window layer)."""
    cd = h.dtype
    A, C, hd = cfg.num_attention_heads * cfg.head_dim, cfg.kv_width, \
        cfg.head_dim
    qkv = h @ blk["wqkv"].astype(cd)
    lead = h.shape[:-1]
    q = qkv[..., :A].reshape(*lead, -1, hd)
    k = qkv[..., A:A + C].reshape(*lead, -1, hd)
    v = qkv[..., A + C:].reshape(*lead, -1, hd)
    if rotary:
        q = ds._rope(q, pos[..., None], cfg.rope_theta)
        k = ds._rope(k, pos[..., None], cfg.rope_theta)
    return q, k, v


def _attend(cfg, q, k, v, mask):
    """XLA's grouped-query attention.  q [B, T, nq, hd]; k, v [B, K, nkv,
    hd]; mask bool [B | 1, T, K].  Returns [B, T, nq * hd]."""
    B, T, nq, hd = q.shape
    nkv = k.shape[2]
    f32 = jnp.float32
    qg = q.reshape(B, T, nkv, nq // nkv, hd)
    s = jnp.einsum("btngd,bknd->bngtk", qg, k,
                   preferred_element_type=f32) / math.sqrt(hd)
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, -1).astype(q.dtype)
    a = jnp.einsum("bngtk,bknd->btngd", p, v)
    return a.reshape(B, T, nq * hd)


def _attend_flash(cfg, q, k, v, lens):
    """:func:`_attend` for a prefill wave (T == K, causal, rows at or
    past ``lens`` [B] padding) through the flash forward, the query
    heads in groups over their K/V head's blocks."""
    B, T = q.shape[:2]
    a_t = flash_prefill.flash_prefill_attention(
        q.reshape(B, T, -1), k.reshape(B, T, -1),
        jnp.swapaxes(v.reshape(B, T, -1), 1, 2), lens,
        heads=cfg.num_attention_heads, scale=1.0 / math.sqrt(cfg.head_dim),
        group=cfg.q_group)
    return jnp.swapaxes(a_t, 1, 2)


def _experts(cfg, h, blk, stacks, li, row_mask):
    """The expert layer over ``h`` [..., H]: this chip's share of the
    routed experts, then the shared experts (their mean: the ``1 / n_s``
    is in ``sd``).  Returns (y [..., H], counts int32
    [share.count_width])."""
    flat = h.reshape(-1, h.shape[-1])
    mask = None if row_mask is None else row_mask.reshape(-1)
    with jax.named_scope("routed_share"):
        routed, counts = ds.moe_ffn(
            cfg, flat, dict(wr=blk["wr"], li=li, **stacks), mask)
    with jax.named_scope("shared_experts"):
        y = routed + ds._gated_mlp(flat, blk["sg"], blk["su"], blk["sd"],
                                   flat.dtype)
    return y.reshape(h.shape), counts


def _walk(cfg, params, x, carry, window, full, row_mask=None):
    """Every layer in published order, the stacked weights the scans'
    ``xs`` and ``carry`` (cache arrays) updated where it lies: a scan
    over the periods, in each a scan over its three window layers, then
    its full layer.  ``window(h, blk, w, carry) -> (a, carry)`` and
    ``full(h, blk, f, carry) -> (a, carry)`` run a layer's attention
    (``w`` / ``f`` the layer's index in its stack) on the normed input
    ``h``; the expert layer reads the same ``h``.  Returns (x, carry,
    counts int32 [layers, count_width]: window layers, then full)."""
    eps = cfg.layer_norm_eps
    P = cfg.periods

    def parts(stack):
        stacks = {k: stack[k] for k in ds.EXPERT_STACKS}
        return stacks, {k: v for k, v in stack.items() if k not in stacks}

    w_stacks, w_sliced = parts(params["window"])
    f_stacks, f_sliced = parts(params["full"])
    w_sliced = jax.tree_util.tree_map(
        lambda v: v.reshape(P, 3, *v.shape[1:]), w_sliced)

    def layer(x, blk, stacks, li, attend, carry):
        with jax.named_scope("layer"):
            h = _layer_norm(x, blk["ln"], eps)
            a, carry = attend(h, blk, li, carry)
            m, counts = _experts(cfg, h, blk, stacks, li, row_mask)
            return x + a + m, carry, counts

    def period(c, xs):
        x, carry = c
        w_blk, f_blk, p = xs

        def one(c, ys):
            x, carry = c
            blk, j = ys
            x, carry, counts = layer(x, blk, w_stacks, 3 * p + j, window,
                                     carry)
            return (x, carry), counts

        (x, carry), w_counts = jax.lax.scan(
            one, (x, carry), (w_blk, jnp.arange(3, dtype=jnp.int32)))
        x, carry, f_counts = layer(x, f_blk, f_stacks, p, full, carry)
        return (x, carry), (w_counts, f_counts)

    (x, carry), (w_counts, f_counts) = jax.lax.scan(
        period, (x, tuple(carry)),
        (w_sliced, f_sliced, jnp.arange(P, dtype=jnp.int32)))
    width = cfg.expert_share.count_width
    counts = jnp.concatenate([w_counts.reshape(-1, width),
                              f_counts.reshape(-1, width)])
    return x, carry, counts


def _head(cfg, params, x):
    with jax.named_scope("head_sample"):
        h = _layer_norm(x, params["norm_f"], cfg.layer_norm_eps)
        logits = jnp.einsum("...h,vh->...v", h,
                            params["embed"].astype(h.dtype),
                            preferred_element_type=jnp.float32)
        return logits if cfg.logit_scale == 1 else logits * cfg.logit_scale


def _embed(cfg, params, tokens):
    with jax.named_scope("embed"):
        return jnp.take(params["embed"], tokens, axis=0).astype(
            jnp.dtype(cfg.dtype))


def _ring_geometry(cfg, ring):
    """(window, rows a ring page holds, ring pages a slot owns)."""
    W, rp = cfg.sliding_window, ring.shape[2]
    return W, rp, W // rp


def _ring_pages(cfg, ring, slot_ids):
    """int32 [B, W / rp]: the strip of ring pages each slot owns (a slot
    id past the slots names pages past the array: dropped)."""
    _, _, n = _ring_geometry(cfg, ring)
    return slot_ids[:, None] * n + jnp.arange(n, dtype=jnp.int32)


# --------------------------------------------------------------------------
# a sequence: forward, prefill and chunked prefill are one walk
# --------------------------------------------------------------------------

def _sequence(params, cfg, pools, tokens, count, offset=0, slot_ids=None,
              ptab=None, prior=False, flash=False):
    """The model over ``tokens`` [B, T], rows at absolute positions
    ``offset ..``, of which the first ``count`` [B] are true.

    ``pools`` () keeps nothing (:func:`forward`); else the engine's
    tuple (pool K, pool V, ring K, ring V), and every layer leaves in it
    what a later call for ``slot_ids`` [B] needs: the full layers' K/V
    through ``ptab`` (page ids of the rows' positions), the rings' last
    ``W`` rows as of position ``offset + count``.  ``prior``: the rows
    continue what the slot holds (a chunk; one row, ``ptab`` its whole
    table).  ``flash``: attention through the flash forward (a wave no
    longer than the window).  Returns (the last layer's output [B, T,
    H], pools): the caller puts the rows it wants through the head."""
    cd = jnp.dtype(cfg.dtype)
    B, T = tokens.shape
    W, hd = cfg.sliding_window, cfg.head_dim
    keep = bool(pools)
    q_pos = offset + jnp.arange(T, dtype=jnp.int32)              # [T]
    rows = jnp.arange(W, dtype=jnp.int32)

    def window(h, blk, w, carry):
        with jax.named_scope("window_attn"):
            q, k, v = _qkv(cfg, blk, h, q_pos[None], True)
            k_all, v_all, k_pos, k_ok = k, v, q_pos, jnp.ones((T,), bool)
            if keep:
                pool_k, pool_v, ring_k, ring_v = carry
                _, rp, n = _ring_geometry(cfg, ring_k)
                pages = _ring_pages(cfg, ring_k, slot_ids)
            if prior:
                # ring row j holds the last position before ``offset``
                # that is j mod W, if there is one
                old_pos = offset - 1 - jnp.mod(offset - 1 - rows, W)
                old = [r[w, pages].reshape(B, W, -1, hd).astype(cd)
                       for r in (ring_k, ring_v)]
                k_all = jnp.concatenate([old[0], k], 1)
                v_all = jnp.concatenate([old[1], v], 1)
                k_pos = jnp.concatenate([old_pos, q_pos])
                k_ok = jnp.concatenate([old_pos >= 0, k_ok])
            if flash:
                a = _attend_flash(cfg, q, k, v, count)
            else:
                mask = (k_ok & (k_pos <= q_pos[:, None])
                        & (k_pos > q_pos[:, None] - W))           # [T, K]
                a = _attend(cfg, q, k_all, v_all, mask[None])
            if keep:
                # ring row j now holds the last position before ``offset
                # + count`` that is j mod W: one of these rows, or what
                # it held
                end = (offset + count)[:, None]                   # [B, 1]
                new_pos = end - 1 - jnp.mod(end - 1 - rows, W)    # [B, W]
                src = jnp.clip(new_pos - offset, 0, T - 1)
                with jax.named_scope("kv_write"):
                    strips = []
                    for ring, fresh in ((ring_k, k), (ring_v, v)):
                        strip = jnp.take_along_axis(
                            fresh.reshape(B, T, -1), src[..., None], 1)
                        if prior:
                            strip = jnp.where(
                                (new_pos >= offset)[..., None], strip,
                                ring[w, pages].reshape(B, W, -1).astype(cd))
                        strips.append(ring.at[w, pages].set(
                            strip.reshape(B, n, rp, -1).astype(ring.dtype),
                            mode="drop"))
                carry = (pool_k, pool_v, *strips)
            return a @ blk["wo"].astype(cd), carry

    def full(h, blk, f, carry):
        with jax.named_scope("full_attn"):
            q, k, v = _qkv(cfg, blk, h, None, False)
            k_all, v_all, k_pos = k, v, q_pos
            if keep:
                pool_k, pool_v, ring_k, ring_v = carry
                ps = pool_k.shape[2]
                if prior:
                    # the slot's page view with these rows spliced in;
                    # only the rows' own pages are written back
                    views = []
                    for pool, new in ((pool_k, k), (pool_v, v)):
                        view = pool[f, ptab].reshape(1, -1, *k.shape[2:])
                        views.append(jax.lax.dynamic_update_slice(
                            view.astype(cd), new, (0, offset, 0, 0)))
                    k_all, v_all = views
                    k_pos = jnp.arange(k_all.shape[1], dtype=jnp.int32)
                    at = (f, jax.lax.dynamic_slice(ptab, (offset // ps,),
                                                   (T // ps,)))
                else:
                    at = (f, ptab.reshape(-1))
                with jax.named_scope("kv_write"):
                    lead = (B * (T // ps), ps)
                    (pool_k,) = gpt._write((pool_k,), at, k, lead)
                    (pool_v,) = gpt._write((pool_v,), at, v, lead)
                carry = (pool_k, pool_v, ring_k, ring_v)
            if flash:
                a = _attend_flash(cfg, q, k, v, count)
            else:
                a = _attend(cfg, q, k_all, v_all,
                            (k_pos <= q_pos[:, None])[None])
            return a @ blk["wo"].astype(cd), carry

    x, pools, _ = _walk(cfg, params, _embed(cfg, params, tokens), pools,
                        window, full)
    return x, pools


def forward(params, tokens, cfg: Cohere2MoeConfig):
    """tokens [B, N] int32 -> logits [B, N, V] float32.  No cache."""
    count = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    return _head(cfg, params, _sequence(params, cfg, (), tokens, count)[0])


# --------------------------------------------------------------------------
# the paged engine's family interface (inference/serving.py names it)
# --------------------------------------------------------------------------

def check_serving(cfg, *, engine, quant=None, kv_dtype=None, tp=1, pp=1,
                  kv_handoff=False, host_tier_mb=0.0):
    """Raise, by name, for every composition this family does not build
    (a prefill bucket longer than the window on the flash path is
    refused where the wave's program is built: :func:`prefill_paged`)."""
    why = {
        "the slot engine (ServingEngine)": (
            engine == "ServingEngine",
            "its per-slot K/V strip is a GPT program's — use "
            "PagedServingEngine"),
        "speculative decoding": (
            engine not in ("ServingEngine", "PagedServingEngine"),
            "a rejected draft would have to roll the rings back"),
        "quant=": (quant is not None,
                   "gpt.quantize_params knows GPT's leaves only"),
        "kv_dtype='int8'": (
            kv_dtype == "int8",
            "the grouped-query kernel reads bf16 or float32 pages; the "
            "rings have no quantized form"),
        "tp > 1": (int(tp) > 1,
                   "no layer has a sharded form and the experts' exchange "
                   "is not built"),
        "pp > 1": (int(pp) > 1, "gpt_pp's stage step is a GPT program"),
        "kv_handoff (KV extract/inject)": (
            bool(kv_handoff),
            "a payload would have to carry the slot's rings beside its "
            "pages"),
        "the host KV tier": (
            float(host_tier_mb or 0) > 0,
            "a page faulted back restores the full layers' K/V but not "
            "the rings"),
    }
    for name, (hit, reason) in why.items():
        if hit:
            raise ValueError(f"cohere2_moe does not compose with {name} "
                             f"yet — {reason}")


def shard_params_for_serving(params, cfg, mesh):
    raise ValueError("cohere2_moe does not compose with tp > 1 / pp > 1 "
                     "yet")


def kv_pool_spec(mesh):
    return (None, None, None, None)


def prefix_salt(cfg):
    """What the pager's prefix hashes are salted with: a page of this
    family holds the full layers' K/V of these widths (and a layer's
    output is the expert share's)."""
    sh = cfg.expert_share
    return (f"/family=cohere2_moe/kv={cfg.num_key_value_heads}x"
            f"{cfg.head_dim}/full_layers={cfg.periods}"
            f"/experts={sh.first}+{sh.held}of{sh.routed}")


def kv_bytes_per_position(cfg, itemsize):
    """Bytes of K and V one cached position holds in the pool: the full
    layers' (the window layers keep rings: :func:`slot_state_shapes`)."""
    return cfg.periods * 2 * cfg.kv_width * itemsize


def slot_state_arrays(cfg):
    """The rings (K, V) at the end of ``init_paged_pools``' tuple are
    indexed by SLOT (``inference/serving.py::FAMILY_INTERFACE``)."""
    return 2


def ring_page_rows(cfg, page_size):
    """Rows of a ring page: the pool's page where it divides the
    window."""
    return math.gcd(int(page_size), cfg.sliding_window)


def paged_pool_shapes(cfg, num_pages, page_size):
    """The stored shapes of the full layers' K and V pools."""
    shape = (cfg.periods, num_pages, page_size, cfg.kv_width)
    return shape, shape


def slot_state_shapes(cfg, slots, page_size):
    """(shape, dtype) of the rings K and V."""
    rp = ring_page_rows(cfg, page_size)
    ring = (3 * cfg.periods, slots * (cfg.sliding_window // rp), rp,
            cfg.kv_width)
    return ((ring, jnp.dtype(cfg.dtype)),) * 2


def init_paged_pools(cfg, num_pages, page_size, dtype=None, mesh=None,
                     kv_quant=False, slots=None):
    """(pool K, pool V, ring K, ring V), zeros.  Page 0 is the scratch
    page."""
    if mesh is not None or kv_quant:
        raise ValueError("cohere2_moe does not compose with tp > 1 / pp > 1 "
                         "or kv_dtype='int8' yet")
    if slots is None:
        raise ValueError("cohere2_moe keeps its window layers' rings per "
                         "slot: init_paged_pools needs slots=")
    cd = jnp.dtype(dtype or cfg.dtype)
    return (*(gpt._pool_zeros(s, cd)
              for s in paged_pool_shapes(cfg, num_pages, page_size)),
            *(gpt._pool_zeros(s, cd)
              for s, _ in slot_state_shapes(cfg, slots, page_size)))


def prefill_uses_flash(cfg, b, s):
    return flash_prefill.use_flash_prefill(b, s, cfg.num_attention_heads,
                                           cfg.head_dim, cfg.head_dim)


def prefill_paged(params, cfg, pools, tokens, lens, ptab, slots=None):
    """Causal forward over padded prompts ``tokens`` [b, s] for the slots
    ``slots`` [b] (a pad row: a slot id past the slots): the full layers
    scatter their K/V through ``ptab`` [b, s / page_size]; every window
    layer's ring is OVERWRITTEN for the rows' slots with what the
    prompt leaves there (the reset of a slot given to a new request).
    Returns (logits of each row's last true position [b, V], pools)."""
    if slots is None:
        raise ValueError("cohere2_moe keeps per-slot rings: prefill_paged "
                         "needs the rows' slot ids (slots=)")
    b, s = tokens.shape
    flash = prefill_uses_flash(cfg, b, s)
    if flash and s > cfg.sliding_window:
        raise ValueError(
            f"cohere2_moe: a prefill bucket of {s} is longer than the "
            f"window {cfg.sliding_window}, and the flash forward masks "
            f"causality, not the window")
    x, pools = _sequence(params, cfg, pools, tokens, lens, slot_ids=slots,
                         ptab=ptab, flash=flash)
    return _head(cfg, params, ds.last_rows(x, lens)), pools


def chunk_paged(params, cfg, pools, tokens, pt_row, offset, slot=None,
                take=None):
    """One chunked-prefill piece for slot ``slot``: ``tokens`` [1, C]
    from absolute position ``offset`` (traced, a whole number of pages),
    of which the first ``take`` are true, continuing the slot's rings
    (starting them when ``offset`` is 0).  Returns (logits of the last
    true row [V], pools)."""
    if slot is None or take is None:
        raise ValueError("cohere2_moe keeps per-slot rings: chunk_paged "
                         "needs slot= and take=")
    take = jnp.reshape(take, (1,))
    x, pools = _sequence(params, cfg, pools, tokens, take, offset=offset,
                         slot_ids=jnp.reshape(slot, (1,)), ptab=pt_row,
                         prior=True)
    return _head(cfg, params, ds.last_rows(x, take)[0]), pools


def decode_extra_stats(cfg, flat):
    """The engine's counters from what :func:`decode_paged` returned
    beside the logits (host side, numpy): the expert layers' count
    vectors (``deepseek_v3.decode_extra_stats``), then the K/V rows the
    window layers and the full layers read, summed over the layers of
    each kind and the active slots."""
    out = ds.decode_extra_stats(cfg, flat[:-2])
    out["attn_window_rows"] = int(flat[-2])
    out["attn_full_rows"] = int(flat[-1])
    return out


def decode_paged(params, cfg, pools, page_table, write_pages, write_offs,
                 lens, tokens, mesh=None):
    """One decode iteration for every slot: one token a slot at its own
    ``lens[s]``.  A slot the step does not run (``lens == 0``) computes
    a row, writes the scratch page and leaves its rings as they are.
    Returns (logits [S, V] float32, pools, int32 [layers * count_width +
    2]: the expert layers' counts from the ACTIVE slots, then the K/V
    rows the window and the full layers read)."""
    cd = jnp.dtype(cfg.dtype)
    S = tokens.shape[0]
    _, _, ring_k, _ = pools
    W, rp, n = _ring_geometry(cfg, ring_k)
    live = lens > 0
    slot = jnp.arange(S, dtype=jnp.int32)
    ring_table = _ring_pages(cfg, ring_k, slot)
    row = jnp.mod(lens, W)
    # an idle slot's ring row goes to a page past the array: dropped
    ring_page = jnp.where(live, slot * n + row // rp, ring_k.shape[1])
    ring_lens = jnp.minimum(lens, W - 1)

    def attend(q, blk, pools_, table, lens_, index):
        with jax.named_scope("paged_attn"):
            a = paged_attn.paged_attention(q[:, None], pools_, table, lens_,
                                           index)
        return a.reshape(S, -1) @ blk["wo"].astype(cd)

    def window(h, blk, w, carry):
        pool_k, pool_v, ring_k, ring_v = carry
        with jax.named_scope("window_attn"):
            q, k, v = _qkv(cfg, blk, h, lens, True)
            with jax.named_scope("kv_write"):
                at = (w, ring_page, jnp.mod(row, rp))
                ring_k, ring_v = (
                    r.at[at].set(new.reshape(S, -1).astype(r.dtype),
                                 mode="drop")
                    for r, new in ((ring_k, k), (ring_v, v)))
            a = attend(q, blk, (ring_k, ring_v), ring_table, ring_lens, w)
        return a, (pool_k, pool_v, ring_k, ring_v)

    def full(h, blk, f, carry):
        pool_k, pool_v, ring_k, ring_v = carry
        with jax.named_scope("full_attn"):
            q, k, v = _qkv(cfg, blk, h, None, False)
            with jax.named_scope("kv_write"):
                at = (f, write_pages, write_offs)
                (pool_k,) = gpt._write((pool_k,), at, k, (S,))
                (pool_v,) = gpt._write((pool_v,), at, v, (S,))
            a = attend(q, blk, (pool_k, pool_v), page_table, lens, f)
        return a, (pool_k, pool_v, ring_k, ring_v)

    x, pools, counts = _walk(cfg, params, _embed(cfg, params, tokens), pools,
                             window, full, row_mask=live)
    read = jnp.stack([3 * cfg.periods * jnp.sum(jnp.where(live, ring_lens
                                                          + 1, 0)),
                      cfg.periods * jnp.sum(jnp.where(live, lens + 1, 0))])
    return (_head(cfg, params, x), pools,
            jnp.concatenate([counts.reshape(-1), read]).astype(jnp.int32))


def slot_state_of(cfg, pools, slot):
    """What ``slot`` holds that no page does: each window layer's ring,
    K and V [3 P, W, nkv * hd], row ``j`` the last position ``= j mod
    W`` the slot has folded in (``PagedServingEngine.slot_state`` hands
    it to the benchmark's check of the stored K/V)."""
    ring_k, ring_v = pools[2], pools[3]
    W, _, n = _ring_geometry(cfg, ring_k)
    return {name: r[:, slot * n:(slot + 1) * n].reshape(r.shape[0], W, -1)
            for name, r in (("ring_k", ring_k), ("ring_v", ring_v))}


# --------------------------------------------------------------------------
# the benchmark's checks of the sublayers
# --------------------------------------------------------------------------

def expert_sublayer(params, cfg, h, layer):
    """The expert layer of published layer ``layer`` (a Python int)
    alone over its normed input ``h`` [S, H], as the layer walk runs it
    (the experts' stacks whole and the layer's index traced), for the
    benchmark's check against the reference
    (``benchmark/drivers/serve_cohere2.py``, at a decode step's ``S =
    slots`` rows).  Returns (y [S, H], the router's chosen experts
    int32 [S, k])."""
    period, kind = divmod(layer, 4)
    stack = params["window" if kind < 3 else "full"]
    li = jnp.asarray(3 * period + kind if kind < 3 else period, jnp.int32)
    stacks = {k: stack[k] for k in ds.EXPERT_STACKS}
    blk = {k: stack[k][li] for k in ("wr", "sg", "su", "sd")}
    h = h.astype(jnp.dtype(cfg.dtype))
    y, _ = _experts(cfg, h, blk, stacks, li, None)
    chosen, _ = ds.route(cfg, h, blk["wr"], None)
    return y, chosen


def attention_sublayer(params, cfg, h, layer, rows, page_size):
    """The program's decode attention of published layer ``layer`` (a
    Python int) alone, for the benchmark's check against the reference
    (``benchmark/drivers/serve_cohere2.py``): ``h`` [N, H] is the layer's
    normed input at positions ``0 .. N - 1`` of ONE history (N a whole
    number of pages), ``rows`` int32 [S] the positions to compute, a
    decode step's slots.  The history's K/V are projected (and rotated)
    by the program and read by the decode kernel as a step reads them:
    a full layer's through a page table over the history's pages,
    masked at ``p``; a window layer's from a ring of ``W`` rows a slot
    (the window's positions, in another order than a ring's: the
    softmax does not see the order), masked at ``min(p, W - 1)``.
    Returns the sublayer's output [S, H] (after ``Wo``)."""
    cd = jnp.dtype(cfg.dtype)
    N = h.shape[0]
    W = cfg.sliding_window
    period, kind = divmod(layer, 4)
    rotary = kind < 3
    at = 3 * period + kind if rotary else period
    blk = {k: params["window" if rotary else "full"][k][at]
           for k in ("wqkv", "wo")}
    pos = jnp.arange(N, dtype=jnp.int32)
    _, k, v = _qkv(cfg, blk, h.astype(cd), pos, rotary)
    q, _, _ = _qkv(cfg, blk, h[rows].astype(cd), rows, rotary)
    S, C = rows.shape[0], cfg.kv_width
    if rotary:
        rp = ring_page_rows(cfg, page_size)
        start = jnp.maximum(rows - (W - 1), 0)
        at = start[:, None] + jnp.arange(W, dtype=jnp.int32)    # [S, W]
        rings = tuple(jnp.pad(a.reshape(N, C), ((0, W), (0, 0)))[at]
                      .reshape(1, S * (W // rp), rp, C) for a in (k, v))
        table = jnp.arange(S * (W // rp), dtype=jnp.int32).reshape(S, -1)
        lens = jnp.minimum(rows, W - 1)
    else:
        rings = tuple(a.reshape(1, N // page_size, page_size, C)
                      for a in (k, v))
        table = jnp.broadcast_to(
            jnp.arange(N // page_size, dtype=jnp.int32), (S, N // page_size))
        lens = rows
    a = paged_attn.paged_attention(q[:, None], rings, table, lens, 0)
    return a.reshape(S, -1) @ blk["wo"].astype(cd)
