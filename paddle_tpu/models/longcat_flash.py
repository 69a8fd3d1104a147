"""The ``longcat_flash`` model family (the config's own ``model_type``):
LongCat-Flash's language model — two latent-attention (MLA) blocks, two
dense FFNs and ONE expert layer a layer, the expert layer fed from the
first block and added at the end (the "shortcut-connected MoE", ScMoE),
softmax routing over routed and identity ("zero-compute") experts —
served through ``inference/serving.py::PagedServingEngine``.

Keys are the published ones (``config.json`` of
meituan-longcat/LongCat-Flash-Omni; transformers' ``LongcatFlash*``).
For a layer's input ``x`` at position ``p``::

    x1 = x  + MLA_0(rmsnorm(x;  g_in0))
    h0 = rmsnorm(x1; g_post0)
    m  = MoE(h0)                                   # the shortcut
    x2 = x1 + FFN_0(h0)
    x3 = x2 + MLA_1(rmsnorm(x2; g_in1))
    x' = x3 + FFN_1(rmsnorm(x3; g_post1)) + m

    MLA(h): q = (rmsnorm(h Wqa; g_qa) * sqrt(H / q_lora_rank)) Wqb
            c = rmsnorm(h Wdkv[:, :rank]; g_kv) * sqrt(H / rank)
            kr = rope(h Wdkv[:, rank:], p)  ;  q_rope = rope(q[..., nope:], p)
            [k_nope | v] = c Wukv ; score = (q_nope.k_nope + q_rope.kr) / sqrt(nope + rope)
    MoE(h0): s = softmax(h0 Wr)           over routed + identity experts, float32
             chosen = top_k(s + b)        b: selection only
             w = routed_scaling_factor * s[chosen]        (not renormalised)
             MoE = sum_{e routed} w_e SwiGLU_e(h0) + (sum_{e identity} w_e) h0
    logits = rmsnorm(x_last; g_f) Whead

The sublayers are ``models/deepseek_v3.py``'s: ``_mla_project`` with its
query low-rank path and latent scales, the three attention paths
(``prefill_attend``, ``chunk_attend``, ``decode_attend``: decode
absorbed through ``ops/pallas/paged_mla.py``, prefill through the flash
forward where ``use_flash_prefill`` says so) and ``moe_ffn``, told which
experts it holds (``LongcatFlashConfig.expert_share``): of the routed
experts this chip holds ``n_routed_experts`` from ``first_held_expert``
(a deployment shares each layer over chips by expert parallelism); it
routes over all ``n_routed_experts_total + zero_expert_num``, computes
its own experts' part and the identity term, and counts and skips what
other chips would compute (the exchange is not built).

The latent pool is deepseek_v3's with TWO attention blocks a layer on
its leading axis: ``[2 L, pages, page_size, kv_lora_rank]`` and
``[2 L, pages, page_size, 128]``; block ``j`` of layer ``l`` is ``2 l +
j``.  Layers run as one ``gpt._layer_scan`` over the stacked layers,
each step writing its two blocks where they lie.

Parameter tree: ``embed [V, H]``, ``head [H, V]``, ``norm_f [H]`` and
``layers`` stacked on a leading axis: ``attn`` (two blocks: ``g_in, wqa,
g_qa, wqb, wdkv, g_kv, wukv, wo, g_post``), ``mlp`` (two: ``wg, wu,
wd``) and ``moe`` (``wr, b, eg, eu, ed``; the expert stacks go to the
grouped matmul whole, with the layer's index).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from . import deepseek_v3 as ds
from . import gpt


@dataclasses.dataclass
class LongcatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int | None = 1536
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    routed_scaling_factor: float = 6.0
    # routed experts HELD here, from ``first_held_expert``, of the
    # ``n_routed_experts_total`` the router routes over
    n_routed_experts: int = 512
    n_routed_experts_total: int = 512
    first_held_expert: int = 0
    zero_expert_num: int = 256
    zero_expert_type: str = "identity"
    moe_topk: int = 12
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    rope_interleave: bool = True
    rope_scaling: dict | None = None
    attention_method: str = "MLA"
    attention_bias: bool = False
    router_bias: bool = False
    norm_topk_prob: bool = False
    tie_word_embeddings: bool = False
    hidden_act: str = "silu"
    dtype: str = "bfloat16"          # compute dtype
    param_dtype: str = "bfloat16"
    initializer_range: float = 0.02
    # b is trained, not initialised: drawn uniform in +-this share of
    # the mean softmax score 1 / (router width)
    router_bias_range: float = 0.01

    def __post_init__(self):
        unbuilt = {
            "zero_expert_type != identity":
                self.zero_expert_type != "identity",
            "attention_method != MLA": self.attention_method != "MLA",
            "q_lora_rank null": self.q_lora_rank is None,
            "rope_interleave false": not self.rope_interleave,
            "rope_scaling": self.rope_scaling is not None,
            "attention_bias": self.attention_bias,
            "router_bias": self.router_bias,
            "hidden_act != silu": self.hidden_act != "silu",
            "tie_word_embeddings": self.tie_word_embeddings,
        }
        bad = [k for k, v in unbuilt.items() if v]
        if bad:
            raise ValueError(f"longcat_flash: not built here: {bad}")
        if self.qk_rope_head_dim % 2 or self.qk_rope_head_dim > ds.ROPE_LANES:
            raise ValueError(f"qk_rope_head_dim {self.qk_rope_head_dim}")
        self.expert_share          # raises for a share that is not one

    # what the engine asks of any family's config
    @property
    def max_seq_len(self):
        return self.max_position_embeddings

    @property
    def num_heads(self):
        return self.num_attention_heads

    # what deepseek_v3's sublayers ask
    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def num_experts_per_tok(self):
        return self.moe_topk

    @property
    def scoring_func(self):
        return "softmax"

    @property
    def expert_share(self):
        return ds.ExpertShare(self.first_held_expert, self.n_routed_experts,
                              self.n_routed_experts_total,
                              self.zero_expert_num).checked()

    @property
    def attention_blocks(self):
        return 2 * self.num_layers


def longcat_flash_tiny(**kw):
    """The CPU tests' size: every mechanism, no published width; a
    share of 4 of 12 routed experts beside 6 identity ones."""
    base = dict(vocab_size=256, hidden_size=64, ffn_hidden_size=96,
                expert_ffn_hidden_size=32, num_layers=2,
                num_attention_heads=4, kv_lora_rank=32, q_lora_rank=48,
                qk_rope_head_dim=8, qk_nope_head_dim=16, v_head_dim=16,
                n_routed_experts=4, n_routed_experts_total=12,
                first_held_expert=4, zero_expert_num=6, moe_topk=4,
                max_position_embeddings=256, dtype="float32",
                param_dtype="float32")
    base.update(kw)
    return LongcatFlashConfig(**base)


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def init_params(cfg: LongcatFlashConfig, key):
    """Seeded random weights, the layers stacked on a leading axis."""
    H, nh, L = cfg.hidden_size, cfg.num_attention_heads, cfg.num_layers
    E, Im = cfg.n_routed_experts, cfg.expert_ffn_hidden_size
    width = cfg.expert_share.width
    pd = jnp.dtype(cfg.param_dtype)
    std = cfg.initializer_range
    res = std / math.sqrt(2.0 * cfg.attention_blocks)
    keys = iter(jax.random.split(key, 40))

    def nrm(shape, scale=std):
        return (jax.random.normal(next(keys), (L,) + shape, jnp.float32)
                * scale).astype(pd)

    def ones(n):
        return jnp.ones((L, n), pd)

    def attn():
        return {
            "g_in": ones(H),
            "wqa": nrm((H, cfg.q_lora_rank)),
            "g_qa": ones(cfg.q_lora_rank),
            "wqb": nrm((cfg.q_lora_rank, nh * cfg.qk_head_dim)),
            "wdkv": nrm((H, cfg.kv_lora_rank + cfg.qk_rope_head_dim)),
            "g_kv": ones(cfg.kv_lora_rank),
            "wukv": nrm((cfg.kv_lora_rank,
                         nh * (cfg.qk_nope_head_dim + cfg.v_head_dim))),
            "wo": nrm((nh * cfg.v_head_dim, H), res),
            "g_post": ones(H),
        }

    def mlp():
        F = cfg.ffn_hidden_size
        return {"wg": nrm((H, F)), "wu": nrm((H, F)), "wd": nrm((F, H), res)}

    moe = {"wr": nrm((H, width)),
           "b": (jax.random.uniform(next(keys), (L, width), jnp.float32,
                                    -1.0, 1.0)
                 * cfg.router_bias_range / width),
           "eg": nrm((E, H, Im)), "eu": nrm((E, H, Im)),
           "ed": nrm((E, Im, H), res)}
    layers = {"attn": [attn(), attn()], "mlp": [mlp(), mlp()], "moe": moe}

    def table(shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * std).astype(pd)

    return {"embed": table((cfg.vocab_size, H)),
            "head": table((H, cfg.vocab_size)),
            "norm_f": jnp.ones((H,), pd), "layers": layers}


# --------------------------------------------------------------------------
# the double layer
# --------------------------------------------------------------------------

def _layer(cfg, x, blk, stacks, li, attend, pools, row_mask=None):
    """One layer over ``x`` [..., H].  ``attend(j, x, block, pools) ->
    (a, pools)`` runs attention block ``j``'s projections, cache writes
    and attention.  Returns (x, pools, the expert layer's counts)."""
    cd = jnp.dtype(cfg.dtype)
    eps = cfg.rms_norm_eps
    (a0, a1), (f0, f1) = blk["attn"], blk["mlp"]
    a, pools = attend(0, x, a0, pools)
    with jax.named_scope("mla_out"):
        x = x + a @ a0["wo"].astype(cd)
    h0 = ds._rmsnorm(x, a0["g_post"], eps)
    with jax.named_scope("scmoe_shortcut"):
        mask = None if row_mask is None else row_mask.reshape(-1)
        m, counts = ds.moe_ffn(cfg, h0.reshape(-1, h0.shape[-1]),
                               dict(blk["moe"], li=li, **stacks), mask)
    with jax.named_scope("dense_mlp"):
        x = x + ds._gated_mlp(h0, f0["wg"], f0["wu"], f0["wd"], cd)
    a, pools = attend(1, x, a1, pools)
    with jax.named_scope("mla_out"):
        x = x + a @ a1["wo"].astype(cd)
    h1 = ds._rmsnorm(x, a1["g_post"], eps)
    with jax.named_scope("dense_mlp"):
        x = x + ds._gated_mlp(h1, f1["wg"], f1["wu"], f1["wd"], cd)
    return x + m.reshape(x.shape), pools, counts


def _layers(cfg, params, attend, x, pools=(), row_mask=None):
    """Every layer, ``pools`` carried (``gpt._layer_scan``); the expert
    stacks go whole to the grouped matmul with the layer's index.
    ``attend(j, x, block, pools, layer)`` with ``layer`` the pool's
    block ``2 l + j``.  Returns (x, pools, stacked counts)."""
    moe = params["layers"]["moe"]
    stacks = {k: moe[k] for k in ds.EXPERT_STACKS}
    sliced = dict(params["layers"],
                  moe={k: v for k, v in moe.items() if k not in stacks})

    def body(xx, blk, li, pp):
        return _layer(cfg, xx, blk, stacks, li,
                      lambda j, y, b, q: attend(j, y, b, q, 2 * li + j),
                      pp, row_mask)

    return gpt._layer_scan(body, x, sliced, tuple(pools))


def forward(params, tokens, cfg: LongcatFlashConfig):
    """tokens [B, N] int32 -> logits [B, N, V] float32.  No cache."""
    B, N = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32), (B, N))

    def attend(j, x, blk, pools, layer):
        q_nope, q_rope, c, kr = ds._mla_project(cfg, x, blk, pos)
        with jax.named_scope("mla_attn"):
            return ds._mla_attend(cfg, blk, q_nope, q_rope, c, kr,
                                  ds._causal(N, N)), pools

    x, _, _ = _layers(cfg, params, attend, ds._embed(cfg, params, tokens))
    return ds._head(cfg, params, x)


def expert_layer(params, cfg, h, layer):
    """The expert sublayer of ``layer`` (an int or a traced int32) alone
    over its normed input ``h`` [T, H]: the program's own
    (``deepseek_v3.moe_ffn`` with the experts' stacks whole and the
    layer's index, as the scan hands them), for the benchmark's check of
    the held experts against the reference (``drivers/serve_scmoe.py``,
    at a decode step's ``T = slots`` rows)."""
    moe = params["layers"]["moe"]
    li = jnp.asarray(layer, jnp.int32)
    blk = {k: v if k in ds.EXPERT_STACKS else v[li]
           for k, v in moe.items()}
    return ds.moe_ffn(cfg, h, dict(blk, li=li))[0]


# --------------------------------------------------------------------------
# the paged engine's family interface (inference/serving.py names it)
# --------------------------------------------------------------------------

check_serving = ds.check_serving
decode_extra_stats = ds.decode_extra_stats
kv_pool_spec = ds.kv_pool_spec
decode_group_pages = ds.decode_group_pages
decode_copy_descriptors = ds.decode_copy_descriptors


def shard_params_for_serving(params, cfg, mesh):
    raise ValueError("longcat_flash does not compose with tp > 1 / pp > 1 "
                     "yet")


def prefix_salt(cfg):
    """What the pager's prefix hashes are salted with, so that a page of
    this family never aliases another family's page of the same tokens
    (nor a page of another expert share's: the layer's output is the
    share's)."""
    sh = cfg.expert_share
    return (f"/family=longcat_flash/latent={cfg.kv_lora_rank}"
            f"+{cfg.qk_rope_head_dim}x{cfg.attention_blocks}"
            f"/experts={sh.first}+{sh.held}of{sh.routed}+{sh.zero}")


def kv_bytes_per_position(cfg, itemsize):
    """Bytes one cached position NEEDS, both blocks of every layer."""
    return (cfg.attention_blocks
            * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * itemsize)


def paged_pool_shapes(cfg, num_pages, page_size):
    """``deepseek_v3.paged_pool_shapes``' one array of ``c`` and rope
    rows, two blocks a layer."""
    return ((cfg.attention_blocks, num_pages, page_size,
             cfg.kv_lora_rank + ds.ROPE_LANES),)


def init_paged_pools(cfg, num_pages, page_size, dtype=None, mesh=None,
                     kv_quant=False):
    """The latent pool: ``(rows,)``, zeros, two blocks a layer.  Page 0
    is the scratch page."""
    assert mesh is None and not kv_quant
    cd = jnp.dtype(dtype or cfg.dtype)
    return tuple(gpt._pool_zeros(s, cd)
                 for s in paged_pool_shapes(cfg, num_pages, page_size))


def prefill_paged(params, cfg, pools, tokens, lens, ptab):
    """Causal forward over padded prompts ``tokens`` [b, s]; both blocks
    of every layer scatter their latents through ``ptab`` [b, s /
    page_size].  Returns (logits of each row's last true position [b,
    V], pools)."""
    b, s = tokens.shape
    flat = ptab.reshape(-1)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    flash = ds.prefill_uses_flash(cfg, b, s)

    def attend(j, x, blk, pp, layer):
        return ds.prefill_attend(cfg, blk, x, pos, pp, layer, flat, lens,
                                 flash)

    x, pools, _ = _layers(cfg, params, attend,
                          ds._embed(cfg, params, tokens), pools)
    return ds._head(cfg, params, ds.last_rows(x, lens)), pools


def chunk_paged(params, cfg, pools, tokens, pt_row, offset):
    """One chunked-prefill piece for one slot (``ds.chunk_attend`` for
    each block).  Returns (logits [1, C, V], pools)."""
    C = tokens.shape[1]
    pos = (offset + jnp.arange(C, dtype=jnp.int32))[None]

    def attend(j, x, blk, pp, layer):
        return ds.chunk_attend(cfg, blk, x, pos, pp, layer, pt_row, offset)

    x, pools, _ = _layers(cfg, params, attend,
                          ds._embed(cfg, params, tokens), pools)
    return ds._head(cfg, params, x), pools


def decode_paged(params, cfg, pools, page_table, write_pages, write_offs,
                 lens, tokens, mesh=None, absorbed=True):
    """One decode iteration for every slot.  Returns (logits [S, V]
    float32, pools, counts int32 [layers, held + 4]: the held experts'
    loads, the identity and the remote assignments, from the ACTIVE
    slots — ``lens > 0`` —, then the rows the expert layer's combine
    walked and its output's rows, from every slot).  ``absorbed`` as
    ``deepseek_v3``'s."""
    active = lens > 0

    def attend(j, x, blk, pp, layer):
        return ds.decode_attend(cfg, blk, x, lens, pp, layer, page_table,
                                write_pages, write_offs, absorbed)

    x, pools, counts = _layers(cfg, params, attend,
                               ds._embed(cfg, params, tokens), pools,
                               row_mask=active)
    return ds._head(cfg, params, x), pools, counts
