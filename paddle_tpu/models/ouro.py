"""The ``ouro`` model family (the config's own ``model_type``): a looped
language model — one stack of dense layers run ``total_ut_steps`` times
over the SAME weights, an exit gate after every pass — served through
``inference/serving.py::PagedServingEngine``.

Keys are the published ones (``config.json`` of ByteDance/Ouro-2.6B;
arXiv 2510.25741).  With ``N(x; g) = x * rsqrt(mean(x^2) + eps) * g`` in
float32, one layer on ``x`` at positions ``p``::

    h = N(x; g1) ;  q, k, v = h Wq^T, h Wk^T, h Wv    -> [nh, hd] each, no bias
    q, k = rope(q, p), rope(k, p)                       half-split, base rope_theta
    a = softmax(q k^T / sqrt(hd)) v  Wo                 causal
    x = x + N(a; g2)
    m = (silu(N(x; g3) Wg) * (N(x; g3) Wu)) Wd
    x = x + N(m; g4)                                    four norms a layer

and the model, for ``t = 1..T``::

    x^0 = E[tokens]
    x^t = N(layer_L(... layer_1(x^(t-1))); g_f)         the same L layers every pass
    lam_t = sigmoid(w_e . x^t + b_e)                    one gate, shared by the passes
    p_t = lam_t prod_{j<t}(1 - lam_j)  (t < T),  p_T the remainder
    leave at the first t whose  sum_{j<=t} p_j >= early_exit_threshold
    logits = Whead x^(that t)                           t = T at the published threshold 1

``rope`` is the ``rotate_half`` convention: column ``i`` of a head turns
with column ``i + hd/2`` at ``theta^(-2i/hd)`` (not the adjacent pairs
of ``deepseek_v3._rope``).

The cache: pass ``t`` of layer ``l`` keeps K and V of its own, so a
cached position holds ``T * L`` layers' K and V.  The pool is GPT's
layout with ``T * L`` on the leading axis, ``[T * L, pages, page_size,
nh * hd]``, and the index every pool access and the decode kernel take
is ``t * L + l`` (``ops/pallas/paged_attn.py``, shared with GPT as it
is).  The passes are a ``scan`` around ``gpt._layer_scan``: the pool is
a carry of both, updated where it lies.

Parameter tree: ``embed [V, H]``, ``head [H, V]``, ``norm_f [H]``,
``gate_w [H]``, ``gate_b []`` and ``layers`` (stacked on a leading
axis: ``g1..g4 [L, H]``, ``wq, wk [L, nh * hd, H]``, ``wv [L, H, nh *
hd]``, ``wo [L, nh * hd, H]``, ``wg, wu [L, H, I]``, ``wd [L, I, H]``).
``wq`` and ``wk`` are stored (out, in), as the published checkpoint's
``Linear`` weights are: the compiler computes q and k with the head's
columns major for the half-split rotation, and from (in, out) stacks it
copied both into that layout in every program (0.75 GiB of temporaries
by ``memory_analysis()``; AOT, PR 33).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from . import gpt


@dataclasses.dataclass
class OuroConfig:
    vocab_size: int = 49152
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    intermediate_size: int = 5632
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    rope_scaling: dict | None = None
    max_position_embeddings: int = 65536
    tie_word_embeddings: bool = False
    sliding_window: int | None = None
    use_sliding_window: bool = False
    layer_types: list | None = None
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    dtype: str = "bfloat16"          # compute dtype
    param_dtype: str = "bfloat16"
    initializer_range: float = 0.02

    def __post_init__(self):
        unbuilt = {
            "num_key_value_heads != num_attention_heads":
                self.num_key_value_heads != self.num_attention_heads,
            "hidden_act != silu": self.hidden_act != "silu",
            "rope_scaling": self.rope_scaling is not None,
            "tie_word_embeddings": self.tie_word_embeddings,
            "sliding_window": self.use_sliding_window
            or self.sliding_window is not None,
            "layer_types other than full_attention": any(
                t != "full_attention" for t in self.layer_types or ()),
        }
        bad = [k for k, v in unbuilt.items() if v]
        if bad:
            raise ValueError(f"ouro: not built here: {bad}")
        if self.total_ut_steps < 1 or self.head_dim % 2:
            raise ValueError(f"ouro: total_ut_steps {self.total_ut_steps}, "
                             f"head_dim {self.head_dim}")

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
    def virtual_layers(self):
        """(pass, layer) pairs: what the pool's leading axis counts."""
        return self.total_ut_steps * self.num_hidden_layers


def ouro_tiny(**kw):
    """The CPU tests' size: every mechanism, no published width."""
    base = dict(vocab_size=512, hidden_size=64, num_hidden_layers=3,
                num_attention_heads=4, num_key_value_heads=4, head_dim=32,
                intermediate_size=96, total_ut_steps=3,
                max_position_embeddings=256, dtype="float32",
                param_dtype="float32")
    base.update(kw)
    return OuroConfig(**base)


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def init_params(cfg: OuroConfig, key):
    """Seeded random weights: normal, std ``initializer_range``, the
    residual projections (Wo, Wd) scaled by 1 / sqrt(2 L); gains 1,
    the gate's bias 0."""
    H, L, I = cfg.hidden_size, cfg.num_hidden_layers, cfg.intermediate_size
    A = cfg.num_attention_heads * cfg.head_dim
    pd = jnp.dtype(cfg.param_dtype)
    std = cfg.initializer_range
    res = std / math.sqrt(2.0 * L)
    keys = iter(jax.random.split(key, 16))

    def nrm(shape, scale=std):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(pd)

    layers = {f"g{i}": jnp.ones((L, H), pd) for i in (1, 2, 3, 4)}
    layers.update(wq=nrm((L, A, H)), wk=nrm((L, A, H)), wv=nrm((L, H, A)),
                  wo=nrm((L, A, H), res), wg=nrm((L, H, I)),
                  wu=nrm((L, H, I)), wd=nrm((L, I, H), res))
    return {"embed": nrm((cfg.vocab_size, H)),
            "head": nrm((H, cfg.vocab_size)),
            "norm_f": jnp.ones((H,), pd), "gate_w": nrm((H,)),
            "gate_b": jnp.zeros((), pd), "layers": layers}


# --------------------------------------------------------------------------
# the sublayers
# --------------------------------------------------------------------------

def _rmsnorm(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, theta):
    """Half-split rotary embedding over the whole head: x [..., nh, hd],
    ``pos`` int32 over x's leading axes.  Column i turns with column
    i + hd/2 by ``pos * theta^(-2i/hd)``; float32 inside."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


def _attention(q, k, v, offset=0):
    """Causal attention as written.  q: [B, T, nh, hd] whose row i sits
    at absolute position ``offset + i``; k, v: [B, K, nh, hd] from
    position 0.  Scores and softmax in float32."""
    cd = q.dtype
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k.astype(cd),
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(q.shape[-1])
    mask = (jnp.arange(k.shape[1])[None, :]
            <= offset + jnp.arange(q.shape[1])[:, None])
    probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), -1).astype(cd)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(cd))


def _layer(cfg, x, blk, pos, attend):
    """One layer on ``x`` [..., H] at positions ``pos`` (x's leading
    axes).  ``attend(q, k, v) -> (a, aux)`` is the attention inner loop
    over [..., nh, hd]: each paged program writes its cache there.
    Returns (x, aux)."""
    cd = jnp.dtype(cfg.dtype)
    eps = cfg.rms_norm_eps
    heads = (cfg.num_attention_heads, cfg.head_dim)
    lead = x.shape[:-1]
    with jax.named_scope("attn_qkv"):
        h = _rmsnorm(x, blk["g1"], eps)
        q, k = (jnp.einsum("...h,ah->...a", h, blk[w].astype(cd))
                .reshape(*lead, *heads) for w in ("wq", "wk"))
        v = (h @ blk["wv"].astype(cd)).reshape(*lead, *heads)
    with jax.named_scope("rope"):
        q = _rope(q, pos, cfg.rope_theta)
        k = _rope(k, pos, cfg.rope_theta)
    a, aux = attend(q, k, v)
    with jax.named_scope("attn_out"):
        a = a.reshape(*lead, -1) @ blk["wo"].astype(cd)
        x = x + _rmsnorm(a, blk["g2"], eps)
    with jax.named_scope("mlp"):
        h = _rmsnorm(x, blk["g3"], eps)
        m = ((jax.nn.silu(h @ blk["wg"].astype(cd))
              * (h @ blk["wu"].astype(cd))) @ blk["wd"].astype(cd))
        x = x + _rmsnorm(m, blk["g4"], eps)
    return x, aux


def exit_steps(lam, threshold):
    """The published exit rule.  lam: float32 [T, ...], the gate after
    each pass.  Returns int32 [...] in 1..T: the first pass whose
    cumulative exit probability reaches ``threshold`` (the last pass
    takes the remainder, so it always does)."""
    stay = jnp.cumprod(1.0 - lam[:-1], 0)                 # prod_{j<=t}
    before = jnp.concatenate([jnp.ones_like(lam[:1]), stay[:-1]], 0)
    cdf = jnp.cumsum(lam[:-1] * before, 0)                # passes 1..T-1
    return 1 + jnp.sum(cdf < threshold, 0).astype(jnp.int32)


def _passes(params, cfg, x, pools, layer_body, pick=lambda x: x):
    """The loop: ``total_ut_steps`` passes of the stacked layers, the
    final norm and the gate after each.  ``layer_body(x, blk, vl, pools)
    -> (x, pools)`` runs one layer whose cache is virtual layer ``vl =
    t * L + l``; ``pick`` takes the rows of a pass's output that the
    head will see.  Returns (those rows of the pass each LEAVES at,
    pools, lam float32 [T, rows...], leave int32 [rows...])."""
    L = cfg.num_hidden_layers

    def one_pass(carry, t):
        xx, pp = carry
        with jax.named_scope("ut_step"):
            xx, pp, _ = gpt._layer_scan(
                lambda y, blk, i, p: (*layer_body(y, blk, t * L + i, p),
                                      None),
                xx, params["layers"], pp)
            with jax.named_scope("loop_norm"):
                xx = _rmsnorm(xx, params["norm_f"], cfg.rms_norm_eps)
            rows = pick(xx)
            with jax.named_scope("exit_gate"):
                f32 = jnp.float32
                lam = jax.nn.sigmoid(
                    jnp.sum(rows.astype(f32) * params["gate_w"].astype(f32),
                            -1) + params["gate_b"].astype(f32))
        return (xx, pp), (rows, lam)

    (_, pools), (rows, lam) = jax.lax.scan(
        one_pass, (x, tuple(pools)),
        jnp.arange(cfg.total_ut_steps, dtype=jnp.int32))
    leave = exit_steps(lam, cfg.early_exit_threshold)
    out = jnp.take_along_axis(rows, (leave - 1)[None, ..., None], 0)[0]
    return out, pools, lam, leave


def _head(params, x):
    with jax.named_scope("head_sample"):
        return (x @ params["head"].astype(x.dtype)).astype(jnp.float32)


def _embed(cfg, params, tokens):
    with jax.named_scope("embed"):
        return jnp.take(params["embed"], tokens, axis=0).astype(
            jnp.dtype(cfg.dtype))


def forward(params, tokens, cfg: OuroConfig):
    """tokens [B, N] int32 -> (logits [B, N, V] float32, the gate after
    each pass, float32 [T, B, N]).  No cache."""
    B, N = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32), (B, N))

    def body(x, blk, vl, pools):
        def attend(q, k, v):
            with jax.named_scope("attn"):
                return _attention(q, k, v), pools
        return _layer(cfg, x, blk, pos, attend)

    x, _, lam, _ = _passes(params, cfg, _embed(cfg, params, tokens), (),
                           body)
    return _head(params, x), lam


# --------------------------------------------------------------------------
# the paged engine's family interface (inference/serving.py names it)
# --------------------------------------------------------------------------

def check_serving(cfg, *, engine, quant=None, kv_dtype=None, tp=1, pp=1,
                  kv_handoff=False, host_tier_mb=0.0):
    """Raise, by name, for every composition this family does not build
    (as ``deepseek_v3.check_serving`` does)."""
    why = {
        "the slot engine (ServingEngine)": (
            engine == "ServingEngine",
            "its per-slot K/V strip is a GPT program's — use "
            "PagedServingEngine"),
        "speculative decoding": (
            engine not in ("ServingEngine", "PagedServingEngine"),
            "the verify step and the draft model are GPT programs"),
        "early_exit_threshold < 1": (
            cfg.early_exit_threshold < 1,
            "sequences of one batched step would leave the loop at "
            "different passes, which the scheduler does not plan"),
        "quant=": (quant is not None,
                   "gpt.quantize_params knows GPT's leaves only"),
        "tp > 1": (int(tp) > 1,
                   "the looped layer has no sharded form yet"),
        "pp > 1": (int(pp) > 1,
                   "a stage would be revisited every pass; gpt_pp's stage "
                   "step is a GPT program"),
        "kv_handoff (KV extract/inject)": (
            bool(kv_handoff),
            f"a payload would carry {cfg.virtual_layers} layers a position; "
            "not sized"),
        "the host KV tier": (
            float(host_tier_mb or 0) > 0,
            "spills ride the extract/inject executables"),
    }
    for name, (hit, reason) in why.items():
        if hit:
            raise ValueError(f"ouro does not compose with {name} yet — "
                             f"{reason}")


def shard_params_for_serving(params, cfg, mesh):
    raise ValueError("ouro does not compose with tp > 1 / pp > 1 yet")


def kv_pool_spec(mesh):
    return (None, None, None, None)


def prefix_salt(cfg):
    """What the pager's prefix hashes are salted with: a page of this
    family holds ``T`` passes' K/V, another family's (or another T's)
    page of the same tokens does not."""
    return f"/family=ouro/ut_steps={cfg.total_ut_steps}"


def kv_bytes_per_position(cfg, itemsize):
    """Bytes of K and V one cached position holds: every layer of every
    pass."""
    return (2 * cfg.virtual_layers * cfg.num_attention_heads * cfg.head_dim
            * itemsize)


# the decode kernel is GPT's, and so is its grid's rule of shapes
decode_group_pages = gpt.decode_group_pages


def decode_extra_stats(cfg, flat):
    """The engine's counters from what :func:`decode_paged` returned
    beside the logits (host side, numpy): ``flat`` int32 [T], the active
    slots the exit rule kept running at each pass."""
    return {"loop_tokens": int(flat[0]), "loop_passes": int(flat.sum())}


def paged_pool_shapes(cfg, num_pages, page_size):
    """The stored shapes of the paged K and V pools: GPT's, with the
    (pass, layer) pairs on the leading axis."""
    shape = (cfg.virtual_layers, num_pages, page_size,
             cfg.num_attention_heads * cfg.head_dim)
    return shape, shape


def init_paged_pools(cfg, num_pages, page_size, dtype=None, mesh=None,
                     kv_quant=False):
    """The paged KV pool, zeros, in GPT's operand order and layout
    (``gpt.init_paged_pools``): (k, v), or with ``kv_quant`` (k,
    k_scale, v, v_scale).  Page 0 is the scratch page.  Made ON the
    device, not through the host as ``gpt._pool_zeros`` makes GPT's:
    at the published widths the pool is 9.2 GiB, which cost as much
    host memory and 24 s of set-up to send (my chip runs, PR 33);
    the one tiny program a shape compiles lands in the persistent
    cache."""
    if mesh is not None:
        raise ValueError("ouro does not compose with tp > 1 / pp > 1 yet")
    shapes = paged_pool_shapes(cfg, num_pages, page_size)
    if not kv_quant:
        cd = jnp.dtype(dtype or cfg.dtype)
        return tuple(jnp.zeros(s, cd) for s in shapes)
    return tuple(jnp.zeros(*what) for s in shapes for what in (
        (s, jnp.int8),
        (s[:-1] + (cfg.num_attention_heads,), jnp.float32)))


def prefill_paged(params, cfg, pools, tokens, lens, ptab):
    """Causal forward over padded prompts ``tokens`` [b, s]; every
    (pass, layer) scatters its K/V page chunks into the pools through
    ``ptab`` [b, s / page_size] inside the scan (pad rows and pad pages
    target the scratch page), so no second copy of ``T * L`` layers'
    K/V is ever held.  The prompt attends its own K/V in the compute
    dtype; an int8 pool quantizes on write.  Returns (logits of each
    row's last true position [b, V], pools)."""
    b, s = tokens.shape
    ps = pools[0].shape[2]
    flat = ptab.reshape(-1)
    lead = (b * (s // ps), ps)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    idx = jnp.clip(lens - 1, 0, s - 1)

    def body(x, blk, vl, pp):
        k_half, v_half = gpt._halves(pp)

        def attend(q, k, v):
            with jax.named_scope("kv_scatter"):
                new = (*gpt._write(k_half, (vl, flat), k, lead),
                       *gpt._write(v_half, (vl, flat), v, lead))
            with jax.named_scope("attn"):
                return _attention(q, k, v), new
        return _layer(cfg, x, blk, pos, attend)

    last, pools, _, _ = _passes(
        params, cfg, _embed(cfg, params, tokens), pools, body,
        pick=lambda x: jnp.take_along_axis(x, idx[:, None, None], 1)[:, 0])
    return _head(params, last), pools


def chunk_paged(params, cfg, pools, tokens, pt_row, offset):
    """One chunked-prefill piece for one slot: ``tokens`` [1, C] from
    absolute position ``offset`` (traced), attending the slot's filled
    pages and the chunk's causal prefix.  Returns (logits [1, C, V],
    pools).  Each (pass, layer) gathers the slot's page view, splices
    the chunk in and writes back ONLY the chunk's own pages (as
    ``gpt.chunk_paged``: earlier positions must not be requantized)."""
    C = tokens.shape[1]
    maxP = pt_row.shape[0]
    ps = pools[0].shape[2]
    heads = (cfg.num_attention_heads, cfg.head_dim)
    held = gpt._float_dtype(cfg, pools)
    pos = (offset + jnp.arange(C, dtype=jnp.int32))[None]
    own = jax.lax.dynamic_slice(pt_row, (offset // ps,), (C // ps,))

    def body(x, blk, vl, pp):
        halves = gpt._halves(pp)

        def attend(q, k, v):
            views, new = [], ()
            with jax.named_scope("kv_scatter"):
                for half, fresh in zip(halves, (k, v)):
                    view = gpt._unrows(tuple(p[vl, pt_row] for p in half),
                                       (1, maxP * ps), heads, held)
                    views.append(jax.lax.dynamic_update_slice(
                        view, fresh.astype(held), (0, offset, 0, 0)))
                    new += gpt._write(half, (vl, own), fresh[0],
                                      (C // ps, ps))
            with jax.named_scope("attn"):
                return _attention(q, *views, offset), new
        return _layer(cfg, x, blk, pos, attend)

    x, pools, _, _ = _passes(params, cfg, _embed(cfg, params, tokens),
                             pools, body)
    return _head(params, x), pools


def decode_paged(params, cfg, pools, page_table, write_pages, write_offs,
                 lens, tokens, mesh=None):
    """One decode iteration for every slot: one token per slot at its
    own ``lens[s]``, through every pass.  Returns (logits [S, V]
    float32, pools, ran int32 [T]: how many ACTIVE slots — ``lens > 0``
    — the exit rule kept running at each pass; an idle slot's row is
    computed, its table is scratch, and it counts nothing).  The logits
    are those of the pass each slot leaves at; every slot still runs
    (and caches) every pass: :func:`check_serving` refuses a threshold
    under 1, where they would differ."""
    from ..ops.pallas.paged_attn import paged_attention
    S = tokens.shape[0]

    def body(x, blk, vl, pp):
        k_half, v_half = gpt._halves(pp)
        at = (vl, write_pages, write_offs)

        def attend(q, k, v):
            with jax.named_scope("kv_write"):
                new = (*gpt._write(k_half, at, k, (S,)),
                       *gpt._write(v_half, at, v, (S,)))
            with jax.named_scope("paged_attn"):
                a = paged_attention(q[:, None], new, page_table, lens, vl,
                                    mesh=mesh)[:, 0]
            return a, new
        return _layer(cfg, x, blk, lens, attend)

    x, pools, _, leave = _passes(params, cfg, _embed(cfg, params, tokens),
                                 pools, body)
    with jax.named_scope("exit_gate"):
        passes = jnp.arange(1, cfg.total_ut_steps + 1)[:, None]
        ran = jnp.sum((leave[None] >= passes) & (lens > 0)[None], -1)
    return _head(params, x), pools, ran.astype(jnp.int32)
