"""The ``phi4flash`` model family (the config's own ``model_type``): a
decoder-hybrid-decoder language model — selective state-space layers,
differential attention over a sliding window, ONE full-attention layer
whose K/V every later attention layer reads, and gated memory units that
read one state-space layer's output — served through
``inference/serving.py::PagedServingEngine``.

Keys are the published ones (``config.json`` of
microsoft/Phi-4-mini-flash-reasoning; arXiv 2507.06607, 2410.05258,
2312.00752).  With ``LN`` a LayerNorm with gain and bias in float32,
every layer ``i`` of ``L`` is::

    x = x + Mix_i(LN(x))
    x = x + W2 (silu(g) * u),  [g, u] = W1 LN'(x)        no bias
    logits = E^T LN_f(x)                                 tied embedding

and ``Mix_i`` by the layer's kind (:func:`layer_kinds`; ``half = L /
2``, no positional term anywhere):

``ssm`` (even ``i <= half``), Mamba-1 with inner width ``di``, state
``N``, ``dt_rank`` ``R``, a causal depthwise convolution of 4::

    [u, z] = W_in h ;  u = silu(conv(u) + b_c) ;  [d, B, C] = W_x u
    D = softplus(W_dt d + b_dt) ;  A = -exp(A_log)       [di, N]
    S_t = exp(D_t A) * S_(t-1) + (D_t * u_t) B_t^T       float32
    y_t = S_t C_t + D_skip * u_t ;  Mix = W_out (y * silu(z))

Layer ``half`` also hands on ``m_t = y_t``, the memory.
``window`` (odd ``i < half``) and ``full`` (``i = half + 1``):
differential attention (``ops/pallas/paged_diff_attn.py`` has the
equations), position ``t`` seeing itself and the ``sliding_window - 1``
before it, or everything before it; ``Wqkv`` and ``Wo`` carry biases;
``o_p = RMSNorm(...; g) * (1 - lambda_init(i))``, ``lambda_init(i) =
0.8 - 0.6 exp(-0.3 i)``.  ``gmu`` (even ``i >= half + 2``): ``Mix =
W_out (m * silu(W_in h))``.  ``cross`` (odd ``i >= half + 3``): ``q =
W_q h`` only, K and V the ``full`` layer's, causal, the same
differential form with the layer's own lambda.

What a sequence keeps on the device, and where:

* the ``full`` layer's K and V for every position: the ONE-layer page
  pool, ``[1, pages, page_size, nkv * hd]`` each, GPT's layout and
  helpers (``gpt._write``), owned by the pager;
* per SLOT, not per page: each ``window`` layer's last
  ``sliding_window`` K and V rows as a ring (row ``t mod W``; stored as
  the strip of ring pages a slot owns, ``[n_window, slots * W / rp, rp,
  nkv * hd]``, so the decode kernel reads it through a fixed table),
  each ``ssm`` layer's ``S`` (float32) and the last 3 inputs of its
  convolution.  These ride behind the pool in ``init_paged_pools``'
  tuple (:func:`slot_state_arrays` says how many), are zeroed and
  rebuilt by prefill for the rows' slots, carried from chunk to chunk,
  and left alone by a decode step for a slot it does not run.

Layers ``half + 2 ..`` keep no state, so prefill runs them for each
prompt's LAST row only: exact, not an approximation.

Parameter tree: ``embed [V, H]``, ``norm_f {g, b}``, ``first`` (the
``half / 2`` (ssm, window) pairs stacked on a leading axis: ``ssm``,
``ssm_mlp``, ``attn``, ``attn_mlp``), ``mid`` (layer ``half`` and the
full layer, the same four groups unstacked), ``second`` (the (gmu,
cross) pairs stacked: ``gmu``, ``gmu_mlp``, ``cross``, ``cross_mlp``).
Every kind is one ``scan``; matmul weights are stored (in, out).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from . import gpt
from ..ops.pallas.paged_diff_attn import (diff_attention, lambda_of,
                                          paged_diff_attention)

# positions the prefill scan takes in one ``lax.scan`` step, the
# recurrence inside it written out
SCAN_BLOCK = 16


@dataclasses.dataclass
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    intermediate_size: int = 10240
    hidden_act: str = "silu"
    layer_norm_eps: float = 1e-5
    sliding_window: int = 512
    mb_per_layer: int = 2
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = True
    mlp_bias: bool = False
    lm_head_bias: bool = False
    embd_pdrop: float = 0.0
    resid_pdrop: float = 0.0
    # not in config.json: the config class's defaults
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int | None = None     # hidden_size / 16
    dtype: str = "bfloat16"              # compute dtype
    param_dtype: str = "bfloat16"
    initializer_range: float = 0.02

    def __post_init__(self):
        if self.mamba_dt_rank is None:
            self.mamba_dt_rank = math.ceil(self.hidden_size / 16)
        L = self.num_hidden_layers
        unbuilt = {
            "hidden_act != silu": self.hidden_act != "silu",
            "tie_word_embeddings false": not self.tie_word_embeddings,
            "mlp_bias / lm_head_bias": self.mlp_bias or self.lm_head_bias,
            "dropout": bool(self.embd_pdrop or self.resid_pdrop),
            "mb_per_layer != 2": self.mb_per_layer != 2,
            "no sliding_window": not self.sliding_window,
            "num_hidden_layers not a multiple of 4, or under 8":
                L % 4 != 0 or L < 8,
            "num_attention_heads != 2 * num_key_value_heads, or odd "
            "key/value heads": (
                self.num_attention_heads != 2 * self.num_key_value_heads
                or self.num_key_value_heads % 2 != 0),
            "mamba_d_conv != 4": self.mamba_d_conv != 4,
        }
        bad = [k for k, v in unbuilt.items() if v]
        if bad:
            raise ValueError(f"phi4flash: not built here: {bad}")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("phi4flash: hidden_size must divide by "
                             "num_attention_heads")

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
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_width(self):
        """A page row: the K (or V) heads side by side."""
        return self.num_key_value_heads * self.head_dim

    @property
    def d_inner(self):
        return self.mamba_expand * self.hidden_size


def phi4flash_tiny(**kw):
    """The CPU tests' size: every kind of layer (two (ssm, window)
    pairs, the memory and full layers, one (gmu, cross) pair), a window
    of 8 so that a short run wraps the ring; no published width."""
    base = dict(vocab_size=512, hidden_size=64, num_hidden_layers=8,
                num_attention_heads=8, num_key_value_heads=4,
                intermediate_size=96, sliding_window=8,
                max_position_embeddings=256, mamba_d_state=4,
                dtype="float32", param_dtype="float32")
    base.update(kw)
    return Phi4FlashConfig(**base)


def layer_kinds(cfg):
    """The kind of each of the ``num_hidden_layers`` layers, from
    ``num_hidden_layers``, ``mb_per_layer`` and ``sliding_window`` (as
    the published modeling file decides them)."""
    half = cfg.num_hidden_layers // 2
    kinds = []
    for i in range(cfg.num_hidden_layers):
        if cfg.mb_per_layer > 0 and i % cfg.mb_per_layer == 0:
            kinds.append("ssm" if i < half + 2 else "gmu")
        elif i < half and cfg.sliding_window:
            kinds.append("window")
        else:
            kinds.append("full" if i < half + 2 else "cross")
    return kinds


def count_of(cfg, kind):
    return layer_kinds(cfg).count(kind)


def lambda_init(layer):
    """The differential attention's constant at (0-based) layer
    ``layer``, a Python or a traced index: float32."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def init_params(cfg: Phi4FlashConfig, key):
    """Seeded random weights: normal, std ``initializer_range``, the
    residual projections (W_out, Wo, W2) scaled by 1 / sqrt(2 L); norm
    gains 1, biases 0; the state-space layer as Mamba initialises it
    (``A_log = log(1..N)``, ``D = 1``, ``b_dt`` the inverse softplus of
    a step log-uniform in [1e-3, 0.1]); the lambdas normal, std 0.1."""
    H, L, I = cfg.hidden_size, cfg.num_hidden_layers, cfg.intermediate_size
    di, N, R = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    hd, A, Ckv = cfg.head_dim, cfg.hidden_size, cfg.kv_width
    pd = jnp.dtype(cfg.param_dtype)
    std = cfg.initializer_range
    res = std / math.sqrt(2.0 * L)
    keys = iter(jax.random.split(key, 128))

    def nrm(shape, scale=std):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(pd)

    def ln(n):
        return {"ln_g": jnp.ones((*n, H), pd), "ln_b": jnp.zeros((*n, H), pd)}

    def mlp(n):
        return dict(ln(n), w1=nrm((*n, H, 2 * I)), w2=nrm((*n, I, H), res))

    def ssm(n):
        dt = jnp.exp(jax.random.uniform(next(keys), (*n, di), jnp.float32)
                     * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return dict(
            ln(n), w_in=nrm((*n, H, 2 * di)),
            conv_w=nrm((*n, cfg.mamba_d_conv, di), 0.3),
            conv_b=jnp.zeros((*n, di), pd),
            w_x=nrm((*n, di, R + 2 * N)), w_dt=nrm((*n, R, di), R ** -0.5),
            b_dt=(dt + jnp.log(-jnp.expm1(-dt))).astype(pd),
            a_log=jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)),
                (*n, di, N)).astype(jnp.float32),
            d_skip=jnp.ones((*n, di), pd), w_out=nrm((*n, di, H), res))

    def lambdas(n):
        return dict({f"lam_{a}": nrm((*n, hd), 0.1)
                     for a in ("q1", "k1", "q2", "k2")},
                    subln_g=jnp.ones((*n, 2 * hd), pd))

    def attn(n):
        return dict(ln(n), **lambdas(n), wqkv=nrm((*n, H, A + 2 * Ckv)),
                    bqkv=jnp.zeros((*n, A + 2 * Ckv), pd),
                    wo=nrm((*n, A, H), res), bo=jnp.zeros((*n, H), pd))

    def gmu(n):
        return dict(ln(n), w_in=nrm((*n, H, di)), w_out=nrm((*n, di, H), res))

    def cross(n):
        return dict(ln(n), **lambdas(n), wq=nrm((*n, H, A)),
                    bq=jnp.zeros((*n, A), pd), wo=nrm((*n, A, H), res),
                    bo=jnp.zeros((*n, H), pd))

    n1, n2 = (count_of(cfg, "window"),), (count_of(cfg, "gmu"),)
    return {
        "embed": nrm((cfg.vocab_size, H)),
        "norm_f": {"g": jnp.ones((H,), pd), "b": jnp.zeros((H,), pd)},
        "first": {"ssm": ssm(n1), "ssm_mlp": mlp(n1), "attn": attn(n1),
                  "attn_mlp": mlp(n1)},
        "mid": {"ssm": ssm(()), "ssm_mlp": mlp(()), "attn": attn(()),
                "attn_mlp": mlp(())},
        "second": {"gmu": gmu(n2), "gmu_mlp": mlp(n2), "cross": cross(n2),
                   "cross_mlp": mlp(n2)},
    }




# --------------------------------------------------------------------------
# the sublayers
# --------------------------------------------------------------------------

def _layer_norm(x, blk, eps, g="ln_g", b="ln_b"):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * blk[g].astype(jnp.float32)
            + blk[b].astype(jnp.float32)).astype(x.dtype)


def _mlp(cfg, x, blk):
    cd = x.dtype
    with jax.named_scope("mlp"):
        h = _layer_norm(x, blk, cfg.layer_norm_eps)
        g, u = jnp.split(h @ blk["w1"].astype(cd), 2, -1)
        return x + (jax.nn.silu(g) * u) @ blk["w2"].astype(cd)


def _ssm_coeffs(cfg, blk, u):
    """From the convolved, activated ``u`` [..., di]: the step ``D``
    float32 [..., di], ``B`` and ``C`` float32 [..., N] and ``A``
    float32 [di, N]."""
    N, R = cfg.mamba_d_state, cfg.mamba_dt_rank
    f32 = jnp.float32
    dbc = (u @ blk["w_x"].astype(u.dtype)).astype(f32)
    step = jax.nn.softplus(dbc[..., :R] @ blk["w_dt"].astype(f32)
                           + blk["b_dt"].astype(f32))
    return (step, dbc[..., R:R + N], dbc[..., R + N:],
            -jnp.exp(blk["a_log"].astype(f32)))


def _ssm_seq(cfg, blk, h, state, tail, count):
    """The state-space mixer over ``h`` [B, T, H] (already normed) from
    ``state`` float32 [B, di, N] and the convolution's last inputs
    ``tail`` [B, 3, di]; only the first ``count`` [B] positions of a row move its
    state.  Returns (Mix [B, T, H], y [B, T, di] — the memory, before
    the gate —, state', tail')."""
    cd, f32 = h.dtype, jnp.float32
    B, T, _ = h.shape
    u, z = jnp.split(h @ blk["w_in"].astype(cd), 2, -1)
    ucat = jnp.concatenate([tail.astype(cd), u], 1)          # [B, T + 3, di]
    w = blk["conv_w"].astype(f32)
    conv = sum(ucat[:, j:j + T].astype(f32) * w[j] for j in range(4))
    new_tail = jnp.take_along_axis(
        ucat, (count[:, None] + jnp.arange(3))[:, :, None], 1)
    u = jax.nn.silu(conv + blk["conv_b"].astype(f32)).astype(cd)
    step, Bm, Cm, A = _ssm_coeffs(cfg, blk, u)
    step = jnp.where((jnp.arange(T)[None] < count[:, None])[..., None],
                     step, 0.0)
    uf = u.astype(f32)
    Q = math.gcd(T, SCAN_BLOCK)

    def blocks(x):              # [B, T, ...] -> [T / Q, Q, B, ...]
        return jnp.moveaxis(x, 1, 0).reshape(T // Q, Q, B, *x.shape[2:])

    def block(S, xs):
        dt, du, bb, cc = xs
        ys = []
        for t in range(Q):      # the in-block recurrence, written out
            S = (jnp.exp(dt[t][..., None] * A) * S
                 + du[t][..., None] * bb[t][:, None, :])
            ys.append(jnp.sum(S * cc[t][:, None, :], -1))
        return S, jnp.stack(ys)

    with jax.named_scope("ssm_scan"):
        state, ys = jax.lax.scan(
            block, state,
            (blocks(step), blocks(step * uf), blocks(Bm), blocks(Cm)))
    y = jnp.moveaxis(ys.reshape(T, B, -1), 0, 1)             # [B, T, di]
    y = (y + blk["d_skip"].astype(f32) * uf).astype(cd)
    mix = (y * jax.nn.silu(z)) @ blk["w_out"].astype(cd)
    return mix, y, state, new_tail


def _ssm_step(cfg, blk, h, state, tail):
    """One position of the mixer for every row of ``h`` [S, H].
    Returns (Mix [S, H], y [S, di], state', tail')."""
    cd, f32 = h.dtype, jnp.float32
    u, z = jnp.split(h @ blk["w_in"].astype(cd), 2, -1)
    ucat = jnp.concatenate([tail.astype(cd), u[:, None]], 1)  # [S, 4, di]
    conv = jnp.sum(ucat.astype(f32) * blk["conv_w"].astype(f32), 1)
    u = jax.nn.silu(conv + blk["conv_b"].astype(f32)).astype(cd)
    step, Bm, Cm, A = _ssm_coeffs(cfg, blk, u)
    uf = u.astype(f32)
    S = (jnp.exp(step[..., None] * A) * state
         + (step * uf)[..., None] * Bm[:, None, :])
    y = (jnp.sum(S * Cm[:, None, :], -1)
         + blk["d_skip"].astype(f32) * uf).astype(cd)
    mix = (y * jax.nn.silu(z)) @ blk["w_out"].astype(cd)
    return mix, y, S, ucat[:, 1:]


def _qkv(cfg, blk, h):
    """h [..., H] -> q [..., nq, hd], k and v [..., nkv, hd]."""
    cd = h.dtype
    A, C, hd = cfg.hidden_size, cfg.kv_width, cfg.head_dim
    qkv = h @ blk["wqkv"].astype(cd) + blk["bqkv"].astype(cd)
    lead = h.shape[:-1]
    return (qkv[..., :A].reshape(*lead, -1, hd),
            qkv[..., A:A + C].reshape(*lead, -1, hd),
            qkv[..., A + C:].reshape(*lead, -1, hd))


def _lam(blk, layer):
    return lambda_of(blk["lam_q1"], blk["lam_k1"], blk["lam_q2"],
                     blk["lam_k2"], lambda_init(layer))


def _attn_out(cfg, blk, pairs, layer, cd):
    """The pairs' outputs [..., nq / 2, 2 * hd] (float32) -> the
    mixer's output [..., H]: sub-layer RMSNorm, the ``1 - lambda_init``
    scale, ``Wo``."""
    y = pairs * jax.lax.rsqrt(jnp.mean(jnp.square(pairs), -1, keepdims=True)
                              + cfg.layer_norm_eps)
    y = y * blk["subln_g"].astype(jnp.float32) * (1.0 - lambda_init(layer))
    y = y.reshape(*y.shape[:-2], -1).astype(cd)
    return y @ blk["wo"].astype(cd) + blk["bo"].astype(cd)


def _head(params, cfg, x):
    with jax.named_scope("head_sample"):
        h = _layer_norm(x, params["norm_f"], cfg.layer_norm_eps, "g", "b")
        return jnp.einsum("...h,vh->...v", h, params["embed"].astype(h.dtype),
                          preferred_element_type=jnp.float32)


def _embed(cfg, params, tokens):
    with jax.named_scope("embed"):
        return jnp.take(params["embed"], tokens, axis=0).astype(
            jnp.dtype(cfg.dtype))


def _scan_pairs(body, x, blocks, carry):
    """``body(x, blk, i, carry) -> (x, carry)`` over stacked pairs of
    layers: the weights are the scan's ``xs`` and ``carry`` (cache
    arrays) is updated where it lies."""
    n = jax.tree_util.tree_leaves(blocks)[0].shape[0]

    def step(c, layer):
        with jax.named_scope("layer"):
            return body(c[0], layer[0], layer[1], c[1]), None

    (x, carry), _ = jax.lax.scan(
        step, (x, tuple(carry)), (blocks, jnp.arange(n, dtype=jnp.int32)))
    return x, carry


def _gmu(cfg, x, blk, m):
    cd = x.dtype
    with jax.named_scope("gmu"):
        h = _layer_norm(x, blk, cfg.layer_norm_eps)
        return x + (m * jax.nn.silu(h @ blk["w_in"].astype(cd))
                    ) @ blk["w_out"].astype(cd)


def _cross_q(cfg, blk, x):
    cd = x.dtype
    h = _layer_norm(x, blk, cfg.layer_norm_eps)
    return (h @ blk["wq"].astype(cd) + blk["bq"].astype(cd)).reshape(
        *h.shape[:-1], -1, cfg.head_dim)


def _ring_geometry(cfg, ring):
    """(window, rows a ring page holds, ring pages a slot owns)."""
    W, rp = cfg.sliding_window, ring.shape[2]
    return W, rp, W // rp


def _ring_pages(cfg, ring, slot_ids):
    """int32 [B, W / rp]: the strip of ring pages each slot owns (a
    slot id past the slots names pages past the array: dropped)."""
    _, _, n = _ring_geometry(cfg, ring)
    return slot_ids[:, None] * n + jnp.arange(n, dtype=jnp.int32)


# --------------------------------------------------------------------------
# a sequence: forward, prefill and chunked prefill are one walk
# --------------------------------------------------------------------------

def _sequence(params, cfg, pools, tokens, count, offset=0, slot_ids=None,
              ptab=None, prior=False, all_rows=False):
    """The model over ``tokens`` [B, T], rows at absolute positions
    ``offset ..``, of which the first ``count`` [B] are true.

    ``pools`` () keeps nothing (:func:`forward`); else the engine's
    tuple, and every stateful layer leaves in it what a later call for
    ``slot_ids`` [B] needs: the full layer's K/V through ``ptab`` (page
    ids of the rows' positions), the rings' last rows, ``S`` and the
    convolution's tail as of position ``offset + count``.  ``prior``:
    the rows continue what the slot's state holds (a chunk; one row,
    ``ptab`` its whole table) instead of starting from nothing.

    The stateless layers run on every row (``all_rows``) or on each
    row's last true position alone.  Returns (logits float32 [B, T or
    1, V], pools, the rows that went through the stateful layers and
    through the stateless ones: two Python ints, the traced shapes)."""
    cd = jnp.dtype(cfg.dtype)
    eps = cfg.layer_norm_eps
    B, T = tokens.shape
    half = cfg.num_hidden_layers // 2
    n_pairs = half // 2
    W = cfg.sliding_window
    keep = bool(pools)
    q_pos = offset + jnp.arange(T, dtype=jnp.int32)          # [T]
    if keep:
        pool_k, pool_v, *slot_state = pools
    else:
        pool_k = pool_v = None
        slot_state = ()
    di, N = cfg.d_inner, cfg.mamba_d_state
    fresh = (jnp.zeros((B, di, N), jnp.float32), jnp.zeros((B, 3, di), cd))

    def ssm_layer(x, blk, idx, state, tail):
        """Layer ``idx`` of the state arrays: returns (x, y, arrays)."""
        start = fresh
        if prior:
            began = offset == 0
            start = tuple(jnp.where(began, f, a[idx, slot_ids])
                          for f, a in zip(fresh, (state, tail)))
        h = _layer_norm(x, blk, eps)
        mix, y, s_new, t_new = _ssm_seq(cfg, blk, h, *start, count)
        if keep:
            with jax.named_scope("state_reset"):
                state = state.at[idx, slot_ids].set(s_new, mode="drop")
                tail = tail.at[idx, slot_ids].set(t_new.astype(tail.dtype),
                                                  mode="drop")
        return x + mix, y, state, tail

    def window_layer(x, blk, i, ring_k, ring_v):
        layer = 2 * i + 1
        h = _layer_norm(x, blk, eps)
        q, k, v = _qkv(cfg, blk, h)
        k_all, v_all, k_pos, k_ok = k, v, q_pos, jnp.ones((T,), bool)
        if keep:
            _, rp, n = _ring_geometry(cfg, ring_k)
            pages = _ring_pages(cfg, ring_k, slot_ids)
            rows = jnp.arange(W, dtype=jnp.int32)
        if prior:
            # ring row j holds the last position before ``offset`` that
            # is j mod W, if there is one
            old_pos = offset - 1 - jnp.mod(offset - 1 - rows, W)
            old = [r[i, pages].reshape(B, W, -1, cfg.head_dim).astype(cd)
                   for r in (ring_k, ring_v)]
            k_all = jnp.concatenate([old[0], k], 1)
            v_all = jnp.concatenate([old[1], v], 1)
            k_pos = jnp.concatenate([old_pos, q_pos])
            k_ok = jnp.concatenate([old_pos >= 0, k_ok])
        mask = (k_ok & (k_pos <= q_pos[:, None])
                & (k_pos > q_pos[:, None] - W))               # [T, K]
        with jax.named_scope("window_attn"):
            pairs = diff_attention(q, k_all, v_all, mask[None],
                                   _lam(blk, layer))
        x = x + _attn_out(cfg, blk, pairs, layer, cd)
        if keep:
            # ring row j now holds the last position before ``offset +
            # count`` that is j mod W: one of these rows, or what it held
            end = (offset + count)[:, None]                   # [B, 1]
            new_pos = end - 1 - jnp.mod(end - 1 - rows, W)    # [B, W]
            src = jnp.clip(new_pos - offset, 0, T - 1)
            with jax.named_scope("kv_write"):
                strips = []
                for ring, fresh_rows in ((ring_k, k), (ring_v, v)):
                    strip = jnp.take_along_axis(
                        fresh_rows.reshape(B, T, -1), src[..., None], 1)
                    if prior:
                        strip = jnp.where(
                            (new_pos >= offset)[..., None], strip,
                            ring[i, pages].reshape(B, W, -1).astype(cd))
                    strips.append(ring.at[i, pages].set(
                        strip.reshape(B, n, rp, -1).astype(ring.dtype),
                        mode="drop"))
            ring_k, ring_v = strips
        return x, ring_k, ring_v

    def first(x, blk, i, carry):
        ring_k, ring_v, state, tail = carry if keep else (None,) * 4
        x, _, state, tail = ssm_layer(x, blk["ssm"], i, state, tail)
        x = _mlp(cfg, x, blk["ssm_mlp"])
        x, ring_k, ring_v = window_layer(x, blk["attn"], i, ring_k, ring_v)
        x = _mlp(cfg, x, blk["attn_mlp"])
        return x, ((ring_k, ring_v, state, tail) if keep else ())

    x = _embed(cfg, params, tokens)
    x, slot_state = _scan_pairs(first, x, params["first"], slot_state)
    ring_k, ring_v, state, tail = slot_state if keep else (None,) * 4

    # ---- layer ``half``: the state-space layer whose output is the
    # memory; layer ``half + 1``: the one whose K/V are kept whole
    mid = params["mid"]
    x, m, state, tail = ssm_layer(x, mid["ssm"], n_pairs, state, tail)
    x = _mlp(cfg, x, mid["ssm_mlp"])
    blk = mid["attn"]
    q, k, v = _qkv(cfg, blk, _layer_norm(x, blk, eps))
    k_all, v_all, k_pos = k, v, q_pos
    if keep:
        ps = pool_k.shape[2]
        if prior:
            # the slot's page view with these rows spliced in; only the
            # rows' own pages are written back
            views = []
            for pool, rows_ in ((pool_k, k), (pool_v, v)):
                view = pool[0, ptab].reshape(1, -1, *k.shape[2:]).astype(cd)
                views.append(jax.lax.dynamic_update_slice(
                    view, rows_, (0, offset, 0, 0)))
            k_all, v_all = views
            k_pos = jnp.arange(k_all.shape[1], dtype=jnp.int32)
            at = (0, jax.lax.dynamic_slice(ptab, (offset // ps,),
                                           (T // ps,)))
        else:
            at = (0, ptab.reshape(-1))
        with jax.named_scope("kv_write"):
            lead = (B * (T // ps), ps)
            (pool_k,) = gpt._write((pool_k,), at, k, lead)
            (pool_v,) = gpt._write((pool_v,), at, v, lead)
    with jax.named_scope("full_attn"):
        pairs = diff_attention(q, k_all, v_all,
                               (k_pos <= q_pos[:, None])[None],
                               _lam(blk, half + 1))
    x = x + _attn_out(cfg, blk, pairs, half + 1, cd)
    x = _mlp(cfg, x, mid["attn_mlp"])

    # ---- layers ``half + 2 ..`` keep nothing: only the rows whose
    # logits are wanted go through them
    if not all_rows:
        last = jnp.clip(count - 1, 0, T - 1)[:, None, None]
        x = jnp.take_along_axis(x, last, 1)                   # [B, 1, H]
        m = jnp.take_along_axis(m, last, 1)
        row_pos = offset + last[:, :, 0]                      # [B, 1]
    else:
        row_pos = jnp.broadcast_to(q_pos, (B, T))
    mask = k_pos <= row_pos[..., None]                        # [B, rows, K]
    rows_run = (B * T, x.shape[0] * x.shape[1])

    def second(x, blk, i, carry):
        layer = half + 3 + 2 * i
        x = _mlp(cfg, _gmu(cfg, x, blk["gmu"], m), blk["gmu_mlp"])
        c = blk["cross"]
        with jax.named_scope("cross_attn"):
            pairs = diff_attention(_cross_q(cfg, c, x), k_all, v_all, mask,
                                   _lam(c, layer))
        x = x + _attn_out(cfg, c, pairs, layer, cd)
        return _mlp(cfg, x, blk["cross_mlp"]), carry

    x, _ = _scan_pairs(second, x, params["second"], ())
    out = (pool_k, pool_v, ring_k, ring_v, state, tail) if keep else ()
    return _head(params, cfg, x), out, rows_run


def forward(params, tokens, cfg: Phi4FlashConfig):
    """tokens [B, N] int32 -> logits [B, N, V] float32.  No cache, every
    layer over every position."""
    count = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    return _sequence(params, cfg, (), tokens, count, all_rows=True)[0]


# --------------------------------------------------------------------------
# the paged engine's family interface (inference/serving.py names it)
# --------------------------------------------------------------------------

def check_serving(cfg, *, engine, quant=None, kv_dtype=None, tp=1, pp=1,
                  kv_handoff=False, host_tier_mb=0.0):
    """Raise, by name, for every composition this family does not build:
    each would have to carry the per-slot state as well as pages."""
    why = {
        "the slot engine (ServingEngine)": (
            engine == "ServingEngine",
            "its per-slot K/V strip is a GPT program's — use "
            "PagedServingEngine"),
        "speculative decoding": (
            engine not in ("ServingEngine", "PagedServingEngine"),
            "a rejected draft would have to roll the state-space state "
            "and the rings back"),
        "quant=": (quant is not None,
                   "gpt.quantize_params knows GPT's leaves only"),
        "kv_dtype='int8'": (
            kv_dtype == "int8",
            "the differential kernel reads bf16 or float32 pages; the "
            "rings and the state have no quantized form"),
        "tp > 1": (int(tp) > 1, "no layer has a sharded form yet"),
        "pp > 1": (int(pp) > 1,
                   "the memory and the shared K/V cross the stages"),
        "kv_handoff (KV extract/inject)": (
            bool(kv_handoff),
            "a payload would have to carry the slot's state-space "
            "state, convolution tails and rings beside its pages"),
        "the host KV tier": (
            float(host_tier_mb or 0) > 0,
            "a page faulted back restores K/V but not the state that "
            "was built with it"),
    }
    for name, (hit, reason) in why.items():
        if hit:
            raise ValueError(f"phi4flash does not compose with {name} yet "
                             f"— {reason}")


def shard_params_for_serving(params, cfg, mesh):
    raise ValueError("phi4flash does not compose with tp > 1 / pp > 1 yet")


def kv_pool_spec(mesh):
    return (None, None, None, None)


def prefix_salt(cfg):
    """What the pager's prefix hashes are salted with: a page of this
    family holds ONE layer's K/V of these widths."""
    return (f"/family=phi4flash/kv={cfg.num_key_value_heads}x"
            f"{cfg.head_dim}/layers={cfg.num_hidden_layers}")


def kv_bytes_per_position(cfg, itemsize):
    """Bytes of K and V one cached position holds: the full layer's
    alone (the later attention layers read it; the window layers keep a
    ring, the state-space layers a state: :func:`slot_state_shapes`)."""
    return 2 * cfg.kv_width * itemsize


def slot_state_arrays(cfg):
    """How many arrays at the END of ``init_paged_pools``' tuple are
    indexed by SLOT, not by page (ring K, ring V, ``S``, convolution
    tails).  The engine hands a family that says so the rows' slot ids
    (``prefill_paged(..., slots=)``, ``chunk_paged(..., slot=,
    take=)``) and ``init_paged_pools`` the slots, copies, extracts and
    injects pages of the arrays before these only, and reports their
    bytes (``slot_state_bytes``)."""
    return 4


def slot_state_of(cfg, pools, slot):
    """What ``slot`` holds that no page does and no logit shows apart:
    every state-space layer's ``S`` float32 [n, di, N]
    (``PagedServingEngine.slot_state`` hands it to a check against a
    reference; the rings are K/V rows like the pool's and the
    convolution's tails three inputs: the logits are their check)."""
    return {"ssm_state": pools[4][:, slot]}


def prefill_extra_stats(cfg, flat):
    """The engine's counters from what :func:`prefill_paged` returned
    beside the logits (host side, numpy): ``flat`` int32 [2], the rows
    the wave's program put through the stateful layers, and through the
    stateless ones."""
    return {"prefill_rows": int(flat[0]), "prefill_cross_rows": int(flat[1])}


def decode_extra_stats(cfg, flat):
    """The engine's counters from what :func:`decode_paged` returned
    beside the logits (host side, numpy): ``flat`` int32 [2], the slots
    the step ran and the ring rows their window layers read (each)."""
    return {"state_steps": int(flat[0]), "window_rows_read": int(flat[1])}


def ring_page_rows(cfg, page_size):
    """Rows of a ring page: the pool's page where it divides the
    window."""
    return math.gcd(int(page_size), cfg.sliding_window)


def paged_pool_shapes(cfg, num_pages, page_size):
    """The stored shapes of the one-layer K and V pools."""
    shape = (1, num_pages, page_size, cfg.kv_width)
    return shape, shape


def slot_state_shapes(cfg, slots, page_size):
    """(shape, dtype) of the per-slot arrays, in operand order: ring K,
    ring V, ``S``, the convolution's tails."""
    cd = jnp.dtype(cfg.dtype)
    rp = ring_page_rows(cfg, page_size)
    ring = (count_of(cfg, "window"), slots * (cfg.sliding_window // rp), rp,
            cfg.kv_width)
    n = count_of(cfg, "ssm")
    return ((ring, cd), (ring, cd),
            ((n, slots, cfg.d_inner, cfg.mamba_d_state), jnp.float32),
            ((n, slots, 3, cfg.d_inner), cd))


def init_paged_pools(cfg, num_pages, page_size, dtype=None, mesh=None,
                     kv_quant=False, slots=None):
    """(pool K, pool V, ring K, ring V, S, convolution tails), zeros,
    made on the device.  Page 0 is the scratch page."""
    if mesh is not None or kv_quant:
        raise ValueError("phi4flash does not compose with tp > 1 / pp > 1 "
                         "or kv_dtype='int8' yet")
    if slots is None:
        raise ValueError("phi4flash keeps per-slot state: "
                         "init_paged_pools needs slots=")
    if dtype is not None and jnp.dtype(dtype) != jnp.dtype(cfg.dtype):
        cfg = dataclasses.replace(cfg, dtype=str(jnp.dtype(dtype)))
    cd = jnp.dtype(cfg.dtype)
    return (*(jnp.zeros(s, cd)
              for s in paged_pool_shapes(cfg, num_pages, page_size)),
            *(jnp.zeros(s, d)
              for s, d in slot_state_shapes(cfg, slots, page_size)))


def prefill_paged(params, cfg, pools, tokens, lens, ptab, slots=None,
                  all_rows=False):
    """Causal forward over padded prompts ``tokens`` [b, s] for the
    slots ``slots`` [b] (a pad row: a slot id past the slots): the full
    layer scatters its K/V through ``ptab`` [b, s / page_size]; every
    window layer's ring and every state-space layer's state are
    OVERWRITTEN for the rows' slots with what the prompt leaves there
    (that is the reset of a slot given to a new request).  Layers
    ``half + 2 ..`` run on each row's last true position only.  Returns
    (logits of that position [b, V], pools, int32 [2]: the rows that
    went through the stateful layers and through the stateless ones, as
    the program was traced); ``all_rows`` (tests): every layer over
    every row, logits [b, s, V]."""
    if slots is None:
        raise ValueError("phi4flash keeps per-slot state: prefill_paged "
                         "needs the rows' slot ids (slots=)")
    logits, pools, rows = _sequence(params, cfg, pools, tokens, lens,
                                    slot_ids=slots, ptab=ptab,
                                    all_rows=all_rows)
    return ((logits if all_rows else logits[:, 0]), pools,
            jnp.asarray(rows, jnp.int32))


def chunk_paged(params, cfg, pools, tokens, pt_row, offset, slot=None,
                take=None):
    """One chunked-prefill piece for slot ``slot``: ``tokens`` [1, C]
    from absolute position ``offset`` (traced), of which the first
    ``take`` are true, continuing the slot's state (starting it when
    ``offset`` is 0).  Returns (logits of the last true row [V],
    pools)."""
    if slot is None or take is None:
        raise ValueError("phi4flash keeps per-slot state: chunk_paged "
                         "needs slot= and take=")
    logits, pools, _ = _sequence(
        params, cfg, pools, tokens, jnp.reshape(take, (1,)),
        offset=offset, slot_ids=jnp.reshape(slot, (1,)), ptab=pt_row,
        prior=True)
    return logits[0, 0], pools


def decode_paged(params, cfg, pools, page_table, write_pages, write_offs,
                 lens, tokens, mesh=None):
    """One decode iteration for every slot: one token a slot at its own
    ``lens[s]``.  A slot the step does not run (``lens == 0``) computes
    a row, writes the scratch page and leaves its state, tails and rings
    as they are.  Returns (logits [S, V] float32, pools, int32 [2]: the
    slots run and the ring rows each window layer read)."""
    cd = jnp.dtype(cfg.dtype)
    eps = cfg.layer_norm_eps
    pool_k, pool_v, ring_k, ring_v, state, tail = pools
    S = tokens.shape[0]
    half = cfg.num_hidden_layers // 2
    W, rp, n = _ring_geometry(cfg, ring_k)
    live = lens > 0
    slot = jnp.arange(S, dtype=jnp.int32)
    ring_table = _ring_pages(cfg, ring_k, slot)
    row = jnp.mod(lens, W)
    # an idle slot's ring row goes to a page past the array: dropped
    ring_page = jnp.where(live, slot * n + row // rp, ring_k.shape[1])
    ring_lens = jnp.minimum(lens, W - 1)

    def ssm_layer(x, blk, idx, state, tail):
        with jax.named_scope("ssm_step"):
            mix, y, s_new, t_new = _ssm_step(
                cfg, blk, _layer_norm(x, blk, eps), state[idx], tail[idx])
            state = state.at[idx].set(
                jnp.where(live[:, None, None], s_new, state[idx]))
            tail = tail.at[idx].set(
                jnp.where(live[:, None, None], t_new.astype(tail.dtype),
                          tail[idx]))
        return x + mix, y, state, tail

    def attend(q, blk, layer, pools_, table, lens_, index):
        with jax.named_scope("paged_diff_attn"):
            pairs = paged_diff_attention(q, pools_, table, lens_, index,
                                         _lam(blk, layer))
        return _attn_out(cfg, blk, pairs, layer, cd)

    def first(x, blk, i, carry):
        ring_k, ring_v, state, tail = carry
        x, _, state, tail = ssm_layer(x, blk["ssm"], i, state, tail)
        x = _mlp(cfg, x, blk["ssm_mlp"])
        a = blk["attn"]
        with jax.named_scope("window_attn"):
            q, k, v = _qkv(cfg, a, _layer_norm(x, a, eps))
            with jax.named_scope("kv_write"):
                at = (i, ring_page, jnp.mod(row, rp))
                ring_k, ring_v = (
                    r.at[at].set(new.reshape(S, -1).astype(r.dtype),
                                 mode="drop")
                    for r, new in ((ring_k, k), (ring_v, v)))
            x = x + attend(q, a, 2 * i + 1, (ring_k, ring_v), ring_table,
                           ring_lens, i)
        x = _mlp(cfg, x, blk["attn_mlp"])
        return x, (ring_k, ring_v, state, tail)

    x = _embed(cfg, params, tokens)
    x, (ring_k, ring_v, state, tail) = _scan_pairs(
        first, x, params["first"], (ring_k, ring_v, state, tail))

    mid = params["mid"]
    x, m, state, tail = ssm_layer(x, mid["ssm"], half // 2, state, tail)
    x = _mlp(cfg, x, mid["ssm_mlp"])
    a = mid["attn"]
    with jax.named_scope("full_attn"):
        q, k, v = _qkv(cfg, a, _layer_norm(x, a, eps))
        with jax.named_scope("kv_write"):
            at = (0, write_pages, write_offs)
            (pool_k,) = gpt._write((pool_k,), at, k, (S,))
            (pool_v,) = gpt._write((pool_v,), at, v, (S,))
        x = x + attend(q, a, half + 1, (pool_k, pool_v), page_table, lens, 0)
    x = _mlp(cfg, x, mid["attn_mlp"])

    def second(x, blk, i, carry):
        x = _mlp(cfg, _gmu(cfg, x, blk["gmu"], m), blk["gmu_mlp"])
        c = blk["cross"]
        with jax.named_scope("cross_attn"):
            x = x + attend(_cross_q(cfg, c, x), c, half + 3 + 2 * i,
                           (pool_k, pool_v), page_table, lens, 0)
        return _mlp(cfg, x, blk["cross_mlp"]), carry

    x, _ = _scan_pairs(second, x, params["second"], ())
    ran = jnp.stack([jnp.sum(live), jnp.sum(jnp.where(live, ring_lens + 1,
                                                      0))])
    return (_head(params, cfg, x),
            (pool_k, pool_v, ring_k, ring_v, state, tail),
            ran.astype(jnp.int32))
