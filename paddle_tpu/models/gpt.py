"""GPT decoder-only LM — the flagship benchmark model.

Reference trains GPT-3-style models through fleet hybrid parallel with fused
CUDA attention (ref: paddle/fluid/operators/fused/fused_multi_transformer_op.cu,
python/paddle/distributed/fleet/meta_parallel/).  Here the model is a pure
functional core over a parameter pytree:

  * params live in fp32 (master weights), compute casts to ``cfg.dtype``
    (bf16 on TPU so matmuls hit the MXU at full rate);
  * blocks are stacked on a leading layer axis and applied with ``lax.scan``
    (constant compile time in depth, and the natural layout for sharding the
    layer axis over a pipeline mesh axis — see models/gpt_hybrid.py);
  * attention goes through the Pallas flash kernel (ops/pallas/flash_attn.py);
  * ``jax.checkpoint`` on each block trades FLOPs for HBM when ``remat``.

The eager ``GPT``/``GPTForPretraining`` Layers wrap the same core for the
dygraph API (tape autograd, state_dict, hapi.Model).
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from .common import PytreeLayer
from ..ops.pallas.flash_attn import flash_attention
from ..ops import dispatch


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304          # multiple of 128: pads to MXU lanes
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_size: int = 0                # 0 -> 4*hidden
    max_seq_len: int = 1024
    dtype: str = "bfloat16"          # compute dtype
    param_dtype: str = "float32"     # master weights
    initializer_range: float = 0.02
    layer_norm_eps: float = 1e-5
    use_flash: bool = True
    # Pallas fused-FFN / fused-LayerNorm routing for the hot blocks;
    # default off — bench.py flips them on when the committed on-chip
    # kernel check shows the Pallas kernel beating XLA at bench shapes
    # (same gate as use_flash; see tools/tpu_kernel_check.py)
    use_fused_ffn: bool = False
    use_pallas_norm: bool = False
    remat: bool = True
    # "full": recompute the whole block in the backward (min HBM, +~33%
    # FLOPs); "dots": save matmul outputs, recompute elementwise/norms only
    # (the TPU sweet spot — matmul results are what's expensive to redo)
    remat_policy: str = "full"
    # mixture-of-experts FFN (ISSUE 20): >0 replaces every block's dense
    # FFN with ``moe_experts`` expert MLPs behind a top-1 softmax gate.
    # Routing is capacity-factor dispatch traced IN-GRAPH — the mix of
    # experts a batch hits is data flowing through one executable, never
    # a shape (the serving zero-recompile contract).  Per forward call
    # each expert accepts at most ceil(tokens/experts * capacity_factor)
    # tokens per batch row; overflow tokens pass through on the residual
    # only (the standard Switch-style drop).
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25

    def __post_init__(self):
        if self.ffn_size == 0:
            self.ffn_size = 4 * self.hidden_size
        assert self.hidden_size % self.num_heads == 0
        assert self.remat_policy in ("full", "dots"), self.remat_policy
        assert self.moe_experts >= 0, self.moe_experts
        assert self.moe_capacity_factor > 0, self.moe_capacity_factor

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    def num_params(self):
        H, L, F, V, S = (self.hidden_size, self.num_layers, self.ffn_size,
                         self.vocab_size, self.max_seq_len)
        per_block = 4 * H + 3 * H * H + 3 * H + H * H + H + H * F + F + F * H + H
        return V * H + S * H + L * per_block + 2 * H

    def flops_per_token(self):
        """Training FLOPs/token (fwd+bwd ~ 6*N + attention term)."""
        H, L, S = self.hidden_size, self.num_layers, self.max_seq_len
        return 6 * self.num_params() + 12 * L * H * S


def gpt_tiny():
    return GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                     num_heads=4, max_seq_len=128, dtype="float32",
                     use_flash=False, remat=False)


def gpt_345m():
    return GPTConfig(hidden_size=1024, num_layers=24, num_heads=16,
                     max_seq_len=1024)


def gpt3_1p3b():
    return GPTConfig(hidden_size=2048, num_layers=24, num_heads=32,
                     max_seq_len=2048)


# --------------------------------------------------------------------------
# functional core
# --------------------------------------------------------------------------

def init_params(cfg: GPTConfig, key):
    """Parameter pytree.  Block params are stacked on a leading [L] axis."""
    H, L, F = cfg.hidden_size, cfg.num_layers, cfg.ffn_size
    pd = jnp.dtype(cfg.param_dtype)
    std = cfg.initializer_range
    ks = jax.random.split(key, 8)

    def nrm(k, shape, scale=std):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(pd)

    # residual-path projections scaled by 1/sqrt(2L) (GPT-2 init)
    res_std = std / math.sqrt(2.0 * L)
    blocks = {
        "ln1_g": jnp.ones((L, H), pd), "ln1_b": jnp.zeros((L, H), pd),
        "qkv_w": nrm(ks[2], (L, H, 3, H)),
        "qkv_b": jnp.zeros((L, 3, H), pd),
        "proj_w": nrm(ks[3], (L, H, H), res_std),
        "proj_b": jnp.zeros((L, H), pd),
        "ln2_g": jnp.ones((L, H), pd), "ln2_b": jnp.zeros((L, H), pd),
    }
    if cfg.moe_experts > 0:
        # expert-parallel FFN: every dense fc leaf gains a leading [E]
        # expert axis (after [L]) — the axis the serving mesh shards
        E = cfg.moe_experts
        blocks.update({
            "moe_gate_w": nrm(ks[6], (L, H, E)),
            "moe_w1": nrm(ks[4], (L, E, H, F)),
            "moe_b1": jnp.zeros((L, E, F), pd),
            "moe_w2": nrm(ks[5], (L, E, F, H), res_std),
            "moe_b2": jnp.zeros((L, E, H), pd),
        })
    else:
        blocks.update({
            "fc1_w": nrm(ks[4], (L, H, F)),
            "fc1_b": jnp.zeros((L, F), pd),
            "fc2_w": nrm(ks[5], (L, F, H), res_std),
            "fc2_b": jnp.zeros((L, H), pd),
        })
    return {
        "wte": nrm(ks[0], (cfg.vocab_size, H)),
        "wpe": nrm(ks[1], (cfg.max_seq_len, H)),
        "blocks": blocks,
        "lnf_g": jnp.ones((H,), pd), "lnf_b": jnp.zeros((H,), pd),
    }


def save_params_npz(path, params):
    """Checkpoint a param pytree (nested dicts of arrays — fp or the
    quantized {'qw','scale'} leaves) as one npz, keys = '/'-joined
    paths.  The serving-replica boot format: a replacement replica
    loads weights from here instead of re-running the seeded init
    (which compiles RNG executables — the AOT cold boot must not)."""
    import numpy as np
    flat = {}

    def walk(prefix, node):
        if isinstance(node, dict):
            for k2 in node:
                walk(f"{prefix}/{k2}" if prefix else str(k2), node[k2])
        else:
            flat[prefix] = np.asarray(node)
    walk("", params)
    np.savez(path, **flat)
    return path


def load_params_npz(path):
    """Inverse of :func:`save_params_npz`: pure ``device_put`` — zero
    traces, zero XLA compiles."""
    import numpy as np
    out = {}
    with np.load(path) as z:
        for key in z.files:
            node = out
            parts = key.split("/")
            for p2 in parts[:-1]:
                node = node.setdefault(p2, {})
            node[parts[-1]] = jax.device_put(z[key])
    return out


def sharding_rules(cfg: GPTConfig = None):
    """Model-parallel layout hook for the distributed.auto rule registry
    (family "gpt"): the Megatron column/row splits over 'tp' (attention
    heads divide across ranks via the column-split qkv; FFN up-proj
    column / down-proj row) with the stacked layer axis over 'pp' —
    defined next to init_params so layout and structure can't drift.
    Delegates to models/gpt_hybrid.py::param_specs, the same specs the
    explicit shard_map train step uses."""
    from .gpt_hybrid import param_specs
    return param_specs(cfg)


# --------------------------------------------------------------------------
# tensor-parallel serving placement (ISSUE 15)
# --------------------------------------------------------------------------
#
# Serving past one device reuses the TRAINING layouts: params are placed
# with the megatron column/row PartitionSpecs the distributed.auto rule
# registry already owns (sharding_rules above delegates to
# gpt_hybrid.param_specs), and the KV pools shard the HEAD axis over
# 'tp' — each rank holds nh/tp heads of every page/slot, so the paged
# page tables and the paged-attention math stay per-shard-local (a page
# id means the same physical page on every rank; only its head slice
# differs).  The executables themselves stay the single-device jnp code
# below: GSPMD partitions them from the operand shardings, which is
# exactly the pjit/NamedSharding recipe the training engine uses.

# the KV pool sharding: head axis (axis 3 of [L, P, ps, nh * hd] pages —
# a rank's heads are a contiguous column range of the merged axis — of
# [L, S, max_len, nh, hd] slots, and of [L, P, ps, nh] int8 scales alike)
KV_POOL_SPEC = (None, None, None, "tp")

# stage-local pools on a ('pp','tp') serving mesh: the stacked layer
# axis splits over 'pp' (each stage pages ONLY its own layers' K/V —
# the per-shard page-byte contract becomes per-stage-per-shard) and the
# head axis still splits over 'tp'.  Works unchanged for the int8 scale
# arrays ([L, P, ps, nh]: L over pp, nh over tp).
KV_POOL_SPEC_PP = ("pp", None, None, "tp")


def serving_mesh(tp, pp=1):
    """The serving mesh over the first ``pp * tp`` local devices (built
    through framework/jax_compat.py like every mesh in this repo): a
    1-D ``('tp',)`` mesh for plain tensor-parallel serving, or a 2-D
    ``('pp', 'tp')`` mesh when ``pp > 1`` — pipeline stages over the
    leading mesh axis, tensor shards within each stage."""
    import numpy as _np
    from ..framework import jax_compat
    tp, pp = int(tp), int(pp)
    if pp < 1:
        raise ValueError(f"serving_mesh wants pp >= 1, got {pp}")
    if pp == 1 and tp < 2:
        raise ValueError(f"serving_mesh wants tp >= 2, got {tp} "
                         "(tp=1 is the plain single-device engine)")
    if pp > 1 and tp < 1:
        raise ValueError(f"serving_mesh wants tp >= 1, got {tp}")
    need = pp * tp
    devs = jax.devices()
    if len(devs) < need:
        raise ValueError(
            f"pp={pp} x tp={tp} needs {need} devices but only "
            f"{len(devs)} are visible (CPU runs: "
            "--xla_force_host_platform_device_count)")
    if pp > 1:
        grid = _np.array(devs[:need]).reshape(pp, tp)
        return jax_compat.make_mesh(grid, ("pp", "tp"))
    return jax_compat.make_mesh(_np.array(devs[:tp]), ("tp",))


def shard_params_for_serving(params, cfg, mesh):
    """Place the serving param pytree with the gpt megatron column/row
    rules from the distributed.auto registry, pruned to ``mesh`` (the
    serving mesh carries only 'tp', so the training rules' 'pp' axis
    drops out).  Returns ``(placed_params, specs)``.  Shapes that don't
    divide raise up front with every violation named — a silently
    replicated leaf would void the fits-past-one-device claim."""
    from ..distributed.auto import rules
    specs = rules.prune_to_mesh(rules.rules_for("gpt", cfg), mesh)
    # weight-quantized trees ({'qw','scale'} dict leaves) get matching
    # dict specs: int8 payload keeps the fp column/row split, scales
    # keep everything but the collapsed contraction axis (rules.py::
    # quantized_like) — this is what lets tp=N compose with quant=
    specs = rules.quantized_like(specs, params)
    shapes = jax.tree_util.tree_map(lambda x: tuple(x.shape), params)
    bad = rules.validate(specs, shapes, mesh)
    if bad:
        raise ValueError(
            f"gpt params don't shard over this mesh: {bad} — pick a "
            "config whose sharded axes divide the tp degree")
    return rules.place(params, mesh, specs), specs


def replicate_on_mesh(tree, mesh):
    """device_put every leaf of ``tree`` fully replicated on ``mesh`` —
    mesh-sharded executables reject operands committed off-mesh, so
    small replicated operands (the speculative engine's draft model)
    must still live on it."""
    from ..framework import jax_compat
    sh = jax_compat.named_sharding(mesh, ())
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), tree)


def kv_pool_spec(mesh):
    """The KV pool PartitionSpec for ``mesh``: stage-local pools
    (:data:`KV_POOL_SPEC_PP`) when the mesh carries a 'pp' axis,
    head-sharded (:data:`KV_POOL_SPEC`) otherwise."""
    if mesh is not None and "pp" in getattr(mesh, "axis_names", ()):
        return KV_POOL_SPEC_PP
    return KV_POOL_SPEC


def _kv_pool_sharding(mesh):
    from ..framework import jax_compat
    return jax_compat.named_sharding(mesh, kv_pool_spec(mesh))


QUANT_MODES = ("int8", "int8_dynamic", "fp8")


def quantize_params(params, quant="int8"):
    """Weight-only storage quantization of the serving param pytree
    (ISSUE 9).  The four block matmul weights (qkv_w, proj_w, fc1_w,
    fc2_w — the overwhelming share of the bytes) become
    ``{"qw": int8/fp8 [L, K, ...out], "scale": fp32 [L, 1, ...out]}``
    dict leaves: per-OUTPUT-channel absmax scales over the contraction
    axis (``quantization.quant_absmax_scale``), which
    :func:`block_apply` routes through the fused dequant matmul.
    Embeddings, biases, layernorms and the tied lm head stay in master
    precision — they're a sliver of the bytes and dominate the accuracy
    budget.  Modes:

    * ``"int8"`` — weight-only: int8 storage, dequant fused into the
      matmul tile loop (ops/pallas/dequant_matmul.py; lax fallback off
      TPU).  The AWQ-shaped serving recipe.
    * ``"int8_dynamic"`` — W8A8: int8 storage AND activations
      dynamically quantized per-ROW in-graph (batch-invariant, so
      retries stay deterministic), through
      ``quantization.int8_matmul``'s int8xint8 MXU core.  More
      throughput on int8-rich TPUs, looser accuracy.
    * ``"fp8"`` — float8_e4m3 storage
      (framework/jax_compat.py::fp8_dtype), dequant-fused via the lax
      path.

    The quantized tree scans exactly like the fp tree (every dict leaf
    keeps the leading [L] axis), so every cached/paged forward variant
    below is quant-aware for free."""
    if quant not in QUANT_MODES:
        raise ValueError(
            f"unknown quant mode {quant!r}; expected one of {QUANT_MODES}")
    from .. import quantization as Q
    fp8 = None
    if quant == "fp8":
        from ..framework import jax_compat
        fp8 = jax_compat.fp8_dtype()
    key = "qw_dyn" if quant == "int8_dynamic" else "qw"
    blocks = dict(params["blocks"])
    if "moe_w1" in blocks:
        raise ValueError(
            "MoE expert weights have no quantized serving path yet — "
            "quant= needs a dense-FFN model (moe_experts=0); expert "
            "bytes scale down by sharding the expert axis instead")
    for name in ("qkv_w", "proj_w", "fc1_w", "fc2_w"):
        w = jnp.asarray(blocks[name], jnp.float32)
        if fp8 is not None:
            # e4m3 max-normal is 448; absmax scaling keeps the cast
            # from saturating
            s = jnp.maximum(
                jnp.max(jnp.abs(w), axis=1, keepdims=True) / 448.0, 1e-8)
            qw = (w / s).astype(fp8)
        else:
            keep = tuple(i for i in range(w.ndim) if i != 1)
            s = jnp.expand_dims(Q.quant_absmax_scale(w, axis=keep), 1)
            qw = jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8)
        blocks[name] = {key: qw, "scale": s.astype(jnp.float32)}
    out = dict(params)
    out["blocks"] = blocks
    return out


def _is_qweight(w):
    return isinstance(w, dict)


def _q_matmul(x, w, cd):
    """x [..., K] through a quantized weight dict (per-layer view of
    :func:`quantize_params`' leaves, L axis stripped by the scan).
    Returns [..., *out] in ``cd``."""
    qw = w["qw_dyn"] if "qw_dyn" in w else w["qw"]
    out_shape = qw.shape[1:]
    x2 = x.reshape(-1, qw.shape[0])
    w2 = qw.reshape(qw.shape[0], -1)
    s2 = w["scale"].reshape(1, -1)
    if "qw_dyn" in w:
        from ..quantization import int8_dynamic_matmul
        y = int8_dynamic_matmul(x2, w2, s2)
    else:
        from ..ops.pallas.dequant_matmul import dequant_matmul
        y = dequant_matmul(x2, w2, s2)
    return y.reshape(*x.shape[:-1], *out_shape).astype(cd)


def _layer_norm(x, g, b, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * g.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def _pallas_layer_norm(x, g, b, eps):
    from ..ops.pallas.norms import layer_norm
    return layer_norm(x, g, b, eps)


def _attention(q, k, v, cfg):
    # q,k,v: [B, N, nh, hd]
    if cfg.use_flash:
        with jax.named_scope("flash_attn"):
            return flash_attention(q, k, v, True)
    with jax.named_scope("attn"):
        d = q.shape[-1]
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
        n = logits.shape[-1]
        mask = jnp.tril(jnp.ones((n, n), bool))
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits.astype(jnp.float32),
                               -1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _moe_ffn(cfg: GPTConfig, x, blk):
    """Top-1 capacity-factor expert FFN over the ln2 output ``x``
    [B, N, H] (ISSUE 20).  Everything about the routing MIX is traced
    data — gate logits, argmax expert ids, capacity slots — so two
    traffic mixes run through the SAME executable; only N (the bucket)
    shapes the graph, via the static per-row capacity
    ``C = max(1, ceil(N/E * capacity_factor))``.

    Per batch row: softmax gate over ``moe_gate_w`` picks each token's
    expert (fp32, like attention's softmax), tokens claim capacity
    slots in position order (onehot cumsum), overflow tokens are
    dropped (they ride the residual), kept tokens are scattered into an
    [E, C, H] dispatch buffer, both expert matmuls run as one batched
    einsum over the expert axis — the axis GSPMD shards when the expert
    weights carry an 'tp'-axis NamedSharding (expert-parallel serving)
    — and outputs gather back gate-scaled.  Decode (N == 1) has C == 1
    and a row's single token always claims slot 0: no drop, which keeps
    paged decode token-exact with the full forward."""
    cd = jnp.dtype(cfg.dtype)
    E = cfg.moe_experts
    B, N, H = x.shape
    C = max(1, int(math.ceil(N / E * cfg.moe_capacity_factor)))
    gate_w = blk["moe_gate_w"].astype(jnp.float32)
    w1 = blk["moe_w1"].astype(cd)
    b1 = blk["moe_b1"].astype(cd)
    w2 = blk["moe_w2"].astype(cd)
    b2 = blk["moe_b2"].astype(cd)

    def route_row(h):                                     # h: [N, H]
        gl = h.astype(jnp.float32) @ gate_w               # [N, E]
        probs = jax.nn.softmax(gl, -1)
        eidx = jnp.argmax(gl, -1)                         # [N]
        gate = jnp.take_along_axis(probs, eidx[:, None], -1)[:, 0]
        onehot = jax.nn.one_hot(eidx, E, dtype=jnp.int32)
        # capacity slot: this token's rank among earlier tokens routed
        # to the same expert (deterministic position-order claim)
        cidx = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, -1) - 1
        keep = cidx < C
        csafe = jnp.clip(cidx, 0, C - 1)
        buf = jnp.zeros((E, C, H), cd).at[eidx, csafe].add(
            jnp.where(keep[:, None], h, 0))               # dropped: +0
        hid = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", buf, w1)
                          + b1[:, None], approximate=True)
        out = jnp.einsum("ecf,efh->ech", hid, w2) + b2[:, None]
        y = out[eidx, csafe] * gate[:, None].astype(cd)
        return jnp.where(keep[:, None], y, 0)

    return jax.vmap(route_row)(x)


def block_apply(cfg: GPTConfig, x, blk, attn_fn=None):
    """One transformer block.  x: [B, N, H]; blk: per-layer param dict
    (no leading L axis).  ``attn_fn(q, k, v) -> ([B,N,nh,hd], aux)`` swaps
    the attention inner loop (KV-cache decode passes one; default is the
    training causal attention, aux=None).  The hybrid-parallel path has its
    own tp-sharded block (models/gpt_hybrid.py::_sharded_block) — keep the
    math in sync.

    The ``jax.named_scope``s (``ln_qkv``, the attention's own, ``attn_out``,
    ``ffn``) are metadata: they name each op's ``op_name`` path in the
    compiled program and the profiler's trace, and add no operation."""
    cd = jnp.dtype(cfg.dtype)
    B, N, H = x.shape
    nh, hd = cfg.num_heads, cfg.head_dim

    ln = _pallas_layer_norm if cfg.use_pallas_norm else _layer_norm
    with jax.named_scope("ln_qkv"):
        h = ln(x, blk["ln1_g"], blk["ln1_b"], cfg.layer_norm_eps)
        if _is_qweight(blk["qkv_w"]):
            qkv = _q_matmul(h, blk["qkv_w"], cd)
        else:
            qkv = jnp.einsum("bnh,hcd->bncd", h, blk["qkv_w"].astype(cd))
        qkv = qkv + blk["qkv_b"].astype(cd)
        q, k, v = [qkv[:, :, i].reshape(B, N, nh, hd) for i in range(3)]
    if attn_fn is None:
        a, aux = _attention(q, k, v, cfg), None
    else:
        a, aux = attn_fn(q, k, v)
    with jax.named_scope("attn_out"):
        a = a.reshape(B, N, -1)
        if _is_qweight(blk["proj_w"]):
            a = _q_matmul(a, blk["proj_w"], cd) + blk["proj_b"].astype(cd)
        else:
            a = a @ blk["proj_w"].astype(cd) + blk["proj_b"].astype(cd)
        x = x + a
    with jax.named_scope("ffn"):
        x = x + _ffn(cfg, ln, x, blk, cd)
    return x if attn_fn is None else (x, aux)


def _ffn(cfg, ln, x, blk, cd):
    """ln2 and the block's feed-forward half (the residual is the
    caller's)."""
    h = ln(x, blk["ln2_g"], blk["ln2_b"], cfg.layer_norm_eps)
    if "moe_w1" in blk:
        h = _moe_ffn(cfg, h, blk)
    elif _is_qweight(blk["fc1_w"]):
        # quantized FFN goes through the fused dequant matmul — the
        # fused_ffn kernel only knows float weights
        h = jax.nn.gelu(_q_matmul(h, blk["fc1_w"], cd)
                        + blk["fc1_b"].astype(cd), approximate=True)
        h = _q_matmul(h, blk["fc2_w"], cd) + blk["fc2_b"].astype(cd)
    elif cfg.use_fused_ffn:
        from ..ops.pallas.fused_ffn import fused_ffn
        h = fused_ffn(h, blk["fc1_w"].astype(cd), blk["fc1_b"].astype(cd),
                      blk["fc2_w"].astype(cd), blk["fc2_b"].astype(cd))
    else:
        h = jax.nn.gelu(h @ blk["fc1_w"].astype(cd)
                        + blk["fc1_b"].astype(cd), approximate=True)
        h = h @ blk["fc2_w"].astype(cd) + blk["fc2_b"].astype(cd)
    return h


def _embed_at(params, tokens, pos):
    """Token plus learned position embeddings of ``tokens`` at the
    absolute positions ``pos``, in the parameters' dtype."""
    return jnp.take(params["wte"], tokens, axis=0) + jnp.take(
        params["wpe"], pos, axis=0)


def embed(cfg: GPTConfig, params, tokens, pos_offset=0):
    N = tokens.shape[-1]
    with jax.named_scope("embed"):
        x = _embed_at(params, tokens, pos_offset + jnp.arange(N))
        return x.astype(jnp.dtype(cfg.dtype))


def _head(cfg: GPTConfig, params, x):
    """Final norm and the tied output head (logits = x @ wte^T) of the
    serving programs: float32 logits."""
    with jax.named_scope("head_sample"):
        x = _layer_norm(x, params["lnf_g"], params["lnf_b"],
                        cfg.layer_norm_eps)
        return (x @ params["wte"].astype(x.dtype).T).astype(jnp.float32)


def forward(params, tokens, cfg: GPTConfig):
    """tokens [B, N] int32 -> logits [B, N, V] in fp32."""
    x = embed(cfg, params, tokens)
    blk_fn = functools.partial(block_apply, cfg)
    if cfg.remat:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat_policy == "dots" else None)
        blk_fn = jax.checkpoint(blk_fn, policy=policy)

    def scan_body(carry, blk):
        return blk_fn(carry, blk), None

    x, _ = jax.lax.scan(scan_body, x, params["blocks"])
    x = _layer_norm(x, params["lnf_g"], params["lnf_b"], cfg.layer_norm_eps)
    # tied embeddings: logits = x @ wte^T
    return (x @ params["wte"].astype(x.dtype).T).astype(jnp.float32)


def init_cache(cfg: GPTConfig, batch, max_len, dtype=None):
    """Per-layer KV cache stacked on the layer axis:
    {'k','v': [L, B, max_len, nh, hd], 'len': int32 tokens filled}."""
    if max_len > cfg.max_seq_len:
        raise ValueError(
            f"cache max_len {max_len} exceeds cfg.max_seq_len "
            f"{cfg.max_seq_len}: positions past it would silently reuse "
            "the last positional embedding (jnp.take clamps)")
    cd = jnp.dtype(dtype or cfg.dtype)
    shape = (cfg.num_layers, batch, max_len, cfg.num_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cd), "v": jnp.zeros(shape, cd),
            "len": jnp.int32(0)}


def _cached_block(cfg, x, blk, k_cache, v_cache, cur_len):
    """block_apply with a cache-appending attention: this chunk's K/V are
    written at ``cur_len`` and queries attend the filled prefix.  x:
    [B, T, H]; k_cache/v_cache: [B, max_len, nh, hd].  Returns
    (x_out, k_cache, v_cache)."""
    cd = jnp.dtype(cfg.dtype)
    hd = cfg.head_dim
    max_len = k_cache.shape[1]

    def cached_attn(q, k, v):
        T = q.shape[1]
        with jax.named_scope("kv_write"):
            kc = jax.lax.dynamic_update_slice(
                k_cache, k.astype(k_cache.dtype), (0, cur_len, 0, 0))
            vc = jax.lax.dynamic_update_slice(
                v_cache, v.astype(v_cache.dtype), (0, cur_len, 0, 0))
        # attend over the whole cache buffer, masking beyond cur_len+T and
        # the causal future (query i at absolute position cur_len+i)
        with jax.named_scope("attn"):
            logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                                kc.astype(jnp.float32)) / math.sqrt(hd)
            q_pos = cur_len + jnp.arange(T)[:, None]      # [T,1]
            k_pos = jnp.arange(max_len)[None, :]          # [1,max_len]
            mask = k_pos <= q_pos                     # causal + fill bound
            logits = jnp.where(mask[None, None], logits, -1e30)
            probs = jax.nn.softmax(logits, -1).astype(cd)
            a = jnp.einsum("bhqk,bkhd->bqhd", probs, vc.astype(cd))
        return a, (kc, vc)

    x, (k_cache, v_cache) = block_apply(cfg, x, blk, attn_fn=cached_attn)
    return x, k_cache, v_cache


def forward_cached(params, tokens, cfg: GPTConfig, cache):
    """Prefill/decode forward: consumes ``tokens`` [B, T] starting at
    cache['len'], returns (logits [B, T, V] fp32, updated cache)."""
    cur = cache["len"]
    max_len = cache["k"].shape[2]
    if (not isinstance(cur, jax.core.Tracer)
            and int(cur) + tokens.shape[1] > max_len):
        raise ValueError(
            f"cache overflow: len {int(cur)} + {tokens.shape[1]} new tokens "
            f"> cache size {max_len} (dynamic_update_slice would clamp the "
            "write position and corrupt the cache)")
    x = embed(cfg, params, tokens, pos_offset=cur)

    def scan_body(carry, layer):
        xx = carry
        blk, kc, vc = layer
        with jax.named_scope("layer"):
            xx, kc, vc = _cached_block(cfg, xx, blk, kc, vc, cur)
        return xx, (kc, vc)

    x, (ks, vs) = jax.lax.scan(scan_body, x,
                               (params["blocks"], cache["k"], cache["v"]))
    return (_head(cfg, params, x),
            {"k": ks, "v": vs, "len": cur + tokens.shape[1]})


def generate(params, cfg: GPTConfig, prompt, max_new_tokens,
             temperature=0.0, top_k=0, key=None, eos_token=None):
    """Jit-compatible autoregressive decoding with a KV cache.

    prompt: [B, T0] int32.  Greedy when temperature == 0; otherwise
    temperature softmax sampling, optionally top-k truncated.  Returns
    [B, T0 + max_new_tokens] (generation continues past eos; shapes stay
    static for XLA — trim finished rows host-side with :func:`trim_eos`,
    which honors ``eos_token``).  Replaces the reference's fused decoding
    ops (ref: paddle/fluid/operators/fused/fused_multi_transformer_op.cu
    int8/cache path) with a scanned XLA program."""
    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens}")
    B, T0 = prompt.shape
    total = T0 + max_new_tokens
    cache = init_cache(cfg, B, total)
    logits, cache = forward_cached(params, prompt, cfg, cache)
    last = logits[:, -1]
    if key is None:
        key = jax.random.PRNGKey(0)

    def sample(lg, k):
        if temperature and temperature > 0:
            lg = lg / temperature
            if top_k:
                kth = jnp.sort(lg, -1)[:, -top_k][:, None]
                lg = jnp.where(lg >= kth, lg, -1e30)
            return jax.random.categorical(k, lg)
        return jnp.argmax(lg, -1)

    if max_new_tokens == 1:
        # the scan below would have length 0 — skip it entirely (a
        # zero-length scan still traces its body, compiling an L-layer
        # forward that never runs).  RNG consumption matches the scan
        # path exactly: the single sample uses split(key)[1].
        _, sub = jax.random.split(key)
        final = sample(last, sub).astype(jnp.int32)
        return jnp.concatenate([prompt, final[:, None]], axis=1)

    def step(carry, _):
        cache, last, k = carry
        k, sub = jax.random.split(k)
        tok = sample(last, sub).astype(jnp.int32)
        lg, cache = forward_cached(params, tok[:, None], cfg, cache)
        return (cache, lg[:, -1], k), tok

    # scan produces max_new_tokens-1 tokens; the final token needs only a
    # sample from the last logits, not another L-layer forward
    (_, last, key), toks = jax.lax.scan(step, (cache, last, key),
                                        None, length=max_new_tokens - 1)
    _, sub = jax.random.split(key)
    final = sample(last, sub).astype(jnp.int32)
    toks = jnp.concatenate([jnp.swapaxes(toks, 0, 1), final[:, None]],
                           axis=1)
    return jnp.concatenate([prompt, toks], axis=1)


def trim_eos(sequences, prompt_len, eos_token, include_eos=True):
    """Host-side early-stop: cut each row of a ``generate`` result at the
    first ``eos_token`` in the GENERATED region (the prompt may legally
    contain eos).  Device shapes stay static — generation runs to
    ``max_new_tokens`` and this trims afterwards, which is the XLA-shaped
    analogue of the reference's dynamic ``is_finished`` early exit.
    Returns a list of 1-D int numpy arrays (ragged)."""
    import numpy as np
    seqs = np.asarray(sequences)
    out = []
    for row in seqs:
        gen = row[prompt_len:]
        hits = np.nonzero(gen == eos_token)[0]
        if hits.size:
            end = prompt_len + int(hits[0]) + (1 if include_eos else 0)
        else:
            end = row.shape[0]
        out.append(row[:end])
    return out


# --------------------------------------------------------------------------
# slot-batched decode (the serving engine's KV layout)
# --------------------------------------------------------------------------
#
# Training/`generate` cache one REQUEST per batch row with a shared scalar
# ``len``.  The serving engine instead owns a fixed pool of decode slots
# backed by one [L, S, max_len, nh, hd] buffer with a PER-SLOT ``len``
# vector: every iteration one jitted, buffer-donated step advances all
# in-flight sequences a token, and a finished sequence's slot is re-filled
# by a new request's prefill without touching the others (continuous
# batching — Orca's iteration-level scheduling).  Stale K/V beyond a
# slot's ``len`` is masked off in attention, so slot reuse needs no
# zeroing, only a length reset.


def _pool_zeros(shape, dtype, sharding=None):
    """Host-side zero pool allocation: ``device_put(np.zeros)`` instead
    of ``jnp.zeros``, because the eager broadcast COMPILES a tiny XLA
    program per distinct shape — and the AOT-warm serving replica's
    contract is ZERO backend compiles at boot.  Only the host-called
    pool constructors use this; in-trace allocations stay jnp.
    ``sharding`` (a NamedSharding) places the pool mesh-sharded for the
    tensor-parallel engine."""
    import numpy as np
    import jax
    z = np.zeros(shape, jnp.dtype(dtype))
    return jax.device_put(z) if sharding is None \
        else jax.device_put(z, sharding)


def init_slot_cache(cfg: GPTConfig, slots, max_len, dtype=None,
                    mesh=None):
    """Slot-pooled KV cache: {'k','v': [L, S, max_len, nh, hd],
    'len': int32[S] tokens filled per slot}.  With ``mesh`` the K/V
    buffers shard the head axis over 'tp' (:data:`KV_POOL_SPEC`)."""
    if max_len > cfg.max_seq_len:
        raise ValueError(
            f"slot cache max_len {max_len} exceeds cfg.max_seq_len "
            f"{cfg.max_seq_len}: positions past it would reuse the last "
            "positional embedding")
    cd = jnp.dtype(dtype or cfg.dtype)
    sh = None if mesh is None else _kv_pool_sharding(mesh)
    shape = (cfg.num_layers, slots, max_len, cfg.num_heads, cfg.head_dim)
    return {"k": _pool_zeros(shape, cd, sh), "v": _pool_zeros(shape, cd, sh),
            "len": _pool_zeros((slots,), jnp.int32)}


def reset_slots(lens, slots):
    """Zero the fill lengths of ``slots`` (int or sequence).  Works on the
    host numpy mirror the engine keeps or on the device vector; K/V need
    no reset — everything past len is masked."""
    import numpy as np
    if isinstance(lens, np.ndarray):
        lens[np.asarray(slots)] = 0
        return lens
    return lens.at[jnp.asarray(slots)].set(0)


def _slot_block(cfg, x, blk, k_cache, v_cache, lens):
    """block_apply for the slot-batched single-token decode: each slot's
    new K/V land at ITS OWN ``lens[s]`` (a vmapped scatter, one write
    position per slot) and its query attends ``k_pos <= lens[s]``.
    x: [S, 1, H]; k_cache/v_cache: [S, max_len, nh, hd]; lens: int32[S]."""
    cd = jnp.dtype(cfg.dtype)
    hd = cfg.head_dim
    max_len = k_cache.shape[1]

    def slot_attn(q, k, v):
        def write(c, new, l):
            return jax.lax.dynamic_update_slice(
                c, new.astype(c.dtype), (l, 0, 0))
        kc = jax.vmap(write)(k_cache, k, lens)
        vc = jax.vmap(write)(v_cache, v, lens)
        logits = jnp.einsum("sqhd,skhd->shqk", q.astype(jnp.float32),
                            kc.astype(jnp.float32)) / math.sqrt(hd)
        # per-slot fill bound: the new token sits at position lens[s]
        mask = jnp.arange(max_len)[None, :] <= lens[:, None]   # [S,max_len]
        logits = jnp.where(mask[:, None, None, :], logits, -1e30)
        probs = jax.nn.softmax(logits, -1).astype(cd)
        a = jnp.einsum("shqk,skhd->sqhd", probs, vc.astype(cd))
        return a, (kc, vc)

    x, (k_cache, v_cache) = block_apply(cfg, x, blk, attn_fn=slot_attn)
    return x, k_cache, v_cache


def decode_step_slots(params, tokens, cfg: GPTConfig, cache, active=None):
    """One decode iteration for EVERY slot at once: consume one token per
    slot (each at its own position ``cache['len'][s]``), return
    (logits [S, V] fp32, updated cache).  ``active`` (bool[S]) gates the
    length advance — inactive slots still compute (the batch shape is
    static) but their ``len`` stays put, so their K/V write lands on the
    same spot every iteration and is harmlessly overwritten by the next
    prefill into that slot."""
    lens = cache["len"]
    x = jnp.take(params["wte"], tokens, axis=0) \
        + jnp.take(params["wpe"], lens, axis=0)
    x = x[:, None, :].astype(jnp.dtype(cfg.dtype))        # [S, 1, H]

    def scan_body(carry, layer):
        xx = carry
        blk, kc, vc = layer
        xx, kc, vc = _slot_block(cfg, xx, blk, kc, vc, lens)
        return xx, (kc, vc)

    x, (ks, vs) = jax.lax.scan(scan_body, x,
                               (params["blocks"], cache["k"], cache["v"]))
    x = _layer_norm(x, params["lnf_g"], params["lnf_b"], cfg.layer_norm_eps)
    logits = (x @ params["wte"].astype(x.dtype).T).astype(jnp.float32)
    new_len = lens + 1 if active is None else jnp.where(active, lens + 1,
                                                        lens)
    return logits[:, 0], {"k": ks, "v": vs, "len": new_len}


# --------------------------------------------------------------------------
# paged decode (the block-table KV layout — ISSUE 8)
# --------------------------------------------------------------------------
#
# The slot cache above still reserves a contiguous [max_len] strip per
# slot.  The paged layout breaks the pool into fixed-size pages
# ([L, num_pages, page_size, nh * hd]) and gives each slot a PAGE TABLE
# (int32[maxP] of physical page ids, scratch page 0 padding the unused
# tail): position p of a slot's sequence lives at
# (table[p // page_size], p % page_size).  Attention gathers K/V through
# the table (ops/pallas/paged_attn.py: a Pallas kernel that DMAs exactly
# the referenced pages on TPU, a lax gather view elsewhere), so the HBM
# a request pins is proportional to its LENGTH, not to max_len — and
# identical prompt prefixes can share physical pages
# (inference/kv_pager.py owns that bookkeeping).
#
# The pool is held IN PLACE by every step.  Its stored shape merges the
# head axes: a minor axis of nh * hd (a multiple of the 128 lanes at
# 32 x 64 and 16 x 128 alike) under page_size rows is what the device
# lays out major-to-minor with no padding, which is also the only
# layout a Mosaic kernel takes — a [.., nh, 64] tail would be stored
# page-minor and relaid out around every kernel call.  And the layer
# loops CARRY the whole pool, writing it with layer-indexed scatters:
# as the ``xs``/``ys`` of a scan it would be a second pool, sliced and
# restacked a layer at a time.
#
# A page holds K/V in the compute dtype (4 bytes on the CPU bench path,
# 2 on TPU bf16), or — the quantized pool, ISSUE 9 — int8 with an fp32
# absmax scale PER (page, position, head): position granularity because
# pages are written position-at-a-time (decode appends, chunked
# prefill), and a page-granular scale would need the whole page
# requantized on every append, which drifts.  Each position's scale is
# written exactly once, together with its K/V bytes, and never touched
# again — which also keeps shared prefix pages byte-deterministic (same
# prompt, same params => same int8 bytes + scales), the property the
# pager's content hash relies on.  Reads dequantize: the Pallas
# paged-attention kernel does it inside the copied block
# (ops/pallas/paged_attn.py), the lax fallback on the gathered view.
#
# ``pools`` is the donated pool arrays in executable-operand order,
# (k, v) or (k, k_scale, v, v_scale).  WHICH of the two a program was
# handed is read off the pools, by the five helpers under
# :func:`init_paged_pools` and by ``paged_attention``; every paged
# program below is written once over them.


def paged_pool_shape(cfg: GPTConfig, num_pages, page_size):
    """The stored shape of one paged K or V pool."""
    return (cfg.num_layers, num_pages, page_size,
            cfg.num_heads * cfg.head_dim)


def quantize_kv(x):
    """Per-position-per-head absmax int8: x [..., nh, hd] float ->
    (q int8 same shape, scale fp32 [..., nh])."""
    xf = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1) / 127.0, 1e-8)
    q = jnp.clip(jnp.round(xf / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


def dequantize_kv(q, s, dtype):
    """Inverse of :func:`quantize_kv` (up to rounding)."""
    return (q.astype(jnp.float32) * s[..., None]).astype(dtype)


def init_paged_pools(cfg: GPTConfig, num_pages, page_size, dtype=None,
                     mesh=None, kv_quant=False):
    """The paged KV pool, zeros, as the donated arrays in operand
    order: (k, v), each [L, num_pages, page_size, nh * hd] in ``dtype``
    — a page row is every head's hd values side by side, head-major —
    or with ``kv_quant`` (k, k_scale, v, v_scale), int8 pages and fp32
    scales [L, P, ps, nh].  Page 0 is the scratch page (inactive lanes
    / padded prefill rows scatter there; nothing reads it).  With
    ``mesh`` pages and scale rows alike shard the head axis (axis 3 in
    either rank) over 'tp' — page ids stay rank-invariant, a page's
    bytes AND scales live on the same rank, and the absmax quantizer
    needs only its own heads, so the quantize-once byte contract holds
    per shard."""
    sh = None if mesh is None else _kv_pool_sharding(mesh)
    shape = paged_pool_shape(cfg, num_pages, page_size)
    if not kv_quant:
        cd = jnp.dtype(dtype or cfg.dtype)
        return (_pool_zeros(shape, cd, sh), _pool_zeros(shape, cd, sh))
    scales = shape[:-1] + (cfg.num_heads,)
    return (_pool_zeros(shape, jnp.int8, sh),
            _pool_zeros(scales, jnp.float32, sh),
            _pool_zeros(shape, jnp.int8, sh),
            _pool_zeros(scales, jnp.float32, sh))


def _halves(pools):
    """(K's arrays, V's arrays) of the pool operands: (pages,) each, or
    (pages, scales) each for the int8 pool."""
    n = len(pools) // 2
    return pools[:n], pools[n:]


def _float_dtype(cfg, pools):
    """The dtype a program holds K/V in on their way into and out of
    the pool: the pool's own, or the compute dtype over int8 pages."""
    return jnp.dtype(cfg.dtype) if len(pools) == 4 else pools[0].dtype


def _rows(half, x, lead):
    """What a committed write of fresh K or V ``x`` [..., nh, hd] stores
    in ``half``, one array for each of its arrays, laid out
    ``[*lead, nh * hd | nh]`` as the pool's rows are: the cast to the
    pool's dtype, or — quantized exactly once — the int8 bytes and
    their per-position-per-head scales."""
    if len(half) == 2:
        q, s = quantize_kv(x)
        return q.reshape(*lead, -1), s.reshape(*lead, -1)
    return (x.reshape(*lead, -1).astype(half[0].dtype),)


def _write(half, index, x, lead):
    """``half`` with fresh K or V ``x`` written at ``index``, whose
    result is ``lead`` rows: every array of the half at the same
    coordinate (:func:`_rows`)."""
    return tuple(p.at[index].set(r)
                 for p, r in zip(half, _rows(half, x, lead)))


def _unrows(rows, lead, heads, dtype):
    """What attention reads back from ``rows`` as a pool half stores
    them (its gathered pages, or :func:`_rows` of a window not yet
    written): [*lead, nh, hd] in ``dtype``, dequantized where the rows
    are int8 bytes with their scales."""
    x = rows[0].reshape(*lead, *heads)
    if len(rows) == 2:
        return dequantize_kv(x, rows[1].reshape(*lead, heads[0]), dtype)
    return x.astype(dtype)


def _layer_scan(body, x, blocks, pools):
    """Run ``body(x, blk, layer, pools) -> (x, pools, out)`` over the
    stacked layers with the weights as the scan's ``xs`` and the KV
    pools as CARRIES, so each layer updates them where they lie.
    Returns (x, pools, stacked outs)."""
    n_layers = jax.tree_util.tree_leaves(blocks)[0].shape[0]

    def step(carry, layer):
        xx, pp = carry
        blk, i = layer
        with jax.named_scope("layer"):
            xx, pp, out = body(xx, blk, i, pp)
        return (xx, pp), out

    (x, pools), outs = jax.lax.scan(
        step, (x, tuple(pools)),
        (blocks, jnp.arange(n_layers, dtype=jnp.int32)))
    return x, pools, outs


def _paged_slot_block(cfg, x, blk, layer, pools, page_table, write_pages,
                      write_offs, lens, mesh=None):
    """block_apply for the page-table single-token decode: slot s's new
    K/V land at (layer, write_pages[s], write_offs[s]) — a batched
    scatter into the shared pool, an int8 pool's scales at the same
    coordinate — and its query attends the layer's pages through the
    table, masked to ``k_pos <= lens[s]``.  x: [S, 1, H]; pools: the
    whole pool; page_table: int32 [S, maxP].  Returns (x, pools)."""
    from ..ops.pallas.paged_attn import paged_attention
    S = x.shape[0]
    k_half, v_half = _halves(pools)
    at = (layer, write_pages, write_offs)

    def pattn(q, k, v):
        with jax.named_scope("kv_write"):
            new = (*_write(k_half, at, k[:, 0], (S,)),
                   *_write(v_half, at, v[:, 0], (S,)))
        with jax.named_scope("paged_attn"):
            a = paged_attention(q, new, page_table, lens, layer, mesh=mesh)
        return a, new

    return block_apply(cfg, x, blk, attn_fn=pattn)


# --------------------------------------------------------------------------
# the paged engine's family interface
# --------------------------------------------------------------------------
#
# What differs by model family behind ``PagedServingEngine``
# (inference/serving.py::family_of names the functions and finds the
# module by the type of ``cfg``): the pools, the three paged programs,
# what a cached position costs, the prefix-hash salt, and which engine
# compositions exist.  Only ``init_paged_pools`` is TOLD which pool to
# make; the programs take whichever they are handed.

def check_serving(cfg, **composition):
    """Every engine composition is built for this family; the engine's
    own checks (pp x quant, chunk sizes, ...) name the rest."""


def prefix_salt(cfg):
    return ""


def kv_bytes_per_position(cfg: GPTConfig, itemsize):
    """Bytes of K and V one cached position holds, all layers."""
    return 2 * cfg.num_layers * cfg.hidden_size * itemsize


def decode_group_pages(cfg: GPTConfig, pools, table_width, tp=1):
    """Pages a grid step of the decode kernel takes over these pools
    (what one 'tp' rank runs; ops/pallas/paged_attn.py::group_pages)."""
    from ..ops.pallas.paged_attn import group_pages
    page_size, width = pools[0].shape[2:]
    return group_pages(table_width, page_size, width // tp,
                       pools[0].dtype.itemsize, cfg.num_heads // tp)


def prefill_paged(params, cfg: GPTConfig, pools, tokens, lens, ptab):
    """Causal forward over the padded prompts ``tokens`` [b, s], then
    one batched scatter of the filled K/V page chunks into the pools
    through the page tables ``ptab`` [b, s / page_size] (pad rows target
    the scratch page; shared pages receive content identical to what
    they already hold, so duplicate indices are benign).  Returns
    (logits of each row's last true position [b, V], pools).

    On the int8 pool the forward still runs — and attends its own
    prompt — in the compute dtype; K/V QUANTIZE ON WRITE, scales
    landing in the scale arrays at the same page coordinates.
    Quantization error only ever enters on later reads."""
    b, s = tokens.shape
    ps = pools[0].shape[2]
    fresh = init_cache(cfg, b, s, dtype=_float_dtype(cfg, pools))
    logits, filled = forward_cached(params, tokens, cfg, fresh)
    flat = ptab.reshape(-1)
    # [L, b, s, ...] -> page chunks [L, b * s/ps, ps, nh*hd | nh]: rows
    # as the pool stores them, written where it lies
    lead = (cfg.num_layers, b * (s // ps), ps)
    k_half, v_half = _halves(pools)
    at = (slice(None), flat)
    with jax.named_scope("kv_scatter"):
        pools = (*_write(k_half, at, filled["k"], lead),
                 *_write(v_half, at, filled["v"], lead))
    with jax.named_scope("head_sample"):
        idx = jnp.clip(lens - 1, 0, s - 1)
        last = jnp.take_along_axis(logits, idx[:, None, None],
                                   axis=1)[:, 0]
    return last, pools


def chunk_paged(params, cfg: GPTConfig, pools, tokens, pt_row, offset):
    """One chunked-prefill piece for a single slot: consume ``tokens``
    [1, C] starting at absolute position ``offset`` (a traced scalar, so
    every chunk of every prompt reuses ONE executable), attending the
    slot's already-filled pages plus the in-chunk causal prefix.
    Returns (logits [1, C, V] fp32, pools).

    Per layer: gather the slot's page view (dequantized where the pool
    is int8), splice the chunk in with the exact `_cached_block` math,
    then write back ONLY the chunk's own positions — static width C,
    page-aligned because the engine rounds ``prefill_chunk`` to whole
    pages and chunk offsets are C-multiples.  Earlier positions are
    never rewritten (from an unchanged view they would be the same
    bytes, and on the int8 pool they must not round-trip through
    requantization: the pager's content-hash contract).  Padded tail
    rows of the final chunk write garbage at positions past the true
    prompt length, on the table's scratch-padded page ids or masked by
    ``len`` until decode overwrites them — the same contract as the
    wave prefill's pads."""
    maxP = pt_row.shape[0]
    ps = pools[0].shape[2]
    C = tokens.shape[1]
    heads = (cfg.num_heads, cfg.head_dim)
    held = _float_dtype(cfg, pools)
    x = embed(cfg, params, tokens, pos_offset=offset)
    own = jax.lax.dynamic_slice(pt_row, (offset // ps,), (C // ps,))

    def body(xx, blk, layer, pp):
        halves = _halves(pp)
        views = [_unrows(tuple(p[layer, pt_row] for p in half),
                         (1, maxP * ps), heads, held) for half in halves]
        xx, *views = _cached_block(cfg, xx, blk, *views, offset)
        out = ()
        for half, view in zip(halves, views):
            chunk = jax.lax.dynamic_slice(view[0], (offset, 0, 0),
                                          (C,) + heads)
            out += _write(half, (layer, own), chunk, (C // ps, ps))
        return xx, out, None

    x, pools, _ = _layer_scan(body, x, params["blocks"], pools)
    return _head(cfg, params, x), pools


def decode_paged(params, cfg: GPTConfig, pools, page_table, write_pages,
                 write_offs, lens, tokens, mesh=None):
    """One decode iteration for every slot through the paged pool:
    consume one token per slot (at its own ``lens[s]``), return
    (logits [S, V] fp32, pools, None) — the third value is what a
    family returns WITH the sampled tokens in the step's one readback;
    this one has nothing.  Inactive slots point their write coordinates
    at the scratch page and their table rows at scratch, so the batch
    shape stays static and their garbage never lands on a real page —
    the host advances only active lens.  ``mesh``: the 'tp' serving
    mesh of a head-sharded pool, which the paged-attention kernel needs
    to run per shard (GSPMD cannot split it;
    ops/pallas/paged_attn.py::_over_heads)."""
    with jax.named_scope("embed"):
        x = _embed_at(params, tokens, lens)
        x = x[:, None, :].astype(jnp.dtype(cfg.dtype))    # [S, 1, H]

    def body(xx, blk, layer, pp):
        xx, pp = _paged_slot_block(cfg, xx, blk, layer, pp, page_table,
                                   write_pages, write_offs, lens, mesh)
        return xx, pp, None

    x, pools, _ = _layer_scan(body, x, params["blocks"], pools)
    return _head(cfg, params, x)[:, 0], pools, None


# --------------------------------------------------------------------------
# speculative verify + draft plumbing (ISSUE 13)
# --------------------------------------------------------------------------
#
# Speculative decoding turns the one-token decode step into a W = k+1
# position VERIFY: window position 0 consumes the last committed token,
# positions 1..k consume draft candidates, and one batched forward
# scores every position at once.  The hard paged-KV constraint is that
# rejected candidates must never corrupt the page pool, so the verify
# forward below is DEFERRED-COMMIT: the pool is strictly read-only
# during the forward (queries attend the gathered page view of the
# committed prefix plus an in-window causal mask over the window's own
# K/V), and the window K/V are RETURNED to the caller, which scatters
# only the accepted prefix — a masked page-aligned write whose rejected
# lanes redirect to the scratch page, so accept length stays a traced
# value and the executable set stays fixed.  Accepted positions write
# the exact bytes a sequential decode would have (same cast to the pool
# dtype, same quantize-once per position on the int8 pool), which is
# what keeps the prefix-hash/page-byte determinism contract intact.


def _paged_verify_block(cfg, x, blk, layer, pools, page_table, lens):
    """block_apply for the W-token speculative verify window: queries at
    absolute positions ``lens[s] + j`` attend the gathered page view
    with the window's own K/V SPLICED IN at their true positions
    (``lens[s] + i``, a per-row scatter whose out-of-bounds lanes drop)
    under the mask ``k_pos <= lens[s] + j`` — the in-window causal mask
    and the fill bound in one.  Splicing (rather than concatenating the
    window) keeps the attention contraction width exactly the
    non-speculative decode's ``maxP * ps``, so each ACCEPTED position's
    activations — and therefore the K/V bytes the engine later commits —
    are bit-identical to a sequential decode, which is what the
    page-byte determinism regression demands.  The window is spliced in
    as attention READS BACK what a committed write would store
    (:func:`_rows`, :func:`_unrows`): on the int8 pool a token's own
    K/V round-trips through the quantizer before attention sees it,
    exactly as in the sequential decode.  x: [S, W, H]; pools: the
    whole pool, read at ``layer`` and untouched; page_table: int32
    [S, maxP].  Returns (x_out, windows): the window's rows
    [S, W, nh * hd | nh], one array for each pool array in pool order,
    as a committed write stores them."""
    S, maxP = page_table.shape
    view = maxP * pools[0].shape[2]
    heads = (cfg.num_heads, cfg.head_dim)
    cd = jnp.dtype(cfg.dtype)

    def vattn(q, k, v):
        W = q.shape[1]
        rows = jnp.arange(S)[:, None]
        cols = lens[:, None] + jnp.arange(W)[None, :]
        windows, spliced = [], []
        # K is read in float32 (the score math), V in the compute dtype
        for half, fresh, dt in zip(_halves(pools), (k, v),
                                   (jnp.float32, cd)):
            win = _rows(half, fresh, (S, W))
            held = _unrows(tuple(p[layer, page_table] for p in half),
                           (S, view), heads, dt)
            spliced.append(held.at[rows, cols].set(     # OOB lanes drop
                _unrows(win, (S, W), heads, dt)))
            windows += win
        kcf, vcc = spliced
        # one single-query attention PER LANE (W is small and static):
        # each lane's dot_generals have exactly the one-token decode's
        # shapes, so XLA accumulates in the same order and an accepted
        # lane's output — hence the K/V bytes committed downstream — is
        # BITWISE what the sequential decode would have produced.  A
        # W-query batched einsum is ulp-close but not bit-equal (the
        # page-byte determinism regression catches exactly that).
        # Lanes > j sit spliced in the view but masked for query j: the
        # same ``k_pos <= len`` bound the decode applies at the step
        # that would have consumed lane j sequentially; their exp(-1e30)
        # underflows to exactly 0, so their differing values never leak.
        outs = []
        for j in range(W):
            lg = jnp.einsum("sqhd,skhd->shqk",
                            q[:, j:j + 1].astype(jnp.float32),
                            kcf) / math.sqrt(heads[1])
            m = jnp.arange(view)[None, :] <= (lens + j)[:, None]
            lg = jnp.where(m[:, None, None, :], lg, -1e30)
            pj = jax.nn.softmax(lg, -1).astype(cd)
            outs.append(jnp.einsum("shqk,skhd->sqhd", pj, vcc))
        a = jnp.concatenate(outs, axis=1)             # [S, W, nh, hd]
        return a, tuple(windows)

    return block_apply(cfg, x, blk, attn_fn=vattn)


def decode_step_paged_verify(params, cfg: GPTConfig, pools, tokens,
                             page_table, lens):
    """Speculative verify forward (ISSUE 13): consume ``tokens`` [S, W]
    (W = spec_k + 1 — the last committed token plus the k draft
    candidates) at absolute positions ``lens[s] + j`` through the paged
    pool, WITHOUT writing it.  Returns (logits [S, W, V] fp32,
    windows): the window's rows [L, S, W, nh * hd | nh] in pool order,
    exactly the bytes (and, on the int8 pool, the once-per-position
    scales) a sequential decode would have written — the caller commits
    the accepted prefix with one masked scatter per pool array."""
    W = tokens.shape[1]
    pos = lens[:, None] + jnp.arange(W)[None, :]
    x = _embed_at(params, tokens, pos).astype(jnp.dtype(cfg.dtype))

    def body(xx, blk, layer, pp):
        xx, win = _paged_verify_block(cfg, xx, blk, layer, pp, page_table,
                                      lens)
        return xx, pp, win

    x, _, windows = _layer_scan(body, x, params["blocks"], pools)
    return _head(cfg, params, x), windows


def draft_prefill_slot(params, tokens, cfg: GPTConfig, cache_k, cache_v,
                       slot, offset):
    """One C-token chunk of the DRAFT model's prompt ingestion into a
    single slot of its slot-contiguous cache (ISSUE 13 draft mode).
    ``slot`` and ``offset`` are traced scalars, so every chunk of every
    prompt reuses ONE executable.  No logits are returned — the first
    sampled token always comes from the TARGET prefill.  Padded tail
    positions write garbage past the true prompt length, masked by the
    draft length until the catch-up writes overwrite them (the same
    contract as the target engine's prefill pads)."""
    x = embed(cfg, params, tokens, pos_offset=offset)

    def scan_body(carry, layer):
        xx = carry
        blk, kc, vc = layer                   # kc: [S, maxd, nh, hd]
        row_k = jax.lax.dynamic_index_in_dim(kc, slot, 0, keepdims=True)
        row_v = jax.lax.dynamic_index_in_dim(vc, slot, 0, keepdims=True)
        xx, row_k, row_v = _cached_block(cfg, xx, blk, row_k, row_v,
                                         offset)
        kc = jax.lax.dynamic_update_slice(kc, row_k, (slot, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, row_v, (slot, 0, 0, 0))
        return xx, (kc, vc)

    _, (ks, vs) = jax.lax.scan(scan_body, x,
                               (params["blocks"], cache_k, cache_v))
    return ks, vs


def draft_catchup_and_draft(params, cfg: GPTConfig, cache_k, cache_v,
                            ctx, n_ctx, lens, k):
    """The draft model's per-engine-iteration work, ONE executable for
    every step of every request (ISSUE 13 draft mode): first CATCH UP on
    the tokens the target committed last iteration (``ctx`` [S, W],
    left-aligned, ``n_ctx`` of them per row — the verify commits at most
    W = k+1, so the backlog always fits), then DRAFT ``k`` candidates by
    greedy self-sampling.  Runs ``W + k - 1`` single-token slot decodes:
    iteration ``j`` consumes ``ctx[:, j]`` while ``j < n_ctx[s]``, else
    the token the row itself sampled at ``j - 1``; K/V land at position
    ``lens[s] + j`` of the slot cache.  Only the ctx writes are durable
    (the caller advances ``lens`` by ``n_ctx``); draft-token K/V past
    that are speculative garbage masked by the fill bound and
    overwritten by the next catch-up — the slot cache must therefore be
    ``2k`` positions deeper than the longest sequence.  Returns
    (cache_k, cache_v, drafts [S, k] int32)."""
    S, W = ctx.shape
    steps = W + k - 1

    def body(carry, j):
        kc, vc, prev = carry
        tok = jnp.where(j < n_ctx,
                        jax.lax.dynamic_index_in_dim(ctx, j, 1, False),
                        prev)
        cache = {"k": kc, "v": vc, "len": lens + j}
        logits, cache = decode_step_slots(params, tok, cfg, cache)
        y = jnp.argmax(logits, -1).astype(jnp.int32)
        return (cache["k"], cache["v"], y), y

    (kc, vc, _), ys = jax.lax.scan(body, (cache_k, cache_v, ctx[:, 0]),
                                   jnp.arange(steps))
    ys = jnp.swapaxes(ys, 0, 1)                       # [S, steps]
    idx = jnp.clip(n_ctx[:, None] - 1 + jnp.arange(k)[None, :], 0,
                   steps - 1)
    drafts = jnp.take_along_axis(ys, idx, axis=1)
    return kc, vc, drafts


def loss_fn(params, tokens, labels, cfg: GPTConfig):
    """Mean next-token cross entropy.  labels [B, N] int32 (-100 = ignore)."""
    logits = forward(params, tokens, cfg)
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    ce = jnp.where(valid, lse - tgt, 0.0)
    return jnp.sum(ce) / jnp.maximum(jnp.sum(valid), 1)


# --------------------------------------------------------------------------
# eager Layer wrappers (dygraph API)
# --------------------------------------------------------------------------

class GPT(PytreeLayer):
    """Eager wrapper: holds the pytree leaves as Parameters so state_dict /
    optimizers / hapi work; forward routes the whole functional core through
    one tape node (dispatch.call records jax.vjp of the full model)."""

    def __init__(self, cfg: GPTConfig = None, **kwargs):
        super().__init__()
        self.cfg = cfg or GPTConfig(**kwargs)
        from ..framework import core
        self._adopt_tree(init_params(self.cfg, core.next_rng_key()))

    def forward(self, tokens):
        fn = functools.partial(
            lambda p, t: forward(p, t, self.cfg))
        return dispatch.call(fn, self._tree(), tokens, _name="gpt")

    def loss(self, tokens, labels):
        fn = lambda p, t, l: loss_fn(p, t, l, self.cfg)  # noqa: E731
        return dispatch.call(fn, self._tree(), tokens, labels,
                             _name="gpt_loss")


class GPTForPretraining(GPT):
    def forward(self, tokens, labels=None):
        if labels is None:
            return super().forward(tokens)
        return self.loss(tokens, labels)
