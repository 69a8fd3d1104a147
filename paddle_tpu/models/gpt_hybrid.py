"""Hybrid-parallel GPT train step: dp × pp × tp × sp over one jax Mesh.

TPU-native replacement for the reference's fleet hybrid-parallel stack
(ref: python/paddle/distributed/fleet/meta_parallel/{tensor_parallel.py,
pipeline_parallel.py}, meta_optimizers/sharding_optimizer.py, and the
c_allreduce/c_identity ops in paddle/fluid/operators/collective/).  The
reference rewrites the program graph to insert NCCL ops; here the whole train
step is ONE SPMD program inside ``shard_map`` over mesh axes
('dp','pp','tp','sp'), and every collective is an explicit XLA op on ICI:

  * tp — Megatron layout: qkv/fc1 column-sharded, proj/fc2 row-sharded,
    activations made whole again by ``psum('tp')`` (2 allreduces/block);
    vocab-parallel embedding + cross entropy (masked local lookup + psum).
  * pp — GPipe microbatch pipeline (parallel/pipeline.py): layer-stacked
    block params sharded on the leading axis, activations hop stages via
    ``ppermute``; reverse-mode AD through the loop yields the backward
    pipeline automatically.
  * sp — ring attention (parallel/ring_attention.py): sequence sharded,
    K/V blocks rotate the 'sp' ring, online-softmax merge.
  * dp — batch sharded; gradient ``psum('dp')`` is the allreduce.

Gradients are synced spec-aware (block grads live on their pipeline stage;
embedding/head grads psum over pp because stage-gating zeroes them
elsewhere), clipped by true global norm, and updated by a fused AdamW — all
inside the same compiled step so XLA overlaps collectives with compute.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from ..framework.jax_compat import shard_map
from ..framework.jax_compat import (enable_persistent_cache, named_sharding,
                                    partition_spec_class)

P = partition_spec_class()

from .gpt import GPTConfig, init_params, _layer_norm
from ..optimizer.functional import adamw_update
from ..parallel.pipeline import pipeline_forward
from ..parallel.ring_attention import ring_attention
from ..ops.pallas.flash_attn import flash_attention

MESH_AXES = ("dp", "pp", "tp", "sp")

# AdamW decay exclusions for the gpt param tree — the ONE definition
# every train-step builder (this module, distributed/auto/engine.py,
# bench.py's reference loop, tests) imports; a leaf added to
# init_params gets its decay policy decided here, nowhere else
NO_DECAY = frozenset({"wpe", "lnf_g", "lnf_b"})
LN_NAMES = frozenset({"ln1_g", "ln1_b", "ln2_g", "ln2_b",
                      "proj_b", "qkv_b", "fc1_b", "fc2_b",
                      "moe_b1", "moe_b2"})


# --------------------------------------------------------------------------
# sharding specs
# --------------------------------------------------------------------------

def param_specs(cfg: GPTConfig):
    """PartitionSpec pytree matching init_params' structure."""
    blocks = {
        "ln1_g": P("pp"), "ln1_b": P("pp"),
        "qkv_w": P("pp", None, None, "tp"),
        "qkv_b": P("pp", None, "tp"),
        "proj_w": P("pp", "tp"),
        "proj_b": P("pp"),
        "ln2_g": P("pp"), "ln2_b": P("pp"),
    }
    if getattr(cfg, "moe_experts", 0):
        # expert-parallel: the [E] axis (after [L]) shards over 'tp' —
        # each rank holds E/tp whole expert MLPs; the gate is tiny and
        # replicated (parallel/moe.py's layout, stacked on [L])
        blocks.update({
            "moe_gate_w": P("pp"),
            "moe_w1": P("pp", "tp"),
            "moe_b1": P("pp", "tp"),
            "moe_w2": P("pp", "tp"),
            "moe_b2": P("pp", "tp"),
        })
    else:
        blocks.update({
            "fc1_w": P("pp", None, "tp"),
            "fc1_b": P("pp", "tp"),
            "fc2_w": P("pp", "tp"),
            "fc2_b": P("pp"),
        })
    return {
        "wte": P("tp"),                      # vocab-sharded
        "wpe": P(),
        "blocks": blocks,
        "lnf_g": P(), "lnf_b": P(),
    }


def init_sharded(cfg: GPTConfig, mesh, key, moment_dtype=jnp.float32):
    """Init params + AdamW moments, placed with their NamedShardings.
    ``moment_dtype=bfloat16`` halves optimizer-state HBM (the update math
    still runs fp32 — see optimizer/functional.adamw_update), which is what
    lets the 1.3B flagship train on a single 16GB v5e chip.

    The init runs as ONE jitted program whose outputs carry the
    shardings, so every device materialises only its own shards: an
    eager ``init_params`` followed by ``device_put`` would first build
    every full array (and the fp32 draw behind each bf16 leaf) on device
    0.  The values do not depend on the mesh (jax's threefry is
    partitionable), which is what lets a 2x2 run be compared with a
    one-chip run from the same key."""
    shardings = jax.tree_util.tree_map(
        lambda s: named_sharding(mesh, s), param_specs(cfg),
        is_leaf=lambda s: isinstance(s, P))

    def init(k):
        params = init_params(cfg, k)
        zeros = functools.partial(
            jax.tree_util.tree_map,
            lambda p: jnp.zeros(p.shape, moment_dtype))
        # two zero trees, never one aliased: the train step donates
        # m and v separately
        return params, zeros(params), zeros(params)

    return jax.jit(init, out_shardings=(shardings,) * 3)(key)


# --------------------------------------------------------------------------
# sharded forward (runs INSIDE shard_map; all shapes are per-device locals)
# --------------------------------------------------------------------------

def _vp_embed(cfg, params, tokens):
    """Vocab-parallel embedding: masked local lookup + psum('tp').
    tokens: [B_l, N_l] local shard (batch over dp, sequence over sp)."""
    wte = params["wte"]                      # [V/tp, H]
    v_local = wte.shape[0]
    tp_idx = jax.lax.axis_index("tp")
    ids = tokens - tp_idx * v_local
    ok = (ids >= 0) & (ids < v_local)
    e = jnp.take(wte, jnp.clip(ids, 0, v_local - 1), axis=0)
    e = jnp.where(ok[..., None], e, 0.0)
    e = jax.lax.psum(e, "tp")
    n_l = tokens.shape[-1]
    pos = jax.lax.axis_index("sp") * n_l + jnp.arange(n_l)
    return (e + jnp.take(params["wpe"], pos, axis=0)).astype(cfg.dtype)


def _attn_local(cfg, q, k, v, sp_size):
    """q,k,v: [mb, N_l, nh_local, hd].  sp==1 -> Pallas flash; sp>1 -> ring
    attention over the 'sp' axis (K/V rotate, online-softmax merge)."""
    if sp_size == 1:
        if cfg.use_flash:
            return flash_attention(q, k, v, True)
        from .gpt import _attention
        return _attention(q, k, v, cfg)
    qt, kt, vt = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))
    out = ring_attention(qt, kt, vt, axis_name="sp", causal=True)
    return jnp.swapaxes(out, 1, 2)


def _sharded_block(cfg, sp_size, x, blk):
    """Megatron-sharded transformer block.  x: [mb, N_l, H] (whole hidden,
    tp-replicated); blk leaves are this device's tp/pp shards."""
    cd = jnp.dtype(cfg.dtype)
    mb, n_l, H = x.shape
    hd = cfg.head_dim

    h = _layer_norm(x, blk["ln1_g"], blk["ln1_b"], cfg.layer_norm_eps)
    qkv = jnp.einsum("bnh,hcd->bncd", h, blk["qkv_w"].astype(cd))
    qkv = qkv + blk["qkv_b"].astype(cd)      # [mb, N_l, 3, H/tp]
    nh_local = qkv.shape[-1] // hd
    q, k, v = [qkv[:, :, i].reshape(mb, n_l, nh_local, hd) for i in range(3)]
    a = _attn_local(cfg, q, k, v, sp_size).reshape(mb, n_l, -1)
    a = a @ blk["proj_w"].astype(cd)         # row-parallel: partial sums
    a = jax.lax.psum(a, "tp") + blk["proj_b"].astype(cd)
    x = x + a

    h = _layer_norm(x, blk["ln2_g"], blk["ln2_b"], cfg.layer_norm_eps)
    h = jax.nn.gelu(h @ blk["fc1_w"].astype(cd) + blk["fc1_b"].astype(cd),
                    approximate=True)        # [mb, N_l, F/tp]
    h = h @ blk["fc2_w"].astype(cd)
    h = jax.lax.psum(h, "tp") + blk["fc2_b"].astype(cd)
    return x + h


def _vp_xent(logits, labels):
    """Vocab-parallel cross entropy (fp32).  logits: [B_l, N_l, V/tp]."""
    v_local = logits.shape[-1]
    tp_idx = jax.lax.axis_index("tp")
    # stability shift only — constant w.r.t. autodiff (pmax has no vjp rule,
    # and d(ce)/d(logits) is exact with m held constant)
    m = jax.lax.stop_gradient(
        jax.lax.pmax(jax.lax.stop_gradient(jnp.max(logits, -1)), "tp"))
    z = jax.lax.psum(jnp.sum(jnp.exp(logits - m[..., None]), -1), "tp")
    ids = labels - tp_idx * v_local
    ok = (ids >= 0) & (ids < v_local)
    tgt = jnp.take_along_axis(
        logits, jnp.clip(ids, 0, v_local - 1)[..., None], axis=-1)[..., 0]
    tgt = jax.lax.psum(jnp.where(ok, tgt, 0.0), "tp")
    return jnp.log(z) + m - tgt


def _check_mesh(cfg, mesh):
    """Validate axis presence + divisibility; returns (sp_size, pp_size)."""
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for name in MESH_AXES:
        if name not in axes:
            raise ValueError(f"mesh must have axis '{name}'")
    if cfg.num_layers % axes["pp"]:
        raise ValueError("num_layers must divide by pp")
    if cfg.num_heads % axes["tp"]:
        raise ValueError("num_heads must divide by tp")
    if cfg.vocab_size % axes["tp"]:
        raise ValueError("vocab_size must divide by tp")
    return axes["sp"], axes["pp"]


def _backbone(cfg, sp_size, pp_size, n_microbatch, params, x,
              pipeline_fn=None):
    """Embed-to-final-hidden shared by train and inference forwards: scan
    this stage's blocks, pipelined over 'pp' when the axis is sized.
    ``pipeline_fn(stage_fn, x, n_microbatch, axis_name)`` swaps the
    microbatch scheduler (default: the GPipe loop in parallel/pipeline.py;
    distributed/auto passes its 1F1B scheduler)."""
    blk_fn = functools.partial(_sharded_block, cfg, sp_size)
    if cfg.remat:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat_policy == "dots" else None)
        blk_fn = jax.checkpoint(blk_fn, policy=policy)

    def stage_fn(xx):
        def body(c, blk):
            return blk_fn(c, blk), None
        out, _ = jax.lax.scan(body, xx, params["blocks"])
        return out

    if pp_size > 1:
        pipe = pipeline_fn if pipeline_fn is not None else pipeline_forward
        x = pipe(stage_fn, x, n_microbatch, axis_name="pp")
    else:
        x = stage_fn(x)
    return _layer_norm(x, params["lnf_g"], params["lnf_b"],
                       cfg.layer_norm_eps)


def _fwd_loss(cfg, sp_size, pp_size, n_microbatch, params, tokens, labels,
              xent_chunks=1, pipeline_fn=None):
    x = _vp_embed(cfg, params, tokens)       # [B_l, N_l, H]
    x = _backbone(cfg, sp_size, pp_size, n_microbatch, params, x,
                  pipeline_fn=pipeline_fn)
    wte = params["wte"]

    def ce_of(xc, lc):
        logits = (xc @ wte.astype(xc.dtype).T).astype(jnp.float32)
        ce = _vp_xent(logits, lc)
        valid = (lc >= 0).astype(jnp.float32)
        return jnp.sum(ce * valid), jnp.sum(valid)

    if xent_chunks > 1:
        # the [B_l, N_l, V/tp] fp32 logits are the activation-memory hog at
        # 1.3B scale (~400MB/sample-K); scanning sequence chunks under
        # jax.checkpoint keeps only one chunk's logits live in fwd AND bwd
        # at ~2% extra FLOPs (vocab-matmul recompute)
        B_l, N_l = labels.shape
        assert N_l % xent_chunks == 0, (N_l, xent_chunks)
        C = N_l // xent_chunks
        xr = x.reshape(B_l, xent_chunks, C, x.shape[-1]).swapaxes(0, 1)
        lr = labels.reshape(B_l, xent_chunks, C).swapaxes(0, 1)

        def body(carry, xl):
            xc, lc = xl
            t, c = jax.checkpoint(ce_of)(xc, lc)
            return (carry[0] + t, carry[1] + c), None

        (total, count), _ = jax.lax.scan(
            body, (jnp.float32(0), jnp.float32(0)), (xr, lr))
    else:
        total, count = ce_of(x, labels)
    # every pp rank holds the broadcast outputs and contributes an identical
    # term; psum-ing both numerator and count over pp keeps the mean AND the
    # backward weights exact (the broadcast-ppermute transpose sums them).
    total = jax.lax.psum(total, ("dp", "sp", "pp"))
    count = jax.lax.psum(count, ("dp", "sp", "pp"))
    return total / jnp.maximum(count, 1.0)


# --------------------------------------------------------------------------
# gradient sync / clip / fused AdamW
# --------------------------------------------------------------------------

def _spec_axes(spec):
    return tuple(a for part in spec if part is not None
                 for a in ((part,) if isinstance(part, str) else part))


def _sync_grads(grads, specs, mesh_size):
    """Cross-replica grad reduction.

    Because the loss is made replicated by collectives (psum over dp/sp/pp,
    tp-internal psums), reverse-mode AD inside shard_map — where
    transpose(psum) = psum — yields per-rank grads of the SUM of every
    rank's (identical) loss: each copy's grad carries a factor of
    ``mesh_size``.  The true gradient of one leaf is the sum of the partials
    over all of its copies, i.e. a psum over the leaf's REPLICATED axes
    (complement of its PartitionSpec), divided by ``mesh_size``."""
    def red(g, spec):
        sharded = set(_spec_axes(spec))
        axes = tuple(a for a in MESH_AXES if a not in sharded)
        if axes:
            g = jax.lax.psum(g, axes)
        return g / mesh_size
    return jax.tree_util.tree_map(red, grads, specs)


def _global_norm(grads, specs):
    """True global grad norm: each leaf's local sumsq is psum'ed only over
    mesh axes its spec shards (replicated axes would double count)."""
    total = 0.0
    leaves = jax.tree_util.tree_leaves_with_path(grads)
    spec_leaves = dict(jax.tree_util.tree_leaves_with_path(
        specs, is_leaf=lambda x: isinstance(x, P)))
    for path, g in leaves:
        spec = spec_leaves[path]
        sq = jnp.sum(jnp.square(g.astype(jnp.float32)))
        axes = _spec_axes(spec)
        # axes come from the static PartitionSpec pytree (only the
        # tree-path indexing confuses taint), never from the tracer
        if axes:  # ptl: disable=PTL002 -- static PartitionSpec axes
            sq = jax.lax.psum(sq, axes)
        total = total + sq
    return jnp.sqrt(total)


def make_train_step(cfg: GPTConfig, mesh, n_microbatch=1,
                    beta1=0.9, beta2=0.95, eps=1e-8, weight_decay=0.1,
                    clip_norm=1.0, xent_chunks=1):
    """Returns jitted ``step(params, m, v, t, tokens, labels, lr) ->
    (params, m, v, loss)``.  tokens/labels: GLOBAL [B, N] int32, batch
    sharded over dp, sequence over sp; t: int32 step count (1-based).
    ``xent_chunks>1`` chunk-scans the vocab projection + cross entropy
    (rematerialized) to cap logits activation memory."""
    sp_size, pp_size = _check_mesh(cfg, mesh)
    specs = param_specs(cfg)
    # a restarted trainer re-loads the step it compiled last time (no-op
    # unless a cache directory is named from outside — the one rule)
    enable_persistent_cache()

    def fwd_loss(p, tokens, labels):
        # metadata only: forward ops read jvp(forward)/..., and autodiff
        # names their transposes transpose(jvp(forward))/... — that IS
        # the backward (and the remat's recomputed forward inside it)
        with jax.named_scope("forward"):
            return _fwd_loss(cfg, sp_size, pp_size, n_microbatch,
                             p, tokens, labels, xent_chunks=xent_chunks)

    def step(params, m, v, t, tokens, labels, lr):
        loss, grads = jax.value_and_grad(
            lambda p: fwd_loss(p, tokens, labels))(params)
        with jax.named_scope("grad_sync_clip"):
            grads = _sync_grads(grads, specs, mesh.size)
            if clip_norm:
                gn = _global_norm(grads, specs)
                scale = jnp.minimum(1.0,
                                    clip_norm / jnp.maximum(gn, 1e-12))
                grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
        tf = t.astype(jnp.float32)

        def upd(path, p, g, mm, vv):
            leaf = str(getattr(path[-1], "key", path[-1]))
            decay = leaf not in NO_DECAY and leaf not in LN_NAMES
            return adamw_update(p, g, mm, vv, lr, tf, beta1, beta2, eps,
                                weight_decay, decay)
        with jax.named_scope("adamw"):
            out = jax.tree_util.tree_map_with_path(upd, params, grads, m, v)
        new_p = jax.tree_util.tree_map(lambda o: o[0], out,
                                       is_leaf=lambda o: isinstance(o, tuple))
        new_m = jax.tree_util.tree_map(lambda o: o[1], out,
                                       is_leaf=lambda o: isinstance(o, tuple))
        new_v = jax.tree_util.tree_map(lambda o: o[2], out,
                                       is_leaf=lambda o: isinstance(o, tuple))
        return new_p, new_m, new_v, loss

    sharded = shard_map(
        step, mesh=mesh,
        in_specs=(specs, specs, specs, P(), P("dp", "sp"), P("dp", "sp"),
                  P()),
        out_specs=(specs, specs, specs, P()),
        check_vma=False)
    return jax.jit(sharded, donate_argnums=(0, 1, 2))


def make_forward(cfg: GPTConfig, mesh):
    """Jitted sharded inference forward: (params, tokens) -> local-loss-free
    logits gathered full.  Pipeline + tp sharded; logits psum-gathered."""
    sp_size, pp_size = _check_mesh(cfg, mesh)
    specs = param_specs(cfg)

    def fwd(params, tokens):
        x = _vp_embed(cfg, params, tokens)
        x = _backbone(cfg, sp_size, pp_size, 1, params, x)
        logits = (x @ params["wte"].astype(x.dtype).T).astype(jnp.float32)
        # gather the tp-sharded vocab dim: [B_l, N_l, V/tp] -> [B_l, N_l, V]
        return jax.lax.all_gather(logits, "tp", axis=2, tiled=True)

    sharded = shard_map(
        fwd, mesh=mesh,
        in_specs=(specs, P("dp", "sp")),
        out_specs=P("dp", "sp"),
        check_vma=False)
    return jax.jit(sharded)
