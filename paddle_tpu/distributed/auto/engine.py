"""The composed model-parallel train step: GSPMD tensor parallelism +
1F1B pipeline stages + ZeRO-sharded optimizer states, one compiled SPMD
program over a dp×pp×tp mesh.

This is the subsystem PAPER.md's layer map calls "fleet = GSPMD
shardings over jax.sharding.Mesh (ICI)": sharding RULES (rules.py) say
where every parameter lives, the Megatron block math is shared with
models/gpt_hybrid.py (column/row splits, vocab-parallel embedding and
cross entropy), the 1F1B microbatch scheduler (pipeline.py) drives the
'pp' axis, and the AdamW update runs SHARD-LOCAL over dp with
reduce-scattered grads (zero.py) — so a model whose replicated
params+moments cannot fit one device trains on the host mesh.

The whole step — forward, backward, per-axis grad reduction, global-norm
clip, sharded AdamW, param regather — is ONE buffer-donated jitted
shard_map program; XLA overlaps the collectives with compute.  The
builder derives a static per-step collective plan (one dp reduce-scatter
per leaf "bucket", the tp psums the block math issues per tick, the pp
ppermute handoffs per schedule) and the step wrapper publishes it into
the ``sharding.*`` registry family — the contract bench.py
--model-parallel asserts.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ...framework import compile_cache as _cc
from ...framework import jax_compat
from ...framework.jax_compat import shard_map, partition_spec as P
from ...optimizer.functional import adamw_update
from . import pipeline as pipe_mod
from . import rules as rules_mod
from . import zero as zero_mod
from .stats import _sharding_stats

MESH_AXES = ("dp", "pp", "tp", "sp")

# module-level: the donated MP step cache outlives any one
# make_train_step call (repeated builders with identical identity
# reuse one compiled program)
_mp_step_site = _cc.site("mp.train_step", maxsize=8)


def make_mesh(dp=1, tp=1, pp=1, devices=None):
    """The subsystem's mesh: axes ('dp', 'pp', 'tp', 'sp') with sp
    pinned to 1 (sequence parallelism rides models/gpt_hybrid.py's ring
    attention; the auto engine schedules dp/tp/pp).  Routed through
    framework/jax_compat.py per the standing constraint."""
    devices = list(devices if devices is not None else jax.devices())
    n = dp * tp * pp
    if len(devices) < n:
        raise ValueError(
            f"mesh dp={dp} tp={tp} pp={pp} needs {n} devices, "
            f"have {len(devices)}")
    arr = np.asarray(devices[:n]).reshape(dp, pp, tp, 1)
    return jax_compat.make_mesh(arr, MESH_AXES)


def mesh_axis_sizes(mesh):
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _gpt_shapes(cfg):
    from ...models import gpt
    return jax.eval_shape(lambda k: gpt.init_params(cfg, k),
                          jax.random.PRNGKey(0))


def _resolve_specs(cfg, mesh, family):
    specs = rules_mod.prune_to_mesh(rules_mod.rules_for(family, cfg), mesh)
    bad = rules_mod.validate(specs, _gpt_shapes(cfg), mesh)
    if bad:
        raise ValueError(f"sharding rules don't divide {family} shapes "
                         f"on this mesh: {bad}")
    return specs


# --------------------------------------------------------------------------
# static collective plan
# --------------------------------------------------------------------------

class CollectivePlan:
    """What the compiled FORWARD program issues per step, derived from
    the schedule and rules (reverse-mode AD roughly doubles the tp/pp
    counts at runtime; dp grad reductions appear exactly once).  This is
    bookkeeping the host publishes — nothing here is traced."""

    def __init__(self, cfg, mesh, sched, batch, seq):
        sizes = mesh_axis_sizes(mesh)
        dp, tp, pp = sizes["dp"], sizes["tp"], sizes["pp"]
        shapes = _gpt_shapes(cfg)
        leaves = jax.tree_util.tree_leaves(shapes)
        nbytes = [int(np.prod(l.shape)) * 4 for l in leaves]  # fp32 grads

        # dp: ONE reduce-scatter (stage>=2) / psum per param leaf — the
        # leaf IS the bucket on this substrate (grads are consumed by the
        # in-step sharded update, never re-bucketed host-side)
        self.dp_collectives = len(leaves) if dp > 1 else 0
        self.dp_bytes = sum(nbytes) if dp > 1 else 0

        # tp: 2 psums per block application + embed + 3 xent psums; with
        # a pipeline the stage body executes its layer range every tick
        # (bubble ticks included — SPMD programs don't skip)
        if tp > 1:
            layer_apps = (sched.n_ticks * (cfg.num_layers // pp)
                          if pp > 1 else cfg.num_layers)
            self.tp_collectives = 2 * layer_apps + 1 + 3
            act = (batch // max(dp, 1)) * seq * cfg.hidden_size * 4
            self.tp_bytes = 2 * layer_apps * act
        else:
            self.tp_collectives = 0
            self.tp_bytes = 0

        # pp: one ppermute handoff per tick + the output fan-out psum
        if pp > 1:
            self.pp_collectives = sched.handoffs() + 1
            mb_act = ((batch // max(dp, 1)) // sched.n_microbatch) \
                * seq * cfg.hidden_size * 4
            self.pp_bytes = sched.handoffs() * mb_act
        else:
            self.pp_collectives = 0
            self.pp_bytes = 0

        self.bubble_fraction = sched.bubble_fraction if pp > 1 else 0.0
        self.n_leaves = len(leaves)

    def publish(self):
        """Add one step's worth of the plan to the sharding.* family."""
        _sharding_stats.inc("steps")
        _sharding_stats.inc("collectives_dp", self.dp_collectives)
        _sharding_stats.inc("collectives_tp", self.tp_collectives)
        _sharding_stats.inc("collectives_pp", self.pp_collectives)
        _sharding_stats.inc("bytes_dp", self.dp_bytes)
        _sharding_stats.inc("bytes_tp", self.tp_bytes)
        _sharding_stats.inc("bytes_pp", self.pp_bytes)


# --------------------------------------------------------------------------
# state init
# --------------------------------------------------------------------------

def init_state(cfg, mesh, key, zero_stage=2, family="gpt",
               moment_dtype=jnp.float32):
    """(params, m, v) placed by the rules: params tp/pp-sharded per the
    registry, Adam moments additionally dp-sharded on their zero axis
    (``zero_stage>=1``).  Publishes the per-device byte gauges the bench
    asserts (``sharding.param_bytes_per_device`` /
    ``opt_state_bytes_per_device`` / ``opt_state_bytes_replicated``)."""
    from ...models import gpt
    specs = _resolve_specs(cfg, mesh, family)
    params = rules_mod.place(gpt.init_params(cfg, key), mesh, specs)
    if zero_stage >= 1:
        mspecs, _ = zero_mod.zero_specs(specs, params, mesh, record=False)
    else:
        mspecs = specs
    def fresh_zeros():
        # a NEW zeros tree per moment: placing one tree twice can
        # no-op device_put into ALIASED buffers (same array, same
        # sharding), and the donated step then donates one buffer twice
        return jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, moment_dtype), params)
    m = rules_mod.place(fresh_zeros(), mesh, mspecs)
    v = rules_mod.place(fresh_zeros(), mesh, mspecs)

    mdt = jnp.dtype(moment_dtype).itemsize
    replicated = sum(int(np.prod(l.shape)) * mdt * 2
                     for l in jax.tree_util.tree_leaves(params))
    _sharding_stats["param_bytes_per_device"] = \
        rules_mod.bytes_per_device(params)
    _sharding_stats["opt_state_bytes_per_device"] = (
        rules_mod.bytes_per_device(m) + rules_mod.bytes_per_device(v))
    _sharding_stats["opt_state_bytes_replicated"] = replicated
    return params, m, v


# --------------------------------------------------------------------------
# the composed train step
# --------------------------------------------------------------------------

def make_train_step(cfg, mesh, n_microbatch=1, zero_stage=2,
                    beta1=0.9, beta2=0.95, eps=1e-8, weight_decay=0.1,
                    clip_norm=1.0, xent_chunks=1, family="gpt"):
    """Jitted ``step(params, m, v, t, tokens, labels, lr) ->
    (params, m, v, loss)`` over the auto mesh.

    tokens/labels: GLOBAL [B, N] int32, batch sharded over dp; t: int32
    1-based step count; params/m/v from :func:`init_state` with the same
    ``zero_stage``.  ``zero_stage``: 0 replicated moments (the bench
    baseline), 1 moments dp-sharded with full grad psums, 2 moments
    dp-sharded with grad reduce-scatter (a fully cross-dp-reduced grad
    never materializes).  The returned callable carries ``.plan``
    (:class:`CollectivePlan`) and ``.schedule`` and publishes the plan
    into ``sharding.*`` per call."""
    from ...models import gpt_hybrid as H
    if family != "gpt":
        raise NotImplementedError(
            "the composed train step is gpt-family for now; bert/moe "
            "register layouts (rules.py) for the placement APIs")
    sp_size, pp_size = H._check_mesh(cfg, mesh)
    sizes = mesh_axis_sizes(mesh)
    specs = _resolve_specs(cfg, mesh, family)
    shapes = _gpt_shapes(cfg)
    if zero_stage >= 1:
        mspecs, zaxes = zero_mod.zero_specs(specs, shapes, mesh)
    else:
        mspecs = specs
        zaxes = jax.tree_util.tree_map(lambda _: -1, specs,
                                       is_leaf=rules_mod._is_spec)
    sched = pipe_mod.Schedule(n_microbatch, pp_size)
    pipe_fn = pipe_mod.pipeline_forward
    mesh_size = mesh.size

    def step(params, m, v, t, tokens, labels, lr):
        loss, grads = jax.value_and_grad(
            lambda p: H._fwd_loss(cfg, sp_size, pp_size, n_microbatch,
                                  p, tokens, labels,
                                  xent_chunks=xent_chunks,
                                  pipeline_fn=pipe_fn))(params)

        def red(spec, zax, g):
            # psum over the leaf's replicated axes EXCEPT dp, then the
            # dp reduction is the ZeRO scatter (or psum for -1 leaves);
            # total = sum over every copy, /mesh_size = the mean grad
            sharded = set(rules_mod.spec_axes(spec))
            axes = tuple(a for a in MESH_AXES
                         if a not in sharded and a != "dp")
            if axes:
                g = jax.lax.psum(g, axes)
            g = zero_mod.scatter_grad(g.astype(jnp.float32), zax,
                                      zero_stage)
            return g / mesh_size

        gshards = jax.tree_util.tree_map(red, specs, zaxes, grads,
                                         is_leaf=rules_mod._is_spec)

        if clip_norm:
            def sumsq(spec, zax, g):
                sq = jnp.sum(jnp.square(g))
                axes = tuple(rules_mod.spec_axes(spec))
                if zax >= 0:
                    axes = axes + ("dp",)
                return jax.lax.psum(sq, axes) if axes else sq
            sqs = jax.tree_util.tree_map(sumsq, specs, zaxes, gshards,
                                         is_leaf=rules_mod._is_spec)
            gn = jnp.sqrt(sum(jax.tree_util.tree_leaves(sqs)))
            scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gn, 1e-12))
            gshards = jax.tree_util.tree_map(lambda g: g * scale, gshards)

        tf = t.astype(jnp.float32)

        def upd(path, zax, p, g, mm, vv):
            leaf = str(getattr(path[-1], "key", path[-1]))
            decay = leaf not in H.NO_DECAY and leaf not in H.LN_NAMES
            psh = zero_mod.param_shard(p, zax)
            np_, nm_, nv_ = adamw_update(psh, g, mm, vv, lr, tf, beta1,
                                         beta2, eps, weight_decay, decay)
            return (zero_mod.gather_param_shard(np_, zax), nm_, nv_)

        out = jax.tree_util.tree_map_with_path(upd, zaxes, params,
                                               gshards, m, v)
        tup = lambda o: isinstance(o, tuple) and len(o) == 3  # noqa: E731
        new_p = jax.tree_util.tree_map(lambda o: o[0], out, is_leaf=tup)
        new_m = jax.tree_util.tree_map(lambda o: o[1], out, is_leaf=tup)
        new_v = jax.tree_util.tree_map(lambda o: o[2], out, is_leaf=tup)
        return new_p, new_m, new_v, loss

    sharded = shard_map(
        step, mesh=mesh,
        in_specs=(specs, mspecs, mspecs, P(), P("dp", "sp"),
                  P("dp", "sp"), P()),
        out_specs=(specs, mspecs, mspecs, P()),
        check_vma=False)
    # the donated MP step rides the unified compile layer: two
    # make_train_step calls with an identical (cfg, mesh, schedule,
    # hyper) identity share ONE jitted program instead of re-tracing —
    # the step is deterministic in exactly these inputs (params/moments
    # are operands).  No AOT stable_key: shard_map programs are bound to
    # the live mesh's device topology, which the artifact store cannot
    # attest across processes.
    import dataclasses as _dc
    # same rule as every other compile site: a cache directory named
    # from outside is honoured, nothing is started otherwise
    _cc.enable_persistent_cache()
    _mp_key = _cc.make_key(
        "mp_step",
        tuple(sorted((k, str(v))
                     for k, v in _dc.asdict(cfg).items())),
        tuple(mesh.axis_names), tuple(mesh.devices.shape),
        tuple(int(d.id) for d in mesh.devices.flat),
        n_microbatch, zero_stage, beta1, beta2, eps, weight_decay,
        clip_norm, xent_chunks, family,
        donate=(0, 1, 2))
    jitted = _mp_step_site.get(
        _mp_key, lambda: jax.jit(sharded, donate_argnums=(0, 1, 2)))

    # the host wrapper publishes the static plan per launch; batch/seq
    # for byte accounting are read from the first call's operands
    plan_box = [None]

    def step_fn(params, m, v, t, tokens, labels, lr):
        if plan_box[0] is None:
            plan_box[0] = CollectivePlan(cfg, mesh, sched,
                                         tokens.shape[0], tokens.shape[1])
            step_fn.plan = plan_box[0]
            _sharding_stats["bubble_fraction_pct"] = round(
                100.0 * plan_box[0].bubble_fraction, 2)
        out = jitted(params, m, v, jnp.int32(t), tokens, labels,
                     jnp.float32(lr))
        plan_box[0].publish()
        return out

    step_fn.plan = None
    step_fn.schedule = sched
    step_fn.zero_stage = zero_stage
    step_fn.mesh = mesh
    return step_fn


def make_forward(cfg, mesh, family="gpt"):
    """Sharded inference forward (params, tokens) -> full logits — the
    TP logit-parity surface.  Delegates to models/gpt_hybrid.py (same
    block math as the train step)."""
    from ...models import gpt_hybrid as H
    if family != "gpt":
        raise NotImplementedError("forward parity surface is gpt-family")
    return H.make_forward(cfg, mesh)
