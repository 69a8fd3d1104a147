"""Pipeline-stage serving steps for GPT (ISSUE 20 tentpole).

The tensor-parallel serving path (models/gpt.py + inference/serving.py,
ISSUE 15) keeps executables single-device jnp programs and lets GSPMD
partition them from operand shardings.  That recipe cannot express the
'pp' axis: stage parallelism is a SCHEDULE (microbatches hopping
stage-to-stage through collective-permute), not a layout annotation.
So the pp serving step is built the way the training engine builds its
pipelined step — ONE ``shard_map`` over the ('pp', 'tp') mesh running
the 1F1B tick loop from distributed/auto/pipeline.py, with the block
math written tp-explicitly (models/gpt_hybrid.py::_sharded_block's
psum-after-row-matmul recipe) and the paged KV pools threaded through
the tick loop as stage-local carry (each stage pages only its own
layers' K/V — :data:`models.gpt.KV_POOL_SPEC_PP`).

Numerics: per-head attention and per-column matmul math is identical
to the single-device paged step; the two row-parallel matmuls per
block accumulate partial sums via psum('tp') exactly like the GSPMD
tp engine's partitioned executables, so greedy decoding stays
token-exact with the fp32 single-device reference (the serving parity
contract — asserted per request by bench.py's pp phase).

Composition gates (quant / int8 KV / chunked prefill / MoE x pp) are
enforced by the engine constructor, so every function here may assume
full-precision dense weights and whole-prompt prefill waves.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..framework import jax_compat
from ..framework.jax_compat import partition_spec as P
from ..distributed.auto.pipeline import StageAssignment, pipeline_stage_loop
from .gpt import KV_POOL_SPEC_PP, _layer_norm, _layer_scan


def check_pp_config(cfg, pp):
    """The pp step is hand-written block math — the fused/kernel paths
    (flash attention, fused FFN, Pallas norms) and the MoE FFN are not
    wired through it; a silent fallback would change numerics, so
    refuse up front, by name."""
    for knob in ("use_flash", "use_fused_ffn", "use_pallas_norm"):
        if getattr(cfg, knob, False):
            raise ValueError(
                f"pp > 1 serving runs the explicit-collective block "
                f"math, which has no {knob} path — drop {knob} or pp=")
    if getattr(cfg, "moe_experts", 0):
        raise ValueError(
            "pp > 1 does not compose with moe_experts yet — MoE serving "
            "is the expert-parallel GSPMD path (tp mesh); drop pp=")
    # stage ranges must tile the stack evenly (1F1B contract)
    StageAssignment(cfg.num_layers, pp)


def _vp_embed(wte_l, wpe, tokens, pos, cd):
    """Vocab-parallel embedding lookup: each tp rank owns a contiguous
    row range of wte; off-owner lookups contribute exact zeros, so the
    psum('tp') is bit-identical to the unsharded take (one owner per
    id).  ``tokens``/``pos`` may be [S] (decode) or [b, s]/[s]
    (prefill)."""
    tp_idx = jax.lax.axis_index("tp")
    v_local = wte_l.shape[0]
    ids = tokens - tp_idx * v_local
    ok = (ids >= 0) & (ids < v_local)
    x = jnp.take(wte_l, jnp.clip(ids, 0, v_local - 1), axis=0)
    x = jnp.where(ok[..., None], x, 0)
    x = jax.lax.psum(x, "tp")
    return (x + jnp.take(wpe, pos, axis=0)).astype(cd)


def _vp_head(h, wte_l):
    """Tied vocab-parallel LM head: local [..., V/tp] logit shard, then
    tiled all_gather over 'tp' (axis order == vocab shard order, so the
    concat reassembles the exact unsharded column layout)."""
    loc = h @ wte_l.astype(h.dtype).T
    return jax_compat.all_gather(
        loc, "tp", axis=loc.ndim - 1, tiled=True).astype(jnp.float32)


def _pp_paged_block(cfg, x, blk, layer, kp, vp, page_table, write_pages,
                    write_offs, lens):
    """models/gpt.py::_paged_slot_block with the tp collectives made
    explicit: local-head attention over the stage-local page pool,
    psum('tp') closing the row-parallel proj and fc2 matmuls (the
    Megatron two-allreduces-per-block recipe, gpt_hybrid._sharded_block).
    x: [S, 1, H]; kp/vp: this stage's whole [L/pp, P, ps, nh/tp * hd]
    pool shard, written and read at the stage-local ``layer``."""
    from ..ops.pallas.paged_attn import paged_attention
    cd = jnp.dtype(cfg.dtype)
    hd = cfg.head_dim
    S, T, H = x.shape

    h = _layer_norm(x, blk["ln1_g"], blk["ln1_b"], cfg.layer_norm_eps)
    qkv = jnp.einsum("bnh,hcd->bncd", h, blk["qkv_w"].astype(cd)) \
        + blk["qkv_b"].astype(cd)
    nh_loc = qkv.shape[-1] // hd
    q, k, v = [qkv[:, :, i].reshape(S, T, nh_loc, hd) for i in range(3)]
    at = (layer, write_pages, write_offs)
    kc = kp.at[at].set(k[:, 0].reshape(S, -1).astype(kp.dtype))
    vc = vp.at[at].set(v[:, 0].reshape(S, -1).astype(vp.dtype))
    a = paged_attention(q, (kc, vc), page_table, lens, layer)
    a = a.reshape(S, T, -1)
    a = jax.lax.psum(a @ blk["proj_w"].astype(cd), "tp") \
        + blk["proj_b"].astype(cd)
    x = x + a

    h = _layer_norm(x, blk["ln2_g"], blk["ln2_b"], cfg.layer_norm_eps)
    h = jax.nn.gelu(h @ blk["fc1_w"].astype(cd) + blk["fc1_b"].astype(cd),
                    approximate=True)
    h = jax.lax.psum(h @ blk["fc2_w"].astype(cd), "tp") \
        + blk["fc2_b"].astype(cd)
    x = x + h
    return x, kc, vc


def _pp_prefill_block(cfg, x, blk, pool_dtype):
    """models/gpt.py::_cached_block at cur_len=0 over a fresh width-s
    cache (the wave-prefill case: the written cache IS this chunk's
    K/V), with the same explicit tp collectives as the decode block.
    Returns (x_out, kc [b, s, nh/tp, hd], vc) in the pool dtype."""
    cd = jnp.dtype(cfg.dtype)
    hd = cfg.head_dim
    b, s, H = x.shape

    h = _layer_norm(x, blk["ln1_g"], blk["ln1_b"], cfg.layer_norm_eps)
    qkv = jnp.einsum("bnh,hcd->bncd", h, blk["qkv_w"].astype(cd)) \
        + blk["qkv_b"].astype(cd)
    nh_loc = qkv.shape[-1] // hd
    q, k, v = [qkv[:, :, i].reshape(b, s, nh_loc, hd) for i in range(3)]
    kc = k.astype(pool_dtype)
    vc = v.astype(pool_dtype)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        kc.astype(jnp.float32)) / math.sqrt(hd)
    q_pos = jnp.arange(s)[:, None]
    k_pos = jnp.arange(s)[None, :]
    logits = jnp.where((k_pos <= q_pos)[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, -1).astype(cd)
    a = jnp.einsum("bhqk,bkhd->bqhd", probs, vc.astype(cd))
    a = a.reshape(b, s, -1)
    a = jax.lax.psum(a @ blk["proj_w"].astype(cd), "tp") \
        + blk["proj_b"].astype(cd)
    x = x + a

    h = _layer_norm(x, blk["ln2_g"], blk["ln2_b"], cfg.layer_norm_eps)
    h = jax.nn.gelu(h @ blk["fc1_w"].astype(cd) + blk["fc1_b"].astype(cd),
                    approximate=True)
    h = jax.lax.psum(h @ blk["fc2_w"].astype(cd), "tp") \
        + blk["fc2_b"].astype(cd)
    x = x + h
    return x, kc, vc


def make_decode_step(cfg, mesh, param_specs, n_microbatch):
    """The pp x tp paged decode step: ``fn(params, toks, ck, cv,
    page_table, wpages, woffs, lens) -> (logits [S, V] fp32, ck, cv)``
    — same contract as models/gpt.py::decode_paged, but the body
    is one shard_map over ``mesh`` running the 1F1B tick loop: slots
    split into ``n_microbatch`` groups, each group's activation hops
    the stage ring via ppermute while every stage appends the group's
    K/V into ITS OWN layer range of the pool (the stage-local carry of
    pipeline_stage_loop).  Bubble ticks aim their writes at the scratch
    page and zero lens, so the schedule's fill/drain never touches a
    real page."""
    check_pp_config(cfg, mesh.devices.shape[0])
    cd = jnp.dtype(cfg.dtype)
    kvp = P(*KV_POOL_SPEC_PP)
    rep = P()

    def body(params, toks, ck, cv, page_table, wpages, woffs, lens):
        S = toks.shape[0]
        M = n_microbatch
        mb = S // M
        blocks = params["blocks"]
        x0 = _vp_embed(params["wte"], params["wpe"], toks, lens, cd)
        micro = x0.reshape(M, mb, 1, -1)
        pt_r = page_table.reshape(M, mb, -1)
        wp_r = wpages.reshape(M, mb)
        wo_r = woffs.reshape(M, mb)
        ln_r = lens.reshape(M, mb)

        def stage_fn(x, carry, m, valid):
            ptm = jnp.where(valid, pt_r[m], 0)
            wpm = jnp.where(valid, wp_r[m], 0)
            wom = jnp.where(valid, wo_r[m], 0)
            lnm = jnp.where(valid, ln_r[m], 0)

            def body(cx, blk, layer, pools):
                xx, *pools = _pp_paged_block(
                    cfg, cx, blk, layer, *pools, ptm, wpm, wom, lnm)
                return xx, tuple(pools), None

            x, carry, _ = _layer_scan(body, x, blocks, carry)
            return x, carry

        outputs, (ck, cv) = pipeline_stage_loop(stage_fn, micro, (ck, cv))
        h = outputs.reshape(S, 1, -1)
        h = _layer_norm(h, params["lnf_g"], params["lnf_b"],
                        cfg.layer_norm_eps)
        logits = _vp_head(h[:, 0], params["wte"])
        return logits, ck, cv

    def step(params, toks, ck, cv, page_table, wpages, woffs, lens):
        return jax_compat.shard_map(
            body, mesh=mesh,
            in_specs=(param_specs, rep, kvp, kvp, rep, rep, rep, rep),
            out_specs=(rep, kvp, kvp),
            check_vma=False,
        )(params, toks, ck, cv, page_table, wpages, woffs, lens)

    return step


def make_prefill_step(cfg, mesh, param_specs, b, s, page_size):
    """The pp x tp paged prefill wave for one (batch, seq) bucket:
    ``fn(params, ck, cv, tokens [b,s], lens [b], ptab [b, s/ps]) ->
    (ck, cv, first_tok [b], last [b, V] fp32)``.  One microbatch
    through the same 1F1B machinery (ticks == stages — the sequential
    fill; the ppermute handoff and bubble masking are identical to
    decode's), each stage scattering its layers' K/V pages through the
    (bubble-masked) flat page table."""
    check_pp_config(cfg, mesh.devices.shape[0])
    if s % page_size:
        raise ValueError(f"prefill bucket {s} must divide by page_size "
                         f"{page_size}")
    pr = s // page_size
    cd = jnp.dtype(cfg.dtype)
    kvp = P(*KV_POOL_SPEC_PP)
    rep = P()

    def body(params, ck, cv, tokens, lens, ptab):
        blocks = params["blocks"]
        x0 = _vp_embed(params["wte"], params["wpe"], tokens,
                       jnp.arange(s), cd)
        micro = x0[None]                       # [1, b, s, H]
        flat = ptab.reshape(-1)                # [b*pr]

        def stage_fn(x, carry, m, valid):
            fl = jnp.where(valid, flat, 0)     # bubble -> scratch page

            def body(cx, blk, layer, pools):
                kp, vp = pools
                xx, kc, vc = _pp_prefill_block(cfg, cx, blk, kp.dtype)
                kp = kp.at[layer, fl].set(
                    kc.reshape(b * pr, page_size, -1))
                vp = vp.at[layer, fl].set(
                    vc.reshape(b * pr, page_size, -1))
                return xx, (kp, vp), None

            x, carry, _ = _layer_scan(body, x, blocks, carry)
            return x, carry

        outputs, (ck, cv) = pipeline_stage_loop(stage_fn, micro, (ck, cv))
        h = _layer_norm(outputs[0], params["lnf_g"], params["lnf_b"],
                        cfg.layer_norm_eps)
        idx = jnp.clip(lens - 1, 0, s - 1)
        h_last = jnp.take_along_axis(h, idx[:, None, None], axis=1)[:, 0]
        last = _vp_head(h_last, params["wte"])
        first_tok = jnp.argmax(last, -1).astype(jnp.int32)
        return ck, cv, first_tok, last

    def prefill(params, ck, cv, tokens, lens, ptab):
        return jax_compat.shard_map(
            body, mesh=mesh,
            in_specs=(param_specs, kvp, kvp, rep, rep, rep),
            out_specs=(kvp, kvp, rep, rep),
            check_vma=False,
        )(params, ck, cv, tokens, lens, ptab)

    return prefill
