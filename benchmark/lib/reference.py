"""Plain references, independent of the code under test.

``gpt_logits`` is the GPT-3 forward pass as published (Brown et al. 2020,
after GPT-2): learned token and position embeddings, pre-LayerNorm
blocks of causal multi-head attention and a GELU MLP (tanh
approximation, as GPT-2's code has it), a final LayerNorm and the output
head tied to the token embedding.  Straight ``jax.numpy``: no kernel, no
cache, no batching tricks.  It reads the parameter tree the program
builds (``wte, wpe, blocks{ln1_*, qkv_*, proj_*, ln2_*, fc1_*, fc2_*},
lnf_*``, block leaves stacked on a leading layer axis) and takes nothing
else from the program.

``compute`` is the dtype the matmuls run in.  float32 under
``jax.default_matmul_precision("highest")`` is the reference proper (the
serving check).  bfloat16 mirrors where the program rounds (LayerNorm and
softmax in float32, matmul operands in bfloat16, logits in float32): the
training check uses it, because a float32 copy of the trained state does
not fit beside it on the chip.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np


def _layer_norm(x, g, b, eps):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    y = (xf - mu) / jnp.sqrt(var + eps)
    return (y * g.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def _block(x, blk, num_heads, eps, compute):
    b, n, h = x.shape
    hd = h // num_heads
    y = _layer_norm(x, blk["ln1_g"], blk["ln1_b"], eps)
    qkv = jnp.einsum("bnh,hcd->bncd", y, blk["qkv_w"].astype(compute))
    qkv = qkv + blk["qkv_b"].astype(compute)
    q, k, v = (qkv[:, :, i].reshape(b, n, num_heads, hd) for i in range(3))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((n, n), bool))
    scores = jnp.where(causal, scores.astype(jnp.float32), -jnp.inf)
    probs = jax.nn.softmax(scores, -1).astype(compute)
    a = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, n, h)
    x = x + a @ blk["proj_w"].astype(compute) + blk["proj_b"].astype(compute)
    y = _layer_norm(x, blk["ln2_g"], blk["ln2_b"], eps)
    y = jax.nn.gelu(y @ blk["fc1_w"].astype(compute)
                    + blk["fc1_b"].astype(compute), approximate=True)
    return x + y @ blk["fc2_w"].astype(compute) + blk["fc2_b"].astype(compute)


def gpt_logits(params, tokens, num_heads, eps=1e-5, compute=jnp.float32):
    """tokens [B, N] int32 -> logits [B, N, V] float32."""
    n = tokens.shape[-1]
    x = (params["wte"][tokens] + params["wpe"][:n]).astype(compute)

    def layer(x, blk):
        return _block(x, blk, num_heads, eps, compute), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = _layer_norm(x, params["lnf_g"], params["lnf_b"], eps)
    return (x @ params["wte"].astype(compute).T).astype(jnp.float32)


def gpt_loss(params, tokens, labels, num_heads, eps=1e-5,
             compute=jnp.float32):
    """Mean next-token cross entropy over every position."""
    logits = gpt_logits(params, tokens, num_heads, eps, compute)
    lse = jax.nn.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - tgt)


# --------------------------------------------------------------------------
# the comparisons that decide ``correct``
# --------------------------------------------------------------------------

def emitted_logit_gaps(params_f32, num_heads, eps, samples, width):
    """How far below the reference's best logit each EMITTED token sits,
    in units of the reference row's standard deviation.

    ``samples`` is ``[(history int32 array, [row index, ...],
    [emitted id, ...])]``: the reference is teacher-forced on the
    engine's own tokens (``history`` = prompt + generated), so a flipped
    argmax does not compound, and row ``r`` is the model's answer after
    ``history[:r + 1]``.  One padded ``width``, one compile: the model is
    causal, so padding behind a row cannot reach it.

    Logits, not tokens: with seeded random weights the largest logit
    changes on rounding, so the engine's token need not be the
    reference's argmax — but it must be a near-tie.  A paging, masking or
    position fault reads another token's K/V and lands several
    deviations down."""
    fwd = jax.jit(lambda p, t, idx: gpt_logits(
        p, t, num_heads, eps, jnp.float32)[0][idx])
    most = max((len(rows) for _, rows, _ in samples), default=0)
    gaps = []
    with jax.default_matmul_precision("highest"):
        for history, rows, emitted in samples:
            seq = np.zeros((width,), np.int32)
            seq[:len(history)] = history[:width]
            idx = list(rows) + [rows[-1]] * (most - len(rows))  # one shape
            ref = np.asarray(fwd(params_f32, seq[None],
                                 jnp.asarray(idx, jnp.int32)))
            for row, tok in zip(ref, emitted):
                gaps.append(float((row.max() - row[tok]) / row.std()))
    return gaps


def losses_learned(losses, k=5):
    """Finite everywhere, and the mean of the last ``k`` below the mean
    of the first ``k`` — a rule an optimizer that does not learn fails,
    on fresh batches every step (one step's loss against the next is
    noise; five against five is not)."""
    if len(losses) < 2 * k:
        k = max(1, len(losses) // 2)
    if not all(math.isfinite(x) for x in losses):
        return False
    return sum(losses[-k:]) / k < sum(losses[:k]) / k
