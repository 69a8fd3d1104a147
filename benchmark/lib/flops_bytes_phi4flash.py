"""Operations and bytes the ALGORITHM needs for the ``phi4flash`` family
(state-space layers, window layers, one full-attention layer whose K/V
the later attention layers read, gated memory units), computed from the
configuration's published keys and its ``assumed`` state-space sizes.

What is counted, so a share of a peak can be argued with:

* a decode step streams every layer's weights once and the tied
  embedding once (as the head; the lookup of a few rows counts nothing);
* the ONE pooled layer's K and V: every live position is read once by
  the full layer and once by each cross layer (``1 + cross layers``
  kernel calls read the same rows), and the new position is written
  once;
* a window layer's ring: the live rows of every active slot (at most
  the window) read once, one row written;
* a state-space layer's ``S`` read and written for every active slot
  (float32), and its convolution tail;
* the logits of the active slots written (float32): at a 200K
  vocabulary and some hundred slots they are no rounding error;
* one call of the decode kernel: the K and V rows its slots' lengths
  reach, the queries in and the pairs' outputs (float32) back, against
  two score products and two value products of twice the width a pair.
"""


def layer_kinds(cfg):
    """The kind of every layer, as the model derives them."""
    half = cfg["num_hidden_layers"] // 2
    kinds = []
    for i in range(cfg["num_hidden_layers"]):
        if i % cfg["mb_per_layer"] == 0:
            kinds.append("ssm" if i < half + 2 else "gmu")
        elif i < half and cfg["sliding_window"]:
            kinds.append("window")
        else:
            kinds.append("full" if i < half + 2 else "cross")
    return kinds


def count_of(cfg, kind):
    return layer_kinds(cfg).count(kind)


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def kv_width(cfg):
    return cfg["num_key_value_heads"] * head_dim(cfg)


def d_inner(cfg):
    return cfg["mamba_expand"] * cfg["hidden_size"]


def mlp_params(cfg):
    """W1 (gate and up) and W2, no bias, with the sublayer's LayerNorm."""
    h = cfg["hidden_size"]
    return 3 * h * cfg["intermediate_size"] + 2 * h


def ssm_params(cfg):
    h, di = cfg["hidden_size"], d_inner(cfg)
    n, r = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    return (h * 2 * di + di * h                 # in, out
            + di * (r + 2 * n) + r * di + di    # x and dt projections, b_dt
            + cfg["mamba_d_conv"] * di + di     # convolution and its bias
            + di * n + di                       # A_log, D
            + 2 * h)                            # LayerNorm


def lambda_params(cfg):
    """Four lambda vectors and the sub-layer norm's gain."""
    return 4 * head_dim(cfg) + 2 * head_dim(cfg)


def attn_params(cfg):
    h = cfg["hidden_size"]
    qkv = h + 2 * kv_width(cfg)
    return h * qkv + qkv + h * h + h + lambda_params(cfg) + 2 * h


def gmu_params(cfg):
    h = cfg["hidden_size"]
    return 2 * h * d_inner(cfg) + 2 * h


def cross_params(cfg):
    h = cfg["hidden_size"]
    return 2 * (h * h + h) + lambda_params(cfg) + 2 * h


MIXER_PARAMS = {"ssm": ssm_params, "window": attn_params,
                "full": attn_params, "gmu": gmu_params,
                "cross": cross_params}


def layers_params(cfg):
    """Every layer: its mixer and its MLP."""
    return sum(MIXER_PARAMS[k](cfg) + mlp_params(cfg)
               for k in layer_kinds(cfg))


def embed_params(cfg):
    return cfg["vocab_size"] * cfg["hidden_size"]


def total_params(cfg):
    """Every parameter held: layers, the tied embedding, the final
    norm."""
    return layers_params(cfg) + embed_params(cfg) + 2 * cfg["hidden_size"]


def kv_values_per_position(cfg):
    """Values one cached position holds in the pool: K and V of the one
    full layer."""
    return 2 * kv_width(cfg)


def slot_state_bytes(cfg, itemsize):
    """Bytes one slot owns beside its pages: every window layer's ring
    of K and V, every state-space layer's float32 state and its
    convolution's last inputs."""
    rings = (count_of(cfg, "window") * cfg["sliding_window"]
             * 2 * kv_width(cfg) * itemsize)
    di = d_inner(cfg)
    states = count_of(cfg, "ssm") * (
        di * cfg["mamba_d_state"] * 4
        + (cfg["mamba_d_conv"] - 1) * di * itemsize)
    return rings + states


def pool_reads_a_step(cfg):
    """Kernel calls a decode step makes over the pooled layer's rows."""
    return 1 + count_of(cfg, "cross")


def decode_step_bytes(cfg, active, live_positions, ring_rows,
                      weight_itemsize, kv_itemsize):
    """Bytes one decode step must move.  ``live_positions``: cached
    positions of the active slots in total; ``ring_rows``: ring rows ONE
    window layer reads, over the active slots."""
    row = kv_values_per_position(cfg) * kv_itemsize
    di = d_inner(cfg)
    weights = total_params(cfg) * weight_itemsize
    pool = (pool_reads_a_step(cfg) * (live_positions + active)
            + active) * row
    rings = count_of(cfg, "window") * (ring_rows + active) * row
    states = count_of(cfg, "ssm") * active * 2 * (
        di * cfg["mamba_d_state"] * 4
        + (cfg["mamba_d_conv"] - 1) * di * kv_itemsize)
    logits = active * cfg["vocab_size"] * 4
    return weights + pool + rings + states + logits


def attn_flops_per_row(cfg):
    """One query token against one cached row, one attention layer: two
    score products a pair of heads and two value products of twice the
    head's width."""
    nq, hd = cfg["num_attention_heads"], head_dim(cfg)
    return 2 * nq * hd + 2 * nq * 2 * hd


def decode_step_flops(cfg, active, live_positions, ring_rows):
    per_token = layers_params(cfg) + embed_params(cfg)
    return (2 * per_token * active
            + attn_flops_per_row(cfg) * (
                pool_reads_a_step(cfg) * (live_positions + active)
                + count_of(cfg, "window") * ring_rows))


def paged_diff_attn_decode_flops(cfg, rows):
    """One call's products over ``rows`` K/V rows in all."""
    return attn_flops_per_row(cfg) * rows


def paged_diff_attn_decode_bytes(cfg, active, rows, itemsize):
    """One call's bytes: the K and V rows once, the queries in, the
    pairs' outputs (float32) back."""
    width = cfg["hidden_size"]
    return (rows * kv_values_per_position(cfg) * itemsize
            + active * width * (itemsize + 4))


def kernel_calls_a_step(cfg):
    return count_of(cfg, "window") + pool_reads_a_step(cfg)
