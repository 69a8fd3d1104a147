"""Operations and bytes the ALGORITHM needs, computed from shapes.

What is counted, so a share of a peak can be argued with:

* matmul weights only (qkv, proj, fc1, fc2 per layer, and the tied output
  head once); embedding and position LOOKUPS move rows and do no
  arithmetic, so they count nothing;
* attention as CAUSAL: a query at position i meets i+1 keys;
* a backward pass as twice its forward;
* recomputation (remat) never counts — it is work the chip does that the
  algorithm does not require, so it lowers the share, as it should.
"""


def matmul_params(cfg):
    """Weights that take part in a matmul for every token."""
    h, f = cfg["hidden_size"], cfg["ffn_size"]
    per_layer = 3 * h * h + h * h + 2 * h * f
    return cfg["num_layers"] * per_layer + cfg["vocab_size"] * h


def train_flops_per_token(cfg, seq_len):
    """Forward+backward FLOPs one trained token requires at ``seq_len``."""
    h, layers = cfg["hidden_size"], cfg["num_layers"]
    dense_fwd = 2 * matmul_params(cfg)
    # scores and the weighted sum: 2 matmuls x 2 FLOPs x H per key met,
    # (seq_len + 1) / 2 keys on average under the causal mask
    attn_fwd = layers * 2 * 2 * h * (seq_len + 1) / 2
    return 3 * (dense_fwd + attn_fwd)


def kv_bytes_per_token(cfg, kv_itemsize):
    """Bytes of K and V one cached position holds, all layers."""
    return 2 * cfg["num_layers"] * cfg["hidden_size"] * kv_itemsize


def decode_step_flops(cfg, active, live_tokens):
    """FLOPs of one decode step: ``active`` sequences each take one token
    through every weight and attend ``live_tokens`` cached positions in
    total."""
    return (2 * matmul_params(cfg) * active
            + cfg["num_layers"] * 2 * 2 * cfg["hidden_size"] * live_tokens)


def decode_step_bytes(cfg, active, live_tokens, weight_itemsize,
                      kv_itemsize):
    """Bytes one decode step must move: every matmul weight once, the
    live K/V of the active sequences once, and the new position's K/V
    written.  Activations are KB and are left out."""
    weights = matmul_params(cfg) * weight_itemsize
    kv = (live_tokens + active) * kv_bytes_per_token(cfg, kv_itemsize)
    return weights + kv


def roofline_seconds(flops, nbytes, peaks):
    """(least seconds the chip could take, which bound sets it)."""
    t_compute = flops / peaks["bf16_flops_per_s"]
    t_memory = nbytes / peaks["hbm_bytes_per_s"]
    return ((t_compute, "compute") if t_compute >= t_memory
            else (t_memory, "memory"))
