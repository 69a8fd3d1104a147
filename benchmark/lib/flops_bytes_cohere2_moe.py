"""Operations and bytes the ALGORITHM needs for the ``cohere2_moe``
family, computed from the configuration's published keys.

What is counted, so a share of a peak can be argued with:

* matmul weights only — each layer's attention (Wq, Wk, Wv, Wo), its
  router, its shared experts, of its HELD routed experts those a step
  TOUCHES (the program's counter; an expert no token chose is not read),
  and the tied output head.  The embedding's lookup moves a few rows and
  counts nothing; the norm gains are KB and are left out;
* a cached position as the K and V rows of ``num_key_value_heads x
  head_dim`` each that a layer reads of it: a window layer reads at most
  ``sliding_window`` of a slot's, a full layer every one (the program's
  counters give the rows a step read, summed over the layers);
* grouped-query attention as its products: each of the ``nq`` query
  heads meets every row its K/V head holds.
"""


def kv_width(cfg):
    return cfg["num_key_value_heads"] * cfg["head_dim"]


def q_width(cfg):
    return cfg["num_attention_heads"] * cfg["head_dim"]


def attn_params(cfg):
    """Wq, Wk, Wv, Wo of one layer."""
    h = cfg["hidden_size"]
    return h * (q_width(cfg) + 2 * kv_width(cfg)) + q_width(cfg) * h


def router_params(cfg):
    return cfg["hidden_size"] * cfg["num_experts_total"]


def expert_params(cfg):
    """One routed (or shared) expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def head_params(cfg):
    return cfg["vocab_size"] * cfg["hidden_size"]


def outside_experts_params(cfg):
    """What every token of a layer passes through: attention, the router
    and the shared experts."""
    return (attn_params(cfg) + router_params(cfg)
            + cfg["num_shared_experts"] * expert_params(cfg))


def total_params(cfg):
    """Every matmul weight held, the tied embedding once (the sizing's
    count, not a step's)."""
    per_layer = (outside_experts_params(cfg)
                 + cfg["num_experts"] * expert_params(cfg))
    return cfg["num_hidden_layers"] * per_layer + head_params(cfg)


def kv_row_bytes(cfg, kv_itemsize):
    """K and V of one cached position in one layer."""
    return 2 * kv_width(cfg) * kv_itemsize


def paged_attn_decode_bytes(cfg, calls, rows, act_itemsize):
    """``calls`` grouped-query kernel calls that read ``rows`` cached
    rows in all (each row K and V of every K/V head), each call's
    ``slots`` queries in and outputs out (``cfg["engine"]["slots"]``)."""
    slots = cfg["engine"]["slots"]
    return (rows * kv_row_bytes(cfg, act_itemsize)
            + calls * 2 * slots * q_width(cfg) * act_itemsize)


def paged_attn_decode_flops(cfg, rows):
    """Scores and the weighted sum over ``rows`` cached rows: every
    query head meets each row its K/V head holds."""
    return 2 * 2 * q_width(cfg) * rows


def decode_step_weight_params(cfg, experts_touched):
    """Weights one decode step reads: everything every token passes
    through, and of each layer's held experts the ``experts_touched``
    (a mean per layer, from the program's counter)."""
    return (cfg["num_hidden_layers"] * (outside_experts_params(cfg)
                                        + experts_touched
                                        * expert_params(cfg))
            + head_params(cfg))


def decode_step_bytes(cfg, active, rows_read, experts_touched,
                      weight_itemsize, kv_itemsize):
    """Bytes one decode step must move: the weights above once, the
    ``rows_read`` cached rows (all layers) once, and each active slot's
    new row written in every layer."""
    rows = rows_read + active * cfg["num_hidden_layers"]
    return (decode_step_weight_params(cfg, experts_touched) * weight_itemsize
            + rows * kv_row_bytes(cfg, kv_itemsize))


def decode_step_flops(cfg, active, rows_read, held_assignments):
    """FLOPs of one decode step: each active token through what every
    token passes through and the head, the held assignments (a mean per
    layer) through their expert, and attention over ``rows_read``."""
    per_token = (cfg["num_hidden_layers"] * outside_experts_params(cfg)
                 + head_params(cfg))
    return (2 * per_token * active
            + 2 * cfg["num_hidden_layers"] * held_assignments
            * expert_params(cfg)
            + paged_attn_decode_flops(cfg, rows_read))


def grouped_matmul_bytes(cfg, experts_touched, rows, itemsize):
    """One layer's two grouped-matmul calls in a decode step: each
    touched held expert's gate, up and down once, the ``rows`` held
    assignments in (bf16) and out (gate-up bf16, down float32)."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    return (experts_touched * expert_params(cfg) * itemsize
            + rows * (h * itemsize + i * itemsize       # gate-up in, out
                      + i * itemsize + h * 4))          # down in, out


def grouped_matmul_flops(cfg, rows):
    return 2 * rows * expert_params(cfg)
