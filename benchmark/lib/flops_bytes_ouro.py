"""Operations and bytes the ALGORITHM needs for the ``ouro`` family (a
looped language model: the stacked layers run ``total_ut_steps`` times
over the same weights), computed from the configuration's published
keys.

What is counted, so a share of a peak can be argued with:

* a decode step streams the LAYERS' weights once for EVERY pass — the
  loop is the mechanism: at a batch of a few sequences nothing keeps 4.9
  GB of layers on the chip between passes — and the output head, the
  final norm and the exit gate once.  The embedding is a lookup of a few
  rows and counts nothing;
* a cached position is K and V of every (pass, layer) pair: ``2 * T * L
  * heads * head_dim`` values.  A step reads every live position once
  (each pair's own rows, in its own kernel call) and writes the new
  one;
* one call of the decode kernel (one (pass, layer) pair): the live K
  and V rows of the active slots, the queries in and the outputs back,
  against its two products per head.
"""


def layer_matmul_params(cfg):
    """Wq, Wk, Wv, Wo and the gated MLP's three, one layer."""
    h = cfg["hidden_size"]
    a = cfg["num_attention_heads"] * cfg["head_dim"]
    return 4 * h * a + 3 * h * cfg["intermediate_size"]


def layer_params(cfg):
    """One layer with its four norm gains."""
    return layer_matmul_params(cfg) + 4 * cfg["hidden_size"]


def head_params(cfg):
    return cfg["vocab_size"] * cfg["hidden_size"]


def closing_params(cfg):
    """The final norm's gain, the exit gate's weight and bias."""
    return 2 * cfg["hidden_size"] + 1


def total_params(cfg):
    """Every parameter held, embedding and head untied."""
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + 2 * head_params(cfg) + closing_params(cfg))


def virtual_layers(cfg):
    """(pass, layer) pairs: each keeps K and V of its own."""
    return cfg["total_ut_steps"] * cfg["num_hidden_layers"]


def kv_values_per_position(cfg):
    """Values one cached position holds: K and V of every pair."""
    return (2 * virtual_layers(cfg) * cfg["num_attention_heads"]
            * cfg["head_dim"])


def decode_step_weight_params(cfg):
    """Weights one decode step reads: the layers once a pass, the head
    and the closing parameters once."""
    return (cfg["total_ut_steps"] * cfg["num_hidden_layers"]
            * layer_params(cfg) + head_params(cfg) + closing_params(cfg))


def decode_step_bytes(cfg, active, live_positions, weight_itemsize,
                      kv_itemsize):
    """Bytes one decode step must move: the weights above, the live K/V
    of the active slots once, and the new positions written."""
    kv = ((live_positions + active) * kv_values_per_position(cfg)
          * kv_itemsize)
    return decode_step_weight_params(cfg) * weight_itemsize + kv


def decode_step_flops(cfg, active, live_positions):
    """FLOPs of one decode step: each active token through every layer
    of every pass and the head, and attention over ``live_positions``
    in total in each (pass, layer) pair."""
    per_token = (virtual_layers(cfg) * layer_matmul_params(cfg)
                 + head_params(cfg))
    return (2 * per_token * active
            + virtual_layers(cfg) * paged_attn_decode_flops(
                cfg, live_positions))


def paged_attn_decode_flops(cfg, live_positions):
    """One call's two products: scores and the weighted sum, per head."""
    return (2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]
            * live_positions)


def paged_attn_decode_bytes(cfg, active, live_positions, itemsize):
    """One call's bytes: every live K row and V row once, the queries in
    and the outputs back."""
    row = cfg["num_attention_heads"] * cfg["head_dim"]
    return itemsize * row * (2 * live_positions + 2 * active)
