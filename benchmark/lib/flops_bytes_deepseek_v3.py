"""Operations and bytes the ALGORITHM needs for the ``deepseek_v3``
family, computed from the configuration's published keys.

What is counted, so a share of a peak can be argued with:

* matmul weights only — the attention's four projections, the router,
  the shared experts, the routed experts a step TOUCHES (never all of
  them: an expert no token chose is not read), layer 0's dense MLP and
  the output head.  The embedding is a lookup of a few rows and counts
  nothing; norm gains and the router bias are KB and are left out;
* a cached position as the 512 + 64 values the model needs of it, not
  the 640-wide row the pool stores;
* decode attention in its absorbed form (what the cache makes
  possible): per head, one product of the 512 + 64 wide query with
  every live position and one of the probabilities with the 512 wide
  latent.  The absorption's own products (q_nope Wuk, o_lat Wuv) are
  weights' work and are counted with the weights.
"""


def attn_params(cfg):
    """Wq, Wdkv, Wukv, Wo of one layer."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    latent = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return (h * nh * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
            + h * latent
            + cfg["kv_lora_rank"] * nh * (cfg["qk_nope_head_dim"]
                                          + cfg["v_head_dim"])
            + nh * cfg["v_head_dim"] * h)


def expert_params(cfg):
    """One routed expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg):
    return cfg["n_shared_experts"] * expert_params(cfg)


def router_params(cfg):
    return cfg["hidden_size"] * cfg["n_routed_experts"]


def dense_mlp_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def head_params(cfg):
    return cfg["vocab_size"] * cfg["hidden_size"]


def moe_layers(cfg):
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def total_params(cfg):
    """Every matmul weight held, the embedding included (the sizing's
    count, not a step's)."""
    per_moe = (attn_params(cfg) + router_params(cfg) + shared_params(cfg)
               + cfg["n_routed_experts"] * expert_params(cfg))
    per_dense = attn_params(cfg) + dense_mlp_params(cfg)
    return (moe_layers(cfg) * per_moe
            + cfg["first_k_dense_replace"] * per_dense
            + 2 * head_params(cfg))


def latent_values_per_position(cfg):
    """Values ONE layer caches for one position: c and kr."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def decode_step_weight_params(cfg, experts_touched):
    """Weights one decode step reads: everything every token passes
    through, and of each expert layer's routed experts the
    ``experts_touched`` (a mean per layer, from the program's counter)."""
    n_moe = moe_layers(cfg)
    return (cfg["num_hidden_layers"] * attn_params(cfg)
            + cfg["first_k_dense_replace"] * dense_mlp_params(cfg)
            + n_moe * (router_params(cfg) + shared_params(cfg)
                       + experts_touched * expert_params(cfg))
            + head_params(cfg))


def decode_step_bytes(cfg, active, live_positions, experts_touched,
                      weight_itemsize, kv_itemsize):
    """Bytes one decode step must move: the weights above once, the live
    latents of the active slots once in every layer, and the new
    positions written."""
    latents = ((live_positions + active) * cfg["num_hidden_layers"]
               * latent_values_per_position(cfg) * kv_itemsize)
    return (decode_step_weight_params(cfg, experts_touched) * weight_itemsize
            + latents)


def decode_step_flops(cfg, active, live_positions):
    """FLOPs of one decode step: each active token through the weights
    it meets (``num_experts_per_tok`` routed experts, not the touched
    ones) and through attention over ``live_positions`` in total."""
    per_token = (cfg["num_hidden_layers"] * attn_params(cfg)
                 + cfg["first_k_dense_replace"] * dense_mlp_params(cfg)
                 + moe_layers(cfg) * (
                     router_params(cfg) + shared_params(cfg)
                     + cfg["num_experts_per_tok"] * expert_params(cfg))
                 + head_params(cfg))
    return (2 * per_token * active
            + cfg["num_hidden_layers"]
            * mla_decode_flops(cfg, live_positions))


def mla_decode_flops(cfg, live_positions):
    """The kernel's two products in ONE layer: per head, scores over the
    512 + 64 wide latent and the weighted sum of the 512 wide one."""
    nh = cfg["num_attention_heads"]
    return 2 * nh * live_positions * (latent_values_per_position(cfg)
                                      + cfg["kv_lora_rank"])


def mla_decode_bytes(cfg, active, live_positions, itemsize):
    """The kernel's bytes in ONE layer: every live latent once, the
    absorbed queries in and the latent outputs back."""
    nh = cfg["num_attention_heads"]
    return itemsize * (
        live_positions * latent_values_per_position(cfg)
        + active * nh * (latent_values_per_position(cfg)
                         + cfg["kv_lora_rank"]))
