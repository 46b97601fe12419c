"""Bytes and operations one decode tick of a ``glm4_moe_lite``
configuration must move: the weights outside the routed experts once, the
routed experts that the tick's tokens TOUCH once, and each live latent row
ONCE, ``kv_lora_rank + qk_rope_head_dim`` values a position and layer."""


def cost(cfg: dict, slots: float, touched: float, live_rows: float,
         bytes_per_el: int = 2) -> dict:
    """`slots`: sequences decoded in the tick; `touched`: routed experts
    that took an assignment, a layer (the mean over expert layers);
    `live_rows`: cache positions holding a live token, summed over the
    slots (what every layer's attention reads, once).

    Weights outside experts: the five attention matrices of every layer
    (query down and up, key-value down and up, output), layer 0's dense
    MLP, each expert layer's router, bias and shared expert, the norms,
    and the untied head (the embedding is a lookup of `slots` rows).  An
    expert: three matrices of hidden x width.  Operations: two a weight
    element and token outside the experts and in each of a token's
    `num_experts_per_tok` experts; absorbed attention: a score over the
    whole row and a value over its latent part for every head, ``2 x heads
    x (row + kv_lora_rank)`` a live row and layer (`attn_flops`, with
    `attn_bytes` the kernel's own floor)."""
    n = int(cfg["num_hidden_layers"])
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    row = rkv + rope
    dense = int(cfg["first_k_dense_replace"])
    layers_e = n - dense
    attn = (d * rq + rq * H * (nope + rope) + d * row
            + rkv * H * (nope + dv) + H * dv * d + rq + rkv)
    shared = 3 * d * cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    router = d * cfg["n_routed_experts"] + cfg["n_routed_experts"]
    outside = (n * attn + dense * 3 * d * cfg["intermediate_size"]
               + layers_e * (shared + router) + (2 * n + 1) * d
               + cfg["vocab_size"] * d)
    expert = 3 * d * cfg["moe_intermediate_size"]
    attn_el = n * live_rows * row
    attn_flops = 2 * H * (row + rkv) * live_rows * n
    weight_el = outside + layers_e * touched * expert
    return {"bytes": (weight_el + attn_el) * bytes_per_el,
            "flops": 2 * slots * (outside + layers_e * expert
                                  * cfg["num_experts_per_tok"]) + attn_flops,
            "outside_bytes": outside * bytes_per_el,
            "expert_bytes": layers_e * touched * expert * bytes_per_el,
            "kv_bytes": attn_el * bytes_per_el,
            "attn_bytes": attn_el * bytes_per_el,
            "attn_flops": attn_flops}
