"""Bytes and operations one decode tick of a Laguna configuration must
move: the weights outside the routed experts once, the held experts that
the tick's tokens TOUCH once, the live keys and values by layer kind."""


def cost(cfg: dict, slots: float, touched: float, live_full: float,
         live_window: float, bytes_per_el: int = 2) -> dict:
    """`slots`: sequences decoded in the tick; `touched`: held experts
    that took an assignment, a layer (the mean over expert layers);
    `live_full`: cache positions holding a live token, summed over the
    slots (what a full layer reads); `live_window`: the same capped at
    the window a slot (what a sliding layer reads).

    Weights outside experts: q / k / v / gate / out of every layer, layer
    0's dense MLP, each expert layer's router and shared expert, the
    norms, and the head over the rows held (the embedding is a lookup of
    `slots` rows).  An expert: three matrices of hidden x width.
    Operations: two a weight element and token outside the experts, two a
    weight element for each of a token's assignments that an expert held
    here takes (top_k x held / routed of them on average), and 4 x heads x
    head size a live position and layer."""
    n = int(cfg["num_hidden_layers"])
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"]
    heads = cfg["num_attention_heads_per_layer"][:n]
    sliding = [t == "sliding_attention" for t in cfg["layer_types"][:n]]
    dense = [t == "dense" for t in cfg["mlp_layer_types"][:n]]
    attn = sum(d * h * hd * 2 + 2 * d * kv * hd + d * h for h in heads)
    shared = 3 * d * cfg["shared_expert_intermediate_size"]
    router = d * cfg.get("router_experts", cfg["num_experts"])
    ffn = sum(3 * d * cfg["intermediate_size"] if dn else shared + router
              for dn in dense)
    outside = attn + ffn + (2 * n + 1) * d + cfg["vocab_size"] * d
    expert = 3 * d * cfg["moe_intermediate_size"]
    layers_e = dense.count(False)
    held_share = cfg["num_experts"] / cfg.get("router_experts",
                                              cfg["num_experts"])
    kv_el = 2 * kv * hd * (sliding.count(False) * live_full
                           + sliding.count(True) * live_window)
    attn_ops = 4 * hd * sum(
        h * (live_window if s else live_full)
        for h, s in zip(heads, sliding))
    weight_el = outside + layers_e * touched * expert
    return {"bytes": (weight_el + kv_el) * bytes_per_el,
            "flops": 2 * slots * (outside + layers_e * expert
                                  * cfg["num_experts_per_tok"] * held_share)
            + attn_ops,
            "outside_bytes": outside * bytes_per_el,
            "expert_bytes": layers_e * touched * expert * bytes_per_el,
            "kv_bytes": kv_el * bytes_per_el}
