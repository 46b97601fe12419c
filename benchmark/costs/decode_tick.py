"""Bytes and operations one decode tick must move: every weight once,
the live keys and values once."""


def cost(cfg: dict, slots: int, live_tokens: float,
         param_bytes_per_el: int = 2, kv_bytes_per_el: int = 2) -> dict:
    """`live_tokens`: cache positions holding a live token, summed over
    the decoding slots.  Weights: every parameter is read once a tick
    (the tied embedding as the head; the position table is a lookup).
    Keys and values: 2 x layers x d a live position.  Operations: two a
    parameter and slot, plus 4 x d a live position and layer."""
    n = int(cfg["parameters"]) - cfg["n_positions"] * cfg["n_embd"]
    d = cfg["n_head"] * cfg["head_dim"]
    kv = 2 * cfg["n_layer"] * d * live_tokens
    return {"bytes": n * param_bytes_per_el + kv * kv_bytes_per_el,
            "flops": 2 * n * slots + 4 * cfg["n_layer"] * d * live_tokens,
            "weight_bytes": n * param_bytes_per_el,
            "kv_bytes": kv * kv_bytes_per_el}
