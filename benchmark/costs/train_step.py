"""Operations one trained token requires: forward and backward, nothing
recomputed."""


def cost(cfg: dict, seq_len: int) -> dict:
    """6 x parameters (each weight is used in one multiply-add forward and
    two backward; the tied embedding counts once, as the head) plus causal
    attention: QK^T and PV are 2 x 2 x T x d operations a token and layer
    over the full square, half of it under the causal mask, three times
    for forward + backward."""
    n = int(cfg["parameters"])
    attn = 6 * cfg["n_layer"] * seq_len * cfg["n_head"] * cfg["head_dim"]
    return {"flops_per_token": 6 * n + attn, "dense": 6 * n,
            "attention": attn}
