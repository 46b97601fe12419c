"""Operations and bytes of the flash-attention kernels in one train step
on one chip: every layer's forward and backward calls together."""


def cost(cfg: dict, rows: int, seq_len: int, bytes_per_el: int = 2) -> dict:
    """Causal attention over (rows, T, H, D).  Forward: QK^T and PV;
    backward: dV, dP, dQ, dK: six matrix products of 2 x T^2 x D
    operations a head, half of each under the causal mask.  The kernel's
    recomputation of the scores in its backward pass is not counted.
    Bytes: forward reads q, k, v and writes o; backward reads q, k, v, o,
    do and writes dq, dk, dv (the per-row statistics are small)."""
    b, t = rows, seq_len
    h, d, layers = cfg["n_head"], cfg["head_dim"], cfg["n_layer"]
    flops = layers * 6 * b * h * t * t * d          # 6 products x 2 / 2
    bytes_ = layers * 12 * b * t * h * d * bytes_per_el
    return {"flops": flops, "bytes": bytes_}
