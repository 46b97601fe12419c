"""Bytes and operations of the held experts' grouped products
(``ops/grouped_matmul_pallas.py``: device events ``grouped_swiglu``, gate
and up fused in one call, and ``grouped_product``, down): what a call must
move is its TOUCHED experts' matrices once and the held rows in and out."""


def cost(cfg: dict, touched: float, rows: float,
         bytes_per_el: int = 2) -> dict:
    """`touched`: held experts that took an assignment, SUMMED over the
    expert layers the calls belong to (one layer: that layer's count);
    `rows`: held rows (assignments an expert held here took), summed
    likewise.  An expert layer makes two calls:

    * ``swiglu``: two matrices of hidden x width an expert touched (gate
      and up), the rows in at hidden wide, out at width;
    * ``down``: one matrix of width x hidden, the rows in at width wide,
      out at hidden.

    Operations: 2 x rows x hidden x width a matrix.  ``bytes`` / ``flops``
    are the two calls together."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    matrix = d * f * bytes_per_el
    row = (d + f) * bytes_per_el
    calls = {
        "swiglu": {"weight_bytes": 2 * touched * matrix,
                   "row_bytes": rows * row, "flops": 4 * rows * d * f},
        "down": {"weight_bytes": touched * matrix,
                 "row_bytes": rows * row, "flops": 2 * rows * d * f},
    }
    for c in calls.values():
        c["bytes"] = c["weight_bytes"] + c["row_bytes"]
    return {**calls,
            **{k: sum(c[k] for c in calls.values())
               for k in ("bytes", "flops", "weight_bytes", "row_bytes")}}
