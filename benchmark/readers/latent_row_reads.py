"""How many times over the decode program's attention reads a live latent
row: the bytes a row and layer that the program's own tick ring reports
(``counters["latent"]["row_bytes"]``, the mean over decode ticks: the engine
counts them off its attention kernel's call as traced for its shapes, the
pool's operands a grid step against the positions the step covers) over the
bytes the row holds, ``kv_lora_rank + qk_rope_head_dim`` values in the
type the configuration serves in.  1.0 is the floor; 2.0 is keys and
values read apart.  None where the program writes no such counter."""
from benchmark.readers import tick_counters

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def read(ctx):
    cfg = ctx["config"]
    got = tick_counters.read(ctx, ["latent", "row_bytes"])
    if got is None or "kv_lora_rank" not in cfg:
        return None
    row = (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) \
        * _BYTES[cfg["serve_param_dtype"]]
    return got / row
