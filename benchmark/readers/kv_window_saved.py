"""Share of the cache one shape for every layer would hold that the
window layers' rings do not: 1 - (blocks in use, weighted by the layers of
each kind) / (the full kind's blocks x all layers), the mean over decode
ticks of the engine's own block counts by kind."""
from benchmark.readers.tick_counters import at, tick_counters


def read(ctx):
    n = int(ctx["config"].get("num_hidden_layers", 0))
    kinds = ctx["config"].get("layer_types", [])[:n]
    sliding = kinds.count("sliding_attention")
    shares = []
    for c in tick_counters():
        full = at(c, ["kv_blocks", "full"])
        window = at(c, ["kv_blocks", "window"])
        if not full or window is None:
            continue
        shares.append(1.0 - ((n - sliding) * full + sliding * window)
                      / (n * full))
    return 100.0 * sum(shares) / len(shares) if shares else None
