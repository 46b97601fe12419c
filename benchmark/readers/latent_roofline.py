"""A latent-attention model's decode tick against its roofline.

Without `kernel`: the decode PROGRAM's share, as `moe_decode_roofline`
reads it: the least time for what a tick must read (the cost function:
weights outside the experts once, the experts the tick's tokens TOUCHED,
each live latent row once) over the median device time of a run of
`program`, picked by module name.

With `kernel` (a pattern of the attention kernel's device events): the
KERNEL's share: the least time for the live rows alone, a call, over the
mean device time of a call.

What a tick held comes from the program's own tick ring: the experts
touched (``counters["experts"]``) and the live rows its latent layers read
(``counters["latent"]["rows"]``, summed over the layers).  A program that
writes no such counters, or a trace without the program or the kernel,
gives None."""
import statistics

from benchmark import harness, trace_reduce
from benchmark.harness import say
from benchmark.readers import tick_counters
from benchmark.readers.moe_decode_roofline import runs_of


def read(ctx, cost: str = "glm_decode_tick",
         program: str = "jit_paged_decode", kernel: str | None = None):
    if not ctx["trace"] or ctx["peaks"] is None:
        return None
    events = ctx["trace"]["events"]
    layers = int(ctx["config"]["num_hidden_layers"])
    touched = tick_counters.read(ctx, ["experts", "touched"])
    rows = tick_counters.read(ctx, ["latent", "rows"])
    share = ctx["counters"].get("mean_decoding_slots_share")
    if touched is None or rows is None or share is None:
        return None
    need = harness.cost_function(cost)(
        ctx["config"], share * ctx["traffic"]["engine"]["max_slots"],
        touched, rows / layers)
    bw, peak = ctx["peaks"]["hbm_bytes_per_s"], ctx["peaks"]["bf16_flops"]
    if kernel is not None:
        got = trace_reduce.op_seconds(events, kernel)
        if not got["count"]:
            return None
        by_bytes, by_flops = (need["attn_bytes"] / layers / bw,
                              need["attn_flops"] / layers / peak)
        took = got["seconds"] / got["count"]
        say(f"roofline {cost} / {kernel}: {got['count']} kernel events, "
            f"mean {took * 1e6:.1f}us a call over {rows / layers:.0f} live "
            f"rows; least {by_bytes * 1e6:.1f}us by bytes "
            f"({need['attn_bytes'] / layers / 1e6:.1f} MB a call), "
            f"{by_flops * 1e6:.1f}us by operations: bound by "
            f"{'bytes' if by_bytes >= by_flops else 'operations'}")
        return 100.0 * max(by_bytes, by_flops) / took
    runs = [(t - s) / 1e9 for s, t in runs_of(events, program)]
    if not runs:
        return None
    by_bytes, by_flops = need["bytes"] / bw, need["flops"] / peak
    took = statistics.median(runs)
    say(f"roofline {cost}: {len(runs)} decode programs, median "
        f"{took * 1e3:.3f}ms; least {by_bytes * 1e3:.3f}ms by bytes "
        f"({need['outside_bytes'] / 1e9:.2f} GB weights outside experts + "
        f"{need['expert_bytes'] / 1e9:.2f} GB of {touched:.1f} touched "
        f"experts a layer + {need['kv_bytes'] / 1e9:.3f} GB of "
        f"{rows / layers:.0f} live latent rows a layer), "
        f"{by_flops * 1e3:.3f}ms by operations: bound by "
        f"{'bytes' if by_bytes >= by_flops else 'operations'}")
    return 100.0 * max(by_bytes, by_flops) / took
