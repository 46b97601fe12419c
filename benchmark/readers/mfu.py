"""Model FLOP/s utilisation: required operations a token (the cost
function's, nothing recomputed) x tokens a second over chips x peak."""
from benchmark import harness


def read(ctx, cost: str = "train_step"):
    rate = ctx["counters"].get("train_tokens_per_s")
    if rate is None or ctx["peaks"] is None:
        return None
    need = harness.cost_function(cost)(
        ctx["config"], ctx["traffic"]["seq_len"])["flops_per_token"]
    return 100.0 * need * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops"])
