"""The held experts a CHUNK program touched, a layer, over those held:
the mean over the traced window's chunk records (``counters["programs"]``,
each chunk's own ``experts``), scaled.  What the grouped kernels' floor is
made of, beside the decode ticks' ``serve_expert_touched_pct``.  None for
a program that records no chunk's experts, or with no traced window."""
from benchmark.readers import traced_run


def _read(scale):
    record = traced_run.traced_record()
    if record is None:
        return None
    got = [r["experts"] for r in traced_run.programs(record)
           if r["program"] == "paged_chunk" and "experts" in r]
    got = [e["touched"] / e["held"] for e in got if e["held"]]
    return scale * sum(got) / len(got) if got else None


def read(ctx, scale: float = 100.0):
    return traced_run.guarded("chunk experts", _read, scale)
