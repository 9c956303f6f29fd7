"""The whole served step's share of the chip's peak bf16 rate, in percent:
forward FLOPs of every token the engine processed in the window (prefill
chunks and decodes, from the window's ``batch_step`` events) over the
window's length and the peak.  Attention scores are not counted, so this
is a lower bound; it bounds every kernel's gain from above."""

from bench import flops as F


def read(run):
    if run["peaks"] is None:
        return None
    steps = [e for e in run["events"] if e["kind"] == "batch_step"]
    tokens = sum(e["tokens"] for e in steps)
    rows = sum(e["decode"] + e["chunks"] for e in steps)
    if tokens <= 0:
        return None
    flops = F.step_flops(run["model"], tokens, rows)
    return 100.0 * flops / run["seconds"] / run["peaks"]["bf16_flops_per_s"]
