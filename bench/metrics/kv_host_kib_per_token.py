"""K/V KiB copied between host and device per prompt token prefilled in
the window: the step loop's ``kv_host_bytes`` (prefix uploads, each
chunk's download and the commit's upload), as the window's ``batch_step``
events carry it per step (``kv_bytes``), over the growth of the engine's
``prefill_tokens`` counter.  A program whose steps do not report the
bytes leaves nothing to read."""


def read(run):
    steps = [e["kv_bytes"] for e in run["events"]
             if e["kind"] == "batch_step" and "kv_bytes" in e]
    prefilled = run["s1"]["prefill_tokens"] - run["s0"]["prefill_tokens"]
    if not steps or prefilled <= 0:
        return None
    return sum(steps) / 1024.0 / prefilled
