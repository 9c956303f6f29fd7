"""The paged decode attention kernel's share of its roofline, in percent.

Least time the chip could take for the work the kernel had to do in the
traced window (the larger of bytes over peak bandwidth and FLOPs over peak
rate; at these widths the bytes bound it), over the kernel's device time in
the trace; which bound applies goes to standard error beside it.  The work
is what the algorithm needs: each decode token reads the keys and values
of its own context once per layer.

The context of every decode token of a request that finished in the window
is known from its prompt and answer lengths.  Decode tokens of requests
still running at the close, or dropped while decoding (the window's
``batch_step`` events count them all), are given the window's shortest
prompt plus one: the share reads low by their part, never high."""

import sys

from bench import flops as F
from bench import trace as T


def decode_context(run):
    """(context tokens summed over decode tokens, decode tokens)."""
    total = sum(e["decode"] for e in run["events"] if e["kind"] == "batch_step")
    ctx = known = 0
    for r in run["records"]:
        if r["status"] == "done" and r["done"] <= run["seconds"]:
            p, n = len(r["item"].prompt), r["item"].n_new
            known += n - 1
            ctx += (n - 1) * p + n * (n - 1) // 2
    rest = max(0, total - known)
    if rest:
        ctx += rest * (min(len(r["item"].prompt) for r in run["records"]) + 1)
    return ctx, total


def read(run):
    tr = run["trace"]
    if tr is None:
        return None
    t_kernel = sum(e.dur_ns for e in tr["ops"] if T.is_paged_attn(e.name))
    ctx, n = decode_context(run)
    if t_kernel <= 0 or n <= 0:
        return None
    nbytes, flops = F.paged_attn_cost(run["model"], ctx, n)
    t_min, bound = F.roofline_seconds(nbytes, flops, run["peaks"])
    print(f"paged_attn_roofline: bound={bound} least_s={t_min!r} "
          f"kernel_s={t_kernel * 1e-9!r}", file=sys.stderr)
    return 100.0 * t_min / (t_kernel * 1e-9)
