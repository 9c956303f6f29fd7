"""Mean number of sequences decoding in an engine step, over the window's
``batch_step`` telemetry events (their counts are sound; their ``dt`` is the
engine's modelled clock and is not read)."""


def read(run):
    steps = [e["decode"] for e in run["events"] if e["kind"] == "batch_step"]
    return sum(steps) / len(steps) if steps else None
