"""Output tokens emitted inside the window, per second of the window (wall
clock).

A served request's tokens become visible only when it completes, so the
tokens are counted where the engine makes them: each ``batch_step``
telemetry event stamped inside the window counts one token per sequence
it decoded and one per prompt whose last prefill chunk it ran (every
prompt is one chunk here).  Counting the answers of requests that finished
inside the window instead would move by a whole answer whenever one
finished just before or just after the close."""


def read(run):
    return sum(e["decode"] + e["chunks"] for e in run["events"]
               if e["kind"] == "batch_step") / run["seconds"]
