"""Mean wall time a request waited before its first prefill chunk was
dispatched, in milliseconds: the program's ``request.queued`` span, from
``Router.submit`` to that dispatch, as the ``first_token`` events stamped
inside the window carry it (``queued_s``).  A request dropped before its
first token has no such event and is not counted; a program without the
event leaves nothing to read."""


def read(run):
    waits = [e["queued_s"] for e in run["events"]
             if e["kind"] == "first_token" and "queued_s" in e]
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
