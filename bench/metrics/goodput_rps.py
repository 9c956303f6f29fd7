"""Requests due in the window that finished, with right-sized answers, by
their wall-clock deadline, per second of the window.  A request due near
the close is judged by its deadline, not by the close: the harness serves
on past the close until every request due in the window has finished or
``DRAIN_S`` has passed.  Drops, late answers and requests unfinished then
are misses."""


def read(run):
    t = run["seconds"]
    ok = sum(1 for r in run["records"]
             if r["due"] < t and r["status"] == "done" and r["ok"]
             and r["done"] <= r["deadline"])
    return ok / t
