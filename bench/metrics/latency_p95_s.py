"""95th percentile, over every request due in the window, of the wall time
from its due time to its completion.  A dropped or unfinished request ranks
after every finished one; should the percentile fall on one, the time from
its due time to the end of the drain is given, a lower bound."""

import math


def read(run):
    due = [r for r in run["records"] if r["due"] < run["seconds"]]
    if not due:
        return None
    end = max((r["done"] for r in due if r["done"] is not None),
              default=run["seconds"])
    keyed = sorted(
        (0, r["done"] - r["due"]) if r["status"] == "done" and r["ok"]
        else (1, max(end, r["done"] or end) - r["due"]) for r in due)
    return keyed[max(0, math.ceil(0.95 * len(keyed)) - 1)][1]
