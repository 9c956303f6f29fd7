"""Device time per run of the paged-decode step program, in milliseconds:
the mean length of the ``XLA Modules`` runs during which the paged
attention kernel ran, from the profiler trace."""

from bench import trace as T


def read(run):
    tr = run["trace"]
    if tr is None:
        return None
    runs = T.runs_holding(tr["modules"], tr["ops"], T.is_paged_attn)
    if not runs:
        return None
    return sum(m.dur_ns for m in runs) / len(runs) * 1e-6
