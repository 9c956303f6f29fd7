"""Device time per run of the chunked-prefill step program, in
milliseconds: the mean length of the window's ``XLA Modules`` runs of the
program named ``jit_chunk_prefill``, from the profiler trace.  A program
whose step is not so named leaves nothing to read."""

from bench.spans import PREFILL_PROGRAM, program_name


def read(run):
    tr = run["trace"]
    if tr is None:
        return None
    runs = [m for m in tr["modules"]
            if program_name(m.name) == PREFILL_PROGRAM]
    if not runs:
        return None
    return sum(m.dur_ns for m in runs) / len(runs) * 1e-6
