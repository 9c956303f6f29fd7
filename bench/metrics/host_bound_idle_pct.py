"""Share of the traced window in which the device sat idle inside the
engine's step loop, in percent: the wall time of the window's
``unit.step`` spans (the ``step_s`` that each ``batch_step`` event
carries) less the device's busy time in the window, over the window.

The engine launches device work only inside ``unit.step``, and every step
waits for its launches, so the device's busy time lies inside the steps
and what is left of them is host work that kept the chip waiting.  Work
that outlives its step (a prefill-only step's page commit) is counted as
busy all the same, so the share reads low by that much, never high.
This subtraction mixes the host's clock (the steps) with the device's
(the busy time); it stands in for the device's idle time inside
``unit.step`` spans put on the device clock (``bench/spans.py``), which
needs the program's spans in the run the harness hands its readers.  A
program whose steps do not report their wall time leaves nothing to
read."""


def read(run):
    tr = run["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    steps = [e["step_s"] for e in run["events"]
             if e["kind"] == "batch_step" and "step_s" in e]
    if not steps:
        return None
    return 100.0 * (sum(steps) - tr["busy_s"]) / tr["window_s"]
