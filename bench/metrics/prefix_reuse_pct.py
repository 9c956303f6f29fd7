"""Prompt tokens served from the prefix KV cache, as a share of the prompt
tokens admitted in the window (reused plus prefilled)."""


def read(run):
    s0, s1 = run["s0"], run["s1"]
    reused = s1["prefix_tokens_reused"] - s0["prefix_tokens_reused"]
    prefilled = s1["prefill_tokens"] - s0["prefill_tokens"]
    if reused + prefilled <= 0:
        return None
    return 100.0 * reused / (reused + prefilled)
