"""Prompt tokens prefilled plus tokens generated in the window's steps,
over the window's seconds."""


def read(run):
    tokens = sum(s.prefill_len + s.generated for s in run.steps)
    return tokens / run.window_s if tokens else None
