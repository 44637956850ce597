"""Tokens emitted inside the window by the requests due inside it, over
the window's length (unfinished requests' tokens count up to the window's
end).  Below the knee it reads whether the window's own requests were
served in the window; it falls when the engine stalls or falls behind, and
not when work due before the window finishes sooner."""
from bench.records import served_tokens_in


def read(run):
    return served_tokens_in(run.requests, run.w0, run.w1) / (run.w1 - run.w0)
