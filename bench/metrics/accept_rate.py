"""Draft acceptance over the window, from the engine's counters: (tokens
committed by steps - rows verified) / tokens drafted.  Each verified row
commits one token of its own beside the drafts it accepts."""


def read(run):
    c = run.window_counters
    if not c or c["drafted"] <= 0:
        return None
    return (c["committed"] - c["rows_verified"]) / c["drafted"]
