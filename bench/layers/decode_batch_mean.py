"""scheduler: sequences advanced per decode step (the engine's exact
``tokens_decoded`` and decode-step counters over the window)."""


def read(r):
    c = r.win.counters
    return c["tokens_decoded"] / c["decode_steps"] if c["decode_steps"] \
        else None
