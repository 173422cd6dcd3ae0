"""prefix cache: prompt tokens served from cached pages over prompt tokens
admitted, from the engine's counters over the window."""


def read(r):
    c = r.win.counters
    return 100.0 * c["cache_hit_tokens"] / c["prompt_tokens"] \
        if c["prompt_tokens"] else None
