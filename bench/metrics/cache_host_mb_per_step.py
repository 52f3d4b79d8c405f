"""Megabytes the decode step's batch cache crosses between device and host
per decode step: the ``cache_host_bytes`` counter's delta over 1e6 times
the ``decode_steps`` delta, in the window.  The gather and the scatter of
the whole batch cache through numpy count each array they convert, either
way.  Moves ``itl_p50_ms``."""


def read(run):
    c = run.counters
    if not c.get("decode_steps") or "cache_host_bytes" not in c:
        return None
    return c["cache_host_bytes"] / (1e6 * c["decode_steps"])
