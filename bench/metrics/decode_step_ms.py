"""Mean host-clock wall time of the window's ``step_decode`` calls that
admitted nothing: one batched decode step with its host gather and
scatter of the KV caches.  Moves ``itl_p50_ms``."""


def read(run):
    walls = [s.t1 - s.t0 for s in run.window.steps if s.kind == "decode"]
    return 1e3 * sum(walls) / len(walls) if walls else None
