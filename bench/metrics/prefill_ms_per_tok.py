"""Host-clock milliseconds of admission and prefill per prompt token: the
wall time of the window's admitting ``step_decode`` calls, less one mean
decode step each, over the prompt tokens they admitted.  Moves
``ttft_p50_s``."""


def read(run):
    steps = run.window.steps
    decode = [s.t1 - s.t0 for s in steps if s.kind == "decode"]
    admit = [s for s in steps if s.kind == "admit"]
    tokens = sum(n for s in admit for n in s.admitted)
    if not decode or not tokens:
        return None
    step = sum(decode) / len(decode)
    return 1e3 * sum(s.t1 - s.t0 - step for s in admit) / tokens
