"""Share of its roofline that the one-token step program reaches: the least
time the chip could take for every ``_step`` call in the traced window
(``bench/cost.py``: the larger of operations over peak and needed bytes
over bandwidth, per call), over the device time of the ``jit__step``
programs in the trace, which holds the window's last steps whole.  Moves
``out_tok_per_s``."""

from bench.cost import roofline_seconds


def calls(steps):
    """Contexts of every ``_step`` call these steps made: each admitted
    prompt's batch-1 prefill calls, then each step's batched decode."""
    for s in steps:
        for n in s.admitted:
            for c in range(1, n):
                yield [c]
        yield s.contexts


def read(run):
    t = run.trace
    if t is None or t.step_device_s <= 0:
        return None
    need = 0.0
    n = 0
    for ctx in calls(run.window.traced_steps):
        need += roofline_seconds(run.model, ctx, run.peaks)
        n += 1
    if n != t.step_programs:
        raise RuntimeError(f"the trace holds {t.step_programs} step "
                           f"programs, its steps made {n} calls")
    return 100.0 * need / t.step_device_s
