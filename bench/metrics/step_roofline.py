"""Share of the traced window's device busy time that the model work of its
steps needs at the least: the least time the chip could take for that work
(``bench/cost.py``: the larger of operations over peak and needed bytes
over bandwidth), over the union of the device's operation intervals in the
traced window (``Reduction.busy_s``).  Moves ``out_tok_per_s``.

The work of a traced step is one prefill pass over the first ``n - 1``
tokens of each prompt of ``n`` tokens it admitted, and one decode call over
its rows at their contexts.  It is counted from the steps alone, whatever
programs the system runs it as, and however many: a prefill that feeds its
prompt one token at a time does the weights' reads once per token, and
reads that much lower.
"""

from bench.cost import prefill_seconds, roofline_seconds


def read(run):
    t = run.trace
    steps = run.window.traced_steps
    if t is None or t.busy_s <= 0 or not steps:
        return None
    need = 0.0
    for s in steps:
        need += sum(prefill_seconds(run.model, n, run.peaks)
                    for n in s.admitted)
        if s.contexts:
            need += roofline_seconds(run.model, s.contexts, run.peaks)
    return 100.0 * need / t.busy_s
