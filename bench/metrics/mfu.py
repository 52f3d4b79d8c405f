"""Model FLOP utilisation of the whole window: the operations of every
token the window processed (prompt tokens in prefill, and each decode
row's fed token, attention at its own context) over the window's length
times the chip's peak bf16 rate.  Moves ``out_tok_per_s``."""

from bench.cost import token_flops


def read(run):
    w = run.window
    flops = 0
    for s in w.steps:
        for n in s.admitted:
            flops += sum(token_flops(run.model, c) for c in range(1, n))
        flops += sum(token_flops(run.model, c) for c in s.contexts)
    return 100.0 * flops / (w.seconds * run.peaks["bf16_flops_per_s"])
