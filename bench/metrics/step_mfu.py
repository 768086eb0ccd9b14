"""Model FLOP utilization of the whole run (%): model FLOPs of every token
forwarded in the window (prompt tokens not attached from the cache, and
each decoded token; 2 x matmul weights, the LM head where logits are used,
attention over the actual context) over window x chips x bf16 peak."""
from bench.metrics._common import decode_polls, first_token_in, prefill_flops


def read(run):
    if run.peaks is None:
        return None
    flops = sum(p.decode_flops for p in decode_polls(run, run.window))
    flops += prefill_flops(run, first_token_in(run, run.window))
    if not flops:
        return None
    span = run.window[1] - run.window[0]
    return 100.0 * flops / (span * run.chips * run.peaks["bf16_flops_per_s"])
