"""Model FLOP utilization of the whole run on a routed-expert model (%):
model FLOPs of every token forwarded in the window over window x chips x
bf16 peak.  The expert FLOPs are those of the routed pairs the engine
counted in the window (``Engine.stats()["moe"]["steps"]``); the rest, per
token forwarded (prompt tokens not attached from the cache, and each
decoded token): attention projections and router, scores and weighted sum
over the keys each layer attends (capped by the window on sliding layers),
and the LM head where its logits are used (the reference module's
counts)."""
from bench import moe_scopes, spec
from bench.metrics._common import cached_tokens, first_token_in, in_window


def read(run):
    execs = moe_scopes.counted(run, run.window)
    if run.peaks is None or not execs:
        return None
    config = run.cell.config
    ref = spec.load_module(run.cell.search, "configs", config["reference"])
    tokens = keys = logits = 0
    for r in first_token_in(run, run.window):
        start, end = cached_tokens(run, r), len(r.prompt)
        tokens += end - start
        keys += ref.keys_attended_span(config, start, end)
        logits += 1
    for r in run.client.recs.values():
        if r.rid < 0:
            continue
        P = len(r.prompt)
        for k, t in enumerate(r.times[1:], start=1):
            if in_window(t, run.window):
                tokens += 1
                keys += ref.keys_attended(config, P + k - 1)
                logits += 1
    flops = (ref.non_expert_flops(config, tokens, keys, logits)
             + ref.expert_flops(config, sum(p for _, p, _ in execs)))
    span = run.window[1] - run.window[0]
    return 100.0 * flops / (span * run.chips * run.peaks["bf16_flops_per_s"])
