"""Share of the roofline reached by the routed-expert sublayers (%): their
least time over their device time, in the traced window's decode and
prefill executions.  Per execution the engine counts the routed (token,
expert) pairs and the distinct experts hit, summed over the MoE layers
(``Engine.stats()["moe"]["steps"]``); its least time is the larger of the
FLOPs of the pairs and of the router over the bf16 peak, and of the bytes
of the experts hit and of the router over HBM bandwidth (the reference
module's counts).  The device time is the ``moe_*`` scopes' self time in
the same programs.  Only experts hit are counted, so an implementation
that reads every expert's weights reads lower, never above 100 %."""
from bench import moe_scopes, roofline, spec


def read(run):
    red = moe_scopes.for_run(run)
    execs = moe_scopes.counted(run, run.trace_window)
    if red is None or not execs or run.peaks is None:
        return None
    config = run.cell.config
    ref = spec.load_module(run.cell.search, "configs", config["reference"])
    k = config["num_experts_per_tok"]
    least = sum(roofline.least_time(
        ref.expert_flops(config, pairs) + ref.router_flops(config, pairs / k),
        ref.expert_bytes(config, hit) + ref.router_bytes(config), run.peaks)
        for _, pairs, hit in execs)
    device = sum(red["decode_s"].values()) + sum(red["prefill_s"].values())
    return 100.0 * least / device if device else None
