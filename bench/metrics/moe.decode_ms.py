"""Device time of the routed-expert sublayers per decode program (ms):
self time of the ops under the ``moe_route`` / ``moe_experts`` /
``moe_combine`` scopes in the traced window's ``jit_serve_step``
executions, summed over the MoE layers, over those executions."""
from bench import moe_scopes


def read(run):
    red = moe_scopes.for_run(run)
    if red is None or not red["decode_n"] or not red["decode_s"]:
        return None
    return 1e3 * sum(red["decode_s"].values()) / red["decode_n"]
