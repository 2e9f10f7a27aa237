"""Device time of the routed experts over device busy time: their grouped matmuls (`ragged-dot*`) and the ordering, gather and scatter of (token, expert) pairs around them.

The operations around the matmuls are told by what they produce (kbench/expert_math.is_routing_op).  The router's own matmul and the shared expert are dense operations of the layer and are not in it.  Returns nothing for a configuration without routed experts or a trace without a grouped matmul."""

from kbench import expert_math, manifest

LAYER = "expert layers"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    trace, cfg = run["trace"], run["hf_config"]
    if (not trace or not trace.get("busy_s")
            or not expert_math.has_experts(cfg)):
        return None
    policy = manifest.resolve_cell(run["cell"]).deployment["engine_policy"]
    matmul = expert_math.grouped_matmul_seconds(trace)
    if not matmul:
        return None
    routing = expert_math.routing_seconds(trace, run["flags"], policy, cfg)
    return 100.0 * (matmul + routing) / trace["busy_s"]
