"""The expert kernels' share of their roofline over the decode ticks.

Time: device time of the operations ``pattern`` names inside whole
executions of ``module`` in the traced window, per execution
(``readers/op_time.py``).  Need: ``flops_moe.expert_kernels_cost`` of
the window's mean tick, from the counters ``moe_local_assignments`` and
``moe_local_experts_hit`` over ``decode_steps`` (the hit experts'
weights, never all that are held), the larger of FLOPs over peak FLOP/s
and bytes over peak bytes/s.  A program without the kernels or the
counters gives None.
"""

from benchmarks import flops, flops_moe
from benchmarks.readers import op_time


def read(obs, pattern, module):
    counters = obs.get("counters", {})
    shapes = obs.get("moe")
    ticks = counters.get("decode_steps")
    if (not shapes or not ticks
            or "moe_local_experts_hit" not in counters
            or obs.get("device", {}).get("platform") != "tpu"):
        return None
    seconds = op_time.read(obs, pattern, module)
    if not seconds:
        return None
    cost = flops_moe.expert_kernels_cost(
        counters["moe_local_assignments"] / ticks,
        counters["moe_local_experts_hit"] / ticks,
        shapes["d_model"], shapes["d_expert"], shapes["itemsize"])
    least = flops.roofline_seconds(
        cost, flops.peaks_for(obs["device"]["kind"]))["seconds"]
    return 100.0 * least / seconds
