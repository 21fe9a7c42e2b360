"""The gated delta rule's decode kernel's share of its roofline over
the decode ticks.

Time: device time of the operations ``pattern`` names inside whole
executions of ``module`` in the traced window, per execution
(``readers/op_time.py``).  Need: ``flops_gdn.gdn_decode_cost`` of the
window's mean tick, from the counter ``decode_state_slot_layers`` (the
active slots times the linear layers) over ``decode_steps``, the larger
of FLOPs over peak FLOP/s and bytes over peak bytes/s (the bytes).  A
program without the kernel or the counter (the parent, another family,
a rehearsal) gives None.
"""

from benchmarks import flops, flops_gdn
from benchmarks.readers import op_time


def read(obs, pattern, module):
    counters = obs.get("counters", {})
    shapes = obs.get("gdn")
    ticks = counters.get("decode_steps")
    if (not shapes or not ticks
            or not counters.get("decode_state_slot_layers")
            or obs.get("device", {}).get("platform") != "tpu"):
        return None
    seconds = op_time.read(obs, pattern, module)
    if not seconds:
        return None
    cost = flops_gdn.gdn_decode_cost(
        counters["decode_state_slot_layers"] / ticks,
        shapes["n_value_heads"], shapes["n_key_heads"], shapes["key_dim"],
        shapes["value_dim"])
    least = flops.roofline_seconds(
        cost, flops.peaks_for(obs["device"]["kind"]))["seconds"]
    return 100.0 * least / seconds
