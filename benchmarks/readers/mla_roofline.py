"""The latent decode kernel's share of its roofline over the decode
ticks.

Time: device time of the operations ``pattern`` names inside whole
executions of ``module`` in the traced window, per execution
(``readers/op_time.py``).  Need: ``flops_mla.mla_decode_cost`` of the
window's mean tick, from the counters ``decode_latent_positions`` (the
positions attended, over slots and layers) and ``tokens_out -
prefills`` (slot-ticks) over ``decode_steps``, the larger of FLOPs over
peak FLOP/s and bytes over peak bytes/s.  A program without the kernel
or the counters (the parent, a rehearsal) gives None.
"""

from benchmarks import flops, flops_mla
from benchmarks.readers import op_time


def read(obs, pattern, module):
    counters = obs.get("counters", {})
    shapes = obs.get("mla")
    ticks = counters.get("decode_steps")
    if (not shapes or not ticks
            or not counters.get("decode_latent_positions")
            or "tokens_out" not in counters or "prefills" not in counters
            or obs.get("device", {}).get("platform") != "tpu"):
        return None
    seconds = op_time.read(obs, pattern, module)
    if not seconds:
        return None
    slot_ticks = counters["tokens_out"] - counters["prefills"]
    cost = flops_mla.mla_decode_cost(
        counters["decode_latent_positions"] / ticks,
        slot_ticks * shapes["layers"] / ticks,
        shapes["n_head"], shapes["rank"], shapes["rope_dim"],
        shapes["itemsize"])
    least = flops.roofline_seconds(
        cost, flops.peaks_for(obs["device"]["kind"]))["seconds"]
    return 100.0 * least / seconds
