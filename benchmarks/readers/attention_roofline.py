"""A flash-attention kernel's share of its roofline: the least time
the chip needs for the calls seen in the trace (``flops.py``: causal
half, the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s)
over the device time those calls took.  At GPT-2 shapes (head size 64,
sequence 1024) the compute bound applies."""

from benchmarks import flops


def read(obs, pattern, direction, identified_by=None):
    trace = obs.get("trace")
    shapes = obs.get("shapes")
    if trace is None or not shapes:
        return None
    # The kernels carry no stable name in the trace today, so the
    # pattern is their output signature at this cell's shapes.
    runs = trace.op_runs(pattern.format(
        bh=shapes["batch"] * shapes["n_head"], seq=shapes["seq"],
        head_dim=shapes["head_dim"]))
    if not runs or obs["device"]["platform"] != "tpu":
        return None
    cost = flops.attention_kernel_cost(
        shapes["batch"], shapes["n_head"], shapes["seq"],
        shapes["head_dim"])[direction]
    least = flops.roofline_seconds(
        cost, flops.peaks_for(obs["device"]["kind"]))["seconds"]
    return 100.0 * least * len(runs) / sum(runs)
