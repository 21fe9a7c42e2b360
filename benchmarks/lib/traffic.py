"""The one general traffic generator.

A traffic mix is a data file of parameters (``traffic/<name>.json``);
this module turns it and ``--seed`` into the work of a run.  Two kinds:

``requests``   serving requests: prompt length, answer length, token
               contents, optional shared prefixes, and how they arrive
               (``closed_loop`` with N callers, or ``open_loop`` with a
               seeded inter-arrival schedule).
``lm_batches`` training batches: batch size, sequence length, how many
               distinct batches the seeded pool holds.

Every seed gets the SAME multiset of sizes: the sizes of one block are
the quantiles of the stated distributions, paired by a fixed
permutation; a seed only changes their order inside each block and the
token contents.  So two seeds offer the same work in another order.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np


@dataclass(frozen=True)
class Request:
    index: int
    prompt: List[int]
    max_new_tokens: int
    due_s: Optional[float] = None   # open loop: offset from window start


def _quantiles(spec: Dict[str, Any], n: int) -> List[int]:
    """n values at the mid-quantiles of the distribution ``spec``."""
    u = (np.arange(n) + 0.5) / n
    low, high = float(spec["low"]), float(spec["high"])
    dist = spec["dist"]
    if dist == "log_uniform":
        vals = np.exp(math.log(low) + u * (math.log(high) - math.log(low)))
    elif dist == "uniform":
        vals = low + u * (high - low)
    elif dist == "constant":
        vals = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    return [int(round(v)) for v in vals]


def block_sizes(params: Dict[str, Any]) -> List[tuple]:
    """The (prompt_len, max_new_tokens) pairs of one block — the same
    for every seed."""
    n = int(params["block"])
    prompts = _quantiles(params["prompt_len"], n)
    news = _quantiles(params["max_new_tokens"], n)
    pairing = np.random.default_rng(0).permutation(n)  # fixed, not the seed
    return [(prompts[i], news[int(pairing[i])]) for i in range(n)]


def requests(params: Dict[str, Any], seed: int, vocab_size: int,
             count: int) -> List[Request]:
    """The first ``count`` requests of the mix under ``seed``."""
    if params["kind"] != "requests":
        raise ValueError(f"not a request mix: {params['kind']!r}")
    rng = np.random.default_rng(int(seed))
    sizes = block_sizes(params)
    prefix_spec = params.get("shared_prefix")
    prefixes: List[List[int]] = []
    if prefix_spec:
        prefixes = [
            rng.integers(1, vocab_size, size=int(prefix_spec["len"])).tolist()
            for _ in range(int(prefix_spec["groups"]))
        ]
    arrivals = params["arrivals"]
    due = 0.0
    out: List[Request] = []
    while len(out) < count:
        for j in rng.permutation(len(sizes)):
            plen, new = sizes[int(j)]
            body = rng.integers(1, vocab_size, size=plen).tolist()
            if prefixes:
                head = prefixes[int(rng.integers(len(prefixes)))]
                body = (head + body)[:plen] if len(head) < plen else body
            when = None
            if arrivals["kind"] == "open_loop":
                due += float(rng.exponential(1.0 / arrivals["rate_per_s"]))
                when = due
            out.append(Request(len(out), body, new, when))
            if len(out) == count:
                break
    return out


def lm_token_pool(params: Dict[str, Any], seed: int, vocab_size: int
                  ) -> np.ndarray:
    """The seeded pool of training sequences, (rows, seq_len + 1) int32."""
    if params["kind"] != "lm_batches":
        raise ValueError(f"not a batch mix: {params['kind']!r}")
    rng = np.random.default_rng(int(seed))
    rows = int(params["batch_size"]) * int(params["pool_batches"])
    return rng.integers(
        0, vocab_size, size=(rows, int(params["seq_len"]) + 1)
    ).astype(np.int32)
