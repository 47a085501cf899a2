"""The one traffic generator: turns a mix file (``traffic/<mix>.json``)
and ``--seed`` into the requests the program receives.

Every seed gets the same multiset of sizes and arrival gaps in another
order, so seeds change the order of the work and the token ids, never
its amount.  Sizes come in blocks of ``sizes_per_block`` pairs (prompt
tokens, output tokens): the ``(i + 0.5) / n`` quantiles of each length
distribution, paired by a fixed permutation; each block is shuffled by
the seed.  An open loop draws its gaps the same way from the quantiles
of the exponential distribution at ``rate_per_s`` (Poisson arrivals).

Mix keys:
  loop: "closed" (``clients`` callers, each sends again when its reply
        is complete) or "open" (``rate_per_s``, Poisson);
  prompt_tokens / output_tokens: {"min", "max", "dist": "log-uniform"},
        and optionally "multiple_of": each length rounded to the nearest
        multiple of it (at least one);
  sizes_per_block: pairs per shuffled block;
  serve: the serving settings sized to this traffic (slots, page_size,
         prompt_buckets, decode_chunk, page_view_chunk); the page pool
         holds every slot's longest request;
  source: where the lengths come from (read by no code).
"""

from __future__ import annotations

import dataclasses
import json
import math
from typing import Iterator, List, Optional

import numpy as np


@dataclasses.dataclass
class Req:
    index: int                 # order in the stream
    prompt: np.ndarray         # (L,) int32
    max_new: int
    due_s: Optional[float]     # open loop: offset from the window start


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix["loop"] not in ("closed", "open"):
        raise ValueError(f"unknown loop {mix['loop']!r}")
    return mix


def _quantiles(spec: dict, n: int) -> List[int]:
    if spec.get("dist", "log-uniform") != "log-uniform":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    lo, hi = math.log(spec["min"]), math.log(spec["max"])
    q = [math.exp(lo + (i + 0.5) / n * (hi - lo)) for i in range(n)]
    k = spec.get("multiple_of", 1)
    return [max(k, k * int(round(x / k))) for x in q]


def size_block(mix: dict) -> List[tuple]:
    """The fixed (prompt, output) pairs of one block, same for every seed."""
    n = mix["sizes_per_block"]
    prompts = _quantiles(mix["prompt_tokens"], n)
    outs = _quantiles(mix["output_tokens"], n)
    pair = np.random.default_rng(0).permutation(n)
    return [(prompts[i], outs[int(pair[i])]) for i in range(n)]


def gap_block(mix: dict) -> List[float]:
    """The fixed inter-arrival gaps (s) of one block: exponential quantiles."""
    n = mix["sizes_per_block"]
    rate = float(mix["rate_per_s"])
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def stream(mix: dict, seed: int, vocab: int) -> Iterator[Req]:
    """Endless request stream of ``mix`` for ``seed``."""
    rng = np.random.default_rng(int(seed))
    sizes = size_block(mix)
    gaps = gap_block(mix) if mix["loop"] == "open" else None
    t, k = 0.0, 0
    while True:
        order = rng.permutation(len(sizes))
        gorder = rng.permutation(len(sizes))
        for j, i in enumerate(order):
            p, o = sizes[int(i)]
            due = None
            if gaps is not None:
                t += gaps[int(gorder[j])]
                due = t
            yield Req(index=k, prompt=rng.integers(0, vocab, size=p,
                                                   dtype=np.int32),
                      max_new=o, due_s=due)
            k += 1


def serve_settings(mix: dict) -> dict:
    """Cache geometry sized to the traffic: prompts pad to their bucket,
    and a slot holds the longest prompt plus the longest answer of the
    block plus one decode chunk of room."""
    s = dict(mix["serve"])
    b = s["prompt_buckets"]
    sizes = size_block(mix)
    s["prompt_pad"] = -(-max(p for p, _ in sizes) // b) * b
    s["max_new_tokens"] = max(o for _, o in sizes)
    s["max_len"] = (s["prompt_pad"] + s["max_new_tokens"]
                    + s["decode_chunk"])
    return s


def prompt_rows(settings: dict, prompt_len: int) -> int:
    """Rows a prompt occupies once left-padded to its bucket (the
    program's ``ServeConfig.prompt_rows`` rule for these settings)."""
    b = settings["prompt_buckets"]
    return min(settings["prompt_pad"], -(-max(prompt_len, 1) // b) * b)


def buckets(settings: dict, mix: dict) -> List[int]:
    """Every prompt bucket the mix's lengths land in."""
    return sorted({prompt_rows(settings, p) for p, _ in size_block(mix)})
