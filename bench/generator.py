"""The one generator of request traffic: a mix's parameters (a file in
``traffic/``) and a seed in, requests out.

Every seed gets the same sizes in the same proportions: the mix is dealt
in cycles of ``sum(mix)`` requests, each cycle holding exactly ``mix[j]``
prompts of ``prompt_lengths[j]`` tokens in an order drawn from the seed.
Token ids are drawn from the seed too, uniform over the vocabulary.  So two
seeds differ in data and order, never in the amount of work.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Iterator

import numpy as np

REQUEST_KEYS = {"kind", "loop", "clients", "batch", "prompt_lengths", "mix",
                "token_ids", "max_seq_multiple"}


@dataclass
class Request:
    index: int
    #: prompt tokens, ``(batch, length)`` int32
    tokens: np.ndarray
    #: the KV cache length the prompt is served with
    max_seq: int

    @property
    def length(self) -> int:
        return int(self.tokens.shape[1])


def check_requests(traffic: Dict[str, Any], name: str) -> None:
    """Refuse a mix this generator would not honour."""
    unknown = set(traffic) - REQUEST_KEYS
    problems = []
    if traffic.get("kind") != "requests":
        problems.append(f"kind {traffic.get('kind')!r}")
    if unknown:
        problems.append(f"unknown keys {sorted(unknown)}")
    if traffic.get("loop") != "closed" or traffic.get("clients") != 1:
        problems.append("only a closed loop of one client is generated")
    if traffic.get("token_ids", "uniform") != "uniform":
        problems.append(f"token_ids {traffic.get('token_ids')!r}")
    if len(traffic["prompt_lengths"]) != len(traffic["mix"]):
        problems.append("prompt_lengths and mix differ in length")
    if problems:
        raise ValueError(f"traffic {name}: " + "; ".join(problems))


def cycle_length(traffic: Dict[str, Any]) -> int:
    """Requests in one cycle of the mix."""
    return int(sum(traffic["mix"]))


def _entropy(seed: int) -> int:
    return int(seed) % (1 << 64)


def requests(traffic: Dict[str, Any], seed: int, vocab: int,
             stream: int = 0) -> Iterator[Request]:
    """Endless requests of the mix; ``stream`` 1 gives the warm-up's,
    which share no tokens with the measured stream 0."""
    lengths = [int(s) for s in traffic["prompt_lengths"]]
    cycle = [s for s, m in zip(lengths, traffic["mix"]) for _ in range(m)]
    batch = int(traffic.get("batch", 1))
    multiple = int(traffic.get("max_seq_multiple", 1))
    base = _entropy(seed)
    index, c = 0, 0
    while True:
        order = np.random.default_rng([base, stream, c]).permutation(
            len(cycle))
        for j in order:
            s = cycle[j]
            rng = np.random.default_rng([base, stream, c, int(j) + 1])
            tokens = rng.integers(0, vocab, (batch, s), dtype=np.int32)
            yield Request(index, tokens, -(-s // multiple) * multiple)
            index += 1
        c += 1
