"""The one general traffic generator. A traffic mix is a data file under
``traffic/`` and nothing else: this module reads its parameters and makes the
inputs from ``--seed``.

What the seed decides and what it does not. The *sizes* of a mix (prompt and
output lengths, the gaps between arrivals) are one fixed sequence drawn from
the mix's own ``sizes_seed``, stratified so that every run of ``block``
requests holds every stratum of both length distributions. ``--seed`` draws
the tokens (and, in the runners, the weights) and nothing else: every seed
gives the system the same requests of the same lengths at the same times, so
what differs between two runs is the system and not the sample. (Starting the
sequence at a seeded place was tried: between two seeds it moved the 95th
percentile of the time to first token by 9 % and saturated tokens per second
by 1 %, more than any bound could have covered; my chip runs, PR 23.)

Training mixes (``task``): ``seqcls`` is MRPC-shaped sequence classification
(a keyword planted at positions 1-4 decides the label, as in
``examples/nlp_example.py``); ``clm`` is causal language modelling on uniform
random tokens. Every batch has the same shape.
"""

from __future__ import annotations

import dataclasses

import numpy as np


def _rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed) % (2**63))


# ------------------------------------------------------------------ training


def train_rows(mix: dict, vocab_size: int, seed: int) -> dict:
    """``global_batch * n_batches`` rows of the mix's task as numpy arrays."""
    n = int(mix["global_batch"]) * int(mix["n_batches"])
    seq_len = int(mix["seq_len"])
    rng = _rng(seed)
    if mix["task"] == "clm":
        return {"input_ids": rng.integers(0, vocab_size, (n, seq_len), dtype=np.int32)}
    if mix["task"] != "seqcls":
        raise ValueError(f"unknown training task {mix['task']!r}")
    ids = rng.integers(10, vocab_size, (n, seq_len), dtype=np.int32)
    keywords = rng.integers(2, 10, n, dtype=np.int32)
    ids[:, 1:5] = keywords[:, None]
    ids[:, 0] = 1  # [CLS]
    half = seq_len // 2
    token_type = np.zeros((n, seq_len), np.int32)
    token_type[:, half:] = 1
    return {
        "input_ids": ids,
        "token_type_ids": token_type,
        "attention_mask": np.ones((n, seq_len), np.int32),
        "labels": (keywords >= 6).astype(np.int32),
    }


class Rows:
    """A map-style dataset over :func:`train_rows` (``__len__``/``__getitem__``
    is all the program's ``DataLoader`` asks for)."""

    def __init__(self, data: dict):
        self.data = data

    def __len__(self) -> int:
        return len(next(iter(self.data.values())))

    def __getitem__(self, i):
        return {k: v[i] for k, v in self.data.items()}


# ------------------------------------------------------------------- serving


@dataclasses.dataclass
class RequestSpec:
    due_s: float          # when the request is due, from the start of the window
    prompt: np.ndarray    # int32 token ids
    max_new_tokens: int


def _log_uniform(u: np.ndarray, lo: int, hi: int) -> np.ndarray:
    return np.rint(lo * (hi / lo) ** u).astype(np.int64)


def _stratified(rng, n: int, block: int) -> np.ndarray:
    """``n`` numbers in [0, 1): each run of ``block`` holds one from every
    ``1/block`` stratum, in an order of its own. A longer sequence starts
    with the shorter one."""
    blocks = -(-n // block)
    u = [(rng.permutation(block) + rng.random(block)) / block for _ in range(blocks)]
    return np.concatenate(u)[:n]


def request_sizes(mix: dict, n: int) -> tuple[np.ndarray, np.ndarray]:
    """The mix's fixed sequence of (prompt length, output length), seed-free."""
    sizes_seed, block = int(mix.get("sizes_seed", 0)), int(mix.get("block", 16))
    prompt = _log_uniform(_stratified(_rng(sizes_seed), n, block), *mix["prompt_len"])
    output = _log_uniform(_stratified(_rng(sizes_seed + 1), n, block), *mix["output_len"])
    return prompt, output


def arrival_gaps(mix: dict, n: int) -> np.ndarray:
    """The mix's fixed sequence of gaps between arrivals, in seconds: all zero
    for ``arrival.kind == "at_zero"``; for ``"open_loop"`` gamma-distributed
    with mean ``1/rate_per_s`` and coefficient of variation ``cv`` (1, the
    default, is a Poisson process; above 1 is bursty), scaled so that the
    sample's own mean is exactly ``1/rate_per_s``."""
    arrival = mix["arrival"]
    if arrival["kind"] == "at_zero":
        return np.zeros(n)
    if arrival["kind"] != "open_loop":
        raise ValueError(f"unknown arrival kind {arrival['kind']!r}")
    rate, cv = float(arrival["rate_per_s"]), float(arrival.get("cv", 1.0))
    rng = _rng(int(mix.get("sizes_seed", 0)) + 2)
    gaps = rng.gamma(shape=1.0 / cv**2, scale=cv**2, size=n)
    return gaps / gaps.mean() / rate


def n_requests(mix: dict, horizon_s: float) -> int:
    arrival = mix["arrival"]
    if arrival["kind"] == "at_zero":
        return int(arrival["n_requests"])
    return int(np.ceil(float(arrival["rate_per_s"]) * horizon_s)) + 1


def requests(mix: dict, vocab_size: int, seed: int, horizon_s: float) -> list:
    """The requests of one run, in the order in which they are due. An
    optional ``shared_prefix`` (``{"tokens": t, "groups": g}``) puts one of
    ``g`` seeded prefixes of ``t`` tokens, taken in turn, before every prompt
    (``prompt_len`` is then the private part); ``repeats`` sends each prompt
    that many times in a row."""
    repeats = int(mix.get("repeats", 1))
    n = n_requests(mix, horizon_s)
    distinct = -(-n // repeats)
    prompt_len, output_len = request_sizes(mix, distinct)
    gaps = arrival_gaps(mix, n)
    due = np.cumsum(gaps) - gaps[0]

    rng = _rng(seed)
    shared = mix.get("shared_prefix")
    prefixes = None
    if shared:
        prefixes = rng.integers(0, vocab_size, (int(shared["groups"]), int(shared["tokens"])),
                                dtype=np.int32)
    out = []
    for i in range(distinct):
        prompt = rng.integers(0, vocab_size, int(prompt_len[i]), dtype=np.int32)
        if prefixes is not None:
            prompt = np.concatenate([prefixes[i % len(prefixes)], prompt])
        for r in range(repeats):
            j = i * repeats + r
            if j < n:
                out.append(RequestSpec(float(due[j]), prompt, int(output_len[i])))
    return out
