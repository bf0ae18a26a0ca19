"""The one traffic generator: reads a mix's data file and makes the
requests of one run from ``--seed``.

An ``open_loop`` mix is chat requests due on a fixed schedule, whatever
the server does.  The arrival times and the request sizes, in order,
come from the mix's own ``schedule_seed``; ``--seed`` draws every token
id (and the weights), so every seed carries the same work and the same
bursts.  (Dealing the sizes to the slots in a seeded order made runs of
different seeds differ by 20% in the TTFT and ITL tails, against a few
percent for two runs of one seed: with some fifty requests in a window,
the order is the work.)
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass
class ChatRequest:
    rid: int
    due_s: float
    prompt: List[int]
    max_new: int
    system: int  # which shared system prompt


def _gaps(rng, n: int, rate: float, cv: float) -> np.ndarray:
    """Gamma inter-arrival gaps of mean ``1 / rate`` and the given CV."""
    shape = 1.0 / (cv * cv)
    return rng.gamma(shape, 1.0 / (rate * shape), n)


def _output_lengths(rng, n: int, spec: dict) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def chat_requests(mix: dict, rate_rps: float, seconds: float, seed: int,
                  vocab: int) -> List[ChatRequest]:
    """The requests due in ``[0, seconds)`` at ``rate_rps``."""
    base = np.random.default_rng(mix["schedule_seed"])
    n_max = int(rate_rps * seconds * 4) + 64
    due = np.cumsum(_gaps(base, n_max, rate_rps, mix["arrival"]["cv"]))
    due -= due[0]  # the first request is due when the window opens
    n = int(np.searchsorted(due, seconds))
    sys_p = np.asarray(mix["system_prompts"]["zipf_weights"], float)
    sys_p /= sys_p.sum()
    system = base.choice(len(sys_p), n_max, p=sys_p)[:n]
    user = base.choice(mix["user_turn"]["lengths"], n_max)[:n]
    out = _output_lengths(base, n_max, mix["output"])[:n]

    rng = np.random.default_rng(seed)
    sys_len = mix["system_prompts"]["lengths"]
    sys_tok = [rng.integers(0, vocab, sys_len[i]).tolist()
               for i in range(len(sys_len))]
    reqs = []
    for j in range(n):
        s = int(system[j])
        prompt = sys_tok[s] + rng.integers(0, vocab, int(user[j])).tolist()
        reqs.append(ChatRequest(rid=j, due_s=float(due[j]), prompt=prompt,
                                max_new=int(out[j]), system=s))
    return reqs


def prompt_lengths(mix: dict) -> List[int]:
    """Every prompt length the mix can send (one prefill shape each)."""
    return sorted({a + b for a in mix["system_prompts"]["lengths"]
                   for b in mix["user_turn"]["lengths"]})
