"""Open-loop chat sessions, generated from a seed.

The arrival gaps are the quantiles of an exponential (a Poisson process
at ``rate_per_s``), and the user-message and answer lengths the
quantiles of clipped lognormals, laid out in an order drawn once for
the traffic's parameters: every seed offers the same sizes at the same
times, and the seed draws the tokens, the weights and the sampling
seeds.  The order of arrivals sets how many rows decode at once, and
with it every latency, so a seed that reordered them would change the
work; the spread between runs then measures the system, not the draw.

Requests are laid out over ``lanes`` concurrent users.  Arrival slot
``i`` belongs to lane ``i % lanes``; a lane runs one session after
another, each of 1 to ``max_turns`` turns, on one of the shared system
prompts.  Turn ``k``'s prompt is the system prompt, the earlier turns'
user messages and answers (the answers are drawn tokens, so the prompt
is known before the run), and the new message.  A turn whose prompt
would pass ``max_prompt_tokens`` starts a new session instead.  A share
``greedy_share`` of the requests decodes greedily; the others sample at
``temperature`` with a seed of their own.

Due times are seconds relative to the opening of the measured window:
the first ``preroll_s`` seconds of traffic (negative due times) load
the server before it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Tuple

import numpy as np


@dataclass
class ChatRequest:
    rid: int
    due: float                  # seconds from the window's opening
    prompt: np.ndarray          # int32 token ids
    max_new: int
    temperature: float
    sample_seed: int
    session: int
    turn: int
    system: int                 # which shared system prompt

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lognormal_grid(n: int, median: float, sigma: float, lo: int,
                    hi: int) -> np.ndarray:
    from statistics import NormalDist

    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(int)


def generate(p: dict, seed: int, seconds: float, vocab: int
             ) -> List[ChatRequest]:
    """The requests due from ``-preroll_s`` to ``seconds``, in due order."""
    shape = np.random.default_rng(0x5E55)      # sizes and arrivals
    rng = np.random.default_rng([seed, 0x5E55])  # tokens and sampling
    rate = float(p["rate_per_s"])
    due, msg, out, greedy = [], [], [], []
    # the pre-roll and the window each get their own grids, so the
    # window always holds the same requests, whatever the seed
    for start, length in ((-float(p["preroll_s"]), float(p["preroll_s"])),
                          (0.0, float(seconds))):
        k = int(round(rate * length))
        if k == 0:
            continue
        gaps = shape.permutation(-np.log1p(-_quantiles(k)))
        gaps *= length / gaps.sum()
        due.append(start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]]))
        msg.append(shape.permutation(_lognormal_grid(k,
                                                   **p["message_tokens"])))
        out.append(shape.permutation(_lognormal_grid(k,
                                                   **p["output_tokens"])))
        g = np.zeros(k, bool)
        g[:int(round(p["greedy_share"] * k))] = True
        greedy.append(shape.permutation(g))
    due, msg, out, greedy = (np.concatenate(a)
                             for a in (due, msg, out, greedy))
    n = len(due)

    def tokens(k: int) -> np.ndarray:
        return rng.integers(0, vocab, size=k, dtype=np.int32)

    systems = [tokens(p["system_prompt_tokens"])
               for _ in range(p["system_prompts"])]
    lanes = int(p["lanes"])
    cap = int(p["max_prompt_tokens"])
    state = [None] * lanes  # per lane: [session, turn, system, history, turns]
    session_ids = 0
    reqs: List[ChatRequest] = []
    for i in range(n):
        lane = i % lanes
        st = state[lane]
        new_msg = tokens(int(msg[i]))
        if st is not None:
            prompt = np.concatenate([st[3], new_msg])
            if st[1] >= st[4] or len(prompt) > cap:
                st = None
        if st is None:
            sys_id = int(shape.integers(p["system_prompts"]))
            turns = int(shape.integers(1, p["max_turns"] + 1))
            st = [session_ids, 0, sys_id, systems[sys_id], turns]
            session_ids += 1
            prompt = np.concatenate([st[3], new_msg])
        temp = 0.0 if greedy[i] else float(p["temperature"])
        reqs.append(ChatRequest(
            rid=i, due=float(due[i]), prompt=prompt.astype(np.int32),
            max_new=int(out[i]), temperature=temp,
            sample_seed=int(rng.integers(2 ** 31)), session=st[0],
            turn=st[1], system=st[2]))
        # the next turn carries this turn's message and a drawn answer
        st[3] = np.concatenate([prompt, tokens(int(out[i]))])
        st[1] += 1
        state[lane] = st
    return reqs


def bounds(p: dict) -> Tuple[int, int, int]:
    """The shortest and longest prompt, and the longest prompt plus its
    answer, that the parameters allow (every seed stays inside them)."""
    shortest = p["system_prompt_tokens"] + p["message_tokens"]["lo"]
    longest = max(p["max_prompt_tokens"],
                  p["system_prompt_tokens"] + p["message_tokens"]["hi"])
    return shortest, longest, longest + p["output_tokens"]["hi"]


def summary(reqs: List[ChatRequest]) -> dict:
    """Sizes of a generated mix, for the log."""
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new for r in reqs])
    return {"requests": len(reqs),
            "in_window": sum(r.due >= 0 for r in reqs),
            "prompt_mean": float(p.mean()), "prompt_max": int(p.max()),
            "output_mean": float(o.mean()), "output_max": int(o.max()),
            "greedy": sum(r.greedy for r in reqs),
            "sessions": len({r.session for r in reqs})}
