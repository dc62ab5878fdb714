"""Traffic generator: one general generator over the mixes in ``traffic/``.

A mix file fixes the loop (``open``: Poisson arrivals at ``rate_per_s``;
``closed``: ``clients`` that each send their next request when the last
one finished), and the lognormal prompt and output length distributions,
each with its median, ``sigma`` and clip.  A mix may name a ``base`` mix
and override some of its keys.

Every seed gets the same work.  The lengths are the distribution's
quantiles at evenly spaced points and the Poisson gaps the
exponential's, so the multiset of prompt lengths, of output lengths and
of gaps is the same for every seed (for a closed loop, in every round
of one request per client).  In an open loop the seed orders and pairs
them; two seeds then differ in the order of the work, not in its
amount.  A closed loop's window holds only a few rounds, and there the
order is the work: which requests run side by side, and how many
prompts are admitted in the window.  So a closed mix states ``deal``,
the number its order and pairing of lengths are drawn from, the same
for every seed.  The seed always draws the token ids.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np

FIRST_TOKEN_ID = 2          # ids 0 and 1 are left out of generated prompts


@dataclasses.dataclass
class Spec:
    """One request as the generator makes it."""

    rid: int
    prompt: np.ndarray      # (P,) int32
    max_new: int
    due_s: float = 0.0      # open loop: offset of its due time in the window
    client: int = 0         # closed loop: the client that sends it


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 1/2) / n of the clipped
    lognormal ``dist`` (keys ``median``, ``sigma``, ``min``, ``max``)."""
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    x = dist["median"] * np.exp(dist["sigma"] * np.asarray(z))
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def quantile_gaps(rate_per_s: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps at the quantiles (i + 1/2) / n of the
    exponential distribution with mean ``1 / rate_per_s``."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate_per_s


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, stream])


def n_open_requests(mix: dict, seconds: float) -> int:
    """Requests an open-loop window of ``seconds`` holds: as many as fit
    whole, so that the last one is due before the window closes for
    every seed (the sum of the gaps does not depend on their order)."""
    n = max(1, int(mix["rate_per_s"] * seconds))
    while n > 1 and quantile_gaps(mix["rate_per_s"], n).sum() >= seconds:
        n -= 1
    return n


def make_requests(mix: dict, *, seed: int, seconds: float,
                  vocab: int) -> list[Spec]:
    """The run's requests, in the order they are sent (open loop) or in
    each client's order (closed loop).

    A closed loop sends as many requests as the window lets it, so its
    work is fixed round by round: round r gives each of the ``clients``
    one request, and every round holds the same lengths (the
    distributions' quantiles at ``clients`` points), dealt to the
    clients in an order drawn from the mix's ``deal``, not the seed."""
    if mix["loop"] == "open":
        order = seed_rng(seed, 0)
        n = n_open_requests(mix, seconds)
        prompt_len = order.permutation(quantile_lengths(mix["prompt"], n))
        output_len = order.permutation(quantile_lengths(mix["output"], n))
    elif mix["loop"] == "closed":
        if "deal" not in mix:
            raise ValueError("a closed-loop mix states its 'deal', the "
                             "number its order of lengths is drawn from")
        order = seed_rng(int(mix["deal"]), 0)
        c = int(mix["clients"])
        n = int(mix["requests"]) // c * c
        p_round = quantile_lengths(mix["prompt"], c)
        o_round = quantile_lengths(mix["output"], c)
        prompt_len = np.concatenate([order.permutation(p_round)
                                     for _ in range(n // c)])
        output_len = np.concatenate([order.permutation(o_round)
                                     for _ in range(n // c)])
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    tokens = seed_rng(seed, 1)
    specs = [Spec(rid=i,
                  prompt=tokens.integers(FIRST_TOKEN_ID, vocab,
                                         size=int(p)).astype(np.int32),
                  max_new=int(m))
             for i, (p, m) in enumerate(zip(prompt_len, output_len))]
    if mix["loop"] == "open":
        gaps = order.permutation(quantile_gaps(mix["rate_per_s"], n))
        for spec, t in zip(specs, np.cumsum(gaps)):
            spec.due_s = float(t)
    else:
        for spec in specs:
            spec.client = spec.rid % c
    return specs


def describe(mix: dict) -> str:
    """One line naming the mix, for the run's log."""
    load = (f"Poisson {mix['rate_per_s']}/s" if mix["loop"] == "open"
            else f"{mix['clients']} closed-loop clients")
    p, o = mix["prompt"], mix["output"]
    return (f"{load}; prompt lognormal median {p['median']} sigma "
            f"{p['sigma']} in [{p['min']}, {p['max']}]; output median "
            f"{o['median']} sigma {o['sigma']} in [{o['min']}, {o['max']}]")

