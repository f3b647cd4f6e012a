"""The one traffic generator: multi-turn chat sessions from a parameter file.

A mix file (traffic/<mix>.json, overlaid by the cell's own file) gives the
shape of the traffic: shared system templates, turns per session, lognormal
user and output lengths, exponential think times, and the session start
rate. This module turns those numbers and `--seed` into a `Schedule`. The
program sees only the requests built from it.

Two random streams, so that every seed does the same work:

  population  drawn from the mix's fixed `population_seed`: the Poisson
              session start times and each session's sizes (user
              lengths, output lengths, think times). Every seed gets the
              same arrivals and the same sizes, in the same order.
  seed        `--seed`: which template and region each session takes, and
              every token id (the weights are the seed's too).

Time 0 is the start of the measured window. Sessions start over
[-history_s, seconds). The open loop itself starts at -ramp_s. A session
that started earlier is placed by a virtual clock (each turn takes
`est_ttft_s + out_len * est_itl_s`, then the think time): the turns it
finished before -ramp_s become its history, with output tokens drawn from
the seed, and its next turn is submitted when due, at once if it was
already running at -ramp_s. Set-up prefills the histories, so the prefix
cache holds, and the engine serves, what a running deployment would.

A session ends early where its next turn would pass the engine's
`max_seq_len`, so no request is ever refused for its length.
"""
from __future__ import annotations

import dataclasses

import numpy as np

TEMPLATE_STREAM, SESSION_STREAM = 0, 1


def _rng(*words: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([w % 2**64 for w in words]))


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


@dataclasses.dataclass
class Turn:
    user: tuple           # the user message's token ids
    out_len: int          # exact output length (max_new_tokens, no stop)
    think_s: float        # think time after this turn's last token


@dataclasses.dataclass
class Session:
    sid: int
    region: str
    template: int
    turns: list           # [Turn]
    history: list         # synthesized outputs of turns [0, live)
    live: int             # first turn served for real
    first_due: float      # due time of turn `live` (s, window start = 0;
                          # before -ramp_s: running when the ramp starts)

    def prompt(self, k: int, templates: list, outputs: dict) -> tuple:
        """Turn k's prompt: template, then every earlier turn's user
        message and output (history or what the engine returned)."""
        toks = list(templates[self.template])
        for j in range(k):
            toks += self.turns[j].user
            toks += self.history[j] if j < self.live else outputs[j]
        toks += self.turns[k].user
        return tuple(toks)


@dataclasses.dataclass
class Schedule:
    templates: list       # [tuple] shared system prompts
    sessions: list        # [Session] with at least one live turn
    ramp_s: float         # the open loop starts at -ramp_s

    def warm_prompts(self) -> list:
        """(region, prompt) prefilled during set-up: each template once,
        then each running session's history up to its first live turn."""
        out = [(r, t) for t in self.templates
               for r in sorted({s.region for s in self.sessions})]
        for s in self.sessions:
            if s.live:
                hist = list(self.templates[s.template])
                for j in range(s.live):
                    hist += s.turns[j].user
                    hist += s.history[j]
                out.append((s.region, tuple(hist)))
        return out


def _profiles(params: dict, n: int, rng) -> list:
    T = int(params["turns"])
    users = _lognormal(rng, params["user_len"], n * T).reshape(n, T)
    outs = _lognormal(rng, params["output_len"], n * T).reshape(n, T)
    thinks = rng.exponential(params["think_s_mean"], n * T).reshape(n, T)
    return [(users[i], outs[i], thinks[i]) for i in range(n)]


def _fit(template_len: int, users, outs, max_seq_len: int) -> int:
    """Turns that fit: a turn's prompt plus its output stays within
    max_seq_len."""
    total = template_len
    for k in range(len(users)):
        total += int(users[k]) + int(outs[k])
        if total > max_seq_len:
            return k
    return len(users)


def build_schedule(params: dict, *, seed: int, seconds: float, vocab: int,
                   max_seq_len: int) -> Schedule:
    rate = float(params["rate_sessions_s"])
    H, ramp = float(params["history_s"]), float(params["ramp_s"])
    pop = _rng(int(params["population_seed"]))
    starts, t = [], -H
    while True:
        t += pop.exponential(1.0 / rate)
        if t >= seconds:
            break
        starts.append(t)
    profiles = _profiles(params, len(starts), pop)

    tok_rng = _rng(seed, TEMPLATE_STREAM)
    L = int(params["template_len"])
    templates = [tuple(int(x) for x in tok_rng.integers(1, vocab, L))
                 for _ in range(int(params["templates"]))]
    share = params.get("arrival_share", {"us": 1.0})
    regions, weights = list(share), np.asarray(list(share.values()), float)

    ttft, itl = float(params["est_ttft_s"]), float(params["est_itl_s"])
    sessions = []
    for sid, start in enumerate(starts):
        users, outs, thinks = profiles[sid]
        n_turns = _fit(L, users, outs, max_seq_len)
        rng = _rng(seed, SESSION_STREAM, sid)
        template = int(rng.integers(len(templates)))
        region = regions[int(rng.choice(len(regions), p=weights / weights.sum()))]
        turns = [Turn(tuple(int(x) for x in rng.integers(1, vocab, users[k])),
                      int(outs[k]), float(thinks[k])) for k in range(n_turns)]
        due, live = start, 0
        while live < n_turns:
            end = due + ttft + turns[live].out_len * itl
            if end >= -ramp:            # running at the ramp's start, or due
                break                   # after it
            due = end + turns[live].think_s
            live += 1
        if live == n_turns or due >= seconds:
            continue                    # finished before, or due after
        history = [tuple(int(x) for x in rng.integers(1, vocab,
                                                      turns[j].out_len))
                   for j in range(live)]
        sessions.append(Session(sid, region, template, turns, history,
                                live, due))
    return Schedule(templates, sessions, ramp)
