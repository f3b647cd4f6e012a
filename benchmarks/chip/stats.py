"""End-to-end arithmetic over the client's records.

Every time here is on the host's monotonic clock, relative to the start of
the measured window. A request is timed from when it was due, not from
when it was submitted: a stall that delays later submissions counts
against them.
"""
from __future__ import annotations

import dataclasses


def pct(xs: list, p: float) -> float:
    """Nearest-rank percentile (the arithmetic of repro.core.metrics.pct)."""
    if not xs:
        return float("nan")
    xs = sorted(xs)
    k = min(len(xs) - 1, max(0, int(round(p / 100 * (len(xs) - 1)))))
    return xs[k]


@dataclasses.dataclass
class Record:
    """One turn as the client saw it."""
    sid: int
    turn: int
    due: float                  # when the turn was due
    submitted: float            # when the client submitted it
    max_new: int
    prompt_len: int = 0
    times: list = dataclasses.field(default_factory=list)   # per token
    tokens: list = dataclasses.field(default_factory=list)
    state: str = "queued"
    done_at: float | None = None

    @property
    def finished(self) -> bool:
        return self.state == "finished" and len(self.tokens) == self.max_new


def in_window(records: list, seconds: float) -> list:
    return [r for r in records if 0.0 <= r.due < seconds]


def ttfts_ms(records: list, horizon: float) -> list:
    """Due to first token. A request with none by `horizon` (the end of
    the drain) counts as having waited until then."""
    return [1e3 * ((r.times[0] if r.times else horizon) - r.due)
            for r in records]


def gaps_ms(records: list) -> list:
    """Every gap between consecutive tokens, pooled over requests."""
    return [1e3 * (b - a) for r in records
            for a, b in zip(r.times, r.times[1:])]


def pace(records: list, seconds: float, horizon: float) -> dict:
    """The readings a cell's virtual clock (`est_ttft_s`, `est_itl_s`) is
    set from, and the output tokens of the requests due in the window per
    second, against which the sweep compares the tokens delivered."""
    timed = in_window(records, seconds)
    gaps = gaps_ms(timed)
    return {"ttft_p50_ms": pct(ttfts_ms(timed, horizon), 50),
            "itl_p50_ms": pct(gaps, 50),
            "itl_mean_ms": sum(gaps) / len(gaps) if gaps else float("nan"),
            "due_tok_s": sum(r.max_new for r in timed) / seconds}


def output_tokens_in(records: list, seconds: float) -> int:
    return sum(1 for r in records for t in r.times if 0.0 <= t < seconds)


def end_to_end(records: list, seconds: float, horizon: float) -> dict:
    timed = in_window(records, seconds)
    ttft = ttfts_ms(timed, horizon)
    return {
        "ttft_p50_ms": pct(ttft, 50),
        "ttft_p90_ms": pct(ttft, 90),
        "itl_p95_ms": pct(gaps_ms(timed), 95),
        "output_tok_s": output_tokens_in(records, seconds) / seconds,
    }
