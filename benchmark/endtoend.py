"""From per-request records to the end-to-end metrics.

The window is ``[t_open, t_close)`` on the parent's monotonic clock. A tail
is the tail of all requests due in the window; a rate is all tokens
received in it over its length. Nothing is trimmed.
"""

from __future__ import annotations

from .client import ABORTED, FAILED


def percentile(values: list, q: float) -> float:
    """Linear interpolation between closest ranks, ``q`` in [0, 100]."""
    if not values:
        raise ValueError("percentile of no values")
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def due_in(records: list, t_open: float, t_close: float) -> list:
    return [r for r in records if t_open <= r.due < t_close]


def failed_in(records: list, t_open: float, t_close: float,
              unanswered_fail: bool) -> list:
    """The requests due in the window that failed, were refused or came
    back short or wrong. A stream cut by the stop is not a failure, since
    nothing is drained; but with ``unanswered_fail`` (the open loop, whose
    load runs on past the window so that every request due in it can be
    answered) one that had no first token by then is: it would otherwise be
    missing from the tail."""
    return [r for r in due_in(records, t_open, t_close) if r.status == FAILED
            or (unanswered_fail and r.status in (ABORTED, "") and not r.first)]


def failures(records: list, t_open: float, t_close: float,
             unanswered_fail: bool) -> tuple:
    """``(attempted, failed)``: how many requests were due in the window,
    and how many of them ``failed_in`` names."""
    return (len(due_in(records, t_open, t_close)),
            len(failed_in(records, t_open, t_close, unanswered_fail)))


def ttft_ms(records: list, t_open: float, t_close: float) -> list:
    """First token received minus due time, requests due in the window."""
    return [(r.first - r.due) * 1e3
            for r in due_in(records, t_open, t_close) if r.first]


def tpot_ms(records: list, t_open: float, t_close: float) -> list:
    """Per request that completed in the window: (last token time - first
    token time) / (tokens - 1). Tokens arrive in clumps of one burst, so a
    per-gap median would read about zero."""
    out = []
    for r in records:
        if r.status == "ok" and t_open <= r.done < t_close and r.n_tokens > 1:
            out.append((r.spans[-1][0] - r.first) * 1e3 / (r.n_tokens - 1))
    return out


def tokens_in(records: list, t_open: float, t_close: float) -> int:
    """Output tokens whose receive time falls in the window: a request
    astride an edge counts for the part inside."""
    return sum(n for r in records for t, n in r.spans if t_open <= t < t_close)


def live_positions(records: list, t: float) -> tuple:
    """``(requests holding a lane, their cached positions summed)`` at time
    ``t``, as the client can know it: a request holds a lane from its first
    token to its last, at prompt + tokens received so far."""
    lanes = positions = 0
    for r in records:
        if r.first and r.first <= t and r.spans[-1][0] >= t:
            lanes += 1
            positions += r.prompt_len + sum(n for ts, n in r.spans if ts <= t)
    return lanes, positions


def compute(name: str, records: list, t_open: float, t_close: float) -> float:
    """One end-to-end metric by name (``setup_s`` is the caller's)."""
    if name.startswith("ttft_p") and name.endswith("_ms"):
        return percentile(ttft_ms(records, t_open, t_close), float(name[6:-3]))
    if name.startswith("tpot_p") and name.endswith("_ms"):
        return percentile(tpot_ms(records, t_open, t_close), float(name[6:-3]))
    if name == "tokens_per_s":
        return tokens_in(records, t_open, t_close) / (t_close - t_open)
    raise ValueError(f"no end-to-end metric {name!r}")
