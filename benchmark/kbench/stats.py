"""Metric arithmetic on recorded client-side timings.

Every judged number is taken at the load generator's own socket: a record
holds, per request, when it was due, when it was sent and when each output
token's SSE chunk arrived, all in seconds from the window's opening.  The
window is [0, seconds).
"""

import math
from dataclasses import dataclass, field
from typing import List, Optional


@dataclass
class Record:
    index: int
    phase: str  # ramp | window | cooldown | closed
    prompt_len: int
    output_len: int  # max_tokens asked
    due_s: Optional[float] = None  # open loop
    sent_s: Optional[float] = None
    token_times: List[float] = field(default_factory=list)
    token_ids: List[Optional[int]] = field(default_factory=list)
    finish_reason: Optional[str] = None
    done: bool = False  # the stream ended with [DONE]
    error: Optional[str] = None
    client: int = -1

    @property
    def start_s(self) -> Optional[float]:
        """What TTFT is timed from: the due time in an open loop (so a
        stall's wait on later requests counts), the send time in a closed
        one."""
        return self.due_s if self.due_s is not None else self.sent_s


def percentile(values, q: float) -> Optional[float]:
    """Linear-interpolated percentile (q in 0..100); None on no samples."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def mean(values) -> Optional[float]:
    values = list(values)
    return sum(values) / len(values) if values else None


def attempted(records, seconds: float) -> List[Record]:
    """Requests due (open loop) or started (closed loop) inside the window."""
    out = []
    for r in records:
        if r.due_s is not None:
            if r.phase == "window":
                out.append(r)
        elif r.sent_s is not None and 0.0 <= r.sent_s < seconds:
            out.append(r)
    return out


def is_failed(r: Record) -> bool:
    """Failed, refused, timed out, or left without a first token when the
    open loop's generator stopped (`error` says which); or ended with the
    wrong count.  A stream the generator cut off while its tokens were
    arriving has not failed: it is judged on what it received."""
    if r.error is not None:
        return True
    return r.done and (len(r.token_times) != r.output_len
                       or r.finish_reason != "length")


def ttfts_ms(records, seconds: float) -> List[float]:
    return [
        (r.token_times[0] - r.start_s) * 1e3
        for r in attempted(records, seconds)
        if r.token_times and r.error is None]


def _in_window(r: Record, seconds: float) -> List[float]:
    return [t for t in r.token_times if 0.0 <= t < seconds]


def request_gaps_ms(records, seconds: float, min_gaps: int = 16) -> List[float]:
    """Per request, the mean gap between the output tokens it received
    inside the window; requests with fewer than `min_gaps` gaps there are
    left out (8-token bursts quantise a short request's mean)."""
    out = []
    for r in records:
        ts = _in_window(r, seconds)
        if len(ts) - 1 >= min_gaps:
            out.append((ts[-1] - ts[0]) / (len(ts) - 1) * 1e3)
    return out


def pooled_gaps_ms(records, seconds: float) -> List[float]:
    """Every single gap between consecutive output tokens of one request,
    both received inside the window, all requests pooled."""
    out = []
    for r in records:
        ts = _in_window(r, seconds)
        out.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:]))
    return out


def tokens_in_window(records, seconds: float) -> int:
    return sum(len(_in_window(r, seconds)) for r in records)


def late_ms(records, seconds: float) -> List[float]:
    return [
        (r.sent_s - r.due_s) * 1e3 for r in attempted(records, seconds)
        if r.due_s is not None and r.sent_s is not None]


def slo_share(records, seconds: float, limits: dict) -> Optional[float]:
    """Percent of attempted requests that met the mix's TTFT limit and its
    limit on the request's mean gap.  A failed request misses (one with no
    token when the generator stopped is failed).  One the generator cut off
    in mid-stream is judged on what it had received."""
    tried = attempted(records, seconds)
    if not tried:
        return None
    met = 0
    for r in tried:
        if is_failed(r) or not r.token_times:
            continue
        ttft = (r.token_times[0] - r.start_s) * 1e3
        n = len(r.token_times)
        gap = ((r.token_times[-1] - r.token_times[0]) / (n - 1) * 1e3
               if n > 1 else 0.0)
        if ttft <= limits["ttft_ms"] and gap <= limits["tpot_ms"]:
            met += 1
    return 100.0 * met / len(tried)


def end_to_end(records, seconds: float, chips: int) -> dict:
    """The client-side end-to-end numbers, each with its sample count."""
    ttft = ttfts_ms(records, seconds)
    per_request = request_gaps_ms(records, seconds)
    pooled = pooled_gaps_ms(records, seconds)
    tokens = tokens_in_window(records, seconds)
    return {
        "ttft_mean_ms": (mean(ttft), len(ttft)),
        "ttft_p50_ms": (percentile(ttft, 50), len(ttft)),
        "ttft_p95_ms": (percentile(ttft, 95), len(ttft)),
        "ttft_max_ms": (max(ttft, default=None), len(ttft)),
        "tpot_p50_ms": (percentile(per_request, 50), len(per_request)),
        "tpot_p95_ms": (percentile(per_request, 95), len(per_request)),
        "itl_p99_ms": (percentile(pooled, 99), len(pooled)),
        # the longest pause any reader saw: a stall of the whole server
        "itl_max_ms": (max(pooled, default=None), len(pooled)),
        "output_tok_s": (tokens / seconds / chips, tokens),
    }
