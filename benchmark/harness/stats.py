"""From the load generator's records to end-to-end numbers.  Standard
library only.

What counts:

* a request FAILED unless it returned HTTP 200, ended with ``[DONE]`` and
  delivered exactly its ``max_tokens`` tokens;
* open loop: the population is every request DUE inside the window (one
  that has not ended when the drain gives up has failed); times run from
  when the request was due, so a late generator or a stalled server shows;
* closed loop: the population is every request that ENDED inside the
  window; what is in flight when the window closes was cut, not failed;
* ``out_tok_s`` counts output tokens whose SSE event arrived inside the
  window, whichever request they belong to, over the window's length.
"""

from __future__ import annotations

import math
import re


def percentile(values, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def tokens_of(rec: dict) -> int:
    return sum(n for _, n in rec["events"])


def failed(rec: dict) -> bool:
    return not (rec.get("status") == 200 and rec.get("done")
                and not rec.get("error") and tokens_of(rec) == rec["want"])


def ttft_ms(rec: dict) -> float:
    return (rec["events"][0][0] - rec["due"]) * 1e3


def tpot_ms(rec: dict):
    """(last token - first token) / (tokens - 1): the mean gap a reader
    sees.  The engine streams a fused window of tokens at once, so single
    gaps are either ~0 or one window long."""
    n = tokens_of(rec)
    if n < 2:
        return None
    return (rec["events"][-1][0] - rec["events"][0][0]) * 1e3 / (n - 1)


def max_gap_ms(rec: dict):
    ts = [t for t, _ in rec["events"]]
    if len(ts) < 2:
        return None
    return max(b - a for a, b in zip(ts, ts[1:])) * 1e3


def summarize(records: list, loop: str, t_window: float, t_end: float) -> dict:
    """Everything the end-to-end metrics and the earlier lines need."""
    if loop == "open":
        population = [r for r in records if r["phase"] == "window"]
    else:
        population = [r for r in records if not r.get("cut")
                      and t_window <= r.get("end", math.inf) < t_end]
    ok = [r for r in population if not failed(r)]
    tokens_in_window = sum(n for r in records for t, n in r["events"]
                           if t_window <= t < t_end)
    sent = [r for r in records if "sent" in r
            and t_window <= r["due"] < t_end]
    late = [(r["sent"] - r["due"]) * 1e3 for r in sent]
    tpots = [x for x in map(tpot_ms, ok) if x is not None]
    gaps = [x for x in map(max_gap_ms, ok) if x is not None]
    return {
        "loop": loop, "seconds": t_end - t_window,
        "attempted": len(population), "failed": len(population) - len(ok),
        "cut": sum(1 for r in records if r.get("cut")),
        "errors": sorted({str(r.get("error") or r.get("status"))
                          for r in population if failed(r)})[:5],
        "tokens_in_window": tokens_in_window,
        "ttft_ms": [ttft_ms(r) for r in ok if r["events"]],
        "tpot_ms": tpots, "max_gap_ms": gaps,
        "loadgen_late_ms": late,
    }


_TAIL = re.compile(r"^(ttft|tpot)_p(\d{1,2})_ms$")


def end_to_end(name: str, s: dict) -> float:
    """One end-to-end metric by its name in ``BENCHMARK.json``:
    ``out_tok_s``, or ``<ttft|tpot>_p<NN>_ms``."""
    if name == "out_tok_s":
        return s["tokens_in_window"] / s["seconds"]
    m = _TAIL.match(name)
    if not m:
        raise KeyError(f"no arithmetic for the end-to-end metric {name!r}")
    return percentile(s[f"{m.group(1)}_ms"], float(m.group(2)))
