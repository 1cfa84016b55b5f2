"""Mean wait from a request's arrival to its first prefill scheduling,
over the requests admitted in the window: the scheduler's own
``tpuserve_queue_delay_seconds`` histogram (sum / count, end minus start).
In an open loop this is mostly the wait for the running fused decode window
to end (ROADMAP A3)."""

LAYER = "scheduler"
UNIT = "ms"
BETTER = "lower"
MOVES = "ttft_p95_ms"
SOURCE = "program_counter"


def compute(run):
    a, b = run["metrics_start"], run["metrics_end"]
    n = b.get("tpuserve_queue_delay_seconds_count", 0) \
        - a.get("tpuserve_queue_delay_seconds_count", 0)
    if n <= 0:
        return None
    s = b.get("tpuserve_queue_delay_seconds_sum", 0) \
        - a.get("tpuserve_queue_delay_seconds_sum", 0)
    return s / n * 1e3
