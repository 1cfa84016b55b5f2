"""Sequences preempted and re-prefilled in the window
(``vllm_num_preemptions``, end minus start)."""

LAYER = "block manager"
UNIT = "count"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "program_counter"


def compute(run):
    key = "vllm_num_preemptions_total"
    a, b = run["metrics_start"], run["metrics_end"]
    if key not in b:
        return None
    return b[key] - a.get(key, 0)
