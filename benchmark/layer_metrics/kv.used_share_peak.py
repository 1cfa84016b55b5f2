"""Highest share of KV blocks in use seen in the window
(``vllm_kv_cache_usage_perc``, polled twice a second): memory reserved
against memory used."""

LAYER = "block manager"
UNIT = "%"
BETTER = "higher"
MOVES = "out_tok_s"
SOURCE = "program_counter"


def compute(run):
    seen = [p["vllm_kv_cache_usage_perc"] for p in run["polls"]
            if "vllm_kv_cache_usage_perc" in p]
    if not seen:
        return None
    return 100.0 * max(seen)
