"""Share of the KV pool that is held for nothing: token-layers of WINDOWED
layers at positions more than the window plus one block behind their
sequence's end, which no step will read again
(``tpuserve_kv_window_dead_tokens``), over the pool's token-layers
(``tpuserve_kv_pool_tokens`` x the layers that run); the larger of the
readings at the window's two ends (the poller keeps other gauges only, and
a closed loop holds the share steady).  A model whose layers are of two
kinds releases nothing today: this is what an allocator by layer kind
would give back.  None where the program has no such gauge."""

LAYER = "block manager"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "program_counter"


def compute(run):
    pages = [p for p in (run["metrics_start"], run["metrics_end"])
             if "tpuserve_kv_window_dead_tokens" in p
             and p.get("tpuserve_kv_pool_tokens")]
    if not pages:
        return None
    layers = run["config"]["num_hidden_layers"]
    return 100.0 * max(p["tpuserve_kv_window_dead_tokens"]
                       / (p["tpuserve_kv_pool_tokens"] * layers)
                       for p in pages)
