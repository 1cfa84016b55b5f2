"""Of the rows an expert layer behind a GROUP-LIMITED router routed over
the window, the share one of whose surviving groups is held by this chip:
``tpuserve_moe_group_rows`` over ``tpuserve_moe_routed_rows / experts per
token`` (counters of ``/metrics``, end minus start; both summed over the
expert layers).  These are the rows a chip of the deployment is sent at
all: ``topk_group / n_group`` = 50 % under even routing where a chip holds
one group, and what the exchange between the shares would carry.  None
where the program has no such counter (a router without groups, a program
from before it) or no row was routed."""

LAYER = "model trunk"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "program_counter"


def compute(run):
    a, b = run["metrics_start"], run["metrics_end"]
    here, routed = (b.get(key, 0) - a.get(key, 0) for key in (
        "tpuserve_moe_group_rows_total", "tpuserve_moe_routed_rows_total"))
    k = (run.get("config") or {}).get("num_experts_per_tok")
    if here <= 0 or routed <= 0 or not k:
        return None
    return 100.0 * here * k / routed
