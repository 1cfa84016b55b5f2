"""Of the rows an expert layer that holds a share of its experts gathered
and multiplied over the window, the share that belonged to no held expert:
``1 - tpuserve_moe_held_rows / tpuserve_moe_buffer_rows`` (counters of
``/metrics``, end minus start).  The layer moves whole pieces of buffer,
as many as what landed here needs, so this is the last piece's slack: 87.5
% for a layer that sorted, gathered and multiplied every pick of an
eighth-share, a few per cent to a third for one whose buffer follows the
share (a piece is the even-routing count plus three standard deviations,
which is a third of 64 decode rows' picks and 3 % of a large prefill's).
None where the program has no such counters or no row landed."""

LAYER = "model trunk"
UNIT = "%"
BETTER = "lower"
MOVES = "out_tok_s"
SOURCE = "program_counter"


def compute(run):
    a, b = run["metrics_start"], run["metrics_end"]
    moved, held = (b.get(key, 0) - a.get(key, 0) for key in (
        "tpuserve_moe_buffer_rows_total", "tpuserve_moe_held_rows_total"))
    if moved <= 0 or held <= 0:
        return None
    return 100.0 * (1.0 - held / moved)
