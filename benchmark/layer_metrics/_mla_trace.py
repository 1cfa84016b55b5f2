"""Latent attention (MLA) in a traced run (the leading underscore keeps
``plan.discover_layer_metrics`` from taking this for a metric): what the
five ``mla.*`` readers share.

**The work is the architecture's, from the configuration's keys**, the same
whatever implements it and however the page is stored (``kv_bytes_per_token``
is what the LAYOUT stores: 640 lanes where the latent is 576).  A decode
row attends, for every cached token of every layer, ONE latent vector of
``kv_lora_rank + qk_rope_head_dim`` values: in the absorbed form each query
head scores it (a dot of that width) and takes its first ``kv_lora_rank``
values weighted (another of that width), 2 operations a multiply-add; and
the vector is read once, 2 bytes a value, no second copy for the values::

    operations = 2 x heads x ((kv_lora_rank + qk_rope_head_dim) + kv_lora_rank)
    bytes      = 2 x (kv_lora_rank + qk_rope_head_dim)

278,528 and 1,152 at the published sizes (128 heads, 512 + 64): 242
operations a byte against the chip's 197e12 / 819e9 = 240, so neither bound
is wide and ``roofline.share`` takes the larger.

**Time and work from the same calls** (``_moe_held_trace.py``'s way).  Time:
the self seconds under ``decode/attn.kernel`` (``_scope_trace``: the paged
decode kernel's latent entry, one call a layer a fused step).  Work: those
calls (counted from the same events) times the context tokens ONE call
attends, the mean over fused decode steps of the step records'
``ctx_tokens`` (a window of S steps over n rows attends S ctx + n S (S-1)/2:
``host_spans.attended``), from the nearest records that hold a decode step:
the ``seq`` join's, those stamped inside the traced span, the window's.

A span that holds none of a reader's scope reads 0.0, not None (a metric
left out of a traced run's line refuses the run: PERF.md section 7 row 25);
None only without a trace, without scopes in it, or for a configuration
with no latent attention."""

from benchmark.harness import host_spans
from benchmark.layer_metrics import _scope_trace as st

_KEY = "_mla_trace"


def latent_work(config: dict):
    """``(operations, bytes)`` a cached token a layer, or None where the
    configuration has no latent attention."""
    rank, rope = config.get("kv_lora_rank"), config.get("qk_rope_head_dim")
    heads = config.get("num_attention_heads")
    if not rank or rope is None or not heads:
        return None
    width = rank + rope
    return 2.0 * heads * (width + rank), 2.0 * width


def ctx_tokens_a_step(records: list):
    """Context tokens one fused decode step attends in ONE layer, the mean
    over the records' decode dispatches; None where they hold none."""
    decodes = [s for s in records if s.get("kind") in ("window", "decode")
               and s.get("rows") and "ctx_tokens" in s]
    fused = st.fused_steps(decodes)
    if fused <= 0:
        return None
    return sum(host_spans.attended(s) for s in decodes) / fused


def ctx_of(run, joined: list):
    span = run.get("trace_span")
    steps = run.get("steps") or []
    for records in (joined,
                    [s for s in steps if span and span[0] <= s["t"] < span[1]],
                    steps):
        ctx = ctx_tokens_a_step(records)
        if ctx is not None:
            return ctx
    return None


def scopes(run):
    """``_scope_trace.measure(run)`` for a configuration with latent
    attention and a trace with busy time, else None."""
    if latent_work(run.get("config") or {}) is None:
        return None
    m = st.measure(run)
    return m if m is not None and m["busy_s"] > 0 else None


def share_of_busy(run, phases, parts):
    """Per cent of busy time under these scopes; 0.0 where the span holds
    none of them."""
    m = scopes(run)
    if m is None:
        return None
    return 100.0 * st.seconds(m, phases, parts) / m["busy_s"]


def decode_attention(run):
    """``{"ns", "token_layers", "calls"}`` of the latent decode calls in
    the traced span (zeros where it holds none), or None."""
    if _KEY in run:
        return run[_KEY]
    run[_KEY] = None
    m = scopes(run)
    if m is None:
        return None
    calls = m["calls"].get((st.DECODE_KERNEL, "decode"), 0)
    ns = st.seconds(m, ("decode",), ("attn.kernel",)) * 1e9
    joined = (host_spans.analyse(run) or {}).get("steps_joined") or []
    ctx = ctx_of(run, joined) if calls else 0.0
    if ctx is None:
        return None
    print(f"[bench] latent decode attention: {calls:.0f} calls of "
          f"{st.DECODE_KERNEL} under decode/, {ns * 1e-6:.3f} ms, "
          f"{ctx:.0f} context tokens a call", flush=True)
    run[_KEY] = {"ns": ns, "token_layers": calls * ctx, "calls": calls}
    return run[_KEY]
