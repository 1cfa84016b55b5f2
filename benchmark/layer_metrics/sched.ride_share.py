"""Share of the window's answer tokens that rode a prompt dispatch: the
``ridden_tokens`` of the step records of kind ``mixed`` (a mixed step's
decode rows, one token each: the weights were read once for them and the
prompt chunks together) over the tokens the clients were streamed inside
the window, which is what ``out_tok_s`` counts.  0 on an engine that keeps
the phase split (its records hold no mixed step); None where the run has
no count of the window's tokens."""

LAYER = "scheduler"
UNIT = "%"
BETTER = "higher"
MOVES = "out_tok_s"
SOURCE = "program_span"


def compute(run):
    tokens = (run.get("summary") or {}).get("tokens_in_window")
    if not tokens:
        return None
    ridden = sum(s.get("ridden_tokens", 0) for s in run.get("steps") or ())
    return 100.0 * ridden / tokens
