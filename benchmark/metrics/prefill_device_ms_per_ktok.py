"""Device time of the prefill programs' runs (``XLA Modules`` events named
``jit_prefill``) in the traced stretch over thousands of prompt tokens whose
first token came in it."""

from benchmark import trace

PATTERN = r"^jit_prefill\b"


def read(run):
    runs = trace.module_runs(run.trace, PATTERN)
    tokens = run.measures.get("traced", {}).get("prompt_tokens")
    if not runs or not tokens:
        return None
    return 1e3 * sum(runs) / (tokens / 1e3)
