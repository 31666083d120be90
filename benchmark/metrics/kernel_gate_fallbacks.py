"""Layers a Pallas shape gate sent to the XLA path over the run
(``pallas_gate_fallbacks_total``, counted once a trace of the step). 0 says
the full-attention layer ran the flash kernel and not
``blockwise_attention``, whose backward would keep the scores."""


def read(run):
    return run.measures.get("gate_fallbacks")
