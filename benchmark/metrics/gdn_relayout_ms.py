"""Device milliseconds a train step spends under ``gdn:chunk_local``
outside its kernels: every instruction of that scope but the custom calls
(``gdn_chunk_local_fwd`` and ``_bwd``, which ``gdn_chunk_local_ms`` reads):
the relayouts of q, k and v into chunks and of the cotangents back."""

from benchmark.metrics import step_scopes


def read(run):
    return step_scopes.ms_per_step(
        run, lambda op: op.scope == "gdn:chunk_local"
        and op.opcode != "custom-call")
