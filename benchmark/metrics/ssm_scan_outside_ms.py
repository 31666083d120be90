"""Device milliseconds a train step spends under ``ssm:scan`` outside its
kernels: every instruction of that scope but the custom calls
(``selective_scan_fwd`` and ``_bwd``, which ``selective_scan_ms`` reads):
``D x`` and its transpose, ``B_t`` and ``C_t`` repeated along the lanes,
the lane sums of ``dB`` and ``dC``. It is what a kernel that broadcasts for
itself would take away."""

from benchmark.metrics import step_scopes


def read(run):
    return step_scopes.ms_per_step(
        run, lambda op: op.scope == "ssm:scan"
        and op.opcode != "custom-call")
