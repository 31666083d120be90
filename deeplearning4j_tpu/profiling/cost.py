"""Compiled-step cost analysis: FLOPs, bytes accessed, analytic MFU.

XLA attaches a cost model to every compiled executable —
``jitted.lower(args).compile().cost_analysis()`` — with per-program
FLOP and bytes-accessed totals. Because the cost model runs at compile
time, the whole analysis works on CPU with no accelerator attached:
lower the container's real train step for the real batch shapes, read
the FLOPs, divide by a chip's peak — an **analytic MFU** you can compute
(and regress against) before paying any device time, the way µ-cuDNN
picked convolution configurations from per-layer cost models instead of
device sweeps.

``train_step_cost(net, batch)`` drives it for either container (and for
the SPMD ``ParallelTrainer``'s step via the net it wraps). Its readers:
``net.cost_analysis``, ``TrainingStats.export()`` (``stats.set_cost``),
the autotuner's model and shardcheck's comm-bytes rule.

``dp_comm_bytes_per_update`` and ``dp_gradient_hbm_bytes`` model the
data-parallel trainers' weight-update traffic and gradient HBM per chip
for the three layouts (replicated, ``weight_update_sharding="zero1"``,
``"zero2"``).

NOTE: the AOT ``lower().compile()`` pays one real XLA compile and its
executable is NOT reused by later ``net.fit_batch`` calls (jax's jit
dispatch cache is separate from the AOT path) — call it once per
(model, batch shape), not per step.
"""

from __future__ import annotations

import weakref
from typing import Optional

# Peak dense bf16 matmul FLOP/s per chip, by device_kind substring, public
# cloud specs. First match wins, so longer/more-specific keys come first.
# Accelerators only: a CPU has no MFU.
PEAK_FLOPS_PER_CHIP = (
    ("v6", 918e12),       # TPU v6e (Trillium)
    ("v5p", 459e12),
    ("v5 lite", 197e12),  # v5e reports device_kind "TPU v5 lite"
    ("v5litepod", 197e12),
    ("v5e", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def peak_flops(device_kind: str) -> Optional[float]:
    """Peak FLOP/s of the accelerator ``device_kind`` names (substring
    match). None for the CPU backend, which has no utilization to
    report; an accelerator the table does not know is an error, not a
    default."""
    kind = (device_kind or "").lower()
    if kind == "cpu":
        return None
    for key, peak in PEAK_FLOPS_PER_CHIP:
        if key in kind:
            return peak
    raise ValueError(
        f"no peak FLOP/s on record for device_kind {device_kind!r}; add "
        "it to profiling.cost.PEAK_FLOPS_PER_CHIP with its source")


def analytic_mfu(flops_per_step: float, step_seconds: float,
                 peak_flops_per_chip: float, n_chips: int = 1
                 ) -> Optional[float]:
    """Model FLOPs utilization: achieved FLOP/s over peak.

    ``flops_per_step`` is the compiled program's total (fwd+bwd+update,
    as XLA counts it), ``step_seconds`` the measured (or target) wall
    time per step, ``n_chips`` how many chips share the program's FLOPs
    (SPMD: the cost analysis of the sharded program is already
    per-device on most jax versions — pass n_chips=1 then).
    """
    if not flops_per_step or not step_seconds or not peak_flops_per_chip:
        return None
    if step_seconds <= 0 or peak_flops_per_chip <= 0:
        return None
    return flops_per_step / (step_seconds * peak_flops_per_chip
                             * max(n_chips, 1))


# ---------------------------------------------------------------------------
# data-parallel weight-update cost model (replicated vs zero1)
# ---------------------------------------------------------------------------

def dp_comm_bytes_per_update(param_count: int, dp: int,
                             dtype_bytes: int = 4,
                             gradient_accumulation: int = 1,
                             weight_update_sharding: str = "off") -> int:
    """Analytic cross-chip bytes PER CHIP per optimizer update for the
    data-parallel trainers, on the standard ring-collective model
    (all-reduce moves ``2.(dp-1)/dp`` of the payload per chip;
    reduce-scatter and all-gather move ``(dp-1)/dp`` each).

    ``off``  : one gradient all-reduce per microbatch —
               ``k . 2 . (dp-1)/dp . P.b``.
    ``zero1``: one gradient reduce-scatter per microbatch + one param
               all-gather per update — ``(k+1) . (dp-1)/dp . P.b``
               (the layout-sharded update lets XLA fold the per-
               microbatch all-reduce + shard slice into a reduce-
               scatter, and only the final params travel back).
    ``zero2``: same wire traffic as zero1 — the reduce-scatter is
               already the minimum that preserves the per-microbatch
               reduction order (the bitwise-parity contract rules out
               the textbook accumulate-unreduced-then-reduce-once
               floor) — so ``comm(zero2) == comm(zero1) <= comm(off)``
               for ``k >= 1``; what zero2 sheds is the full-size
               REDUCED-gradient buffer (see
               :func:`dp_gradient_hbm_bytes`), because the shards are
               the gradients' native layout rather than a slice of an
               anchored replicated copy.

    At ``gradient_accumulation=4`` that is 8x vs 5x the reduce-scatter
    unit. dp=1 is 0 either way (no cross-chip axis).
    """
    from deeplearning4j_tpu.analysis.graphcheck import SHARDED_WUS_MODES
    dp = max(1, int(dp))
    if dp == 1:
        return 0
    k = max(1, int(gradient_accumulation))
    payload = int(param_count) * int(dtype_bytes)
    unit = payload * (dp - 1) // dp
    if weight_update_sharding in SHARDED_WUS_MODES:
        return (k + 1) * unit
    return 2 * k * unit


def dp_gradient_hbm_bytes(param_count: int, dp: int,
                          dtype_bytes: int = 4,
                          weight_update_sharding: str = "off") -> int:
    """Per-chip HBM of the REDUCED gradient the update consumes.

    ``off`` keeps a full replicated gradient (``P.b``); ``zero1``
    anchors the reduced gradient replicated before slicing it, so its
    peak is still ``P.b``; ``zero2`` holds only the ``(dp, chunk)``
    shard — ``P.b / dp`` — because the sharded view is the gradients'
    only layout from the reduce-scatter onward (the per-microbatch
    pre-reduction partial is transient on every mode and not modeled
    here)."""
    total = int(param_count) * int(dtype_bytes)
    if weight_update_sharding == "zero2" and dp > 1:
        return -(-total // int(dp))
    return total


# Per-net census cache (ISSUE 13): the autotuner's configuration sweeps
# call param_census / train_step_cost once per CANDIDATE, but the
# underlying numbers depend only on the net (param sizes, updater) and —
# for the compiled census — the batch signature. Keyed on the net object
# itself (weak: a released net must not pin its params' metadata — and
# NOTHING stored in a value may strongly reach the net, or the weak key
# never dies), so a 100-config sweep pays the model walk and the AOT
# compile once, not 100 times. param_census returns the cached dict
# itself (read-only by contract); train_step_cost returns a fresh copy
# per call (its callers mutate their results).
_PARAM_CENSUS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_STEP_COST: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def param_census(net) -> dict:
    """{param_count, dtype_bytes, updater} for an initialized container,
    memoized on net identity (the flops/param census every candidate of
    an autotune sweep shares). The returned dict is the cached object —
    treat it as read-only."""
    try:
        cached = _PARAM_CENSUS.get(net)
    except TypeError:  # un-weakref-able container: compute, don't cache
        cached = None
    if cached is not None:
        return cached
    import jax
    import numpy as np
    leaves = jax.tree_util.tree_leaves(net.params)
    census = {
        "param_count": sum(
            int(np.prod(np.shape(leaf))) if np.shape(leaf) else 1
            for leaf in leaves),
        "dtype_bytes": (np.dtype(leaves[0].dtype).itemsize
                        if leaves and hasattr(leaves[0], "dtype") else 4),
        "updater": net.conf.training.updater.name,
    }
    try:
        _PARAM_CENSUS[net] = census
    except TypeError:
        pass
    return census


def _batch_signature(batch) -> tuple:
    """Hashable (shapes + dtypes) key of a DataSet/MultiDataSet — the
    only batch facts a compiled step's cost analysis depends on."""
    import numpy as np

    def sig(x):
        if x is None:
            return None
        if isinstance(x, dict):
            return tuple(sorted((k, sig(v)) for k, v in x.items()))
        return (tuple(np.shape(x)), str(np.asarray(x).dtype)
                if not hasattr(x, "dtype") else str(x.dtype))

    return (sig(getattr(batch, "features", None)),
            sig(getattr(batch, "labels", None)),
            sig(getattr(batch, "features_mask", None)),
            sig(getattr(batch, "labels_mask", None)))


def _normalize_cost(raw) -> dict:
    """``cost_analysis()`` returns a dict, or None on a backend without
    a cost model. Normalize to {flops, bytes_accessed, ...} floats."""
    out = {}
    for key, val in dict(raw or {}).items():
        if key == "flops":
            out["flops"] = float(val)
        elif key in ("bytes accessed", "bytes_accessed"):
            out["bytes_accessed"] = float(val)
        elif key in ("optimal_seconds", "optimal seconds"):
            out["optimal_seconds"] = float(val)
    return out


def lower_and_compile(jitted, *args, **kwargs):
    """``(lowered, compiled)`` for a jitted function on example args —
    ONE real XLA compile, shared by :func:`compiled_cost` and
    ``analysis/shardcheck.lower_step_program`` (which also reads the
    StableHLO/HLO texts off the same pair)."""
    lowered = jitted.lower(*args, **kwargs)
    return lowered, lowered.compile()


def compiled_cost(jitted, *args, **kwargs) -> dict:
    """Lower + compile ``jitted`` for the given example args and return
    its normalized cost analysis (one real XLA compile)."""
    _, compiled = lower_and_compile(jitted, *args, **kwargs)
    return _normalize_cost(compiled.cost_analysis())


def step_example_args(net, batch):
    """The positional argument tuple of a container's jitted train step
    for one example ``batch`` — the arg-assembly both
    :func:`train_step_cost` and ``net.shardcheck`` lower with."""
    import jax
    import jax.numpy as jnp

    rng = jax.random.PRNGKey(0)
    if hasattr(net, "_split"):  # ComputationGraph: name-keyed dicts
        inputs, labels, masks, lmasks = net._split(batch)
        return (net.params, net.opt_state, net.states, inputs, labels,
                masks, lmasks, rng)
    fmask = (None if batch.features_mask is None
             else jnp.asarray(batch.features_mask))
    lmask = (None if batch.labels_mask is None
             else jnp.asarray(batch.labels_mask))
    return (net.params, net.opt_state, net.states,
            jnp.asarray(batch.features), jnp.asarray(batch.labels),
            fmask, lmask, rng)


def train_step_cost(net, batch, peak: Optional[float] = None) -> dict:
    """Cost-analyze a container's jitted train step on ``batch``.

    ``net``: an initialized MultiLayerNetwork or ComputationGraph.
    Returns {flops_per_step, flops_per_example, bytes_accessed,
    arithmetic_intensity, comm_bytes_hlo, batch, device_kind,
    peak_flops_per_chip}, plus ``mfu_at(step_seconds)`` left to the
    caller via ``analytic_mfu``. Pure compile-time work — runs on CPU
    without a chip. ``comm_bytes_hlo`` is the compiled program's actual
    per-chip collective bytes on the ring model (shardcheck's SC007
    surface) — 0 for a single-device program, and the number a sharded
    program's cost-model prediction is calibrated against.

    Memoized on (net's built step fn, batch signature, peak): the AOT
    compile is the expensive part, and an autotune sweep asks for the
    same program's census once per candidate. The cache entry pins the
    step fn only WEAKLY and is dropped whenever the net's current step
    is a different object — so a sentinel attach/detach (a rebuilt
    program) misses instead of serving stale numbers, a collected fn
    cannot alias a new one by id reuse, and the entry's contents never
    strongly reach the net (the step's closure holds the net, so a
    strong ref here would make the weak key immortal).
    """
    import jax

    net._check_init()
    if net._train_step_fn is None:
        net._train_step_fn = net._build_train_step()
    cache_key = (_batch_signature(batch), peak)
    try:
        entry = _STEP_COST.get(net)
    except TypeError:
        entry = None
    if entry is not None and entry[0]() is not net._train_step_fn:
        entry = None  # step rebuilt: every cached program is stale
    hit = entry[1].get(cache_key) if entry is not None else None
    if hit is not None:
        return dict(hit)
    args = step_example_args(net, batch)
    n_examples = batch.num_examples()
    comm_bytes_hlo = None
    try:
        from deeplearning4j_tpu.analysis.shardcheck import (
            hlo_comm_bytes, lower_step_program,
        )
        program = lower_step_program(net._train_step_fn, *args)
        cost = dict(program.cost)
        comm_bytes_hlo = hlo_comm_bytes(program)
    except Exception:  # noqa: BLE001 — cost numbers stand without the parse
        cost = compiled_cost(net._train_step_fn, *args)
    device_kind = str(jax.devices()[0].device_kind)
    peak = peak if peak is not None else peak_flops(device_kind)
    flops = cost.get("flops")
    out = {
        "flops_per_step": flops,
        "flops_per_example": (flops / n_examples
                              if flops and n_examples else None),
        "bytes_accessed": cost.get("bytes_accessed"),
        "comm_bytes_hlo": comm_bytes_hlo,
        "arithmetic_intensity": (
            flops / cost["bytes_accessed"]
            if flops and cost.get("bytes_accessed") else None),
        "batch": n_examples,
        "device_kind": device_kind,
        "peak_flops_per_chip": peak,
    }
    try:
        if entry is None:
            entry = (weakref.ref(net._train_step_fn), {})
            _STEP_COST[net] = entry
        entry[1][cache_key] = dict(out)
    except TypeError:
        pass
    return out
