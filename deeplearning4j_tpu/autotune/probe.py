"""Measured probes: a few REAL compiled steps per shortlisted candidate.

The analytic model ranks; the probe decides. Each probe builds a FRESH
net from the model's own configuration (same seed — deterministic
init), wraps it in a ``ParallelTrainer`` constructed from the
candidate's ``trainer_kwargs()`` (the exact recipe ``TunedConfig`` uses,
so what is measured is what ships), pays the compile in warmup steps,
then times ``steps`` asynchronously-dispatched steps closed by one
``block_until_ready``. Compile time
is reported separately (``compile_s``), never inside the measurement.

Probes never touch the caller's net: parameter state, optimizer state
and RNG all belong to the throwaway probe net.
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np


def _one_hot_labels(rng, t, batch_size: int):
    """Deterministic one-hot labels matching one loss head's OUTPUT
    InputType: [B, K] for feed-forward heads, [B, T, K] per-timestep
    for recurrent heads (the LM case)."""
    k = max(2, int(t.size or 2))
    if t.kind == "rnn":
        T = int(t.timesteps or 1)
        return np.eye(k, dtype=np.float32)[
            rng.integers(0, k, (batch_size, T))]
    return np.eye(k, dtype=np.float32)[rng.integers(0, k, batch_size)]


def synthesize_batch(conf, batch_size: int):
    """A deterministic synthetic batch for a shape-resolved config
    (seeded by the conf's own seed).

    MultiLayer configs: random-normal features in the input type's
    example shape, one-hot labels at the loss head's width.

    ComputationGraph configs (ROADMAP item 4d): one feature array per
    ``network_inputs`` entry from the declared ``input_types``, one
    one-hot label array per ``network_outputs`` head from the RESOLVED
    output type — returned as a DataSet for single-input/single-output
    graphs (every trainer path accepts it) and a MultiDataSet
    otherwise, so ``autotune(ComputationGraph(...), ...)`` and
    ``tools/autotune.py`` need no explicit example batch."""
    from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
    rng = np.random.default_rng(int(conf.training.seed))
    if hasattr(conf, "nodes"):  # ComputationGraph configuration
        if not conf.input_types or not conf.resolved_types:
            raise ValueError(
                "cannot synthesize a probe batch: the graph config has "
                "no input_types (call set_input_types(...) at build, or "
                "pass batch= to autotune())")
        feats = []
        for name in conf.network_inputs:
            t = conf.input_types[name]
            feats.append(rng.normal(
                size=(batch_size,) + tuple(t.example_shape())
                ).astype(np.float32))
        labels = [_one_hot_labels(rng, conf.resolved_types[o], batch_size)
                  for o in conf.network_outputs]
        if len(feats) == 1 and len(labels) == 1:
            return DataSet(feats[0], labels[0])
        return MultiDataSet(feats, labels)
    input_type = getattr(conf, "input_type", None)
    if input_type is None:
        raise ValueError(
            "cannot synthesize a probe batch: the config has no "
            "input_type")
    feats = rng.normal(size=(batch_size,) + tuple(
        input_type.example_shape())).astype(np.float32)
    head = conf.layers[-1]
    n_out = int(getattr(head, "n_out", None) or 2)
    labels = np.eye(n_out, dtype=np.float32)[
        rng.integers(0, n_out, batch_size)]
    if input_type.kind == "rnn":
        # recurrent heads emit per-timestep distributions: [B, T, K]
        T = feats.shape[1] if feats.ndim == 3 else 1
        labels = np.eye(n_out, dtype=np.float32)[
            rng.integers(0, n_out, (batch_size, T))]
    return DataSet(feats, labels)


def build_probe_net(net):
    """A fresh, identically-seeded container from ``net``'s config —
    the throwaway model every probe trains instead of the caller's."""
    fresh = type(net)(net.conf)
    fresh.init()
    return fresh


def measure_candidate(net, candidate, batch, steps: int = 3,
                      warmup: int = 1,
                      devices: Optional[list] = None) -> dict:
    """Run one candidate for real and return
    {measured_step_s, compile_s, losses}.

    ``net`` is only the blueprint (config + container class); the
    trained state lives and dies here. ``candidate`` must be probeable
    (pp == 1 — enforced by the tuner's shortlist).
    """
    import jax

    from deeplearning4j_tpu.parallel.mesh import MeshContext
    from deeplearning4j_tpu.parallel.trainer import ParallelTrainer

    if not candidate.probeable:
        raise ValueError(f"candidate {candidate.slug()} is not probeable "
                         "(pp > 1 needs the pipeline trainer)")
    probe_net = build_probe_net(net)
    mesh = MeshContext.create(n_data=candidate.dp, n_model=candidate.tp,
                              n_seq=candidate.sp, devices=devices)
    trainer = ParallelTrainer(probe_net, mesh,
                              **candidate.trainer_kwargs())
    t0 = time.perf_counter()
    losses = []
    for _ in range(max(1, warmup)):
        losses.append(trainer.fit_batch(batch))
    jax.block_until_ready(probe_net.params)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(max(1, steps)):
        losses.append(trainer.fit_batch(batch))
    jax.block_until_ready(probe_net.params)
    dt = time.perf_counter() - t0
    return {"measured_step_s": dt / max(1, steps),
            "compile_s": compile_s,
            "losses": [float(np.asarray(l)) for l in losses]}
