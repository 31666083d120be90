"""What the cases of a model's test file share with the plain reference:
its sound run of a seed, made once for the module, and the program's three
steps from the same weights. Only values are kept (the seed's weights on
the host, its batches, the reference's numbers): each case that fits builds
its own net from them, so no case sees another's parameters or optimizer
state."""

import jax
import jax.numpy as jnp

from benchmark import program
from deeplearning4j_tpu.datasets import DataSet


def on_device(host: dict) -> dict:
    """A fresh device copy of flat weights (the reference consumes the
    weights it is given)."""
    return {k: jnp.array(v) for k, v in host.items()}


class SoundRuns:
    """The reference's sound run of each seed, made on first use and kept:
    ``runs(seed)`` is the seed's weights on the host, its batches and the
    reference's numbers; ``runs.planted(seed, ...)`` runs the reference
    from the same weights and batches with a fault or a precision
    planted."""

    def __init__(self, cfg: dict, reference, batches):
        self.cfg, self.reference, self.batches = cfg, reference, batches
        self._runs = {}

    def __call__(self, seed: int):
        if seed not in self._runs:
            start = jax.device_get(self.reference.make_weights(self.cfg, seed))
            batches = self.batches(seed)
            self._runs[seed] = start, batches, self.reference.train_steps(
                self.cfg, on_device(start), batches)
        return self._runs[seed]

    def planted(self, seed: int, **planted) -> dict:
        start, batches, _ = self(seed)
        return self.reference.train_steps(self.cfg, on_device(start),
                                          batches, **planted)


def three_steps(cfg: dict, start: dict, batches):
    """The program's net built from ``start`` and fitted one step a batch,
    and what the check compares: each step's loss, every leaf's gradient
    norm at step 1 (the first moment) and every leaf's change at the end."""
    net = program.build_net(cfg, start)
    prog = {"losses": []}
    for i, (x, y) in enumerate(batches):
        net.fit(DataSet(x, y))
        prog["losses"].append(float(net.score_value))
        if i == 0:
            prog["grad_norm"] = program.leaf_norms(
                program.first_moment(net.opt_state))
    prog["delta_norm"] = program.change_norms(program.flatten(net.params),
                                              start)
    return net, prog
