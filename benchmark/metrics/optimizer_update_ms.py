"""Device milliseconds a train step spends in the step's shell round the
gradient: ``train:update`` (``compute_updates``: the freeze mask, gradient
normalization, the update rule and the parameters' sum) and ``train:cast``
(the precision policy's seams: the compute-dtype copies of the inputs and
of every parameter, the gradients back to the master dtype), which
``nn/netcommon.build_train_step`` and ``nn/updater.precision_value_and_grad``
write. The bytes set its floor: parameters, momentum and gradients read and
written once.

It reads what stands ALONE of the two. A fusion goes whole to its own
``op_name``, so what XLA fuses of an update or a cast into a neighbour is
read as the neighbour's: the decoders' head leaf is updated inside the
head's weight-gradient fusion (some 5 % of their parameters), and a net of
convolutions has next to all its updates inside the weight-gradient
convolutions, which is why no cell of ResNet-50's lists this metric. A
change that moves update work into or out of a fusion moves this reading
without moving the step: read it beside ``train_step_device_ms``."""

from benchmark.metrics import step_scopes


def read(run):
    return step_scopes.scope_ms(run, "train:update", "train:cast")
