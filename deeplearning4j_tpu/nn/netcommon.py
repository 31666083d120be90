"""Shared container plumbing for MultiLayerNetwork and ComputationGraph.

Both containers (the reference's two model types, ref:
nn/multilayer/MultiLayerNetwork.java and nn/graph/ComputationGraph.java)
need the same device-friendly mechanics; keeping them here prevents the
two copies from drifting:

- ``LazyScoreMixin``: ``fit_batch`` stores the RAW device scalar loss so
  back-to-back training steps dispatch asynchronously — converting to
  float eagerly would force a device round-trip per step, which on a
  remote-TPU link serializes the whole pipeline. The first read of
  ``score_value`` synchronizes and caches the float.
- ``jit_init``: run a param-building closure as ONE jitted program. Eager
  per-tensor init compiles + dispatches hundreds of tiny device programs
  (one per shape) — minutes over a remote-TPU link; jitted it is a single
  compile and a single execution.
- ``FitLoopMixin``: the epoch loop of ``fit`` and the standard step of
  ``fit_batch``, written once so that they are instrumented once.
- ``apply_layer`` and ``build_train_step``: what applying one layer inside
  a step means, and the shell of a compiled training step (cast seams,
  gradient, update, guard, result, donation). Each container's
  ``_forward`` keeps its own walk and its step builders their own loss.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.datasets.iterator import AsyncDataSetIterator
from deeplearning4j_tpu.nn.remat import checkpoint_after_cotangent
from deeplearning4j_tpu.nn.updater import (
    PrecisionPolicy, cast_floats, compute_updates, precision_value_and_grad,
)
from deeplearning4j_tpu.optimize.listeners import TrainingListener
from deeplearning4j_tpu.profiling import scopes
from deeplearning4j_tpu.profiling.metrics import get_registry
from deeplearning4j_tpu.profiling.tracer import get_tracer


class LazyScoreMixin:
    """Lazy float conversion of the last minibatch loss.

    Containers assign ``self.score_value = <device scalar or float>`` and
    read ``self.score_value`` as a float; ``self._score_raw`` holds
    whatever was last assigned (listener-free training never syncs).
    """

    _score_raw = float("nan")

    @property
    def score_value(self) -> float:
        v = self._score_raw
        if not isinstance(v, float):
            v = float(v)  # device sync happens here, on first read
            self._score_raw = v
        return v

    @score_value.setter
    def score_value(self, v) -> None:
        self._score_raw = v


def jit_init(build, seed: int):
    """Run ``build(key) -> (params, opt_state)`` as one jitted program."""
    return jax.jit(build)(jax.random.PRNGKey(seed))


class SentinelMixin:
    """Divergence-sentinel attachment shared by both containers (and
    read by all three parallel trainers at step-build time).

    With a sentinel attached, every compiled train step grows an
    in-step non-finite guard (``resilience/sentinel.py:guard_update``):
    a NaN/inf loss or grad-norm means the update never lands, and the
    step returns one extra device-scalar flag that ``fit_batch`` hands
    to the sentinel's lag-based drain. Attaching/detaching drops the
    container's cached jitted steps here (guarded and unguarded steps
    are different programs); the parallel trainers detect the change
    themselves at their next ``fit_batch`` and rebuild their own cached
    steps.
    """

    _sentinel = None

    def set_divergence_sentinel(self, sentinel):
        self._sentinel = sentinel
        self._train_step_fn = None
        # derived caches key on _train_step_fn identity or are rebuilt
        # lazily; the tBPTT step is cached separately
        self._tbptt_step_fn = None
        return self

    def _observe_sentinel(self, flag) -> None:
        """Hand the just-completed step's flag to the sentinel (may
        raise per policy — see resilience/sentinel.py)."""
        if self._sentinel is not None and flag is not None:
            self._sentinel.observe(flag, self.iteration_count)


def apply_layer(layer, params, h, state, rng, mask, *, train: bool,
                remat: bool, carried: bool = False, carry=None):
    """One layer (or layer node) of a container's forward walk, after its
    preprocessor and the split of its key: ``(h, state, carry, mask)``.

    ``carried`` says that the walk threads RNN carries (tBPTT,
    ``rnn_time_step``): a layer that supports one then runs ``scan`` from
    ``carry`` (its initial carry when None) and hands the new one back;
    every other layer runs ``apply`` and hands back None. ``remat``
    (``conf.gradient_checkpointing`` in a training step) keeps the layer's
    input and rebuilds its activations in the backward pass, trading
    FLOPs for HBM. A frozen layer runs in inference mode and keeps its
    state (BN running stats don't move)."""
    layer_train = train and not layer.frozen
    if carried and getattr(layer, "supports_carry", False):
        if carry is None:
            carry = layer.initial_carry(h.shape[0], h.dtype)
        # scan() bypasses apply(): input dropout must still fire so
        # tBPTT training regularizes like standard BPTT
        h = layer._dropout_input(h, layer_train, rng)
        scan_fn = jax.checkpoint(layer.scan) if remat else layer.scan
        h, carry = scan_fn(params, h, carry, mask)
    else:
        def apply_fn(p, hh, s_in, r, m):
            return layer.apply(p, hh, state=s_in, train=layer_train, rng=r,
                               mask=m)
        if remat:
            # jax.checkpoint alone does not bound memory on the chip: the
            # compiler runs the rebuild early (nn/remat.py)
            apply_fn = checkpoint_after_cotangent(apply_fn)
        h, new_state = apply_fn(params, h, state, rng, mask)
        if not layer.frozen:
            state = new_state
        carry = None
    # layers that consume or rearrange the time axis drop the mask
    return h, state, carry, layer.propagate_mask(mask)


def step_result(guard: bool, loss, grads, old: tuple, new: tuple, *rest):
    """A compiled step's result: ``(*new, loss, *rest)``, and with a
    divergence sentinel attached ``(*selected, loss, *rest, bad)``: the
    non-finite guard selects ``old`` in-program (no host sync), so a
    diverged update never lands, and the flag goes to the sentinel's
    drain (``resilience/sentinel.py``)."""
    if not guard:
        return (*new, loss, *rest)
    from deeplearning4j_tpu.resilience.sentinel import guard_update
    selected, bad = guard_update(loss, grads, old, new)
    return (*selected, loss, *rest, bad)


def build_train_step(net, layers, loss_of, *, carried: bool = False,
                     after_update=None):
    """The jitted training step of a container, standard or tBPTT.

    The container gives what differs: ``layers`` (the list
    ``compute_updates`` walks), and ``loss_of(params, states, inputs,
    labels, masks, lmasks, carries, rng) -> (loss, (new_states, extra))``
    where ``extra`` is the new carries of a ``carried`` (tBPTT) step and
    otherwise whatever ``after_update(params, new_params, extra, labels)
    -> new_params`` wants of the forward pass. The shell owns the rest:
    the precision policy and its two cast seams at the step's boundary
    (forward and backward in the compute dtype, fp32 master parameters
    stay the update's), the gradient, the update, the sentinel's guard,
    and the donation of parameters, updater state and layer states
    (ResNet-scale nets must not copy their whole state every step).

    Returns ``train_step(params, opt_state, states, inputs, labels, masks,
    lmasks, rng) -> (params, opt_state, states, loss, grads or None[,
    bad])``, or when ``carried`` ``step(..., lmasks, carries, rng) ->
    (params, opt_state, states, carries, loss[, bad])`` with the carries
    guarded too (a NaN window must not poison the next window's recurrent
    state). The trace's ``jit_train_step`` is the first one's name. The
    containers' ``loss_of`` reach their net through a ``weakref.proxy``:
    the jitted step hangs on the net, so a closure that held the net would
    close a cycle, and a dropped net's parameters and updater state would
    stay on the device until the cyclic collector came by (the benchmark
    drops the net to make room for its float32 reference). The
    shell's own operations carry the scopes ``train:cast`` (the policy's
    seams: inputs and parameters into the compute dtype, gradients out of
    it) and ``train:update`` in the compiled step's ``op_name``
    (``profiling/scopes.py`` reads them)."""
    tx, training = net._tx, net.conf.training
    collect_grads = (not carried) and getattr(net, "_collect_grads", False)
    guard = net._sentinel is not None
    policy = PrecisionPolicy.parse(
        getattr(training, "precision", None),
        loss_scale=getattr(training, "loss_scale", None))

    def run(params, opt_state, states, inputs, labels, masks, lmasks,
            carries, rng):
        if policy.mixed:
            with jax.named_scope("train:cast"):
                inputs = cast_floats(inputs, policy.compute_dtype)
                masks = cast_floats(masks, policy.compute_dtype)

        def loss_for_grad(p):
            return loss_of(p, states, inputs, labels, masks, lmasks,
                           carries, rng)

        (loss, (new_states, extra)), grads = precision_value_and_grad(
            loss_for_grad, policy)(params)
        with jax.named_scope("train:update"):
            new_params, new_opt = compute_updates(
                tx, grads, opt_state, params, layers, training)
            if after_update is not None:
                new_params = after_update(params, new_params, extra, labels)
        if not carried:
            return step_result(
                guard, loss, grads, (params, opt_state, states),
                (new_params, new_opt, new_states),
                grads if collect_grads else None)
        # stop gradients across tBPTT boundaries
        new_carries = jax.tree.map(jax.lax.stop_gradient, extra)
        return step_result(
            guard, loss, grads, (params, opt_state, states, carries),
            (new_params, new_opt, new_states, new_carries))

    # the argument names reach the compiled step's metadata
    if carried:
        def step(params, opt_state, states, inputs, labels, masks, lmasks,
                 carries, rng):
            return run(params, opt_state, states, inputs, labels, masks,
                       lmasks, carries, rng)
        return jax.jit(step, donate_argnums=(0, 1, 2))

    def train_step(params, opt_state, states, inputs, labels, masks, lmasks,
                   rng):
        return run(params, opt_state, states, inputs, labels, masks, lmasks,
                   None, rng)
    return jax.jit(train_step, donate_argnums=(0, 1, 2))


class FitLoopMixin:
    """``fit``'s epoch loop and ``fit_batch``'s standard step for both
    containers, under one set of host spans (what hangs when a compile
    or a transfer wedges, and where the loop's time goes between two
    steps) and counters, and the tBPTT steps of one batch:

        fit                       one per fit() call
          input:wait              the feed's queue (datasets/iterator.py)
          fit_batch  it= batch=   the whole of fit_batch; fit_steps_total
            fit:split             the batch as device arrays
            fit:rng               the step's key
            fit:dispatch          the jitted step's (async) dispatch;
                                  fit_dispatch_seconds_total
            fit:listeners         the sentinel's drain and the listeners

    ``batch`` is the k-th batch of the epoch, the identifier the feed's
    spans of both threads carry. Containers provide ``_fit_batch(data)``
    (which takes the standard path through ``_standard_step``) and
    ``_fit_epoch_scan``."""

    _tbptt_step_fn = None
    _scoped_step = None     # the step whose program profiling/scopes.py has

    def _fit_epochs(self, data, epochs: int, use_async: bool,
                    scan_window: int):
        it = (AsyncDataSetIterator(data)
              if use_async and data.async_supported() else data)
        with get_tracer().span("fit"):
            for _ in range(epochs):
                for listener in self.listeners:
                    if isinstance(listener, TrainingListener):
                        listener.on_epoch_start(self)
                if scan_window > 1:
                    self._fit_epoch_scan(it, scan_window)
                else:
                    # __iter__ resets the (async) iterator
                    for k, batch in enumerate(it):
                        self._spanned_fit_batch(batch, batch=k)
                self.epoch_count += 1
                for listener in self.listeners:
                    if isinstance(listener, TrainingListener):
                        listener.on_epoch_end(self)
        return self

    def fit_batch(self, data) -> float:
        """One optimization step on one minibatch (ref: fit(DataSet) /
        ComputationGraph.fit).

        NOTE: the previous ``net.params`` / ``net.opt_state`` /
        ``net.states`` device buffers are DONATED to the step (ResNet-scale
        nets must not copy their whole state every step). External aliases
        held across a step raise "Array has been deleted" on access — copy
        with ``np.asarray`` first if you need before/after snapshots."""
        return self._spanned_fit_batch(data)

    def _spanned_fit_batch(self, data, **ids) -> float:
        with get_tracer().span("fit_batch", it=self.iteration_count + 1,
                               **ids):
            loss = self._fit_batch(data)
        registry = get_registry()
        registry.counter(
            "fit_steps_total", help="fit_batch calls completed").inc()
        fed = getattr(data, "features", None)
        if hasattr(fed, "dtype") and jnp.issubdtype(fed.dtype, jnp.integer):
            registry.counter(
                "train_tokens_total",
                help="token ids of the integer-fed batches fit_batch took"
            ).inc(fed.size)
        return loss

    def _standard_step(self, data, split) -> float:
        """The standard-backprop step: ``split(data)`` gives the jitted
        step's batch arguments (features, labels and the two masks, as
        device arrays)."""
        tracer = get_tracer()
        with tracer.span("fit:split"):
            batch_args = split(data)
        with tracer.span("fit:rng"):
            self._rng, step_rng = jax.random.split(self._rng)
        args = (self.params, self.opt_state, self.states, *batch_args,
                step_rng)
        with tracer.span("fit:dispatch") as dispatch:
            out = self._train_step_fn(*args)
            (self.params, self.opt_state, self.states, loss,
             self.last_grads) = out[:5]
        if self._scoped_step is not self._train_step_fn:
            # once a compiled step, after its first dispatch
            self._scoped_step = self._train_step_fn
            scopes.record_step("jit_train_step", self._train_step_fn, args)
        get_registry().counter(
            "fit_dispatch_seconds_total",
            help="host seconds dispatching the jitted train step"
        ).inc(dispatch.dur_ns / 1e9)
        self.last_batch_size = data.num_examples()
        # store the RAW device scalar: converting here would force a
        # device sync every step (a full round-trip on a remote-TPU link),
        # serializing the dispatch pipeline. The score_value property
        # converts on first read (listeners below, score(), callers that
        # float() the return value).
        self.score_value = loss
        self.iteration_count += 1
        with tracer.span("fit:listeners"):
            self._observe_sentinel(out[5] if len(out) > 5 else None)
            for listener in self.listeners:
                listener.iteration_done(self, self.iteration_count,
                                        self.score_value)
        return self._score_raw


    def _tbptt_steps(self, data, T: int, carries, window) -> float:
        """The truncated-BPTT steps of one batch of ``T`` time steps: one
        compiled step per ``tbptt_fwd_length`` of them, the RNN carries
        handed from each to the next. ``window(start, end)`` gives the
        step's batch arguments for that slice of the time axis. Returns
        the mean of the slices' losses (a device scalar)."""
        if self._tbptt_step_fn is None:
            self._tbptt_step_fn = self._build_tbptt_step()
        self.last_grads = None  # tBPTT step doesn't collect gradients
        fwd = self.conf.training.tbptt_fwd_length
        total, slices = 0.0, 0
        for start in range(0, T, fwd):
            self._rng, step_rng = jax.random.split(self._rng)
            out = self._tbptt_step_fn(
                self.params, self.opt_state, self.states,
                *window(start, min(start + fwd, T)), carries, step_rng)
            (self.params, self.opt_state, self.states, carries,
             loss) = out[:5]
            total = total + loss  # device accumulate — no per-slice sync
            slices += 1
            self.iteration_count += 1
            self.score_value = loss
            self._observe_sentinel(out[5] if len(out) > 5 else None)
            for listener in self.listeners:
                listener.iteration_done(self, self.iteration_count,
                                        self.score_value)
        self.last_batch_size = data.num_examples()
        return total / max(slices, 1)


class EvalMixin:
    """Shared evaluation drivers (ref: MultiLayerNetwork.evaluate /
    evaluateROC:2436 / evaluateROCMultiClass:2449 / evaluateRegression —
    ComputationGraph mirrors the same four). Containers provide
    ``output(features)``; every evaluator shares one drive loop so the
    batch semantics cannot drift between the four."""

    def _drive_eval(self, evaluator, iterator):
        import numpy as np
        iterator.reset()
        for batch in iterator:
            # the feature mask must reach the forward pass: padded steps
            # would otherwise flow through the recurrence as real data
            out = self.output(batch.features, mask=batch.features_mask)
            evaluator.eval(batch.labels, np.asarray(out),
                           mask=batch.labels_mask)
        return evaluator

    def evaluate(self, iterator):
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        return self._drive_eval(Evaluation(), iterator)

    def evaluate_roc(self, iterator, threshold_steps: int = 100):
        from deeplearning4j_tpu.eval.roc import ROC
        return self._drive_eval(ROC(threshold_steps), iterator)

    def evaluate_roc_multi_class(self, iterator,
                                 threshold_steps: int = 100):
        from deeplearning4j_tpu.eval.roc import ROCMultiClass
        return self._drive_eval(ROCMultiClass(threshold_steps), iterator)

    def evaluate_regression(self, iterator):
        from deeplearning4j_tpu.eval.regression import RegressionEvaluation
        return self._drive_eval(RegressionEvaluation(), iterator)


class CostAnalysisMixin:
    """``cost_analysis(batch)`` for both containers: XLA's compile-time
    cost model over the REAL jitted train step — FLOPs and bytes
    accessed per optimization step, plus the chip's peak for an analytic
    MFU. Pure compile-time work (runs on CPU, no accelerator needed);
    pays one AOT compile per call, so call it once per batch shape, not
    per step."""

    def cost_analysis(self, batch, peak=None) -> dict:
        from deeplearning4j_tpu.profiling.cost import train_step_cost
        return train_step_cost(self, batch, peak=peak)


class ShardCheckMixin:
    """``shardcheck(batch)`` for both containers: static analysis of
    the container's own COMPILED train step (analysis/shardcheck) —
    donation landed (SC005), no host transfers in the hot path (SC006),
    precision boundaries honored (SC004), collective census (SC002).
    The zero1/zero2 layout rules live on the data-parallel trainers'
    ``shardcheck`` (the container's own step is the single-device
    program). Same compile cost as ``cost_analysis``: one AOT lower per
    (model, batch shape), no execution."""

    def shardcheck(self, batch, **overrides):
        from deeplearning4j_tpu.analysis.shardcheck import (
            check_step_program, net_step_program, param_leaf_sizes,
        )
        training = self.conf.training
        ctx = dict(weight_update_sharding="off", dp=1,
                   precision=getattr(training, "precision", None),
                   expect_donation=True,
                   param_leaf_sizes=param_leaf_sizes(self.params))
        ctx.update(overrides)
        return check_step_program(net_step_program(self, batch), **ctx)


def make_pretrain_step(layer, tx):
    """Jitted single-layer pretraining step for the greedy layerwise walk
    both containers run (ref: MultiLayerNetwork.pretrain /
    ComputationGraph.pretrainLayer:547-579): RBM layers step on CD
    gradients, AE/VAE layers on grad of their reconstruction/ELBO loss.

    Returns ``step(params, opt_state, x, rng) -> (params, opt_state,
    loss)``.
    """
    if hasattr(layer, "cd_gradients"):  # RBM: contrastive divergence
        def step(p, opt, x, rng):
            grads, err = layer.cd_gradients(p, x, rng=rng)
            updates, opt = tx.update(grads, opt, p)
            return jax.tree.map(lambda a, u: a + u, p, updates), opt, err
    else:
        def step(p, opt, x, rng):
            loss, grads = jax.value_and_grad(
                lambda pp: layer.pretrain_loss(pp, x, rng=rng))(p)
            updates, opt = tx.update(grads, opt, p)
            return jax.tree.map(lambda a, u: a + u, p, updates), opt, loss
    # both pretrain drivers overwrite (params, opt) with the step's
    # returns, so the old buffers are donatable
    return jax.jit(step, donate_argnums=(0, 1))


def emit_scan_burst(net, losses, n, t0, stats=None):
    """Post-window listener burst shared by the containers and
    ParallelTrainer: one iteration event per scanned step with that
    step's loss. ``net.last_scan_window`` carries {n, wall_s} for the
    duration of the burst so time-based listeners (PerformanceListener)
    amortize the window wall time per step instead of misreading the
    burst cadence; try/finally guarantees a raising listener can't leave
    the stale window dict behind."""
    import time as _time
    jax.block_until_ready(losses)
    net.last_scan_window = {"n": n, "wall_s": _time.perf_counter() - t0}
    t_l = _time.perf_counter()
    try:
        for i in range(n):
            net.iteration_count += 1
            # listeners reading model.score_value must see THIS
            # iteration's loss, not the window's final one
            net.score_value = float(losses[i])
            for listener in net.listeners:
                listener.iteration_done(net, net.iteration_count,
                                        net.score_value)
    finally:
        net.last_scan_window = None
    if stats:
        stats.record("listener", _time.perf_counter() - t_l)


def make_scan_fit(step_fn, donate_argnums=(0, 1, 2)):
    """Multi-step training as ONE jitted program: ``lax.scan`` of the
    container's train step over a leading batch axis.

    Per-step host dispatch costs a host->device round trip per iteration;
    for a small model that latency can exceed the step's compute (a
    fixed ms/step floor). Scanning
    N steps inside one program pays ONE dispatch for the whole window —
    the idiomatic XLA shape for a training loop (static trip count,
    donated carry).

    ``step_fn`` is the (non-jitted semantics of the) per-batch step with
    signature (params, opt, states, feats, labels, fmask, lmask, rng) ->
    (params, opt, states, loss[, grads]) — both arities are accepted
    (the containers' steps emit grads, ParallelTrainer's doesn't; the
    body reads only the first four outputs).
    Masks are fixed to None in the scanned program. feats/labels may be
    arrays (MultiLayerNetwork) or name-keyed dicts (ComputationGraph) —
    lax.scan slices pytrees.
    """

    def scan_program(params, opt_state, states, feats, labels, rng):
        def body(carry, xs):
            p, o, s, r = carry
            f, l = xs
            r, sub = jax.random.split(r)
            out = step_fn(p, o, s, f, l, None, None, sub)
            p, o, s, loss = out[:4]
            return (p, o, s, r), loss

        (p, o, s, _), losses = jax.lax.scan(
            body, (params, opt_state, states, rng), (feats, labels))
        return p, o, s, losses

    return jax.jit(scan_program, donate_argnums=donate_argnums)


class ScanFitMixin:
    """``fit_batches_scan(datasets)`` for both containers."""

    def _fit_epoch_scan(self, it, scan_window: int) -> None:
        """One epoch's batches grouped into scan windows; the short tail
        (and any unscannable window, via fit_batches_scan's fallback)
        still trains per batch."""
        window: list = []
        for batch in it:
            window.append(batch)
            if len(window) == scan_window:
                self.fit_batches_scan(window)
                window = []
        for batch in window:
            self.fit_batch(batch)

    def fit_batches_scan(self, datasets):
        """Run one optimization step per DataSet, all inside ONE jitted
        scan program (see make_scan_fit). Requirements: SGD-family
        optimizer, standard backprop, uniform batch shapes, no masks, no
        gradient-collecting listeners — anything else falls back to the
        per-batch ``fit_batch`` loop. Returns the per-step losses as a
        device array (no sync unless converted)."""
        import jax.numpy as jnp
        import numpy as _np

        self._check_init()
        datasets = list(datasets)
        if not datasets:
            return _np.zeros((0,), _np.float32)
        def has_mask(d):
            # DataSet: singular attrs; MultiDataSet: plural lists
            for attr in ("features_mask", "labels_mask",
                         "features_masks", "labels_masks"):
                m = getattr(d, attr, None)
                if isinstance(m, (list, tuple)):
                    if any(x is not None for x in m):
                        return True
                elif m is not None:
                    return True
            return False

        def shape_sig(d):
            f, l = d.features, d.labels
            if isinstance(f, (list, tuple)):  # MultiDataSet
                return (tuple(_np.shape(x) for x in f),
                        tuple(_np.shape(y) for y in l))
            return (_np.shape(f), _np.shape(l))

        algo = self.conf.training.optimization_algo
        scannable = (
            algo in ("sgd", "stochastic_gradient_descent")
            and self.conf.training.backprop_type != "truncated_bptt"
            and not getattr(self, "_collect_grads", False)
            # a divergence sentinel needs per-step host observation
            # (raise/rollback policies); the scan body would silently
            # drop the flags — train per batch instead
            and getattr(self, "_sentinel", None) is None
            and not any(has_mask(d) for d in datasets)
            # a ragged batch (short dataset tail) cannot stack — loop it
            and len({shape_sig(d) for d in datasets}) == 1)
        if not scannable:
            return _np.asarray([float(self.fit_batch(d))
                                for d in datasets], _np.float32)
        if self._train_step_fn is None:
            self._train_step_fn = self._build_train_step()
        cached = getattr(self, "_scan_fit", None)
        if cached is None or cached[0] is not self._train_step_fn:
            self._scan_fit = (self._train_step_fn,
                              make_scan_fit(self._train_step_fn))
        scan_fn = self._scan_fit[1]

        if hasattr(self, "_split"):  # ComputationGraph: name-keyed dicts
            splits = [self._split(d) for d in datasets]
            feats = jax.tree.map(lambda *xs: jnp.stack(
                [jnp.asarray(x) for x in xs]), *[s[0] for s in splits])
            labels = jax.tree.map(lambda *xs: jnp.stack(
                [jnp.asarray(x) for x in xs]), *[s[1] for s in splits])
        else:
            feats = jnp.stack([jnp.asarray(d.features) for d in datasets])
            labels = jnp.stack([jnp.asarray(d.labels) for d in datasets])

        import time as _time
        t0 = _time.perf_counter()
        self._rng, r = jax.random.split(self._rng)
        self.params, self.opt_state, self.states, losses = scan_fn(
            self.params, self.opt_state, self.states, feats, labels, r)
        self.last_batch_size = datasets[-1].num_examples()
        self.last_grads = None
        self.last_input = getattr(datasets[-1], "features", None)
        if self.listeners:
            emit_scan_burst(self, losses, len(datasets), t0)
        else:
            self.iteration_count += len(datasets)
        self.score_value = losses[-1]
        return losses
