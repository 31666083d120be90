"""Sharded SPMD trainer.

The reference's "TrainingMaster role becomes the SPMD program itself"
(SURVEY §2.3 DP-3): one jitted train step whose inputs carry NamedShardings
— batch over the 'data' axis, params replicated or 'model'-sharded — and
XLA inserts the gradient all-reduce over ICI (the explicit
Nd4j.averageAndPropagate / Aeron push-pull / Spark aggregate all disappear).

Gradient accumulation maps the reference's ``averagingFrequency`` knob
(ParallelWrapper.java:412): accumulate k local microbatch gradients between
parameter updates. Under synchronous all-reduce the reference's
updater-state averaging becomes a no-op (state is replicated & consistent)
— a correctness improvement noted in SURVEY §5.8.
"""

from __future__ import annotations

import time
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import AsyncDataSetIterator, DataSetIterator
from deeplearning4j_tpu.nn.netcommon import (
    ScanFitMixin, emit_scan_burst, make_scan_fit, step_result,
)
from deeplearning4j_tpu.nn.updater import (
    PrecisionPolicy, cast_floats, compute_updates, compute_updates_sharded,
    gather_updater_state, precision_value_and_grad, shard_updater_state,
)
from deeplearning4j_tpu.optimize.training_stats import (
    TrainingStats, maybe_phase,
)
from deeplearning4j_tpu.parallel.mesh import (
    MeshContext, WeightUpdateSharding, sequence_parallel_scope,
    zero1_shard_leaf,
)
from deeplearning4j_tpu.profiling import get_tracer


class ParallelTrainer:
    """Data/tensor-parallel trainer for a MultiLayerNetwork or
    ComputationGraph.

    The model's params are resharded onto the mesh; each ``fit`` step feeds a
    global batch (sharded over 'data') through ONE jitted step compiled for
    the mesh. Collectives ride ICI automatically.

    ``weight_update_sharding="zero1"`` (see
    :class:`~deeplearning4j_tpu.parallel.mesh.WeightUpdateSharding`)
    shards the weight update ZeRO-1 style: optax state leaves live as
    flattened ``(dp, chunk)`` views 1/dp per replica, gradients are
    reduce-scattered into that layout (under ``gradient_accumulation``
    the inner scan accumulates directly into the sharded view — each
    microbatch ships a reduce-scatter instead of a full all-reduce, and
    only ONE param-sized gather rides the update), the update is
    applied to the local shard only, and the updated params are
    all-gathered. The loss/param trajectory is exactly the replicated
    layout's — only the execution layout changes. While the trainer is
    attached, ``net.opt_state`` holds the SHARDED views (sharded
    checkpoints round-trip them natively); call :meth:`gather_opt_state`
    before handing the net to the zip serializer or a non-zero1 trainer.

    ``weight_update_sharding="zero2"`` goes one rung further: on the
    per-update path the reduced gradient exists ONLY as the flattened
    ``(dp, chunk)`` shards — zero1's replicated gradient anchor is
    dropped, so the program never requires a full-size reduced gradient
    per replica, gradient HBM drops 1/dp alongside the updater state,
    and the only full-size collective left per update is the param
    all-gather. Inside the ``gradient_accumulation`` scan the
    per-microbatch anchor is retained (GSPMD repartitions the scan body
    without it and bitwise parity dies — see ``to_shards``); the
    sharded ACCUMULATOR carries the scan path's 1/dp gradient memory.
    Same fp32 bitwise-parity guarantee as zero1
    (``tools/zero2_smoke.py``).

    ``precision`` (a :class:`~deeplearning4j_tpu.nn.updater.
    PrecisionPolicy`, a preset name like ``"bf16"``, or None to inherit
    ``net.conf.training.precision``): under a mixed policy the step
    casts params and float batch features to the compute dtype at its
    boundary, runs forward/backward in half precision, and keeps the
    fp32 master weights + every post-gradient op (loss, clip, optax,
    divergence sentinel) in fp32 — composing with every
    weight-update-sharding mode. The fp32 default gates all casts out.

    ``tuned`` (a :class:`~deeplearning4j_tpu.autotune.config.
    TunedConfig`): construct at the autotuner's chosen configuration —
    fills the mesh (when none is given) and any of
    ``gradient_accumulation`` / ``weight_update_sharding`` /
    ``precision`` left at their defaults. Explicit kwargs win, so a
    tuned config can be partially overridden. Probe parity
    (``tools/autotune_smoke.py``) gates that this path trains bitwise
    identically to hand-building the same knobs.
    """

    def __init__(self, net, mesh: Optional[MeshContext] = None,
                 gradient_accumulation: int = 1,
                 donate_params: bool = True,
                 collect_training_stats: bool = False,
                 weight_update_sharding=None,
                 precision=None,
                 tuned=None):
        if tuned is not None:
            if mesh is None:
                mesh = tuned.mesh_context()
            if gradient_accumulation == 1:
                gradient_accumulation = tuned.gradient_accumulation
            if weight_update_sharding is None:
                weight_update_sharding = tuned.weight_update_sharding
            if precision is None:
                precision = tuned.precision
        self.net = net
        self.mesh = mesh or MeshContext.create()
        self.gradient_accumulation = max(1, gradient_accumulation)
        self.weight_update_sharding = WeightUpdateSharding.parse(
            weight_update_sharding)
        self.mesh.validate_weight_update_sharding(
            self.weight_update_sharding)
        training_conf = net.conf.training
        self.precision = PrecisionPolicy.parse(
            precision if precision is not None
            else getattr(training_conf, "precision", None),
            loss_scale=getattr(training_conf, "loss_scale", None))
        self._step = None
        self._donate = donate_params
        # per-phase telemetry, ref ParameterAveragingTrainingMasterStats
        # (Spark tier's collectTrainingStats flag). Syncs the device every
        # step when on — accurate step timing is not free.
        self.training_stats = (TrainingStats()
                               if collect_training_stats else None)
        net._check_init()
        self._is_graph = not hasattr(net, "layers")
        self._layers = (
            [net.conf.nodes[n].layer for n in net._layer_nodes]
            if self._is_graph else net.layers)
        # reshard model state onto the mesh
        net.params = self.mesh.shard_params(net.params)
        net.states = jax.tree.map(
            lambda x: jax.device_put(x, self.mesh.replicated()), net.states)
        # PRESERVE accumulated optimizer state (Adam moments etc.) when
        # wrapping an already-trained net — re-initializing would spike
        # the loss on resume. Replicated mode: leaves land replicated and
        # the first donated step re-lays them out to whatever XLA
        # computes. zero1: leaves are flattened+padded and placed 1/dp
        # over the data axis — the layout they keep for the whole run.
        self._opt_template = None
        if self.weight_update_sharding.enabled:
            net.opt_state, self._opt_template = shard_updater_state(
                net.opt_state, self.mesh,
                self.weight_update_sharding.axis)
        else:
            rep = self.mesh.replicated()
            net.opt_state = jax.tree.map(
                lambda x: jax.device_put(x, rep) if hasattr(x, "shape")
                else x, net.opt_state)

    # ------------------------------------------------------------- the step
    def _build_step(self):
        net = self.net
        training = net.conf.training
        tx = net._tx
        accum = self.gradient_accumulation
        sentinel = getattr(net, "_sentinel", None)
        layers = self._layers
        sharded = self.weight_update_sharding.enabled
        zero2 = self.weight_update_sharding.zero2
        mesh_ctx = self.mesh
        z_axis = self.weight_update_sharding.axis
        policy = self.precision
        mixed = policy.mixed
        if sharded:
            dp = mesh_ctx.zero1_shards(z_axis)
            z_sharding = mesh_ctx.zero1_sharding(z_axis)
            rep_sharding = mesh_ctx.replicated()
            # COMPOSITION WORKAROUND (flushed out by the GPT LM, ISSUE
            # 14): on a mesh that ALSO carries an 'sp' axis, the
            # with_sharding_constraint(zero1_shard_leaf(g), P(dp, None))
            # op makes GSPMD double-apply the sp-axis psum to gradient
            # leaves whose grad is a pure reduction over the (data, sp)-
            # sharded batch (measured on CPU dp=2 x sp=2, jax 0.4.37:
            # a loss-head bias gradient comes back exactly sp-times too
            # large; every other leaf bitwise-identical; the replicated
            # anchor alone and the unconstrained (dp, chunk) reshape are
            # both correct — ONLY the explicit shard constraint
            # miscompiles). Under sp, keep the anchored (dp, chunk)
            # VIEW but skip the layout constraint: values stay exactly
            # the replicated program's (the bitwise spine holds,
            # tools/lm_smoke.py gates it); the in-step gradient may
            # stay replicated instead of reduce-scattered — a layout
            # pessimization on sp meshes, never a correctness change.
            sp_mesh = mesh_ctx.seq_axis is not None

            def pin_replicated(tree):
                return jax.tree.map(
                    lambda t: jax.lax.with_sharding_constraint(
                        t, rep_sharding), tree)

            def to_shards(g, in_scan: bool = False):
                """Full-shape gradient tree -> flattened (dp, chunk)
                views sharded over the data axis. Under zero1 a
                replicated anchor first pins the forward/backward
                partitioning to the exact replicated-mode program (loss
                parity stays bitwise); the shard constraint then lets
                XLA fold the gradient all-reduce + shard slice into a
                reduce-scatter. Under zero2 the anchor is DROPPED from
                the per-update path: the sharded view is the
                gradients' only constraint, so the reduce-scatter is
                their native layout and the program never requires a
                full-size reduced gradient per replica — gradient HBM
                drops with the axis. INSIDE the ga scan the anchor is
                kept for every mode: without it GSPMD repartitions the
                scan body itself (measured on CPU dp=2 — the local
                forward/loss reductions reassociate, and in one
                observed layout the forward matmuls all-gather sharded
                weights), which breaks the bitwise gate; the sharded
                ACCUMULATOR already holds the scan path's 1/dp
                gradient-memory win, and the anchored per-microbatch
                sum stays transient.
                """
                if in_scan or not zero2 or sp_mesh:
                    g = pin_replicated(g)
                if sp_mesh:
                    # see sp_mesh above: anchored view, no constraint
                    return jax.tree.map(
                        lambda t: zero1_shard_leaf(t, dp), g)
                return jax.tree.map(
                    lambda t: jax.lax.with_sharding_constraint(
                        zero1_shard_leaf(t, dp), z_sharding), g)

        # both containers' _loss_fn share the positional signature
        # (params, states, inputs, labels, masks, label_masks) — inputs/
        # labels/masks are arrays for MLN, name-keyed dicts for a graph
        def loss_fn(p, states, feats, labels, fmask, lmask, rng):
            return net._loss_fn(p, states, feats, labels, fmask, lmask,
                                rng=rng, train=True)

        # fp32 policy: the plain jax.value_and_grad — the exact
        # pre-policy program. Mixed: params/features cast to the compute
        # dtype at the step boundary, loss + grads handed back in fp32.
        vag = precision_value_and_grad(loss_fn, policy)

        def step(params, opt_state, states, feats, labels, fmask, lmask, rng):
            if mixed:
                feats = cast_floats(feats, policy.compute_dtype)
                fmask = cast_floats(fmask, policy.compute_dtype)
            if accum == 1:
                (loss, new_states), grads = vag(params, states, feats,
                                                labels, fmask, lmask, rng)
                if sharded:
                    grads = to_shards(grads)
            else:
                # microbatch split along the batch axis inside the step:
                # local accumulation between synchronizations = the
                # averagingFrequency semantics, without ever materializing
                # per-worker model copies
                def micro(carry, mb):
                    g_acc, l_acc, st = carry
                    f, l, fm, lm, r = mb
                    (loss, st2), g = vag(params, st, f, l, fm, lm, r)
                    if sharded:
                        # accumulate straight into the sharded layout:
                        # cross-chip traffic per microbatch becomes one
                        # reduce-scatter of g instead of a full
                        # all-reduce, and the accumulator itself holds
                        # only 1/dp per chip
                        g = to_shards(g, in_scan=True)
                    g_acc = jax.tree.map(lambda a, b: a + b, g_acc, g)
                    return (g_acc, l_acc + loss, st2), None

                leaves = jax.tree_util.tree_leaves(feats)
                B = leaves[0].shape[0]
                if B % accum != 0:
                    raise ValueError(
                        f"batch size {B} not divisible by "
                        f"gradient_accumulation={accum}")
                mb_size = B // accum

                def split(x):
                    return jax.tree.map(
                        lambda a: a.reshape((accum, mb_size) + a.shape[1:]),
                        x)

                rngs = jax.random.split(rng, accum)
                zero_g = jax.tree.map(jnp.zeros_like, params)
                if sharded:
                    zero_g = to_shards(zero_g, in_scan=True)
                (grads, loss, new_states), _ = jax.lax.scan(
                    micro, (zero_g, jnp.zeros(()), states),
                    (split(feats), split(labels), split(fmask),
                     split(lmask), rngs))
                grads = jax.tree.map(lambda g: g / accum, grads)
                loss = loss / accum
            if sharded:
                new_params, new_opt = compute_updates_sharded(
                    tx, grads, opt_state, params, layers, training,
                    mesh_ctx, z_axis)
            else:
                new_params, new_opt = compute_updates(
                    tx, grads, opt_state, params, layers, training)
            # the sentinel's guard: under zero1/zero2 `grads` are the
            # sharded (dp, chunk) views, so its grad-norm reduction is a
            # psum of local-shard norms — same flag value, no extra
            # gather. Under a mixed policy both loss and grads crossed
            # the fp32 seam before reaching it.
            return step_result(
                sentinel is not None, loss, grads,
                (params, opt_state, states),
                (new_params, new_opt, new_states))

        donate = (0, 1, 2) if self._donate else ()
        return jax.jit(step, donate_argnums=donate)

    # ---------------------------------------------------- shared step prep
    def _ensure_step(self) -> None:
        """(Re)build the cached jitted step — shared by fit_batch and
        step_program so the analyzed program is EXACTLY the one fit
        runs, including the sentinel-change rebuild."""
        net = self.net
        if (self.weight_update_sharding.enabled
                and self._opt_template is None):
            # a gather_opt_state() between fits put the replicated
            # layout back on the net — restore the sharded contract the
            # compiled zero1 step runs on
            net.opt_state, self._opt_template = shard_updater_state(
                net.opt_state, self.mesh, self.weight_update_sharding.axis)
        if (self._step is None
                or getattr(self, "_step_sentinel", None)
                is not getattr(net, "_sentinel", None)):
            # a sentinel attached/detached after the first build: the
            # guarded step is a different program — rebuild
            self._step_sentinel = getattr(net, "_sentinel", None)
            self._step = self._build_step()

    def _shard_batch_args(self, batch):
        """Place one batch in the step's NamedSharding layout —
        (feats, labels, fmask, lmask), the per-batch half of the step's
        argument list. One copy, so fit and shardcheck cannot drift."""
        net = self.net
        if self._is_graph:
            # name-keyed dicts (DataSet or MultiDataSet), every leaf
            # sharded over the data axis
            inputs, lbls, masks, lmasks_d = net._split(batch)
            shard = lambda t: jax.tree.map(self.mesh.shard_batch, t)
            return (shard(inputs), shard(lbls), shard(masks),
                    shard(lmasks_d))
        feats, labels = self.mesh.shard_batch(
            jnp.asarray(batch.features), jnp.asarray(batch.labels))
        fmask = lmask = None
        if batch.features_mask is not None:
            fmask = self.mesh.shard_batch(jnp.asarray(batch.features_mask))
        if batch.labels_mask is not None:
            lmask = self.mesh.shard_batch(jnp.asarray(batch.labels_mask))
        return feats, labels, fmask, lmask

    # ------------------------------------------------------- shardcheck
    def step_program(self, batch):
        """Capture THIS trainer's compiled per-batch step program for
        ``batch`` (analysis/shardcheck) — one AOT compile, no
        execution, donated buffers untouched."""
        from deeplearning4j_tpu.analysis.shardcheck import lower_step_program
        net = self.net
        self._ensure_step()
        feats, labels, fmask, lmask = self._shard_batch_args(batch)
        with sequence_parallel_scope(self.mesh):
            return lower_step_program(
                self._step, net.params, net.opt_state, net.states, feats,
                labels, fmask, lmask, jax.random.PRNGKey(0))

    def shardcheck_context(self) -> dict:
        """The layout context ``analysis/shardcheck`` validates this
        trainer's program against — what the program CLAIMS to be."""
        from deeplearning4j_tpu.analysis.shardcheck import param_leaf_sizes
        return dict(
            weight_update_sharding=self.weight_update_sharding.mode,
            dp=self.mesh.n_data,
            gradient_accumulation=self.gradient_accumulation,
            sp=(self.mesh.mesh.shape[self.mesh.seq_axis]
                if self.mesh.seq_axis else 1),
            precision=self.precision,
            expect_donation=self._donate,
            param_leaf_sizes=param_leaf_sizes(self.net.params))

    def shardcheck(self, batch, **overrides):
        """Statically verify the compiled step honors this trainer's
        declared layout: reduce-scatter form under zero1/zero2 (SC001),
        collective census (SC002), ga-scan anchor (SC003), precision
        boundaries (SC004), donation (SC005), no host transfers
        (SC006), comm-bytes calibration (SC007). Returns findings; runs
        on CPU in seconds with no training step executed."""
        from deeplearning4j_tpu.analysis.shardcheck import check_step_program
        ctx = self.shardcheck_context()
        ctx.update(overrides)
        return check_step_program(self.step_program(batch), **ctx)

    def gather_opt_state(self):
        """Restore ``net.opt_state`` to its original (replicated) layout
        and return it. Under zero1 the net holds the flattened sharded
        views while this trainer is attached; gather before handing the
        net to the zip serializer, a non-zero1 trainer, or single-device
        inference-with-resume. A no-op in replicated mode."""
        if self._opt_template is not None:
            self.net.opt_state = gather_updater_state(
                self.net.opt_state, self._opt_template)
            self._opt_template = None
        return self.net.opt_state

    # ------------------------------------------------------------------- fit
    def fit_batch(self, batch) -> float:
        net = self.net
        self._ensure_step()
        stats = self.training_stats
        # global-tracer spans (profiling/): host-side timeline of the
        # same phases the stats flag times — unconditional because the
        # tracer is cheap and the open-span stack is the hang diagnosis.
        # `with` (not bare begin/end): a raising step must close the
        # span AND note it on the tracer's error stack, or one caught
        # exception would leak an open span into every later diagnosis
        tracer = get_tracer()
        with tracer.span("shard"):
            t_shard = time.perf_counter() if stats else 0.0
            feats, labels, fmask, lmask = self._shard_batch_args(batch)
            if stats:
                # sync the async device_put so transfer time lands in
                # 'shard', not 'step' — over a slow host link that
                # distinction is the whole point of the phase
                jax.block_until_ready((feats, labels))
                stats.record("shard", time.perf_counter() - t_shard)
                t_step = time.perf_counter()
        with tracer.span("step"):
            net._rng, step_rng = jax.random.split(net._rng)
            # the scope routes SelfAttentionLayer through ring attention
            # over the mesh's 'sp' axis at trace time (no-op without one)
            with sequence_parallel_scope(self.mesh):
                out = self._step(
                    net.params, net.opt_state, net.states, feats, labels,
                    fmask, lmask, step_rng)
                net.params, net.opt_state, net.states, loss = out[:4]
            if stats:
                jax.block_until_ready(loss)
                stats.record("step", time.perf_counter() - t_step)
        net.last_batch_size = batch.num_examples()
        net.last_grads = None  # SPMD step doesn't collect gradients
        # raw device scalar: converting here would sync the SPMD pipeline
        # every step (see MultiLayerNetwork.score_value)
        net.score_value = loss
        net.iteration_count += 1
        if hasattr(net, "_observe_sentinel"):
            net._observe_sentinel(out[4] if len(out) > 4 else None)
        with tracer.span("listener"), maybe_phase(stats, "listener"):
            for listener in net.listeners:
                listener.iteration_done(net, net.iteration_count,
                                        net.score_value)
        return net._score_raw

    def fit(self, data: Union[DataSet, DataSetIterator], epochs: int = 1,
            use_async: bool = True,
            scan_window: int = 1) -> "ParallelTrainer":
        """``scan_window > 1``: see fit_batches_scan."""
        if isinstance(data, DataSet):
            for _ in range(epochs):
                self.fit_batch(data)
            return self
        if hasattr(data, "attach"):
            # streaming input pipeline: bind its device stage to THIS
            # mesh so batches arrive pre-placed in the step's
            # NamedSharding batch layout (the in-step shard_batch then
            # finds them already placed and moves nothing) — instead of
            # landing replicated and resharding every step. The scan
            # path stacks a window of batches HOST-side before placing
            # the stack, so per-batch device staging would only force a
            # D2H round trip (and crash multi-process, where pulling a
            # global array back to one host is illegal) — keep those
            # host-side.
            data.attach(mesh=self.mesh,
                        place=False if scan_window > 1 else None)
        it = (AsyncDataSetIterator(data)
              if use_async and data.async_supported() else data)
        stats = self.training_stats
        for _ in range(epochs):
            src = stats.timed_iter(it) if stats else it
            if scan_window > 1:
                # reuse the containers' windowing loop (only needs
                # fit_batches_scan / fit_batch from self)
                ScanFitMixin._fit_epoch_scan(self, src, scan_window)
            else:
                for batch in src:
                    self.fit_batch(batch)
            self.net.epoch_count += 1
        return self

    # ---------------------------------------------------------- scan windows
    def fit_batches_scan(self, batches):
        """N SPMD optimization steps as ONE jitted lax.scan program over
        the mesh (the single-device fit_batches_scan, sharded): stacked
        batches are placed with the leading window axis replicated and
        the batch axis sharded over 'data', so the scan body runs the
        same NamedSharding step the per-batch path compiles. Falls back
        to the fit_batch loop for masked/ragged/MultiDataSet windows."""
        net = self.net
        batches = list(batches)
        if not batches:
            return np.zeros((0,), np.float32)
        scannable = (
            not self._is_graph
            # sentinel policies need per-step flags (see netcommon's
            # fit_batches_scan) — fall back to the fit_batch loop
            and getattr(net, "_sentinel", None) is None
            and all(isinstance(b, DataSet)
                    and b.features_mask is None and b.labels_mask is None
                    for b in batches)
            and len({(np.shape(b.features), np.shape(b.labels))
                     for b in batches}) == 1)
        if not scannable:
            return np.asarray([float(self.fit_batch(b))
                               for b in batches], np.float32)
        if (self.weight_update_sharding.enabled
                and self._opt_template is None):
            net.opt_state, self._opt_template = shard_updater_state(
                net.opt_state, self.mesh, self.weight_update_sharding.axis)
        if self._step is None:
            self._step = self._build_step()
        cached = getattr(self, "_scan_step", None)
        if cached is None or cached[0] is not self._step:
            self._scan_step = (self._step, make_scan_fit(
                self._step,
                donate_argnums=(0, 1, 2) if self._donate else ()))
        scan_fn = self._scan_step[1]

        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = self.mesh.mesh
        data_axis = self.mesh.data_axis

        def place(arrs):
            stacked = np.stack([np.asarray(a) for a in arrs])
            # reuse the per-batch sharding policy (incl. its sp-axis
            # rule) with the window axis prepended — reimplementing the
            # divisibility decision here would let the two paths drift
            batch_spec = self.mesh.batch_sharding(
                stacked.ndim - 1, stacked.shape[1:]).spec
            spec = P(None, *batch_spec)
            return jax.device_put(stacked, NamedSharding(mesh, spec))

        stats = self.training_stats
        tracer = get_tracer()
        with tracer.span("shard", window=len(batches)):
            t_shard = time.perf_counter() if stats else 0.0
            feats = place([b.features for b in batches])
            labels = place([b.labels for b in batches])
            if stats:
                jax.block_until_ready((feats, labels))
                stats.record("shard", time.perf_counter() - t_shard)
                t_step = time.perf_counter()
        with tracer.span("scan_step", window=len(batches)):
            t0 = time.perf_counter()
            net._rng, r = jax.random.split(net._rng)
            with sequence_parallel_scope(self.mesh):
                net.params, net.opt_state, net.states, losses = scan_fn(
                    net.params, net.opt_state, net.states, feats, labels, r)
            if stats:
                jax.block_until_ready(losses)
                stats.record("step", time.perf_counter() - t_step)
        net.last_batch_size = batches[-1].num_examples()
        net.last_grads = None
        if net.listeners:
            emit_scan_burst(net, losses, len(batches), t0, stats=stats)
        else:
            net.iteration_count += len(batches)
        net.score_value = losses[-1]
        return losses
