"""Pipeline parallelism: GPipe-style microbatch pipeline over a mesh axis.

No counterpart exists in the reference (data parallelism only — SURVEY
§2.3); this is part of the TPU build's first-class scale-out. Two tiers:

- ``pipeline_apply`` — the homogeneous-stage primitive (same activation
  shape everywhere): stacked params sharded over mesh axis ``pp``, one
  ``ppermute`` ring hop per tick (ICI neighbor traffic only).
- ``PipelineTrainer`` — a real ``MultiLayerNetwork`` partitioned into S
  contiguous stages balanced by parameter count, with NON-homogeneous
  activation shapes and heterogeneous per-stage layer programs. Every
  device runs the same SPMD program (an XLA requirement): stage programs
  are branches of one ``lax.switch`` selected by the device's position on
  the ``pp`` axis, and both params and boundary activations travel as
  flat, right-padded buffers of the maximum stage size, reshaped to their
  true shapes inside each branch. The GPipe schedule is unchanged: M
  microbatches drain the bubble in S-1 ticks — utilization M/(M+S-1).
  Composes with data parallelism: if the mesh also has a ``dp`` axis the
  microbatch batch dim is sharded over it (dp×pp), and XLA inserts the
  gradient all-reduce over ``dp`` outside the shard_map.

Reachable through the strategy SPI: ``create_trainer("pipeline", net,
mesh)`` (ref: TrainingMaster SPI, spark/dl4j-spark/.../api/
TrainingMaster.java:29 — the strategy seam this plugs into).
"""

from __future__ import annotations

import logging
import time
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.netcommon import step_result
from deeplearning4j_tpu.nn.updater import compute_updates, l1_l2_penalty
from deeplearning4j_tpu.profiling import get_tracer

logger = logging.getLogger(__name__)

# one process-wide aux-loss semantics warning (see PipelineTrainer)
_WARNED_AUX_MICROBATCH = False


def _pvary(x, axis):
    return jax.lax.pcast(x, axis, to="varying")


def pipeline_apply(stage_fn: Callable, stacked_params, x_microbatches,
                   mesh: Mesh, axis: str = "pp"):
    """Run a homogeneous pipeline.

    stage_fn(params_slice, x) -> y with y.shape == x.shape (homogeneous
    stages). ``stacked_params``: pytree with leading stage axis S == mesh
    size over ``axis``. ``x_microbatches``: [M, B_mb, ...] (replicated).
    Returns [M, B_mb, ...] outputs of the final stage.
    """
    S = mesh.shape[axis]
    M = x_microbatches.shape[0]
    T = M + S - 1  # total ticks incl. pipeline fill

    def device_fn(params, xs):
        # params: this stage's slice, leading axis 1; xs: all microbatches
        params = jax.tree.map(lambda a: a[0], params)
        sid = jax.lax.axis_index(axis)
        perm = [(j, (j + 1) % S) for j in range(S)]

        def tick(carry, t):
            held, outbuf = carry
            # stage 0 injects microbatch t (zeros once drained)
            inject = jnp.where(t < M, t, 0)
            x_in = jnp.where(sid == 0, xs[inject], held)
            y = stage_fn(params, x_in)
            # last stage stores finished microbatch t-(S-1)
            done_idx = t - (S - 1)
            store = jnp.logical_and(sid == S - 1, done_idx >= 0)
            idx = jnp.maximum(done_idx, 0)
            cur = jax.lax.dynamic_index_in_dim(outbuf, idx, 0, keepdims=False)
            val = jnp.where(store, y, cur)
            outbuf = jax.lax.dynamic_update_index_in_dim(outbuf, val, idx, 0)
            # hand activation to the next stage
            held_next = jax.lax.ppermute(y, axis, perm)
            return (held_next, outbuf), None

        # carries must be device-varying to match the scan body
        held0 = _pvary(xs[0] * 0.0, axis)
        outbuf0 = _pvary(xs * 0.0, axis)
        (_, outbuf), _ = jax.lax.scan(tick, (held0, outbuf0), jnp.arange(T))
        # every device returns its buffer; only the last stage's is real.
        # psum gathers it to all (cheap: zeros elsewhere).
        return jax.lax.psum(outbuf, axis)

    fn = shard_map(device_fn, mesh=mesh,
                   in_specs=(P(axis), P()),
                   out_specs=P())
    return fn(stacked_params, x_microbatches)


def stack_stage_params(param_list):
    """Stack per-stage param pytrees along a new leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *param_list)


def _make_ring(mesh: Mesh, axis: str, dp_axis: Optional[str], S: int,
               M: int, branches):
    """The GPipe ring schedule as a shard_map callable shared by the MLN
    and graph pipeline trainers:
    pipe(param_bufs [S, Pmax], state_bufs [S, Smax], carry_bufs [S, Cmax],
    xs [M, B_mb, Amax]) -> (outputs [M, B_mb, Amax],
    new_state_bufs [S, Smax], new_carry_bufs [S, Cmax]).

    Each branch is branch(pflat, sflat, cflat, xbuf, key, m) ->
    (ybuf, sflat_new, cflat_new); ``key`` is a per-(tick, stage[, dp
    shard]) PRNG key folded from the step's base rng — the dropout
    stream — and ``m`` the microbatch index the tick processes (carry
    segments are per-microbatch slices). State updates apply only on
    REAL ticks (stage s works on genuine microbatches at ticks
    s <= t < s+M; fill/drain ticks process ring garbage). Running-state
    rows pmean-sync over ``dp_axis`` after the window; carry rows do NOT
    (tBPTT carries are per-batch-row, never averaged — the trainers
    reject dp meshes when carries are live)."""

    def device_fn(bufs, sbufs, cbufs, xs, rng):
        sid = jax.lax.axis_index(axis)
        pflat, srow, crow = bufs[0], sbufs[0], cbufs[0]
        perm = [(j, (j + 1) % S) for j in range(S)]
        key_base = jax.random.fold_in(rng, sid)
        if dp_axis is not None:
            # decorrelate dropout masks across dp shards
            key_base = jax.random.fold_in(
                key_base, jax.lax.axis_index(dp_axis))

        def tick(carry, t):
            held, outbuf, sflat, cflat = carry
            inject = jnp.where(t < M, t, 0)
            x_in = jnp.where(sid == 0, xs[inject], held)
            m = jnp.clip(t - sid, 0, M - 1)
            y, sflat2, cflat2 = jax.lax.switch(
                sid, branches, pflat, sflat, cflat, x_in,
                jax.random.fold_in(key_base, t), m)
            real = jnp.logical_and(t >= sid, t < sid + M)
            sflat = jnp.where(real, sflat2, sflat)
            cflat = jnp.where(real, cflat2, cflat)
            done_idx = t - (S - 1)
            store = jnp.logical_and(sid == S - 1, done_idx >= 0)
            idx = jnp.maximum(done_idx, 0)
            cur = jax.lax.dynamic_index_in_dim(outbuf, idx, 0,
                                               keepdims=False)
            outbuf = jax.lax.dynamic_update_index_in_dim(
                outbuf, jnp.where(store, y, cur), idx, 0)
            return (jax.lax.ppermute(y, axis, perm), outbuf, sflat,
                    cflat), None

        held0 = _pvary(xs[0] * 0.0, axis)
        outbuf0 = _pvary(xs * 0.0, axis)
        # the state carry must enter the switch varying over EVERY mesh
        # axis: stateful branches derive their output from the
        # (dp-varying) batch shard while stateless ones return the carry
        # itself — mismatched varying sets are a type error
        sflat0 = srow
        cflat0 = crow
        if dp_axis is not None:
            sflat0 = _pvary(sflat0, dp_axis)
            cflat0 = _pvary(cflat0, dp_axis)
        (_, outbuf, sflat, cflat), _ = jax.lax.scan(
            tick, (held0, outbuf0, sflat0, cflat0), jnp.arange(M + S - 1))
        if dp_axis is not None:
            # dp replicas saw different microbatch shards: sync the
            # running averages (normalization itself stays per-replica,
            # standard unsynced-BN semantics)
            sflat = jax.lax.pmean(sflat, dp_axis)
            cflat = jax.lax.pmean(cflat, dp_axis)  # dummy rows when dp on
        out = jax.lax.psum(outbuf, axis)
        return out, sflat[None], cflat[None]

    batch_spec = P(None, dp_axis, None)
    return shard_map(device_fn, mesh=mesh,
                     in_specs=(P(axis), P(axis), P(axis), batch_spec, P()),
                     out_specs=(batch_spec, P(axis), P(axis)))


class _RingFitMixin:
    """fit_batch/fit shared by the MLN and graph pipeline trainers (the
    jitted step signature and all bookkeeping are identical; only stage
    construction differs). Subclasses provide ``_build_step(b_mb)``
    setting ``self._amax``, and the attrs net/M/mesh/dp_axis; they may
    set ``training_stats`` (a TrainingStats) for per-phase telemetry."""

    training_stats = None
    _tbptt = False

    def fit_batch(self, batch: DataSet) -> float:
        net = self.net
        multi_io = getattr(self, "in_names", None)
        if not isinstance(batch, DataSet):
            from deeplearning4j_tpu.datasets.dataset import MultiDataSet
            if not (multi_io and isinstance(batch, MultiDataSet)):
                raise ValueError(
                    "this pipeline trainer takes a single-input DataSet; "
                    f"got {type(batch).__name__}")
            if any(m is not None for m in (batch.features_masks or []))\
                    or any(m is not None
                           for m in (batch.labels_masks or [])):
                raise ValueError("masked MultiDataSets are unsupported "
                                 "in the pipeline trainers")
            if len(batch.features) != len(self.in_names) \
                    or len(batch.labels) != len(self.out_names):
                raise ValueError(
                    f"MultiDataSet arity {len(batch.features)}in/"
                    f"{len(batch.labels)}out != network "
                    f"{len(self.in_names)}in/{len(self.out_names)}out")
            B = batch.features[0].shape[0]
            rt = net.conf.resolved_types
            for name, f in zip(self.in_names, batch.features):
                want = _type_elems(rt[name])
                got = int(np.prod(f.shape[1:]))
                if got != want:
                    raise ValueError(
                        f"input {name!r}: got {got} elements/sample "
                        f"{tuple(f.shape)}, network expects {want} "
                        f"({rt[name]})")
            # stage 0 unpacks the inputs from one concatenated flat
            # buffer, in network_inputs order (matches _make_branch)
            feats = jnp.concatenate(
                [jnp.asarray(f).reshape(B, -1) for f in batch.features],
                axis=1)
            labels = {o: jnp.asarray(l)
                      for o, l in zip(self.out_names, batch.labels)}
        else:
            if (batch.features_mask is not None
                    or batch.labels_mask is not None):
                # loud, like the other unsupported features — a silently
                # dropped mask would train a whole run subtly wrong
                raise ValueError("masked DataSets are unsupported in the "
                                 "pipeline trainers (mask threading "
                                 "through the ring schedule is future "
                                 "work)")
            feats = jnp.asarray(batch.features)
            labels = jnp.asarray(batch.labels)
        B = feats.shape[0]
        if B % self.M != 0:
            raise ValueError(f"batch size {B} not divisible by "
                             f"n_microbatches={self.M}")
        b_mb = B // self.M
        if self.dp_axis is not None:
            dp = self.mesh.shape[self.dp_axis]
            if b_mb % dp != 0:
                raise ValueError(
                    f"microbatch size {b_mb} (batch {B} / {self.M} "
                    f"microbatches) not divisible by the dp axis ({dp})")
        if self._tbptt and feats.ndim == 3:
            # rank-3 features + truncated_bptt => window the updates,
            # exactly MLN.fit_batch's routing (multilayer.py:327) —
            # including its loud rank-3-labels requirement: slicing a
            # rank-2 label tensor along time would shear off classes
            if labels.ndim != 3:
                raise ValueError(
                    "truncated_bptt requires rank-3 (time-distributed) "
                    "labels [B, T, K]; got rank-"
                    f"{labels.ndim} {tuple(labels.shape)} — use "
                    "standard backprop for sequence-to-one training")
            return self._fit_batch_tbptt(feats, labels, b_mb, B)
        if (self._step is None or getattr(self, "_b_mb", None) != b_mb
                or getattr(self, "_step_sentinel", None)
                is not getattr(net, "_sentinel", None)):
            # microbatch shape OR sentinel changed: different program
            self._step_sentinel = getattr(net, "_sentinel", None)
            self._step = self._build_step(b_mb)
            self._b_mb = b_mb
            self._tbptt_cache = getattr(self, "_tbptt_cache", {})
            self._tbptt_cache.clear()
        stats = self.training_stats
        # `with` spans (not bare begin/end): a raising step must close
        # its span and note it on the tracer's error stack, or a caught
        # exception would leak an open span into later hang diagnoses
        tracer = get_tracer()
        with tracer.span("shard"):
            t_shard = time.perf_counter() if stats else 0.0
            x = feats.reshape(self.M, b_mb, -1)
            xs = jnp.pad(x, ((0, 0), (0, 0),
                             (0, self._amax - x.shape[-1])))
            if stats:
                jax.block_until_ready(xs)
                stats.record("shard", time.perf_counter() - t_shard)
                t_step = time.perf_counter()
        with tracer.span("step", microbatches=self.M):
            net._rng, step_rng = jax.random.split(net._rng)
            cbuf = jnp.zeros((self.S, getattr(self, "_cmax", 1)),
                             jnp.float32)
            out = self._step(
                net.params, net.opt_state, net.states, cbuf, xs, labels,
                step_rng)
            net.params, net.opt_state, net.states, _, loss = out[:5]
            if stats:
                jax.block_until_ready(loss)
                stats.record("step", time.perf_counter() - t_step)
        net.last_batch_size = B
        net.score_value = loss
        net.iteration_count += 1
        if hasattr(net, "_observe_sentinel"):
            net._observe_sentinel(out[5] if len(out) > 5 else None)
        with tracer.span("listener"):
            t_l = time.perf_counter() if stats else 0.0
            for listener in net.listeners:
                listener.iteration_done(net, net.iteration_count,
                                        net.score_value)
            if stats:
                stats.record("listener", time.perf_counter() - t_l)
        return net._score_raw

    def _fit_batch_tbptt(self, feats, labels, b_mb: int, B: int) -> float:
        """Truncated BPTT through the ring: time windows run one pipeline
        step each; recurrent layers' final carries ride the (no-grad)
        carry buffer between windows, so gradients stop at window edges
        exactly like MLN._fit_tbptt (ref:
        MultiLayerNetwork.doTruncatedBPTT:1119-1183). Carries reset to
        zeros at batch start."""
        net = self.net
        fwd = net.conf.training.tbptt_fwd_length
        T = feats.shape[1]
        if (getattr(self, "_tbptt_sentinel", None)
                is not getattr(net, "_sentinel", None)):
            # sentinel changed: cached window steps are unguarded (or
            # stale-guarded) programs — rebuild them
            self._tbptt_sentinel = getattr(net, "_sentinel", None)
            self._tbptt_cache.clear()
        cbuf = None
        total, slices = 0.0, 0
        for start in range(0, T, fwd):
            end = min(start + fwd, T)
            w = end - start
            key = (b_mb, w)
            if key not in self._tbptt_cache:
                step = self._build_step(b_mb, timesteps=w)
                self._tbptt_cache[key] = (step, self._amax, self._cmax)
            step, amax, cmax = self._tbptt_cache[key]
            if cbuf is None:
                cbuf = jnp.zeros((self.S, cmax), jnp.float32)
            stats = self.training_stats
            t_shard = time.perf_counter() if stats else 0.0
            x = jnp.asarray(feats[:, start:end]).reshape(self.M, b_mb, -1)
            xs = jnp.pad(x, ((0, 0), (0, 0), (0, amax - x.shape[-1])))
            lw = jnp.asarray(labels[:, start:end])
            if stats:
                jax.block_until_ready((xs, lw))
                stats.record("shard", time.perf_counter() - t_shard)
                t_step = time.perf_counter()
            net._rng, step_rng = jax.random.split(net._rng)
            out = step(
                net.params, net.opt_state, net.states, cbuf, xs, lw,
                step_rng)
            net.params, net.opt_state, net.states, cbuf, loss = out[:5]
            if stats:
                jax.block_until_ready(loss)
                stats.record("step", time.perf_counter() - t_step)
            total = total + loss
            slices += 1
            net.score_value = loss
            net.iteration_count += 1
            if hasattr(net, "_observe_sentinel"):
                net._observe_sentinel(out[5] if len(out) > 5 else None)
            t_l = time.perf_counter() if stats else 0.0
            for listener in net.listeners:
                listener.iteration_done(net, net.iteration_count,
                                        net.score_value)
            if stats:
                stats.record("listener", time.perf_counter() - t_l)
        net.last_batch_size = B
        # device scalar, like MLN._fit_tbptt: converting here would sync
        # the dispatch pipeline every batch (multilayer.py:459-465)
        return total / max(slices, 1)

    def fit(self, data, epochs: int = 1):
        from deeplearning4j_tpu.optimize.listeners import TrainingListener
        net = self.net
        if isinstance(data, DataSet):
            data = [data]
        stats = self.training_stats
        for _ in range(epochs):
            for listener in net.listeners:
                if isinstance(listener, TrainingListener):
                    listener.on_epoch_start(net)
            src = stats.timed_iter(data) if stats else data
            for batch in src:
                self.fit_batch(batch)
            net.epoch_count += 1
            for listener in net.listeners:
                if isinstance(listener, TrainingListener):
                    listener.on_epoch_end(net)
        return self


def _reject_remat(conf):
    """The pipeline branches run layer.apply without jax.checkpoint: a
    remat'd config would silently lose its gradient checkpointing (and
    its memory headroom) — fail loudly like the other unsupported
    features."""
    if getattr(conf.training, "remat", False):
        raise ValueError(
            "gradient_checkpointing (remat) is unsupported in the "
            "pipeline trainers — stage branches store activations for "
            "backward; disable remat or train without the pipeline")


# ---------------------------------------------------------------------------
# heterogeneous pipeline over a real MultiLayerNetwork
# ---------------------------------------------------------------------------

def _optimal_cuts(costs, boundaries, n_stages):
    """Place ``n_stages - 1`` cuts from the candidate ``boundaries``
    (each a (position, activation_elems) pair; position b cuts between
    item b-1 and item b) minimizing

        max_stage(sum costs) + act_weight-scaled max_cut(activation)

    where the caller pre-scales the activation term into the boundary
    values. Exact O(S * n^2) DP — the candidate sets are tiny (layers of
    one network). Returns the chosen cut positions, sorted."""
    n = len(costs)
    ps = [0]
    for c in costs:
        ps.append(ps[-1] + c)

    def seg(a, b):  # cost of items a..b-1
        return ps[b] - ps[a]

    acts = sorted({a for _, a in boundaries})
    best_obj, best_cuts = None, None
    for amax in acts:
        allowed = sorted(p for p, a in boundaries if a <= amax)
        if len(allowed) < n_stages - 1:
            continue
        # dp over (stage count k, last cut position): minimal max stage
        # cost for items[0:pos] split into k stages. This pass finds only
        # the optimal VALUE; the winning amax's DP is re-run below with
        # parent links to recover the actual cut positions.
        INF = float("inf")
        dp = {0: 0.0}  # pos -> best max-cost using k cuts so far
        for _ in range(n_stages - 1):
            nxt = {}
            for pos, m in dp.items():
                for q in allowed:
                    if q <= pos:
                        continue
                    v = max(m, seg(pos, q))
                    if v < nxt.get(q, INF):
                        nxt[q] = v
            dp = nxt
            if not dp:
                break
        if not dp:
            continue
        m = min((max(v, seg(pos, n)), pos) for pos, v in dp.items())
        obj = m[0] + amax
        if best_obj is None or obj < best_obj:
            best_obj, best_cuts = obj, (amax, m[0])
    if best_cuts is None:
        return None
    # re-run the DP for the winning amax, tracking parents, to recover
    # the actual cut positions
    amax = best_cuts[0]
    allowed = sorted(p for p, a in boundaries if a <= amax)
    dp = {0: (0.0, None)}
    layers_dp = [dp]
    for _ in range(n_stages - 1):
        nxt = {}
        for pos, (m, _par) in layers_dp[-1].items():
            for q in allowed:
                if q <= pos:
                    continue
                v = max(m, seg(pos, q))
                if q not in nxt or v < nxt[q][0]:
                    nxt[q] = (v, pos)
        layers_dp.append(nxt)
    end = min(layers_dp[-1].items(), key=lambda kv: max(kv[1][0], seg(kv[0], n)))
    cuts = []
    pos = end[0]
    for k in range(n_stages - 1, 0, -1):
        cuts.append(pos)
        pos = layers_dp[k][pos][1]
    return sorted(cuts)


def partition_stages(layers, params, n_stages: int,
                     act_elems: Optional[Sequence[float]] = None,
                     act_weight: float = 1.0) -> List[List[int]]:
    """Split body-layer indices into ``n_stages`` contiguous groups (the
    reference has no analog — its scale-out clones whole models; stage
    partitioning is the TPU build's model-parallel axis).

    Cost model: exact DP minimizing ``max_stage(param_count) +
    act_weight * max_cut(act_elems)``. The second term is the ring's
    per-tick ppermute payload — boundary activations travel right-padded
    to the LARGEST cut's size, so one fat cut (e.g. ResNet's 56x56x256
    early stage) taxes every hop of every tick; a param-only balance
    cannot see that (VERDICT r4 weak #3). ``act_elems[i]`` = activation
    elements per sample crossing the boundary after layer ``i``; when
    None the activation term is zero and the DP reduces to optimal
    param-count balance (better than the old greedy fair-share, same
    objective)."""
    n = len(layers)
    if n_stages > n:
        # more devices on the pp axis than body layers: trailing stages
        # are identity pass-throughs (the ring hop still runs; they add
        # bubble ticks but keep the mesh shape unconstrained)
        return ([[i] for i in range(n)]
                + [[] for _ in range(n_stages - n)])
    costs = [sum(int(np.prod(v.shape)) for v in params[i].values()) + 1
             for i in range(n)]
    if act_elems is None:
        bounds = [(b, 0.0) for b in range(1, n)]
    else:
        bounds = [(b, act_weight * float(act_elems[b - 1]))
                  for b in range(1, n)]
    cuts = _optimal_cuts(costs, bounds, n_stages)
    if cuts is None:  # n_stages == 1
        return [list(range(n))]
    edges = [0] + cuts + [n]
    return [list(range(edges[i], edges[i + 1]))
            for i in range(len(edges) - 1)]


def _type_elems(t) -> int:
    """Per-sample activation elements of an InputType."""
    return int(np.prod(_type_shape(t, 1)))


def _true_layer_shapes(conf, layers, b: int,
                       timesteps: Optional[int] = None) -> List[tuple]:
    """[input_shape, out_of_layer_0, ..., out_of_last] — the TRUE tensor
    shapes flowing between layers. This differs from the InputType walk
    in one place: RnnToFeedForward/FeedForwardToRnn preprocessors are
    no-ops here (the broadcast form keeps [B, T, F] through FF layers,
    see nn/conf/preprocessors.py:84-104), so an ff-typed tensor inside
    such a region still carries the time axis. ``timesteps`` overrides
    the recurrent input length (tBPTT windows)."""
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.preprocessors import (
        FeedForwardToRnnPreProcessor, RnnToFeedForwardPreProcessor)
    cur = conf.input_type
    if timesteps is not None and cur.kind == "rnn":
        cur = InputType.recurrent(cur.size, timesteps)
    broadcast_t: Optional[int] = None  # live time axis on an ff type

    def true_shape(t, bt):
        if t.kind == "ff" and bt:
            return (b, bt, t.size)
        return _type_shape(t, b)

    shapes = [true_shape(cur, broadcast_t)]
    for i, layer in enumerate(layers):
        if i in conf.preprocessors:
            pre = conf.preprocessors[i]
            if isinstance(pre, RnnToFeedForwardPreProcessor):
                broadcast_t = cur.timesteps
            cur = pre.infer_output_type(cur)
            if (isinstance(pre, FeedForwardToRnnPreProcessor)
                    and cur.timesteps is None and broadcast_t):
                cur = InputType.recurrent(cur.size, broadcast_t)
            if cur.kind != "ff":
                broadcast_t = None
        cur = layer.infer_output_type(cur)
        if cur.kind == "rnn":
            if cur.timesteps is None and broadcast_t:
                cur = InputType.recurrent(cur.size, broadcast_t)
            broadcast_t = None
        shapes.append(true_shape(cur, broadcast_t))
    return shapes


def _mln_boundary_elems(conf, layers) -> List[int]:
    """Per-sample activation elements leaving each body layer (the ring
    payload if the stage cut lands after that layer)."""
    shapes = _true_layer_shapes(conf, layers, 1)
    return [int(np.prod(s[1:])) for s in shapes[1:]]


def _type_shape(t, batch: int):
    """Concrete activation shape for an InputType at a given batch size."""
    if t.kind == "ff":
        return (batch, t.size)
    if t.kind == "rnn":
        if t.timesteps is None:
            raise ValueError("PipelineTrainer needs fixed timesteps in the "
                             "recurrent InputType (static shapes under jit)")
        return (batch, t.timesteps, t.size)
    if t.kind == "cnn":
        return (batch, t.height, t.width, t.channels)
    raise ValueError(f"Unsupported InputType kind {t.kind!r}")


class PipelineTrainer(_RingFitMixin):
    """GPipe pipeline-parallel trainer for a ``MultiLayerNetwork``.

    The net's body layers (all but the loss head) are partitioned into S
    contiguous stages; each pipeline tick every device applies ITS stage
    (a ``lax.switch`` branch) to the flat activation buffer it holds and
    ppermutes the result to its ring neighbor. The loss head, gradient
    normalization, optimizer update, and L1/L2 all reuse the exact
    single-device code (``compute_updates``), so a pipeline step is
    loss-parity-identical to ``net.fit_batch`` up to float reassociation.

    Layer running state (BatchNormalization's mean/var) threads through
    the ring schedule: each device carries its stage's flattened state in
    the tick scan, updating it only on REAL ticks (stage s works on
    genuine microbatches at ticks s <= t < s+M; fill/drain ticks process
    ring garbage and must not touch statistics). Note the standard GPipe
    semantics: BN statistics are per-MICROBATCH (and per-dp-replica, with
    running averages pmean-synced over 'dp' after the window), so they
    match the single-device step exactly only when n_microbatches == 1.

    Dropout runs inside the ring: each tick's switch branch receives a
    PRNG key folded from the step rng by (stage, tick[, dp shard]), so
    masks differ per microbatch/stage/shard and a fixed seed reproduces.

    MoE aux-loss semantics under microbatching: with
    ``n_microbatches == M > 1`` each microbatch computes its balancing
    loss over its OWN 1/M slice of the batch and the objective takes
    the mean of those per-microbatch values — which differs from the
    single-device step's aux computed over the full batch (mean of
    per-slice balance != full-batch balance; the same approximation the
    dp gradient all-reduce makes). Exact parity holds only at M=1 on a
    pp-only mesh; a one-time ``logger.warning`` marks runs that train
    aux layers with M > 1 (see PARITY.md).

    Recurrent layers pipeline too: a stage runs its layer's full
    sequence scan in-stage (plain BPTT, zero carry per batch), and under
    truncated BPTT the final carries ride the ring's no-grad carry
    buffer between time windows — per-microbatch slices, gradients
    stopped at window edges by construction (pp-only meshes; see
    __init__).
    """

    def __init__(self, net, mesh: Optional[Mesh] = None, axis: str = "pp",
                 n_microbatches: Optional[int] = None,
                 stages: Optional[Sequence[Sequence[int]]] = None,
                 collect_training_stats: bool = False):
        from deeplearning4j_tpu.optimize.training_stats import TrainingStats
        from deeplearning4j_tpu.parallel.mesh import MeshContext
        if collect_training_stats:
            self.training_stats = TrainingStats()
        if isinstance(mesh, MeshContext):
            mesh = mesh.mesh
        if mesh is None:
            devs = np.array(jax.devices())
            mesh = Mesh(devs.reshape(len(devs)), (axis,))
        if axis not in mesh.axis_names:
            raise ValueError(f"mesh has no {axis!r} axis: {mesh.axis_names}")
        net._check_init()
        _reject_remat(net.conf)
        if not hasattr(net, "layers"):
            raise ValueError("PipelineTrainer supports MultiLayerNetwork "
                             "(graph stage partitioning is future work)")
        if net.conf.input_type is None:
            raise ValueError("PipelineTrainer needs set_input_type() on the "
                             "config (static boundary shapes under jit)")
        self.net = net
        self.mesh = mesh
        self.axis = axis
        self.dp_axis = "dp" if "dp" in mesh.axis_names else None
        self.S = mesh.shape[axis]
        self.M = int(n_microbatches or self.S)
        body = net.layers[:-1]
        head = net.layers[-1]
        if not hasattr(head, "compute_loss"):
            raise ValueError("Last layer must be an output/loss layer")
        # MixtureOfExperts-style aux losses ride a dedicated
        # DIFFERENTIABLE column of the ring activation buffer (the state
        # buffer is no-grad; the activation buffer is not) — see
        # _make_branch. Under dp each shard accumulates its local aux
        # and the loss takes the row mean, the same approximation the
        # dp gradient all-reduce already makes.
        self._aux_layers = [i for i, l in enumerate(body)
                            if "aux_loss" in net.states[i]]
        global _WARNED_AUX_MICROBATCH
        if self._aux_layers and self.M > 1 and not _WARNED_AUX_MICROBATCH:
            _WARNED_AUX_MICROBATCH = True
            logger.warning(
                "PipelineTrainer: %d aux-loss layer(s) with "
                "n_microbatches=%d — the balancing loss is a mean of "
                "per-microbatch values, not the full-batch aux; exact "
                "single-device parity holds only at n_microbatches=1 "
                "(see the class docstring / PARITY.md)",
                len(self._aux_layers), self.M)
        # recurrent layers run their full sequence INSIDE their stage
        # (zero initial carry per batch, exactly layer.apply); under
        # tBPTT the final carries additionally thread through the ring's
        # no-grad carry buffer across time windows — which gives the
        # stop-gradient-at-window-edges semantics for free (ref:
        # MultiLayerNetwork.doTruncatedBPTT:1119-1183 / LSTMHelpers.java)
        self._carry_layers = [i for i, l in enumerate(body)
                              if getattr(l, "supports_carry", False)]
        # gate on backprop_type alone: a truncated_bptt net with NO
        # carry layers (e.g. bidirectional-only) still windows its
        # updates on a single device, and must window here too — gating
        # on carries would silently train full-sequence BPTT instead
        self._tbptt = (net.conf.training.backprop_type == "truncated_bptt")
        if self._tbptt and self._carry_layers and self.dp_axis is not None:
            raise ValueError(
                "tBPTT under the pipeline needs a pp-only mesh: carries "
                "are per-batch-row and cannot ride the dp-averaged state "
                "buffer — drop the dp axis or train without tBPTT")
        if self._tbptt:
            tr = net.conf.training
            bwd = tr.tbptt_bwd_length or tr.tbptt_fwd_length
            if bwd < tr.tbptt_fwd_length:
                # MLN's split-window trick (forward-only head, backprop
                # tail — multilayer.py:368-378) doesn't fit the ring: a
                # silently full-window backprop would train differently
                raise ValueError(
                    "tbptt_bwd_length < tbptt_fwd_length is unsupported "
                    "under the pipeline (windows backprop whole); set "
                    "bwd == fwd or train without the pipeline")
        self._tbptt_cache = {}
        self.stages = ([list(s) for s in stages] if stages is not None
                       else partition_stages(
                           body, net.params, self.S,
                           act_elems=_mln_boundary_elems(net.conf, body)))
        if len(self.stages) != self.S:
            raise ValueError(f"{len(self.stages)} stages != pp size {self.S}")
        flat = [i for st in self.stages for i in st]
        if flat != list(range(len(body))):
            raise ValueError(f"stages must cover body layers 0..{len(body)-1}"
                             f" contiguously, got {self.stages}")
        if any(not st for st in self.stages[:-1]) and any(
                st for i, st in enumerate(self.stages) if i
                and not self.stages[i - 1]):
            raise ValueError("empty (identity) stages must be trailing, "
                             f"got {self.stages}")
        self._step = None

    # ---------------------------------------------------------------- shapes
    def _boundary_shapes(self, b_mb: int, timesteps: Optional[int] = None):
        """TRUE activation shape entering each stage plus the final body
        output feeding the loss head (via _true_layer_shapes — an ff-typed
        tensor between Rnn<->FF preprocessors still carries its time
        axis). ``timesteps`` overrides the recurrent input length (tBPTT
        windows are shorter than the configured sequence)."""
        body = [self.net.layers[i] for st in self.stages for i in st]
        shapes = _true_layer_shapes(self.net.conf, body, b_mb, timesteps)
        stage_in, pos = [], 0
        for st in self.stages:
            stage_in.append(shapes[pos])
            pos += len(st)
        return stage_in, shapes[-1]

    # ------------------------------------------------------------ stage fns
    def _make_branch(self, stage: List[int], in_shape, amax: int,
                     seg_shapes, state_shapes, smax: int,
                     carry_meta=None):
        """One lax.switch branch: unpack this stage's flat param segment,
        flat state segment, and activation buffer, run its layers exactly
        as MLN._forward does (dropout runs in-ring with per-stage/tick/
        dp-shard folded RNG keys), repack both. Under tBPTT
        (``carry_meta``), recurrent layers read their microbatch-``m``
        carry slice from the no-grad carry buffer, scan the window, and
        write the final carry back — MLN._forward's carries branch, in
        ring form. The batch dim reshapes with -1: under dp×pp the local
        batch is the global microbatch divided by the dp size."""
        net = self.net
        conf = net.conf
        in_size = int(np.prod(in_shape[1:]))
        carry_meta = carry_meta or {}
        if not stage:
            # identity (pass-through) stage
            return lambda pflat, sflat, cflat, xbuf, key, m: (
                xbuf, sflat, cflat)

        def branch(pflat, sflat, cflat, xbuf, key, m):
            # unflatten this stage's params/states from padded segments
            p, s = {}, {}
            off = soff = 0
            for i in stage:
                layer_p, layer_s = {}, {}
                for name in net.layers[i].param_order():
                    shp, dt = seg_shapes[i][name]
                    n = int(np.prod(shp))
                    layer_p[name] = pflat[off:off + n].reshape(shp).astype(dt)
                    off += n
                for name, (shp, dt) in state_shapes[i].items():
                    n = int(np.prod(shp))
                    layer_s[name] = (sflat[soff:soff + n]
                                     .reshape(shp).astype(dt))
                    soff += n
                p[i], s[i] = layer_p, layer_s
            h = xbuf[:, :in_size].reshape((-1,) + in_shape[1:])
            in_types = conf.input_types
            new_s = {}
            for i in stage:
                layer = net.layers[i]
                if i in conf.preprocessors:
                    it = in_types[i] if in_types else None
                    h = conf.preprocessors[i].transform(h, it)
                sub = jax.random.fold_in(key, i)
                if i in carry_meta:
                    coff, per_mb, leaf_meta, treedef = carry_meta[i]
                    seg = jax.lax.dynamic_slice(
                        cflat, (coff + m * per_mb,), (per_mb,))
                    leaves, o = [], 0
                    for shp, dt in leaf_meta:
                        n = int(np.prod(shp))
                        leaves.append(seg[o:o + n].reshape(shp).astype(dt))
                        o += n
                    c_in = jax.tree_util.tree_unflatten(treedef, leaves)
                    # scan() bypasses apply(): input dropout must still
                    # fire (exactly MLN._forward's carries branch)
                    h = layer._dropout_input(h, not layer.frozen, sub)
                    h, c_out = layer.scan(p[i], h, c_in, None)
                    flat_out = jnp.concatenate(
                        [jnp.reshape(x, (-1,)).astype(jnp.float32)
                         for x in jax.tree_util.tree_leaves(c_out)])
                    cflat = jax.lax.dynamic_update_slice(
                        cflat, flat_out, (coff + m * per_mb,))
                    new_s[i] = s[i]
                else:
                    # recurrent layers included: apply() scans the full
                    # window from a zero carry, which _carry_like (in
                    # nn/layers/recurrent.py) marks varying over the mesh
                    # axes so the in-stage lax.scan type-checks under
                    # shard_map
                    h, s_out = layer.apply(p[i], h, state=s[i],
                                           train=not layer.frozen,
                                           rng=sub, mask=None)
                    new_s[i] = s[i] if layer.frozen else s_out
            y = h.reshape(xbuf.shape[0], -1)
            leaves = [new_s[i][name].reshape(-1).astype(jnp.float32)
                      for i in stage for name in state_shapes[i]]
            sflat_new = (jnp.pad(jnp.concatenate(leaves),
                                 (0, smax - sum(l.shape[0] for l in leaves)))
                         if leaves else sflat)
            y_pad = jnp.pad(y, ((0, 0), (0, amax - y.shape[1])))
            # running aux-loss accumulator: read the incoming sum from
            # the (differentiable) last column, add this stage's aux
            # scalars, write it back for the next hop
            aux = xbuf[0, amax - 1]
            for i in stage:
                # same predicate as loss_of's gate (self._aux_layers,
                # init_state-declared) — a split predicate could silently
                # drop a layer's balancing term from the objective
                if i in self._aux_layers and "aux_loss" in new_s[i]:
                    aux = aux + new_s[i]["aux_loss"].astype(jnp.float32)
            y_pad = y_pad.at[:, amax - 1].set(aux.astype(y_pad.dtype))
            return y_pad, sflat_new, cflat

        return branch

    # ------------------------------------------------------------- the step
    def _build_step(self, b_mb: int, timesteps: Optional[int] = None):
        net = self.net
        S, M, axis = self.S, self.M, self.axis
        mesh = self.mesh
        stage_in, head_in_shape = self._boundary_shapes(b_mb, timesteps)
        head_in_size = int(np.prod(head_in_shape[1:]))
        # +1: the last buffer column is the differentiable running
        # aux-loss accumulator (zero-cost when no aux layers exist)
        amax = max([int(np.prod(s[1:])) for s in stage_in]
                   + [head_in_size]) + 1
        # per-layer param segment metadata (static shapes for unflatten)
        seg_shapes = {i: {k: (v.shape, v.dtype)
                          for k, v in net.params[i].items()}
                      for st in self.stages for i in st}
        seg_sizes = [sum(int(np.prod(seg_shapes[i][k][0]))
                         for i in st for k in seg_shapes[i])
                     for st in self.stages]
        pmax = max(seg_sizes)
        # per-layer running-state segment metadata (BN mean/var)
        state_shapes = {i: {k: (v.shape, v.dtype)
                            for k, v in net.states[i].items()}
                        for st in self.stages for i in st}
        ssizes = [sum(int(np.prod(state_shapes[i][k][0]))
                      for i in st for k in state_shapes[i])
                  for st in self.stages]
        smax = max([1] + ssizes)
        self._amax = amax
        # per-stage carry segment layout (tBPTT only): for each recurrent
        # layer, M per-microbatch slices of its flattened (h, c) carry
        carry_metas: List[dict] = []
        csizes = []
        if self._tbptt and self._carry_layers:
            dt_tr = net.params[self._carry_layers[0]][
                net.layers[self._carry_layers[0]].param_order()[0]].dtype
            for st in self.stages:
                meta, coff = {}, 0
                for i in st:
                    if i not in self._carry_layers:
                        continue
                    c0 = net.layers[i].initial_carry(b_mb, dt_tr)
                    leaves, treedef = jax.tree_util.tree_flatten(c0)
                    leaf_meta = [(x.shape, x.dtype) for x in leaves]
                    per_mb = sum(int(np.prod(x.shape)) for x in leaves)
                    meta[i] = (coff, per_mb, leaf_meta, treedef)
                    coff += per_mb * M
                carry_metas.append(meta)
                csizes.append(coff)
        else:
            carry_metas = [{} for _ in self.stages]
        cmax = max([1] + csizes)
        self._cmax = cmax
        branches = [self._make_branch(st, stage_in[s], amax, seg_shapes,
                                      state_shapes, smax, carry_metas[s])
                    for s, st in enumerate(self.stages)]

        def pack_bufs(params):
            """[S, Pmax] padded flat param buffer (differentiable)."""
            rows = []
            for st in self.stages:
                leaves = [params[i][k].reshape(-1).astype(jnp.float32)
                          for i in st for k in net.layers[i].param_order()]
                row = jnp.concatenate(leaves) if leaves else jnp.zeros((0,))
                rows.append(jnp.pad(row, (0, pmax - row.shape[0])))
            return jnp.stack(rows)

        def pack_states(states):
            rows = []
            for st in self.stages:
                leaves = [states[i][k].reshape(-1).astype(jnp.float32)
                          for i in st for k in state_shapes[i]]
                row = jnp.concatenate(leaves) if leaves else jnp.zeros((0,))
                rows.append(jnp.pad(row, (0, smax - row.shape[0])))
            return jnp.stack(rows)

        def unpack_states(sbuf):
            out = list(net.states)
            for s, st in enumerate(self.stages):
                soff = 0
                for i in st:
                    layer_s = {}
                    for name, (shp, dt) in state_shapes[i].items():
                        n = int(np.prod(shp))
                        layer_s[name] = (sbuf[s, soff:soff + n]
                                         .reshape(shp).astype(dt))
                        soff += n
                    out[i] = layer_s
            return out

        pipe = _make_ring(mesh, axis, self.dp_axis, S, M, branches)

        tx = net._tx
        training = net.conf.training
        head = net.layers[-1]
        head_idx = len(net.layers) - 1
        head_pre = net.conf.preprocessors.get(head_idx)
        head_pre_type = (net.conf.input_types[head_idx]
                         if net.conf.input_types else None)

        def loss_of(params, sbuf, cbuf, xs, labels, rng):
            outs, new_sbuf, new_cbuf = pipe(pack_bufs(params), sbuf, cbuf,
                                            xs, rng)
            h = outs[..., :head_in_size].reshape(
                (M * b_mb,) + head_in_shape[1:])
            if head_pre is not None:
                # e.g. the auto CnnToFeedForward flatten before an
                # OutputLayer head — exactly as MLN._forward applies it
                h = head_pre.transform(h, head_pre_type)
            data_loss = head.compute_loss(params[head_idx], h, labels,
                                          mask=None)
            # per-microbatch aux sums arrive in the buffer's last column
            # (rows within a shard are identical; the mean also averages
            # over dp shards and microbatches — exact at M=1, pp-only)
            aux = (outs[..., amax - 1].mean().astype(data_loss.dtype)
                   if self._aux_layers else 0.0)
            return (data_loss + l1_l2_penalty(params, net.layers) + aux,
                    (new_sbuf, new_cbuf))

        sentinel = getattr(net, "_sentinel", None)

        def step(params, opt_state, states, cbuf, xs, labels, rng):
            sbuf = pack_states(states)
            (loss, (new_sbuf, new_cbuf)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params, sbuf, cbuf, xs, labels, rng)
            new_params, new_opt = compute_updates(
                tx, grads, opt_state, params, net.layers, training)
            # the guard takes in the carry buffer: a NaN window must not
            # poison the next tBPTT window's carries
            return step_result(
                sentinel is not None, loss, grads,
                (params, opt_state, states, cbuf),
                (new_params, new_opt, unpack_states(new_sbuf), new_cbuf))

        return jax.jit(step, donate_argnums=(0, 1, 2, 3))


# ---------------------------------------------------------------------------
# pipeline over a ComputationGraph (DAG stage partitioning)
# ---------------------------------------------------------------------------

def find_graph_cut_points(conf) -> List[Tuple[int, str]]:
    """Valid stage boundaries of a DAG: positions ``p`` in the topological
    order where exactly ONE node's activation crosses from the prefix
    ``topo[:p]`` to the suffix — the single tensor the ring can carry.
    Returns [(p, crossing_node_name)]. ResNet-style block chains cut at
    every block output; a skip connection spanning a candidate boundary
    disqualifies it (two tensors would cross). The algorithm itself is
    ``analysis/graphcheck.graph_cut_points`` — ONE implementation, so
    the GC017 composition validator and this trainer's partition can
    never disagree about which cuts exist."""
    from deeplearning4j_tpu.analysis.graphcheck import graph_cut_points
    return graph_cut_points(conf)


class GraphPipelineTrainer(_RingFitMixin):
    """GPipe pipeline-parallel trainer for a ``ComputationGraph`` — the
    DAG analog of PipelineTrainer (ResNet-50, the flagship BASELINE
    model, is a graph here). The topological order is split at single-
    tensor cut points (find_graph_cut_points) into S contiguous stages
    balanced by parameter count; skip connections live entirely inside
    stages, so the ring still carries one activation buffer. Running
    state (BN) threads exactly as in PipelineTrainer; the output node's
    loss head and compute_updates reuse the graph's single-device code.

    Multi-input graphs inject every network input into stage 0 as one
    concatenated flat buffer; multi-output graphs put every loss head's
    input on the final boundary (find_graph_cut_points counts heads as
    consumers, so no cut can strand a head input in an earlier stage)
    and the loss sums the heads, exactly like the single-device graph.

    Out of scope: masks, RNN/carry vertices (LastTimeStep /
    DuplicateToTimeSeries), aux-loss layers, truncated BPTT. Dropout
    runs in-ring (per-stage/tick/dp-shard folded RNG keys), as in
    PipelineTrainer.
    """

    def __init__(self, net, mesh: Optional[Mesh] = None, axis: str = "pp",
                 n_microbatches: Optional[int] = None,
                 collect_training_stats: bool = False):
        from deeplearning4j_tpu.nn.conf.graph import (
            DuplicateToTimeSeriesVertex, LastTimeStepVertex)
        from deeplearning4j_tpu.optimize.training_stats import TrainingStats
        from deeplearning4j_tpu.parallel.mesh import MeshContext
        if collect_training_stats:
            self.training_stats = TrainingStats()
        if isinstance(mesh, MeshContext):
            mesh = mesh.mesh
        if mesh is None:
            devs = np.array(jax.devices())
            mesh = Mesh(devs.reshape(len(devs)), (axis,))
        if axis not in mesh.axis_names:
            raise ValueError(f"mesh has no {axis!r} axis: {mesh.axis_names}")
        net._check_init()
        _reject_remat(net.conf)
        conf = net.conf
        if not conf.resolved_types:
            raise ValueError("GraphPipelineTrainer needs set_input_types() "
                             "on the config (static boundary shapes)")
        self.net = net
        self.mesh = mesh
        self.axis = axis
        self.dp_axis = "dp" if "dp" in mesh.axis_names else None
        self.S = mesh.shape[axis]
        self.M = int(n_microbatches or self.S)
        # multi-input: every network input is injected into stage 0 as a
        # concatenated flat buffer. Multi-output: heads count as
        # consumers in find_graph_cut_points (they sit in out_set), so
        # no cut can separate a head input from its head — all head
        # inputs are provably computed in the final stage, whose
        # boundary carries their concatenation.
        self.in_names = list(conf.network_inputs)
        self.out_names = list(conf.network_outputs)
        consumers_of = {n: 0 for n in conf.topological_order}
        for n in conf.topological_order:
            for i in conf.nodes[n].inputs:
                consumers_of[i] += 1
        for o in self.out_names:
            out_node = conf.nodes[o]
            if out_node.kind != "layer" \
                    or not hasattr(out_node.layer, "compute_loss"):
                raise ValueError(f"output node {o!r} must be a loss head")
            if consumers_of[o]:
                raise ValueError(f"output node {o!r} feeds other nodes — "
                                 "unsupported in the graph pipeline")
        self.head_in_names = []
        for o in self.out_names:
            for i in conf.nodes[o].inputs:
                if i not in self.head_in_names:
                    self.head_in_names.append(i)
        for name in conf.topological_order:
            node = conf.nodes[name]
            if node.kind == "vertex" and isinstance(
                    node.vertex, (LastTimeStepVertex,
                                  DuplicateToTimeSeriesVertex)):
                raise ValueError(f"vertex {name!r} "
                                 f"({type(node.vertex).__name__}) is "
                                 "unsupported in the graph pipeline v1")
            if node.kind != "layer":
                continue
            l = node.layer
            if "aux_loss" in net.states.get(name, {}):
                raise ValueError(f"layer node {name!r} carries an "
                                 "auxiliary loss — unsupported (see "
                                 "PipelineTrainer)")
            if getattr(l, "supports_carry", False):
                raise ValueError(f"layer node {name!r} is recurrent — "
                                 "unsupported in the graph pipeline v1")
            if getattr(l, "tied_to", None) and name not in self.out_names:
                # tied weights resolve at the LOSS seam (outside the
                # ring), where the full params dict is in scope; a tied
                # layer inside a stage would need its partner's params
                # in the packed buffer — not wired
                raise ValueError(
                    f"layer node {name!r} ties weights (tied_to="
                    f"{l.tied_to!r}) but is not an output head — only "
                    "tied LOSS heads are supported in the graph pipeline")
        if conf.training.backprop_type == "truncated_bptt":
            # the single-device graph windows updates via _fit_tbptt;
            # running full-sequence BPTT here instead would silently
            # train differently (PipelineTrainer implements windowing,
            # the graph trainer does not yet)
            raise ValueError(
                "truncated_bptt is unsupported in the graph pipeline v1 "
                "— use PipelineTrainer (MLN) for windowed tBPTT or "
                "standard backprop for the graph")
        self.stages, self.boundaries = self._partition()
        self._step = None

    # ------------------------------------------------------------ partition
    def _partition(self):
        """Split the non-input, non-head topo nodes into S contiguous
        groups at balanced cut points. Returns (stages: list of
        node-name lists, boundaries: LIST of tensor names entering each
        stage — all network inputs for stage 0, the single crossing
        node after)."""
        conf = self.net.conf
        topo = list(conf.topological_order)
        heads = set(self.out_names)
        body = [n for n in topo
                if conf.nodes[n].kind != "input" and n not in heads]
        if not body:
            raise ValueError("no body nodes to pipeline")
        body_set = set(body)
        cuts = [(p, n) for p, n in find_graph_cut_points(conf)
                if 0 < p < len(topo) and n in body_set]

        def cost(name):
            node = conf.nodes[name]
            if node.kind != "layer":
                return 1
            return 1 + sum(int(np.prod(v.shape))
                           for v in self.net.params[name].values())

        # map topo cut positions onto body-list boundaries, with the
        # crossing tensor's per-sample size as the cut's activation term
        # (same DP + cost model as partition_stages: max stage params +
        # max ring payload — a fat skip-free boundary early in a ResNet
        # would otherwise set every tick's ppermute size)
        topo_to_bidx = {}
        b = 0
        for p, name in enumerate(topo):
            topo_to_bidx[p + 1] = b + (1 if name in body_set else 0)
            if name in body_set:
                b += 1
        rt = conf.resolved_types
        boundaries, bound_name = [], {}
        for p, crossing in cuts:
            bidx = topo_to_bidx[p]
            if 0 < bidx < len(body):
                boundaries.append((bidx, float(_type_elems(rt[crossing]))))
                bound_name[bidx] = crossing
        costs = [cost(n) for n in body]
        n_cuts_usable = min(self.S - 1, len(boundaries))
        cut_idx = (_optimal_cuts(costs, boundaries, n_cuts_usable + 1)
                   if n_cuts_usable else None) or []
        stages, bounds = [], [list(self.in_names)]
        edges = [0] + list(cut_idx) + [len(body)]
        for i in range(len(edges) - 1):
            stages.append(body[edges[i]:edges[i + 1]])
            if i + 1 < len(edges) - 1:
                bounds.append([bound_name[edges[i + 1]]])
        # fewer cut points than stages: trailing identity stages
        while len(stages) < self.S:
            stages.append([])
            bounds.append(bounds[-1])
        return stages, bounds

    # ---------------------------------------------------------------- shapes
    def _boundary_shapes(self, b_mb: int):
        """Per-stage lists of (name, shape) entering each stage + the
        final boundary (the concatenated head inputs)."""
        rt = self.net.conf.resolved_types
        stage_in = [[(n, _type_shape(rt[n], b_mb)) for n in names]
                    for names in self.boundaries]
        head_in = [(n, _type_shape(rt[n], b_mb))
                   for n in self.head_in_names]
        return stage_in, head_in

    # ------------------------------------------------------------ stage fns
    def _make_branch(self, stage: List[str], b_in: List[str],
                     b_out: Optional[List[str]], amax: int,
                     seg_shapes, state_shapes, smax: int):
        """``b_in``/``b_out``: the named tensors entering/leaving this
        stage, packed as one concatenated flat buffer (stage 0 unpacks
        every network input; the last real stage emits every head
        input)."""
        net = self.net
        conf = net.conf
        # deterministic per-node dropout-stream ids (Python's hash() is
        # salted per process — it would break seed reproducibility and
        # desync masks across multihost trace constants)
        node_ix = {n: i for i, n in enumerate(net._layer_nodes)}

        if not stage:
            return lambda pflat, sflat, cflat, xbuf, key, m: (
                xbuf, sflat, cflat)

        rt = conf.resolved_types
        in_shapes = [(n, _type_shape(rt[n], 1)[1:]) for n in b_in]

        def branch(pflat, sflat, cflat, xbuf, key, m):
            p, s = {}, {}
            off = soff = 0
            for name in stage:
                if conf.nodes[name].kind != "layer":
                    continue
                layer_p, layer_s = {}, {}
                for pname in conf.nodes[name].layer.param_order():
                    shp, dt = seg_shapes[name][pname]
                    n = int(np.prod(shp))
                    layer_p[pname] = (pflat[off:off + n]
                                      .reshape(shp).astype(dt))
                    off += n
                for sname, (shp, dt) in state_shapes[name].items():
                    n = int(np.prod(shp))
                    layer_s[sname] = (sflat[soff:soff + n]
                                      .reshape(shp).astype(dt))
                    soff += n
                p[name], s[name] = layer_p, layer_s
            acts = {}
            xoff = 0
            for name, shp in in_shapes:
                n = int(np.prod(shp))
                acts[name] = xbuf[:, xoff:xoff + n].reshape((-1,) + shp)
                xoff += n
            new_s = {}
            for name in stage:
                node = conf.nodes[name]
                in_acts = [acts[i] for i in node.inputs]
                if node.kind == "vertex":
                    acts[name] = node.vertex.apply(in_acts)
                else:
                    h = in_acts[0]
                    if node.preprocessor is not None:
                        h = node.preprocessor.transform(h, None)
                    layer = node.layer
                    h, s_out = layer.apply(
                        p[name], h, state=s[name],
                        train=not layer.frozen,
                        rng=jax.random.fold_in(key, node_ix[name]),
                        mask=None)
                    new_s[name] = s[name] if layer.frozen else s_out
                    acts[name] = h
            rows = xbuf.shape[0]
            y = jnp.concatenate([acts[n].reshape(rows, -1) for n in b_out],
                                axis=1)
            leaves = [new_s[nm][k].reshape(-1).astype(jnp.float32)
                      for nm in stage if nm in new_s
                      for k in state_shapes[nm]]
            sflat_new = (jnp.pad(
                jnp.concatenate(leaves),
                (0, smax - sum(l.shape[0] for l in leaves)))
                if leaves else sflat)
            return (jnp.pad(y, ((0, 0), (0, amax - y.shape[1]))),
                    sflat_new, cflat)

        return branch

    # ------------------------------------------------------------- the step
    def _build_step(self, b_mb: int):
        net = self.net
        conf = net.conf
        S, M, axis = self.S, self.M, self.axis
        stage_in, head_in = self._boundary_shapes(b_mb)

        def width(named_shapes):
            return sum(int(np.prod(shp[1:])) for _, shp in named_shapes)

        head_in_size = width(head_in)
        amax = max([width(si) for si in stage_in] + [head_in_size])
        last_real = max(i for i, st in enumerate(self.stages) if st)
        out_lists = []
        for s in range(S):
            if s == last_real:
                out_lists.append(self.head_in_names)
            elif s < last_real:
                out_lists.append(self.boundaries[s + 1])
            else:
                out_lists.append(None)  # identity pass-through
        layer_stage_nodes = [[n for n in st
                              if conf.nodes[n].kind == "layer"]
                             for st in self.stages]
        seg_shapes = {n: {k: (v.shape, v.dtype)
                          for k, v in net.params[n].items()}
                      for st in layer_stage_nodes for n in st}
        state_shapes = {n: {k: (v.shape, v.dtype)
                            for k, v in net.states[n].items()}
                        for st in layer_stage_nodes for n in st}
        pmax = max(1, max(sum(int(np.prod(seg_shapes[n][k][0]))
                              for n in st for k in seg_shapes[n])
                          for st in layer_stage_nodes))
        smax = max([1] + [sum(int(np.prod(state_shapes[n][k][0]))
                             for n in st for k in state_shapes[n])
                          for st in layer_stage_nodes])
        self._amax = amax
        branches = [self._make_branch(st, self.boundaries[s], out_lists[s],
                                      amax, seg_shapes, state_shapes, smax)
                    for s, st in enumerate(self.stages)]

        def pack_bufs(params):
            rows = []
            for st in layer_stage_nodes:
                leaves = [params[n][k].reshape(-1).astype(jnp.float32)
                          for n in st
                          for k in conf.nodes[n].layer.param_order()]
                row = jnp.concatenate(leaves) if leaves else jnp.zeros((0,))
                rows.append(jnp.pad(row, (0, pmax - row.shape[0])))
            return jnp.stack(rows)

        def pack_states(states):
            rows = []
            for st in layer_stage_nodes:
                leaves = [states[n][k].reshape(-1).astype(jnp.float32)
                          for n in st for k in state_shapes[n]]
                row = jnp.concatenate(leaves) if leaves else jnp.zeros((0,))
                rows.append(jnp.pad(row, (0, smax - row.shape[0])))
            return jnp.stack(rows)

        def unpack_states(sbuf):
            out = dict(net.states)
            for s, st in enumerate(layer_stage_nodes):
                soff = 0
                for n in st:
                    layer_s = {}
                    for name, (shp, dt) in state_shapes[n].items():
                        k = int(np.prod(shp))
                        layer_s[name] = (sbuf[s, soff:soff + k]
                                         .reshape(shp).astype(dt))
                        soff += k
                    out[n] = layer_s
            return out

        pipe = _make_ring(self.mesh, axis, self.dp_axis, S, M, branches)

        tx = net._tx
        training = conf.training
        layer_list = [conf.nodes[n].layer for n in net._layer_nodes]
        # static slicing metadata: where each head input lives in the
        # final boundary buffer
        head_slices = {}
        hoff = 0
        for n, shp in head_in:
            sz = int(np.prod(shp[1:]))
            head_slices[n] = (hoff, sz, shp[1:])
            hoff += sz

        def loss_of(params, sbuf, cbuf, xs, labels, rng):
            outs, new_sbuf, new_cbuf = pipe(pack_bufs(params), sbuf, cbuf,
                                            xs, rng)
            flat = outs[..., :head_in_size].reshape(M * b_mb, head_in_size)
            data_loss = 0.0
            for o in self.out_names:
                node = conf.nodes[o]
                off, sz, shp = head_slices[node.inputs[0]]
                h = flat[:, off:off + sz].reshape((M * b_mb,) + shp)
                if node.preprocessor is not None:
                    h = node.preprocessor.transform(h, None)
                lab = labels[o] if isinstance(labels, dict) else labels
                # tied head (TiedRnnOutputLayer): the container's one
                # tying seam injects the tied node's embedding matrix
                # from the FULL params tree — the head's gradient flows
                # into the embedding alongside the ring path's own use
                data_loss = data_loss + node.layer.compute_loss(
                    net._layer_params(params, o), h, lab, mask=None)
            # l1_l2_penalty wants a LIST aligned with layer_list (the
            # graph loss path does the same, nn/graph.py:296-299)
            reg = l1_l2_penalty([params[n] for n in net._layer_nodes],
                                layer_list)
            return data_loss + reg, (new_sbuf, new_cbuf)

        sentinel = getattr(net, "_sentinel", None)

        def step(params, opt_state, states, cbuf, xs, labels, rng):
            sbuf = pack_states(states)
            (loss, (new_sbuf, new_cbuf)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params, sbuf, cbuf, xs, labels, rng)
            new_params, new_opt = compute_updates(
                tx, grads, opt_state, params, layer_list, training)
            # the guard takes in the carry buffer (see the MLN pipeline
            # step above)
            return step_result(
                sentinel is not None, loss, grads,
                (params, opt_state, states, cbuf),
                (new_params, new_opt, unpack_states(new_sbuf), new_cbuf))

        return jax.jit(step, donate_argnums=(0, 1, 2, 3))


