"""ComputationGraph: the DAG model container.

Ref: nn/graph/ComputationGraph.java:79 — init (:273-483), fit (:701-771),
topologicalSortOrder (:888), computeGradientAndScore (:995-1036),
calcBackpropGradients (:1224). As with MultiLayerNetwork, the reference's
hand-written reverse-topological epsilon propagation collapses into
``jax.grad`` over one pure forward walk; the whole train step is a single
jitted XLA program.

Params are a dict keyed by node name -> {param name -> array}. Multi-input /
multi-output training uses MultiDataSet; plain DataSet maps to the first
input/output (ref: ComputationGraph.fit(DataSet) does the same).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import weakref

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.datasets.iterator import (
    DataSetIterator, ListDataSetIterator,
)
from deeplearning4j_tpu.nn.conf.graph import (
    DuplicateToTimeSeriesVertex, LastTimeStepVertex)
from deeplearning4j_tpu.nn.conf.graph_builder import ComputationGraphConfiguration
from deeplearning4j_tpu.nn.netcommon import (CostAnalysisMixin, EvalMixin,
                                              FitLoopMixin, LazyScoreMixin,
                                              apply_layer, build_train_step,
                                              jit_init, ScanFitMixin,
                                              SentinelMixin, ShardCheckMixin,
)
from deeplearning4j_tpu.nn.updater import build_optimizer, l1_l2_penalty
from deeplearning4j_tpu.optimize.listeners import IterationListener, TrainingListener
from deeplearning4j_tpu.profiling.tracer import get_tracer

Array = jax.Array


def _dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16, "float64": jnp.float64}[name]


def _time_slice(d: Optional[Dict[str, Array]], lo: int, hi: int,
                min_ndim: int = 3,
                only: Optional[set] = None) -> Optional[Dict[str, Array]]:
    """Slice the time axis (dim 1) of every time-distributed array in a
    name->array dict. ``min_ndim=3`` for features/labels ([B, T, ...];
    static [B, F] side inputs pass through unsliced), ``min_ndim=2`` for
    masks ([B, T]). ``only`` restricts slicing to the named keys (the
    recurrent inputs — a CNN input's [B, H, W, C] must NOT be sliced on
    its height axis)."""
    if d is None:
        return None
    return {k: (v if v is None or v.ndim < min_ndim
                or (only is not None and k not in only) else v[:, lo:hi])
            for k, v in d.items()}


class ComputationGraph(LazyScoreMixin, EvalMixin, FitLoopMixin, ScanFitMixin,
                       CostAnalysisMixin, ShardCheckMixin, SentinelMixin):
    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.params: Optional[Dict[str, Dict[str, Array]]] = None
        self.states: Optional[Dict[str, Dict[str, Array]]] = None
        self.opt_state = None
        self.iteration_count = 0
        self.epoch_count = 0
        self.score_value = float("nan")
        self.listeners: List[IterationListener] = []
        self.last_batch_size = 0
        self.last_grads = None  # most recent gradient pytree (for listeners)
        self._tx = build_optimizer(conf.training)
        self._train_step_fn = None
        self._jit_infer = None          # cached jitted inference forward
        self._infer_traces = 0          # trace counter (tests)
        self._rng = jax.random.PRNGKey(conf.training.seed)
        self._rnn_carries: Optional[Dict[str, Any]] = None  # rnnTimeStep
        self._decode_fns = None         # (prefill, decode) pure fns
        self._paged_decode_fns: Dict[int, Any] = {}  # page_len -> step fn
        # layer nodes in topological order (the trainable walk)
        self._layer_nodes = [n for n in conf.topological_order
                             if conf.nodes[n].kind == "layer"]
        self._output_layers = [conf.nodes[o] for o in conf.network_outputs]
        # weight tying (TiedRnnOutputLayer.tied_to): resolve once, fail
        # loudly at construction — a dangling tie would otherwise only
        # surface as a missing-param KeyError deep inside a traced step
        for name in self._layer_nodes:
            tied = getattr(conf.nodes[name].layer, "tied_to", None)
            if not tied:
                continue
            src = conf.nodes.get(tied)
            if src is None or src.kind != "layer":
                raise ValueError(
                    f"node {name!r}: tied_to={tied!r} does not name a "
                    "layer node in this graph")
            if "W" not in (src.layer.param_order() or []):
                raise ValueError(
                    f"node {name!r}: tied_to node {tied!r} "
                    f"({type(src.layer).__name__}) has no 'W' param to "
                    "tie to")

    # ------------------------------------------------------------------ init
    def init(self, params=None) -> "ComputationGraph":
        dtype = _dtype_of(self.conf.training.dtype)
        if params is not None:
            self.params = params
            self.opt_state = jax.jit(self._tx.init)(self.params)
        else:
            # One jitted program for the whole init: eager per-tensor
            # jax.random calls would compile + dispatch hundreds of tiny
            # device programs (one per shape), which is pathological over
            # a remote-TPU link (round-trip each). Jitted, it is a single
            # compile and a single device execution.
            def _build(key):
                keys = jax.random.split(key, max(len(self._layer_nodes), 1))
                p = {}
                for name, k in zip(self._layer_nodes, keys):
                    layer = self.conf.nodes[name].layer
                    p[name] = (layer.init_params(k, dtype)
                               if layer.has_params() else {})
                return p, self._tx.init(p)
            self.params, self.opt_state = jit_init(
                _build, self.conf.training.seed)
        self.states = {name: self.conf.nodes[name].layer.init_state()
                       for name in self._layer_nodes}
        return self

    def _check_init(self):
        if self.params is None:
            raise RuntimeError("Call init() before using the network")

    def _layer_params(self, params, name: str):
        """Effective params of one layer node: its own dict, plus — for a
        tied head (``layer.tied_to``) — the tied node's token-embedding
        matrix injected as ``W_tok``. Indexing ``params`` (not a cached
        array) keeps autodiff honest: the head's gradient flows into the
        embedding's ``W``, which is the whole point of weight tying."""
        node = self.conf.nodes[name]
        tied = getattr(node.layer, "tied_to", None)
        if tied:
            return {**params[name], "W_tok": params[tied]["W"]}
        return params[name]


    def set_listeners(self, *listeners: IterationListener):
        self.listeners = list(listeners)
        # see MultiLayerNetwork._on_listeners_changed
        want = any(getattr(l, "collects_gradients", False)
                   for l in self.listeners)
        if want != getattr(self, "_collect_grads", False):
            self._collect_grads = want
            self._train_step_fn = None

    # ---------------------------------------------------------------- forward
    def _forward(self, params, states, inputs: Dict[str, Array], *,
                 train: bool, rng, masks: Optional[Dict[str, Array]] = None,
                 stop_before_loss: bool = True,
                 carries: Optional[Dict[str, Any]] = None,
                 subset: Optional[set] = None):
        """Walk the DAG in topological order.

        Returns (activations dict, masks dict, new_states). For output-layer
        nodes with a loss head, the stored activation is the node's INPUT
        (pre-head) when stop_before_loss — compute_loss consumes it —
        mirroring feedForward(excludeOutput=true) (ref: CG.java:1006).

        ``carries``: optional per-layer-node RNN carry dict (tBPTT /
        rnnTimeStep — ref: CG.java rnnTimeStep:1868 keeps per-vertex state
        maps). When given, recurrent layers run ``scan`` from their carry
        and the return is a 4-tuple (acts, masks, states, new_carries).
        """
        acts: Dict[str, Array] = {}
        out_masks: Dict[str, Optional[Array]] = {}
        new_states: Dict[str, Dict[str, Array]] = {}
        new_carries: Dict[str, Any] = {}
        output_set = set(self.conf.network_outputs)
        for name in self.conf.topological_order:
            if subset is not None and name not in subset:
                continue
            node = self.conf.nodes[name]
            if node.kind == "input":
                acts[name] = inputs[name]
                out_masks[name] = (masks or {}).get(name)
                continue
            # the scope puts the node's name into the op_name of every
            # operation, and so of every fusion, it lowers to
            with jax.named_scope(name):
                in_acts = [acts[i] for i in node.inputs]
                in_mask = (out_masks.get(node.inputs[0]) if node.inputs
                           else None)
                if node.kind == "vertex":
                    if isinstance(node.vertex, LastTimeStepVertex):
                        acts[name] = node.vertex.apply_masked(in_acts, in_mask)
                        out_masks[name] = None
                    elif isinstance(node.vertex, DuplicateToTimeSeriesVertex) \
                            and isinstance(node.vertex.timesteps, str):
                        # runtime T from the named reference node's activation
                        acts[name] = node.vertex.apply(
                            in_acts, acts[node.vertex.timesteps])
                        out_masks[name] = in_mask
                    else:
                        acts[name] = node.vertex.apply(in_acts)
                        out_masks[name] = in_mask
                    continue
                # layer node
                h = in_acts[0]
                cur_mask = in_mask
                if node.preprocessor is not None:
                    h = node.preprocessor.transform(h, None)
                    cur_mask = node.preprocessor.transform_mask(cur_mask, None)
                layer = node.layer
                if layer.N_INPUTS > 1:
                    # the stream and other nodes' outputs, as ordinary
                    # edges: the gradient of an output that several nodes
                    # read is the sum autodiff makes, and under remat the
                    # reader keeps all its inputs and rebuilds none of
                    # their producers
                    h = (h, *in_acts[1:])
                if rng is not None:
                    rng, sub = jax.random.split(rng)
                else:
                    sub = None
                if (stop_before_loss and name in output_set
                        and hasattr(layer, "compute_loss")):
                    acts[name] = h          # input to the loss head
                    out_masks[name] = cur_mask
                    new_states[name] = states[name]
                    continue
                carried = carries is not None
                (acts[name], new_states[name], carry,
                 out_masks[name]) = apply_layer(
                    layer, self._layer_params(params, name), h, states[name],
                    sub, cur_mask, train=train,
                    remat=train and self.conf.training.remat, carried=carried,
                    carry=carries.get(name) if carried else None)
                if carry is not None:
                    new_carries[name] = carry
        if carries is not None:
            return acts, out_masks, new_states, new_carries
        return acts, out_masks, new_states

    def _infer_fn(self):
        """Cached JITTED inference forward (ref: the reference's output()
        reuses the same compiled-graph machinery as fit — CG.java:1006 /
        MultiLayerNetwork.java:1512); jax.jit re-traces per input shape and
        ``_infer_traces`` counts traces for tests."""
        if self._jit_infer is None:
            def infer(params, states, in_map, masks):
                self._infer_traces += 1  # python side effect: runs per TRACE
                acts, _, _ = self._forward(params, states, in_map,
                                           train=False, rng=None,
                                           masks=masks,
                                           stop_before_loss=False)
                return [acts[o] for o in self.conf.network_outputs]
            self._jit_infer = jax.jit(infer)
        return self._jit_infer

    def outputs(self, inputs: Union[Array, Sequence[Array], Dict[str, Array]],
                train: bool = False, mask=None) -> List[Array]:
        """Final activations of all output nodes
        (ref: ComputationGraph.output(...)). ``mask``: a [B, T] feature
        mask for the first input, or a name->mask dict."""
        self._check_init()
        in_map = self._to_input_map(inputs)
        masks = None
        if mask is not None:
            masks = (
                {k: (None if v is None else jnp.asarray(v))
                 for k, v in mask.items()} if isinstance(mask, dict)
                else {self.conf.network_inputs[0]: jnp.asarray(mask)})
        if not train:
            return self._infer_fn()(self.params, self.states, in_map,
                                    masks)
        acts, _, _ = self._forward(self.params, self.states, in_map,
                                   train=train, rng=None, masks=masks,
                                   stop_before_loss=False)
        return [acts[o] for o in self.conf.network_outputs]

    def output(self, inputs, train: bool = False, mask=None) -> Array:
        return self.outputs(inputs, train=train, mask=mask)[0]

    def _to_input_map(self, inputs) -> Dict[str, Array]:
        names = self.conf.network_inputs
        if isinstance(inputs, dict):
            return {k: jnp.asarray(v) for k, v in inputs.items()}
        if isinstance(inputs, (list, tuple)):
            return {n: jnp.asarray(x) for n, x in zip(names, inputs)}
        return {names[0]: jnp.asarray(inputs)}

    # ------------------------------------------------------------------- loss
    def _data_loss(self, params, acts, out_masks, labels: Dict[str, Array],
                   label_masks) -> Array:
        """Sum of output-head losses (shared by the standard and tBPTT
        steps so the mask-fallback semantics cannot diverge)."""
        total = jnp.zeros(())
        for out_name in self.conf.network_outputs:
            layer = self.conf.nodes[out_name].layer
            if not hasattr(layer, "compute_loss"):
                raise ValueError(f"Output node {out_name!r} has no loss head")
            lm = (label_masks or {}).get(out_name)
            if lm is None:
                lbl = labels[out_name]
                lm = out_masks.get(out_name) if lbl.ndim > 2 else None
            # the head's own operations under the node's name, as every
            # other node's are (it was left out of _forward's scope)
            with jax.named_scope(out_name):
                total = total + layer.compute_loss(
                    self._layer_params(params, out_name), acts[out_name],
                    labels[out_name], mask=lm)
        return total

    def _layer_list(self):
        return [self.conf.nodes[n].layer for n in self._layer_nodes]

    def _with_penalties(self, data_loss, params, new_states) -> Array:
        """The score: Σ output losses + L1/L2 over all layer params (ref:
        CG.computeGradientAndScore:1016-1028) + the layers' auxiliary
        losses."""
        from deeplearning4j_tpu.nn.multilayer import _sum_aux_losses
        total = data_loss + l1_l2_penalty(
            [params[n] for n in self._layer_nodes], self._layer_list())
        return total + _sum_aux_losses(new_states)

    def _loss_fn(self, params, states, inputs, labels: Dict[str, Array],
                 masks, label_masks, rng, train=True):
        acts, out_masks, new_states = self._forward(
            params, states, inputs, train=train, rng=rng, masks=masks)
        total = self._data_loss(params, acts, out_masks, labels, label_masks)
        return self._with_penalties(total, params, new_states), new_states

    def score(self, data: Union[DataSet, MultiDataSet], train: bool = False) -> float:
        self._check_init()
        inputs, labels, masks, lmasks = self._split(data)
        loss, _ = self._loss_fn(self.params, self.states, inputs, labels,
                                masks, lmasks, rng=None, train=train)
        return float(loss)

    def _split(self, data: Union[DataSet, MultiDataSet]):
        names_in = self.conf.network_inputs
        names_out = self.conf.network_outputs
        if isinstance(data, DataSet):
            inputs = {names_in[0]: jnp.asarray(data.features)}
            labels = {names_out[0]: jnp.asarray(data.labels)}
            masks = ({names_in[0]: jnp.asarray(data.features_mask)}
                     if data.features_mask is not None else None)
            lmasks = ({names_out[0]: jnp.asarray(data.labels_mask)}
                      if data.labels_mask is not None else None)
            return inputs, labels, masks, lmasks
        inputs = {n: jnp.asarray(x) for n, x in zip(names_in, data.features)}
        labels = {n: jnp.asarray(x) for n, x in zip(names_out, data.labels)}
        masks = None
        if data.features_masks is not None:
            masks = {n: (None if m is None else jnp.asarray(m))
                     for n, m in zip(names_in, data.features_masks)}
        lmasks = None
        if data.labels_masks is not None:
            lmasks = {n: (None if m is None else jnp.asarray(m))
                      for n, m in zip(names_out, data.labels_masks)}
        return inputs, labels, masks, lmasks

    # ------------------------------------------------------------- train step
    def _build_train_step(self):
        # the step's closures reach the net through a weak proxy: with a
        # cycle net -> step -> closure -> net a dropped net's device memory
        # waits for the cyclic collector, some time (nn/netcommon.py)
        net = weakref.proxy(self)
        def loss_of(p, states, inputs, labels, masks, lmasks, _, rng):
            loss, new_states = net._loss_fn(p, states, inputs, labels,
                                             masks, lmasks, rng)
            return loss, (new_states, None)

        return build_train_step(self, self._layer_list(), loss_of)

    def _fit_batch(self, data: Union[DataSet, MultiDataSet]) -> float:
        """``fit_batch`` under its span (ref: ComputationGraph.fit)."""
        self._check_init()
        algo = self.conf.training.optimization_algo
        if algo not in ("sgd", "stochastic_gradient_descent"):
            # line-search family (ref: BaseOptimizer.java:295-300 — the
            # same Solver serves ComputationGraph)
            from deeplearning4j_tpu.optimize.solvers import solver_fit_batch
            return solver_fit_batch(self, data)
        if self.conf.training.backprop_type == "truncated_bptt":
            all_feats = ([data.features] if isinstance(data, DataSet)
                         else list(data.features))
            all_labels = ([data.labels] if isinstance(data, DataSet)
                          else list(data.labels))
            has_rnn_input = any(f.ndim == 3 for f in all_feats)
            # EVERY label must be time-distributed (a rank-2 [B, C] label
            # would silently train its head per slice against the full-
            # sequence target), and EVERY rank-3 feature must really be a
            # time series: a CNN input's [B, H, W, C] would be sliced
            # along its height axis. The declared InputTypes disambiguate
            # (the reference falls back to standard BPTT with a warning).
            rnn_ok = all(
                (self.conf.input_types.get(n) is None and f.ndim == 3)
                or (self.conf.input_types.get(n) is not None
                    and (self.conf.input_types[n].kind == "rnn"
                         or f.ndim != 3))
                for n, f in zip(self.conf.network_inputs, all_feats)
                if f.ndim >= 3)
            if has_rnn_input and rnn_ok \
                    and all(l.ndim == 3 for l in all_labels):
                return self._fit_tbptt(data)
            if has_rnn_input:
                # hard failure, matching the reference's config-time error
                # (VERDICT r3 weak #7 — see MultiLayerNetwork.fit_batch)
                raise ValueError(
                    "truncated_bptt requires rank-3 (time-distributed) "
                    "labels on every output and recurrent InputTypes for "
                    "every rank-3 input; use backprop_type('standard') "
                    "for sequence-to-one heads")
        if self._train_step_fn is None:
            self._train_step_fn = self._build_train_step()
        return self._standard_step(data, self._split)

    def fit(self, data, epochs: int = 1, use_async: bool = True,
            scan_window: int = 1) -> "ComputationGraph":
        """(ref: ComputationGraph.fit(DataSetIterator):701-771).
        ``scan_window``: see MultiLayerNetwork.fit — batches grouped into
        one jitted multi-step scan program per window."""
        self._check_init()
        if isinstance(data, (DataSet, MultiDataSet)):
            batches = [data]
            data = ListDataSetIterator(batches) if isinstance(data, DataSet) else None
            if data is None:
                with get_tracer().span("fit"):
                    for _ in range(epochs):
                        self.fit_batch(batches[0])
                return self
        assert isinstance(data, DataSetIterator)
        return self._fit_epochs(data, epochs, use_async, scan_window)

    def _tbptt_rnn_inputs(self) -> set:
        """Network inputs whose time axis tBPTT may slice: declared-rnn
        InputTypes, or untyped inputs (the fit_batch gate only admits
        untyped inputs when they are rank-3 time series)."""
        return {n for n in self.conf.network_inputs
                if self.conf.input_types.get(n) is None
                or self.conf.input_types[n].kind == "rnn"}

    # ------------------------------------------------------------------ tBPTT
    def _build_tbptt_step(self):
        # the step's closures reach the net through a weak proxy: with a
        # cycle net -> step -> closure -> net a dropped net's device memory
        # waits for the cyclic collector, some time (nn/netcommon.py)
        net = weakref.proxy(self)
        training = self.conf.training
        fwd = training.tbptt_fwd_length
        bwd = training.tbptt_bwd_length or fwd
        rnn_inputs = self._tbptt_rnn_inputs()

        def loss_of(p, states, inputs, labels, masks, lmasks, carries, rng):
            # bwd < fwd: run the slice head forward-only (stop-gradded
            # activations + carries), backprop through the last bwd steps
            # only — same semantics as MultiLayerNetwork._build_tbptt_step
            # (ref: ComputationGraph.doTruncatedBPTT:2042 shares the MLN
            # backward time-loop truncation via LSTMHelpers.java:333)
            T = next(v.shape[1] for n, v in inputs.items() if n in rnn_inputs)
            split = max(T - bwd, 0) if bwd < fwd else 0
            if split == 0:
                acts, om, new_states, new_carries = net._forward(
                    p, states, inputs, train=True, rng=rng, masks=masks,
                    carries=carries)
                data_loss = net._data_loss(p, acts, om, labels, lmasks)
            else:
                rng1, rng2 = (jax.random.split(rng) if rng is not None
                              else (None, None))
                head = lambda d, m=3, o=None: _time_slice(d, 0, split, m, only=o)
                tail = lambda d, m=3, o=None: _time_slice(d, split, T, m, only=o)
                acts1, om1, states1, carries1 = net._forward(
                    p, states, head(inputs, o=rnn_inputs), train=True,
                    rng=rng1, masks=head(masks, 2, rnn_inputs),
                    carries=carries)
                acts1 = jax.tree.map(jax.lax.stop_gradient, acts1)
                carries1 = jax.tree.map(jax.lax.stop_gradient, carries1)
                acts2, om2, new_states, new_carries = net._forward(
                    p, states1, tail(inputs, o=rnn_inputs), train=True,
                    rng=rng2, masks=tail(masks, 2, rnn_inputs),
                    carries=carries1)
                # per-timestep losses SUM over time: head + tail ==
                # the single-call slice loss
                data_loss = (
                    net._data_loss(p, acts1, om1, head(labels),
                                   head(lmasks, 2))
                    + net._data_loss(p, acts2, om2, tail(labels),
                                   tail(lmasks, 2)))
            return (net._with_penalties(data_loss, p, new_states),
                    (new_states, new_carries))

        return build_train_step(self, self._layer_list(), loss_of,
                                carried=True)

    def _fit_tbptt(self, data: Union[DataSet, MultiDataSet]) -> float:
        """Truncated BPTT over time slices, carrying per-node RNN state
        (ref: ComputationGraph.doTruncatedBPTT:2042-2103)."""
        inputs, labels, masks, lmasks = self._split(data)
        rnn_inputs = self._tbptt_rnn_inputs()
        T = next(v.shape[1] for n, v in inputs.items() if n in rnn_inputs)
        B = next(iter(inputs.values())).shape[0]
        # materialize initial carries so the jit signature is stable —
        # in the configured training dtype, not initial_carry's f32
        # default (a bf16 net must not run its recurrence in f32)
        dt = _dtype_of(self.conf.training.dtype)
        carries = {name: self.conf.nodes[name].layer.initial_carry(B, dt)
                   for name in self._layer_nodes
                   if getattr(self.conf.nodes[name].layer,
                              "supports_carry", False)}

        def window(start, end):
            return (_time_slice(inputs, start, end, only=rnn_inputs),
                    _time_slice(labels, start, end),
                    _time_slice(masks, start, end, 2, rnn_inputs),
                    _time_slice(lmasks, start, end, 2))

        return self._tbptt_steps(data, T, carries, window)

    # ------------------------------------------------------- rnn statefulness
    def rnn_clear_previous_state(self) -> None:
        self._rnn_carries = None

    def rnn_time_step(self, inputs):
        """Stateful streaming inference (ref: ComputationGraph.rnnTimeStep:
        1868 — keeps per-vertex state maps between calls).

        Inputs as in ``outputs()``; [B, F] inputs are treated as one
        timestep and squeezed back. Returns the single output activation,
        or a list for multi-output graphs."""
        self._check_init()
        in_map = self._to_input_map(inputs)
        squeeze = all(v.ndim == 2 for v in in_map.values())
        if squeeze:
            in_map = {k: v[:, None, :] for k, v in in_map.items()}
        if self._rnn_carries is None:
            # materialize all carries up front so the jit signature is
            # stable from the first call (empty-dict -> populated-dict
            # would force a second trace/compile)
            B = next(iter(in_map.values())).shape[0]
            dt = _dtype_of(self.conf.training.dtype)
            self._rnn_carries = {
                name: self.conf.nodes[name].layer.initial_carry(B, dt)
                for name in self._layer_nodes
                if getattr(self.conf.nodes[name].layer,
                           "supports_carry", False)}
        if getattr(self, "_rnn_step_jit", None) is None:
            # one jitted program per streaming step (see MLN.rnn_time_step)
            def step(params, states, im, carries):
                acts, _, _, new_carries = self._forward(
                    params, states, im, train=False, rng=None,
                    stop_before_loss=False, carries=carries)
                return ([acts[o] for o in self.conf.network_outputs],
                        new_carries)
            self._rnn_step_jit = jax.jit(step)  # jaxlint: disable=JL006 -- inference step: params/states are NOT consumed, they persist across streaming calls
        outs_list, new_carries = self._rnn_step_jit(
            self.params, self.states, in_map, self._rnn_carries)
        self._rnn_carries = {**self._rnn_carries, **new_carries}
        outs = outs_list
        if squeeze:
            outs = [o[:, 0] if o.ndim == 3 else o for o in outs]
        return outs[0] if len(outs) == 1 else outs

    # ----------------------------------------------------- incremental decode
    # Token-level serving (ISSUE 15): an autoregressive decoder served
    # token-at-a-time needs a STEP program whose shapes never depend on
    # how far each request has generated — per-request KV caches of
    # static [rows, H, max_len, D] shape are threaded through the step
    # as carry state (the serving analog of the tBPTT scan carries),
    # every row masks its own prefix, and the serving engine AOT-
    # compiles one prefill program per pow2 prompt-length bucket and
    # one decode program per pow2 row bucket (keras/generation.py).

    def kv_cache_nodes(self) -> List[str]:
        """Layer nodes that thread a KV cache (causal attention)."""
        return [n for n in self._layer_nodes
                if getattr(self.conf.nodes[n].layer,
                           "supports_kv_cache", False)]

    def decode_max_len(self) -> int:
        """Static cache length: the learned position table's capacity
        (every decode position must index it)."""
        for n in self._layer_nodes:
            ml = getattr(self.conf.nodes[n].layer, "max_timesteps", 0)
            if ml:
                return int(ml)
        for t in self.conf.input_types.values():
            if t is not None and t.kind == "rnn" and t.timesteps:
                return int(t.timesteps)
        raise ValueError(
            "decode needs a static max sequence length (a "
            "PositionalEmbeddingLayer max_timesteps or a recurrent "
            "InputType with fixed timesteps)")

    def decode_vocab(self) -> int:
        t = self.conf.input_types.get(self.conf.network_inputs[0])
        if t is None or t.kind != "rnn":
            raise ValueError("decode needs a recurrent input type")
        return int(t.size)

    def _check_decodable(self) -> None:
        """Fail loudly at engine-build time — not as a shape error deep
        inside a traced step — when the graph is not an incremental
        decoder: single input/output, every time-mixing layer either a
        CAUSAL attention (KV cache) or the positional embedding, and
        everything else per-timestep-local."""
        from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex
        from deeplearning4j_tpu.nn.layers.attention import (
            SelfAttentionLayer)
        from deeplearning4j_tpu.nn.layers.normalization import (
            LayerNormalization)
        from deeplearning4j_tpu.nn.layers.shape import TimeDistributedLayer
        if len(self.conf.network_inputs) != 1 \
                or len(self.conf.network_outputs) != 1:
            raise ValueError("incremental decode supports single-input/"
                             "single-output graphs")
        for name in self.conf.topological_order:
            node = self.conf.nodes[name]
            if node.kind == "vertex":
                if not isinstance(node.vertex, ElementWiseVertex):
                    raise ValueError(
                        f"vertex {name!r} ({type(node.vertex).__name__}) "
                        "is not per-timestep-local; cannot decode "
                        "incrementally")
                continue
            if node.kind != "layer":
                continue
            layer = node.layer
            if isinstance(layer, SelfAttentionLayer):
                if not layer.supports_kv_cache:
                    raise ValueError(
                        f"attention node {name!r} is not causal — "
                        "incremental decode would change its output")
                continue
            if getattr(layer, "supports_carry", False):
                raise ValueError(
                    f"recurrent node {name!r} "
                    f"({type(layer).__name__}) has no decode path")
            ok = (hasattr(layer, "decode_step")
                  or isinstance(layer, (LayerNormalization,
                                        TimeDistributedLayer))
                  or hasattr(layer, "compute_loss"))
            if not ok:
                raise ValueError(
                    f"node {name!r} ({type(layer).__name__}) is not "
                    "known to be per-timestep-local; cannot decode "
                    "incrementally")

    def init_decode_cache(self, rows: int, max_len: Optional[int] = None
                          ) -> Dict[str, Dict[str, Array]]:
        """Fresh zeroed KV caches for a ``rows``-row decode bucket —
        one {k, v} pair per causal-attention node, static shapes."""
        if max_len is None:
            max_len = self.decode_max_len()
        dt = _dtype_of(self.conf.training.dtype)
        return {n: {"k": jnp.zeros(self.conf.nodes[n].layer.cache_shape(
                        rows, max_len), dt),
                    "v": jnp.zeros(self.conf.nodes[n].layer.cache_shape(
                        rows, max_len), dt)}
                for n in self.kv_cache_nodes()}

    def decode_cache_bytes(self, rows: int,
                           max_len: Optional[int] = None) -> int:
        """HBM footprint of a ``rows``-row bucket's KV caches — what the
        serving engine budgets ring-buffer eviction against."""
        if max_len is None:
            max_len = self.decode_max_len()
        dt = np.dtype(self.conf.training.dtype)
        total = 0
        for n in self.kv_cache_nodes():
            shape = self.conf.nodes[n].layer.cache_shape(rows, max_len)
            total += 2 * int(np.prod(shape)) * dt.itemsize
        return total

    def _incremental_forward(self, params, states, x, caches, positions,
                             lengths=None):
        """One DAG walk shared by prefill (``lengths`` given, x is the
        padded [B, T, V] prompt block) and decode (x is the [B, 1, V]
        current token, ``positions`` the per-row sequence position).
        Returns (output activation, new caches)."""
        acts: Dict[str, Array] = {self.conf.network_inputs[0]: x}
        new_caches: Dict[str, Dict[str, Array]] = {}
        for name in self.conf.topological_order:
            node = self.conf.nodes[name]
            if node.kind == "input":
                continue
            in_acts = [acts[i] for i in node.inputs]
            if node.kind == "vertex":
                acts[name] = node.vertex.apply(in_acts)
                continue
            layer = node.layer
            h = in_acts[0]
            if node.preprocessor is not None:
                h = node.preprocessor.transform(h, None)
            p = self._layer_params(params, name)
            if getattr(layer, "supports_kv_cache", False):
                cache = caches[name]
                if lengths is not None:
                    h, kc, vc = layer.prefill(p, h, cache["k"],
                                              cache["v"], lengths)
                else:
                    h, kc, vc = layer.decode_step(p, h, cache["k"],
                                                  cache["v"], positions)
                new_caches[name] = {"k": kc, "v": vc}
            elif lengths is None and hasattr(layer, "decode_step"):
                h = layer.decode_step(p, h, positions)
            else:
                h, _ = layer.apply(p, h, state=states[name], train=False,
                                   rng=None, mask=None)
            acts[name] = h
        return acts[self.conf.network_outputs[0]], new_caches

    def decode_fns(self):
        """The two PURE step functions token-level serving AOT-compiles
        (params/states stay arguments — fit never invalidates a
        compiled bucket; caches are donate-able carries):

        - ``prefill(params, states, caches, x, lengths)`` -> ``(probs
          [B, V] at each row's last prompt position, caches)`` — x is
          the pow2-padded one-hot prompt block [B, T, V].
        - ``decode(params, states, caches, x, positions)`` -> ``(probs
          [B, V], caches)`` — x is the [B, 1, V] one-hot of each row's
          current token.
        """
        if self._decode_fns is None:
            self._check_decodable()

            def prefill(params, states, caches, x, lengths):
                out, new_caches = self._incremental_forward(
                    params, states, x, caches, None, lengths=lengths)
                probs = jnp.take_along_axis(
                    out, (lengths - 1)[:, None, None], axis=1)[:, 0, :]
                return probs, new_caches

            def decode(params, states, caches, x, positions):
                out, new_caches = self._incremental_forward(
                    params, states, x, caches, positions)
                return out[:, 0, :], new_caches

            self._decode_fns = (prefill, decode)
        return self._decode_fns

    # ------------------------------------------------- block-paged decode
    # ISSUE 20: the serving engine stores KV state as a fixed pool of
    # [n_pages, H, page_len, D] pages per attention node plus a per-row
    # page table. The paged step gathers each row's pages into the
    # EXACT dense [rows, H, max_len, D] shape the unmodified decode
    # path expects (page_len must divide max_len), runs it, and
    # scatters the one new K/V token per row back into its write page —
    # values and shapes are identical to the dense step, so batched
    # paged decode stays bitwise equal to singleton dense decode.

    def kv_page_len(self, page_len: Optional[int] = None) -> int:
        """Resolve (and validate) the KV page length: must divide the
        static ``decode_max_len`` so pages tile a row exactly."""
        ml = self.decode_max_len()
        if page_len is None:
            from deeplearning4j_tpu.analysis.memory import (
                default_kv_page_len)
            return default_kv_page_len(ml)
        page_len = int(page_len)
        if page_len < 1 or ml % page_len:
            raise ValueError(
                f"kv_page_len={page_len} must divide the static decode "
                f"max_len {ml} (pages must tile a cache row exactly)")
        return page_len

    def init_kv_page_pool(self, n_pages: int, page_len: int
                          ) -> Dict[str, Dict[str, Array]]:
        """Fresh zeroed page pool — one {k, v} pair of
        ``[n_pages, H, page_len, D]`` arrays per causal-attention node.
        A physical page id addresses ONE page group: the same slot
        across every node's k and v arrays."""
        dt = _dtype_of(self.conf.training.dtype)
        return {n: {"k": jnp.zeros(self.conf.nodes[n].layer.cache_shape(
                        n_pages, page_len), dt),
                    "v": jnp.zeros(self.conf.nodes[n].layer.cache_shape(
                        n_pages, page_len), dt)}
                for n in self.kv_cache_nodes()}

    def kv_page_group_bytes(self, page_len: int) -> int:
        """HBM footprint of ONE page group (k + v, ``page_len``
        positions, across every causal-attention node) — the eviction
        granularity the paged serving engine budgets against."""
        return self.decode_cache_bytes(1, page_len)

    def paged_decode_fn(self, page_len: Optional[int] = None):
        """The PURE paged decode step the serving engine AOT-compiles:

        ``paged_decode(params, states, pool, x, positions, page_table)
        -> (probs [rows, V], new_pool)`` — ``pool`` is the donate-able
        page-pool pytree, ``page_table`` ``[rows, max_len // page_len]``
        int32. Gather -> dense decode -> scatter-back keeps the
        attention math untouched; shardcheck SC010 statically proves
        both the gather indirection and that the pool pages stayed
        donated through it."""
        page_len = self.kv_page_len(page_len)
        cached = self._paged_decode_fns.get(page_len)
        if cached is not None:
            return cached
        _, decode = self.decode_fns()   # validates decodability
        from deeplearning4j_tpu.nn.layers.attention import (
            gather_kv_pages, scatter_kv_token)

        def paged_decode(params, states, pool, x, positions, page_table):
            caches = {n: {k: gather_kv_pages(v, page_table)
                          for k, v in kv.items()}
                      for n, kv in pool.items()}
            probs, new_caches = decode(params, states, caches, x,
                                       positions)
            rows = jnp.arange(x.shape[0])
            new_pool = {}
            for n, kv in pool.items():
                new_pool[n] = {}
                for k, v in kv.items():
                    tok_kv = new_caches[n][k][rows, :, positions, :]
                    new_pool[n][k] = scatter_kv_token(
                        v, tok_kv, page_table, positions)
            return probs, new_pool

        self._paged_decode_fns[page_len] = paged_decode
        return paged_decode

    # --------------------------------------------------------------- pretrain
    def _ancestors(self, target: str) -> set:
        """Ancestor closure of ``target`` (exclusive), for partial walks.
        Includes runtime reference nodes (DuplicateToTimeSeriesVertex's
        named T source) so subset walks can resolve them."""
        seen: set = set()
        stack = list(self.conf.nodes[target].inputs)
        while stack:
            n = stack.pop()
            if n in seen:
                continue
            seen.add(n)
            node = self.conf.nodes[n]
            stack.extend(node.inputs)
            if (node.kind == "vertex"
                    and isinstance(node.vertex, DuplicateToTimeSeriesVertex)
                    and isinstance(node.vertex.timesteps, str)):
                stack.append(node.vertex.timesteps)
        return seen

    def _activations_to(self, target: str, in_map: Dict[str, Array],
                        masks: Optional[Dict[str, Array]] = None) -> Array:
        """Inference activations feeding node ``target`` (after its
        preprocessor) — the graph analog of feedForwardToLayer. Walks only
        the target's ancestor subgraph, mask-aware, as ONE jitted program
        per target (eager per-op dispatch would be pathological on a
        remote-TPU link; see init())."""
        node = self.conf.nodes[target]
        if node.kind != "layer":
            raise ValueError(f"Node {target!r} is not a layer node")
        if len(node.inputs) != 1:
            raise ValueError(
                f"Node {target!r} has {len(node.inputs)} inputs; layerwise "
                "pretraining needs a single-input node (pretraining on "
                "inputs[0] alone would silently use the wrong objective)")
        cache = getattr(self, "_act_to_fns", None)
        if cache is None:
            cache = self._act_to_fns = {}
        if target not in cache:
            subset = self._ancestors(target)

            def fn(params, states, inputs, msks, _subset=subset):
                acts, _, _ = self._forward(params, states, inputs,
                                           train=False, rng=None, masks=msks,
                                           stop_before_loss=True,
                                           subset=_subset)
                h = acts[node.inputs[0]]
                if node.preprocessor is not None:
                    h = node.preprocessor.transform(h, None)
                return h
            cache[target] = jax.jit(fn)
        return cache[target](self.params, self.states, in_map, masks)

    def pretrain(self, iterator, epochs: int = 1) -> None:
        """Greedy layerwise pretraining over the topological order
        (ref: ComputationGraph.pretrain:527-545)."""
        self._check_init()
        for name in self._layer_nodes:
            self.pretrain_layer(name, iterator, epochs=epochs)

    def pretrain_layer(self, name: str, iterator, epochs: int = 1) -> None:
        """Pretrain one layer node on the activations of the subgraph
        below it (ref: ComputationGraph.pretrainLayer:547-579). Layers
        that are not pretrainable (no AE/RBM/VAE objective) are skipped,
        as the reference does."""
        self._check_init()
        from deeplearning4j_tpu.nn.layers.core import RBM, AutoEncoder
        from deeplearning4j_tpu.nn.layers.variational import (
            VariationalAutoencoder)

        layer = self.conf.nodes[name].layer
        if not isinstance(layer, (RBM, AutoEncoder, VariationalAutoencoder)):
            return
        from deeplearning4j_tpu.nn.netcommon import make_pretrain_step
        tx = build_optimizer(self.conf.training)
        layer_opt = tx.init(self.params[name])
        step = make_pretrain_step(layer, tx)

        for _ in range(epochs):
            iterator.reset()
            for batch in iterator:
                inputs, _, masks, _ = self._split(batch)
                x = self._activations_to(name, inputs, masks)
                self._rng, k = jax.random.split(self._rng)
                # reassign every step: the jitted step donates its param
                # buffer, so a stale self.params[name] would alias a
                # deleted Array on donation-capable backends
                p, layer_opt, loss = step(self.params[name], layer_opt,
                                          x, k)
                self.params[name] = p
                self.score_value = loss

    # ----------------------------------------------------------- param access
    def num_params(self) -> int:
        self._check_init()
        return sum(int(np.prod(a.shape))
                   for p in self.params.values() for a in p.values())

    def params_flat(self) -> np.ndarray:
        """Flat param vector in topological-order/param-order
        (coefficients.bin contract for graphs)."""
        self._check_init()
        chunks = []
        for name in self._layer_nodes:
            layer = self.conf.nodes[name].layer
            for pname in layer.param_order():
                chunks.append(np.asarray(self.params[name][pname]).ravel())
        return np.concatenate(chunks) if chunks else np.zeros(0, np.float32)

    def set_params_flat(self, flat: np.ndarray) -> None:
        self._check_init()
        pos = 0
        for name in self._layer_nodes:
            layer = self.conf.nodes[name].layer
            for pname in layer.param_order():
                ref = self.params[name][pname]
                n = int(np.prod(ref.shape))
                self.params[name][pname] = jnp.asarray(
                    flat[pos:pos + n].reshape(ref.shape), ref.dtype)
                pos += n
        if pos != len(flat):
            raise ValueError(f"Expected {pos} params, got {len(flat)}")

    def predict(self, inputs) -> np.ndarray:
        return np.asarray(jnp.argmax(self.output(inputs), axis=-1))

    # evaluate / evaluate_roc / evaluate_roc_multi_class /
    # evaluate_regression come from EvalMixin (netcommon.py)
