"""A cell's training step compiled for a v5e that is described and not
attached: its memory, its text and the ``op_name`` of every operation,
without a chip. Nothing runs, so nothing here is a result or a time.

    JAX_PLATFORMS=cpu python tools/step_for_v5e.py <cell> [out.txt]

The configuration, the traffic's batch and length (or, for an image cell,
the batch in the dtype its feed narrows to) and the program's builder are
the cell's own (``BENCHMARK.json``); parameters, momentum and states
are shapes (``jax.eval_shape``), never arrays. The program asks the backend
which path its kernels take and here sees the CPU, so this script, and no
option of the program, says "compiled" in its place. With ``out.txt`` the
compiled text is written there, and

    JAX_PLATFORMS=cpu python tools/step_for_v5e.py --same parent.txt change.txt

tells whether two trees' texts are the same program: every line but the
tables of source files and lines, and every Mosaic kernel's body parsed
back to MLIR and printed without its source locations (the bytes of a body
hold the path and the line of each operation, so they differ between two
checkouts of one kernel). With ``--same-but-names`` in ``--same``'s place
every ``op_name="..."`` is taken out of both texts first, which is how a
change of ``jax.named_scope``s alone is shown to be the same program under
other labels.
"""

import base64
import importlib
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding


def main(cell_name, out=None):
    from benchmark import manifest, traffic
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.ops import pallas_kernels

    jax.config.update("jax_enable_compilation_cache", False)
    for module in list(sys.modules.values()):
        if getattr(module, "lstm_mode", None) is pallas_kernels.lstm_mode \
                and module is not pallas_kernels:
            module.lstm_mode = lambda: "compiled"
    pallas_kernels.lstm_mode = lambda: "compiled"

    m = manifest.load(ROOT)
    cell = manifest.cell(m, cell_name)
    with open(os.path.join(ROOT, manifest.config_entry(
            m, cell["config"])["file"])) as f:
        cfg = traffic.with_dry(json.load(f), False)
    mix = traffic.load(ROOT, cell["traffic"])
    spec = cfg["program"]
    module, func = spec["builder"].split(":")
    kwargs = dict(spec.get("kwargs", {}))
    kwargs.update({k: cfg[v] for k, v in spec.get("kwargs_from", {}).items()})
    conf = getattr(importlib.import_module(module), func)(**kwargs)
    for field, key in spec.get("training", {}).items():
        setattr(conf.training, field, cfg[key])
    for module in list(sys.modules.values()):
        if getattr(module, "__name__", "").startswith("deeplearning4j_tpu") \
                and hasattr(module, "lstm_mode"):
            module.lstm_mode = lambda: "compiled"
    net = ComputationGraph(conf)
    dtype = jnp.dtype(cfg["dtype"])

    def build(key):
        keys = jax.random.split(key, len(net._layer_nodes))
        p = {name: (conf.nodes[name].layer.init_params(k, dtype)
                    if conf.nodes[name].layer.has_params() else {})
             for name, k in zip(net._layer_nodes, keys)}
        states = {name: conf.nodes[name].layer.init_state()
                  for name in net._layer_nodes}
        return p, net._tx.init(p), states

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=chip), t)
    params, opt_state, states = on_chip(jax.eval_shape(
        build, jax.random.PRNGKey(0)))
    print(f"{cell_name}: {sum(a.size for a in jax.tree.leaves(params)):,} "
          "parameters", flush=True)
    batch = lambda *shape, dtype: jax.ShapeDtypeStruct(
        (mix["batch"],) + shape, dtype, sharding=chip)
    if mix["data"] == "images":      # as the feed hands them: narrowed
        x = batch(cfg["height"], cfg["width"], cfg["channels"],
                  dtype=jnp.dtype(mix["feed_dtype"]))
        y = batch(cfg["n_classes"], dtype=jnp.dtype(mix["feed_dtype"]))
    else:
        x = y = batch(mix["seq_len"], dtype=jnp.int32)
    key = on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    net.params, net.opt_state, net.states = params, opt_state, states
    t0 = time.time()
    lowered = net._build_train_step().lower(
        params, opt_state, states, {conf.network_inputs[0]: x},
        {conf.network_outputs[0]: y}, None, None, key)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    gb = lambda n: f"{n / 1e9:.3f} GB"
    print(f"compiled in {time.time() - t0:.1f} s: arguments "
          f"{gb(ma.argument_size_in_bytes)}, temporaries "
          f"{gb(ma.temp_size_in_bytes)}, outputs "
          f"{gb(ma.output_size_in_bytes)} (aliased "
          f"{gb(ma.alias_size_in_bytes)}), together "
          f"{gb(ma.argument_size_in_bytes + ma.temp_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes)}")
    text = compiled.as_text()
    print(f"{text.count(chr(10)):,} lines, {text.count('tpu_custom_call')} "
          "kernel calls")
    if out:
        with open(out, "w") as f:
            f.write(text)


BODY = r'custom_call_config":\{"body":"([^"]*)"'


def program(path, names=True):
    """A compiled text as ``(lines, kernel bodies)`` with what names a
    checkout and a source line taken out, and without ``names`` every
    ``op_name`` too."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    with open(path) as f:
        text = f.read()
    kernels = []
    for body in re.findall(BODY, text):
        ctx = mlir.make_ir_context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        with ctx:
            kernels.append(ir.Module.parse(base64.b64decode(
                body)).operation.get_asm(enable_debug_info=False))
    text = re.sub(BODY, "", text)
    text = re.sub(r"stack_frame_id=\d+", "", text)
    if not names:
        text = re.sub(r'op_name="[^"]*"', "", text)
    return [l for l in text.splitlines()
            if not re.match(r'^\s*\d+ ("|\{)', l)], kernels


def same(a, b, names=True) -> int:
    (la, ka), (lb, kb) = program(a, names), program(b, names)
    lines = sum(x != y for x, y in zip(la, lb)) + abs(len(la) - len(lb))
    bodies = sum(x != y for x, y in zip(ka, kb)) + abs(len(ka) - len(kb))
    print(f"{len(la):,} and {len(lb):,} lines, {lines} differ; {len(ka)} "
          f"and {len(kb)} kernel bodies "
          f"({sum(len(k.splitlines()) for k in ka):,} lines of MLIR), "
          f"{bodies} differ")
    return 1 if lines or bodies else 0


if __name__ == "__main__":
    if sys.argv[1] in ("--same", "--same-but-names"):
        sys.exit(same(*sys.argv[2:4], names=sys.argv[1] == "--same"))
    main(*sys.argv[1:3])
