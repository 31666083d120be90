"""One layer's sparse attention and routed experts alone at the cell's shape
(``keye_vl2_train_seq8k_1chip``: 1 x 8,192 tokens of 2,048; 32 / 4 heads of
128; an indexer of 16 heads of 64 keeping 2,048 keys; 16 held of 128 experts
of 768, 8 a token), in bfloat16: wall milliseconds a call over five calls,
forward and forward with backward, on whatever device JAX has.

    chiprun -- python tools/sparse_moe_bench.py [part ...]

Parts: ``index`` (the indexer's selection: index scores and top-k),
``topk`` (the threshold of ``[512, 8192]`` float32 rows alone, 16 times:
``lax.top_k``, a sort of the values alone, and the counting search of
``ops/topk_threshold.py``, its thresholds compared with ``lax.top_k``'s;
``chip_smoke.py`` P3 compares the selections),
``flash`` (the flash kernels with the selection and without), ``experts``
(the expert layer; and its grouped products at 8,192 rows, what the held
share needs). The readings land in ``chiprun_out/pr36/sparse_moe_bench.json``.
No test and no run of the benchmark calls this.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.attention import SparseIndexerLayer
from deeplearning4j_tpu.nn.layers.experts import RoutedExpertsLayer
from deeplearning4j_tpu.ops.pallas_attention import flash_attention
from deeplearning4j_tpu.ops.topk_threshold import topk_threshold

B, T, F = 1, 8192, 2048
CALLS = 5


def timed(fn, *args):
    fn = jax.jit(fn)
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(CALLS):
        out = fn(*args)
    jax.block_until_ready(out)
    return {"ms": 1e3 * (time.perf_counter() - t0) / CALLS,
            "compile_s": compile_s}


def built(layer, key):
    layer.set_n_in(InputType.recurrent(F, T))
    params = layer.init_params(key, jnp.float32)
    return layer, jax.tree.map(lambda a: (0.02 * jax.random.normal(
        key, a.shape)).astype(jnp.bfloat16) if a.ndim > 1 else
        a.astype(jnp.bfloat16), params)


def main(parts):
    key = jax.random.PRNGKey(0)
    u = jax.random.normal(key, (B, T, F), jnp.bfloat16)
    out = {"device": str(jax.devices()[0])}
    idx, idx_p = built(SparseIndexerLayer(rope_theta=1e7), key)
    select = lambda: jax.jit(lambda p, x: idx.apply(
        p, x, state={}, train=True, rng=None)[0])(idx_p, u)
    if "index" in parts:
        out["index"] = timed(lambda p, x: idx.apply(
            p, x, state={}, train=True, rng=None)[0], idx_p, u)
        print("index", out["index"], flush=True)
    if "topk" in parts:
        scores = jax.random.normal(key, (16, 512, T), jnp.float32)
        out["topk"] = timed(lambda s: jax.lax.map(
            lambda r: jax.lax.top_k(r, 2048)[0][:, -1], s), scores)
        out["sort"] = timed(lambda s: jax.lax.map(
            lambda r: jnp.sort(r, axis=-1)[:, T - 2048], s), scores)
        out["threshold"] = timed(lambda s: jax.lax.map(
            lambda r: topk_threshold(r, 2048)[:, 0], s), scores)
        print({k: out[k] for k in ("topk", "sort", "threshold")}, flush=True)
        edges = [jax.jit(lambda s, f=f: jax.lax.map(f, s))(scores) for f in (
            lambda r: jax.lax.top_k(r, 2048)[0][:, -1:],
            lambda r: topk_threshold(r, 2048))]
        out["thresholds_differ"] = int(jnp.sum(edges[0] != edges[1]))
        print("thresholds that differ from lax.top_k's:",
              out["thresholds_differ"], flush=True)
    if "flash" in parts:
        sel = select()
        print("selected a query", float(jnp.mean(jnp.sum(
            sel.astype(jnp.float32), axis=-1))), flush=True)
        q = jax.random.normal(key, (B, 32, T, 128), jnp.bfloat16)
        for name, s in (("causal", None), ("select", sel)):
            fwd = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                  select=s)
            both = jax.grad(lambda q, k, v: fwd(q, k, v).astype(
                jnp.float32).sum(), argnums=(0, 1, 2))
            out[f"flash_{name}_fwd"] = timed(fwd, q, q, q)
            out[f"flash_{name}_bwd"] = timed(both, q, q, q)
            print(name, out[f"flash_{name}_fwd"], out[f"flash_{name}_bwd"],
                  flush=True)
    if "experts" in parts:
        moe, moe_p = built(RoutedExpertsLayer(
            n_experts=128, top_k=8, n_hidden=768, first=0, count=16,
            activation="silu"), key)
        fwd = lambda p, x: moe.apply(p, x, state=moe.init_state(),
                                     train=True, rng=None)
        both = jax.grad(lambda p, x: fwd(p, x)[0].astype(jnp.float32).sum(),
                        argnums=(0, 1))
        out["experts_fwd"] = timed(fwd, moe_p, u)
        out["experts_bwd"] = timed(both, moe_p, u)
        print("assigned", fwd(moe_p, u)[1]["assigned"], flush=True)
        uneven = fwd(moe_p, u)[1]["assigned"]
        for rows in (8192, 16384, 65536):
            x = jax.random.normal(key, (rows, F), jnp.bfloat16)
            for name, sizes in (("even", jnp.full((16,), 512, jnp.int32)),
                                ("uneven", uneven)):
                if int(sizes.sum()) > rows:
                    continue
                out[f"ragged_dot_{rows}_rows_{name}"] = timed(
                    lambda x, w, s: jax.lax.ragged_dot(x, w, s), x,
                    moe_p["W_gate"], sizes)
                out[f"ragged_dot_{rows}_rows_{name}_f32w"] = timed(
                    lambda x, w, s: jax.lax.ragged_dot(
                        x, w.astype(jnp.bfloat16), s), x,
                    moe_p["W_gate"].astype(jnp.float32), sizes)
        print({k: v for k, v in out.items() if "experts" in k or "ragged"
               in k}, flush=True)
    os.makedirs("chiprun_out/pr36", exist_ok=True)
    with open("chiprun_out/pr36/sparse_moe_bench.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1:] or ["index", "topk", "flash", "experts"])
