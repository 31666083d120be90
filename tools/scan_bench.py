"""One layer's selective scan alone at the SambaY cell's shape (1 x 8,192
tokens, 5,120 channels, 16 states, ``x`` in bfloat16), forward and forward
with backward, in the forms ``PERF.md`` section 6 (PRs 33, 34) compares:
wall milliseconds a call over five calls, on whatever device JAX has.

    chiprun -- python tools/scan_bench.py kernel 16x16 32x16 0x64 kernel:256x512

``kernel`` is the Pallas kernels of ``ops/pallas_selective_scan.py`` as the
layer runs them on a TPU, and ``kernel:<tokens>x<channels>`` the same with
another block a grid step; ``<steps>x<lanes>`` is ``selective_scan_chunked``
with that block (16x16 is the XLA path's); ``0x<L>`` is the form issue 33
gave as its example, ``lax.associative_scan`` over the ``L`` tokens of a
chunk, kept here and nowhere in the program. The readings land in
``chiprun_out/pr34/scan_bench.json``. No test and no run of the benchmark
calls this.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.nn.layers.state_space import selective_scan_chunked
from deeplearning4j_tpu.ops import pallas_selective_scan as pss

B, T, D, N = 1, 8192, 5120, 16
CALLS = 5


def scan_associative(x, delta, a, b, c, chunk):
    """The recurrence solved inside a chunk by ``lax.associative_scan`` on
    the pairs ``(exp(Delta A), Delta x B^T)``, a ``lax.scan`` across
    chunks: every token's ``[N, d_in]`` state of a chunk is live at once,
    and each of the scan's log-depth sweeps sends it through HBM."""
    n = T // chunk
    blocks = lambda z: jnp.moveaxis(z, 1, 0).reshape(n, chunk, B, z.shape[-1])
    chain = lambda l, r: (r[0] * l[0], r[0] * l[1] + r[1])

    @jax.checkpoint
    def body(h, xs):
        x_b, d_b, b_b, c_b = xs
        decay = jnp.exp(d_b[:, :, None, :] * a)
        write = (d_b * x_b.astype(jnp.float32))[:, :, None, :] * b_b[..., None]
        through, own = lax.associative_scan(chain, (decay, write), axis=0)
        states = through * h + own
        return states[-1], jnp.sum(states * c_b[..., None], axis=2)

    h0 = jnp.zeros((B, N, D), jnp.float32)
    _, y = lax.scan(body, h0, tuple(map(blocks, (x, delta, b, c))))
    return jnp.moveaxis(y.reshape(T, B, D), 0, 1)


def scan_form(form):
    """The scan a form's name stands for, as a function of ``x, delta, a,
    b, c``."""
    if form.startswith("kernel"):
        if ":" in form:     # read when the jitted runners trace
            pss.BLOCK_T, pss.BLOCK_D = map(int, form.split(":")[1].split("x"))
            jax.clear_caches()
        return pss.selective_scan
    steps, lanes = map(int, form.split("x"))
    if not steps:
        return lambda *z: scan_associative(*z, chunk=lanes)
    return lambda *z: selective_scan_chunked(*z, steps=steps, lanes=lanes)


def main(forms) -> int:
    rng = np.random.default_rng(0)
    f32 = lambda z: jnp.asarray(z, jnp.float32)
    args = (jnp.asarray(rng.normal(size=(B, T, D)), jnp.bfloat16),
            f32(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (B, T, D)))),
            f32(-np.broadcast_to(np.arange(1, N + 1)[:, None], (N, D))),
            f32(rng.normal(size=(B, T, N))), f32(rng.normal(size=(B, T, N))))
    cot = f32(rng.normal(size=(B, T, D)))
    out = {"device": jax.devices()[0].device_kind}
    for form in forms:
        fn = scan_form(form)
        both = jax.grad(lambda *z: jnp.sum(fn(*z) * cot),
                        argnums=(0, 1, 2, 3, 4))
        rec = {}
        for name, f in (("fwd", jax.jit(fn)), ("fwd_bwd", jax.jit(both))):
            t0 = time.perf_counter()
            jax.block_until_ready(f(*args))
            compile_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            for _ in range(CALLS):
                result = f(*args)
            jax.block_until_ready(result)
            rec[name] = {"ms": 1e3 * (time.perf_counter() - t0) / CALLS,
                         "compile_s": round(compile_s, 1)}
        out[form] = rec
        print(form, json.dumps(rec), flush=True)
    os.makedirs("chiprun_out/pr34", exist_ok=True)
    with open("chiprun_out/pr34/scan_bench.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:] or ["kernel", "16x16"]))
