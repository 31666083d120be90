#!/usr/bin/env python
"""GPT decoder-LM composition smoke (tools/run_checks.sh, ISSUE 14).

The LM is the one workload that composes every expensive subsystem —
causal flash/blockwise attention, ring-attention sequence parallelism,
GPipe pipelining, ZeRO-1/2 weight-update sharding, the bf16
PrecisionPolicy, gradient accumulation — and this smoke gates the
composed configs on the repo's parity spine, all on a 4-device CPU mesh:

1. dp=4 x zero2 x accum=2        == dp=4 replicated x accum=2   BITWISE
2. dp=2 x sp=2(ring) x zero1     == dp=2 x sp=2 replicated      BITWISE
   (+ shardcheck statically proves the ring: SC008 collective-permute,
    and the sp-mesh zero contract adaptations hold)
3. dp=2 x sp=2 x zero2 x bf16    == dp=2 x sp=2 x bf16          BITWISE
   losses, fp32 master weights, finite trajectory
4. pp=2 GPipe (graph pipeline, M=1) == the SINGLE-REPLICA program
   BITWISE losses
5. every composed fp32 trajectory matches the single-replica program
   within tolerance (cross-mesh loss reductions reassociate — see
   PARITY.md "composition parity map" for what is bitwise vs carved)

Exit 0 = the full composition surface (dp x tp-or-sp x pp x zero2 x
bf16) trains and every gate above holds.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()

DEVICES = 4
STEPS = 3
SEQ = 8
BATCH = 8
TOL = 1e-4  # cross-mesh fp32 loss agreement (reassociation only)


def main() -> int:
    import jax
    import numpy as np

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", DEVICES)
    if len(jax.devices()) < DEVICES:
        print(f"lm_smoke: FAIL need {DEVICES} cpu devices, "
              f"have {jax.devices()}")
        return 1

    from jax.sharding import Mesh

    from deeplearning4j_tpu.models.gpt import (
        char_lm_batches, char_vocab, gpt_tiny, synthetic_char_text,
    )
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.parallel.mesh import MeshContext
    from deeplearning4j_tpu.parallel.pipeline import GraphPipelineTrainer
    from deeplearning4j_tpu.parallel.trainer import ParallelTrainer

    text = synthetic_char_text(6000, seed=1)
    cs = char_vocab(text)
    batches = char_lm_batches(text, SEQ, BATCH, charset=cs,
                              max_batches=STEPS)

    def build():
        conf = gpt_tiny(vocab_size=len(cs), seq_len=SEQ, seed=7)
        findings = conf.validate(batch_size=BATCH)
        if findings:
            raise AssertionError(f"gpt config not clean: {findings}")
        return ComputationGraph(conf).init()

    def train_pt(n_data, n_seq=1, wus=None, precision=None, accum=1):
        net = build()
        trainer = ParallelTrainer(
            net, MeshContext.create(n_data=n_data, n_model=1,
                                    n_seq=n_seq),
            gradient_accumulation=accum, weight_update_sharding=wus,
            precision=precision)
        losses = [np.float32(np.asarray(trainer.fit_batch(b)))
                  for b in batches]
        return net, trainer, losses

    def bitwise(name, a, b, na, nb, params=True):
        if any(x.tobytes() != y.tobytes() for x, y in zip(a, b)):
            print(f"lm_smoke: FAIL {name}: loss sequences differ\n"
                  f"  {[float(x) for x in a]}\n  {[float(y) for y in b]}")
            return False
        if params:
            pa = np.asarray(na.params_flat())
            pb = np.asarray(nb.params_flat())
            if pa.tobytes() != pb.tobytes():
                print(f"lm_smoke: FAIL {name}: params diverged bitwise")
                return False
        print(f"lm_smoke: {name}: bitwise OK")
        return True

    # single-replica reference program (plain graph fit)
    ref_net = build()
    ref = [np.float32(np.asarray(ref_net.fit_batch(b))) for b in batches]

    # 1. dp x zero2 x accum vs its replicated twin
    n_off, _, l_off = train_pt(4, accum=2)
    n_z2, _, l_z2 = train_pt(4, wus="zero2", accum=2)
    if not bitwise("dp4 x zero2 x ga2 == dp4 x replicated x ga2",
                   l_z2, l_off, n_z2, n_off):
        return 1

    # 2. dp x sp (ring attention) x zero1 vs its replicated twin
    n_sp, _, l_sp = train_pt(2, n_seq=2)
    n_spz, tr_spz, l_spz = train_pt(2, n_seq=2, wus="zero1")
    if not bitwise("dp2 x sp2 x zero1 == dp2 x sp2 x replicated",
                   l_spz, l_sp, n_spz, n_sp):
        return 1
    # static proof the ring formed (SC008) and the sp-mesh zero
    # contract holds (no SC001/SC003 regressions on this program)
    from deeplearning4j_tpu.analysis.findings import Severity
    findings = [f for f in tr_spz.shardcheck(batches[0])
                if f.severity != Severity.INFO]
    if findings:
        print("lm_smoke: FAIL shardcheck on the dp2 x sp2 x zero1 "
              "program:\n  " + "\n  ".join(str(f) for f in findings))
        return 1
    print("lm_smoke: shardcheck dp2 x sp2 x zero1: ring present, "
          "contracts clean")

    # 3. dp x sp x zero2 x bf16: bitwise losses vs the bf16 replicated
    # twin, fp32 masters, finite
    n_bf, _, l_bf = train_pt(2, n_seq=2, precision="bf16")
    n_bfz, _, l_bfz = train_pt(2, n_seq=2, wus="zero2", precision="bf16")
    if not all(np.isfinite(l_bfz)):
        print(f"lm_smoke: FAIL bf16 composed run non-finite: {l_bfz}")
        return 1
    if not bitwise("dp2 x sp2 x zero2 x bf16 == dp2 x sp2 x bf16 "
                   "(losses)", l_bfz, l_bf, n_bfz, n_bf, params=False):
        return 1
    np.testing.assert_allclose(  # master drift: last-ulp association
        np.asarray(n_bfz.params_flat()), np.asarray(n_bf.params_flat()),
        rtol=0, atol=1e-7, err_msg="bf16 master weights drifted past ulp")
    master_dtypes = {str(p.dtype)
                     for p in jax.tree_util.tree_leaves(n_bfz.params)}
    if master_dtypes != {"float32"}:
        print(f"lm_smoke: FAIL bf16 masters not fp32: {master_dtypes}")
        return 1
    print("lm_smoke: bf16 masters fp32, drift <= 1e-7")

    # 4. GPipe pipeline (graph stage partitioning at the residual-stream
    # cut points) vs the single-replica program — BITWISE losses
    pp_net = build()
    devs = np.array(jax.devices()[:2])
    pp_tr = GraphPipelineTrainer(pp_net, Mesh(devs.reshape(2), ("pp",)),
                                 n_microbatches=1)
    l_pp = [np.float32(np.asarray(pp_tr.fit_batch(b))) for b in batches]
    if not bitwise("pp2 GPipe (M=1) == single-replica program",
                   l_pp, ref, pp_net, ref_net, params=False):
        return 1
    np.testing.assert_allclose(
        np.asarray(pp_net.params_flat()), np.asarray(ref_net.params_flat()),
        rtol=0, atol=1e-6, err_msg="pipeline params drifted")

    # 5. cross-mesh tolerance: every fp32 composed trajectory tracks the
    # single-replica program (loss reductions reassociate across meshes)
    for name, ls in (("dp4-zero2-ga2", l_z2), ("dp2-sp2-zero1", l_spz)):
        err = max(abs(float(a) - float(b)) for a, b in zip(ls, ref))
        if err > TOL:
            print(f"lm_smoke: FAIL {name} vs single-replica: {err:.2e} "
                  f"> {TOL}")
            return 1
    print(f"lm_smoke: OK — {STEPS} steps; composed configs "
          "dp4xzero2xga2, dp2xsp2xzero1, dp2xsp2xzero2xbf16 bitwise vs "
          "their single-replica-state twins; pp2 GPipe bitwise vs the "
          "single-replica program; ring statically proven (SC008); "
          "bf16 masters fp32")
    return 0


if __name__ == "__main__":
    sys.exit(main())
